"""Median, over the scored requests that got a first token, of first on_token
time minus the time the request was due.  A few tens of requests fit a
window today, and above capacity the queue grows all through it, so this is
recorded and not judged."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "ttft_p50_ms")
