"""95th percentile, over the scored requests that got a first token, of first
on_token time minus the time the request was due.  With a few tens of
requests in a window it is close to the maximum: recorded, not judged."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "ttft_p95_ms")
