"""Prompt and output tokens of the requests that completed inside the window,
over its length: ISSUE 22's count.  A window holds a dozen such requests and
a completion trails its prefill by an output's length, so the count moves by
a request's worth from seed to seed: recorded, not judged."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "completed_tokens_per_s")
