"""Mean wait from first admission to first token (the request's turn at
the chunk budget, and its chunks), on the engine's clock, of the requests
that got their first token in the window (engine.stats: prefill_wait_s /
first_tokens)."""

from benchmarks.layer_metrics import _readers


def read(run):
    wait = _readers.stat(run, "prefill_wait_s")
    n = _readers.stat(run, "first_tokens")
    return None if wait is None or not n else 1e3 * wait / n
