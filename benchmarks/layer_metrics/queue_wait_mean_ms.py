"""Mean wait from enqueue to first admission (a slot and pages), on the
engine's clock, of the requests first admitted in the window
(engine.stats: queue_wait_s / admissions)."""

from benchmarks.layer_metrics import _readers


def read(run):
    wait = _readers.stat(run, "queue_wait_s")
    n = _readers.stat(run, "admissions")
    return None if wait is None or not n else 1e3 * wait / n
