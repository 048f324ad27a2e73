"""Host milliseconds a step spends inside program dispatch calls, over the
window (engine.stats: 1e3 x dispatch_s / steps): the runtime's share of the
host's turn, beside the engine's own Python.  A program without the
counters reads None."""

from benchmarks.layer_metrics import _readers


def read(run):
    dispatch, steps = (_readers.stat(run, k) for k in ("dispatch_s", "steps"))
    return None if dispatch is None or not steps else 1e3 * dispatch / steps
