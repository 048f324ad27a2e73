"""Wall seconds of the window's stalled steps, each of
``engine.SLOW_STEP_S`` or more (engine.stats: slow_step_s): 0 in a window
without a stall, the stall's length in one that has it.  A program without
the counter reads None."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.stat(run, "slow_step_s")
