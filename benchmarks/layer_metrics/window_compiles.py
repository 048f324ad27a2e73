"""Programs traced for compilation inside the window (expected 0)."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "window_compiles")
