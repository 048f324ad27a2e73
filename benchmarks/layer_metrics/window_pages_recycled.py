"""Ring pages of the window group given to a later logical page, in the
window."""
from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.stat(run, "window_pages_recycled")
