"""Share of scored requests whose time to first token and mean gap both met
the cell's limits; a failed request meets neither."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "slo_attained_pct")
