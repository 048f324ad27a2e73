"""Share of the router's assignments that fell on the experts held here."""
from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "moe_local_assignments", "moe_assignments")
