"""Rows of the busiest held expert over the mean of the held experts, per
expert-layer pass, averaged over the window by its sums."""
from benchmarks.layer_metrics import _readers


def read(run):
    top = _readers.stat(run, "moe_expert_tokens_max")
    total = _readers.stat(run, "moe_local_assignments")
    sz = run.get("moe_sizes")
    if top is None or not total or not sz:
        return None
    return top * sz["experts_held"][1] / total
