"""Share of the device's busy time in the expert layers' operations
(routed and shared), found by their result shapes."""
from benchmarks import flops_cohere2
from benchmarks.layer_metrics import _readers


def pattern(run):
    """The expert operations' pattern, or None for a run that does not say
    what rows its programs run."""
    sz = run.get("moe_sizes")
    if not sz or "decode_rows" not in run:
        return None
    buckets, c = [], 8
    while c < run["chunk_tokens"]:
        buckets.append(c)
        c *= 2
    return flops_cohere2.expert_op_pattern(
        sz, (run["decode_rows"], run["chunk_tokens"], *buckets),
        float32_output=run["config"]["dtype"] != "float32")


def read(run):
    rx = pattern(run)
    return None if rx is None else (_readers.kernel_time_pct(run, rx) or None)
