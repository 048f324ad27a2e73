"""Least time the chip could take for the expert layers' work (the routed
rows' and the shared experts' operations; the bytes of the experts that
had a row) over the time their operations took.  Work is counted by the
engine over the whole window, time is read from the traced part of it:
both are taken per second."""
from benchmarks import flops, flops_cohere2, trace_reduce
from benchmarks.layer_metrics import _readers, moe_time_pct


def read(run):
    tr, rx = run.get("trace"), moe_time_pct.pattern(run)
    need = ("moe_local_assignments", "moe_assignments", "moe_experts_active",
            "moe_layer_passes")
    if not tr or rx is None or tr["window_s"] <= 0 \
            or any(_readers.stat(run, k) is None for k in need):
        return None
    sz = run["moe_sizes"]
    took = trace_reduce.seconds_matching(tr["op_seconds"], rx)
    if took <= 0:
        return None
    ops, nbytes = flops_cohere2.expert_layer_work(
        sz, routed_rows=_readers.stat(run, "moe_local_assignments"),
        row_passes=_readers.stat(run, "moe_assignments") / sz["top_k"],
        experts_active=_readers.stat(run, "moe_experts_active"),
        layer_passes=_readers.stat(run, "moe_layer_passes"))
    least = flops.roofline_seconds(ops, nbytes, run["peaks"])[0]
    return 100.0 * (least / run["window_s"]) / (took / tr["window_s"])
