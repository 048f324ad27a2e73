"""Least time the chip could take for the decode state steps of the LIVE
lanes (engine.stats: ssm_live_lane_steps; each moves one layer's state of
one slot in and out) over the time the ``ssm_state_step`` kernel took.  Work
is counted over the whole window, time in the traced part of it: both are
taken per second."""
from benchmarks import flops, flops_falcon_h1, trace_reduce
from benchmarks.layer_metrics import _readers, ssm_time_pct


def share(run, kernel: str, work):
    """``work`` (operations, bytes) over the window, or None."""
    tr = run.get("trace")
    if not tr or work is None or tr["window_s"] <= 0 or not run.get("peaks"):
        return None
    took = trace_reduce.seconds_matching(tr["op_seconds"],
                                         ssm_time_pct.KERNELS[kernel])
    if took <= 0:
        return None
    least = flops.roofline_seconds(*work, run["peaks"])[0]
    return 100.0 * (least / run["window_s"]) / (took / tr["window_s"])


def read(run):
    lanes, sz = _readers.stat(run, "ssm_live_lane_steps"), run.get("ssm_sizes")
    if lanes is None or not sz:
        return None
    return share(run, "step", flops_falcon_h1.state_step_work(
        sz, lane_layers=lanes))
