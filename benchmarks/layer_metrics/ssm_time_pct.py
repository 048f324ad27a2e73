"""Share of the device's busy time in the two kernels of the state-space
recurrence (chunk scan and decode step), found by their names."""
from benchmarks.layer_metrics import _readers

KERNELS = {"scan": r"^ssd_chunk_scan ", "step": r"^ssm_state_step "}


def read(run):
    return _readers.kernel_time_pct(run, "|".join(KERNELS.values())) or None
