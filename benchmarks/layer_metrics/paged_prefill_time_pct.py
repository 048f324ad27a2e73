"""Device time in the paged_prefill kernel over busy time."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.kernel_time_pct(run, r"^paged_prefill ")
