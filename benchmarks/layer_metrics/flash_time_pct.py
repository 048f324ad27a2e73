"""Device time in flash_fwd / flash_bwd_dq / flash_bwd_dkv over busy time."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.kernel_time_pct(run, r"^flash_(fwd|bwd_dq|bwd_dkv) ")
