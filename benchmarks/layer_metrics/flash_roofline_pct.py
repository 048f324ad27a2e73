"""The flash kernels' share of their roofline, from the device trace."""

from benchmarks.layer_metrics._readers import flash_roofline_pct as read  # noqa: F401
