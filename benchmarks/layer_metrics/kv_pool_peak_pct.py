"""Largest pages_in_use seen after a step in the window, over num_pages."""

from benchmarks.layer_metrics._readers import kv_pool_peak_pct as read  # noqa: F401
