"""1 - union of device-operation intervals over the traced window, on the
first device."""

from benchmarks.layer_metrics._readers import device_idle_pct as read  # noqa: F401
