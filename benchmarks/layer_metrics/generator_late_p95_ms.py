"""95th percentile of how long after its due time a scored request was handed
to add_request: how late the open loop ran."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "generator_late_p95_ms")
