"""Host time blocked on the decode result over the engine's step time
(engine.stats: decode_sync_s / step_wall_s), over the window."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "decode_sync_s", "step_wall_s")
