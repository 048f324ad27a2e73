"""Lane-layers the decode state step was given over those of live slots
(engine.stats: ssm_lane_steps / ssm_live_lane_steps): 100 where dead lanes
are skipped, max_slots over the live lanes where every lane is computed."""
from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "ssm_lane_steps", "ssm_live_lane_steps")
