"""Share of the engine's step time in which the host was not blocked on a
device result (engine.stats: 1 - (decode_sync_s + prefill_sync_s) /
step_wall_s), over the window."""

from benchmarks.layer_metrics import _readers


def read(run):
    syncs = [_readers.stat(run, k) for k in ("decode_sync_s", "prefill_sync_s")]
    wall = _readers.stat(run, "step_wall_s")
    if None in syncs or not wall:
        return None
    return 100.0 * (1.0 - sum(syncs) / wall)
