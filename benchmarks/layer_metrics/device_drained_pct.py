"""Steps whose first dispatch found the decode in flight already done,
over the decode dispatches of the window (engine.stats: 100 x
device_drained / decode_calls).  Device order is dispatch order, so such a
step found the device idle, waiting for the host: the share of steps on
which the host set the pace.  A program without the counter reads None."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "device_drained", "decode_calls")
