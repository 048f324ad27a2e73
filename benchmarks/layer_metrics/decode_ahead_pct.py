"""Decode dispatches made while the previous decode's tokens were still
unread, over all decode dispatches of the window (engine.stats:
decode_ahead / decode_calls): the share of steps on which the host's turn
overlapped the device's step.  A program without the counter reads None."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "decode_ahead", "decode_calls")
