"""Tokens generated per decode dispatch over the window (engine.stats)."""

from benchmarks.layer_metrics import _readers


def read(run):
    pct = _readers.ratio_pct(run, "tokens_generated", "decode_calls")
    return None if pct is None else pct / 100.0
