"""Share of the decode dispatches made behind two or more prefill chunks of
the same step (engine.stats: decode_calls_after_2plus_chunks /
decode_calls)."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "decode_calls_after_2plus_chunks",
                              "decode_calls")
