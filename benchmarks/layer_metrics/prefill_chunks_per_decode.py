"""Chunk dispatches made per decode dispatch over the window (engine.stats:
prefill_calls / decode_calls): how many prompt chunks share one decode, its
sync and the host's turn.  1 is a chunk a step; below 1 some decodes ran
with no prompt waiting."""

from benchmarks.layer_metrics import _readers


def read(run):
    chunks = _readers.stat(run, "prefill_calls")
    decodes = _readers.stat(run, "decode_calls")
    return None if chunks is None or not decodes else chunks / decodes
