"""Table entries the decode kernels walked over the entries the tables hold
(engine.stats: decode_pages_walked / decode_pages_in_table): the live
(slot, page) pairs of every decode or verify dispatch, summed over lanes and
layers by the kernel's own range function, over max_slots x max_pages x
layers a dispatch (what a grid over the whole table walks)."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "decode_pages_walked",
                              "decode_pages_in_table")
