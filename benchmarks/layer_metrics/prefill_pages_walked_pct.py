"""Table entries the chunk kernel walked over the entries the tables hold
(engine.stats: prefill_pages_walked / prefill_pages_in_table): the live pages
of every chunk dispatch, summed over layers by the kernel's own range
function (each layer under its own window), over max_pages x layers a
dispatch (what a grid over the whole table walks)."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.ratio_pct(run, "prefill_pages_walked",
                              "prefill_pages_in_table")
