"""Seconds from the window's end, when arrivals stop, until the last scored
request had what the cell's drain waits for.  Above capacity this is the time
to work off the backlog, decode included: it grows if decode is starved to
make the prefill rate look better."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.field(run, "drain_s")
