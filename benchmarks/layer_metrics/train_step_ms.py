"""Median host-clock time of one train step in the window (each ends in
block_until_ready on its loss)."""

import statistics


def read(run):
    steps = run.get("step_s")
    return 1e3 * statistics.median(steps) if steps else None
