"""Requests preempted in the window (engine.stats delta)."""

from benchmarks.layer_metrics import _readers


def read(run):
    return _readers.stat(run, "preemptions")
