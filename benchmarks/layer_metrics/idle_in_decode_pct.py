"""Idle time of the first device that falls inside the engine's ``decode``
phase (host span ``engine.decode``), as a share of the traced window."""

from benchmarks.layer_metrics import _profile


def read(run):
    return _profile.idle_inside_pct(_profile.own_xplane(run), "engine.decode")
