"""Median device time of one execution of the decode program, from the
device plane of the run's own profile (line ``XLA Modules``)."""

from benchmarks.layer_metrics import _profile


def read(run):
    return _profile.program_ms(_profile.own_xplane(run), r"^jit_decode\(")
