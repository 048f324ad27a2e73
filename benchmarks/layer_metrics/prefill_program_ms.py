"""Median device time of one execution of a prefill-chunk program (every
chunk bucket), from the device plane of the run's own profile."""

from benchmarks.layer_metrics import _profile


def read(run):
    return _profile.program_ms(_profile.own_xplane(run), r"^jit_prefill\(")
