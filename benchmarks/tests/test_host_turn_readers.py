"""The three readers of the host's turn (PR 39) on fabricated runs:
``device_drained_pct``, ``host_dispatch_ms`` and ``stalled_s`` read the
window's deltas of ``engine.stats`` and return None where the program has
no such counter (the parent's program under this PR's benchmark files)."""

import pytest

from benchmarks.layer_metrics import (device_drained_pct, host_dispatch_ms,
                                      stalled_s)

STATS = dict(steps=2000, decode_calls=1990, dispatch_s=3.1,
             device_drained=25, slow_steps=1, slow_step_s=2.47,
             step_wall_s=39.9)
NEW = ("steps", "dispatch_s", "device_drained", "slow_steps", "slow_step_s")


def _run(**stats):
    return {"window_s": 40.0, "stats": stats, "trace": None}


def test_each_reader_on_a_window_with_the_counters():
    run = _run(**STATS)
    assert device_drained_pct.read(run) == pytest.approx(100 * 25 / 1990)
    assert host_dispatch_ms.read(run) == pytest.approx(1e3 * 3.1 / 2000)
    assert stalled_s.read(run) == pytest.approx(2.47)


def test_a_window_without_a_stall_or_a_drained_step_reads_zero():
    run = _run(**dict(STATS, device_drained=0, slow_steps=0,
                      slow_step_s=0.0))
    assert device_drained_pct.read(run) == 0.0
    assert stalled_s.read(run) == 0.0


@pytest.mark.parametrize("reader", [device_drained_pct, host_dispatch_ms,
                                    stalled_s])
@pytest.mark.parametrize("stats", [
    None,                                            # no engine in the job
    {k: v for k, v in STATS.items() if k not in NEW},  # the parent's program
], ids=["no_stats", "parent_program"])
def test_none_where_the_program_has_no_counter(reader, stats):
    assert reader.read({"window_s": 40.0, "stats": stats}) is None


@pytest.mark.parametrize("reader,den", [(device_drained_pct, "decode_calls"),
                                        (host_dispatch_ms, "steps")])
def test_none_on_an_empty_window(reader, den):
    assert reader.read(_run(**dict(STATS, **{den: 0}))) is None
