"""``knee_sweep.py --requests`` on the CPU at the tiny preset: the records
it prints per request are enough to recompute the judged rate, which is what
they are for (a run that reads far from its neighbours is run down from
them).  Nothing here is a device metric."""

import argparse

import pytest

from benchmarks import knee_sweep, run
from benchmarks.tests.test_cells_tiny import TINY

SECONDS = 1.5


@pytest.fixture(scope="module")
def lines():
    out = []
    args = argparse.Namespace(workload="tiny-longshort", seed=7,
                              seconds=SECONDS, rates="6,12", lead=None,
                              requests=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run.Context, "log", lambda self, msg: None)
        knee_sweep.sweep(TINY, args, out.append)
    return out


def _of_rate(lines, rate):
    """(the rate's line, its request records, its engine line)"""
    at = next(i for i, ln in enumerate(lines)
              if ln.get("rate_rps") == rate and "values" in ln)
    rest = lines[at + 1:]
    reqs = []
    for ln in rest:
        if "request" not in ln:
            break
        reqs.append(ln)
    return lines[at], reqs, rest[len(reqs)]


def test_the_check_comes_first_and_each_rate_has_its_records(lines):
    assert lines[0]["checks"] == {"reference_logits": True}
    for rate, scored in ((6.0, 9), (12.0, 18)):
        head, reqs, engine = _of_rate(lines, rate)
        assert head["scored"] == scored and head["failed"] == 0
        assert sum(r["scored"] for r in reqs) == scored
        assert engine["rate_rps"] == rate and "engine" in engine
        for r in reqs:
            assert r["scored"] == (0 <= r["due"] < SECONDS)
            assert r["scored"] or 0 <= r["first_token"] < SECONDS
            assert r["completed"] is None or r["completed"] >= r["first_token"]


@pytest.mark.parametrize("rate", (6.0, 12.0))
def test_the_records_give_the_judged_rate_back(lines, rate):
    head, reqs, _ = _of_rate(lines, rate)
    inside = [r for r in reqs if r["first_token"] is not None
              and 0 <= r["first_token"] < SECONDS]
    counted = [r for r in inside if r["in_served_interval"]]
    assert len(counted) == len(inside) - 1       # all but the opener
    firsts = [r["first_token"] for r in inside]
    tokens = sum(r["prompt"] + r["new"] for r in counted
                 if r["completed"] is not None)
    assert tokens / (max(firsts) - min(firsts)) == pytest.approx(
        head["values"]["serve_tokens_per_s"])


@pytest.mark.parametrize("rate", (6.0, 12.0))
def test_the_engines_own_rate_counts_every_row(lines, rate):
    e = _of_rate(lines, rate)[2]["engine"]
    assert e["prefill_calls_per_s"] > 0
    # rows are prompt tokens and decode lanes: more than the tokens sampled
    assert e["rows_per_s"] > e["generated_tokens_per_s"] > 0
    assert 0 < e["longest_step_ms"] < 1e3 * SECONDS
