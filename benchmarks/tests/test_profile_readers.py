"""``layer_metrics/_profile.py`` and the readers of PR 24, on the trace
recorded on a TPU v5e (``data/fixture.xplane.pb``, described in
``test_trace_reduce.py``), on hand-made planes, and on runs that lack what
a reader needs (no trace; the counters of a program without the span
site): there a reader returns ``None`` and does not raise."""

import importlib
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.layer_metrics import _profile
from benchmarks.tests.test_trace_reduce import FIXTURE
from benchmarks.tests.test_trace_reduce import _profile as _plane_profile

PROFILE_READERS = ("decode_program_ms", "prefill_program_ms",
                   "idle_in_admit_pct", "idle_in_prefill_pct",
                   "idle_in_decode_pct")
COUNTER_READERS = ("host_busy_pct", "queue_wait_mean_ms",
                   "prefill_wait_mean_ms", "decode_behind_2plus_chunks_pct",
                   "decode_context_fill_pct")
ENGINE_SIZES = {"max_slots": 4, "max_seq_len": 64}


def _reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def test_program_ms_is_the_median_execution_inside_the_window():
    # six executions of 90.218-90.219 us; the first starts before the window
    assert len(_profile.load(FIXTURE)["programs"]) == 5
    assert _profile.program_ms(FIXTURE, r"^jit__lambda\(") == \
        pytest.approx(0.0902, abs=5e-5)
    assert _profile.program_ms(FIXTURE, r"^jit_decode\(") is None
    assert _profile.program_ms(None, r"^jit__lambda\(") is None


def test_idle_inside_a_span_matches_the_fixtures_intervals_by_hand():
    """Window 47,895,089-113,722,027 ns.  ``engine_step`` x 3 is 1,952,850
    + 1,831,360 + 1,695,980 ns long and holds 90,212 + 9,866 + 0 ns of
    device operations; ``generator_sleep`` x 3 is 20,122,039 + 20,054,539 +
    20,127,989 ns and holds 164,548 + 180,424 + 0 (the device's events lie
    ~1 ms before the host span that dispatched them)."""
    window = 113722027 - 47895089
    step = (1952850 - 90212) + (1831360 - 9866) + 1695980
    sleep = (20122039 - 164548) + (20054539 - 180424) + 20127989
    got_step = _profile.idle_inside_pct(FIXTURE, "engine_step")
    got_sleep = _profile.idle_inside_pct(FIXTURE, "generator_sleep")
    assert got_step == pytest.approx(100.0 * step / window, rel=1e-6)
    assert got_sleep == pytest.approx(100.0 * sleep / window, rel=1e-6)
    red = tr.reduce_file(FIXTURE, ("engine_step", "generator_sleep"))
    idle_pct = 100.0 * (1.0 - red["busy_s_first"] / red["window_s"])
    assert got_step + got_sleep <= idle_pct
    assert got_step + got_sleep == pytest.approx(idle_pct, abs=0.1)
    assert _profile.idle_inside_pct(FIXTURE, "engine.decode") is None
    assert _profile.idle_inside_pct(None, "engine_step") is None


def test_spans_nest_arguments_are_cut_and_the_first_device_is_read():
    op = "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)"
    prof = _profile.digest(_plane_profile({
        "/device:TPU:1": {"XLA Ops": [(op, 0.0, 10.0)],
                          "XLA Modules": [("jit_decode(1)", 0.0, 10.0)]},
        "/device:TPU:0": {
            "XLA Ops": [(op, 1.0, 3.0), (op, 5.0, 6.0)],
            "XLA Modules": [("jit_prefill(7)", 1.0, 3.0),
                            ("jit_decode(9)", 5.0, 6.0),
                            ("jit_decode(9)", 9.5, 10.5)]},   # cut by the end
        "/host:CPU": {"python": [
            (tr.WINDOW_SPAN, 0.0, 10.0),
            ("engine_step", 0.5, 9.0),
            ("engine.step#step=3#", 1.0, 8.0),
            ("engine.prefill", 1.0, 4.0),
            ("engine.prefill_dispatch#rid=1,start=0,n=8#", 1.0, 2.0),
            ("engine.decode", 4.0, 8.0),
            ("engine.decode_sync", 4.5, 6.5)]}}))
    assert prof["window"] == (0.0, 10.0)
    assert prof["idle"] == [(0.0, 1.0), (3.0, 5.0), (6.0, 10.0)]
    assert [p[0] for p in prof["programs"]] == ["jit_prefill(7)",
                                                "jit_decode(9)"]
    assert prof["spans"]["engine.step"] == [(1.0, 8.0)]
    assert prof["spans"]["engine.prefill_dispatch"] == [(1.0, 2.0)]
    # idle inside engine.decode: (4, 5) and (6, 8)
    assert tr.total(_profile.inside(
        prof["idle"], prof["spans"]["engine.decode"], (0.0, 10.0))) == 3.0
    own = _profile.innermost(prof["spans"], _profile.ENGINE_SPANS
                             + ("engine_step",))
    assert dict(own) == {
        "engine_step": [(0.5, 1.0), (8.0, 9.0)],
        "engine.prefill_dispatch": [(1.0, 2.0)],
        "engine.prefill": [(2.0, 4.0)],
        "engine.decode": [(4.0, 4.5), (6.5, 8.0)],
        "engine.decode_sync": [(4.5, 6.5)]}
    text = _profile.table(prof)
    assert "jit_prefill(7)" in text and _profile.OUTSIDE in text
    assert _profile.digest(_plane_profile({"/host:CPU": {"python": []}})) \
        is None


def test_own_xplane_is_the_newest_profile_of_a_run_that_has_a_trace(
        tmp_path, monkeypatch):
    monkeypatch.setattr(_profile, "TRACE_ROOT", str(tmp_path))
    assert _profile.own_xplane({"trace": {"window_s": 1.0}}) is None
    for i, cell in enumerate(("older", "newer")):
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        shutil.copy(FIXTURE, d / "host.xplane.pb")
        os.utime(d / "host.xplane.pb", (1000 + i, 1000 + i))
    path = _profile.own_xplane({"trace": {"window_s": 1.0}})
    assert path == str(tmp_path / "newer" / "plugins" / "profile"
                       / "2026_01_01" / "host.xplane.pb")
    assert _profile.own_xplane({"trace": None}) is None
    assert _profile.own_xplane({}) is None
    # a program without the span site (the fixture has no engine.* span and
    # no serving program): the readers find a profile and report nothing
    run = {"trace": {"window_s": 1.0}}
    for name in PROFILE_READERS:
        assert _reader(name).read(run) is None, name


@pytest.mark.parametrize("name", PROFILE_READERS + COUNTER_READERS)
def test_a_reader_without_a_trace_or_its_counters_reports_nothing(name):
    read = _reader(name).read
    config = {"engine": ENGINE_SIZES}
    assert read({"trace": None, "config": config}) is None
    assert read({"trace": None, "stats": {}, "config": config}) is None
    # the counters of the engine before PR 24
    old = {"decode_calls": 40, "decode_sync_s": 3.0, "step_wall_s": 4.0,
           "tokens_generated": 90, "prefill_calls": 12}
    assert read({"trace": None, "stats": old, "config": config}) is None
    # nothing happened in the window: no division by zero
    zero = dict.fromkeys(
        ("decode_calls", "decode_sync_s", "prefill_sync_s", "step_wall_s",
         "admissions", "queue_wait_s", "first_tokens", "prefill_wait_s",
         "decode_calls_after_2plus_chunks", "decode_attended_tokens"), 0)
    assert read({"trace": None, "stats": zero, "config": config}) is None


def test_counter_readers_on_hand_made_deltas():
    stats = {"decode_calls": 40, "decode_sync_s": 3.0, "prefill_sync_s": 0.5,
             "step_wall_s": 4.0, "admissions": 4, "queue_wait_s": 0.2,
             "first_tokens": 5, "prefill_wait_s": 7.5,
             "decode_calls_after_0_chunks": 10,
             "decode_calls_after_1_chunk": 24,
             "decode_calls_after_2plus_chunks": 6,
             "decode_attended_tokens": 40 * 4 * 64 // 8}
    run = {"trace": None, "stats": stats, "config": {"engine": ENGINE_SIZES}}
    want = {"host_busy_pct": 12.5, "queue_wait_mean_ms": 50.0,
            "prefill_wait_mean_ms": 1500.0,
            "decode_behind_2plus_chunks_pct": 15.0,
            "decode_context_fill_pct": 12.5}
    assert {n: _reader(n).read(run) for n in COUNTER_READERS} == \
        pytest.approx(want)


def test_benchmark_json_lists_the_eighteen_entries_with_their_readers():
    bench = bench_run.load_json(os.path.join(bench_run.CHECKOUT,
                                             "BENCHMARK.json"))
    # PR 24's entries, wherever later PRs appended theirs: these readers
    # under the two prefixes the 1.3b serving cells had then
    mine = [m for m in bench["per_layer"]
            if m["name"].rpartition(".")[0] in ("chat", "doc")
            and m["name"].rpartition(".")[2]
            in PROFILE_READERS + COUNTER_READERS]
    assert len(mine) == 18
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    for m in mine:
        prefix, _, reader = m["name"].rpartition(".")
        assert len(m["workloads"]) == 1 and m["layer"] in layers
        assert m["workloads"][0].endswith("-" + prefix)
        assert m["source"] == (
            "program_span" if reader.startswith("idle_in_") else
            "device_trace" if reader.endswith("_program_ms") else
            "program_counter")
    both = [r for r in PROFILE_READERS + COUNTER_READERS
            if not r.startswith("decode_behind") and "context" not in r]
    assert sorted(m["name"] for m in mine) == sorted(
        [f"{c}.{r}" for c in ("chat", "doc") for r in both]
        + ["chat.decode_behind_2plus_chunks_pct",
           "chat.decode_context_fill_pct"])
