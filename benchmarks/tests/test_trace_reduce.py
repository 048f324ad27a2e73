"""trace_reduce.py on a small trace recorded on a TPU v5e
(``data/fixture.xplane.pb``: one jitted matmul run twice inside each of three
``engine_step`` spans, with 20 ms ``generator_sleep`` spans between, all
inside the window span) and on hand-made planes."""

import os
import types

import pytest

from benchmarks import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fixture.xplane.pb")
SPANS = ("engine_step", "generator_sleep")


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 6)]) == 4
    assert tr.clip([(0, 3), (5, 6)], 2, 5.5) == [(2, 3), (5, 5.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 1), (2, 3)], [(0, 5)]) == []


def test_short_name_keeps_kernel_name_result_and_opcode():
    full = ("%flash_fwd.18 = (bf16[96,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, "
            "f32[96,1,2048]{2,1,0:T(1,128)}) custom-call(bf16[96,2048,128]"
            "{2,1,0:T(8,128)(2,1)} %bitcast.2182), custom_call_target=\"tpu\"")
    assert tr.short_name(full) == \
        "flash_fwd (bf16[96,2048,128], f32[96,1,2048]) custom-call"
    user = "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %flash_fwd.18), kind=kLoop"
    assert tr.short_name(user) == "fusion bf16[8] fusion"
    assert tr.short_name("engine_step") == "engine_step"


def test_fixture_busy_union_names_and_gap_attribution():
    red = tr.reduce_file(FIXTURE, SPANS)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.065827, abs=1e-5)
    # five of the six matmuls lie inside the window, 90.2 us each
    key = "fusion bf16[] fusion"
    assert red["op_counts"][key] == 5
    assert red["op_seconds"][key] == pytest.approx(5 * 90.2e-6, rel=1e-3)
    assert red["busy_s"] == pytest.approx(4.51e-4, rel=1e-2)
    assert red["busy_s"] >= red["op_seconds"][key]
    gaps = red["idle_gaps"]
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(
        red["window_s"], rel=1e-6)
    # the device idles while the host sleeps, not while it steps
    assert gaps["generator_sleep"] > 0.060
    assert gaps["generator_sleep"] > 100 * gaps.get("engine_step", 0)
    assert red["collective_s"] == 0 and red["collective_exposed_s"] == 0
    assert tr.seconds_matching(red["op_seconds"], r"^fusion ") == \
        red["op_seconds"][key]
    assert tr.top(red["op_seconds"], 1)[0][0] == key


def _profile(planes):
    ev = lambda n, a, b: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=a * 1e9, duration_ns=(b - a) * 1e9)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pn, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
            for ln, evs in lines.items()]) for pn, lines in planes.items()])


def test_collectives_containers_and_two_devices():
    dev0 = {
        "XLA Ops": [
            ("%while.1 = f32[] while(f32[] %x)", 0.0, 4.0),       # container
            ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 0.0, 1.0),
            ("%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %b)",
             1.0, 1.1),
            ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %c)", 1.1, 2.0),
            ("%all-reduce-done.1 = f32[4]{0} all-reduce-done(f32[4]{0} %d)",
             2.0, 3.0),
            ("%all-gather.1 = f32[8]{0} all-gather(f32[4]{0} %e)", 3.0, 4.0),
        ],
        "Async XLA Ops": [
            ("%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %b)",
             1.0, 3.0)],
    }
    dev1 = {"XLA Ops": [("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)",
                         0.0, 2.0)]}
    host = {"python": [(tr.WINDOW_SPAN, 0.0, 5.0), ("engine_step", 3.9, 5.0)]}
    red = tr.reduce_profile(_profile({
        "/device:TPU:0": dev0, "/device:TPU:1": dev1, "/host:CPU": host}),
        ("engine_step",))
    assert red["devices"] == 2 and red["window_s"] == 5.0
    assert red["busy_s_first"] == 4.0 and red["busy_s"] == (4.0 + 2.0) / 2
    # device 0: collectives cover [1, 4]; compute hides [1.1, 2.0] of it
    assert red["collective_s"] == pytest.approx(3.0 / 2)
    assert red["collective_exposed_s"] == pytest.approx((3.0 - 0.9) / 2)
    assert not any(k.startswith("while") for k in red["op_seconds"])
    assert red["op_seconds"]["fusion f32[4] fusion"] == pytest.approx(
        (1.9 + 2.0) / 2)
    assert red["idle_gaps"] == {"engine_step": pytest.approx(1.0)}


def test_no_device_plane_gives_nothing():
    assert tr.reduce_profile(_profile({"/host:CPU": {"python": []}})) is None
