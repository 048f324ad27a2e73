"""The ``serve_falcon_h1`` job end to end on the CPU at a tiny, test-only
preset (2 layers, 10 query heads over 2 KV heads of 16: the cell's GQA group
of 5; 4 mixer heads of 16 in 2 groups, state 16), and every reader the cell
adds: the counter reader on the run itself, the trace readers on a
hand-made reduction.  Nothing here is a device metric."""

import dataclasses
import importlib
import os
import time

import pytest

from benchmarks import flops_falcon_h1, run
from benchmarks.tests.test_cells_tiny import TINY, bench_file  # noqa: F401

CELL = "tiny-h1chat"
SZ = dict(ssm_heads=4, ssm_head_dim=16, ssm_groups=2, d_state=16, layers=2)
NEW = ("ssm_time_pct", "ssm_step_roofline_pct", "ssd_scan_roofline_pct",
       "ssm_lanes_walked_pct")


def _reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def _execute(bench_file, trace, monkeypatch):  # noqa: F811
    failed = []
    monkeypatch.setattr(run.Context, "log", lambda self, msg: (
        failed.append(msg) if msg.startswith("CHECK FAILED") else None))
    res = run.execute(bench_file, TINY, CELL, 5, 1.5, trace,
                      run.device_info(), time.perf_counter())
    # the Pallas kernels are absent on the CPU: the one check that fails
    assert failed == ["CHECK FAILED: compiled_kernels"], failed
    assert res["correct"] is False and res["failed"] == 0
    return res


def test_cell_serves_and_scores(bench_file, monkeypatch):  # noqa: F811
    res = _execute(bench_file, False, monkeypatch)
    assert res["attempted"] == 9          # round(6 req/s x 1.5 s)
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_cell_traced_reports_its_counters(bench_file, monkeypatch):  # noqa: F811
    res = _execute(bench_file, True, monkeypatch)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # counters only: a CPU trace has no device plane
    assert not {"ssm_time_pct", "ssm_step_roofline_pct",
                "ssd_scan_roofline_pct"} & set(m)
    # the jnp path computes every lane of the 4, live or not
    assert m["h1chat.ssm_lanes_walked_pct"] >= 100.0
    assert m["h1chat.window_compiles"] == 0
    assert m["h1chat.preemptions"] == 0
    assert 0 < m["h1chat.kv_pool_peak_pct"] <= 100
    assert 1.0 <= m["h1chat.decode_batch_mean"] <= 4.0
    assert m["h1chat.ttft_p50_ms"] > 0
    assert 0 < m["h1chat.decode_sync_pct"] < 100
    assert m["h1chat.completed_tokens_per_s"] > 0
    assert m["h1chat.drain_s"] >= 0
    assert "h1chat.idle_in_decode_pct" not in m      # needs a device trace


def _run(**kw):
    stats = dict(ssm_lane_steps=480.0, ssm_live_lane_steps=400.0,
                 ssm_scan_rows=900.0, ssm_scan_row_passes=1280.0,
                 prefill_calls=10.0)
    trace = dict(window_s=2.0, busy_s=1.0, op_seconds={
        "ssm_state_step (f32[4,2,16,2], f32[8,4,16,16]) custom-call": 0.2,
        "ssd_chunk_scan (f32[16,64], f32[8,4,16,16]) custom-call": 0.05,
        "paged_attention bf16[4,10,16] custom-call": 0.3,
        "fusion f32[4,64] fusion": 0.45})
    peaks = dict(bf16_flops_per_s=1e9, hbm_bytes_per_s=1e6)
    return dict(dict(stats=stats, trace=trace, ssm_sizes=SZ, window_s=4.0,
                     peaks=peaks), **kw)


def test_required_work_is_counted_from_shapes_and_counters():
    n = 4 * 16 * 16
    row = 4 * (2 * 4 * 16 + 2 * 2 * 16)
    assert flops_falcon_h1.state_elements(SZ) == n
    assert flops_falcon_h1.state_step_work(SZ, lane_layers=400) == (
        5.0 * n * 400, 400 * (8.0 * n + row))
    assert flops_falcon_h1.chunk_scan_work(
        SZ, row_layers=900, chunk_layers=20) == (
        5.0 * n * 900, 20 * 8.0 * n + 900 * row)
    # one lane in one layer: the float32 state in and out, and its row
    assert flops_falcon_h1.state_step_work(
        SZ, lane_layers=1)[1] == 2 * 4.0 * n + row


def test_new_readers_on_a_recorded_reduction():
    assert _reader("ssm_time_pct").read(_run()) == pytest.approx(25.0)
    assert _reader("ssm_lanes_walked_pct").read(_run()) == 120.0
    ops, nbytes = flops_falcon_h1.state_step_work(SZ, lane_layers=400)
    least = max(ops / 1e9, nbytes / 1e6)
    assert _reader("ssm_step_roofline_pct").read(_run()) == pytest.approx(
        100.0 * (least / 4.0) / (0.2 / 2.0))
    ops, nbytes = flops_falcon_h1.chunk_scan_work(
        SZ, row_layers=900, chunk_layers=10 * 2)
    least = max(ops / 1e9, nbytes / 1e6)
    assert _reader("ssd_scan_roofline_pct").read(_run()) == pytest.approx(
        100.0 * (least / 4.0) / (0.05 / 2.0))


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_nothing_on_a_program_without_state(name):
    """The parent's program has neither the counters nor the kernels: every
    new reader returns None there, traced or not, and does not raise."""
    parent = dict(stats={"prefill_calls": 3.0, "decode_calls": 5.0},
                  trace=dict(window_s=2.0, busy_s=1.0, op_seconds={
                      "paged_attention bf16[4,10,16] custom-call": 0.3}),
                  window_s=4.0, peaks=dict(bf16_flops_per_s=1e9,
                                           hbm_bytes_per_s=1e6))
    assert _reader(name).read(parent) is None
    assert _reader(name).read(dict(parent, trace=None)) is None
    assert _reader(name).read({}) is None


def test_check_has_two_readings():
    """The program passes the tiny limits; the reference with its weights
    rounded, or with its recurrent state kept in bfloat16, goes through the
    same ``judge`` and fails; a wrong token fails it, and so does a slab
    that is not what the configuration states."""
    from benchmarks import weights
    from benchmarks.jobs import serve_falcon_h1 as job
    from benchmarks.reference import falcon_h1_ref as ref

    cell = run.load_json(os.path.join(TINY, "workloads", f"{CELL}.json"))
    config = run.load_json(os.path.join(TINY, "configs",
                                        f"{cell['config']}.json"))
    ctx = run.Context(cell=cell, config=config, traffic={},
                      sizes=weights.sizes(config), seed=11, seconds=0.0,
                      tracer=run.WindowTracer(False, "", 0.0),
                      t_process=time.perf_counter(), spans=job.SPANS)
    ctx.log = lambda msg: None
    server = job.Server(ctx)
    widen, carry = ref._w, ref._state
    assert job.reference_check(server, ctx) == {"state_slab": True,
                                                "reference_logits": True}
    c = server.checked
    # the five, the pair, and all four of the crowd: one request a slot
    n = len(job.SINGLES) + len(job.PAIR) + 4
    assert c["emitted"].shape == (n, job.CHECK_NEW)
    assert c["slots"] == [0] * 5 + [0, 1] + [0, 1, 2, 3]
    assert server.engine.stats["state_resets"] == n
    got = job.precision_study(server, ctx,
                              ("float8_e4m3fn", "state_bfloat16"))
    assert ref._w is widen and ref._state is carry
    assert got["program"]["ok"]
    for kind in ("float8_e4m3fn", "state_bfloat16"):
        assert not got[kind]["ok"]
        assert got[kind]["logit_rms_err"] > 0
        assert got[kind]["shortfall_mean"] >= got["program"]["shortfall_mean"]
        assert got[kind]["state_err_max"] > 10 * got["program"][
            "state_err_max"]
    assert got["float8_e4m3fn"]["argmax_share"] < got["program"][
        "argmax_share"]
    wrong = c["emitted"].copy()
    wrong[0, 0] = c["logits"][0, 0].argmin()
    exact = [0.0] * n
    assert job.judge(c["logits"], c["emitted"], exact, config["check"])["ok"]
    assert not job.judge(c["logits"], wrong, exact, config["check"])["ok"]
    # a slab in another type than the configuration's is not the cell
    assert not job.slab_as_stated(server, dataclasses.replace(
        ctx, config=dict(config, ssm_state_dtype="bfloat16")))


def test_longest_steps_name_a_stall_and_when():
    from benchmarks.jobs import serve_falcon_h1 as job

    ends = [(-1.0, 3), (-0.98, 3), (0.5, 4), (0.53, 4), (0.93, 2)]
    assert job.longest_steps({"depth": ends}, n=2) == [(1480.0, 0.5),
                                                       (400.0, 0.93)]
    assert job.longest_steps({"depth": ends[:1]}) == []


def test_watch_steps_keeps_a_slow_step_with_its_phases():
    from benchmarks.jobs import serve_falcon_h1 as job

    class Engine:
        stats = dict(last_step_s=0.0, last_admit_s=0.0, last_prefill_s=0.0,
                     last_decode_s=0.0, last_decode_sync_s=0.0)

        def step(self):
            return ["fin"]

    eng = Engine()
    slow = job.watch_steps(eng)
    assert eng.step() == ["fin"] and slow == []
    eng.stats.update(last_step_s=2.5, last_decode_s=2.4,
                     last_decode_sync_s=2.375)
    assert eng.step() == ["fin"]
    (kept,) = slow
    assert kept.pop("at") > 0
    assert kept == dict(step=2500.0, admit=0.0, prefill=0.0, decode=2400.0,
                        decode_sync=2375.0)
