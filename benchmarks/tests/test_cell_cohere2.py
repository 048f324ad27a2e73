"""The ``serve_cohere2`` job end to end on the CPU at a tiny, test-only
preset (4 layers so that both kinds of layer occur, window 64 under the
longest prompts, 8 experts top-2 of which 4 are held, 2 shared), and every
reader the cell adds: the counter readers on the run itself, the trace
readers on hand-made reductions.  Nothing here is a device metric."""

import importlib
import os
import re
import time

import pytest

from benchmarks import flops_cohere2, run, trace_reduce, weights_cohere2
from benchmarks.tests.test_cells_tiny import TINY, bench_file  # noqa: F401

CELL = "tiny-longshort"
SZ = dict(hidden=64, expert_width=32, experts_held=(2, 4), shared=2, top_k=2)


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
    assert "moe_time_pct" not in m and "moe_roofline_pct" not in m
    assert m["longshort.window_compiles"] == 0
    assert m["longshort.preemptions"] == 0
    # 4 of 8 experts held: about half of the assignments fall here
    assert 25.0 < m["longshort.expert_local_share_pct"] < 75.0
    assert 1.0 <= m["longshort.expert_load_max_over_mean"] <= 4.0
    assert 0 < m["longshort.kv_pool_peak_pct"] <= 100
    assert 0 < m["longshort.kv_window_pool_peak_pct"] <= 100
    # prompts run past the 64-token window, so ring pages come round
    assert m["longshort.window_pages_recycled"] > 0
    assert 1.0 <= m["longshort.decode_batch_mean"] <= 4.0
    assert m["longshort.ttft_p50_ms"] > 0
    # the accepted serving readers the cell's layers also feed
    assert 0 < m["longshort.decode_sync_pct"] < 100
    assert m["longshort.completed_tokens_per_s"] > 0
    assert m["longshort.drain_s"] >= 0
    assert m["longshort.generator_late_p95_ms"] >= 0
    assert m["longshort.queue_wait_mean_ms"] >= 0
    assert "longshort.idle_in_admit_pct" not in m      # needs a device trace


def _run(**kw):
    stats = dict(moe_local_assignments=600.0, moe_assignments=1600.0,
                 moe_experts_active=90.0, moe_layer_passes=30.0,
                 moe_expert_tokens_max=240.0,
                 window_pages_recycled=7.0)
    trace = dict(window_s=2.0, busy_s=1.0, op_seconds={
        "fusion bf16[4,8,32] fusion": 0.1,          # held, decode rows
        "fusion bf16[4,32,16] fusion": 0.05,        # held, (e, width, rows)
        "convolution_fusion bf16[2,16,64] fusion": 0.1,  # shared, a chunk
        "copy-done f32[8,64] copy-done": 0.3,       # a prefetch, no fusion
        "fusion f32[8,64] fusion": 0.05,            # the weighted sum
        "fusion bf16[8,64] fusion": 0.2,            # the residual stream
        "fusion bf16[4,8,33] fusion": 0.2,          # not an expert shape
        "paged_attention bf16[8,8,16] custom-call": 0.3})
    peaks = dict(bf16_flops_per_s=1e9, hbm_bytes_per_s=1e6)
    return dict(dict(stats=stats, trace=trace, moe_sizes=SZ, decode_rows=8,
                     config={"dtype": "bfloat16"},
                     chunk_tokens=16, window_s=4.0, peaks=peaks,
                     window_pages=20, window_pages_peak=5), **kw)


def test_moe_time_is_found_by_result_shape():
    assert _reader("moe_time_pct").read(_run()) == pytest.approx(30.0)
    # served in float32, a float32 (rows, hidden) result says nothing
    assert _reader("moe_time_pct").read(
        _run(config={"dtype": "float32"})) == pytest.approx(25.0)
    # no trace, or a run that does not say its rows: nothing, not a guess
    assert _reader("moe_time_pct").read(_run(trace=None)) is None
    assert _reader("moe_time_pct").read(_run(moe_sizes=None)) is None
    assert _reader("moe_time_pct").read({}) is None


def test_moe_roofline_is_required_work_over_measured_time():
    one = 3 * 64 * 32
    ops, nbytes = flops_cohere2.expert_layer_work(
        SZ, routed_rows=600, row_passes=800, experts_active=90,
        layer_passes=30)
    assert ops == 2.0 * (600 + 800 * 2) * one
    assert nbytes == 2.0 * (90 + 30 * 2) * one
    least = max(ops / 1e9, nbytes / 1e6)          # memory-bound here
    want = 100.0 * (least / 4.0) / (0.3 / 2.0)
    assert _reader("moe_roofline_pct").read(_run()) == pytest.approx(want)
    assert _reader("moe_roofline_pct").read(_run(trace=None)) is None
    assert _reader("moe_roofline_pct").read(_run(stats={})) is None


def test_counter_readers_and_a_program_without_the_counters():
    assert _reader("expert_local_share_pct").read(_run()) == 37.5
    assert _reader("expert_load_max_over_mean").read(_run()) == \
        pytest.approx(240 * 4 / 600)
    assert _reader("kv_window_pool_peak_pct").read(_run()) == 25.0
    assert _reader("window_pages_recycled").read(_run()) == 7.0
    parent = dict(stats={"prefill_calls": 3.0}, trace=None)
    for name in ("expert_local_share_pct", "expert_load_max_over_mean",
                 "kv_window_pool_peak_pct", "window_pages_recycled",
                 "moe_time_pct", "moe_roofline_pct"):
        assert _reader(name).read(parent) is None, name


def test_check_has_two_readings_and_leaves_router_ties_out():
    """The program passes the tiny limits; the reference with its weights
    rounded (``precision_study``) goes through the same ``judge`` and errs
    the more the coarser the rounding; a position where the router is
    tied is left out of the shortfall limits and only there."""
    import numpy as np

    from benchmarks import weights
    from benchmarks.jobs import serve_cohere2 as job
    from benchmarks.reference import cohere2_moe_ref as ref

    cell = run.load_json(os.path.join(TINY, "workloads", f"{CELL}.json"))
    config = run.load_json(os.path.join(TINY, "configs",
                                        f"{cell['config']}.json"))
    ctx = run.Context(cell=cell, config=config, traffic={},
                      sizes=weights.sizes(config), seed=11, seconds=0.0,
                      tracer=run.WindowTracer(False, "", 0.0),
                      t_process=time.perf_counter(), spans=job.SPANS)
    ctx.log = lambda msg: None
    server = job.Server(ctx)
    widen = ref._w
    assert job.reference_check(server, ctx) == {"reference_logits": True}
    c = server.checked
    assert c["emitted"].shape == (len(job.CHECK_PROMPTS), job.CHECK_NEW)
    assert c["margin"].shape == c["emitted"].shape and (c["margin"] > 0).all()
    # the two long prompts were served in one batch, in two slots' rings
    assert server.engine.stats["window_pages_recycled"] > 0
    got = job.precision_study(server, ctx, ("int8", "float8_e4m3fn"))
    assert ref._w is widen
    assert got["program"]["ok"] and got["program"]["tie_share"] == 0.0
    assert 0 < got["int8"]["logit_rms_err"] < got["float8_e4m3fn"][
        "logit_rms_err"]
    assert got["float8_e4m3fn"]["argmax_share"] < got["program"][
        "argmax_share"]
    # a wrong token where the router is tied does not count; elsewhere it does
    lg, tol = c["logits"], config["check"]
    wrong = c["emitted"].copy()
    wrong[0, 0] = lg[0, 0].argmin()
    assert not job.judge(lg, wrong, c["margin"], tol)["ok"]
    tied = c["margin"].copy()
    tied[0, 0] = 0.0
    verdict = job.judge(lg, wrong, tied, dict(tol, serve_argmax_share_min=0.9))
    assert verdict["ok"] and verdict["tied_shortfall_max"] > 0.01
    assert not job.judge(lg, wrong, tied, dict(
        tol, serve_argmax_share_min=0.9, router_tie_share_max=0.0))["ok"]


# ---------------------------------------------------------------------------
# the expert operations' pattern, held to the compiled programs' own scope
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip (the recipe of ``tests/test_tpu_lowering.py``;
    one process at a time may load libtpu)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")
    env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    env.undo()


def _real(kind, name):
    return run.load_json(os.path.join(run.HERE, kind, f"{name}.json"))


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill_8"])
def test_expert_pattern_is_the_compiled_programs_moe_scope(
        monkeypatch, one_chip, program):
    """The real cell's programs, from shapes alone, compiled for the chip:
    every entry instruction ``moe_time_pct``'s pattern matches was traced
    under ``moe_ffn``, and what it leaves of that scope is the router
    (results no wider than the router)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.jobs import serve_cohere2
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import paged_prefill as pp
    from paddle_tpu.serving import ServingEngine

    bench = run.load_json(os.path.join(run.CHECKOUT, "BENCHMARK.json"))
    cell = _real("workloads", next(
        w["name"] for w in bench["workloads"]
        if _real("workloads", w["name"])["job"] == "serve_cohere2"))
    config = _real("configs", cell["config"])
    sz = weights_cohere2.sizes(config)
    monkeypatch.setattr(pa, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(pp, "_backend_is_tpu", lambda: True)

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(config["dtype"]))

    class ShapesOnly:
        from paddle_tpu.models import Cohere2MoeForCausalLM
        layer_specs = Cohere2MoeForCausalLM.layer_specs
        cfg = serve_cohere2.model_config(sz, config)

        def decoder_params(self):
            return {"wte": leaf(sz["vocab"], sz["hidden"]),
                    "lnf_g": leaf(sz["hidden"]),
                    "blocks": [{n: leaf(*shape) for n, (shape, _)
                                in weights_cohere2.leaf_shapes(sz).items()}
                               for _ in range(sz["layers"])]}

    model = ShapesOnly()
    eng = ServingEngine(model, **config["engine"])
    assert set(eng.attention_paths().values()) == {"kernel"}

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params, bufs, key = jax.tree_util.tree_map(
        on_chip, (eng.params, eng._device_pool(), eng._key))
    s, mp = eng.max_slots, eng.max_pages
    rows = {"decode": s, "prefill": eng.chunk_tokens, "prefill_8": 8}[program]
    if program == "decode":
        fn, args = eng._decode_fn, (ints(s), ints(s),
                                    (ints(s, mp), ints(s, mp)), ints(s))
    else:
        fn, args = eng._prefill_fn, (ints(rows), ints(), ints(),
                                     (ints(mp), ints(mp)), ints())
    text = fn.lower(params, bufs, *args, key).compile().as_text()
    entry = re.search(r"^ENTRY .*?^\}", text, re.S | re.M).group(0)
    rx = re.compile(flops_cohere2.expert_op_pattern(
        sz, (rows,), float32_output=config["dtype"] != "float32"))
    matched, router = 0, 0
    for line in entry.splitlines():
        if " = " not in line:
            continue
        name = trace_reduce.short_name(line.strip().removeprefix("ROOT "))
        scope = re.search(r'op_name="[^"]*moe_ffn', line) is not None
        if rx.search(name):
            assert scope, f"outside the expert layer: {name}"
            matched += 1
        elif scope:
            dims = re.search(r"\[([\d,]*)\]", name).group(1).split(",")
            assert int(dims[-1] or 1) <= sz["router_width"], \
                f"an expert operation the pattern misses: {name}"
            router += 1
    # gate/up (one or two fusions), down + sum, shared likewise: a layer
    assert matched >= 4 * sz["layers"] and router >= 2 * sz["layers"]
