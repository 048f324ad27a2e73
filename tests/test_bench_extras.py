"""bench.py extras must be runnable on CPU: the eager-vs-jit
dispatch-latency microbench and the DataLoader spawn+shm-ring throughput
microbench (ISSUE r06 acceptance), and a tiny-sized smoke of each leg."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_dispatch_latency_bench_emits_numbers():
    res = bench._dispatch_latency_bench(n_ops=20, size=64, repeats=3)
    assert res["eager_us_per_op"] > 0
    assert res["jit_us_per_op"] > 0
    assert np.isfinite(res["dispatch_overhead_x"])
    assert res["config"]["n_ops"] == 40


def test_dataloader_bench_emits_numbers():
    res = bench._dataloader_bench(n=16, shape=(32, 32), batch_size=4,
                                  num_workers=2)
    assert res["single_process"]["batches_per_sec"] > 0
    assert res["spawn_shm_ring"]["batches_per_sec"] > 0
    assert res["spawn_shm_ring"]["num_workers"] == 2
    assert res["single_process"]["mb_per_sec"] > 0


def test_int8_flagship_bench_config_runs():
    """CPU-runnable smoke of the flagship_int8 training config (tiny
    shapes): the W8A8 path must keep producing finite, decreasing loss
    without a TPU (ISSUE r07 CI satellite)."""
    from paddle_tpu.models import GPTConfig

    res = bench._run(
        GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=2,
                  max_seq_len=64, dropout=0.0, int8=True),
        batch=2, seq=32, steps=2, peak_flops=1e12,
        dtype="float32", remat=False, ce_rows=0)
    assert res["tokens_per_sec"] > 0
    assert np.isfinite(res["loss"])
    assert res["config"]["int8"] is True


def test_decode_bench_emits_numbers():
    """bf16-vs-int8 decode bench on tiny shapes: both paths run, the
    argmax-match contract is reported, and tokens/sec are finite."""
    res = bench._decode_bench(hidden=64, layers=1, heads=2, vocab=256,
                              batch=2, prompt=8, new_tokens=8,
                              dtype="float32")
    assert res["bf16"]["tokens_per_sec"] > 0
    assert res["int8"]["tokens_per_sec"] > 0
    assert 0.0 <= res["argmax_match"] <= 1.0
    assert res["argmax_match"] >= 0.9  # tiny config: int8 tracks fp argmax
    assert np.isfinite(res["speedup"])


def test_serving_bench_smoke():
    """Fast CPU smoke of bench.py's serving bench path (ISSUE r08 CI
    satellite): the static baseline and the continuous-batching engine
    both complete the mixed load, every request gets a latency, and the
    report carries the throughput/latency fields the TPU run records."""
    res = bench._serving_bench(hidden=48, layers=1, heads=2, vocab=128,
                               n_requests=5, max_slots=2, page_size=8,
                               prompt_len=8, new_tokens_max=12,
                               dtype="float32", decode_block=4)
    for side in ("static", "engine"):
        assert res[side]["tokens_per_sec"] > 0
        assert res[side]["p50_latency_s"] > 0
        assert res[side]["p99_latency_s"] >= res[side]["p50_latency_s"]
    assert res["engine"]["decode_steps"] > 0
    assert np.isfinite(res["speedup"])
    assert res["config"]["useful_tokens"] > 0
    # r11 satellite: the engine leg carries the registry's machine-
    # readable metrics dict, consistent with the bench's own report
    m = res["engine"]["metrics"]
    assert m["serving_decode_calls"] == res["engine"]["decode_steps"]
    assert m["serving_tokens_generated"] > 0
    assert m["serving_ttft_s_count"] == res["config"]["n_requests"]
    assert m["serving_ttft_s_p99"] >= m["serving_ttft_s_p50"] > 0
    assert sum(m[f"serving_requests_terminal_{r}"]
               for r in ("eos", "length", "rejected", "expired",
                         "cancelled")) == res["config"]["n_requests"]


def test_serving_bench_poisson_arrivals():
    """The Poisson-arrival mode (arrival_rate set) also completes and
    latencies stay positive (completion can't precede arrival)."""
    res = bench._serving_bench(hidden=48, layers=1, heads=2, vocab=128,
                               n_requests=4, max_slots=2, page_size=8,
                               prompt_len=8, new_tokens_max=8,
                               dtype="float32", decode_block=2,
                               arrival_rate=200.0)
    assert res["engine"]["p50_latency_s"] > 0
    assert res["static"]["p50_latency_s"] > 0


def test_prefix_serving_bench_smoke():
    """Fast CPU smoke of the shared-system-prompt serving bench (ISSUE
    r09 satellite): both engine runs (prefix cache off and on) complete
    the same load, the cached run reports a NONZERO hit rate, and the
    no-cache run reports zero (the control is really a control)."""
    res = bench._prefix_serving_bench(hidden=48, layers=1, heads=2,
                                      vocab=128, n_requests=4, max_slots=2,
                                      page_size=8, shared_len=16,
                                      unique_len=8, new_tokens=6,
                                      dtype="float32", chunk_tokens=16,
                                      decode_block=2)
    assert res["no_cache"]["tokens_per_sec"] > 0
    assert res["cache"]["tokens_per_sec"] > 0
    assert res["no_cache"]["prefix_hit_rate"] == 0.0
    assert res["cache"]["prefix_hit_rate"] > 0.0
    # the cache must SAVE prefill work on the identical load
    assert res["cache"]["prefill_calls"] < res["no_cache"]["prefill_calls"]
    assert np.isfinite(res["speedup"])
    assert res["config"]["useful_tokens"] == 4 * 6
    # r11: per-leg registry dicts agree with the legs' own reports
    assert res["cache"]["metrics"]["serving_prefix_hit_tokens"] > 0
    assert res["no_cache"]["metrics"]["serving_prefix_hit_tokens"] == 0
    for leg in ("cache", "no_cache"):
        assert (res[leg]["metrics"]["serving_prefill_calls"]
                == res[leg]["prefill_calls"])


def test_metrics_overhead_bench_smoke():
    """Smoke of the observability-cost leg: the bare, metrics+trace and
    full-stack engines each finish the SAME load, report a finite positive
    rate, and the observed legs embed a registry that counted that load.
    The cost itself (the ratio of two wall-clock rates) is no assertion
    here: on a shared CPU a sub-second run's ratio is scheduling noise."""
    res = bench._metrics_overhead_bench(hidden=48, layers=1, heads=2,
                                        vocab=128, n_requests=8,
                                        max_slots=2, page_size=8,
                                        prompt_len=8, new_tokens=12,
                                        dtype="float32")
    assert res["config"]["n_requests"] == 8
    for name in ("off", "on", "full"):
        rate = res[f"{name}_tokens_per_sec"]
        assert np.isfinite(rate) and rate > 0, (name, rate)
        leg = res["legs"][name]
        assert (leg["requests"], leg["tokens"]) == (8, 8 * 12), (name, leg)
    for ratio in ("on_off_ratio", "full_off_ratio"):
        assert np.isfinite(res[ratio]) and res[ratio] > 0
    assert res["legs"]["off"]["metrics"] is None
    for name in ("on", "full"):
        m = res["legs"][name]["metrics"]
        # the registry was attached before the two-token warm-up request
        assert m["serving_requests_terminal_length"] == 8 + 1
        assert m["serving_tokens_generated"] == 8 * 12 + 2
    assert res["legs"]["full"]["metrics"][
        "serving_tenant_tokens_generated.tenant=bench"] == 8 * 12


@pytest.mark.slow
def test_serving_bench_tpu_scale():
    """The flagship-sized serving point bench.py records on TPU (marked
    slow: hours on CPU, minutes on a v5e).  The r08 acceptance bar lives
    here: continuous batching must deliver >= 1.3x aggregate tokens/s
    over static batching on the mixed-length load."""
    res = bench._serving_bench(hidden=1536, layers=24, heads=12,
                               vocab=50304, n_requests=64, max_slots=8,
                               page_size=64, prompt_len=128,
                               new_tokens_max=256, dtype="bfloat16",
                               decode_block=16)
    assert res["speedup"] >= 1.3, res


@pytest.mark.slow
def test_prefix_serving_bench_tpu_scale():
    """The flagship-sized shared-system-prompt point bench.py records on
    TPU (marked slow).  The r09 acceptance bar lives here: a nonzero
    prefix hit rate and goodput >= the no-cache engine path on the
    identical load."""
    res = bench._prefix_serving_bench(hidden=1536, layers=24, heads=12,
                                      vocab=50304, n_requests=64,
                                      max_slots=8, page_size=64,
                                      shared_len=64, unique_len=64,
                                      new_tokens=128, dtype="bfloat16",
                                      chunk_tokens=128, decode_block=8)
    assert res["cache"]["prefix_hit_rate"] > 0.0, res
    assert res["speedup"] >= 1.0, res


def test_overload_serving_bench_smoke():
    """Fast CPU smoke of the overload bench (ISSUE r10 satellite): the
    calibration phase and both overload phases (bounded queue + deadlines
    vs unbounded) complete, terminal accounting is total (completed +
    rejected + expired covers every request in the bounded run), and the
    unbounded control neither rejects nor expires."""
    res = bench._overload_serving_bench(hidden=48, layers=1, heads=2,
                                        vocab=128, n_requests=5,
                                        max_slots=2, page_size=8,
                                        prompt_len=8, new_tokens=8,
                                        dtype="float32",
                                        overload_factor=3.0,
                                        decode_block=2)
    assert res["at_capacity"]["goodput_tokens_per_sec"] > 0
    b, u = res["overload_bounded"], res["overload_unbounded"]
    n = res["config"]["n_requests"]
    assert b["completed"] + round((b["reject_rate"] + b["expire_rate"]) * n) \
        == n
    assert u["reject_rate"] == 0.0 and u["expire_rate"] == 0.0
    assert u["completed"] == n and u["goodput_tokens_per_sec"] > 0
    assert res["config"]["deadline_s"] > 0
    assert np.isfinite(res["goodput_ratio_bounded_vs_capacity"])


def test_slo_serving_bench_smoke():
    """Fast CPU smoke of the multi-tenant SLO bench (ISSUE r12
    satellite): calibration + both overload legs (FCFS vs WFQ over 3
    weighted tenants) complete, per-tenant accounting is total, shares
    sum to ~1 where anything completed, and the weight-share targets are
    recorded.  The +/-10-point share bar lives in the slow TPU test —
    CPU timing noise at this size swamps real scheduling effects."""
    res = bench._slo_serving_bench(hidden=48, layers=1, heads=2, vocab=128,
                                   n_per_tenant=2, weights=(3.0, 1.0),
                                   max_slots=2, page_size=8, prompt_len=8,
                                   new_tokens=8, dtype="float32",
                                   overload_factor=3.0, decode_block=2)
    assert res["at_capacity"]["goodput_tokens_per_sec"] > 0
    assert res["config"]["n_requests"] == 4
    assert abs(sum(res["weight_shares"].values()) - 1.0) < 1e-6
    for leg in ("fcfs", "wfq"):
        pt = res[leg]["per_tenant"]
        assert set(pt) == {"a", "b"}
        done = sum(t["completed"] for t in pt.values())
        exp = sum(t["expired"] for t in pt.values())
        assert done + exp <= res["config"]["n_requests"]
        if res[leg]["goodput_tokens_per_sec"] > 0:
            assert abs(sum(t["share_of_completed_tokens"]
                           for t in pt.values()) - 1.0) < 1e-6
        # per-tenant labeled token counters made it into the registry
        m = res[leg]["metrics"]
        assert any(k.startswith("serving_tenant_tokens_generated.tenant=")
                   for k in m)
    assert np.isfinite(res["aggregate_ratio_wfq_vs_fcfs"])
    assert res["max_share_error_wfq"] >= 0


@pytest.mark.slow
def test_slo_serving_bench_tpu_scale():
    """The flagship-sized multi-tenant SLO point bench.py records on TPU
    (marked slow).  The r12 acceptance bar lives here: under 3x-capacity
    overload, WFQ per-tenant completed-token shares are within +/-10
    points of the configured weight shares AND aggregate goodput stays
    >= 0.95x FCFS — isolation without a throughput tax."""
    res = bench._slo_serving_bench(hidden=1536, layers=24, heads=12,
                                   vocab=50304, n_per_tenant=16,
                                   weights=(3.0, 2.0, 1.0), max_slots=8,
                                   page_size=64, prompt_len=96,
                                   new_tokens=96, dtype="bfloat16",
                                   overload_factor=3.0, decode_block=8)
    assert res["max_share_error_wfq"] <= 0.10, res
    assert res["aggregate_ratio_wfq_vs_fcfs"] >= 0.95, res


@pytest.mark.slow
def test_overload_serving_bench_tpu_scale():
    """The flagship-sized overload point bench.py records on TPU (marked
    slow).  The r10 acceptance bar lives here: with backpressure on
    (bounded queue + deadlines), goodput under 3x-capacity overload stays
    >= 0.9x the at-capacity goodput — load shedding keeps the engine
    serving instead of drowning."""
    res = bench._overload_serving_bench(hidden=1536, layers=24, heads=12,
                                        vocab=50304, n_requests=48,
                                        max_slots=8, page_size=64,
                                        prompt_len=96, new_tokens=96,
                                        dtype="bfloat16",
                                        overload_factor=3.0,
                                        decode_block=8)
    assert res["goodput_ratio_bounded_vs_capacity"] >= 0.9, res


def test_spec_serving_bench_smoke():
    """Fast CPU smoke of the speculative-decoding bench (ISSUE r13
    satellite): both workload legs complete spec-off and spec-on with
    identical budgets, the repetitive leg's acceptance is high (tiled
    prompts are the prompt-lookup sweet spot), and the report carries
    the throughput/acceptance fields the TPU run records."""
    res = bench._spec_serving_bench(hidden=32, layers=1, heads=2,
                                    vocab=128, n_requests=4, max_slots=2,
                                    page_size=8, prompt_len=15,
                                    new_tokens=12, dtype="float32",
                                    spec_k=2)
    for leg in ("repetitive", "mixed"):
        for side in ("spec_off", "spec_on"):
            assert res[leg][side]["tokens_per_sec"] > 0
            assert res[leg][side]["decode_steps"] > 0
        on = res[leg]["spec_on"]
        assert 0.0 <= on["acceptance_rate"] <= 1.0
        assert on["spec_drafted"] >= on["spec_rejected"] >= 0
        # speculation advances >= 1 token per verify: never MORE decode
        # steps than the plain engine on the identical load
        assert on["decode_steps"] <= res[leg]["spec_off"]["decode_steps"]
        assert np.isfinite(res[leg]["speedup"])
    # tiled (period-5) prompts keep the n-gram lookup hitting
    assert res["repetitive"]["spec_on"]["acceptance_rate"] >= 0.5
    assert res["config"]["spec_k"] == 2


def test_kv_capacity_bench_smoke():
    """Fast CPU smoke of the KV-capacity bench (ISSUE r14): all four legs
    (mha / gqa / gqa+window / gqa+int4) complete the identical load at a
    FIXED pool byte budget, bytes/token strictly shrinks mha > gqa >
    gqa_int4, the capacity winner holds >= 2x the concurrent slots with
    no more preemptions or recompute than the baseline, and the per-leg
    registry dicts carry the capacity gauges every serving bench embeds."""
    res = bench._kv_capacity_bench(hidden=64, layers=2, heads=4, vocab=256,
                                   n_requests=8, max_slots=8, page_size=8,
                                   prompt_len=12, new_tokens=12,
                                   dtype="float32", kv_group=4, window=8,
                                   decode_block=2)
    legs = res
    for leg in ("mha", "gqa", "gqa_window", "gqa_int4"):
        assert legs[leg]["goodput_tokens_per_sec"] > 0
        assert legs[leg]["peak_concurrent_slots"] >= 1
        m = legs[leg]["metrics"]
        assert m["serving_kv_bytes_per_token"] == legs[leg]["kv_bytes_per_token"]
        assert "serving_pages_per_slot_p50" in m
    bpt = {leg: legs[leg]["kv_bytes_per_token"]
           for leg in ("mha", "gqa", "gqa_int4")}
    assert bpt["mha"] > bpt["gqa"] > bpt["gqa_int4"]
    # every leg got MORE pages out of the same byte budget than mha
    assert legs["gqa_int4"]["pool_pages"] > legs["gqa"]["pool_pages"] \
        > legs["mha"]["pool_pages"]
    assert res["capacity_multiplier_gqa_int4_vs_mha"] >= 8.0
    assert res["concurrency_ratio_gqa_int4_vs_mha"] >= 2.0
    assert legs["gqa_int4"]["preemptions"] <= legs["mha"]["preemptions"]
    assert legs["gqa_int4"]["recompute_tokens"] <= legs["mha"]["recompute_tokens"]
    assert res["config"]["pool_budget_bytes"] > 0


@pytest.mark.slow
def test_kv_capacity_bench_tpu_scale():
    """The flagship-sized KV-capacity point bench.py records on TPU
    (marked slow).  The r14 acceptance bar lives here: at an equal pool
    byte budget, GQA(4x) + int4 pages serve >= 2x the concurrent slots of
    the MHA/full-precision baseline, with preemptions and recompute
    tokens no higher."""
    res = bench._kv_capacity_bench(hidden=1536, layers=24, heads=12,
                                   vocab=50304, n_requests=32, max_slots=16,
                                   page_size=64, prompt_len=96,
                                   new_tokens=96, dtype="bfloat16",
                                   kv_group=4, window=64, decode_block=8)
    legs = res
    assert res["concurrency_ratio_gqa_int4_vs_mha"] >= 2.0, res
    assert legs["gqa_int4"]["preemptions"] <= legs["mha"]["preemptions"], res
    assert legs["gqa_int4"]["recompute_tokens"] \
        <= legs["mha"]["recompute_tokens"], res


@pytest.mark.slow
def test_spec_serving_bench_tpu_scale():
    """The flagship-sized speculative point bench.py records on TPU
    (marked slow).  The r13 acceptance bar lives here: >= 1.3x decode
    tokens/s/request spec-on vs spec-off on the repetitive-suffix leg,
    at acceptance >= 0.5."""
    res = bench._spec_serving_bench(hidden=1536, layers=24, heads=12,
                                    vocab=50304, n_requests=32,
                                    max_slots=8, page_size=64,
                                    prompt_len=128, new_tokens=192,
                                    dtype="bfloat16", spec_k=4)
    rep = res["repetitive"]
    assert rep["spec_on"]["acceptance_rate"] >= 0.5, res
    assert rep["spec_on"]["tokens_per_sec_per_request"] >= \
        1.3 * rep["spec_off"]["tokens_per_sec_per_request"], res


def test_disagg_serving_bench_smoke():
    """Fast CPU smoke of the disaggregated-serving bench (ISSUE r15):
    both topology legs complete the identical Poisson trace, the
    router counters account for every request exactly once (all routed
    to the one prefill target, every one handed off with payload bytes,
    none degraded), the prefix probe hits the shared system prefix, and
    the single engine reports its sync-stall ledger and how often it
    dispatched ahead of the read.  No perf
    assertion — CPU step timing is host-loop noise; the 1.7x bar lives
    in the slow TPU test below."""
    res = bench._disagg_serving_bench(hidden=64, layers=2, heads=2,
                                      vocab=256, n_requests=6, max_slots=2,
                                      page_size=8, prompt_len=16,
                                      shared_len=8, new_tokens=12,
                                      dtype="float32", decode_block=2)
    for leg in ("single", "cluster2"):
        assert res[leg]["goodput_tokens_per_sec"] > 0
        assert res[leg]["completed"] == 6
        assert res[leg]["p99_ttft_s"] is not None
    router = res["cluster2"]["router"]
    assert sum(router["routed"]) == 6
    assert router["handoffs"] == 6
    assert router["handoff_bytes"] > 0
    assert router["degraded_handoffs"] == 0
    assert router["rejected"] == 0
    # 5 of 6 requests share the 8-token system prefix -> probe hits
    assert router["prefix_hit_rate"] > 0
    assert router["prefix_match_tokens"] > 0
    roles = [r["role"] for r in res["cluster2"]["per_replica"]]
    assert roles == ["prefill", "decode"]
    pre, dec = res["cluster2"]["per_replica"]
    assert pre["handoffs_out"] == 6 and pre["decode_calls"] == 0
    assert dec["handoffs_in"] == 6 and dec["prefill_calls"] == 0
    # the sync-stall ledger exists, and most decodes were dispatched
    # while the one before them was still unread
    assert res["single"]["decode_sync_s"] > 0
    assert res["single"]["decode_ahead_share"] > 0.5
    assert res["config"]["arrival_rate_req_per_s"] > 0


@pytest.mark.slow
def test_disagg_serving_bench_tpu_scale():
    """The flagship-sized disaggregation point bench.py records on TPU
    (marked slow).  The r15 acceptance bar lives here: the 2-replica
    disaggregated cluster serves >= 1.7x the monolith's aggregate
    goodput with p99 TTFT no worse."""
    res = bench._disagg_serving_bench(hidden=1536, layers=24, heads=12,
                                      vocab=50304, n_requests=48,
                                      max_slots=8, page_size=64,
                                      prompt_len=96, shared_len=64,
                                      new_tokens=96, dtype="bfloat16",
                                      decode_block=8)
    assert res["speedup_cluster_vs_single"] >= 1.7, res
    assert res["cluster2"]["p99_ttft_s"] <= res["single"]["p99_ttft_s"], res
    assert res["cluster2"]["router"]["handoffs"] == 48, res
