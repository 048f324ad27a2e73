"""Serving observability (r11): registry, exporters, tracing, guards.

CPU-only smoke of the whole observability layer: the dependency-free
MetricsRegistry (counters / gauges / exponential-bucket histograms with
percentile readout), the TensorBoard + Prometheus file exporters, the
Chrome trace-event recorder (schema-validated: every event carries
name/ph/ts/pid/tid and B/E spans balance per track), the engine
integration end-to-end (run(metrics_dir=...) producing all three
artifacts with terminal counters exactly matching FinishedRequests),
metrics surviving snapshot/restore, the profiler RecordEvent bridge, and
the no-new-imports guard keeping ``paddle_tpu.serving`` on
jax/numpy/stdlib only.
"""

import json
import sys
from collections import Counter as TallyCounter
from collections import defaultdict

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (TERMINAL_REASONS, MetricsFileExporter,
                                MetricsRegistry, ServingEngine,
                                TraceRecorder)
from paddle_tpu.serving.metrics import Counter, Gauge, Histogram

# 1-layer model: these files assert scheduling/fault/metrics properties,
# not KV layout — multi-layer paged-KV exactness lives in test_serving.py.
CFG = dict(vocab_size=512, hidden_size=64, num_layers=1, num_heads=2,
           max_seq_len=96, dropout=0.0)


def _model(seed=3):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**CFG))
    m.eval()
    return m


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = reg.gauge("depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8
    # get-or-create returns the SAME instance…
    assert reg.counter("reqs") is c
    assert reg.gauge("depth") is g
    # …and a kind clash is a programming error
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reqs")
    assert reg.scalars() == {"reqs": 4.0, "depth": 8.0}


def test_histogram_buckets_and_quantiles():
    h = Histogram("lat_s")
    assert h.quantile(0.5) == 0.0          # empty readout, not NaN
    for _ in range(50):
        h.observe(0.001)
    for _ in range(50):
        h.observe(0.1)
    assert h.count == 100
    assert h.sum == pytest.approx(50 * 0.001 + 50 * 0.1)
    assert h.min == 0.001 and h.max == 0.1
    # p50 lands in the 0.001 bucket (bounds are 1e-4 * 2^i), p99 in the
    # 0.1 bucket, both clamped to observed extremes
    assert 0.001 <= h.quantile(0.50) <= 0.002
    assert 0.05 <= h.quantile(0.99) <= 0.1
    assert h.quantile(1.0) == 0.1
    sc = h.scalars()
    assert set(sc) == {f"lat_s_{k}" for k in
                       ("count", "sum", "mean", "min", "max",
                        "p50", "p90", "p99")}
    assert sc["lat_s_mean"] == pytest.approx(h.sum / 100)
    # identical observations -> identical readout (the determinism the
    # chaos suite leans on)
    h2 = Histogram("lat_s")
    for _ in range(50):
        h2.observe(0.001)
    for _ in range(50):
        h2.observe(0.1)
    assert h2.scalars() == sc


def test_histogram_overflow_bucket():
    h = Histogram("t", start=1e-4, factor=2.0, n_buckets=4)  # max bound .8ms
    h.observe(5.0)
    h.observe(7.0)
    assert h.counts[-1] == 2               # +Inf bucket
    assert h.quantile(0.5) == pytest.approx(5.0)   # clamped to observed min
    assert 5.0 <= h.quantile(0.99) <= 7.0  # interpolated within [min, max]
    assert h.quantile(1.0) == pytest.approx(7.0)


def test_registry_state_roundtrip():
    reg = MetricsRegistry()
    reg.counter("a", "ca").inc(5)
    reg.gauge("b").set(2.5)
    h = reg.histogram("c")
    for v in (0.01, 0.02, 0.3):
        h.observe(v)
    back = MetricsRegistry.from_state(reg.to_state())
    assert back.scalars() == reg.scalars()
    assert back.counter("a").help == "ca"
    # restored metrics keep counting
    back.counter("a").inc()
    assert back.scalars()["a"] == 6


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req total/weird").inc(3)          # name sanitized
    reg.gauge("depth").set(1.5)
    h = reg.histogram("lat", start=0.1, factor=2.0, n_buckets=2)
    h.observe(0.05)
    h.observe(0.15)
    h.observe(9.0)
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    assert "# TYPE req_total_weird counter" in lines
    assert "req_total_weird 3" in lines
    assert "depth 1.5" in lines
    assert "# TYPE lat histogram" in lines
    assert 'lat_bucket{le="0.1"} 1' in lines       # cumulative
    assert 'lat_bucket{le="0.2"} 2' in lines
    assert 'lat_bucket{le="+Inf"} 3' in lines      # == count
    assert "lat_count 3" in lines
    assert any(line.startswith("lat_sum 9.2") for line in lines)


# ---------------------------------------------------------------------------
# labeled series (r12)
# ---------------------------------------------------------------------------


def test_labeled_series_are_distinct_and_flatten():
    """labels= makes one instance per (name, labels) combination; label
    order in the dict is irrelevant; scalars flatten as name.k=v."""
    reg = MetricsRegistry()
    a = reg.counter("toks", "per tenant", labels={"tenant": "a"})
    b = reg.counter("toks", labels={"tenant": "b"})
    plain = reg.counter("other")
    assert a is not b
    a.inc(3)
    b.inc(5)
    plain.inc()
    # canonical identity: key order in the labels dict doesn't matter
    assert reg.counter("toks", labels={"tenant": "a"}) is a
    two = reg.counter("multi", labels={"x": "1", "y": "2"})
    assert reg.counter("multi", labels={"y": "2", "x": "1"}) is two
    sc = reg.scalars()
    assert sc["toks.tenant=a"] == 3.0
    assert sc["toks.tenant=b"] == 5.0
    assert sc["other"] == 1.0
    assert "multi.x=1.y=2" in sc
    # one family, one kind: a labeled gauge under a counter family fails
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("toks", labels={"tenant": "c"})


def test_labeled_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs", "by tenant", labels={"tenant": "a"}).inc(2)
    reg.counter("reqs", labels={"tenant": "b", "reason": "eos"}).inc()
    h = reg.histogram("lat", start=0.1, factor=2.0, n_buckets=2,
                      labels={"tenant": "a"})
    h.observe(0.05)
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    assert 'reqs{tenant="a"} 2' in lines
    # labels render sorted by key
    assert 'reqs{reason="eos",tenant="b"} 1' in lines
    # ONE TYPE header per family, not per labeled series
    assert sum(1 for ln in lines if ln == "# TYPE reqs counter") == 1
    assert 'lat_bucket{tenant="a",le="0.1"} 1' in lines
    assert 'lat_bucket{tenant="a",le="+Inf"} 1' in lines
    assert 'lat_count{tenant="a"} 1' in lines
    # label values escape quotes/backslashes instead of corrupting lines
    reg.gauge("g", labels={"q": 'say "hi"\\'}).set(1)
    assert 'g{q="say \\"hi\\"\\\\"} 1' in reg.to_prometheus()


def test_prometheus_families_contiguous_despite_interleaved_creation():
    """Lazily-created per-tenant series register interleaved across
    families; the exposition must still emit each family as ONE
    contiguous block (strict parsers reject split families)."""
    reg = MetricsRegistry()
    reg.counter("toks", labels={"tenant": "a"}).inc()
    reg.counter("terms", labels={"tenant": "a"}).inc()
    reg.counter("toks", labels={"tenant": "b"}).inc()   # interleaved
    reg.counter("terms", labels={"tenant": "b"}).inc()
    lines = reg.to_prometheus().strip().splitlines()
    # "# TYPE <name> <kind>" / "# HELP <name> ..." -> token 2;
    # sample lines -> the name before any label brace
    fam_of = [ln.split()[2] if ln.startswith("#")
              else ln.split("{")[0] for ln in lines]
    seen, last = set(), None
    for fam in fam_of:
        if fam != last:
            assert fam not in seen, f"family {fam} split across the page"
            seen.add(fam)
            last = fam


def test_labeled_series_state_roundtrip():
    reg = MetricsRegistry()
    reg.counter("t", "help", labels={"tenant": "a"}).inc(7)
    reg.counter("t", labels={"tenant": "b"}).inc(1)
    reg.gauge("plain").set(2.0)
    h = reg.histogram("lat", labels={"tenant": "a"})
    h.observe(0.01)
    back = MetricsRegistry.from_state(reg.to_state())
    assert back.scalars() == reg.scalars()
    # restored labeled series resolve under the same (name, labels) and
    # keep counting
    c = back.counter("t", labels={"tenant": "a"})
    assert c.value == 7 and c.help == "help"
    c.inc()
    assert back.scalars()["t.tenant=a"] == 8
    assert back.counter("t", labels={"tenant": "b"}).value == 1
    # prometheus rendering survives the round trip too
    assert 'lat_count{tenant="a"} 1' in back.to_prometheus()


def test_engine_per_tenant_labeled_metrics():
    """Requests carrying tenant= produce labeled token/terminal series;
    tenantless requests don't (the default path stays label-free)."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, metrics=True,
                        tenants={"a": 3.0, "b": 1.0})
    rng = np.random.RandomState(7)
    for tenant in ("a", "a", "b"):
        eng.add_request(rng.randint(0, 512, (5,)).astype("int32"), 4,
                        tenant=tenant)
    out = eng.run()
    sc = eng.metrics.scalars()
    assert sc["serving_tenant_tokens_generated.tenant=a"] == 8
    assert sc["serving_tenant_tokens_generated.tenant=b"] == 4
    assert sc["serving_tenant_requests_terminal.reason=length.tenant=a"] == 2
    assert sc["serving_tenant_requests_terminal.reason=length.tenant=b"] == 1
    prom = eng.metrics.to_prometheus()
    assert 'serving_tenant_tokens_generated{tenant="a"} 8' in prom
    assert ('serving_tenant_requests_terminal'
            '{reason="length",tenant="b"} 1') in prom
    assert len(out) == 3


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------


def test_trace_recorder_balance_and_schema(tmp_path):
    clk = [0.0]
    tr = TraceRecorder(clock=lambda: clk[0])
    tr.process_name(1, "engine")
    tr.begin("outer", 2, 7)
    clk[0] = 0.5
    tr.begin("inner", 2, 7)
    clk[0] = 1.0
    assert tr.end(2, 7) == "inner"         # pops LIFO
    tr.instant("mark", 2, 7)
    assert tr.open_span(2, 7) == "outer"
    assert tr.end(2, 7) == "outer"
    with pytest.raises(ValueError, match="no open span"):
        tr.end(2, 7)
    tr.complete("phase", 0.25, 0.5, 1, 0)
    path = tr.save(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and xs[0]["dur"] == pytest.approx(0.5e6)
    # inner nested strictly inside outer on the timeline
    b = {(e["name"], e["ph"]): e["ts"] for e in evs if e["ph"] in "BE"}
    assert b[("outer", "B")] <= b[("inner", "B")]
    assert b[("inner", "E")] <= b[("outer", "E")]


def test_profiler_record_event_bridge():
    from paddle_tpu import profiler
    from paddle_tpu.serving import PID_HOST, attach_profiler, detach_profiler

    tr = TraceRecorder()
    sink = attach_profiler(tr)
    try:
        # idempotent per tracer: a re-attach returns the SAME sink and
        # must not double every span
        assert attach_profiler(tr) is sink
        with profiler.RecordEvent("host_span"):
            pass
    finally:
        detach_profiler(sink)
    spans = [e for e in tr.events
             if e["ph"] == "X" and e["name"] == "host_span"]
    assert len(spans) == 1 and spans[0]["pid"] == PID_HOST
    # detached: no more forwarding, and the tracer can be re-bridged
    with profiler.RecordEvent("after_detach"):
        pass
    assert not any(e["name"] == "after_detach" for e in tr.events)
    sink2 = attach_profiler(tr)
    assert sink2 is not sink
    detach_profiler(sink2)


# ---------------------------------------------------------------------------
# engine integration: the three artifacts
# ---------------------------------------------------------------------------


def _drive_mixed_load(eng, rng, n=8, cancel_one=True):
    rids = []
    for i in range(n):
        plen = int(rng.randint(3, 20))
        new = int(rng.randint(4, 12))
        rids.append(eng.add_request(
            rng.randint(0, 512, (plen,)).astype("int32"), new))
    if cancel_one:
        eng.cancel(rids[1])
    return rids


def test_engine_metrics_dir_artifacts(tmp_path):
    """run(metrics_dir=...) must leave (a) a TB event file whose scalars
    round-trip through the reader with >= 10 tags over >= 20 steps,
    (b) a schema-valid Chrome trace with balanced spans for every
    request, (c) a Prometheus dump whose terminal counters sum exactly
    to the finished requests — the r11 acceptance triple, chaos-free
    version (the chaos leg lives in test_serving_faults.py)."""
    from paddle_tpu.utils.tensorboard import read_scalars

    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=8,
                        metrics=True, trace=True)
    rng = np.random.RandomState(0)
    rids = _drive_mixed_load(eng, rng, n=8)
    out = eng.run(metrics_dir=str(tmp_path))

    # (a) TB scalars round-trip
    series = read_scalars(str(tmp_path))
    assert len(series) >= 10
    steps = {s for pts in series.values() for s, _ in pts}
    assert len(steps) >= 20
    # a non-trivial series really moved
    toks = dict(series["serving_tokens_generated"])
    assert toks[max(toks)] == eng.stats["tokens_generated"] > 0

    # (b) trace schema + balance, every request present
    doc = json.load(open(tmp_path / "trace.json"))
    evs = doc["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    depth = defaultdict(int)
    for e in evs:
        if e["ph"] == "B":
            depth[(e["pid"], e["tid"])] += 1
        elif e["ph"] == "E":
            depth[(e["pid"], e["tid"])] -= 1
            assert depth[(e["pid"], e["tid"])] >= 0, "E before B"
    assert all(v == 0 for v in depth.values())
    from paddle_tpu.serving import PID_REQUESTS

    traced_rids = {e["tid"] for e in evs if e["pid"] == PID_REQUESTS}
    assert traced_rids >= set(rids)

    # (c) Prometheus terminal counters == finished requests
    prom = open(tmp_path / "metrics.prom").read()
    totals = {}
    for line in prom.splitlines():
        if line.startswith("serving_requests_terminal_"):
            name, v = line.rsplit(" ", 1)
            totals[name.replace("serving_requests_terminal_", "")] = int(v)
    assert set(totals) == set(TERMINAL_REASONS)
    assert sum(totals.values()) == len(out) == len(rids)
    by_reason = TallyCounter(f.finish_reason for f in out.values())
    assert totals == {r: by_reason.get(r, 0) for r in TERMINAL_REASONS}
    assert "serving_ttft_s_bucket" in prom           # histograms exported


@pytest.mark.chaos
def test_chaos_run_metrics_dir_artifacts(tmp_path):
    """The r11 acceptance triple under FAULTS: a chaos run with
    run(metrics_dir=...) still produces round-trippable TB scalars
    (>= 10 tags over >= 20 steps), a balanced trace for every request
    INCLUDING preempted ones, and a .prom dump whose terminal counters
    sum to the finished requests."""
    from paddle_tpu.serving import FaultPlan, PID_REQUESTS
    from paddle_tpu.utils.tensorboard import read_scalars

    model = _model()
    plan = FaultPlan.random(11, n_steps=30, p_alloc=0.25, p_raise=0.10,
                            p_latency=0.10, step_tick_s=1e-3)
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=8,
                        chunk_tokens=8, max_queue=4, faults=plan,
                        metrics=True, trace=True)
    rng = np.random.RandomState(5)
    rids = [eng.add_request(
        rng.randint(0, 512, (int(rng.randint(3, 18)),)).astype("int32"),
        int(rng.randint(4, 10))) for _ in range(8)]
    out = eng.run(metrics_dir=str(tmp_path))
    assert set(out) == set(rids)

    series = read_scalars(str(tmp_path))
    assert len(series) >= 10
    assert len({s for pts in series.values() for s, _ in pts}) >= 20

    doc = json.load(open(tmp_path / "trace.json"))
    evs = doc["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    depth = defaultdict(int)
    for e in evs:
        if e["ph"] == "B":
            depth[(e["pid"], e["tid"])] += 1
        elif e["ph"] == "E":
            depth[(e["pid"], e["tid"])] -= 1
    assert all(v == 0 for v in depth.values())
    assert {e["tid"] for e in evs if e["pid"] == PID_REQUESTS} >= set(rids)
    if eng.stats["preemptions"]:           # preempted tracks balance too
        pre = {e["tid"] for e in evs if e["name"] == "preempt"}
        assert pre and all(depth.get((PID_REQUESTS, t), 0) == 0
                           for t in pre)

    prom = open(tmp_path / "metrics.prom").read()
    totals = {line.rsplit(" ", 1)[0]: int(line.rsplit(" ", 1)[1])
              for line in prom.splitlines()
              if line.startswith("serving_requests_terminal_")}
    assert sum(totals.values()) == len(out)
    assert plan.injected["alloc_fail"] + plan.injected["raise"] > 0


def test_engine_stats_phase_accounting():
    """r11 satellite: per-phase wall time reported separately, cumulative
    phases bounded by the step total, and stats_snapshot() is a COPY."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8)
    rng = np.random.RandomState(1)
    _drive_mixed_load(eng, rng, n=3, cancel_one=False)
    snap0 = eng.stats_snapshot()
    eng.run()
    for ph in ("admit", "prefill", "decode"):
        assert eng.stats[f"{ph}_s"] > 0
        assert eng.stats[f"last_{ph}_s"] >= 0
    phases = sum(eng.stats[f"{p}_s"] for p in ("admit", "prefill", "decode"))
    assert phases <= eng.stats["step_wall_s"] + 1e-6
    assert eng.stats["last_step_s"] + 1e-9 >= sum(
        eng.stats[f"last_{p}_s"] for p in ("admit", "prefill", "decode"))
    # the snapshot taken before the run did NOT move with the live dict
    assert snap0["tokens_generated"] == 0
    assert eng.stats["tokens_generated"] > 0
    snap1 = eng.stats_snapshot()
    eng.stats["tokens_generated"] = -1
    assert snap1["tokens_generated"] != -1
    eng.stats["tokens_generated"] = snap1["tokens_generated"]


def test_engine_metrics_survive_snapshot_restore():
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, metrics=True)
    rng = np.random.RandomState(2)
    _drive_mixed_load(eng, rng, n=3, cancel_one=False)
    for _ in range(4):
        eng.step()
    snap = eng.snapshot()
    # read AFTER the capture: it retires the decode in flight, which feeds
    # the token histograms
    before = eng.metrics.scalars()
    assert before["serving_steps"] == 4
    # default-policy engines snapshot the trivial FCFS policy state
    # (v3) and restore across it without disturbance
    assert snap["scheduler"]["policy"] == {"name": "fcfs"}
    eng2 = ServingEngine.restore(model, snap)
    assert eng2.metrics is not None
    assert eng2.metrics.scalars() == before
    out = eng2.run()                       # counters keep rising, no reset
    after = eng2.metrics.scalars()
    assert after["serving_steps"] > before["serving_steps"]
    total = sum(after[f"serving_requests_terminal_{r}"]
                for r in TERMINAL_REASONS)
    assert total == len(out)


def test_engine_accepts_empty_registry():
    """Regression: a fresh MetricsRegistry has len 0 and is FALSY — the
    ctor must attach it anyway (identity test, not truthiness)."""
    model = _model()
    reg = MetricsRegistry()
    assert not reg                         # the trap
    eng = ServingEngine(model, max_slots=2, page_size=8, metrics=reg)
    assert eng.metrics is reg
    rng = np.random.RandomState(4)
    _drive_mixed_load(eng, rng, n=2, cancel_one=False)
    eng.run()
    assert reg.scalars()["serving_requests_enqueued"] == 2


def test_run_flush_every_tail_flush(tmp_path):
    """Regression: a run shorter than flush_every still writes its final
    scalars to the event file (tail flush in the finally block)."""
    from paddle_tpu.utils.tensorboard import read_scalars

    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8)
    rng = np.random.RandomState(5)
    _drive_mixed_load(eng, rng, n=2, cancel_one=False)
    eng.run(metrics_dir=str(tmp_path), flush_every=10_000)
    series = read_scalars(str(tmp_path))
    assert len(series) >= 10
    toks = dict(series["serving_tokens_generated"])
    assert toks[max(toks)] == eng.stats["tokens_generated"] > 0


def test_restore_rebases_timestamps_across_clock_bases():
    """Regression: restoring in a 'new process' whose monotonic clock
    reads far BELOW the snapshotted one must not feed negative durations
    into the latency histograms, and a deadline-bearing request resumes
    with its remaining budget (relative intervals preserved)."""
    model = _model()
    clock_a = [10_000.0]                   # old process: high clock base
    eng = ServingEngine(model, max_slots=2, page_size=8, metrics=True,
                        clock=lambda: clock_a[0])
    rng = np.random.RandomState(6)
    rid = eng.add_request(rng.randint(0, 512, (6,)).astype("int32"), 6,
                          deadline_s=100.0)
    for _ in range(2):
        eng.step()
        clock_a[0] += 1.0
    snap = eng.snapshot()

    clock_b = [5.0]                        # new process: fresh low base
    eng2 = ServingEngine.restore(model, snap, clock=lambda: clock_b[0])
    req = next(s.request for s in eng2._slots if s is not None)
    assert req.t_enqueue >= 0              # rebased, not raw 10_000
    assert not req.expired(clock_b[0])     # remaining budget intact
    out = eng2.run()
    assert out[rid].ok
    sc = eng2.metrics.scalars()
    assert sc["serving_e2e_latency_s_min"] >= 0
    assert sc["serving_tbt_s_min"] >= 0
    assert sc["serving_e2e_latency_s_count"] == 1


def test_engine_off_by_default_pays_nothing():
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8)
    assert eng.metrics is None and eng.tracer is None
    rng = np.random.RandomState(3)
    _drive_mixed_load(eng, rng, n=2, cancel_one=False)
    eng.run()                              # no registry, no trace, no crash


# ---------------------------------------------------------------------------
# no-new-imports guard — the policy itself (allowed roots, per-file
# network scoping) lives in paddle_tpu/analysis/import_guard.py; these
# tests are thin invocations keeping the contract on the tier-1 path.
# ---------------------------------------------------------------------------


def test_serving_imports_only_jax_numpy_stdlib():
    """The serving package (metrics + tracing included) must stay
    importable with only jax/numpy/stdlib — observability cannot drag in
    tensorboard/prometheus/opentelemetry client deps — and the network
    stdlib (asyncio/http/socket, plus json) is scoped to the front end:
    a scheduler or engine change that starts talking to the network
    fails HERE, not in a security review."""
    from paddle_tpu.analysis import run

    findings = [f for f in run(rules=["import-guard"],
                               paths=["paddle_tpu/serving"])
                if f.active]
    assert not findings, "disallowed/mis-scoped absolute imports:\n" + \
        "\n".join(f.format() for f in findings)


def test_int4_kv_helpers_import_only_jax_numpy_stdlib():
    """The int4 pack/unpack helpers the KV pool and paged kernels share
    (ops/quant_ops.py, r14) sit on the serving-critical import path — the
    same no-new-deps discipline applies: jax/numpy/stdlib only, with
    paddle_tpu-relative imports free."""
    from paddle_tpu.analysis import run
    from paddle_tpu.ops import quant_ops

    findings = [f for f in run(rules=["import-guard"],
                               paths=["paddle_tpu/ops/quant_ops.py"])
                if f.active]
    assert not findings, "disallowed absolute imports:\n" + \
        "\n".join(f.format() for f in findings)
    for helper in ("pack_int4", "unpack_int4", "quantize_int4_per_token",
                   "quantize_per_token"):
        assert callable(getattr(quant_ops, helper))


def test_serving_runtime_modules_loaded_clean():
    """Belt to the AST braces: every serving module is already imported
    (this file imported the package) — none of the forbidden client
    libraries may have come along for the ride."""
    for mod in ("metrics", "tracing", "flight_recorder", "kv_pool",
                "prefix_cache", "scheduler", "engine", "faults",
                "snapshot", "drafter"):
        assert f"paddle_tpu.serving.{mod}" in sys.modules
    for banned in ("tensorboard", "prometheus_client", "opentelemetry",
                   "tensorboardX", "visualdl"):
        assert banned not in sys.modules
