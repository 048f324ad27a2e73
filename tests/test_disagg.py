"""Disaggregated multi-replica serving (ISSUE r15 tentpole).

Acceptance contracts, all CPU-runnable (``disagg`` marker):

  * the prefill→decode handoff round-trips page payloads BIT-EXACTLY
    (fp, int8 and nibble-packed int4 pages, scale planes included), a
    foreign layout is refused with the per-key diff, and both pools'
    refcounts audit clean after the adoption;
  * a routed 2-replica disaggregated cluster produces greedy outputs
    token-for-token identical to one monolithic engine — fp/int8 ×
    jnp/kernel, under pool-pressure preemption, and with the handoff
    fabric faulted (degraded records re-prefill on the decode replica);
  * router-global WFQ: member policies share ONE virtual-counter table,
    ``vt == served/weight`` holds across the cluster exactly, and
    preempt/recompute never double-bills;
  * seeded FaultPlans against every replica keep the r10 invariants
    across the replica boundary: every request exactly one terminal,
    leak-free drain on every replica (conftest audits every step);
  * dispatching decode N+1 before decode N is read (every engine's step)
    is parity-exact (with and without preemption/cancel) and
    snapshot/restore quiesces it.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.generation import build_generate_fn
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (FaultPlan, Router, ServingEngine,
                                TERMINAL_REASONS, make_cluster)

pytestmark = pytest.mark.disagg

# 1-layer models (r13 tier-1 budget precedent): routing, handoff,
# fairness and dispatch-ahead properties are layer-count-independent —
# multi-layer paged-KV exactness lives in test_serving.py
CFG = dict(vocab_size=512, hidden_size=64, num_layers=1, num_heads=2,
           max_seq_len=96, dropout=0.0)


def _model(seed=3, **over):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**{**CFG, **over}))
    m.eval()
    return m


def _prompts(rng, lens, vocab=512):
    return [rng.randint(0, vocab, (n,)).astype("int32") for n in lens]


def _dense_refs(model, prompts, news, int8=False):
    outs = []
    for p, n in zip(prompts, news):
        fn = build_generate_fn(model, n, greedy=True, int8=int8)
        outs.append(np.asarray(fn(p[None]))[0, len(p):])
    return outs


# ---------------------------------------------------------------------------
# the handoff wire format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_bits", [None, 8, 4])
def test_handoff_roundtrip_bitexact(kv_bits):
    """Export on the prefill replica, ingest on the decode replica: the
    adopted full pages must be byte-identical to the payload (quantized
    pages ride with their scale planes), the sender must end the
    transfer holding zero pages, and the decode replica must then finish
    the request with the exact single-engine greedy continuation."""
    model = _model()
    kw = dict(max_slots=2, page_size=8, num_pages=32, kv_bits=kv_bits)
    prompt = _prompts(np.random.RandomState(5), [21])[0]
    ref = ServingEngine(model, **kw)
    rid_ref = ref.add_request(prompt, 8)
    want = ref.run()[rid_ref].tokens

    pre = ServingEngine(model, role="prefill", **kw)
    dec = ServingEngine(model, role="decode", **kw)
    rid = pre.add_request(prompt, 8)
    steps = 0
    while not pre._handoff_out:
        pre.step()
        steps += 1
        assert steps < 20, "prefill replica never exported"
    assert not pre.has_work and pre.pool.pages_in_use == 0
    (h,) = pre.drain_handoffs()
    assert h["version"] == 5 and h["n_pages"] >= 1
    bufs = h["payload"]["buffers"]
    assert set(bufs) == ({"k", "v", "ks", "vs"} if kv_bits
                         else {"k", "v"})
    assert h["nbytes"] == sum(a.nbytes for a in bufs.values()) > 0
    assert pre.stats["handoffs_out"] == 1
    assert pre.stats["handoff_bytes"] == h["nbytes"]

    assert dec.ingest_handoff(h) == rid
    done = {}
    first_pages = None
    while dec.has_work:
        for f in dec.step():
            done[f.rid] = f
        if first_pages is None:
            (st,) = [s for s in dec._slots if s is not None]
            first_pages = list(st.pages)
            # full prompt pages adopt bit-exactly — compare every
            # buffer row against the wire payload (the partial tail
            # page is the one decode writes into, so compare the
            # immutable full-page prefix)
            nfull = int(h["base_len"]) // 8
            for name, arr in bufs.items():
                got = np.asarray(dec.pool.buffers[name])[
                    :, first_pages[:nfull]]
                np.testing.assert_array_equal(got, arr[:, :nfull])
    np.testing.assert_array_equal(done[rid].tokens, want)
    assert dec.stats["handoffs_in"] == 1
    # zero recompute: the pages were adopted, not re-prefilled
    assert dec.stats["recompute_tokens"] == 0
    assert dec.pool.pages_in_use == 0
    pre.check_invariants()
    dec.check_invariants()


def test_handoff_layout_mismatch_refused():
    """A payload from an int8 pool must be refused by an fp pool (and
    vice versa) with the offending keys in the error — silent byte
    reinterpretation is the one unforgivable failure mode here."""
    model = _model()
    pre = ServingEngine(model, role="prefill", max_slots=2, page_size=8,
                        num_pages=32, kv_bits=8)
    dec = ServingEngine(model, role="decode", max_slots=2, page_size=8,
                        num_pages=32)
    pre.add_request(np.arange(12, dtype=np.int32), 4)
    while not pre._handoff_out:
        pre.step()
    (h,) = pre.drain_handoffs()
    with pytest.raises(ValueError, match="kv_bits|page_dtype"):
        dec.ingest_handoff(h)
    # nothing stuck: the decode replica took no record, holds no pages
    assert not dec._handoff_in and dec.pool.pages_in_use == 0
    pre.check_invariants()
    dec.check_invariants()


def test_prefill_role_refuses_ingest_and_router_validates():
    model = _model()
    pre = ServingEngine(model, role="prefill", max_slots=2, page_size=8,
                        num_pages=32)
    with pytest.raises(ValueError, match="prefill"):
        pre.ingest_handoff({"payload": None})
    with pytest.raises(ValueError, match="role"):
        ServingEngine(model, role="bogus")
    with pytest.raises(ValueError, match="decode"):
        Router([pre])
    with pytest.raises(ValueError, match="replica"):
        Router([])
    # dispatching ahead of the read is the step, not a mode: no keyword
    with pytest.raises(TypeError, match="double_buffer"):
        ServingEngine(model, double_buffer=True)


# ---------------------------------------------------------------------------
# routed-cluster greedy parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["fp_jnp", "fp_kernel", "int8_jnp",
                                  "int8_kernel"])
def test_disagg_cluster_greedy_parity(mode):
    """Acceptance: 2-replica disaggregated greedy outputs are
    token-for-token the single-engine outputs, fp/int8 × jnp/kernel,
    with every request crossing the replica boundary exactly once."""
    int8, kernel = "int8" in mode, "kernel" in mode
    model = _model()
    rng = np.random.RandomState(1)
    prompts = _prompts(rng, [7, 19, 12])
    news = [8, 5, 10]
    kw = dict(max_slots=4, page_size=8, num_pages=48, int8=int8,
              use_paged_kernel=kernel)
    eng = ServingEngine(model, **kw)
    ref = eng.run(list(zip(prompts, news)))

    router = make_cluster(model, 2, disaggregate=True, **kw)
    rids = [router.add_request(p, n) for p, n in zip(prompts, news)]
    out = router.run()
    for (r_ref, fin), rid in zip(sorted(ref.items()), rids):
        np.testing.assert_array_equal(fin.tokens, out[rid].tokens)
    assert router.stats["handoffs"] == len(prompts)
    assert router.stats["handoff_bytes"] > 0
    assert router.stats["degraded_handoffs"] == 0
    router.check_invariants()
    for eng_i in router.replicas:
        assert eng_i.pool.pages_in_use == 0


def test_disagg_parity_under_pool_pressure_preemption():
    """Preemption on the decode replica (tiny pool, long continuations)
    must not break cross-replica parity: recompute re-prefills from the
    ORIGINAL prompt + generated-so-far, exactly as in one engine."""
    model = _model()
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, [16, 24])
    news = [24, 20]
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=64)
    ref = eng.run(list(zip(prompts, news)))

    # prefill replica roomy, decode replica page-starved: growth there
    # must preempt and recompute
    pre = ServingEngine(model, role="prefill", max_slots=2, page_size=8,
                        num_pages=64)
    dec = ServingEngine(model, role="decode", max_slots=2, page_size=8,
                        num_pages=9, prefix_cache=False)
    router = Router([pre, dec])
    rids = [router.add_request(p, n) for p, n in zip(prompts, news)]
    out = router.run()
    for (_, fin), rid in zip(sorted(ref.items()), rids):
        np.testing.assert_array_equal(fin.tokens, out[rid].tokens)
    assert dec.stats["preemptions"] >= 1
    assert dec.stats["recompute_tokens"] > 0


def test_dispatch_ahead_parity_and_overlap_accounting():
    """The decode sync comes one step after the dispatch: outputs stay
    token-for-token the dense decoder's (schedule-invariant greedy),
    under pool pressure too, and the sync-time ledger and the two
    counters of the order actually record."""
    model = _model()
    rng = np.random.RandomState(3)
    prompts = _prompts(rng, [9, 14, 22])
    news = [14, 10, 8]
    refs = _dense_refs(model, prompts, news)
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=10)
    out = eng.run(list(zip(prompts, news)))
    for ref, rid in zip(refs, sorted(out)):
        np.testing.assert_array_equal(ref, out[rid].tokens)
    s = eng.stats
    assert s["decode_sync_s"] > 0.0
    assert s["decode_ahead"] > s["decode_calls"] // 2
    assert s["decode_ahead"] + s["decode_sync_first"] < s["decode_calls"]
    assert eng._inflight is None and eng.pool.pages_in_use == 0


def test_cancel_mid_flight_drops_dead_tokens():
    """Cancelling a request whose decode dispatch is still in flight:
    retirement must skip the dead slot (identity check), deliver exactly
    one terminal, and leak nothing."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=32)
    ra = eng.add_request(np.arange(6, dtype=np.int32), 20)
    rb = eng.add_request(np.arange(3, 12, dtype=np.int32), 20)
    eng.step()                   # admit+prefill+dispatch, sync deferred
    assert eng._inflight is not None
    assert eng.cancel(ra)
    terminals = {}
    while eng.has_work:
        for f in eng.step():
            assert f.rid not in terminals
            terminals[f.rid] = f
    assert terminals[ra].finish_reason == "cancelled"
    assert terminals[rb].finish_reason == "length"
    assert len(terminals[rb].tokens) == 20
    assert eng.pool.pages_in_use == 0


def test_disagg_snapshot_restores_handoff_state():
    """snapshot/restore across the handoff boundary: a decode replica
    with an un-admitted inbox record resumes exactly — same continuation
    as the unsnapshotted run."""
    from paddle_tpu.serving import restore_engine, snapshot_engine

    model = _model()
    kw = dict(max_slots=2, page_size=8, num_pages=32)
    prompt = _prompts(np.random.RandomState(11), [13])[0]
    want = ServingEngine(model, **kw).run([(prompt, 8)])
    (want_fin,) = want.values()

    pre = ServingEngine(model, role="prefill", **kw)
    pre.add_request(prompt, 8)
    while not pre._handoff_out:
        pre.step()
    (h,) = pre.drain_handoffs()
    dec = ServingEngine(model, role="decode", **kw)
    rid = dec.ingest_handoff(h)
    snap = snapshot_engine(dec)
    dec2 = restore_engine(model, snap)
    assert len(dec2._handoff_in) == 1
    done = dec2.run()
    np.testing.assert_array_equal(done[rid].tokens, want_fin.tokens)
    dec2.check_invariants()


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------


def test_router_prefix_affinity_routes_to_cached_replica():
    """Two monolithic replicas, a shared system prefix: after the first
    request lands (wherever), every later request sharing the prefix
    must follow it to the SAME replica — the router's probe_len prefers
    the warm cache over the idle replica."""
    model = _model()
    router = make_cluster(model, 2, max_slots=2, page_size=8,
                          num_pages=64)
    sys_prefix = np.arange(100, 132, dtype=np.int32)        # 4 full pages
    rng = np.random.RandomState(9)

    def req(i):
        tail = rng.randint(0, 512, (5 + i,)).astype("int32")
        return np.concatenate([sys_prefix, tail])

    router.run([(req(0), 4)])
    first = int(np.argmax(router.stats["routed"]))
    for i in range(1, 4):
        router.add_request(req(i), 4)
        router.run()
    assert router.stats["routed"][first] == 4
    assert router.stats["prefix_routed"] >= 3
    assert router.stats["prefix_match_tokens"] >= 3 * 32
    # the warm replica really served the prefix from cache
    assert router.replicas[first].stats["prefix_hit_tokens"] >= 3 * 32


def test_router_load_balance_and_cluster_max_queue():
    """Cold caches: requests spread by load score; the cluster queue
    bound rejects at the ROUTER with a proper terminal (engines never
    see the overflow)."""
    model = _model()
    router = make_cluster(model, 2, max_slots=1, page_size=8,
                          num_pages=16, router_max_queue=2,
                          prefix_cache=False)
    rng = np.random.RandomState(4)
    rids = [router.add_request(p, 30)
            for p in _prompts(rng, [6, 7, 8, 9, 10, 11])]
    done = router.run()
    assert sorted(done) == sorted(rids)
    by_reason = {}
    for fin in done.values():
        by_reason.setdefault(fin.finish_reason, []).append(fin)
    assert len(by_reason.get("rejected", [])) == router.stats["rejected"]
    assert router.stats["rejected"] >= 1
    for fin in by_reason["rejected"]:
        assert fin.tokens.size == 0 and fin.n_steps == 0
    # both replicas actually admitted work (load spread, not pile-up)
    assert all(n > 0 for n in router.stats["routed"])
    # engines never counted the router-level rejects
    assert sum(e.stats["rejected"] for e in router.replicas) == 0


def test_router_streams_tokens_fleet_wide():
    """on_token assigned on the router observes every replica's tokens;
    rids are globally unique so one stream disambiguates the fleet."""
    model = _model()
    router = make_cluster(model, 2, disaggregate=True, max_slots=2,
                          page_size=8, num_pages=32)
    seen = {}
    router.on_token = lambda rid, tok: seen.setdefault(rid, []).append(tok)
    rng = np.random.RandomState(2)
    rids = [router.add_request(p, 6) for p in _prompts(rng, [5, 9])]
    done = router.run()
    for rid in rids:
        np.testing.assert_array_equal(np.asarray(seen[rid], np.int32),
                                      done[rid].tokens)


# ---------------------------------------------------------------------------
# router-global WFQ
# ---------------------------------------------------------------------------


def test_cluster_wfq_global_virtual_counters_exact():
    """3 weighted tenants over a 2-replica cluster sharing one
    ClusterWFQState: every member policy aliases the SAME vt table, and
    at drain vt[t] equals the tenant's total first-time-served tokens /
    weight EXACTLY — cross-replica, preemption and handoff included,
    with no double billing."""
    from paddle_tpu.serving import Request

    model = _model()
    weights = {"a": 1.0, "b": 2.0, "c": 4.0}
    router = make_cluster(model, 2, disaggregate=True, tenants=weights,
                          max_slots=2, page_size=8, num_pages=12,
                          chunk_tokens=8, prefix_cache=False)
    pols = [e.scheduler.policy for e in router.replicas]
    assert all(p.vt is pols[0].vt for p in pols[1:])
    assert all(p.tenants is pols[0].tenants for p in pols[1:])

    rng = np.random.RandomState(6)
    reqs = []
    for i in range(9):
        t = "abc"[i % 3]
        plen = int(rng.randint(5, 18))
        reqs.append(Request(
            prompt=rng.randint(0, 512, (plen,)).astype("int32"),
            max_new_tokens=int(rng.randint(4, 10)), tenant=t))
    done = router.run(reqs)
    assert sorted(done) == sorted(r.rid for r in reqs)
    # exactness: every token charged exactly once cluster-wide — the
    # full prompt plus every generated token, split across the replica
    # boundary.  The prefill replica bills prompt + the carry token and
    # the wire record carries vt_charged forward, so the decode replica
    # bills exactly the remaining tokens - 1; the monotone high-water
    # makes re-admissions and preemption recompute bill zero.
    vt = pols[0].vt
    for r in reqs:
        # the ORIGINAL object freezes at handoff: prompt + carry token
        assert r.vt_charged == r.prompt_len + 1
    for t, w in weights.items():
        served = sum(r.prompt_len + len(done[r.rid].tokens)
                     for r in reqs if r.tenant == t)
        assert vt[t] == pytest.approx(served / w)
    # residency ledgers zeroed on every member
    for p in pols:
        assert all(v == 0 for v in p.resident.values())


def test_cluster_wfq_quota_is_cluster_wide():
    """max_resident on a shared state counts residents across ALL
    replicas — a tenant cannot double its concurrency by having slots
    on two replicas at once."""
    from paddle_tpu.serving import ClusterWFQState, TenantConfig, WFQPolicy

    state = ClusterWFQState({"t": TenantConfig(weight=1.0,
                                               max_resident=1)})
    pa = WFQPolicy(state=state)
    pb = WFQPolicy(state=state)

    class _R:
        def __init__(self, rid):
            self.rid, self.tenant, self.arrival = rid, "t", 0.0
    ra, rb = _R(1), _R(2)
    pa.push(ra)
    pb.push(rb)
    pa.on_admit(ra)
    # tenant t is at its cluster-wide cap: the OTHER replica must not
    # admit from its queue either
    assert pb.peek() is None
    pa.on_release(ra)
    assert pb.peek() is rb
    with pytest.raises(ValueError, match="ClusterWFQState"):
        WFQPolicy(tenants={"x": 1.0}, state=state)


# ---------------------------------------------------------------------------
# chaos across the replica boundary
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_handoff_fault_degrades_to_recompute_with_exact_output():
    """A scripted handoff-phase fault drops the page payloads: the
    records still deliver, the decode replica re-prefills them (charged
    as recompute, billed zero by the high-water mark), and the greedy
    continuation is STILL token-for-token exact."""
    model = _model()
    kw = dict(max_slots=2, page_size=8, num_pages=48)
    rng = np.random.RandomState(8)
    prompts = _prompts(rng, [10, 17])
    news = [9, 7]
    ref = ServingEngine(model, **kw).run(list(zip(prompts, news)))

    plan = FaultPlan(raise_steps={1: "handoff", 2: "handoff",
                                  3: "handoff"})
    pre = ServingEngine(model, role="prefill", faults=plan, **kw)
    dec = ServingEngine(model, role="decode", **kw)
    router = Router([pre, dec])
    rids = [router.add_request(p, n) for p, n in zip(prompts, news)]
    out = router.run()
    for (_, fin), rid in zip(sorted(ref.items()), rids):
        np.testing.assert_array_equal(fin.tokens, out[rid].tokens)
    assert pre.stats["handoff_faults"] >= 1
    assert router.stats["degraded_handoffs"] >= 1
    assert dec.stats["recompute_tokens"] > 0      # re-prefilled there
    # a degraded handoff ships no payload bytes
    assert pre.stats["handoff_bytes"] == router.stats["handoff_bytes"]
    router.check_invariants()


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 2])
def test_chaos_cluster_terminal_totality_and_leak_freedom(seed):
    """Seeded FaultPlans on BOTH replicas of a disaggregated cluster
    (alloc exhaustion, phase exceptions — including the handoff phase —
    and virtual latency): every request ends in exactly one terminal
    across the fleet, and every replica drains leak-free.  The conftest
    fixture audits check_invariants() on every replica's every step."""
    model = _model()
    pre = ServingEngine(
        model, role="prefill", max_slots=2, page_size=8, num_pages=16,
        chunk_tokens=8, max_queue=4,
        faults=FaultPlan.random(seed, n_steps=30, p_alloc=0.15,
                                p_raise=0.12, p_latency=0.1,
                                max_latency_s=0.01, step_tick_s=1e-3))
    dec = ServingEngine(
        model, role="decode", max_slots=2, page_size=8, num_pages=16,
        chunk_tokens=8,
        faults=FaultPlan.random(seed + 100, n_steps=30, p_alloc=0.15,
                                p_raise=0.12, p_latency=0.1,
                                max_latency_s=0.01, step_tick_s=1e-3))
    router = Router([pre, dec])
    rng = np.random.RandomState(40 + seed)
    rids, terminals, steps = [], {}, 0

    def make(deadline=None):
        plen = int(rng.randint(3, 14))
        return router.add_request(
            rng.randint(0, 512, (plen,)).astype("int32"),
            int(rng.randint(3, 8)), deadline_s=deadline)

    for _ in range(2):
        rids.append(make())
    while router.has_work or steps < 12:
        steps += 1
        assert steps < 500, "cluster chaos run failed to converge"
        if steps in (2, 4, 6):
            rids.append(make(0.02 if steps == 4 else None))
        if steps == 5:
            router.cancel(rids[0])
        for fin in router.step():
            assert fin.rid not in terminals, \
                f"rid {fin.rid} reached two terminal states"
            terminals[fin.rid] = fin
    assert set(terminals) == set(rids)
    for fin in terminals.values():
        assert fin.finish_reason in TERMINAL_REASONS
    assert (pre.faults.injected["raise"]
            + pre.faults.injected["alloc_fail"]
            + dec.faults.injected["raise"]
            + dec.faults.injected["alloc_fail"]) > 0
    for eng in router.replicas:
        assert eng.scheduler.n_active == 0
        assert eng.pool.pages_in_use == 0
        assert not eng._handoff_in and not eng._handoff_out
        eng.check_invariants()


# ---------------------------------------------------------------------------
# fleet observability
# ---------------------------------------------------------------------------


def test_cluster_metrics_aggregate_and_prometheus_page():
    """Per-replica registries roll up: counters sum, histogram buckets
    merge (so cluster quantiles are REAL, r16 — not dropped), and the
    cluster scrape page labels every series with its replica while
    keeping one HELP/TYPE per family."""
    model = _model()
    router = make_cluster(model, 2, disaggregate=True, max_slots=2,
                          page_size=8, num_pages=32)
    router.attach_metrics()
    rng = np.random.RandomState(12)
    done = router.run([(p, 5) for p in _prompts(rng, [6, 11, 8])])
    agg = router.scalars()
    want_tokens = sum(len(f.tokens) for f in done.values())
    assert agg["serving_tokens_generated"] == want_tokens
    assert agg["serving_handoffs_out"] == 3
    assert agg["serving_handoffs_in"] == 3
    # r16: bucket-merged histograms aggregate — cluster quantiles exist
    assert any(k.startswith("serving_step_s_p") for k in agg)
    assert agg["serving_step_s_count"] > 0
    page = router.to_prometheus()
    assert 'replica="replica0"' in page and 'replica="replica1"' in page
    # one TYPE header per family even with per-replica series
    assert page.count("# TYPE serving_tokens_generated counter") == 1
    # histogram mean recomputed from summed totals
    assert "serving_step_s" in page


def test_frontend_serves_a_router():
    """The HTTP front end drives a Router end-to-end: completions route
    through the fleet with exact tokens, /healthz aggregates replicas,
    /metrics exposes the replica-labeled page + HTTP series."""
    import asyncio
    import json

    from paddle_tpu.serving import ServingFrontend

    model = _model()
    router = make_cluster(model, 2, disaggregate=True, max_slots=2,
                          page_size=8, num_pages=32, chunk_tokens=8)
    # precompile both replicas' programs so the server loop is steps
    router.run([(np.arange(4, dtype=np.int32), 2)])
    prompt = np.asarray([7, 3, 9, 11, 2, 5], np.int32)
    ref = np.asarray(build_generate_fn(model, 6, greedy=True)(
        prompt[None]))[0, len(prompt):]

    def _http(method, path, payload=None):
        body = json.dumps(payload).encode() if payload is not None else b""
        return (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode() + body

    async def _call(port, method, path, payload=None):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(_http(method, path, payload))
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), 60.0)
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        status = int(head.decode("latin-1").split("\r\n")[0].split()[1])
        return status, body

    async def main():
        fe = await ServingFrontend(router).start()
        try:
            comp = await _call(fe.port, "POST", "/v1/completions",
                               {"prompt": [int(t) for t in prompt],
                                "max_tokens": 6, "stream": False})
            health = await _call(fe.port, "GET", "/healthz")
            metrics = await _call(fe.port, "GET", "/metrics")
        finally:
            await fe.stop()
        return comp, health, metrics

    (cs, cbody), (hs, hbody), (ms, mbody) = asyncio.run(main())
    assert cs == 200
    np.testing.assert_array_equal(
        np.asarray(json.loads(cbody)["tokens"], np.int32), ref)
    assert hs == 200
    health = json.loads(hbody)
    assert health["replicas"] == 2 and health["roles"] == ["prefill",
                                                           "decode"]
    assert ms == 200
    text = mbody.decode()
    assert 'replica="replica0"' in text
    assert "serving_http_requests" in text
