"""Multi-tenant scheduling (r12): SchedulerPolicy, weighted fair
queueing, quotas, preemption accounting, snapshot survival.

Policy-level tests drive WFQPolicy directly (pure host-side state, no
model); engine-level tests assert the integration contracts — weighted
service under contention, preempted requests keeping their tenant's
virtual counter (no double-charge of recomputed tokens), quota
backpressure becoming ``rejected`` terminals, and virtual counters
surviving snapshot/restore.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (FCFSPolicy, KVPool, Request, ServingEngine,
                                TenantConfig, WFQPolicy)
from paddle_tpu.serving.tenancy import make_policy

# 1-layer model: these files assert scheduling/fault/metrics properties,
# not KV layout — multi-layer paged-KV exactness lives in test_serving.py.
CFG = dict(vocab_size=512, hidden_size=64, num_layers=1, num_heads=2,
           max_seq_len=96, dropout=0.0)


def _model(seed=3):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**CFG))
    m.eval()
    return m


def _req(rng, plen=4, new=4, tenant=None, deadline=None):
    return Request(prompt=rng.randint(0, 512, (plen,)).astype("int32"),
                   max_new_tokens=new, tenant=tenant, deadline_s=deadline)


# ---------------------------------------------------------------------------
# policy units (no model)
# ---------------------------------------------------------------------------


def test_tenant_config_validation():
    with pytest.raises(ValueError, match="weight"):
        TenantConfig(weight=0)
    with pytest.raises(ValueError, match="max_resident"):
        TenantConfig(max_resident=0)
    with pytest.raises(ValueError, match="max_waiting"):
        TenantConfig(max_waiting=-1)


def test_make_policy_resolution():
    assert isinstance(make_policy(None), FCFSPolicy)
    assert isinstance(make_policy("fcfs"), FCFSPolicy)
    assert isinstance(make_policy("wfq"), WFQPolicy)
    # naming tenants implies wanting isolation
    assert isinstance(make_policy(None, {"a": 2.0}), WFQPolicy)
    custom = WFQPolicy()
    assert make_policy(custom) is custom
    with pytest.raises(ValueError, match="unknown"):
        make_policy("srpt")
    with pytest.raises(ValueError, match="wfq"):
        make_policy("fcfs", {"a": 1.0})


def test_fcfs_policy_is_the_old_deque():
    rng = np.random.RandomState(0)
    pol = FCFSPolicy()
    a, b, c = _req(rng), _req(rng), _req(rng)
    for r in (a, b, c):
        pol.push(r)
    assert pol.peek() is a and len(pol) == 3
    assert pol.pop() is a
    pol.requeue_head(a)                    # preemption: back in front
    assert pol.peek() is a
    assert pol.remove(b.rid) is b and pol.remove(b.rid) is None
    assert list(pol) == [a, c]


def test_wfq_weighted_interleave_deterministic():
    """Equal per-pop charges, weights 2:1 -> admissions converge to 2:1,
    with a fully deterministic order (vt ties break on tenant name)."""
    rng = np.random.RandomState(1)
    pol = WFQPolicy({"a": 2.0, "b": 1.0})
    for _ in range(6):
        pol.push(_req(rng, tenant="a"))
    for _ in range(6):
        pol.push(_req(rng, tenant="b"))
    order = []
    for _ in range(9):
        req = pol.peek()
        assert pol.pop() is req
        pol.on_admit(req)
        pol.charge(req, 10)                # 10 tokens served
        pol.on_release(req)
        order.append(req.tenant)
    # vt_a rises 5/pop, vt_b 10/pop: a,b,a,a,b,a,a,b,a
    assert order == ["a", "b", "a", "a", "b", "a", "a", "b", "a"]
    assert order.count("a") == 6 and order.count("b") == 3
    assert pol.vt["a"] == pytest.approx(30.0)  # 6 pops * 10 / weight 2
    assert pol.vt["b"] == pytest.approx(30.0)  # 3 pops * 10 / weight 1


def test_wfq_fcfs_within_tenant_and_requeue_head():
    rng = np.random.RandomState(2)
    pol = WFQPolicy()
    first, second = _req(rng, tenant="t"), _req(rng, tenant="t")
    pol.push(first)
    pol.push(second)
    assert pol.pop() is first              # FIFO within the tenant
    pol.on_admit(first)
    pol.charge(first, 4)
    vt_before = pol.vt["t"]
    pol.on_release(first)                  # preempted: leaves its slot…
    pol.requeue_head(first)                # …and rejoins at the HEAD
    assert pol.peek() is first             # ahead of `second`
    assert pol.vt["t"] == vt_before        # counter untouched by requeue


def test_wfq_priority_tier_beats_counters():
    rng = np.random.RandomState(3)
    pol = WFQPolicy({"hi": TenantConfig(priority=1),
                     "lo": TenantConfig(weight=100.0)})
    pol.push(_req(rng, tenant="lo"))
    hi = _req(rng, tenant="hi")
    pol.push(hi)
    pol.charge(hi, 10_000)                 # huge counter, still first
    assert pol.peek() is hi


def test_wfq_idle_lift_prevents_banked_credit():
    """A tenant idling while others serve cannot spend the banked idle
    time monopolizing admission later: on return its counter lifts to
    the minimum over active tenants (never lowered)."""
    rng = np.random.RandomState(4)
    pol = WFQPolicy()
    busy = _req(rng, tenant="busy")
    pol.push(busy)
    pol.pop()
    pol.on_admit(busy)                     # busy stays resident (active)
    pol.charge(busy, 90)
    pol.push(_req(rng, tenant="idler"))
    assert pol.vt["idler"] == pytest.approx(90.0)
    # and a tenant AHEAD of the pack is not pulled back down
    ahead = _req(rng, tenant="idler")
    pol.charge(ahead, 60)                  # idler now at 150, busy at 90
    pol.push(ahead)
    assert pol.vt["idler"] == pytest.approx(150.0)
    # the lift sees RESIDENT-ONLY tenants too (post-restore shape: all
    # of a tenant's requests in slots, none queued -> no queue entry)
    pol2 = WFQPolicy()
    seated = _req(rng, tenant="seated")
    pol2.on_admit(seated)                  # resident, never queued
    pol2.charge(seated, 40)
    pol2.push(_req(rng, tenant="late"))
    assert pol2.vt["late"] == pytest.approx(40.0)


def test_wfq_quotas_waiting_and_resident():
    rng = np.random.RandomState(5)
    pol = WFQPolicy({"q": TenantConfig(max_waiting=1, max_resident=1)})
    assert not pol.quota_reject("q")
    r1 = _req(rng, tenant="q")
    pol.push(r1)
    assert pol.quota_reject("q")           # waiting quota hit
    assert not pol.quota_reject("other")   # unknown tenants default-share
    # a rejected probe must not mint permanent tenant state
    assert "other" not in pol.tenants
    popped = pol.pop()
    pol.on_admit(popped)
    pol.push(_req(rng, tenant="q"))
    assert pol.peek() is None              # resident quota blocks admission
    pol.on_release(popped)
    assert pol.peek() is not None          # slot freed: eligible again


def test_wfq_expiry_and_remove_span_all_tenants():
    rng = np.random.RandomState(6)
    pol = WFQPolicy()
    keep = _req(rng, tenant="a")
    dead_a = _req(rng, tenant="a", deadline=0.1)
    dead_b = _req(rng, tenant="b", deadline=0.1)
    for r in (keep, dead_a, dead_b):
        r.t_enqueue = 0.0
        pol.push(r)
    expired = pol.pop_expired(now=1.0)
    assert set(expired) == {dead_a, dead_b}
    assert list(pol) == [keep]
    assert pol.remove(keep.rid) is keep and len(pol) == 0


# ---------------------------------------------------------------------------
# scheduler + engine integration
# ---------------------------------------------------------------------------


def test_scheduler_wfq_admission_order_with_pages():
    """Through the real FCFSScheduler plumbing: WFQ picks the lowest-
    counter tenant's head, FCFS within the tenant, pages still gate."""
    from paddle_tpu.serving import FCFSScheduler

    rng = np.random.RandomState(7)
    pool = KVPool(1, 1, 8, num_pages=9, page_size=8)
    sched = FCFSScheduler(n_slots=2, pool=pool, policy="wfq",
                          tenants={"a": 1.0, "b": 1.0})
    ra = _req(rng, plen=8, tenant="a")
    rb = _req(rng, plen=8, tenant="b")
    sched.add(ra)
    sched.add(rb)
    # charge AFTER both are active (an idle tenant's arrival would lift
    # its counter to the active minimum): a falls behind, b admits first
    sched.charge(ra, 100)
    adms = sched.schedule_step()
    assert [a.request for a in adms] == [rb, ra]       # b first: lower vt
    for a in adms:
        sched.release(a.slot, a.pages, a.request)
    assert sched.policy.resident == {"a": 0, "b": 0}


def test_engine_wfq_weighted_service_under_contention():
    """Weights 3:1 with saturating equal demand: the heavy tenant's
    requests finish disproportionately early.  Deterministic on CPU —
    greedy engine, all requests enqueued up front."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8,
                        tenants={"a": 3.0, "b": 1.0})
    assert eng.scheduler.policy.name == "wfq"
    rng = np.random.RandomState(8)
    n_each = 8
    tenant_of = {}
    for i in range(n_each):
        for t in ("a", "b"):
            rid = eng.add_request(
                rng.randint(0, 512, (4,)).astype("int32"), 4, tenant=t)
            tenant_of[rid] = t
    finish_order = []
    while eng.has_work:
        finish_order.extend(eng.step())
    assert len(finish_order) == 2 * n_each
    assert all(f.reason == "length" for f in finish_order)
    n_a = sum(1 for f in finish_order[:n_each]
              if tenant_of[f.rid] == "a")
    assert n_a > n_each - n_a, (
        f"heavy tenant finished only {n_a}/{n_each} of the early slots")
    # total service equal (everything completed), so final virtual
    # counters differ by exactly the weight ratio
    vt = eng.scheduler.policy.vt
    assert vt["b"] == pytest.approx(3.0 * vt["a"])


def test_engine_wfq_preempted_request_keeps_virtual_counter():
    """The ISSUE satellite edge case: a preempted request's recompute
    (chunked re-prefill of prompt + survived tokens) must NOT re-charge
    its tenant — at drain the tenant's counter equals exactly
    first-time-served tokens / weight, despite recompute_tokens > 0."""
    model = _model()
    rng = np.random.RandomState(51)
    A = rng.randint(0, 512, (8,)).astype("int32")
    B = rng.randint(0, 512, (16,)).astype("int32")
    # same pressure shape as test_engine_preempt_recompute_exact: 6
    # usable pages < both residents' worst case -> B preempts
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=7,
                        chunk_tokens=16, policy="wfq",
                        tenants={"a": 2.0, "b": 1.0})
    ra = eng.add_request(A, 24, tenant="a")
    rb = eng.add_request(B, 16, tenant="b")
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["recompute_tokens"] > 0
    assert out[ra].reason == "length" and out[rb].reason == "length"
    vt = eng.scheduler.policy.vt
    # first-time service: prompt + generated, charged exactly once
    assert vt["a"] == pytest.approx((8 + 24) / 2.0)
    assert vt["b"] == pytest.approx((16 + 16) / 1.0)


def test_wfq_spec_charges_accepted_only():
    """r13 satellite: with speculation on, WFQ bills ACCEPTED tokens only
    — rejected draft positions cost compute but never touch a tenant's
    virtual counter.  At drain each tenant's counter equals exactly
    (prompt + generated) / weight, the same invariant as the r12
    preempt-no-double-charge test, while the run provably rejected
    drafts (``stats["spec_rejected"] > 0`` via an adversarial drafter
    that always proposes wrong tokens for one leg of the load)."""

    class HalfWrongDrafter:
        """Oracle-free adversarial drafter: always proposes vocab-edge
        tokens a random-weights greedy decode essentially never picks —
        every draft rejects, so spec_rejected grows with every step."""

        def draft(self, history, max_tokens=None):
            k = 2 if max_tokens is None else min(2, int(max_tokens))
            return np.full((max(k, 0),), 511, np.int32)

    model = _model()
    rng = np.random.RandomState(60)
    A = rng.randint(0, 500, (8,)).astype("int32")
    B = rng.randint(0, 500, (16,)).astype("int32")
    eng = ServingEngine(model, max_slots=2, page_size=8, policy="wfq",
                        tenants={"a": 2.0, "b": 1.0}, spec_k=2,
                        drafter=HalfWrongDrafter())
    ra = eng.add_request(A, 24, tenant="a")
    rb = eng.add_request(B, 16, tenant="b")
    out = eng.run()
    assert out[ra].reason == "length" and out[rb].reason == "length"
    assert eng.stats["spec_rejected"] > 0
    assert eng.stats["spec_drafted"] == \
        eng.stats["spec_accepted"] + eng.stats["spec_rejected"]
    vt = eng.scheduler.policy.vt
    # served = prompt + generated, with NO term for rejected drafts
    assert vt["a"] == pytest.approx((8 + 24) / 2.0)
    assert vt["b"] == pytest.approx((16 + 16) / 1.0)


def test_engine_wfq_greedy_tokens_match_fcfs():
    """Fairness reorders ADMISSION, not math: the same request set
    produces token-for-token identical greedy outputs under FCFS and
    WFQ (each request's tokens depend only on its own prompt)."""
    model = _model()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 512, (int(rng.randint(3, 12)),))
               .astype("int32") for _ in range(6)]
    outs = {}
    for policy in ("fcfs", "wfq"):
        eng = ServingEngine(model, max_slots=2, page_size=8, policy=policy,
                            tenants=({"x": 2.0, "y": 1.0}
                                     if policy == "wfq" else None))
        rids = [eng.add_request(p, 6, tenant=("x" if i % 2 else "y")
                                if policy == "wfq" else None)
                for i, p in enumerate(prompts)]
        fins = eng.run()
        outs[policy] = [fins[r].tokens for r in rids]
    for got, want in zip(outs["wfq"], outs["fcfs"]):
        np.testing.assert_array_equal(got, want)


def test_engine_tenant_max_waiting_rejects_explicitly():
    model = _model()
    eng = ServingEngine(
        model, max_slots=1, page_size=8, policy="wfq",
        tenants={"cap": TenantConfig(max_waiting=1)})
    rng = np.random.RandomState(10)
    p = rng.randint(0, 512, (4,)).astype("int32")
    keep = eng.add_request(p, 3, tenant="cap")          # admitted soon
    eng.step()                                          # resident now
    q1 = eng.add_request(p.copy(), 3, tenant="cap")     # waits (1/1)
    q2 = eng.add_request(p.copy(), 3, tenant="cap")     # over quota
    other = eng.add_request(p.copy(), 3, tenant="free")  # unaffected
    out = eng.run()
    assert out[q2].reason == "rejected" and out[q2].tokens.size == 0
    assert out[keep].ok and out[q1].ok and out[other].ok
    assert eng.stats["rejected"] == 1


def test_engine_wfq_snapshot_restores_virtual_counters():
    """WFQ counters + tenant configs survive snapshot/restore: the
    fairness ledger carries across a restart and the resumed run
    completes every request."""
    from paddle_tpu.serving.snapshot import SNAPSHOT_VERSION

    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8,
                        tenants={"a": TenantConfig(weight=3.0),
                                 "b": TenantConfig(weight=1.0)})
    rng = np.random.RandomState(11)
    rids = [eng.add_request(rng.randint(0, 512, (6,)).astype("int32"), 8,
                            tenant=("a" if i % 2 else "b"))
            for i in range(6)]
    for _ in range(3):
        eng.step()
    assert eng.scheduler.n_waiting > 0          # genuinely mid-flight
    snap = eng.snapshot()
    # read AFTER the capture: it retires the decode in flight, which bills
    # the tokens it reads
    vt_before = dict(eng.scheduler.policy.vt)
    assert any(v > 0 for v in vt_before.values())
    assert snap["version"] == SNAPSHOT_VERSION == 5
    assert snap["scheduler"]["policy"]["name"] == "wfq"

    eng2 = ServingEngine.restore(model, snap)
    assert eng2.scheduler.policy.name == "wfq"
    assert eng2.scheduler.policy.vt == vt_before
    assert eng2.scheduler.policy.tenants["a"].weight == 3.0
    out = eng2.run()
    assert set(out) >= set(rids)
    assert all(out[r].ok for r in rids)
    # residency accounting was rebuilt from the restored slots: drained
    # engine shows zero residents per tenant
    assert all(v == 0 for v in eng2.scheduler.policy.resident.values())


# (Default-policy FCFS snapshots restoring across the v2->v3 bump is
# covered by test_metrics.py::test_engine_metrics_survive_snapshot_restore,
# which also asserts the trivial {"name": "fcfs"} policy state.)
