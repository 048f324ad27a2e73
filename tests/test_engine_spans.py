"""The serving engine's one span site and the counters beside it (PR 24).

Every dispatch, sync and phase of ``ServingEngine.step`` runs inside
``engine._span(name, **args)``: a ``jax.profiler.TraceAnnotation`` on the
profiler's clock which, for the four phases, also fills ``_phase_s`` as
the hand-written ``perf_counter`` blocks used to.  Here the annotation is
replaced by a recording fake, so the names, their nesting and their
closing under faults are checked without a profiler session; the
counters are checked against runs counted by hand.
"""

import gc
import time

import jax
import numpy as np
import pytest

import test_cohere2_moe as c2
import test_falcon_h1 as fh
import paddle_tpu as paddle
from paddle_tpu.models import Cohere2MoeForCausalLM, FalconH1ForCausalLM
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import FaultPlan, ServingEngine, TraceRecorder
from paddle_tpu.serving.engine import SLOW_STEP_S

CFG = dict(vocab_size=512, hidden_size=64, num_layers=1, num_heads=2,
           max_seq_len=96, dropout=0.0)

#: span -> the span it must open inside (the table of ISSUE 24; PR 38: a
#: first token is read after the decode's dispatch, inside ``engine.decode``,
#: and inside ``engine.prefill`` only where the engine never decodes; PR 39:
#: every program dispatch of a step has its span, inside the phase that
#: runs it, a carry put in prefill or with an adopted handoff)
PARENT = {
    "engine.step": None,
    "engine.admit": "engine.step",
    "engine.prefill": "engine.step",
    "engine.prefill_dispatch": "engine.prefill",
    "engine.carry_dispatch": ("engine.prefill", "engine.admit"),
    "engine.first_token_sync": "engine.decode",
    "engine.handoff": "engine.step",
    "engine.decode": "engine.step",
    "engine.decode_dispatch": "engine.decode",
    "engine.decode_sync": "engine.decode",
    "engine.cow_dispatch": "engine.admit",
    "engine.state_reset_dispatch": "engine.admit",
    "engine.ingest_dispatch": "engine.admit",
}
#: dispatches of a partial-tail prefix match, a model with recurrent state
#: and an adopted handoff: none of them in the GPT runs below
RARE = {"engine.cow_dispatch", "engine.state_reset_dispatch",
        "engine.ingest_dispatch"}
DISPATCHES = {n for n in PARENT if n.endswith("_dispatch")}
NEW_COUNTERS = ("prefill_sync_s", "admissions", "queue_wait_s",
                "first_tokens", "prefill_wait_s",
                "decode_calls_after_0_chunks", "decode_calls_after_1_chunk",
                "decode_calls_after_2plus_chunks", "decode_attended_tokens")


def _model(seed=3):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**CFG))
    m.eval()
    return m


def _prompt(rng, n):
    return rng.randint(0, 512, (n,)).astype("int32")


class _Recorder:
    """What the fake annotations saw: one dict per span, in opening order."""

    def __init__(self):
        self.events, self.stack = [], []

    def named(self, name):
        return [e for e in self.events if e["name"] == name]


@pytest.fixture
def spans(monkeypatch):
    rec = _Recorder()

    class FakeAnnotation:
        def __init__(self, name, **args):
            self.ev = dict(name=name, args=args, parent=None, closed=False,
                           error=None)

        def __enter__(self):
            self.ev["parent"] = rec.stack[-1]["name"] if rec.stack else None
            rec.stack.append(self.ev)
            rec.events.append(self.ev)
            self.ev["t0"] = time.perf_counter()
            return self

        def __exit__(self, etype, exc, tb):
            self.ev["dur"] = time.perf_counter() - self.ev["t0"]
            assert rec.stack.pop() is self.ev, "spans closed out of order"
            self.ev["closed"] = True
            self.ev["error"] = etype

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return rec


def _assert_well_formed(rec, role="both"):
    parent = dict(PARENT)
    if role == "prefill":
        parent["engine.first_token_sync"] = "engine.prefill"
    assert not rec.stack
    for e in rec.events:
        assert e["closed"], e
        want = parent[e["name"]]
        assert e["parent"] in (want if isinstance(want, tuple) else (want,)), e


@pytest.mark.parametrize("kw,absent", [
    (dict(), {"engine.handoff"}),
    (dict(decode_block=2), {"engine.handoff"}),
    (dict(spec_k=2), {"engine.handoff"}),
    (dict(role="prefill"), {"engine.decode", "engine.decode_dispatch",
                            "engine.decode_sync"}),
], ids=["both", "decode_block", "spec", "prefill_role"])
def test_mixed_load_emits_the_spans_nested_as_documented(spans, kw, absent):
    """Between them the engine's modes emit all ten names of a GPT run;
    each opens inside its documented parent, carries its documented
    arguments, and a request's spans share its ``rid``."""
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8,
                        **kw)
    rng = np.random.RandomState(0)
    lens = [3, 19, 8, 11, 5]
    rids = [eng.add_request(_prompt(rng, n), 4 + i)
            for i, n in enumerate(lens)]
    steps = 0
    while eng.has_work:
        eng.step()
        eng.drain_handoffs()
        steps += 1
        assert steps < 200
    _assert_well_formed(spans, kw.get("role", "both"))
    assert {e["name"] for e in spans.events} == set(PARENT) - absent - RARE
    # a completed prompt's sample goes into its lane of the carry
    assert len(spans.named("engine.carry_dispatch")) == len(lens)
    assert {e["parent"] for e in spans.named("engine.carry_dispatch")} == \
        {"engine.prefill"}
    assert [e["args"] for e in spans.named("engine.step")] == \
        [{"step": i + 1} for i in range(steps)]
    # one admit and one prefill phase per step, in that order
    per_step = [e["name"] for e in spans.events
                if e["parent"] == "engine.step"]
    last = "engine.handoff" if "role" in kw else "engine.decode"
    assert per_step == ["engine.admit", "engine.prefill", last] * steps
    # a request's chunks tile its prompt and end in one first-token sync
    for rid, n in zip(rids, lens):
        chunks = [e["args"] for e in spans.named("engine.prefill_dispatch")
                  if e["args"]["rid"] == rid]
        assert [c["start"] for c in chunks] == \
            list(np.cumsum([0] + [c["n"] for c in chunks[:-1]]))
        assert sum(c["n"] for c in chunks) == n
        assert max(c["n"] for c in chunks) <= 8
        assert len([e for e in spans.named("engine.first_token_sync")
                    if e["args"] == {"rid": rid}]) == 1
    assert len(spans.named("engine.prefill_dispatch")) == \
        eng.stats["prefill_calls"]
    if "role" not in kw:
        disp = spans.named("engine.decode_dispatch")
        assert len(disp) == eng.stats["decode_calls"] == \
            len(spans.named("engine.decode_sync"))
        assert all(1 <= e["args"]["slots"] <= 2 for e in disp)
    else:
        assert eng.stats["handoff_s"] > 0 and eng.stats["decode_s"] == 0


@pytest.mark.parametrize("phase,kw", [
    ("admit", {}), ("prefill", {}), ("decode", {}),
    ("verify", dict(spec_k=2)), ("handoff", dict(role="prefill")),
])
def test_an_injected_fault_closes_every_span(spans, phase, kw):
    """A fault aborts the rest of the step at its phase boundary; the
    phase's span still closes (seeing the exception) and still records its
    time, as the ``finally`` it replaces did.  A ``handoff`` fault degrades
    the transfer instead of raising."""
    plan = FaultPlan(raise_steps={2: phase})
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8,
                        faults=plan, **kw)
    rng = np.random.RandomState(1)
    eng.add_request(_prompt(rng, 5), 4)
    eng.step()
    eng.add_request(_prompt(rng, 6), 4)
    before, stats0 = len(spans.events), dict(eng.stats)
    eng.step()                                   # the faulted step
    faulted = spans.events[before:]
    assert plan.injected["raise"] == 1
    _assert_well_formed(spans, kw.get("role", "both"))
    span = {"verify": "engine.decode"}.get(phase, f"engine.{phase}")
    (ev,) = [e for e in faulted if e["name"] == span]
    if phase == "handoff":
        assert eng.stats["handoff_faults"] == 1 and ev["error"] is None
    else:
        assert eng.stats["step_faults"] == 1
        assert ev["error"].__name__ == "InjectedFault"
        # nothing after the aborted phase ran in that step
        assert [e["name"] for e in faulted
                if e["parent"] == "engine.step"][-1] == span
    ph = span.split(".")[1]
    assert eng.stats[f"{ph}_s"] - stats0[f"{ph}_s"] == \
        pytest.approx(eng._phase_s[ph][1], abs=1e-12) and eng._phase_s[ph][1] > 0
    while eng.has_work:
        eng.step()
        eng.drain_handoffs()
    _assert_well_formed(spans, kw.get("role", "both"))
    assert eng.pool.pages_in_use == 0


def test_phase_times_and_trace_recorder_events_keep_their_source(spans):
    """``stats["<phase>_s"]`` and the TraceRecorder's phase events are
    what they were: ``perf_counter`` wall time of the same four phases,
    one X event per phase and step, inside the step's wall time."""
    tracer = TraceRecorder()
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8,
                        trace=tracer)
    rng = np.random.RandomState(2)
    for n in (4, 17, 9):
        eng.add_request(_prompt(rng, n), 5)
    eng.run()
    steps = len(spans.named("engine.step"))
    phase_evs = [e for e in tracer.events if e["ph"] == "X"]
    assert sorted({e["name"] for e in phase_evs}) == \
        ["admit", "decode", "prefill"]
    assert len(phase_evs) == 3 * steps
    assert {e["args"]["step"] for e in phase_evs} == set(range(1, steps + 1))
    for ph in ("admit", "prefill", "decode"):
        total_us = sum(e["dur"] for e in phase_evs if e["name"] == ph)
        assert eng.stats[f"{ph}_s"] > 0
        assert total_us == pytest.approx(eng.stats[f"{ph}_s"] * 1e6,
                                         rel=1e-3, abs=steps)
    assert sum(eng.stats[f"{ph}_s"] for ph in ("admit", "prefill", "decode")
               ) <= eng.stats["step_wall_s"] + 1e-6
    # the syncs are inside their phases, and the recorder got no new names
    assert 0 < (eng.stats["decode_sync_s"] + eng.stats["prefill_sync_s"]
                ) <= eng.stats["decode_s"]
    assert eng.stats["decode_sync_s"] > 0 and eng.stats["prefill_sync_s"] > 0
    assert not any(e["name"].startswith("engine.") for e in tracer.events)


def test_waits_are_exact_under_an_injected_clock():
    """One slot, two requests of two chunks each, the clock moved by hand:
    A waits 3 s for admission and 2 s more for its second chunk, B 8 s
    (A holds the slot until its one decode is read, the step after the
    dispatch) and 2 s."""
    now = {"t": 0.0}
    eng = ServingEngine(_model(), max_slots=1, page_size=8, chunk_tokens=8,
                        clock=lambda: now["t"])
    rng = np.random.RandomState(3)
    eng.add_request(_prompt(rng, 12), 2)          # A, enqueued at 0
    now["t"] = 1.0
    eng.add_request(_prompt(rng, 12), 2)          # B, enqueued at 1
    seen = []
    for t in (3.0, 5.0, 7.0, 9.0, 11.0):
        now["t"] = t
        eng.step()
        s = eng.stats
        seen.append((s["admissions"], s["queue_wait_s"], s["first_tokens"],
                     s["prefill_wait_s"]))
    assert seen == [(1, 3.0, 0, 0.0), (1, 3.0, 1, 2.0), (1, 3.0, 1, 2.0),
                    (2, 11.0, 1, 2.0), (2, 11.0, 2, 4.0)]
    assert eng.has_work            # B's decode is in flight
    now["t"] = 13.0
    (fin,) = eng.step()
    assert len(fin.tokens) == 2 and not eng.has_work


def test_a_preempted_and_readmitted_request_is_counted_once():
    """The pool of ``test_engine_preempt_recompute_exact``: B is preempted
    by A's growth and prefilled again, and is still one admission and one
    first token; a request cancelled while it waited is neither."""
    eng = ServingEngine(_model(), max_slots=2, page_size=8, num_pages=7,
                        chunk_tokens=16)
    rng = np.random.RandomState(51)
    eng.add_request(_prompt(rng, 8), 24)
    eng.add_request(_prompt(rng, 16), 16)
    eng.step()
    never = eng.add_request(_prompt(rng, 8), 4)   # no slot is free
    eng.cancel(never)
    out = eng.run()
    assert eng.stats["preemptions"] >= 1 and eng.stats["recompute_tokens"] > 0
    assert eng.stats["admissions"] == eng.stats["first_tokens"] == 2
    assert sum(f.ok for f in out.values()) == 2
    assert eng.stats["prefill_calls"] > 2          # B's prompt ran twice


def test_step_mix_and_attended_tokens_of_a_hand_counted_run():
    """Prompts of 5 and 9 tokens fit one step's chunk budget (16), so the
    first decode runs behind two chunks and the others behind none.  A
    (4 new) decodes at context lengths 5, 6, 7; B (3 new) at 9, 10: the
    first token of each comes from its prefill."""
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=16)
    rng = np.random.RandomState(4)
    eng.add_request(_prompt(rng, 5), 4)
    eng.add_request(_prompt(rng, 9), 3)
    eng.run()
    s = eng.stats
    assert s["decode_calls"] == 3 and s["prefill_calls"] == 2
    assert (s["decode_calls_after_0_chunks"], s["decode_calls_after_1_chunk"],
            s["decode_calls_after_2plus_chunks"]) == (2, 0, 1)
    assert s["decode_attended_tokens"] == (5 + 6 + 7) + (9 + 10)
    assert s["tokens_generated"] == 7

    # a longer, mixed run: the mix still sums to the calls
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8,
                        spec_k=2)
    for i, n in enumerate((3, 19, 8, 11, 5)):
        eng.add_request(_prompt(rng, n), 4 + i)
    eng.run()
    s = eng.stats
    mix = [s["decode_calls_after_0_chunks"], s["decode_calls_after_1_chunk"],
           s["decode_calls_after_2plus_chunks"]]
    assert sum(mix) == s["decode_calls"] > 0 and mix[0] > 0 and mix[1] > 0
    assert s["decode_attended_tokens"] > s["decode_calls"]


def _reads_and_dispatches(rec):
    """Per step, in opening order, the names of the dispatch and read spans
    (``engine.*_dispatch``, ``engine.*_sync``)."""
    steps = []
    for e in rec.events:
        if e["name"] == "engine.step":
            steps.append([])
        elif e["name"].endswith(("_dispatch", "_sync")):
            steps[-1].append(e["name"].split(".")[1])
    return steps


def test_a_step_dispatches_before_it_reads_and_reads_the_previous_decode(
        spans):
    """No pool pressure, several requests: within a step every dispatch
    opens before the first read; decode k+1 is dispatched before decode k
    is read, and before any first token of its own step is; every decode
    but the busy period's first was dispatched ahead of a read."""
    eng = ServingEngine(_model(), max_slots=4, page_size=8, chunk_tokens=8)
    rng = np.random.RandomState(6)
    for i, n in enumerate((3, 19, 8, 11)):
        eng.add_request(_prompt(rng, n), 5 + i)
    out = eng.run()
    assert sorted(len(f.tokens) for f in out.values()) == [5, 6, 7, 8]
    steps = _reads_and_dispatches(spans)
    for names in steps:
        reads = [i for i, n in enumerate(names) if n.endswith("_sync")]
        if reads:
            assert all(n.endswith("_sync") for n in names[reads[0]:]), names
        assert names.count("decode_dispatch") <= 1
        assert names.count("decode_sync") <= 1
        if "first_token_sync" in names:        # it rides this step's decode
            assert names.index("decode_dispatch") < \
                names.index("first_token_sync")
            if "decode_sync" in names:
                assert names.index("decode_sync") < \
                    names.index("first_token_sync")
    # D1 D2 S1 D3 S2 ... Dn S(n-1) Sn: sync j follows dispatch j + 1
    flat = [n for names in steps for n in names if n.startswith("decode_")]
    n = eng.stats["decode_calls"]
    assert flat == ["decode_dispatch"] + \
        ["decode_dispatch", "decode_sync"] * (n - 1) + ["decode_sync"]
    assert eng.stats["decode_ahead"] == n - 1 > 0
    assert eng.stats["decode_sync_first"] == 0
    assert eng._inflight is None and not eng._first_unread


def test_a_preemption_of_a_lane_in_flight_retires_first_and_loses_no_token(
        spans):
    """The pool of ``test_engine_preempt_recompute_exact``: A's growth
    preempts B while B's last decode is unread.  That decode is read first
    (a dispatch that follows counts as ``decode_sync_first``), so B's
    recompute prompt holds every token sampled for it: both requests emit
    what they emit with room."""
    model, rng = _model(), np.random.RandomState(51)
    prompts = [_prompt(rng, 8), _prompt(rng, 16)]
    roomy = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=16)
    want = roomy.run(list(zip(prompts, (24, 16))))
    assert roomy.stats["preemptions"] == roomy.stats["decode_sync_first"] == 0
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=7,
                        chunk_tokens=16)
    got = eng.run(list(zip(prompts, (24, 16))))
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["decode_sync_first"] >= 1
    for a, b in zip(sorted(want), sorted(got)):
        np.testing.assert_array_equal(want[a].tokens, got[b].tokens)
    # in the step that preempted, the read came before the decode's dispatch
    assert any("decode_dispatch" in names and names.index("decode_sync")
               < names.index("decode_dispatch")
               for names in _reads_and_dispatches(spans)
               if "decode_sync" in names)
    s = eng.stats
    assert s["decode_ahead"] + s["decode_sync_first"] < s["decode_calls"]


def test_a_snapshot_without_the_new_counters_restores():
    """``snapshot.py`` restores ``stats`` with ``update``: a snapshot from
    before PR 24 lacks the nine keys, loads, and counts from zero."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=8)
    rng = np.random.RandomState(5)
    rids = [eng.add_request(_prompt(rng, n), 6) for n in (4, 13, 7)]
    eng.step()
    snap = eng.snapshot()
    assert set(NEW_COUNTERS) <= set(snap["engine"]["stats"])
    assert snap["engine"]["stats"]["admissions"] == 2
    for key in NEW_COUNTERS:
        del snap["engine"]["stats"][key]
    old = ServingEngine.restore(model, snap)
    assert all(old.stats[k] == 0 for k in NEW_COUNTERS)
    assert old.stats["prefill_calls"] == eng.stats["prefill_calls"] > 0
    out, ref = old.run(), eng.run()
    for rid in rids:
        np.testing.assert_array_equal(out[rid].tokens, ref[rid].tokens)
    assert old.stats["admissions"] == 1            # the third request only
    assert not any(k.startswith("last_") for k in NEW_COUNTERS)


# -- the host's turn (PR 39) -------------------------------------------------


def test_steps_and_dispatch_seconds_of_a_mixed_run(spans):
    """``steps`` counts ``step()`` calls; ``dispatch_s`` holds every
    dispatch span the step opened (the fake annotation's own time lies
    inside the span's) and lies inside the steps' wall time."""
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8)
    rng = np.random.RandomState(7)
    for i, n in enumerate((3, 19, 8, 11, 5)):
        eng.add_request(_prompt(rng, n), 4 + i)
    calls = 0
    while eng.has_work:
        eng.step()
        calls += 1
    s = eng.stats
    assert s["steps"] == calls == len(spans.named("engine.step"))
    disp = [e for e in spans.events if e["name"] in DISPATCHES]
    assert {e["name"] for e in disp} == DISPATCHES - RARE
    assert sum(e["dur"] for e in disp) <= s["dispatch_s"] <= s["step_wall_s"]
    # nothing outside a step: the snapshot's reads leave the count alone
    before = dict(s)
    eng.snapshot()
    assert eng.stats["steps"] == before["steps"]
    assert eng.stats["dispatch_s"] == before["dispatch_s"]


def _warm_then_slow(decode_extra, metrics=False):
    """One request through the engine (every program compiles), then the
    same again with one decode dispatch made slow by ``decode_extra``:
    (the engine, stats deltas of the second request, its flight records)."""
    eng = ServingEngine(_model(), max_slots=2, page_size=8, chunk_tokens=8,
                        metrics=metrics)
    rng = np.random.RandomState(8)
    prompt = _prompt(rng, 6)
    eng.add_request(prompt, 6)
    eng.run()
    s0 = dict(eng.stats)
    eng.attach_flight()
    run, left = eng._decode_fn, [1]

    def decode(*a):
        if left[0]:
            left[0] -= 1
            decode_extra()
        return run(*a)

    eng._decode_fn = decode
    eng.add_request(prompt, 6)
    eng.run()
    delta = {k: eng.stats[k] - s0[k] for k in ("steps", "slow_steps",
                                                "slow_step_s", "dispatch_s")}
    recs = [r for r in eng.flight.to_json()["records"]
            if r["kind"] == "slow_step"]
    return eng, delta, recs


def test_a_slow_decode_dispatch_is_counted_and_recorded():
    """A decode dispatch that sleeps 0.3 s makes its step slow: counted
    once with its seconds, fed to the registry, and described by one
    ``slow_step`` record whose dispatch time holds the sleep, spent off
    the CPU."""
    eng, d, (rec,) = _warm_then_slow(lambda: time.sleep(0.3), metrics=True)
    assert SLOW_STEP_S < 0.3
    assert d["slow_steps"] == 1 and 0.3 <= d["slow_step_s"] < 0.3 + 5
    assert d["dispatch_s"] >= 0.3
    assert rec["step_s"] == pytest.approx(d["slow_step_s"])
    assert 0.3 <= rec["dispatch_s"] <= rec["step_s"]
    assert 0.3 <= rec["decode_s"] <= rec["step_s"]
    # the step's chunk, its carry put and the decode: three calls
    assert len(rec["dispatch_calls_s"]) == 3
    assert max(rec["dispatch_calls_s"]) >= 0.3
    assert rec["decode_s"] + rec["prefill_s"] + rec["admit_s"] <= \
        rec["step_s"]
    # the sleep is off the CPU (the chip's host reports no context
    # switches or faults at all, so only their presence is held here)
    assert 0 <= rec["cpu_s"] < rec["step_s"] - 0.2
    assert min(rec[k] for k in ("voluntary_switches", "involuntary_switches",
                                "major_faults")) >= 0
    # the slow step dispatched the request's first decode: nothing in flight
    assert rec["drained"] is None
    sc = eng.metrics.scalars()
    assert sc["serving_slow_steps"] == eng.stats["slow_steps"] >= 1
    assert sc["serving_slow_step_s"] == pytest.approx(eng.stats["slow_step_s"])


def test_a_full_collection_inside_a_slow_step_shows_in_its_record():
    """``gc.collect()`` (generation 2) inside the decode dispatch of a
    slow step: the record's GC seconds hold it, and lie inside the
    dispatch's time."""
    def collect_and_wait():
        gc.collect()
        time.sleep(0.3)

    _, d, (rec,) = _warm_then_slow(collect_and_wait)
    assert d["slow_steps"] == 1
    assert 0 < rec["gc_s"] <= rec["dispatch_s"] - 0.3


class _Tokens:
    """A decode's device tokens whose ``is_ready`` says what the test
    wants; the engine reads them through ``block_until_ready``."""

    def __init__(self, arr, ready):
        self.arr, self.ready = arr, ready

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.arr.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.arr, dtype)


def _kind_model(kind):
    if kind == "gpt":
        return _model(), 512
    if kind == "cohere2":
        return Cohere2MoeForCausalLM(c2._cfg(), seed=1), 256
    return FalconH1ForCausalLM(fh._cfg(), seed=3), 512


@pytest.mark.parametrize("kind", ["gpt", "cohere2", "falcon_h1"])
def test_device_drained_counts_steps_whose_decode_in_flight_was_done(
        spans, kind):
    """The same load twice, every decode's tokens reporting done, then
    never done.  Counted by hand from the spans: a step whose first
    dispatch opened while a decode was dispatched and not yet read.  All
    of those count when the tokens are done, none when they are not; the
    tokens are the same either way."""
    model, vocab = _kind_model(kind)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (9, 30, 5, 17)]
    outs, counted = [], []
    for ready in (True, False):
        start = len(spans.events)
        eng = ServingEngine(model, max_slots=2, page_size=8,
                            chunk_tokens=16, max_seq_len=96)
        run = eng._decode_fn

        def decode(*a, run=run, ready=ready):
            bufs, toks, *rest = run(*a)
            return (bufs, _Tokens(toks, ready), *rest)

        eng._decode_fn = decode
        outs.append(eng.run([(p, n) for p, n in zip(prompts, (7, 5, 9, 4))]))
        unread = hand = 0
        first = True
        for e in spans.events[start:]:
            if e["name"] == "engine.step":
                first = True
            elif e["name"] in DISPATCHES:
                hand += first and unread > 0
                first = False
                unread += e["name"] == "engine.decode_dispatch"
            elif e["name"] == "engine.decode_sync":
                unread -= 1
        counted.append((eng.stats["device_drained"], hand))
    assert counted[0][0] == counted[0][1] > 0
    assert counted[1][0] == 0 and counted[1][1] == counted[0][1]
    for a, b in zip(sorted(outs[0]), sorted(outs[1])):
        np.testing.assert_array_equal(outs[0][a].tokens, outs[1][b].tokens)
