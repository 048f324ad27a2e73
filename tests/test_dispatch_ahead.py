"""The engine's step dispatches decode N+1 before it reads decode N (PR 38):
what a late read may find, for each kind of model the engine serves.

GPT (one page group), Command A+ at a small size (two page groups: the
window ring turns from lengths the host advances at dispatch) and Falcon-H1
at a small size (recurrent state beside the pages: the slab is advanced by
the programs in dispatch order).  The oracle is each request ALONE through a
roomy one-slot engine, itself held to the model's unbatched reference (the
dense decoder for GPT, the plain references' logits for the other two).
Greedy tokens do not depend on the schedule, so every scenario below must
emit the oracle's tokens, cut where the scenario cuts them:

* pool pressure and preemption (a victim with a decode in flight is read
  first), with a request of ``max_new_tokens=1`` in the batch;
* an ``eos`` found late: as a first token (its lane already rode the step's
  decode), mid-decode (its next decode is in flight), and on the step the
  lane would also finish by length;
* cancel and expiry between a decode's dispatch and its retirement;
* a page freed by such an ``eos`` and taken by the next admission while the
  stray row's decode is still unread: device order is dispatch order, the
  new owner's rows land after it; Falcon-H1's slot state ends as it does
  alone.
"""

import numpy as np
import pytest

import test_cohere2_moe as c2
import test_falcon_h1 as fh
import paddle_tpu as paddle
from paddle_tpu.models import Cohere2MoeForCausalLM, FalconH1ForCausalLM
from paddle_tpu.models.generation import build_generate_fn
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import ServingEngine

SIZES = dict(page_size=8, chunk_tokens=16, max_seq_len=160)
#: prompts and budgets of the batch (the third finishes on its first token)
LENS, NEWS = (9, 30, 5, 17), (14, 10, 1, 12)


class Served:
    """One kind of model, its oracle engine and the oracle's outputs."""

    def __init__(self, kind):
        self.kind = kind
        if kind == "gpt":
            paddle.seed(3)
            self.model = GPTForPretraining(GPTConfig(
                vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
                max_seq_len=160, dropout=0.0))
            self.model.eval()
            self.vocab = 512
        elif kind == "cohere2":
            self.model, self.vocab = Cohere2MoeForCausalLM(c2._cfg(),
                                                           seed=1), 256
        else:
            self.model, self.vocab = FalconH1ForCausalLM(fh._cfg(),
                                                         seed=3), 512
        self.alone = ServingEngine(self.model, max_slots=1, **SIZES)
        self._oracle = {}
        rng = np.random.default_rng(19)
        self.prompts = [rng.integers(0, self.vocab, n).astype(np.int32)
                        for n in LENS]

    def engine(self, **kw):
        return ServingEngine(self.model, **dict(SIZES, **kw))

    def oracle(self, prompt, n):
        """``n`` greedy tokens after ``prompt``, alone, checked against the
        unbatched reference the first time they are asked for."""
        key = (prompt.tobytes(), n)
        if key not in self._oracle:
            rid = self.alone.add_request(prompt, n)
            toks = np.asarray(self.alone.run()[rid].tokens)
            self._check(prompt, toks)
            self._oracle[key] = toks
        return self._oracle[key]

    def _check(self, prompt, toks):
        if self.kind == "gpt":
            fn = build_generate_fn(self.model, len(toks), greedy=True)
            np.testing.assert_array_equal(
                np.asarray(fn(prompt[None]))[0, len(prompt):], toks)
            return
        mod = c2 if self.kind == "cohere2" else fh
        lg = mod._ref_logits(self.model, np.concatenate(
            [prompt, toks[:-1]]))[len(prompt) - 1:]
        short = lg.max(-1) - np.take_along_axis(lg, toks[:, None], -1)[:, 0]
        assert short.max() <= 1e-4, short


@pytest.fixture(scope="module", params=["gpt", "cohere2", "falcon_h1"])
def served(request):
    return Served(request.param)


def _drain(eng):
    done = {}
    while eng.has_work:
        for fin in eng.step():
            assert fin.rid not in done, "two terminals for one request"
            done[fin.rid] = fin
    assert eng.pool.pages_in_use == 0 and eng._inflight is None
    return done


def _cut(toks, eos):
    """What a request emits when ``eos`` ends it: (tokens, reason)."""
    hit = np.flatnonzero(toks == eos)
    if len(hit):
        return toks[:hit[0] + 1], "eos"
    return toks, "length"


def test_pool_pressure_and_preemption_emit_the_oracles_tokens(served):
    """Four requests, three slots, a pool that cannot hold their growth: a
    victim is preempted while its decode is unread (read first, counted),
    recomputes, and everyone emits what they emit alone; the request with
    one token to give finishes on its first, read after the decode's
    dispatch."""
    eng = served.engine(max_slots=3, num_pages=8)
    rids = [eng.add_request(p, n) for p, n in zip(served.prompts, NEWS)]
    done = _drain(eng)
    s = eng.stats
    assert s["preemptions"] >= 1 and s["decode_sync_first"] >= 1
    assert s["decode_ahead"] > s["decode_calls"] // 2
    for rid, p, n in zip(rids, served.prompts, NEWS):
        np.testing.assert_array_equal(done[rid].tokens, served.oracle(p, n))
        assert done[rid].finish_reason == "length"


@pytest.mark.parametrize("where", ["first_token", "mid_decode",
                                   "with_length"])
def test_an_eos_found_late_cuts_where_it_would_have(served, where):
    """The ``eos`` id is taken from the oracle's own output, so that it
    arrives where the case wants it in one request, and wherever it falls
    in the others; under the same pool pressure."""
    outs = [served.oracle(p, n) for p, n in zip(served.prompts, NEWS)]
    news = list(NEWS)
    if where == "first_token":
        eos = int(outs[0][0])
    else:
        # a token whose FIRST occurrence in a request's output lies strictly
        # inside its decode tokens
        r, k = next((r, k) for r, o in enumerate(outs)
                    for k in range(1, len(o) - 1)
                    if o[k] not in o[:k])
        eos = int(outs[r][k])
        if where == "with_length":
            news[r] = k + 1          # its budget ends on the same token
    eng = served.engine(max_slots=3, num_pages=8, eos_token_id=eos)
    rids = [eng.add_request(p, n) for p, n in zip(served.prompts, news)]
    done = _drain(eng)
    reasons = []
    for rid, o, n in zip(rids, outs, news):
        want, reason = _cut(o[:n], eos)
        np.testing.assert_array_equal(done[rid].tokens, want)
        assert done[rid].finish_reason == reason
        reasons.append(reason)
    assert "eos" in reasons
    if where == "first_token":
        assert len(done[rids[0]].tokens) == 1
    if where == "with_length":
        assert len(done[rids[r]].tokens) == news[r]
        assert done[rids[r]].finish_reason == "eos"


def test_cancel_and_expiry_between_dispatch_and_retirement(served):
    """A is cancelled and B expires while the decode that holds a row of
    each is unread: its tokens for them are dropped, what they had is a
    prefix of the oracle's, C is untouched, nothing leaks."""
    now = {"t": 0.0}
    eng = served.engine(max_slots=3, clock=lambda: now["t"])
    (pa, pb, _, pc), n = served.prompts, 12
    ra = eng.add_request(pa, n)
    rb = eng.add_request(pb, n, deadline_s=5.0)
    rc = eng.add_request(pc, n)
    done = {}
    while not all(s is not None and len(s.tokens) >= 2 for s in eng._slots):
        for fin in eng.step():
            done[fin.rid] = fin
    assert not done and eng._inflight is not None
    assert {st.request.rid for _, st in eng._inflight[0]} == {ra, rb, rc}
    assert eng.cancel(ra)
    now["t"] = 6.0                               # B is overdue
    done.update(_drain(eng))
    assert done[ra].finish_reason == "cancelled"
    assert done[rb].finish_reason == "expired"
    for rid, p in ((ra, pa), (rb, pb)):
        got = done[rid].tokens
        assert 2 <= len(got) < n
        np.testing.assert_array_equal(got, served.oracle(p, n)[:len(got)])
    assert done[rc].finish_reason == "length"
    np.testing.assert_array_equal(done[rc].tokens, served.oracle(pc, n))


def test_a_page_freed_by_a_late_eos_serves_its_next_owner(served):
    """One slot.  A's ``eos`` is found while its next decode is in flight;
    that decode's row goes into a page A still owned at the dispatch.  The
    page is freed at retirement and B, admitted in the next step, takes it
    (the free list is last in, first out) while the stray decode is still
    unread.  B's chunk is dispatched after it, so B reads its own rows:
    its tokens are the oracle's, and Falcon-H1's slot state ends as it
    does when B runs alone."""
    pa, pb = served.prompts[0], served.prompts[3]
    out_a = served.oracle(pa, 14)
    k = next(k for k in range(2, len(out_a) - 1) if out_a[k] not in out_a[:k])
    eng = served.engine(max_slots=1, eos_token_id=int(out_a[k]))
    ra = eng.add_request(pa, 14)
    rb = eng.add_request(pb, 12)
    fins, pages_a = [], set()
    while not fins:
        pages_a |= set(eng._slots[0].pages if eng._slots[0] else ())
        fins = eng.step()
    (fin_a,) = fins
    assert fin_a.rid == ra and fin_a.finish_reason == "eos"
    np.testing.assert_array_equal(fin_a.tokens, out_a[:k + 1])
    # the stray decode: dispatched for A before the eos was read, unread
    (_, stray), = eng._inflight[0]
    assert stray.request.rid == ra and eng._slots[0] is None
    eng.step()                                   # admits B, reads the stray
    assert eng._slots[0].request.rid == rb
    assert set(eng._slots[0].pages) & pages_a
    done = _drain(eng)
    want_b = served.oracle(pb, 12)
    assert out_a[k] not in want_b                # B runs its length
    np.testing.assert_array_equal(done[rb].tokens, want_b)
    if eng.slab is not None:
        alone = served.engine(max_slots=1)
        alone.run([(pb, 12)])
        for name, buf in eng.slab.buffers.items():
            np.testing.assert_array_equal(
                np.asarray(buf), np.asarray(alone.slab.buffers[name]))
