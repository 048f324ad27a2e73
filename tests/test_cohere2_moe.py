"""Command A+ (``cohere2_moe``) against its plain reference, at a small
size on seeded weights: 4 layers so that both kinds occur (sliding,
sliding, sliding, full), window 16 under the prompts, 8 experts top-2 of
which 4 are held, 2 shared experts.

* eager forward, then chunked prefill + paged decode through
  ``ServingEngine`` (jnp and interpreted-kernel attention), against
  ``tests/reference_cohere2_moe.py`` (float32, ``"highest"``);
* the tie of the share to the model: the parts of two and of four shares,
  shared experts counted once, add up to the uncut reference's layer;
* the window group: ring cap per slot during prefill and decode, the full
  group never recycled, both groups empty after every request ends, and
  preemption under a small full group exact against an unpressured run;
* what a model with two page groups refuses, by a named error.
"""

import filecmp
import os

import numpy as np
import pytest

import jax.numpy as jnp

import reference_cohere2_moe as ref
from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeForCausalLM
from paddle_tpu.models.moe import MoESpec, experts, moe_ffn
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import MultiGroupUnsupported

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES = dict(n_head=8, n_kv_head=2, head_dim=16, eps=1e-5, theta=50000.0,
             top_k=2)
#: float32 on both sides: rounding only.  Logits are ~0.5 in size.
TOL = 2e-5


def _cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=8,
                num_kv_heads=2, head_dim=16, intermediate_size=32,
                num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
                experts_held=(2, 4), sliding_window=16, max_seq_len=160)
    return Cohere2MoeConfig(**dict(base, **kw))


@pytest.fixture(scope="module")
def model():
    return Cohere2MoeForCausalLM(_cfg(), seed=1)


def _ref_logits(model, ids):
    cfg = model.cfg
    return np.asarray(ref.logits(
        model.decoder_params(), ids,
        windows=[cfg.window_of(li) for li in range(cfg.num_layers)],
        experts_held=cfg.experts_held, **SIZES))


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def test_the_two_reference_files_are_byte_equal():
    assert filecmp.cmp(
        os.path.join(HERE, "reference_cohere2_moe.py"),
        os.path.join(os.path.dirname(HERE), "benchmarks", "reference",
                     "cohere2_moe_ref.py"), shallow=False)


def test_the_reference_in_blocks_of_rows_is_the_reference(model, monkeypatch):
    """Attention a block of query rows at a time (how a prompt of several
    thousand tokens fits on the chip) changes nothing."""
    ids = _prompts(7, (70,))[0]
    whole = _ref_logits(model, ids)
    monkeypatch.setattr(ref, "ROWS", 16)
    np.testing.assert_allclose(_ref_logits(model, ids), whole, atol=2e-6,
                               rtol=0)


def test_the_reference_shows_each_layers_router_its_input(model):
    """``tap`` is shown what every layer's router reads, and ``route``'s
    weights are the ``top_k`` largest of ``router_logits`` there,
    normalised over all of them (held or not): what a study of router
    ties needs, and nothing else changes."""
    cfg, ids = model.cfg, _prompts(5, (24,))[0]
    seen = []
    hid = ref.hidden(
        model.decoder_params(), ids, experts_held=cfg.experts_held,
        windows=[cfg.window_of(li) for li in range(cfg.num_layers)],
        tap=lambda n, p: seen.append((n, p)), **SIZES)
    assert len(seen) == cfg.num_layers
    np.testing.assert_array_equal(np.asarray(hid), np.asarray(ref.hidden(
        model.decoder_params(), ids, experts_held=cfg.experts_held,
        windows=[cfg.window_of(li) for li in range(cfg.num_layers)],
        **SIZES)))
    for n, p in seen:
        assert n.shape == (24, cfg.hidden_size)
        z = np.asarray(ref.router_logits(n, p["router_w"]))
        w = np.asarray(ref.route(n, p["router_w"], top_k=2))
        top = np.argsort(-z, axis=-1)[:, :2]
        assert ((w > 0).sum(-1) == 2).all()
        assert (np.take_along_axis(w, top, -1) > 0).all()
        np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)


def test_layer_kinds_follow_the_period(model):
    specs = model.layer_specs()
    assert [s.window for s in specs] == [16, 16, 16, None]
    assert [s.position for s in specs] == ["rope", "rope", "rope", "none"]
    assert all(s.parallel and not s.norm_bias and s.moe.held == (2, 4)
               for s in specs)


def test_eager_forward_matches_the_reference(model):
    ids = np.stack(_prompts(0, (40, 40)))
    got = np.asarray(model.logits(ids))
    for b in range(2):
        want = _ref_logits(model, ids[b])
        assert np.abs(want).max() > 0.3
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


@pytest.mark.parametrize("attention", ["jnp", "kernel"])
def test_prefill_and_decode_through_the_engine_match_the_reference(
        model, attention):
    """Prompts shorter and longer than the window and than a chunk, several
    in flight; every emitted token is the reference's argmax along the
    engine's own tokens, by the reference's logits to ``TOL``."""
    eng = ServingEngine(model, max_slots=4, page_size=8, max_seq_len=160,
                        chunk_tokens=16,
                        use_paged_kernel=attention == "kernel" or None)
    assert eng.ring is not None and eng.ring.ring == 5
    assert eng.stats["prefix_index_refused"] == 1 and eng.pool.prefix is None
    prompts = _prompts(1, (5, 40, 70, 23, 90, 33))
    rids = [eng.add_request(p, 12) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        toks = done[rid].tokens
        assert done[rid].ok and len(toks) == 12
        lg = _ref_logits(model, np.concatenate([p, toks])[:-1])[len(p) - 1:]
        short = lg.max(-1) - lg[np.arange(12), toks]
        assert short.max() <= TOL, (len(p), short)
    st = eng.stats
    assert st["window_pages_recycled"] > 0
    assert st["pages_in_use"] == st["pages_in_use_window"] == 0
    # every valid row makes top_k assignments in each of the 4 layers; a
    # request's last token is sampled and never fed back
    rows = sum(len(p) for p in prompts) + st["tokens_generated"] \
        - len(prompts)
    assert st["moe_assignments"] == rows * 2 * 4
    assert 0 < st["moe_local_assignments"] < st["moe_assignments"]
    assert st["moe_expert_tokens_max"] * 4 >= st["moe_local_assignments"]


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Each share routes over all 8 experts, weighs by the sum over both
    selected, and computes its own experts' rows; with the shared experts
    counted once the shares sum to the reference's uncut expert layer."""
    full = Cohere2MoeForCausalLM(_cfg(num_layers=1, experts_held=(0, 8)),
                                 seed=3)
    p = full.decoder_params()["blocks"][0]
    n = jnp.asarray(np.random.default_rng(2).standard_normal((24, 64)),
                    jnp.float32)
    want = np.asarray(ref._moe(n, p, top_k=2, experts_held=(0, 8)))
    shared = np.asarray(jnp.mean(
        experts(n, p["sh_gate_w"], p["sh_up_w"], p["sh_down_w"]), axis=0))
    per, total, counted = 8 // shares, 0.0, 0
    for s in range(shares):
        cut = dict(p, **{k: p[k][s * per:(s + 1) * per]
                         for k in ("gate_w", "up_w", "down_w")})
        y, counts = moe_ffn(cut, n, MoESpec(8, 2, (s * per, per), n_shared=2))
        total = total + np.asarray(y)
        counted += int(counts.sum())
    np.testing.assert_allclose(total - (shares - 1) * shared, want,
                               atol=TOL, rtol=0)
    assert counted == 24 * 2        # every assignment lands on one share


def test_the_ring_turns_in_prefill_and_decode_and_both_groups_drain(model):
    eng = ServingEngine(model, max_slots=3, page_size=8, max_seq_len=160,
                        chunk_tokens=16)
    ring = eng.ring
    prompts = _prompts(4, (120, 9, 64, 100))
    for p in prompts:
        eng.add_request(p, 24)
    most, seen_prefill_turn = 0, False
    while eng.has_work:
        fins = eng.step()           # conftest audits both groups each step
        live = ring.hi - ring.lo
        most = max(most, int(live.max()))
        assert live.max() <= ring.ring
        for i, s in enumerate(eng._slots):
            if s is None:
                assert not ring.table[i].any()
            else:
                assert s.hw_pages == len(s.pages)      # full: no recycling
                seen_prefill_turn |= (not s.started and ring.lo[i] > 0)
        assert len(fins) == 0 or all(f.ok for f in fins)
    # a slot fills its ring (but for the page a turn frees) and never more
    assert ring.ring - 1 <= most <= ring.ring and seen_prefill_turn
    assert eng.pool.pages_in_use == 0 and ring.pages_in_use == 0
    assert not ring.table.any()


def test_a_backlog_spends_several_chunks_a_step_and_turns_the_ring_each(
        model):
    """Two long prompts wait beside one decoding request: the step's
    budget is two chunks of the 16-row program, the window ring turns once
    per chunk (never by more than the chunk it was sized for), and the
    tokens are those of an engine held to one chunk a step."""
    first, *backlog = _prompts(36, (9, 112, 96))

    def serve(**kw):
        eng = ServingEngine(model, max_slots=3, page_size=8, max_seq_len=160,
                            chunk_tokens=16, **kw)
        turns, advance = [], eng.ring.advance

        def counted(slot, start, end):
            if not eng._slots[slot].started:
                turns.append((slot, start, end))
            advance(slot, start, end)

        eng.ring.advance = counted
        rids = [eng.add_request(first, 20)]
        eng.step()
        rids += [eng.add_request(p, 8) for p in backlog]
        before = eng.stats["prefill_calls"]
        eng.step()
        in_a_step = eng.stats["prefill_calls"] - before
        out = eng.run()
        return eng, [out[r].tokens for r in rids], in_a_step, turns

    one, want, n_one, _ = serve(token_budget=16)
    eng, got, n, turns = serve()
    assert (n_one, n) == (1, 2)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert len(turns) == eng.stats["prefill_calls"] == 1 + 7 + 6
    assert all(0 < end - start <= 16 for _, start, end in turns)
    for slot in {t[0] for t in turns[1:]}:       # each prompt in order
        mine = [t for t in turns[1:] if t[0] == slot]
        assert [t[1] for t in mine[1:]] == [t[2] for t in mine[:-1]]
    assert eng.stats["prefill_budget_chunks"] > eng.stats["decode_calls"]
    assert eng.stats["window_pages_recycled"] > 0
    assert eng.pool.pages_in_use == 0 and eng.ring.pages_in_use == 0


def test_preemption_under_a_small_full_group_is_exact(model):
    prompts = _prompts(5, (8, 16, 30))
    new = (24, 16, 12)

    def serve(num_pages):
        eng = ServingEngine(model, max_slots=3, page_size=8, max_seq_len=160,
                            chunk_tokens=16, num_pages=num_pages)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        out = eng.run()
        return eng, [out[r].tokens for r in rids]

    free, want = serve(None)
    tight, got = serve(9)           # 8 pages of 8: 64 < 32 + 32 + 42
    assert free.stats["preemptions"] == 0
    assert tight.stats["preemptions"] >= 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert tight.pool.pages_in_use == 0 and tight.ring.pages_in_use == 0


@pytest.mark.parametrize("kw", [
    dict(spec_k=2), dict(decode_block=2),
    dict(role="prefill"), dict(kv_bits=8)], ids=lambda kw: next(iter(kw)))
def test_what_two_page_groups_refuse_is_a_named_error(model, kw):
    with pytest.raises(MultiGroupUnsupported):
        ServingEngine(model, max_slots=2, page_size=8, max_seq_len=160, **kw)


def test_two_page_groups_dispatch_ahead_of_the_read(model):
    """What was a refused mode is the step: the ring turns from lengths the
    host advances at dispatch, so decode N+1 goes out before N is read."""
    eng = ServingEngine(model, max_slots=2, page_size=8, max_seq_len=160,
                        chunk_tokens=16)
    for p in _prompts(11, (30, 9)):
        eng.add_request(p, 12)
    eng.run()
    s = eng.stats
    assert s["decode_ahead"] == s["decode_calls"] - 1 > 0
    assert s["decode_sync_first"] == 0 and s["window_pages_recycled"] > 0


def test_snapshot_of_two_page_groups_is_refused(model):
    eng = ServingEngine(model, max_slots=2, page_size=8, max_seq_len=160)
    with pytest.raises(MultiGroupUnsupported):
        eng.snapshot()


def test_a_dispatch_is_handed_copies_of_the_tables(model):
    """The ring's rows are turned in place before the next chunk while the
    last dispatch may still be reading what it was handed (the CPU backend
    aliases a NumPy row it is given): the programs get copies."""
    eng = ServingEngine(model, max_slots=2, page_size=8, max_seq_len=160)
    full, window = eng._device_tables(0)
    both = eng._device_tables()
    eng._table[:] = 7
    eng.ring.table[:] = 7
    for handed in (full, window, *both):
        assert not np.asarray(handed).any()
    eng._table[:] = 0
    eng.ring.table[:] = 0


def test_pages_walked_counter_is_the_kernels_own_range(model):
    """``stats["decode_pages_walked"]`` over a short run with three window
    layers and a full one: per decode dispatch, the size of
    ``paged_attention.live_pages`` (what the kernel's grid is built from)
    summed over every lane the program was handed and every layer under its
    own window; ``decode_pages_in_table`` is the whole tables."""
    from paddle_tpu.kernels import paged_attention as pa

    eng = ServingEngine(model, max_slots=3, page_size=8, max_seq_len=160,
                        chunk_tokens=16)
    handed, run = [], eng._decode_fn
    eng._decode_fn = lambda *a: (handed.append(np.array(a[3])), run(*a))[1]
    for p in _prompts(6, (70, 9, 33, 100)):
        eng.add_request(p, 12)
    eng.run()
    windows = [s.window for s in model.layer_specs()]
    assert windows == [16, 16, 16, None] and len(handed) > 12
    want = 0
    for lengths in handed:
        assert lengths.shape == (3,) and (lengths + 1 >= 1).all()
        for w in windows:
            lo, hi = pa.live_pages(lengths + 1, 8, w, 1, eng.max_pages)
            want += int((hi - lo).sum())
    st = eng.stats
    assert st["decode_pages_walked"] == want
    assert st["decode_pages_in_table"] == len(handed) * 3 * eng.max_pages * 4
    # contexts past the window: the window layers walk fewer pages than the
    # full one, and all of them far fewer than the tables hold
    full_only = sum(int(np.subtract(*pa.live_pages(
        n + 1, 8, None, 1, eng.max_pages)[::-1]).sum()) for n in handed)
    assert full_only < want < 4 * full_only
    assert want < st["decode_pages_in_table"] // 2


def test_prefill_pages_walked_counter_is_the_chunk_kernels_own_range(model):
    """``stats["prefill_pages_walked"]`` with two page groups: per chunk
    dispatch, the size of ``live_pages(start + 1, rows=bucket width)`` for
    every layer under its own window (three ring layers and a full one over
    one table width); ``prefill_pages_in_table`` is the whole row of each."""
    from paddle_tpu.kernels import paged_attention as pa

    eng = ServingEngine(model, max_slots=3, page_size=8, max_seq_len=160,
                        chunk_tokens=16)
    handed, run = [], eng._prefill_fn    # (p, bufs, toks, start, n, ...)
    eng._prefill_fn = lambda *a: (handed.append(
        (int(a[3]), a[2].shape[0])), run(*a))[1]
    for p in _prompts(6, (70, 9, 33, 100)):
        eng.add_request(p, 3)
    eng.run()
    windows = [s.window for s in model.layer_specs()]
    assert windows == [16, 16, 16, None] and len(handed) > 12

    def walked(w):
        return sum(int(np.subtract(*pa.live_pages(
            start + 1, 8, w, width, eng.max_pages)[::-1]))
            for start, width in handed)

    st = eng.stats
    assert st["prefill_calls"] == len(handed)
    assert st["prefill_pages_walked"] == sum(map(walked, windows))
    assert st["prefill_pages_in_table"] == len(handed) * eng.max_pages * 4
    # chunks deep in a prompt: a window layer walks fewer pages than the
    # full one, and all of them fewer than the tables hold
    assert walked(16) < walked(None)
    assert st["prefill_pages_walked"] < st["prefill_pages_in_table"] // 2
