"""Continuous-batching serving engine + paged KV cache (ISSUE r08 + r09).

Acceptance contracts, all CPU-runnable:
  * the Pallas paged-attention decode kernel AND the paged-prefill chunk
    kernel (interpret mode — the exact TPU code path) match their jnp
    references for bf16-style float and int8 pages;
  * paged decode produces EXACTLY the dense-KV-cache decoder's greedy
    tokens (fp and int8, jnp path and interpret-kernel path, single device
    and tp2, decode_block 1 and >1, chunked and unchunked prefill, prefix
    cache hits and misses, COW tail pages) on mixed-length prompts;
  * the pool allocator, prefix index and FCFS scheduler enforce their
    invariants (null page, O(1) double-free, refcounted sharing, LRU
    eviction of reclaimable pages, FCFS order, chunk budget, page-limited
    admission);
  * EOS frees the slot and its pages mid-flight and the engine admits the
    next waiting request into them; after a full drain the pool returns
    to the cached-prefix-only baseline (asserted in run() itself and by
    the conftest leak fixture after every step).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import paged_prefill as pp
from paddle_tpu.models.generation import build_generate_fn
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import (FCFSScheduler, KVPool, PrefixIndex, Request,
                                ServingEngine)

CFG = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
           max_seq_len=96, dropout=0.0)


def _model(seed=3, **over):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**{**CFG, **over}))
    m.eval()
    return m


def _prompts(rng, lens, vocab=512):
    return [rng.randint(0, vocab, (n,)).astype("int32") for n in lens]


_REF_CACHE = {}


def _dense_greedy(model, prompts, n, int8=False, cache_key=None):
    """Per-request static-batch reference continuations.  ``cache_key``
    memoizes across parametrized re-runs: the model is rebuilt from the
    same seed each time, so the references are deterministic — no need
    to recompile the dense decoder once per param."""
    if cache_key is not None and cache_key in _REF_CACHE:
        return _REF_CACHE[cache_key]
    outs = []
    for p in prompts:
        fn = build_generate_fn(model, n, greedy=True, int8=int8)
        outs.append(np.asarray(fn(p[None]))[0, len(p):])
    if cache_key is not None:
        _REF_CACHE[cache_key] = outs
    return outs


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def test_paged_kernel_matches_ref_float():
    rng = np.random.RandomState(0)
    B, H, D, PS, MAXP, P = 3, 2, 16, 8, 4, 10
    q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    bt = jnp.asarray(rng.randint(1, P, (B, MAXP)).astype("int32"))
    lens = jnp.asarray(np.array([5, 17, 32], "int32"))
    out = pa.paged_attention(q, kp, vp, bt, lens, interpret=True)
    ref = pa.paged_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_matches_ref_int8():
    from paddle_tpu.ops.quant_ops import quantize_per_token

    rng = np.random.RandomState(1)
    B, H, D, PS, MAXP, P = 2, 3, 16, 8, 3, 8
    q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    kq, ks = quantize_per_token(kp)
    vq, vs = quantize_per_token(vp)
    bt = jnp.asarray(rng.randint(1, P, (B, MAXP)).astype("int32"))
    lens = jnp.asarray(np.array([3, 21], "int32"))
    out = pa.paged_attention(q, kq, vq, bt, lens, k_scales=ks, v_scales=vs,
                             interpret=True)
    ref = pa.paged_attention_ref(q, kq, vq, bt, lens, k_scales=ks,
                                 v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # int8 pages approximate the float pages (quantization error band)
    full = pa.paged_attention_ref(q, kp, vp, bt, lens)
    assert np.abs(np.asarray(ref) - np.asarray(full)).max() < 0.15


def test_paged_ref_masks_beyond_length():
    """Positions past `lengths` cannot influence the output: rewriting
    them (e.g. the null page filling with garbage) changes nothing."""
    rng = np.random.RandomState(2)
    P, H, PS, D = 6, 2, 8, 16
    q = jnp.asarray(rng.randn(1, H, D).astype("float32"))
    kp = rng.randn(P, H, PS, D).astype("float32")
    vp = rng.randn(P, H, PS, D).astype("float32")
    bt = jnp.asarray(np.array([[1, 2, 3]], "int32"))
    lens = jnp.asarray(np.array([11], "int32"))
    a = pa.paged_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp), bt, lens)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[2, :, 3:] = 99.0   # page 2 holds positions 8..15; 11.. are masked
    vp2[2, :, 3:] = -99.0
    kp2[3], vp2[3] = 7.0, 7.0   # page 3 fully masked
    b = pa.paged_attention_ref(q, jnp.asarray(kp2), jnp.asarray(vp2), bt,
                               lens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# pool + scheduler
# ---------------------------------------------------------------------------


def test_kv_pool_alloc_free_invariants():
    pool = KVPool(2, 2, 16, num_pages=8, page_size=4)
    assert pool.num_free == 7  # page 0 reserved
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert pool.alloc(1) is None  # exhausted
    assert 0 not in a + b  # null page never handed out
    assert len(set(a + b)) == 7
    pool.free(a)
    assert pool.num_free == 3
    with pytest.raises(ValueError):
        pool.free(a)  # double free
    with pytest.raises(ValueError):
        pool.free([0])  # null page
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    c = pool.alloc(3)
    assert sorted(c) == sorted(a)  # freed pages recycle
    assert pool.buffers["k"].shape == (2, 8, 2, 4, 16)


def test_scheduler_fcfs_pages_gate_admission():
    """Admission is slot- and page-gated FCFS on the PROMPT's pages only
    (r10 on-demand growth: decode pages are allocated later, preempting
    under pressure) — a blocked HEAD stops the scan (no out-of-order
    admission of a smaller request)."""
    pool = KVPool(1, 1, 8, num_pages=9, page_size=4)
    sched = FCFSScheduler(n_slots=4, pool=pool, token_budget=10)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, 9, (n,)), max_new_tokens=4)
            for n in (14, 14, 14)]
    for r in reqs:
        sched.add(r)
    adm = sched.schedule_step()
    # 8 usable pages, 4 PROMPT pages per request (max_new_tokens costs
    # nothing at admission): first two admit, third blocks on pages
    assert [a.request.rid for a in adm] == [reqs[0].rid, reqs[1].rid]
    assert all(len(a.pages) == 4 for a in adm)
    assert sched.schedule_step() == []
    sched.release(adm[0].slot, adm[0].pages)
    adm3 = sched.schedule_step()
    assert [a.request.rid for a in adm3] == [reqs[2].rid]


def test_scheduler_admission_ignores_max_new_tokens():
    """The r10 occupancy win: a request with a tiny prompt and a huge
    new-token budget admits on ONE page — the pre-r10 scheduler would
    have reserved pages_for(total_len) upfront and blocked."""
    pool = KVPool(1, 1, 8, num_pages=9, page_size=4)
    sched = FCFSScheduler(n_slots=2, pool=pool)
    rng = np.random.RandomState(1)
    sched.add(Request(prompt=rng.randint(0, 9, (3,)), max_new_tokens=29))
    adm = sched.schedule_step()
    assert len(adm) == 1 and len(adm[0].pages) == 1  # not pages_for(32)


@pytest.mark.parametrize("call, token_budget, kw, want", [
    # token_budget minus one token per active decode, under one chunk's
    # width while nothing asks for more, floored at 1
    ("prefill_budget", 16, dict(n_decoding=0, chunk_tokens=64), 16),
    ("prefill_budget", 16, dict(n_decoding=4, chunk_tokens=64), 12),
    ("prefill_budget", 16, dict(n_decoding=4, chunk_tokens=8), 8),
    ("prefill_budget", 16, dict(n_decoding=99, chunk_tokens=8), 1),
    # k chunks: one with nothing decoding, one while decodes outnumber the
    # requests waiting on prefill, ceil(p / b) beyond
    ("prefill_budget", 600,
     dict(n_decoding=0, chunk_tokens=128, n_prefilling=40), 128),
    ("prefill_budget", 600,
     dict(n_decoding=7, chunk_tokens=128, n_prefilling=7), 128),
    ("prefill_budget", 600,
     dict(n_decoding=7, chunk_tokens=128, n_prefilling=8), 256),
    ("prefill_budget", 600,
     dict(n_decoding=7, chunk_tokens=128, n_prefilling=15), 384),
    # ... capped by token_budget less the decodes' reservation
    ("prefill_budget", 544,
     dict(n_decoding=7, chunk_tokens=128, n_prefilling=40), 537),
    ("prefill_budget", 544, dict(n_decoding=7, chunk_tokens=128,
                                 decode_cost=5, n_prefilling=40), 509),
    ("prefill_budget", 128,
     dict(n_decoding=1, chunk_tokens=128, n_prefilling=40), 127),
    ("prefill_budget", 8,
     dict(n_decoding=9, chunk_tokens=128, n_prefilling=40), 1),
    # the budget goes in whole dispatches: a full chunk, a prompt's end
    ("chunk_rows", None,
     dict(remaining=900, budget=537, spent=0, chunk_tokens=128), 128),
    ("chunk_rows", None,
     dict(remaining=30, budget=537, spent=256, chunk_tokens=128), 30),
    # a tail under chunk_tokens is spent only to finish a prompt
    ("chunk_rows", None,
     dict(remaining=20, budget=25, spent=512, chunk_tokens=128), 20),
    ("chunk_rows", None,
     dict(remaining=900, budget=25, spent=512, chunk_tokens=128), 0),
    ("chunk_rows", None,
     dict(remaining=26, budget=25, spent=512, chunk_tokens=128), 0),
    # ... but a step's first chunk_tokens are spent to the token, as a
    # budget of one chunk always was
    ("chunk_rows", None,
     dict(remaining=900, budget=98, spent=30, chunk_tokens=128), 98),
    ("chunk_rows", None,
     dict(remaining=900, budget=4, spent=0, chunk_tokens=64), 4),
])
def test_scheduler_chunk_budget(call, token_budget, kw, want):
    """Sarathi budget arithmetic: prefill allowance = k chunks under
    token_budget minus one token per active decode, k from the counts of
    decoding and prefilling requests, floored at 1 so a saturated decode
    batch can't starve prefill; and what one dispatch takes of it."""
    pool = KVPool(1, 1, 8, num_pages=20, page_size=4)
    sched = FCFSScheduler(n_slots=8, pool=pool, token_budget=token_budget)
    assert getattr(sched, call)(**kw) == want


def test_scheduler_rejects_oversized_request():
    pool = KVPool(1, 1, 8, num_pages=4, page_size=4)  # 12 usable tokens
    sched = FCFSScheduler(n_slots=2, pool=pool)
    with pytest.raises(ValueError):
        sched.add(Request(prompt=np.arange(20), max_new_tokens=4))


# ---------------------------------------------------------------------------
# engine parity vs the dense static-batch decoder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["jnp", "kernel", "jnp_block4",
                                  "kernel_block4"])
def test_engine_greedy_matches_dense_decode(mode):
    """Mixed-length prompts through the engine == per-request static-batch
    greedy decode, exactly (the r08 acceptance contract), with the paged
    path forced through the jnp reference or the interpret-mode kernel."""
    model = _model()
    rng = np.random.RandomState(3)
    prompts = _prompts(rng, (5, 11, 23, 7))
    refs = _dense_greedy(model, prompts, 12, cache_key="r08_greedy12")
    eng = ServingEngine(model, max_slots=2, page_size=8,
                        decode_block=4 if "block4" in mode else 1,
                        use_paged_kernel="kernel" in mode)
    rids = [eng.add_request(p, 12) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])
    # continuous batching really reused its two programs: ONE decode trace
    # and one prefill trace per prompt-length bucket
    assert eng.stats["decode_traces"] == 1
    assert eng.stats["prefill_traces"] <= 3  # buckets: 8, 16, 32


@pytest.mark.parametrize("mode", ["jnp", "kernel"])
def test_engine_int8_matches_dense_int8_decode(mode):
    """int8 paged decode (int8 pages + fp32 page scales, W8A8 projections)
    == the dense int8-KV decoder, exactly, on the test configs — with the
    prompts CHUNK-prefilled (chunk_tokens=8) through the int8 paged
    prefill path."""
    model = _model()
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, (6, 13, 9))
    refs = _dense_greedy(model, prompts, 10, int8=True,
                         cache_key="r08_int8_10")
    eng = ServingEngine(model, max_slots=2, page_size=8, int8=True,
                        chunk_tokens=8, use_paged_kernel=mode == "kernel")
    assert eng.pool.buffers["k"].dtype == jnp.int8
    assert eng.pool.buffers["ks"].dtype == jnp.float32
    rids = [eng.add_request(p, 10) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


def test_engine_tp2_matches_single_device():
    """tp2 engine decode (use_parallel weights on an mp=2 mesh, GSPMD
    global arrays) reproduces the single-device dense greedy tokens."""
    from paddle_tpu.distributed import mesh as mesh_mod

    single = _model(seed=0)
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, (5, 9))
    refs = _dense_greedy(single, prompts, 8)

    mesh_mod.build_hybrid_mesh(dp=1, mp=2, pp=1, sharding=1)
    paddle.seed(0)
    tp = GPTForPretraining(GPTConfig(**CFG, use_parallel=True))
    tp.eval()
    for int8 in (False, True):
        # fp leg also exercises tp2 x chunked prefill (chunk < prompt)
        eng = ServingEngine(tp, max_slots=2, page_size=8, int8=int8,
                            chunk_tokens=128 if int8 else 4,
                            use_paged_kernel=False)
        rids = [eng.add_request(p, 8) for p in prompts]
        out = eng.run()
        if int8:
            ref8 = _dense_greedy(single, prompts, 8, int8=True)
            for i, rid in enumerate(rids):
                np.testing.assert_array_equal(out[rid].tokens, ref8[i])
        else:
            for i, rid in enumerate(rids):
                np.testing.assert_array_equal(out[rid].tokens, refs[i])


# ---------------------------------------------------------------------------
# continuous-batching behavior
# ---------------------------------------------------------------------------


def test_engine_admits_into_freed_slot():
    """More requests than slots: the engine must finish them ALL without
    draining — a later request is admitted the step a slot frees."""
    model = _model()
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, (4, 4, 4, 4, 4))
    eng = ServingEngine(model, max_slots=2, page_size=8)
    rids = [eng.add_request(p, n) for p, n in
            zip(prompts, (3, 9, 3, 5, 4))]
    seen_busy = []
    done = {}
    while eng.has_work:
        for fin in eng.step():
            done[fin.rid] = fin
        seen_busy.append(eng.scheduler.n_active)
    assert set(done) == set(rids)
    assert max(seen_busy) == 2  # both slots saturated
    # short requests finished first despite FCFS admission: slot turnover
    assert [len(done[r].tokens) for r in rids] == [3, 9, 3, 5, 4]
    assert eng.pool.utilization() == 0.0  # everything freed
    assert eng.scheduler.n_active == 0


def test_engine_eos_frees_slot_and_pages():
    """EOS mid-flight: the sequence stops, its pages return to the pool,
    and a waiting request takes the slot."""
    model = _model(seed=2)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 512, (6,)).astype("int32")
    # greedy continuation without EOS; pick its 3rd token as the EOS id
    ref = _dense_greedy(model, [prompt], 10)[0]
    eos = int(ref[2])
    first_hit = int(np.argmax(ref == eos))
    eng = ServingEngine(model, max_slots=1, page_size=8, eos_token_id=eos)
    other = rng.randint(0, 512, (5,)).astype("int32")
    r1 = eng.add_request(prompt, 10)
    r2 = eng.add_request(other, 3)
    out = eng.run()
    assert out[r1].finish_reason == "eos"
    assert len(out[r1].tokens) == first_hit + 1
    assert out[r1].tokens[-1] == eos
    np.testing.assert_array_equal(out[r1].tokens, ref[:first_hit + 1])
    assert out[r2].finish_reason in ("length", "eos")
    assert eng.pool.utilization() == 0.0
    assert eng.scheduler.n_active == 0


def test_generate_eos_masks_finished_rows():
    """Static-batch early stop: after a row emits EOS every later position
    is EOS, and pre-EOS tokens are untouched."""
    model = _model(seed=2)
    rng = np.random.RandomState(9)
    ids = rng.randint(0, 512, (2, 6)).astype("int32")
    ref = np.asarray(build_generate_fn(model, 10, greedy=True)(ids))
    cont = ref[:, 6:]
    eos = int(cont[0, 2])
    out = np.asarray(build_generate_fn(model, 10, greedy=True,
                                       eos_token_id=eos)(ids))
    for b in range(2):
        row, ref_row = out[b, 6:], cont[b]
        hits = np.where(ref_row == eos)[0]
        if hits.size:
            j = int(hits[0])
            np.testing.assert_array_equal(row[:j + 1], ref_row[:j + 1])
            assert (row[j + 1:] == eos).all()
        else:
            np.testing.assert_array_equal(row, ref_row)


def test_engine_rejects_oversized_request_on_every_path():
    """Both admission paths (add_request AND run() with raw Requests) hit
    the same max_seq_len gate — an over-long request can never be admitted
    and then crash/corrupt mid-flight."""
    model = _model()
    eng = ServingEngine(model, max_slots=1, page_size=8)
    long_prompt = np.arange(CFG["max_seq_len"] - 2, dtype=np.int32) % 512
    with pytest.raises(ValueError):
        eng.add_request(long_prompt, 8)
    with pytest.raises(ValueError):
        eng.run([Request(prompt=long_prompt, max_new_tokens=8)])


def test_engine_pool_exhaustion_queues_instead_of_failing():
    """A pool too small for two concurrent requests serializes them."""
    model = _model()
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, (8, 8))
    # 5 usable pages of 8 = 40 tokens; each request needs 8+16=24 -> 3 pages
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=6)
    refs = _dense_greedy(model, prompts, 16)
    rids = [eng.add_request(p, 16) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


# ---------------------------------------------------------------------------
# the paged-prefill chunk kernel (r09)
# ---------------------------------------------------------------------------


def test_paged_prefill_kernel_matches_ref_float():
    rng = np.random.RandomState(40)
    C, H, D, PS, MAXP, P = 7, 2, 16, 8, 4, 10
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    bt = jnp.asarray(rng.randint(1, P, (MAXP,)).astype("int32"))
    for start in (0, 5, 13):
        out = pp.paged_prefill(q, kp, vp, bt, start, interpret=True)
        ref = pp.paged_prefill_ref(q, kp, vp, bt, start)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (16, 2)],
                         ids=["heads", "kvgroup"])
def test_paged_prefill_costs_the_context_not_the_table(heads, kv_heads):
    """A chunk's answer does not depend on how wide its table is: the walk
    stops at the chunk's last page, so a 4-entry and a 40-entry table with
    the same live entries give the same bits (the rest may name anything)."""
    rng = np.random.RandomState(41)
    C, D, PS, P = 8, 16, 8, 12
    q = jnp.asarray(rng.randn(C, heads, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, kv_heads, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, kv_heads, PS, D).astype("float32"))
    live = rng.randint(1, P - 1, (4,)).astype("int32")
    kp, vp = kp.at[P - 1].set(1e4), vp.at[P - 1].set(1e4)
    wide = np.concatenate([live, np.full((36,), P - 1, "int32")])
    for start in (0, 13, 24):
        tight = pp.paged_prefill(q, kp, vp, jnp.asarray(live), start,
                                 interpret=True)
        out = pp.paged_prefill(q, kp, vp, jnp.asarray(wide), start,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(tight))
        ref = pp.paged_prefill_ref(q, kp, vp, jnp.asarray(wide), start)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("kv_bits", [None, 8])
def test_paged_prefill_kernel_tiled_by_kv_head_matches_ref(window, kv_bits):
    """GQA with a sublane-aligned group (16 query heads over 2 KV heads):
    the kernel's grid runs over (KV heads, pages) and a block is one KV
    head's group of 8, rows and heads merged into one matmul dimension
    (``paged_prefill.block_heads``).  Same answers as the jnp oracle, with
    and without a sliding window, float and int8 pages."""
    from paddle_tpu.ops.quant_ops import quantize_per_token

    rng = np.random.RandomState(43)
    C, H, HKV, D, PS, MAXP, P = 16, 16, 2, 32, 8, 12, 20
    assert pp.block_heads(H, PS, D, C, HKV) == H // HKV
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, HKV, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, HKV, PS, D).astype("float32"))
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:MAXP].astype("int32"))
    kw = {}
    if kv_bits:
        (kp, kw["k_scales"]), (vp, kw["v_scales"]) = (
            quantize_per_token(kp), quantize_per_token(vp))
    for start in (0, 21, 77):
        out = pp.paged_prefill(q, kp, vp, bt, start, window=window,
                               interpret=True, **kw)
        ref = pp.paged_prefill_ref(q, kp, vp, bt, start, window=window, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_paged_prefill_block_follows_the_shapes():
    # MHA keeps the whole block (Cerebras-GPT 1.3B: 16 heads of 128)
    assert pp.block_heads(16, 64, 128, 128, 16) == 16
    # 128 query heads over 8 KV heads: one KV head's 16 at a time
    assert pp.block_heads(128, 64, 128, 128, 8) == 16
    assert pp.supported(128, 64, 128, 128, n_kv_heads=8)
    # a group that is not sublane-aligned is padded up to one that is, a
    # KV head at a time as well (Falcon-H1: 20 heads over 4, 5 -> 8)
    assert pp.block_heads(20, 64, 128, 128, 4) == 8
    assert pp.block_heads(8, 64, 128, 128, 2) == 8
    assert pp.block_heads(128, 64, 128, 128, 32) == 8
    assert pp.supported(128, 64, 128, 128, n_kv_heads=32)
    # MHA too wide for the budget has no block: the jnp path
    assert pp.block_heads(128, 64, 128, 128, 128) is None
    assert not pp.supported(128, 64, 128, 128, n_kv_heads=128)


def test_paged_prefill_kernel_matches_ref_int8():
    from paddle_tpu.ops.quant_ops import quantize_per_token

    rng = np.random.RandomState(41)
    C, H, D, PS, MAXP, P = 5, 3, 16, 8, 3, 8
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    vp = jnp.asarray(rng.randn(P, H, PS, D).astype("float32"))
    kq, ks = quantize_per_token(kp)
    vq, vs = quantize_per_token(vp)
    bt = jnp.asarray(rng.randint(1, P, (MAXP,)).astype("int32"))
    out = pp.paged_prefill(q, kq, vq, bt, 6, k_scales=ks, v_scales=vs,
                           interpret=True)
    ref = pp.paged_prefill_ref(q, kq, vq, bt, 6, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # int8 pages approximate the float pages (quantization error band)
    full = pp.paged_prefill_ref(q, kp, vp, bt, 6)
    assert np.abs(np.asarray(ref) - np.asarray(full)).max() < 0.2


def test_paged_prefill_ref_causal_mask():
    """Chunk row i sees exactly positions <= start + i: rewriting any
    later position (e.g. stale COW-page tail garbage, unwritten pool
    zeros) cannot change that row's output."""
    rng = np.random.RandomState(42)
    P, H, PS, D, C, start = 5, 2, 8, 16, 4, 9
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kp = rng.randn(P, H, PS, D).astype("float32")
    vp = rng.randn(P, H, PS, D).astype("float32")
    bt = jnp.asarray(np.array([1, 2, 3], "int32"))
    a = pp.paged_prefill_ref(q, jnp.asarray(kp), jnp.asarray(vp), bt, start)
    kp2, vp2 = kp.copy(), vp.copy()
    # positions 11.. live at page idx 1 offset 3.. and page idx 2: row i
    # sees up to start + i = 9 + i, so row 0 (sees <= 9) and row 1
    # (sees <= 10) must be untouched by garbage at 11..
    kp2[2, :, 3:] = 99.0
    vp2[2, :, 3:] = -99.0
    kp2[3], vp2[3] = 7.0, 7.0
    b = pp.paged_prefill_ref(q, jnp.asarray(kp2), jnp.asarray(vp2), bt,
                             start)
    np.testing.assert_array_equal(np.asarray(a)[:2], np.asarray(b)[:2])
    assert np.abs(np.asarray(a)[2:] - np.asarray(b)[2:]).max() > 0


# ---------------------------------------------------------------------------
# refcounts, prefix index, O(1) allocator (r09)
# ---------------------------------------------------------------------------


def test_kv_pool_refcount_sharing_and_reclaim():
    """Shared pages die only at refcount 0; cached pages then park as
    RECLAIMABLE (matchable, out of the free list) until allocation
    pressure LRU-evicts them — never eagerly freed."""
    pool = KVPool(1, 1, 8, num_pages=6, page_size=4, prefix_cache=True)
    pages = pool.alloc(2)                     # rc 1 each
    pool.prefix.insert(np.arange(8, dtype=np.int32), pages)
    pool.retain(pages)                        # a second request shares them
    pool.free(pages)                          # first owner done (rc 1)
    assert pool.num_free == 3 and pool.pages_in_use == 2
    pool.free(pages)                          # rc 0: cached -> reclaimable
    assert pool.num_free == 3
    assert pool.num_reclaimable == 2 and pool.pages_in_use == 0
    with pytest.raises(ValueError):
        pool.free(pages)                      # over-free fails loudly
    with pytest.raises(ValueError):
        pool.free([pool._free[-1]])           # free page double-free
    got = pool.alloc(5)                       # needs the cached pages back
    assert got is not None and len(got) == 5
    assert pool.num_cached == 0 and len(pool.prefix) == 0
    pool.check()
    pool.free(got)
    assert pool.num_free == 5


def test_kv_pool_alloc_free_stress():
    """Satellite: thousands of random alloc/retain/free cycles against the
    set-mirrored free list keep every invariant (null page reserved, no
    aliasing, refcounts balanced) — checked via pool.check()."""
    rng = np.random.RandomState(0)
    pool = KVPool(1, 1, 8, num_pages=64, page_size=4, prefix_cache=True)
    live = []
    for i in range(4000):
        r = rng.rand()
        if live and (r < 0.45 or pool.num_free < 4):
            pool.free(live.pop(rng.randint(len(live))))
        elif live and r < 0.55:
            lease = live[rng.randint(len(live))]
            pool.retain(lease)                # share...
            pool.free(lease)                  # ...and drop again
        else:
            got = pool.alloc(int(rng.randint(1, 5)))
            if got is not None:
                live.append(got)
        if i % 500 == 0:
            pool.check()
    for pages in live:
        pool.free(pages)
    pool.check()
    assert pool.pages_in_use == 0 and pool.num_free == 63


def test_prefix_index_match_insert_lru():
    idx = PrefixIndex(4)
    t = np.arange(16, dtype=np.int32)
    assert idx.match(t) == ([], None)
    assert idx.insert(t, [5, 6, 7, 8]) == [5, 6, 7, 8]
    pages, partial = idx.match(t)
    assert pages == [5, 6, 7, 8] and partial is None
    # page-aligned prefix + partial-tail (COW) match
    q = np.concatenate([t[:6], [99, 99]]).astype(np.int32)
    pages, partial = idx.match(q)
    assert pages == [5] and partial == (6, 2)
    # an already-cached chunk keeps its page; the duplicate isn't adopted
    assert idx.insert(t[:8], [50, 51]) == []
    assert len(idx) == 4

    # LRU eviction: refcount-0 LEAVES first, parents only once childless
    idx2 = PrefixIndex(4)
    idx2.insert(np.arange(8, dtype=np.int32), [1, 2])
    # chunk 0 is already node 1 (the page slot is ignored); chunk 1 is new
    idx2.insert(np.array([0, 1, 2, 3, 9, 9, 9, 9], np.int32), [1, 3])
    idx2.match(np.arange(8, dtype=np.int32))      # branch [1, 2] is recent
    rc = [0] * 10
    assert idx2.evict(1, rc) == [3]               # LRU leaf goes first
    assert idx2.evict(5, rc) == [2, 1]            # leaf, then freed parent
    assert len(idx2) == 0
    # a pinned leaf (refcount > 0) blocks itself AND its parent chain
    idx3 = PrefixIndex(4)
    idx3.insert(np.arange(8, dtype=np.int32), [1, 2])
    assert idx3.evict(2, [0, 0, 1] + [0] * 7) == []


# ---------------------------------------------------------------------------
# chunked prefill + prefix caching through the engine (r09)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["jnp", "kernel"])
def test_engine_chunked_matches_dense_decode(mode):
    """chunk_tokens=4 < page_size=8 (the satellite edge case): prompts
    prefill in sub-page chunks across multiple program calls, greedy
    tokens still EXACTLY match the dense decoder."""
    model = _model()
    rng = np.random.RandomState(13)
    prompts = _prompts(rng, (5, 11, 9))
    refs = _dense_greedy(model, prompts, 8, cache_key="r09_chunked8")
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=4,
                        use_paged_kernel=mode == "kernel")
    rids = [eng.add_request(p, 8) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])
    assert eng.stats["prefill_calls"] > len(prompts)  # chunking happened
    assert eng.pool.pages_in_use == 0


def test_engine_prefix_cache_hits_and_exact():
    """Shared-system-prompt load: every request starts with the same
    16-token prefix (2 full pages).  Greedy tokens match the dense
    decoder EXACTLY while later admissions serve the shared pages from
    cache, and the drained engine parks only reclaimable cached pages."""
    model = _model()
    rng = np.random.RandomState(21)
    shared = rng.randint(0, 512, (16,)).astype("int32")
    prompts = [np.concatenate([shared,
                               rng.randint(0, 512, (n,)).astype("int32")])
               for n in (5, 3, 7, 4)]
    refs = _dense_greedy(model, prompts, 6)
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=16)
    rids = [eng.add_request(p, 6) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])
    # the first slot-pair admits cold; the second wave hits both pages
    assert eng.stats["prefix_hit_tokens"] >= 2 * 16
    assert 0.0 < eng.prefix_hit_rate() < 1.0
    assert eng.pool.pages_in_use == 0
    assert eng.pool.num_cached > 0
    first = eng.stats["prefix_hit_tokens"]
    # re-serving over the drained engine hits the cache immediately
    rids2 = [eng.add_request(p, 6) for p in prompts[:2]]
    out2 = eng.run()
    for i, rid in enumerate(rids2):
        np.testing.assert_array_equal(out2[rid].tokens, refs[i])
    assert eng.stats["prefix_hit_tokens"] >= first + 2 * 16


def test_engine_cow_tail_page():
    """Copy-on-write partial-tail reuse: B shares A's first page plus
    HALF of its second page — the engine clones the cached page and
    prefills only the divergent suffix; an identical re-request (C) gets
    everything but its final token from cache (the cap that keeps the
    first output token computable).  Tokens stay exact throughout."""
    model = _model(seed=4)
    rng = np.random.RandomState(4)
    A = rng.randint(0, 512, (16,)).astype("int32")
    B = np.concatenate([A[:12], rng.randint(0, 512, (6,)).astype("int32")])
    refA = _dense_greedy(model, [A], 6)[0]
    refB = _dense_greedy(model, [B], 6)[0]
    eng = ServingEngine(model, max_slots=1, page_size=8, chunk_tokens=16)
    ra = eng.add_request(A, 6)
    np.testing.assert_array_equal(eng.run()[ra].tokens, refA)
    assert eng.stats["prefix_hit_tokens"] == 0
    rb = eng.add_request(B, 6)
    np.testing.assert_array_equal(eng.run()[rb].tokens, refB)
    # B matched page 0 whole (8) + 4 tokens of A's second page via COW
    assert eng.stats["prefix_hit_tokens"] == 12
    rc = eng.add_request(A.copy(), 6)
    np.testing.assert_array_equal(eng.run()[rc].tokens, refA)
    # C matched page 0 whole (8) + 7 of 8 tokens of page 1 (capped at
    # prompt_len - 1, served via COW)
    assert eng.stats["prefix_hit_tokens"] == 12 + 15
    assert eng.pool.pages_in_use == 0


def test_engine_mid_prefill_admission_and_budget():
    """Sarathi co-scheduling: a 16-token prompt at token_budget=4 spreads
    its prefill over >= 4 steps WITHOUT blocking admission — the second
    request occupies the other slot from step one — and both still finish
    with exact tokens."""
    model = _model()
    rng = np.random.RandomState(31)
    long_p = rng.randint(0, 512, (16,)).astype("int32")
    short_p = rng.randint(0, 512, (4,)).astype("int32")
    refs = _dense_greedy(model, [long_p, short_p], 4)
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=4,
                        token_budget=4, prefix_cache=False)
    r1 = eng.add_request(long_p, 4)
    r2 = eng.add_request(short_p, 4)
    fins, steps = {}, 0
    while eng.has_work:
        for f in eng.step():
            fins[f.rid] = f
        steps += 1
        if steps == 1:
            assert eng.scheduler.n_active == 2  # head mid-prefill, both in
    np.testing.assert_array_equal(fins[r1].tokens, refs[0])
    np.testing.assert_array_equal(fins[r2].tokens, refs[1])
    assert steps >= 5          # 16 prompt tokens at <= 4 per step + decode


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
def test_engine_backlog_spends_several_chunks_a_step(int8):
    """Three long prompts wait on prefill beside ONE decoding request: the
    step's budget is ceil(3 / 1) chunks of the same 8-row program, spent
    whole before the decode, the tokens are those of an engine held to one
    chunk a step (``token_budget=chunk_tokens``), and the counter reads
    the allowance."""
    model = _model()
    rng = np.random.RandomState(36)
    first, *backlog = _prompts(rng, (5, 40, 32, 24))

    def serve(**kw):
        eng = ServingEngine(model, max_slots=4, page_size=8, chunk_tokens=8,
                            prefix_cache=False, int8=int8, **kw)
        rids = [eng.add_request(first, 24)]
        eng.step()                              # first decodes from here on
        rids += [eng.add_request(p, 6) for p in backlog]
        fins, per_step = {}, []
        while eng.has_work:
            before = eng.stats["prefill_calls"]
            for f in eng.step():
                fins[f.rid] = f
            per_step.append(eng.stats["prefill_calls"] - before)
        return eng, [fins[r].tokens for r in rids], per_step

    one, want, one_steps = serve(token_budget=8)
    eng, got, steps = serve()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # b = 1, p = 3: three chunks a step until the first long prompt is
    # through; then b = 2, p = 2 and b = 3, p = 1: one.  The backlog is
    # prefilled in fewer steps (the run's length is the first request's 24
    # decodes either way)
    assert steps[:9] == [3, 3, 1, 1, 1, 1, 1, 1, 0]
    assert np.count_nonzero(one_steps) > 8
    assert eng.stats["prefill_traces"] == one.stats["prefill_traces"]
    s = eng.stats
    assert one.stats["prefill_budget_chunks"] == one.stats["decode_calls"]
    assert s["prefill_budget_chunks"] == s["decode_calls"] + 2 + 2
    assert s["decode_calls_after_2plus_chunks"] == 2
    # 40 + 32 + 24 tokens in whole chunks: no dispatch was cut short by
    # the budget, where 7 tokens a step (8 less the decode) cut every one
    assert s["prefill_calls"] - 1 == 5 + 4 + 3
    assert one.stats["prefill_calls"] - 1 > 5 + 4 + 3


def test_engine_rejects_prompt_larger_than_pool():
    """A prompt the page pool can never hold is rejected CLEANLY at
    enqueue — not admitted to deadlock the loop — and pool-sized requests
    after it still run."""
    model = _model()
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=4)
    with pytest.raises(ValueError):
        eng.add_request(np.arange(30, dtype=np.int32) % 512, 8)  # 38 > 24
    rng = np.random.RandomState(7)
    p = rng.randint(0, 512, (6,)).astype("int32")
    ref = _dense_greedy(model, [p], 4)[0]
    rid = eng.add_request(p, 4)
    np.testing.assert_array_equal(eng.run()[rid].tokens, ref)


def test_engine_stats_and_teardown_leak_assert():
    """engine.stats carries the r09 observability fields, and run()'s
    teardown assert actually fires when a page reference leaks."""
    model = _model()
    rng = np.random.RandomState(17)
    eng = ServingEngine(model, max_slots=2, page_size=8)
    rid = eng.add_request(rng.randint(0, 512, (9,)).astype("int32"), 4)
    out = eng.run()
    assert len(out[rid].tokens) == 4
    s = eng.stats
    assert s["pages_in_use"] == 0 and s["queue_depth"] == 0
    assert s["prompt_tokens"] == 9
    assert s["step_wall_s"] > 0 and s["last_step_s"] > 0
    eng.check_invariants()
    stray = eng.pool.alloc(1)  # simulate a leaked page reference
    with pytest.raises(AssertionError):
        eng.run()
    eng.pool.free(stray)
    eng.run()                  # clean again


def test_engine_cow_pin_cannot_deadlock_admission():
    """Regression (r09 review): a request sized to the WHOLE remaining
    pool whose prompt has a partial-tail (COW) match used to pin the COW
    source page and push peak demand over the admission arithmetic —
    alloc failed identically every step, spinning run() forever.  Under
    r10's prompt-only admission the same request admits WITH its COW
    match (decode pages grow on demand, LRU-evicting the reclaimable
    cached pages when the pool tightens), and the scheduler still keeps
    the drop-the-COW-pin fallback for the exactly-full case."""
    model = _model(seed=4)
    rng = np.random.RandomState(4)
    A = rng.randint(0, 512, (16,)).astype("int32")
    refA = _dense_greedy(model, [A], 8)[0]
    # 3 usable pages of 8 = 24 tokens; A caches its 2 full prompt pages
    eng = ServingEngine(model, max_slots=1, page_size=8, num_pages=4,
                        chunk_tokens=16)
    ra = eng.add_request(A, 8)
    np.testing.assert_array_equal(eng.run()[ra].tokens, refA)
    # identical re-request needs the whole pool (16 + 8 = 24 tokens) and
    # matches page 0 fully + 7 tokens of page 1 via COW (capped at
    # prompt_len - 1); decode growth evicts the reclaimable source later
    rb = eng.add_request(A.copy(), 8)
    np.testing.assert_array_equal(eng.run()[rb].tokens, refA)
    assert eng.stats["prefix_hit_tokens"] == 8 + 7
    assert eng.stats["preemptions"] == 0   # single resident: never preempts
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# fault tolerance: preemption, lifecycle, snapshot/restore (r10)
# ---------------------------------------------------------------------------


def _fake_clock():
    state = {"t": 0.0}

    def now():
        return state["t"]

    return state, now


@pytest.mark.parametrize("mode", ["fp_jnp", "int8_kernel"])
def test_engine_preempt_recompute_exact(mode):
    """The r10 acceptance contract: a pool too small for both residents'
    decode growth forces >= 1 preemption (youngest evicted, requeued,
    recompute-restarted through chunked prefill with its generated tokens
    carried), and every request still produces EXACTLY the dense greedy
    tokens.  The victim's full prompt pages park reclaimable in the
    prefix index, so re-admission serves them from cache (cheap
    recompute).  (jnp x kernel preempt-parity needs no full matrix here —
    the kernel/jnp contract is pinned by the r08/r09 parity tests and the
    chaos suite runs both paths; int8 x jnp rides through the tp2 test
    below.)"""
    int8 = "int8" in mode
    model = _model()
    rng = np.random.RandomState(51)
    A = rng.randint(0, 512, (8,)).astype("int32")    # oldest: 8 + 24 new
    B = rng.randint(0, 512, (16,)).astype("int32")   # victim: 16 + 16 new
    refs = _dense_greedy(model, [A], 24, int8=int8)
    refB = _dense_greedy(model, [B], 16, int8=int8)[0]
    # 6 usable pages of 8 = 48 tokens < A's 32 + B's 32 worst case: B (the
    # younger) must be preempted when A's decode growth exhausts the pool
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=7,
                        chunk_tokens=16, int8=int8,
                        use_paged_kernel="kernel" in mode)
    ra = eng.add_request(A, 24)
    rb = eng.add_request(B, 16)
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["recompute_tokens"] > 0
    # B's 2 full prompt pages were re-adopted from the prefix cache
    assert eng.stats["prefix_hit_tokens"] >= 16
    np.testing.assert_array_equal(out[ra].tokens, refs[0])
    np.testing.assert_array_equal(out[rb].tokens, refB)
    assert out[ra].reason == "length" and out[rb].reason == "length"
    assert eng.pool.pages_in_use == 0


def test_engine_preempt_recompute_exact_tp2():
    """Preempt-and-recompute parity on an mp=2 mesh (GSPMD global
    arrays): the preempted run's greedy tokens == the single-device dense
    decoder's, fp and int8."""
    from paddle_tpu.distributed import mesh as mesh_mod

    single = _model(seed=0)
    rng = np.random.RandomState(52)
    A = rng.randint(0, 512, (8,)).astype("int32")
    B = rng.randint(0, 512, (16,)).astype("int32")

    mesh_mod.build_hybrid_mesh(dp=1, mp=2, pp=1, sharding=1)
    paddle.seed(0)
    tp = GPTForPretraining(GPTConfig(**CFG, use_parallel=True))
    tp.eval()
    for int8 in (False, True):
        refA = _dense_greedy(single, [A], 14, int8=int8)[0]
        refB = _dense_greedy(single, [B], 10, int8=int8)[0]
        eng = ServingEngine(tp, max_slots=2, page_size=8, num_pages=6,
                            chunk_tokens=16, int8=int8,
                            use_paged_kernel=False)
        ra = eng.add_request(A, 14)
        rb = eng.add_request(B, 10)
        out = eng.run()
        assert eng.stats["preemptions"] >= 1
        np.testing.assert_array_equal(out[ra].tokens, refA)
        np.testing.assert_array_equal(out[rb].tokens, refB)


def test_engine_preempts_mid_prefill_slot():
    """Preemption during a CHUNKED PREFILL of another slot (satellite
    edge case): the oldest slot's decode growth exhausts the pool while a
    younger slot is still chunk-prefilling its long prompt — the partial
    prefill is evicted cleanly (its pages free, progress reset), requeued
    and finished later with exact tokens."""
    model = _model()
    rng = np.random.RandomState(53)
    A = rng.randint(0, 512, (8,)).astype("int32")    # 8 + 24 new
    B = rng.randint(0, 512, (32,)).astype("int32")   # long prompt, 4 new
    refs = _dense_greedy(model, [A], 24) + _dense_greedy(model, [B], 4)
    # token_budget=2 starves B's prefill to 1 token/step once A decodes,
    # so A's growth at position 24 (needing a 4th page) lands while B is
    # still mid-prefill; 7 usable pages: A(1)+B(4)=5 at admit, A grows to
    # 7 by position 16, then preempts B at position 24
    eng = ServingEngine(model, max_slots=2, page_size=8, num_pages=8,
                        chunk_tokens=4, token_budget=2, prefix_cache=False)
    ra = eng.add_request(A, 24)
    rb = eng.add_request(B, 4)
    preempted_mid_prefill = False
    done = {}
    while eng.has_work:
        before = next((s.prefilled for s in eng._slots
                       if s is not None and s.request.rid == rb
                       and not s.started), None)
        n_pre = eng.stats["preemptions"]
        for f in eng.step():
            done[f.rid] = f
        if (before is not None and 0 < before < 32
                and eng.stats["preemptions"] > n_pre):
            preempted_mid_prefill = True
    assert preempted_mid_prefill
    np.testing.assert_array_equal(done[ra].tokens, refs[0])
    np.testing.assert_array_equal(done[rb].tokens, refs[1])
    assert eng.pool.pages_in_use == 0


def test_engine_cancel_all_states():
    """cancel(rid) is valid in every live state (satellite edge cases):
    waiting (queue removal), mid-prefill (partial pages released same
    call) and decoding (tokens so far returned); unknown/terminal rids
    return False."""
    model = _model()
    rng = np.random.RandomState(54)
    long_p = rng.randint(0, 512, (24,)).astype("int32")
    short_p = rng.randint(0, 512, (4,)).astype("int32")

    # waiting: one slot, head occupies it, the queued one cancels.  The
    # chunk/budget knobs below also slow prefill for the mid-prefill
    # case — ONE engine (and one pair of compiled programs) serves all
    # three lifecycle states.
    eng = ServingEngine(model, max_slots=1, page_size=8, chunk_tokens=4,
                        token_budget=4, prefix_cache=False)
    r1 = eng.add_request(short_p, 6)
    r2 = eng.add_request(short_p.copy(), 6)
    assert eng.cancel(r2) is True
    out = eng.run()
    assert out[r2].reason == "cancelled" and out[r2].tokens.size == 0
    assert out[r1].reason == "length" and len(out[r1].tokens) == 6
    assert eng.cancel(r1) is False          # already terminal
    assert eng.cancel(10**9) is False       # unknown rid

    # mid-prefill: chunk 4 + budget 4 spreads the 24-token prompt over
    # many steps; cancel after the first chunk lands
    r3 = eng.add_request(long_p, 6)
    eng.step()
    st = eng._slots[0]
    assert st is not None and not st.started and st.prefilled > 0
    assert eng.pool.pages_in_use > 0
    assert eng.cancel(r3) is True
    assert eng.pool.pages_in_use == 0       # pages released same call
    out = eng.run()
    assert out[r3].reason == "cancelled"

    # decoding: cancel keeps the tokens generated so far
    ref = _dense_greedy(model, [short_p], 12)[0]
    r4 = eng.add_request(short_p, 12)
    for _ in range(5):
        eng.step()
    n_so_far = len(eng._slots[0].tokens)
    assert 0 < n_so_far < 12
    assert eng.cancel(r4) is True
    out = eng.run()
    assert out[r4].reason == "cancelled"
    np.testing.assert_array_equal(out[r4].tokens, ref[:n_so_far])
    assert eng.pool.pages_in_use == 0


def test_engine_deadline_expiry_queued_and_resident():
    """deadline_s on the engine clock: an overdue WAITING request is
    dropped at queue-pop time (satellite edge case), an overdue RESIDENT
    one releases its pages mid-flight; deadline-free requests are
    untouched."""
    model = _model()
    rng = np.random.RandomState(55)
    p = rng.randint(0, 512, (6,)).astype("int32")
    clock, now = _fake_clock()
    eng = ServingEngine(model, max_slots=1, page_size=8, clock=now)
    ref = _dense_greedy(model, [p], 8)[0]
    r1 = eng.add_request(p, 8)                        # no deadline
    r2 = eng.add_request(p.copy(), 8, deadline_s=0.5)  # expires queued
    clock["t"] = 1.0
    fins = eng.step()
    assert [f.rid for f in fins] == [r2]
    assert fins[0].reason == "expired" and fins[0].tokens.size == 0
    out = eng.run()
    np.testing.assert_array_equal(out[r1].tokens, ref)

    # resident expiry (same engine, reused drained): the deadline hits
    # while decoding; the partial continuation is kept
    r3 = eng.add_request(p, 64, deadline_s=5.0)
    clock["t"] = 2.0
    for _ in range(3):
        eng.step()
    n_so_far = len(eng._slots[0].tokens)
    clock["t"] = 8.0
    out = eng.run()
    assert out[r3].reason == "expired"
    assert len(out[r3].tokens) == n_so_far > 0
    np.testing.assert_array_equal(out[r3].tokens, ref[:n_so_far])
    assert eng.pool.pages_in_use == 0


def test_engine_bounded_queue_backpressure():
    """max_queue bounds the waiting queue: overflow becomes an explicit
    `rejected` terminal (empty tokens, counted in stats) instead of
    unbounded growth; accepted requests are unaffected, and a preempted
    request's requeue BYPASSES the bound."""
    model = _model()
    rng = np.random.RandomState(56)
    prompts = _prompts(rng, (4, 4, 4, 4, 4))
    refs = _dense_greedy(model, prompts[:3], 5)  # rejects need no refs
    eng = ServingEngine(model, max_slots=1, page_size=8, max_queue=2)
    rids = [eng.add_request(p, 5) for p in prompts]
    # the queue bound counts WAITING requests (admission happens at
    # step()): the first two queue, the last three reject at enqueue
    assert eng.stats["rejected"] == 3
    out = eng.run()
    for i in (0, 1):
        np.testing.assert_array_equal(out[rids[i]].tokens, refs[i])
        assert out[rids[i]].reason == "length"
    for i in (2, 3, 4):
        assert out[rids[i]].reason == "rejected"
        assert out[rids[i]].tokens.size == 0
    assert eng.stats["queue_depth"] == 0
    # draining the queue reopens it
    r5 = eng.add_request(prompts[2], 5)
    np.testing.assert_array_equal(eng.run()[r5].tokens, refs[2])


def test_engine_snapshot_restore_exact():
    """r10 acceptance: snapshot -> kill -> restore resumes the host loop
    with token-for-token identical final outputs.  The snapshot is taken
    mid-flight (one slot decoding, one mid-prefill, one request still
    queued) and the original engine keeps running as the reference."""
    from paddle_tpu.serving import restore_engine, snapshot_engine

    model = _model()
    rng = np.random.RandomState(57)
    prompts = _prompts(rng, (5, 19, 7))
    refs = _dense_greedy(model, prompts, 10)
    eng = ServingEngine(model, max_slots=2, page_size=8, chunk_tokens=4,
                        token_budget=6)
    rids = [eng.add_request(p, 10) for p in prompts]
    done_pre = {}
    for _ in range(3):
        for f in eng.step():
            done_pre[f.rid] = f
    snap = snapshot_engine(eng)
    assert any(s is not None and not s.started for s in eng._slots) or \
        eng.scheduler.n_waiting > 0      # genuinely mid-flight
    # reference: the original engine runs to completion
    done_a = dict(done_pre)
    done_a.update(eng.run())
    # "kill" the engine; rebuild the same weights and restore
    del eng
    model2 = _model()
    eng2 = restore_engine(model2, snap)
    done_b = dict(done_pre)
    done_b.update(eng2.run())
    assert set(done_b) == set(rids)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(done_b[rid].tokens, refs[i])
        np.testing.assert_array_equal(done_b[rid].tokens,
                                      done_a[rid].tokens)
    assert eng2.pool.pages_in_use == 0

    # ServingEngine.restore is the method spelling of the same plumbing:
    # restored state matches without re-running the whole drain
    eng3 = ServingEngine.restore(_model(), snap)
    assert eng3.scheduler.n_waiting == snap["engine"]["stats"]["queue_depth"]
    assert [s is None for s in eng3._slots] == \
        [s is None for s in snap["slots"]]
    np.testing.assert_array_equal(eng3._table, snap["engine"]["table"])


def test_finished_request_reason_surface():
    """FinishedRequest exposes .reason (the r10 lifecycle name for
    finish_reason) and .ok; TERMINAL_REASONS names the closed set."""
    from paddle_tpu.serving import TERMINAL_REASONS

    assert TERMINAL_REASONS == ("eos", "length", "rejected", "expired",
                                "cancelled")
    model = _model()
    rng = np.random.RandomState(58)
    p = rng.randint(0, 512, (4,)).astype("int32")
    eng = ServingEngine(model, max_slots=1, page_size=8)
    rid = eng.add_request(p, 3)
    fin = eng.run()[rid]
    assert fin.reason == fin.finish_reason == "length" and fin.ok


@pytest.mark.parametrize("mode,block", [("fp", 1), ("int8", 4)])
def test_engine_on_token_streams_exactly_delivered_tokens(mode, block):
    """r12 streaming hook: on_token(rid, token) fires once per emitted
    token per slot per step, in delivery order — the streamed sequence
    is token-for-token identical to the FinishedRequest tokens, across
    fp/int8 and decode_block 1/4 (where a block emits up to k tokens per
    dispatch), with EOS cut respected mid-block."""
    int8 = mode == "int8"
    model = _model()
    streamed = {}

    def on_token(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    eng = ServingEngine(model, max_slots=2, page_size=8, int8=int8,
                        decode_block=block, eos_token_id=7,
                        on_token=on_token)
    rng = np.random.RandomState(60)
    rids = [eng.add_request(
        rng.randint(0, 512, (int(rng.randint(3, 14)),)).astype("int32"),
        int(rng.randint(3, 10))) for _ in range(5)]
    out = eng.run()
    assert set(out) == set(rids)
    for rid in rids:
        np.testing.assert_array_equal(
            np.asarray(streamed.get(rid, []), np.int32), out[rid].tokens)
    assert sum(len(v) for v in streamed.values()) == \
        eng.stats["tokens_generated"]


def test_engine_on_token_settable_post_ctor_and_chains_nothing():
    """The hook is a plain settable attribute (the HTTP front end chains
    onto it after construction) and None costs nothing."""
    model = _model()
    eng = ServingEngine(model, max_slots=1, page_size=8)
    assert eng.on_token is None
    rng = np.random.RandomState(61)
    r1 = eng.add_request(rng.randint(0, 512, (4,)).astype("int32"), 3)
    eng.run()
    got = []
    eng.on_token = lambda rid, tok: got.append((rid, tok))
    r2 = eng.add_request(rng.randint(0, 512, (5,)).astype("int32"), 4)
    out = eng.run()
    assert [t for _, t in got] == list(out[r2].tokens)
    assert all(rid == r2 for rid, _ in got) and r1 not in dict(got)
