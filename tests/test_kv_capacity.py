"""KV-capacity matrix (ISSUE r14): GQA + sliding-window + int4 KV pages.

Three orthogonal knobs multiply how many tokens a fixed KV budget holds —
``num_kv_heads`` (grouped-query attention), ``attn_window`` (sliding-window
attention with page recycling) and ``kv_bits=4`` (nibble-packed pages) —
and EXACTNESS is the contract: every leg must reproduce the corresponding
dense decoder token-for-token, not approximately.  All CPU-runnable:

  * kernel parity matrices: paged decode / multi-query verify / chunked
    prefill, each across group factor {1, 2, 4} x window {off, on} x page
    bits {float, 8, 4}, kernel (interpret — the exact TPU code path) vs
    jnp reference;
  * layout: the flash bsnd GQA path reaches the Pallas kernel with ZERO
    transpose primitives, and GQA adds zero transposes to the ring
    engine's jaxpr;
  * int4 plumbing: pack/unpack round-trip, the quantization error band,
    and gather_pages making the IDENTICAL dequant decision the kernels
    make in VMEM;
  * pool accounting: int4/GQA buffer shapes, bytes_per_token, layout(),
    ctor validation;
  * engine end-to-end: GQA + window + int4 greedy decode == the dense
    decoder's tokens (jnp and interpret-kernel, tp2, under preemption,
    speculative decoding, prefix-cache COW), windowed page recycling
    keeps live pages bounded while high-water grows, the prefix cache
    refuses (and counts) windowed long prompts, and snapshot v5 records
    the pool layout — restore refuses a mismatched engine.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
# primitive walks (pallas bodies excluded) live in the analysis package
from paddle_tpu.analysis.jaxpr_audit import count_primitive
from paddle_tpu.kernels import flash
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import paged_prefill as pp
from paddle_tpu.models.generation import build_generate_fn
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.ops.quant_ops import (pack_int4, quantize_int4_per_token,
                                      quantize_per_token, unpack_int4)
from paddle_tpu.serving import KVPool, PrefixIndex, ServingEngine
from paddle_tpu.serving.snapshot import restore_engine, snapshot_engine

pytestmark = pytest.mark.kvcap

# 1-layer models keep the tier-1 budget (r13 convention): every property
# here — kernel masks, page recycling, pool accounting, scheduler legs —
# is layer-count-independent.  Multi-layer paged-KV addressing has one
# dedicated 2-layer cell (test_engine_two_layer_kernel_int4_exact) and
# full multi-layer serving exactness lives in test_serving.py.
CFG = dict(vocab_size=512, hidden_size=64, num_layers=1, num_heads=4,
           max_seq_len=96, dropout=0.0)

_REF_CACHE = {}


def _model(seed=3, **over):
    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(**{**CFG, **over}))
    m.eval()
    return m


def _dense(model, prompts, n, kv_bits=None, cache_key=None):
    """Greedy dense-decoder reference; ``cache_key`` dedups the jit trace
    across parametrized cells that share a model config."""
    if cache_key is not None and (cache_key, kv_bits) in _REF_CACHE:
        return _REF_CACHE[(cache_key, kv_bits)]
    fn = build_generate_fn(model, n, greedy=True, kv_bits=kv_bits)
    refs = [np.asarray(fn(p[None]))[0, len(p):] for p in prompts]
    if cache_key is not None:
        _REF_CACHE[(cache_key, kv_bits)] = refs
    return refs


def _mk_pages(rng, P, HKV, PS, D, bits):
    kf = jnp.asarray(rng.randn(P, HKV, PS, D).astype("float32"))
    vf = jnp.asarray(rng.randn(P, HKV, PS, D).astype("float32"))
    if bits is None:
        return kf, vf, None, None
    qf = quantize_int4_per_token if bits == 4 else quantize_per_token
    kq, ks = qf(kf)
    vq, vs = qf(vf)
    return kq, vq, ks, vs


# ---------------------------------------------------------------------------
# kernel parity matrices: group x window x bits, kernel (interpret) vs ref
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 12], ids=["full", "win"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_paged_decode_kernel_matrix(group, window, bits):
    rng = np.random.RandomState(17 * group + (bits or 1))
    B, HKV, D, PS, MAXP, P = 3, 2, 16, 8, 4, 10
    H = HKV * group
    q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
    kq, vq, ks, vs = _mk_pages(rng, P, HKV, PS, D, bits)
    bt = jnp.asarray(rng.randint(1, P, (B, MAXP)).astype("int32"))
    lens = jnp.asarray(np.array([5, 17, 32], "int32"))
    out = pa.paged_attention(q, kq, vq, bt, lens, k_scales=ks, v_scales=vs,
                             interpret=True, window=window)
    ref = pa.paged_attention_ref(q, kq, vq, bt, lens, k_scales=ks,
                                 v_scales=vs, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _ragged(rng, bits, group, rows=1):
    """Ragged lengths in one batch: 1, a page, a page + 1, a full table
    (for a block of ``rows`` query rows), an idle lane (nothing to attend,
    every table entry the null page) and one in between.  Each slot's pages
    are its own; the pool's last page is spare (``_poison`` takes it)."""
    B, HKV, D, PS, MAXP = 6, 2, 16, 8, 5
    H = HKV * group
    P = 1 + B * MAXP + 1
    full = MAXP * PS - (rows - 1)
    lens = np.array([1, PS, PS + 1, full, 1, 19], "int32")
    kq, vq, ks, vs = _mk_pages(rng, P, HKV, PS, D, bits)
    bt = 1 + np.arange(B * MAXP, dtype="int32").reshape(B, MAXP)
    bt[4] = 0
    return B, H, D, PS, MAXP, P, lens, kq, vq, ks, vs, bt


def _poison(bt, kq, vq, ks, vs, live):
    """``bt`` with every entry outside ``live`` = (lo, hi) naming the last
    page, and that page all NaN (a quantized pool carries it in the
    scales)."""
    lo, hi = live
    col = np.arange(bt.shape[1])[None, :]
    dead = (col < np.asarray(lo)[:, None]) | (col >= np.asarray(hi)[:, None])
    bad = kq.shape[0] - 1
    nan = lambda a: a.at[bad].set(jnp.nan)
    if ks is None:
        kq, vq = nan(kq), nan(vq)
    else:
        ks, vs = nan(ks), nan(vs)
    return jnp.asarray(np.where(dead, bad, bt)), kq, vq, ks, vs, int(dead.sum())


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 12], ids=["full", "win"])
@pytest.mark.parametrize("group", [1, 16])
def test_paged_decode_kernel_ragged_lengths(group, window, bits):
    """One batch of every kind of lane, the window (12) crossing a page
    boundary (8): the walk over live pages equals the dense reference."""
    rng = np.random.RandomState(5 * group + (bits or 1))
    B, H, D, PS, MAXP, P, lens, kq, vq, ks, vs, bt = _ragged(rng, bits, group)
    q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
    out = pa.paged_attention(q, kq, vq, jnp.asarray(bt), jnp.asarray(lens),
                             k_scales=ks, v_scales=vs, interpret=True,
                             window=window)
    ref = pa.paged_attention_ref(q, kq, vq, jnp.asarray(bt),
                                 jnp.asarray(lens), k_scales=ks, v_scales=vs,
                                 window=window)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 12], ids=["full", "win"])
@pytest.mark.parametrize("group", [1, 16])
def test_paged_decode_kernel_never_reads_a_dead_page(group, window, bits):
    """Every table entry outside a slot's live range names a page of NaN
    and the result is BIT-equal to the clean run: the kernel reads no page
    the mask throws away (a grid over the whole table lets the NaN through
    as 0 x NaN)."""
    rng = np.random.RandomState(7 * group + (bits or 1))
    B, H, D, PS, MAXP, P, lens, kq, vq, ks, vs, bt = _ragged(rng, bits, group)
    q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
    clean = pa.paged_attention(q, kq, vq, jnp.asarray(bt), jnp.asarray(lens),
                               k_scales=ks, v_scales=vs, interpret=True,
                               window=window)
    bt2, kq, vq, ks, vs, n_dead = _poison(
        bt, kq, vq, ks, vs, pa.live_pages(lens, PS, window, 1, MAXP))
    assert n_dead >= B * MAXP // 2
    out = pa.paged_attention(q, kq, vq, bt2, jnp.asarray(lens), k_scales=ks,
                             v_scales=vs, interpret=True, window=window)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 12], ids=["full", "win"])
def test_paged_mq_kernel_ragged_and_never_reads_a_dead_page(window, bits):
    """The verify kernel shares the walk: ragged lengths against its
    reference, and bit-equal with every dead table entry poisoned."""
    rng = np.random.RandomState(11 + (bits or 1))
    T = 3
    B, H, D, PS, MAXP, P, lens, kq, vq, ks, vs, bt = _ragged(
        rng, bits, 4, rows=T)
    lens = lens - 1                  # positions valid BEFORE the block
    q = jnp.asarray(rng.randn(B, T, H, D).astype("float32"))
    out = pa.paged_attention_mq(q, kq, vq, jnp.asarray(bt), jnp.asarray(lens),
                                k_scales=ks, v_scales=vs, interpret=True,
                                window=window)
    ref = pa.paged_attention_mq_ref(q, kq, vq, jnp.asarray(bt),
                                    jnp.asarray(lens), k_scales=ks,
                                    v_scales=vs, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    bt2, kq, vq, ks, vs, n_dead = _poison(
        bt, kq, vq, ks, vs, pa.live_pages(lens + 1, PS, window, T, MAXP))
    assert n_dead >= B * MAXP // 2
    again = pa.paged_attention_mq(q, kq, vq, bt2, jnp.asarray(lens),
                                  k_scales=ks, v_scales=vs, interpret=True,
                                  window=window)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("window", [None, 1, 12, 40], ids=str)
def test_live_pages_are_the_pages_of_the_visible_positions(window, rows):
    """The one range function against a count by hand, for every length a
    table of 5 pages of 8 can hold; and the contract that keeps a softmax
    from emptying: a range is never empty, whatever the length."""
    PS, MAXP = 8, 5
    lens = np.arange(0, MAXP * PS - rows + 2, dtype=np.int32)
    lo, hi = pa.live_pages(lens, PS, window, rows, MAXP)
    assert ((0 <= lo) & (lo < hi) & (hi <= MAXP)).all()
    for n, a, b in zip(lens, lo, hi):
        seen = set()
        for i in range(rows):        # row i sees n + i positions, its own too
            first = 0 if window is None else max(0, n + i - window)
            seen |= {pos // PS for pos in range(first, n + i)}
        seen = {p for p in seen if p < MAXP}
        assert set(range(a, b)) == (seen or {0}), (n, a, b)
    # the same arithmetic on jax values (what the program and the kernel use)
    jlo, jhi = pa.live_pages(jnp.asarray(lens), PS, window, rows, MAXP)
    np.testing.assert_array_equal(np.asarray(jlo), lo)
    np.testing.assert_array_equal(np.asarray(jhi), hi)


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 7], ids=["full", "win"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_paged_mq_kernel_matrix(group, window, bits):
    rng = np.random.RandomState(31 * group + (bits or 1))
    B, T, HKV, D, PS, MAXP, P = 2, 3, 2, 16, 8, 3, 8
    H = HKV * group
    q = jnp.asarray(rng.randn(B, T, H, D).astype("float32"))
    kq, vq, ks, vs = _mk_pages(rng, P, HKV, PS, D, bits)
    bt = jnp.asarray(rng.randint(1, P, (B, MAXP)).astype("int32"))
    lens = jnp.asarray(np.array([5, 13], "int32"))
    out = pa.paged_attention_mq(q, kq, vq, bt, lens, k_scales=ks,
                                v_scales=vs, interpret=True, window=window)
    ref = pa.paged_attention_mq_ref(q, kq, vq, bt, lens, k_scales=ks,
                                    v_scales=vs, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 5], ids=["full", "win"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_paged_prefill_kernel_matrix(group, window, bits):
    rng = np.random.RandomState(53 * group + (bits or 1))
    C, HKV, D, PS, MAXP, P = 8, 2, 16, 8, 4, 9
    H = HKV * group
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kq, vq, ks, vs = _mk_pages(rng, P, HKV, PS, D, bits)
    bt = jnp.asarray(rng.randint(1, P, (MAXP,)).astype("int32"))
    out = pp.paged_prefill(q, kq, vq, bt, 6, k_scales=ks, v_scales=vs,
                           interpret=True, window=window)
    ref = pp.paged_prefill_ref(q, kq, vq, bt, 6, k_scales=ks, v_scales=vs,
                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _walked_ids(monkeypatch):
    """Spy on ``paged_prefill``'s ``pallas_call``: per call, the pool page
    the K index map names at every step of the grid it was built with (a
    concrete bound: the calls here are eager)."""
    calls, real = [], pp.pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        run = real(kernel, grid_spec=grid_spec, **kw)

        def call(bt, walk, *args):
            at = grid_spec.in_specs[1].index_map
            calls.append([
                int(at(*step, np.asarray(bt), np.asarray(walk))[0])
                for step in np.ndindex(*map(int, grid_spec.grid))])
            return run(bt, walk, *args)

        return call

    monkeypatch.setattr(pp.pl, "pallas_call", spy)
    return calls


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("group", [1, 8], ids=["heads", "kvgroup"])
@pytest.mark.parametrize("window", [None, 12, 64],
                         ids=["full", "win12", "win64"])
@pytest.mark.parametrize("start", [0, 13, 16, 40],
                         ids=["first", "midpage", "boundary", "last"])
def test_paged_prefill_kernel_walks_live_pages_only(monkeypatch, start,
                                                    window, group, bits):
    """The grid's page axis is the chunk's live range and nothing else,
    for both block shapes (all heads; one KV head's group) and every pool
    type.  Every dead table entry names a page of its own that holds large
    finite values: a walked one shows in the COUNT of pages the index map
    handed the body (it would round away in the output: its scores are
    masked), and the output equals the dense reference's."""
    rng = np.random.RandomState(59 * group + 7 * start + (bits or 1))
    C, HKV, D, PS, MAXP = 8, 2, 16, 8, 6
    H, P = HKV * group, 1 + 2 * MAXP
    assert pp.block_heads(H, PS, D, C, HKV) == (H if group == 1 else group)
    q = jnp.asarray(rng.randn(C, H, D).astype("float32"))
    kq, vq, ks, vs = _mk_pages(rng, P, HKV, PS, D, bits)
    lo, hi = (int(x) for x in pa.live_pages(start + 1, PS, window, C, MAXP))
    live = 1 + np.arange(MAXP)
    dead = 1 + MAXP + np.arange(MAXP)
    col = np.arange(MAXP)
    bt = np.where((col >= lo) & (col < hi), live, dead).astype("int32")
    big = lambda a: a.at[dead].set(1e4)
    if bits is None:
        kq, vq = big(kq), big(vq)
    else:
        ks, vs = big(ks), big(vs)
    calls = _walked_ids(monkeypatch)
    out = pp.paged_prefill(q, kq, vq, jnp.asarray(bt), start, k_scales=ks,
                           v_scales=vs, interpret=True, window=window)
    ref = pp.paged_prefill_ref(q, kq, vq, jnp.asarray(bt), start,
                               k_scales=ks, v_scales=vs, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # each live page once per step of the leading axis, in order; no other
    (ids,) = calls
    assert ids == list(live[lo:hi]) * (1 if group == 1 else HKV)


@pytest.mark.parametrize("attn_window", [None, 24], ids=["full", "win24"])
def test_engine_counts_the_pages_its_chunk_kernel_walks(attn_window):
    """``prefill_pages_walked`` is the chunk kernel's own live range summed
    over the layers of every ``_prefill_fn`` dispatch, recomputed here from
    the ``start`` and the bucket width the program was handed."""
    m = _model(seed=1, attn_window=attn_window, num_layers=2)
    eng = ServingEngine(m, max_slots=3, page_size=8, chunk_tokens=16,
                        use_paged_kernel=False)
    handed, run = [], eng._prefill_fn    # (p, bufs, toks, start, n, ...)
    eng._prefill_fn = lambda *a: (handed.append(
        (int(a[3]), a[2].shape[0])), run(*a))[1]
    rng = np.random.RandomState(3)
    for p in _prompts(rng, (30, 5, 17, 41)):
        eng.add_request(p, 4)
    eng.run()
    want = sum(2 * int(np.subtract(*pa.live_pages(
        start + 1, 8, attn_window, width, eng.max_pages)[::-1]))
        for start, width in handed)
    assert len(handed) == eng.stats["prefill_calls"] > 6
    assert {w for _, w in handed} == {8, 16} and max(handed)[0] >= 32
    assert eng.stats["prefill_pages_walked"] == want
    assert eng.stats["prefill_pages_in_table"] == \
        len(handed) * eng.max_pages * 2
    assert 0 < want < eng.stats["prefill_pages_in_table"]


def test_windowed_ref_ignores_out_of_window_positions():
    """The window bound is as hard as the length bound: rewriting page
    positions at or below ``lengths - window`` (what the engine's ring
    recycling overwrites) changes nothing."""
    rng = np.random.RandomState(2)
    P, HKV, PS, D, W = 6, 2, 8, 16, 10
    q = jnp.asarray(rng.randn(1, 4, D).astype("float32"))   # group 2
    kp = rng.randn(P, HKV, PS, D).astype("float32")
    vp = rng.randn(P, HKV, PS, D).astype("float32")
    bt = jnp.asarray(np.array([[1, 2, 3]], "int32"))
    lens = jnp.asarray(np.array([20], "int32"))
    a = pa.paged_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp), bt,
                               lens, window=W)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[1], vp2[1] = 99.0, -99.0     # page 1 = positions 0..7 < 20 - 10
    kp2[2, :, :2] = 55.0             # positions 8, 9 also below the window
    b = pa.paged_attention_ref(q, jnp.asarray(kp2), jnp.asarray(vp2), bt,
                               lens, window=W)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# int4 plumbing
# ---------------------------------------------------------------------------


def test_int4_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-8, 8, (3, 5, 16)).astype("int8"))
    packed = pack_int4(q)
    assert packed.shape == (3, 5, 8) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(q))


def test_int4_quant_error_band():
    """Per-token symmetric int4: reconstruction error <= scale / 2
    elementwise (round-to-nearest on a 15-level grid)."""
    rng = np.random.RandomState(1)
    x = rng.randn(4, 6, 16).astype("float32")
    packed, s = quantize_int4_per_token(jnp.asarray(x))
    deq = np.asarray(unpack_int4(packed)).astype("float32") * np.asarray(s)
    assert np.all(np.abs(deq - x) <= np.asarray(s) * 0.5 + 1e-6)


def test_gather_pages_int4_matches_manual_dequant():
    """gather_pages makes the IDENTICAL dequant decision the kernels make
    in VMEM: unpack nibbles, then apply the per-position scales."""
    rng = np.random.RandomState(3)
    B, HKV, D, PS, MAXP, P = 2, 2, 16, 8, 3, 7
    kq, _, ks, _ = _mk_pages(rng, P, HKV, PS, D, 4)
    bt = np.asarray(rng.randint(1, P, (B, MAXP)).astype("int32"))
    got = np.asarray(pa.gather_pages(kq, jnp.asarray(bt), ks, head_dim=D))
    dense = np.asarray(unpack_int4(kq)).astype("float32") * np.asarray(ks)
    want = dense[bt].transpose(0, 2, 1, 3, 4).reshape(B, HKV, MAXP * PS, D)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# layout: GQA adds zero transposes around the bsnd kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 48])
def test_flash_bsnd_gqa_window_no_transposes(window):
    """The bsnd flash entry consumes GQA K/V in place — query-head groups
    gather onto the shared K/V head inside the BlockSpec index maps, so
    the jaxpr reaches pallas_call without one transpose primitive, window
    on or off."""
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    q = jnp.zeros((b, s, h, d), jnp.float32)
    k = jnp.zeros((b, s, hkv, d), jnp.float32)
    v = jnp.zeros((b, s, hkv, d), jnp.float32)
    jx = jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, layout="bsnd", window=window,
        interpret=True))(q, k, v)
    assert count_primitive(jx, "pallas_call") >= 1
    assert count_primitive(jx, "transpose") == 0


def test_ring_gqa_adds_zero_transposes():
    """The ring engine's GQA grouping is a reshape + grouped einsum, never
    a K/V head repeat or a layout transpose: the GQA jaxpr carries no more
    transpose primitives than the MHA jaxpr on the same shapes."""
    from paddle_tpu.kernels.ring import ring_attention

    b, h, hkv, s, d = 1, 4, 2, 32, 16
    q = jnp.zeros((b, h, s, d), jnp.float32)
    kf = jnp.zeros((b, h, s, d), jnp.float32)
    kg = jnp.zeros((b, hkv, s, d), jnp.float32)

    def probe(k):
        jx = jax.make_jaxpr(lambda q, k: ring_attention(
            q, k, k, causal=True, use_flash=False, window=16))(q, k)
        return count_primitive(jx, "transpose")

    assert probe(kg) <= probe(kf)


def _repeat_heads_reference(q, k, v, window):
    """Causal (windowed) attention over [b, h, s, d] q and [b, hkv, s, d]
    k/v with K/V heads repeated to h: the plain oracle for GQA."""
    _, h, s_len, d = q.shape
    g = h // k.shape[1]
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhid,bhjd->bhij", q, kk) / np.sqrt(d)
    i = jnp.arange(s_len)[:, None]
    j = jnp.arange(s_len)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    att = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhij,bhjd->bhid", att, vv)


@pytest.mark.parametrize("hkv,w", [(2, 100), (2, None), (4, 100)],
                         ids=["gqa2_window", "gqa2", "window"])
def test_flash_bsnd_gqa_window_matches_reference(hkv, w):
    """Forward AND gradients of the bsnd kernel under GQA, under a window
    and under both == the repeat-heads einsum oracle."""
    rng = np.random.RandomState(0)
    b, s, h, d = 2, 256, 4, 32
    q = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype("float32"))

    def f(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, layout="bsnd",
                                     window=w, interpret=True)

    def oracle(q, k, v):  # bsnd -> the oracle's bnsd and back
        return jnp.swapaxes(_repeat_heads_reference(
            *(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), w), 1, 2)

    out = f(q, k, v)
    ref = oracle(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_k = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))
    g_r = jax.grad(lambda *a: jnp.sum(oracle(*a) ** 2), argnums=(0, 1, 2))
    for a, b_ in zip(g_k(q, k, v), g_r(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_rejects_bnsd_gqa_and_acausal_window():
    q = jnp.zeros((2, 4, 64, 16), jnp.float32)
    k = jnp.zeros((2, 2, 64, 16), jnp.float32)
    with pytest.raises(ValueError):
        flash.flash_attention(q, k, k, causal=True, interpret=True)
    qf = jnp.zeros((2, 4, 64, 16), jnp.float32)
    with pytest.raises(ValueError):
        flash.flash_attention(qf, qf, qf, causal=False, window=8,
                              interpret=True)


def test_ring_gqa_window_matches_reference():
    """Sequence-sharded ring attention with GQA + window == the dense
    repeat-heads oracle (the einsum engine carries both knobs)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.kernels.ring import ring_attention

    s_ = fleet.DistributedStrategy()
    s_.hybrid_configs = {"dp_degree": 1, "mp_degree": 8, "pp_degree": 1,
                         "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=s_)
    rng = np.random.RandomState(7)
    b, h, hkv, s, d, w = 1, 4, 2, 64, 16, 20
    q = rng.randn(b, h, s, d).astype("float32")
    k = rng.randn(b, hkv, s, d).astype("float32")
    v = rng.randn(b, hkv, s, d).astype("float32")
    out = np.asarray(ring_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), axis="mp", causal=True,
                                    window=w))
    ref = np.asarray(_repeat_heads_reference(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), w))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# pool accounting
# ---------------------------------------------------------------------------


def test_kv_pool_int4_gqa_layout_and_bytes():
    pool = KVPool(2, 4, 16, 8, 8, num_kv_heads=2, kv_bits=4, window=16)
    assert pool.buffers["k"].shape == (2, 8, 2, 8, 8)   # last dim D // 2
    assert pool.buffers["k"].dtype == jnp.int8
    assert pool.buffers["ks"].shape == (2, 8, 2, 8, 1)
    assert pool.buffers["ks"].dtype == jnp.float32
    # per layer, per side: 2 kv heads x (8 packed bytes + 4 scale bytes)
    assert pool.bytes_per_token() == 2 * 2 * (2 * 8 + 2 * 4) == 96
    base = KVPool(2, 4, 16, 8, 8)
    assert base.bytes_per_token() == 2 * 2 * (4 * 16 * 4) == 1024
    lay = pool.layout()
    assert lay == {"kv_heads": 2, "page_dtype": "int8", "kv_bits": 4,
                   "window": 16, "page_size": 8, "head_dim": 16}
    assert base.layout()["kv_bits"] is None
    assert base.layout() != lay


def test_kv_pool_ctor_validation():
    with pytest.raises(ValueError):
        KVPool(1, 4, 16, 8, 8, kv_bits=3)
    with pytest.raises(ValueError):
        KVPool(1, 4, 15, 8, 8, kv_bits=4)          # odd head_dim
    with pytest.raises(ValueError):
        KVPool(1, 4, 16, 8, 8, num_kv_heads=3)     # 4 % 3 != 0
    # legacy coupling: int8=True still means an int8 page pool
    assert KVPool(1, 2, 16, 8, 8, int8=True).kv_bits == 8


# ---------------------------------------------------------------------------
# engine end-to-end exactness
# ---------------------------------------------------------------------------


def _prompts(rng, lens, vocab=512):
    return [rng.randint(0, vocab, (n,)).astype("int32") for n in lens]


@pytest.mark.parametrize("kv_bits", [None, 4], ids=["fp", "int4"])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_engine_gqa_window_matches_dense(kernel, kv_bits):
    """Paged GQA + sliding-window decode (fp and int4 pages, jnp path and
    interpret-kernel path) == the dense decoder, token for token."""
    m = _model(num_kv_heads=2, attn_window=24)
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, (13, 21, 9))
    refs = _dense(m, prompts, 12, kv_bits=kv_bits, cache_key="gqa_win12")
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=kv_bits,
                        use_paged_kernel=kernel)
    assert eng.window == 24 and eng.kv_bits == kv_bits
    rids = [eng.add_request(p, 12) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


def test_engine_two_layer_kernel_int4_exact():
    """The one multi-layer cell: stacked-layer page addressing (the L axis
    of the page buffers) through the interpret kernel with every knob on
    at once — GQA + window + int4 — still lands the dense tokens."""
    m = _model(num_layers=2, num_kv_heads=2, attn_window=24)
    rng = np.random.RandomState(34)
    prompts = _prompts(rng, (13, 7))
    refs = _dense(m, prompts, 10, kv_bits=4)
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=4,
                        use_paged_kernel=True)
    rids = [eng.add_request(p, 10) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


def test_engine_gqa_int4_window_preemption_exact():
    """Pool pressure preempts a windowed int4 slot mid-decode; the
    restarted request still lands the exact dense tokens."""
    m = _model(seed=0, num_kv_heads=2, attn_window=24)
    rng = np.random.RandomState(52)
    A = rng.randint(0, 512, (8,)).astype("int32")
    B = rng.randint(0, 512, (16,)).astype("int32")
    refA = _dense(m, [A], 14, kv_bits=4)[0]
    refB = _dense(m, [B], 10, kv_bits=4)[0]
    eng = ServingEngine(m, max_slots=2, page_size=8, num_pages=6,
                        chunk_tokens=16, kv_bits=4, use_paged_kernel=False)
    ra = eng.add_request(A, 14)
    rb = eng.add_request(B, 10)
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    np.testing.assert_array_equal(out[ra].tokens, refA)
    np.testing.assert_array_equal(out[rb].tokens, refB)


def test_engine_spec_decode_gqa_window_int4_exact():
    """Speculative decoding (multi-query verify) over GQA + window + int4
    pages stays token-exact vs the plain dense decoder, and repetitive
    prompts keep the drafter accepting."""
    m = _model(seed=1, num_kv_heads=2, attn_window=20)
    rng = np.random.RandomState(4)
    prompts = [np.tile(rng.randint(0, 512, (5,)), 4)[:15].astype("int32")
               for _ in range(3)]
    refs = _dense(m, prompts, 12, kv_bits=4)
    eng = ServingEngine(m, max_slots=2, page_size=8, spec_k=2, kv_bits=4,
                        use_paged_kernel=False)
    rids = [eng.add_request(p, 12) for p in prompts]
    out = eng.run()
    assert eng.stats["spec_drafted"] > 0
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


@pytest.mark.parametrize("kw", [{}, {"decode_block": 2}, {"spec_k": 2}],
                         ids=["decode", "decode_block2", "spec_k2"])
def test_engine_counts_the_pages_its_decode_kernels_walk(kw):
    """``decode_pages_walked`` is the kernels' own live range summed over
    the lanes and layers of every decode or verify dispatch (an inner step
    of a ``decode_block`` counts as a dispatch: it is one kernel call a
    layer), recomputed here from the arguments the programs were handed."""
    m = _model(seed=1, attn_window=24, num_layers=2)
    eng = ServingEngine(m, max_slots=3, page_size=8, chunk_tokens=8,
                        use_paged_kernel=False, **kw)
    handed = []
    if "spec_k" in kw:
        run = eng._verify_fn       # (p, bufs, toks, draft, n_draft, lengths, ..)
        eng._verify_fn = lambda *a: (handed.append(
            [np.asarray(a[5]) + 1]), run(*a))[1]
        rows = 3
    else:
        run = eng._decode_fn       # (p, bufs, toks, lengths, table, remaining, ..)
        eng._decode_fn = lambda *a: (handed.append(
            [np.asarray(a[3]) + np.minimum(i, np.asarray(a[5])) + 1
             for i in range(eng.decode_block)]), run(*a))[1]
        rows = 1
    rng = np.random.RandomState(3)
    for p in _prompts(rng, (30, 5, 17, 41)):
        eng.add_request(p, 10)
    eng.run()
    calls = [seen for call in handed for seen in call]
    want = sum(2 * int(np.subtract(*pa.live_pages(
        seen, 8, 24, rows, eng.max_pages)[::-1]).sum()) for seen in calls)
    assert len(handed) == eng.stats["decode_calls"] > 4
    assert eng.stats["decode_pages_walked"] == want
    assert eng.stats["decode_pages_in_table"] == \
        len(calls) * 3 * eng.max_pages * 2
    assert 0 < want < eng.stats["decode_pages_in_table"]


def test_engine_tp2_gqa_window_int4_matches_single_device():
    """tp2 GQA engine (use_parallel weights on an mp=2 mesh) with window +
    int4 pages reproduces the single-device dense greedy tokens."""
    from paddle_tpu.distributed import mesh as mesh_mod

    over = dict(num_kv_heads=2, attn_window=24)
    single = _model(seed=0, **over)
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, (5, 9))
    refs = _dense(single, prompts, 8, kv_bits=4)

    mesh_mod.build_hybrid_mesh(dp=1, mp=2, pp=1, sharding=1)
    paddle.seed(0)
    tp = GPTForPretraining(GPTConfig(**{**CFG, **over}, use_parallel=True))
    tp.eval()
    eng = ServingEngine(tp, max_slots=2, page_size=8, kv_bits=4,
                        chunk_tokens=4, use_paged_kernel=False)
    rids = [eng.add_request(p, 8) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid].tokens, refs[i])


def test_engine_gqa_int4_prefix_cow_exact():
    """Prefix-cache hits and a copy-on-write tail clone on int4/GQA pages:
    shared nibble-packed pages are reused bit-identically."""
    m = _model(seed=2, num_kv_heads=2)
    rng = np.random.RandomState(9)
    shared = rng.randint(0, 512, (20,)).astype("int32")
    B = np.concatenate([shared[:12],
                        rng.randint(0, 512, (6,)).astype("int32")])
    refs = _dense(m, [shared, B], 10, kv_bits=4)
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=4,
                        use_paged_kernel=False)
    ra = eng.add_request(shared, 10)
    eng.run()
    rb = eng.add_request(B, 10)         # full-page hit + partial-tail COW
    out = eng.run()
    assert eng.stats["prefix_hit_tokens"] > 0
    assert ra != rb
    np.testing.assert_array_equal(out[rb].tokens, refs[1])


# ---------------------------------------------------------------------------
# windowed page recycling + prefix refusal
# ---------------------------------------------------------------------------


def test_windowed_recycling_bounds_live_pages():
    """A long windowed generation keeps its LIVE page count bounded by the
    window while the high-water logical length keeps growing — recycled
    pages return to the pool mid-request — and the tokens still match the
    dense windowed decoder exactly."""
    m = _model(seed=6, num_kv_heads=2, attn_window=16)
    rng = np.random.RandomState(11)
    p = rng.randint(0, 512, (5,)).astype("int32")
    ref = _dense(m, [p], 40)[0]
    eng = ServingEngine(m, max_slots=1, page_size=8, prefix_cache=False,
                        use_paged_kernel=False)
    rid = eng.add_request(p, 40)
    live_max, hw_final, fins = 0, 0, {}
    while eng.has_work:
        for f in eng.step():
            fins[f.rid] = f
        st = eng._slots[0]
        if st is not None:
            live_max = max(live_max, len(st.pages))
            hw_final = max(hw_final, st.hw_pages)
    cap = eng.pool.pages_for(16 + 1) + 1     # window + cmax, +1 ring slack
    assert live_max <= cap < hw_final        # bounded live, growing high-water
    np.testing.assert_array_equal(fins[rid].tokens, ref)
    # every recycled page really went back: drained pool is fully free
    assert eng.pool.num_free == eng.pool.num_pages - 1


def test_prefix_cache_refuses_windowed_long_prompts():
    """A windowed request whose prompt extends past the window must NOT be
    indexed (its leading pages are about to be recycled) — refused cleanly
    with a counter; prompts inside the window still insert."""
    m = _model(seed=7, num_kv_heads=2, attn_window=16)
    rng = np.random.RandomState(13)
    long_p = rng.randint(0, 512, (24,)).astype("int32")    # 24 > 16
    short_p = rng.randint(0, 512, (16,)).astype("int32")   # 16 <= 16
    eng = ServingEngine(m, max_slots=2, page_size=8, use_paged_kernel=False)
    eng.add_request(long_p, 4)
    eng.run()
    assert eng.pool.prefix.window_refusals == 1
    assert len(eng.pool.prefix) == 0
    eng.add_request(short_p, 4)
    eng.run()
    assert eng.pool.prefix.window_refusals == 1
    assert len(eng.pool.prefix) == 2           # two full in-window pages
    # the counter survives a tree snapshot round-trip
    clone = PrefixIndex.from_state(eng.pool.prefix.to_state())
    assert clone.window_refusals == 1


# ---------------------------------------------------------------------------
# snapshot v5: pool layout travels with the capture
# ---------------------------------------------------------------------------


def test_snapshot_v5_roundtrip_gqa_window_int4():
    m = _model(seed=5, num_kv_heads=2, attn_window=24)
    rng = np.random.RandomState(21)
    prompts = _prompts(rng, (13, 9))
    refs = _dense(m, prompts, 12, kv_bits=4)
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=4,
                        use_paged_kernel=False)
    rids = [eng.add_request(p, 12) for p in prompts]
    for _ in range(4):
        eng.step()
    snap = snapshot_engine(eng)
    assert snap["version"] == 5
    assert snap["kv_layout"] == {"kv_heads": 2, "page_dtype": "int8",
                                 "kv_bits": 4, "window": 24,
                                 "page_size": 8, "head_dim": 16}
    out_a = eng.run()
    eng2 = restore_engine(_model(seed=5, num_kv_heads=2, attn_window=24),
                          snap)
    out_b = eng2.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out_a[rid].tokens, refs[i])
        np.testing.assert_array_equal(out_b[rid].tokens, refs[i])


def test_snapshot_v5_layout_mismatch_rejected():
    m = _model(seed=5, num_kv_heads=2, attn_window=24)
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=4,
                        use_paged_kernel=False)
    eng.add_request(np.arange(5, dtype="int32"), 3)
    eng.run()
    snap = snapshot_engine(eng)
    with pytest.raises(ValueError, match="KV layout"):
        restore_engine(m, snap, kv_bits=8)
    with pytest.raises(ValueError, match="KV layout"):
        restore_engine(m, snap, attn_window=32)
    # unchanged knobs restore fine
    restore_engine(m, snap)


# ---------------------------------------------------------------------------
# capacity observables
# ---------------------------------------------------------------------------


def test_engine_kv_capacity_gauges():
    """The registry carries the capacity denominators every serving bench
    embeds in BENCH json: kv_bytes_per_token and pages_per_slot_p50."""
    m = _model(seed=8, num_kv_heads=2)
    eng = ServingEngine(m, max_slots=2, page_size=8, kv_bits=4,
                        use_paged_kernel=False)
    eng.attach_metrics()
    rng = np.random.RandomState(15)
    eng.add_request(rng.randint(0, 512, (9,)).astype("int32"), 6)
    eng.run()
    s = eng.metrics.scalars()
    assert s["serving_kv_bytes_per_token"] == eng.pool.bytes_per_token()
    assert s["serving_kv_bytes_per_token"] == 48        # 1L x 2H x int4+scale
    assert "serving_pages_per_slot_p50" in s
