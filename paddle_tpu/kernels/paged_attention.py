"""Pallas TPU paged-attention decode kernel (single-query, block-table KV).

Role parity: vLLM's PagedAttention decode kernel (SOSP '23) over the
serving engine's page-pool KV cache (``serving/kv_pool.py``) — the
continuous-batching answer to the reference inference engine's fused
decode attention (``operators/fused/fused_multi_transformer_op.cu``).

Decode attention is a (B, H, 1, S) matvec against the cache, i.e. pure
HBM bandwidth; with a PAGED cache the valid positions of a sequence live
scattered across pool pages, so the kernel must gather them through the
slot's block table.  Design (pallas_guide.md):

  * the grid is the LIVE WORK and nothing else: one axis over the
    (slot, logical page) pairs some query row of the slot can see, in slot
    order.  :func:`live_pages` is the one definition of that range (the
    pages of the visible positions ``[max(0, len - window), len)``);
    :func:`_page_walk` lists the pairs from ``lengths`` with ``jnp`` inside
    the program, and the list, its count (the traced grid bound), the
    block table and the lengths ride in as SCALAR-PREFETCH args
    (``pltpu.PrefetchScalarGridSpec``).  The K/V page of step ``w`` is
    ``block_table[slot[w], page[w]]`` — the gather happens in the BlockSpec
    index_map, i.e. it IS the DMA schedule, no materialized gather in HBM
    — and the output block is ``slot[w]``'s.  A decode therefore costs the
    context it attends: no grid step, DMA or score for a table entry past
    a slot's length or under its window, one step for a lane with nothing
    to attend (``lengths >= 1`` is the contract; the engine passes
    ``lengths + 1``);
  * one program holds one (H, page_size, D) K page + V page in VMEM and
    runs the flash online-softmax recurrence (m/l/acc scratch initialised
    on a slot's first pair and divided out on its last), masking the
    in-page positions outside the visible range — the pool's reserved
    null page (page 0) is never read unmasked, and a page outside the
    live range is never read at all (it may hold anything);
  * int8 pages (serving with ``int8=True``) carry fp32 per-position
    scales; the dequant multiply happens in VMEM right after the page
    DMA, fused into the attention compute — HBM streams int8 values +
    one fp32 scalar per (page-position, head), exactly the layout the
    dense int8 KV cache uses (models/generation.py), so the quantization
    decisions carry over unchanged;
  * ``interpret=True`` runs the identical body through the Pallas
    interpreter (flash.py convention) and :func:`paged_attention_ref`
    is the jnp oracle making the same masking/dequant decisions — the
    parity contract tests/test_serving.py asserts.

Speculative verify (r13): :func:`paged_attention_mq` scores a q_tile > 1
block of draft positions per slot in one pass — each row attends to the
block-table pages AND causally to the block's earlier rows (mask
``page_pos <= lengths[b] + row``, the paged_prefill causal rule batched
over slots).  q_tile == 1 dispatches to the single-query kernel above,
so the r08 decode path stays the one lowering for that case.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _backend_is_tpu, _x64_off

_NEG_INF = -1e30


def available() -> bool:
    """Dispatch gate: True when the running backend executes Mosaic/Pallas
    TPU kernels (tests monkeypatch this to force the kernel in interpret
    mode)."""
    return _backend_is_tpu()


def supported(n_heads: int, page_size: int, head_dim: int,
              n_kv_heads: int | None = None,
              kv_bits: int | None = None) -> bool:
    """Shape gate for the fused kernel: lane-aligned head_dim and a
    sublane-aligned page (the int8 tile is (32, 128); bf16 is (16, 128)).
    GQA needs the group to divide evenly; int4 pages DMA a packed
    ``head_dim // 2`` lane dim, which must itself be lane-aligned.
    Ragged shapes take the jnp reference path instead of failing at
    lowering."""
    nkv = n_kv_heads or n_heads
    if n_heads % nkv != 0:
        return False
    lane_d = head_dim // 2 if kv_bits == 4 else head_dim
    if lane_d % 128 != 0:
        return False
    if page_size % 32 != 0:
        return False
    # VMEM: q (H, D) + K/V pages (Hkv, ps, D) + scratch; tiny vs 16MB/core
    return (n_heads * head_dim + 2 * nkv * page_size * head_dim) * 4 \
        < 8 * 1024 * 1024


def _pad_q_tile(q_tile: int) -> int:
    """Sublane-align the verify block's query rows (pad rows are computed
    and discarded; their outputs are garbage but finite — position 0 is
    visible to every row, so no row's softmax ever empties)."""
    return max(8, -(-q_tile // 8) * 8)


def supported_mq(n_heads: int, page_size: int, head_dim: int,
                 q_tile: int, n_kv_heads: int | None = None,
                 kv_bits: int | None = None) -> bool:
    """Shape gate for the multi-query verify kernel — the decode gate
    plus the padded query block's VMEM footprint (same arithmetic as
    paged_prefill.supported with chunk = padded q_tile)."""
    nkv = n_kv_heads or n_heads
    if n_heads % nkv != 0:
        return False
    lane_d = head_dim // 2 if kv_bits == 4 else head_dim
    if lane_d % 128 != 0 or page_size % 32 != 0:
        return False
    tp = _pad_q_tile(q_tile)
    vmem = 4 * (2 * tp * n_heads * head_dim
                + 2 * nkv * page_size * head_dim)
    return vmem < 8 * 1024 * 1024


def _unpack4_vmem(pk):
    """In-VMEM int4 nibble unpack: the packed (.., ps, D/2) int8 page block
    -> (.., ps, D) fp32, calling the ONE pack/unpack definition
    (ops/quant_ops.unpack_int4) so the paged dequant cannot fork from the
    dense cache's."""
    from ..ops.quant_ops import unpack_int4

    return unpack_int4(pk).astype(jnp.float32)


def visible_positions(lengths, window=None, rows=1):
    """Positions ``[lo, hi)`` visible to a block of ``rows`` causal query
    rows whose FIRST row sees ``lengths`` positions, its own included (a
    decode is one row): row ``i`` sees ``[max(0, lengths + i - window),
    lengths + i)``, and this is their union.  THE definition of what paged
    decode attends — the kernels' masks, the reference's mask, the pages
    the kernels walk (:func:`live_pages`) and the engine's
    ``decode_pages_walked`` counter all call it.  Plain arithmetic on
    numpy values, jax arrays or a kernel's prefetched scalars alike."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    lo = 0 if window is None else xp.maximum(lengths - window, 0)
    return lo, lengths + (rows - 1)


def live_pages(lengths, page_size, window=None, rows=1, max_pages=None):
    """Logical pages ``[lo, hi)`` that hold :func:`visible_positions`: all
    the kernels read of a slot's ``max_pages`` table entries.  Never empty:
    a lane with nothing to attend keeps one page, so it takes one grid
    step and its output is defined."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    lo, hi = visible_positions(lengths, window, rows)
    hi = -(-hi // page_size)
    if max_pages is not None:
        hi = xp.minimum(hi, max_pages)
    hi = xp.maximum(hi, 1)
    return xp.minimum(lo // page_size, hi - 1), hi


def _page_walk(seen, page_size, max_pages, window, rows):
    """The kernels' grid as data: the live (slot, logical page) pairs of
    :func:`live_pages` in slot order — ``slot_of`` and ``page_of``, padded
    with valid ids past the static worst case ``slots x max_pages`` — and
    their count, the grid's traced bound.  Compares and sums only (no
    gather, no sort), one small fusion per distinct window of a program."""
    b = seen.shape[0]
    lo, hi = live_pages(seen, page_size, window, rows, max_pages)
    ends = jnp.cumsum(hi - lo)
    starts = ends - (hi - lo)
    # one entry more than the worst case: the pipeline reads the NEXT
    # step's ids at every step, the last included
    w = jnp.arange(b * max_pages + 1, dtype=jnp.int32)[:, None]
    slot_of = jnp.minimum(jnp.sum(w >= ends, axis=1), b - 1)
    mine = (w >= starts) & (w < ends)                      # (pairs, slots)
    page_of = jnp.sum(jnp.where(mine, w - starts + lo, 0), axis=1)
    return slot_of.astype(jnp.int32), page_of.astype(jnp.int32), ends[-1]


def _page_recurrence(seen, p, first, last, q_ref, k, v, o_ref, m_ref, l_ref,
                     acc_ref, page_size, scale, window=None, n_kv=None):
    """The ONE online-softmax page step of the single-query kernel: init
    scratch on the slot's first live page, score this page and mask it to
    :func:`visible_positions`, fold it into the m/l/acc flash recurrence,
    divide out on the slot's last page.  Under GQA (``n_kv`` < q's head
    count) the query heads regroup over the shared K/V head with
    leading-dim reshapes — K/V stay at ``n_kv`` heads in VMEM, never
    repeated."""
    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (H, D)
    h, d = q.shape
    nkv = n_kv or h
    # one contraction for MHA and GQA: query heads grouped over their K/V
    # head (g == 1 under MHA).  Mosaic has no batched mat-VEC — the flat
    # "hd,hsd->hs" form leaves the lhs without a free dimension and does
    # not lower — so the group axis doubles as the matmul's M dimension.
    g = h // nkv
    s = jnp.einsum("ngd,nsd->ngs", q.reshape(nkv, g, d), k,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(h, page_size)                            # (H, ps)
    base = p * jnp.int32(page_size)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    lo, hi = visible_positions(seen, window)
    keep = pos < hi
    if window is not None:
        keep = keep & (pos >= lo)
    s = jnp.where(keep, s, jnp.float32(_NEG_INF))

    m_prev = m_ref[:, :1]                                  # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    l_new = l_ref[:, :1] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    upd = jnp.einsum("ngs,nsd->ngd", pexp.reshape(nkv, g, page_size), v,
                     preferred_element_type=jnp.float32).reshape(h, d)
    acc_ref[...] = acc_ref[...] * alpha + upd
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _walk_kernel(bt_ref, len_ref, slot_ref, page_ref, q_ref, *refs, fold,
                 kv_bits, rows, max_pages, page_size, window, **fold_kw):
    """The body both kernels share: find this grid step's (slot, logical
    page) and whether it opens or closes the slot's live range, materialize
    the K/V page in VMEM — float pages cast; int8 pages times their fp32
    per-(head, position) scales, the dequant fused right after the page
    DMA; int4 pages nibble-unpacked first — and hand it to ``fold``, the
    single-query or the multi-query recurrence."""
    *kv, o_ref, m_ref, l_ref, acc_ref = refs
    if kv_bits is None:
        k = kv[0][0].astype(jnp.float32)                   # (Hkv, ps, D)
        v = kv[1][0].astype(jnp.float32)
    else:
        k_ref, ks_ref, v_ref, vs_ref = kv
        widen = (_unpack4_vmem if kv_bits == 4
                 else lambda x: x.astype(jnp.float32))
        k = widen(k_ref[0]) * ks_ref[0]
        v = widen(v_ref[0]) * vs_ref[0]
    w = pl.program_id(0)
    b, p = slot_ref[w], page_ref[w]
    seen = len_ref[b]
    lo, hi = live_pages(seen, page_size, window, rows, max_pages)
    fold(seen, p, p == lo, p == hi - 1, q_ref, k, v, o_ref, m_ref, l_ref,
         acc_ref, page_size, window=window, **fold_kw)


def _walk_call(name, fold, q, k_pages, v_pages, block_tables, seen, *,
               k_scales, v_scales, rows, window, scratch_shapes, interpret,
               **fold_kw):
    """One ``pallas_call`` over the live (slot, page) pairs of ``seen``
    (what each slot's first query row sees) — the grid, the scalar
    prefetch and the index maps of both paged decode kernels.  ``q`` is
    (B, ...) and is blocked, like the output, one slot at a time."""
    h, d = q.shape[-2:]
    _, hkv, ps, d_store = k_pages.shape
    max_pages = block_tables.shape[1]
    if interpret is None:
        interpret = not _backend_is_tpu()
    win = None if window is None else int(window)
    kv = (k_pages, v_pages) if k_scales is None else \
        (k_pages, k_scales, v_pages, v_scales)
    kv_bits = None if k_scales is None else (8 if d_store == d else 4)
    seen = seen.astype(jnp.int32)
    slot_of, page_of, n_pairs = _page_walk(seen, ps, max_pages, win, rows)

    def at_slot(w, bt, ln, sl, pg):
        return (sl[w],) + (0,) * (q.ndim - 1)

    def at_page(w, bt, ln, sl, pg):
        return (bt[sl[w], pg[w]], 0, 0, 0)

    q_spec = pl.BlockSpec((1,) + q.shape[1:], at_slot)
    kernel = functools.partial(
        _walk_kernel, fold=fold, kv_bits=kv_bits, rows=rows,
        max_pages=max_pages, page_size=ps, window=win,
        n_kv=None if hkv == h else hkv, **fold_kw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pairs,),
        in_specs=[q_spec] + [pl.BlockSpec((1,) + a.shape[1:], at_page)
                             for a in kv],
        out_specs=q_spec,
        scratch_shapes=scratch_shapes,
    )
    with _x64_off():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret,
            name=name,
        )(block_tables.astype(jnp.int32), seen, slot_of, page_of, q, *kv)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    k_scales=None, v_scales=None, scale=None,
                    interpret: bool | None = None, window=None):
    """Single-query decode attention through a paged KV pool.

    ``q`` (B, H, D) float; ``k_pages``/``v_pages`` (P, Hkv, page_size, D)
    float (Hkv a divisor of H — GQA regroups query heads in VMEM, the
    pages never repeat) — or int8 with ``k_scales``/``v_scales``
    (P, Hkv, page_size, 1) fp32, or PACKED int4 (last dim D // 2, two
    nibbles per byte — detected from the shape) with the same scales
    layout; ``block_tables`` (B, max_pages) int32 page ids — every entry
    must name a valid page, but only those of :func:`live_pages` are read
    (padding is the pool's null page 0; a lane with nothing to attend
    reads its first entry); ``lengths`` (B,) int32 valid-position counts,
    at least 1 (the engine passes ``lengths + 1``: the row just written).
    ``window`` masks positions below ``lengths - window`` (sliding-window
    attention) and the pages wholly under that bound are not walked.
    Returns (B, H, D) in q.dtype.  Callers gate on
    :func:`available`/:func:`supported` first.
    """
    h, d = q.shape[1:]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    return _walk_call(
        "paged_attention", _page_recurrence, q, k_pages, v_pages,
        block_tables, lengths, k_scales=k_scales, v_scales=v_scales,
        rows=1, window=window, interpret=interpret, scale=np.float32(scale),
        scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),   # running max
                        pltpu.VMEM((h, 128), jnp.float32),   # running denom
                        pltpu.VMEM((h, d), jnp.float32)])    # weighted acc


def _mq_recurrence(seen, p, first, last, q_ref, k, v, o_ref, m_ref, l_ref,
                   acc_ref, page_size, scale, t, window=None, n_kv=None):
    """The online-softmax page step of the MULTI-query (speculative
    verify) kernel: ``t`` rows per slot, row i seeing
    ``visible_positions(seen + i)`` — the paged_prefill causal rule with
    the slot's length as the chunk start, batched over slots like the
    decode kernel, with the same sliding-window lower bound.  GQA
    (``n_kv``) regroups query heads over the shared K/V head with
    leading-dim reshapes, like the decode recurrence."""
    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (T, H, D)
    h, d = q.shape[1], q.shape[2]
    nkv = n_kv or h
    if nkv != h:
        g = h // nkv
        qg = q.reshape(t, nkv, g, d)
        s = jnp.einsum("tngd,nsd->ngts", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(h, t, page_size)                     # (H, T, ps)
    else:
        s = jnp.einsum("thd,hsd->hts", q, k,
                       preferred_element_type=jnp.float32) * scale
    pos = p * jnp.int32(page_size) + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    lo, hi = visible_positions(
        seen + jax.lax.broadcasted_iota(jnp.int32, (1, t, 1), 1), window)
    keep = pos < hi
    if window is not None:
        keep = keep & (pos >= lo)
    s = jnp.where(keep, s, jnp.float32(_NEG_INF))

    m_prev = m_ref[...]                                    # (H, T)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[:, :, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=2)
    if nkv != h:
        g = h // nkv
        pg = pexp.reshape(nkv, g, t, page_size)
        upd = jnp.einsum("ngts,nsd->ngtd", pg, v,
                         preferred_element_type=jnp.float32) \
            .reshape(h, t, d)
    else:
        upd = jnp.einsum("hts,hsd->htd", pexp, v,
                         preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, :, None] + upd
    m_ref[...] = m_new

    @pl.when(last)
    def _finish():
        out = acc_ref[...] / l_ref[...][:, :, None]        # (H, T, D)
        o_ref[0] = jnp.einsum("htd->thd", out).astype(o_ref.dtype)


def paged_attention_mq(q, k_pages, v_pages, block_tables, lengths, *,
                       k_scales=None, v_scales=None, scale=None,
                       interpret: bool | None = None, window=None):
    """Multi-query (speculative verify) decode attention through a paged
    KV pool.

    ``q`` (B, T, H, D) float — T = q_tile query rows per slot, row i at
    global position ``lengths[b] + i``; ``lengths`` (B,) int32 counts the
    positions valid BEFORE the block (the block's own K/V must already be
    written into the pages, like paged_prefill).  Row i attends to page
    position j iff ``j <= lengths[b] + i``: the history AND the block's
    earlier rows, causally.  Other operands as :func:`paged_attention`,
    and the same walk: the pages some row of the block sees
    (``live_pages(lengths + 1, rows=T)``), no others.
    Returns (B, T, H, D) in q.dtype.

    T == 1 degenerates exactly to the single-query decode kernel (mask
    ``j <= lengths[b]`` == ``j < lengths[b] + 1``), so this dispatches to
    :func:`paged_attention` — the r08 path stays the one lowering for the
    q_tile=1 case (asserted at the jaxpr level by the parity suite).
    Callers gate on :func:`available`/:func:`supported_mq` first.
    """
    b, t, h, d = q.shape
    if t == 1:
        out = paged_attention(q[:, 0], k_pages, v_pages, block_tables,
                              lengths + 1, k_scales=k_scales,
                              v_scales=v_scales, scale=scale,
                              interpret=interpret, window=window)
        return out[:, None]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    tp = _pad_q_tile(t)
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    out = _walk_call(
        "paged_attention_mq", _mq_recurrence, q, k_pages, v_pages,
        block_tables, lengths + 1, k_scales=k_scales, v_scales=v_scales,
        rows=t, window=window, interpret=interpret, scale=np.float32(scale),
        t=tp,
        scratch_shapes=[pltpu.VMEM((h, tp), jnp.float32),    # running max
                        pltpu.VMEM((h, tp), jnp.float32),    # running denom
                        pltpu.VMEM((h, tp, d), jnp.float32)])  # weighted acc
    return out[:, :t]


def gather_pages(pages, block_tables, scales=None, head_dim=None):
    """Materialize each slot's paged KV as a dense (B, Hkv, S, D) view
    (S = max_pages * page_size): ``pages[block_tables]`` + layout shuffle.
    With quantized ``scales`` the dequant happens here — including the
    int4 nibble unpack when the pages' last dim is narrower than
    ``head_dim`` — making the IDENTICAL dequant decision the fused kernel
    makes in VMEM."""
    p, h, ps, d = pages.shape
    b, max_pages = block_tables.shape
    g = pages[block_tables]                        # (B, max_pages, H, ps, D)
    if scales is not None:
        if head_dim is not None and d != head_dim:
            from ..ops.quant_ops import unpack_int4

            g = unpack_int4(g)
            d = head_dim
        g = g.astype(jnp.float32) * scales[block_tables]
    g = jnp.einsum("bphsd->bhpsd", g)
    return g.reshape(b, h, max_pages * ps, d)


def _group_scores(q, k_eff, eq_grouped, eq_flat):
    """Scores einsum with GQA regrouping: q carries H heads, ``k_eff``
    Hkv <= H; grouped shapes reshape query heads over the shared K/V head
    (never repeating K/V), exactly like the dense decoder."""
    h = q.shape[-2]
    hkv = k_eff.shape[1]
    if h == hkv:
        return jnp.einsum(eq_flat, q, k_eff,
                          preferred_element_type=jnp.float32), False
    g = h // hkv
    if q.ndim == 3:                                # (B, H, D) single query
        qg = q.reshape(q.shape[0], hkv, g, q.shape[-1])
    else:                                          # (B, T, H, D) multi query
        qg = q.reshape(q.shape[0], q.shape[1], hkv, g, q.shape[-1])
    s = jnp.einsum(eq_grouped, qg, k_eff,
                   preferred_element_type=jnp.float32)
    return s, True


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        k_scales=None, v_scales=None, scale=None,
                        window=None):
    """jnp reference path: gathers the pages dense and runs the EXACT
    einsum/mask/softmax sequence of the dense KV-cache decoder
    (models/generation._block_fwd) — including the GQA grouping, the
    sliding-window lower bound, and the int4 unpack — so paged decode is
    bit-comparable to dense decode; the CPU fallback and the kernel's
    parity oracle."""
    b, h, d = q.shape
    ps = k_pages.shape[2]
    hkv = k_pages.shape[1]
    s_max = block_tables.shape[1] * ps
    k_eff = gather_pages(k_pages, block_tables, k_scales, head_dim=d)
    v_eff = gather_pages(v_pages, block_tables, v_scales, head_dim=d)
    s, grouped = _group_scores(q, k_eff, "bngd,bnsd->bngs", "bhd,bhsd->bhs")
    if scale is None:
        # divide, exactly as the dense decoder scales its scores — keeps
        # the two decode substrates bit-comparable, not just close
        s = s / np.sqrt(d).astype(np.float32)
    else:
        s = s * jnp.float32(scale)
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    lo, hi = visible_positions(lengths[:, None], window)
    keep = (pos >= lo) & (pos < hi)
    bmask = keep[:, None, None] if grouped else keep[:, None]
    s = jnp.where(bmask, s, _NEG_INF)
    att = jax.nn.softmax(s, axis=-1).astype(v_eff.dtype)
    if grouped:
        out = jnp.einsum("bngs,bnsd->bngd", att, v_eff) \
            .reshape(b, h, v_eff.shape[-1])
    else:
        out = jnp.einsum("bhs,bhsd->bhd", att, v_eff)
    return out.astype(q.dtype)


def paged_attention_mq_ref(q, k_pages, v_pages, block_tables, lengths, *,
                           k_scales=None, v_scales=None, scale=None,
                           window=None):
    """jnp reference for :func:`paged_attention_mq`: gathers the pages
    dense and applies the same causal rule ``page_pos <= lengths[b] + i``
    (and window lower bound) with the same dequant/grouping decisions —
    the CPU fallback and the multi-query kernel's parity oracle.  T == 1
    dispatches to :func:`paged_attention_ref` (the masks coincide),
    keeping the r08 single-query reference the one definition of that
    case."""
    b, t, h, d = q.shape
    if t == 1:
        out = paged_attention_ref(q[:, 0], k_pages, v_pages, block_tables,
                                  lengths + 1, k_scales=k_scales,
                                  v_scales=v_scales, scale=scale,
                                  window=window)
        return out[:, None]
    ps = k_pages.shape[2]
    s_max = block_tables.shape[1] * ps
    k_eff = gather_pages(k_pages, block_tables, k_scales, head_dim=d)
    v_eff = gather_pages(v_pages, block_tables, v_scales, head_dim=d)
    s, grouped = _group_scores(q, k_eff, "btngd,bnsd->bngts",
                               "bthd,bhsd->bhts")
    if scale is None:
        # divide, exactly as the dense decoder scales its scores — keeps
        # the verify path bit-comparable to dense decode, not just close
        s = s / np.sqrt(d).astype(np.float32)
    else:
        s = s * jnp.float32(scale)
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, None, :]
    # row i sees what a single query with lengths + 1 + i valid positions
    # sees: j <= lengths + i, and under a window j > lengths + i - window
    lo, hi = visible_positions(
        lengths[:, None, None] + 1
        + jnp.arange(t, dtype=jnp.int32)[None, :, None], window)
    keep = (pos >= lo) & (pos < hi)
    bmask = keep[:, None, None] if grouped else keep[:, None]
    s = jnp.where(bmask, s, _NEG_INF)
    att = jax.nn.softmax(s, axis=-1).astype(v_eff.dtype)
    if grouped:
        out = jnp.einsum("bngts,bnsd->btngd", att, v_eff) \
            .reshape(b, t, h, v_eff.shape[-1])
    else:
        out = jnp.einsum("bhts,bhsd->bthd", att, v_eff)
    return out.astype(q.dtype)
