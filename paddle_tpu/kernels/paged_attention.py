"""Pallas TPU paged-attention decode kernel (single-query, block-table KV).

Role parity: vLLM's PagedAttention decode kernel (SOSP '23) over the
serving engine's page-pool KV cache (``serving/kv_pool.py``) — the
continuous-batching answer to the reference inference engine's fused
decode attention (``operators/fused/fused_multi_transformer_op.cu``).

Decode attention is a (B, H, 1, S) matvec against the cache, i.e. pure
HBM bandwidth; with a PAGED cache the valid positions of a sequence live
scattered across pool pages, so the kernel must gather them through the
slot's block table.  Design (pallas_guide.md):

  * grid = (slots, pages-per-slot); the block table and per-slot lengths
    ride in as SCALAR-PREFETCH args (``pltpu.PrefetchScalarGridSpec``) so
    the K/V page picked by grid step (b, p) is ``block_table[b, p]`` —
    the gather happens in the BlockSpec index_map, i.e. it IS the DMA
    schedule, no materialized gather in HBM;
  * one program holds one (H, page_size, D) K page + V page in VMEM and
    runs the flash online-softmax recurrence (m/l/acc scratch carried
    across the sequential page axis), masking positions >= the slot's
    length — pages past the end contribute nothing, and the pool's
    reserved null page (page 0) is never read unmasked;
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


def _page_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page_size, scale, window=None, n_kv=None):
    """The ONE online-softmax page step shared by the float/int8/int4
    kernel entries (only how k/v are materialized in VMEM differs): init
    scratch on the first page, score + length-mask this page (plus the
    sliding-window lower bound when ``window`` is set), fold it into the
    m/l/acc flash recurrence, divide out on the last page.  Under GQA
    (``n_kv`` < q's head count) the query heads regroup over the shared
    K/V head with leading-dim reshapes — K/V stay at ``n_kv`` heads in
    VMEM, never repeated."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
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
    keep = pos < len_ref[b]
    if window is not None:
        keep = keep & (pos >= len_ref[b] - jnp.int32(window))
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

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size, scale, window=None,
                  n_kv=None):
    k = k_ref[0].astype(jnp.float32)                       # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32)
    _page_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page_size, scale, window=window, n_kv=n_kv)


# the int8 entry has its own arity (scale refs) but the same recurrence
def _paged_kernel_int8(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, page_size, scale,
                       window=None, n_kv=None):
    # dequant fused right after the page DMA: int8 values * fp32
    # per-(head, position) scale, in VMEM
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]           # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]
    _page_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page_size, scale, window=window, n_kv=n_kv)


# the int4 entry: packed nibble pages, unpack + dequant fused after the DMA
def _paged_kernel_int4(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, page_size, scale,
                       window=None, n_kv=None):
    k = _unpack4_vmem(k_ref[0]) * ks_ref[0]                # (Hkv, ps, D)
    v = _unpack4_vmem(v_ref[0]) * vs_ref[0]
    _page_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                     page_size, scale, window=window, n_kv=n_kv)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    k_scales=None, v_scales=None, scale=None,
                    interpret: bool | None = None, window=None):
    """Single-query decode attention through a paged KV pool.

    ``q`` (B, H, D) float; ``k_pages``/``v_pages`` (P, Hkv, page_size, D)
    float (Hkv a divisor of H — GQA regroups query heads in VMEM, the
    pages never repeat) — or int8 with ``k_scales``/``v_scales``
    (P, Hkv, page_size, 1) fp32, or PACKED int4 (last dim D // 2, two
    nibbles per byte — detected from the shape) with the same scales
    layout; ``block_tables`` (B, max_pages) int32 page ids (padding
    entries must reference a valid page — the pool's null page 0);
    ``lengths`` (B,) int32 valid-position counts.  ``window`` masks
    positions below ``lengths - window`` (sliding-window attention — the
    engine's recycled ring pages point at the null page and fall under
    this bound).  Returns (B, H, D) in q.dtype.  Callers gate on
    :func:`available`/:func:`supported` first.
    """
    b, h, d = q.shape
    _, hkv, ps, d_store = k_pages.shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    scale = np.float32(scale)
    if interpret is None:
        interpret = not _backend_is_tpu()
    win = None if window is None else int(window)
    nkv = None if hkv == h else hkv
    quant = k_scales is not None
    int4 = quant and d_store != d

    q_spec = pl.BlockSpec((1, h, d), lambda b, p, bt, ln: (b, 0, 0))
    pg_spec = pl.BlockSpec((1, hkv, ps, d_store),
                           lambda b, p, bt, ln: (bt[b, p], 0, 0, 0))
    sc_spec = pl.BlockSpec((1, hkv, ps, 1),
                           lambda b, p, bt, ln: (bt[b, p], 0, 0, 0))
    if quant:
        kern = _paged_kernel_int4 if int4 else _paged_kernel_int8
        kernel = functools.partial(kern, page_size=ps, scale=scale,
                                   window=win, n_kv=nkv)
        in_specs = [q_spec, pg_spec, sc_spec, pg_spec, sc_spec]
        args = (q, k_pages, k_scales, v_pages, v_scales)
    else:
        kernel = functools.partial(_paged_kernel, page_size=ps, scale=scale,
                                   window=win, n_kv=nkv)
        in_specs = [q_spec, pg_spec, pg_spec]
        args = (q, k_pages, v_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),   # running max
                        pltpu.VMEM((h, 128), jnp.float32),   # running denom
                        pltpu.VMEM((h, d), jnp.float32)],    # weighted acc
    )
    with _x64_off():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
            interpret=interpret,
            name="paged_attention",
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)


def _mq_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                   page_size, scale, t, window=None, n_kv=None):
    """The online-softmax page step of the MULTI-query (speculative
    verify) kernel: q_tile rows per slot, row i at global position
    ``lengths[b] + i``, causally visible to page position j iff
    ``j <= lengths[b] + i`` — the paged_prefill causal rule with the
    slot's length as the chunk start, batched over slots like the decode
    kernel; ``window`` adds the sliding-window lower bound
    ``j > lengths[b] + i - window``.  GQA (``n_kv``) regroups query heads
    over the shared K/V head with leading-dim reshapes, like the decode
    recurrence.  Shared by the float/int8/int4 entries (only how k/v
    materialize in VMEM differs)."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
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
    qpos = len_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (1, t, 1), 1)
    keep = pos <= qpos
    if window is not None:
        keep = keep & (pos > qpos - jnp.int32(window))
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

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...] / l_ref[...][:, :, None]        # (H, T, D)
        o_ref[0] = jnp.einsum("htd->thd", out).astype(o_ref.dtype)


def _mq_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, page_size, scale, t, window=None,
               n_kv=None):
    k = k_ref[0].astype(jnp.float32)                       # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32)
    _mq_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                   page_size, scale, t, window=window, n_kv=n_kv)


# the int8 entry has its own arity (scale refs) but the same recurrence
def _mq_kernel_int8(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                    o_ref, m_ref, l_ref, acc_ref, *, page_size, scale, t,
                    window=None, n_kv=None):
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]           # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]
    _mq_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                   page_size, scale, t, window=window, n_kv=n_kv)


# the int4 entry: packed nibble pages, unpack + dequant fused after the DMA
def _mq_kernel_int4(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                    o_ref, m_ref, l_ref, acc_ref, *, page_size, scale, t,
                    window=None, n_kv=None):
    k = _unpack4_vmem(k_ref[0]) * ks_ref[0]                # (Hkv, ps, D)
    v = _unpack4_vmem(v_ref[0]) * vs_ref[0]
    _mq_recurrence(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                   page_size, scale, t, window=window, n_kv=n_kv)


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
    earlier rows, causally.  Other operands as :func:`paged_attention`.
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
    _, hkv, ps, d_store = k_pages.shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    scale = np.float32(scale)
    if interpret is None:
        interpret = not _backend_is_tpu()
    win = None if window is None else int(window)
    nkv = None if hkv == h else hkv
    quant = k_scales is not None
    int4 = quant and d_store != d

    tp = _pad_q_tile(t)
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))

    q_spec = pl.BlockSpec((1, tp, h, d), lambda b, p, bt, ln: (b, 0, 0, 0))
    pg_spec = pl.BlockSpec((1, hkv, ps, d_store),
                           lambda b, p, bt, ln: (bt[b, p], 0, 0, 0))
    sc_spec = pl.BlockSpec((1, hkv, ps, 1),
                           lambda b, p, bt, ln: (bt[b, p], 0, 0, 0))
    if quant:
        kern = _mq_kernel_int4 if int4 else _mq_kernel_int8
        kernel = functools.partial(kern, page_size=ps, scale=scale, t=tp,
                                   window=win, n_kv=nkv)
        in_specs = [q_spec, pg_spec, sc_spec, pg_spec, sc_spec]
        args = (q, k_pages, k_scales, v_pages, v_scales)
    else:
        kernel = functools.partial(_mq_kernel, page_size=ps, scale=scale,
                                   t=tp, window=win, n_kv=nkv)
        in_specs = [q_spec, pg_spec, pg_spec]
        args = (q, k_pages, v_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tp, h, d),
                               lambda b, p, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, tp), jnp.float32),    # running max
                        pltpu.VMEM((h, tp), jnp.float32),    # running denom
                        pltpu.VMEM((h, tp, d), jnp.float32)],  # weighted acc
    )
    with _x64_off():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, tp, h, d), q.dtype),
            interpret=interpret,
            name="paged_attention_mq",
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
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
    keep = pos < lengths[:, None]
    if window is not None:
        keep = keep & (pos >= lengths[:, None] - window)
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
    qpos = lengths[:, None, None] + jnp.arange(t, dtype=jnp.int32)[None, :,
                                                                   None]
    keep = pos <= qpos
    if window is not None:
        keep = keep & (pos > qpos - window)
    bmask = keep[:, None, None] if grouped else keep[:, None]
    s = jnp.where(bmask, s, _NEG_INF)
    att = jax.nn.softmax(s, axis=-1).astype(v_eff.dtype)
    if grouped:
        out = jnp.einsum("bngts,bnsd->btngd", att, v_eff) \
            .reshape(b, t, h, v_eff.shape[-1])
    else:
        out = jnp.einsum("bhts,bhsd->bthd", att, v_eff)
    return out.astype(q.dtype)
