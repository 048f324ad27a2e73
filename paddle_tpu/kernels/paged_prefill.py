"""Pallas TPU paged-prefill attention kernel (multi-query chunk, block-table KV).

The chunked-prefill half of the serving engine (Sarathi-Serve, OSDI '24):
where ``paged_attention.py`` answers "one new token per slot against its
pages", this kernel answers "a CHUNK of a prompt's tokens against the
pages already written — cached prefix pages, earlier chunks, and the
chunk itself".  The engine writes the chunk's K/V into the slot's pages
FIRST, so self-attention within the chunk arrives through the same page
gather as the history and the kernel needs no separate in-chunk path.

Design (pallas_guide.md, same skeleton as the decode kernel):

  * the grid is the chunk's LIVE PAGES and nothing else: the logical pages
    ``[lo, hi)`` some row of the chunk sees, ``paged_attention.live_pages(
    start + 1, rows=chunk)`` under the layer's window — the range the
    decode kernels, the masks and the engine's counters share.  ``hi - lo``
    is the grid's traced bound on the page axis; the slot's block table and
    ``(start, lo, hi)`` ride in as SCALAR-PREFETCH args, so the K/V page of
    grid step p is ``block_table[lo + p]`` — the gather IS the BlockSpec
    index_map, i.e. the DMA schedule.  A chunk therefore costs the context
    it attends: no grid step, DMA or score for a table entry past the
    chunk's end or under its window, whatever the table's width (such an
    entry may name any page);
  * under MHA the whole (chunk, H, D) query block sits in VMEM across the
    page grid.  Under GQA the grid gains a leading axis over KV heads and a
    block is one KV head's group, (chunk, H / Hkv, D), against that head's
    (page_size, D) rows of the page (128 query heads of 128 would not fit
    whole: 17 MB), the group padded with zero query heads to a multiple of
    8 where it is not one (20 heads over 4: 5 -> 8; the padding's output is
    dropped): a regrouping of the whole block's scores over KV heads does
    not lower for the chip at any group.  :func:`block_heads` decides from
    the shapes.  Each
    live page folds into a flash online-softmax recurrence with per-query
    m/l/acc scratch, initialised on page ``lo`` and divided out on page
    ``hi - 1``.  Inside a live page CAUSALITY (and the window's lower
    bound) is the mask: page position j is visible to chunk row i iff
    ``j <= start + i`` — a row of the prompt sees its own position, which
    lies in a live page, so none is ever fully masked;
  * int8 pages carry fp32 per-(position, head) scales dequantized in
    VMEM right after the page DMA — the identical layout/decision as the
    decode kernel and the dense int8 KV cache;
  * ``interpret=True`` runs the identical body through the Pallas
    interpreter and :func:`paged_prefill_ref` is the jnp oracle making
    the same masking/dequant decisions — the parity contract
    tests/test_serving.py asserts, which is what keeps chunked paged
    prefill bit-comparable to the dense decoder's monolithic prefill.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _backend_is_tpu, _x64_off
from .paged_attention import _unpack4_vmem, gather_pages, live_pages

_NEG_INF = -1e30


def available() -> bool:
    """Dispatch gate: True when the running backend executes Mosaic/Pallas
    TPU kernels (tests monkeypatch this to force the kernel in interpret
    mode)."""
    return _backend_is_tpu()


_VMEM_BUDGET = 8 * 1024 * 1024      # of the core's 16 MB


def block_heads(n_heads: int, page_size: int, head_dim: int, chunk: int,
                n_kv_heads: int | None = None) -> int | None:
    """Query heads in one block of the kernel, or None where no block
    fits.  All ``n_heads`` under MHA: q + acc (chunk, H, D) and the K/V
    pages (H, ps, D) have to fit the VMEM budget.  One KV head's group
    under GQA, ``H / Hkv`` rounded up to a multiple of 8 (the grid then
    also runs over KV heads, and :func:`paged_prefill` pads the group)."""
    nkv = n_kv_heads or n_heads
    heads, kv = (n_heads, nkv) if nkv == n_heads \
        else (-(-(n_heads // nkv) // 8) * 8, 1)
    vmem = 4 * (2 * chunk * heads * head_dim + 2 * kv * page_size * head_dim)
    return heads if vmem < _VMEM_BUDGET else None


def supported(n_heads: int, page_size: int, head_dim: int, chunk: int,
              n_kv_heads: int | None = None,
              kv_bits: int | None = None) -> bool:
    """Shape gate for the fused kernel: lane-aligned head_dim (stored
    width for int4 pages), a sublane-aligned page and chunk, a query
    head count that divides evenly over the KV heads, and a block
    (:func:`block_heads`) that fits.  Ragged shapes take the jnp
    reference path instead of failing at lowering."""
    nkv = n_kv_heads or n_heads
    if n_heads % nkv != 0:
        return False
    lane_d = head_dim // 2 if kv_bits == 4 else head_dim
    if lane_d % 128 != 0 or page_size % 32 != 0 or chunk % 8 != 0:
        return False
    return block_heads(n_heads, page_size, head_dim, chunk, nkv) is not None


def _group_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                      page_size, scale, window=None):
    """The page step of the grid over (KV heads, live pages): the block is
    one KV head's group of query heads, ``q_ref`` (C, g, D) against that
    head's ``k``/``v`` (1, ps, D).  Rows and heads merge into one matmul
    dimension of C * g (row-major, so row r is chunk row ``r // g``): two
    plain 2-D products a page, no regrouping of the scores.  Same walk,
    same mask, same recurrence and the same division at the end as
    :func:`_chunk_recurrence`; m and l are kept lane-broadcast like the
    decode kernel's."""
    start, lo, hi = walk_ref[0], walk_ref[1], walk_ref[2]
    p = lo + pl.program_id(1)              # this step's logical page

    @pl.when(p == lo)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c, g, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32).reshape(c * g, d)
    s = jax.lax.dot_general(q, k[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = p * jnp.int32(page_size) + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    qpos = start + jax.lax.broadcasted_iota(
        jnp.int32, (c * g, 1), 0) // jnp.int32(g)
    keep = pos <= qpos
    if window is not None:
        keep = keep & (pos > qpos - window)
    s = jnp.where(keep, s, jnp.float32(_NEG_INF))          # (C * g, ps)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    l_new = l_ref[:, :1] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pexp, v[0], preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == hi - 1)
    def _finish():
        out = acc_ref[...] / l_ref[:, :1]
        o_ref[...] = out.reshape(c, g, v.shape[-1]).astype(o_ref.dtype)


def _chunk_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                      page_size, scale, chunk, window=None, page_axis=0):
    """The ONE online-softmax page step shared by the float/int8/int4
    entries (only how k/v materialize in VMEM differs).  ``walk_ref``
    holds ``(start, lo, hi)``: grid step ``p`` of the page axis is logical
    page ``lo + p`` of the chunk's live range.  Init scratch on the first
    live page, score + causal-mask this page against every chunk row
    (sliding window drops keys more than ``window`` behind each row), fold
    into the m/l/acc flash recurrence, divide out on the last live page.
    ``page_axis`` is the grid axis that runs over pages: 0 (MHA, all heads
    in the block), or 1 under a leading axis over KV heads (GQA:
    :func:`_group_recurrence` then does the step)."""
    if page_axis:
        return _group_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref,
                                 acc_ref, page_size, scale, window=window)
    start, lo, hi = walk_ref[0], walk_ref[1], walk_ref[2]
    p = lo + pl.program_id(0)              # this step's logical page

    @pl.when(p == lo)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                     # (C, H, D)
    s = jnp.einsum("chd,hsd->hcs", q, k,
                   preferred_element_type=jnp.float32) * scale  # (H, C, ps)
    pos = p * jnp.int32(page_size) + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    qpos = start + jax.lax.broadcasted_iota(
        jnp.int32, (1, chunk, 1), 1)
    keep = pos <= qpos
    if window is not None:
        keep = keep & (pos > qpos - window)
    s = jnp.where(keep, s, jnp.float32(_NEG_INF))

    m_prev = m_ref[...]                                    # (H, C)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[:, :, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=2)
    upd = jnp.einsum("hcs,hsd->hcd", pexp, v,
                     preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, :, None] + upd
    m_ref[...] = m_new

    @pl.when(p == hi - 1)
    def _finish():
        out = acc_ref[...] / l_ref[...][:, :, None]        # (H, C, D)
        o_ref[...] = jnp.einsum("hcd->chd", out).astype(o_ref.dtype)


def _prefill_kernel(bt_ref, walk_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, page_size, scale, chunk,
                    window=None, page_axis=0):
    k = k_ref[0].astype(jnp.float32)                       # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32)
    _chunk_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                      page_size, scale, chunk, window=window,
                      page_axis=page_axis)


# the int8 entry has its own arity (scale refs) but the same recurrence
def _prefill_kernel_int8(bt_ref, walk_ref, q_ref, k_ref, ks_ref, v_ref,
                         vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         page_size, scale, chunk, window=None, page_axis=0):
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]           # (Hkv, ps, D)
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]
    _chunk_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                      page_size, scale, chunk, window=window,
                      page_axis=page_axis)


# int4 pages arrive nibble-packed (D//2 bytes per position); the unpack
# happens in VMEM right after the page DMA — same decision as decode
def _prefill_kernel_int4(bt_ref, walk_ref, q_ref, k_ref, ks_ref, v_ref,
                         vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         page_size, scale, chunk, window=None, page_axis=0):
    k = _unpack4_vmem(k_ref[0]) * ks_ref[0]                # (Hkv, ps, D)
    v = _unpack4_vmem(v_ref[0]) * vs_ref[0]
    _chunk_recurrence(walk_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                      page_size, scale, chunk, window=window,
                      page_axis=page_axis)


def paged_prefill(q, k_pages, v_pages, block_table, start, *,
                  k_scales=None, v_scales=None, scale=None, window=None,
                  interpret: bool | None = None):
    """Chunk attention through a paged KV pool.

    ``q`` (C, H, D) float — the chunk's queries, row i at global position
    ``start + i``; ``k_pages``/``v_pages`` (P, Hkv, page_size, D) float —
    Hkv may divide H (GQA) — or int8 with ``k_scales``/``v_scales``
    (P, Hkv, page_size, 1) fp32, or nibble-packed int4 (last dim D//2)
    with the same scale layout; ``block_table`` (max_pages,) int32 page
    ids for THIS slot — every entry must name a valid page (padding is the
    pool's null page 0), but only those of ``live_pages(start + 1, rows=C)``
    are read; ``start`` scalar int32 positions already valid
    before the chunk; ``window`` optional sliding-window width — row i
    sees positions ``(start + i - window, start + i]``.  The chunk's own
    K/V must ALREADY be written into the pages.  Returns (C, H, D) in
    q.dtype.  Callers gate on :func:`available` / :func:`supported`
    first.
    """
    c, h, d = q.shape
    _, hkv, ps, d_store = k_pages.shape
    max_pages = block_table.shape[0]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    scale = np.float32(scale)
    group = h // hkv
    if hkv != h and group % 8:
        # a group that is not sublane-aligned: zero query heads fill it up
        # (their rows attend like any other and are dropped)
        full = -(-group // 8) * 8
        qp = jnp.pad(q.reshape(c, hkv, group, d),
                     ((0, 0), (0, 0), (0, full - group), (0, 0)))
        out = paged_prefill(
            qp.reshape(c, hkv * full, d), k_pages, v_pages, block_table,
            start, k_scales=k_scales, v_scales=v_scales, scale=scale,
            window=window, interpret=interpret)
        return out.reshape(c, hkv, full, d)[:, :, :group].reshape(c, h, d)
    if interpret is None:
        interpret = not _backend_is_tpu()
    quant = k_scales is not None
    int4 = quant and d_store != d
    win = None if window is None else int(window)

    # the walk: the logical pages some row of the chunk sees, [lo, hi) of
    # the table's entries, is the page axis of the grid (a traced bound);
    # step p reads entry lo + p.  Clamped: the pipeline reads the NEXT
    # step's ids at the last step too
    start = jnp.asarray(start, jnp.int32).reshape(())
    lo, hi = live_pages(start + 1, ps, win, rows=c, max_pages=max_pages)

    def entry(p, bt, walk):
        return bt[jnp.minimum(walk[1] + p, max_pages - 1)]

    if hkv == h:
        # the whole chunk's heads at once; the grid runs over live pages
        hb, grid, page_axis = h, (hi - lo,), 0
        kvb = hkv

        def at(page):
            return lambda p, bt, walk: ((entry(p, bt, walk), 0, 0, 0)
                                        if page else (0, 0, 0))
    else:
        # one KV head's group at a time: grid (KV heads, live pages)
        hb, grid, page_axis = group, (hkv, hi - lo), 1
        kvb = 1

        def at(page):
            return lambda n, p, bt, walk: ((entry(p, bt, walk), n, 0, 0)
                                           if page else (0, n, 0))

    q_spec = pl.BlockSpec((c, hb, d), at(False))
    pg_spec = pl.BlockSpec((1, kvb, ps, d_store), at(True))
    sc_spec = pl.BlockSpec((1, kvb, ps, 1), at(True))
    if quant:
        body = _prefill_kernel_int4 if int4 else _prefill_kernel_int8
        in_specs = [q_spec, pg_spec, sc_spec, pg_spec, sc_spec]
        args = (q, k_pages, k_scales, v_pages, v_scales)
    else:
        body = _prefill_kernel
        in_specs = [q_spec, pg_spec, pg_spec]
        args = (q, k_pages, v_pages)
    kernel = functools.partial(body, page_size=ps, scale=scale, chunk=c,
                               window=win, page_axis=page_axis)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((c, hb, d), at(False)),
        # running max, running denominator, weighted accumulator
        scratch_shapes=([pltpu.VMEM((c * hb, 128), jnp.float32)] * 2
                        + [pltpu.VMEM((c * hb, d), jnp.float32)]
                        if page_axis else
                        [pltpu.VMEM((h, c), jnp.float32)] * 2
                        + [pltpu.VMEM((h, c, d), jnp.float32)]),
    )
    with _x64_off():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((c, h, d), q.dtype),
            interpret=interpret,
            name="paged_prefill",
        )(block_table.astype(jnp.int32), jnp.stack([start, lo, hi]), *args)


def paged_prefill_ref(q, k_pages, v_pages, block_table, start, *,
                      k_scales=None, v_scales=None, scale=None,
                      window=None):
    """jnp reference path: gathers this slot's pages dense and runs the
    EXACT einsum/mask/softmax sequence of the dense prefill
    (models/generation._block_fwd) with the same causal rule
    ``page_pos <= start + row`` (and window lower bound) and the same
    GQA grouping / dequant decisions, so a chunked paged prefill is
    bit-comparable to the monolithic dense prefill — the CPU fallback and
    the kernel's parity oracle."""
    c, h, d = q.shape
    ps = k_pages.shape[2]
    hkv = k_pages.shape[1]
    s_max = block_table.shape[0] * ps
    k_eff = gather_pages(k_pages, block_table[None], k_scales,
                         head_dim=d)[0]                    # (Hkv, S, D)
    v_eff = gather_pages(v_pages, block_table[None], v_scales,
                         head_dim=d)[0]
    if h == hkv:
        s = jnp.einsum("chd,hsd->hcs", q, k_eff,
                       preferred_element_type=jnp.float32)
        grouped = False
    else:
        qg = q.reshape(c, hkv, h // hkv, d)
        s = jnp.einsum("cngd,nsd->ngcs", qg, k_eff,
                       preferred_element_type=jnp.float32)
        grouped = True
    if scale is None:
        # divide, exactly as the dense decoder scales its scores — keeps
        # the two prefill substrates bit-comparable, not just close
        s = s / np.sqrt(d).astype(np.float32)
    else:
        s = s * jnp.float32(scale)
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, None, :]
    qpos = start + jnp.arange(c, dtype=jnp.int32)[None, :, None]
    keep = pos <= qpos
    if window is not None:
        keep = keep & (pos > qpos - window)
    s = jnp.where(keep[None] if grouped else keep, s, _NEG_INF)
    att = jax.nn.softmax(s, axis=-1).astype(v_eff.dtype)
    if grouped:
        out = jnp.einsum("ngcs,nsd->cngd", att, v_eff) \
            .reshape(c, h, v_eff.shape[-1])
    else:
        out = jnp.einsum("hcs,hsd->chd", att, v_eff)
    return out.astype(q.dtype)
