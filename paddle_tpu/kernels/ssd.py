"""Pallas TPU kernels of the Mamba-2 recurrence over the serving engine's
state slab.

The recurrence, one head ``h`` of a slot (``models/ssm.py`` has what
surrounds it)::

    state_t = exp(dt_t A_h) state_{t-1} + dt_t * x_t (outer) B_t   (P, N)
    y_t     = state_t C_t + D_h x_t                                 (P,)

The slab is ``(rows, H, P, N)`` in the state's dtype (``serving/kv_pool.
StateSlab``'s ``(L, slots, H, P, N)``, the layers and slots merged): both
kernels take the WHOLE slab, index it by scalar-prefetched row ids in
their BlockSpec index maps and return it aliased, so a dispatch reads and
writes the rows it advances and no others: no gather, no scatter and no
copy of the slab in HBM.

  * :func:`ssd_chunk_scan` (chunk prefill): one slot, a chunk of ``T`` rows
    (``T`` at most the model's scan chunk, so an engine chunk is one SSD
    chunk).  A grid over (groups, heads of a group); a group's ``C B^T``
    (T, T) is formed once on the MXU and kept in scratch for its heads.
    With ``cum_t = sum_{s <= t} dt_s A`` the chunk is the quadratic form
    ``y_t = sum_{s <= t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s`` plus the
    carried state's ``exp(cum_t) C_t state_0``, and the state after the
    chunk is ``exp(cum_T) state_0 + sum_s exp(cum_T - cum_s) dt_s x_s
    (outer) B_s``.  A padding row has ``dt = 0``: it decays nothing and
    adds nothing, so the state out is the state at the last valid row.
  * :func:`ssm_state_step` (decode): one row for each LIVE lane.  A grid
    over (live lanes, blocks of heads); the live lanes' ids ride in as a
    scalar-prefetch list and their count is the grid's traced bound, so a
    dead lane's state is neither streamed nor changed.  Pure VPU work on
    ``(P, N)`` tiles, bound by the state's bytes.

float32 inside whatever the operands' types.  ``interpret=True`` runs the
same bodies through the Pallas interpreter, and the ``*_ref`` functions are
the jnp paths (the sequential recurrence, a ``lax.scan`` over rows) with
the same signatures: the CPU fallback and the kernels' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _backend_is_tpu, _x64_off

_HIGHEST = lax.Precision.HIGHEST


def available() -> bool:
    """Dispatch gate: True when the running backend executes Mosaic/Pallas
    TPU kernels (tests monkeypatch this to force the kernels in interpret
    mode)."""
    return _backend_is_tpu()


def supported(n_heads: int, head_dim: int, n_groups: int, d_state: int,
              chunk: int) -> bool:
    """Shape gate of both kernels: lane-aligned state and head widths, a
    sublane-aligned chunk, whole groups.  Ragged shapes take the jnp
    paths instead of failing at lowering."""
    return (n_heads % n_groups == 0 and head_dim % 128 == 0
            and d_state % 128 == 0 and chunk % 8 == 0)


def _head_block(heads_per_group: int) -> int:
    """Heads in one block of the step kernel: the largest divisor of a
    group's heads up to 8 (8 x (128, 256) float32 tiles are 1 MiB a side)."""
    return max(b for b in range(1, 9) if heads_per_group % b == 0)


# ---------------------------------------------------------------------------
# the sequential recurrence: both kernels' oracle, and the jnp paths
# ---------------------------------------------------------------------------

def scan_rows(state, x, dt, a, b, c, d):
    """The recurrence row by row from ``state`` (H, P, N) float32: ``x``
    (T, H, P), ``dt`` (T, H), ``a``/``d`` (H,), ``b``/``c`` (T, G, N), all
    float32.  Returns ``y`` (T, H, P) and the state after row T.  A row
    with ``dt == 0`` leaves the state as it was."""
    hg = x.shape[1] // b.shape[1]

    def row(h, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, hg, axis=0) for v in (b_t, c_t))  # (H, N)
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    state, y = lax.scan(row, state, (x, dt, b, c))
    return y, state


def _f32(*xs):
    return tuple(v.astype(jnp.float32) for v in xs)


def ssd_chunk_scan_ref(slab, row, x, dt, a, b, c, d):
    """jnp path of :func:`ssd_chunk_scan`, same signature."""
    x, dt, a, b, c, d = _f32(x, dt, a, b, c, d)
    y, state = scan_rows(slab[row].astype(jnp.float32), x, dt, a, b, c, d)
    return y, slab.at[row].set(state.astype(slab.dtype))


def ssm_state_step_ref(slab, base, x, dt, a, b, c, d, active):
    """jnp path of :func:`ssm_state_step`, same signature: every lane is
    computed and a dead lane's result is dropped."""
    x, dt, a, b, c, d = _f32(x, dt, a, b, c, d)
    s = x.shape[0]
    old = lax.dynamic_slice_in_dim(slab, base, s, axis=0)
    y, new = jax.vmap(
        lambda h, x1, dt1, b1, c1: scan_rows(
            h, x1[None], dt1[None], a, b1[None], c1[None], d))(
        old.astype(jnp.float32), x, dt, b, c)
    live = active[:, None, None]
    new = jnp.where(live[..., None], new.astype(slab.dtype), old)
    return (jnp.where(live, y[:, 0], 0.0),
            lax.dynamic_update_slice_in_dim(slab, new, base, axis=0))


# ---------------------------------------------------------------------------
# chunk scan
# ---------------------------------------------------------------------------

def _chunk_kernel(row_ref, x_ref, b_ref, c_ref, cumc_ref, cumr_ref, dtr_ref,
                  wc_ref, tot_ref, s_ref, y_ref, so_ref, g_ref):
    t = x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _group():                   # C B^T, shared by the group's heads
        g_ref[...] = lax.dot_general(
            c_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    x = x_ref[...].astype(jnp.float32)                     # (T, P)
    bm = b_ref[...].astype(jnp.float32)                    # (T, N)
    cm = c_ref[...].astype(jnp.float32)
    cum_c, cum_r = cumc_ref[0], cumr_ref[0]                # (T, 1), (1, T)
    dt_r = dtr_ref[0]
    h0 = s_ref[0, 0].astype(jnp.float32)                   # (P, N)
    rows = lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = lax.broadcasted_iota(jnp.int32, (t, t), 1)
    seen = cols <= rows
    # exp(cum_t - cum_s) dt_s for s <= t: the exponent is never positive
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, cum_c - cum_r, 0.0)),
                      0.0) * dt_r
    y = jnp.dot(g_ref[...] * decay, x, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
    carried = lax.dot_general(cm, h0, (((1,), (1,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)  # (T, P)
    y_ref[...] = (y + jnp.exp(cum_c) * carried).astype(y_ref.dtype)
    # wc_ref: exp(cum_T - cum_s) dt_s, a column; tot_ref: exp(cum_T), a row
    so_ref[0, 0] = (tot_ref[0] * h0 + lax.dot_general(
        x * wc_ref[0], bm, (((0,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)).astype(so_ref.dtype)


def ssd_chunk_scan(slab, row, x, dt, a, b, c, d, *,
                   interpret: bool | None = None):
    """One chunk of one slot through the recurrence.

    ``slab`` (rows, H, P, N): the state slab, returned advanced in row
    ``row`` (scalar int32) and nowhere else; ``x`` (T, H, P); ``dt`` (T, H)
    float32 after its softplus, ZERO on padding rows; ``a`` = -exp(A_log)
    and ``d`` (H,); ``b``/``c`` (T, G, N).  Returns ``(y (T, H, P) float32,
    slab)``.  Callers gate on :func:`available` / :func:`supported`."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    hg = h // g
    if interpret is None:
        interpret = not _backend_is_tpu()
    dt, a, d = _f32(dt, a, d)
    cum = jnp.cumsum(dt * a, axis=0).T                     # (H, T)
    dt_t = dt.T
    left = jnp.exp(cum[:, -1:] - cum) * dt_t               # (H, T)
    total = jnp.broadcast_to(jnp.exp(cum[:, -1:])[:, :, None], (h, 1, n))

    def head(gi, j, row):
        return gi * hg + j

    col = pl.BlockSpec((1, t, 1), lambda gi, j, row: (head(gi, j, row), 0, 0))
    line = pl.BlockSpec((1, 1, t), lambda gi, j, row: (head(gi, j, row), 0, 0))
    x_spec = pl.BlockSpec((t, p), lambda gi, j, row: (0, head(gi, j, row)))
    bc_spec = pl.BlockSpec((t, n), lambda gi, j, row: (0, gi))
    s_spec = pl.BlockSpec((1, 1, p, n),
                          lambda gi, j, row: (row[0], head(gi, j, row), 0, 0))
    with _x64_off():
        y, slab = pl.pallas_call(
            _chunk_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(g, hg),
                in_specs=[x_spec, bc_spec, bc_spec, col, line, line, col,
                          pl.BlockSpec((1, 1, n), lambda gi, j, row: (
                              head(gi, j, row), 0, 0)), s_spec],
                out_specs=[x_spec, s_spec],
                scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((t, h * p), jnp.float32),
                       jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
            input_output_aliases={9: 1},
            interpret=interpret,
            name="ssd_chunk_scan",
        )(jnp.asarray(row, jnp.int32).reshape(1), x.reshape(t, h * p),
          b.reshape(t, g * n), c.reshape(t, g * n), cum[:, :, None],
          cum[:, None, :], dt_t[:, None, :], left[:, :, None], total, slab)
    return y.reshape(t, h, p) + d[:, None] * x.astype(jnp.float32), slab


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _step_kernel(live_ref, base_ref, dtx_ref, dec_ref, b_ref, c_ref, s_ref,
                 y_ref, so_ref, *, heads):
    dtx, dec = dtx_ref[0, 0], dec_ref[0, 0]                # (P, heads)
    bm = b_ref[0, 0].astype(jnp.float32)                   # (1, N)
    cm = c_ref[0, 0].astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
    y = jnp.zeros(dtx.shape, jnp.float32)
    for k in range(heads):
        h = (s_ref[0, k].astype(jnp.float32) * dec[:, k:k + 1]
             + dtx[:, k:k + 1] * bm)                       # (P, N)
        so_ref[0, k] = h.astype(so_ref.dtype)
        y = jnp.where(lane == k, jnp.sum(h * cm, axis=1, keepdims=True), y)
    y_ref[0, 0] = y


def ssm_state_step(slab, base, x, dt, a, b, c, d, active, *,
                   interpret: bool | None = None):
    """One row of every live lane through the recurrence.

    ``slab`` (rows, H, P, N): lane ``i``'s state is row ``base + i``
    (``base`` scalar int32); it is returned advanced where ``active``
    (S,) is set and untouched (not even read) elsewhere.  ``x`` (S, H, P);
    ``dt`` (S, H) float32 after its softplus; ``a``/``d`` (H,);
    ``b``/``c`` (S, G, N).  Returns ``(y (S, H, P) float32, zero on dead
    lanes; slab)``.  Callers gate on :func:`available` /
    :func:`supported`."""
    s, h, p = x.shape
    g, n = b.shape[1:]
    hb = _head_block(h // g)
    nb = h // hb
    if interpret is None:
        interpret = not _backend_is_tpu()
    x, dt, a, d = _f32(x, dt, a, d)

    def by_block(v):               # (S, H, P) -> (S, H / hb, P, hb)
        return jnp.swapaxes(v.reshape(s, nb, hb, p), 2, 3)

    dtx = by_block(dt[:, :, None] * x)
    dec = by_block(jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], (s, h, p)))
    # live lanes first, in lane order; their count bounds the grid
    live = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n_live = jnp.sum(active, dtype=jnp.int32)

    def at_lane(i, j, live, base):
        return (live[i], j, 0, 0)

    def at_group(i, j, live, base):
        return (live[i], j * hb * g // h, 0, 0)

    def at_state(i, j, live, base):
        return (base[0] + live[i], j, 0, 0)

    blk = pl.BlockSpec((1, 1, p, hb), at_lane)
    bc_spec = pl.BlockSpec((1, 1, 1, n), at_group)
    s_spec = pl.BlockSpec((1, hb, p, n), at_state)
    with _x64_off():
        y, slab = pl.pallas_call(
            functools.partial(_step_kernel, heads=hb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n_live, nb),
                in_specs=[blk, blk, bc_spec, bc_spec, s_spec],
                out_specs=[blk, s_spec]),
            out_shape=[jax.ShapeDtypeStruct((s, nb, p, hb), jnp.float32),
                       jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
            input_output_aliases={6: 1},
            interpret=interpret,
            name="ssm_state_step",
        )(live, jnp.asarray(base, jnp.int32).reshape(1), dtx, dec,
          b[:, :, None, :], c[:, :, None, :], slab)
    y = jnp.swapaxes(y, 2, 3).reshape(s, h, p) + d[:, None] * x
    return jnp.where(active[:, None, None], y, 0.0), slab
