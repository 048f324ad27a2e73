"""Pallas TPU flash attention — tiled online-softmax fwd + bwd.

Role parity: the reference's fused attention CUDA kernel
(``/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu:1`` and
the 53-file ``operators/fused/`` zoo).  That kernel is inference-only; this
one is a full fwd/bwd flash attention (Dao et al. 2022 recurrence) so
activation memory is O(seq) instead of O(seq^2) — the main MFU lever for
long-sequence GPT pretraining on TPU (BASELINE.md north star).

Design (pallas_guide.md):
  * grid = (batch*heads, seq blocks); K/V for one (b,h) live whole in VMEM,
    the q-block loops over k-blocks with ``lax.fori_loop`` doing the online
    softmax in fp32 on the MXU (``preferred_element_type``);
  * causal masking skips fully-masked k-blocks (loop bound, not a mask);
  * backward = two kernels (dQ; dK+dV) recomputing probabilities from the
    saved logsumexp — no O(s^2) residuals;
  * ``interpret=True`` runs the same kernels through the Pallas interpreter
    so CPU tests cover the exact TPU code path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _x64_off():
    """Context manager tracing the kernels with x64 promotion off: Mosaic
    has no 64-bit types, and ``paddle_tpu`` turns ``jax_enable_x64`` on
    globally for int64 id parity."""
    return jax.enable_x64(False)


def _backend_is_tpu() -> bool:
    """True when the default JAX backend is a TPU.  A backend that fails to
    initialise raises here — it must not turn into a quiet interpret-mode
    or reference-path run."""
    return jax.devices()[0].platform == "tpu"


def available() -> bool:
    """Dispatch gate: True when the running backend can execute Mosaic/Pallas
    TPU kernels.  (Tests monkeypatch this to force the flash path; the
    interpret-mode default keys off the backend directly.)"""
    return _backend_is_tpu()


def _pick_block(s: int, want: int = 512):
    """512x512 tiles measured fastest on v5e at seq 1024 (block sweep,
    round 3): 128->48.9%, 256->54.7%, 512->57.3%, 1024->56.6% flagship
    MFU; asymmetric q/k tiles were all worse."""
    for b in (want, 512, 256, 128, 64, 32, 16, 8):
        if b <= s and s % b == 0:
            return b
    return None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, grid_axis=1, window=None):
    q = q_ref[...]
    bq, d = q.shape
    s_len = k_ref.shape[0]
    i = pl.program_id(grid_axis)

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    nkb = s_len // block_k
    if causal:
        # q rows for this block end at (i+1)*bq - 1; k-blocks past that are
        # fully masked — skip them entirely.  (i32 constants throughout: in
        # interpret mode the body is evaluated under the caller's dtype
        # config, where x64 promotion breaks the i32 index math.)
        hi = jnp.minimum(((i + 1) * jnp.int32(bq) + jnp.int32(block_k - 1))
                         // jnp.int32(block_k), jnp.int32(nkb))
    else:
        hi = nkb
    if causal and window is not None:
        # sliding window: the earliest k visible to this q-block's first
        # row is i*bq - window + 1 — k-blocks wholly before it are skipped
        lo = jnp.maximum(
            (i * jnp.int32(bq) - jnp.int32(window - 1)) // jnp.int32(block_k),
            jnp.int32(0))
    else:
        lo = jnp.int32(0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
        if causal:
            qi = i * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kj = j * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            keep = qi >= kj
            if window is not None:
                keep = keep & (kj > qi - jnp.int32(window))
            s = jnp.where(keep, s, jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # pin the bounds to i32: in interpret mode the body is evaluated under
    # the CALLER's dtype config, where jax_enable_x64 would promote the
    # python-int lower bound to i64 against an i32 upper bound
    m, l, acc = lax.fori_loop(lo, jnp.asarray(hi, jnp.int32),
                              body, (m0, l0, acc0))
    l = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l)).reshape(1, bq)


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
               window=None):
    bh, s_len, d = q3.shape
    nq = s_len // block_q
    # Mosaic has no 64-bit types; trace the kernel with x64 promotion off so
    # the framework-global jax_enable_x64 (int64 id parity) can't leak
    # int64/f64 scalars into the lowering.
    with _x64_off():
        out, lse = _fwd_call(q3, k3, v3, scale, causal, block_q, block_k,
                             interpret, bh, s_len, d, nq, window)
    return out, lse


def _fwd_call(q3, k3, v3, scale, causal, block_q, block_k, interpret,
              bh, s_len, d, nq, window=None):
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, window=window),
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s_len, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_len), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_k, grid_axis=1, window=None):
    q = q_ref[...]
    do = do_ref[...].astype(jnp.float32)
    bq, d = q.shape
    s_len = k_ref.shape[0]
    i = pl.program_id(grid_axis)
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]

    nkb = s_len // block_k
    if causal:
        hi = jnp.minimum(((i + 1) * jnp.int32(bq) + jnp.int32(block_k - 1))
                         // jnp.int32(block_k), jnp.int32(nkb))
    else:
        hi = nkb
    if causal and window is not None:
        lo = jnp.maximum(
            (i * jnp.int32(bq) - jnp.int32(window - 1)) // jnp.int32(block_k),
            jnp.int32(0))
    else:
        lo = jnp.int32(0)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
        if causal:
            qi = i * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kj = j * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            keep = qi >= kj
            if window is not None:
                keep = keep & (kj > qi - jnp.int32(window))
            s = jnp.where(keep, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(lo, jnp.asarray(hi, jnp.int32), body,
                       jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q,
                    grid_axis=1, window=None):
    k = k_ref[...]
    v = v_ref[...]
    bk, d = k.shape
    s_len = q_ref.shape[0]
    j = pl.program_id(grid_axis)

    nqb = s_len // block_q
    lo = (j * jnp.int32(bk)) // jnp.int32(block_q) if causal else 0
    if causal and window is not None:
        # last q that can see this k-block is (j+1)*bk - 1 + window - 1
        hi = jnp.minimum(
            ((j + 1) * jnp.int32(bk) + jnp.int32(window - 1)
             + jnp.int32(block_q - 1)) // jnp.int32(block_q),
            jnp.int32(nqb))
    else:
        hi = jnp.int32(nqb)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, pl.ds(i * block_q, block_q)]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
        if causal:
            qi = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            kj = j * bk + lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            keep = qi >= kj
            if window is not None:
                keep = keep & (kj > qi - jnp.int32(window))
            s = jnp.where(keep, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dv = dv + jnp.dot(p.T.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        dk = dk + jnp.dot(ds.T.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(jnp.asarray(lo, jnp.int32), hi, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, out, lse, do, scale, causal, block_q, block_k,
               interpret, window=None):
    with _x64_off():
        return _bwd_call(q3, k3, v3, out, lse, do, scale, causal, block_q,
                         block_k, interpret, window)


def _bwd_call(q3, k3, v3, out, lse, do, scale, causal, block_q, block_k,
              interpret, window=None):
    bh, s_len, d = q3.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s_len)

    nq = s_len // block_q
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, window=window),
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_len, d), q3.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do, lse, delta)

    nk = s_len // block_k
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, window=window),
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((None, s_len, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s_len, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s_len), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s_len), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s_len, d), v3.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# bsnd call variants — q/k/v stay [b, s, nh*d], blocks select one head's
# 128-wide column slab per program
# ---------------------------------------------------------------------------
#
# Why: a QKV projection yields [b, s, nh*d]; feeding the (bh, s, d) kernels
# makes XLA MATERIALIZE [b, nh, s, d] transposes on both sides of the custom
# call (Pallas custom calls can't absorb layout changes the way XLA fusions
# do).  Per-head COLUMN blocks over [b, s, nh*d] keep the Mosaic block rules
# happy (last-two block dims = (block_q, d), both aligned) where a
# squeezed-head 4-D spec does not; the kernel bodies are the same ones the
# bnsd path runs, and lse keeps its (b*nh, 1, s) shape with a computed head
# index.


def _smajor_specs(b, s_len, nh, d, block, what, nkv=None):
    """BlockSpecs for [b, s, nh*d] arrays (one head-column slab per
    program) and (b*nh, 1, s) lse/delta rows; grid = (b, nh, blocks).

    GQA: ``kv_tile``/``kv_full`` address [.., .., nkv*d] K/V arrays with the
    head index mapped through the query-head group (h -> h // (nh//nkv)) —
    the gather happens in the index_map, so K/V are never repeated in HBM
    and consecutive query heads of a group reuse the resident VMEM block."""
    g = 1 if nkv is None else nh // nkv
    if what in ("tile", "kv_tile"):
        hmap = (lambda h: h) if what == "tile" else (lambda h: h // g)
        return pl.BlockSpec((None, block, d),
                            lambda b_, h, i: (b_, i, hmap(h)))
    if what in ("full", "kv_full"):
        hmap = (lambda h: h) if what == "full" else (lambda h: h // g)
        return pl.BlockSpec((None, s_len, d),
                            lambda b_, h, i: (b_, 0, hmap(h)))
    if what == "row":
        return pl.BlockSpec((None, 1, block),
                            lambda b_, h, i, nh=nh: (b_ * nh + h, 0, i))
    if what == "row_full":
        return pl.BlockSpec((None, 1, s_len),
                            lambda b_, h, i, nh=nh: (b_ * nh + h, 0, 0))
    raise ValueError(what)


def _fwd_call_smajor(q3, k3, v3, nh, scale, causal, block_q, block_k,
                     interpret, nkv=None, window=None):
    b, s_len, H = q3.shape
    d = H // nh
    nq = s_len // block_q

    def sp(what, block):
        return _smajor_specs(b, s_len, nh, d, block, what, nkv=nkv)

    with _x64_off():
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              block_k=block_k, grid_axis=2, window=window),
            grid=(b, nh, nq),
            in_specs=[
                sp("tile", block_q),
                sp("kv_full", block_q),
                sp("kv_full", block_q),
            ],
            out_specs=[
                sp("tile", block_q),
                sp("row", block_q),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                jax.ShapeDtypeStruct((b * nh, 1, s_len), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(q3, k3, v3)
    return out, lse


def _bwd_call_smajor(q3, k3, v3, out, lse, do, nh, scale, causal, block_q,
                     block_k, interpret, nkv=None, window=None):
    b, s_len, H = q3.shape
    d = H // nh

    def sp(what, block):
        return _smajor_specs(b, s_len, nh, d, block, what, nkv=nkv)

    with _x64_off():
        dsum = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32))
                       .reshape(b, s_len, nh, d), axis=-1)
        # rows of the (b*nh, 1, s) delta
        delta = jnp.transpose(dsum, (0, 2, 1)).reshape(b * nh, 1, s_len)

        nq = s_len // block_q
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_k=block_k, grid_axis=2, window=window),
            grid=(b, nh, nq),
            in_specs=[
                sp("tile", block_q),
                sp("kv_full", block_q),
                sp("kv_full", block_q),
                sp("tile", block_q),
                sp("row", block_q),
                sp("row", block_q),
            ],
            out_specs=sp("tile", block_q),
            out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            interpret=interpret,
            name="flash_bwd_dq",
        )(q3, k3, v3, do, lse, delta)

        nk = s_len // block_k
        # dk/dv are emitted at QUERY-head granularity (each program owns its
        # (h, k-block) tile exclusively) and group-summed below — the sum
        # over a group is the mathematically required reduction, done once
        # outside the kernel instead of via cross-program accumulation.
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, grid_axis=2, window=window),
            grid=(b, nh, nk),
            in_specs=[
                sp("full", block_k),
                sp("kv_tile", block_k),
                sp("kv_tile", block_k),
                sp("full", block_k),
                sp("row_full", block_k),
                sp("row_full", block_k),
            ],
            out_specs=[
                sp("tile", block_k),
                sp("tile", block_k),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q3.shape, k3.dtype),
                jax.ShapeDtypeStruct(q3.shape, v3.dtype),
            ],
            interpret=interpret,
            name="flash_bwd_dkv",
        )(q3, k3, v3, do, lse, delta)
        if nkv is not None and nkv != nh:
            g = nh // nkv
            red = (b, s_len, nkv, g, d)
            dk = dk.astype(jnp.float32).reshape(red).sum(axis=3) \
                .reshape(k3.shape).astype(k3.dtype)
            dv = dv.astype(jnp.float32).reshape(red).sum(axis=3) \
                .reshape(v3.shape).astype(v3.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _flash_smajor(nh, nkv, causal, scale, window, block_q, block_k,
                  interpret, q3, k3, v3):
    out, _ = _fwd_call_smajor(q3, k3, v3, nh, scale, causal, block_q,
                              block_k, interpret, nkv=nkv, window=window)
    return out


def _flash_smajor_fwd(nh, nkv, causal, scale, window, block_q, block_k,
                      interpret, q3, k3, v3):
    out, lse = _fwd_call_smajor(q3, k3, v3, nh, scale, causal, block_q,
                                block_k, interpret, nkv=nkv, window=window)
    return out, (q3, k3, v3, out, lse)


def _flash_smajor_bwd(nh, nkv, causal, scale, window, block_q, block_k,
                      interpret, res, do):
    q3, k3, v3, out, lse = res
    return _bwd_call_smajor(q3, k3, v3, out, lse, do, nh, scale, causal,
                            block_q, block_k, interpret, nkv=nkv,
                            window=window)


_flash_smajor.defvjp(_flash_smajor_fwd, _flash_smajor_bwd)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _flash(causal, scale, window, block_q, block_k, interpret, q3, k3, v3):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                        interpret, window)
    return out


def _flash_fwd_rule(causal, scale, window, block_q, block_k, interpret,
                    q3, k3, v3):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                          interpret, window)
    return out, (q3, k3, v3, out, lse)


def _flash_bwd_rule(causal, scale, window, block_q, block_k, interpret,
                    res, do):
    q3, k3, v3, out, lse = res
    dq, dk, dv = _flash_bwd(q3, k3, v3, out, lse, do, scale, causal,
                            block_q, block_k, interpret, window)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# The q/k/v layouts the flash entries take, each with its (heads, seq) axes.
# This is the one statement of which layouts exist: ``flash_attention``,
# ``supported`` and the sdpa dispatcher (kernels/attention.py) read it.
#   "bnsd": [..., heads, seq, head_dim] — the GPT model's path;
#   "bsnd": [batch, seq, heads, head_dim] — consumed in place, takes GQA.
LAYOUTS = {"bnsd": (-3, -2), "bsnd": (-2, -3)}


def layout_axes(layout):
    """(heads axis, seq axis) of ``layout``; ValueError for a layout no
    kernel implements (never a silent fall-through to another one)."""
    if layout not in LAYOUTS:
        raise ValueError(
            f"unknown attention layout {layout!r}: accepted layouts are "
            f"{sorted(LAYOUTS)}")
    return LAYOUTS[layout]


def flash_attention(q, k, v, causal=False, scale=None, interpret=None,
                    block_q=None, block_k=None, layout="bnsd", window=None):
    """Flash attention.  ``layout="bnsd"``: [..., seq, head_dim] (q/k same
    length); ``layout="bsnd"``: [batch, seq, heads, head_dim] — consumed
    IN PLACE, so the caller pays no materialized [b,nh,s,d] transposes
    around the custom call, and accepts GQA (k/v with fewer heads, a
    divisor of q's): query-head groups are gathered onto the shared K/V
    head inside the BlockSpec index maps.  Any other ``layout`` raises
    ValueError.
    ``window`` (causal only) masks keys older than ``window`` positions and
    skips fully-masked blocks.  Raises ValueError on unsupported shapes —
    callers should gate on :func:`supported` first (the sdpa dispatcher
    does)."""
    h_axis, s_axis = layout_axes(layout)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = not _backend_is_tpu()
    if window is not None and not causal:
        raise ValueError("flash_attention: window requires causal=True")
    win = None if window is None else int(window)
    s_len = q.shape[s_axis]
    bq = block_q or _pick_block(s_len)
    bk = block_k or _pick_block(s_len)
    if bq is None or bk is None or k.shape[s_axis] != s_len:
        raise ValueError(
            f"flash_attention: unsupported seq len {s_len} (needs a power-of-"
            f"two-ish divisor >= 8) or cross-attention q/k lengths")
    if layout == "bsnd":
        assert q.ndim == 4, "bsnd layout expects 4-D q/k/v"
        b, _, nh, d = q.shape
        nkv = k.shape[h_axis]
        if nh % nkv != 0:
            raise ValueError(
                f"flash_attention: q heads {nh} not a multiple of kv heads "
                f"{nkv}")
        kv_flat = (b, s_len, nkv * d)
        out = _flash_smajor(int(nh), int(nkv), causal, float(scale), win,
                            int(bq), int(bk), bool(interpret),
                            q.reshape((b, s_len, nh * d)),
                            k.reshape(kv_flat), v.reshape(kv_flat))
        return out.reshape(q.shape)
    if q.ndim >= 3 and q.shape[h_axis] != k.shape[h_axis]:
        raise ValueError(
            "flash_attention: GQA (mismatched head counts) requires "
            "layout='bsnd'")
    lead = q.shape[:-2]
    d = q.shape[-1]
    q3 = q.reshape((-1, s_len, d))
    k3 = k.reshape((-1, s_len, d))
    v3 = v.reshape((-1, s_len, d))
    out = _flash(causal, float(scale), win, int(bq), int(bk), bool(interpret),
                 q3, k3, v3)
    return out.reshape(lead + (s_len, d))


def supported(q, k, mask=None, dropout_p=0.0, layout="bnsd") -> bool:
    """Shape/feature gate used by the sdpa dispatcher."""
    if layout not in LAYOUTS or mask is not None or dropout_p != 0.0:
        return False
    h_axis, s_axis = LAYOUTS[layout]
    if layout == "bsnd" and q.ndim != 4:
        return False
    if q.ndim < 3 or q.shape[s_axis] != k.shape[s_axis]:
        return False
    # GQA: only bsnd gathers query-head groups in its index maps; the bnsd
    # flat (-1, s, d) reshape can't express it
    nh, nkv = q.shape[h_axis], k.shape[h_axis]
    if nh != nkv and (layout != "bsnd" or nkv == 0 or nh % nkv != 0):
        return False
    # head_dim gate: Mosaic wants lane-aligned (multiple-of-8) head dims in a
    # validated range; odd geometries (80, 12, ...) take the XLA sdpa path
    # instead of failing at lowering (ADVICE round 2)
    d = q.shape[-1]
    if d % 8 != 0 or not (16 <= d <= 256):
        return False
    return _pick_block(q.shape[s_axis]) is not None
