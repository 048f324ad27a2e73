"""Ring attention: sequence/context parallelism over a mesh axis.

SURVEY.md §5 names long-context ring attention a fresh-design mandate (the
reference has no equivalent — its sequence length is bounded by one GPU's
memory).  Design (Liu et al., "Ring Attention with Blockwise Transformers
for Near-Infinite Context", 2023):

  * Q, K, V are sharded over the sequence dim on a mesh axis; each device
    keeps its Q shard resident and STREAMS the K/V shards around the ring
    via ``lax.ppermute`` over ICI;
  * each ring step computes blockwise attention of the local Q against the
    visiting K/V block and folds it into an online-softmax accumulator
    (running max m, normalizer l, unnormalized output o) — the same math
    as the Pallas flash kernel's inner loop (kernels/flash.py), lifted one
    level up so the *sequence axis* scales with the number of devices;
  * XLA overlaps the ppermute with the next block's compute inside the
    ``lax.scan`` (compute/comm overlap the paper schedules by hand);
  * causal masking uses GLOBAL positions (device i's Q rows are offset by
    i*S_local), so fully-masked visiting blocks contribute zero.

Peak memory per device is O(S/P * S/P) for one score block instead of
O(S^2): sequence length scales linearly with the ring size.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed import mesh as mesh_mod

NEG_INF = -1e30


def _ring_block(q, k, v, o, m, l, q_off, kv_off, scale, causal,
                window=None):
    """Fold one visiting K/V block into the online-softmax accumulator.

    q: (B, H, Sq, D); k/v: (B, Hkv, Sk, D) with Hkv a divisor of H (GQA:
    query-head groups share a K/V head via a reshape, no K/V repeat);
    o: like q (unnormalized); m/l: (B, H, Sq) running max / normalizer.
    Offsets are the blocks' global sequence positions (traced scalars).
    ``window`` (causal only) hides keys older than ``window`` positions.
    """
    b, h, sq, _ = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if h != hkv:
        g = h // hkv
        qg = q.reshape(b, hkv, g, sq, q.shape[-1])
        s = jnp.einsum("bngqd,bnkd->bngqk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(b, h, sq, sk)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(sq)
        kv_pos = kv_off + jnp.arange(sk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new == NEG_INF): keep them at zero weight
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])  # masked scores underflow to 0
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
    l_new = alpha * l + jnp.sum(p, axis=-1)
    if h != hkv:
        g = h // hkv
        pg = p.reshape(b, hkv, g, sq, sk)
        o_blk = jnp.einsum("bngqk,bnkd->bngqd", pg, v.astype(p.dtype))
        o_blk = o_blk.reshape(b, h, sq, v.shape[-1])
    else:
        o_blk = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    o_new = o * alpha[..., None] + o_blk
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis: str = "mp", causal: bool = False,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None,
                   window: Optional[int] = None):
    """Attention over sequence-sharded Q/K/V (global arrays, (B, H, S, D)).

    The sequence dim is (re)sharded over ``axis``; returns the global
    output with the same sharding.  Equivalent to
    ``softmax(QK^T * scale [+causal mask]) V`` computed without any device
    ever holding the full sequence.

    ``use_flash`` selects the per-device block engine: the Pallas flash
    kernel (default on TPU; per-visiting-block flash with global-LSE
    merging — see :func:`ring_flash_attention`) or the einsum online-
    softmax fallback.  The single-device fallback dispatches through
    ``sdpa`` and therefore also runs flash on TPU.
    """
    mesh = mesh_mod.get_mesh()
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        # single chip: the sdpa dispatcher picks the flash kernel on TPU
        from .attention import sdpa

        return sdpa(q, k, v, scale=scale, is_causal=causal, window=window)
    grouped = q.shape[1] != k.shape[1]
    if grouped or window is not None:
        # the flash ring composition merges heads into the flat (bh, s, d)
        # block engine and gates visiting blocks whole — GQA grouping and
        # the window's partial-block masking both live in the einsum engine
        use_flash = False
    if use_flash is None:
        from . import flash as _fl

        use_flash = _fl.available() and _fl.supported(q, k)
    if use_flash:
        return ring_flash_attention(q, k, v, axis=axis, causal=causal,
                                    scale=scale)
    ring = int(mesh.shape[axis])
    b, h, s, d = q.shape
    if s % ring:
        raise ValueError(f"seq len {s} must divide the ring size {ring}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s_local = s // ring

    spec = P(None, None, axis, None)
    sharded = NamedSharding(mesh, spec)
    q = jax.device_put(jnp.asarray(q), sharded)
    k = jax.device_put(jnp.asarray(k), sharded)
    v = jax.device_put(jnp.asarray(v), sharded)

    def per_device(ql, kl, vl):
        i = lax.axis_index(axis)
        q_off = i * s_local
        o = jnp.zeros(ql.shape[:3] + (vl.shape[-1],), jnp.float32)
        m = jnp.full(ql.shape[:3], NEG_INF, jnp.float32)
        l = jnp.zeros(ql.shape[:3], jnp.float32)
        perm = [(src, (src + 1) % ring) for src in range(ring)]

        def step(carry, r):
            o, m, l, k_r, v_r = carry
            kv_off = ((i - r) % ring) * s_local
            o, m, l = _ring_block(ql, k_r, v_r, o, m, l, q_off, kv_off,
                                  scale, causal, window=window)
            # rotate AFTER using the block; XLA overlaps this ppermute with
            # the next iteration's einsum
            k_r = lax.ppermute(k_r, axis, perm)
            v_r = lax.ppermute(v_r, axis, perm)
            return (o, m, l, k_r, v_r), None

        (o, m, l, _, _), _ = lax.scan(step, (o, m, l, kl, vl),
                                      jnp.arange(ring))
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l[..., None]).astype(ql.dtype)

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# ring + flash composition
# ---------------------------------------------------------------------------


def ring_flash_attention(q, k, v, axis: str = "mp", causal: bool = False,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         window: Optional[int] = None):
    """Ring attention whose per-device block engine is the Pallas flash
    kernel (kernels/flash.py) instead of the einsum online-softmax.

    Forward: each ring step runs flash over (local Q, visiting K/V block)
    — the diagonal step with the kernel's causal mask, later steps gated
    by block visibility — and the per-block (out, lse) pairs merge by
    log-sum-exp weighting into the exact global softmax.

    Backward (custom vjp): the flash backward kernels take the GLOBAL lse
    and global-out delta, so replaying them per visiting block yields the
    exact partial dq / dk / dv sums; dk/dv accumulators travel the ring
    WITH their K/V blocks and arrive home after the full cycle.
    """
    from . import flash as _fl

    mesh = mesh_mod.get_mesh()
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        from .attention import sdpa

        return sdpa(q, k, v, scale=scale, is_causal=causal, window=window)
    if q.shape[1] != k.shape[1] or window is not None:
        return ring_attention(q, k, v, axis=axis, causal=causal,
                              scale=scale, use_flash=False, window=window)
    ring = int(mesh.shape[axis])
    b, h, s, d = q.shape
    if s % ring:
        raise ValueError(f"seq len {s} must divide the ring size {ring}")
    s_local = s // ring
    blk = _fl._pick_block(s_local)
    if blk is None or d % 8 != 0 or not (16 <= d <= 256):
        # shapes the Mosaic kernel can't take: einsum engine
        return ring_attention(q, k, v, axis=axis, causal=causal,
                              scale=scale, use_flash=False)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = not _fl._backend_is_tpu()

    spec = P(None, None, axis, None)
    sharded = NamedSharding(mesh, spec)
    q = jax.device_put(jnp.asarray(q), sharded)
    k = jax.device_put(jnp.asarray(k), sharded)
    v = jax.device_put(jnp.asarray(v), sharded)
    perm = [(src, (src + 1) % ring) for src in range(ring)]

    def _merge(o_acc, L, o_r, lse_r):
        """LSE-weighted merge of a normalized block output into the
        accumulator.  o: (bh, s, d) f32; lse/L: (bh, 1, s) f32."""
        m = jnp.maximum(L, lse_r)
        m_safe = jnp.where(jnp.isinf(m) & (m < 0), 0.0, m)
        w_old = jnp.where(L <= NEG_INF / 2, 0.0, jnp.exp(L - m_safe))
        w_new = jnp.where(lse_r <= NEG_INF / 2, 0.0,
                          jnp.exp(lse_r - m_safe))
        denom = jnp.maximum(w_old + w_new, 1e-30)
        wo = (w_old / denom)[:, 0, :, None]
        wn = (w_new / denom)[:, 0, :, None]
        o_new = o_acc * wo + o_r.astype(jnp.float32) * wn
        return o_new, m_safe + jnp.log(denom)

    def _gate(lse_r, i, r):
        if not causal or r == 0:
            return lse_r
        visible = ((i - r) % ring) < i
        return jnp.where(visible, lse_r, jnp.float32(NEG_INF))

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _pd(ql, kl, vl):
        out, _ = _pd_fwd(ql, kl, vl)
        return out

    def _pd_fwd(ql, kl, vl):
        i = lax.axis_index(axis)
        bh = ql.shape[0] * ql.shape[1]
        q3 = ql.reshape(bh, s_local, d)
        k_r = kl.reshape(bh, s_local, d)
        v_r = vl.reshape(bh, s_local, d)
        o_acc = jnp.zeros((bh, s_local, d), jnp.float32)
        L = jnp.full((bh, 1, s_local), jnp.float32(NEG_INF))
        for r in range(ring):
            o_r, lse_r = _fl._flash_fwd(
                q3, k_r, v_r, scale, causal and r == 0, blk, blk, interpret)
            lse_r = _gate(lse_r, i, r)
            o_acc, L = _merge(o_acc, L, o_r, lse_r)
            k_r = lax.ppermute(k_r, axis, perm)
            v_r = lax.ppermute(v_r, axis, perm)
        out = o_acc.astype(ql.dtype).reshape(ql.shape)
        return out, (ql, kl, vl, o_acc, L, i)

    def _pd_bwd(res, do):
        ql, kl, vl, o_acc, L, i = res
        bh = ql.shape[0] * ql.shape[1]
        q3 = ql.reshape(bh, s_local, d)
        k_r = kl.reshape(bh, s_local, d)
        v_r = vl.reshape(bh, s_local, d)
        do3 = do.reshape(bh, s_local, d)
        out3 = o_acc.astype(q3.dtype)
        dq = jnp.zeros((bh, s_local, d), jnp.float32)
        dk_acc = jnp.zeros((bh, s_local, d), jnp.float32)
        dv_acc = jnp.zeros((bh, s_local, d), jnp.float32)
        for r in range(ring):
            dq_r, dk_r, dv_r = _fl._flash_bwd(
                q3, k_r, v_r, out3, L, do3, scale, causal and r == 0,
                blk, blk, interpret)
            if causal and r > 0:
                g = (((i - r) % ring) < i).astype(jnp.float32)
                dq_r = dq_r * g
                dk_r = dk_r * g
                dv_r = dv_r * g
            dq = dq + dq_r.astype(jnp.float32)
            dk_acc = dk_acc + dk_r.astype(jnp.float32)
            dv_acc = dv_acc + dv_r.astype(jnp.float32)
            k_r = lax.ppermute(k_r, axis, perm)
            v_r = lax.ppermute(v_r, axis, perm)
            dk_acc = lax.ppermute(dk_acc, axis, perm)
            dv_acc = lax.ppermute(dv_acc, axis, perm)
        shp = ql.shape
        return (dq.astype(ql.dtype).reshape(shp),
                dk_acc.astype(kl.dtype).reshape(shp),
                dv_acc.astype(vl.dtype).reshape(shp))

    _pd.defvjp(_pd_fwd, _pd_bwd)

    fn = jax.shard_map(_pd, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
