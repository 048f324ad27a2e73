"""Fused scaled-dot-product attention.

Role parity: the reference's attention fusion ``multihead_matmul_op.cu``
(`/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu`) —
inference-only there; here a full fwd/bwd fused attention usable from
``paddle.nn.functional.scaled_dot_product_attention`` and MultiHeadAttention.

Two tiers:
  * ``_sdpa_reference``: straight jnp — XLA fuses the softmax chain; this is
    the CPU/interpret path and the autodiff path.
  * Pallas flash-attention kernel (paddle_tpu.kernels.flash) used on TPU for
    long sequences — registered lazily to keep CPU tests hermetic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.registry import register_op


def _sdpa_reference(q, k, v, mask=None, scale=None, is_causal=False,
                    dropout_p=0.0, rng=None, window=None):
    """q,k,v: [..., seq, head_dim] (any leading batch/head dims).  Dropout is
    applied to the attention PROBABILITIES (paddle/reference semantics).

    GQA: k/v may carry FEWER heads on dim -3 than q (a divisor) — query
    heads are grouped over the shared K/V head by a reshape, never by
    repeating K/V.  ``window`` (with ``is_causal``) restricts each query to
    the trailing ``window`` positions: ``kv_pos in (q_pos - window, q_pos]``.
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    nh = q.shape[-3] if q.ndim >= 3 else 1
    nkv = k.shape[-3] if k.ndim >= 3 else 1
    grouped = q.ndim >= 3 and nh != nkv
    if grouped:
        g = nh // nkv
        qg = q.reshape(q.shape[:-3] + (nkv, g, q.shape[-2], d))
        logits = jnp.einsum("...gqd,...kd->...gqk", qg, k) * jnp.asarray(s, q.dtype)
    else:
        logits = jnp.einsum("...qd,...kd->...qk", q, k) * jnp.asarray(s, q.dtype)
    if is_causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        qpos = jnp.arange(kl - ql, kl)[:, None]
        kpos = jnp.arange(kl)[None, :]
        causal = kpos <= qpos
        if window is not None:
            causal = causal & (kpos > qpos - window)
        logits = jnp.where(causal, logits, jnp.asarray(-1e9, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and rng is not None:
        keep = jax.random.uniform(
            rng, probs.shape, dtype=jnp.float32) < jnp.float32(1.0 - dropout_p)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), jnp.zeros_like(probs))
    if grouped:
        out = jnp.einsum("...gqk,...kd->...gqd", probs, v)
        return out.reshape(q.shape[:-1] + (v.shape[-1],))
    return jnp.einsum("...qk,...kd->...qd", probs, v)


def _flash_per_shard(mesh, q, k, v, layout, **kw):
    """Run the flash kernel on each device's LOCAL shard of a GSPMD mesh.

    GSPMD cannot partition a Pallas custom call: left bare inside a
    partitioned jit it all-gathers q/k/v and every device computes every
    head of every batch row.  Attention is independent per (batch row,
    head), so ``shard_map`` over the axes that shard batch
    (``mesh.BATCH_AXES``) and heads (``'mp'``) is exact.  An axis whose
    size does not divide its dim stays out of the spec (that dim is then
    gathered, as before)."""
    from . import flash
    from ..distributed import mesh as mesh_mod

    def call(ql, kl, vl):
        return flash.flash_attention(ql, kl, vl, layout=layout, **kw)

    if mesh is None or q.ndim != 4:
        return call(q, k, v)
    h_dim = flash.LAYOUTS[layout][0] % 4  # batch leads in every layout
    batch = tuple(a for a in mesh_mod.BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    mp = mesh.shape.get("mp", 1)
    heads = "mp" if mp > 1 and not (
        q.shape[h_dim] % mp or k.shape[h_dim] % mp) else None
    spec = [None] * 4
    spec[0] = batch or None
    spec[h_dim] = heads
    spec = P(*spec)
    return jax.shard_map(call, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def sdpa(q, k, v, mask=None, scale=None, is_causal=False, dropout_p=0.0,
         rng=None, layout="bnsd", window=None, mesh=None):
    """Dispatch to the Pallas flash kernel on TPU when profitable, else the
    XLA-fused reference (dropout always takes the reference path).  Which
    one a program took is readable from its jaxpr
    (``analysis.jaxpr_audit.pallas_kernels``).  ``mesh``: the multi-device
    GSPMD mesh the caller is being partitioned over, if any — the kernel
    then runs per shard (:func:`_flash_per_shard`).

    ``layout`` is one of ``flash.LAYOUTS`` (ValueError otherwise).
    ``layout="bsnd"`` ([b, s, nh, d], what a QKV projection yields) feeds
    the kernel's column-slab specs directly — no materialized transposes
    around the custom call (flash._fwd_call_smajor).  GQA (k/v with fewer
    heads) and ``window`` thread through to the kernel's in-kernel group
    gather / window mask."""
    from . import flash
    from ..framework import flags

    _, s_axis = flash.layout_axes(layout)
    if (flags.flag("FLAGS_tpu_flash_attention")
            and flash.available() and q.shape[s_axis] >= 512
            and flash.supported(q, k, mask=mask, dropout_p=dropout_p,
                                layout=layout)):
        return _flash_per_shard(mesh, q, k, v, layout, causal=is_causal,
                                scale=scale, window=window)
    if layout == "bsnd":
        if q.ndim != 4:
            raise ValueError(
                f"layout={layout!r} expects 4-D q/k/v, got {q.shape}")
        # reference path works on [..., s, d]: transpose in/out (CPU tests;
        # perf path is the kernel above)
        out = _sdpa_reference(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                              mask=mask, scale=scale, is_causal=is_causal,
                              dropout_p=dropout_p, rng=rng, window=window)
        return jnp.swapaxes(out, 1, 2)
    return _sdpa_reference(q, k, v, mask=mask, scale=scale, is_causal=is_causal,
                           dropout_p=dropout_p, rng=rng, window=window)


@register_op("scaled_dot_product_attention", needs_rng=True)
def sdpa_kernel(ins, attrs, rng=None):
    q, k, v = ins["Q"], ins["K"], ins["V"]
    mask = ins.get("Mask")
    p = attrs.get("dropout_p", 0.0)
    if attrs.get("is_test", False):
        p = 0.0
    out = sdpa(
        q, k, v, mask=mask,
        scale=attrs.get("scale"),
        is_causal=attrs.get("is_causal", False),
        dropout_p=p, rng=rng,
        layout=attrs.get("layout", "bnsd"),
        window=attrs.get("window"),
        mesh=attrs.get("mesh"),
    )
    return {"Out": out}


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True,
                                 layout="bnsd", window=None):
    from ..distributed import mesh as mesh_mod
    from ..dygraph import tracer
    from ..framework import program as fw
    from ..ops.dispatch import dispatch, single

    ins = {"Q": [query], "K": [key], "V": [value]}
    if attn_mask is not None:
        ins["Mask"] = [attn_mask]
    attrs = {"dropout_p": dropout_p, "is_causal": is_causal,
             "is_test": not training, "layout": layout, "window": window}
    # The mesh rides in the attrs, not in a trace-time read of the global:
    # attrs key the per-op jit cache, so a program traced under one mesh is
    # never replayed under another.  Inside a shard_map region the arrays
    # are already per-device.
    mesh = mesh_mod.get_mesh()
    if (mesh is not None and mesh.devices.size > 1 and fw.in_dygraph_mode()
            and not tracer.in_manual_mesh_context()):
        attrs["mesh"] = mesh
    return single(dispatch("scaled_dot_product_attention", ins, attrs))
