"""GPT-2's block in plain ``jax.numpy``: the yardstick for ``correct``.

Float32 throughout at ``default_matmul_precision("highest")``, no kernels, no
cache, no batching tricks.  Independent of ``paddle_tpu``: it sees only a
weight tree (see ``benchmarks/weights.py`` for the names) and the published
sizes.  Weights may arrive in bfloat16 (the type the cell serves or trains
in); they are widened to float32 at use, so the reference computes exactly
on the values the program holds.

Follows Radford et al. 2019 / the HF ``gpt2`` model type as Cerebras-GPT
configures it: learned positions, pre-LayerNorm blocks, fused QKV laid out
``[q | k | v]`` with heads contiguous, causal softmax attention scaled by
``1/sqrt(head_dim)``, exact (erf) GELU, a final LayerNorm and an output head
tied to the token embedding.  Departure: none in the mathematics; the vocab
rows past the published size are zero (``weights.py``) and so never win.

Layers run in a Python loop over one jitted block, so a 24-layer model
compiles one block and never holds a stacked copy of the weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32) + b.astype(F32)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, p, *, n_head, eps):
    """One pre-LN decoder block on ``x`` of shape (batch, seq, hidden)."""
    with jax.default_matmul_precision("highest"):
        b, s, h = x.shape
        d = h // n_head
        y = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = y @ p["qkv_w"].astype(F32) + p["qkv_b"].astype(F32)
        q, k, v = (t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(F32(d))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(scores, axis=-1), v)
        att = att.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = x + att @ p["proj_w"].astype(F32) + p["proj_b"].astype(F32)
        y = _ln(x, p["ln2_g"], p["ln2_b"], eps)
        y = jax.nn.gelu(y @ p["fc1_w"].astype(F32) + p["fc1_b"].astype(F32),
                        approximate=False)
        return x + y @ p["fc2_w"].astype(F32) + p["fc2_b"].astype(F32)


@jax.jit
def _embed(ids, wte, wpe):
    return wte[ids].astype(F32) + wpe[: ids.shape[1]].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, lnf_g, lnf_b, wte, *, eps):
    """Final LayerNorm and the tied output head: float32 logits of ``x``."""
    with jax.default_matmul_precision("highest"):
        return _ln(x, lnf_g, lnf_b, eps) @ wte.astype(F32).T


def hidden(weights, ids, *, n_head, eps):
    """Final residual stream (before the last LayerNorm), (batch, seq, h)."""
    x = _embed(jnp.asarray(ids, jnp.int32), weights["wte"], weights["wpe"])
    for p in weights["blocks"]:
        x = _block(x, p, n_head=n_head, eps=eps)
    return x


def logits(weights, ids, *, n_head, eps, last=None):
    """Float32 logits (batch, seq, vocab); ``last`` keeps only that many
    trailing positions, which is all a decode check needs."""
    x = hidden(weights, ids, n_head=n_head, eps=eps)
    if last is not None:
        x = x[:, -last:]
    return head(x, weights["lnf_g"], weights["lnf_b"], weights["wte"], eps=eps)


def loss(weights, ids, labels, *, n_head, eps):
    """Mean next-token cross entropy over every position of every row,
    one row at a time so the (seq, vocab) logits never exceed one row."""
    labels = jnp.asarray(labels, jnp.int32)
    total, count = 0.0, 0
    for i in range(ids.shape[0]):
        lg = logits(weights, ids[i:i + 1], n_head=n_head, eps=eps)[0]
        picked = jnp.take_along_axis(lg, labels[i][:, None], axis=-1)[:, 0]
        total += float(jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked))
        count += lg.shape[0]
    return total / count
