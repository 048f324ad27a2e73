"""The ``cohere2_moe`` decoder layer in plain ``jax.numpy``: the yardstick
for ``correct``.

Float32 throughout at ``default_matmul_precision("highest")``, no kernels, no
cache, no batching tricks.  It imports nothing of the system under test: it
sees only a weight tree and the published sizes.  Weights arrive in bfloat16
(what the cell serves in) and are widened to float32 one matrix at a time,
at use, so the reference computes exactly on the values the program holds
and never needs the whole tree in float32.

The layer, as CohereLabs' ``config.json`` for ``model_type: cohere2_moe``
describes it (``x`` is (T, hidden), layer ``l``)::

    n     = (x - mean) / sqrt(var + eps) * g           one LayerNorm, gain only
    q,k,v = n Wq, n Wk, n Wv                            no bias, no qk-norm
    sliding layer: q, k rotated in interleaved pairs (rope_gptj), all dims;
                   key j seen by query i iff i - window < j <= i
    full layer:    no positional encoding at all; causal mask
    attn  = softmax(q k^T / sqrt(d) + mask) v, heads joined, times Wo
            (KV head h serves query heads g*h .. g*h + g - 1)
    s     = sigmoid(n Wr);  S = the top_k largest of s;  w_e = s_e / sum_S s
    E(n)  = (silu(n Wgate) * (n Wup)) Wdown             routed and shared alike
    moe   = sum_{e in S, e held here} w_e E_e(n) + mean_j Esh_j(n)
    x'    = x + attn + moe                              parallel block
    logits = LayerNorm_f(x_L) Emb^T * logit_scale       Emb tied

Readings the config leaves open (the configuration file lists them under
``assumed``): ``intermediate_size`` is the width of one routed and of one
shared expert; ``average`` is the mean of the shared experts' outputs, added
unweighted; the top-k is taken on the sigmoid scores and ``norm_topk_prob``
divides by the sum over ALL selected experts, held here or not, so the shares
of the chips that divide a layer's experts add up to the whole layer.

``experts_held = (first, count)`` says which of the router's experts this
share holds: ``weights["blocks"][l]["gate_w"][i]`` is expert ``first + i``.
What the absent experts would add is left out.

The weight tree::

    {"wte": (V, h), "lnf_g": (h,),
     "blocks": [{"ln1_g": (h,), "qkv_w": (h, (H + 2 Hkv) d)  [q | k | v],
                 "proj_w": (H d, h), "router_w": (h, E),
                 "gate_w", "up_w": (held, h, f), "down_w": (held, f, h),
                 "sh_gate_w", "sh_up_w": (S, h, f), "sh_down_w": (S, f, h)}]}

Attention is computed a KV head and ``ROWS`` query rows at a time, so that a
prompt of several thousand tokens fits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 512
VOCAB_ROWS = 8192


def _w(w):
    """A weight, widened for use.  (Every use goes through here, so a
    study of precision can round the weights further first.)"""
    return w.astype(F32)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, *, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _w(g)


@jax.jit
@_highest
def _matmul(x, w):
    return x @ _w(w)


@functools.partial(jax.jit, static_argnames=("theta",))
def _rope(x, row0, *, theta):
    """Rotate (R, heads, d) in interleaved pairs (2i, 2i + 1), row r (at
    position ``row0 + r``) by the angle ``(row0 + r) * theta ** (-2i / d)``."""
    r, _, d = x.shape
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (row0 + jnp.arange(r)).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("window",))
@_highest
def _attend(q, k, v, row0, *, window):
    """Query rows ``row0 ..`` of one KV head's group: ``q`` (R, g, d)
    against all of ``k``, ``v`` (T, d)."""
    r, _, d = q.shape
    t = k.shape[0]
    scores = jnp.einsum("rgd,td->grt", q, k) / jnp.sqrt(F32(d))
    i = row0 + jnp.arange(r)[:, None]
    j = jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("grt,td->rgd", jax.nn.softmax(scores, axis=-1), v)


def _attention(n, p, *, n_head, n_kv_head, head_dim, window, theta):
    t = n.shape[0]
    g, wide = n_head // n_kv_head, n_head * head_dim
    wq, wkv = p["qkv_w"][:, :wide], p["qkv_w"][:, wide:]
    k, v = jnp.split(_matmul(n, wkv).reshape(t, 2 * n_kv_head, head_dim), 2,
                     axis=1)
    if window is not None:          # sliding layers rotate; full ones do not
        k = _rope(k, 0, theta=theta)
    rows = []
    for r0 in range(0, t, ROWS):
        q = _matmul(n[r0:r0 + ROWS], wq).reshape(-1, n_head, head_dim)
        if window is not None:
            q = _rope(q, r0, theta=theta)
        heads = [_attend(q[:, h * g:(h + 1) * g], k[:, h], v[:, h], r0,
                         window=window) for h in range(n_kv_head)]
        rows.append(_matmul(jnp.concatenate(heads, axis=1).reshape(-1, wide),
                            p["proj_w"]))
    return jnp.concatenate(rows, axis=0)


@jax.jit
@_highest
def _expert(n, gate, up, down):
    return (jax.nn.silu(n @ _w(gate)) * (n @ _w(up))) @ _w(down)


@jax.jit
@_highest
def router_logits(n, router_w):
    """(T, E): what the router's sigmoid is taken of."""
    return n @ _w(router_w)


@functools.partial(jax.jit, static_argnames=("top_k",))
def route(n, router_w, *, top_k):
    """(T, E) weights: ``s_e / sum_S s`` on the ``top_k`` largest sigmoid
    scores of each row, 0 elsewhere."""
    s = jax.nn.sigmoid(router_logits(n, router_w))
    kth = jnp.sort(s, axis=-1)[:, -top_k][:, None]
    chosen = jnp.where(s >= kth, s, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _moe(n, p, *, top_k, experts_held):
    first, count = experts_held
    w = route(n, p["router_w"], top_k=top_k)
    y = jnp.zeros_like(n)
    for i in range(count):
        y = y + w[:, first + i, None] * _expert(
            n, p["gate_w"][i], p["up_w"][i], p["down_w"][i])
    n_shared = p["sh_gate_w"].shape[0]
    for j in range(n_shared):
        y = y + _expert(n, p["sh_gate_w"][j], p["sh_up_w"][j],
                        p["sh_down_w"][j]) / n_shared
    return y


def layer(x, p, *, n_head, n_kv_head, head_dim, eps, window, theta, top_k,
          experts_held, tap=None):
    """One parallel block on ``x`` (T, hidden) float32; ``window`` is None
    for a full-attention layer (which then has no positions either).
    ``tap(n, p)``, if given, is shown the block's normed input, which is
    what attention, router and experts all read."""
    n = _norm(x, p["ln1_g"], eps=eps)
    if tap is not None:
        tap(n, p)
    attn = _attention(n, p, n_head=n_head, n_kv_head=n_kv_head,
                      head_dim=head_dim, window=window, theta=theta)
    return x + attn + _moe(n, p, top_k=top_k, experts_held=experts_held)


def hidden(weights, ids, *, windows, experts_held, **sizes):
    """Final residual stream (before the last LayerNorm) of one sequence
    ``ids`` (T,): (T, hidden) float32.  ``windows`` gives each layer's
    sliding window, None for a full-attention layer."""
    x = _w(weights["wte"][jnp.asarray(ids, jnp.int32)])
    for p, window in zip(weights["blocks"], windows, strict=True):
        x = layer(x, p, window=window, experts_held=experts_held, **sizes)
    return x


@jax.jit
@_highest
def _project(x, rows):
    return x @ _w(rows).T


def head(x, lnf_g, wte, *, eps, logit_scale=1.0):
    """Final LayerNorm and the tied output head: float32 logits of ``x``,
    ``VOCAB_ROWS`` rows of the embedding at a time (the whole of it in
    float32, and again transposed, is a gigabyte at 32k x 4096)."""
    n = _norm(x, lnf_g, eps=eps)
    parts = [_project(n, wte[v0:v0 + VOCAB_ROWS])
             for v0 in range(0, wte.shape[0], VOCAB_ROWS)]
    return jnp.concatenate(parts, axis=-1) * F32(logit_scale)


def logits(weights, ids, *, eps, logit_scale=1.0, **kw):
    x = hidden(weights, ids, eps=eps, **kw)
    return head(x, weights["lnf_g"], weights["wte"], eps=eps,
                logit_scale=logit_scale)
