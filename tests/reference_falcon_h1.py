"""The ``falcon_h1`` decoder layer in plain ``jax.numpy``: the yardstick for
``correct``.

Float32 throughout at ``default_matmul_precision("highest")``, no kernels, no
cache, no batching tricks, the recurrence a sequential ``lax.scan`` over
positions.  It imports nothing of the system under test: it sees only a
weight tree and the published sizes.  Weights arrive in bfloat16 (what the
cell serves in) and are widened to float32 one matrix at a time, at use, so
the reference computes exactly on the values the program holds and never
needs the whole tree in float32.

The layer, as tiiuae's ``config.json`` for ``model_type: falcon_h1`` and the
``falcon_h1`` modelling code of ``transformers`` describe it (``x`` is
(T, hidden))::

    e      = Emb[ids] * embedding_multiplier
    n      = x / sqrt(mean(x^2) + eps) * g_in               RMSNorm
    a      = n * attention_in_multiplier
    q,k,v  = a Wq, (a Wk) * key_multiplier, a Wv            no bias
    q,k    rotated in the pairs (i, i + d/2) over all d dims (rotate-half)
    attn   = softmax(q k^T / sqrt(d) + causal) v, heads joined, times Wo,
             times attention_out_multiplier
             (KV head h serves query heads g*h .. g*h + g - 1)
    u      = (n * ssm_in_multiplier) W_in * mup             [z | x | B | C | dt]
    xBC    = silu(causal depthwise conv1d(xBC, d_conv taps, bias))
    dt     = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t    = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t   per head; a
             group's heads share B_t and C_t; h_0 = 0
    y_t    = h_t C_t + D x_t
    y      = RMSNorm over each group of (y * silu(z)), gain g_ssm
    ssm    = y W_out * ssm_out_multiplier
    x'     = x + attn + ssm
    m      = RMSNorm(x'; g_ff)
    x''    = x' + (silu(m Wg * mlp_multipliers[0]) * (m Wu)) Wd * mlp_multipliers[1]
    logits = RMSNorm(x_L; g_f) W_head^T * lm_head_multiplier   head untied

Departures from the published code, none of which changes a value it
computes: (1) the recurrence runs row by row where the published code scans
in chunks of ``mamba_chunk_size`` (the same sums in another order); (2)
``time_step_limit`` is its default (0, inf), so ``dt`` is not clamped; (3)
the convolution's weight is held (d_conv, channels), tap ``j`` multiplying
the row ``d_conv - 1 - j`` positions back, where ``nn.Conv1d`` holds
(channels, 1, d_conv); (4) the matrices are held (in, out), the head and
the embedding (vocab, hidden); (5) no attention mask, dropout or padding:
one sequence at a time.  The configuration file lists what ``config.json``
leaves open under ``assumed``.

The weight tree::

    {"wte", "lm_head": (V, h), "lnf_g": (h,),
     "blocks": [{"ln1_g": (h,), "qkv_w": (h, (H + 2 Hkv) d)  [q | k | v],
                 "proj_w": (H d, h), "ssm_in_w": (h, 2 d_ssm + 2 G N + Hs),
                 "conv_w": (d_conv, d_ssm + 2 G N), "conv_b": (d_ssm + 2 G N,),
                 "dt_bias", "A_log", "D": (Hs,), "ssm_norm_g": (d_ssm,),
                 "ssm_out_w": (d_ssm, h), "ln2_g": (h,),
                 "gate_w", "up_w": (h, f), "down_w": (f, h)}]}

Everything wide is computed ``ROWS`` rows at a time (attention a KV head at
a time besides), so that a prompt of a thousand tokens fits at the
published widths.
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


def _state(h):
    """The recurrent state as it is carried from one row to the next.
    (A study of precision can keep it in a narrower type.)"""
    return h


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, *, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _w(g)


@jax.jit
@_highest
def _matmul(x, w):
    return x @ _w(w)


@functools.partial(jax.jit, static_argnames=("theta",))
def _rope(x, row0, *, theta):
    """Rotate (R, heads, d) in the pairs (i, i + d/2), row r (at position
    ``row0 + r``) by the angle ``(row0 + r) * theta ** (-2i / d)``."""
    r, n, d = x.shape
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (row0 + jnp.arange(r)).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    halves = x.reshape(r, n, 2, d // 2)
    a, b = halves[:, :, 0], halves[:, :, 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=2).reshape(x.shape)


@jax.jit
@_highest
def _attend(q, k, v, row0):
    """Query rows ``row0 ..`` of one KV head's group: ``q`` (R, g, d)
    against all of ``k``, ``v`` (T, d), causally."""
    r, _, d = q.shape
    scores = jnp.einsum("rgd,td->grt", q, k) / jnp.sqrt(F32(d))
    seen = jnp.arange(k.shape[0])[None, :] <= row0 + jnp.arange(r)[:, None]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("grt,td->rgd", jax.nn.softmax(scores, axis=-1), v)


def _attention(n, p, *, n_head, n_kv_head, head_dim, theta, mult):
    t = n.shape[0]
    g, wide = n_head // n_kv_head, n_head * head_dim
    a = n * F32(mult["attention_in"])
    wq, wkv = p["qkv_w"][:, :wide], p["qkv_w"][:, wide:]
    k, v = jnp.split(_matmul(a, wkv).reshape(t, 2 * n_kv_head, head_dim), 2,
                     axis=1)
    k = _rope(k * F32(mult["key"]), 0, theta=theta)
    rows = []
    for r0 in range(0, t, ROWS):
        q = _rope(_matmul(a[r0:r0 + ROWS], wq).reshape(-1, n_head, head_dim),
                  r0, theta=theta)
        heads = [_attend(q[:, h * g:(h + 1) * g], k[:, h], v[:, h], r0)
                 for h in range(n_kv_head)]
        rows.append(_matmul(jnp.concatenate(heads, axis=1).reshape(-1, wide),
                            p["proj_w"]))
    return jnp.concatenate(rows, axis=0) * F32(mult["attention_out"])


@jax.jit
def _conv(xbc, w, b):
    """Causal depthwise convolution of (T, channels) from nothing before
    row 0, its bias and SiLU: tap ``j`` of the ``K`` multiplies the row
    ``K - 1 - j`` positions back."""
    k, t = w.shape[0], xbc.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    out = _w(b) + sum(_w(w)[j] * padded[j:j + t] for j in range(k))
    return jax.nn.silu(out)


@jax.jit
def _recurrence(x, dt, a, b, c, d):
    """Row by row from a zero state: ``x`` (T, H, P), ``dt`` (T, H), ``a``
    and ``d`` (H,), ``b`` and ``c`` (T, H, N) (a group's, repeated for its
    heads).  Returns ``y`` (T, H, P) and the state (H, P, N) after the last
    row."""
    def row(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = _state(jnp.exp(dt_t * a)[:, None, None] * h
                   + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + (b.shape[-1],), F32)
    h, y = jax.lax.scan(row, zero, (x, dt, b, c))
    return y, h


@functools.partial(jax.jit, static_argnames=("groups", "eps"))
def _gated_norm(y, z, g, *, groups, eps):
    t = y * jax.nn.silu(z)
    t = t.reshape(t.shape[0], groups, -1)
    t = t * jax.lax.rsqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True)
                          + eps)
    return t.reshape(y.shape) * _w(g)


def _mixer(n, p, *, rows, d_ssm, ssm_heads, ssm_groups, d_state, eps, mult):
    """The mixer's term of the residual (T, hidden), and the state
    (H, P, N) its first ``rows`` rows leave behind: a row after them has
    its ``dt`` put to 0, which neither decays the state nor adds to it."""
    t = n.shape[0]
    gn = ssm_groups * d_state
    mup = jnp.concatenate([jnp.full((w,), m, F32) for w, m in zip(
        (d_ssm, d_ssm, gn, gn, ssm_heads), mult["ssm"])])
    u = jnp.concatenate(
        [_matmul(n[r0:r0 + ROWS] * F32(mult["ssm_in"]), p["ssm_in_w"])
         for r0 in range(0, t, ROWS)], axis=0) * mup
    z, xbc, dt = jnp.split(u, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    x, b, c = jnp.split(_conv(xbc, p["conv_w"], p["conv_b"]),
                        [d_ssm, d_ssm + gn], axis=-1)
    per_group = ssm_heads // ssm_groups
    b, c = (jnp.repeat(v.reshape(t, ssm_groups, d_state), per_group, axis=1)
            for v in (b, c))
    dt = jnp.where(jnp.arange(t)[:, None] < rows,
                   jax.nn.softplus(dt + _w(p["dt_bias"])), 0.0)
    y, h = _recurrence(x.reshape(t, ssm_heads, -1), dt,
                       -jnp.exp(_w(p["A_log"])), b, c, _w(p["D"]))
    y = _gated_norm(y.reshape(t, d_ssm), z, p["ssm_norm_g"],
                    groups=ssm_groups, eps=eps)
    return _matmul(y, p["ssm_out_w"]) * F32(mult["ssm_out"]), h


@jax.jit
@_highest
def _mlp(m, gate, up, down, gate_mult, down_mult):
    return (jax.nn.silu(m @ _w(gate) * gate_mult) * (m @ _w(up))) \
        @ _w(down) * down_mult


def layer(x, p, *, rows, n_head, n_kv_head, head_dim, theta, eps, d_ssm,
          ssm_heads, ssm_groups, d_state, mult):
    """One block on ``x`` (T, hidden) float32, and the recurrent state its
    first ``rows`` rows leave.  ``mult``: the multipliers, ``attention_in``,
    ``attention_out``, ``key``, ``ssm_in``, ``ssm_out``, ``ssm`` (five),
    ``mlp`` (two)."""
    n = _norm(x, p["ln1_g"], eps=eps)
    ssm, h = _mixer(n, p, rows=rows, d_ssm=d_ssm, ssm_heads=ssm_heads,
                    ssm_groups=ssm_groups, d_state=d_state, eps=eps,
                    mult=mult)
    x = x + _attention(n, p, n_head=n_head, n_kv_head=n_kv_head,
                       head_dim=head_dim, theta=theta, mult=mult) + ssm
    m = _norm(x, p["ln2_g"], eps=eps)
    gm, dm = (F32(v) for v in mult["mlp"])
    return x + jnp.concatenate(
        [_mlp(m[r0:r0 + ROWS], p["gate_w"], p["up_w"], p["down_w"], gm, dm)
         for r0 in range(0, m.shape[0], ROWS)], axis=0), h


def hidden(weights, ids, *, mult, rows=None, **sizes):
    """Final residual stream (before the last norm) of one sequence ``ids``
    (T,): (T, hidden) float32; and, a layer, the recurrent state
    (H, P, N) after its first ``rows`` rows (all of them unless given: a
    caller that pads ``ids`` on the right says where the sequence ends)."""
    x = _w(weights["wte"][jnp.asarray(ids, jnp.int32)]) \
        * F32(mult["embedding"])
    states = []
    for p in weights["blocks"]:
        x, h = layer(x, p, mult=mult, rows=x.shape[0] if rows is None
                     else rows, **sizes)
        states.append(h)
    return x, states


@jax.jit
@_highest
def _project(x, rows):
    return x @ _w(rows).T


def head(x, lnf_g, lm_head, *, eps, lm_head_mult):
    """Final RMSNorm and the untied output head: float32 logits of ``x``,
    ``VOCAB_ROWS`` rows of the head at a time."""
    n = _norm(x, lnf_g, eps=eps)
    parts = [_project(n, lm_head[v0:v0 + VOCAB_ROWS])
             for v0 in range(0, lm_head.shape[0], VOCAB_ROWS)]
    return jnp.concatenate(parts, axis=-1) * F32(lm_head_mult)


def logits(weights, ids, *, eps, mult, **kw):
    x, _ = hidden(weights, ids, eps=eps, mult=mult, **kw)
    return head(x, weights["lnf_g"], weights["lm_head"], eps=eps,
                lm_head_mult=mult["lm_head"])
