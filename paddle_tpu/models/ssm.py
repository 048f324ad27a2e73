"""A Mamba-2 state-space mixer, as data and as the substrate's functions.

One head ``h`` of ``n_heads`` keeps a state ``(head_dim, d_state)``; the
``n_heads / n_groups`` heads of a group share one ``B_t`` and one ``C_t``
(``d_state`` each).  For row ``t`` (``n`` is the block's normed input)::

    u          = (n * in_mult) W_in * mup      columns [z | x | B | C | dt]
    z, xBC, dt = split(u, [d_ssm, d_ssm + 2 groups d_state, n_heads])
    xBC        = silu(causal depthwise conv1d(xBC, d_conv taps, bias))
    x, B, C    = split(xBC, [d_ssm, groups d_state, groups d_state])
    dt         = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t        = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t
    y_t        = h_t C_t + D x_t
    y          = RMSNorm over each group of (y * silu(z)), gain g
    out        = y W_out * out_mult

What a slot carries from row to row is the state ``h`` (in
``state_dtype``) and the convolution's tail, the last ``d_conv - 1`` rows
of ``xBC`` before the convolution.  The recurrence itself lives in
``kernels/ssd.py`` (a chunk at a time in prefill, a row at a time in
decode, and the sequential scan both are held to); everything around it is
here, shared by the serving engine's two programs and the model's eager
forward, in float32 from the in-projection's accumulator to the
out-projection's input.

Block leaves: ``ssm_in_w`` (hidden, in_dim), ``conv_w`` (d_conv, conv_dim),
``conv_b`` (conv_dim,), ``dt_bias``/``A_log``/``D`` (n_heads,),
``ssm_norm_g`` (d_ssm,), ``ssm_out_w`` (d_ssm, hidden).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_ssm: int                      # n_heads * head_dim
    n_heads: int
    head_dim: int
    n_groups: int
    d_state: int
    d_conv: int = 4
    chunk: int = 128                # the published scan chunk (rows)
    in_mult: float = 1.0
    out_mult: float = 1.0
    mup: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)   # [z | x | B | C | dt]
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.d_ssm != self.n_heads * self.head_dim:
            raise ValueError(f"d_ssm {self.d_ssm} != {self.n_heads} heads x "
                             f"{self.head_dim}")
        if self.n_heads % self.n_groups:
            raise ValueError("n_heads must be a multiple of n_groups")
        if len(self.mup) != 5:
            raise ValueError("mup has one factor for each of z, x, B, C, dt")

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.n_heads

    def mup_vector(self) -> np.ndarray:
        gn = self.n_groups * self.d_state
        widths = (self.d_ssm, self.d_ssm, gn, gn, self.n_heads)
        return np.concatenate([np.full((w,), m, np.float32)
                               for w, m in zip(widths, self.mup)])


def ssm_in(p, n, spec: SSMSpec):
    """``n`` (..., hidden), the block's normed input -> float32 ``z``
    (..., d_ssm), ``xBC`` (..., conv_dim) before the convolution, and
    ``dt`` (..., n_heads) after its softplus."""
    u = jnp.dot(n * jnp.asarray(spec.in_mult, n.dtype), p["ssm_in_w"],
                preferred_element_type=jnp.float32)
    if any(m != 1.0 for m in spec.mup):
        u = u * spec.mup_vector()
    z, xbc, dt = jnp.split(u, [spec.d_ssm, spec.d_ssm + spec.conv_dim],
                           axis=-1)
    return z, xbc, jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def ssm_conv(p, xbc, tail, n_valid):
    """The causal depthwise convolution and its SiLU over ``xbc``
    (..., T, conv_dim) whose first ``n_valid`` rows are real, after the
    ``tail`` (..., d_conv - 1, conv_dim) of rows that came before.  Returns
    the convolved rows (float32) and the new tail (in ``tail``'s type): the
    last ``d_conv - 1`` real rows, the old tail's among them where the
    block is shorter than that."""
    w = p["conv_w"].astype(jnp.float32)
    k, t = w.shape[0], xbc.shape[-2]
    padded = jnp.concatenate([tail.astype(jnp.float32), xbc], axis=-2)
    out = p["conv_b"].astype(jnp.float32) + sum(
        w[j] * lax.slice_in_dim(padded, j, j + t, axis=-2) for j in range(k))
    new_tail = lax.dynamic_slice_in_dim(padded, n_valid, k - 1, axis=-2)
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def ssm_split(xbc, spec: SSMSpec):
    """Convolved ``xBC`` (..., conv_dim) -> ``x`` (..., H, P), ``B`` and
    ``C`` (..., G, N)."""
    gn = spec.n_groups * spec.d_state
    x, b, c = jnp.split(xbc, [spec.d_ssm, spec.d_ssm + gn], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (spec.n_heads, spec.head_dim)),
            b.reshape(lead + (spec.n_groups, spec.d_state)),
            c.reshape(lead + (spec.n_groups, spec.d_state)))


def ssm_out(p, y, z, spec: SSMSpec, eps: float, dtype):
    """``y`` (..., H, P) float32, the recurrence's output, and the gate
    ``z`` (..., d_ssm) -> the mixer's term of the residual (..., hidden)
    in ``dtype``."""
    lead = z.shape[:-1]
    g = (y.reshape(lead + (spec.d_ssm,)) * jax.nn.silu(z)).reshape(
        lead + (spec.n_groups, -1))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    g = g.reshape(lead + (spec.d_ssm,)) * p["ssm_norm_g"].astype(jnp.float32)
    w = p["ssm_out_w"]
    out = jnp.dot(g.astype(w.dtype), w, preferred_element_type=jnp.float32)
    return (out * spec.out_mult).astype(dtype)
