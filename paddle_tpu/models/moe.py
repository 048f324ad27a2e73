"""A sparse expert layer that is told which experts it holds.

Expert parallelism divides a layer's routed experts over chips; each chip
routes every token over ALL experts (the router is whole everywhere), then
computes the part of the result its own experts give.  ``MoESpec.held =
(first, count)`` names this chip's share: ``gate_w[i]`` is expert
``first + i``.  Weights are normalised over the ``top_k`` selected experts
BEFORE the absent ones are dropped, so the parts the chips compute add up
to the whole layer; the exchange that would gather them is not modelled
here (nothing stands in for the absent chips).

Selection is sigmoid scores, top-k, optionally renormalised (DeepSeek-V3 /
``cohere2_moe``'s ``expert_selection_fn: sigmoid`` with ``norm_topk_prob``).
Shared experts run on every token and are averaged.

The held experts are computed by a MASKED DENSE contraction: every held
expert sees every row and the router weight (zero where the expert was not
chosen) does the selection.  No token is ever dropped whatever the
imbalance, the shapes are static, and the expert weights are read where
they lie, ``(E, h, f)`` with the expert as the batch dimension, with no
gather, re-layout or copy of them per dispatch.  At serving batch sizes
the layer is bound by the bytes of the expert weights, which a grouped
contraction would read just the same.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int                  # the router's width: every expert
    top_k: int
    held: Tuple[int, int]           # (first, count) of the experts held here
    n_shared: int = 0
    norm_topk: bool = True

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.held} outside the "
                             f"router's {self.n_experts} experts")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")


def route(n, router_w, spec: MoESpec):
    """(T, held) float32 weights of the held experts for rows ``n`` (T, h):
    ``s_e / sum_S s`` where expert ``e`` is among the row's ``top_k``
    largest sigmoid scores, else 0."""
    s = jax.nn.sigmoid(jnp.dot(n, router_w,
                               preferred_element_type=jnp.float32))
    top = jax.lax.top_k(s, spec.top_k)[0]
    w = jnp.where(s >= top[:, -1:], s, 0.0)
    if spec.norm_topk:
        w = w / jnp.sum(top, axis=-1, keepdims=True)
    first, count = spec.held
    return w[:, first:first + count]


def experts(n, gate, up, down):
    """Every expert of a stack on every row: ``n`` (T, h), ``gate``/``up``
    (E, h, f), ``down`` (E, f, h) -> (E, T, h) of ``(silu(n Wg) * n Wu)
    Wd``."""
    g = jnp.einsum("th,ehf->etf", n, gate)
    u = jnp.einsum("th,ehf->etf", n, up)
    return jnp.einsum("etf,efh->eth", jax.nn.silu(g) * u, down)


def moe_ffn(p, n, spec: MoESpec, valid: Optional[jnp.ndarray] = None):
    """The expert layer on ``n`` (..., h).  Returns ``(y, counts)``: ``y``
    like ``n``; ``counts`` (held,) int32, the rows routed to each held
    expert (``valid`` (...,) masks padded rows out of the count only)."""
    shape = n.shape
    n = n.reshape(-1, shape[-1])
    # the scope is how a compiled program's expert operations are told
    # from the rest (``metadata={op_name=".../moe_ffn/..."}`` in its HLO)
    with jax.named_scope("moe_ffn"):
        w = route(n, p["router_w"], spec)                      # (T, held)
        y = jnp.einsum("te,eth->th", w.astype(n.dtype),
                       experts(n, p["gate_w"], p["up_w"], p["down_w"]),
                       preferred_element_type=jnp.float32)
        if spec.n_shared:
            y = y + jnp.mean(
                experts(n, p["sh_gate_w"], p["sh_up_w"], p["sh_down_w"]),
                axis=0, dtype=jnp.float32)
        y = y.astype(n.dtype)
    hit = w > 0
    if valid is not None:
        hit = hit & valid.reshape(-1, 1)
    return y.reshape(shape), jnp.sum(hit, axis=0, dtype=jnp.int32)
