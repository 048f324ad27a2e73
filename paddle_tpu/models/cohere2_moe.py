"""Command A+ (``model_type: cohere2_moe``): a parallel attention + sparse
expert block, three sliding-window layers in four.

The layer (``x`` (T, hidden), layer ``l``; CohereLabs' ``config.json``)::

    n     = LayerNorm(x), gain only                     one norm feeds both
    q,k,v = n Wq, n Wk, n Wv                            no bias, no qk-norm
    sliding (l % layer_switch != layer_switch - 1):
            q, k rotated in interleaved pairs (rope_gptj), theta, all dims;
            key j seen by query i iff i - window < j <= i
    full:   no positional encoding at all; causal mask
    attn  = softmax(q k^T / sqrt(d) + mask) v Wo        GQA, heads joined
    moe   = sum_{e in top-k, held here} w_e E_e(n) + mean_j Esh_j(n)
            (``models/moe.py``: sigmoid scores, normalised over the top-k)
    x'    = x + attn + moe                              parallel block
    logits = LayerNorm_f(x_L) Emb^T                     Emb tied

The model is described to the decode substrate by DATA:
:meth:`Cohere2MoeForCausalLM.layer_specs` gives one
:class:`~paddle_tpu.models.generation.LayerSpec` a layer (norm kind,
position kind, window or none, residual form, MLP kind) and
:meth:`decoder_params` the parameter tree; ``ServingEngine`` serves it
through the same programs as GPT-2.  :meth:`forward` is the whole eager
forward (dense attention, no cache) built from the same substrate
functions.  No path trains it: at 16 bytes a parameter one period of the
published widths is 48 GB.

``experts_held = (first, count)`` is the share of the routed experts this
chip holds (expert parallelism); ``vocab_size`` may likewise be a slice of
the published rows.  ``Cohere2MoeForCausalLM(cfg, weights=tree)`` adopts an
existing tree leaf by leaf and never materialises initial values of its
own (9 GB at the benchmark's sizes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer_base import EagerParameter
from .generation import (LayerSpec, _block_finish, _block_qkv, _lm_head,
                         dense_attention)
from .moe import MoESpec


@dataclasses.dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144            # rows held here (a slice is fine)
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 4096       # width of one routed / shared expert
    num_experts: int = 128              # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # None: all of them
    sliding_window: int = 4096
    layer_switch: int = 4               # every layer_switch-th layer is full
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.logit_scale != 1.0:
            raise ValueError("logit_scale other than 1 is not implemented")

    @property
    def moe(self) -> MoESpec:
        return MoESpec(n_experts=self.num_experts,
                       top_k=self.num_experts_per_tok,
                       held=self.experts_held,
                       n_shared=self.num_shared_experts,
                       norm_topk=self.norm_topk_prob)

    def window_of(self, layer: int) -> Optional[int]:
        """``local_attn_first``: the last layer of each period is full."""
        full = layer % self.layer_switch == self.layer_switch - 1
        return None if full else self.sliding_window

    def leaf_shapes(self) -> dict:
        """name -> (shape, mean) of one block's leaves, and of the two
        outside the blocks under ``wte`` / ``lnf_g``."""
        h, f, d = self.hidden_size, self.intermediate_size, self.head_dim
        e, s = self.experts_held[1], self.num_shared_experts
        return {
            "ln1_g": ((h,), 1.0),
            "qkv_w": ((h, (self.num_heads + 2 * self.num_kv_heads) * d), 0.0),
            "proj_w": ((self.num_heads * d, h), 0.0),
            "router_w": ((h, self.num_experts), 0.0),
            "gate_w": ((e, h, f), 0.0), "up_w": ((e, h, f), 0.0),
            "down_w": ((e, f, h), 0.0),
            "sh_gate_w": ((s, h, f), 0.0), "sh_up_w": ((s, h, f), 0.0),
            "sh_down_w": ((s, f, h), 0.0),
        }


class Cohere2MoeForCausalLM(nn.Layer):
    """The language model.  Parameters are registered flat
    (``blocks.<l>.<leaf>``, ``wte``, ``lnf_g``)."""

    def __init__(self, cfg: Cohere2MoeConfig, weights: Optional[dict] = None,
                 seed: int = 0):
        super().__init__()
        self.cfg = cfg
        shapes = cfg.leaf_shapes()
        if weights is None:
            weights = self._init_tree(shapes, seed)
        outer = {"wte": (cfg.vocab_size, cfg.hidden_size),
                 "lnf_g": (cfg.hidden_size,)}
        for name, shape in outer.items():
            self._adopt(name, weights[name], shape)
        if len(weights["blocks"]) != cfg.num_layers:
            raise ValueError("weights: wrong number of blocks")
        for li, blk in enumerate(weights["blocks"]):
            if set(blk) != set(shapes):
                raise ValueError(f"block {li}: leaves {sorted(blk)}")
            for name, (shape, _) in shapes.items():
                self._adopt(f"blocks.{li}.{name}", blk[name], shape)

    def _adopt(self, name: str, array, shape) -> None:
        if tuple(array.shape) != tuple(shape):
            raise ValueError(f"{name}: {tuple(array.shape)}, want {shape}")
        self.add_parameter(name, EagerParameter(array, trainable=False,
                                                name=name))

    def _init_tree(self, shapes: dict, seed: int) -> dict:
        cfg = self.cfg
        dtype, std = jnp.dtype(cfg.dtype), cfg.initializer_range
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     2 + cfg.num_layers * len(shapes)))

        def draw(shape, mean=0.0):
            return (mean + std * jax.random.normal(next(keys), shape,
                                                   jnp.float32)).astype(dtype)

        return {"wte": draw((cfg.vocab_size, cfg.hidden_size)),
                "lnf_g": draw((cfg.hidden_size,), 1.0),
                "blocks": [{n: draw(s, m) for n, (s, m) in shapes.items()}
                           for _ in range(cfg.num_layers)]}

    # -- the description the decode substrate reads ----------------------

    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        cfg = self.cfg
        return tuple(
            LayerSpec(norm_bias=False, head_dim=cfg.head_dim, parallel=True,
                      position="none" if cfg.window_of(li) is None else "rope",
                      rope_theta=cfg.rope_theta, window=cfg.window_of(li),
                      moe=cfg.moe)
            for li in range(cfg.num_layers))

    def decoder_params(self) -> dict:
        """The tree the programs take: the parameters' own arrays."""
        ps = self._parameters
        return {"wte": ps["wte"]._array, "lnf_g": ps["lnf_g"]._array,
                "blocks": [{n: ps[f"blocks.{li}.{n}"]._array
                            for n in self.cfg.leaf_shapes()}
                           for li in range(self.cfg.num_layers)]}

    # -- eager forward ---------------------------------------------------

    def logits(self, ids) -> jnp.ndarray:
        """``ids`` (B, T) int -> float32 logits (B, T, V): the whole
        forward, dense attention, no cache."""
        cfg, p = self.cfg, self.decoder_params()
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                               ids.shape)
        x = p["wte"][ids]
        specs = self.layer_specs()
        for spec, bp in zip(specs, p["blocks"]):
            q, k, v = _block_qkv(bp, x, cfg.num_heads, cfg.layer_norm_eps,
                                 n_kv_heads=cfg.num_kv_heads, spec=spec,
                                 pos=pos)
            out = dense_attention(q, k, v, window=spec.window)
            x = _block_finish(bp, x, out.astype(x.dtype), cfg.layer_norm_eps,
                              spec=spec)
        return _lm_head(p, x, cfg.layer_norm_eps, specs[-1])

    def forward(self, ids):
        from ..dygraph.tensor import Tensor

        arr = ids._array if isinstance(ids, Tensor) else ids
        return Tensor(self.logits(arr), stop_gradient=True)
