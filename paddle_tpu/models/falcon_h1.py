"""Falcon-H1 (``model_type: falcon_h1``): a Mamba-2 state-space mixer beside
grouped-query attention in every block, on one norm, then a gated MLP.

The layer (``x`` (T, hidden); tiiuae's ``config.json``; what it leaves
open follows the ``falcon_h1`` modelling code of ``transformers``)::

    e      = Emb[ids] * embedding_multiplier
    n      = RMSNorm(x; g_in)
    q,k,v  = a Wq, (a Wk) * key_multiplier, a Wv,   a = n * attention_in_multiplier
    q,k    rotated in the pairs (i, i + d/2) over all of the head's dims
    attn   = softmax(q k^T / sqrt(d) + causal) v Wo * attention_out_multiplier
    ssm    = the Mamba-2 mixer of n (``models/ssm.py``)
    x'     = x + attn + ssm
    m      = RMSNorm(x'; g_ff)
    x''    = x' + (silu(m Wg * mlp_multipliers[0]) * (m Wu)) Wd * mlp_multipliers[1]
    logits = RMSNorm(x_L; g_f) W_head * lm_head_multiplier      head untied

The model is described to the decode substrate by DATA:
:meth:`FalconH1ForCausalLM.layer_specs` gives one
:class:`~paddle_tpu.models.generation.LayerSpec` a layer (RMSNorm,
rotate-half positions, the multipliers, the gated MLP and the mixer's
:class:`~paddle_tpu.models.ssm.SSMSpec`) and :meth:`decoder_params` the
parameter tree; ``ServingEngine`` serves it through the same two programs
as GPT-2, each slot's recurrent state in a slab beside its KV pages.
:meth:`logits` is the whole eager forward (dense attention, the sequential
recurrence, no cache) built from the same substrate functions.  No path
trains it.

``FalconH1ForCausalLM(cfg, weights=tree)`` adopts an existing tree leaf by
leaf and never materialises initial values of its own (10 GB at the
benchmark's sizes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..kernels import ssd
from ..nn.layer_base import EagerParameter
from .generation import (LayerSpec, MuP, _block_finish, _block_qkv, _embed,
                         _lm_head, _norm, dense_attention)
from .ssm import SSMSpec, ssm_conv, ssm_in, ssm_out, ssm_split


@dataclasses.dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    max_seq_len: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"
    ssm_state_dtype: str = "float32"

    @property
    def layer_norm_eps(self) -> float:      # the name the substrate reads
        return self.rms_norm_eps

    @property
    def ssm(self) -> SSMSpec:
        return SSMSpec(
            d_ssm=self.mamba_d_ssm, n_heads=self.mamba_n_heads,
            head_dim=self.mamba_d_head, n_groups=self.mamba_n_groups,
            d_state=self.mamba_d_state, d_conv=self.mamba_d_conv,
            chunk=self.mamba_chunk_size, in_mult=self.ssm_in_multiplier,
            out_mult=self.ssm_out_multiplier,
            mup=tuple(float(m) for m in self.ssm_multipliers),
            state_dtype=self.ssm_state_dtype)

    def leaf_shapes(self) -> dict:
        """name -> (shape, kind) of one block's leaves; ``kind`` says how
        a leaf is drawn (:func:`init_leaf`).  ``wte``, ``lm_head`` (both
        (V, h)) and ``lnf_g`` lie outside the blocks."""
        h, f, d, s = (self.hidden_size, self.intermediate_size,
                      self.head_dim, self.ssm)
        return {
            "ln1_g": ((h,), "gain"),
            "qkv_w": ((h, (self.num_heads + 2 * self.num_kv_heads) * d),
                      "matrix"),
            "proj_w": ((self.num_heads * d, h), "matrix"),
            "ssm_in_w": ((h, s.in_dim), "matrix"),
            "conv_w": ((s.d_conv, s.conv_dim), "conv"),
            "conv_b": ((s.conv_dim,), "conv"),
            "dt_bias": ((s.n_heads,), "dt_bias"),
            "A_log": ((s.n_heads,), "A_log"),
            "D": ((s.n_heads,), "gain"),
            "ssm_norm_g": ((s.d_ssm,), "gain"),
            "ssm_out_w": ((s.d_ssm, h), "matrix"),
            "ln2_g": ((h,), "gain"),
            "gate_w": ((h, f), "matrix"), "up_w": ((h, f), "matrix"),
            "down_w": ((f, h), "matrix"),
        }


#: leaves kept in float32 whatever the serving type: the recurrence's own
#: parameters, 96 numbers a layer
F32_LEAVES = ("dt_bias", "A_log", "D")


def init_leaf(key, shape, kind: str, std: float = 0.02, d_conv: int = 4):
    """One leaf's float32 values.  ``matrix`` N(0, std); ``gain`` 1 + N(0,
    std); ``conv`` U(-1, 1) / sqrt(d_conv) (a depthwise ``Conv1d``'s
    default); ``A_log`` log U[1, 16]; ``dt_bias`` the inverse softplus of a
    step drawn log-uniform in [1e-3, 1e-1] (both as the published Mamba-2
    code draws them): some heads remember a few rows, some hundreds, and
    no term of the block is a no-op."""
    f32 = jnp.float32
    if kind == "matrix":
        return std * jax.random.normal(key, shape, f32)
    if kind == "gain":
        return 1.0 + std * jax.random.normal(key, shape, f32)
    if kind == "conv":
        return jax.random.uniform(key, shape, f32, -1.0, 1.0) / d_conv ** 0.5
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                        jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"leaf kind {kind!r}")


class FalconH1ForCausalLM(nn.Layer):
    """The language model.  Parameters are registered flat
    (``blocks.<l>.<leaf>``, ``wte``, ``lm_head``, ``lnf_g``)."""

    def __init__(self, cfg: FalconH1Config, weights: Optional[dict] = None,
                 seed: int = 0):
        super().__init__()
        self.cfg = cfg
        shapes = cfg.leaf_shapes()
        if weights is None:
            weights = self._init_tree(shapes, seed)
        table = (cfg.vocab_size, cfg.hidden_size)
        for name, shape in (("wte", table), ("lm_head", table),
                            ("lnf_g", (cfg.hidden_size,))):
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
                                     3 + cfg.num_layers * len(shapes)))

        def draw(name, shape, kind):
            leaf = init_leaf(next(keys), shape, kind, std, cfg.mamba_d_conv)
            return leaf if name in F32_LEAVES else leaf.astype(dtype)

        table = (cfg.vocab_size, cfg.hidden_size)
        return {"wte": draw("wte", table, "matrix"),
                "lm_head": draw("lm_head", table, "matrix"),
                "lnf_g": draw("lnf_g", (cfg.hidden_size,), "gain"),
                "blocks": [{n: draw(n, s, k) for n, (s, k) in shapes.items()}
                           for _ in range(cfg.num_layers)]}

    # -- the description the decode substrate reads ----------------------

    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        cfg = self.cfg
        spec = LayerSpec(
            norm="rms", norm_bias=False, position="rope_half",
            rope_theta=cfg.rope_theta, head_dim=cfg.head_dim,
            mlp="gated_silu", ssm=cfg.ssm,
            mup=MuP(embedding=cfg.embedding_multiplier,
                    attn_in=cfg.attention_in_multiplier,
                    key=cfg.key_multiplier,
                    attn_out=cfg.attention_out_multiplier,
                    mlp_gate=cfg.mlp_multipliers[0],
                    mlp_down=cfg.mlp_multipliers[1],
                    head=cfg.lm_head_multiplier))
        return (spec,) * cfg.num_layers

    def decoder_params(self) -> dict:
        """The tree the programs take: the parameters' own arrays."""
        ps = self._parameters
        return {"wte": ps["wte"]._array, "lm_head": ps["lm_head"]._array,
                "lnf_g": ps["lnf_g"]._array,
                "blocks": [{n: ps[f"blocks.{li}.{n}"]._array
                            for n in self.cfg.leaf_shapes()}
                           for li in range(self.cfg.num_layers)]}

    # -- eager forward ---------------------------------------------------

    def logits(self, ids) -> jnp.ndarray:
        """``ids`` (B, T) int -> float32 logits (B, T, V): the whole
        forward, dense attention, the recurrence row by row from a zero
        state, no cache."""
        cfg, p = self.cfg, self.decoder_params()
        ids = jnp.asarray(ids, jnp.int32)
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), ids.shape)
        specs = self.layer_specs()
        eps, s = cfg.layer_norm_eps, cfg.ssm
        x = _embed(p, ids, pos, specs[0])
        for spec, bp in zip(specs, p["blocks"]):
            q, k, v = _block_qkv(bp, x, cfg.num_heads, eps,
                                 n_kv_heads=cfg.num_kv_heads, spec=spec,
                                 pos=pos)
            out = dense_attention(q, k, v)
            z, xbc, dt = ssm_in(bp, _norm(bp, "ln1", x, eps, spec), s)
            tail = jnp.zeros((b, s.d_conv - 1, s.conv_dim), x.dtype)
            xs, bm, cm = ssm_split(ssm_conv(bp, xbc, tail, t)[0], s)
            a = -jnp.exp(bp["A_log"].astype(jnp.float32))
            d = bp["D"].astype(jnp.float32)
            zero = jnp.zeros((s.n_heads, s.head_dim, s.d_state), jnp.float32)
            y = jax.vmap(lambda x1, dt1, b1, c1: ssd.scan_rows(
                zero, x1, dt1, a, b1, c1, d)[0])(xs, dt, bm, cm)
            x = _block_finish(bp, x, out.astype(x.dtype), eps, spec=spec,
                              mix=ssm_out(bp, y, z, s, eps, x.dtype))
        return _lm_head(p, x, eps, specs[-1])

    def forward(self, ids):
        from ..dygraph.tensor import Tensor

        arr = ids._array if isinstance(ids, Tensor) else ids
        return Tensor(self.logits(arr), stop_gradient=True)
