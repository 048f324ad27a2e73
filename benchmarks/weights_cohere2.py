"""Seeded weights for a ``cohere2_moe`` share, made on the device leaf by
leaf.

The tree is the one ``reference/cohere2_moe_ref.py`` reads and the model
adopts as it is (``Cohere2MoeForCausalLM(cfg, weights=tree)``), so the nine
gigabytes exist once::

    {"wte": (V, h), "lnf_g": (h,),
     "blocks": [{"ln1_g", "qkv_w" (h, (H + 2 Hkv) d), "proj_w" (H d, h),
                 "router_w" (h, E), "gate_w"/"up_w" (held, h, f),
                 "down_w" (held, f, h), "sh_gate_w"/"sh_up_w" (S, h, f),
                 "sh_down_w" (S, f, h)}, ...]}

Matrices and the embedding are N(0, 0.02), norm gains 1 + N(0, 0.02), so that
no term of the block is a no-op the comparison with the reference could
miss.  Each leaf is one jitted draw (one program per distinct shape): its
float32 values exist only until they are rounded to the serving type, one
leaf at a time, so making the tree never needs a second copy of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def sizes(config: dict) -> dict:
    """The sizes a job needs, from the configuration's published keys."""
    return dict(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_width=config["intermediate_size"],
        router_width=config["held"]["router_width"],
        experts_held=tuple(config["held"]["experts_held"]),
        top_k=config["num_experts_per_tok"],
        shared=config["num_shared_experts"], vocab=config["vocab_size"],
        window=config["sliding_window"], period=config["layer_switch"],
        theta=config["rope_theta"], eps=config["layer_norm_eps"],
        layer_types=config["layer_types"][:config["num_hidden_layers"]])


def leaf_shapes(sz: dict) -> dict:
    """name -> (shape, mean) of one block's leaves."""
    h, f, d = sz["hidden"], sz["expert_width"], sz["head_dim"]
    e, s = sz["experts_held"][1], sz["shared"]
    return {
        "ln1_g": ((h,), 1.0),
        "qkv_w": ((h, (sz["heads"] + 2 * sz["kv_heads"]) * d), 0.0),
        "proj_w": ((sz["heads"] * d, h), 0.0),
        "router_w": ((h, sz["router_width"]), 0.0),
        "gate_w": ((e, h, f), 0.0), "up_w": ((e, h, f), 0.0),
        "down_w": ((e, f, h), 0.0),
        "sh_gate_w": ((s, h, f), 0.0), "sh_up_w": ((s, h, f), 0.0),
        "sh_down_w": ((s, f, h), 0.0),
    }


@functools.partial(jax.jit, static_argnames=("shape", "mean", "dtype"))
def _draw(key, *, shape, mean, dtype):
    return (mean + STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def make(config: dict, seed: int, dtype) -> dict:
    """The whole tree for ``config`` from ``seed``, in ``dtype``, on the
    default device."""
    sz, dtype = sizes(config), jnp.dtype(dtype)
    shapes = leaf_shapes(sz)
    keys = iter(jax.random.split(jax.random.key(seed, impl="rbg"),
                                 2 + sz["layers"] * len(shapes)))

    def draw(shape, mean=0.0):
        return _draw(next(keys), shape=shape, mean=mean, dtype=dtype)

    return {"wte": draw((sz["vocab"], sz["hidden"])),
            "lnf_g": draw((sz["hidden"],), 1.0),
            "blocks": [{name: draw(shape, mean)
                        for name, (shape, mean) in shapes.items()}
                       for _ in range(sz["layers"])]}
