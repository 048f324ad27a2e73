"""Seeded weights, made on the device in one jitted call.

The tree is the one ``reference/gpt2_ref.py`` reads::

    {"wte": (V, h), "wpe": (P, h), "lnf_g": (h,), "lnf_b": (h,),
     "blocks": [{"ln1_g", "ln1_b", "qkv_w" (h, 3h), "qkv_b", "proj_w" (h, h),
                 "proj_b", "ln2_g", "ln2_b", "fc1_w" (h, f), "fc1_b",
                 "fc2_w" (f, h), "fc2_b"}, ...]}

Linear weights are (in, out).  Matrices and embeddings are N(0, 0.02) as
GPT-2 initialises them; biases and LayerNorm offsets are N(0, 0.02) too and
LayerNorm gains 1 + N(0, 0.02), so that no term of the block is a no-op the
comparison with the reference could miss.  Vocabulary rows past the
published size (the padding to a multiple of 128) are zero: their logit is
0, far below the maximum, so neither traffic nor greedy sampling ever
produces them.

Each kind of block leaf is drawn once at shape (layers, ...) and sliced, so
the program holds a dozen generators whatever the depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def sizes(config: dict) -> dict:
    """The sizes a job needs, from a configuration file's published keys."""
    h = config["n_embd"]
    return dict(
        hidden=h, layers=config["n_layer"], heads=config["n_head"],
        ffn=config.get("n_inner") or 4 * h, positions=config["n_positions"],
        vocab=config["vocab_size"],
        padded_vocab=config.get("changed", {}).get("padded_vocab_size",
                                                   config["vocab_size"]),
        eps=config["layer_norm_epsilon"])


@functools.partial(jax.jit, static_argnames=(
    "hidden", "layers", "ffn", "positions", "vocab", "padded_vocab", "dtype"))
def _make(key, *, hidden, layers, ffn, positions, vocab, padded_vocab, dtype):
    h, f, n = hidden, ffn, layers
    kinds = {  # name -> (shape of one layer's leaf, mean)
        "ln1_g": ((h,), 1.0), "ln1_b": ((h,), 0.0),
        "qkv_w": ((h, 3 * h), 0.0), "qkv_b": ((3 * h,), 0.0),
        "proj_w": ((h, h), 0.0), "proj_b": ((h,), 0.0),
        "ln2_g": ((h,), 1.0), "ln2_b": ((h,), 0.0),
        "fc1_w": ((h, f), 0.0), "fc1_b": ((f,), 0.0),
        "fc2_w": ((f, h), 0.0), "fc2_b": ((h,), 0.0),
    }
    keys = jax.random.split(key, len(kinds) + 4)

    def draw(k, shape, mean=0.0):
        return (mean + STD * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    stacked = {name: draw(k, (n,) + shape, mean)
               for k, (name, (shape, mean)) in zip(keys, kinds.items())}
    blocks = [{name: arr[i] for name, arr in stacked.items()}
              for i in range(n)]
    wte = draw(keys[-4], (padded_vocab, h))
    wte = jnp.where(jnp.arange(padded_vocab)[:, None] < vocab, wte, 0)
    return {"wte": wte, "wpe": draw(keys[-3], (positions, h)),
            "lnf_g": draw(keys[-2], (h,), 1.0), "lnf_b": draw(keys[-1], (h,)),
            "blocks": blocks}


def make(config: dict, seed: int, dtype) -> dict:
    """The whole tree for ``config`` from ``seed``, in ``dtype``, on the
    default device."""
    sz = sizes(config)
    sz.pop("heads")
    sz.pop("eps")
    key = jax.random.key(seed, impl="rbg")
    return _make(key, dtype=jnp.dtype(dtype), **sz)
