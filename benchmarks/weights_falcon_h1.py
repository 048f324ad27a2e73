"""Seeded weights for a ``falcon_h1`` stage, made on the device leaf by leaf.

The tree is the one ``reference/falcon_h1_ref.py`` reads and the model
adopts as it is (``FalconH1ForCausalLM(cfg, weights=tree)``), so the ten
gigabytes exist once::

    {"wte", "lm_head": (V, h), "lnf_g": (h,),
     "blocks": [{"ln1_g", "qkv_w" (h, (H + 2 Hkv) d), "proj_w" (H d, h),
                 "ssm_in_w" (h, 2 d_ssm + 2 G N + Hs),
                 "conv_w" (d_conv, d_ssm + 2 G N), "conv_b", "dt_bias",
                 "A_log", "D" (Hs,), "ssm_norm_g" (d_ssm,),
                 "ssm_out_w" (d_ssm, h), "ln2_g", "gate_w"/"up_w" (h, f),
                 "down_w" (f, h)}, ...]}

The initialiser (``config.json`` has none; the configuration lists it
under ``assumed``): matrices, the embedding and the head N(0, 0.02); norm
gains and ``D`` 1 + N(0, 0.02); the convolution's taps and bias U(-1, 1) /
sqrt(d_conv) (a depthwise ``nn.Conv1d``'s default: N(0, 0.02) taps would
leave the bias alone to speak); ``A_log = log U[1, 16]`` and ``dt_bias`` the
inverse softplus of a step drawn LOG-uniform in [1e-3, 1e-1], as the
published Mamba-2 code draws both (``A_init_range`` (1, 16), ``dt_min``
0.001, ``dt_max`` 0.1): ``dt A`` then runs from 0.001 to 1.6 a row with a
median near 0.06, so a tenth of the heads remember hundreds of rows (there
a state kept in fewer bits than the configuration states drifts row after
row, and the check's comparison of the state sees it), others a few, and
no term of the block is a no-op the comparison could miss.  ``dt_bias``, ``A_log`` and ``D`` stay float32 (96
numbers a layer); every other leaf is rounded to the serving type as it is
drawn, one jitted draw a leaf, so making the tree never needs a second copy
of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
F32_LEAVES = ("dt_bias", "A_log", "D")


def sizes(config: dict) -> dict:
    """The sizes a job needs, from the configuration's published keys."""
    return dict(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        ffn=config["intermediate_size"], vocab=config["vocab_size"],
        d_ssm=config["mamba_d_ssm"], ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_groups=config["mamba_n_groups"], d_state=config["mamba_d_state"],
        d_conv=config["mamba_d_conv"], ssm_chunk=config["mamba_chunk_size"],
        theta=config["rope_theta"], eps=config["rms_norm_eps"])


def leaf_shapes(sz: dict) -> dict:
    """name -> (shape, kind) of one block's leaves."""
    h, f, d = sz["hidden"], sz["ffn"], sz["head_dim"]
    conv = sz["d_ssm"] + 2 * sz["ssm_groups"] * sz["d_state"]
    hs = sz["ssm_heads"]
    return {
        "ln1_g": ((h,), "gain"),
        "qkv_w": ((h, (sz["heads"] + 2 * sz["kv_heads"]) * d), "matrix"),
        "proj_w": ((sz["heads"] * d, h), "matrix"),
        "ssm_in_w": ((h, sz["d_ssm"] + conv + hs), "matrix"),
        "conv_w": ((sz["d_conv"], conv), "conv"),
        "conv_b": ((conv,), "conv"),
        "dt_bias": ((hs,), "dt_bias"), "A_log": ((hs,), "A_log"),
        "D": ((hs,), "gain"),
        "ssm_norm_g": ((sz["d_ssm"],), "gain"),
        "ssm_out_w": ((sz["d_ssm"], h), "matrix"),
        "ln2_g": ((h,), "gain"),
        "gate_w": ((h, f), "matrix"), "up_w": ((h, f), "matrix"),
        "down_w": ((f, h), "matrix"),
    }


@functools.partial(jax.jit,
                   static_argnames=("shape", "kind", "d_conv", "dtype"))
def _draw(key, *, shape, kind, d_conv, dtype):
    f32 = jnp.float32
    if kind == "matrix":
        x = STD * jax.random.normal(key, shape, f32)
    elif kind == "gain":
        x = 1.0 + STD * jax.random.normal(key, shape, f32)
    elif kind == "conv":
        x = jax.random.uniform(key, shape, f32, -1.0, 1.0) / d_conv ** 0.5
    elif kind == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                        jnp.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) == dt
    else:
        raise ValueError(f"leaf kind {kind!r}")
    return x.astype(dtype)


def make(config: dict, seed: int, dtype) -> dict:
    """The whole tree for ``config`` from ``seed``, in ``dtype``, on the
    default device."""
    sz, dtype = sizes(config), jnp.dtype(dtype)
    shapes = leaf_shapes(sz)
    keys = iter(jax.random.split(jax.random.key(seed, impl="rbg"),
                                 3 + sz["layers"] * len(shapes)))

    def draw(name, shape, kind):
        return _draw(next(keys), shape=shape, kind=kind, d_conv=sz["d_conv"],
                     dtype=jnp.dtype(jnp.float32) if name in F32_LEAVES
                     else dtype)

    table = (sz["vocab"], sz["hidden"])
    return {"wte": draw("wte", table, "matrix"),
            "lm_head": draw("lm_head", table, "matrix"),
            "lnf_g": draw("lnf_g", (sz["hidden"],), "gain"),
            "blocks": [{name: draw(name, shape, kind)
                        for name, (shape, kind) in shapes.items()}
                       for _ in range(sz["layers"])]}
