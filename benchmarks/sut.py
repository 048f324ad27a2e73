"""The one place the benchmark touches the system under test.

Everything here is a call into ``paddle_tpu`` through the entry points a
user calls (``GPTForPretraining``, ``build_functional_train_step``,
``ServingEngine``, ``build_hybrid_mesh``).  Only the *sizes* a configuration
or a cell fixes are passed; every other argument stays at the program's
default, so a PR that improves a default shows in the numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def configure_compile_cache() -> str:
    """The program's own rule (``utils/compile_cache.py``): the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` at the root of
    the checkout.  Small programs are cached too, so a second run of a cell
    compiles nothing."""
    from paddle_tpu.utils import compile_cache

    path = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build_mesh(mesh: dict | None):
    """Install the hybrid mesh a cell names (``{"dp": 2, "mp": 2}``)."""
    if not mesh:
        return None
    from paddle_tpu.distributed import mesh as mesh_mod

    return mesh_mod.build_hybrid_mesh(**mesh)


def build_model(sz: dict, *, parallel: bool, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    return GPTForPretraining(GPTConfig(
        vocab_size=sz["padded_vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"], ffn_hidden=sz["ffn"],
        max_seq_len=sz["positions"], dropout=0.0, layer_norm_eps=sz["eps"],
        use_parallel=parallel))


def load_weights(model, w: dict) -> None:
    """Put the benchmark's seeded tree into the model, each leaf in the
    placement the model gave its own (tensor-parallel layers keep their
    mesh sharding)."""
    gpt = model.gpt
    pairs = [(gpt.embeddings.word_embeddings.weight, w["wte"]),
             (gpt.embeddings.position_embeddings.weight, w["wpe"]),
             (gpt.ln_f.weight, w["lnf_g"]), (gpt.ln_f.bias, w["lnf_b"])]
    for blk, p in zip(gpt.blocks, w["blocks"]):
        pairs += [
            (blk.ln1.weight, p["ln1_g"]), (blk.ln1.bias, p["ln1_b"]),
            (blk.attn.qkv.weight, p["qkv_w"]), (blk.attn.qkv.bias, p["qkv_b"]),
            (blk.attn.proj.weight, p["proj_w"]),
            (blk.attn.proj.bias, p["proj_b"]),
            (blk.ln2.weight, p["ln2_g"]), (blk.ln2.bias, p["ln2_b"]),
            (blk.mlp.fc1.weight, p["fc1_w"]), (blk.mlp.fc1.bias, p["fc1_b"]),
            (blk.mlp.fc2.weight, p["fc2_w"]), (blk.mlp.fc2.bias, p["fc2_b"])]
    assert len(pairs) == len(list(model.parameters())), "a leaf was missed"
    for param, new in pairs:
        assert tuple(param.shape) == tuple(new.shape), (param.shape, new.shape)
        old = param._array
        param._array = (jax.device_put(new, old.sharding)
                        if len(old.sharding.device_set) > 1 else new)


def build_train_step(model, **job_args):
    from paddle_tpu.models.gpt import build_functional_train_step

    return build_functional_train_step(model, **job_args)


def shard_batch(arr, mesh):
    if mesh is None:
        return jnp.asarray(arr)
    from paddle_tpu.distributed import mesh as mesh_mod

    return mesh_mod.shard_batch(arr)


def traced_kernels(jitted, *args) -> dict:
    """{(kernel name, interpreted): count} of the Pallas dispatches in the
    program ``jitted`` traces for ``args``."""
    from paddle_tpu.analysis.jaxpr_audit import pallas_kernels

    return dict(pallas_kernels(jitted.trace(*args).jaxpr))


def build_engine(model, *, sizes: dict, seed: int, on_token):
    from paddle_tpu.serving import ServingEngine

    return ServingEngine(model, seed=seed, on_token=on_token, **sizes)
