"""Pallas flash-attention kernel vs the jnp reference (interpret mode on CPU).

Parity role: numeric checks of the fused attention kernel against the
unfused composition — OpTest-style (SURVEY.md §4) but for the Pallas tier.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.attention import _sdpa_reference
from paddle_tpu.kernels import flash


def _rand_qkv(rng, b, h, s, d, dtype="float32"):
    q = rng.randn(b, h, s, d).astype(dtype)
    k = rng.randn(b, h, s, d).astype(dtype)
    v = rng.randn(b, h, s, d).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,d", [(64, 32), (128, 64)])
def test_flash_forward_matches_reference(causal, s, d):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, 2, 3, s, d)
    out = flash.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _sdpa_reference(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, 1, 2, 64, 32)

    def loss_flash(q, k, v):
        o = flash.flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = _sdpa_reference(q, k, v, is_causal=causal)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_flash_custom_scale():
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, 1, 1, 64, 32)
    out = flash.flash_attention(q, k, v, scale=0.5, interpret=True)
    ref = _sdpa_reference(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_under_jit_and_vmapless_batch():
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, 4, 2, 64, 16)

    @jax.jit
    def f(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, interpret=True)

    out = f(q, k, v)
    ref = _sdpa_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_supported_gate():
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, 1, 1, 64, 16)
    assert flash.supported(q, k)
    assert not flash.supported(q, k, mask=jnp.zeros((64, 64)))
    assert not flash.supported(q, k, dropout_p=0.1)
    q65 = jnp.asarray(rng.randn(1, 1, 65, 16).astype("float32"))
    assert not flash.supported(q65, q65)


def test_sdpa_dispatch_uses_flash_seamlessly(monkeypatch):
    """The nn.functional path must route through the flash kernel when the
    gate opens, and produce the reference math (interpret mode on CPU)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    calls = []
    real_flash_attention = flash.flash_attention

    def spy(q, k, v, **kw):
        calls.append(q.shape)
        return real_flash_attention(q, k, v, **kw)

    monkeypatch.setattr(flash, "available", lambda: True)
    monkeypatch.setattr(flash, "flash_attention", spy)

    rng = np.random.RandomState(5)
    qn = rng.randn(2, 2, 512, 32).astype("float32")
    q = paddle.to_tensor(qn)
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True, training=False)
    assert calls, "flash path was not taken by the dispatcher"
    ref = _sdpa_reference(jnp.asarray(qn), jnp.asarray(qn), jnp.asarray(qn),
                          is_causal=True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_sdpa_flash_runs_per_shard_under_a_gspmd_mesh(monkeypatch):
    """GSPMD cannot partition a Pallas custom call: bare inside a dp x mp
    partitioned jit it would gather q/k/v and run every head of every batch
    row on every device.  Under a multi-device mesh the dispatcher wraps the
    kernel in shard_map over the batch and head axes, so the train step's
    pallas_calls see the LOCAL (batch/dp * heads/mp, seq, d) shard, and the
    loss equals the single-device one."""
    from paddle_tpu.analysis.jaxpr_audit import iter_eqns, pallas_kernels
    from paddle_tpu.distributed import mesh as mesh_mod
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.models.gpt import GPTConfig, build_functional_train_step

    monkeypatch.setattr(flash, "available", lambda: True)
    dims = dict(vocab_size=256, hidden_size=64, num_layers=1, num_heads=4,
                max_seq_len=512, dropout=0.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (4, 512)).astype("int32")
    labels = rng.randint(0, 256, (4, 512)).astype("int64")

    def first_loss(parallel):
        paddle.seed(0)
        model = GPTForPretraining(GPTConfig(**dims, use_parallel=parallel))
        step, params, opt = build_functional_train_step(
            model, lr=1e-3, remat=False, ce_chunk_rows=0)
        feed = [mesh_mod.shard_batch(a) if parallel else a
                for a in (ids, labels)]
        jaxpr = step.trace(params, opt, *feed).jaxpr
        assert sorted(n for n, _ in pallas_kernels(jaxpr)) == [
            "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
        shapes = {tuple(e.invars[0].aval.shape) for e in iter_eqns(jaxpr)
                  if e.primitive.name == "pallas_call"}
        return float(step(params, opt, *feed)[2]), shapes

    ref, global_shapes = first_loss(False)
    assert global_shapes == {(4 * 4, 512, 16)}
    mesh_mod.build_hybrid_mesh(dp=2, mp=2)
    loss, local_shapes = first_loss(True)
    assert local_shapes == {(2 * 2, 512, 16)}
    np.testing.assert_allclose(loss, ref, rtol=1e-5)


def test_sdpa_dispatch_falls_back_on_unsupported_shape(monkeypatch):
    """Odd seq lens must take the reference path, not crash (supported() gate)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    monkeypatch.setattr(flash, "available", lambda: True)
    rng = np.random.RandomState(6)
    qn = rng.randn(1, 2, 700, 16).astype("float32")
    q = paddle.to_tensor(qn)
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True, training=False)
    ref = _sdpa_reference(jnp.asarray(qn), jnp.asarray(qn), jnp.asarray(qn),
                          is_causal=True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bsnd_seq_major_matches_bnsd():
    """Seq-major specs (no transposes around the kernel) == the bnsd path,
    forward AND gradients."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import flash

    rng = np.random.RandomState(0)
    b, s, nh, d = 2, 128, 3, 32
    q = jnp.asarray(rng.randn(b, s, nh, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, s, nh, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, s, nh, d).astype("float32"))

    def f_bsnd(q, k, v):
        return jnp.sum(flash.flash_attention(
            q, k, v, causal=True, layout="bsnd", interpret=True) ** 2)

    def f_bnsd(q, k, v):
        qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        out = flash.flash_attention(qt, kt, vt, causal=True, interpret=True)
        return jnp.sum(jnp.swapaxes(out, 1, 2) ** 2)

    np.testing.assert_allclose(np.asarray(f_bsnd(q, k, v)),
                               np.asarray(f_bnsd(q, k, v)), rtol=2e-5)
    g1 = jax.grad(f_bsnd, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_bnsd, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def _call_flash(q, layout):
    return flash.flash_attention(q, q, q, causal=True, layout=layout,
                                 interpret=True)


def _call_sdpa(q, layout):
    from paddle_tpu.kernels.attention import sdpa

    return sdpa(q, q, q, is_causal=True, layout=layout)


def _call_functional(q, layout):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    t = paddle.to_tensor(np.asarray(q))
    return F.scaled_dot_product_attention(t, t, t, is_causal=True,
                                          training=False, layout=layout)


@pytest.mark.parametrize("layout", ["sbnd", "no-such-layout"])
@pytest.mark.parametrize("entry", [_call_flash, _call_sdpa, _call_functional],
                         ids=["flash_attention", "sdpa",
                              "F.scaled_dot_product_attention"])
def test_unknown_layout_is_refused(entry, layout):
    """A layout no kernel implements is an error that names the accepted
    ones at every entry, never a quiet computation in another layout (on a
    4-D array any permutation of the axes has a valid shape)."""
    q = jnp.zeros((1, 16, 16, 16), jnp.float32)
    assert not flash.supported(q, q, layout=layout)
    with pytest.raises(ValueError, match="accepted layouts.*bnsd.*bsnd"):
        entry(q, layout)
    for known in flash.LAYOUTS:     # the same calls with a known layout run
        assert np.asarray(entry(q, known)).shape == q.shape
