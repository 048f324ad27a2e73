"""The GPT training path: the criterion's ``loss_mask``, the chunked
cross-entropy of the functional train step (``ce_chunk_rows > 0``, the
default both train cells run) against the plain one, and the eager
tensor-parallel model against one device."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import (
    GPTConfig,
    GPTForPretraining,
    GPTPretrainingCriterion,
    build_functional_train_step,
)

CFG = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
           max_seq_len=64, dropout=0.0)


def _model(**extra):
    paddle.seed(0)
    return GPTForPretraining(GPTConfig(**CFG, **extra))


def _data(b=4, s=16):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, CFG["vocab_size"], (b, s)).astype("int32")
    labels = rng.randint(0, CFG["vocab_size"], (b, s)).astype("int64")
    return ids, labels


def _token_losses(logits, labels):
    """Per-token softmax cross-entropy in float64 numpy."""
    z = logits.astype("float64")
    z = z - z.max(-1, keepdims=True)
    lse = np.log(np.exp(z).sum(-1))
    return lse - np.take_along_axis(z, labels[..., None], -1)[..., 0]


def test_criterion_loss_mask():
    """``loss_mask`` gives the mean over the kept tokens only; with nothing
    kept the denominator is clamped to one, so the loss is 0 and finite."""
    ids, labels = _data()
    logits = _model()(paddle.to_tensor(ids))
    per_token = _token_losses(logits.numpy(), labels)
    crit = GPTPretrainingCriterion()

    def masked(mask):
        return float(crit(logits, paddle.to_tensor(labels),
                          paddle.to_tensor(mask)).numpy())

    mask = (np.random.RandomState(7).rand(*labels.shape) > 0.3) \
        .astype("float32")
    assert 0 < mask.sum() < mask.size
    np.testing.assert_allclose(masked(mask),
                               (per_token * mask).sum() / mask.sum(),
                               rtol=1e-5)
    np.testing.assert_allclose(masked(np.ones_like(mask)), per_token.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(
        float(crit(logits, paddle.to_tensor(labels)).numpy()),
        per_token.mean(), rtol=1e-5)
    assert masked(np.zeros_like(mask)) == 0.0


# rows = 4 x 16 = 64: 32 divides them; 48 does not, and the step then halves
# the chunk until it does (24, 12, 6, 3, 1: one row a chunk)
@pytest.mark.parametrize("rows", [32, 48])
def test_chunked_ce_matches_plain(rows):
    """Chunked cross-entropy is the plain one computed a few rows at a
    time: the same loss and, after two AdamW steps, the same parameters.
    Both sides are float32 and differ in summation order only (per-chunk
    partial sums against one mean over all rows, and the head matmul's
    gradient accumulated chunk by chunk): rtol 1e-5 on the loss.  AdamW
    moves every parameter by about lr = 1e-3 a step whatever its
    gradient's size, so a gradient component that cancels to near zero
    hands its rounding on undiminished: atol 1e-5 on the parameters, 1 %
    of one step (measured 2.0e-6 and 4.3e-6)."""
    ids, labels = _data()

    def run(ce_chunk_rows):
        step, params, opt = build_functional_train_step(
            _model(), lr=1e-3, remat=False, ce_chunk_rows=ce_chunk_rows)
        losses = []
        for _ in range(2):
            params, opt, loss = step(params, opt, ids, labels)
            losses.append(float(np.asarray(loss)))
        return losses, [np.asarray(a)
                        for a in jax.tree_util.tree_leaves(params)]

    plain_losses, plain_params = run(0)
    chunk_losses, chunk_params = run(rows)
    assert plain_losses[1] < plain_losses[0]
    np.testing.assert_allclose(chunk_losses, plain_losses, rtol=1e-5)
    assert len(chunk_params) == len(plain_params)
    for got, want in zip(chunk_params, plain_params):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_tp2_eager_logits_loss_grads_match_one_device():
    """The eager model with tensor-parallel layers on an mp=2 mesh (the
    same seed gives the same global weights) computes one device's logits,
    loss and parameter gradients."""
    from paddle_tpu.distributed import mesh as mesh_mod

    ids, labels = _data()

    def run(**extra):
        model = _model(**extra)
        logits = model(paddle.to_tensor(ids))
        loss = GPTPretrainingCriterion()(logits, paddle.to_tensor(labels))
        loss.backward()
        grads = {p.name: p.grad.numpy() for p in model.parameters()
                 if p.grad is not None}
        return logits.numpy(), float(loss.numpy()), grads

    one_logits, one_loss, one_grads = run()
    mesh_mod.build_hybrid_mesh(dp=1, mp=2, pp=1, sharding=1)
    tp_logits, tp_loss, tp_grads = run(use_parallel=True)
    np.testing.assert_allclose(tp_logits, one_logits, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tp_loss, one_loss, rtol=1e-6)
    assert len(tp_grads) == len(one_grads) > 0
    for got, want in zip(tp_grads.values(), one_grads.values()):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
