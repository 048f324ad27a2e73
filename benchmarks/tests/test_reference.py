"""gpt2_ref.py agrees with the program's GPTForPretraining at a tiny size
on the CPU, in float32, on the benchmark's seeded weights."""

import json
import os

import numpy as np

from benchmarks import sut, weights
from benchmarks.reference import gpt2_ref

TINY = os.path.join(os.path.dirname(__file__), "tiny", "configs",
                    "tiny-gpt.json")


def test_reference_matches_the_program_forward():
    import paddle_tpu as paddle

    with open(TINY) as f:
        cfg = json.load(f)
    sz = weights.sizes(cfg)
    w = weights.make(cfg, 5, "float32")
    model = sut.build_model(sz, parallel=False, seed=5)
    sut.load_weights(model, w)
    model.eval()
    ids = np.random.default_rng(0).integers(0, sz["vocab"], (2, 48))
    got = np.asarray(model(paddle.to_tensor(ids.astype("int64")))._array)
    want = np.asarray(gpt2_ref.logits(w, ids, n_head=sz["heads"],
                                      eps=sz["eps"]))
    assert got.shape == want.shape == (2, 48, sz["padded_vocab"])
    # float32 both sides; only the order of summation differs
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # padded vocabulary rows are zero, so their logit is exactly 0
    assert np.all(want[..., sz["vocab"]:] == 0)
    assert np.abs(want[..., :sz["vocab"]]).max() > 0.5


def test_weights_reproduce_from_the_seed():
    with open(TINY) as f:
        cfg = json.load(f)
    a, b, c = (weights.make(cfg, s, "float32") for s in (1, 1, 2))
    assert np.array_equal(a["blocks"][1]["fc1_w"], b["blocks"][1]["fc1_w"])
    assert not np.array_equal(a["wte"], c["wte"])
    assert not np.array_equal(a["blocks"][0]["qkv_w"], a["blocks"][1]["qkv_w"])
    assert abs(float(np.std(a["blocks"][0]["fc1_w"])) - 0.02) < 2e-3
    assert abs(float(np.mean(a["blocks"][0]["ln1_g"])) - 1.0) < 1e-2
