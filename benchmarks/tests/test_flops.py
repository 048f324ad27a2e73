"""flops.py against counts made by hand for both configurations."""

import json
import os

import pytest

from benchmarks import flops, weights

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _sizes(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return weights.sizes(json.load(f))


def test_590m_train_flops_per_token():
    sz = _sizes("cerebras-gpt-590m")
    # per layer 4*1536^2 + 2*1536*6144 = 28,311,552; x18; head 1536*50257
    assert flops.matmul_params(sz) == 18 * 28_311_552 + 1536 * 50257
    # attention, causal at half: fwd 2*s*h per layer, bwd twice that
    attn = 6 * 2048 * 1536 * 18
    assert flops.train_flops_per_token(sz, 2048) == pytest.approx(
        6 * (18 * 28_311_552 + 77_194_752) + attn)
    assert flops.train_flops_per_token(sz, 2048) == pytest.approx(3.861e9,
                                                                  rel=1e-3)


def test_1p3b_train_flops_per_token():
    sz = _sizes("cerebras-gpt-1.3b")
    assert flops.matmul_params(sz) == 24 * 50_331_648 + 2048 * 50257
    assert flops.train_flops_per_token(sz, 2048) == pytest.approx(8.470e9,
                                                                  rel=1e-3)
    assert flops.kv_bytes_per_token(sz) == 192 * 1024
    # bf16 weights one dispatch streams: blocks + the padded head
    assert flops.weight_bytes(sz) == 2 * (24 * 50_331_648 + 2048 * 50304)


def test_flash_call_counts_causal_at_half():
    f, b = flops.flash_call("flash_fwd", batch=8, heads=12, seq=2048,
                            head_dim=128)
    assert f == 2 * (2 * 8 * 12 * 2048 * 2048 * 128) / 2   # QK^T and PV
    assert b == 4 * 8 * 12 * 2048 * 128 * 2                # q k v o in bf16
    dq, _ = flops.flash_call("flash_bwd_dq", batch=8, heads=12, seq=2048,
                             head_dim=128)
    dkv, _ = flops.flash_call("flash_bwd_dkv", batch=8, heads=12, seq=2048,
                              head_dim=128)
    assert (dq, dkv) == (1.5 * f, 2 * f)


def test_roofline_names_its_bound_and_unknown_device_is_an_error():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    t, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")
    with pytest.raises(KeyError):
        flops.peaks("cpu")
