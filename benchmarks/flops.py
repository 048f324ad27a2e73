"""Required operations and bytes, from shapes alone.

"Required" is what the algorithm needs, whatever the program does: causal
attention is counted at half the square (the masked half is never needed),
recomputation is not counted, and the vocabulary is the published one, not
the padded one.  A matrix product of (m, k) by (k, n) is ``2 m k n``.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: matrix products of one causal (s, s, d) attention in each flash kernel:
#: fwd QK^T, PV; dq re-forms S, then dP, dQ; dkv re-forms S, then dP, dV, dK.
#: The re-formed S is part of the algorithm (flash keeps no S), so it counts.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
#: (b, n, s, d) tensors each kernel reads and writes once: q k v -> o;
#: q k v do (+ o for delta) -> dq; q k v do -> dk dv
FLASH_TENSORS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 6}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmarks/peaks.json")
    return table[device_kind]


def matmul_params(sz: dict) -> int:
    """Weights that multiply every token: the blocks' four projections and
    the output head (tied to the embedding, so counted once, as a head)."""
    h, f = sz["hidden"], sz["ffn"]
    return sz["layers"] * (4 * h * h + 2 * h * f) + h * sz["vocab"]


def attention_flops_per_token(sz: dict, seq: int, *, backward: bool) -> float:
    """Causal self-attention over ``seq`` positions, per token, all layers:
    QK^T and PV at half the square forward (2 * seq * h), and the four
    products of the backward pass (dP, dQ, dK, dV) at twice that."""
    fwd = 2.0 * seq * sz["hidden"] * sz["layers"]
    return fwd * (3.0 if backward else 1.0)


def train_flops_per_token(sz: dict, seq: int) -> float:
    """Forward plus backward of one token in a sequence of ``seq``."""
    return 6.0 * matmul_params(sz) + attention_flops_per_token(
        sz, seq, backward=True)


def flash_call(kernel: str, *, batch: int, heads: int, seq: int,
               head_dim: int, itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one causal call of a flash kernel."""
    one = 2.0 * batch * heads * seq * seq * head_dim / 2.0
    nbytes = FLASH_TENSORS[kernel] * batch * heads * seq * head_dim * itemsize
    return FLASH_MATMULS[kernel] * one, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def weight_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes of weights one decode or prefill dispatch streams."""
    h = sz["hidden"]
    return itemsize * (matmul_params(sz) - h * sz["vocab"]
                       + h * sz["padded_vocab"])


def kv_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    return 2 * sz["layers"] * sz["hidden"] * itemsize
