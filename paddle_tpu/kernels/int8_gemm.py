"""Pallas TPU fused dynamic-quantize + W8A8 int8 GEMM.

Role parity: the reference's TensorRT int8 GEMM engines
(``inference/tensorrt/trt_int8_calibrator.h``) and the fused dequant
epilogues of its int8 CUDA kernels.  The plain ``quantized_matmul`` int8
path read 1.50x (4096^3) / 1.65x (8192^3) over bf16 on the v5e MXU
(2026-07-31, jax 0.4.37, retired transport; not re-measured); this kernel
is what lets the GPT flagship's linears ride that headroom
(GPTConfig.int8) without paying a separate quantize-pass over the
activations in HBM.

Design (pallas_guide.md):
  * grid = (M blocks, N blocks); each program holds one [bm, K] activation
    slab and one [K, bn] int8 weight slab whole in VMEM;
  * the per-token (per-row) activation abs-max, the int8 round/clip, the
    int8 x int8 -> int32 MXU dot and the fused rescale
    (row_scale * col_scale) all happen in ONE kernel — the fp activations
    are read from HBM exactly once and no int8/fp32 intermediate ever
    round-trips;
  * weights arrive PRE-quantized (per-output-channel int8 + fp32 scale):
    in training they are re-quantized per step by cheap VPU ops XLA fuses
    into the producing update, in decode they are quantized once at setup;
  * ``interpret=True`` runs the identical kernel body through the Pallas
    interpreter so CPU tests cover the exact TPU code path (flash.py
    convention), and the jnp reference path below makes the identical
    quantization decisions (same round-half-to-even, same clamp) so the
    two paths differ only by float-rescale rounding (~1e-6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash import _backend_is_tpu, _x64_off

# quantization constants shared with ops/quant_ops.py: symmetric int8,
# scale = absmax / 127, clamp guards against all-zero rows
_QMAX = 127.0
_EPS = 1e-8


def available() -> bool:
    """Dispatch gate: True when the running backend executes Mosaic/Pallas
    TPU kernels (tests monkeypatch this to force the kernel in interpret
    mode)."""
    return _backend_is_tpu()


def _pick_tile(n: int, want: int) -> int | None:
    for b in (want, 512, 256, 128, 64, 32, 16, 8):
        if b <= n and n % b == 0:
            return b
    return None


def supported(m: int, k: int, n: int) -> bool:
    """Shape gate for the fused kernel: lane-aligned K/N (the int8 MXU tile
    is (32, 128)) and a divisible M tile.  Decode-sized matvecs (tiny M)
    and ragged shapes take the jnp path instead of failing at lowering."""
    if k % 128 != 0 or n % 128 != 0:
        return False
    if _pick_tile(m, 256) is None or _pick_tile(n, 256) is None:
        return False
    # VMEM budget: x slab (bm*K fp32) + w slab (K*bn int8) + acc; keep the
    # resident slabs comfortably under the ~16MB/core VMEM
    bm, bn = _pick_tile(m, 256), _pick_tile(n, 256)
    vmem = bm * k * 4 + k * bn + bm * bn * 4
    return vmem < 12 * 1024 * 1024


def _w8a8_kernel(x_ref, wq_ref, ws_ref, o_ref):
    """One [bm, bn] output tile: fused row-quantize + int8 dot + rescale."""
    x = x_ref[...].astype(jnp.float32)                       # [bm, K]
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                     jnp.float32(_EPS)) / jnp.float32(_QMAX)  # [bm, 1]
    xq = jnp.clip(jnp.round(x / sx), -_QMAX, _QMAX).astype(jnp.int8)
    acc = jnp.dot(xq, wq_ref[...], preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * sx * ws_ref[...]
                  ).astype(o_ref.dtype)


def w8a8_gemm(x2, wq, ws, *, block_m: int | None = None,
              block_n: int | None = None, interpret: bool | None = None,
              out_dtype=None):
    """Fused dynamic per-token quantize + int8 GEMM.

    ``x2`` [M, K] float; ``wq`` [K, N] int8 (pre-quantized weight);
    ``ws`` [N] float32 per-output-channel dequant scale.  Returns
    [M, N] in ``out_dtype`` (default: x2.dtype).  Callers gate on
    :func:`supported` first; ragged shapes raise at the BlockSpec layer.
    """
    m, k = x2.shape
    n = wq.shape[1]
    bm = block_m or _pick_tile(m, 256)
    bn = block_n or _pick_tile(n, 256)
    if interpret is None:
        interpret = not _backend_is_tpu()
    ws2 = ws.astype(jnp.float32).reshape(1, n)
    out_dtype = out_dtype or x2.dtype
    with _x64_off():
        out = pl.pallas_call(
            _w8a8_kernel,
            grid=(m // bm, n // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            interpret=interpret,
            name="w8a8_gemm",
        )(x2, wq, ws2)
    return out


def w8a8_gemm_ref(x2, wq, ws, out_dtype=None):
    """jnp reference making the same quantization decisions (the CPU/ragged
    fallback and the parity oracle for the kernel tests)."""
    from ..ops.quant_ops import quantize_per_token

    xq, sx = quantize_per_token(x2)
    acc = jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * sx * ws.astype(jnp.float32)
    return out.astype(out_dtype or x2.dtype)
