"""chip_smoke.py — the standing proof that paddle_tpu starts on the chip.

One process, no arguments, no network, seeded synthetic inputs.  Drives the
repo's two hot paths through the entry points a user calls, at the width of
the GPT-760M flagship (``bench.py``: vocab 50304, hidden 1536, 12 heads x
128, 24 layers), bf16:

  * kernel leg — every Pallas entry COMPILED (never interpreted) at the
    shapes the two legs below use and compared with its own jnp oracle;
  * state leg — the same for a model with recurrent state beside its pages
    (Falcon-H1's cell): the two kernels of the Mamba-2 recurrence
    (``kernels/ssd.py``) against their jnp paths, and the two paged kernels
    at its GQA group of 5;
  * train leg — ``models.gpt.build_functional_train_step``, batch 12 x seq
    1024, a few steps on a fixed batch;
  * serve leg — ``serving.ServingEngine`` with default auto-dispatch, a
    mixed-length load with shared prefixes; then short int8 and
    speculative runs;
  * four-chip leg — the same width over ``build_hybrid_mesh(dp=2, mp=2)``
    when the host has four devices; ``skipped`` otherwise.

It exits non-zero — and prints no result line — unless JAX's default
backend is a TPU: it never sets ``JAX_PLATFORMS``, never forces interpret
mode, never falls back to a reference path.  A failed leg makes the exit
code non-zero.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Step and leg times are printed as information only; this is not a
benchmark and nothing here is a rate or a utilization.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import re
import sys
import time
import traceback
from typing import Callable, Dict, List, Tuple

import numpy as np

# the flagship width (bench.py main(): GPT-760M); depth is the only cut
WIDTH = dict(vocab_size=50304, hidden_size=1536, num_heads=12,
             max_seq_len=1024, dropout=0.0)
LAYERS = 24
HEADS, HEAD_DIM = 12, 128
BATCH, SEQ = 12, 1024
# serving shapes (ISSUE 21): the engine the serve leg builds
SLOTS, PAGE, CHUNK = 8, 64, 128
MAX_PAGES = WIDTH["max_seq_len"] // PAGE
SPEC_K = 4

# Stated tolerances, as max|kernel - oracle| / max|oracle| per output.
# Inputs are bf16 (8 mantissa bits, ulp 2^-8 = 3.9e-3); the oracles run in
# fp32 at "highest" matmul precision on the same bf16-rounded inputs.
TOL_FWD = 2e-2
TOL_GRAD = 3e-2
TOL_STATE = 1e-3      # the recurrence's kernels are float32 throughout


class SmokeFailure(Exception):
    """A check the smoke makes did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _flagship_cfg(**kw):
    from paddle_tpu.models import GPTConfig

    return GPTConfig(num_layers=LAYERS, **{**WIDTH, **kw})


def _bf16_model(cfg):
    """Seeded flagship model with bf16 parameters (bench._run's layout:
    bf16 params, fp32 AdamW masters inside the train step)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    for p in model.parameters():
        p._array = p._array.astype(jnp.bfloat16)
    return model


def _fixed_batch():
    rng = np.random.RandomState(0)
    ids = rng.randint(0, WIDTH["vocab_size"], (BATCH, SEQ)).astype("int32")
    labels = rng.randint(0, WIDTH["vocab_size"], (BATCH, SEQ)).astype("int64")
    return ids, labels


def _on_tpu(tree) -> bool:
    import jax

    return all(d.platform == "tpu" for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelCase:
    """One Pallas entry at one shape: ``kernel(*args)`` and ``oracle(*args)``
    return the same tuple of arrays.  A compiler refusal of a ``required``
    case (the main-path four plus every variant a later leg depends on)
    fails the leg; of any other case it is reported by name.  A case that
    compiles and disagrees with its oracle always fails the leg."""

    required: bool
    tol: float
    kernel: Callable
    oracle: Callable
    args: tuple


def _with_grads(attn):
    """(q, k, v, do) -> (out, dq, dk, dv) through ``attn``'s vjp."""
    import jax

    def f(q, k, v, do):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out.astype(do.dtype),) + tuple(vjp(do.astype(out.dtype)))

    return f


def _flash_case(*, layout, seq, batch, heads, required, n_kv=None,
                window=None):
    import jax.numpy as jnp

    from paddle_tpu.kernels import flash
    from paddle_tpu.kernels.attention import _sdpa_reference

    rng = np.random.RandomState(0)
    n_kv = n_kv or heads

    def operand(n):
        shape = {"bnsd": (batch, n, seq, HEAD_DIM),
                 "bsnd": (batch, seq, n, HEAD_DIM)}[layout]
        return jnp.asarray(rng.randn(*shape).astype("float32"), jnp.bfloat16)

    args = (operand(heads), operand(n_kv), operand(n_kv), operand(heads))
    # to the oracle's bnsd and back: the same swap both ways
    swap = {"bnsd": lambda a: a,
            "bsnd": lambda a: jnp.swapaxes(a, 1, 2)}[layout]

    def kernel(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, layout=layout,
                                     window=window, interpret=False)

    def oracle(q, k, v):
        f32 = [swap(a).astype(jnp.float32) for a in (q, k, v)]
        return swap(_sdpa_reference(*f32, is_causal=True, window=window))

    return KernelCase(required, TOL_GRAD, _with_grads(kernel),
                      _with_grads(oracle), args)


def _paged_operands(rng, n_kv=HEADS, kv_bits=None):
    """A (P, Hkv, page, D) pool at the serve leg's geometry with every
    slot's block table pointing at distinct non-null pages."""
    import jax.numpy as jnp

    from paddle_tpu.ops import quant_ops

    n_pages = 1 + SLOTS * MAX_PAGES
    kf = rng.randn(n_pages, n_kv, PAGE, HEAD_DIM).astype("float32")
    vf = rng.randn(n_pages, n_kv, PAGE, HEAD_DIM).astype("float32")
    tables = jnp.asarray(
        1 + rng.permutation(SLOTS * MAX_PAGES).reshape(SLOTS, MAX_PAGES),
        jnp.int32)
    if kv_bits is None:
        return (jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16),
                tables, {})
    quant = {8: quant_ops.quantize_per_token,
             4: quant_ops.quantize_int4_per_token}[kv_bits]
    kq, ks = quant(jnp.asarray(kf))
    vq, vs = quant(jnp.asarray(vf))
    return kq, vq, tables, dict(k_scales=ks, v_scales=vs)


def _decode_case(*, required, n_kv=HEADS, kv_bits=None, window=None):
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    rng = np.random.RandomState(0)
    kp, vp, tables, scales = _paged_operands(rng, n_kv, kv_bits)
    q = jnp.asarray(rng.randn(SLOTS, HEADS, HEAD_DIM).astype("float32"),
                    jnp.bfloat16)
    lengths = jnp.asarray(rng.randint(1, MAX_PAGES * PAGE, (SLOTS,)),
                          jnp.int32)

    def kernel(q, kp, vp, tables, lengths, scales):
        return (pa.paged_attention(q, kp, vp, tables, lengths, window=window,
                                   interpret=False, **scales),)

    def oracle(q, kp, vp, tables, lengths, scales):
        return (pa.paged_attention_ref(q, kp, vp, tables, lengths,
                                       window=window, **scales),)

    return KernelCase(required, TOL_FWD, kernel, oracle,
                      (q, kp, vp, tables, lengths, scales))


def _cell_decode_case(*, required, slots, heads, n_kv, max_pages,
                      window=None):
    """The decode kernel at a benchmark cell's own shape and at ragged
    lengths: one token, a page, a page and one, a full table, a context
    past longshort's window, a few chat-sized ones, and the rest idle lanes
    (nothing to attend, every table entry the null page).  Live entries
    name pages of a small pool (shared between slots: the kernel only
    reads); dead ones the null page."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    rng = np.random.RandomState(0)
    n_pages = 1 + 256
    kp, vp = (jnp.asarray(
        rng.randn(n_pages, n_kv, PAGE, HEAD_DIM).astype("float32"),
        jnp.bfloat16) for _ in range(2))
    lengths = np.ones((slots,), np.int32)
    ragged = [1, PAGE, PAGE + 1, max_pages * PAGE,
              min(5000, max_pages * PAGE - 1), *rng.randint(100, 700, (6,))]
    lengths[rng.choice(slots, len(ragged), replace=False)] = ragged
    live = -(-lengths // PAGE) * (lengths > 1)
    tables = np.zeros((slots, max_pages), np.int32)
    for i, n in enumerate(live):
        tables[i, :n] = 1 + (rng.randint(256) + np.arange(n)) % 256
    q = jnp.asarray(rng.randn(slots, heads, HEAD_DIM).astype("float32"),
                    jnp.bfloat16)

    def kernel(q, kp, vp, tables, lengths):
        return (pa.paged_attention(q, kp, vp, tables, lengths, window=window,
                                   interpret=False),)

    def oracle(q, kp, vp, tables, lengths):
        return (pa.paged_attention_ref(q, kp, vp, tables, lengths,
                                       window=window),)

    return KernelCase(required, TOL_FWD, kernel, oracle,
                      (q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)))


def _verify_case(*, required, kv_bits=None):
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    t = SPEC_K + 1
    rng = np.random.RandomState(0)
    kp, vp, tables, scales = _paged_operands(rng, kv_bits=kv_bits)
    q = jnp.asarray(rng.randn(SLOTS, t, HEADS, HEAD_DIM).astype("float32"),
                    jnp.bfloat16)
    lengths = jnp.asarray(rng.randint(1, MAX_PAGES * PAGE - t, (SLOTS,)),
                          jnp.int32)

    def kernel(q, kp, vp, tables, lengths, scales):
        return (pa.paged_attention_mq(q, kp, vp, tables, lengths,
                                      interpret=False, **scales),)

    def oracle(q, kp, vp, tables, lengths, scales):
        return (pa.paged_attention_mq_ref(q, kp, vp, tables, lengths,
                                          **scales),)

    return KernelCase(required, TOL_FWD, kernel, oracle,
                      (q, kp, vp, tables, lengths, scales))


def _prefill_case(*, required, kv_bits=None):
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_prefill as pp

    rng = np.random.RandomState(0)
    kp, vp, tables, scales = _paged_operands(rng, kv_bits=kv_bits)
    q = jnp.asarray(rng.randn(CHUNK, HEADS, HEAD_DIM).astype("float32"),
                    jnp.bfloat16)
    start = jnp.int32(3 * PAGE)     # a chunk that follows 3 written pages

    def kernel(q, kp, vp, table, start, scales):
        return (pp.paged_prefill(q, kp, vp, table, start, interpret=False,
                                 **scales),)

    def oracle(q, kp, vp, table, start, scales):
        return (pp.paged_prefill_ref(q, kp, vp, table, start, **scales),)

    return KernelCase(required, TOL_FWD, kernel, oracle,
                      (q, kp, vp, tables[0], start, scales))


def _w8a8_case(*, required, m, k, n):
    import jax.numpy as jnp

    from paddle_tpu.kernels import int8_gemm
    from paddle_tpu.ops.quant_ops import quantize_per_channel

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k).astype("float32"), jnp.bfloat16)
    wq, ws = quantize_per_channel(
        jnp.asarray(rng.randn(k, n).astype("float32") * 0.02), axis=1)

    def kernel(x, wq, ws):
        return (int8_gemm.w8a8_gemm(x, wq, ws, interpret=False),)

    def oracle(x, wq, ws):
        return (int8_gemm.w8a8_gemm_ref(x, wq, ws),)

    return KernelCase(required, TOL_FWD, kernel, oracle, (x, wq, ws))

def _cell_prefill_case(*, required, heads, n_kv, max_pages, start,
                       window=None):
    """The chunk kernel at a benchmark cell's own shape: one 128-row chunk
    that follows ``start`` written positions of a ``max_pages``-entry
    table.  The entries up to the chunk's end name pages of a small pool,
    the rest the null page, as the engine leaves them."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_prefill as pp

    rng = np.random.RandomState(0)
    kp, vp = (jnp.asarray(
        rng.randn(1 + 256, n_kv, PAGE, HEAD_DIM).astype("float32"),
        jnp.bfloat16) for _ in range(2))
    written = -(-(start + CHUNK) // PAGE)
    table = np.zeros((max_pages,), np.int32)
    table[:written] = 1 + rng.permutation(256)[:written]
    q = jnp.asarray(rng.randn(CHUNK, heads, HEAD_DIM).astype("float32"),
                    jnp.bfloat16)

    def kernel(q, kp, vp, table, start):
        return (pp.paged_prefill(q, kp, vp, table, start, window=window,
                                 interpret=False),)

    def oracle(q, kp, vp, table, start):
        return (pp.paged_prefill_ref(q, kp, vp, table, start,
                                     window=window),)

    return KernelCase(required, TOL_FWD, kernel, oracle,
                      (q, kp, vp, jnp.asarray(table), jnp.int32(start)))


def _ssm_operands(rng, rows, heads=32, groups=2, head_dim=128, d_state=256,
                  slab_rows=24):
    """A state slab and ``rows`` rows of the recurrence's operands at
    Falcon-H1's widths (32 heads of 128 in 2 groups, state 256), float32:
    decays between 0.2 and 0.999 a row."""
    import jax.numpy as jnp

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape).astype("float32"))

    slab = arr(slab_rows, heads, head_dim, d_state)
    x, b, c = arr(rows, heads, head_dim), arr(rows, groups, d_state), \
        arr(rows, groups, d_state)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (rows, heads)).astype("float32"))
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)).astype("float32"))
    return slab, x, dt, a, b, c, arr(heads)


def _scan_case(*, required, rows, valid):
    """The chunk scan at the cell's widths: a bucket of ``rows`` rows of
    which ``valid`` are real, from a carried state, into row 7 of a slab
    whose other rows must come back untouched."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import ssd

    slab, x, dt, a, b, c, d = _ssm_operands(np.random.RandomState(0), rows)
    dt = dt * (jnp.arange(rows) < valid)[:, None]

    def kernel(slab, x, dt, a, b, c, d):
        return ssd.ssd_chunk_scan(slab, 7, x, dt, a, b, c, d,
                                  interpret=False)

    def oracle(slab, x, dt, a, b, c, d):
        return ssd.ssd_chunk_scan_ref(slab, 7, x, dt, a, b, c, d)

    return KernelCase(required, TOL_STATE, kernel, oracle,
                      (slab, x, dt, a, b, c, d))


def _step_case(*, required, slots, live):
    """The decode state step at the cell's widths: ``slots`` lanes of
    which every ``live``-th is dead, on the second layer's rows of a slab
    of two: dead lanes and the first layer must come back untouched."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import ssd

    slab, x, dt, a, b, c, d = _ssm_operands(
        np.random.RandomState(0), slots, slab_rows=2 * slots)
    active = jnp.asarray(np.arange(slots) % live != live - 1)

    def kernel(slab, x, dt, a, b, c, d, active):
        return ssd.ssm_state_step(slab, slots, x, dt, a, b, c, d, active,
                                  interpret=False)

    def oracle(slab, x, dt, a, b, c, d, active):
        return ssd.ssm_state_step_ref(slab, slots, x, dt, a, b, c, d, active)

    return KernelCase(required, TOL_STATE, kernel, oracle,
                      (slab, x, dt, a, b, c, d, active))


_H = WIDTH["hidden_size"]

#: Every Pallas entry at the flagship shapes: name -> (builder, arguments).
#: Built one at a time (:func:`kernel_case`) so one case's operands are
#: freed before the next exists.  ``tests/test_tpu_lowering.py`` cross-lowers
#: the same cases for platform ``tpu`` from the CPU.
KERNEL_CASES = {
    # the four on the main path
    "flash_bnsd_seq1024": (_flash_case, dict(
        layout="bnsd", seq=1024, batch=2, heads=HEADS, required=True)),
    "flash_bnsd_seq8192": (_flash_case, dict(
        layout="bnsd", seq=8192, batch=1, heads=2, required=True)),
    "paged_attention_fp": (_decode_case, dict(required=True)),
    "paged_prefill_fp": (_prefill_case, dict(required=True)),
    # what the serve leg's int8 and speculative runs depend on
    "paged_attention_int8": (_decode_case, dict(required=True, kv_bits=8)),
    "paged_prefill_int8": (_prefill_case, dict(required=True, kv_bits=8)),
    "paged_attention_mq_fp": (_verify_case, dict(required=True)),
    # the serving cells' own decode shapes (cerebras-gpt-1.3b; Command A+'s
    # full and sliding layers), at ragged lengths
    "paged_attention_cell_1.3b": (_cell_decode_case, dict(
        required=True, slots=64, heads=16, n_kv=16, max_pages=32)),
    "paged_attention_cell_gqa16": (_cell_decode_case, dict(
        required=True, slots=32, heads=128, n_kv=8, max_pages=256)),
    "paged_attention_cell_gqa16_window": (_cell_decode_case, dict(
        required=True, slots=32, heads=128, n_kv=8, max_pages=256,
        window=4096)),
    # ... and their chunk shapes: a chunk part-way through a 1.3b prompt,
    # one deep in a long Command A+ prompt on a full layer, and the same on
    # a sliding layer (``start`` past the window)
    "paged_prefill_cell_1.3b": (_cell_prefill_case, dict(
        required=True, heads=16, n_kv=16, max_pages=32, start=9 * PAGE + 7)),
    "paged_prefill_cell_gqa16": (_cell_prefill_case, dict(
        required=True, heads=128, n_kv=8, max_pages=256, start=9000)),
    "paged_prefill_cell_gqa16_window": (_cell_prefill_case, dict(
        required=True, heads=128, n_kv=8, max_pages=256, start=9000,
        window=4096)),
    "w8a8_gemm_chunk": (_w8a8_case, dict(
        required=True, m=CHUNK, k=_H, n=3 * _H)),
    # Falcon-H1's cell: the paged kernels at its GQA group of 5 (20 query
    # heads over 4 KV heads), and the two kernels of its recurrence
    "paged_attention_cell_gqa5": (_cell_decode_case, dict(
        required=True, slots=96, heads=20, n_kv=4, max_pages=32)),
    "paged_prefill_cell_gqa5": (_cell_prefill_case, dict(
        required=True, heads=20, n_kv=4, max_pages=32, start=9 * PAGE + 7)),
    "ssd_chunk_scan_cell": (_scan_case, dict(
        required=True, rows=CHUNK, valid=CHUNK)),
    "ssd_chunk_scan_bucket8": (_scan_case, dict(
        required=True, rows=8, valid=5)),
    "ssm_state_step_cell": (_step_case, dict(
        required=True, slots=96, live=4)),
    # attempted and reported
    "paged_attention_int4": (_decode_case, dict(required=False, kv_bits=4)),
    "paged_attention_window": (_decode_case, dict(
        required=False, window=256)),
    "paged_attention_gqa": (_decode_case, dict(required=False, n_kv=4)),
    "paged_attention_mq_int8": (_verify_case, dict(
        required=False, kv_bits=8)),
    "w8a8_gemm_train": (_w8a8_case, dict(
        required=False, m=BATCH * SEQ, k=_H, n=3 * _H)),
    "flash_bsnd_seq1024": (_flash_case, dict(
        layout="bsnd", seq=1024, batch=2, heads=HEADS, required=False)),
    "flash_bsnd_gqa4_seq1024": (_flash_case, dict(
        layout="bsnd", seq=1024, batch=2, heads=HEADS, n_kv=HEADS // 4,
        required=False)),
    "flash_bsnd_window_seq1024": (_flash_case, dict(
        layout="bsnd", seq=1024, batch=2, heads=HEADS, window=256,
        required=False)),
    "flash_bnsd_window_seq1024": (_flash_case, dict(
        layout="bnsd", seq=1024, batch=2, heads=HEADS, window=256,
        required=False)),
}


def kernel_case(name: str) -> KernelCase:
    build, kw = KERNEL_CASES[name]
    return build(**kw)


def _max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


#: the cases of the state leg: a model with recurrent state beside its pages
STATE_CASES = ("paged_attention_cell_gqa5", "paged_prefill_cell_gqa5",
               "ssd_chunk_scan_cell", "ssd_chunk_scan_bucket8",
               "ssm_state_step_cell")


def kernel_leg(names=None) -> None:
    import jax

    from paddle_tpu.analysis.jaxpr_audit import pallas_kernels

    refused: List[str] = []
    broken: List[str] = []
    for name in (names if names is not None
                 else [n for n in KERNEL_CASES if n not in STATE_CASES]):
        case = kernel_case(name)
        t0 = time.perf_counter()
        try:
            traced = jax.jit(case.kernel).trace(*case.args)
            kernels = pallas_kernels(traced.jaxpr)
            got = jax.block_until_ready(traced.lower().compile()(*case.args))
        except Exception as e:  # the compiler's refusal, reported by name
            first = str(e).strip().splitlines()[0][:300]
            refused.append(name)
            print(f"  {name}: REFUSED {type(e).__name__}: {first}",
                  flush=True)
            if case.required:
                traceback.print_exc()
                broken.append(name)
            continue
        if not kernels or any(interp for _, interp in kernels):
            print(f"  {name}: NOT A COMPILED PALLAS PROGRAM: {dict(kernels)}",
                  flush=True)
            broken.append(name)
            continue
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(case.oracle)(*case.args))
        errs = [_max_rel_err(g, w) for g, w in zip(got, want)]
        ok = max(errs) <= case.tol
        print(f"  {name}: compiled, max rel err "
              f"{', '.join(f'{e:.2e}' for e in errs)} (tol {case.tol:.0e}) "
              f"{'ok' if ok else 'MISMATCH'} "
              f"[{time.perf_counter() - t0:.1f}s]", flush=True)
        if not ok:
            broken.append(name)
        del got, want, case
    print(f"  refused by the compiler: {refused or 'none'}", flush=True)
    check(not broken, f"kernels failed or mismatched: {broken}")


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------


def _train(cfg, shard, steps) -> Dict[str, object]:
    """Build the functional train step for ``cfg`` exactly as bench._run
    and examples/train_gpt.py do, run ``steps`` on the fixed batch, and
    return what the legs assert on."""
    import jax

    from paddle_tpu.analysis.jaxpr_audit import find_f64, pallas_kernels
    from paddle_tpu.models.gpt import build_functional_train_step

    model = _bf16_model(cfg)
    step, params, opt_state = build_functional_train_step(
        model, lr=1e-4, remat=False, ce_chunk_rows=2048)
    ids, labels = (shard(a) for a in _fixed_batch())

    traced = step.trace(params, opt_state, ids, labels)
    kernels = pallas_kernels(traced.jaxpr)
    want = {(n, False): cfg.num_layers
            for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    check(dict(kernels) == want,
          f"train step must hold the compiled flash kernel fwd+bwd once per "
          f"layer and nothing else; traced {dict(kernels)}")

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(np.asarray(loss)))
        times.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(_on_tpu((params, loss)), "parameters / loss are not on a TPU")

    # the optimized program the chip ran (the persistent cache makes this
    # second compile a read): no f64 anywhere, which the CPU-side
    # tests/test_no_f64.py can only assert on the jaxpr
    hlo = step.lower(params, opt_state, ids, labels).compile().as_text()
    check(not find_f64(hlo), f"f64 arrays in the TPU program: {find_f64(hlo)}")
    n_custom = len(re.findall(r'custom_call_target="tpu_custom_call"', hlo))
    check(n_custom == 3 * cfg.num_layers,
          f"expected {3 * cfg.num_layers} tpu_custom_call ops in the "
          f"optimized program, found {n_custom}")
    print(f"  losses {[round(x, 4) for x in losses]}", flush=True)
    print(f"  step wall s (first includes compile) "
          f"{[round(t, 2) for t in times]}", flush=True)
    n_s64 = len(set(re.findall(r"s64\[[0-9,]+\]", hlo)))
    print(f"  s64 arrays in the optimized program: {n_s64} distinct shapes "
          f"(int64 labels; x64 stays on for id parity)", flush=True)
    return dict(losses=losses, params=params, ids=ids, hlo=hlo)


def train_leg() -> float:
    out = _train(_flagship_cfg(), shard=lambda a: a, steps=4)
    return out["losses"][0]


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------


def _requests(max_new: int) -> List[Tuple[np.ndarray, int]]:
    """12 requests of mixed prompt length; the even ones share a 64-token
    (one page) prefix, two prompts are longer than a chunk.  More requests
    than slots, so the second admission wave meets a warm prefix cache."""
    rng = np.random.RandomState(1)
    vocab = WIDTH["vocab_size"]
    shared = rng.randint(0, vocab, (PAGE,))
    lengths = [96, 64, 160, 32, 224, 96, 128, 64, 160, 96, 192, 32]
    reqs = []
    for i, n in enumerate(lengths):
        tail = rng.randint(0, vocab, (n,))
        prompt = np.concatenate([shared, tail]) if i % 2 == 0 else tail
        reqs.append((prompt.astype(np.int32), max_new))
    return reqs


def _serve(model, label: str, max_new: int, want_paths: Dict[str, str],
           **engine_kw):
    from paddle_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(model, max_slots=SLOTS, page_size=PAGE,
                        chunk_tokens=CHUNK, prefix_cache=True, **engine_kw)
    check(eng.attention_paths() == want_paths,
          f"{label}: engine chose {eng.attention_paths()}, "
          f"expected {want_paths}")
    reqs = _requests(max_new)
    rids = [eng.add_request(p, n) for p, n in reqs]
    done = eng.run()        # raises on a page leak after drain
    st = eng.stats
    for rid, (_, n) in zip(rids, reqs):
        fin = done[rid]
        check(fin.finish_reason in ("eos", "length"),
              f"{label}: request {rid} ended {fin.finish_reason!r}")
        toks = np.asarray(fin.tokens)
        check(toks.shape == (n,) and toks.min() >= 0
              and toks.max() < WIDTH["vocab_size"],
              f"{label}: request {rid} produced {toks.shape} tokens")
    check(st["decode_traces"] == 1,
          f"{label}: {st['decode_traces']} decode traces, expected 1")
    check(st["prefix_hit_tokens"] > 0, f"{label}: no prefix-cache hits")
    print(f"  {label}: {len(rids)} requests ok, paths {eng.attention_paths()}"
          f", prefill traces {st['prefill_traces']}, decode calls "
          f"{st['decode_calls']}, prefix hit tokens {st['prefix_hit_tokens']}"
          f"/{st['prompt_tokens']}, spec accepted {st['spec_accepted']}"
          f"/{st['spec_drafted']} [{time.perf_counter() - t0:.1f}s]",
          flush=True)
    return st


def serve_leg() -> None:
    import jax

    from paddle_tpu.analysis.jaxpr_audit import pallas_kernels
    from paddle_tpu.ops.quant_ops import w8a8_apply

    model = _bf16_model(_flagship_cfg())
    kern = {"decode": "kernel", "prefill": "kernel"}
    _serve(model, "bf16", 24, kern)
    # the W8A8 projections pick kernel or jnp per shape inside the engine's
    # programs; read the choice where it is made, at the engine's shapes
    args = kernel_case("w8a8_gemm_chunk").args
    w8a8 = pallas_kernels(jax.make_jaxpr(w8a8_apply)(*args))
    check(dict(w8a8) == {("w8a8_gemm", False): 1},
          f"w8a8_apply at the prefill-chunk shape traced {dict(w8a8)}")
    _serve(model, "int8", 8, kern, int8=True)
    st = _serve(model, f"spec_k={SPEC_K}", 32, {**kern, "verify": "kernel"},
                spec_k=SPEC_K)
    check(st["spec_accepted"] > 0, "speculation accepted no draft token")


# ---------------------------------------------------------------------------
# four-chip leg
# ---------------------------------------------------------------------------

# bf16 compute with another reduction order (TP splits two contractions per
# block) against a ~10.9 loss at random init
TOL_LOSS_4CHIP = 2e-2


def four_chip_leg(one_chip_first_loss: float) -> None:
    import jax

    from paddle_tpu.distributed import mesh as mesh_mod

    dp = mp = 2
    old_mesh = mesh_mod.get_mesh()
    mesh_mod.build_hybrid_mesh(dp=dp, mp=mp)
    try:
        out = _train(_flagship_cfg(use_parallel=True),
                     shard=mesh_mod.shard_batch, steps=3)
    finally:
        mesh_mod.set_mesh(old_mesh)
    delta = abs(out["losses"][0] - one_chip_first_loss)
    check(delta <= TOL_LOSS_4CHIP,
          f"first-step loss {out['losses'][0]} vs one chip "
          f"{one_chip_first_loss}: |delta| {delta:.3e} > {TOL_LOSS_4CHIP}")

    # every device holds its share
    for name, arr in _named_tp_weights(out["params"]):
        shard = arr.addressable_shards[0].data
        check(len(arr.addressable_shards) == dp * mp
              and shard.nbytes * mp == arr.nbytes,
              f"{name}: shard {shard.shape} is not 1/{mp} of {arr.shape}")
    check(out["ids"].addressable_shards[0].data.shape[0] * dp == BATCH,
          "batch is not split 1/dp")
    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:dp * mp]]
    check(max(used) <= 2 * min(used),
          f"device memory is lopsided: bytes_in_use {used}")

    # flash ran on the LOCAL shard: every custom call's operands are
    # (batch/dp * heads/mp, seq, head_dim) — no chip does all heads of all
    # rows.  What the program still gathers is printed, not judged.
    local_bh = (BATCH // dp) * (HEADS // mp)
    lines = out["hlo"].splitlines()
    calls = [ln for ln in lines
             if 'custom_call_target="tpu_custom_call"' in ln]
    bad = [ln.strip()[:160] for ln in calls
           if f"bf16[{local_bh},{SEQ},{HEAD_DIM}]" not in ln]
    check(not bad, f"flash custom calls not on the local "
                   f"[{local_bh},{SEQ},{HEAD_DIM}] shard: {bad[:3]}")
    gathered = sorted({re.sub(r"\{[^}]*\}", "", m.group(1)) for m in (
        re.search(r"= (.+?) all-gather(?:-start)?\(", ln) for ln in lines)
        if m})
    print(f"  first-step loss {out['losses'][0]:.4f} vs one chip "
          f"{one_chip_first_loss:.4f} (|delta| {delta:.2e}, tol "
          f"{TOL_LOSS_4CHIP}); bytes_in_use {used}; {len(calls)} flash "
          f"custom calls on the local [{local_bh},{SEQ},{HEAD_DIM}] shard; "
          f"all-gather results in the program: {gathered[:12]}", flush=True)


def _named_tp_weights(params):
    """(name, array) for the tensor-parallel leaves of the train step's
    parameter tree: the ones a TP layer placed with an 'mp' spec."""
    import jax

    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        spec = getattr(leaf.sharding, "spec", ())
        if "mp" in tuple(spec):
            yield f"param[{i}]{tuple(leaf.shape)}", leaf


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _run_leg(name: str, fn: Callable, failed: List[str]):
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    try:
        return fn()
    except Exception:
        traceback.print_exc()
        failed.append(name)
        return None
    finally:
        gc.collect()
        print(f"[{name}] {'FAILED' if name in failed else 'ok'} "
              f"in {time.perf_counter() - t0:.1f}s", flush=True)


def main() -> int:
    t_start = time.perf_counter()
    import importlib.metadata

    import jax

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    versions = " ".join(f"{pkg} {importlib.metadata.version(pkg)}"
                        for pkg in ("jax", "jaxlib", "libtpu"))
    print(f"{versions} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={n_dev}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: the default JAX backend is not a TPU; refusing "
              "to run small or on a fallback", file=sys.stderr)
        return 1

    from paddle_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache {cache_dir}: {n_cached} entries at start",
          flush=True)

    failed: List[str] = []
    _run_leg("kernel leg", kernel_leg, failed)
    _run_leg("state leg", lambda: kernel_leg(STATE_CASES), failed)
    first_loss = _run_leg("train leg", train_leg, failed)
    _run_leg("serve leg", serve_leg, failed)
    if n_dev >= 4 and first_loss is not None:
        _run_leg("four-chip leg", lambda: four_chip_leg(first_loss), failed)
    elif n_dev >= 4:
        print("[four-chip leg] FAILED: needs the train leg's loss",
              flush=True)
        failed.append("four-chip leg")
    else:
        print(f"[four-chip leg] skipped: {n_dev} device(s)", flush=True)

    print(f"total wall {time.perf_counter() - t_start:.1f}s", flush=True)
    ok = not failed
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
