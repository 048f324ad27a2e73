"""The pretraining job: one donated train step, back to back, on a fresh
seeded batch every step.

Cell keys read here: ``mesh`` (``{"dp", "mp"}`` or null), ``batch`` (global
rows per step), ``train_step_args`` (the arguments of
``build_functional_train_step`` the cell fixes; everything else stays at the
program's default) and ``kernels`` (the Pallas kernels the compiled step
must hold and nothing else, with how often per layer: selective remat runs
the flash forward a second time in the backward pass).  Configuration keys: ``dtype`` and
``check.train_loss_abs_tol``.

Set-up: seeded weights on the device, the reference's loss on the check
rows, one compile, one step on the check batch (whose loss is the program's
loss on the seeded weights, compared with the reference) and one on a
stream batch.  The window then runs whole steps until ``seconds`` have
passed; a step ends in ``block_until_ready`` on its loss, and the next
batch is drawn on the host while the device works.
"""

from __future__ import annotations

import math
import importlib
import time

import jax
import numpy as np

from benchmarks import sut, weights
from benchmarks.reference import gpt2_ref

#: the job's host spans; idle gaps of the device are attributed to
#: whichever of them covers the gap
SPANS = ("train_put_batch", "train_step_enqueue", "train_draw_next_batch",
         "train_wait_loss")


def run(ctx) -> dict:
    cell, cfg, sz = ctx.cell, ctx.config, ctx.sizes
    batch = int(cell["batch"])
    mesh = sut.build_mesh(cell.get("mesh"))
    parallel = bool(cell.get("mesh")) and cell["mesh"].get("mp", 1) > 1
    model = sut.build_model(sz, parallel=parallel, seed=ctx.seed)
    w = weights.make(cfg, ctx.seed, cfg["dtype"])
    sut.load_weights(model, w)
    ctx.mark("weights")

    stream = importlib.import_module(
        f"benchmarks.traffic.{ctx.traffic['generator']}").make(ctx.traffic, vocab=sz["vocab"], seed=ctx.seed)
    ids_c, labels_c, ids_ref, labels_ref = stream.check_batch(batch)
    ref_loss = gpt2_ref.loss(w, ids_ref, labels_ref, n_head=sz["heads"],
                             eps=sz["eps"])
    del w
    ctx.mark("reference")

    step, params, opt = sut.build_train_step(model, **cell["train_step_args"])
    put = lambda a: sut.shard_batch(a, mesh)  # noqa: E731
    ids, labels = put(ids_c), put(labels_c)
    kernels = sut.traced_kernels(step, params, opt, ids, labels)
    want = {(name, False): per_layer * sz["layers"]
            for name, per_layer in cell["kernels"].items()}
    params, opt, loss = step(params, opt, ids, labels)
    prog_loss = float(loss)
    ctx.mark("compile_and_check_step")
    ids, labels = (put(a) for a in stream.batch(0, batch))
    params, opt, loss = step(params, opt, ids, labels)
    float(loss)
    compiles0 = step._cache_size()
    tol = cfg["check"]["train_loss_abs_tol"]
    ctx.log(f"train: reference loss {ref_loss:.6f}, program {prog_loss:.6f}, "
            f"|delta| {abs(ref_loss - prog_loss):.2e} (tol {tol}); kernels "
            f"{kernels}; compiled programs {compiles0}")

    # ---- the window ------------------------------------------------------
    nxt = stream.batch(1, batch)
    losses, ends = [], []
    ctx.window_open()
    t0 = time.perf_counter()
    n = 0
    while True:
        ctx.tracer.poll(time.perf_counter() - t0)
        with ctx.span("train_put_batch"):
            ids, labels = put(nxt[0]), put(nxt[1])
        with ctx.span("train_step_enqueue"):
            params, opt, loss = step(params, opt, ids, labels)
        with ctx.span("train_draw_next_batch"):
            nxt = stream.batch(n + 2, batch)
        with ctx.span("train_wait_loss"):
            losses.append(float(jax.block_until_ready(loss)))
        ends.append(time.perf_counter() - t0)
        n += 1
        if ends[-1] >= ctx.seconds:
            break
    ctx.tracer.close()
    window_s = ends[-1]
    step_s = np.diff([0.0] + ends)
    tokens = n * batch * stream.seq
    head = float(np.mean(losses[:3]))
    tail = float(np.mean(losses[-3:]))
    checks = {
        "reference_loss": abs(ref_loss - prog_loss) <= tol,
        "compiled_kernels": kernels == want,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_fell": tail < head,
    }
    ctx.log(f"train: {n} steps in {window_s:.3f}s, loss {head:.4f} -> "
            f"{tail:.4f}")
    return dict(
        checks=checks, attempted=n,
        failed=sum(not math.isfinite(x) for x in losses),
        values={"train_tokens_per_s_per_chip":
                tokens / window_s / cell["chips"]},
        run=dict(window_s=window_s, step_s=step_s.tolist(), tokens=tokens,
                 seq=stream.seq, batch=batch,
                 window_compiles=step._cache_size() - compiles0,
                 mesh=cell.get("mesh") or {}))
