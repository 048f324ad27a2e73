"""BASELINE config 3: GPT pretraining with hybrid parallelism, end to end.

One jitted train step (fwd+bwd+AdamW) over the 4-axis hybrid mesh:
dp x mp(tensor) x pp(weight-sharded scan) x sharding(ZeRO).  On one chip
all degrees default to 1 and this is the single-device flagship path
bench.py measures; on a virtual CPU mesh it exercises the full hybrid
sharding (how the driver's dryrun runs it).

    python examples/train_gpt.py --steps 10 --config tiny
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_gpt.py --dp 2 --mp 2 --pp 2 --config tiny
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# Published per-chip bf16 peak FLOP/s, keyed by ``jax.devices()[0].device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16).  A device that
# is not listed gets no MFU gauge — never a default peak.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="tiny",
                   choices=["tiny", "small", "medium", "1p3b", "13b"])
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=0, help="0 = config default")
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--mp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--sharding", type=int, default=1)
    p.add_argument("--sharding-stage", type=int, default=None)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--remat", default="0", choices=["0", "1", "dots"])
    p.add_argument("--int8", action="store_true",
                   help="W8A8 int8 projections (GPTConfig.int8): real "
                        "int8 GEMMs with dynamic per-token activation "
                        "quant and an STE backward")
    p.add_argument("--kv-heads", type=int, default=None, metavar="N",
                   help="grouped-query attention "
                        "(GPTConfig.num_kv_heads): train with N KV heads "
                        "(must divide the config's num_heads) — the QKV "
                        "projection shrinks and serving stores N-head "
                        "pages (r14)")
    p.add_argument("--window", type=int, default=None, metavar="W",
                   help="sliding-window attention "
                        "(GPTConfig.attn_window): causal attention over "
                        "the last W positions, trained with the same "
                        "mask serving decodes under (r14)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="train-side observability (r11): loss / step "
                        "time / tokens-per-sec (and MFU on a device "
                        "PEAK_BF16_FLOPS lists) through the serving "
                        "MetricsRegistry — TensorBoard scalars per step "
                        "plus a Prometheus metrics.prom dump in DIR")
    args = p.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.utils import compile_cache

    compile_cache.configure()

    need = args.dp * args.mp * args.pp * args.sharding
    if need > 1:
        mesh_mod.build_hybrid_mesh(dp=args.dp, mp=args.mp, pp=args.pp,
                                   sharding=args.sharding)
        print(f"mesh: dp={args.dp} mp={args.mp} pp={args.pp} "
              f"sharding={args.sharding} over {need} of "
              f"{len(jax.devices())} devices")

    cfg_fn = {"tiny": gpt_mod.gpt_tiny, "small": gpt_mod.gpt_small,
              "medium": gpt_mod.gpt_medium, "1p3b": gpt_mod.gpt_1p3b,
              "13b": gpt_mod.gpt_13b}[args.config]
    cfg = cfg_fn(use_parallel=args.mp > 1, int8=args.int8,
                 num_kv_heads=args.kv_heads, attn_window=args.window)
    seq = args.seq or min(cfg.max_seq_len, 512)

    paddle.seed(args.seed)
    model = GPTForPretraining(cfg)
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    print(f"GPT-{args.config}: {n_params/1e6:.1f}M params, seq {seq}, "
          f"batch {args.batch}")

    remat = {"0": False, "1": True, "dots": "dots"}[args.remat]
    step, params, opt_state = gpt_mod.build_functional_train_step(
        model, lr=args.lr, remat=remat,
        sharding_stage=args.sharding_stage,
        ce_chunk_rows=2048 if cfg.vocab_size > 10000 else 0)

    rng = np.random.RandomState(args.seed)
    ids = rng.randint(0, cfg.vocab_size, (args.batch, seq)).astype("int32")
    labels = rng.randint(0, cfg.vocab_size,
                         (args.batch, seq)).astype("int64")
    if need > 1:
        ids = mesh_mod.shard_batch(ids)
        labels = mesh_mod.shard_batch(labels)

    exporter = None
    if args.metrics_dir is not None:
        # the serving registry doubles as the train-side metrics surface
        # (ROADMAP item 4): same exponential histograms, same TB event
        # files, same .prom dump — one observability substrate for both
        # halves of the system
        from paddle_tpu.serving.metrics import (MetricsFileExporter,
                                                MetricsRegistry)

        reg = MetricsRegistry()
        m_loss = reg.gauge("train_loss", "cross-entropy at the step")
        m_toks = reg.gauge("train_tokens_per_sec", "steady-state rate")
        peak = PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)
        m_mfu = None if peak is None else reg.gauge(
            "train_mfu", "model FLOP utilization vs the device's "
                         "published bf16 peak")
        m_steps = reg.counter("train_steps", "optimizer steps done")
        m_step_s = reg.histogram("train_step_s", "train step wall time")
        exporter = MetricsFileExporter(reg, args.metrics_dir)
        # ~6ND forward+backward FLOPs/token (standard MFU numerator);
        # the rate below counts the GLOBAL batch, so the denominator is
        # per-chip peak x mesh size
        flops_per_token = 6.0 * n_params

    losses = []
    t0 = time.time()
    t_step = t0
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(np.asarray(loss)))
        now = time.time()
        if i == 0:
            t0 = now  # exclude compile
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {losses[-1]:.4f}", flush=True)
        if exporter is not None:
            m_steps.inc()                  # every optimizer step counts
            m_loss.set(losses[-1])
            if i > 0:
                # step 0 pays JIT compilation — keep it out of the
                # step-time histogram and rate gauges (same post-warmup
                # convention the serving benches use), matching the
                # printed tokens/s which also excludes compile
                dt = max(now - t_step, 1e-9)
                rate = args.batch * seq / dt
                m_toks.set(rate)
                if m_mfu is not None:
                    m_mfu.set(rate * flops_per_token
                              / (peak * max(need, 1)))
                m_step_s.observe(dt)
            exporter.flush(i)
        t_step = now
    steps_timed = max(args.steps - 1, 1)
    tok_s = args.batch * seq * steps_timed / max(time.time() - t0, 1e-9)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"{tok_s:,.0f} tokens/s")
    if exporter is not None:
        exporter.close()
        print(f"metrics: tensorboard --logdir {args.metrics_dir} "
              f"({len(reg.scalars())} series); Prometheus dump "
              f"{exporter.prom_path}")


if __name__ == "__main__":
    main()
