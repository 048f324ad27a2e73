"""BASELINE config 4 (stretch): DeepFM / wide&deep CTR training on the
collective path.

The reference serves these PaddleRec workloads through the brpc parameter
server; the north star routes them through the collective path instead —
one fused on-device embedding table (rows shardable over a mesh axis, the
``c_embedding`` role) and dense XLA gradients.  Synthetic Criteo-like data
with a recoverable signal; reports loss + AUC.

    python examples/train_deepfm.py --steps 100
    python examples/train_deepfm.py --model wide_deep --fields 26 --vocab 10000
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=["deepfm", "wide_deep"], default="deepfm")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--fields", type=int, default=26)
    p.add_argument("--vocab", type=int, default=1000,
                   help="vocabulary per categorical field")
    p.add_argument("--dense", type=int, default=13)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.nn import functional as F
    from paddle_tpu.metric import Auc
    from paddle_tpu.models import (
        DeepFM, RecConfig, WideDeep, synthetic_click_batch)
    from paddle_tpu.utils import compile_cache

    compile_cache.configure()

    paddle.seed(args.seed)
    cfg = RecConfig(
        field_vocab_sizes=(args.vocab,) * args.fields,
        dense_dim=args.dense, embedding_dim=args.dim)
    model = (DeepFM if args.model == "deepfm" else WideDeep)(cfg)
    optimizer = opt.Adam(args.lr, parameters=model.parameters())

    n_params = sum(int(np.prod(p_.shape)) for p_ in model.parameters())
    print(f"{args.model}: {cfg.num_fields} fields x {args.vocab} vocab, "
          f"{n_params / 1e6:.1f}M params")

    t0 = time.time()
    losses = []
    for step in range(args.steps):
        ids, dense, label = synthetic_click_batch(cfg, args.batch, seed=step)
        logit = model(paddle.to_tensor(ids), paddle.to_tensor(dense))
        loss = F.binary_cross_entropy_with_logits(logit, paddle.to_tensor(label))
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        losses.append(float(loss.numpy()))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {losses[-1]:.4f}")
    dt = time.time() - t0

    # held-out AUC
    ids, dense, label = synthetic_click_batch(cfg, 8192, seed=10**6)
    logit = model(paddle.to_tensor(ids), paddle.to_tensor(dense))
    prob = 1 / (1 + np.exp(-np.asarray(logit.numpy()).ravel()))
    m = Auc()
    m.update(np.stack([1 - prob, prob], axis=1), label)
    ex_s = args.steps * args.batch / dt
    print(f"done: loss {np.mean(losses[:5]):.4f} -> {np.mean(losses[-5:]):.4f}, "
          f"held-out AUC {m.accumulate():.4f}, {ex_s:,.0f} examples/s")
    if args.steps > 10:
        assert np.mean(losses[-5:]) < np.mean(losses[:5])


if __name__ == "__main__":
    main()
