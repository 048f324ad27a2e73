"""BASELINE config 1 (second backbone): ViT classification training.

End-to-end supervised training of a VisionTransformer with CrossEntropyLoss
+ AdamW (synthetic images; the compute path — patch conv, SDPA encoder,
head — is the real one).

    python examples/train_vit.py --steps 20
    python examples/train_vit.py --arch vit_b_16 --img 224   # full size
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--img", type=int, default=32)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--arch", type=str, default="vit_tiny")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.vision import models as vm
    from paddle_tpu.utils import compile_cache

    compile_cache.configure()

    paddle.seed(args.seed)
    ctor = getattr(vm, args.arch)
    model = ctor(num_classes=args.classes, img_size=args.img)
    criterion = nn.CrossEntropyLoss()
    optimizer = opt.AdamW(learning_rate=args.lr,
                          parameters=model.parameters(), weight_decay=0.05,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))

    rng = np.random.RandomState(args.seed)
    images = rng.randn(args.batch, 3, args.img, args.img).astype("float32")
    labels = rng.randint(0, args.classes, (args.batch, 1)).astype("int64")

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        logits = model(paddle.to_tensor(images))
        loss = criterion(logits, paddle.to_tensor(labels))
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        losses.append(float(loss.numpy()))
        if step % 5 == 0 or step == args.steps - 1:
            img_s = (args.batch * (step + 1)) / (time.time() - t0)
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"images/s {img_s:,.1f}", flush=True)
    assert np.isfinite(losses).all(), "non-finite loss"
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
