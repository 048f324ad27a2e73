"""A v2.1-era fluid MNIST script, UNMODIFIED in style — the done-criterion
for the ``paddle.fluid`` compat namespace (round-4 verdict item 4): every
call below is the classic pre-2.x API (``fluid.layers.data``,
``fluid.nets.simple_img_conv_pool``, ``fluid.layers.fc``,
``fluid.layers.cross_entropy``, ``AdamOptimizer.minimize``, ``Executor``
feed/fetch), running on TPU through the same whole-block XLA executor as
the 2.x static path.

    python examples/fluid_mnist.py --steps 30
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.utils import compile_cache


def convolutional_neural_network(img, label):
    conv_pool_1 = fluid.nets.simple_img_conv_pool(
        input=img, filter_size=5, num_filters=20, pool_size=2,
        pool_stride=2, act="relu")
    conv_pool_2 = fluid.nets.simple_img_conv_pool(
        input=conv_pool_1, filter_size=5, num_filters=50, pool_size=2,
        pool_stride=2, act="relu")
    prediction = fluid.layers.fc(input=conv_pool_2, size=10, act="softmax")
    loss = fluid.layers.cross_entropy(input=prediction, label=label)
    avg_loss = fluid.layers.mean(loss)
    acc = fluid.layers.accuracy(input=prediction, label=label)
    return prediction, avg_loss, acc


def main():
    compile_cache.configure()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args()

    paddle.enable_static()
    paddle.seed(0)

    img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    prediction, avg_loss, acc = convolutional_neural_network(img, label)

    optimizer = fluid.optimizer.AdamOptimizer(learning_rate=args.lr)
    optimizer.minimize(avg_loss)

    place = fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())

    # synthetic MNIST-shaped data: class k lights up a distinct 7x7 patch
    rng = np.random.RandomState(0)
    losses, accs = [], []
    for step in range(args.steps):
        y = rng.randint(0, 10, (args.batch,))
        x = rng.rand(args.batch, 1, 28, 28).astype("float32") * 0.3
        for i, k in enumerate(y):
            r, c = divmod(int(k), 4)
            x[i, 0, r * 7:(r + 1) * 7, c * 7:(c + 1) * 7] += 1.0
        y = y.astype("int64").reshape(-1, 1)
        lv, av = exe.run(
            fluid.default_main_program(),
            feed={"img": x, "label": y},
            fetch_list=[avg_loss, acc])
        losses.append(float(lv))
        accs.append(float(av))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:3d}  loss {losses[-1]:.4f}  acc {accs[-1]:.3f}",
                  flush=True)

    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"acc {accs[0]:.3f} -> {accs[-1]:.3f}")
    return losses


if __name__ == "__main__":
    main()
