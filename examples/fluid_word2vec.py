"""The classic fluid N-gram word2vec tutorial, v2.1 style — a second
unmodified-pre-2.x-script proof for the ``paddle.fluid`` compat namespace
(alongside examples/fluid_mnist.py): ``fluid.layers.embedding`` with
``param_attr`` sharing, ``concat``, ``fc``, ``cross_entropy``,
``SGDOptimizer.minimize``, ``fluid.DataFeeder`` + ``paddle.batch`` feeding
an ``Executor`` loop.

    python examples/fluid_word2vec.py --steps 60
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.utils import compile_cache

EMBED_SIZE = 32
HIDDEN_SIZE = 64
N = 4  # 4-gram: 3 context words -> next word
DICT_SIZE = 120


def inference_program(words):
    embeds = []
    for w in words[:-1]:
        embeds.append(fluid.layers.embedding(
            input=w, size=[DICT_SIZE, EMBED_SIZE],
            param_attr=fluid.ParamAttr(name="shared_w")))
    concat_embed = fluid.layers.concat(embeds, axis=1)
    hidden1 = fluid.layers.fc(input=concat_embed, size=HIDDEN_SIZE,
                              act="sigmoid")
    predict_word = fluid.layers.fc(input=hidden1, size=DICT_SIZE,
                                   act="softmax")
    return predict_word


def train_program(words):
    predict_word = inference_program(words)
    cost = fluid.layers.cross_entropy(input=predict_word, label=words[-1])
    avg_cost = fluid.layers.mean(cost)
    return predict_word, avg_cost


def synthetic_corpus_reader(seed=0, n_sent=400):
    """A deterministic 'language': word k is usually followed by
    (3k + 1) % DICT_SIZE — learnable 4-gram structure."""

    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(n_sent):
            w = int(rng.randint(0, DICT_SIZE))
            sent = [w]
            for _ in range(N - 1):
                w = (3 * w + 1) % DICT_SIZE if rng.rand() < 0.9 \
                    else int(rng.randint(0, DICT_SIZE))
                sent.append(w)
            yield tuple([x] for x in sent)  # each word as a [1] int column

    return reader


def main():
    compile_cache.configure()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.5)
    args = p.parse_args()

    paddle.enable_static()
    paddle.seed(0)

    word_names = ["firstw", "secondw", "thirdw", "nextw"]
    words = [fluid.layers.data(name=n, shape=[1], dtype="int64")
             for n in word_names]
    predict, avg_cost = train_program(words)
    sgd = fluid.optimizer.SGDOptimizer(learning_rate=args.lr)
    sgd.minimize(avg_cost)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feeder = fluid.DataFeeder(feed_list=words, place=fluid.CPUPlace())
    batch_reader = paddle.batch(synthetic_corpus_reader(), args.batch)

    losses = []
    step = 0
    while step < args.steps:
        for batch in batch_reader():
            lv, = exe.run(fluid.default_main_program(),
                          feed=feeder.feed(batch), fetch_list=[avg_cost])
            losses.append(float(np.asarray(lv)))
            step += 1
            if step % 20 == 0 or step == args.steps:
                print(f"step {step:4d}  loss {losses[-1]:.4f}", flush=True)
            if step >= args.steps:
                break

    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    # the shared embedding was reused across the 3 context positions
    from paddle_tpu.framework.scope import global_scope

    w = np.asarray(global_scope().find_var("shared_w"))
    assert w.shape == (DICT_SIZE, EMBED_SIZE)
    print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(shared embedding {w.shape})")
    return losses


if __name__ == "__main__":
    main()
