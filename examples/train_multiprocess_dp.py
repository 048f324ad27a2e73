"""Multi-process data-parallel training via the launcher.

    python -m paddle_tpu.distributed.launch --nproc_per_node=2 \
        examples/train_multiprocess_dp.py

Each process holds its own devices and feeds its LOCAL batch shard; the
global batch is assembled with ``jax.make_array_from_process_local_data``
over a mesh spanning every process, so gradients are globally exact (XLA
inserts the cross-process reductions).  Parameters stay replicated and
bit-identical on all ranks — verified at the end with a cross-process
allgather.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# one device per process keeps the arithmetic obvious on CPU test runs
flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = " ".join(
    f for f in flags.split() if "host_platform_device_count" not in f)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--local_batch", type=int, default=8)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import parallel
    from paddle_tpu.utils import compile_cache

    compile_cache.configure()

    env = parallel.init_parallel_env()
    rank, ws = env.rank, env.world_size

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    repl = NamedSharding(mesh, P())
    batched = NamedSharding(mesh, P("dp"))

    rng = np.random.RandomState(0)  # same init on every rank
    w1 = jax.device_put(rng.randn(16, args.hidden).astype("float32") * 0.1, repl)
    w2 = jax.device_put(rng.randn(args.hidden, 1).astype("float32") * 0.1, repl)

    @jax.jit
    def step(w1, w2, x, y):
        def loss_fn(w1, w2):
            h = jnp.tanh(x @ w1)
            return jnp.mean((h @ w2 - y) ** 2)

        loss, (g1, g2) = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2)
        return w1 - args.lr * g1, w2 - args.lr * g2, loss

    data_rng = np.random.RandomState(100 + rank)  # DIFFERENT data per rank
    for i in range(args.steps):
        xl = data_rng.randn(args.local_batch, 16).astype("float32")
        yl = xl.sum(1, keepdims=True).astype("float32") * 0.3
        x = jax.make_array_from_process_local_data(batched, xl)
        y = jax.make_array_from_process_local_data(batched, yl)
        w1, w2, loss = step(w1, w2, x, y)
        if rank == 0 and (i % 5 == 0 or i == args.steps - 1):
            print(f"step {i:3d} loss {float(np.asarray(loss)):.5f}",
                  flush=True)

    # params must be bit-identical across ranks (global grads)
    from jax.experimental import multihost_utils

    mine = np.asarray(w1).ravel()[:8]
    allw = np.asarray(multihost_utils.process_allgather(jnp.asarray(mine)))
    for r in range(ws):
        np.testing.assert_array_equal(allw.reshape(ws, -1)[r], mine)
    print(f"rank {rank}: params identical across {ws} processes OK",
          flush=True)
    # serialize shutdown: without a final barrier, rank 0 can exit (taking
    # the coordinator service with it) while peers are mid-heartbeat
    multihost_utils.sync_global_devices("exit")


if __name__ == "__main__":
    main()
