"""2-rank training script used by test_launch.py (run via the launcher or
spawn).  Exercises: PADDLE_* env consumption, jax.distributed rendezvous, a
cross-process collective, and one data-parallel grad computation whose
result provably mixes both ranks' data."""

import json
import os
import sys

# one CPU device per process: scrub the 8-device test flag BEFORE jax's
# backend initializes.  The launcher is proven on CPU ranks only, so pin
# the platform here rather than trust the caller's environment.
flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = " ".join(
    f for f in flags.split() if "host_platform_device_count" not in f)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from paddle_tpu.distributed import parallel  # noqa: E402

env = parallel.init_parallel_env()
rank, ws = env.rank, env.world_size
assert ws == 2, f"world_size {ws}"
assert jax.process_count() == 2, jax.process_count()
assert env.current_endpoint and len(env.trainer_endpoints) == 2

# cross-process collective
from jax.experimental import multihost_utils  # noqa: E402

g = multihost_utils.process_allgather(jnp.array([float(rank + 1)]))
gathered = np.asarray(g).reshape(-1).tolist()
assert gathered == [1.0, 2.0], gathered

# data-parallel grad step over a global mesh spanning both processes:
# rank r contributes rows full of (r+1); grad of mean(X @ w) w.r.t. w is the
# column mean over the GLOBAL batch = (1+2)/2 = 1.5 — provably cross-rank.
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

mesh = Mesh(np.array(jax.devices()), ("dp",))
local = np.full((2, 4), float(rank + 1), "float32")
gx = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), local)
w = jnp.ones((4,), jnp.float32)


@jax.jit
def grad_fn(x, w):
    return jax.grad(lambda w_: jnp.mean(x @ w_))(w)


gw = np.asarray(grad_fn(gx, w))
assert np.allclose(gw, 1.5), gw

out_dir = sys.argv[1]
with open(os.path.join(out_dir, f"result.{rank}.json"), "w") as f:
    json.dump({"rank": rank, "world_size": ws, "gathered": gathered,
               "grad": gw.tolist(),
               "endpoint": env.current_endpoint}, f)
print(f"rank {rank} OK")
