"""``paddle.distributed.spawn`` — in-Python multi-process launch.

Parity: ``/root/reference/python/paddle/distributed/spawn.py`` (``spawn``:
func + args + nprocs + join, per-process env prepared by
``_prepare_trainer_env``).  Each child gets the same ``PADDLE_*`` protocol
the CLI launcher produces, then runs ``func(*args)``; rank is available via
``paddle.distributed.get_rank()`` / ``ParallelEnv`` as in the reference.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import subprocess
import sys
import traceback
from typing import Optional, Sequence

from .launch_utils import Cluster, find_free_port, rank_env


class MultiprocessContext:
    """Parity: spawn.py MultiprocessContext — join/terminate over the pool."""

    def __init__(self, processes, error_queues):
        self.processes = processes
        self.error_queues = error_queues

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for ALL ranks concurrently; terminate the pool on the first
        failure (a serial per-rank join would deadlock when a crashed later
        rank leaves an earlier rank blocked in a collective)."""
        import time

        deadline = time.time() + timeout if timeout is not None else None
        failed = []
        while True:
            alive = [p for p in self.processes if p.exitcode is None]
            failed = [(i, p.exitcode) for i, p in enumerate(self.processes)
                      if p.exitcode not in (0, None)]
            if failed or not alive:
                break
            if deadline and time.time() > deadline:
                return False
            time.sleep(0.1)
        if failed:
            for p in self.processes:
                if p.is_alive():
                    p.terminate()
            for p in self.processes:
                p.join(10)
            msgs = []
            for i, code in failed:
                err = ""
                try:
                    if not self.error_queues[i].empty():
                        err = self.error_queues[i].get()
                except OSError:
                    pass
                msgs.append(f"rank {i} exited with code {code}\n{err}")
            raise RuntimeError("spawn: trainer failure:\n" + "\n".join(msgs))
        return True


def _worker(func, args, env, error_queue):
    try:
        os.environ.update(env)
        func(*args)
    except KeyboardInterrupt:
        pass
    except Exception:
        error_queue.put(traceback.format_exc())
        raise


def _local_device_count() -> int:
    """Count this host's devices in a short-lived child.  The parent must
    stay off JAX: a process that has initialised the backend holds the
    chip, and the ranks it then starts could not open it."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.local_device_count())"],
        check=True, capture_output=True, text=True)
    return int(out.stdout.strip().splitlines()[-1])


def spawn(func, args: Sequence = (), nprocs: int = -1, join: bool = True,
          daemon: bool = False, **options):
    """Spawn ``nprocs`` processes running ``func(*args)`` with the PADDLE_*
    env protocol installed (reference spawn.py semantics).  Proven on CPU
    ranks only: nothing here limits which chips a rank opens, so N ranks
    on a multi-chip host would each reach for every chip."""
    if nprocs == -1:
        nprocs = max(_local_device_count(), 1)
    cluster = Cluster(ips=["127.0.0.1"], nproc_per_node=nprocs,
                      master="127.0.0.1",
                      master_port=int(options.get("master_port")
                                      or find_free_port()))
    ctx = mp.get_context(options.get("start_method", "spawn"))
    processes, error_queues = [], []
    for rank in range(nprocs):
        env = rank_env(cluster, rank, devices=str(rank))
        env.update(options.get("env", {}))
        q = ctx.SimpleQueue()
        p = ctx.Process(target=_worker, args=(func, tuple(args), env, q),
                        daemon=daemon)
        p.start()
        processes.append(p)
        error_queues.append(q)
    context = MultiprocessContext(processes, error_queues)
    if not join:
        return context
    context.join()
    return context
