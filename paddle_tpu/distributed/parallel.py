"""Parallel environment bootstrap.

Parity: ``/root/reference/python/paddle/distributed/parallel.py``
(``init_parallel_env``:58 — env parsing, TCP store, NCCLParallelContext init)
— mapped to ``jax.distributed.initialize`` + a device mesh (SURVEY.md §2.4):
no ring ids, no comm streams, no TCP id exchange.
"""

from __future__ import annotations

import os
from typing import Optional

from . import env as dist_env


class ParallelEnv:
    """Parity: fluid/dygraph/parallel.py ParallelEnv."""

    def __init__(self):
        self._rank = dist_env.get_rank()
        self._world_size = dist_env.get_world_size()
        from ..framework import flags as _flags

        sel = _flags.flag("FLAGS_selected_tpus") or os.environ.get(
            "FLAGS_selected_tpus", "0")
        self._device_id = int(str(sel).split(",")[0] or 0)

    @property
    def rank(self):
        return self._rank

    @property
    def local_rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def nranks(self):
        return self._world_size

    @property
    def device_id(self):
        return self._device_id

    @property
    def current_endpoint(self):
        eps = self.trainer_endpoints
        return eps[self._rank] if self._rank < len(eps) else ""

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")


_initialized = False


def init_parallel_env() -> ParallelEnv:
    """Initialize multi-host jax.distributed when launched by the fleet
    launcher (PADDLE_* env present) or TPU pod env; idempotent."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR")
    nprocs = os.environ.get("PADDLE_TRAINERS_NUM")
    pid = os.environ.get("PADDLE_TRAINER_ID")
    if coord and nprocs and int(nprocs) > 1:
        import jax

        # Multi-PROCESS collectives on the CPU backend need the gloo
        # transport (the default CPU client only wires intra-process
        # device collectives and fails jitted collectives with
        # "Multiprocess computations aren't implemented on the CPU
        # backend").  Must be set before the backend initializes, so key
        # off the configured platform rather than jax.default_backend().
        plats = (jax.config.jax_platforms or os.environ.get(
            "JAX_PLATFORMS", "")).split(",")
        if plats and plats[0].strip() == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}" if ":" not in coord else coord,
            num_processes=int(nprocs),
            process_id=int(pid or 0),
        )
    _initialized = True
    # default mesh over all devices (1-D data-parallel) unless fleet topology
    # installs a hybrid mesh later
    from . import mesh as mesh_mod

    mesh_mod.ensure_default_mesh()
    return ParallelEnv()


def get_rank():
    return dist_env.get_rank()


def get_world_size():
    return dist_env.get_world_size()
