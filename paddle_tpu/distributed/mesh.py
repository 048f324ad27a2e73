"""Global device-mesh registry — the TPU-native replacement for the
reference's communicator registry.

Parity role: ``/root/reference/paddle/fluid/platform/collective_helper.h:69``
(per-ring NCCLComm map) + ``fleet/base/topology.py`` rank arithmetic.  Here a
"ring" is a NAMED MESH AXIS of one global ``jax.sharding.Mesh``; groups are
axis names, shardings are PartitionSpecs, and XLA lowers collectives onto ICI.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None

# canonical hybrid axis order (outermost..innermost): dp, pp, sharding, mp
# — mp innermost so tensor-parallel collectives ride the fastest ICI links,
# matching the reference's HybridCommunicateGroup order (topology.py:36).
HYBRID_AXES = ("dp", "pp", "sharding", "mp")

# the axes a global batch is split over: ZeRO's sharding group IS a
# data-parallel group (each sharding rank consumes different data; only
# optimizer state/grads/params are partitioned — reference
# fleet/meta_optimizers/sharding_optimizer.py semantics)
BATCH_AXES = ("dp", "sharding")


def set_mesh(mesh: Mesh) -> Mesh:
    global _MESH
    _MESH = mesh
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def ensure_default_mesh() -> Mesh:
    global _MESH
    if _MESH is None:
        devs = np.array(jax.devices())
        _MESH = Mesh(devs.reshape(-1), axis_names=("dp",))
    return _MESH


def build_hybrid_mesh(dp: int = 1, mp: int = 1, pp: int = 1, sharding: int = 1,
                      devices=None) -> Mesh:
    """Create (and install) the 4-axis hybrid mesh ``(dp, pp, sharding, mp)``.

    Parity: HybridCommunicateGroup's rank mesh (topology.py:117); degrees from
    DistributedStrategy.hybrid_configs (distributed_strategy.py:835-847).
    """
    devices = np.array(devices if devices is not None else jax.devices())
    need = dp * mp * pp * sharding
    if devices.size < need:
        raise ValueError(
            f"hybrid topology dp={dp} mp={mp} pp={pp} sharding={sharding} "
            f"needs {need} devices, have {devices.size}"
        )
    devices = devices[:need].reshape(dp, pp, sharding, mp)
    return set_mesh(Mesh(devices, axis_names=HYBRID_AXES))


def sharding_for(*spec) -> NamedSharding:
    return NamedSharding(ensure_default_mesh(), P(*spec))


def replicate(x):
    """Place an array replicated across the mesh."""
    mesh = ensure_default_mesh()
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard_batch(x, axis_names: Tuple[str, ...] = BATCH_AXES):
    """Shard the leading (batch) dim over the given mesh axes."""
    mesh = ensure_default_mesh()
    names = tuple(a for a in axis_names if a in mesh.axis_names and mesh.shape[a] > 1)
    if not names:
        return jax.device_put(x, NamedSharding(mesh, P()))
    spec = P(names if len(names) > 1 else names[0])
    return jax.device_put(x, NamedSharding(mesh, spec))


def axis_size(name: str) -> int:
    mesh = get_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return int(mesh.shape[name])
