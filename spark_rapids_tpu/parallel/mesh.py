"""Device-mesh plumbing: the ICI/DCN analogue of the reference's shuffle
transport (SURVEY §2.7, UCXShuffleTransport).

The reference moves shuffle blocks peer-to-peer over UCX (RDMA/NVLink).
TPU-native, an exchange between co-scheduled workers is a `lax.all_to_all`
over a `jax.sharding.Mesh` axis: every chip owns a row shard, hash-
partitions it by key, and the collective delivers each chip its hash range
over ICI.  Multi-host meshes extend the same program over DCN — the code is
identical, only the mesh construction differs (jax.distributed).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"


def make_mesh(n_devices: Optional[int] = None,
              axis: str = SHARD_AXIS) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return Mesh(np.asarray(devs[:n]), (axis,))


def row_sharding(mesh: Mesh, axis: str = SHARD_AXIS) -> NamedSharding:
    """Rows split over the mesh: the SQL data-parallel layout."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    """Broadcast layout (GpuBroadcastExchangeExec analogue)."""
    return NamedSharding(mesh, P())
