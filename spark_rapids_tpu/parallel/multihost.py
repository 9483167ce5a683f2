"""Multi-host distributed backend: DCN-aware meshes + two-level exchange.

Role of the reference's cluster-scale shuffle transport (SURVEY §2.7:
UCXShuffleTransport peer-to-peer over RDMA between executors on
different nodes, driver-RPC heartbeat registration Plugin.scala:436-447).
TPU-native, cross-host traffic rides DCN while intra-host traffic rides
ICI, and both are the SAME jax collective — only the mesh axis differs.
This module owns:

- `init_distributed()`: idempotent jax.distributed initialization from
  explicit args or the standard env (COORDINATOR_ADDRESS, NUM_PROCESSES,
  PROCESS_ID) — the executor-plugin startup step (Plugin.scala:496) for
  a multi-host deployment.  Single-process when nothing is configured.
- `make_cluster_mesh(ici_size)`: a 2-axis ("dcn", "ici") mesh: devices
  grouped so the minor axis stays within a host (ICI-connected) and the
  major axis crosses hosts (DCN).  On one host it still works — the
  "dcn" axis degenerates to groups of local devices, which is exactly
  how the 8-virtual-CPU tests model a 2-host x 4-chip topology.
- `two_level_exchange_plan` / `two_level_all_to_all`: hash exchange
  decomposed hierarchically — rows first all_to_all to the owning host
  over "dcn", then to the owning chip over "ici" — so each chip sends
  one DCN message per host instead of one per remote chip (the bounce-
  buffer windowing role, BounceBufferManager.scala, done by topology
  instead of buffering).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DCN_AXIS = "dcn"
ICI_AXIS = "ici"

_INITIALIZED = False


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> bool:
    """Initialize jax.distributed once per process.  Returns True when a
    multi-process runtime was started, False for single-process."""
    global _INITIALIZED
    if _INITIALIZED:
        return jax.process_count() > 1
    explicit = (coordinator is not None or num_processes is not None
                or process_id is not None)
    coordinator = coordinator or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes if num_processes is not None else \
        int(os.environ.get("NUM_PROCESSES", "0") or 0)
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    if not coordinator or num_processes <= 1:
        # standard Cloud TPU pod tooling sets no COORDINATOR_ADDRESS —
        # an argless initialize() auto-detects the slice via TPU
        # metadata.  Only when the caller passed NOTHING explicit
        # (explicit args always win, incl. num_processes=1 meaning
        # "stay single-process"); TPU_SKIP_DISTRIBUTED_INIT=1 opts out.
        skip = os.environ.get("TPU_SKIP_DISTRIBUTED_INIT", "").lower() \
            in ("1", "true", "yes")
        if not explicit and not skip and \
                os.environ.get("TPU_WORKER_HOSTNAMES"):
            jax.distributed.initialize()
            _INITIALIZED = True
            return jax.process_count() > 1
        _INITIALIZED = True
        return False
    # process_id=None lets jax's cluster auto-detection assign ids
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True
    return True


def make_cluster_mesh(ici_size: Optional[int] = None,
                      devices: Optional[Sequence] = None) -> Mesh:
    """(dcn, ici) mesh.  `ici_size` = chips per host group; defaults to
    jax.local_device_count() (every local chip shares ICI)."""
    devs = list(devices if devices is not None else jax.devices())
    ici = ici_size or jax.local_device_count()
    if ici > len(devs):
        raise ValueError(f"ici_size={ici} exceeds device count "
                         f"{len(devs)} — an 'intra-host' axis spanning "
                         f"hosts would put DCN traffic on the ICI hop")
    if len(devs) % ici:
        raise ValueError(f"{len(devs)} devices not divisible by "
                         f"ici_size={ici}")
    grid = np.asarray(devs).reshape(len(devs) // ici, ici)
    return Mesh(grid, (DCN_AXIS, ICI_AXIS))


def cluster_row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows data-parallel over ALL chips (both axes)."""
    return NamedSharding(mesh, P((DCN_AXIS, ICI_AXIS)))


def owner_of_partition(part: int, n_hosts: int, ici: int
                       ) -> Tuple[int, int]:
    """Partition p lives on chip (host, lane) = divmod(p, ici): hash
    ranges are contiguous per host so the DCN hop is a single
    neighbor-set exchange."""
    if not 0 <= part < n_hosts * ici:
        raise ValueError(f"partition {part} out of range for "
                         f"{n_hosts}x{ici} mesh")
    return divmod(part, ici)


def two_level_all_to_all(mesh: Mesh, lanes, live, dest):
    """Hierarchical exchange of fixed-capacity shards.

    Per chip: rows carry a destination chip id in [0, n_chips).  Stage 1
    routes every row to its destination HOST over the "dcn" axis; stage
    2 routes within the host to the destination chip over "ici".  Data
    crosses DCN exactly once, in host-count messages, then fans out over
    ICI — the hierarchical (hybrid) collective pattern for TPU pods.

    lanes: global value arrays [n_chips * cap]; live: bool; dest: int32
    chip ids.  Returns (out_lanes, out_live) where each chip's output
    block is cap * n_hosts * ici rows (stage 1 multiplies per-chip
    capacity by n_hosts, stage 2 by ici — the worst case is every row
    targeting one chip); derive per-chip block size from the returned
    shape.  Rows land grouped by source, order within a chip is not
    specified (exchange semantics, same contract as a flat all_to_all).
    """
    from ..runtime.faults import fire_active
    fire_active("exchange")     # chaos site: the DCN/ICI collective hop
    n_hosts, ici = mesh.devices.shape

    def stage(axis: str, n_groups: int, group_of, chip_lanes, chip_live,
              chip_dest, forward_dest: bool = True):
        # bucket rows by destination group along `axis`, pad to quota,
        # then all_to_all delivers each group its bucket
        quota = chip_lanes[0].shape[0]
        order = jnp.argsort(jnp.where(chip_live, group_of(chip_dest),
                                      n_groups))
        counts = jnp.bincount(
            jnp.where(chip_live, group_of(chip_dest), n_groups),
            length=n_groups + 1)[:n_groups]
        starts = jnp.concatenate(
            [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
        idx = jnp.arange(n_groups * quota)
        g = idx // quota
        k = idx % quota
        valid = k < counts[g]
        src = jnp.where(valid, order[
            jnp.clip(starts[g] + k, 0, quota - 1)], 0)
        outs = []
        # the dest lane only travels when a later stage still routes on
        # it — the final stage skips that whole collective
        send = chip_lanes + ([chip_dest] if forward_dest else [])
        for lane in send:
            staged = lane[src].reshape(n_groups, quota)
            outs.append(jax.lax.all_to_all(
                staged, axis, 0, 0, tiled=False))
        staged_live = (chip_live[src] & valid).reshape(n_groups, quota)
        live_out = jax.lax.all_to_all(staged_live, axis, 0, 0,
                                      tiled=False)
        flat = [o.reshape(-1) for o in outs]
        if forward_dest:
            return flat[:-1], live_out.reshape(-1), flat[-1]
        return flat, live_out.reshape(-1), None

    def prog(*args):
        n = len(lanes)
        chip_lanes = [a.reshape(-1) for a in args[:n]]
        chip_live = args[n].reshape(-1)
        chip_dest = args[n + 1].reshape(-1)
        # stage 1: to owning host over DCN
        l1, live1, dest1 = stage(DCN_AXIS, n_hosts,
                                 lambda d: d // ici,
                                 chip_lanes, chip_live, chip_dest)
        # stage 2: to owning chip over ICI
        l2, live2, _ = stage(ICI_AXIS, ici, lambda d: d % ici,
                             l1, live1, dest1, forward_dest=False)
        return tuple(o[None, :] for o in l2) + (live2[None, :],)

    shard = cluster_row_sharding(mesh)
    spec = P((DCN_AXIS, ICI_AXIS))
    fn = shard_map(prog, mesh=mesh,
                       in_specs=tuple([spec] * (len(lanes) + 2)),
                       out_specs=tuple([spec] * (len(lanes) + 1)))
    put = lambda a: jax.device_put(a, shard)
    outs = fn(*[put(a) for a in lanes], put(live),
              put(dest.astype(jnp.int32)))
    return list(outs[:-1]), outs[-1]
