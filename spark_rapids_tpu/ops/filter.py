"""Row-selection kernels: mask compaction and index gather.

The reference filters by cuDF `Table.filter` / applies gather maps produced
by joins (GpuFilterExec basicPhysicalOperators.scala:795, JoinGatherer).
TPU-first realization on static shapes:

  * compaction = one stable argsort of the inverted keep-mask (int8 keys);
    kept rows move to the front preserving order, padding/dropped rows sink.
    XLA lowers the sort onto the device; no host round-trip besides the
    selected-row count, which must come back anyway because `num_rows` is
    host metadata (same host sync the reference performs to size outputs).

  * gather = plain take along axis with clipped indices; out-of-range
    semantics are handled by an explicit validity lane, mirroring cuDF's
    OutOfBoundsPolicy.NULLIFY.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..config import TpuConf, DEFAULT_CONF
from ..ops.kernels import live_mask

_COMPACT_CACHE = {}


#: compaction_order finds the first `first` kept rows by rank, without a
#: sort, where they are at most one in this many of the rows.  The rank
#: search gathers log2(rows) times for every row it finds (23 ns a
#: gathered row on a v5e) and the argsort costs 4.5 ns a row: they meet
#: near one in 110.  At one in 16, q5's seam (262,144 of 4M rows) ran
#: 34 ms slower than its argsort (my chip run, PR 35, call 4)
_FIRST_KEPT_SHARE = 256


def compaction_order(keep: jax.Array, first: Optional[int] = None
                     ) -> jax.Array:
    """Indices that bring keep=True rows to the front, stably.

    `first`: only that many leading entries are wanted (a caller that
    knows the kept rows fit: a split-plan seam after its row-count sync).
    Where they are a small share of the rows (_FIRST_KEPT_SHARE), the
    j-th kept row is found
    by rank: the first position at which the running count of kept rows
    reaches j, a binary search in the cumulative sum.  No sort: on the
    chip the capacity-long argsort was the seams' own cost (18 ms at 4M
    rows, 0.3 s at 67M: PERF.md), and a sort of every new length
    compiles for most of a minute.  Entries past the kept rows point at
    the last row; callers mask them by the count, as before."""
    n = keep.shape[0]
    if first is not None and first * _FIRST_KEPT_SHARE <= n:
        from .kernels import blocked_cumsum
        seen = blocked_cumsum(keep.astype(jnp.int32))
        at = jnp.searchsorted(seen, jnp.arange(1, first + 1, dtype=jnp.int32),
                              side="left", method="scan_unrolled")
        return jnp.minimum(at, n - 1).astype(jnp.int32)
    return jnp.argsort(jnp.where(keep, jnp.int8(0), jnp.int8(1)),
                       stable=True)


def grouped_take(lanes, idx: jax.Array):
    """Gather many (capacity,) lanes at the same indices, same-dtype
    lanes stacked into one (capacity, k) matrix per dtype.

    TPU gathers pay per gathered ROW (descriptor-driven DMA), so k lanes
    gathered as one matrix cost ~1 lane's descriptors instead of k —
    measured 3x for 8 int64 lanes at 8M on v5e.  (A variadic payload
    sort would be faster still at runtime but TPU sort COMPILE time
    scales ~linearly with operand count — 7.5 min for 17 operands at 8M
    — so gathers win end to end.)  Returns gathered lanes in order."""
    groups: dict = {}
    for slot, arr in enumerate(lanes):
        groups.setdefault(str(arr.dtype), []).append((slot, arr))
    out: dict = {}
    for _dt, members in groups.items():
        if len(members) == 1:
            slot, arr = members[0]
            out[slot] = jnp.take(arr, idx, axis=0)
        else:
            mat = jnp.stack([arr for _s, arr in members], axis=1)
            g = jnp.take(mat, idx, axis=0)
            for k, (slot, _arr) in enumerate(members):
                out[slot] = g[:, k]
    return [out[i] for i in range(len(lanes))]


def take_keys_valid(keys, keys_valid, extra, idx):
    """grouped_take of key lanes + their (possibly None) validity lanes
    + extra lanes at `idx`, preserving None validity slots.

    Returns (keys_out, keys_valid_out, extra_out).  One stacked gather
    pass per dtype class — the shared permute idiom of the sort-segment
    kernels (groupby/percentile), kept in one place so the lane
    bookkeeping cannot drift between copies."""
    kv = [v for v in keys_valid if v is not None]
    moved = grouped_take(list(keys) + kv + list(extra), idx)
    nk = len(keys)
    it = iter(moved[nk:nk + len(kv)])
    out_kv = [None if v is None else next(it) for v in keys_valid]
    return moved[:nk], out_kv, moved[nk + len(kv):]


def _compact_trace(ncols: int, has_hi: Tuple[bool, ...],
                   out_capacity=None):
    def run(datas, valids, his, keep):
        order = compaction_order(keep, out_capacity)
        count = jnp.sum(keep, dtype=jnp.int32)
        if out_capacity is not None:
            order = order[:out_capacity]
            count = jnp.minimum(count, out_capacity)
        lanes = []
        for i in range(ncols):
            lanes.append(datas[i])
            lanes.append(valids[i])
            if has_hi[i]:
                lanes.append(his[i])
        moved = grouped_take(lanes, order)
        live = jnp.arange(order.shape[0], dtype=jnp.int32) < count
        out = []
        j = 0
        for i in range(ncols):
            d = moved[j]
            v = moved[j + 1] & live
            j += 2
            h = None
            if has_hi[i]:
                h = moved[j]
                j += 1
            out.append((d, v, h))
        return out, count
    return run


def compact_batch(db: DeviceBatch, keep: jax.Array,
                  conf: TpuConf = DEFAULT_CONF,
                  sync: bool = False,
                  out_capacity: Optional[int] = None) -> DeviceBatch:
    """Keep rows where `keep` is True (padding rows must already be False).

    `out_capacity` (default: the batch's own) is the capacity of the
    result: the first that many kept rows, gathered at that capacity.  A
    caller passes it when it knows the kept rows fit.

    By default the surviving row count stays on device (`num_rows` becomes a
    0-d jax scalar) so a filter feeding another device operator costs zero
    host round-trips — one host sync per seam costs more than the padding
    a smaller bucket would save downstream.  Pass `sync=True` to
    fetch the count and re-bucket down (worth it before expensive downstream
    work when selectivity is high).
    """
    from .batch_ops import shrink_to_rows
    if db.thin is not None:
        # thin batch: deferred columns gather straight from their lane
        # sources into compacted position — one pass, no
        # materialize-then-compact double gather
        from ..columnar.lanes import compact_thin
        db = compact_thin(db, keep, out_capacity)
        if not sync:
            return db
        return shrink_to_rows(db, int(db.num_rows), conf)
    has_hi = tuple(c.data_hi is not None for c in db.columns)
    sig = (db.num_columns, has_hi, db.capacity,
           tuple(str(c.data.dtype) for c in db.columns), out_capacity)
    fn = _COMPACT_CACHE.get(sig)
    if fn is None:
        fn = jax.jit(_compact_trace(db.num_columns, has_hi, out_capacity))
        _COMPACT_CACHE[sig] = fn
    if any(has_hi):
        zeros = jnp.zeros((db.capacity,), jnp.int64)
        his = tuple(c.data_hi if h else zeros
                    for c, h in zip(db.columns, has_hi))
    else:
        his = tuple(c.data for c in db.columns)  # ignored by the trace
    outs, count = fn(tuple(c.data for c in db.columns),
                     tuple(c.validity for c in db.columns), his, keep)
    cols = [DeviceColumn(d, v, c.dtype, c.dictionary, h)
            for (d, v, h), c in zip(outs, db.columns)]
    if not sync:
        return DeviceBatch(cols, count, db.names, db.origin_file)
    return shrink_to_rows(
        DeviceBatch(cols, int(count), db.names, db.origin_file),
        int(count), conf)


def gather_batch(db: DeviceBatch, indices: jax.Array, out_rows: int,
                 names: List[str] = None,
                 null_out_of_bounds: bool = False) -> DeviceBatch:
    """Gather rows of `db` at `indices` (shape (out_capacity,)).

    Rows with index < 0 or >= num_rows become null when
    `null_out_of_bounds` (cuDF NULLIFY), used by outer joins; rows past
    `out_rows` are padding.
    """
    cap_out = indices.shape[0]
    in_bounds = (indices >= 0) & (indices < jnp.int32(db.num_rows))
    safe = jnp.clip(indices, 0, max(db.capacity - 1, 0)).astype(jnp.int32)
    live = live_mask(cap_out, jnp.int32(out_rows))
    vmask = live & in_bounds if null_out_of_bounds else live

    lanes = []
    slots = []          # (col index, lane kind) per lane
    for ci, c in enumerate(db.columns):
        lanes.append(c.data)
        slots.append((ci, "d"))
        lanes.append(c.validity)
        slots.append((ci, "v"))
        if c.data_hi is not None:
            lanes.append(c.data_hi)
            slots.append((ci, "h"))
    moved = grouped_take(lanes, safe)
    gathered = {slot: arr for slot, arr in zip(slots, moved)}
    cols = []
    for ci, c in enumerate(db.columns):
        d = gathered[(ci, "d")]
        v = gathered[(ci, "v")] & vmask
        h = gathered.get((ci, "h"))
        cols.append(DeviceColumn(d, v, c.dtype, c.dictionary, h))
    return DeviceBatch(cols, out_rows, names or list(db.names))
