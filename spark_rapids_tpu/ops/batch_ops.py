"""Device batch utilities: concat, coalesce, slice (GpuCoalesceBatches role).

Concat is the workhorse under aggregation-merge, sort and join build sides
(reference Table.concatenate / GpuCoalesceBatches.scala:697).  String columns
carry per-batch dictionaries, so concat first unifies dictionaries on host
(dictionaries are small) and remaps codes on device.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn, bucket_capacity
from ..config import TpuConf, DEFAULT_CONF


def unify_dictionaries(dicts: Sequence[Optional[pa.Array]]):
    """-> (unified dict, [np remap array per input dict])."""
    arrs = [d.cast(pa.string()) if d is not None else pa.array([], pa.string())
            for d in dicts]
    combined = pa.concat_arrays(arrs)
    enc = pc.dictionary_encode(combined)
    codes = enc.indices.to_numpy(zero_copy_only=False).astype(np.int32)
    remaps = []
    off = 0
    for a in arrs:
        n = len(a)
        remaps.append(codes[off:off + n] if n else np.zeros(1, np.int32))
        off += n
    return enc.dictionary, remaps


def remap_string_column(col: DeviceColumn, remap: np.ndarray,
                        unified: pa.Array) -> DeviceColumn:
    table = jnp.asarray(remap)
    data = table[jnp.clip(col.data, 0, table.shape[0] - 1)]
    return DeviceColumn(data, col.validity, col.dtype, unified)


# Dictionary-identity caches: the SAME pa.Array dictionary object flows
# through every batch of a scan (and every sub-partition bucket of a
# materialized build side), so the O(dictionary) host work — uniqueness
# unification, cross-dictionary remap tables — is computed once per
# dictionary (pair), not once per probe batch.  Entries pin the
# dictionaries so id() reuse cannot alias a stale hit; tracers are never
# cached (whole-plan tracing).  The ``dict_remaps`` registry counter
# counts actual host computations, so a regression back to per-batch
# remapping is visible in the metrics plane.
#
# LOOKUP AND PUBLISH hold one lock: serving prepares plans concurrently,
# and without it two tenants preparing the same scan could interleave a
# miss-path compute with the eviction clear() (or each observe the other
# mid-publish) — the compute must be decided and the finished table
# published under a single critical section.

import threading

_DICT_CACHE_LOCK = threading.Lock()
_UNIQUE_DICT_CACHE: dict = {}
_REMAP_TABLE_CACHE: dict = {}


def _count_dict_remap() -> None:
    from ..obs.registry import DICT_REMAPS
    DICT_REMAPS.inc()


def ensure_unique_dict(col: DeviceColumn) -> DeviceColumn:
    """Code-equality == string-equality requires a duplicate-free dict."""
    d = col.dictionary
    if d is None:
        return col
    with _DICT_CACHE_LOCK:
        hit = _UNIQUE_DICT_CACHE.get(id(d))
        if hit is not None and hit[0] is d:
            unified, remap = hit[1], hit[2]
        else:
            _count_dict_remap()
            unified, remaps = unify_dictionaries([d])
            remap = None if len(unified) == len(d) else remaps[0]
            if len(_UNIQUE_DICT_CACHE) > 1024:
                _UNIQUE_DICT_CACHE.clear()
            _UNIQUE_DICT_CACHE[id(d)] = (d, unified, remap)
    if remap is None:
        return col
    return remap_string_column(col, remap, unified)


def remap_codes_into(col: DeviceColumn, target_dict: pa.Array) -> DeviceColumn:
    """Remap a string column's codes into `target_dict`'s code space; codes
    whose string is absent from the target map to -1 (equal to no valid
    code).  Lets a join probe stream remap against a build-side dictionary
    unified ONCE instead of re-unifying build+probe per batch; the remap
    table itself is cached per (source, target) dictionary pair so
    repeated probe batches (and sub-partition buckets) sharing
    dictionaries never recompute the host index_in."""
    src = col.dictionary
    if src is None:
        raise ValueError("remap_codes_into needs a dictionary column")
    if src is target_dict:
        # same dictionary object: codes are ALREADY in target space —
        # the common same-scan self-join / shared-upload case needs
        # neither table nor per-row gather
        return col
    key = (id(src), id(target_dict))
    with _DICT_CACHE_LOCK:
        hit = _REMAP_TABLE_CACHE.get(key)
        dev = hit[2] if hit is not None and hit[0] is src and \
            hit[1] is target_dict else None
    if dev is None:
        _count_dict_remap()
        idx = pc.index_in(src.cast(pa.string()), value_set=target_dict)
        table = np.asarray(idx.fill_null(-1).to_numpy(zero_copy_only=False),
                           dtype=np.int32)
        if not len(table):
            table = np.full(1, -1, np.int32)
        dev = jnp.asarray(table)
        if not isinstance(dev, jax.core.Tracer):
            with _DICT_CACHE_LOCK:
                if len(_REMAP_TABLE_CACHE) > 1024:
                    _REMAP_TABLE_CACHE.clear()
                _REMAP_TABLE_CACHE[key] = (src, target_dict, dev)
    data = dev[jnp.clip(col.data, 0, dev.shape[0] - 1)]
    return DeviceColumn(data, col.validity, col.dtype, target_dict)




def _hi_lane_of(col: DeviceColumn, upto=None) -> "jax.Array":
    """The column's hi int64 lane, synthesizing the sign-extension for
    single-lane (device-computed) wide values so mixed streams concat
    correctly."""
    if col.data_hi is not None:
        return col.data_hi if upto is None else col.data_hi[:upto]
    d = col.data if upto is None else col.data[:upto]
    d = d.astype(jnp.int64)
    return jnp.where(d < 0, jnp.int64(-1), jnp.int64(0))


def ensure_prefix(db: DeviceBatch, conf: TpuConf = DEFAULT_CONF
                  ) -> DeviceBatch:
    """Materialize a lazy selection vector (DeviceBatch.sel) and any
    deferred columns (DeviceBatch.thin) into the dense front-prefix form
    every slicing/concat/fetch path assumes."""
    if db.sel is None:
        if db.thin is None:
            return db
        from ..columnar.lanes import materialize_batch
        return materialize_batch(db, conf)
    from .filter import compact_batch
    # compact_batch resolves thin state in the same pass (compact_thin)
    return compact_batch(db, db.sel, conf)


def concat_batches(batches: List[DeviceBatch],
                   conf: TpuConf = DEFAULT_CONF,
                   masked: bool = False) -> DeviceBatch:
    """Concatenate device batches (same schema) into one bucketed batch.

    Batches with host-known counts concatenate tightly (layout decisions
    on host).  If ANY count is lazy (a device scalar / tracer), the lazy
    path concatenates full-capacity lanes and compacts live rows to the
    front on device — zero host syncs, at the cost of padding up to the
    capacity sum.  `masked`: the caller reads liveness as a mask
    (`row_mask()`: a sorted group-by does); where every count is lazy and
    nothing is deferred, the lanes are stacked as they are, selection
    vectors included, and come back under one — no compaction, which is
    an argsort and a gather a column over the whole capacity sum."""
    assert batches, "concat of zero batches"
    if masked and all(b.thin is None and not isinstance(b.num_rows, int)
                      for b in batches):
        return batches[0] if len(batches) == 1 \
            else _concat_batches_lazy(batches, conf, compact=False)
    batches = [ensure_prefix(b, conf) for b in batches]
    if len(batches) == 1:
        return batches[0]
    if any(not isinstance(b.num_rows, int) for b in batches):
        return _concat_batches_lazy(batches, conf)
    batches = [DeviceBatch(b.columns, int(b.num_rows), b.names,
                           b.origin_file) for b in batches]
    total = sum(b.num_rows for b in batches)
    cap = bucket_capacity(max(total, 1), conf)
    names = list(batches[0].names)
    ncols = batches[0].num_columns
    out_cols = []
    for ci in range(ncols):
        cols = [b.column(ci) for b in batches]
        dt = cols[0].dtype
        unified = None
        if isinstance(dt, t.StringType):
            unified, remaps = unify_dictionaries([c.dictionary for c in cols])
            cols = [remap_string_column(c, r, unified)
                    for c, r in zip(cols, remaps)]
        data_parts = [c.data[:b.num_rows] for c, b in zip(cols, batches)]
        if isinstance(dt, t.DoubleType) and \
                len({str(p.dtype) for p in data_parts}) > 1:
            # DOUBLE has two storage lanes (int64 bit patterns from host
            # uploads, native f64 from device compute; see columnar/device):
            # concatenating them raw would convert bit patterns NUMERICALLY.
            # Unify on f64 via the bitcast view.
            from .kernels import compute_view
            data_parts = [compute_view(p, dt) for p in data_parts]
        valid_parts = [c.validity[:b.num_rows] for c, b in zip(cols, batches)]
        pad = cap - total
        if pad:
            data_parts.append(jnp.zeros((pad,), cols[0].data.dtype))
            valid_parts.append(jnp.zeros((pad,), bool))
        hi = None
        if any(c.data_hi is not None for c in cols):
            hi_parts = [_hi_lane_of(c, b.num_rows)
                        for c, b in zip(cols, batches)]
            if pad:
                hi_parts.append(jnp.zeros((pad,), jnp.int64))
            hi = jnp.concatenate(hi_parts)
        out_cols.append(DeviceColumn(jnp.concatenate(data_parts),
                                     jnp.concatenate(valid_parts),
                                     dt, unified, hi))
    from ..columnar.device import merge_origin
    return DeviceBatch(out_cols, total, names,
                       merge_origin(b.origin_file for b in batches))


def _concat_batches_lazy(batches: List[DeviceBatch], conf: TpuConf,
                         compact: bool = True) -> DeviceBatch:
    """Sync-free concat: stack full-capacity lanes, then compact live rows
    to the front on device (ops/filter.py), or leave them where they are
    under a selection vector (`compact` false).  Capacities are host
    facts, so the output shape is static; the row count stays a device
    scalar."""
    from ..columnar.device import merge_origin
    from .filter import compact_batch
    cap_total = sum(b.capacity for b in batches)
    cap = bucket_capacity(max(cap_total, 1), conf)
    pad = cap - cap_total
    names = list(batches[0].names)
    live_parts = [b.row_mask() for b in batches]
    if pad:
        live_parts.append(jnp.zeros((pad,), bool))
    keep = jnp.concatenate(live_parts)
    out_cols = []
    for ci in range(batches[0].num_columns):
        cols = [b.column(ci) for b in batches]
        dt = cols[0].dtype
        unified = None
        if isinstance(dt, t.StringType):
            unified, remaps = unify_dictionaries(
                [c.dictionary for c in cols])
            cols = [remap_string_column(c, r, unified)
                    for c, r in zip(cols, remaps)]
        data_parts = [c.data for c in cols]
        if isinstance(dt, t.DoubleType) and \
                len({str(p.dtype) for p in data_parts}) > 1:
            from .kernels import compute_view
            data_parts = [compute_view(p, dt) for p in data_parts]
        valid_parts = [c.validity for c in cols]
        if pad:
            data_parts = data_parts + [jnp.zeros((pad,),
                                                 data_parts[0].dtype)]
            valid_parts = valid_parts + [jnp.zeros((pad,), bool)]
        hi = None
        if any(c.data_hi is not None for c in cols):
            hi_parts = [_hi_lane_of(c) for c in cols]
            if pad:
                hi_parts.append(jnp.zeros((pad,), jnp.int64))
            hi = jnp.concatenate(hi_parts)
        out_cols.append(DeviceColumn(jnp.concatenate(data_parts),
                                     jnp.concatenate(valid_parts),
                                     dt, unified, hi))
    total = sum(jnp.int32(b.num_rows) for b in batches)
    origin = merge_origin(b.origin_file for b in batches)
    if not compact:
        return DeviceBatch(out_cols, total, names, origin, sel=keep)
    return compact_batch(DeviceBatch(out_cols, total, names, origin), keep,
                         conf)


def shrink_to_capacity(db: DeviceBatch, row_bound: int,
                       conf: TpuConf = DEFAULT_CONF) -> DeviceBatch:
    """Slice lanes down to the bucket fitting `row_bound` WITHOUT reading
    the (possibly lazy) num_rows.  Sound when the caller can statically
    bound the live row count (e.g. LIMIT N): live rows are a prefix, so
    rows past the bound are guaranteed padding.  Keeps collect()/to_host
    from shipping a full-capacity batch over the link for a tiny limit."""
    db = ensure_prefix(db, conf)
    cap = bucket_capacity(max(row_bound, 1), conf)
    if cap >= db.capacity:
        return db
    cols = [DeviceColumn(c.data[:cap], c.validity[:cap], c.dtype,
                         c.dictionary,
                         None if c.data_hi is None else c.data_hi[:cap])
            for c in db.columns]
    return DeviceBatch(cols, db.num_rows, db.names, db.origin_file)


def shrink_to_rows(db: DeviceBatch, num_rows: int,
                   conf: TpuConf = DEFAULT_CONF) -> DeviceBatch:
    """Re-bucket a padded batch down to the bucket fitting `num_rows`
    (used after groupby/filter when occupancy dropped a bucket or more)."""
    db = ensure_prefix(db, conf)
    cap = bucket_capacity(max(num_rows, 1), conf)
    if cap >= db.capacity:
        return DeviceBatch(db.columns, num_rows, db.names, db.origin_file)
    cols = [DeviceColumn(c.data[:cap], c.validity[:cap], c.dtype,
                         c.dictionary,
                         None if c.data_hi is None else c.data_hi[:cap])
            for c in db.columns]
    return DeviceBatch(cols, num_rows, db.names, db.origin_file)
