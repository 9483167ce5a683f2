"""Unified segmented-reduction / packed-sort kernel layer.

Every holistic operator in this engine (group-by, count-distinct,
percentile, collect, window frames, sort-merge joins) reduces over
CONTIGUOUS RUNS of a sorted batch.  This module is the one home for the
primitives those operators share, shaped by the two platform costs that
dominated the round-5 chip profiles:

  * **Scatters are the enemy at runtime** (~70 ms per 1M rows, and their
    outputs land in S(1)-space buffers whose consumers run ~200 MB/s).
    Wherever an order exists, a segment reduction is a *blocked
    segmented scan* (the `blocked_cumsum` pattern: fixed 512-row blocks,
    compiles in seconds where one long scan costs minutes) followed by a
    gather at each run's END row — scan + gather, never scatter.

  * **Sort operand count is the enemy at compile time** (2-operand sort
    31 s, 3×i64 lexsort 164 s, 10-operand ≈ 10 min at 1M on v5e).
    `lexsort_capped` emits a chain of stable ≤N-operand sorts instead of
    one wide variadic sort, and `sorted_segments` folds statically
    bounded group keys — and, new here, bounded minor/value lanes — into
    ONE packed integer lane so the whole (keys, values) order is a
    single 2-operand sort.

`sorted_segments` (previously in ops/percentile.py; ops/distinct.py used
to import it cross-module from there) is the shared sort-segment core
for the holistic aggregates.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import blocked_cumsum

_SEG_BLOCK = 512
_SEG_MIN = 4096


def op_identity(op, dtype):
    """Identity element of a scan combiner over `dtype` lanes: the value
    e with op(e, v) == v for every v the lane can carry."""
    dt = np.dtype(dtype)
    if op is jnp.add:
        return np.zeros((), dt)
    if dt == np.bool_:
        # minimum == logical and (ident True), maximum == or (ident False)
        return np.bool_(op is jnp.minimum)
    if np.issubdtype(dt, np.inexact):
        return dt.type(np.inf if op is jnp.minimum else -np.inf)
    info = np.iinfo(dt)
    return dt.type(info.max if op is jnp.minimum else info.min)


def _doubling_seg_scan(v, f, length, op, ident, axis: int):
    """Hillis-Steele inclusive segmented scan along `axis` via log2(length)
    shift+combine steps over the (value, boundary-flag) monoid — every
    step is elementwise VPU work, so COMPILE time stays flat where XLA's
    native log-depth scan lowering of one long axis runs minutes."""
    step = 1
    while step < length:
        pad_shape = list(v.shape)
        pad_shape[axis] = step
        sl = [slice(None)] * v.ndim
        sl[axis] = slice(None, -step)
        pv = jnp.concatenate(
            [jnp.full(pad_shape, ident, v.dtype), v[tuple(sl)]], axis=axis)
        fpad = list(f.shape)
        fpad[axis] = step
        fsl = [slice(None)] * f.ndim
        fsl[axis] = slice(None, -step)
        pf = jnp.concatenate(
            [jnp.zeros(fpad, bool), f[tuple(fsl)]], axis=axis)
        fb = f if f.ndim == v.ndim else f[..., None]
        v = jnp.where(fb, v, op(pv, v))
        f = f | pf
        step <<= 1
    return v, f


def blocked_seg_scan(vals: jax.Array, boundary: jax.Array, op,
                     ident=None) -> jax.Array:
    """Segmented INCLUSIVE scan along axis 0: the running op-combine that
    resets at rows where `boundary` is True.  `vals` is (n,) or (n, k)
    with one boundary lane shared by all k columns.

    Identical semantics to a `lax.associative_scan` over the standard
    (value, start-flag) segmented monoid, but compiled as fixed 512-row
    blocks + a cross-block carry (the `blocked_cumsum` shape): an 80 s
    associative_scan compile at 1M becomes ~2 s of elementwise passes.
    """
    n = vals.shape[0]
    if ident is None:
        ident = op_identity(op, vals.dtype)
    ident = jnp.asarray(ident, vals.dtype)
    if n < _SEG_MIN or n % _SEG_BLOCK != 0:
        v, _f = _doubling_seg_scan(vals, boundary, n, op, ident, axis=0)
        return v
    nb = n // _SEG_BLOCK
    v = vals.reshape((nb, _SEG_BLOCK) + vals.shape[1:])
    f = boundary.reshape(nb, _SEG_BLOCK)
    v, f = _doubling_seg_scan(v, f, _SEG_BLOCK, op, ident, axis=1)
    # cross-block carry: exclusive segmented scan of per-block totals;
    # a block's carry only reaches rows before its first boundary, which
    # is exactly where the scanned in-block flag is still False
    tv, tf = v[:, -1], f[:, -1]
    cv, _cf = _doubling_seg_scan(tv, tf, nb, op, ident, axis=0)
    carry = jnp.expand_dims(jnp.concatenate(
        [jnp.full((1,) + tv.shape[1:], ident, v.dtype), cv[:-1]]), 1)
    fb = f if f.ndim == v.ndim else f[..., None]
    out = jnp.where(fb, v, op(carry, v))
    return out.reshape(vals.shape)


def seg_reduce_sorted(vals: jax.Array, boundary: jax.Array,
                      ends_c: jax.Array, op, ident=None) -> jax.Array:
    """Per-segment reduction over sorted runs, scatter-free: the
    segmented scan's value at each run's last row IS the run's
    reduction — one gather at `ends_c` (segment-slot -> last row index)
    replaces a jax.ops.segment_* scatter whose output would land in a
    slow S(1) buffer."""
    return blocked_seg_scan(vals, boundary, op, ident)[ends_c]


def seg_sums_sorted(lanes: Sequence[jax.Array], starts_c: jax.Array,
                    ends_c: jax.Array, abutting: bool = False,
                    riders: Sequence[jax.Array] = ()):
    """(num_segments, k) per-segment sums of int lanes over sorted runs:
    ONE stacked blocked cumsum + two boundary gathers.  int64
    wraparound cancels in the diff, so this is exact whenever the
    segment sum fits int64 — segment_sum's own contract.

    `abutting`: the caller states that every live run starts where the
    run before it ended (slot g's start - 1 is slot g-1's end, slot 0
    starts at row 0; slots past the live runs hold anything).  The
    cumsum below a run is then the cumsum AT the end of the run before
    it, a shift of what is gathered anyway: one gather, not two (a TPU
    gather pays per gathered row).  `riders`: int64 lanes of the rows'
    length to read at `ends_c` in the same gather; with riders the
    result is (sums, [rider at ends_c, ...])."""
    cs = blocked_cumsum(jnp.stack(list(lanes), axis=1))
    k = cs.shape[1]
    if riders:
        cs = jnp.concatenate(
            [cs] + [r.astype(cs.dtype)[:, None] for r in riders], axis=1)
    at_ends = cs[ends_c]
    hi = at_ends[:, :k]
    if abutting:
        lo = jnp.concatenate([jnp.zeros((1, k), hi.dtype), hi[:-1]])
    else:
        lo = jnp.where((starts_c > 0)[:, None],
                       cs[jnp.maximum(starts_c - 1, 0)][:, :k], 0)
    if riders:
        return hi - lo, [at_ends[:, k + j] for j in range(len(riders))]
    return hi - lo


def row0_true(capacity: int) -> jax.Array:
    """Boundary-lane seed: True at row 0.  Built by concatenation, not
    `.at[0].set` — the scatter that set would lower to is exactly the op
    class this layer exists to avoid (and the jaxpr scatter lint counts
    it)."""
    return jnp.concatenate([jnp.ones((1,), bool),
                            jnp.zeros((capacity - 1,), bool)])


# ---------------------------------------------------------------------------
# Operand-capped lexsort
# ---------------------------------------------------------------------------

def lexsort_capped(lanes: Sequence[jax.Array],
                   max_operands: int = 2) -> jax.Array:
    """`jnp.lexsort` semantics (LAST lane is the primary key) emitting
    only sorts of <= max_operands operands (keys + payload lane).

    One variadic lexsort compiles in time that grows brutally with
    operand count on TPU (3×i64 at 1M: 164 s; 10 operands: ~10 min); a
    chain of stable (key..., perm) sorts — most-minor lane first, each
    later key gathered through the running permutation — costs one
    ~20 ms/1M gather per extra lane at runtime but keeps every emitted
    sort within the compile-friendly budget."""
    lanes = list(lanes)
    assert lanes, "lexsort of zero lanes"
    keys_per_sort = max(1, max_operands - 1)
    if len(lanes) + 1 <= max_operands:
        return jnp.lexsort(lanes)
    perm = None
    i = 0
    while i < len(lanes):
        chunk = lanes[i:i + keys_per_sort]
        i += keys_per_sort
        if perm is None:
            n = chunk[0].shape[0]
            perm = jnp.arange(n, dtype=jnp.int32)
        else:
            chunk = [c[perm] for c in chunk]
        # lax.sort key order is primary-first; chunk arrives minor-first
        ops = tuple(reversed(chunk)) + (perm,)
        out = jax.lax.sort(ops, num_keys=len(chunk), is_stable=True)
        perm = out[-1]
    return perm


# ---------------------------------------------------------------------------
# Merge-rank matched flags (the scatter-free segment_max over indices)
# ---------------------------------------------------------------------------

def matched_flags(idx: jax.Array, ok: jax.Array, n: int) -> jax.Array:
    """(n,) flags: row r is True iff some position has ok & idx == r.

    The scatter formulation (`zeros.at[idx].max(ok)`) pays the ~70 ms/1M
    serialization cost and parks its output in an S(1) buffer; here the
    ok-masked indices sort into one lane (1-operand sort) and each row's
    hit count falls out of a merge-rank difference (two lean 2-operand
    sorts, ops/join._merge_rank)."""
    from .join import _merge_rank
    s = jnp.sort(jnp.where(ok, idx, n).astype(jnp.uint32))
    hi = _merge_rank(s.astype(jnp.uint64),
                     jnp.arange(n, dtype=jnp.uint64), side="right")
    prev = jnp.concatenate([jnp.zeros((1,), hi.dtype), hi[:-1]])
    return hi > prev


# ---------------------------------------------------------------------------
# sorted_segments: the shared sort-segment core for holistic aggregates
# ---------------------------------------------------------------------------

class SegRuns(NamedTuple):
    """Sorted-run structure shared by the holistic aggregates.

    perm: row permutation into (group, minor) order; s_live: liveness in
    sorted order; s_keys/s_keys_valid: sorted key lanes (None on the
    packed path — keys decode arithmetically); seg_ids: per-row segment
    id; start_idx/end_idx: per segment-slot first/last row (clipped,
    garbage beyond num_groups); out_keys: [(data, valid)] per key;
    num_groups: live-group count scalar; group_live: segment-slot mask.
    """
    perm: jax.Array
    s_live: jax.Array
    s_keys: Optional[list]
    s_keys_valid: Optional[list]
    seg_ids: jax.Array
    start_idx: jax.Array
    end_idx: jax.Array
    out_keys: list
    num_groups: jax.Array
    group_live: jax.Array


def segment_ends(start_raw, count, capacity: int):
    """Per segment-slot last-row index from the slot-ordered UNCLIPPED
    starts (dead slots carry the `capacity` sentinel): the next slot's
    start - 1, clipped into the live prefix."""
    nexts = jnp.concatenate(
        [start_raw[1:], jnp.full((1,), capacity, jnp.int32)])
    return jnp.clip(jnp.minimum(nexts - 1, count - 1), 0, capacity - 1)


def pack_minor_spec(minor_lanes, minor_spec):
    """Fold statically bounded minor lanes into (packed lane, span), or
    (None, 1) when any lane is unbounded.  minor_spec entries are
    (lo, span) with every lane value in [lo, lo+span)."""
    if minor_spec is None or len(minor_spec) != len(minor_lanes) or \
            any(s is None for s in minor_spec):
        return None, 1
    total = 1
    for _lo, span in minor_spec:
        total *= int(span)
    if total >= (1 << 31):
        return None, 1
    # minor_lanes arrive most-minor FIRST: lane i's stride is the span
    # product of the lanes minor to it, so the most-major lane weighs
    # highest and the packed integer order IS the lexsort order
    packed = None
    stride = 1
    for lane, (lo, span) in zip(minor_lanes, minor_spec):
        slot = jnp.clip(lane.astype(jnp.int64) - jnp.int64(int(lo)),
                        0, int(span) - 1)
        packed = slot * jnp.int64(stride) if packed is None \
            else packed + slot * jnp.int64(stride)
        stride *= int(span)
    return packed, total


def sorted_segments(key_lanes_info, keys, keys_valid, live,
                    minor_lanes, capacity: int, num_segments: int,
                    pack_spec=None, minor_spec=None,
                    max_sort_operands: int = 2) -> SegRuns:
    """Shared sort-segment core for holistic aggregates (percentile,
    count-distinct, collect): order rows by (dead-last, group keys,
    minor_lanes most-minor-first), find group boundaries, return a
    SegRuns.

    `minor_lanes` order rows WITHIN a group (value lanes, null flags);
    they do not contribute to boundaries.

    pack_spec: per-key (lo, span) covering EVERY key (exec layer: plan
    range stats, dictionary sizes, bools) folds the whole key tuple plus
    liveness into ONE sort lane; group keys decode arithmetically (zero
    key gathers) and the boundary compare touches one lane.

    minor_spec: optional per-minor-lane (lo, span) bounds.  When both
    specs cover everything and the combined span fits, keys AND minor
    lanes fold into ONE lane and the whole ordering is a single
    2-operand (lane, iota) sort — the count-distinct / approx-percentile
    analogue of ops/groupby.packed_groupby_trace, killing the
    q16-class multi-operand-lexsort cold-compile cost.  Unpacked lanes
    fall back to a lexsort_capped chain, so no emitted sort ever
    exceeds `max_sort_operands` operands either way."""
    from .filter import take_keys_valid
    from .groupby import _eq_prev, _null_first_key_lanes, _packed_key_lane
    from .kernels import compute_view

    count = jnp.sum(live, dtype=jnp.int32)
    iota = jnp.arange(capacity, dtype=jnp.int32)

    packed_all = pack_spec is not None and len(pack_spec) == \
        len(key_lanes_info) and all(s is not None for s in pack_spec)
    if packed_all:
        spans = [s[1] for s in pack_spec]
        total = 1
        for sp in spans:
            total *= sp
        packed = _packed_key_lane(keys, keys_valid, pack_spec)
        key_lane = jnp.where(live, packed, jnp.int64(total))

        minor_packed, minor_total = pack_minor_spec(minor_lanes,
                                                    minor_spec)
        if minor_packed is not None and \
                (total + 1) * minor_total < (1 << 62):
            # ONE fused (key, minor) lane -> ONE 2-operand stable sort
            fused = key_lane * jnp.int64(minor_total) + minor_packed
            fused_s, perm = jax.lax.sort((fused, iota), num_keys=1,
                                         is_stable=True)
            s_key = fused_s // jnp.int64(minor_total)
        else:
            if total < (1 << 31) - 1:
                key_lane = key_lane.astype(jnp.int32)
            perm = lexsort_capped(list(minor_lanes) + [key_lane],
                                  max_sort_operands)
            s_key = key_lane[perm]
        s_live = s_key < jnp.asarray(total, s_key.dtype)
        boundary = _eq_prev(s_key)
        seg_ids = blocked_cumsum(boundary.astype(jnp.int32)) - 1
        num_groups = jnp.where(count > 0,
                               seg_ids[jnp.maximum(count - 1, 0)] + 1, 0)
        group_live = jnp.arange(num_segments,
                                dtype=jnp.int32) < num_groups
        start_raw = jnp.sort(jnp.where(
            boundary & s_live, iota, jnp.int32(capacity)))[:num_segments]
        end_idx = segment_ends(start_raw, count, capacity)
        start_idx = jnp.clip(start_raw, 0, capacity - 1)
        # keys decode from the packed value at segment starts
        strides = []
        tot = 1
        for sp in reversed(spans):
            strides.append(tot)
            tot *= sp
        strides.reverse()
        pk = s_key[start_idx].astype(jnp.int64)
        out_keys = []
        for (dt, _hv, lane_dt), (lo, span), stride in zip(
                key_lanes_info, pack_spec, strides):
            slot = (pk // jnp.int64(stride)) % jnp.int64(span)
            okd = (slot - 1 + jnp.int64(lo)).astype(jnp.dtype(lane_dt))
            out_keys.append((okd, (slot > 0) & group_live))
        return SegRuns(perm, s_live, None, None, seg_ids, start_idx,
                       end_idx, out_keys, num_groups, group_live)

    lanes = []
    for (dt, _hv, _ld), kd, kv in zip(key_lanes_info, keys, keys_valid):
        sub = _null_first_key_lanes(compute_view(kd, dt), kv, dt)
        lanes.extend([l for l in sub if l is not None])
    # lexsort semantics: LAST lane is primary
    sort_keys = list(minor_lanes) + list(reversed(lanes)) + \
        [(~live).astype(jnp.int8)]
    perm = lexsort_capped(sort_keys, max_sort_operands)
    # one stacked gather pass per dtype class (TPU gathers pay per row,
    # ~20ms per 1M-row pass — per-lane takes multiply that)
    s_keys, s_keys_valid, (s_live,) = take_keys_valid(
        keys, keys_valid, [live], perm)

    boundary = row0_true(capacity)
    for (dt, _hv, _ld), kd, kv in zip(key_lanes_info, s_keys,
                                      s_keys_valid):
        sub = _null_first_key_lanes(compute_view(kd, dt), kv, dt)
        for lane in sub:
            if lane is not None:
                boundary = boundary | _eq_prev(lane)
    pad_start = jnp.concatenate([jnp.ones((1,), bool),
                                 s_live[1:] != s_live[:-1]])
    boundary = boundary | pad_start
    seg_ids = blocked_cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.where(count > 0,
                           seg_ids[jnp.maximum(count - 1, 0)] + 1, 0)
    group_live = jnp.arange(num_segments, dtype=jnp.int32) < num_groups

    # seg ids rise with position, so the g-th boundary IS segment g's
    # start: a single-lane sort compacts them (no segment_min scatter —
    # scatter outputs land in slow S(1) buffers on this platform)
    start_raw = jnp.sort(jnp.where(
        boundary, iota, jnp.int32(capacity)))[:num_segments]
    end_idx = segment_ends(start_raw, count, capacity)
    start_idx = jnp.clip(start_raw, 0, capacity - 1)
    okds, okvs, _ = take_keys_valid(s_keys, s_keys_valid, [], start_idx)
    out_keys = []
    for okd, okv in zip(okds, okvs):
        okv = jnp.ones((num_segments,), bool) if okv is None else okv
        out_keys.append((okd, okv & group_live))
    return SegRuns(perm, s_live, s_keys, s_keys_valid, seg_ids,
                   start_idx, end_idx, out_keys, num_groups, group_live)
