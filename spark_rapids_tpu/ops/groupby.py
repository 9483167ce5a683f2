"""Sort-based segmented group-by: the cuDF `Table.groupBy().aggregate()` role.

cuDF uses a device hash table; on TPU the idiomatic shape is sort + segment
reduction (static shapes, no scatter contention, MXU/VPU-friendly):

  1. lexsort rows by (liveness, key lanes with validity)  — padding rows and
     null keys each group cleanly (Spark groups nulls as equal)
  2. boundary flags where any key lane differs from the previous row
  3. segment_ids = cumsum(flags); group count = one scalar D2H
  4. jax.ops.segment_{sum,min,max} per aggregate with null/live masking
  5. group keys gathered from each segment's first row

Everything is one jit per (shape-bucket, agg signature); outputs stay padded
to capacity so downstream operators reuse the same bucket.

Min/max float ordering follows Java's Double.compare (NaN greatest,
-0.0 < 0.0) by running the comparison in bit-space when the column carries
the int64-bits storage lane, else a NaN-tracked value-space fallback.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from .kernels import blocked_cumsum, compute_view
from .segments import (blocked_seg_scan, lexsort_capped, row0_true,
                       seg_reduce_sorted, seg_sums_sorted, segment_ends)


# Aggregate kernel op kinds understood by the kernel.
# (update vs merge distinction lives in plan/aggregates.py; by kernel time
# everything is one of these.)
SUM = "sum"
COUNT = "count"          # counts valid rows
COUNT_ALL = "count_all"  # counts live rows (count(*) / count(1))
MIN = "min"
MAX = "max"
FIRST = "first"          # first live row's value (Spark ignoreNulls=false)
LAST = "last"
FIRST_NN = "first_nn"    # first non-null (ignoreNulls=true)
LAST_NN = "last_nn"
ANY = "any"              # boolean or
EVERY = "every"          # boolean and


@dataclasses.dataclass(frozen=True)
class AggSpec:
    kind: str
    input_idx: int                 # index into the agg-input column list
    dtype: object                  # logical result type (t.DataType)


def _null_first_key_lanes(data, valid, dt):
    """Lanes making (valid, data) lexsort-comparable; nulls group together."""
    if valid is None:
        valid_lane = None
    else:
        valid_lane = (~valid).astype(jnp.int8)   # nulls first among live rows
        # canonicalize null rows' payload so they compare equal regardless of
        # what the producing kernel left in the data lane
        data = jnp.where(valid, data, jnp.zeros((), data.dtype))
    if dt is not None and isinstance(dt, t.DoubleType) and data.dtype == jnp.float64:
        # computed f64: order by value; NaN needs a consistent slot — push to
        # the top via isnan lane handled by caller. Grouping only needs
        # consistency, and NaN != NaN would split groups: map NaN to a
        # canonical key by replacing with +inf and adding an isnan lane.
        isnan = jnp.isnan(data)
        canon = jnp.where(isnan, jnp.float64(np.inf), data)
        canon = jnp.where(canon == 0.0, jnp.float64(0.0), canon)  # -0.0==0.0
        return [valid_lane, isnan.astype(jnp.int8), canon]
    return [valid_lane, data]


def _eq_prev(lane):
    """Boundary lane: True where row differs from previous sorted row."""
    return jnp.concatenate([jnp.ones((1,), bool), lane[1:] != lane[:-1]])


def _scatter_reducer(seg_ids, num_segments):
    """reduce_to(lane, ident, op) -> (num_segments,) by one
    jax.ops.segment_<op> scatter; op is "sum", "min" or "max".  `ident`
    is op's identity in the lane's dtype: the lane already holds it on
    the rows that must not count, and a scatter needs it nowhere else."""
    ops = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
           "max": jax.ops.segment_max}

    def reduce_to(lane, ident, op):
        return ops[op](lane, seg_ids, num_segments=num_segments)
    return reduce_to


def _masked_reducer(hit):
    """The same contract without a scatter: `hit` is the (D, N) mask of
    the rows of each bucket (XLA fuses it into every reduce; it is never
    materialised) and each bucket reduces the lane over its own rows,
    the other rows standing in as `ident`.  Integers accumulate in the
    lane's own dtype (no promotion, no narrowing)."""
    def reduce_to(lane, ident, op):
        rows = jnp.where(hit, lane[None, :], jnp.asarray(ident, lane.dtype))
        if op == "sum":
            return jnp.sum(rows, axis=1, dtype=lane.dtype)
        return (jnp.min if op == "min" else jnp.max)(rows, axis=1)
    return reduce_to


def _segment_minmax_float(vals, valid_live, reduce_to, is_min):
    """Java-ordering min/max for float values (NaN greatest).

    Value-space with NaN tracking; the exact bit-space path for int64-bits
    DOUBLE lanes lives inline in groupby_trace via _bits_total_order."""
    isnan = jnp.isnan(vals) & valid_live
    has_nan = reduce_to(isnan.astype(jnp.int32), 0, "max") > 0
    all_nan_ident = jnp.float64(np.inf) if is_min else jnp.float64(-np.inf)
    clean = jnp.where(valid_live & ~isnan, vals, all_nan_ident)
    red = reduce_to(clean, all_nan_ident, "min" if is_min else "max")
    non_nan_count = reduce_to((valid_live & ~isnan).astype(jnp.int32), 0,
                              "sum")
    if is_min:
        # min is NaN only when every valid value is NaN
        return jnp.where(has_nan & (non_nan_count == 0), jnp.float64(np.nan),
                         red)
    return jnp.where(has_nan, jnp.float64(np.nan), red)


_EXP_MASK = np.int64(0x7FF0000000000000)
_MANT_MASK = np.int64(0x000FFFFFFFFFFFFF)
_CANON_NAN = np.int64(0x7FF8000000000000)


def _bits_total_order(b):
    """Monotone int64 mapping of f64 bit patterns (Java Double.compare).

    -inf < ... < -0.0 < 0.0 < ... < +inf < NaN.  NaN bits are canonicalized
    first so the int64 extremes stay free for masking identities."""
    is_nan = ((b & _EXP_MASK) == _EXP_MASK) & ((b & _MANT_MASK) != 0)
    b = jnp.where(is_nan, jnp.int64(_CANON_NAN), b)
    # int64 wraparound makes -2^63-1-b correct mod 2^64 for all negative b
    return jnp.where(b >= 0, b, jnp.int64(-2**63) - jnp.int64(1) - b)


def _bits_from_order(o):
    return jnp.where(o >= 0, o, jnp.int64(-2**63) - jnp.int64(1) - o)


_ORDER_MAX = np.int64(2**63 - 1)   # unreachable after NaN canonicalization
_ORDER_MIN = np.int64(-2**63)




def _queue_sum_lanes(agg_specs, spec_vls, live_all, count_dtype=jnp.int64):
    """Collect every sum-like lane (SUM buffers, COUNT/COUNT_ALL,
    per-input valid counts) into two dtype-class stacks.  Shared by all
    group-by variants so the lane/dtype rules cannot drift.
    `count_dtype`: what the 0/1 lanes of the counts add up in.  A stack
    needs its sums' int64; a lane reduced alone may count in int32,
    which holds any capacity (row indices are int32 throughout).

    Returns (int_lanes, int_slots, f64_lanes, f64_slots)."""
    int_lanes, int_slots = [], {}
    f64_lanes, f64_slots = [], {}

    def queue(key, lane, is_float):
        lanes_, slots = (f64_lanes, f64_slots) if is_float \
            else (int_lanes, int_slots)
        if key not in slots:
            slots[key] = len(lanes_)
            lanes_.append(lane)

    for si, spec in enumerate(agg_specs):
        d, vl = spec_vls[si]
        dt = spec.dtype
        if spec.kind == COUNT_ALL:
            queue(("cnt", si), live_all.astype(count_dtype), False)
        elif spec.kind == COUNT:
            queue(("cnt", si), vl.astype(count_dtype), False)
        elif spec.kind == SUM:
            cd = compute_view(d, dt)
            if t.is_floating(dt):
                queue(("sum", si),
                      jnp.where(vl, cd.astype(jnp.float64), 0.0), True)
            else:
                queue(("sum", si),
                      jnp.where(vl, cd.astype(jnp.int64), 0), False)
        if spec.kind not in (COUNT, COUNT_ALL):
            queue(("vc", spec.input_idx), vl.astype(count_dtype), False)
    return int_lanes, int_slots, f64_lanes, f64_slots


def _batched_sums(agg_specs, spec_vls, live_all, sum_lanes,
                  count_dtype=jnp.int64):
    """Every sum-like lane of the dense group-by through
    `sum_lanes(lanes) -> column`, one call per dtype class: `lanes` are
    K (N,) arrays, `column(j)` is the (G,) sums of lane j by group.
    dense_groupby_trace passes one of two:

      * the scatter: ONE wide (N, K) segment_sum.  On the chip a scatter
        pays per row, not per lane (my chip runs, PR 28, 4M rows into 12
        buckets: 366 ms for K = 2 int64 lanes, 365 ms for K = 18), so
        K-wide rows cost what one lane does;
      * up to MASKED_DOMAIN_MAX: each lane reduced apart under the bucket
        masks (_masked_reducer), one fused pass over the batch a lane,
        no (N, K) stack.  Lanes that hold the same values (the valid
        counts of inputs without nulls) are one lane to XLA.  The count
        lanes add up in int32 (`count_dtype`) and widen afterwards: in
        Q1's program that took the batch from 2.4 to 1.85 ms (my chip
        runs, PR 28).

    spec_vls: per-spec (data, valid&live); live_all: the COUNT(*) lane.
    Returns sum_of(key, is_float) -> (G,) lane."""
    int_lanes, int_slots, f64_lanes, f64_slots = _queue_sum_lanes(
        agg_specs, spec_vls, live_all, count_dtype)
    int_col = sum_lanes(int_lanes) if int_lanes else None
    f64_col = sum_lanes(f64_lanes) if f64_lanes else None

    def sum_of(key, is_float):
        return (f64_col(f64_slots[key]) if is_float
                else int_col(int_slots[key]))
    return sum_of


def _segment_minmax_float_sorted(vals, valid_live, boundary, ends_c,
                                 is_min):
    """Java-ordering float min/max over SORTED runs, scatter-free: the
    NaN flag, the clean reduction and the non-NaN count all ride
    segmented scans gathered at run ends (ops/segments.py) instead of
    three segment_* scatters."""
    isnan = jnp.isnan(vals) & valid_live
    has_nan = seg_reduce_sorted(isnan.astype(jnp.int8), boundary, ends_c,
                                jnp.maximum) > 0
    all_nan_ident = jnp.float64(np.inf) if is_min else jnp.float64(-np.inf)
    clean = jnp.where(valid_live & ~isnan, vals, all_nan_ident)
    red = seg_reduce_sorted(clean, boundary, ends_c,
                            jnp.minimum if is_min else jnp.maximum)
    if is_min:
        non_nan = seg_reduce_sorted(
            (valid_live & ~isnan).astype(jnp.int32), boundary, ends_c,
            jnp.add)
        # min is NaN only when every valid value is NaN
        return jnp.where(has_nan & (non_nan == 0), jnp.float64(np.nan),
                         red)
    return jnp.where(has_nan, jnp.float64(np.nan), red)


def sorted_agg_outputs(agg_specs, spec_vls, s_live, boundary, starts_c,
                       ends_c, group_live, num_segments: int,
                       capacity: int, scatter_free: bool,
                       abutting: bool = False, riders=None):
    """Aggregate outputs over SORTED runs — the one implementation both
    the packed and the generic sort-segment group-bys share.

    spec_vls: per-spec (data, valid&live) lanes already in sorted order;
    boundary: live-run starts; starts_c/ends_c: per segment-slot
    first/last row (clipped).  With `scatter_free` every reduction is a
    blocked segmented scan + boundary gather / stacked-cumsum diff
    (ops/segments.py) — zero jax.ops.segment_* scatters in the emitted
    program; without it the legacy segment (scatter) reductions run, so
    the two modes are flip-comparable under one knob.

    `abutting`, `riders`: seg_sums_sorted's (the packed group-by's runs
    abut, and its key lane rides the sums' gather at the run ends).
    With `riders` (a list of int64 lanes) the result is (outs, the lanes
    read at `ends_c`)."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    big = jnp.int32(capacity)
    seg_ids = None

    def seg():
        nonlocal seg_ids
        if seg_ids is None:
            # dead rows continue the last segment; their vl is False
            seg_ids = jnp.clip(
                blocked_cumsum(boundary.astype(jnp.int32)) - 1,
                0, num_segments - 1)
        return seg_ids

    def reduce_lane(lane, is_min):
        if scatter_free:
            return seg_reduce_sorted(
                lane, boundary, ends_c,
                jnp.minimum if is_min else jnp.maximum)
        return (jax.ops.segment_min if is_min else jax.ops.segment_max)(
            lane, seg(), num_segments=num_segments)

    # ---- the sum/count family: ONE stacked pass each dtype class ----
    int_lanes, int_slots, f64_lanes, f64_slots = _queue_sum_lanes(
        agg_specs, spec_vls, s_live)
    int_out = f64_out = None
    rode = None
    if int_lanes:
        if scatter_free:
            # stacked cumsum + two boundary gathers; int64 wraparound
            # cancels in the diff (exact whenever the group sum fits
            # int64 — segment_sum's own contract)
            int_out = seg_sums_sorted(int_lanes, starts_c, ends_c,
                                      abutting, riders or ())
            if riders:
                int_out, rode = int_out
        else:
            int_out = jax.ops.segment_sum(
                jnp.stack(int_lanes, axis=1), seg(),
                num_segments=num_segments)
    if f64_lanes:
        if scatter_free:
            # SEGMENTED scan, not cumsum-diff: the per-run reset keeps
            # each group's accumulation independent, so one group's sum
            # is never absorbed by preceding groups' magnitudes
            f64_out = blocked_seg_scan(
                jnp.stack(f64_lanes, axis=1), boundary, jnp.add)[ends_c]
        else:
            f64_out = jax.ops.segment_sum(
                jnp.stack(f64_lanes, axis=1), seg(),
                num_segments=num_segments)

    def sum_of(key, is_float):
        return (f64_out[:, f64_slots[key]] if is_float
                else int_out[:, int_slots[key]])

    outs = []
    for si, spec in enumerate(agg_specs):
        d, vl = spec_vls[si]
        dt = spec.dtype
        if spec.kind in (COUNT, COUNT_ALL):
            outs.append((sum_of(("cnt", si), False), group_live))
            continue
        valid_count = sum_of(("vc", spec.input_idx), False)
        out_valid = (valid_count > 0) & group_live
        cd = compute_view(d, dt)
        if spec.kind == SUM:
            data = sum_of(("sum", si), t.is_floating(dt))
        elif spec.kind == FIRST:
            # runs hold only live rows (liveness is the primary sort
            # lane), so first/last are pure boundary gathers
            data = cd[starts_c]
            out_valid = vl[starts_c] & group_live
        elif spec.kind == LAST:
            data = cd[ends_c]
            out_valid = vl[ends_c] & group_live
        elif spec.kind in (MIN, MAX):
            is_min = spec.kind == MIN
            if isinstance(dt, t.DoubleType) and d.dtype == jnp.int64:
                o = _bits_total_order(d)
                ident = jnp.int64(_ORDER_MAX if is_min else _ORDER_MIN)
                o = jnp.where(vl, o, ident)
                data = _bits_from_order(reduce_lane(o, is_min))
            elif t.is_floating(dt):
                if scatter_free:
                    data = _segment_minmax_float_sorted(
                        cd, vl, boundary, ends_c, is_min)
                else:
                    data = _segment_minmax_float(
                        cd, vl, _scatter_reducer(seg(), num_segments),
                        is_min)
            else:
                if isinstance(dt, t.BooleanType):
                    ident = jnp.asarray(is_min)
                else:
                    info = np.iinfo(np.dtype(cd.dtype))
                    ident = jnp.asarray(info.max if is_min else info.min,
                                        cd.dtype)
                data = reduce_lane(jnp.where(vl, cd, ident), is_min)
        elif spec.kind in (FIRST_NN, LAST_NN):
            is_first = spec.kind == FIRST_NN
            masked = jnp.where(vl, iota, big if is_first else -1)
            pick = jnp.clip(reduce_lane(masked, is_first), 0,
                            capacity - 1)
            data = cd[pick]
            # a run with no valid row picks out of range, clipped onto
            # another run's row: the valid count says so, vl[pick] not
            out_valid = vl[pick] & out_valid
        elif spec.kind == ANY:
            data = reduce_lane(
                jnp.where(vl, cd, False).astype(jnp.int8), False) > 0
        elif spec.kind == EVERY:
            data = reduce_lane(
                jnp.where(vl, cd, True).astype(jnp.int8), True) > 0
        else:
            raise ValueError(f"unknown agg kind {spec.kind}")
        outs.append((data, out_valid))
    if riders is None:
        return outs
    return outs, rode if rode is not None else [r[ends_c] for r in riders]


def _packed_key_lane(keys, keys_valid, pack_spec):
    """Fold the statically-bounded keys into ONE int64 lane (slot 0 per
    key = null; values offset by -lo+1).  TPU sort compile time AND run
    time scale with operand count (~15-30s compile per extra 8M operand
    on v5e), so a k-key group-by sorting one packed lane instead of 2k
    (validity+data per key) lanes is the difference between a 1-minute
    and a 20-minute query compile."""
    packed = None
    for i, spec in enumerate(pack_spec):
        if spec is None:
            continue
        lo, span = spec
        kd = keys[i].astype(jnp.int64)
        kv = keys_valid[i]
        slot = jnp.clip(kd - jnp.int64(lo) + 1, 0, span - 1)
        if kv is not None:
            slot = jnp.where(kv, slot, jnp.int64(0))
        packed = slot if packed is None \
            else packed * jnp.int64(span) + slot
    return packed


def packed_groupby_trace(pack_spec, key_lanes_info, agg_specs,
                         num_segments, capacity, scatter_free=True,
                         in_place=False):
    """All-keys-packed group-by: ONE sort lane, NO scatters for the
    sum/count family, group keys decoded arithmetically.

    When every key has a static (lo, span) bound the whole key tuple —
    including liveness — folds into one integer sort lane.  This changes
    the cost shape on both axes that dominate this platform:

      * compile: a 2-operand (key, iota) sort compiles in ~30s where a
        k-key lexsort is minutes (TPU sort compile scales with operand
        count — measured 164s for 3 int64 lanes at 1M vs 31s for
        key+payload);
      * run: per-lane permutation gathers collapse into grouped_take
        stacks (~one gather pass per dtype class instead of per lane;
        TPU gathers pay per-row descriptor latency, ~20ms per pass at
        1M), sums/counts become ONE stacked cumsum + two small gathers
        at segment boundaries instead of scatter passes (~70ms each at
        1M, and scatter outputs land in slow S(1)-space buffers), and
        segment starts come from a single-lane sort instead of a
        segment_min scatter.

    int64 cumsum-diff is exact for any group sum that fits int64
    (two's-complement wraparound cancels in the subtraction), matching
    segment_sum semantics.  MIN/MAX, ignore-null FIRST/LAST, ANY/EVERY
    and f64 sums run through the same scatter-free sorted-run layer
    (sorted_agg_outputs): segmented scans gathered at run ends, so the
    whole program emits ZERO scatters when `scatter_free` holds.

    Two realisations take the row gathers out as well (on the chip a
    gather over the capacity costs more than the sort: PERF.md section
    6, PR 35):

      * the payload sort: where the aggregates read ONE input lane of at
        most 32 bits, the lane and its validity ride the sort as its
        second operand, (value << 1 | valid) in an int64, in the place of
        the row ids; nothing is permuted afterwards;
      * `in_place` (in_place_supported): every run's result is left at
        the run's LAST row, where a segmented scan has it, and the
        function returns a fourth value, the mask of those rows, for the
        caller to hand on as a selection vector.  No group starts are
        sorted to the front and nothing is gathered at the run ends; the
        groups are compacted by whoever needs them dense (a whole-plan
        seam, at the bucket of the rows that are left by then)."""
    spans = [s[1] for s in pack_spec]
    los = [s[0] for s in pack_spec]
    strides = []
    tot = 1
    for s in reversed(spans):
        strides.append(tot)
        tot *= s
    strides.reverse()
    total = tot
    key_dt = jnp.int32 if total < (1 << 31) - 1 else jnp.int64
    need = sorted({s.input_idx for s in agg_specs if s.input_idx >= 0})
    in_place = in_place and in_place_supported(agg_specs)

    def decode_keys(pk, alive):
        """Keys decode from the packed value — zero key gathers."""
        out_keys = []
        for (dt, _hv, lane_dt), lo, span, stride in zip(
                key_lanes_info, los, spans, strides):
            slot = (pk // jnp.int64(stride)) % jnp.int64(span)
            data = (slot - 1 + jnp.int64(lo)).astype(jnp.dtype(lane_dt))
            out_keys.append((data, (slot > 0) & alive))
        return out_keys

    def run(keys, keys_valid, agg_data, agg_valid, live):
        packed = _packed_key_lane(keys, keys_valid, pack_spec)
        skey = jnp.where(live, packed, jnp.int64(total)).astype(key_dt)
        iota = jnp.arange(capacity, dtype=jnp.int32)
        valid_of = {i: jnp.ones((capacity,), bool) if agg_valid[i] is None
                    else agg_valid[i] for i in need}
        payload = len(need) == 1 and _rides_the_sort(agg_data[need[0]])
        if not need:
            skey_s, perm = jnp.sort(skey), None
        elif payload:
            d = agg_data[need[0]]
            ride = (d.astype(jnp.int64) << 1) \
                | valid_of[need[0]].astype(jnp.int64)
            skey_s, ride_s = jax.lax.sort((skey, ride), num_keys=1,
                                          is_stable=True)
        else:
            skey_s, perm = jax.lax.sort((skey, iota), num_keys=1,
                                        is_stable=True)
        s_live = skey_s < jnp.asarray(total, key_dt)
        count = jnp.sum(live, dtype=jnp.int32)
        boundary = jnp.concatenate(
            [jnp.ones((1,), bool), skey_s[1:] != skey_s[:-1]]) & s_live
        num_groups = jnp.sum(boundary, dtype=jnp.int32)

        s_in = {}
        if payload:
            s_in[need[0]] = ((ride_s >> 1).astype(d.dtype),
                             (ride_s & 1).astype(bool) & s_live)
        elif need:
            # permute agg inputs once, stacked by dtype class; an integer
            # lane's validity rides in the lane's own dtype, so that the
            # two are rows of ONE gathered matrix (a TPU gather pays per
            # gathered row, not per lane)
            from .filter import grouped_take
            lanes = []
            for i in need:
                lanes.append(agg_data[i])
                lanes.append(valid_of[i].astype(agg_data[i].dtype)
                             if jnp.issubdtype(agg_data[i].dtype,
                                               jnp.integer)
                             else valid_of[i])
            moved = grouped_take(lanes, perm)
            for j, i in enumerate(need):
                s_in[i] = (moved[2 * j],
                           moved[2 * j + 1].astype(bool) & s_live)
        spec_vls = [s_in[spec.input_idx] if spec.input_idx >= 0
                    else (None, s_live) for spec in agg_specs]

        if in_place:
            is_end = jnp.concatenate(
                [skey_s[1:] != skey_s[:-1], jnp.ones((1,), bool)]) & s_live
            outs = _in_place_outputs(agg_specs, spec_vls, s_live, boundary,
                                     is_end)
            return (decode_keys(skey_s.astype(jnp.int64), is_end), outs,
                    num_groups, is_end)

        # group start positions, compacted to the front by a SINGLE-lane
        # sort (scatter-free segment_min)
        starts = jnp.sort(jnp.where(boundary, iota, jnp.int32(capacity)))
        starts = starts[:num_segments]
        group_live = jnp.arange(num_segments, dtype=jnp.int32) < num_groups
        starts_c = jnp.clip(starts, 0, capacity - 1)
        nexts = jnp.concatenate(
            [starts[1:], jnp.full((1,), capacity, jnp.int32)])
        ends_c = jnp.clip(jnp.minimum(nexts - 1, count - 1), 0,
                          capacity - 1)

        # ---- every aggregate kind through the shared sorted-run layer
        # (scatter-free segmented scans + boundary gathers by default;
        # the knob flips back to segment scatters for A/B comparison).
        # Live rows sort to the front (dead ones carry the key `total`),
        # so the runs abut; the key lane rides the sums' gather at the
        # run ends (a run's last key is its first)
        outs, (pk,) = sorted_agg_outputs(
            agg_specs, spec_vls, s_live, boundary, starts_c, ends_c,
            group_live, num_segments, capacity, scatter_free,
            abutting=True, riders=[skey_s.astype(jnp.int64)])
        return decode_keys(pk, group_live), outs, num_groups

    return run


def _rides_the_sort(lane) -> bool:
    """An aggregate's input lane of at most 32 bits: it and its validity
    fit an int64 beside each other (packed_groupby_trace's payload
    sort)."""
    return jnp.issubdtype(lane.dtype, jnp.integer) \
        and lane.dtype.itemsize <= 4


def in_place_supported(agg_specs) -> bool:
    """Whether every aggregate has a result that a segmented scan leaves
    at its run's last row: sums and counts, and the min / max of a lane
    that is not floating point (those take a NaN-aware reduction)."""
    return all(spec.kind in (SUM, COUNT, COUNT_ALL)
               or (spec.kind in (MIN, MAX)
                   and not t.is_floating(spec.dtype)
                   and not isinstance(spec.dtype, (t.BooleanType,
                                                   t.StringType)))
               for spec in agg_specs)


def _in_place_outputs(agg_specs, spec_vls, s_live, boundary, is_end):
    """sorted_agg_outputs for the `in_place` form: per spec (data,
    valid) at the rows' own length, meaningful at `is_end` rows (valid is
    False elsewhere).  Sums and counts are ONE stacked segmented scan a
    dtype class; int64 sums wrap as segment_sum's do."""
    int_lanes, int_slots, f64_lanes, f64_slots = _queue_sum_lanes(
        agg_specs, spec_vls, s_live)
    int_run = blocked_seg_scan(jnp.stack(int_lanes, axis=1), boundary,
                               jnp.add) if int_lanes else None
    f64_run = blocked_seg_scan(jnp.stack(f64_lanes, axis=1), boundary,
                               jnp.add) if f64_lanes else None

    def sum_of(key, is_float):
        return (f64_run[:, f64_slots[key]] if is_float
                else int_run[:, int_slots[key]])

    outs = []
    for si, spec in enumerate(agg_specs):
        d, vl = spec_vls[si]
        if spec.kind in (COUNT, COUNT_ALL):
            outs.append((sum_of(("cnt", si), False), is_end))
            continue
        out_valid = (sum_of(("vc", spec.input_idx), False) > 0) & is_end
        if spec.kind == SUM:
            data = sum_of(("sum", si), t.is_floating(spec.dtype))
        else:
            cd = compute_view(d, spec.dtype)
            is_min = spec.kind == MIN
            info = np.iinfo(np.dtype(cd.dtype))
            ident = jnp.asarray(info.max if is_min else info.min, cd.dtype)
            data = blocked_seg_scan(jnp.where(vl, cd, ident), boundary,
                                    jnp.minimum if is_min else jnp.maximum)
        outs.append((data, out_valid))
    return outs


#: rows one sorted group-by program can address: its permutation and its
#: group starts are int32 lanes, and `capacity` itself is their sentinel
_MAX_ROW_IDS = (1 << 31) - 1


def all_keys_pack(pack_spec, num_keys: int) -> bool:
    """Whether `pack_spec` folds EVERY group key into the one sort lane
    of packed_groupby_trace; groupby_trace otherwise sorts by a capped
    lexsort over the keys' own lanes."""
    if pack_spec is None or \
            sum(s is not None for s in pack_spec) != num_keys:
        return False
    tot = 1
    for _lo, span in pack_spec:
        tot *= span
    return tot <= (1 << 62)


def groupby_trace(key_lanes_info, agg_specs, num_segments, capacity,
                  pack_spec=None, scatter_free=True,
                  max_sort_operands=2, in_place=False):
    """Build the traced groupby fn for jit.

    key_lanes_info: list of (dtype, has_validity, lane_dtype_str) — static.
    pack_spec: optional per-key (lo, span) or None — keys with exact
    static bounds fold into one packed sort lane (_packed_key_lane).
    scatter_free: route every segment reduction through the sorted-run
    scan layer (sorted_agg_outputs) — no jax.ops.segment_* scatters.
    max_sort_operands: cap on emitted sort width; the unpacked key sort
    chains stable 2-operand sorts instead of one variadic lexsort
    (segments.lexsort_capped — TPU sort compile scales with operands).
    Returns fn(keys_data, keys_valid, agg_data, agg_valid, live) ->
      (perm_keys (data, valid) per key, agg outs (data, valid) per spec,
       num_groups scalar)
    and, where `in_place` is asked for and the all-keys-packed trace
    can give it (packed_groupby_trace, in_place_supported), a fourth
    value: the mask of the rows that hold a group each.

    `live` is an arbitrary row mask, NOT a prefix count: a filter feeding an
    aggregation passes its keep-mask directly, so filtered rows die inside
    the (sorted) segment reduce and no gather/compaction ever runs — row
    gathers are the expensive op on TPU, masked VPU work is nearly free.
    """
    if capacity > _MAX_ROW_IDS:
        # a ValueError is no trace-fallback error: the collect ends with
        # this reason, not on another engine at the same capacity
        raise ValueError(
            f"sorted group-by over {capacity:,} rows of capacity: a row "
            f"id is an int32, so one program sorts at most "
            f"{_MAX_ROW_IDS:,} rows; partial results must be merged at "
            "the sum of their capacities, not re-merged batch by batch")
    packed_idx = {i for i, s in enumerate(pack_spec or []) if s is not None}
    if all_keys_pack(pack_spec, len(key_lanes_info)):
        return packed_groupby_trace(pack_spec, key_lanes_info,
                                    agg_specs, num_segments, capacity,
                                    scatter_free=scatter_free,
                                    in_place=in_place)

    def key_sort_lanes(keys, keys_valid):
        """[(lanes...)] for sorting/boundaries: packed keys collapse into
        one lane, the rest keep their (validity, data) pairs."""
        lanes = []
        if packed_idx:
            lanes.append(_packed_key_lane(keys, keys_valid, pack_spec))
        for i, ((dt, _hv, _ld), kd, kv) in enumerate(
                zip(key_lanes_info, keys, keys_valid)):
            if i in packed_idx:
                continue
            sub = _null_first_key_lanes(compute_view(kd, dt), kv, dt)
            lanes.extend([l for l in sub if l is not None])
        return lanes

    def run(keys, keys_valid, agg_data, agg_valid, live):
        from .filter import grouped_take, take_keys_valid
        # --- 1. sort ---
        lanes = key_sort_lanes(keys, keys_valid)
        # lexsort: LAST key is primary -> order [secondary..., primary];
        # emitted as a chain of <=max_sort_operands stable sorts
        sort_keys = list(reversed(lanes)) + [(~live).astype(jnp.int8)]
        perm = lexsort_capped(sort_keys, max_sort_operands)
        # ONE stacked gather pass per dtype class for every permuted lane
        # (keys, key validity, liveness) — TPU gathers pay per row, not
        # per byte, so per-lane takes multiply a ~20ms/1M latency cost
        s_keys, s_keys_valid, (s_live,) = take_keys_valid(
            keys, keys_valid, [live], perm)

        # --- 2. boundaries ---
        boundary = row0_true(capacity)
        for lane in key_sort_lanes(s_keys, s_keys_valid):
            boundary = boundary | _eq_prev(lane)
        # first padding row opens its own (dead) segment
        pad_start = jnp.concatenate([jnp.ones((1,), bool),
                                     s_live[1:] != s_live[:-1]])
        boundary = boundary | pad_start

        seg_ids = blocked_cumsum(boundary.astype(jnp.int32)) - 1
        count = jnp.sum(live, dtype=jnp.int32)
        num_groups = jnp.where(count > 0,
                               seg_ids[jnp.maximum(count - 1, 0)] + 1, 0)

        # --- 3. group keys: first row of each segment ---
        # seg ids rise with position, so the g-th boundary (position
        # order) IS segment g's start: ONE single-lane sort compacts the
        # boundary positions — no segment_min scatter
        big = jnp.int32(capacity)
        iota = jnp.arange(capacity, dtype=jnp.int32)
        start_raw = jnp.sort(jnp.where(boundary, iota, big))[:num_segments]
        end_idx = segment_ends(start_raw, count, capacity)
        start_idx = jnp.clip(start_raw, 0, capacity - 1)
        group_live = jnp.arange(capacity, dtype=jnp.int32) < num_groups
        okds, okvs, _ = take_keys_valid(s_keys, s_keys_valid, [],
                                        start_idx)
        out_keys = []
        for okd, okv in zip(okds, okvs):
            okv = jnp.ones((capacity,), bool) if okv is None else okv
            out_keys.append((okd, okv & group_live))

        # --- 4. aggregates ---
        need = sorted({s.input_idx for s in agg_specs if s.input_idx >= 0})
        in_lanes = []
        for i in need:
            v = agg_valid[i]
            in_lanes.append(agg_data[i])
            in_lanes.append(jnp.ones((capacity,), bool) if v is None else v)
        moved_in = grouped_take(in_lanes, perm) if in_lanes else []
        s_in = {i: (moved_in[2 * j], moved_in[2 * j + 1] & s_live)
                for j, i in enumerate(need)}
        spec_vls = []
        for spec in agg_specs:
            if spec.input_idx >= 0:
                spec_vls.append(s_in[spec.input_idx])
            else:
                spec_vls.append((None, s_live))
        outs = sorted_agg_outputs(agg_specs, spec_vls, s_live, boundary,
                                  start_idx, end_idx, group_live,
                                  num_segments, capacity, scatter_free)
        return out_keys, outs, num_groups

    return run


def reduce_trace(agg_specs, capacity):
    """No-key aggregation (single output row at index 0).

    `live` is an arbitrary row mask (see groupby_trace)."""
    def run(agg_data, agg_valid, live):
        outs = []
        for spec in agg_specs:
            d = agg_data[spec.input_idx] if spec.input_idx >= 0 else None
            v = agg_valid[spec.input_idx] if spec.input_idx >= 0 else None
            v = jnp.ones((capacity,), bool) if v is None else v
            vl = (v & live) if d is not None else live
            dt = spec.dtype
            if spec.kind in (COUNT, COUNT_ALL):
                val = jnp.sum(vl, dtype=jnp.int64)
                data, valid = val, jnp.asarray(True)
            else:
                nvalid = jnp.sum(vl, dtype=jnp.int32)
                valid = nvalid > 0
                cd = compute_view(d, dt)
                if spec.kind == SUM:
                    acc = cd.astype(jnp.float64 if t.is_floating(dt)
                                    else jnp.int64)
                    data = jnp.sum(jnp.where(vl, acc, 0))
                elif spec.kind in (MIN, MAX):
                    is_min = spec.kind == MIN
                    if isinstance(dt, t.DoubleType) and d.dtype == jnp.int64:
                        o = _bits_total_order(d)
                        ident = jnp.int64(_ORDER_MAX if is_min else _ORDER_MIN)
                        o = jnp.where(vl, o, ident)
                        red = jnp.min(o) if is_min else jnp.max(o)
                        data = _bits_from_order(red)
                    elif t.is_floating(dt):
                        isnan = jnp.isnan(cd) & vl
                        has_nan = jnp.any(isnan)
                        ident = jnp.float64(np.inf) if is_min \
                            else jnp.float64(-np.inf)
                        clean = jnp.where(vl & ~isnan, cd, ident)
                        red = jnp.min(clean) if is_min else jnp.max(clean)
                        n_clean = jnp.sum(vl & ~isnan)
                        if is_min:
                            data = jnp.where(has_nan & (n_clean == 0),
                                             jnp.float64(np.nan), red)
                        else:
                            data = jnp.where(has_nan, jnp.float64(np.nan), red)
                    else:
                        if isinstance(dt, t.BooleanType):
                            ident = jnp.asarray(is_min)
                        else:
                            info = np.iinfo(np.dtype(cd.dtype))
                            ident = jnp.asarray(info.max if is_min else info.min,
                                                cd.dtype)
                        acc = jnp.where(vl, cd, ident)
                        data = jnp.min(acc) if is_min else jnp.max(acc)
                elif spec.kind in (FIRST, LAST, FIRST_NN, LAST_NN):
                    idx = jnp.arange(capacity, dtype=jnp.int32)
                    is_first = spec.kind in (FIRST, FIRST_NN)
                    sel = vl if spec.kind in (FIRST_NN, LAST_NN) else live
                    masked = jnp.where(sel, idx, capacity if is_first else -1)
                    pick = jnp.min(masked) if is_first else jnp.max(masked)
                    pick = jnp.clip(pick, 0, capacity - 1)
                    data = compute_view(d, dt)[pick]
                    valid = vl[pick]
                elif spec.kind == ANY:
                    data = jnp.any(jnp.where(vl, cd, False))
                elif spec.kind == EVERY:
                    data = jnp.all(jnp.where(vl, cd, True))
                else:
                    raise ValueError(spec.kind)
            outs.append((data, valid))
        return outs

    return run


#: Largest combined key domain (null slots included) whose dense group-by
#: reduces under bucket masks; above it the buckets are scattered into.
#: Measured on a TPU v5e at capacity 4M with Q1's update specs (PERF.md
#: section 6, PR 28): masked 4.3 / 5.9 / 8.5 / 22.6 / 80.9 ms a batch at
#: D = 13 / 27 / 64 / 256 / 1024 (0.076 ms a bucket), the scatters
#: 357-365 ms whatever D and however many lanes ride the stack; a lone
#: float64 sum, float or integer min/max and first/last 24-43 ms masked
#: against 650-1300 ms scattered at D = 1024.  The lines would cross near
#: D = 4700, past `agg.denseDomainMax` (4096); 1024 is the largest domain
#: that was measured, and a program of four times Q1's lanes is still
#: ahead there.
MASKED_DOMAIN_MAX = 1024


def dense_domain(domain_sizes) -> int:
    """Buckets of the dense group-by: every key's codes and its null
    slot, multiplied out."""
    total = 1
    for size in domain_sizes:
        total *= size + 1
    return total


def dense_is_masked(domain_sizes) -> bool:
    """Whether dense_groupby_trace builds these domains' program from
    bucket-masked reductions (no scatter) — the trace and the strategy
    counter (exec/aggregate.py `_strategy`) both ask here."""
    return dense_domain(domain_sizes) <= MASKED_DOMAIN_MAX


def dense_groupby_trace(domain_sizes, agg_specs, capacity):
    """Bounded-domain groupby: NO SORT, NO ROW GATHERS.

    When every group key has a small static domain (dictionary codes,
    booleans), rows map to a dense bucket id (base-mixed radix over the
    key slots, one extra slot per key for null) and every aggregate is a
    reduction of the rows into D buckets, in one of two realisations
    chosen by D alone (dense_is_masked):

      * D <= MASKED_DOMAIN_MAX: per bucket a masked reduction over the
        rows, `reduce(where(seg == b, lane, identity))`, every lane a
        (N,) array of its own.  No scatter anywhere in the program: XLA
        fuses the (D, N) compare and select into each lane's reduce.
        TPC-H q1 (12 buckets, 7 int64 sums and the counts) takes 1.8
        ms a 4M-row batch inside its whole-plan program on a v5e.
      * above: one jax.ops.segment_* scatter per reduction (the sum-like
        lanes stacked into one, _batched_sums).  A scatter costs this
        chip 85 ns a row whatever D (360 ms a 4M-row batch for the
        stack, 280-300 more for each min/max; q1 paid 427 ms a batch
        until PR 28).

    Neither sorts nor gathers rows, which is what the sorted group-by
    pays for (O(C log C) multi-lane sort + per-column gathers).

    domain_sizes: static per-key domain size (codes in [0, size)).
    Returns fn(keys, keys_valid, agg_data, agg_valid, live) with the same
    contract as groupby_trace: occupied buckets compact to the front,
    group keys decode from the bucket id.
    """
    strides = []
    d_total = 1
    for size in reversed(domain_sizes):
        strides.append(d_total)
        d_total *= size + 1                       # +1: the null slot
    strides.reverse()
    D = d_total
    masked = dense_is_masked(domain_sizes)

    def run(keys, keys_valid, agg_data, agg_valid, live):
        comb = jnp.zeros((capacity,), jnp.int32)
        for size, stride, kd, kv in zip(domain_sizes, strides, keys,
                                        keys_valid):
            slot = jnp.clip(kd.astype(jnp.int32), 0, size - 1)
            if kv is not None:
                slot = jnp.where(kv, slot, jnp.int32(size))
            comb = comb + slot * jnp.int32(stride)
        seg = jnp.where(live, comb, jnp.int32(D))   # dead rows -> bucket D

        if masked:
            # a dead row's D matches no bucket: no slot for it
            hit = seg[None, :] == jnp.arange(D, dtype=jnp.int32)[:, None]
            reduce_to = _masked_reducer(hit)
            occupied = jnp.any(hit, axis=1)
        else:
            ns = D + 1
            reduce_to = _scatter_reducer(seg, ns)
            occupied = jax.ops.segment_max(live.astype(jnp.int32), seg,
                                           num_segments=ns)[:D] > 0
        num_groups = jnp.sum(occupied, dtype=jnp.int32)
        # compact occupied buckets to the front, stably (bucket order)
        order = jnp.argsort(jnp.where(occupied, jnp.int32(0),
                                      jnp.int32(1)), stable=True)
        group_live = jnp.arange(D, dtype=jnp.int32) < num_groups

        def reindex(a):
            return a[:D][order]

        def bucket(lane, ident, op):
            return reindex(reduce_to(lane, ident, op))

        out_keys = []
        for size, stride, kd in zip(domain_sizes, strides, keys):
            slot = (order // jnp.int32(stride)) % jnp.int32(size + 1)
            okd = slot.astype(kd.dtype)
            okv = (slot < size) & group_live
            out_keys.append((okd, okv))

        spec_vls = []
        for spec in agg_specs:
            if spec.input_idx >= 0:
                d = agg_data[spec.input_idx]
                v = agg_valid[spec.input_idx]
                v = jnp.ones((capacity,), bool) if v is None else v
            else:
                d, v = None, live
            vl = (v & live) if d is not None else live
            spec_vls.append((d, vl))
        if masked:
            def sum_lanes(lanes):
                # an int32 count lane widens to the stack's int64
                return [bucket(lane, 0, "sum").astype(
                    jnp.result_type(lane.dtype, jnp.int64))
                    for lane in lanes].__getitem__
        else:
            def sum_lanes(lanes):
                out = reindex(jax.ops.segment_sum(
                    jnp.stack(lanes, axis=1), seg, num_segments=ns))
                return lambda j: out[:, j]
        sum_of = _batched_sums(agg_specs, spec_vls, live, sum_lanes,
                               jnp.int32 if masked else jnp.int64)

        outs = []
        for si, spec in enumerate(agg_specs):
            d, vl = spec_vls[si]
            dt = spec.dtype
            if spec.kind in (COUNT, COUNT_ALL):
                outs.append((sum_of(("cnt", si), False), group_live))
                continue
            valid_count = sum_of(("vc", spec.input_idx), False)
            out_valid = (valid_count > 0) & group_live
            cd = compute_view(d, dt)
            if spec.kind == SUM:
                data = sum_of(("sum", si), t.is_floating(dt))
            elif spec.kind in (MIN, MAX):
                is_min = spec.kind == MIN
                op = "min" if is_min else "max"
                if isinstance(dt, t.DoubleType) and d.dtype == jnp.int64:
                    o = _bits_total_order(d)
                    ident = jnp.int64(_ORDER_MAX if is_min else _ORDER_MIN)
                    o = jnp.where(vl, o, ident)
                    data = _bits_from_order(bucket(o, ident, op))
                elif t.is_floating(dt):
                    data = reindex(_segment_minmax_float(cd, vl, reduce_to,
                                                         is_min))
                else:
                    if isinstance(dt, t.BooleanType):
                        ident = jnp.asarray(is_min)
                    else:
                        info = np.iinfo(np.dtype(cd.dtype))
                        ident = jnp.asarray(info.max if is_min
                                            else info.min, cd.dtype)
                    data = bucket(jnp.where(vl, cd, ident), ident, op)
            elif spec.kind in (FIRST, LAST, FIRST_NN, LAST_NN):
                idx = jnp.arange(capacity, dtype=jnp.int32)
                is_first = spec.kind in (FIRST, FIRST_NN)
                ignore_nulls = spec.kind in (FIRST_NN, LAST_NN)
                ident = jnp.int32(capacity) if is_first else jnp.int32(-1)
                pick = bucket(jnp.where(vl if ignore_nulls else live, idx,
                                        ident),
                              ident, "min" if is_first else "max")
                pick = jnp.clip(pick, 0, capacity - 1)
                data = cd[pick]
                # ignore-nulls: a bucket with no valid row picks out of
                # range, clipped onto another bucket's row
                out_valid = vl[pick] & (out_valid if ignore_nulls
                                        else group_live)
            elif spec.kind == ANY:
                data = bucket(jnp.where(vl, cd, False).astype(jnp.int8),
                              0, "max") > 0
            elif spec.kind == EVERY:
                data = bucket(jnp.where(vl, cd, True).astype(jnp.int8),
                              1, "min") > 0
            else:
                raise ValueError(f"unknown agg kind {spec.kind}")
            outs.append((data, out_valid))
        return out_keys, outs, num_groups

    return run
