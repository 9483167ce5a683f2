"""Bounded-domain segmented aggregation: block-local accumulate +
single-pass combine, in Pallas.

The portable group-by tiers either sort (packed single-lane sort +
segmented scans) or scatter (the dense no-sort bucket path).  When the
packed key domain is small — dictionary codes, booleans, range-packed
integer tuples — neither is the TPU-native shape: the cuDF/libcudf
answer is a block-local accumulator combined once, and on TPU that
accumulator IS the MXU: a (domain x block) one-hot contraction
accumulates every sum/count lane of a block in one matmul, and
MIN/MAX/FIRST/LAST/ANY/EVERY ride masked VPU reductions over the same
one-hot.  No sort, no scatter, no row permutation at all — aggregate
inputs are read in place, so dictionary codes and FOR-narrowed lanes
aggregate without decoding.

Exactness: int64 sums cannot ride a single f64 matmul (53-bit
mantissa), so integer lanes contract as two exact f64 matmuls over
their unsigned-low/signed-high 32-bit halves — each half's block sum
stays < 2^53 for any block <= 2^21 rows — and recombine in int64,
where wraparound matches jax.ops.segment_sum semantics.  f64 sums
combine block-parallel (different association than the sorted-run
scan, the variableFloatAgg contract the election gate enforces).

Output contract mirrors ops/groupby.packed_groupby_trace /
dense_groupby_trace: occupied buckets compact to the front in
ascending packed-key order (null slot 0 first), keys decode
arithmetically from the bucket id, (domain,)-sized outputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import groupby as G
from ... import types as t
from ..kernels import compute_view

_SEGAGG_CACHE = {}


def _block_rows(capacity: int, domain: int) -> int:
    """Accumulate-block sizing: the (domain x block) one-hot is the
    working set, budgeted at ~2^21 elements; the block must divide
    capacity (interpreter padding would otherwise feed junk rows into
    the accumulator) and stays <= 2^21 so 32-bit-half sums are exact
    in f64."""
    capacity = max(capacity, 1)
    blk = max(512, min(capacity, (1 << 21) // max(domain, 1)))
    p = 1 << (blk.bit_length() - 1)
    while p > 1 and capacity % p:
        p >>= 1
    return p if capacity // p <= 256 else capacity


def _seg_matmul_sums(seg, int_lanes, f64_lanes, domain: int,
                     capacity: int, interpret: bool):
    """(domain, Ki) exact int64 sums + (domain, Kf) f64 sums per bucket
    in ONE kernel pass: one-hot built once per block, integer lanes
    contracted as exact split-f64 half matmuls."""
    ki, kf = len(int_lanes), len(f64_lanes)
    blk = _block_rows(capacity, domain)
    grid = max(1, capacity // blk)
    sig = ("sums", domain, capacity, ki, kf, blk, interpret)
    fn = _SEGAGG_CACHE.get(sig)
    if fn is None:
        def kernel(seg_ref, ints_ref, f64s_ref, iacc_ref, facc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _():
                iacc_ref[...] = jnp.zeros((domain, max(ki, 1)),
                                          jnp.int64)
                facc_ref[...] = jnp.zeros((domain, max(kf, 1)),
                                          jnp.float64)
            onehot = (seg_ref[...][None, :] == jax.lax.broadcasted_iota(
                jnp.int32, (domain, blk), 0)).astype(jnp.float64)
            if ki:
                v = ints_ref[...]
                lo = (v & jnp.int64(0xFFFFFFFF)).astype(jnp.float64)
                hi = (v >> 32).astype(jnp.float64)
                slo = jax.lax.dot_general(
                    onehot, lo, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float64)
                shi = jax.lax.dot_general(
                    onehot, hi, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float64)
                iacc_ref[...] += shi.astype(jnp.int64) * jnp.int64(
                    1 << 32) + slo.astype(jnp.int64)
            if kf:
                facc_ref[...] += jax.lax.dot_general(
                    onehot, f64s_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float64)

        def run(seg, ints, f64s):
            return pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[pl.BlockSpec((blk,), lambda i: (i,)),
                          pl.BlockSpec((blk, max(ki, 1)),
                                       lambda i: (i, 0)),
                          pl.BlockSpec((blk, max(kf, 1)),
                                       lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((domain, max(ki, 1)),
                                        lambda i: (0, 0)),
                           pl.BlockSpec((domain, max(kf, 1)),
                                        lambda i: (0, 0))],
                out_shape=[jax.ShapeDtypeStruct((domain, max(ki, 1)),
                                                jnp.int64),
                           jax.ShapeDtypeStruct((domain, max(kf, 1)),
                                                jnp.float64)],
                interpret=interpret,
            )(seg, ints, f64s)
        fn = jax.jit(run)
        _SEGAGG_CACHE[sig] = fn
    zi = jnp.zeros((capacity, 1), jnp.int64)
    zf = jnp.zeros((capacity, 1), jnp.float64)
    ints = jnp.stack(int_lanes, axis=1) if ki else zi
    f64s = jnp.stack(f64_lanes, axis=1) if kf else zf
    iacc, facc = fn(seg, ints, f64s)
    return iacc, facc


def _seg_reduce(seg, lane, domain: int, capacity: int, is_min: bool,
                ident, interpret: bool):
    """(domain,) per-bucket min/max of one lane via the masked one-hot
    reduction (the VPU leg of the block accumulator)."""
    blk = _block_rows(capacity, domain)
    grid = max(1, capacity // blk)
    dts = str(lane.dtype)
    sig = ("reduce", domain, capacity, dts, is_min, blk, interpret)
    fn = _SEGAGG_CACHE.get(sig)
    if fn is None:
        def kernel(seg_ref, lane_ref, id_ref, acc_ref):
            iv = id_ref[0]

            @pl.when(pl.program_id(0) == 0)
            def _():
                acc_ref[...] = jnp.full((domain,), iv, lane_ref.dtype)
            onehot = seg_ref[...][None, :] == jax.lax.broadcasted_iota(
                jnp.int32, (domain, blk), 0)
            masked = jnp.where(onehot, lane_ref[...][None, :], iv)
            red = (jnp.min if is_min else jnp.max)(masked, axis=1)
            acc_ref[...] = (jnp.minimum if is_min else jnp.maximum)(
                acc_ref[...], red)

        def run(seg, lane, iv):
            return pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[pl.BlockSpec((blk,), lambda i: (i,)),
                          pl.BlockSpec((blk,), lambda i: (i,)),
                          pl.BlockSpec((1,), lambda i: (0,))],
                out_specs=pl.BlockSpec((domain,), lambda i: (0,)),
                out_shape=jax.ShapeDtypeStruct((domain,), lane.dtype),
                interpret=interpret,
            )(seg, lane, iv)
        fn = jax.jit(run)
        _SEGAGG_CACHE[sig] = fn
    iv = jnp.asarray(ident, lane.dtype).reshape((1,))
    return fn(seg, lane, iv)


def pallas_groupby_trace(pack_spec, key_lanes_info, agg_specs,
                         num_segments: int, capacity: int,
                         interpret: bool):
    """The block-accumulate group-by: same call contract AND output
    shape as ops/groupby.packed_groupby_trace — (num_segments,)-sized
    outputs, group order = ascending packed key (null first).  Shape
    parity with the sort path it replaces matters beyond tidiness: the
    adaptive join picks build sides by materialized BYTES, so a
    differently-sized aggregate output would flip join plans (and with
    them whole-plan traceability) when the tier toggles."""
    spans = [s[1] for s in pack_spec]
    los = [s[0] for s in pack_spec]
    strides = []
    tot = 1
    for s in reversed(spans):
        strides.append(tot)
        tot *= s
    strides.reverse()
    D = tot

    def run(keys, keys_valid, agg_data, agg_valid, live):
        packed = G._packed_key_lane(keys, keys_valid, pack_spec)
        seg = jnp.where(live, packed, jnp.int64(D)).astype(jnp.int32)

        iota = jnp.arange(capacity, dtype=jnp.int32)
        big = jnp.int32(capacity)

        # ---- queue every reduction over the original (unsorted) rows
        spec_vls = []
        for spec in agg_specs:
            if spec.input_idx >= 0:
                d = agg_data[spec.input_idx]
                v = agg_valid[spec.input_idx]
                v = jnp.ones((capacity,), bool) if v is None else v
                spec_vls.append((d, v & live))
            else:
                spec_vls.append((None, live))

        int_lanes, int_slots = [], {}
        f64_lanes, f64_slots = [], {}

        def queue_sum(key, lane, is_float):
            lanes_, slots = (f64_lanes, f64_slots) if is_float \
                else (int_lanes, int_slots)
            if key not in slots:
                slots[key] = len(lanes_)
                lanes_.append(lane)

        queue_sum(("rows",), live.astype(jnp.int64), False)
        for si, spec in enumerate(agg_specs):
            d, vl = spec_vls[si]
            dt = spec.dtype
            if spec.kind == G.COUNT_ALL:
                queue_sum(("cnt", si), live.astype(jnp.int64), False)
            elif spec.kind == G.COUNT:
                queue_sum(("cnt", si), vl.astype(jnp.int64), False)
            elif spec.kind == G.SUM:
                cd = compute_view(d, dt)
                if t.is_floating(dt):
                    queue_sum(("sum", si),
                              jnp.where(vl, cd.astype(jnp.float64), 0.0),
                              True)
                else:
                    queue_sum(("sum", si),
                              jnp.where(vl, cd.astype(jnp.int64), 0),
                              False)
            if spec.kind not in (G.COUNT, G.COUNT_ALL):
                queue_sum(("vc", spec.input_idx),
                          vl.astype(jnp.int64), False)

        iacc, facc = _seg_matmul_sums(seg, int_lanes, f64_lanes, D,
                                      capacity, interpret)

        def sum_of(key, is_float):
            return (facc[:, f64_slots[key]] if is_float
                    else iacc[:, int_slots[key]])

        occupied = sum_of(("rows",), False) > 0
        num_groups = jnp.sum(occupied, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(occupied, jnp.int8(0),
                                      jnp.int8(1)), stable=True)
        group_live = jnp.arange(D, dtype=jnp.int32) < num_groups

        out_keys = []
        for (dt, _hv, lane_dt), lo, span, stride in zip(
                key_lanes_info, los, spans, strides):
            slot = (order.astype(jnp.int64) // jnp.int64(stride)) % \
                jnp.int64(span)
            data = (slot - 1 + jnp.int64(lo)).astype(jnp.dtype(lane_dt))
            out_keys.append((data, (slot > 0) & group_live))

        def reduce_of(lane, is_min, ident):
            return _seg_reduce(seg, lane, D, capacity, is_min, ident,
                               interpret)[order]

        def nan_counts(si):
            # per-bucket NaN counts for the float-min contract (min is
            # NaN only when every valid value is NaN); a second small
            # matmul pass rather than churning the main sum signature
            d, vl = spec_vls[si]
            isnan = jnp.isnan(compute_view(d, agg_specs[si].dtype)) & vl
            return _seg_matmul_sums(
                seg, [isnan.astype(jnp.int64)], [], D, capacity,
                interpret)[0][:, 0][order]

        outs = []
        for si, spec in enumerate(agg_specs):
            d, vl = spec_vls[si]
            dt = spec.dtype
            if spec.kind in (G.COUNT, G.COUNT_ALL):
                outs.append((sum_of(("cnt", si), False)[order],
                             group_live))
                continue
            valid_count = sum_of(("vc", spec.input_idx), False)[order]
            out_valid = (valid_count > 0) & group_live
            cd = compute_view(d, dt)
            if spec.kind == G.SUM:
                data = sum_of(("sum", si), t.is_floating(dt))[order]
            elif spec.kind in (G.MIN, G.MAX):
                is_min = spec.kind == G.MIN
                if isinstance(dt, t.DoubleType) and d.dtype == jnp.int64:
                    o = G._bits_total_order(d)
                    ident = G._ORDER_MAX if is_min else G._ORDER_MIN
                    o = jnp.where(vl, o, jnp.int64(ident))
                    data = G._bits_from_order(
                        reduce_of(o, is_min, ident))
                elif t.is_floating(dt):
                    isnan = jnp.isnan(cd) & vl
                    has_nan = reduce_of(isnan.astype(jnp.int8), False,
                                        np.int8(0)) > 0
                    ident = np.float64(np.inf if is_min else -np.inf)
                    clean = jnp.where(vl & ~isnan, cd, ident)
                    red = reduce_of(clean, is_min, ident)
                    if is_min:
                        non_nan = valid_count - nan_counts(si)
                        data = jnp.where(has_nan & (non_nan == 0),
                                         jnp.float64(np.nan), red)
                    else:
                        data = jnp.where(has_nan, jnp.float64(np.nan),
                                         red)
                else:
                    if isinstance(dt, t.BooleanType):
                        ident = bool(is_min)
                    else:
                        info = np.iinfo(np.dtype(cd.dtype))
                        ident = info.max if is_min else info.min
                    data = reduce_of(jnp.where(vl, cd, jnp.asarray(
                        ident, cd.dtype)), is_min, ident)
            elif spec.kind in (G.FIRST, G.LAST):
                is_first = spec.kind == G.FIRST
                masked = jnp.where(live, iota, big if is_first else -1)
                pick = jnp.clip(reduce_of(masked, is_first,
                                          capacity if is_first else -1),
                                0, capacity - 1)
                data = cd[pick]
                out_valid = vl[pick] & group_live
            elif spec.kind in (G.FIRST_NN, G.LAST_NN):
                is_first = spec.kind == G.FIRST_NN
                masked = jnp.where(vl, iota, big if is_first else -1)
                pick = jnp.clip(reduce_of(masked, is_first,
                                          capacity if is_first else -1),
                                0, capacity - 1)
                data = cd[pick]
                # no valid row: the pick is out of range, clipped onto
                # another group's row
                out_valid = vl[pick] & out_valid
            elif spec.kind == G.ANY:
                data = reduce_of(jnp.where(vl, cd, False).astype(
                    jnp.int8), False, np.int8(0)) > 0
            elif spec.kind == G.EVERY:
                data = reduce_of(jnp.where(vl, cd, True).astype(
                    jnp.int8), True, np.int8(1)) > 0
            else:
                raise ValueError(f"unknown agg kind {spec.kind}")
            outs.append((data, out_valid))

        def fit(arr):
            # (D,) bucket lane -> (num_segments,) output lane, matching
            # the packed sort path's shapes (concat, never scatter)
            if D == num_segments:
                return arr
            if D > num_segments:
                return arr[:num_segments]
            pad = jnp.zeros((num_segments - D,), arr.dtype)
            return jnp.concatenate([arr, pad])

        out_keys = [(fit(kd), fit(kv)) for kd, kv in out_keys]
        outs = [(fit(data), fit(valid)) for data, valid in outs]
        return out_keys, outs, num_groups

    return run
