"""Window kernels: segmented scans/reductions over partition-sorted rows.

Role of cuDF RollingAggregation / scan-based running windows in the
reference (window/GpuWindowExec.scala:146, GpuRunningWindowExec.scala:220,
GpuBatchedBoundedWindowExec.scala:220) — re-designed for XLA:

  * the input batch arrives sorted by (partition keys, order keys)
    (ops/sort.py operand-capped lexsort); partition and peer boundaries
    are equality flags on adjacent rows (same trick as the sort-segment
    groupby);
  * running frames  = blocked segmented inclusive scans (ops/segments.py
    — compiles in seconds where associative_scan ran minutes at 1M);
  * unbounded frames = the forward scan gathered at each row's segment
    end (scatter-free segment reduction over sorted runs);
  * bounded ROWS sums/counts = global prefix-sum differences with the
    window clamped to the partition span (exact: clamping keeps both
    gathers inside the current partition);
  * bounded ROWS min/max = static shift-stack reduction when both bounds
    are finite, forward/backward segmented scans gathered at the moving
    bound when one side is unbounded;
  * RANGE frames (UNBOUNDED/CURRENT shapes) = the running result gathered
    at each row's peer-group end / start.

Everything for one operator runs as ONE jit program per
(specs, bucket, layout) key.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from ..plan.window import WindowFrame
from .groupby import (_bits_from_order, _bits_total_order,
                      _null_first_key_lanes, _ORDER_MAX, _ORDER_MIN)
from .kernels import blocked_cumsum, compute_view
from .segments import blocked_seg_scan, row0_true

_NEG_ZERO = np.int64(-2 ** 63)


def _seg_scan(vals: jax.Array, boundary: jax.Array, op) -> jax.Array:
    """Segmented inclusive scan: resets at rows where boundary is True.

    Runs as the blocked two-level segmented scan (ops/segments.py) — the
    `lax.associative_scan` formulation it replaces compiled in ~80 s at
    1M rows on this platform; the blocked form is seconds."""
    return blocked_seg_scan(vals, boundary, op)


def _seg_scan_rev(vals: jax.Array, boundary: jax.Array, op) -> jax.Array:
    """Segmented inclusive scan running from each segment's END backwards.
    `boundary` marks segment STARTS; reversed, segment ends become starts."""
    end_b = jnp.concatenate([boundary[1:], jnp.ones((1,), bool)])
    out = _seg_scan(vals[::-1], end_b[::-1], op)
    return out[::-1]


def _boundary_from_lanes(lanes: List[jax.Array], capacity: int) -> jax.Array:
    """True where any lane differs from the previous row (row 0 True)."""
    b = row0_true(capacity)
    for lane in lanes:
        if lane is None:
            continue
        b = b | jnp.concatenate([jnp.ones((1,), bool),
                                 lane[1:] != lane[:-1]])
    return b


def _key_eq_lanes(cols_info, datas, valids) -> List[jax.Array]:
    lanes: List[jax.Array] = []
    for (dt,), d, v in zip(cols_info, datas, valids):
        if isinstance(dt, t.DoubleType) and d.dtype == jnp.int64:
            # a DOUBLE held as its bit pattern: peers by the bits (NaN
            # canonical, -0.0 = 0.0), not through this TPU's f64, which
            # keeps ~48 of 53 bits and would tie neighbours
            d = _bits_total_order(jnp.where(d == _NEG_ZERO, 0, d))
            dt = None
        else:
            d = compute_view(d, dt)
        lanes.extend(l for l in _null_first_key_lanes(d, v, dt)
                     if l is not None)
    return lanes


def _gather(vals: jax.Array, idx: jax.Array, capacity: int) -> jax.Array:
    return vals[jnp.clip(idx, 0, capacity - 1)]


def _minmax_ident(dtype, is_min: bool):
    if dtype in (jnp.float64, jnp.float32):
        return np.inf if is_min else -np.inf
    if np.dtype(dtype) == np.bool_:
        return is_min          # True is min-identity, False is max-identity
    info = np.iinfo(np.dtype(dtype))
    return info.max if is_min else info.min


def _minmax_lanes(cd, vl, dt, raw_data, is_min):
    """(order lane with invalid rows at identity, identity scalar, decoder,
    nan lane or None).

    DOUBLE int64-bits columns compare in Java total-order bit space (exact,
    NaN greatest).  Computed float lanes order by value with NaN mapped to
    +inf; callers restore NaN results from per-frame NaN counts (Spark:
    max is NaN when any valid value is NaN, min only when ALL are)."""
    if isinstance(dt, t.DoubleType) and raw_data is not None \
            and raw_data.dtype == jnp.int64:
        ident = jnp.int64(_ORDER_MAX if is_min else _ORDER_MIN)
        o = jnp.where(vl, _bits_total_order(raw_data), ident)
        return o, ident, _bits_from_order, None
    if t.is_floating(dt):
        f = cd.astype(jnp.float64)
        nan_lane = (jnp.isnan(f) & vl).astype(jnp.int64)
        o = jnp.where(jnp.isnan(f), jnp.float64(np.inf), f)
        ident = jnp.float64(np.inf if is_min else -np.inf)
        o = jnp.where(vl, o, ident)
        return o, ident, (lambda x: x), nan_lane
    if isinstance(dt, t.BooleanType):
        ident = jnp.int8(1 if is_min else 0)
        o = jnp.where(vl, cd.astype(jnp.int8), ident)
        return o, ident, (lambda x: x > 0), None
    ident = jnp.asarray(_minmax_ident(cd.dtype, is_min), cd.dtype)
    o = jnp.where(vl, cd, ident)
    return o, ident, (lambda x: x), None


def _round_half_up_div(num: jax.Array, den: jax.Array) -> jax.Array:
    """Spark decimal division rounding: HALF_UP (away from zero), den > 0."""
    mag = jnp.abs(num)
    q = (mag + den // 2) // den
    return jnp.where(num < 0, -q, q)


def _nan_restore(red, frame_cnt, frame_nan, is_min):
    """Spark float semantics over the NaN->+inf order lane: max is NaN when
    any valid value in the frame is NaN; min only when ALL are."""
    if frame_nan is None:
        return red
    non_nan = frame_cnt - frame_nan
    nan = jnp.float64(np.nan)
    if is_min:
        return jnp.where((frame_cnt > 0) & (non_nan == 0), nan, red)
    return jnp.where(frame_nan > 0, nan, red)


def _merge_rank_counts(seg, u, query, query_first: bool, part_start,
                       capacity: int, max_sort_operands: int = 2):
    """Per-row count of in-segment key values < query (query_first) or
    <= query (not query_first), computed without binary search: a sort
    merges the key lane with the query lane per segment (reference
    GpuBatchedBoundedWindowExec.scala:220 sizes value-offset frames with
    per-row searches; log-step searchsorted is the slowest access
    pattern on TPU, a merge sort rides the fast sort network)."""
    # tie order rides STABILITY (query-before-key = concat queries
    # first), not a tag lane, and the inversion back to row order is a
    # 2-operand sort — TPU sort compile scales with operand count, and
    # scatter outputs land in slow S(1) buffers
    if query_first:
        segs = jnp.concatenate([seg, seg])
        vals = jnp.concatenate([query, u])
        qlo = 0
    else:
        segs = jnp.concatenate([seg, seg])
        vals = jnp.concatenate([u, query])
        qlo = capacity
    ids = jnp.arange(2 * capacity, dtype=jnp.int32)
    if max_sort_operands >= 3:
        _sg, _vl, s_ids = jax.lax.sort((segs, vals, ids), num_keys=2,
                                       is_stable=True)
    else:
        # chained 2-operand form of the same (seg, val) order: sort by
        # value with the id payload, then stably by segment — the id
        # payload of the second sort IS the merged order
        _v1, p1 = jax.lax.sort((vals, ids), num_keys=1, is_stable=True)
        _s2, s_ids = jax.lax.sort((segs[p1], p1), num_keys=1,
                                  is_stable=True)
    is_key = (s_ids < qlo) | (s_ids >= qlo + capacity)
    cum = blocked_cumsum(is_key.astype(jnp.int32))
    # every batch row is a key, so keys in earlier segments == the
    # segment's starting row index
    _i, counts = jax.lax.sort((s_ids, cum), num_keys=1, is_stable=True)
    counts = counts[qlo:qlo + capacity]
    return counts - part_start


def _range_value_bounds(order_lane, order_valid, asc: bool,
                        nulls_first: bool, frame, seg, part_start,
                        part_end, peer_start, peer_end, capacity: int,
                        max_sort_operands: int = 2):
    """Per-row inclusive [lo, hi] row bounds of a value-offset RANGE
    frame over the single (int-lane) order key.  frame.lower/upper are
    SIGNED value offsets (None = unbounded, 0 = current peer group).
    Null order keys frame their null peer group (Spark)."""
    u = order_lane.astype(jnp.int64)
    if not asc:
        u = -u                      # normalize to ascending value space
    # keep real values off the int64 extremes: the extremes are the null
    # sentinels below, and u + offset must not wrap (saturating query)
    u = jnp.clip(u, jnp.int64(int(_ORDER_MIN) + 1),
                 jnp.int64(int(_ORDER_MAX) - 1))
    if order_valid is not None:
        # null-key rows sit at the segment's head or tail (sort nf);
        # pin their u to that extreme so non-null rows' merge counts
        # step over them correctly (their own bounds are masked below)
        null_u = jnp.int64(_ORDER_MIN if nulls_first else _ORDER_MAX)
        u = jnp.where(order_valid, u, null_u)

    def query(offset: int):
        # saturate u+offset inside the sentinel-free value band; the
        # clip bounds are exact python ints, so no intermediate wrap
        lo_b, hi_b = int(_ORDER_MIN) + 1, int(_ORDER_MAX) - 1
        lo_c = max(lo_b, lo_b - offset)
        hi_c = min(hi_b, hi_b - offset)
        return jnp.clip(u, jnp.int64(lo_c), jnp.int64(hi_c)) + \
            jnp.int64(offset)

    if frame.lower is None:
        lo = part_start
    elif frame.lower == 0:
        lo = peer_start
    else:
        # offsets are direction-free in the normalized (ascending-u)
        # space: for DESC, "x preceding" = key+x = u-x = u+lower
        cnt = _merge_rank_counts(seg, u, query(int(frame.lower)),
                                 query_first=True,
                                 part_start=part_start,
                                 capacity=capacity,
                                 max_sort_operands=max_sort_operands)
        lo = part_start + cnt
    if frame.upper is None:
        hi = part_end
    elif frame.upper == 0:
        hi = peer_end
    else:
        cnt = _merge_rank_counts(seg, u, query(int(frame.upper)),
                                 query_first=False,
                                 part_start=part_start,
                                 capacity=capacity,
                                 max_sort_operands=max_sort_operands)
        hi = part_start + cnt - 1
    if order_valid is not None:
        lo = jnp.where(order_valid, lo, peer_start)
        hi = jnp.where(order_valid, hi, peer_end)
    return lo, hi


def _ilog2(length: jax.Array, capacity: int) -> jax.Array:
    """floor(log2(length)) for 1 <= length <= capacity, exactly (no
    float round-off)."""
    k = jnp.zeros(length.shape, jnp.int32)
    j = 2
    while j <= capacity:
        k = k + (length >= j).astype(jnp.int32)
        j <<= 1
    return k


def _sparse_minmax(o, ident, lo, hi, op, capacity: int):
    """min/max over arbitrary per-row [lo, hi] spans via a sparse table
    (log2(cap) doubled-shift levels, two gathers per query) — the
    variable-width analogue of the static shift-stack used for bounded
    ROWS frames."""
    levels = [o]
    step = 1
    while step < capacity:
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[step:], jnp.full((step,), ident, prev.dtype)])
        levels.append(op(prev, shifted))
        step <<= 1
    table = jnp.stack(levels).reshape(-1)
    length = jnp.maximum(hi - lo + 1, 1).astype(jnp.int32)
    k = _ilog2(length, capacity)
    pow2 = (jnp.int32(1) << k)
    left = jnp.clip(lo, 0, capacity - 1)
    right = jnp.clip(hi - pow2 + 1, 0, capacity - 1)
    a = table[k * capacity + left]
    b = table[k * capacity + right]
    return op(a, b)


def window_trace(part_info, order_info, val_info, specs_frames,
                 capacity: int, order_dirs=(), scatter_free=True,
                 max_sort_operands=2):
    """Build the traced window program.

    part_info/order_info/val_info: tuples of (dtype,) per column (static).
    specs_frames: list of (spec, resolved WindowFrame, input_idx); input_idx
    indexes the value columns, -1 for input-less functions.
    scatter_free: partition/peer extents and whole-frame reductions ride
    segmented scans (+ per-row end gathers) instead of segment_* scatters.

    Returns fn(part_data, part_valid, order_data, order_valid,
               val_data, val_valid, live) -> [(data, valid)] per spec,
    where all lanes belong to the partition-sorted batch.
    """
    def run(part_data, part_valid, order_data, order_valid,
            val_data, val_valid, live):
        idx = jnp.arange(capacity, dtype=jnp.int32)

        # --- partition / peer structure ---
        part_lanes = _key_eq_lanes(part_info, part_data, part_valid)
        live_lane = (~live).astype(jnp.int8)
        part_b = _boundary_from_lanes(part_lanes + [live_lane], capacity)
        seg = blocked_cumsum(part_b.astype(jnp.int32)) - 1

        order_lanes = _key_eq_lanes(order_info, order_data, order_valid)
        peer_b = (part_b | _boundary_from_lanes(order_lanes, capacity)) \
            if order_lanes else part_b

        part_start = _seg_scan(idx, part_b, jnp.minimum)
        pg = blocked_cumsum(peer_b.astype(jnp.int32)) - 1
        peer_start = _seg_scan(idx, peer_b, jnp.minimum)
        if scatter_free:
            # a reverse max-scan IS the per-row segment end — no
            # segment_max scatter, no broadcast gather
            part_end = _seg_scan_rev(idx, part_b, jnp.maximum)
            peer_end = _seg_scan_rev(idx, peer_b, jnp.maximum)
        else:
            part_end = _gather(jax.ops.segment_max(idx, seg,
                                                   num_segments=capacity),
                               seg, capacity)
            peer_end = _gather(jax.ops.segment_max(idx, pg,
                                                   num_segments=capacity),
                               pg, capacity)
        part_rows = (part_end - part_start + 1).astype(jnp.int64)

        rn0 = idx - part_start                     # 0-based row number

        def frame_bounds(frame: WindowFrame):
            """Per-row inclusive [lo, hi] row-index bounds."""
            if frame.kind == "range":
                if frame.is_value_offset:
                    # value-offset RANGE: single int-lane order key
                    # (placement guarantees this)
                    asc, nf = order_dirs[0] if order_dirs else (True, True)
                    ov = order_valid[0]
                    ov = None if ov is None else (ov & live)
                    lo, hi = _range_value_bounds(
                        compute_view(order_data[0], order_info[0][0]),
                        ov, asc, nf, frame, seg, part_start, part_end,
                        peer_start, peer_end, capacity,
                        max_sort_operands=max_sort_operands)
                    return (jnp.clip(lo, part_start, part_end + 1),
                            jnp.clip(hi, part_start - 1, part_end))
                lo = part_start if frame.lower is None else peer_start
                hi = part_end if frame.upper is None else peer_end
                return lo, hi
            lo = part_start if frame.lower is None \
                else jnp.maximum(part_start, idx + frame.lower)
            hi = part_end if frame.upper is None \
                else jnp.minimum(part_end, idx + frame.upper)
            return lo, hi

        outs: List[Tuple[jax.Array, jax.Array]] = []
        for spec, frame, input_idx in specs_frames:
            kind = spec.kind
            if input_idx >= 0:
                d = val_data[input_idx]
                v = val_valid[input_idx]
                v = jnp.ones((capacity,), bool) if v is None else v
                dt = spec.child.dtype
                cd = compute_view(d, dt)
                vl = v & live
            else:
                d = cd = dt = None
                vl = live

            if kind == "row_number":
                outs.append(((rn0 + 1).astype(jnp.int32), live))
            elif kind == "rank":
                outs.append(((peer_start - part_start + 1).astype(jnp.int32),
                             live))
            elif kind == "dense_rank":
                dr = _seg_scan(peer_b.astype(jnp.int32), part_b, jnp.add)
                outs.append((dr, live))
            elif kind == "percent_rank":
                rank0 = (peer_start - part_start).astype(jnp.float64)
                denom = (part_rows - 1).astype(jnp.float64)
                pr = jnp.where(denom > 0, rank0 / jnp.maximum(denom, 1.0),
                               0.0)
                outs.append((pr, live))
            elif kind == "cume_dist":
                cume = (peer_end - part_start + 1).astype(jnp.float64) \
                    / part_rows.astype(jnp.float64)
                outs.append((cume, live))
            elif kind == "ntile":
                n = jnp.int64(spec.n)
                k = part_rows // n
                rem = part_rows % n
                i0 = rn0.astype(jnp.int64)
                cut = rem * (k + 1)
                bucket = jnp.where(
                    i0 < cut, i0 // jnp.maximum(k + 1, 1),
                    rem + (i0 - cut) // jnp.maximum(k, 1))
                bucket = jnp.where(part_rows < n, i0, bucket)
                outs.append(((bucket + 1).astype(jnp.int32), live))
            elif kind in ("lead", "lag"):
                shift = spec.offset * (1 if kind == "lead" else -1)
                src = idx + shift
                in_part = (src >= part_start) & (src <= part_end) & live
                sd = _gather(cd, src, capacity)
                sv = _gather(vl, src, capacity)
                if spec.default is not None:
                    # Spark: default only when the offset row does not
                    # exist; an existing null value stays null
                    dflt = jnp.asarray(spec.default, sd.dtype)
                    data = jnp.where(in_part, sd, dflt)
                    valid = jnp.where(in_part, sv, True) & live
                else:
                    data = jnp.where(in_part, sd, jnp.zeros((), sd.dtype))
                    valid = in_part & sv
                outs.append((data, valid))
            elif kind in ("first_value", "last_value"):
                lo, hi = frame_bounds(frame)
                pick = lo if kind == "first_value" else hi
                nonempty = hi >= lo
                data = _gather(cd, pick, capacity)
                valid = _gather(vl, pick, capacity) & nonempty & live
                outs.append((data, valid))
            elif kind in ("agg_sum", "agg_count", "agg_avg",
                          "agg_min", "agg_max"):
                outs.append(_framed_agg(
                    kind, spec, frame, cd, vl, dt, d, idx, part_b,
                    frame_bounds, seg, pg, peer_end, peer_start, live,
                    capacity, peer_b, part_end, scatter_free))
            else:
                raise ValueError(f"unknown window kind {kind}")
        return outs

    return run


def _framed_agg(kind, spec, frame, cd, vl, dt, raw_data, idx, part_b,
                frame_bounds, seg, pg, peer_end, peer_start, live,
                capacity, peer_b=None, part_end=None,
                scatter_free=True):
    """sum/count/min/max/avg over a frame; returns (data, valid)."""
    is_min = kind == "agg_min"
    count_all = kind == "agg_count" and spec.child is None
    cnt_lane = (live if count_all else vl).astype(jnp.int64)

    if kind in ("agg_sum", "agg_avg"):
        decimal = isinstance(dt, t.DecimalType)
        if decimal:
            acc = jnp.where(vl, cd.astype(jnp.int64), 0)
        elif kind == "agg_avg" or t.is_floating(dt):
            acc = jnp.where(vl, cd.astype(jnp.float64), 0.0)
        else:
            acc = jnp.where(vl, cd.astype(jnp.int64), 0)

    def finish(s, c):
        if kind == "agg_count":
            return c, live
        if kind == "agg_sum":
            return s, (c > 0) & live
        if isinstance(dt, t.DecimalType):
            # avg(decimal(p,s)) -> decimal(p+4, s+4): unscaled*10^4/count
            q = _round_half_up_div(s * jnp.int64(10 ** 4), jnp.maximum(c, 1))
            return q, (c > 0) & live
        return (s / jnp.maximum(c, 1).astype(jnp.float64), (c > 0) & live)

    # --- whole-partition / whole-peer-group frames: reduce + broadcast ---
    peers_only = frame.kind == "range" and frame.lower == 0 \
        and frame.upper == 0
    if frame.is_unbounded_both or peers_only:
        ids = pg if peers_only else seg
        b = peer_b if peers_only else part_b
        end = peer_end if peers_only else part_end

        def red_bcast(lane, op):
            """Whole-segment reduce broadcast to every member row."""
            if scatter_free:
                # the forward scan's value at the segment END is the
                # full reduction; `end` is already per-row — scan + one
                # gather, no segment_* scatter
                return _gather(_seg_scan(lane, b, op), end, capacity)
            red = {jnp.add: jax.ops.segment_sum,
                   jnp.minimum: jax.ops.segment_min,
                   jnp.maximum: jax.ops.segment_max}[op](
                lane, ids, num_segments=capacity)
            return _gather(red, ids, capacity)

        c = red_bcast(cnt_lane, jnp.add)
        if kind == "agg_count":
            return c, live
        if kind in ("agg_sum", "agg_avg"):
            return finish(red_bcast(acc, jnp.add), c)
        o, _ident, back, nan_lane = _minmax_lanes(cd, vl, dt, raw_data,
                                                  is_min)
        red = red_bcast(o, jnp.minimum if is_min else jnp.maximum)
        fnan = None if nan_lane is None else red_bcast(nan_lane, jnp.add)
        return _nan_restore(back(red), c, fnan, is_min), (c > 0) & live

    # --- running frames (incl. RANGE ..CURRENT ROW via peer-end gather) ---
    running_rows = frame.kind == "rows" and frame.is_running
    running_range = frame.kind == "range" and frame.lower is None \
        and frame.upper == 0
    if running_rows or running_range:
        def at_peers(x):
            return _gather(x, peer_end, capacity) if running_range else x
        c = at_peers(_seg_scan(cnt_lane, part_b, jnp.add))
        if kind == "agg_count":
            return c, live
        if kind in ("agg_sum", "agg_avg"):
            s = at_peers(_seg_scan(acc, part_b, jnp.add))
            return finish(s, c)
        o, _ident, back, nan_lane = _minmax_lanes(cd, vl, dt, raw_data,
                                                  is_min)
        red = at_peers(_seg_scan(
            o, part_b, jnp.minimum if is_min else jnp.maximum))
        fnan = None if nan_lane is None else at_peers(
            _seg_scan(nan_lane, part_b, jnp.add))
        return _nan_restore(back(red), c, fnan, is_min), (c > 0) & live

    # --- RANGE CURRENT ROW .. UNBOUNDED FOLLOWING: reverse running ---
    if frame.kind == "range" and frame.lower == 0 and frame.upper is None:
        def at_peer_start(x):
            return _gather(x, peer_start, capacity)
        c = at_peer_start(_seg_scan_rev(cnt_lane, part_b, jnp.add))
        if kind == "agg_count":
            return c, live
        if kind in ("agg_sum", "agg_avg"):
            s = at_peer_start(_seg_scan_rev(acc, part_b, jnp.add))
            return finish(s, c)
        o, _ident, back, nan_lane = _minmax_lanes(cd, vl, dt, raw_data,
                                                  is_min)
        red = at_peer_start(_seg_scan_rev(
            o, part_b, jnp.minimum if is_min else jnp.maximum))
        fnan = None if nan_lane is None else at_peer_start(
            _seg_scan_rev(nan_lane, part_b, jnp.add))
        return _nan_restore(back(red), c, fnan, is_min), (c > 0) & live

    # --- bounded ROWS frames ---
    lo, hi = frame_bounds(frame)
    nonempty = (hi >= lo) & live

    if kind in ("agg_sum", "agg_count", "agg_avg"):
        def pref_window(lane):
            p = blocked_cumsum(lane)
            hi_v = _gather(p, hi, capacity)
            lo_v = jnp.where(lo > 0, _gather(p, lo - 1, capacity),
                             jnp.zeros((), p.dtype))
            return jnp.where(nonempty, hi_v - lo_v, jnp.zeros((), p.dtype))
        c = pref_window(cnt_lane)
        if kind == "agg_count":
            return c, live
        return finish(pref_window(acc), c)

    # bounded min/max
    o, ident, back, nan_lane = _minmax_lanes(cd, vl, dt, raw_data, is_min)
    op = jnp.minimum if is_min else jnp.maximum
    c_cnt = None
    if frame.kind == "range":
        # value-offset RANGE: variable frame widths -> sparse table
        red = jnp.where(nonempty, _sparse_minmax(o, ident, lo, hi, op,
                                                 capacity), ident)
    elif frame.lower is None:
        # UNBOUNDED PRECEDING .. k FOLLOWING: forward scan gathered at hi
        fwd = _seg_scan(o, part_b, op)
        red = jnp.where(nonempty, _gather(fwd, hi, capacity), ident)
    elif frame.upper is None:
        # k PRECEDING .. UNBOUNDED FOLLOWING: backward scan gathered at lo
        bwd = _seg_scan_rev(o, part_b, op)
        red = jnp.where(nonempty, _gather(bwd, lo, capacity), ident)
    else:
        best = jnp.full((capacity,), 0, o.dtype) + ident
        c_cnt = jnp.zeros((capacity,), jnp.int64)
        for off in range(frame.lower, frame.upper + 1):
            src = idx + off
            ok = (src >= lo) & (src <= hi)
            cand_v = ok & _gather(vl, src, capacity)
            cand = jnp.where(cand_v, _gather(o, src, capacity), ident)
            best = op(best, cand)
            c_cnt = c_cnt + cand_v.astype(jnp.int64)
        red = best
    def pref_cnt(lane):
        p = blocked_cumsum(lane)
        hi_v = _gather(p, hi, capacity)
        lo_v = jnp.where(lo > 0, _gather(p, lo - 1, capacity), jnp.int64(0))
        return jnp.where(nonempty, hi_v - lo_v, jnp.int64(0))
    if c_cnt is None:
        c_cnt = pref_cnt(vl.astype(jnp.int64))
    fnan = None if nan_lane is None else pref_cnt(nan_lane)
    return _nan_restore(back(red), c_cnt, fnan, is_min), (c_cnt > 0) & live
