"""Equi-join kernels: dense-domain direct addressing with a sorted-lane
fallback.

Reference: GpuShuffledHashJoinExec / GpuHashJoin (GpuHashJoin.scala:104)
builds a cuDF hash table and gathers via GatherMaps.  Hash tables are a
poor fit for the MXU/VPU (serial probing, dynamic shapes); binary search
is equally hostile (log2(n) dependent gathers — profiled at >50% of
TPC-H join time on v5e).  The TPU-native design is therefore a
*direct-address table over the key domain* whenever exact range
statistics bound it (scan min/max propagated through the plan;
dictionary size for strings; packed-lane span for composite keys):

  1. every key column maps to a *canonical int64 lane* where Spark join
     equality == integer equality (NaN canonicalized to one bit pattern,
     -0.0 -> +0.0, strings -> codes in a dictionary unified across both
     sides, narrow ints sign-extended);
  2. with a known domain [lo, hi] of bounded span, the build side
     scatters row ids (unique keys) or per-key counts+offsets (duplicate
     keys) into a span-sized table — probes are then pure gathers, no
     search, no sort for the unique case, O(1) per probe row;
  3. without a domain, multi-key rows fold their lanes into a 64-bit
     mixed hash, the build side is sorted by it once, and probes binary-
     search the sorted lane (`searchsorted`) for candidate ranges;
  4. candidate pairs expand into a static output bucket (pair ownership
     recovered by scatter + cummax, not search) and are *verified*
     lane-by-lane, so hash collisions cannot produce wrong results, they
     only cost a masked-out row;
  5. outer/semi/anti variants derive from verified-match flags — a
     sorted index lane + merge-rank difference (segments.matched_flags;
     scatter reductions only behind the legacy knob) — never from the
     (overcounted) candidate ranges.

One host sync per probe batch fetches the candidate-pair count (the
reference syncs identically to size its gather maps); unique-build and
semi/anti probes are sync-free.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn, bucket_capacity
from ..config import TpuConf, DEFAULT_CONF
from .kernels import blocked_cummax, blocked_cumsum
from .search import searchsorted


INNER = "inner"
LEFT_OUTER = "left_outer"
RIGHT_OUTER = "right_outer"
FULL_OUTER = "full_outer"
LEFT_SEMI = "left_semi"
LEFT_ANTI = "left_anti"
CROSS = "cross"

_GOLDEN = 0x9E3779B97F4A7C15


def _mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer over uint64 lanes."""
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def _computed_f64_lanes(x: jax.Array) -> List[jax.Array]:
    """Exact injective int64 lane(s) for a *computed* (native-repr) f64 lane.

    The f64->i64 bitcast is unavailable on-TPU, so the encoding is built
    from conversions that exist on each backend:

      * TPU: the emulated f64 IS an (f32 hi, f32 lo) double-double pair, so
        `x.astype(f32)` recovers hi exactly and `x - hi` IS lo — two f32
        bitcasts packed into one int64 capture the full device value with
        zero loss.
      * CPU (real f64, used by the test mesh): the f32 pair keeps only ~48
        of 53 mantissa bits and overflows f32's exponent range, so distinct
        doubles would collide (the round-1 defect, ADVICE.md).  frexp gives
        an exact (53-bit scaled mantissa, exponent) pair instead — two
        int64 lanes, injective for every finite double.

    NaN (any payload) and -0.0 are canonicalized first: Spark equates them.
    """
    x = jnp.where(x == 0.0, jnp.float64(0.0), x)
    isnan = jnp.isnan(x)
    if jax.default_backend() == "tpu":
        hi = x.astype(jnp.float32)
        lo = jnp.where(jnp.isfinite(hi),
                       (x - hi.astype(jnp.float64)).astype(jnp.float32),
                       jnp.float32(0.0))
        hb = jax.lax.bitcast_convert_type(hi, jnp.int32)
        hb = jnp.where(isnan, jnp.int32(0x7FC00000), hb)
        lb = jax.lax.bitcast_convert_type(
            jnp.where(lo == 0.0, jnp.float32(0.0), lo), jnp.int32)
        lb = jnp.where(isnan, jnp.int32(0), lb)
        return [(hb.astype(jnp.int64) << 32) |
                (lb.astype(jnp.int64) & jnp.int64(0xFFFFFFFF))]
    # XLA CPU flushes subnormals to zero in every op INCLUDING == (verified:
    # jnp.float64(2**-1060) == 0.0 is True), so subnormal inputs are
    # indistinguishable from 0 under the backend's own equality — encode
    # them as 0 explicitly rather than trusting frexp's inconsistent
    # subnormal handling.
    sub = jnp.abs(x) < jnp.float64(2.0 ** -1022)
    m, e = jnp.frexp(jnp.where(sub, 0.0, x))  # m in +-[0.5,1), exact
    mi = (m * jnp.float64(2.0 ** 53)).astype(jnp.int64)
    el = e.astype(jnp.int64)
    isinf = jnp.isinf(x)
    mi = jnp.where(isinf, jnp.where(x > 0, jnp.int64(1), jnp.int64(-1)), mi)
    el = jnp.where(isinf, jnp.int64(1 << 30), el)
    mi = jnp.where(isnan, jnp.int64(0x7FF8000000000000), mi)
    el = jnp.where(isnan, jnp.int64(1 << 30), el)
    return [mi, el]


def canonical_lanes(col: DeviceColumn) -> List[jax.Array]:
    """int64 lane(s) with Spark join-equality semantics (see module doc):
    value equality on the column == elementwise equality of every lane.
    Strings must already carry a side-unified dictionary.

    Most types yield one lane; computed DOUBLE yields one or two depending
    on backend (_computed_f64_lanes).  Build and probe sides must derive
    their lanes through the same column representation (exec/join.py keeps
    plain-column keys on the storage lane for both sides)."""
    dt = col.dtype
    data = col.data
    if isinstance(dt, t.StringType):
        return [data.astype(jnp.int64)]
    if isinstance(dt, t.DoubleType):
        if data.dtype != jnp.int64:
            return _computed_f64_lanes(data)
        # int64-bits storage lane (host pass-through): canonicalize NaN
        # (any payload) and -0.0 on the BITS — exact for all 64 bits, no
        # round trip through the (emulated) f64 representation
        exp_mask = jnp.int64(0x7FF0000000000000)
        mant_mask = jnp.int64(0x000FFFFFFFFFFFFF)
        isnan = ((data & exp_mask) == exp_mask) & ((data & mant_mask) != 0)
        bits = jnp.where(isnan, jnp.int64(0x7FF8000000000000), data)
        neg_zero = jnp.int64(np.int64(np.uint64(0x8000000000000000)))
        return [jnp.where(bits == neg_zero, jnp.int64(0), bits)]
    if isinstance(dt, t.FloatType):
        isnan = jnp.isnan(data)
        canon = jnp.where(isnan, jnp.float32(np.nan), data)
        canon = jnp.where(canon == 0.0, jnp.float32(0.0), canon)
        b = jax.lax.bitcast_convert_type(canon, jnp.int32)
        b = jnp.where(isnan, jnp.int32(0x7FC00000), b)
        return [b.astype(jnp.int64)]
    if isinstance(dt, t.DecimalType) and dt.is_wide:
        raise NotImplementedError("wide decimal join keys")
    return [data.astype(jnp.int64)]


def key_cols_lanes(key_cols: Sequence[DeviceColumn]) -> List[jax.Array]:
    """Flat canonical lane list for a key column set."""
    lanes: List[jax.Array] = []
    for c in key_cols:
        lanes.extend(canonical_lanes(c))
    return lanes


def composite_hash(lanes: Sequence[jax.Array]) -> jax.Array:
    """Fold canonical lanes into one uint64 hash lane (single lane: the
    lane itself -> exact ranges, zero collisions)."""
    if len(lanes) == 1:
        # any order-consistent injective transform works; searchsorted only
        # needs build and probe to agree
        return lanes[0].astype(jnp.uint64)
    h = jnp.zeros(lanes[0].shape, jnp.uint64)
    for i, lane in enumerate(lanes):
        u = lane.astype(jnp.uint64)
        h = _mix64(h ^ _mix64(u + jnp.uint64(_GOLDEN * (i + 1) & (2**64 - 1))))
    return h


class BuildTable:
    """Build side of a join (the hash-table analogue): a dense
    direct-address table over the key domain when `domain` is given,
    else a sorted canonical lane.

    `lanes_override` replaces the per-column canonical lanes (e.g. a
    range-packed single lane for composite keys — exec/join.py
    _range_pack_spec); key validity still derives from `key_cols`.

    `domain=(lo, hi)` asserts every VALID build key lies in [lo, hi]
    (exact plan statistics); requires a single lane.  `unique` asserts
    build keys are distinct (plan uniqueness statistics) — with a domain
    this removes the build sort entirely (one scatter builds the table).

    Sort-dependent members (`perm`, `sorted_hash`) and the dense tables
    (`slot`, `offs`) are built lazily so eager-mode probes only pay for
    the structures their join type touches (XLA DCE does the same for
    traced whole-plan programs)."""

    def __init__(self, batch: DeviceBatch, key_cols: Sequence[DeviceColumn],
                 lanes_override: Optional[List[jax.Array]] = None,
                 domain: Optional[Tuple[int, int]] = None,
                 unique: bool = False,
                 extra_valid: Optional[jax.Array] = None,
                 dense_via_sort: bool = True,
                 matched_via_merge: bool = True,
                 matched_via_presence: bool = True):
        self.batch = batch
        lanes = lanes_override if lanes_override is not None \
            else key_cols_lanes(key_cols)
        valid = batch.row_mask() if extra_valid is None else extra_valid
        for c in key_cols:
            valid = valid & c.validity      # null keys never match
        self.lanes = lanes
        self.key_valid = valid
        self.unique = unique
        # scatter-avoidance knobs (config.py JOIN_DENSE_BUILD_VIA_SORT /
        # JOIN_MATCHED_VIA_MERGE): dense tables from a sorted lane +
        # merge-rank, matched flags from merge-rank differences
        self.dense_via_sort = dense_via_sort
        self.matched_via_merge = matched_via_merge
        self.matched_via_presence = matched_via_presence
        if domain is not None and len(lanes) == 1:
            self.domain = (int(domain[0]), int(domain[1]))
        else:
            self.domain = None
        self._perm = None
        self._sorted_hash = None
        self._valid_count = None
        self._slot = None
        self._offs = None
        self._present = None

    @property
    def span(self) -> int:
        lo, hi = self.domain
        return hi - lo + 1

    def _dense_pos(self):
        """(pos, in_bounds): clipped domain position + validity per build
        row."""
        lo, hi = self.domain
        lane = self.lanes[0].astype(jnp.int64)
        inb = self.key_valid & (lane >= lo) & (lane <= hi)
        pos = jnp.clip(lane - lo, 0, self.span - 1).astype(jnp.int32)
        return jnp.where(inb, pos, self.span), inb

    @property
    def slot(self) -> Optional[jax.Array]:
        """Dense-unique direct table: slot[k-lo] = build row of key k,
        -1 for absent keys.  None unless (domain and unique).

        Sort-built by default: the row id at each key's offset in the
        pos-sorted order (dense_via_sort) — the scatter-built table
        lands in an S(1)-space buffer whose per-probe gathers then run
        ~200 MB/s."""
        if self.domain is None or not self.unique:
            return None
        if self._slot is None:
            if self.dense_via_sort:
                offs = self.offs
                first = jnp.take(self.perm,
                                 jnp.clip(offs[:-1], 0,
                                          self.capacity - 1))
                occupied = offs[1:] > offs[:-1]
                self._slot = jnp.where(occupied,
                                       first.astype(jnp.int32), -1)
            else:
                tgt, _inb = self._dense_pos()
                self._slot = jnp.full(
                    (self.span,), -1, jnp.int32).at[tgt].set(
                    jnp.arange(self.capacity, dtype=jnp.int32),
                    mode="drop")
        return self._slot

    @property
    def offs(self) -> Optional[jax.Array]:
        """Dense per-key start offsets into the key-sorted order
        (span+1,); key k's build rows are perm[offs[k-lo]:offs[k-lo+1]].
        None without a domain.

        Sort-built by default: offs[k] = rank of k among the sorted
        domain positions — ONE single-lane sort + a merge-rank (two
        2-operand sorts) instead of a count scatter + cumsum."""
        if self.domain is None:
            return None
        if self._offs is None:
            tgt, _inb = self._dense_pos()
            if self.dense_via_sort:
                sorted_pos = jnp.sort(tgt)
                self._offs = _merge_rank(
                    sorted_pos.astype(jnp.uint64),
                    jnp.arange(self.span + 1, dtype=jnp.uint64),
                    side="left").astype(jnp.int32)
            else:
                counts = jnp.zeros((self.span,), jnp.int32).at[tgt].add(
                    jnp.int32(1), mode="drop")
                self._offs = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32),
                     blocked_cumsum(counts.astype(jnp.int32))])
        return self._offs

    @property
    def present(self) -> Optional[jax.Array]:
        """Dense-domain PRESENCE bitmap: present[k-lo] = some valid
        build row carries key k.  Matched-only probes (semi/anti) need
        exactly this — one bool scatter over build rows and a 1-byte
        gather per probe row, instead of the sorted offs table (a
        build-sized sort + merge-rank the flag never uses).  None
        without a domain."""
        if self.domain is None:
            return None
        if self._present is None:
            tgt, _inb = self._dense_pos()
            self._present = jnp.zeros((self.span,), bool).at[tgt].set(
                True, mode="drop")
        return self._present

    @property
    def perm(self) -> jax.Array:
        if self._perm is None:
            self._sort()
        return self._perm

    @property
    def sorted_hash(self) -> jax.Array:
        if self._sorted_hash is None:
            self._sort()
        return self._sorted_hash

    @property
    def valid_count(self) -> jax.Array:
        if self._valid_count is None:
            self._valid_count = jnp.sum(self.key_valid, dtype=jnp.int32)
        return self._valid_count

    def _sort(self):
        if self.domain is not None:
            # sort on the domain POSITION (int order), consistent with
            # the offs histogram — the uint64 hash order would disagree
            # for negative lanes
            tgt, _inb = self._dense_pos()
            self._perm = jnp.argsort(tgt, stable=True)
            self._sorted_hash = None    # dense probes never search
            return
        h = composite_hash(self.lanes)
        # dead/null-key rows get MAX and liveness-primary order, so the
        # array is globally non-decreasing (searchsorted-safe) and the
        # searchable region is exactly [0, valid_count); emitted as two
        # chained 2-operand stable sorts (TPU sort compile scales with
        # operand count — segments.lexsort_capped)
        from .segments import lexsort_capped
        sort_h = jnp.where(self.key_valid, h, jnp.uint64(2**64 - 1))
        perm = lexsort_capped(
            [sort_h, (~self.key_valid).astype(jnp.int8)], 2)
        self._perm = perm
        self._sorted_hash = jnp.take(sort_h, perm)

    @property
    def capacity(self) -> int:
        return self.batch.capacity


_PROBE_CACHE = {}


def _merge_rank(sorted_vals: jax.Array, queries: jax.Array,
                side: str) -> jax.Array:
    """np.searchsorted(sorted_vals, queries, side) without binary search:
    a stable sort merges both lanes and ranks fall out of a cumsum
    (log-step searchsorted gathers are the slowest access pattern on
    TPU — ~2.1s at 2M/4M vs ~0.2s for the merge on v5e).

    Tie order rides STABILITY, not a tag lane ('left' concatenates
    queries first so equal keys land after them; 'right' the reverse),
    and the rank inversion back to query order is a second stable sort
    on the id payload — both are 2-operand (key, payload) sorts.  TPU
    sort compile time scales with operand count (a 3-operand variadic
    sort costs minutes at 1M) and scatter outputs land in slow S(1)
    buffers, so two lean sorts beat one wide sort plus a scatter on
    both axes."""
    n = sorted_vals.shape[0]
    m = queries.shape[0]
    if side == "left":
        vals = jnp.concatenate([queries, sorted_vals])
        qlo = 0                         # query ids occupy [0, m)
    else:
        vals = jnp.concatenate([sorted_vals, queries])
        qlo = n                         # query ids occupy [n, n+m)
    ids = jnp.arange(n + m, dtype=jnp.int32)
    _v, s_ids = jax.lax.sort((vals, ids), num_keys=1, is_stable=True)
    is_key = (s_ids < qlo) | (s_ids >= qlo + m)
    cum = blocked_cumsum(is_key.astype(jnp.int32))
    # ranks back in query order: id-sort and slice the query span
    _i, ranks = jax.lax.sort((s_ids, cum), num_keys=1, is_stable=True)
    return ranks[qlo:qlo + m]


def _dense_probe_pos(lane: jax.Array, probe_valid: jax.Array,
                     lo: int, hi: int):
    """(pos, in_bounds) of probe keys in a build domain."""
    lane = lane.astype(jnp.int64)
    inb = probe_valid & (lane >= lo) & (lane <= hi)
    pos = jnp.clip(lane - lo, 0, hi - lo).astype(jnp.int32)
    return pos, inb


def probe_aligned(build: BuildTable, probe_lanes: List[jax.Array],
                  probe_valid: jax.Array):
    """Probe a build side whose keys are UNIQUE: each probe row has at
    most one match, so the output is probe-aligned — (build_idx, ok) with
    shape (probe_capacity,) and NO host sync (output capacity is the
    probe's own capacity, known statically).

    With a dense domain this is ONE gather from the direct-address
    table — no search, and the build needed no sort.  Otherwise the slot
    at searchsorted-left is the unique candidate.

    SINGLE-LANE ONLY: with one canonical lane the lane is exact (zero
    collisions).  With multiple lanes the composite hash can collide
    between distinct build keys and the single verified slot could miss
    a real match that sits one slot over — multi-lane joins must use
    probe_counts/expand_pairs, which scan the full candidate range.

    This is the TPU-native fast path for the dominant join shape
    (fact⋈dimension, join-against-group-by): the reference syncs to size
    its gather maps (GpuHashJoin.scala:104); a unique build side makes
    the size a static fact instead."""
    assert len(probe_lanes) == 1 and len(build.lanes) == 1, \
        "probe_aligned requires exact single-lane keys"
    if build.slot is not None:
        lo, hi = build.domain
        sig = ("aligned_dense", build.span, probe_valid.shape[0], lo, hi)
        fn = _PROBE_CACHE.get(sig)
        if fn is None:
            def run(slot, p_lane, p_valid):
                pos, inb = _dense_probe_pos(p_lane, p_valid, lo, hi)
                build_idx = jnp.take(slot, pos)
                ok = inb & (build_idx >= 0)
                return jnp.where(ok, build_idx, 0), ok
            fn = jax.jit(run)
            _PROBE_CACHE[sig] = fn
        return fn(build.slot, probe_lanes[0], probe_valid)
    sig = ("aligned", build.capacity, probe_valid.shape[0],
           len(probe_lanes))
    fn = _PROBE_CACHE.get(sig)
    if fn is None:
        bcap = build.capacity

        def run(perm, sorted_hash, valid_count, p_lanes, p_valid):
            h = composite_hash(p_lanes)
            lo = _merge_rank(sorted_hash, h, side="left")
            in_range = lo < valid_count
            pos = jnp.clip(lo, 0, bcap - 1)
            # the candidate's hash and its build row as ONE gathered
            # matrix: a TPU gather pays per gathered row, and this one is
            # as long as the probe.  With a single lane the hash IS the
            # key (composite_hash), so its equality is the key's; and the
            # sorted order holds exactly the valid-key rows before
            # `valid_count` (BuildTable._sort), so a candidate in range
            # has a valid key: no second and third gather through
            # `build_idx` to say the same again
            cand = jnp.take(
                jnp.stack([sorted_hash, perm.astype(jnp.uint64)], axis=1),
                pos, axis=0)
            ok = p_valid & in_range & (cand[:, 0] == h)
            return cand[:, 1].astype(jnp.int32), ok
        fn = jax.jit(run)
        _PROBE_CACHE[sig] = fn
    return fn(build.perm, build.sorted_hash, build.valid_count,
              tuple(probe_lanes), probe_valid)


def probe_matched_lazy(build: BuildTable, probe_lanes: List[jax.Array],
                       probe_valid: jax.Array) -> jax.Array:
    """Per-probe-row matched flag with NO host sync — sound only for a
    SINGLE canonical lane, where the "hash" is the lane itself and a
    non-empty candidate range proves a true match (semi/anti joins need
    only this flag, never the pairs).  Dense domains answer from the
    per-key counts (two gathers), no search and no build sort."""
    assert len(probe_lanes) == 1, "exact ranges require a single lane"
    if build.domain is not None and build.matched_via_presence:
        # presence bitmap, not the offs table: the flag needs key
        # EXISTENCE only, so the build-sized sort + merge-rank behind
        # `offs` never pays for itself here (q21/q22-class anti joins:
        # a 2M-row build answered by one span-sized bool scatter)
        lo, hi = build.domain
        sig = ("matched_present", build.span, probe_valid.shape[0], lo,
               hi)
        fn = _PROBE_CACHE.get(sig)
        if fn is None:
            def run(present, p_lane, p_valid):
                pos, inb = _dense_probe_pos(p_lane, p_valid, lo, hi)
                return inb & jnp.take(present, pos)
            fn = jax.jit(run)
            _PROBE_CACHE[sig] = fn
        return fn(build.present, probe_lanes[0], probe_valid)
    if build.domain is not None:
        lo, hi = build.domain
        sig = ("matched_dense", build.span, probe_valid.shape[0], lo, hi)
        fn = _PROBE_CACHE.get(sig)
        if fn is None:
            def run(offs, p_lane, p_valid):
                pos, inb = _dense_probe_pos(p_lane, p_valid, lo, hi)
                return inb & (jnp.take(offs, pos + 1) >
                              jnp.take(offs, pos))
            fn = jax.jit(run)
            _PROBE_CACHE[sig] = fn
        return fn(build.offs, probe_lanes[0], probe_valid)
    sig = ("matched_lazy", build.capacity, probe_valid.shape[0])
    fn = _PROBE_CACHE.get(sig)
    if fn is None:
        def run(sorted_hash, valid_count, lanes, pvalid):
            h = composite_hash(lanes)
            lo = _merge_rank(sorted_hash, h, side="left")
            hi = _merge_rank(sorted_hash, h, side="right")
            lo = jnp.minimum(lo, valid_count)
            hi = jnp.minimum(hi, valid_count)
            return pvalid & (hi > lo)
        fn = jax.jit(run)
        _PROBE_CACHE[sig] = fn
    return fn(build.sorted_hash, build.valid_count, tuple(probe_lanes),
              probe_valid)


def probe_counts(build: BuildTable, probe_lanes: List[jax.Array],
                 probe_valid: jax.Array):
    """-> (lo, counts, cum, total) ; total is a host int (one sync).
    `lo` values are candidate-range starts in build.perm order."""
    if build.domain is not None and len(probe_lanes) == 1:
        dlo, dhi = build.domain
        sig = ("counts_dense", build.span, probe_valid.shape[0], dlo, dhi)
        fn = _PROBE_CACHE.get(sig)
        if fn is None:
            def run(offs, p_lane, p_valid):
                pos, inb = _dense_probe_pos(p_lane, p_valid, dlo, dhi)
                lo = jnp.take(offs, pos)
                hi = jnp.take(offs, pos + 1)
                counts = jnp.where(inb, hi - lo, 0).astype(jnp.int32)
                return lo, counts, blocked_cumsum(counts)
            fn = jax.jit(run)
            _PROBE_CACHE[sig] = fn
        lo, counts, cum = fn(build.offs, probe_lanes[0], probe_valid)
        total = int(cum[-1]) if cum.shape[0] else 0
        return lo, counts, cum, total
    sig = ("probe_counts", build.capacity, probe_valid.shape[0],
           len(probe_lanes))
    fn = _PROBE_CACHE.get(sig)
    if fn is None:
        def run(sorted_hash, valid_count, lanes, pvalid):
            h = composite_hash(lanes)
            # restrict the search to the valid prefix
            lo = _merge_rank(sorted_hash, h, side="left")
            hi = _merge_rank(sorted_hash, h, side="right")
            lo = jnp.minimum(lo, valid_count)
            hi = jnp.minimum(hi, valid_count)
            counts = jnp.where(pvalid, hi - lo, 0).astype(jnp.int32)
            cum = blocked_cumsum(counts)
            return lo.astype(jnp.int32), counts, cum
        fn = jax.jit(run)
        _PROBE_CACHE[sig] = fn
    lo, counts, cum = fn(build.sorted_hash, build.valid_count,
                         tuple(probe_lanes), probe_valid)
    total = int(cum[-1]) if cum.shape[0] else 0
    return lo, counts, cum, total


def expand_pairs(build: BuildTable, probe_lanes: List[jax.Array],
                 probe_valid: jax.Array, lo, counts, cum, out_cap: int,
                 total: Optional[int] = None):
    """-> (probe_idx, build_idx, verified, probe_matched, build_matched)

    probe_idx/build_idx: (out_cap,) gather indices for candidate pairs;
    verified: lane-equality check per pair; probe_matched: per probe row;
    build_matched: per build row (for right/full outer).

    Pair ownership (which probe row owns output slot i) is recovered by
    scattering each live probe row's index at its range start and
    cummax-ing forward — O(n) scatter+scan instead of a binary search
    per output slot (the log2(n) dependent gathers of searchsorted are
    the slowest access pattern on TPU)."""
    # exact candidate ranges (single lane or dense domain) need no
    # per-pair verification against collisions, and probe_matched is just
    # counts>0 — skip one of the two segment reductions
    exact = len(build.lanes) == 1
    via_merge = build.matched_via_merge
    sig = ("expand", build.capacity, probe_valid.shape[0], out_cap,
           len(probe_lanes), exact, via_merge)
    fn = _PROBE_CACHE.get(sig)
    if fn is None:
        pcap = probe_valid.shape[0]
        bcap = build.capacity

        def run(perm, b_lanes, b_key_valid, p_lanes, p_valid, lo_,
                counts_, cum_, total):
            i = jnp.arange(out_cap, dtype=jnp.int32)
            pair_live = i < total
            starts = (cum_ - counts_).astype(jnp.int32)
            # pair ownership by MERGE, not scatter: sort probe range
            # starts together with the output slots (starts win ties so a
            # start owns its own slot), cummax the owning probe row
            # forward in merged order, then invert by the id payload —
            # two 2-operand sorts; scatter outputs land in slow S(1)
            # buffers and the variadic alternative is compile-hostile
            tgt = jnp.where(counts_ > 0, starts, out_cap)
            vals = jnp.concatenate([tgt, i])
            ids = jnp.arange(pcap + out_cap, dtype=jnp.int32)
            _v, s_ids = jax.lax.sort((vals, ids), num_keys=1,
                                     is_stable=True)
            is_start = s_ids < pcap
            mark = jnp.where(is_start, s_ids, -1)
            owner = blocked_cummax(mark)
            _i, owner_by_id = jax.lax.sort((s_ids, owner), num_keys=1,
                                           is_stable=True)
            probe_idx = jnp.maximum(owner_by_id[pcap:], 0).astype(jnp.int32)
            off = i - jnp.take(starts, probe_idx)
            pos = jnp.take(lo_, probe_idx) + off
            pos = jnp.clip(pos, 0, bcap - 1)
            build_idx = jnp.take(perm, pos)
            ok = pair_live
            if exact:
                ok = ok & jnp.take(p_valid, probe_idx)
                probe_matched = p_valid & (counts_ > 0)
            else:
                # verify true key equality (kills hash collisions)
                for bl, pl in zip(b_lanes, p_lanes):
                    ok = ok & (jnp.take(bl, build_idx) ==
                               jnp.take(pl, probe_idx))
                ok = ok & jnp.take(p_valid, probe_idx) & \
                    jnp.take(b_key_valid, build_idx)
                if via_merge:
                    from .segments import matched_flags
                    probe_matched = matched_flags(probe_idx, ok, pcap)
                else:
                    probe_matched = jax.ops.segment_max(
                        ok.astype(jnp.int32), probe_idx,
                        num_segments=pcap, indices_are_sorted=True) > 0
            if via_merge:
                from .segments import matched_flags
                build_matched = matched_flags(build_idx, ok, bcap)
            else:
                build_matched = jax.ops.segment_max(
                    ok.astype(jnp.int32), build_idx,
                    num_segments=bcap) > 0
            return probe_idx, build_idx, ok, probe_matched, build_matched
        fn = jax.jit(run, static_argnames=())
        _PROBE_CACHE[sig] = fn
    # callers pass probe_counts' total to avoid a second D2H sync
    true_total = total if total is not None \
        else (int(cum[-1]) if cum.shape[0] else 0)
    if true_total > out_cap:
        # callers size out_cap from probe_counts' total; a smaller cap would
        # silently drop matching rows — fail loudly instead
        raise ValueError(f"join candidate pairs {true_total} exceed output "
                         f"capacity {out_cap}")
    total = jnp.int32(true_total)
    return fn(build.perm, tuple(build.lanes), build.key_valid,
              tuple(probe_lanes), probe_valid, lo, counts, cum, total)
