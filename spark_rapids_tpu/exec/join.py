"""Join exec nodes over the sorted-hash probe kernels (ops/join.py).

Reference execs: GpuShuffledHashJoinExec (GpuShuffledHashJoinExec.scala:107),
GpuBroadcastHashJoinExecBase, GpuHashJoin gather machinery
(org/.../execution/GpuHashJoin.scala:104).  Output schema is
left columns ++ right columns (Spark layout); the build side is fully
materialized (concat of the build stream), probes stream batch-by-batch —
the same shape as the reference's build-then-stream iterator.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import pyarrow as pa

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn, bucket_capacity
from ..ops import join as J
from ..ops.batch_ops import concat_batches, ensure_unique_dict, \
    remap_codes_into
from ..ops.filter import compact_batch, gather_batch
from ..plan import expressions as E
from .evaluator import evaluate_projection
from .plan import ExecContext, PlanNode


def _null_columns(schema: t.StructType, capacity: int) -> List[DeviceColumn]:
    cols = []
    for f in schema.fields:
        dt = f.data_type
        np_dt = jnp.int64 if isinstance(dt, t.DoubleType) \
            else t.physical_np_dtype(dt)
        cols.append(DeviceColumn(jnp.zeros((capacity,), np_dt),
                                 jnp.zeros((capacity,), bool), dt))
    return cols


def key_ref_names(exprs) -> Optional[List[str]]:
    """Column names when every key expression is a plain (possibly
    aliased) column reference, else None.  Shared by HashJoinExec and
    AdaptiveShuffledJoinExec so the aligned-path legality rule cannot
    drift between them."""
    names = []
    for e in exprs:
        inner = e.children[0] if isinstance(e, E.Alias) else e
        if not isinstance(inner, E.ColumnRef):
            return None
        names.append(inner.name)
    return names


def join_keys_unique(join_type: str, left, right, left_keys, right_keys,
                     names) -> bool:
    """Shared statistics-propagation rule for equi-join operators
    (HashJoinExec and the adaptive planner wrap the same semantics):
    semi/anti keep a subset of left rows; otherwise a side's columns stay
    unique iff that side was unique AND the other side's join keys are
    unique (each row matched at most once)."""
    def side_unique(keys, side):
        kn = key_ref_names(keys)
        return kn is not None and side.keys_unique(kn)

    if join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
        return left.keys_unique(names)
    left_names = set(left.output_schema.names)
    if all(n in left_names for n in names):
        return left.keys_unique(names) and side_unique(right_keys, right)
    right_names = set(right.output_schema.names)
    if all(n in right_names for n in names):
        return right.keys_unique(names) and side_unique(left_keys, left)
    return False


def join_column_range(join_type: str, left, right, name):
    """Shared value-range propagation: joins gather existing rows, so a
    column's range only narrows (outer-join nulls are not values)."""
    if name in left.output_schema.names:
        return left.column_range(name)
    if join_type not in (J.LEFT_SEMI, J.LEFT_ANTI) and \
            name in right.output_schema.names:
        return right.column_range(name)
    return None


def subpartition_row_gate(conf) -> int:
    """Build-side rows over which the eager join re-partitions both sides
    into sub-joins (GpuSubPartitionHashJoin's role): two target batches.
    The whole-plan path splits its plan under a join whose build side is
    bounded over it (exec/compiled.py _oversized_builds)."""
    return 2 * conf.batch_size_rows


def _join_partition_ids(key_cols: List[DeviceColumn], db: DeviceBatch,
                        num_buckets: int, salt: int = 0) -> jax.Array:
    """Bucket ids from join-key columns; value-stable across sides and
    batches (reuses the agg fallback's lane-normalized hash).  `salt`
    decorrelates recursive re-partitions of a skewed bucket — the same
    hash would map the bucket onto itself."""
    from .plan import _agg_partition_ids
    kb = DeviceBatch(list(key_cols), db.num_rows,
                     [f"_k{i}" for i in range(len(key_cols))])
    return _agg_partition_ids(kb, len(key_cols), num_buckets, salt)


class HashJoinExec(PlanNode):
    """Equi-join: inner / left|right|full outer / left semi / left anti.

    The RIGHT side is the build side (callers swap inputs to choose, as the
    reference's GpuJoinUtils.getGpuBuildSide does)."""

    def __init__(self, join_type: str, left_keys: Sequence[E.Expression],
                 right_keys: Sequence[E.Expression],
                 left: PlanNode, right: PlanNode,
                 probe_conds: Optional[List[E.Expression]] = None,
                 build_conds: Optional[List[E.Expression]] = None):
        super().__init__(left, right)
        self.join_type = join_type
        self.left_keys = [e.bind(left.output_schema) for e in left_keys]
        self.right_keys = [e.bind(right.output_schema) for e in right_keys]
        assert len(self.left_keys) == len(self.right_keys)
        # pre-fused filter predicates (see _peel_filters): evaluated as
        # masks on raw input batches instead of upstream compactions
        # lazy_sel: a mask-aware parent (negotiated by the overrides
        # post-pass) lets this join emit a selection vector instead of
        # compacting its output
        self.lazy_sel = False
        # seam_lazy: this join's output reaches a whole-plan seam through
        # projections and filters only (exec/compiled.py
        # _hand_masks_to_seam); the seam resolves a selection vector at
        # the bucket of the live rows, so inside the traced program the
        # join emits one as for a mask-aware parent
        self.seam_lazy = False
        # LATE MATERIALIZATION (columnar/lanes.py): output column names
        # the parent pipeline allows to ride as row-id lanes instead of
        # gathered payloads.  None = disabled; set by the overrides
        # legality pass (_negotiate_thin) only when every consumer up to
        # the pipeline sink handles thin batches.
        self.thin_payload = None
        self.probe_conds = list(probe_conds or [])
        self.build_conds = list(build_conds or [])
        if join_type not in (INNER_TYPES := {J.INNER, J.LEFT_OUTER,
                                             J.RIGHT_OUTER, J.FULL_OUTER,
                                             J.LEFT_SEMI, J.LEFT_ANTI}):
            raise ValueError(f"unsupported join type {join_type}")

    @property
    def left(self) -> PlanNode:
        return self.children[0]

    @property
    def right(self) -> PlanNode:
        return self.children[1]

    @property
    def output_schema(self) -> t.StructType:
        lf = list(self.left.output_schema.fields)
        if self.join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
            return t.StructType(lf)
        rf = list(self.right.output_schema.fields)
        return t.StructType(lf + rf)

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _plain_ref(e: E.Expression):
        inner = e.children[0] if isinstance(e, E.Alias) else e
        return inner if isinstance(inner, E.ColumnRef) else None

    def _raw_key_positions(self) -> List[bool]:
        """Key positions where BOTH sides are plain column references: those
        keys stay on their raw storage lanes (for DOUBLE that is the
        bit-exact int64 lane — projecting would force the lossy native-f64
        compute representation, the round-1 ADVICE.md defect).  Both sides
        must agree so build/probe lane encodings match."""
        out = []
        for le, re_ in zip(self.left_keys, self.right_keys):
            out.append(self._plain_ref(le) is not None and
                       self._plain_ref(re_) is not None)
        return out

    def _key_cols(self, db: DeviceBatch, exprs, raw_pos, ctx
                  ) -> List[DeviceColumn]:
        cols: List[Optional[DeviceColumn]] = [None] * len(exprs)
        proj_exprs, proj_slots = [], []
        for i, (e, raw) in enumerate(zip(exprs, raw_pos)):
            if raw:
                cols[i] = db.column_by_name(self._plain_ref(e).name)
            else:
                proj_exprs.append(e)
                proj_slots.append(i)
        if proj_exprs:
            kb = evaluate_projection(
                proj_exprs, [f"_k{i}" for i in proj_slots], db, ctx.conf)
            for slot, c in zip(proj_slots, kb.columns):
                cols[slot] = c
        return cols

    def keys_unique(self, names: Sequence[str]) -> bool:
        return join_keys_unique(self.join_type, self.left, self.right,
                                self.left_keys, self.right_keys, names)

    def _build_unique(self) -> bool:
        names = key_ref_names(self.right_keys)
        return names is not None and self.right.keys_unique(names)

    def _probe_unique(self) -> bool:
        names = key_ref_names(self.left_keys)
        return names is not None and self.left.keys_unique(names)

    def column_range(self, name: str):
        return join_column_range(self.join_type, self.left, self.right,
                                 name)

    def _range_pack_spec(self):
        """([(lo, stride)] per key column, total span) when the composite
        key can fold into ONE injective int64 lane from exact
        column-range statistics (min/max over BOTH sides), else None.
        Packed lane values lie in [0, total) — a ready-made dense domain.
        Gives multi-column joins the exact single-lane probe paths (no
        composite-hash collisions, no sizing sync)."""
        ln = key_ref_names(self.left_keys)
        rn = key_ref_names(self.right_keys)
        if ln is None or rn is None or len(ln) < 2:
            return None
        spans = []
        for l, r in zip(ln, rn):
            lr = self.left.column_range(l)
            rr = self.right.column_range(r)
            if lr is None or rr is None:
                return None
            lo = min(lr[0], rr[0])
            hi = max(lr[1], rr[1])
            spans.append((lo, hi - lo + 1))
        total = 1
        for _lo, span in spans:
            total *= span
            if total >= (1 << 62):
                return None
        spec = []
        stride = 1
        for lo, span in reversed(spans):
            spec.append((lo, stride))
            stride *= span
        spec.reverse()
        return spec, total

    @staticmethod
    def _span_fits(span: int, build_capacity: int) -> bool:
        """Direct-address-table sizing policy, shared by the single-key
        and packed-composite-key dense gates."""
        return span <= max(16 * build_capacity, 1 << 20) and \
            span <= (1 << 26)

    def _dense_domain(self, build_keys, build_capacity: int):
        """(lo, hi) covering every valid BUILD key, for single-key joins
        whose span is bounded enough for a direct-address table:
        dictionary size for strings (codes are dense by construction),
        exact scan statistics for integer-lane types.  None otherwise."""
        if len(self.right_keys) != 1:
            return None
        c = build_keys[0]
        if isinstance(c.dtype, t.StringType):
            if c.dictionary is None:
                return None
            span = max(len(c.dictionary), 1)
            lo, hi = 0, span - 1
        else:
            rn = key_ref_names(self.right_keys)
            if rn is None or key_ref_names(self.left_keys) is None:
                return None
            rng = self.right.column_range(rn[0])
            if rng is None:
                return None
            lo, hi = int(rng[0]), int(rng[1])
            span = hi - lo + 1
        if not self._span_fits(span, build_capacity):
            return None
        return lo, hi

    @staticmethod
    def _packed_lane(key_cols, spec) -> jax.Array:
        """Fold per-column int64 canonical lanes into the packed lane."""
        packed = None
        for c, (lo, stride) in zip(key_cols, spec):
            lane = c.data.astype(jnp.int64)
            part = (lane - jnp.int64(lo)) * jnp.int64(stride)
            packed = part if packed is None else packed + part
        return packed

    @staticmethod
    def _peel_filters(node: PlanNode):
        """Peel the chain of FilterExec children a join can fuse; returns
        (batch source node, conditions outermost-last).  Mirrors
        HashAggregateExec._strip_filters: the predicates become probe /
        build liveness masks instead of upstream mask compactions (a TPU
        compaction is an argsort + row gathers — far costlier than a
        fused mask lane)."""
        from .plan import FilterExec
        conds: List[E.Expression] = []
        while isinstance(node, FilterExec):
            conds.append(node.condition)
            node = node.child
        conds.reverse()
        return node, conds

    @staticmethod
    def _conds_mask(conds, db: DeviceBatch, base, ctx: ExecContext):
        """AND the fused predicates into a row mask over `db`."""
        from .evaluator import compute_predicate
        for c in conds:
            base = base & compute_predicate(c, db, ctx.conf)
        return base

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        right_src, peeled = self._peel_filters(self.right)
        build_conds = list(self.build_conds) + peeled
        left_src, peeled = self._peel_filters(self.left)
        probe_conds = list(self.probe_conds) + peeled
        # ---- build (right side), fully materialized ----
        # No per-batch row-count sync: empty batches are harmless (padding
        # only) and the sub-partition gate sizes by capacity, which bounds
        # rows from above without a D2H round trip.
        right_batches = [db for db in right_src.execute(ctx)
                         if db.capacity > 0 and not
                         (isinstance(db.num_rows, int) and db.num_rows == 0)]
        if not right_batches:
            yield from self._empty_build_output(left_src, probe_conds, ctx)
            return

        from ..config import HASH_SUBPARTITION_FALLBACK
        from . import ooc as O
        build_rows_bound = sum(b.capacity for b in right_batches)
        # the largest static bound a build side of this collect had
        ctx.metrics["join.build_bound_rows"] = max(
            ctx.metrics.get("join.build_bound_rows", 0), build_rows_bound)
        # Inside a whole-plan program the live row count is a value of
        # the program, and the sub-partition / out-of-core route below is
        # not one a program can take (its buckets are sized on the host;
        # spill and re-partitioning run on the eager engine: ROADMAP C3).
        # So a traced join decides from what is static and asks the host
        # for nothing: it builds in-program at the bound, whatever the
        # bound.
        if ctx.conf.get(HASH_SUBPARTITION_FALLBACK) and not ctx.traced:
            # Oversized build side: re-hash-partition BOTH sides into
            # independent sub-joins (GpuSubPartitionHashJoin.scala:32) —
            # equal keys hash to the same bucket on both sides, so the
            # union of bucket joins is the join.  The gate sizes by
            # BYTES against the out-of-core resident window (measured
            # row width from the batches — wide payload rows used to
            # blow past the row count before it tripped), with the
            # legacy 2-target-batch row gate kept as the floor and the
            # escalated/forced context tripping unconditionally.
            policy = O.ooc_policy(ctx)
            rows_trip = build_rows_bound > subpartition_row_gate(ctx.conf)
            bytes_trip = policy.bytes_trip(
                sum(b.nbytes() for b in right_batches))
            if rows_trip or bytes_trip or policy.force:
                build_rows = sum(int(b.num_rows) for b in right_batches)
                build_bytes = sum(O.batch_bytes(b) for b in right_batches)
                if build_rows > subpartition_row_gate(ctx.conf) or \
                        policy.bytes_trip(build_bytes) or policy.force:
                    yield from self._sub_partition_join(
                        right_batches, left_src, build_conds, probe_conds,
                        ctx, policy)
                    return
                right_batches = [b for b in right_batches
                                 if int(b.num_rows)]
                if not right_batches:
                    yield from self._empty_build_output(
                        left_src, probe_conds, ctx)
                    return

        build_batch = concat_batches(right_batches, ctx.conf)
        yield from self._join_stream(build_batch, left_src.execute(ctx),
                                     ctx, build_conds, probe_conds)

    def _sub_partition_join(self, right_batches, left_src, build_conds,
                            probe_conds, ctx: ExecContext, policy=None
                            ) -> Iterator[DeviceBatch]:
        """Budget-sized partitioned-spill join (the out-of-core tier):
        both sides hash-scatter into budget-registered spillable
        buckets; the partition count derives from measured build BYTES
        vs the resident window (exec/ooc.py), and a bucket whose build
        side still exceeds the window re-partitions recursively with a
        re-salted hash (bounded depth) so key skew cannot OOM it —
        past the depth bound the split-retry ladder owns the rest."""
        from ..runtime.memory import Spillable
        from . import ooc as O
        conf = ctx.conf
        if policy is None:
            policy = O.ooc_policy(ctx)
        build_rows = sum(int(b.num_rows) for b in right_batches)
        build_bytes = sum(O.batch_bytes(b) for b in right_batches)
        # legacy row-derived fan-out floors the byte-derived count so
        # budget-less configurations keep their old partition sizing
        rows_k = 1 << max(1, (build_rows // conf.batch_size_rows)
                          .bit_length() - 1)
        rows_k = min(rows_k, 32)
        k = O.partition_count(build_bytes, policy, rows_k=rows_k)
        ctx.bump("join_subpartition_fallbacks")
        O.record_election(
            ctx, "join",
            "bytes" if policy.bytes_trip(build_bytes) else
            ("forced" if policy.force and
             build_rows <= 2 * conf.batch_size_rows else "rows"))

        raw_pos = self._raw_key_positions()

        def scatter(db, exprs, conds, buckets, nparts, salt) -> int:
            if db.thin is not None:
                # key/condition columns must be dense before bucketing;
                # remaining deferred columns resolve inside the bucket
                # compaction (compact_thin — one composed gather)
                from ..columnar.lanes import materialize_refs
                db = materialize_refs(db, list(exprs) + list(conds),
                                      ctx.conf)
            keys = self._key_cols(db, exprs, raw_pos, ctx)
            ids = _join_partition_ids(keys, db, nparts, salt)
            # fused filters apply here — bucket batches are post-filter,
            # so the bucket joins run with no conds
            live = self._conds_mask(conds, db, db.row_mask(), ctx)
            scattered = 0
            for p in range(nparts):
                part = compact_batch(db, (ids == p) & live, ctx.conf)
                from ..ops.batch_ops import shrink_to_rows
                part = shrink_to_rows(part, int(part.num_rows), ctx.conf)
                if int(part.num_rows):
                    sp = Spillable(part, ctx.budget)
                    # live-row-scaled size rides the handle: bucket
                    # recursion must size by actual rows, not the
                    # min-bucket capacity padding of many tiny slices
                    sp.live_nbytes = O.batch_bytes(part)
                    buckets[p].append(sp)
                    scattered += sp.live_nbytes
            return scattered

        def process(bl, pl, depth):
            """Join one (build, probe) bucket pair, re-partitioning
            recursively while its build side exceeds the window."""
            if not bl and not pl:
                return
            bucket_bytes = sum(getattr(sp, "live_nbytes", sp.nbytes)
                               for sp in bl)
            if bl and policy.bytes_trip(bucket_bytes) and \
                    depth < policy.max_depth and \
                    sum(sp.num_rows for sp in bl) > 1:
                # skewed bucket: re-salted recursive re-partition
                O.record_recursion(ctx, "join")
                k2 = O.partition_count(bucket_bytes, policy)
                sub_b = [[] for _ in range(k2)]
                sub_p = [[] for _ in range(k2)]
                try:
                    sbytes = 0
                    for sp in bl:
                        b = sp.get()
                        sp.close()
                        sbytes += scatter(b, self.right_keys, (), sub_b,
                                          k2, depth + 1)
                    for sp in pl:
                        b = sp.get()
                        sp.close()
                        sbytes += scatter(b, self.left_keys, (), sub_p,
                                          k2, depth + 1)
                    O.record_partitions(ctx, "join", k2, sbytes)
                    for p in range(k2):
                        if not sub_b[p] and not sub_p[p]:
                            continue
                        O.fire(ctx, "join", bucket=p, k=k2,
                               depth=depth + 1)
                        yield from process(sub_b[p], sub_p[p], depth + 1)
                finally:
                    for part in sub_b + sub_p:
                        for sp in part:
                            sp.close()
                return

            def probes():
                for sp in pl:
                    b = sp.get()
                    sp.close()
                    yield b
            if not bl:
                if self.join_type in (J.INNER, J.LEFT_SEMI,
                                      J.RIGHT_OUTER):
                    # nothing to emit: release without re-uploading
                    for sp in pl:
                        sp.close()
                    return
                # empty build bucket: the empty-build rule decides
                yield from self._empty_build_stream(probes(), ctx)
                return
            bbs = [sp.get() for sp in bl]
            build_batch = concat_batches(bbs, ctx.conf) \
                if len(bbs) > 1 else bbs[0]
            for sp in bl:
                sp.close()
            yield from self._join_stream(build_batch, probes(), ctx)

        build_parts = [[] for _ in range(k)]
        probe_parts = [[] for _ in range(k)]
        try:
            sbytes = 0
            for db in right_batches:
                sbytes += scatter(db, self.right_keys, build_conds,
                                  build_parts, k, 0)
            for db in left_src.execute(ctx):
                if int(db.num_rows) == 0:
                    continue
                sbytes += scatter(db, self.left_keys, probe_conds,
                                  probe_parts, k, 0)
            O.record_partitions(ctx, "join", k, sbytes)
            for p in range(k):
                bl, pl = build_parts[p], probe_parts[p]
                if not bl and not pl:
                    continue
                O.fire(ctx, "join", bucket=p, k=k, depth=0)
                yield from process(bl, pl, 0)
        finally:
            # early generator abandonment (e.g. LIMIT above the join) must
            # not leak registered spillables / disk spill files; close is
            # idempotent by contract (runtime/memory.py), so handles the
            # bucket loop already consumed release nothing twice
            for part in build_parts + probe_parts:
                for sp in part:
                    sp.close()

    # -- late materialization helpers --------------------------------------

    def _thin_transparent(self) -> bool:
        """Whether this join can carry a THIN probe stream through (pass
        lanes along / compose them) instead of materializing on entry."""
        return self.thin_payload is not None and self.join_type in (
            J.INNER, J.LEFT_OUTER, J.LEFT_SEMI, J.LEFT_ANTI)

    def _defer_right(self) -> List[int]:
        """Right-side column indices this join defers behind a build
        row-id lane (inner/left-outer only: their null-extension falls
        out of the -1 lane; right/full outer emit a dense build tail)."""
        if self.thin_payload is None or \
                self.join_type not in (J.INNER, J.LEFT_OUTER):
            return []
        return [j for j, f in enumerate(self.right.output_schema.fields)
                if f.name in self.thin_payload]

    def _prep_probe(self, pb: DeviceBatch, probe_conds,
                    ctx: ExecContext) -> DeviceBatch:
        """Normalize an incoming probe batch for this join: a thin batch
        materializes fully unless this join is thin-transparent; a
        transparent join still forces early materialization of exactly
        the deferred columns its keys/conditions reference, plus any
        pending column the parent pipeline disallowed."""
        if pb.thin is None:
            return pb
        from ..columnar.lanes import materialize_batch, materialize_refs
        if not self._thin_transparent():
            return materialize_batch(pb, ctx.conf)
        pb = materialize_refs(pb, list(self.left_keys) + list(probe_conds),
                              ctx.conf)
        if pb.thin is not None:
            allowed = self.thin_payload
            bad = [p for p in pb.thin.pending
                   if pb.names[p] not in allowed]
            if bad:
                ctx.bump("join_thin_early_materialized", len(bad))
                pb = materialize_batch(pb, ctx.conf, bad)
        return pb

    @staticmethod
    def _make_thin(out_capacity: int, probe_thin, build_batch, build_lane,
                   defer_right, nleft: int, probe_sources=None):
        """ThinState for a join output: probe-side lane sources ride
        through (pass-through, or pre-composed through the pair
        expansion), the build side appends one new source addressed by
        `build_lane`.  None when nothing ends up pending."""
        from ..columnar.lanes import LaneSource, ThinState
        sources = list(probe_sources if probe_sources is not None
                       else (probe_thin.sources if probe_thin else []))
        pending = dict(probe_thin.pending) if probe_thin else {}
        if defer_right:
            ord_b = len(sources)
            sources.append(LaneSource(build_batch, build_lane))
            for j in defer_right:
                pending[nleft + j] = (ord_b, j)
        if not pending:
            return None
        return ThinState(out_capacity, sources, pending)

    def _join_stream(self, build_batch: DeviceBatch, probe_iter,
                     ctx: ExecContext, build_conds=(), probe_conds=()
                     ) -> Iterator[DeviceBatch]:
        raw_pos = self._raw_key_positions()
        lazy_sel = self.lazy_sel or (self.seam_lazy and ctx.traced)
        build_keys = self._key_cols(build_batch, self.right_keys, raw_pos,
                                    ctx)
        # fused build-side filters: rows failing them never match and
        # never surface as outer-unmatched
        build_pre = self._conds_mask(build_conds, build_batch,
                                     build_batch.row_mask(), ctx)
        # String build keys: dedupe their dictionaries ONCE; probe batches
        # remap into the build code space (-1 for strings the build side
        # never saw), so the build sort below happens once per join, not
        # once per probe batch.
        has_str = [isinstance(c.dtype, t.StringType) for c in build_keys]
        for i, s in enumerate(has_str):
            if s:
                build_keys[i] = ensure_unique_dict(build_keys[i])
        # Composite keys with exact range statistics fold into one
        # injective int64 lane — single-lane probe paths apply.
        pack_and_span = self._range_pack_spec() if all(raw_pos) else None
        pack, pack_span = pack_and_span if pack_and_span is not None \
            else (None, None)
        build_lanes = None if pack is None \
            else [self._packed_lane(build_keys, pack)]
        # Dense key domain (packed-lane span / dictionary size / scan
        # stats): probes become direct-address gathers — no search, and
        # a unique build side needs no sort either (ops/join.py).
        if pack is not None:
            domain = (0, pack_span - 1) if self._span_fits(
                pack_span, build_batch.capacity) else None
        else:
            domain = self._dense_domain(build_keys, build_batch.capacity)
        unique = domain is not None and self._build_unique()
        if domain is not None:
            ctx.bump("join_dense_domain")
        from ..config import (JOIN_DENSE_BUILD_VIA_SORT,
                              JOIN_MATCHED_VIA_MERGE,
                              JOIN_MATCHED_VIA_PRESENCE)
        build = J.BuildTable(build_batch, build_keys, build_lanes,
                             domain=domain, unique=unique,
                             extra_valid=build_pre if build_conds else None,
                             dense_via_sort=ctx.conf.get(
                                 JOIN_DENSE_BUILD_VIA_SORT),
                             matched_via_merge=ctx.conf.get(
                                 JOIN_MATCHED_VIA_MERGE),
                             matched_via_presence=ctx.conf.get(
                                 JOIN_MATCHED_VIA_PRESENCE))
        out_names = list(self.output_schema.names)
        # Sync-free probe-aligned path: a build side whose keys are unique
        # (exact plan statistics — dimension scans, group-by outputs) makes
        # every probe row match at most once, so join output rides the
        # probe's own static capacity and NO host round trip sizes it.
        # Single-lane only: the sorted lane is exact there (no composite-
        # hash collisions), so the one verified slot IS the unique match.
        aligned = all(raw_pos) and len(build.lanes) == 1 \
            and self._build_unique()
        if aligned:
            ctx.bump("join_aligned_fastpath")

        build_matched_acc = jnp.zeros((build_batch.capacity,), bool)

        # late materialization: right-side columns in `defer_right` ride
        # as a build row-id lane instead of being gathered per probe
        # batch; a thin probe stream passes its lanes through
        transparent = self._thin_transparent()
        defer_right = self._defer_right()
        defer_set = frozenset(defer_right)
        nleft = len(self.left.output_schema.names)
        nright = len(self.right.output_schema.fields) \
            if self.join_type not in (J.LEFT_SEMI, J.LEFT_ANTI) else 0
        if defer_right:
            from ..obs.registry import DEFERRED_GATHERS
            from ..columnar.lanes import deferred_column
            mat_right = [j for j in range(nright) if j not in defer_set]
            right_placeholders = {
                j: deferred_column(build_batch.columns[j])
                for j in defer_right}

        def right_out_cols(gathered):
            """Interleave gathered (materialized) right columns with the
            deferred placeholders, in schema order."""
            if not defer_right:
                return list(gathered)
            it = iter(gathered)
            return [right_placeholders[j] if j in defer_set else next(it)
                    for j in range(nright)]

        for pb in probe_iter:
            if isinstance(pb.num_rows, int) and pb.num_rows == 0:
                continue
            pb = self._prep_probe(pb, probe_conds, ctx)
            probe_keys = self._key_cols(pb, self.left_keys, raw_pos, ctx)
            for i, s in enumerate(has_str):
                if s:
                    probe_keys[i] = remap_codes_into(
                        probe_keys[i], build_keys[i].dictionary)
            probe_lanes = [self._packed_lane(probe_keys, pack)] \
                if pack is not None else J.key_cols_lanes(probe_keys)
            # fused probe-side filters: failing rows are dead for every
            # join type (they don't match, and don't surface as outer
            # unmatched rows either)
            pre = self._conds_mask(probe_conds, pb, pb.row_mask(), ctx)
            probe_valid = pre
            for c in probe_keys:
                probe_valid = probe_valid & c.validity

            if self.join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
                # matched flag only — no pair expansion; single-lane keys
                # (exact ranges) need no host sync and no uniqueness
                if len(probe_lanes) == 1 and len(build.lanes) == 1:
                    matched = J.probe_matched_lazy(build, probe_lanes,
                                                   probe_valid)
                else:
                    lo, counts, cum, total = J.probe_counts(
                        build, probe_lanes, probe_valid)
                    if total == 0:
                        matched = jnp.zeros((pb.capacity,), bool)
                    else:
                        out_cap = bucket_capacity(total, ctx.conf)
                        _, _, _, matched, _ = J.expand_pairs(
                            build, probe_lanes, probe_valid, lo, counts,
                            cum, out_cap, total)
                keep = matched if self.join_type == J.LEFT_SEMI \
                    else pre & ~matched
                if lazy_sel or (transparent and pb.thin is not None):
                    # mask-aware parent (aggregation live mask / another
                    # join's probe liveness) or a thin stream: skip the
                    # compaction — row gathers are the dominant device
                    # cost; thin lanes stay output-aligned
                    yield DeviceBatch(list(pb.columns),
                                      jnp.sum(keep, dtype=jnp.int32),
                                      out_names, pb.origin_file, sel=keep,
                                      thin=pb.thin)
                    continue
                out = compact_batch(pb, keep, ctx.conf)
                yield DeviceBatch(out.columns, out.num_rows, out_names)
                continue

            if aligned:
                build_idx, ok = J.probe_aligned(build, probe_lanes,
                                                probe_valid)
                # a masked probe's live rows are NOT a prefix: gather with
                # every position live; sel excludes dead rows downstream
                out_rows = pb.capacity if pb.sel is not None else pb.num_rows
                build_lane = jnp.where(ok, build_idx,
                                       jnp.int32(-1)).astype(jnp.int32)
                if defer_right:
                    # deferred right columns ride the lane; only the
                    # early-needed ones are gathered per probe batch
                    ctx.bump("join_deferred_gathers", len(defer_right))
                    DEFERRED_GATHERS.inc(len(defer_right))
                    rg_cols = right_out_cols(
                        gather_batch(build_batch.select(mat_right),
                                     build_lane, out_rows,
                                     null_out_of_bounds=True).columns
                        if mat_right else [])
                else:
                    rg_cols = gather_batch(build_batch, build_lane,
                                           out_rows,
                                           null_out_of_bounds=True).columns
                thin = self._make_thin(pb.capacity, pb.thin, build_batch,
                                       build_lane, defer_right, nleft) \
                    if (defer_right or pb.thin is not None) else None
                if self.join_type in (J.RIGHT_OUTER, J.FULL_OUTER):
                    if build.matched_via_merge:
                        from ..ops.segments import matched_flags
                        hit = matched_flags(build_idx, ok,
                                            build_batch.capacity)
                    else:
                        hit = jnp.zeros(
                            (build_batch.capacity,), jnp.int32) \
                            .at[jnp.where(ok, build_idx, 0)] \
                            .max(ok.astype(jnp.int32)) > 0
                    build_matched_acc = build_matched_acc | hit
                if self.join_type == J.LEFT_OUTER:
                    # all (filter-surviving) probe rows survive; unmatched
                    # rows carry null right columns (the -1 gather/lane)
                    out = DeviceBatch(list(pb.columns) + rg_cols,
                                      pb.num_rows, out_names, thin=thin)
                    if not probe_conds:
                        # a masked probe's liveness must survive verbatim
                        yield out if pb.sel is None else DeviceBatch(
                            out.columns, pb.num_rows, out_names,
                            sel=pb.sel, thin=thin)
                    elif lazy_sel or thin is not None:
                        yield DeviceBatch(out.columns,
                                          jnp.sum(pre, dtype=jnp.int32),
                                          out_names, sel=pre, thin=thin)
                    else:
                        yield compact_batch(out, pre, ctx.conf)
                else:   # inner / right_outer / full_outer matched part
                    pairs = DeviceBatch(list(pb.columns) + rg_cols,
                                        pb.num_rows, out_names, thin=thin)
                    keep = ok & pre
                    if self.join_type == J.INNER and \
                            (lazy_sel or thin is not None):
                        yield DeviceBatch(pairs.columns,
                                          jnp.sum(keep, dtype=jnp.int32),
                                          out_names, sel=keep, thin=thin)
                    else:
                        yield compact_batch(pairs, keep, ctx.conf)
                    if self.join_type == J.FULL_OUTER:
                        unmatched = pre & ~ok
                        right_nulls = _null_columns(
                            self.right.output_schema, pb.capacity)
                        padded = DeviceBatch(
                            list(pb.columns) + right_nulls, pb.num_rows,
                            out_names)
                        yield compact_batch(padded, unmatched, ctx.conf)
                continue

            lo, counts, cum, total = J.probe_counts(build, probe_lanes,
                                                    probe_valid)
            go_thin = defer_right or (transparent and pb.thin is not None)
            if total > 0:
                out_cap = bucket_capacity(total, ctx.conf)
                probe_idx, build_idx, ok, probe_matched, build_matched = \
                    J.expand_pairs(build, probe_lanes, probe_valid, lo,
                                   counts, cum, out_cap, total)
                build_matched_acc = build_matched_acc | build_matched
                if go_thin:
                    # thin pair expansion: gather only materialized
                    # columns; upstream probe lanes COMPOSE through
                    # probe_idx (one int32 take per source) and the
                    # deferred right columns ride the new build lane
                    from ..columnar.lanes import LaneSource
                    pend_l = pb.thin.pending if pb.thin is not None else {}
                    mat_l = [i for i in range(len(pb.columns))
                             if i not in pend_l]
                    lg = gather_batch(pb.select(mat_l), probe_idx, total)
                    safe_p = jnp.clip(probe_idx, 0,
                                      max(pb.capacity - 1, 0))
                    probe_sources = []
                    if pb.thin is not None:
                        for s in pb.thin.sources:
                            comp = jnp.take(s.lane, safe_p)
                            probe_sources.append(LaneSource(
                                s.batch,
                                jnp.where(ok, comp, jnp.int32(-1))))
                    build_lane = jnp.where(ok, build_idx, jnp.int32(-1))
                    if defer_right:
                        gathered = gather_batch(
                            build_batch.select(mat_right), build_lane,
                            total, null_out_of_bounds=True).columns \
                            if mat_right else []
                        rg_cols = right_out_cols(gathered)
                        ctx.bump("join_deferred_gathers", len(defer_right))
                        DEFERRED_GATHERS.inc(len(defer_right))
                    else:
                        rg_cols = gather_batch(
                            build_batch, build_lane, total,
                            null_out_of_bounds=True).columns
                    left_cols = []
                    lgi = iter(lg.columns)
                    for i in range(nleft):
                        left_cols.append(pb.columns[i] if i in pend_l
                                         else next(lgi))
                    thin = self._make_thin(out_cap, pb.thin, build_batch,
                                           build_lane, defer_right, nleft,
                                           probe_sources=probe_sources)
                    yield DeviceBatch(left_cols + rg_cols,
                                      jnp.sum(ok, dtype=jnp.int32),
                                      out_names, sel=ok, thin=thin)
                else:
                    lg = gather_batch(pb, probe_idx, total)
                    rg = gather_batch(build_batch, build_idx, total)
                    pairs = DeviceBatch(lg.columns + rg.columns, total,
                                        out_names)
                    pairs = compact_batch(pairs, ok, ctx.conf)
                    yield pairs
            else:
                probe_matched = jnp.zeros((pb.capacity,), bool)

            if self.join_type in (J.LEFT_OUTER, J.FULL_OUTER):
                unmatched = pre & ~probe_matched
                left_cols = list(pb.columns)
                if go_thin and self.join_type == J.LEFT_OUTER:
                    # unmatched probe rows: deferred right columns keep a
                    # -1 (null) lane, upstream lanes pass through
                    null_lane = jnp.full((pb.capacity,), -1, jnp.int32)
                    rn_cols = right_out_cols(_null_columns(
                        t.StructType([
                            f for j, f in enumerate(
                                self.right.output_schema.fields)
                            if j not in defer_set]),
                        pb.capacity)) if defer_right else _null_columns(
                        self.right.output_schema, pb.capacity)
                    thin = self._make_thin(pb.capacity, pb.thin,
                                           build_batch, null_lane,
                                           defer_right, nleft)
                    yield DeviceBatch(left_cols + rn_cols,
                                      jnp.sum(unmatched, dtype=jnp.int32),
                                      out_names, sel=unmatched, thin=thin)
                else:
                    right_nulls = _null_columns(self.right.output_schema,
                                                pb.capacity)
                    padded = DeviceBatch(left_cols + right_nulls,
                                         pb.num_rows, out_names)
                    yield compact_batch(padded, unmatched, ctx.conf)

        if self.join_type in (J.RIGHT_OUTER, J.FULL_OUTER):
            unmatched = build_pre & ~build_matched_acc
            left_nulls = _null_columns(self.left.output_schema,
                                       build_batch.capacity)
            padded = DeviceBatch(left_nulls + list(build_batch.columns),
                                 build_batch.num_rows, out_names)
            yield compact_batch(padded, unmatched, ctx.conf)

    def _empty_build_output(self, left_src, probe_conds, ctx
                            ) -> Iterator[DeviceBatch]:
        # top level: inner/semi/right-outer need not execute the probe
        # subtree at all (the pre-sub-partition short-circuit)
        if self.join_type in (J.INNER, J.LEFT_SEMI, J.RIGHT_OUTER):
            return
        yield from self._empty_build_stream(left_src.execute(ctx), ctx,
                                            probe_conds)

    def _empty_build_stream(self, probe_iter, ctx, probe_conds=()
                            ) -> Iterator[DeviceBatch]:
        """Empty build side: inner/semi/right produce nothing; left outer
        and anti pass probe rows through (right side null)."""
        if self.join_type in (J.INNER, J.LEFT_SEMI, J.RIGHT_OUTER):
            for _ in probe_iter:     # drain (sub-partition spill cleanup)
                pass
            return
        out_names = list(self.output_schema.names)
        for pb in probe_iter:
            if int(pb.num_rows) == 0:
                continue
            if pb.thin is not None and not self._thin_transparent():
                from ..columnar.lanes import materialize_batch
                pb = materialize_batch(pb, ctx.conf)
            if probe_conds:
                pb = compact_batch(
                    pb, self._conds_mask(probe_conds, pb, pb.row_mask(),
                                         ctx), ctx.conf)
            if self.join_type == J.LEFT_ANTI:
                yield DeviceBatch(pb.columns, pb.num_rows, out_names,
                                  sel=pb.sel, thin=pb.thin)
            else:   # left/full outer
                right_nulls = _null_columns(self.right.output_schema,
                                            pb.capacity)
                yield DeviceBatch(list(pb.columns) + right_nulls,
                                  pb.num_rows, out_names, sel=pb.sel,
                                  thin=pb.thin)

    def describe(self):
        return (f"HashJoinExec[{self.join_type}, "
                f"keys={len(self.left_keys)}]")


class CrossJoinExec(PlanNode):
    """GpuCartesianProductExec analogue: every (probe, build) pair."""

    def __init__(self, left: PlanNode, right: PlanNode):
        super().__init__(left, right)

    @property
    def output_schema(self) -> t.StructType:
        return t.StructType(list(self.children[0].output_schema.fields) +
                            list(self.children[1].output_schema.fields))

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        out_names = list(self.output_schema.names)
        if self.children[1].static_row_count() == 1:
            # scalar-subquery cross join (the HAVING-against-total shape):
            # exactly one build row broadcasts onto every probe row with
            # zero host syncs
            build = None
            for db in self.children[1].execute(ctx):
                build = db if build is None else build
            for pb in self.children[0].execute(ctx):
                idx0 = jnp.zeros((pb.capacity,), jnp.int32)
                rg = gather_batch(build, idx0, pb.num_rows)
                yield DeviceBatch(list(pb.columns) + rg.columns,
                                  pb.num_rows, out_names)
            return
        right_batches = [db for db in self.children[1].execute(ctx)
                         if int(db.num_rows) > 0]
        if not right_batches:
            return
        build = concat_batches(right_batches, ctx.conf)
        nb = int(build.num_rows)
        for pb in self.children[0].execute(ctx):
            npr = int(pb.num_rows)
            if npr == 0:
                continue
            total = npr * nb
            out_cap = bucket_capacity(total, ctx.conf)
            i = jnp.arange(out_cap, dtype=jnp.int32)
            probe_idx = i // nb
            build_idx = i % nb
            lg = gather_batch(pb, probe_idx, total)
            rg = gather_batch(build, build_idx, total)
            yield DeviceBatch(lg.columns + rg.columns, total, out_names)
