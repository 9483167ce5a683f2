"""Adaptive (runtime-statistics) execution: the engine's AQE analogue.

Reference: the plugin's AQE integration re-applies overrides per query
stage with real sizes in hand (GpuOverrides.scala:496-564,
GpuCustomShuffleReaderExec.scala:37), and
GpuShuffledSymmetricHashJoinExec.scala:354 probes both join inputs'
sizes at runtime to pick the build side.  Spark can do this because a
shuffle stage fully materializes before the next stage is planned.

This engine's plans are single-process pipelines, so the same two
runtime decisions attach directly to the operators that need them:

- `AdaptiveShuffledJoinExec` materializes BOTH join inputs as spillable
  stages (exactly what completed map stages are), measures real bytes,
  and builds the hash table on the smaller side — mirroring the join
  type when that swaps the inputs and restoring the original column
  order on output.
- `plan_coalesced_reads` groups a materialized exchange's partitions to
  an advisory byte target using the shuffle manager's real per-partition
  sizes (the GpuAQEShuffleRead / coalesced CustomShuffleReader role).
"""
from __future__ import annotations

from typing import Iterator, List, Sequence

from .. import types as t
from ..columnar.device import DeviceBatch
from ..plan import expressions as E
from ..runtime.memory import Spillable
from .join import HashJoinExec
from .plan import ExecContext, PlanNode

_MIRROR = {"inner": "inner", "left_outer": "right_outer",
           "right_outer": "left_outer", "full_outer": "full_outer"}


class _ReplayStage(PlanNode):
    """A completed, spillable 'stage' the re-planned join replays."""

    def __init__(self, batches: List[Spillable], schema: t.StructType,
                 source: PlanNode = None):
        super().__init__()
        self.batches = batches
        self._schema = schema
        self._source = source      # statistics delegate (keys_unique)

    @property
    def output_schema(self) -> t.StructType:
        return self._schema

    def keys_unique(self, names):
        # replay preserves exactly the source's rows
        return self._source is not None and self._source.keys_unique(names)

    def column_range(self, name):
        return None if self._source is None \
            else self._source.column_range(name)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for sp in self.batches:
            yield sp.get()

    def describe(self):
        return f"ReplayStage[{len(self.batches)} batches]"


class _BloomFilterStage(PlanNode):
    """Probe-side runtime filter: drop rows whose join key is DEFINITELY
    absent from the build side (ops/bloom.py).  Only wrapped around
    joins where unmatched probe rows never reach the output."""

    def __init__(self, child: PlanNode, bits, key_cols_fn, k: int,
                 key_exprs=None):
        super().__init__(child)
        self.bits = bits
        self.key_cols_fn = key_cols_fn
        self.k = k
        self.key_exprs = list(key_exprs or [])

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..ops.bloom import bloom_might_contain
        from ..ops.filter import compact_batch
        import jax.numpy as jnp
        for db in self.child.execute(ctx):
            if db.thin is not None and self.key_exprs:
                # a THIN probe stream: the bloom probe needs dense key
                # columns — materialize exactly those; payload lanes
                # stay live (the wrapped join composes them)
                from ..columnar.lanes import materialize_refs
                db = materialize_refs(db, self.key_exprs, ctx.conf)
            mask = bloom_might_contain(self.bits, self.key_cols_fn(db),
                                       db, self.k) & db.row_mask()
            if db.thin is not None:
                # preserve the lanes: compose the bloom verdict into the
                # selection vector instead of compacting (a compaction is
                # the row-gather pass late materialization exists to skip)
                ctx.bump("bloom_filtered_rows",
                         jnp.int64(db.num_rows) -
                         jnp.sum(mask, dtype=jnp.int64))
                yield DeviceBatch(list(db.columns),
                                  jnp.sum(mask, dtype=jnp.int32),
                                  db.names, db.origin_file, sel=mask,
                                  thin=db.thin)
                continue
            out = compact_batch(db, mask, ctx.conf)
            # lazy metric: accumulate on device, coerced ONCE at query end
            # (PhysicalQuery._instrumented) instead of a sync per batch
            ctx.bump("bloom_filtered_rows",
                     jnp.int64(db.num_rows) - jnp.int64(out.num_rows))
            yield out

    def describe(self):
        return f"BloomFilterStage[k={self.k}]"


class AdaptiveShuffledJoinExec(PlanNode):
    """Equi-join whose build side is chosen from measured input sizes.

    Output schema and semantics are identical to
    HashJoinExec(join_type, ...) — the mirror swap is invisible outside
    (columns are restored to left-then-right order)."""

    def __init__(self, join_type: str, left_keys: Sequence[E.Expression],
                 right_keys: Sequence[E.Expression],
                 left: PlanNode, right: PlanNode):
        super().__init__(left, right)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.lazy_sel = False      # forwarded to the inner HashJoinExec
        self.seam_lazy = False     # likewise (HashJoinExec.seam_lazy)
        # late-materialization allowance (plan/overrides.py
        # _negotiate_thin), forwarded to the inner HashJoinExec; the
        # mirror swap is invisible (thin state remaps through select)
        self.thin_payload = None

    @property
    def left(self) -> PlanNode:
        return self.children[0]

    @property
    def right(self) -> PlanNode:
        return self.children[1]

    @property
    def output_schema(self) -> t.StructType:
        lf = list(self.left.output_schema.fields)
        if self.join_type in ("left_semi", "left_anti"):
            return t.StructType(lf)
        return t.StructType(lf + list(self.right.output_schema.fields))

    @staticmethod
    def _side_unique(keys, side) -> bool:
        from .join import key_ref_names
        kn = key_ref_names(keys)
        return kn is not None and side.keys_unique(kn)

    def keys_unique(self, names):
        from .join import join_keys_unique
        return join_keys_unique(self.join_type, self.left, self.right,
                                self.left_keys, self.right_keys, names)

    def column_range(self, name):
        from .join import join_column_range
        return join_column_range(self.join_type, self.left, self.right,
                                 name)

    def _materialize(self, node: PlanNode, ctx: ExecContext
                     ) -> List[Spillable]:
        # no per-batch row-count sync: empty batches are padding-only and
        # byte sizing below uses capacity-based nbytes (host-known)
        return [Spillable(db, ctx.budget) for db in node.execute(ctx)
                if not (isinstance(db.num_rows, int) and db.num_rows == 0)]

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        left_stage: List[Spillable] = []
        right_stage: List[Spillable] = []
        # Fuse upstream filters (HashJoinExec._peel_filters): stages hold
        # the RAW child batches and the predicates ride into the join as
        # probe/build masks — no mask compaction on either input.  Byte
        # sizes below are therefore PRE-filter sizes; the build-side
        # choice only shifts when filters are both selective and skewed
        # between sides, and correctness never depends on it.
        left_src, left_conds = HashJoinExec._peel_filters(self.left)
        right_src, right_conds = HashJoinExec._peel_filters(self.right)
        try:
            left_stage = self._materialize(left_src, ctx)
            right_stage = self._materialize(right_src, ctx)
            lbytes = sum(sp._nbytes for sp in left_stage)
            rbytes = sum(sp._nbytes for sp in right_stage)
            ctx.metrics["adaptive_left_bytes"] = lbytes
            ctx.metrics["adaptive_right_bytes"] = rbytes
            swap = (self.join_type in _MIRROR) and lbytes < rbytes
            if self.join_type in _MIRROR:
                # A UNIQUE-keyed build side unlocks the sync-free aligned
                # probe (exec/join.py) — worth more than raw size unless
                # the unique side is dramatically bigger (8x guard).
                run_u = self._side_unique(self.right_keys, self.right)
                lun_u = self._side_unique(self.left_keys, self.left)
                if run_u != lun_u:
                    if ctx.traced:
                        # inside a whole-plan program a build side with
                        # duplicate keys would size its pairs on the
                        # host (ROADMAP C10): the unique side builds,
                        # whatever the sizes
                        swap = lun_u
                    elif lun_u and lbytes <= 8 * max(rbytes, 1):
                        swap = True
                    elif run_u and rbytes <= 8 * max(lbytes, 1):
                        swap = False
            if swap:
                ctx.bump("adaptive_join_mirrored")
                jt = _MIRROR[self.join_type]
                join = HashJoinExec(
                    jt, self.right_keys, self.left_keys,
                    _ReplayStage(right_stage,
                                 self.right.output_schema, self.right),
                    _ReplayStage(left_stage, self.left.output_schema,
                                 self.left),
                    probe_conds=right_conds, build_conds=left_conds)
                join.lazy_sel = self.lazy_sel
                join.seam_lazy = self.seam_lazy
                join.thin_payload = self.thin_payload
                self._maybe_bloom(join, jt, left_stage,
                                  max(rbytes, 1), lbytes, ctx)
                n_r = len(self.right.output_schema.fields)
                n_l = len(self.left.output_schema.fields)
                # mirrored output is right-cols ++ left-cols; restore
                perm = list(range(n_r, n_r + n_l)) + list(range(n_r))
                for db in join.execute(ctx):
                    yield db.select(perm)
            else:
                join = HashJoinExec(
                    self.join_type, self.left_keys, self.right_keys,
                    _ReplayStage(left_stage, self.left.output_schema,
                                 self.left),
                    _ReplayStage(right_stage,
                                 self.right.output_schema, self.right),
                    probe_conds=left_conds, build_conds=right_conds)
                join.lazy_sel = self.lazy_sel
                join.seam_lazy = self.seam_lazy
                join.thin_payload = self.thin_payload
                self._maybe_bloom(join, self.join_type, right_stage,
                                  max(lbytes, 1), rbytes, ctx)
                yield from join.execute(ctx)
        finally:
            for sp in left_stage + right_stage:
                sp.close()

    def _maybe_bloom(self, join: HashJoinExec, effective_jt: str,
                     build_stage: List[Spillable], probe_bytes: int,
                     build_bytes: int, ctx: ExecContext) -> None:
        """Install a probe-side bloom runtime filter when profitable.

        Safe only where unmatched PROBE rows never reach the output
        (inner/left_semi: dropped anyway; right_outer: output = matched
        probe + all build rows).  left/full outer must keep unmatched
        probe rows null-extended, anti must OUTPUT them — never
        filtered."""
        from ..config import RUNTIME_FILTER_ENABLED, RUNTIME_FILTER_RATIO
        if effective_jt not in ("inner", "right_outer", "left_semi"):
            return
        if not ctx.conf.get(RUNTIME_FILTER_ENABLED):
            return
        if probe_bytes < build_bytes * ctx.conf.get(RUNTIME_FILTER_RATIO):
            return
        from .join import key_ref_names
        build_rows = sum(sp.num_rows for sp in build_stage)
        rn = key_ref_names(join.right_keys)
        if rn is not None and len(rn) == 1 and \
                key_ref_names(join.left_keys) is not None and \
                build_rows <= 2 * ctx.conf.batch_size_rows:
            # (sub-partitioned builds never make one dense table, so the
            # skip only applies on the single-batch path)
            rng = join.right.column_range(rn[0])
            if rng is not None and HashJoinExec._span_fits(
                    int(rng[1]) - int(rng[0]) + 1, max(build_rows, 1)):
                # the join will probe a dense direct-address table (two
                # gathers per batch) — a bloom pass costs a full probe
                # compaction, more than it can save there
                return
        from ..config import RUNTIME_FILTER_FPP
        from ..ops.bloom import (bloom_build, optimal_hashes,
                                 optimal_slots)
        m = optimal_slots(build_rows, fpp=ctx.conf.get(RUNTIME_FILTER_FPP))
        k = optimal_hashes(build_rows, m)
        raw_pos = join._raw_key_positions()
        bits = None
        for sp in build_stage:
            bb = sp.get()
            # fused build filters must mask insertion, else the bloom
            # keeps the keys the filter was meant to remove
            live = None
            if join.build_conds:
                live = join._conds_mask(join.build_conds, bb,
                                        bb.row_mask(), ctx)
            bits = bloom_build(
                join._key_cols(bb, join.right_keys, raw_pos, ctx),
                bb, m, k, bits, live=live)

        def probe_keys(db):
            return join._key_cols(db, join.left_keys, raw_pos, ctx)

        # the probe child was just constructed by execute(); wrapping it
        # here keeps key binding (done in HashJoinExec.__init__) intact
        join.children[0] = _BloomFilterStage(
            join.children[0], bits, probe_keys, k,
            key_exprs=join.left_keys)
        ctx.metrics["bloom_filter_slots"] = m

    def describe(self):
        return f"AdaptiveShuffledJoinExec[{self.join_type}]"


def plan_coalesced_reads(exchange, ctx: ExecContext,
                         advisory_bytes: int) -> List[List]:
    """Group a materialized exchange's partitions so each reduce group is
    ~advisory_bytes, from REAL map-output sizes (order preserved: range
    partitions stay contiguous).

    SKEWED partitions — stored bytes above skewedPartitionFactor x the
    median AND the advisory size — split into multiple independent
    sub-read units instead of coalescing (the reference's
    GpuCustomShuffleReaderExec skew reads, which slice one hot
    partition's map outputs across several reduce tasks; a join above
    streams probe batches, so each sub-read joins against the full
    build side exactly as Spark's skew-join sub-tasks do).

    Read units are partition ids, or (partition, block_lo, block_hi)
    map-block slices for split partitions."""
    import statistics
    from ..config import ADAPTIVE_SKEW_FACTOR
    from ..shuffle.manager import get_shuffle_manager
    sid = exchange.materialize(ctx)
    mgr = get_shuffle_manager()
    sizes = mgr.partition_sizes(sid)
    n = exchange.partitioning.num_partitions
    factor = float(ctx.conf.get(ADAPTIVE_SKEW_FACTOR))
    nonzero = sorted(b for b in sizes.values() if b) or [0]
    median = statistics.median(nonzero)
    skew_threshold = max(advisory_bytes, factor * median) \
        if factor > 0 else float("inf")

    groups: List[List] = []
    cur: List = []
    cur_bytes = 0
    skew_splits = 0
    for p in range(n):
        b = sizes.get(p, 0)
        if b > skew_threshold:
            blocks = mgr.block_sizes(sid, p)
            if len(blocks) > 1:
                if cur:
                    groups.append(cur)
                    cur, cur_bytes = [], 0
                nsub = 0
                lo = 0
                acc = 0
                for i, bb in enumerate(blocks):
                    if acc and acc + bb > advisory_bytes:
                        groups.append([(p, lo, i)])
                        nsub += 1
                        lo, acc = i, 0
                    acc += bb
                groups.append([(p, lo, len(blocks))])
                nsub += 1
                if nsub > 1:        # an actual split, not a solo group
                    skew_splits += 1
                continue
            # single stored block: nothing to slice — solo group below
        if cur and cur_bytes + b > advisory_bytes:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(p)
        cur_bytes += b
    if cur:
        groups.append(cur)
    ctx.metrics["adaptive_coalesced_groups"] = len(groups)
    if skew_splits:
        ctx.metrics["adaptive_skew_split_partitions"] = skew_splits
        # always-on plane: skew mitigation engaged (the reduce-side
        # counterpart of the mesh exchange's exchange_skew_split)
        ctx.tracer.instant("shuffle_skew_split", "shuffle",
                           partitions=skew_splits)
    return groups
