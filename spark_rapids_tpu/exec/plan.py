"""Physical plan nodes producing streams of device batches.

The reference's operator contract is `GpuExec.internalDoExecuteColumnar():
RDD[ColumnarBatch]` (GpuExec.scala:365) — each exec pulls an iterator of
batches from its child and pushes transformed batches downstream.  The TPU
analogue keeps the pull-iterator shape (it is what enables out-of-core
execution) but each operator's device work is one cached jit program per
row-bucket (exec/evaluator.py), not a sequence of library kernel launches.

Nodes here are *physical*: expressions arrive already bound to the child's
schema (plan/overrides.py does the tagging/conversion from a logical tree).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import contextmanager
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import pyarrow as pa

from .. import types as t
from ..config import TpuConf, DEFAULT_CONF
from ..columnar.device import (DeviceBatch, empty_device_batch,
                               fetch_result_batch, to_device)
from ..columnar.host import HostBatch, schema_to_struct
from ..obs.tracer import CollectSpan
from ..ops.batch_ops import concat_batches, shrink_to_rows
from ..ops.filter import compact_batch
from ..plan import expressions as E
from ..plan.aggregates import AggregateFunction
from .aggregate import HashAggregate
from .evaluator import evaluate_projection


_BUDGET_INIT_LOCK = threading.Lock()


class QueryDeadlineExceeded(RuntimeError):
    """The query ran past its per-query deadline (serving.deadlineMs /
    submit(deadline_ms=...)) and a cooperative cancellation checkpoint
    cancelled it.  Classified 'query': the ticket fails cleanly, every
    reservation its budget held is released (DeviceCensus shows zero
    residual), and the hosting worker keeps serving."""


class InjectedDeadlineExceeded(QueryDeadlineExceeded):
    """Chaos-harness form (`deadline:timeout:...`, runtime/faults.py):
    a synthetic deadline expiry at the Nth checkpoint."""


class QueryCancelled(QueryDeadlineExceeded):
    """Cooperative cancellation (ExecContext.cancel event set) — the
    graceful-drain / client-abandoned form of the same checkpoint
    contract."""


#: the executing thread's context, for cancellation checkpoints at
#: conf-less brackets (exchange rounds, spill sweeps) — registered for
#: the duration of a deadline-armed execute (cancel_scope)
_TLS_CTX = threading.local()


@contextmanager
def cancel_scope(ctx: "ExecContext"):
    """Register `ctx` as the executing thread's active context so
    conf-less brackets (parallel/exchange.py rounds, runtime/memory.py
    spill sweeps) can reach its cancellation checkpoint."""
    prev = getattr(_TLS_CTX, "ctx", None)
    _TLS_CTX.ctx = ctx
    try:
        yield ctx
    finally:
        _TLS_CTX.ctx = prev


def checkpoint_active(bracket: str = "") -> None:
    """Fire the active context's cancellation checkpoint (no-op when no
    deadline-armed query runs on this thread)."""
    ctx = getattr(_TLS_CTX, "ctx", None)
    if ctx is not None:
        ctx.checkpoint(bracket)


@dataclasses.dataclass
class ExecContext:
    """Per-query execution state threaded through the plan."""
    conf: TpuConf = DEFAULT_CONF
    metrics: dict = dataclasses.field(default_factory=dict)
    _budget: object = None
    # query-lifecycle span tracer (obs/tracer.py); NULL when tracing is
    # off so record calls cost one no-op method dispatch
    tracer: object = None
    # out-of-core escalation flag (exec/ooc.py): set by the query-level
    # OOM ladder / proactive election / serving admission; every
    # eligible hash join and aggregation then runs spill-partitioned
    ooc_force: bool = False
    # True for the context a whole-plan program is traced under
    # (exec/compiled.py _trace_context): row counts may be values of the
    # program, and no route that needs the host or the budget exists
    traced: bool = False
    # cooperative cancellation (serving deadlines / graceful drain):
    # absolute time.monotonic() deadline (0 = none) and an optional
    # threading.Event — checkpoint() raises past either
    deadline: float = 0.0
    cancel: object = None
    # the collect path's span seam (obs/tracer.CollectSpan): the query's
    # process-wide sequence number (the `query` stat of every `tpu.*`
    # annotation), the spans now open on the collecting thread, and
    # those that closed before the query's scope bound a tracer
    query_seq: int = 0
    open_spans: list = dataclasses.field(default_factory=list)
    early_spans: Optional[list] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if self.tracer is None:
            from ..obs.tracer import NULL_TRACER
            self.tracer = NULL_TRACER

    def arm_deadline(self, deadline_ms: float,
                     started: Optional[float] = None) -> None:
        """Arm the per-query deadline `deadline_ms` milliseconds after
        `started` (time.monotonic(); now when None)."""
        if deadline_ms and deadline_ms > 0:
            base = time.monotonic() if started is None else started
            self.deadline = base + float(deadline_ms) / 1e3

    def checkpoint(self, bracket: str = "") -> None:
        """Cooperative cancellation checkpoint — called at the seam /
        per-batch / OOC-pass / exchange-round / spill brackets.  Fires
        the `deadline` chaos site when armed, then raises
        QueryCancelled / QueryDeadlineExceeded when the cancel event is
        set or the deadline has passed.  The disabled path is two
        attribute checks."""
        from ..runtime.faults import get_injector
        inj = get_injector(self.conf)
        if inj.enabled:
            inj.fire("deadline", bracket=bracket or "?")
        if self.cancel is not None and self.cancel.is_set():
            self.bump("deadline_checkpoints_cancelled")
            raise QueryCancelled(
                f"query cancelled at the {bracket or '?'} checkpoint")
        if self.deadline and time.monotonic() > self.deadline:
            self.bump("deadline_checkpoints_cancelled")
            raise QueryDeadlineExceeded(
                f"query deadline exceeded at the {bracket or '?'} "
                f"checkpoint (serving.deadlineMs)")

    @property
    def budget(self):
        """Lazy per-query HBM budget (runtime/memory.py) — the
        RapidsBufferCatalog role for batches operators hold.  Guarded:
        a racing first touch from shuffle/scan worker threads must not
        create two disjoint budgets."""
        if self._budget is None:
            with _BUDGET_INIT_LOCK:
                if self._budget is None:
                    from ..runtime.memory import MemoryBudget
                    self._budget = MemoryBudget(self.conf)
        return self._budget

    def bump(self, name: str, n: int = 1):
        self.metrics[name] = self.metrics.get(name, 0) + n


def fetch_to_host(db: DeviceBatch, bound: Optional[int],
                  ctx: ExecContext) -> HostBatch:
    """The result fetch of one batch, the tail host sync every query
    pays (`tpu.fetch`, overhead.fetch_ms): the wait for the device and
    the copy (`tpu.fetch_wait`, one `device_get`: no `block_until_ready`
    splits them, which costs a second round trip, 1.4 ms of a 8.9 ms q6
    collect on a v5e: PERF.md, PR 25), and the Arrow build
    (`tpu.arrow_build`)."""
    from ..runtime.retry import retry_io
    with CollectSpan(ctx, "fetch", "overhead.fetch_ms", cat="transition"):
        hb = retry_io(ctx.conf, "d2h",
                      lambda: fetch_result_batch(db, bound, ctx))
    ctx.bump("d2h_rows", hb.num_rows)
    ctx.tracer.add_bytes("d2h_bytes", hb.rb.nbytes)
    return hb


class PlanNode:
    """Base physical operator. Children first, Spark-style."""

    def __init__(self, *children: "PlanNode"):
        self.children = list(children)

    @property
    def child(self) -> "PlanNode":
        return self.children[0]

    @property
    def output_schema(self) -> t.StructType:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        raise NotImplementedError

    def name(self) -> str:
        return type(self).__name__

    # -- static statistics (the CBO/AQE-statistics analogue) ---------------
    def keys_unique(self, names: Sequence[str]) -> bool:
        """True if no two live rows can carry equal NON-NULL values in the
        named column tuple.  Drives the sync-free probe-aligned join path
        (ops/join.py probe_aligned): a unique build side makes join output
        size a static fact.  Conservative default: unknown -> False.
        Sources of truth: exact scan statistics (HostScanExec), group-by
        structure, and uniqueness-preserving operators (filter/sort/limit
        keep a subset of rows; joins with unique build sides repeat each
        probe row at most once)."""
        return False

    def static_row_count(self) -> Optional[int]:
        """Exact output row count when statically known (global aggregates
        emit exactly one row), else None.  Lets cross joins against scalar
        subqueries run without a host sync."""
        return None

    def column_range(self, name: str) -> Optional[Tuple[int, int]]:
        """Exact (min, max) of a column's integer-lane values when known
        from scan statistics, else None.  Value-preserving operators
        delegate; values only ever narrow (filter/limit keep subsets,
        joins gather existing rows).  Lets multi-column join keys pack
        into ONE injective int64 lane (exec/join.py), unlocking the
        sync-free aligned/semi probe paths for composite keys."""
        return None

    def row_upper_bound(self) -> Optional[int]:
        """Static UPPER bound on output rows (a limit/top-N cap, a
        single-row global aggregate), else None.  Drives the result-fetch
        head size: over a high-latency low-bandwidth link the collect
        path ships `bound` rows instead of the padded bucket capacity
        (columnar.device.to_host fetch_rows)."""
        return self.static_row_count()

    def tree_string(self, indent: int = 0) -> str:
        # the node id, once assigned (exec/metrics.assign_node_ids), is
        # the name its device ops carry in a profiler trace
        nid = getattr(self, "_node_id", None)
        lines = ["  " * indent + self.describe()
                 + (f"  <{nid}>" if nid else "")]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.name()

    # -- helpers -----------------------------------------------------------
    def collect(self, ctx: Optional[ExecContext] = None) -> pa.Table:
        """Run the plan and bring results back to host (GpuBringBackToHost).

        Transfer policy per batch: fetch_result_batch ships the live-row
        prefix, not the padded capacity — static counts/bounds in one
        exactly-sized trip, unknown counts via a speculative
        count+head-prefix trip (columnar.device.fetch_result_batch)."""
        ctx = ctx or ExecContext()
        bound = self.row_upper_bound()
        hbs = []
        for db in self.execute(ctx):
            ctx.checkpoint("batch")
            if isinstance(db.num_rows, int) and db.num_rows == 0:
                continue
            hbs.append(fetch_to_host(db, bound, ctx))
        schema = None
        batches = []
        for hb in hbs:
            if hb.num_rows > 0:
                schema = schema or hb.rb.schema
                batches.append(hb.rb)
        if not batches:
            from ..columnar.host import struct_to_schema
            return pa.Table.from_batches([], struct_to_schema(self.output_schema))
        return pa.Table.from_batches(batches, schema)


class HostScanExec(PlanNode):
    """Leaf: uploads host Arrow batches to device (HostColumnarToGpu role)."""

    def __init__(self, batches: Sequence[HostBatch],
                 schema: Optional[t.StructType] = None,
                 source_table: Optional[pa.Table] = None):
        super().__init__()
        self.batches = list(batches)
        self._schema = schema or (self.batches[0].schema if self.batches
                                  else t.StructType([]))
        self._source_table = source_table
        # whole-plan compilation hooks (exec/compiled.py): uploaded-once
        # device batches, and tracer stand-ins installed during jit trace
        self._device_cache = None
        self._trace_batches = None
        self._host_nbytes: Optional[int] = None
        # columns approved for FOR-narrowed encoded upload by the
        # _negotiate_encoded legality pass (plan/overrides.py); None =
        # un-negotiated, lanes stay full width
        self.encoded_cols = None

    @classmethod
    def from_table(cls, table: pa.Table, max_rows: Optional[int] = None
                   ) -> "HostScanExec":
        rbs = table.to_batches(max_chunksize=max_rows) if max_rows \
            else table.combine_chunks().to_batches()
        return cls([HostBatch(rb) for rb in rbs],
                   schema_to_struct(table.schema), source_table=table)

    def host_nbytes(self) -> int:
        """Bytes of the host batches, what an upload of this scan moves:
        summed once (Arrow walks every buffer of every batch to answer),
        because a plan kept between collects counts them at each."""
        if self._host_nbytes is None:
            self._host_nbytes = sum(hb.rb.nbytes for hb in self.batches)
        return self._host_nbytes

    def keys_unique(self, names: Sequence[str]) -> bool:
        """Exact scan-time distinctness statistics (the role Delta/Iceberg
        table stats play for the reference's planner), cached per source
        table so repeated queries over the same data pay once."""
        tbl = self._source_table
        if tbl is None or not names or \
                any(n not in tbl.schema.names for n in names):
            return False
        return _table_keys_unique(tbl, tuple(names))

    def column_range(self, name: str) -> Optional[Tuple[int, int]]:
        tbl = self._source_table
        if tbl is None or name not in tbl.schema.names:
            return None
        return _table_column_range(tbl, name)

    @property
    def output_schema(self) -> t.StructType:
        return self._schema

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        if self._trace_batches is not None:   # under whole-plan tracing
            yield from self._trace_batches
            return
        from ..runtime.retry import retry_io
        for hb in self.batches:
            ctx.bump("scanned_rows", hb.num_rows)
            with ctx.tracer.span("upload", "transition"):
                db = retry_io(ctx.conf, "h2d",
                              lambda: to_device(hb, ctx.conf,
                                                encoded_cols=self.encoded_cols))
            ctx.bump("h2d_rows", hb.num_rows)
            ctx.tracer.add_bytes("h2d_bytes", hb.rb.nbytes)
            yield db

    def describe(self):
        return f"HostScanExec[{len(self.batches)} batches]"


_UNIQUE_STAT_CACHE: dict = {}


def _table_keys_unique(tbl: pa.Table, names: tuple) -> bool:
    """No two rows share equal fully-non-null values in `names` (rows with
    any null key are excluded — null join keys never match).

    Cached per (table identity, key tuple) via weakref: stats die with
    the table instead of pinning gigabytes of dropped inputs, and id()
    reuse after GC cannot alias a stale entry (the finalizer removes it)."""
    import weakref
    key = (id(tbl), names)
    hit = _UNIQUE_STAT_CACHE.get(key)
    if hit is not None and hit[0]() is tbl:
        return hit[1]
    import pyarrow.compute as pc
    sub = tbl.select(list(names)).drop_null()
    if sub.num_rows == 0:
        uniq = True
    elif len(names) == 1:
        uniq = pc.count_distinct(sub.column(0)).as_py() == sub.num_rows
    else:
        uniq = sub.group_by(list(names)).aggregate([]).num_rows \
            == sub.num_rows
    try:
        ref = weakref.ref(tbl, lambda _r, k=key:
                          _UNIQUE_STAT_CACHE.pop(k, None))
    except TypeError:        # weakref-unsupported object: don't cache
        return uniq
    if len(_UNIQUE_STAT_CACHE) > 1024:
        _UNIQUE_STAT_CACHE.clear()
    _UNIQUE_STAT_CACHE[key] = (ref, uniq)
    return uniq


_RANGE_STAT_CACHE: dict = {}


def _table_column_range(tbl: pa.Table, name: str):
    """Exact (min, max) of the column's canonical int64 lane (ints/dates
    as-is, bool as 0/1, narrow decimals as unscaled), or None for types
    without a single integer lane.  Weakref-cached like the uniqueness
    stats."""
    import weakref
    key = (id(tbl), name)
    hit = _RANGE_STAT_CACHE.get(key)
    if hit is not None and hit[0]() is tbl:
        return hit[1]
    import pyarrow.compute as pc
    col = tbl.column(name)
    typ = col.type
    rng = None
    try:
        if pa.types.is_integer(typ) or pa.types.is_date(typ) or \
                pa.types.is_boolean(typ):
            mm = pc.min_max(col)
            lo, hi = mm["min"].as_py(), mm["max"].as_py()
            if lo is not None:
                if pa.types.is_boolean(typ):
                    lo, hi = int(lo), int(hi)
                elif pa.types.is_date(typ):
                    import datetime as _dt
                    epoch = _dt.date(1970, 1, 1)
                    lo, hi = (lo - epoch).days, (hi - epoch).days
                rng = (int(lo), int(hi))
        elif pa.types.is_decimal(typ) and typ.precision <= 18:
            mm = pc.min_max(col)
            lo, hi = mm["min"].as_py(), mm["max"].as_py()
            if lo is not None:
                s = typ.scale
                rng = (int(lo.scaleb(s)), int(hi.scaleb(s)))
    except Exception:                            # noqa: BLE001
        rng = None
    try:
        ref = weakref.ref(tbl, lambda _r, k=key:
                          _RANGE_STAT_CACHE.pop(k, None))
    except TypeError:
        return rng
    if len(_RANGE_STAT_CACHE) > 4096:
        _RANGE_STAT_CACHE.clear()
    _RANGE_STAT_CACHE[key] = (ref, rng)
    return rng


class ProjectExec(PlanNode):
    """GpuProjectExec: one fused XLA program per row bucket
    (reference basicPhysicalOperators.scala:350)."""

    def __init__(self, exprs: Sequence[E.Expression], names: Sequence[str],
                 child: PlanNode):
        super().__init__(child)
        self.exprs = [e.bind(child.output_schema) for e in exprs]
        self.names = list(names)

    def keys_unique(self, names: Sequence[str]) -> bool:
        # renames/pass-throughs delegate to the child's columns; the
        # plain-reference rule is the shared join helper so the aligned-
        # path legality cannot drift between project and join
        from .join import key_ref_names
        mapped = []
        for n in names:
            if n not in self.names:
                return False
            ref = key_ref_names([self.exprs[self.names.index(n)]])
            if ref is None:
                return False
            mapped.extend(ref)
        return self.child.keys_unique(mapped)

    def static_row_count(self):
        return self.child.static_row_count()   # projection keeps rows

    def row_upper_bound(self):
        return self.child.row_upper_bound()

    def column_range(self, name):
        from .join import key_ref_names
        if name not in self.names:
            return None
        ref = key_ref_names([self.exprs[self.names.index(name)]])
        return None if ref is None else self.child.column_range(ref[0])

    @property
    def output_schema(self) -> t.StructType:
        return t.StructType([t.StructField(n, e.dtype)
                             for n, e in zip(self.names, self.exprs)])

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from .evaluator import project_batch
        for db in self.child.execute(ctx):
            # thin-aware: plain refs to deferred columns pass through as
            # lanes (project_batch); computed exprs materialize their refs
            yield project_batch(self.exprs, self.names, db, ctx.conf)

    def describe(self):
        return f"ProjectExec[{', '.join(self.names)}]"


class FilterExec(PlanNode):
    """GpuFilterExec: predicate eval fused into one program, then stable
    mask compaction (ops/filter.py) instead of cuDF apply_boolean_mask."""

    def __init__(self, condition: E.Expression, child: PlanNode):
        super().__init__(child)
        self.condition = condition.bind(child.output_schema)
        # set where the filter's output reaches a whole-plan seam through
        # projections and filters only (exec/compiled.py
        # _hand_masks_to_seam): the seam resolves a selection vector at
        # the bucket of the live rows, so inside the traced program the
        # mask is handed over and nothing is compacted at the input's
        # capacity
        self.seam_lazy = False

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # subset of rows

    def column_range(self, name):
        return self.child.column_range(name)   # subset of values

    def row_upper_bound(self):
        return self.child.row_upper_bound()    # filter only shrinks

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from .evaluator import compute_predicate
        for db in self.child.execute(ctx):
            if db.thin is not None:
                # thin input: referenced deferred columns materialize
                # early (just those); the mask then COMPOSES into the
                # selection vector instead of compacting, so the lanes
                # stay live to the pipeline sink
                from ..columnar.lanes import materialize_refs
                db = materialize_refs(db, [self.condition], ctx.conf)
                if db.thin is not None and db.sel is not None and \
                        any(c.offsets is not None for c in db.columns):
                    # ragged+sel forces an internal prefix compaction in
                    # compute_predicate whose row order would desync
                    # from the lanes — resolve them first
                    from ..ops.batch_ops import ensure_prefix
                    db = ensure_prefix(db, ctx.conf)
                keep = compute_predicate(self.condition, db, ctx.conf)
                if db.thin is not None:
                    yield DeviceBatch(list(db.columns),
                                      jnp.sum(keep, dtype=jnp.int32),
                                      db.names, db.origin_file, sel=keep,
                                      thin=db.thin)
                    continue
            else:
                keep = compute_predicate(self.condition, db, ctx.conf)
                if self.seam_lazy and ctx.traced and not any(
                        c.offsets is not None for c in db.columns):
                    yield DeviceBatch(list(db.columns),
                                      jnp.sum(keep, dtype=jnp.int32),
                                      db.names, db.origin_file, sel=keep)
                    continue
            # lazy row count: downstream device ops keep running sync-free
            yield compact_batch(db, keep, ctx.conf)

    def describe(self):
        return f"FilterExec[{self.condition!r}]"


def sample_hash_u32(idx_u32, seed: int):
    """Murmur3 finalizer over the global live-row index mixed with the
    seed.  Pure uint32 lattice ops, so numpy (CPU path) and jnp (device
    path) produce bit-identical hashes — both engines keep exactly the
    same rows for a given seed."""
    h = idx_u32 ^ ((seed * 0x9E3779B9) & 0xFFFFFFFF)
    h = h ^ (h >> 16)
    h = h * 0x85EBCA6B
    h = h ^ (h >> 13)
    h = h * 0xC2B2AE35
    h = h ^ (h >> 16)
    return h


def sample_threshold(fraction: float) -> int:
    """uint32 keep-threshold for a Bernoulli fraction (callers special-
    case fraction >= 1.0: everything is kept, no compare)."""
    return min(int(round(fraction * 2.0 ** 32)), 2 ** 32 - 1)


class SampleExec(PlanNode):
    """GpuSampleExec (basicPhysicalOperators.scala:838): Bernoulli
    row sampling without replacement.  The keep decision is a counter-
    based hash of the row's global live position — no RNG state, so the
    result is deterministic per seed, independent of batch boundaries,
    and identical to the CPU fallback's (CpuSampleExec shares
    sample_hash_u32)."""

    def __init__(self, fraction: float, seed: int, child: PlanNode):
        super().__init__(child)
        self.fraction = float(fraction)
        self.seed = int(seed)

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # subset of rows

    def column_range(self, name):
        return self.child.column_range(name)   # subset of values

    def row_upper_bound(self):
        return self.child.row_upper_bound()    # sampling only shrinks

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..ops.filter import compact_batch
        from ..ops.kernels import live_mask
        threshold = sample_threshold(self.fraction)
        offset = jnp.int64(0)
        for db in self.child.execute(ctx):
            if isinstance(db.num_rows, int) and db.num_rows == 0:
                continue
            if self.fraction >= 1.0:
                yield db
                offset = offset + jnp.asarray(db.num_rows, jnp.int64)
                continue
            cap = db.capacity
            if db.sel is not None:
                # lazy selection: live rows are sel-True, their global
                # position is the running count of earlier True lanes
                live = db.sel
                pos = jnp.cumsum(live.astype(jnp.int64)) - 1
            else:
                live = live_mask(cap, jnp.asarray(db.num_rows))
                pos = jnp.arange(cap, dtype=jnp.int64)
            idx32 = (offset + pos).astype(jnp.uint32)
            keep = live & (sample_hash_u32(idx32, self.seed)
                           < jnp.uint32(threshold))
            offset = offset + jnp.asarray(db.num_rows, jnp.int64)
            yield compact_batch(db, keep, ctx.conf)

    def describe(self):
        return f"SampleExec[{self.fraction}, seed={self.seed}]"


class HashAggregateExec(PlanNode):
    """GpuHashAggregateExec (GpuAggregateExec.scala:1711): streaming partial
    aggregation per batch, concat+merge regroup, final projection."""

    def __init__(self, key_exprs: Sequence[E.Expression],
                 key_names: Sequence[str],
                 aggs: Sequence[Tuple[AggregateFunction, str]],
                 child: PlanNode):
        super().__init__(child)
        schema = child.output_schema
        self.key_exprs = [e.bind(schema) for e in key_exprs]
        self.key_names = list(key_names)
        self.aggs = [(fn.bind(schema), name) for fn, name in aggs]
        # set where this aggregate's output reaches a whole-plan seam
        # through projections and filters only (exec/compiled.py
        # _hand_masks_to_seam): inside the traced program its groups may
        # then stay where their runs ended, under a selection vector
        self.seam_lazy = False

    @property
    def output_schema(self) -> t.StructType:
        fields = []
        for n, e in zip(self.key_names, self.key_exprs):
            fields.append(t.StructField(n, e.dtype))
        for fn, n in self.aggs:
            fields.append(t.StructField(n, fn.dtype))
        return t.StructType(fields)

    def keys_unique(self, names: Sequence[str]) -> bool:
        # the group-key tuple is unique by construction; any superset of a
        # unique tuple is unique.  A global aggregate has exactly one row.
        if not self.key_exprs:
            return True
        return set(self.key_names) <= set(names)

    def column_range(self, name):
        from .join import key_ref_names
        if name in self.key_names:
            # group-key columns pass values through unchanged
            e = self.key_exprs[self.key_names.index(name)]
            ref = key_ref_names([e])
            return None if ref is None else self.child.column_range(ref[0])
        # Min/Max aggregate outputs select existing values -> the child
        # column's range bounds them
        from ..plan.aggregates import Max, Min
        for fn, out_name in self.aggs:
            if out_name == name and isinstance(fn, (Min, Max)):
                ref = key_ref_names([fn.child])
                if ref is not None:
                    return self.child.column_range(ref[0])
        return None

    def static_row_count(self) -> Optional[int]:
        return 1 if not self.key_exprs else None

    def row_upper_bound(self):
        if not self.key_exprs:
            return 1
        # bounded key domains bound the group count (dense-domain shapes:
        # every key has exact range stats)
        ranges = self._key_ranges()
        if any(r is None for r in ranges):
            return None
        prod = 1
        for lo, hi in ranges:
            prod *= (hi - lo + 2)              # +1 span, +1 null slot
            if prod > (1 << 22):
                return None
        return prod

    def _strip_filters(self, can_fuse: bool):
        """Peel the chain of FilterExec children this aggregate can fuse;
        returns (batch source node, conditions outermost-last)."""
        source: PlanNode = self.child
        conds: List[E.Expression] = []
        if can_fuse:
            while isinstance(source, FilterExec):
                conds.append(source.condition)
                source = source.child
            conds.reverse()
        return source, conds

    def _key_ranges(self):
        """Exact (lo, hi) per group key from plan statistics (plain
        column refs only) — unlocks packed-lane group-by sorts."""
        from .join import key_ref_names
        out = []
        for e in self.key_exprs:
            ref = key_ref_names([e])
            out.append(None if ref is None
                       else self.child.column_range(ref[0]))
        return out

    def _input_ranges(self, agg) -> dict:
        """id(input expr) -> exact (lo, hi) for plain column refs with
        scan statistics — feeds the int32 gather narrowing."""
        from .join import key_ref_names
        out = {}
        for e in agg.input_exprs:
            ref = key_ref_names([e])
            if ref is not None:
                rng = self.child.column_range(ref[0])
                if rng is not None:
                    out[id(e)] = rng
        return out

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..config import AGG_FALLBACK_PARTITIONS
        from . import ooc as O
        from .ooc_agg import OutOfCoreAggregator
        agg = HashAggregate(self.key_exprs, self.key_names, self.aggs,
                            ctx.conf, key_ranges=self._key_ranges(),
                            bump=ctx.bump)
        agg._input_ranges_by_expr = self._input_ranges(agg)
        # Fuse upstream filters into the map side for EVERY aggregation:
        # the predicates become the groupby's live-mask, so filter +
        # projections + update aggregation run with no mask compaction
        # (TPU row gathers — one argsort + per-column gathers — cost far
        # more than masked reduction lanes; ~3s at an 8M bucket).  Keys
        # the single-program fuse can't take (host dictionary work) still
        # skip the compact: the mask evaluates as its own program.
        source, conds = self._strip_filters(True)
        policy = O.ooc_policy(ctx)
        # Inside a whole-plan program a group count is a value of the
        # program: a partial aggregate that sorts its rows comes out at
        # its input's capacity however few groups it found, and the
        # merge sorts all of it again (merged as the batches come, as
        # below, at twice the capacity every time: 4M rows x 2^14 after
        # 15 batches).  There the batches' keys and inputs are stacked
        # as they come and aggregated ONCE (HashAggregate.update_stacked);
        # decided on the first batch.
        stack = ctx.traced and bool(self.key_exprs)
        stacked: List[DeviceBatch] = []
        partials: List[DeviceBatch] = []
        partial_bytes = 0
        oocagg: "OutOfCoreAggregator | None" = None
        seen = False

        def start_ooc(mode: str) -> OutOfCoreAggregator:
            k = max(ctx.conf.get(AGG_FALLBACK_PARTITIONS),
                    O.partition_count(partial_bytes, policy))
            ctx.bump("agg_repartition_fallbacks")
            O.record_election(ctx, "agg", mode)
            return OutOfCoreAggregator(agg, len(self.key_names), ctx,
                                       policy, k)

        for db in source.execute(ctx):
            if isinstance(db.num_rows, int) and db.num_rows == 0:
                continue
            seen = True
            if db.thin is not None:
                # aggregation is a pipeline SINK: deferred columns the
                # keys/inputs/fused conds reference materialize here with
                # one composed gather per lane source; unreferenced ones
                # stay zero-capacity placeholders no program reads
                from ..columnar.lanes import materialize_refs
                db = materialize_refs(
                    db, list(conds) + list(self.key_exprs) +
                    list(agg.input_exprs), ctx.conf)
            if stack:
                from .evaluator import compute_predicate
                live = db.row_mask()
                for c in conds:
                    live = live & compute_predicate(c, db, ctx.conf)
                projected = agg.project_inputs(db, live)
                stack = bool(stacked) or \
                    agg.partials_keep_capacity(projected)
                if stack:
                    stacked.append(projected)
                    ctx.bump("agg.partial_batches")
                    continue
            if agg.can_fuse_filter(db):
                p = agg.partial_fused(db, conds)
            else:
                live = None
                if conds:
                    from .evaluator import compute_predicate
                    live = db.row_mask()
                    for c in conds:
                        live = live & compute_predicate(c, db, ctx.conf)
                p = agg.partial(db, live)
            # one input batch more whose partial result the final
            # aggregate merges
            ctx.bump("agg.partial_batches")
            if oocagg is not None:
                oocagg.add(p)
                continue
            partials.append(p)
            partial_bytes += O.batch_bytes(p)
            # OOC byte gate / forced context: the accumulated partial
            # working set exceeds the resident window (or the query is
            # escalated/forced out-of-core) — spill-partition by key NOW
            # instead of betting the merge below still reduces; key-
            # disjoint buckets make the union exact (exec/ooc_agg.py)
            if self.key_exprs and \
                    (policy.force or policy.bytes_trip(partial_bytes)):
                oocagg = start_ooc(
                    "forced" if policy.force else "bytes")
                for q in partials:
                    oocagg.add(q)
                partials = []
                continue
            # Bound the pending set: merge when the partials would overflow
            # one target batch (the reference's tryMergeAggregatedBatches).
            # Capacity is a host fact, so the gate never syncs; it bounds
            # rows from above (merging slightly early is harmless).
            if len(partials) > 1 and \
                    sum(p.capacity for p in partials) > ctx.conf.batch_size_rows:
                merged = agg.merge(partials)
                if self.key_exprs and \
                        isinstance(merged.num_rows, int) and \
                        merged.num_rows > ctx.conf.batch_size_rows:
                    # High-cardinality fallback (GpuAggregateExec.scala:711
                    # repartition-based path): merging no longer reduces, so
                    # hash-split the merged partials into independently
                    # mergeable buckets held as spillables.
                    oocagg = start_ooc("rows")
                    oocagg.add(merged)
                    partials = []
                else:
                    partials = [merged]
                    partial_bytes = O.batch_bytes(merged)
        if oocagg is not None:
            # results() owns the cleanup sweep (idempotent closes), so a
            # LIMIT above this aggregation leaks no spill files
            yield from oocagg.results()
            return
        if stacked:
            yield agg.final(agg.update_stacked(stacked, self.seam_lazy))
            return
        if not seen:
            if self.key_exprs:
                return  # grouped agg over empty input -> no rows
            # global agg over empty input still emits one row (e.g. COUNT=0)
            empty = empty_device_batch(self.child.output_schema, ctx.conf)
            partials = [agg.partial(empty)]
        merged = agg.merge(partials) if len(partials) > 1 else partials[0]
        yield agg.final(merged)

    def collect_device(self, ctx: Optional[ExecContext] = None):
        """Dispatch a global (no-key) aggregation fully async: returns
        (outs, finalize) where `outs` is the list of (scalar, valid) device
        buffers and `finalize(fetched)` turns their host values into the
        result table.  No host sync happens inside this call — callers can
        pipeline many queries and batch all fetches into one D2H round trip
        (the concurrent-GpuSemaphore-tasks analogue for a chip behind a
        high-latency link)."""
        if self.key_exprs:
            raise ValueError("collect_device is for global aggregations")
        ctx = ctx or ExecContext()
        agg = HashAggregate(self.key_exprs, self.key_names, self.aggs,
                            ctx.conf, bump=ctx.bump)
        source, conds = self._strip_filters(True)
        raw = []
        for db in source.execute(ctx):
            if isinstance(db.num_rows, int) and db.num_rows == 0:
                continue
            if db.thin is not None:
                # same sink rule as execute(): deferred columns the
                # fused conds/inputs reference materialize here
                from ..columnar.lanes import materialize_refs
                db = materialize_refs(db, list(conds) +
                                      list(agg.input_exprs), ctx.conf)
            raw.append(agg.partial_fused(db, conds, raw=True))
            ctx.bump("agg.partial_batches")
        if not raw:
            empty = empty_device_batch(source.output_schema, ctx.conf)
            raw.append(agg.partial_fused(empty, conds, raw=True))
        return agg.merge_raw(raw), agg.finalize_fetched

    def collect(self, ctx: Optional[ExecContext] = None) -> pa.Table:
        """Global (no-key) aggregations finish on host from raw buffer
        scalars: N fused partial dispatches + at most one merge dispatch +
        ONE D2H fetch — no 1-row device batches, no device final
        projection."""
        if self.key_exprs:
            return super().collect(ctx)
        import jax
        outs, finalize = self.collect_device(ctx)
        return finalize(jax.device_get(list(outs)))

    def describe(self):
        return (f"HashAggregateExec[keys={self.key_names}, "
                f"aggs={[n for _, n in self.aggs]}]")


_AGG_PART_CACHE = {}


def _agg_partition_ids(pb: DeviceBatch, nkeys: int, num_buckets: int,
                       salt: int = 0):
    """Deterministic bucket id per row from the leading `nkeys` columns.

    Unlike shuffle HashPartitioning this need not be Spark-exact — it only
    must map equal keys to equal buckets across batches: string columns
    hash their dictionary VALUES through a host crc32 table (per-batch
    codes are not stable), other lanes fold to uint32.  `salt` decorrelates
    recursive re-scatters (same hash would map a bucket onto itself).
    crc32 tables pad to power-of-two sizes so per-batch dictionary growth
    does not churn the jit cache."""
    import jax

    tables = {}
    for i, c in enumerate(pb.columns[:nkeys]):
        if c.dictionary is not None:
            tables[i] = _dict_crc_table(c.dictionary)
    dtypes = tuple(c.dtype for c in pb.columns[:nkeys])
    sig = ("aggpart", pb.capacity, num_buckets, nkeys, salt,
           tuple(d.simple_string for d in dtypes),
           tuple((str(c.data.dtype), c.data_hi is not None,
                  i in tables and int(tables[i].shape[0]))
                 for i, c in enumerate(pb.columns[:nkeys])))
    fn = _AGG_PART_CACHE.get(sig)
    if fn is None:
        capacity = pb.capacity

        salt_c = jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)

        def run(datas, valids, his, tabs):
            h = jnp.full((capacity,), 17, jnp.uint32)
            for i in range(nkeys):
                d = datas[i]
                if i in tabs:
                    tab = tabs[i]
                    lane = tab[jnp.clip(d, 0, tab.shape[0] - 1)]
                elif isinstance(dtypes[i], (t.DoubleType, t.FloatType)):
                    # DOUBLE has two storage lanes (int64 bit patterns /
                    # native f64); hash a lane-independent value derivation
                    # so spilled-and-reuploaded batches bucket identically
                    from ..ops.kernels import compute_view
                    f = compute_view(d, dtypes[i]).astype(jnp.float64)
                    isnan = jnp.isnan(f)
                    isinf = jnp.isinf(f)
                    safe = jnp.where(isnan | isinf, 0.0, f)
                    ip = jnp.floor(safe)
                    fr = ((safe - ip) * jnp.float64(1 << 30)) \
                        .astype(jnp.uint32)
                    ii = jnp.clip(ip, -2.0**62, 2.0**62).astype(jnp.int64)
                    lane = ((ii ^ (ii >> 32)).astype(jnp.uint32)
                            * jnp.uint32(31)) ^ fr
                    lane = jnp.where(isnan, jnp.uint32(0xA5A5A5A5), lane)
                    lane = jnp.where(isinf & (f > 0),
                                     jnp.uint32(0x77777777), lane)
                    lane = jnp.where(isinf & (f < 0),
                                     jnp.uint32(0x33333333), lane)
                else:
                    # equal values -> equal lanes is all bucketing needs
                    x = d.astype(jnp.int64)
                    lane = (x ^ (x >> 32)).astype(jnp.uint32)
                lane = jnp.where(valids[i], lane, jnp.uint32(0x9E3779B9))
                # XOR-salt each lane: an additive salt would only rotate
                # bucket labels, leaving re-scatter groupings unchanged
                h = h * jnp.uint32(2654435761) + (lane ^ salt_c)
                if his[i] is not None:
                    hx = his[i]
                    h = h * jnp.uint32(31) + \
                        ((hx ^ (hx >> 32)).astype(jnp.uint32))
            # avalanche so the low bits (the modulo) see every input bit
            h = h ^ (h >> 16)
            h = h * jnp.uint32(0x7FEB352D)
            h = h ^ (h >> 15)
            return (h % jnp.uint32(num_buckets)).astype(jnp.int32)

        fn = jax.jit(run)
        if len(_AGG_PART_CACHE) > 512:
            _AGG_PART_CACHE.clear()
        _AGG_PART_CACHE[sig] = fn
    return fn(tuple(c.data for c in pb.columns[:nkeys]),
              tuple(c.validity for c in pb.columns[:nkeys]),
              tuple(c.data_hi for c in pb.columns[:nkeys]), tables)


_CRC_TABLE_CACHE = {}


def _dict_crc_table(dictionary):
    """crc32-of-value table for a string dictionary, padded to a power of
    two (stable jit signatures) and cached by dictionary identity (the
    same pa.Array flows through every batch sharing the dictionary)."""
    import zlib
    import numpy as np
    key = id(dictionary)
    hit = _CRC_TABLE_CACHE.get(key)
    if hit is not None and hit[0] is dictionary:
        return hit[1]
    ent = [zlib.crc32(s.encode("utf-8")) if s is not None else 0
           for s in dictionary.to_pylist()] or [0]
    padded = 1 << (len(ent) - 1).bit_length()
    ent += [0] * (padded - len(ent))
    tab = jnp.asarray(np.asarray(ent, np.uint32))
    import jax
    if isinstance(tab, jax.core.Tracer):
        return tab               # whole-plan tracing: never cache tracers
    if len(_CRC_TABLE_CACHE) > 512:
        _CRC_TABLE_CACHE.clear()
    # pin the dictionary so its id stays valid while cached
    _CRC_TABLE_CACHE[key] = (dictionary, tab)
    return tab


class LocalLimitExec(PlanNode):
    """Per-stream limit (GpuLocalLimitExec, limit.scala)."""

    def __init__(self, limit: int, child: PlanNode):
        super().__init__(child)
        self.limit = limit

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # prefix of rows

    def column_range(self, name):
        return self.child.column_range(name)

    def row_upper_bound(self):
        child = self.child.row_upper_bound()
        return self.limit if child is None else min(self.limit, child)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        # Never peek ahead: pulling a second batch before emitting would
        # compute an entire extra upstream batch even when the first one
        # already satisfies the limit.  A lazy count costs one scalar
        # sync; the payoff is the capacity slice (shrink_to_capacity), so
        # a tiny LIMIT never ships a full-capacity batch to host.
        from ..ops.batch_ops import ensure_prefix, shrink_to_capacity
        remaining = self.limit
        for db in self.child.execute(ctx):
            if remaining <= 0:
                return
            db = ensure_prefix(db, ctx.conf)   # limit cuts a PREFIX
            n = int(db.num_rows)
            if n == 0:
                continue
            if n < remaining:
                remaining -= n
                yield db
            else:
                yield shrink_to_capacity(_truncate(db, remaining),
                                         remaining, ctx.conf)
                return

    def describe(self):
        return f"{self.name()}[{self.limit}]"


class GlobalLimitExec(LocalLimitExec):
    """Same device semantics as local limit; the global cut happens after
    the single-partition exchange inserted by the planner."""


def _truncate(db: DeviceBatch, rows: int) -> DeviceBatch:
    from ..columnar.device import DeviceColumn
    live = jnp.arange(db.capacity, dtype=jnp.int32) < jnp.int32(rows)
    cols = [DeviceColumn(c.data, c.validity & live, c.dtype, c.dictionary,
                         c.data_hi) for c in db.columns]
    return DeviceBatch(cols, rows, db.names, db.origin_file)


class UnionExec(PlanNode):
    """GpuUnionExec: concatenation of children streams (schema-aligned)."""

    def __init__(self, *children: PlanNode):
        super().__init__(*children)

    @property
    def output_schema(self) -> t.StructType:
        return self.children[0].output_schema

    def column_range(self, name):
        rngs = [c.column_range(name) for c in self.children]
        if any(r is None for r in rngs):
            return None
        return (min(r[0] for r in rngs), max(r[1] for r in rngs))

    def row_upper_bound(self):
        bounds = [c.row_upper_bound() for c in self.children]
        if any(b is None for b in bounds):
            return None
        return sum(bounds)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        names = list(self.output_schema.names)
        for c in self.children:
            for db in c.execute(ctx):
                yield DeviceBatch(db.columns, db.num_rows, names)


class CoalesceBatchesExec(PlanNode):
    """GpuCoalesceBatches (GpuCoalesceBatches.scala:697): concatenate small
    batches until the target row goal so downstream programs run on full
    buckets."""

    def __init__(self, child: PlanNode, target_rows: Optional[int] = None,
                 require_single: bool = False):
        super().__init__(child)
        self.target_rows = target_rows
        self.require_single = require_single

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # same rows, repacked

    def static_row_count(self):
        return self.child.static_row_count()

    def column_range(self, name):
        return self.child.column_range(name)

    def row_upper_bound(self):
        return self.child.row_upper_bound()

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        target = self.target_rows or ctx.conf.batch_size_rows
        pending: List[DeviceBatch] = []
        rows = 0
        for db in self.child.execute(ctx):
            n = int(db.num_rows)   # coalesce sizes batches -> sync point
            if n == 0:
                continue
            if not self.require_single and rows and rows + n > target:
                yield concat_batches(pending, ctx.conf)
                pending, rows = [], 0
            pending.append(db)
            rows += n
        if pending:
            yield concat_batches(pending, ctx.conf)

    def describe(self):
        goal = "RequireSingleBatch" if self.require_single \
            else f"target={self.target_rows or 'conf'}"
        return f"CoalesceBatchesExec[{goal}]"


class SortExec(PlanNode):
    """GpuSortExec (GpuSortExec.scala:86): sorts by SortOrder keys.

    global_sort runs through the out-of-core sorter (exec/ooc_sort.py):
    under an HBM budget the input accumulates as spillable sorted runs
    merged by capstone-bounded concat+resort passes (the
    GpuOutOfCoreSortIterator role); with no budget it degenerates to one
    concat+lexsort.  Local sort orders each batch independently (enough
    for sort-merge structures and windows)."""

    def __init__(self, keys, child: PlanNode, global_sort: bool = True):
        from ..ops.sort import SortKey
        super().__init__(child)
        self.keys = [k if isinstance(k, SortKey) else SortKey(*k)
                     for k in keys]
        self.global_sort = global_sort

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # permutation of rows

    def static_row_count(self):
        return self.child.static_row_count()

    def row_upper_bound(self):
        return self.child.row_upper_bound()

    def column_range(self, name):
        return self.child.column_range(name)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..ops.sort import sort_batch
        if not self.global_sort:
            for db in self.child.execute(ctx):
                yield sort_batch(db, self.keys, ctx.conf)
            return
        # Single-batch input sorts directly with zero host syncs (the
        # dominant case once upstream operators keep lazy row counts);
        # the out-of-core path engages from the second batch on.
        it = self.child.execute(ctx)
        first = next(it, None)
        if first is None:
            return
        second = next(it, None)
        if second is None:
            yield sort_batch(first, self.keys, ctx.conf)
            return
        from .ooc_sort import OutOfCoreSorter
        sorter = OutOfCoreSorter(self.keys, ctx)
        sorter.add(first)
        sorter.add(second)
        for db in it:
            sorter.add(db)
        yield from sorter.results()

    def describe(self):
        scope = "global" if self.global_sort else "local"
        return f"SortExec[{scope}, {self.keys}]"


class TopNExec(PlanNode):
    """GpuTopN (limit.scala): sort + limit without materializing the full
    sorted output — each batch keeps only its top-N prefix, pending rows
    are re-sorted together and cut once more at the end."""

    def __init__(self, limit: int, keys, child: PlanNode):
        from ..ops.sort import SortKey
        super().__init__(child)
        self.limit = limit
        self.keys = [k if isinstance(k, SortKey) else SortKey(*k)
                     for k in keys]

    @property
    def output_schema(self) -> t.StructType:
        return self.child.output_schema

    def keys_unique(self, names):
        return self.child.keys_unique(names)   # prefix of a permutation

    def column_range(self, name):
        return self.child.column_range(name)

    def row_upper_bound(self):
        child = self.child.row_upper_bound()
        return self.limit if child is None else min(self.limit, child)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..ops.sort import sort_batch
        pending: Optional[DeviceBatch] = None
        from ..ops.batch_ops import shrink_to_capacity
        for db in self.child.execute(ctx):
            if isinstance(db.num_rows, int) and db.num_rows == 0:
                continue
            batch = db if pending is None \
                else concat_batches([pending, db], ctx.conf)
            s = sort_batch(batch, self.keys, ctx.conf)
            # lazy cut + static capacity shrink: live rows <= limit by
            # construction, so the bucket slice needs no row-count sync
            nl = jnp.minimum(jnp.int32(self.limit), jnp.int32(s.num_rows))
            pending = shrink_to_capacity(_truncate(s, nl), self.limit,
                                         ctx.conf)
        if pending is not None:
            yield pending

    def describe(self):
        return f"TopNExec[{self.limit}, {self.keys}]"


class RangeExec(PlanNode):
    """GpuRangeExec (basicPhysicalOperators.scala:838): generates id ranges
    directly on device with iota."""

    def __init__(self, start: int, end: int, step: int = 1,
                 name: str = "id", batch_rows: Optional[int] = None):
        super().__init__()
        assert step != 0
        self.start, self.end, self.step = start, end, step
        self.col_name = name
        self.batch_rows = batch_rows

    @property
    def output_schema(self) -> t.StructType:
        return t.StructType([t.StructField(self.col_name, t.LongType())])

    def keys_unique(self, names):
        return list(names) == [self.col_name]   # iota never repeats

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from ..columnar.device import DeviceColumn, bucket_capacity
        total = max(0, -(-(self.end - self.start) // self.step))
        chunk = self.batch_rows or ctx.conf.batch_size_rows
        emitted = 0
        while emitted < total:
            n = min(chunk, total - emitted)
            cap = bucket_capacity(n, ctx.conf)
            base = self.start + emitted * self.step
            data = jnp.int64(base) + jnp.arange(cap, dtype=jnp.int64) * self.step
            live = jnp.arange(cap, dtype=jnp.int32) < jnp.int32(n)
            yield DeviceBatch(
                [DeviceColumn(data, live, t.LongType())], n, [self.col_name])
            emitted += n
        if total == 0:
            return

    def describe(self):
        return f"RangeExec[{self.start},{self.end},{self.step}]"


class ExpandExec(PlanNode):
    """GpuExpandExec (GpuExpandExec.scala:70): N projections per input batch
    (rollup/cube/grouping sets lowering)."""

    def __init__(self, projections: Sequence[Sequence[E.Expression]],
                 names: Sequence[str], child: PlanNode):
        super().__init__(child)
        self.projections = [[e.bind(child.output_schema) for e in p]
                            for p in projections]
        self.names = list(names)

    @property
    def output_schema(self) -> t.StructType:
        return t.StructType([t.StructField(n, e.dtype) for n, e in
                             zip(self.names, self.projections[0])])

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for db in self.child.execute(ctx):
            for proj in self.projections:
                out = evaluate_projection(proj, self.names, db, ctx.conf)
                if ctx.traced:
                    # the rows of capacity a program carries for the
                    # grouping sets: the input's, once a projection
                    ctx.bump("expand.capacity_rows", out.capacity)
                yield out
