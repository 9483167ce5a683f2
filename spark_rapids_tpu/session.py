"""User-facing session + DataFrame API over the overrides engine.

The reference is driven through a SparkSession with the plugin injected
(Plugin.scala:426 driver plugin, SQLExecPlugin.scala:27); queries are
ordinary DataFrames and the plugin rewrites their physical plans.  This
engine owns the whole stack, so the session plays both roles: it holds the
TpuConf (re-read per query, GpuOverrides.scala:4571) and hands out
DataFrames whose `collect()` runs wrap->tag->convert->execute.

    s = TpuSession({"spark.rapids.tpu.sql.explain": "NOT_ON_TPU"})
    df = s.from_arrow(table).filter(col("x") > lit(1)).group_by("k") \
         .agg((Sum(col("x")), "sx"))
    df.collect()      # pyarrow Table
    df.explain()      # placement decisions with fallback reasons
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

import pyarrow as pa

from . import types as t
from .config import TpuConf
from .exec.plan import ExecContext
from .obs.tracer import CollectSpan
from .plan import expressions as E
from .plan import logical as L
from .plan.aggregates import AggregateFunction
from .plan.overrides import PhysicalQuery, apply_overrides


class TpuSession:
    def __init__(self, conf: Optional[Dict] = None):
        self.conf = conf if isinstance(conf, TpuConf) else TpuConf(conf)
        self._last_ctx: Optional[ExecContext] = None
        self._conf_lock = threading.Lock()
        self._serving = None
        # always-on metrics plane: apply the enabled flag / recorder
        # capacity and start any conf'd exporters (heartbeat JSONL,
        # Prometheus endpoint) as soon as a session exists
        from .obs.export import configure_plane
        configure_plane(self.conf)
        # persistent compile cache: JAX_COMPILATION_CACHE_DIR, else
        # spark.rapids.tpu.compile.cacheDir, else <checkout>/.jax_cache
        # (touches jax.config only — no backend, no device)
        from .exec.compiled import configure_persistent_cache
        configure_persistent_cache(self.conf)
        # persistent performance-history store (structure-keyed measured
        # cost, spark.rapids.tpu.history.dir) — warms the on-disk load
        # so the first query/estimate pays nothing; no-op when unset
        from .obs.history import configure_history
        configure_history(self.conf)

    def set_conf(self, key: str, value) -> None:
        """Atomic conf swap: TpuConf instances are immutable, so a
        query that snapshot the old instance (every query snapshots at
        plan/admission time — DataFrame.physical, ServingRuntime.submit)
        keeps its behavior for its whole flight; only queries admitted
        AFTER this call see the new value.  The lock serializes
        concurrent set_conf calls so neither's key is lost."""
        with self._conf_lock:
            raw = dict(self.conf._raw)
            raw[key] = value
            self.conf = TpuConf(raw)
            new_conf = self.conf
        from .obs.export import configure_plane
        configure_plane(new_conf)
        from .exec.compiled import configure_persistent_cache
        configure_persistent_cache(new_conf)
        from .obs.history import configure_history
        configure_history(new_conf)

    def serving(self, conf_overrides: Optional[Dict] = None):
        """The session's ServingRuntime (created on first call): the
        concurrent serving plane — multi-tenant admission with bounded
        backpressure, fair-share device scheduling, phase-overlapped
        execution and the plan+result cache (serving/runtime.py,
        docs/SERVING.md).

            rt = session.serving()
            bi = rt.tenant("bi", weight=2.0)
            table = bi.collect(df)        # or bi.submit(df).result()

        `conf_overrides` apply only on the CREATING call (they shape the
        runtime: worker counts, queue depth, cache bytes)."""
        if self._serving is None:
            from .serving.runtime import ServingRuntime
            self._serving = ServingRuntime(self, conf_overrides)
        return self._serving

    def close(self) -> None:
        """Shut the session's process-wide exporters down cleanly: the
        JSONL heartbeat and Prometheus endpoint threads are stopped AND
        joined, and the listen port is released — so repeated session
        open/close in one process cannot leak threads or ports.  The
        metrics registry itself (process-wide, cheap) stays; a later
        TpuSession restarts exporters from its conf.  Idempotent.
        A serving runtime created by `serving()` is drained and closed
        first."""
        if self._serving is not None:
            self._serving.close()
            self._serving = None
        from .obs.export import shutdown_exporters
        shutdown_exporters()

    def __enter__(self) -> "TpuSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def explain_analyze(self, df: "DataFrame",
                        conf_overrides: Optional[Dict] = None):
        """EXPLAIN ANALYZE for a DataFrame built on this session (the
        engine's query handle — there is no SQL string frontend): plans
        it under the session conf, runs one profiled collect and
        returns the device-time attribution report
        (see DataFrame.explain_analyze / obs/attribution.py)."""
        return df.physical().explain_analyze(conf_overrides)

    def cost_estimate(self, df: "DataFrame"):
        """Admission-style cost estimate for a DataFrame from the
        persistent performance-history oracle (obs/estimator.py):
        {device_us, working_set_bytes, compile_ms, confidence, basis,
        ...} — basis 'exact_history' when the query's canonical
        structure has recorded runs, 'static_cost' otherwise.  None
        when the history plane is off
        (spark.rapids.tpu.history.dir unset)."""
        from .obs.estimator import estimate_query
        return estimate_query(df.physical())

    def perf_history_stats(self):
        """The persistent performance-history store's state (structure
        count, records, corrupt lines tolerated, calibration curves,
        fitted static coefficient), or None when the plane is off."""
        from .obs.history import get_store
        store = get_store(self.conf)
        return None if store is None else store.stats()

    def metrics_snapshot(self, compact: bool = False) -> dict:
        """The process-wide always-on metrics registry: every counter,
        gauge and log2-bucket histogram the runtime publishes
        (obs/registry.py; catalog in docs/METRICS.md).  `compact=True`
        returns the flat `name{labels} -> value` form."""
        from .obs.export import registry_snapshot
        return registry_snapshot(compact)

    def flight_record(self, n: Optional[int] = None):
        """The newest `n` flight-recorder events (all when None) — the
        bounded always-on ring of spans/instants across ALL queries
        that crash dumps embed (obs/recorder.py)."""
        from .obs.export import flight_record
        return flight_record(n)

    def last_query_profile(self):
        """QueryProfile of the most recent collect()/count() on this
        session, or None before the first one.  Span-level detail (time
        split, incidents) needs `spark.rapids.tpu.trace.enabled` (or an
        eventLog.dir); the per-node-id operator table and data-movement
        counters populate from plain metrics either way."""
        if self._last_ctx is None:
            return None
        from .obs.profile import QueryProfile
        return QueryProfile.from_context(self._last_ctx)

    def _record_query(self, ctx: ExecContext) -> None:
        self._last_ctx = ctx

    # -- sources -----------------------------------------------------------
    def from_arrow(self, table: pa.Table) -> "DataFrame":
        return DataFrame(L.LogicalScan(table), self)

    def from_pydict(self, data: dict, schema=None) -> "DataFrame":
        return self.from_arrow(pa.table(data, schema=schema))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              name: str = "id") -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(L.LogicalRange(start, end, step, name), self)

    def read_parquet(self, *paths: str, columns=None) -> "DataFrame":
        from .io.parquet import LogicalParquetScan
        return DataFrame(LogicalParquetScan(list(paths), columns), self)

    def read_csv(self, *paths: str, schema=None, **opts) -> "DataFrame":
        from .io.text import LogicalCsvScan
        return DataFrame(LogicalCsvScan(list(paths), schema, opts), self)

    def read_json(self, *paths: str, schema=None, **opts) -> "DataFrame":
        from .io.text import LogicalJsonScan
        return DataFrame(LogicalJsonScan(list(paths), schema, opts), self)

    def read_orc(self, *paths: str, schema=None, **opts) -> "DataFrame":
        from .io.orc import LogicalOrcScan
        return DataFrame(LogicalOrcScan(list(paths), schema, opts), self)

    def read_avro(self, *paths: str, schema=None, **opts) -> "DataFrame":
        from .io.avro import LogicalAvroScan
        return DataFrame(LogicalAvroScan(list(paths), schema, opts), self)

    def read_hive_text(self, *paths: str, schema=None, **opts
                       ) -> "DataFrame":
        from .io.text import LogicalHiveTextScan
        return DataFrame(LogicalHiveTextScan(list(paths), schema, opts),
                         self)

    def read_iceberg(self, table_path: str, snapshot_id=None,
                     schema=None) -> "DataFrame":
        from .io.iceberg import LogicalIcebergScan
        return DataFrame(LogicalIcebergScan(
            [table_path], schema, {"snapshot_id": snapshot_id}), self)


class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence):
        self._df = df
        self._keys = list(keys)

    def agg(self, *aggs: Tuple[AggregateFunction, str]) -> "DataFrame":
        return DataFrame(
            L.LogicalAggregate(self._keys, list(aggs), self._df._plan),
            self._df._session)

    def _key_names(self) -> list:
        names = []
        for k in self._keys:
            if isinstance(k, str):
                names.append(k)
            elif isinstance(k, E.ColumnRef):
                names.append(k.name)
            else:
                raise TypeError(
                    "pandas group operations need plain column keys")
        schema_names = set(self._df.schema.names)
        for n in names:
            # fail at plan build, not inside the worker feeder thread
            if n not in schema_names:
                raise KeyError(f"group key {n!r} not in "
                               f"{sorted(schema_names)}")
        return names

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """groupBy(keys).applyInPandas(fn, schema): fn maps each group's
        pandas.DataFrame to a result DataFrame (reference
        GpuFlatMapGroupsInPandasExec)."""
        from .columnar.host import schema_to_struct
        import pyarrow as _pa
        if isinstance(schema, _pa.Schema):
            schema = schema_to_struct(schema)
        return DataFrame(
            L.LogicalFlatMapGroupsInPandas(self._key_names(), fn, schema,
                                           self._df._plan),
            self._df._session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """cogroup(l.group_by(k), r.group_by(k)) -> .apply_in_pandas(fn,
        schema) with fn(left_df, right_df) (reference
        GpuFlatMapCoGroupsInPandasExec)."""
        return CoGroupedData(self, other)

    def agg_in_pandas(self, *aggs) -> "DataFrame":
        """Grouped pandas UDAFs: aggs = (fn, input column names, output
        name, output type); each fn maps the group's Series to one
        scalar (reference GpuAggregateInPandasExec)."""
        norm = [(fn, list(cols), name, dt) for fn, cols, name, dt in aggs]
        return DataFrame(
            L.LogicalAggregateInPandas(self._key_names(), norm,
                                       self._df._plan),
            self._df._session)


GROUPING_ID_COLUMN = "spark_grouping_id"


def _expr_column_names(expr) -> set:
    names = set()
    stack = [expr]
    while stack:
        e = stack.pop()
        if isinstance(e, E.ColumnRef):
            names.add(e.name)
        stack.extend(getattr(e, "children", ()) or ())
    return names


class GroupingSets:
    """rollup / cube / grouping-sets aggregation builder.

    Lowers to Expand + Aggregate exactly as the reference plugin's
    GpuExpandExec path does (GpuExpandExec.scala:70): one Expand
    projection per grouping set, with the aggregated-away key columns
    replaced by typed nulls and a literal `spark_grouping_id` bitmask
    column appended (MSB = first key, 1 = key aggregated away — Spark's
    grouping_id() bit order), then a hash aggregate over
    keys + spark_grouping_id.

    The result carries the keys, `spark_grouping_id`, then the
    aggregates; `grouping(name)` / `grouping_id()` build the Spark
    expressions over that column for post-aggregation selects.
    """

    def __init__(self, df: "DataFrame", keys: Sequence,
                 sets: Sequence[Sequence[str]]):
        self._df = df
        self._keys = [k if isinstance(k, str) else k.name for k in keys]
        schema_names = set(df.schema.names)
        for k in self._keys:
            if k not in schema_names:
                raise KeyError(f"grouping key {k!r} not in "
                               f"{sorted(schema_names)}")
            if k == GROUPING_ID_COLUMN:
                raise ValueError(
                    f"column name {GROUPING_ID_COLUMN!r} is reserved")
        norm, seen = [], set()
        for s in sets:
            tup = tuple(k for k in self._keys if k in set(s))
            extra = set(s) - set(self._keys)
            if extra:
                raise KeyError(f"grouping set columns {sorted(extra)} "
                               f"not in keys {self._keys}")
            if tup not in seen:       # duplicate sets collapse, as in Spark
                seen.add(tup)
                norm.append(tup)
        self._sets = norm

    # -- grouping() / grouping_id() expressions -----------------------------
    def grouping_id(self) -> E.Expression:
        """Spark grouping_id(): the bitmask column itself (bit n-1-i set
        when key i is aggregated away in this row's grouping set)."""
        return E.ColumnRef(GROUPING_ID_COLUMN)

    def grouping(self, name: str) -> E.Expression:
        """Spark grouping(col): 1 when `col` is aggregated away in this
        row's grouping set, else 0 — derived from the gid bitmask."""
        if name not in self._keys:
            raise KeyError(f"grouping({name!r}): not a grouping key of "
                           f"{self._keys}")
        shift = len(self._keys) - 1 - self._keys.index(name)
        return E.BitwiseAnd(
            E.ShiftRight(E.ColumnRef(GROUPING_ID_COLUMN),
                         E.Literal(shift)),
            E.Literal(1))

    def agg(self, *aggs: Tuple[AggregateFunction, str]) -> "DataFrame":
        child = self._df._plan
        schema = child.schema
        key_set = set(self._keys)
        for fn, _name in aggs:
            inputs = getattr(fn, "child", None)
            if inputs is not None:
                hit = _expr_column_names(inputs) & key_set
                if hit:
                    # Spark's Expand keeps a second, un-nulled copy of the
                    # child attributes for aggregate inputs; this engine
                    # replaces keys in place, so aggregating a grouping
                    # key would silently see the nulled copies
                    raise NotImplementedError(
                        f"aggregating grouping key(s) {sorted(hit)} under "
                        f"rollup/cube is not supported — aggregate a "
                        f"projected copy instead")
        projections = []
        n = len(self._keys)
        for s in self._sets:
            proj = []
            for f in schema.fields:
                if f.name in key_set and f.name not in s:
                    proj.append(E.Literal(None, f.data_type))
                else:
                    proj.append(E.ColumnRef(f.name))
            gid = 0
            for i, k in enumerate(self._keys):
                if k not in s:
                    gid |= 1 << (n - 1 - i)
            proj.append(E.Literal(gid, t.INT))
            projections.append(proj)
        expand = L.LogicalExpand(
            projections, list(schema.names) + [GROUPING_ID_COLUMN], child)
        plan = L.LogicalAggregate(
            list(self._keys) + [GROUPING_ID_COLUMN], list(aggs), expand)
        return DataFrame(plan, self._df._session)


class CoGroupedData:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        from .columnar.host import schema_to_struct
        import pyarrow as _pa
        if isinstance(schema, _pa.Schema):
            schema = schema_to_struct(schema)
        return DataFrame(
            L.LogicalFlatMapCoGroupsInPandas(
                self._left._key_names(), self._right._key_names(), fn,
                schema, self._left._df._plan, self._right._df._plan),
            self._left._df._session)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session: TpuSession):
        self._plan = plan
        self._session = session
        #: (the session conf it was planned under, the PhysicalQuery) of
        #: the last collect() that left it fit for the next, which runs
        #: through it again (QueryExecution's lazy `executedPlan`):
        #: PhysicalQuery.reusable decides
        self._kept: Optional[Tuple[TpuConf, PhysicalQuery]] = None
        #: held by the one collect that may use and replace `_kept`
        self._kept_lock = threading.Lock()

    # -- transformations ---------------------------------------------------
    def select(self, *exprs, names: Optional[Sequence[str]] = None
               ) -> "DataFrame":
        return self._wrap(L.LogicalProject(list(exprs), self._plan, names))

    def with_column(self, name: str, expr: E.Expression) -> "DataFrame":
        exprs = [E.ColumnRef(n) for n in self.schema.names
                 if n != name] + [expr]
        names = [n for n in self.schema.names if n != name] + [name]
        return self._wrap(L.LogicalProject(exprs, self._plan, names))

    def filter(self, condition) -> "DataFrame":
        return self._wrap(L.LogicalFilter(condition, self._plan))

    where = filter

    def group_by(self, *keys) -> GroupedData:
        return GroupedData(self, keys)

    def rollup(self, *keys) -> GroupingSets:
        """GROUP BY ROLLUP(k1, .., kn): the n+1 prefix grouping sets
        (k1..kn), (k1..kn-1), .., () — subtotal rows per hierarchy level
        (reference GpuExpandExec lowering)."""
        names = [k if isinstance(k, str) else k.name for k in keys]
        sets = [tuple(names[:i]) for i in range(len(names), -1, -1)]
        return GroupingSets(self, names, sets)

    def cube(self, *keys) -> GroupingSets:
        """GROUP BY CUBE(k1, .., kn): all 2^n grouping sets, emitted in
        ascending grouping_id order."""
        names = [k if isinstance(k, str) else k.name for k in keys]
        n = len(names)
        sets = [tuple(names[i] for i in range(n)
                      if not (m >> (n - 1 - i)) & 1)
                for m in range(1 << n)]
        return GroupingSets(self, names, sets)

    def grouping_sets(self, sets, keys=None) -> GroupingSets:
        """GROUP BY GROUPING SETS(...): explicit set list; `keys` fixes
        the output key order (default: first-appearance order)."""
        if keys is None:
            keys, seen = [], set()
            for s in sets:
                for k in s:
                    k = k if isinstance(k, str) else k.name
                    if k not in seen:
                        seen.add(k)
                        keys.append(k)
        return GroupingSets(self, list(keys), [tuple(s) for s in sets])

    def agg(self, *aggs: Tuple[AggregateFunction, str]) -> "DataFrame":
        return GroupedData(self, ()).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             left_on=None, right_on=None) -> "DataFrame":
        if on is not None:
            keys = [on] if isinstance(on, str) else list(on)
            left_on = right_on = keys
        return self._wrap(L.LogicalJoin(how, self._plan, other._plan,
                                        left_on or [], right_on or []))

    def sort(self, *orders, global_sort: bool = True) -> "DataFrame":
        return self._wrap(L.LogicalSort(list(orders), self._plan,
                                        global_sort))

    order_by = sort

    def limit(self, n: int) -> "DataFrame":
        return self._wrap(L.LogicalLimit(n, self._plan))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        """Bernoulli sample: keep each row with probability `fraction`,
        decided by a counter-based hash of (seed, row position) —
        deterministic per seed and identical on device and CPU paths
        (reference GpuSampleExec)."""
        return self._wrap(L.LogicalSample(fraction, seed, self._plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._wrap(L.LogicalUnion(self._plan, other._plan))

    def window(self, window_exprs, partition_by=(), order_by=()
               ) -> "DataFrame":
        """Append window function columns: window_exprs = (spec, name)
        pairs (plan/window.py specs)."""
        return self._wrap(L.LogicalWindow(list(window_exprs),
                                          list(partition_by),
                                          list(order_by), self._plan))

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """mapInPandas: fn(iterator of pandas.DataFrame) -> iterator of
        pandas.DataFrame, executed in a forked Arrow-IPC python worker
        (reference GpuMapInPandasExec).  `schema` is the result
        StructType (or pyarrow schema)."""
        from .columnar.host import schema_to_struct
        import pyarrow as _pa
        if isinstance(schema, _pa.Schema):
            schema = schema_to_struct(schema)
        return self._wrap(L.LogicalMapInPandas(fn, schema, self._plan))

    def with_pandas_udf(self, name: str, fn, input_cols, return_type
                        ) -> "DataFrame":
        """Append a scalar pandas UDF column: fn(pandas.Series...) ->
        pandas.Series (reference GpuArrowEvalPythonExec)."""
        return self._wrap(L.LogicalArrowEvalPython(
            [(fn, list(input_cols), name, return_type)], self._plan))

    def with_window_pandas_udf(self, name: str, fn, input_cols,
                               return_type, partition_by=(),
                               order_by=()) -> "DataFrame":
        """Append a pandas window-UDF column over unbounded partition
        frames: fn(partition Series...) -> Series of the partition's
        length, or one scalar to broadcast (reference
        GpuWindowInPandasExec)."""
        return self._wrap(L.LogicalWindowInPandas(
            list(partition_by), list(order_by),
            [(fn, list(input_cols), name, return_type)], self._plan))

    def cache(self) -> "DataFrame":
        """Materialize once as compressed parquet bytes; downstream plans
        re-decode from the cache (ParquetCachedBatchSerializer role)."""
        from .exec.cache import LogicalCache
        if isinstance(self._plan, LogicalCache):
            return self
        return self._wrap(LogicalCache(self._plan))

    # -- actions -----------------------------------------------------------
    @property
    def schema(self) -> t.StructType:
        return self._plan.schema

    def physical(self) -> PhysicalQuery:
        return apply_overrides(self._plan, self._session.conf)

    def collect(self) -> pa.Table:
        """Plan (or take the plan kept by the last collect), launch,
        fetch.  An unchanged DataFrame keeps its physical plan, and with
        it the compiled plan object and its programs: a warm collect
        goes from here to the launch without planning or looking a
        program up.  The program runs and its answer is fetched in every
        collect; nothing is answered from a result cache."""
        conf = self._session.conf       # TpuConf is immutable: a version
        ctx = ExecContext(conf)
        # one collect at a time runs through the kept plan (a split plan
        # swaps seam leaves into its tree): a concurrent collect of this
        # DataFrame plans anew, as ever, and keeps nothing
        owner = self._kept_lock.acquire(blocking=False)
        try:
            with CollectSpan(ctx, "collect", "overhead.unattributed_ms",
                             whole_key="overhead.collect_ms"):
                with CollectSpan(ctx, "plan", "overhead.plan_ms"):
                    kept = None
                    if owner:
                        kept, self._kept = self._kept, None
                    if kept is not None and kept[0] is conf:
                        q = kept[1]
                        ctx.metrics["plan.reused"] = 1
                    else:
                        q = apply_overrides(self._plan, conf)
                    ctx.conf = q.conf   # planning may have adjusted it
                out = q.collect(ctx)
                with CollectSpan(ctx, "finish", "overhead.finish_ms"):
                    if owner and q.reusable(ctx):
                        q.release()
                        self._kept = (conf, q)
                    self._last_ctx = ctx
                    self._session._record_query(ctx)
        finally:
            if owner:
                self._kept_lock.release()
        return out

    def metrics(self) -> Optional[dict]:
        """Structured metrics of this DataFrame's most recent collect()
        (per-node-id operator counters, transition/shuffle accounting,
        compile cache stats, memory.*), or None before the first one."""
        ctx = getattr(self, "_last_ctx", None)
        return None if ctx is None else dict(ctx.metrics)

    def profile(self):
        """QueryProfile of this DataFrame's most recent collect(), or
        None before the first one (see TpuSession.last_query_profile)."""
        ctx = getattr(self, "_last_ctx", None)
        if ctx is None:
            return None
        from .obs.profile import QueryProfile
        return QueryProfile.from_context(ctx)

    def to_pydict(self) -> dict:
        return self.collect().to_pydict()

    def count(self) -> int:
        from .plan.aggregates import Count
        res = self.agg((Count(None), "count")).collect()
        return res.column("count").to_pylist()[0]

    def explain(self) -> str:
        q = self.physical()
        return q.explain() + "\n\nPhysical plan:\n" + q.physical_tree()

    def explain_analyze(self, conf_overrides: Optional[Dict] = None):
        """EXPLAIN ANALYZE: execute this query ONCE with profiling on
        (trace.enabled + profile.segments — compiled programs re-split
        at the known seam boundaries and each segment's DEVICE wall is
        measured) and return an ExplainAnalyzeReport: the physical plan
        tree annotated with measured ms, rows, bytes, gather volume and
        % of query wall, the per-segment XLA static-cost overlay
        (FLOPs / bytes accessed / peak temp vs measured time, skew
        flagged), and the mesh exchange timeline when the query ran on
        a mesh.  `print(df.explain_analyze())` renders the report;
        `.segments` / `.attributed_pct` / `.to_dict()` expose the data
        (obs/attribution.py)."""
        return self.physical().explain_analyze(conf_overrides)

    def logical_tree(self) -> str:
        return self._plan.tree_string()

    def write_parquet(self, path: str, **opts) -> None:
        from .io.parquet import write_parquet
        write_parquet(self, path, **opts)

    def device_batches(self, ctx: Optional[ExecContext] = None):
        """Zero-copy DeviceBatch stream — the ColumnarRdd escape hatch
        (ColumnarRdd.scala:42) for feeding query results into jax/ML
        code without a host round trip."""
        return self.physical().execute_device_batches(ctx)

    def to_jax(self, ctx: Optional[ExecContext] = None) -> dict:
        """Materialize results as jax arrays on device: numeric columns
        -> (data, validity); string columns -> (codes, validity,
        dictionary) with per-batch codes remapped into ONE unified
        dictionary (equal strings share a code across all batches).
        Rows from all batches are concatenated, padding removed.
        decimal(>18) has no single-lane device representation — use
        collect() for those."""
        import jax.numpy as jnp
        import numpy as np
        from . import types as _t
        per_col: dict = {}
        dicts: dict = {}      # name -> {value: global code}
        for db in self.device_batches(ctx):
            n = int(db.num_rows)
            if n == 0:
                continue
            for name, c in zip(db.names, db.columns):
                from .ops.kernels import compute_view
                if isinstance(c.dtype, _t.DecimalType) and \
                        c.dtype.is_wide:
                    raise TypeError(
                        f"to_jax: column {name} is {c.dtype.simple_string}"
                        f" — wide decimals exceed one int64 lane; use "
                        f"collect()")
                if c.dictionary is not None:
                    gd = dicts.setdefault(name, {})
                    # zeros, not empty: an all-null batch has a 0-length
                    # dictionary and its (invalid) codes must not read
                    # uninitialized memory — code values are only
                    # meaningful where validity is True
                    remap = np.zeros(max(len(c.dictionary), 1), np.int32)
                    for i, v in enumerate(c.dictionary):
                        val = v.as_py()
                        if val not in gd:
                            gd[val] = len(gd)
                        remap[i] = gd[val]
                    codes = jnp.clip(c.data, 0, len(remap) - 1)
                    data = jnp.asarray(remap)[codes][:n]
                else:
                    data = compute_view(c.data, c.dtype)[:n]
                d, v = per_col.get(name, ([], []))
                d.append(data)
                v.append(c.validity[:n])
                per_col[name] = (d, v)
        if not per_col:
            # zero-row result: shape stays schema-driven, not
            # data-dependent — every column present with 0 rows
            from .types import physical_np_dtype, StringType
            out = {}
            for f in self.schema.fields:
                if isinstance(f.data_type, _t.DecimalType) and \
                        f.data_type.is_wide:
                    raise TypeError(
                        f"to_jax: column {f.name} is "
                        f"{f.data_type.simple_string} — wide decimals "
                        f"exceed one int64 lane; use collect()")
                empty_valid = jnp.zeros(0, bool)
                if isinstance(f.data_type, StringType):
                    out[f.name] = (jnp.zeros(0, jnp.int32), empty_valid,
                                   [])
                elif isinstance(f.data_type, _t.DoubleType):
                    # compute_view turns the int64 storage lane into f64
                    out[f.name] = (jnp.zeros(0, jnp.float64), empty_valid)
                else:
                    out[f.name] = (jnp.zeros(
                        0, physical_np_dtype(f.data_type)), empty_valid)
            return out
        out = {}
        for name, (d, v) in per_col.items():
            if name in dicts:
                out[name] = (jnp.concatenate(d), jnp.concatenate(v),
                             list(dicts[name]))
            else:
                out[name] = (jnp.concatenate(d), jnp.concatenate(v))
        return out

    def _wrap(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self._session)


# -- convenience constructors (pyspark.sql.functions analogue) -------------

def col(name: str) -> E.ColumnRef:
    return E.ColumnRef(name)


def lit(value, dtype: Optional[t.DataType] = None) -> E.Literal:
    return E.Literal(value, dtype)
