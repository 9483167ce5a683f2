"""Logical plan nodes — the Catalyst-physical-plan analogue the overrides
engine rewrites.

In the reference, Spark hands the plugin a *physical* plan whose nodes are
wrapped into `RapidsMeta` trees, tagged, and converted
(GpuOverrides.scala:4364 wrapAndTagPlan, RapidsMeta.scala:83).  This engine
owns its own planner, so the pre-rewrite representation is this small
logical algebra: each node declares its schema (resolving expressions
against children) and nothing else — placement (TPU vs CPU), transitions,
and physical operator choice are decided entirely by plan/overrides.py.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import pyarrow as pa

from .. import types as t
from . import expressions as E
from .aggregates import AggregateFunction


class LogicalPlan:
    """Base logical operator. Schema resolves lazily, children first."""

    #: planning this node reads the node and the conf and nothing else
    #: (relational algebra over in-memory Arrow tables), so a physical
    #: plan made from it stands for it as long as the conf does.  A file
    #: scan, a LogicalCache, a Python worker or UDF node reads the world
    #: outside the plan and leaves this False: planned anew every collect
    #: (PhysicalQuery.keepable).
    self_contained = False

    def __init__(self, *children: "LogicalPlan"):
        self.children = list(children)
        self._schema: Optional[t.StructType] = None

    @property
    def child(self) -> "LogicalPlan":
        return self.children[0]

    @property
    def schema(self) -> t.StructType:
        if self._schema is None:
            self._schema = self._resolve_schema()
        return self._schema

    def _resolve_schema(self) -> t.StructType:
        raise NotImplementedError(type(self).__name__)

    def name(self) -> str:
        return type(self).__name__.removeprefix("Logical")

    def describe(self) -> str:
        return self.name()

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


def _as_expr(e) -> E.Expression:
    return E.ColumnRef(e) if isinstance(e, str) else e


def _out_name(e: E.Expression, i: int) -> str:
    if isinstance(e, E.Alias):
        return e.name
    if isinstance(e, E.ColumnRef):
        return e.name
    return f"col{i}"


class LogicalScan(LogicalPlan):
    """Leaf over an in-memory Arrow table (the InMemoryScan / LocalTableScan
    analogue).  File scans are LogicalFileScan (io/)."""

    self_contained = True

    def __init__(self, table: pa.Table):
        super().__init__()
        self.table = table

    def _resolve_schema(self):
        from ..columnar.host import schema_to_struct
        return schema_to_struct(self.table.schema)

    def describe(self):
        return f"Scan[{self.table.num_rows} rows]"


class LogicalProject(LogicalPlan):
    self_contained = True

    def __init__(self, exprs: Sequence, child: LogicalPlan,
                 names: Optional[Sequence[str]] = None):
        super().__init__(child)
        self.exprs = [_as_expr(e) for e in exprs]
        self.names = list(names) if names is not None else \
            [_out_name(e, i) for i, e in enumerate(self.exprs)]

    def _resolve_schema(self):
        bound = [e.bind(self.child.schema) for e in self.exprs]
        return t.StructType([t.StructField(n, e.dtype, e.nullable)
                             for n, e in zip(self.names, bound)])

    def describe(self):
        return f"Project[{', '.join(self.names)}]"


class LogicalFilter(LogicalPlan):
    self_contained = True

    def __init__(self, condition: E.Expression, child: LogicalPlan):
        super().__init__(child)
        self.condition = _as_expr(condition)

    def _resolve_schema(self):
        return self.child.schema

    def describe(self):
        return f"Filter[{self.condition!r}]"


class LogicalAggregate(LogicalPlan):
    """group-by keys + aggregate list.  keys may be arbitrary expressions;
    aggs are (AggregateFunction, output name) pairs."""

    self_contained = True

    def __init__(self, keys: Sequence, aggs: Sequence[Tuple[AggregateFunction, str]],
                 child: LogicalPlan, key_names: Optional[Sequence[str]] = None):
        super().__init__(child)
        self.keys = [_as_expr(k) for k in keys]
        self.key_names = list(key_names) if key_names is not None else \
            [_out_name(k, i) for i, k in enumerate(self.keys)]
        self.aggs = list(aggs)

    def _resolve_schema(self):
        schema = self.child.schema
        fields = []
        for n, k in zip(self.key_names, self.keys):
            fields.append(t.StructField(n, k.bind(schema).dtype))
        for fn, n in self.aggs:
            fields.append(t.StructField(n, fn.bind(schema).dtype))
        return t.StructType(fields)

    def describe(self):
        return (f"Aggregate[keys={self.key_names}, "
                f"aggs={[n for _, n in self.aggs]}]")


class LogicalSort(LogicalPlan):
    """orders: sequence of (expr-or-name, ascending, nulls_first)."""

    self_contained = True

    def __init__(self, orders: Sequence, child: LogicalPlan,
                 global_sort: bool = True):
        super().__init__(child)
        norm = []
        for o in orders:
            if isinstance(o, (str, E.Expression)):
                norm.append((_as_expr(o), True, True))
            else:
                e, *rest = o
                asc = rest[0] if rest else True
                nf = rest[1] if len(rest) > 1 else asc
                norm.append((_as_expr(e), asc, nf))
        self.orders = norm
        self.global_sort = global_sort

    def _resolve_schema(self):
        return self.child.schema

    def describe(self):
        ks = [(e.name if isinstance(e, E.ColumnRef) else repr(e),
               "asc" if a else "desc") for e, a, _ in self.orders]
        return f"Sort[{ks}]"


class LogicalLimit(LogicalPlan):
    self_contained = True

    def __init__(self, limit: int, child: LogicalPlan):
        super().__init__(child)
        self.limit = limit

    def _resolve_schema(self):
        return self.child.schema

    def describe(self):
        return f"Limit[{self.limit}]"


class LogicalJoin(LogicalPlan):
    """Equi-join on key expression pairs.  join_type: inner, left_outer,
    right_outer, full_outer, left_semi, left_anti, cross."""

    self_contained = True

    _MIRROR = {"inner": "inner", "left_outer": "right_outer",
               "right_outer": "left_outer", "full_outer": "full_outer",
               "cross": "cross"}

    def __init__(self, join_type: str, left: LogicalPlan, right: LogicalPlan,
                 left_keys: Sequence = (), right_keys: Sequence = (),
                 broadcast: Optional[str] = None):
        """broadcast: None | "left" | "right" — the BROADCAST hint side.
        A "left" broadcast mirrors the join so the broadcast side becomes
        the build (right) side; non-mirrorable types (semi/anti) keep the
        hint only when it already points right."""
        if broadcast == "left" and join_type in self._MIRROR:
            left, right = right, left
            left_keys, right_keys = right_keys, left_keys
            join_type = self._MIRROR[join_type]
            broadcast = "right"
        super().__init__(left, right)
        self.join_type = join_type
        self.left_keys = [_as_expr(k) for k in left_keys]
        self.right_keys = [_as_expr(k) for k in right_keys]
        self.broadcast = broadcast if broadcast == "right" else None

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def _resolve_schema(self):
        # analysis-time key type check: Spark coerces mismatched key
        # types in the analyzer; this engine (like the physical layer
        # the reference plugs into) requires equal types — callers cast
        # explicitly.  Both engine paths must fail identically, so the
        # error is raised here, not at execution.
        for lk, rk in zip(self.left_keys, self.right_keys):
            lt_ = lk.bind(self.left.schema).dtype
            rt_ = rk.bind(self.right.schema).dtype
            # field-wise inequality: decimal(10,2) vs decimal(10,4) must
            # also fail — join kernels compare raw unscaled lanes
            if lt_ != rt_:
                raise TypeError(
                    f"join key type mismatch: {lt_.simple_string} vs "
                    f"{rt_.simple_string} — add an explicit Cast")
        lf = list(self.left.schema.fields)
        if self.join_type in ("left_semi", "left_anti"):
            return t.StructType(lf)
        return t.StructType(lf + list(self.right.schema.fields))

    def describe(self):
        return f"Join[{self.join_type}, keys={len(self.left_keys)}]"


class LogicalSample(LogicalPlan):
    """Bernoulli row sample (reference GpuSampleExec,
    basicPhysicalOperators.scala:838): each row kept independently with
    probability `fraction`, decided by a counter-based hash of
    (seed, global row position) — deterministic for a given seed AND
    identical on the device and CPU paths."""

    self_contained = True

    def __init__(self, fraction: float, seed: int, child: LogicalPlan):
        super().__init__(child)
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"sample fraction {fraction} not in [0, 1]")
        self.fraction = float(fraction)
        self.seed = int(seed)

    def _resolve_schema(self):
        return self.child.schema

    def describe(self):
        return f"Sample[{self.fraction}, seed={self.seed}]"


class LogicalUnion(LogicalPlan):
    self_contained = True

    def __init__(self, *children: LogicalPlan):
        super().__init__(*children)

    def _resolve_schema(self):
        return self.children[0].schema


class LogicalRange(LogicalPlan):
    def __init__(self, start: int, end: int, step: int = 1, name: str = "id"):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.col_name = name

    def _resolve_schema(self):
        return t.StructType([t.StructField(self.col_name, t.LongType(), False)])

    def describe(self):
        return f"Range[{self.start},{self.end},{self.step}]"


class LogicalExpand(LogicalPlan):
    self_contained = True

    def __init__(self, projections: Sequence[Sequence], names: Sequence[str],
                 child: LogicalPlan):
        super().__init__(child)
        self.projections = [[_as_expr(e) for e in p] for p in projections]
        self.names = list(names)

    def _resolve_schema(self):
        bound = [e.bind(self.child.schema) for e in self.projections[0]]
        return t.StructType([t.StructField(n, e.dtype)
                             for n, e in zip(self.names, bound)])


class LogicalWindow(LogicalPlan):
    """Window functions over (partition keys, order keys).  window_exprs:
    (WindowFunctionSpec, output name) pairs appended to the child schema.
    See plan/window.py for specs."""

    self_contained = True

    def __init__(self, window_exprs: Sequence, partition_keys: Sequence,
                 order_keys: Sequence, child: LogicalPlan):
        from .window import check_window_analysis
        super().__init__(child)
        check_window_analysis(window_exprs, order_keys)
        self.window_exprs = list(window_exprs)
        self.partition_keys = [_as_expr(k) for k in partition_keys]
        norm = []
        for o in order_keys:
            if isinstance(o, (str, E.Expression)):
                norm.append((_as_expr(o), True, True))
            else:
                e, *rest = o
                asc = rest[0] if rest else True
                nf = rest[1] if len(rest) > 1 else asc
                norm.append((_as_expr(e), asc, nf))
        self.order_keys = norm

    def _resolve_schema(self):
        fields = list(self.child.schema.fields)
        for spec, name in self.window_exprs:
            bound = spec.bind(self.child.schema)
            fields.append(t.StructField(name, bound.dtype))
        return t.StructType(fields)

    def describe(self):
        return f"Window[{[n for _, n in self.window_exprs]}]"


class LogicalMapInPandas(LogicalPlan):
    """mapInPandas: iterator-of-pandas-DataFrames transform through a
    forked Arrow-IPC python worker (reference GpuMapInPandasExec)."""

    def __init__(self, fn, schema, child: LogicalPlan):
        super().__init__(child)
        self.fn = fn
        self.result_schema = schema

    def _resolve_schema(self):
        return self.result_schema

    def describe(self):
        return f"MapInPandas[{getattr(self.fn, '__name__', 'fn')}]"


class LogicalArrowEvalPython(LogicalPlan):
    """Scalar pandas-UDF projection outputs appended to the child
    (reference GpuArrowEvalPythonExec)."""

    def __init__(self, udfs, child: LogicalPlan):
        super().__init__(child)
        self.udfs = list(udfs)     # (fn, in_cols, name, dtype)

    def _resolve_schema(self):
        fields = list(self.child.schema.fields)
        for _fn, _cols, name, dt in self.udfs:
            fields.append(t.StructField(name, dt, True))
        return t.StructType(fields)

    def describe(self):
        return f"ArrowEvalPython[{[n for _f, _c, n, _t in self.udfs]}]"


class LogicalGenerate(LogicalPlan):
    """Generator (explode/posexplode) appending generated columns to the
    child's rows — reference GpuGenerateExec (GpuGenerateExec.scala:829).
    Runs on the CPU path by placement (array inputs; plan/collections.py)."""

    self_contained = True

    def __init__(self, generator, child: LogicalPlan,
                 output_names: Sequence[str] = ()):
        super().__init__(child)
        self.generator = generator
        self.output_names = list(output_names)

    def _resolve_schema(self):
        bound = self.generator.bind(self.child.schema)
        fields = list(self.child.schema.fields)
        gen_fields = bound.output_fields()
        names = self.output_names or [f.name for f in gen_fields]
        for f, n in zip(gen_fields, names):
            fields.append(t.StructField(n, f.data_type, f.nullable))
        return t.StructType(fields)

    def describe(self):
        return f"Generate[{self.generator!r}]"


class LogicalFlatMapGroupsInPandas(LogicalPlan):
    """groupBy(keys).applyInPandas(fn, schema) — reference
    GpuFlatMapGroupsInPandasExec."""

    def __init__(self, key_names, fn, schema, child: LogicalPlan):
        super().__init__(child)
        self.key_names = list(key_names)
        self.fn = fn
        self.result_schema = schema

    def _resolve_schema(self):
        return self.result_schema

    def describe(self):
        return (f"FlatMapGroupsInPandas[{self.key_names}, "
                f"{getattr(self.fn, '__name__', 'fn')}]")


class LogicalAggregateInPandas(LogicalPlan):
    """groupBy(keys).agg(pandas UDAFs) — reference
    GpuAggregateInPandasExec.  aggs: (fn, in_cols, name, dtype)."""

    def __init__(self, key_names, aggs, child: LogicalPlan):
        super().__init__(child)
        self.key_names = list(key_names)
        self.aggs = list(aggs)

    def _resolve_schema(self):
        schema = self.child.schema
        fields = [schema.fields[schema.field_index(n)]
                  for n in self.key_names]
        for _fn, _cols, name, dt in self.aggs:
            fields.append(t.StructField(name, dt, True))
        return t.StructType(fields)

    def describe(self):
        return f"AggregateInPandas[{[n for _f, _c, n, _t in self.aggs]}]"


class LogicalWindowInPandas(LogicalPlan):
    """Pandas window UDFs over unbounded partition frames — reference
    GpuWindowInPandasExec.  windows: (fn, in_cols, name, dtype)."""

    def __init__(self, partition_names, order_names, windows,
                 child: LogicalPlan):
        super().__init__(child)
        self.partition_names = list(partition_names)
        self.order_names = list(order_names)
        self.windows = list(windows)

    def _resolve_schema(self):
        fields = list(self.child.schema.fields)
        for _fn, _cols, name, dt in self.windows:
            fields.append(t.StructField(name, dt, True))
        return t.StructType(fields)

    def describe(self):
        return f"WindowInPandas[{[n for _f, _c, n, _t in self.windows]}]"


class LogicalFlatMapCoGroupsInPandas(LogicalPlan):
    """cogroup(l.groupBy(keys), r.groupBy(keys)).applyInPandas(fn, schema)
    — fn maps each key's (left DataFrame, right DataFrame) pair to a
    result DataFrame (reference GpuFlatMapCoGroupsInPandasExec)."""

    def __init__(self, left_keys, right_keys, fn, schema,
                 left: LogicalPlan, right: LogicalPlan):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.fn = fn
        self.result_schema = schema

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def _resolve_schema(self):
        return self.result_schema

    def describe(self):
        return (f"FlatMapCoGroupsInPandas[{self.left_keys}, "
                f"{getattr(self.fn, '__name__', 'fn')}]")
