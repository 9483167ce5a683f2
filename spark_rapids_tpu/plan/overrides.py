"""The plan-rewrite engine: wrap -> tag -> convert with per-node fallback.

Reference: GpuOverrides.scala:904-4720 (rule registry + applyOverrides),
RapidsMeta.scala:83-328 (meta tree, tagForGpu, willNotWorkOnGpu,
canThisBeReplaced), GpuTransitionOverrides.scala:46 (transition insertion),
ExplainPlan (spark.rapids.sql.explain logging).

Lifecycle (same shape as the reference):
  1. wrap   — the logical plan (plan/logical.py) is wrapped into a
     PlanMeta tree; every expression into an ExprMeta tree.
  2. tag    — children first, then self: master kill-switch, per-op conf
     enable keys (`spark.rapids.tpu.sql.exec.<Name>` /
     `...sql.expression.<Name>`), declarative TypeSig checks against the
     rule registry, and op-specific `tag_self` checks.  Every failure is a
     recorded *reason string*, never an exception.
  3. convert — nodes where `can_replace` become device execs (exec/plan.py
     et al); others become CPU execs (exec/host_exec.py).  Transitions
     (HostToDeviceExec / DeviceToHostExec) are inserted exactly where the
     placement flips — the GpuTransitionOverrides role.

Explain: `PhysicalQuery.explain()` renders every placement decision with
its reasons (`spark.rapids.tpu.sql.explain=ALL|NOT_ON_TPU`).
"""
from __future__ import annotations

import dataclasses
import logging
import threading
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Type

import pyarrow as pa

from .. import types as t
from ..config import ENABLED_FORMATS, TpuConf, DEFAULT_CONF
from ..exec import host_exec as H
from ..io.parquet import (CpuParquetScanExec, LogicalParquetScan,
                          ParquetScanExec)
from ..io.orc import CpuOrcScanExec, LogicalOrcScan, OrcScanExec
from ..io.avro import LogicalAvroScan
from ..io.iceberg import LogicalIcebergScan
from ..io.text import (CpuTextScanExec, LogicalCsvScan,
                       LogicalHiveTextScan, LogicalJsonScan, TextScanExec)
from ..exec.plan import (CoalesceBatchesExec, ExecContext, ExpandExec,
                         FilterExec, GlobalLimitExec, HashAggregateExec,
                         HostScanExec, PlanNode, ProjectExec, RangeExec,
                         SampleExec, SortExec, UnionExec)
from . import expressions as E
from . import logical as L
from .aggregates import (AggregateFunction, Average, BoolAnd, BoolOr, Count,
                         First, Last, Max, Min, Sum)

log = logging.getLogger("spark_rapids_tpu.overrides")


# ---------------------------------------------------------------------------
# Rule registry (GpuOverrides.commonExpressions / commonExecs analogue)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExprRule:
    cls: type
    input_sig: t.TypeSig
    output_sig: t.TypeSig
    desc: str = ""


@dataclasses.dataclass
class ExecRule:
    cls: type
    output_sig: t.TypeSig
    desc: str = ""


_EXPR_RULES: Dict[type, ExprRule] = {}
_EXEC_RULES: Dict[type, ExecRule] = {}
_AGG_RULES: Dict[type, ExprRule] = {}


def expr_rule(cls, input_sig, output_sig=None, desc=""):
    _EXPR_RULES[cls] = ExprRule(cls, input_sig, output_sig or input_sig, desc)


def agg_rule(cls, input_sig, output_sig=None, desc=""):
    _AGG_RULES[cls] = ExprRule(cls, input_sig, output_sig or input_sig, desc)


def exec_rule(cls, output_sig, desc=""):
    _EXEC_RULES[cls] = ExecRule(cls, output_sig, desc)


_NUM_BOOL = t.T.NUMERIC + t.T.BOOLEAN + t.T.NULL
_COMMON = t.T.DEVICE_COMMON
# every device-representable simple type — NO BINARY (no device lane for it)
_DEVICE_SIMPLE = t.T.NUMERIC + t.T.STRING + t.T.BOOLEAN + t.T.DATETIME + t.T.NULL

expr_rule(E.ColumnRef, _COMMON + t.T.ARRAY, desc="column reference")

# Ragged ARRAY expression family (plan/collections.py device kernels over
# ops/ragged.py; per-expression tag_self narrows element types further)
from .collections import (ArrayContains, ArrayExists,  # noqa: E402
                          ArrayFilter, ArrayForAll, ArrayMax, ArrayMin,
                          ArrayTransform, GetArrayItem, LambdaVar, Size,
                          SortArray)

_ARR_SIG = (_COMMON + t.T.ARRAY)
for _cls, _desc in [
        (Size, "size(array) from the offsets lane"),
        (GetArrayItem, "array[i] gather"),
        (ArrayContains, "segment any-equal"),
        (ArrayMin, "segment min"),
        (ArrayMax, "segment max"),
        (SortArray, "segment-local lexsort"),
        (ArrayTransform, "lambda over the flat values lane"),
        (ArrayFilter, "values-lane compaction"),
        (ArrayExists, "segment three-valued any"),
        (ArrayForAll, "segment three-valued all"),
        (LambdaVar, "lambda-bound element variable")]:
    expr_rule(_cls, _ARR_SIG, desc=_desc)

from .collections import (ArrayDistinct, ArrayExcept,  # noqa: E402
                          ArrayIntersect, ArrayJoin, ArrayPosition,
                          ArrayRemove, ArrayRepeat, ArraysOverlap,
                          ArrayUnion, ElementAt, Flatten, MapConcat,
                          MapEntries, MapFilter, MapFromArrays, ReverseArray,
                          Sequence, Slice, StrToMap, TransformKeys,
                          TransformValues)

for _cls, _desc in [
        (ElementAt, "1-based element gather (negative from end)"),
        (ArrayPosition, "segment first-match position"),
        (Slice, "values-lane range compaction"),
        (ReverseArray, "per-row reversal gather")]:
    expr_rule(_cls, _ARR_SIG, desc=_desc)
for _cls, _desc in [
        (ArrayRepeat, "array_repeat (CPU)"),
        (Flatten, "flatten array<array> (CPU)"),
        (ArrayDistinct, "first-occurrence dedupe (CPU)"),
        (ArraysOverlap, "3-valued set overlap (CPU)"),
        (ArrayUnion, "set union (CPU)"),
        (ArrayIntersect, "set intersect (CPU)"),
        (ArrayExcept, "set except (CPU)"),
        (ArrayRemove, "drop equal elements (CPU)"),
        (ArrayJoin, "string join (CPU)"),
        (Sequence, "integral range generation (CPU)")]:
    expr_rule(_cls, _ARR_SIG, desc=_desc)
_MAP_SIG = _COMMON + t.T.MAP + t.T.ARRAY + t.T.STRUCT
for _cls, _desc in [
        (StrToMap, "str_to_map (CPU)"),
        (MapFromArrays, "map_from_arrays (CPU)"),
        (MapConcat, "map_concat LAST_WIN (CPU)"),
        (MapEntries, "map_entries (CPU)"),
        (TransformValues, "map value lambda (CPU)"),
        (TransformKeys, "map key lambda (CPU)"),
        (MapFilter, "map entry filter (CPU)")]:
    expr_rule(_cls, _MAP_SIG, desc=_desc)
expr_rule(E.Literal, _COMMON + t.T.NULL, desc="literal value")
expr_rule(E.Alias, _COMMON, desc="named expression")
for _c in (E.Add, E.Subtract, E.Multiply, E.Divide, E.IntegralDivide,
           E.Remainder, E.UnaryMinus, E.Abs):
    expr_rule(_c, t.T.NUMERIC + t.T.NULL, desc="arithmetic")
for _c in (E.EqualTo, E.NotEqual, E.LessThan, E.LessThanOrEqual,
           E.GreaterThan, E.GreaterThanOrEqual, E.EqualNullSafe):
    expr_rule(_c, t.T.COMPARABLE, t.T.BOOLEAN, desc="comparison")
for _c in (E.And, E.Or, E.Not):
    expr_rule(_c, t.T.BOOLEAN + t.T.NULL, t.T.BOOLEAN, desc="boolean logic")
for _c in (E.IsNull, E.IsNotNull):
    expr_rule(_c, t.T.ALL_SIMPLE, t.T.BOOLEAN, desc="null predicate")
expr_rule(E.IsNaN, t.T.FP, t.T.BOOLEAN, desc="NaN predicate")
expr_rule(E.Coalesce, _COMMON, desc="first non-null")
expr_rule(E.If, _COMMON, desc="if/else")
expr_rule(E.CaseWhen, _COMMON, desc="case/when")
expr_rule(E.In, _COMMON, t.T.BOOLEAN, desc="IN list")
for _c in (E.Sqrt, E.Exp, E.Log, E.Pow, E.Sin, E.Cos, E.Tan, E.Asin,
           E.Acos, E.Atan, E.Sinh, E.Cosh, E.Tanh, E.Log10, E.Log2,
           E.Cbrt, E.Signum, E.Atan2, E.ToDegrees, E.ToRadians, E.Expm1,
           E.Log1p, E.Rint, E.Cot, E.Sec, E.Csc, E.Hypot):
    expr_rule(_c, t.T.NUMERIC, t.T.FP, desc="math fn")
for _c in (E.Floor, E.Ceil):
    expr_rule(_c, t.T.NUMERIC, t.T.INTEGRAL, desc="rounding")
for _c in (E.Round, E.BRound):
    expr_rule(_c, t.T.NUMERIC, desc="round/bround (HALF_UP / HALF_EVEN)")
for _c in (E.Greatest, E.Least):
    expr_rule(_c, t.T.NUMERIC + t.T.DATETIME + t.T.BOOLEAN + t.T.NULL,
              desc="n-ary extremum (null-skipping, NaN greatest)")
expr_rule(E.Murmur3Hash, _COMMON, t.T.INTEGRAL,
          desc="Spark hash() — bit-exact murmur3 device kernels")
expr_rule(E.XxHash64, _COMMON, t.T.INTEGRAL,
          desc="Spark xxhash64() — bit-exact XXH64 device kernels")
for _c in (E.BitwiseAnd, E.BitwiseOr, E.BitwiseXor, E.BitwiseNot):
    expr_rule(_c, t.T.INTEGRAL + t.T.NULL, desc="bitwise op")
for _c in (E.ShiftLeft, E.ShiftRight, E.ShiftRightUnsigned):
    expr_rule(_c, t.T.INTEGRAL + t.T.NULL,
              desc="Java shift (distance mod width)")
expr_rule(E.BitCount, t.T.INTEGRAL + t.T.BOOLEAN, t.T.INTEGRAL,
          desc="population count")
expr_rule(E.WidthBucket, t.T.NUMERIC, t.T.INTEGRAL,
          desc="ANSI histogram bucket")

from .hive_udf import HiveGenericUDF, HiveSimpleUDF  # noqa: E402

for _c in (HiveSimpleUDF, HiveGenericUDF):
    expr_rule(_c, t.T.ALL_SIMPLE + t.T.NULL,
              desc="hive UDF: device when TpuHiveUDF (RapidsUDF role), "
                   "row-based host otherwise (rowBasedHiveUDFs role)")
expr_rule(E.RaiseError, t.T.ALL_SIMPLE + t.T.NULL,
          desc="raise_error (CPU path: device programs cannot throw)")
expr_rule(E.Cast, t.T.ALL_SIMPLE, desc="cast (pairs gated by Cast itself)")

from .json_fns import FromJson, ToJson  # noqa: E402

expr_rule(FromJson, t.T.ALL, desc="from_json (STRUCT result: CPU path, "
          "per-expression tagging — GpuJsonToStructs role)")
expr_rule(ToJson, t.T.ALL, desc="to_json (STRUCT input: CPU path — "
          "GpuStructsToJson role)")

from . import datetime as DT  # noqa: E402  (registry population)
from . import strings as STR  # noqa: E402  (registry population)

for _c in (DT.Year, DT.Month, DT.DayOfMonth, DT.DayOfWeek, DT.WeekDay,
           DT.DayOfYear, DT.Quarter, DT.WeekOfYear):
    expr_rule(_c, t.T.DATETIME, t.T.INTEGRAL, desc="date field extract")
for _c in (DT.Hour, DT.Minute, DT.Second):
    expr_rule(_c, t.T.TIMESTAMP, t.T.INTEGRAL, desc="time field extract")
for _c in (DT.DateAdd, DT.DateSub, DT.AddMonths, DT.LastDay, DT.TruncDate):
    expr_rule(_c, t.T.DATE + t.T.INTEGRAL, t.T.DATE, desc="date arithmetic")
expr_rule(DT.DateDiff, t.T.DATE, t.T.INTEGRAL, desc="date difference")
expr_rule(DT.ToUnixTimestamp, t.T.DATETIME, t.T.INTEGRAL,
          desc="epoch seconds")

for _c in (STR.Upper, STR.Lower, STR.InitCap, STR.StringTrim,
           STR.StringTrimLeft, STR.StringTrimRight, STR.Substring,
           STR.Concat, STR.ConcatWs, STR.StringReplace, STR.Lpad, STR.Rpad,
           STR.StringRepeat, STR.Reverse, STR.SplitPart):
    expr_rule(_c, t.T.STRING + t.T.INTEGRAL + t.T.NULL, t.T.STRING,
              desc="string transform (dictionary rewrite)")
for _c in (STR.Length, STR.OctetLength, STR.BitLength, STR.StringLocate,
           STR.Instr, STR.Ascii):
    expr_rule(_c, t.T.STRING + t.T.INTEGRAL, t.T.INTEGRAL,
              desc="string measure (device byte kernel / dict gather)")
for _c in (STR.StartsWith, STR.EndsWith, STR.Contains, STR.Like, STR.RLike):
    expr_rule(_c, t.T.STRING, t.T.BOOLEAN,
              desc="string predicate (device byte kernel)")
for _c in (STR.RegexpExtract, STR.RegexpReplace):
    expr_rule(_c, t.T.STRING,
              desc="regex extract/replace (dictionary transform)")
expr_rule(STR.ParseUrl, t.T.STRING,
          desc="parse_url (JNI ParseURI role; dictionary transform)")
expr_rule(STR.Conv, t.T.STRING + t.T.INTEGRAL, t.T.STRING,
          desc="base conversion (dictionary transform)")
expr_rule(STR.Hex, t.T.STRING, t.T.STRING,
          desc="hex of UTF-8 bytes (dictionary transform)")
expr_rule(STR.FormatNumber, t.T.NUMERIC, t.T.STRING,
          desc="format_number (CPU path)")
expr_rule(STR.Bin, t.T.INTEGRAL, t.T.STRING, desc="bin (CPU path)")
for _c in (STR.Translate, STR.SubstringIndex, STR.Left, STR.Right,
           STR.Base64E, STR.UnBase64, STR.SoundEx):
    expr_rule(_c, t.T.STRING + t.T.INTEGRAL + t.T.NULL, t.T.STRING,
              desc="string transform (dictionary rewrite)")
for _c in (STR.Levenshtein, STR.FindInSet):
    expr_rule(_c, t.T.STRING, t.T.INTEGRAL,
              desc="string measure (dictionary int transform)")

from . import json_fns as JSON  # noqa: E402  (registry population)

expr_rule(JSON.GetJsonObject, t.T.STRING,
          desc="get_json_object (dictionary transform)")

from . import udf as UDF  # noqa: E402  (registry population)

expr_rule(UDF.TpuUDF, t.T.NUMERIC + t.T.BOOLEAN + t.T.DATETIME,
          desc="jax-traceable columnar UDF (fuses into the operator "
               "program)")
expr_rule(UDF.PythonUDF, t.T.ALL_SIMPLE,
          desc="row-at-a-time python UDF (always CPU path)")

from . import misc as MISC  # noqa: E402

expr_rule(MISC.MonotonicallyIncreasingID, _COMMON,
          desc="nondeterministic unique int64 per row (batch-indexed)")
expr_rule(MISC.SparkPartitionID, _COMMON,
          desc="batch ordinal (the engine's partition analogue)")
expr_rule(MISC.InputFileName, _COMMON,
          desc="scan provenance of the current batch; '' when unknown")

for _c in (Count, Sum, Min, Max, Average, First, Last, BoolAnd, BoolOr):
    agg_rule(_c, _COMMON, desc="aggregate function")

from .aggregates import (Corr, CovarPop, CovarSamp, StddevPop,  # noqa: E402
                         StddevSamp, VariancePop, VarianceSamp)

for _c in (VariancePop, VarianceSamp, StddevPop, StddevSamp,
           Corr, CovarPop, CovarSamp):
    agg_rule(_c, t.T.NUMERIC, t.T.FP,
             desc="statistical aggregate (moment sums on device)")

from .aggregates import (ApproximatePercentile, Median,  # noqa: E402
                         Percentile)

for _c in (Percentile, ApproximatePercentile, Median):
    agg_rule(_c, t.T.NUMERIC, t.T.FP,
             desc="sort-based device percentile (exact; satisfies the "
                  "approx rank-error contract trivially)")

from .aggregates import CountDistinct  # noqa: E402

agg_rule(CountDistinct, _COMMON, t.T.INTEGRAL,
         desc="count(DISTINCT) as a sorted value-change count")

from .aggregates import CollectList, CollectSet  # noqa: E402

for _c in (CollectList, CollectSet):
    agg_rule(_c, _COMMON, _COMMON + t.T.ARRAY,
             desc="collect as a sorted group-by emitting ragged lanes")

# Ragged (ARRAY<primitive|string>) device support: values+offsets lanes
# (SURVEY §7c; ops/ragged.py).  Scans upload them, projections carry and
# compute over them, Generate explodes them; row-reordering execs
# (filter/sort/join/agg) keep the CPU path for now.
_RAGGED_ELEM = (t.T.INTEGRAL
                + (t.T.FP - t.TypeSig(frozenset({"DOUBLE"})))
                + t.T.BOOLEAN + t.T.DATE + t.T.STRING)
_DEVICE_RAGGED = (_DEVICE_SIMPLE + t.T.ARRAY).with_nested(_RAGGED_ELEM)

exec_rule(L.LogicalScan, _DEVICE_RAGGED, "in-memory scan + device upload")
exec_rule(L.LogicalProject, (_COMMON + t.T.ARRAY).with_nested(_RAGGED_ELEM),
          "projection")
exec_rule(L.LogicalGenerate, _DEVICE_RAGGED,
          "explode/posexplode over ragged values+offsets lanes")
exec_rule(L.LogicalMapInPandas, t.T.ALL,
          "mapInPandas via forked Arrow-IPC python workers")
exec_rule(L.LogicalArrowEvalPython, t.T.ALL,
          "scalar pandas UDFs via forked Arrow-IPC python workers")
exec_rule(L.LogicalFlatMapGroupsInPandas, t.T.ALL,
          "applyInPandas via group-segmented python workers")
exec_rule(L.LogicalFlatMapCoGroupsInPandas, t.T.ALL,
          "cogrouped applyInPandas via paired python-worker frames")
exec_rule(L.LogicalAggregateInPandas, t.T.ALL,
          "grouped pandas UDAFs via group-segmented python workers")
exec_rule(L.LogicalWindowInPandas, t.T.ALL,
          "pandas window UDFs via partition-segmented python workers")
exec_rule(L.LogicalFilter, _DEVICE_SIMPLE, "filter")
exec_rule(L.LogicalAggregate, _COMMON + t.T.ARRAY, "hash aggregate")
exec_rule(L.LogicalSort, t.T.ORDERABLE, "sort")
exec_rule(L.LogicalLimit, _DEVICE_SIMPLE, "limit")
exec_rule(L.LogicalJoin, _COMMON, "hash join")
exec_rule(L.LogicalUnion, _DEVICE_SIMPLE, "union")
exec_rule(L.LogicalRange, _DEVICE_SIMPLE, "range generator")
exec_rule(L.LogicalExpand, _COMMON, "expand (grouping sets)")
exec_rule(L.LogicalSample, _DEVICE_SIMPLE,
          "bernoulli sample (counter-based hash, seed-deterministic)")
exec_rule(L.LogicalWindow, _COMMON,
          "window functions (partition-sorted segmented scans)")

from ..exec.cache import LogicalCache  # noqa: E402

exec_rule(LogicalCache, _DEVICE_SIMPLE,
          "cached scan (zstd parquet bytes, "
          "ParquetCachedBatchSerializer role)")
exec_rule(LogicalParquetScan, _DEVICE_SIMPLE, "parquet scan")
exec_rule(LogicalCsvScan, _DEVICE_SIMPLE, "csv scan")
exec_rule(LogicalJsonScan, _DEVICE_SIMPLE, "json scan")
exec_rule(LogicalOrcScan, _DEVICE_SIMPLE, "orc scan")
exec_rule(LogicalAvroScan, _DEVICE_SIMPLE, "avro scan")
exec_rule(LogicalIcebergScan, _DEVICE_SIMPLE, "iceberg scan")
exec_rule(LogicalHiveTextScan, _DEVICE_SIMPLE, "hive text scan")


# ---------------------------------------------------------------------------
# Meta hierarchy
# ---------------------------------------------------------------------------

def _host_to_device(node: "H.HostNode") -> PlanNode:
    """Wrap a CPU node for a device parent, pruning columns whose types
    device lanes cannot carry (arrays/maps/structs/binary).  Safe because
    no DEVICE exec's output signature admits those types (the device exec
    rules use _DEVICE_SIMPLE / _COMMON), so a device parent that needed
    such a column was itself tagged onto the CPU — only pass-through
    ballast is cut here."""
    schema = node.output_schema

    def representable(dt) -> bool:
        if isinstance(dt, (t.MapType, t.StructType, t.BinaryType)):
            return False
        if isinstance(dt, t.ArrayType):
            # ragged device lanes exist for primitive/string elements
            from .collections import _device_elem_ok
            return _device_elem_ok(dt.element_type) or \
                isinstance(dt.element_type, t.StringType)
        return True

    keep = [f.name for f in schema.fields
            if representable(f.data_type)]
    if len(keep) != len(schema.fields):
        exprs = [E.ColumnRef(n) for n in keep]
        names = list(keep)
        if not exprs:
            # a zero-column projection would collapse num_rows to 0;
            # carry the row count through a synthetic constant column
            # (device parents resolve columns by name and ignore it)
            exprs = [E.Literal(0, t.INT)]
            names = ["__rows__"]
        node = H.CpuProjectExec(exprs, names, node)
    return H.HostToDeviceExec(node)


def _wide_decimal_names(schema: t.StructType) -> frozenset:
    return frozenset(f.name for f in schema.fields
                     if isinstance(f.data_type, t.DecimalType)
                     and f.data_type.is_wide)


class BaseMeta:
    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.reasons: List[str] = []

    def will_not_work(self, reason: str):
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_replace(self) -> bool:
        return not self.reasons


class ExprMeta(BaseMeta):
    """Wraps one bound expression.  Child reasons roll up: the reference
    replaces expressions only as whole trees inside an operator."""

    def __init__(self, expr: E.Expression, conf: TpuConf):
        super().__init__(conf)
        self.expr = expr
        self.children = [ExprMeta(c, conf) for c in expr.children]

    def tag(self):
        for c in self.children:
            c.tag()
            for r in c.reasons:
                self.will_not_work(r)
        from .misc import InputFileName
        if any(isinstance(c, InputFileName) for c in self.expr.children):
            # nested use would read the placeholder dictionary baked
            # into the traced program (plan/misc.py); only top-level
            # projection outputs carry the per-batch file dictionary
            self.will_not_work(
                "input_file_name nested inside another expression "
                "(device path supports it as a top-level output only)")
        name = type(self.expr).__name__
        if name in self.conf.shims.unavailable_expressions:
            self.will_not_work(
                f"expression {name} does not exist in Spark "
                f"{self.conf.shims.version_prefix} (shim gate)")
            return
        if not self.conf.is_op_enabled("expression", name):
            self.will_not_work(
                f"expression {name} disabled by "
                f"spark.rapids.tpu.sql.expression.{name}")
            return
        rule = _EXPR_RULES.get(type(self.expr))
        if rule is None:
            self.will_not_work(f"expression {name} has no TPU rule")
            return
        for c in self.expr.children:
            if c.dtype is not None and not rule.input_sig.supports(c.dtype):
                self.will_not_work(
                    f"expression {name}: input type "
                    f"{c.dtype.simple_string} not supported")
        if self.expr.dtype is not None and \
                not rule.output_sig.supports(self.expr.dtype):
            self.will_not_work(
                f"expression {name}: output type "
                f"{self.expr.dtype.simple_string} not supported")
        for r in self.expr.unsupported_reasons(self.conf):
            self.will_not_work(f"expression {name}: {r}")


class AggMeta(BaseMeta):
    def __init__(self, fn: AggregateFunction, conf: TpuConf):
        super().__init__(conf)
        self.fn = fn

    def tag(self):
        name = type(self.fn).__name__
        if name in self.conf.shims.unavailable_expressions:
            self.will_not_work(
                f"aggregate {name} does not exist in Spark "
                f"{self.conf.shims.version_prefix} (shim gate)")
            return
        if _AGG_RULES.get(type(self.fn)) is None:
            self.will_not_work(f"aggregate {name} has no TPU rule")
            return
        for r in self.fn.unsupported_reasons(self.conf):
            self.will_not_work(f"aggregate {name}: {r}")


class PlanMeta(BaseMeta):
    """Wraps one logical node; subclasses add expression metas + convert."""

    def __init__(self, node: L.LogicalPlan, conf: TpuConf,
                 parent: Optional["PlanMeta"]):
        super().__init__(conf)
        self.node = node
        self.parent = parent
        self.children = [wrap_plan(c, conf, self) for c in node.children]
        self.expr_metas: List[ExprMeta] = []
        self.agg_metas: List[AggMeta] = []
        #: (bound expression, index of the child whose output it reads)
        self._expr_inputs: List[Tuple[E.Expression, int]] = []
        #: expressions of this node that read a wide decimal which an
        #: operator below computes on the device (set when tagged)
        self.wide_decimal_device = 0

    # -- wrap helpers ------------------------------------------------------
    def _wrap_exprs(self, exprs: Sequence[E.Expression],
                    schema: t.StructType, child: int = 0
                    ) -> List[E.Expression]:
        bound = []
        for e in exprs:
            try:
                b = e.bind(schema)
            except (KeyError, TypeError) as exc:
                self.will_not_work(f"cannot bind {e!r}: {exc}")
                continue
            self.expr_metas.append(ExprMeta(b, self.conf))
            self._expr_inputs.append((b, child))
            bound.append(b)
        return bound

    # -- where a wide decimal comes from ------------------------------------
    def wide_host_columns(self) -> frozenset:
        """The output columns of this node that are decimals wider than
        18 digits AND reach a device consumer as the two-lane (lo, hi)
        host value: whatever a scan or an operator placed on the CPU
        produces, and what a device operator hands through of such a
        column.  Every other wide decimal was computed by a device
        operator: one int64 unscaled lane (ops/decimal.py).  Valid once
        this node is tagged."""
        wide = _wide_decimal_names(self.node.schema)
        if not wide or not self.can_replace:
            return wide
        return wide & self._wide_host_through(wide)

    def _wide_host_through(self, wide: frozenset) -> frozenset:
        """For a node placed on the device: which of its `wide` output
        columns it hands through from a two-lane input.  A node that
        does not say is taken to hand through all of them."""
        return wide

    def _child_wide_host(self) -> frozenset:
        return frozenset().union(
            *(c.wide_host_columns() for c in self.children))

    @staticmethod
    def _wide_host_refs(exprs, names, below: frozenset) -> frozenset:
        """Of the outputs `names`, those whose expression is a plain
        (possibly aliased) reference to a column in `below`."""
        refs = ((E.plain_ref(e), name) for e, name in zip(exprs, names))
        return frozenset(name for ref, name in refs
                         if ref is not None and ref.name in below)

    def _mark_device_decimals(self) -> None:
        """Tell this node's bound expressions which of the wide decimals
        they read were computed on the device (children are tagged by
        now, so their placement is known)."""
        device_names: Dict[int, frozenset] = {}
        for b, child in self._expr_inputs:
            names = device_names.get(child)
            if names is None:
                meta = self.children[child]
                names = device_names[child] = _wide_decimal_names(
                    meta.node.schema) - meta.wide_host_columns()
            if names:
                self.wide_decimal_device += E.mark_device_decimals(b, names)

    # -- tagging -----------------------------------------------------------
    def tag(self):
        for c in self.children:
            c.tag()
        if not self.conf.sql_enabled:
            self.will_not_work("spark.rapids.tpu.sql.enabled is false")
            return
        name = self.node.name()
        key_name = type(self.node).__name__.removeprefix("Logical") + "Exec"
        if not self.conf.is_op_enabled("exec", key_name):
            self.will_not_work(
                f"exec {key_name} disabled by "
                f"spark.rapids.tpu.sql.exec.{key_name}")
        rule = _EXEC_RULES.get(type(self.node))
        if rule is None:
            self.will_not_work(f"operator {name} has no TPU rule")
        else:
            for f in self.node.schema.fields:
                if not rule.output_sig.supports(f.data_type):
                    self.will_not_work(
                        f"output column {f.name}: type "
                        f"{f.data_type.simple_string} not supported")
        self._mark_device_decimals()
        for em in self.expr_metas:
            em.tag()
            for r in em.reasons:
                self.will_not_work(r)
        for am in self.agg_metas:
            am.tag()
            for r in am.reasons:
                self.will_not_work(r)
        self.tag_self()

    def tag_self(self):
        pass

    # -- conversion --------------------------------------------------------
    def convert(self) -> Tuple[str, object]:
        """Returns ("device", PlanNode) or ("host", HostNode)."""
        if self.can_replace and not self.conf.explain_only:
            node = self.to_device()
            if self.wide_decimal_device:
                # kept with the node; a whole-plan program counts what
                # its nodes hold (`expr.wide_decimal_device`)
                node.wide_decimal_exprs = self.wide_decimal_device
            return "device", node
        return "host", self.to_host()

    def to_device(self) -> PlanNode:
        raise NotImplementedError

    def to_host(self) -> H.HostNode:
        raise NotImplementedError

    def _device_child(self, i: int = 0) -> PlanNode:
        kind, node = self.children[i].convert()
        if kind == "device":
            return node
        return _host_to_device(node)

    def _host_child(self, i: int = 0) -> H.HostNode:
        kind, node = self.children[i].convert()
        if kind == "host":
            return node
        return H.DeviceToHostExec(node)

    # -- explain -----------------------------------------------------------
    def explain_lines(self, depth: int = 0) -> List[str]:
        mark = "*" if self.can_replace else "!"
        line = f"{'  ' * depth}{mark}Exec <{self.node.name()}>"
        if self.can_replace:
            line += " will run on TPU"
        else:
            line += (" cannot run on TPU because "
                     + "; ".join(self.reasons[:4]))
            if len(self.reasons) > 4:
                line += f" (+{len(self.reasons) - 4} more)"
        out = [line]
        for c in self.children:
            out += c.explain_lines(depth + 1)
        return out


# ---------------------------------------------------------------------------
# Per-node metas
# ---------------------------------------------------------------------------

class ScanMeta(PlanMeta):
    def to_device(self):
        return HostScanExec.from_table(self.node.table,
                                       self.conf.batch_size_rows)

    def to_host(self):
        return H.HostSourceExec(self.node.table, self.conf.batch_size_rows)


class ProjectMeta(PlanMeta):
    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        self.bound = self._wrap_exprs(node.exprs, node.child.schema)

    def _wide_host_through(self, wide):
        return self._wide_host_refs(self.node.exprs, self.node.names,
                                    self._child_wide_host())

    def to_device(self):
        return ProjectExec(self.node.exprs, self.node.names,
                           self._device_child())

    def to_host(self):
        return H.CpuProjectExec(self.node.exprs, self.node.names,
                                self._host_child())


class FilterMeta(PlanMeta):
    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        self._wrap_exprs([node.condition], node.child.schema)

    def _wide_host_through(self, wide):
        return self._child_wide_host()

    def to_device(self):
        return FilterExec(self.node.condition, self._device_child())

    def to_host(self):
        return H.CpuFilterExec(self.node.condition, self._host_child())


class AggregateMeta(PlanMeta):
    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        schema = node.child.schema
        self._wrap_exprs(node.keys, schema)
        for fn, _name in node.aggs:
            # version-dependent agg semantics route through the shim seam
            # (shims.py) — both the device evaluate() and the CPU
            # cpu_agg() consult it, so the two paths stay oracles of
            # each other for any pinned Spark version
            fn._shims = conf.shims
            try:
                b = fn.bind(schema)
            except (KeyError, TypeError) as exc:
                self.will_not_work(f"cannot bind {fn!r}: {exc}")
                continue
            self.agg_metas.append(AggMeta(b, self.conf))
            if b.child is not None:
                self.expr_metas.append(ExprMeta(b.child, self.conf))
                self._expr_inputs.append((b.child, 0))

    def _wide_host_through(self, wide):
        # a key is handed through; what an aggregate function returns
        # was computed here
        return self._wide_host_refs(self.node.keys, self.node.key_names,
                                    self._child_wide_host())

    def _mark_device_decimals(self):
        super()._mark_device_decimals()
        # an aggregate over a plain reference consumes it itself
        for am in self.agg_metas:
            ref = am.fn.child is not None and E.plain_ref(am.fn.child)
            if ref and ref.device_computed:
                self.wide_decimal_device += 1

    def tag_self(self):
        # group keys must be single flat device lanes: ragged/nested
        # keys have no boundary comparison, and wide (p>18) decimals
        # carry a hi lane the groupby boundary/sort machinery ignores.
        # Keys here are UNBOUND (dtype None) — resolve via the child
        # schema before checking.
        for k, kn in zip(self.node.keys, self.node.key_names):
            try:
                kdt = k.bind(self.node.child.schema).dtype
            except Exception:                    # noqa: BLE001
                continue                         # binding tags elsewhere
            if isinstance(kdt, (t.ArrayType, t.MapType,
                                t.StructType, t.BinaryType)):
                self.will_not_work(
                    f"group key {kn}: {kdt.simple_string} keys have "
                    "no flat device lane")
            if isinstance(kdt, t.DecimalType) and kdt.is_wide:
                self.will_not_work(
                    f"group key {kn}: decimal({kdt.precision}) keys "
                    "carry a second lane the group-by cannot compare")
        # holistic aggregates (sort-based device execs) cannot mix with
        # streaming ones in one device aggregation — the reference
        # routes such plans through separate aggregations
        for family, label in self._holistic_split():
            if any(family) and not all(family):
                self.will_not_work(
                    f"{label} mixed with other aggregates (device path "
                    f"requires a uniform aggregation)")

    def _holistic_split(self):
        from .aggregates import CollectList, CountDistinct, Percentile
        aggs = self.node.aggs
        return (
            ([isinstance(fn, Percentile) for fn, _n in aggs],
             "percentile"),
            ([isinstance(fn, CountDistinct) for fn, _n in aggs],
             "count(DISTINCT)"),
            ([isinstance(fn, CollectList) for fn, _n in aggs],
             "collect_list/collect_set"),
        )

    def to_device(self):
        from .aggregates import CollectList, CountDistinct, Percentile
        from ..config import COLLECT_DEVICE_ENABLED
        if self.node.aggs and all(isinstance(fn, CollectList)
                                  for fn, _n in self.node.aggs) and \
                self.conf.get(COLLECT_DEVICE_ENABLED):
            from ..exec.collect import CollectAggregateExec
            return CollectAggregateExec(
                self.node.keys, self.node.key_names, self.node.aggs,
                self._device_child())
        if self.node.aggs and all(isinstance(fn, Percentile)
                                  for fn, _n in self.node.aggs):
            from ..exec.percentile import PercentileAggregateExec
            return PercentileAggregateExec(
                self.node.keys, self.node.key_names, self.node.aggs,
                self._device_child())
        if self.node.aggs and all(isinstance(fn, CountDistinct)
                                  for fn, _n in self.node.aggs):
            from ..exec.distinct import DistinctAggregateExec
            return DistinctAggregateExec(
                self.node.keys, self.node.key_names, self.node.aggs,
                self._device_child())
        return HashAggregateExec(self.node.keys, self.node.key_names,
                                 self.node.aggs, self._device_child())

    def to_host(self):
        return H.CpuAggregateExec(self.node.keys, self.node.key_names,
                                  self.node.aggs, self._host_child())


class SortMeta(PlanMeta):
    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        self._wrap_exprs([e for e, _, _ in node.orders], node.child.schema)

    def _wide_host_through(self, wide):
        return self._child_wide_host()

    def tag_self(self):
        schema = self.node.child.schema
        for e, _asc, _nf in self.node.orders:
            if not isinstance(e, E.ColumnRef):
                self.will_not_work(
                    f"sort key {e!r} is not a column reference "
                    "(planner pre-projection not yet implemented)")
                continue
            # wide decimal keys sort on device: two-lane (hi, lo) host
            # columns lexicographically, single-lane computed results
            # directly (ops/sort.py order_lanes)

    def to_device(self):
        from ..ops.sort import SortKey
        schema = self.node.child.schema
        keys = [SortKey(schema.field_index(e.name), asc, nf)
                for e, asc, nf in self.node.orders]
        return SortExec(keys, self._device_child(),
                        global_sort=self.node.global_sort)

    def to_host(self):
        return H.CpuSortExec(self.node.orders, self._host_child())


class LimitMeta(PlanMeta):
    def _wide_host_through(self, wide):
        return self._child_wide_host()

    def to_device(self):
        # Limit directly above a global Sort collapses into TopN
        # (reference GpuTopN, limit.scala): per-batch sort+cut keeps the
        # working set at the limit's bucket and, for single-batch
        # streams, runs with zero host syncs (whole-plan traceable).
        child_meta = self.children[0]
        if isinstance(child_meta, SortMeta) and child_meta.can_replace \
                and child_meta.node.global_sort:
            from ..exec.plan import TopNExec
            from ..ops.sort import SortKey
            schema = child_meta.node.child.schema
            keys = [SortKey(schema.field_index(e.name), asc, nf)
                    for e, asc, nf in child_meta.node.orders]
            return TopNExec(self.node.limit, keys,
                            child_meta._device_child())
        return GlobalLimitExec(self.node.limit, self._device_child())

    def to_host(self):
        return H.CpuLimitExec(self.node.limit, self._host_child())


class JoinMeta(PlanMeta):
    _DEVICE_TYPES = {"inner", "left_outer", "right_outer", "full_outer",
                     "left_semi", "left_anti", "cross"}

    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        self._wrap_exprs(node.left_keys, node.left.schema, child=0)
        self._wrap_exprs(node.right_keys, node.right.schema, child=1)

    def _wide_host_through(self, wide):
        return self._child_wide_host()

    def tag_self(self):
        if self.node.join_type not in self._DEVICE_TYPES:
            self.will_not_work(
                f"join type {self.node.join_type} not supported on TPU")

    def to_device(self):
        from ..config import ADAPTIVE_ENABLED
        from ..exec.adaptive import AdaptiveShuffledJoinExec, _MIRROR
        from ..exec.exchange import BroadcastExchangeExec
        from ..exec.join import CrossJoinExec, HashJoinExec
        left = self._device_child(0)
        right = self._device_child(1)
        if getattr(self.node, "broadcast", None) == "right":
            # GpuBroadcastHashJoinExec shape: the build side materializes
            # once and replays to every consumer / replica
            right = BroadcastExchangeExec(right)
        if self.node.join_type == "cross":
            return CrossJoinExec(left, right)
        if (self.conf.get(ADAPTIVE_ENABLED)
                and self.node.broadcast is None
                and (self.node.join_type in _MIRROR
                     or self.node.join_type == "left_semi")):
            # AQE analogue: defer the build-side choice to runtime sizes
            # (GpuShuffledSymmetricHashJoinExec.scala:354 role); an
            # explicit broadcast hint is a planner decision and wins.
            # left_semi never mirrors but qualifies for the bloom
            # runtime filter (unmatched probe rows are dropped anyway)
            return AdaptiveShuffledJoinExec(
                self.node.join_type, self.node.left_keys,
                self.node.right_keys, left, right)
        return HashJoinExec(self.node.join_type, self.node.left_keys,
                            self.node.right_keys, left, right)

    def to_host(self):
        return H.CpuJoinExec(self.node.join_type, self.node.left_keys,
                             self.node.right_keys,
                             self._host_child(0), self._host_child(1))


class UnionMeta(PlanMeta):
    def convert(self):
        kids = [c.convert() for c in self.children]
        if self.can_replace and not self.conf.explain_only:
            dev = [k if kind == "device" else _host_to_device(k)
                   for kind, k in kids]
            return "device", UnionExec(*dev)
        host = [k if kind == "host" else H.DeviceToHostExec(k)
                for kind, k in kids]
        return "host", H.CpuUnionExec(*host)


class RangeMeta(PlanMeta):
    def to_device(self):
        n = self.node
        return RangeExec(n.start, n.end, n.step, n.col_name)

    def to_host(self):
        n = self.node
        return H.CpuRangeExec(n.start, n.end, n.step, n.col_name)


class ExpandMeta(PlanMeta):
    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        for p in node.projections:
            self._wrap_exprs(p, node.child.schema)

    def to_device(self):
        return ExpandExec(self.node.projections, self.node.names,
                          self._device_child())

    def to_host(self):
        return H.CpuExpandExec(self.node.projections, self.node.names,
                               self._host_child())


class SampleMeta(PlanMeta):
    def to_device(self):
        return SampleExec(self.node.fraction, self.node.seed,
                          self._device_child())

    def to_host(self):
        return H.CpuSampleExec(self.node.fraction, self.node.seed,
                               self._host_child())


class ParquetScanMeta(PlanMeta):
    def tag_self(self):
        if not self.conf.get(ENABLED_FORMATS["parquet"]):
            self.will_not_work(
                "parquet scan disabled by "
                "spark.rapids.tpu.sql.format.parquet.enabled")

    def to_device(self):
        n = self.node
        return ParquetScanExec(n.paths, n.columns, n.schema, n.pushed_filter)

    def to_host(self):
        n = self.node
        return CpuParquetScanExec(n.paths, n.columns, n.schema,
                                  n.pushed_filter)


class TextScanMeta(PlanMeta):
    def tag_self(self):
        fmt = type(self.node).fmt
        if not self.conf.get(ENABLED_FORMATS[fmt]):
            self.will_not_work(
                f"{fmt} scan disabled by "
                f"spark.rapids.tpu.sql.format.{fmt}.enabled")

    def to_device(self):
        return TextScanExec(self.node, self.node.schema)

    def to_host(self):
        return CpuTextScanExec(self.node, self.node.schema)


class WindowMeta(PlanMeta):
    """LogicalWindow -> WindowExec (window/GpuWindowExec.scala:146 role).
    Window specs carry their own support checks (plan/window.py); ranking
    functions additionally require order keys, as Spark's analyzer does."""

    def __init__(self, node, conf, parent):
        super().__init__(node, conf, parent)
        schema = node.child.schema
        self._wrap_exprs(node.partition_keys, schema)
        self._wrap_exprs([e for e, _, _ in node.order_keys], schema)
        self.spec_metas = []
        for spec, _name in node.window_exprs:
            # bind failures (e.g. sum over string) are analysis errors, as
            # in Spark — the CPU path cannot run them either, so they raise
            # here rather than half-recording an unusable fallback
            b = spec.bind(schema)
            self.spec_metas.append(b)
            if b.child is not None:
                self.expr_metas.append(ExprMeta(b.child, self.conf))

    def tag_self(self):
        for b in self.spec_metas:
            name = type(b).__name__
            if not self.conf.is_op_enabled("expression", name):
                self.will_not_work(
                    f"window function {name} disabled by "
                    f"spark.rapids.tpu.sql.expression.{name}")
            for r in b.unsupported_reasons(self.conf):
                self.will_not_work(f"window function {b.name}: {r}")
        schema = self.node.child.schema
        for e, _a, _nf in self.node.order_keys:
            try:
                dt = e.bind(schema).dtype
            except (KeyError, TypeError):
                continue     # bind failure already recorded by _wrap_exprs
            if isinstance(dt, t.DecimalType) and dt.is_wide:
                self.will_not_work("decimal128 window order key "
                                   "not yet on device")
        # value-offset RANGE frames need ONE integer-lane order key
        # (merge-rank bounds are value arithmetic on that lane)
        if any(b.frame is not None and b.frame.is_value_offset
               for b in self.spec_metas):
            ok = len(self.node.order_keys) == 1
            if ok:
                try:
                    dt = self.node.order_keys[0][0].bind(schema).dtype
                    ok = isinstance(dt, (t.ByteType, t.ShortType,
                                         t.IntegerType, t.LongType,
                                         t.DateType, t.TimestampType))
                except (KeyError, TypeError):
                    ok = False
            if not ok:
                self.will_not_work(
                    "value-offset RANGE frame needs a single "
                    "integer/date/timestamp order key on device")

    def to_device(self):
        from ..exec.window import WindowExec
        return WindowExec(self.node.window_exprs, self.node.partition_keys,
                          self.node.order_keys, self._device_child())

    def to_host(self):
        return H.CpuWindowExec(self.node.window_exprs,
                               self.node.partition_keys,
                               self.node.order_keys, self._host_child())


class CacheMeta(PlanMeta):
    """LogicalCache -> cached scan (ParquetCachedBatchSerializer role).
    Materialization happens lazily at EXECUTE time (CachedHostScan), so
    plan conversion / explain never runs the child, and batches stream
    from the compressed buffer rather than decoding wholesale."""

    def to_device(self):
        from ..exec.cache import CachedHostScan
        return H.HostToDeviceExec(CachedHostScan(self.node, self.conf))

    def to_host(self):
        from ..exec.cache import CachedHostScan
        return CachedHostScan(self.node, self.conf)


class MapInPandasMeta(PlanMeta):
    """Pandas execs run on the host side of the plan by placement (the
    worker boundary is host Arrow, as in the reference's GPU->JVM->python
    hops); transitions bridge device children."""

    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuMapInPandasExec role)")

    def to_host(self):
        from ..exec.python_exec import MapInPandasExec
        return MapInPandasExec(self.node.fn, self.node.result_schema,
                               self._host_child())


class ArrowEvalPythonMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuArrowEvalPythonExec role)")

    def to_host(self):
        from ..exec.python_exec import ArrowEvalPythonExec
        return ArrowEvalPythonExec(self.node.udfs, self._host_child())


class FlatMapGroupsInPandasMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuFlatMapGroupsInPandasExec role)")

    def to_host(self):
        from ..exec.python_exec import FlatMapGroupsInPandasExec
        return FlatMapGroupsInPandasExec(
            self.node.key_names, self.node.fn, self.node.result_schema,
            self._host_child())


class FlatMapCoGroupsInPandasMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuFlatMapCoGroupsInPandasExec role)")

    def to_host(self):
        from ..exec.python_exec import FlatMapCoGroupsInPandasExec
        return FlatMapCoGroupsInPandasExec(
            self.node.left_keys, self.node.right_keys, self.node.fn,
            self.node.result_schema, self._host_child(0),
            self._host_child(1))


class AggregateInPandasMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuAggregateInPandasExec role)")

    def to_host(self):
        from ..exec.python_exec import AggregateInPandasExec
        return AggregateInPandasExec(self.node.key_names, self.node.aggs,
                                     self._host_child())


class WindowInPandasMeta(PlanMeta):
    def tag_self(self):
        self.will_not_work(
            "pandas UDFs execute in a python worker process "
            "(host Arrow boundary; GpuWindowInPandasExec role)")

    def to_host(self):
        from ..exec.python_exec import WindowInPandasExec
        return WindowInPandasExec(self.node.partition_names,
                                  self.node.order_names,
                                  self.node.windows, self._host_child())


class GenerateMeta(PlanMeta):
    """LogicalGenerate: explode/posexplode runs ON DEVICE over ragged
    values+offsets lanes (exec/generate.py — GpuGenerateExec.scala:829
    role) when

      * the generator input is a plain column reference with a
        device-supported element type,
      * no OTHER nested column rides along (row gathers would corrupt a
        second ragged lane), and
      * the PARENT operator provably never reads the exploded array
        column (Spark's GenerateExec.requiredChildOutput pruning —
        re-expanding each row's array per output element is quadratic).

    Anything else falls to CpuGenerateExec with transitions."""

    def tag_self(self):
        from .collections import _device_elem_ok
        gen = self.node.generator
        child_schema = self.node.child.schema
        arr = getattr(gen, "child", None)
        if not isinstance(arr, E.ColumnRef):
            self.will_not_work("generator input is not a column reference")
            return
        adt = child_schema[arr.name].data_type
        if not isinstance(adt, t.ArrayType) or not (
                _device_elem_ok(adt.element_type)
                or isinstance(adt.element_type, t.StringType)):
            self.will_not_work(
                f"array element type "
                f"{adt.element_type.simple_string if isinstance(adt, t.ArrayType) else adt.simple_string}"
                " has no ragged device lane")
            return
        for f in child_schema.fields:
            if f.name != arr.name and isinstance(
                    f.data_type, (t.ArrayType, t.MapType, t.StructType)):
                self.will_not_work(
                    f"second nested column {f.name} alongside the "
                    "exploded input (row gathers are flat)")
                return
        if not self._parent_prunes_input(arr.name):
            self.will_not_work(
                f"parent operator may read the exploded array column "
                f"{arr.name} (requiredChildOutput pruning not provable)")

    def _parent_prunes_input(self, arr_name: str) -> bool:
        p = self.parent
        if not isinstance(p, ProjectMeta):
            return False
        refs = set()

        def walk(e):
            if isinstance(e, E.ColumnRef):
                refs.add(e.name)
            for c in e.children:
                walk(c)
            body = getattr(e, "body", None)
            if body is not None:
                walk(body)
        for e in p.node.exprs:
            walk(e)
        return arr_name not in refs

    def to_device(self):
        from ..exec.generate import GenerateExec
        return GenerateExec(self.node.generator, self.node.output_names,
                            self._device_child())

    def to_host(self):
        return H.CpuGenerateExec(self.node.generator,
                                 self.node.output_names,
                                 self._host_child())


_META_FOR: Dict[type, Type[PlanMeta]] = {
    L.LogicalScan: ScanMeta,
    L.LogicalProject: ProjectMeta,
    L.LogicalFilter: FilterMeta,
    L.LogicalAggregate: AggregateMeta,
    L.LogicalSort: SortMeta,
    L.LogicalLimit: LimitMeta,
    L.LogicalJoin: JoinMeta,
    L.LogicalUnion: UnionMeta,
    L.LogicalRange: RangeMeta,
    L.LogicalExpand: ExpandMeta,
    L.LogicalSample: SampleMeta,
    L.LogicalWindow: WindowMeta,
    L.LogicalGenerate: GenerateMeta,
    L.LogicalMapInPandas: MapInPandasMeta,
    L.LogicalArrowEvalPython: ArrowEvalPythonMeta,
    L.LogicalFlatMapGroupsInPandas: FlatMapGroupsInPandasMeta,
    L.LogicalFlatMapCoGroupsInPandas: FlatMapCoGroupsInPandasMeta,
    L.LogicalAggregateInPandas: AggregateInPandasMeta,
    L.LogicalWindowInPandas: WindowInPandasMeta,
    LogicalCache: CacheMeta,
    LogicalParquetScan: ParquetScanMeta,
    LogicalCsvScan: TextScanMeta,
    LogicalJsonScan: TextScanMeta,
    LogicalOrcScan: TextScanMeta,
    LogicalAvroScan: TextScanMeta,
    LogicalIcebergScan: TextScanMeta,
    LogicalHiveTextScan: TextScanMeta,
}


class UnknownMeta(PlanMeta):
    """Nodes with no meta: always CPU (and no CPU impl -> plan error)."""

    def tag_self(self):
        self.will_not_work(
            f"operator {type(self.node).__name__} has no TPU rule")

    def to_host(self):
        raise NotImplementedError(
            f"no CPU fallback implementation for {type(self.node).__name__}")


def wrap_plan(node: L.LogicalPlan, conf: TpuConf,
              parent: Optional[PlanMeta] = None) -> PlanMeta:
    meta_cls = _META_FOR.get(type(node), UnknownMeta)
    return meta_cls(node, conf, parent)


# ---------------------------------------------------------------------------
# Entry point (GpuOverrides.applyOverrides analogue)
# ---------------------------------------------------------------------------

class PhysicalQuery:
    """Tagged + converted plan, ready to run."""

    def __init__(self, meta: PlanMeta, kind: str, root, conf: TpuConf):
        self.meta = meta
        self.kind = kind           # "device" | "host" at the root
        self.root = root
        self.conf = conf
        # (name, t0, t1) perf_counter ranges of the planning phases
        # (wrap/tag/convert), stamped by apply_overrides; the tracer
        # replays them as cat=plan spans at collect time
        self.plan_phases: List[tuple] = []
        # planning read nothing but the logical plan and the conf (every
        # node `self_contained`), and the conf arms no fault site, whose
        # counters live on this plan's root: set once by apply_overrides,
        # it is what lets a DataFrame collect through this plan again
        self.keepable = False
        if kind == "device":
            # one id per node (`HashJoinExec#4`) for EXPLAIN, the
            # per-node metrics, every whole-plan segment and, through
            # `jax.named_scope`, the device ops of a profiler trace
            from ..exec.metrics import assign_node_ids
            assign_node_ids(root)

    def explain(self) -> str:
        return "\n".join(self.meta.explain_lines())

    def explain_analyze(self, conf_overrides: Optional[Dict] = None):
        """EXPLAIN ANALYZE: run ONE profiled collect (trace.enabled +
        profile.segments forced on — whole-plan programs re-split at the
        known seam boundaries and every program execution records
        measured DEVICE wall) and return the attribution report: the
        plan tree annotated with measured ms, rows, bytes, gather
        volume and % of query wall per segment, plus the XLA static
        cost overlay (obs/attribution.py).  The caller's cached compiled
        plan is left untouched."""
        from ..obs.attribution import run_explain_analyze
        return run_explain_analyze(self, conf_overrides)

    def physical_tree(self) -> str:
        return self.root.tree_string()

    def reusable(self, ctx: ExecContext) -> bool:
        """Whether the collect that just ran through this plan under
        `ctx` leaves it fit to run the next one: a whole-plan program
        answered it and nothing else did.  The eager engine, an OOM
        replay and the out-of-core tier keep state on the plan's nodes
        that a plan made anew starts without."""
        m = ctx.metrics
        return bool(self.keepable
                    and m.get("whole_plan_compiled_queries") == 1
                    and not m.get("whole_plan_fallbacks")
                    and not m.get("query_oom_replays")
                    and not ctx.ooc_force)

    def release(self) -> None:
        """What a plan kept between collects lets go of: it holds no
        device memory that a plan made anew would not hold (its scans'
        uploads go back to the upload cache's keeping), and no planning
        phase of a collect gone by for the tracer to replay."""
        from ..exec.compiled import release_scan_uploads
        release_scan_uploads(self.root)
        self.plan_phases = []

    def fallback_reasons(self) -> List[str]:
        """Every tagger reason in the meta tree (depth-first) — the
        structured form of the '!Exec ... because ...' explain lines."""
        out, stack = [], [self.meta]
        while stack:
            m = stack.pop()
            for r in m.reasons:
                if r not in out:
                    out.append(r)
            stack.extend(getattr(m, "children", ()))
        return out

    def _instrumented(self, ctx: ExecContext):
        """Shared observability wiring: span tracer, per-op metrics,
        profiler trace, concurrency permit, budget counters
        (GpuTaskMetrics role).  The tracer gates on ctx.conf (not the
        planning conf) so a caller can profile one collect of an
        already-planned query."""
        import time as _time
        from contextlib import contextmanager
        from ..config import EVENT_LOG_DIR
        from ..exec.metrics import (instrument, profile_trace,
                                    publish_registry, should_instrument)
        from ..obs import memattr
        from ..obs.export import configure_plane
        from ..obs.recorder import FLIGHT_RECORDER
        from ..obs.registry import (ACTIVE_QUERIES, QUERIES_TOTAL,
                                    QUERY_WALL_MS)
        from ..obs.tracer import (NULL_TRACER, CollectSpan, bind_tracer,
                                  make_tracer, set_active)
        from ..runtime import faults
        from ..runtime.semaphore import device_permit

        def enter():
            # always-on plane: apply this query's conf (enabled flag,
            # recorder capacity, exporter start) before anything records
            configure_plane(ctx.conf)
            qseq = ctx.query_seq
            ACTIVE_QUERIES.add(1)
            FLIGHT_RECORDER.record("instant", "query_start", "query",
                                   {"plan_kind": self.kind}, query=qseq)
            tracer = make_tracer(ctx.conf)
            gq = ctx.metrics.get("serving.query_id")
            if tracer.enabled and gq is not None:
                # pool mode: adopt the supervisor's GLOBAL query id so
                # the event log is query_<gid>.jsonl — worker-local ids
                # could collide between workers in one pool run dir,
                # and stitching must be key-exact
                import os as _os
                tracer.query_id = int(gq)
                tracer.meta["global_query_id"] = int(gq)
                w = _os.environ.get("SPARK_RAPIDS_TPU_WORKER_ID")
                if w:
                    tracer.meta["worker"] = w
            early = bind_tracer(ctx, tracer)
            # chaos: conf-less sites (mesh exchange collectives) fire on
            # the active injector for this query's scope
            faults.set_active(faults.get_injector(ctx.conf))
            # memory-attribution recorder (obs/memattr.py): armed only
            # under profile.segments + profile.memory; set active so
            # the lazily-created MemoryBudget binds its watermark
            # events to THIS query's HBM timeline
            ctx._memattr = memattr.make_recorder(ctx.conf)
            memattr.set_active(ctx._memattr)
            if tracer.enabled:
                tracer.metrics = ctx.metrics
                tracer.meta["fallbacks"] = self.fallback_reasons()
                tracer.meta["plan_kind"] = self.kind
                for name, t0, t1 in self.plan_phases:
                    tracer.add_span(name, "plan", t0, t1,
                                    parent=early.get("tpu.plan"))
            # an admission-time cost prediction (serving seeds
            # predicted.* into ctx.metrics before collect) rides the
            # trace + event log next to what actually happened
            pred = {k: v for k, v in ctx.metrics.items()
                    if k.startswith("predicted.")}
            if pred:
                if tracer.enabled:
                    tracer.meta["prediction"] = pred
                tracer.instant("admission_prediction", "serving", **pred)
            set_active(tracer)
            return tracer, qseq

        @contextmanager
        def scope():
            t_start = _time.perf_counter()
            status = "ok"
            with CollectSpan(ctx, "scope_enter", "overhead.host_prep_ms"):
                tracer, qseq = enter()

            def write_log():
                tracer.finish(ctx.metrics)
                log_dir = str(ctx.conf.get(EVENT_LOG_DIR) or "")
                if log_dir:
                    ctx.metrics["event_log_files"] = tracer.write(log_dir)

            try:
                if should_instrument(self.conf):
                    with CollectSpan(ctx, "scope_enter",
                                     "overhead.host_prep_ms"):
                        instrument(self.root, ctx)
                with profile_trace(self.conf), \
                        device_permit(self.conf, ctx.metrics):
                    with tracer.span("query", "query"):
                        yield
                with CollectSpan(ctx, "finish", "overhead.finish_ms"):
                    # metrics accumulated as device scalars (lazy
                    # counts) coerce in ONE batched fetch at query end
                    import jax
                    lazy = {k: v for k, v in ctx.metrics.items()
                            if isinstance(v, jax.Array)}
                    if lazy:
                        ctx.bump("host_syncs")
                        for k, v in zip(lazy, jax.device_get(
                                list(lazy.values()))):
                            ctx.metrics[k] = v.item()
                    if ctx._budget is not None:
                        for k, v in ctx.budget.metrics.items():
                            ctx.metrics[f"memory.{k}"] = v
                    # measured working set + HBM timeline + the residual
                    # naked-reservation leak check (exec/metrics.py)
                    from ..exec.metrics import finish_memattr
                    finish_memattr(ctx)
                    publish_registry(ctx)
            except BaseException:
                status = "error"
                raise
            finally:
                with CollectSpan(ctx, "finish", "overhead.finish_ms"):
                    set_active(NULL_TRACER)
                    faults.set_active(faults.NULL_INJECTOR)
                    memattr.set_active(None)
                    wall_ms = (_time.perf_counter() - t_start) * 1e3
                    ACTIVE_QUERIES.add(-1)
                    QUERY_WALL_MS.observe(wall_ms)
                    QUERIES_TOTAL.inc(status=status, kind=self.kind)
                    # NOTE: the crash-dump writer (runtime/failure.py)
                    # runs before this finally (crash_capture is the
                    # inner cm), so a fatal fault's dump never contains
                    # this marker — under default conf its last flight
                    # event stays the fault instant itself
                    FLIGHT_RECORDER.record(
                        "instant", "query_end", "query",
                        {"status": status, "wall_ms": round(wall_ms, 3)},
                        query=qseq)
                if not ctx.open_spans:
                    ctx.query_seq = 0    # a context used again: a new query
                    if tracer.enabled:
                        write_log()
                elif tracer.enabled:
                    # DataFrame.collect()'s `tpu.collect` is still open:
                    # the event log is written when it has closed, so
                    # that it holds every span of the collect
                    ctx.open_spans[0].after = write_log
        return scope()

    def _whole_plan_enabled(self) -> bool:
        from ..config import MESH_ENABLED, WHOLE_PLAN_COMPILE
        mode = str(self.conf.get(WHOLE_PLAN_COMPILE)).upper()
        if mode == "OFF":
            return False
        if mode == "ON" or self.conf.get(MESH_ENABLED):
            # SPMD mesh execution rides the whole-plan program (GSPMD
            # partitions it across chips); mesh implies compile
            return True
        import jax
        return jax.default_backend() == "tpu"

    def collect(self, ctx: Optional[ExecContext] = None) -> pa.Table:
        ctx = ctx or ExecContext(self.conf)
        from ..exec import ooc as O
        from ..exec.metrics import record_history
        from ..obs.tracer import CollectSpan
        from ..runtime.failure import crash_capture, install_fault_injection
        # tpu.scope_enter, in its pieces: the in-wall setup before
        # execution starts (fault wiring, the scope's own entry, the
        # per-node metric wrappers, OOC election) — a named category of the wall decomposition
        # (obs/profile.wall_breakdown)
        with CollectSpan(ctx, "scope_enter", "overhead.host_prep_ms"):
            from ..plan.misc import set_current_input_file
            set_current_input_file("")   # provenance never leaks across queries
            from ..config import SESSION_TIMEZONE
            from ..plan.datetime import set_session_timezone
            set_session_timezone(str(self.conf.get(SESSION_TIMEZONE)))
            install_fault_injection(self.root, self.conf)
        with self._instrumented(ctx), crash_capture(self.conf, ctx):
            import time as _time
            if self.kind == "device":
                # proactive OOC election: the cost oracle's MEASURED
                # working-set history vs the HBM budget — an oversized
                # query runs spilled from the start (exec/ooc.py)
                with CollectSpan(ctx, "scope_enter",
                                 "overhead.host_prep_ms"):
                    O.elect_proactive(self, ctx)
            t0 = _time.perf_counter()
            out = self._collect_with_query_retry(ctx)
            # the performance-history feed: runs INSIDE crash_capture
            # (the `history` chaos site's fatal kind dumps classified;
            # ioerror skips the entry, the result below is untouched)
            with CollectSpan(ctx, "finish", "overhead.finish_ms"):
                record_history(self, ctx,
                               (_time.perf_counter() - t0) * 1e3)
            return out

    def prewarm(self, ctx: Optional[ExecContext] = None) -> bool:
        """AOT-compile this query's whole-plan program WITHOUT executing
        it — the --compile-only warmup hook (bench.py) and the serving
        plane's ahead-of-traffic compile.  Populates the in-process
        structure cache and, when spark.rapids.tpu.compile.cacheDir is
        set, the persistent on-disk cache.  For split plans only the
        first segment is statically known; later segments compile at
        run time (the background service pipelines them).  Returns True
        when a program is ready, False when this plan cannot compile
        ahead of time (host-kind, whole-plan off, host-decision plan)."""
        ctx = ctx or ExecContext(self.conf)
        if self.kind != "device" or not self._whole_plan_enabled():
            return False
        from ..exec.compiled import (_TRACE_FALLBACK_ERRORS, CompiledPlan,
                                     SplitCompiledPlan, build_plan)
        plan = getattr(self, "_compiled_plan", None)
        if plan is False:
            return False
        if plan is None:
            plan = build_plan(self.root, ctx)
        try:
            if isinstance(plan, SplitCompiledPlan):
                plan._install_leaves()
                try:
                    plan._segment(0, (), ctx).ensure_compiled(ctx)
                finally:
                    plan._restore_leaves()
            else:
                plan.ensure_compiled(ctx)
        except _TRACE_FALLBACK_ERRORS:
            self._compiled_plan = False
            return False
        self._compiled_plan = plan
        return True

    def _collect_once(self, ctx: ExecContext) -> pa.Table:
        if self.kind == "device" and self._whole_plan_enabled() and \
                not ctx.ooc_force:
            # an OOC-escalated context runs the EAGER batch engine: the
            # out-of-core tier (budget-registered spillables, partition
            # recursion) lives there, while compiled whole-plan programs
            # allocate their intermediates outside the budget's reach
            from ..exec.compiled import collect_with_fallback
            out = collect_with_fallback(self.root, ctx, cache_on=self)
            if out is not None:
                return out
        return self.root.collect(ctx)

    def _collect_with_query_retry(self, ctx: ExecContext) -> pa.Table:
        """The query-level rungs of the recovery ladder (the task-retry
        role).  An OOM that escapes every operator-level retry — the
        TpuSplitAndRetryOOM the exhausted split ladder raises included —
        first escalates into the OUT-OF-CORE rung: spill everything and
        replay with `ctx.ooc_force` armed, so every eligible hash join
        and aggregation runs spill-partitioned (exec/ooc.py).  Only an
        OOM that survives the OOC replay reaches the final whole-query
        replay rung.  Plans replay idempotently (pure operators;
        exchanges reuse their materialized shuffle ids), so the reruns
        are safe; anything non-OOM — or an OOM past the last rung —
        propagates for classification."""
        from ..config import RETRY_ENABLED
        from ..exec import ooc as O
        from ..runtime.memory import is_oom_error
        try:
            return self._collect_once(ctx)
        except Exception as e:                   # noqa: BLE001
            if not ctx.conf.get(RETRY_ENABLED) or not is_oom_error(e):
                raise
            if O.escalate(ctx):
                # the OOC rung: replay degraded instead of solo
                if ctx._budget is not None:
                    ctx.budget.spill_all()
                try:
                    return self._collect_once(ctx)
                except Exception as e2:          # noqa: BLE001
                    if not is_oom_error(e2):
                        raise
                    e = e2
            if ctx._budget is not None:
                ctx.budget.spill_all()
            ctx.bump("query_oom_replays")
            ctx.tracer.instant("query_replay", "runtime",
                               error=type(e).__name__)
            return self._collect_once(ctx)

    def execute_host_batches(self, ctx: Optional[ExecContext] = None):
        """Stream results as pyarrow RecordBatches (same permit/metrics
        scope as collect — the permit is held while the stream drains)."""
        ctx = ctx or ExecContext(self.conf)
        from ..config import SESSION_TIMEZONE
        from ..plan.datetime import set_session_timezone
        set_session_timezone(str(self.conf.get(SESSION_TIMEZONE)))
        if self.kind == "device":
            node = H.DeviceToHostExec(self.root)
        else:
            node = self.root
        with self._instrumented(ctx):
            yield from node.execute(ctx)

    def execute_device_batches(self, ctx: Optional[ExecContext] = None):
        """Stream results as DeviceBatches WITHOUT bringing them to host
        — the ColumnarRdd escape hatch (ColumnarRdd.scala:42 /
        InternalColumnarRddConverter role) for ML pipelines that feed
        query output straight into jax models.  Host-kind plans upload
        at the boundary (HostColumnarToGpu role)."""
        ctx = ctx or ExecContext(self.conf)
        if self.kind == "device":
            node = self.root
        else:
            # user-facing boundary: unlike the internal _host_to_device
            # transition (which prunes pass-through ballast), silently
            # dropping user-visible columns would be data loss — reject
            bad = [f.name for f in self.root.output_schema.fields
                   if isinstance(f.data_type,
                                 (t.ArrayType, t.MapType, t.StructType,
                                  t.BinaryType))]
            if bad:
                raise TypeError(
                    f"device_batches/to_jax: columns {bad} have no "
                    f"device lane representation; use collect() or "
                    f"execute_host_batches()")
            node = H.HostToDeviceExec(self.root)
        with self._instrumented(ctx):
            yield from node.execute(ctx)


def _plain_names(exprs):
    """Column names when every expression is a plain (possibly aliased)
    reference, else None."""
    names = []
    for e in exprs:
        inner = e.children[0] if isinstance(e, E.Alias) else e
        inner = E.ColumnRef(inner) if isinstance(inner, str) else inner
        if not isinstance(inner, E.ColumnRef):
            return None
        names.append(inner.name)
    return names


def _logical_keys_unique(plan: L.LogicalPlan, names) -> bool:
    """Logical-level distinctness: exact scan statistics propagated
    through uniqueness-preserving operators (conservative False when
    unknown) — the planner-side mirror of PlanNode.keys_unique."""
    if not names:
        return False
    if type(plan) is L.LogicalScan:
        from ..exec.plan import _table_keys_unique
        tbl = plan.table
        if any(n not in tbl.schema.names for n in names):
            return False
        return _table_keys_unique(tbl, tuple(names))
    if type(plan) in (L.LogicalFilter, L.LogicalLimit, L.LogicalSort):
        return _logical_keys_unique(plan.child, names)
    if type(plan) is L.LogicalProject:
        mapped = []
        for n in names:
            if n not in plan.names:
                return False
            ref = _plain_names([plan.exprs[plan.names.index(n)]])
            if ref is None:
                return False
            mapped.append(ref[0])
        return _logical_keys_unique(plan.child, mapped)
    if type(plan) is L.LogicalAggregate:
        return bool(plan.key_names) and \
            set(plan.key_names) <= set(names)
    return False


def _expr_refs(e, out: set) -> None:
    if isinstance(e, E.ColumnRef):
        out.add(e.name)
    for c in getattr(e, "children", ()) or ():
        if isinstance(c, E.Expression):
            _expr_refs(c, out)


#: (id(source table), kept column names) -> (weakref(source), pruned
#: table).  Replanning the same query used to build a FRESH
#: `table.select(...)` per plan, which broke every identity-anchored
#: cache downstream — the shared scan-upload cache re-uploaded per
#: replan, the PR 7 plan-executable anchors never matched, and the
#: serving result cache keyed each submit differently.  Memoizing the
#: pruned view (zero-copy: select() shares the source's buffers) makes
#: the pruned table a stable identity for the source's lifetime.
_PRUNED_SCAN_TABLES: dict = {}
_PRUNED_SCAN_LOCK = threading.Lock()


def _pruned_scan_table(table, names) -> object:
    key = (id(table), tuple(names))
    with _PRUNED_SCAN_LOCK:
        hit = _PRUNED_SCAN_TABLES.get(key)
        if hit is not None and hit[0]() is table:
            return hit[1]
    pruned = table.select(list(names))
    try:
        ref = weakref.ref(table, lambda _r, k=key:
                          _PRUNED_SCAN_TABLES.pop(k, None))
    except TypeError:
        return pruned
    with _PRUNED_SCAN_LOCK:
        return _PRUNED_SCAN_TABLES.setdefault(key, (ref, pruned))[1]


def prune_columns(plan: L.LogicalPlan, required=None) -> L.LogicalPlan:
    """Column-pruning pre-pass: narrow every in-memory scan to the
    columns the query actually reads (the Catalyst ColumnPruning /
    SchemaPruning role).  On TPU this matters more than on the CPU
    engine it was borrowed from: every surplus column is a full padded
    device lane that rides through every compaction, join gather and
    exchange of the plan (profiled: TPC-H q3 moved 27 lanes where 10
    carry the answer).

    Only structurally-understood operators participate; anything else
    (window, generate, expand, pandas execs, unions, file scans — which
    have their own reader-level pruning) conservatively requires its
    full input, and pruning continues below it."""
    if required is None:
        required = set(plan.schema.names)
    if type(plan) is L.LogicalScan:
        names = [n for n in plan.table.schema.names if n in required]
        if len(names) == len(plan.table.schema.names):
            return plan
        if not names:                 # keep row counts representable
            names = plan.table.schema.names[:1]
        return L.LogicalScan(_pruned_scan_table(plan.table, names))
    if type(plan) is L.LogicalProject:
        keep = [i for i, n in enumerate(plan.names) if n in required]
        if not keep:
            keep = [0]
        exprs = [plan.exprs[i] for i in keep]
        names = [plan.names[i] for i in keep]
        child_req: set = set()
        for e in exprs:
            _expr_refs(e, child_req)
        return L.LogicalProject(exprs, prune_columns(plan.child, child_req),
                                names)
    if type(plan) is L.LogicalFilter:
        req = set(required)
        _expr_refs(plan.condition, req)
        return L.LogicalFilter(plan.condition,
                               prune_columns(plan.child, req))
    if type(plan) is L.LogicalAggregate:
        req: set = set()
        for k in plan.keys:
            _expr_refs(k, req)
        for fn, _n in plan.aggs:
            # fn.inputs() needs a bound fn (derived lanes), but every
            # derived input is an expression over the declared children
            # (child / child2 for binary stats), so their refs cover it
            if fn.child is not None:
                _expr_refs(fn.child, req)
            child2 = getattr(fn, "child2", None)
            if child2 is not None:
                _expr_refs(child2, req)
        return L.LogicalAggregate(plan.keys, plan.aggs,
                                  prune_columns(plan.child, req),
                                  key_names=plan.key_names)
    if type(plan) is L.LogicalSort:
        req = set(required)
        for e, _asc, _nf in plan.orders:
            _expr_refs(e, req)
        out = L.LogicalSort(plan.orders, prune_columns(plan.child, req),
                            plan.global_sort)
        return out
    if type(plan) is L.LogicalLimit:
        return L.LogicalLimit(plan.limit,
                              prune_columns(plan.child, required))
    if type(plan) is L.LogicalJoin:
        lnames = set(plan.left.schema.names)
        rnames = set(plan.right.schema.names)
        lreq = {n for n in required if n in lnames}
        rreq = {n for n in required if n in rnames}
        join_type = plan.join_type
        left, right = plan.left, plan.right
        lk, rk = plan.left_keys, plan.right_keys
        broadcast = plan.broadcast
        # An inner join where ONE side contributes no output column and
        # has unique keys IS a semi join of the other side: each row
        # matches at most once and only existence matters.  The device
        # semi probe reads two offsets per row instead of gathering
        # every build lane at probe capacity — on TPU (row gathers
        # ~1.6 GB/s) this is the difference between a filter and a
        # materialization (q9's part join, q3's customer join, q5's
        # region join are pure filters of this shape).
        if join_type == "inner" and not rreq and \
                _logical_keys_unique(right, _plain_names(rk)):
            join_type = "left_semi"
        elif join_type == "inner" and not lreq and \
                _logical_keys_unique(left, _plain_names(lk)):
            join_type = "left_semi"
            left, right = right, left
            lk, rk = rk, lk
            lreq, rreq = rreq, lreq
            broadcast = None          # hint sides no longer apply
        for k in lk:
            _expr_refs(k, lreq)
        for k in rk:
            _expr_refs(k, rreq)
        return L.LogicalJoin(join_type,
                             prune_columns(left, lreq),
                             prune_columns(right, rreq),
                             lk, rk, broadcast=broadcast)
    # unknown operator: require everything it could read, keep pruning
    # below it (children rebuilt in place — node identity preserved)
    for i, c in enumerate(plan.children):
        plan.children[i] = prune_columns(c, set(c.schema.names))
    return plan


def _push_down_filters(plan: L.LogicalPlan) -> None:
    """Scan pushdown pre-pass: a Filter directly above a parquet scan hands
    its condition to the scan for row-group stat pruning (the filter itself
    stays — pruning is a bandwidth optimization, not an evaluation).
    Reference: GpuParquetFileFilterHandler row-group filtering."""
    if isinstance(plan, L.LogicalFilter) and \
            isinstance(plan.child, LogicalParquetScan):
        plan.child.pushed_filter = plan.condition
    for c in plan.children:
        _push_down_filters(c)


def _plan_uses_input_file_name(plan: L.LogicalPlan) -> bool:
    from .misc import InputFileName

    def expr_has(e) -> bool:
        return isinstance(e, InputFileName) or \
            any(expr_has(c) for c in getattr(e, "children", ()))

    def any_expr(items) -> bool:
        for item in items:
            if isinstance(item, E.Expression):
                if expr_has(item):
                    return True
            elif isinstance(item, (tuple, list)) and item:
                # (expr, asc, nf) orders, (fn, name) aggs,
                # (spec, name) window exprs, Expand projection rows
                head = item[0]
                if isinstance(head, E.Expression) and expr_has(head):
                    return True
                child = getattr(head, "child", None)
                if isinstance(child, E.Expression) and expr_has(child):
                    return True
                if isinstance(head, (tuple, list)) and any_expr(item):
                    return True
        return False

    for node in _walk(plan):
        for attr in ("exprs", "keys", "left_keys", "right_keys",
                     "partition_keys", "aggs", "orders", "order_keys",
                     "window_exprs", "projections"):
            if any_expr(getattr(node, attr, ())):
                return True
        cond = getattr(node, "condition", None)
        if cond is not None and expr_has(cond):
            return True
    return False


def _walk(plan: L.LogicalPlan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


def apply_overrides(plan: L.LogicalPlan,
                    conf: TpuConf = DEFAULT_CONF) -> PhysicalQuery:
    """wrapAndTagPlan + doConvertPlan + explain logging.

    Phase wall times (rewrite / wrap+tag / convert) are stamped on the
    returned PhysicalQuery; the query tracer replays them as cat=plan
    spans so the profile shows planning cost next to execution."""
    import time as _time
    from ..runtime.failure import faults_armed
    keepable = not faults_armed(conf) and all(
        node.self_contained for node in _walk(plan))
    phases = []
    t0 = _time.perf_counter()
    if conf.sql_enabled:
        # nested-type shatter only matters for device placement; the
        # pure-CPU engine (oracle) keeps the original nested plan
        from .structs import shatter_nested
        plan = shatter_nested(plan)
    plan = prune_columns(plan)
    _push_down_filters(plan)
    if _plan_uses_input_file_name(plan):
        # the InputFileBlockRule role: COALESCING stitches row groups of
        # many files into one batch (mixed provenance -> ""), so
        # input_file_name forces the per-file reader
        from ..config import PARQUET_READER_TYPE
        conf = TpuConf({**conf._raw, PARQUET_READER_TYPE.key: "PERFILE"})
    t1 = _time.perf_counter()
    phases.append(("plan.rewrite", t0, t1))
    meta = wrap_plan(plan, conf)
    meta.tag()
    from ..config import CBO_ENABLED
    if conf.get(CBO_ENABLED):
        from .cbo import apply_cbo
        apply_cbo(meta)
    mode = conf.explain
    if mode != "NONE":
        for line in meta.explain_lines():
            if mode == "ALL" or line.lstrip().startswith("!"):
                log.info(line)
    t2 = _time.perf_counter()
    phases.append(("plan.wrap_tag", t1, t2))
    kind, root = meta.convert()
    if kind == "device":
        from ..config import JOIN_LATE_MATERIALIZATION, JOIN_LAZY_SELECTION
        _dedupe_agg_twins(root)
        if conf.get(JOIN_LAZY_SELECTION):
            _negotiate_lazy_sel(root)
        if conf.get(JOIN_LATE_MATERIALIZATION):
            _negotiate_thin(root)
        from ..ops.encodings import encoding_policy
        if encoding_policy(conf).narrow_lanes:
            _negotiate_encoded(root)
    phases.append(("plan.convert", t2, _time.perf_counter()))
    pq = PhysicalQuery(meta, kind, root, conf)
    pq.plan_phases = phases
    pq.keepable = keepable
    return pq


def _negotiate_lazy_sel(root) -> None:
    """Mark joins whose parent consumes liveness as a MASK so they skip
    output compaction (DeviceBatch.sel, the JoinGatherer-deferred-gather
    role): aggregations fold the mask into their live lane, a parent
    join folds it into probe liveness, projections pass it through.  Row
    gathers dominate device time on TPU, so every skipped compaction is
    a full stacked gather pass saved."""
    from ..exec.adaptive import AdaptiveShuffledJoinExec
    from ..exec.join import HashJoinExec
    from ..exec.plan import FilterExec, HashAggregateExec, ProjectExec

    def producer(node):
        # look through the mask-transparent chain (filters fold the mask
        # into their predicate; projections propagate sel)
        while isinstance(node, (FilterExec, ProjectExec)):
            node = node.child
        if isinstance(node, (HashJoinExec, AdaptiveShuffledJoinExec)):
            return node
        return None

    seen = set()

    def walk(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        if isinstance(node, HashAggregateExec):
            p = producer(node.child)
            if p is not None:
                p.lazy_sel = True
        elif isinstance(node, (HashJoinExec, AdaptiveShuffledJoinExec)):
            p = producer(node.left)      # probe side only
            if p is not None:
                p.lazy_sel = True
        for c in node.children:
            walk(c)

    walk(root)


def _negotiate_thin(root) -> None:
    """Per-pipeline legality pass for join LATE MATERIALIZATION
    (columnar/lanes.py): mark every equi-join whose consumer chain —
    through the thin-TRANSPARENT operators (project passes deferred
    refs through as lanes, filter composes its mask into the selection
    vector) — terminates in a thin-aware pipeline SINK (one that
    resolves deferred columns with composed gathers: aggregate build,
    sort, exchange, coalesce/limit, another join, or the whole-plan
    program boundary).  A marked join emits THIN batches: payload
    columns ride as row-id lanes instead of being gathered per probe
    batch; runtime hooks force early materialization of exactly the
    columns a mid-pipeline condition/projection/key actually references,
    so the pass only needs chain SAFETY, not per-column reference
    tracking.  Consumers not on the lists below (windows, generate,
    python/host boundaries, user-facing device streams) keep dense
    inputs — their producing joins simply stay unmarked."""
    from ..exec.adaptive import AdaptiveShuffledJoinExec
    from ..exec.collect import CollectAggregateExec
    from ..exec.distinct import DistinctAggregateExec
    from ..exec.exchange import (BroadcastExchangeExec,
                                 ShuffleExchangeExec, ShuffleReadExec)
    from ..exec.join import HashJoinExec
    from ..exec.plan import (CoalesceBatchesExec, ExpandExec, FilterExec,
                             HashAggregateExec, LocalLimitExec,
                             ProjectExec, SortExec, TopNExec)

    transparent = (ProjectExec, FilterExec)
    sinks = (HashAggregateExec, SortExec, TopNExec, CoalesceBatchesExec,
             LocalLimitExec, ShuffleExchangeExec, ShuffleReadExec,
             BroadcastExchangeExec, CollectAggregateExec,
             DistinctAggregateExec, ExpandExec)

    allowed: dict = {}       # id(join) -> AND over every consumer path
    joins: dict = {}

    def walk(node, thin_ok: bool):
        if isinstance(node, (HashJoinExec, AdaptiveShuffledJoinExec)):
            allowed[id(node)] = allowed.get(id(node), True) and thin_ok
            joins[id(node)] = node
            for c in node.children:
                # both sides handle thin inputs: the probe path via
                # _prep_probe (pass lanes through or materialize refs),
                # the build path via concat/scatter materialization
                walk(c, True)
        elif isinstance(node, transparent):
            walk(node.child, thin_ok)
        elif isinstance(node, sinks):
            for c in node.children:
                walk(c, True)
        else:
            for c in node.children:
                walk(c, False)

    # the root's own consumer is the result boundary: the compiled
    # program materializes thin outputs inside the trace and the eager
    # fetch path resolves them in to_host — but execute_device_batches
    # hands raw batches to users, so the root chain stays conservative
    walk(root, False)
    for nid, node in joins.items():
        if allowed[nid]:
            node.thin_payload = frozenset(node.output_schema.names)


def _dedupe_agg_twins(root) -> None:
    """Plan-level CSE for aggregate subtrees: a grouped view referenced
    several times in one query (q15's revenue view — read directly AND
    under its own MAX subquery) converts into structurally identical
    but SEPARATE physical subtrees, so every execution tier pays the
    expensive collapse once per reference.  Re-point later references
    at the FIRST subtree object: whole-plan traces emit the shared ops
    once (XLA CSE holds by construction), and the seam-split compiler
    materializes the shared aggregate in ONE segment with every parent
    reading the seam leaf (exec/compiled._swap_child replaces all
    links) — measured 2x on q15 at SF1.  Identity = FULL expression
    fingerprints + node extras + SOURCE-TABLE identity per scan (the
    structural-key walk of exec/compiled.py, with literal values and
    tables kept: q56-class per-channel aggregates are shape-identical
    over DIFFERENT fact tables and must never merge); any node class
    outside the canonical key's coverage makes its subtree
    non-dedupable.  Sharing is sound because physical nodes hold no
    per-execution state."""
    from ..exec.compiled import _node_exprs, _node_extras
    from ..exec.plan import HashAggregateExec, HostScanExec

    def fp(n) -> "Optional[str]":
        exprs = _node_exprs(n)
        if exprs is None:
            return None
        parts = [type(n).__name__,
                 ";".join(e.fingerprint() for e in exprs),
                 repr(_node_extras(n))]
        if isinstance(n, HostScanExec):
            if n._source_table is None:
                return None           # no stable source identity
            parts.append(f"tbl{id(n._source_table)}")
        for c in n.children:
            cfp = fp(c)
            if cfp is None:
                return None
            parts.append(cfp)
        return "(" + "|".join(parts) + ")"

    by_fp: dict = {}
    seen = set()

    def walk(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for i, c in enumerate(node.children):
            if isinstance(c, HashAggregateExec):
                cfp = fp(c)
                if cfp is not None:
                    first = by_fp.get(cfp)
                    if first is None:
                        by_fp[cfp] = c
                    elif first is not c:
                        node.children[i] = c = first
            walk(c)

    walk(root)


def _negotiate_encoded(root) -> None:
    """Per-pipeline legality pass for ENCODED scan uploads
    (ops/encodings.py FOR-narrowed lanes), mirroring _negotiate_thin:
    a scan's columns may stay encoded (value-preserving narrow dtypes)
    while every consumer up the chain either computes on encoded lanes
    (comparisons/arithmetic in plan/expressions.py), is representation-
    agnostic (filters, compaction, joins and group-bys over canonical
    int64 lanes, sorts — all promote via plain dtype widening, which is
    exact for value-preserving narrowing), or is a SINK that decodes on
    entry (host fetch, exchange serialization).  Consumers outside the
    whitelist — window partitioning, generate, python/host boundaries,
    device-resident seams whose representation another program already
    baked — keep full-width scans: the decode is sunk to the scan
    instead of risking a consumer that assumes physical dtypes.  The
    verdict is per SCAN; sorted-dictionary encoding needs no
    negotiation (a pure representation change every consumer already
    handles)."""
    from ..exec.adaptive import AdaptiveShuffledJoinExec
    from ..exec.collect import CollectAggregateExec
    from ..exec.distinct import DistinctAggregateExec
    from ..exec.exchange import (BroadcastExchangeExec,
                                 ShuffleExchangeExec, ShuffleReadExec)
    from ..exec.join import CrossJoinExec, HashJoinExec
    from ..exec.plan import (CoalesceBatchesExec, ExpandExec, FilterExec,
                             GlobalLimitExec, HashAggregateExec,
                             HostScanExec, LocalLimitExec, ProjectExec,
                             SampleExec, SortExec, TopNExec, UnionExec)

    safe = (ProjectExec, FilterExec, HashJoinExec,
            AdaptiveShuffledJoinExec, CrossJoinExec, HashAggregateExec,
            CollectAggregateExec, DistinctAggregateExec, SortExec,
            TopNExec, CoalesceBatchesExec, GlobalLimitExec,
            LocalLimitExec, UnionExec, ExpandExec, SampleExec,
            ShuffleExchangeExec, ShuffleReadExec, BroadcastExchangeExec)

    allowed: dict = {}
    scans: dict = {}

    def walk(node, enc_ok: bool):
        if isinstance(node, HostScanExec):
            allowed[id(node)] = allowed.get(id(node), True) and enc_ok
            scans[id(node)] = node
            return
        ok = enc_ok and isinstance(node, safe)
        for c in node.children:
            walk(c, ok)

    # the root boundary is fine encoded: result fetch widens on host
    walk(root, True)
    for nid, node in scans.items():
        node.encoded_cols = frozenset(node.output_schema.names) \
            if allowed[nid] else None


# ---------------------------------------------------------------------------
# supported_ops doc generation (reference TypeChecks -> docs/supported_ops.md)
# ---------------------------------------------------------------------------

def generate_supported_ops() -> str:
    lines = ["# Supported expressions and operators", "",
             "Generated from the overrides rule registry "
             "(plan/overrides.py).", "",
             "## Execs", "", "| operator | supported output types |",
             "|---|---|"]
    for cls, rule in sorted(_EXEC_RULES.items(), key=lambda kv: kv[0].__name__):
        lines.append(f"| {cls.__name__.removeprefix('Logical')} | "
                     f"{', '.join(sorted(rule.output_sig.tags))} |")
    lines += ["", "## Expressions", "",
              "| expression | input types | output types |", "|---|---|---|"]
    for cls, rule in sorted(_EXPR_RULES.items(), key=lambda kv: kv[0].__name__):
        lines.append(f"| {cls.__name__} | "
                     f"{', '.join(sorted(rule.input_sig.tags))} | "
                     f"{', '.join(sorted(rule.output_sig.tags))} |")
    lines += ["", "## Aggregate functions", "",
              "| function | input types |", "|---|---|"]
    for cls, rule in sorted(_AGG_RULES.items(), key=lambda kv: kv[0].__name__):
        lines.append(f"| {cls.__name__} | "
                     f"{', '.join(sorted(rule.input_sig.tags))} |")
    lines += ["", "## DECIMAL128: what runs on the device", "",
              "DECIMAL128 in the tables above is a decimal wider than 18 "
              "digits. Whether an operator over one runs on the device "
              "follows from where the column comes from in the plan "
              "(`PlanMeta.wide_host_columns`), not from its type:", "",
              "* **computed on the device** (an aggregate's sum, an "
              "arithmetic result, by an operator that is itself placed "
              "on the device): one int64 unscaled lane. Comparisons, "
              "arithmetic, casts and aggregates over it run on the "
              "device, exactly; a value past int64's unscaled range is "
              "null where Spark's 128-bit arithmetic would hold it "
              "(ops/decimal.py), and a comparison with null is null. "
              "`having sum(l_quantity) > 300` over decimal(12,2) is "
              "this case. A collect counts such expressions in "
              "`expr.wide_decimal_device`.",
              "* **arrives from the host** (a scan, or the output of an "
              "operator that was placed on the CPU, handed through "
              "filters, projections of plain references, sorts, limits "
              "and joins): the two-lane (lo, hi) value. It is scanned, "
              "handed through, sorted on and fetched on the device; "
              "anything that would compute over it falls back to the "
              "CPU with `128-bit host decimal lane not consumable on "
              "device`. Group-by keys, window order keys, "
              "count(DISTINCT) and collect_list/collect_set of any "
              "DECIMAL128 stay on the CPU."]
    lines += ["", "## TPC-DS tranche status", "",
              "First tranche of the TPC-DS corpus "
              "(spark_rapids_tpu/tpcds.py QUERIES); every registered "
              "query is tier-1 oracle-tested at tiny scale "
              "(tests/test_tpcds.py) and benchmarked by "
              "`bench.py --suite tpcds`, which also emits the "
              "fallback/coverage matrix.", "",
              "| query | operator shape |", "|---|---|"]
    from .. import tpcds
    for name in sorted(tpcds.QUERIES, key=lambda q: int(q[1:])):
        doc = (tpcds.QUERIES[name].__doc__ or "").strip()
        para = " ".join(ln.strip()
                        for ln in doc.split("\n\n")[0].splitlines())
        lines.append(f"| {name} | {para} |")
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":
    import pathlib
    out = pathlib.Path(__file__).resolve().parent.parent.parent / "docs"
    out.mkdir(exist_ok=True)
    (out / "supported_ops.md").write_text(generate_supported_ops())
    print(f"wrote {out / 'supported_ops.md'}")
