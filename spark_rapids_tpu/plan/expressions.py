"""Expression trees: the Catalyst-expression analogue with dual backends.

Reference roles played here (SURVEY §2.5):
  * `GpuExpression.columnarEval` -> `eval_dev`, traced under jax.jit. The
    whole projection/filter of an operator traces into ONE XLA program, so
    "AST compilation" (reference ai.rapids.cudf.ast / convertToAst) is free:
    tracing IS the AST compile, and XLA fuses the elementwise pipeline.
  * CPU fallback per expression -> `eval_cpu` over pyarrow arrays with
    Spark semantics. This is both the fallback engine (unsupported exprs run
    on host, like the reference's per-operator CPU fallback) and the test
    oracle (reference strategy §4: same query, two backends, compare).
  * Tag-time support checks -> `unsupported_reasons`, collected by the
    overrides engine into fallback explanations.

Evaluation protocol per batch (two phases, see columnar/device.py on why):
  1. host `prepare`: bottom-up walk computing dictionary-derived metadata
     (literal code lookups, transformed dictionaries, per-dict predicate
     masks) and registering small device aux arrays. Deterministic preorder
     so aux slot indices are stable across batches of the same tree.
  2. device `eval_dev`: traced inside jit; consumes input column lanes and
     the aux arrays positionally.

Spark (non-ANSI) semantics encoded here: integer ops wrap like Java;
divide/remainder by zero -> NULL; three-valued AND/OR (Kleene); comparisons
null-out when either side is null; NaN handling per Spark (NaN == NaN in
sorting; see individual ops).
"""
from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as t
from ..config import TpuConf
from ..ops.kernels import compute_dtype, merge_validity


class PrepCtx:
    """Host-phase context: collects device aux arrays in deterministic order."""

    def __init__(self, conf: TpuConf, dicts: Dict[str, Optional[pa.Array]],
                 batch=None, lift_literals: bool = False):
        self.conf = conf
        self.dicts = dicts            # input column name -> dictionary or None
        self.batch = batch            # the DeviceBatch under evaluation
        self.aux: List[np.ndarray] = []
        self.node_slots: Dict[int, List[int]] = {}
        # per-node prepare-time decisions eval_dev must follow exactly
        # (encoded-execution path choices: code-space vs rank-table vs
        # legacy remap — ops/encodings.py); keyed like node_slots
        self.node_info: Dict[int, object] = {}
        # constant lifting (sql.compile.constantLifting): eligible
        # Literals route their value through the aux channel — a runtime
        # ARGUMENT of the compiled program — instead of a baked constant,
        # so programs key on expression structure, not literal values
        self.lift_literals = lift_literals
        self._parents: List["Expression"] = []

    def add(self, node: "Expression", arr) -> None:
        self.node_slots.setdefault(id(node), []).append(len(self.aux))
        # whole-plan tracing hands lifted literal values in as TRACERS of
        # the outer program — pass them through untouched (they become
        # arguments of the inner jit, never closure-captured constants)
        if not isinstance(arr, (jax.Array, jax.core.Tracer)):
            arr = np.asarray(arr)
        self.aux.append(arr)

    def current_parent(self) -> Optional["Expression"]:
        """The expression whose children are being prepared (None at a
        projection/predicate root)."""
        return self._parents[-1] if self._parents else None


# -- whole-plan literal bindings --------------------------------------------
# While exec/compiled.py traces a whole-plan program, lifted literal
# values enter the program as flat TOP-LEVEL inputs; the binding maps
# each Literal (by identity) to its traced scalar so Literal._prepare
# hands the tracer — not the host value — into the aux channel.
# Thread-local: background compiles trace concurrently.

_LIFT_BINDINGS = threading.local()


def set_literal_bindings(bindings: Optional[Dict[int, object]]) -> None:
    """Install (or clear, with None) the id(Literal) -> traced scalar
    map for the whole-plan trace running on THIS thread."""
    _LIFT_BINDINGS.map = bindings


def get_literal_binding(lit: "Expression"):
    m = getattr(_LIFT_BINDINGS, "map", None)
    return None if m is None else m.get(id(lit))


class HostVal:
    """Per-node host metadata flowing through prepare (dictionaries)."""

    def __init__(self, dictionary: Optional[pa.Array] = None):
        self.dictionary = dictionary


class EvalCtx:
    """Device-phase context available while tracing eval_dev."""

    def __init__(self, capacity: int, num_rows, inputs, aux, node_slots,
                 conf, raw=None, node_info=None):
        self.capacity = capacity
        self.num_rows = num_rows
        self.inputs = inputs          # name -> DevVal
        self.aux = aux                # tuple of jnp arrays (positional)
        self.node_slots = node_slots
        self.conf = conf
        # name -> STORAGE lane (DOUBLE keeps its int64 f64-bits form when
        # host-scanned) — consumers needing bit-exact lanes (hash) read it
        self.raw = raw or {}
        # prepare-time encoded-path decisions (PrepCtx.node_info)
        self.node_info = node_info or {}

    def aux_of(self, node: "Expression") -> List[jax.Array]:
        return [self.aux[i] for i in self.node_slots.get(id(node), [])]

    def info_of(self, node: "Expression"):
        return self.node_info.get(id(node))


class DevVal:
    """A traced column value: compute-representation lane + validity.

    `hi` carries the high int64 lane of a HOST-scanned wide (p>18)
    decimal; device-computed wide results are single-lane (hi None).
    Ragged ARRAY values carry `offsets` (int32, rows+1) + `elem_valid`
    (per flat value) with `data` as the flat values lane."""

    def __init__(self, data, validity, dtype: t.DataType,
                 dictionary: Optional[pa.Array] = None, hi=None,
                 offsets=None, elem_valid=None, narrow=None):
        self.data = data
        self.validity = validity      # None = all rows valid
        self.dtype = dtype
        self.dictionary = dictionary
        self.hi = hi
        self.offsets = offsets
        self.elem_valid = elem_valid
        # FOR-narrowed storage lane (ops/encodings.py): same values as
        # `data` in a smaller signed dtype; encoded-aware consumers
        # (comparisons, narrow arithmetic) compute on it, everything
        # else reads the full-width `data` view
        self.narrow = narrow


class Expression:
    children: Tuple["Expression", ...] = ()
    dtype: t.DataType = None
    nullable: bool = True
    #: True when this node consumes literal children ONLY through their
    #: traced DevVal (never reading `.value` on the host to specialize a
    #: kernel) — the gate for constant lifting.  Conservative default
    #: False: an unmarked parent keeps its literal children baked into
    #: the program and keyed by value.
    lifts_literal_children = False

    # ---- resolution ----
    def bind(self, schema: t.StructType) -> "Expression":
        """Return a copy with children bound and dtype resolved."""
        bound = self._with_children([c.bind(schema) for c in self.children])
        bound._resolve()
        return bound

    def _with_children(self, kids) -> "Expression":
        import copy
        c = copy.copy(self)
        c.children = tuple(kids)
        return c

    def _resolve(self):
        raise NotImplementedError(type(self).__name__)

    # ---- tagging ----
    def unsupported_reasons(self, conf: TpuConf) -> List[str]:
        """Reasons THIS node can't run on device ([] = supported)."""
        return []

    def tree_unsupported(self, conf: TpuConf) -> List[str]:
        out = []
        if not conf.is_op_enabled("expression", type(self).__name__):
            out.append(f"{type(self).__name__} disabled by conf")
        out += [f"{type(self).__name__}: {r}"
                for r in self.unsupported_reasons(conf)]
        for c in self.children:
            out += c.tree_unsupported(conf)
        return out

    # ---- host phase ----
    def prepare(self, pctx: PrepCtx) -> HostVal:
        # the parent stack lets Literal._prepare see WHOSE child it is:
        # lifting is only legal under parents that never host-read the
        # literal value (lifts_literal_children)
        pctx._parents.append(self)
        try:
            kids = [c.prepare(pctx) for c in self.children]
        finally:
            pctx._parents.pop()
        return self._prepare(pctx, kids)

    def _prepare(self, pctx: PrepCtx, kids: List[HostVal]) -> HostVal:
        return HostVal()

    # ---- device phase (traced) ----
    def eval_dev(self, ctx: EvalCtx) -> DevVal:
        kids = [c.eval_dev(ctx) for c in self.children]
        return self._eval_dev(ctx, kids)

    def _eval_dev(self, ctx: EvalCtx, kids: List[DevVal]) -> DevVal:
        raise NotImplementedError(type(self).__name__)

    # ---- CPU fallback / oracle ----
    def eval_cpu(self, rb: pa.RecordBatch) -> pa.Array:
        kids = [c.eval_cpu(rb) for c in self.children]
        return self._eval_cpu(rb, kids)

    def _eval_cpu(self, rb, kids) -> pa.Array:
        raise NotImplementedError(type(self).__name__)

    # ---- identity ----
    def fingerprint(self) -> str:
        kids = ",".join(c.fingerprint() for c in self.children)
        return f"{type(self).__name__}({self._fp_extra()};{kids})"

    def canonical_fingerprint(self, lift_ok: bool = True) -> str:
        """Structure fingerprint with LIFTED literal values erased to a
        dtype-only slot marker: the compile-cache key under constant
        lifting.  `lift_ok` carries the parent-safety bit down the tree
        (top-level call = root position = liftable) and must mirror
        Literal._prepare's lift decision exactly — a value this
        fingerprint hides is a value the program receives at runtime."""
        kids = ",".join(
            c.canonical_fingerprint(self.lifts_literal_children)
            for c in self.children)
        return f"{type(self).__name__}({self._fp_extra()};{kids})"

    def _fp_extra(self) -> str:
        return ""

    def __repr__(self):
        return self.fingerprint()

    # ---- Column-style operator sugar (pyspark Column analogue) ----
    @staticmethod
    def _lift(v) -> "Expression":
        return v if isinstance(v, Expression) else Literal(v)

    def alias(self, name: str) -> "Expression":
        return Alias(self, name)

    def cast(self, to: "t.DataType") -> "Expression":
        return Cast(self, to)

    def __add__(self, o):
        return Add(self, self._lift(o))

    def __sub__(self, o):
        return Subtract(self, self._lift(o))

    def __mul__(self, o):
        return Multiply(self, self._lift(o))

    def __truediv__(self, o):
        return Divide(self, self._lift(o))

    def __mod__(self, o):
        return Remainder(self, self._lift(o))

    def __neg__(self):
        return UnaryMinus(self)

    def __gt__(self, o):
        return GreaterThan(self, self._lift(o))

    def __ge__(self, o):
        return GreaterThanOrEqual(self, self._lift(o))

    def __lt__(self, o):
        return LessThan(self, self._lift(o))

    def __le__(self, o):
        return LessThanOrEqual(self, self._lift(o))

    def __eq__(self, o):
        return EqualTo(self, self._lift(o))

    def __ne__(self, o):
        return NotEqual(self, self._lift(o))

    __hash__ = object.__hash__

    def __and__(self, o):
        return And(self, self._lift(o))

    def __or__(self, o):
        return Or(self, self._lift(o))

    def __invert__(self):
        return Not(self)

    def is_null(self):
        return IsNull(self)

    def is_not_null(self):
        return IsNotNull(self)


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class ColumnRef(Expression):
    #: set by the planner (`mark_device_decimals`) on a bound reference to
    #: a wide decimal that an operator below computed on the device
    device_computed = False

    def __init__(self, name: str):
        self.name = name
        self.children = ()

    def bind(self, schema: t.StructType) -> "Expression":
        b = ColumnRef(self.name)
        f = schema[self.name]
        b.dtype = f.data_type
        b.nullable = f.nullable
        return b

    def _eval_dev(self, ctx, kids):
        return ctx.inputs[self.name]

    def _prepare(self, pctx, kids):
        return HostVal(pctx.dicts.get(self.name))

    def _eval_cpu(self, rb, kids):
        return rb.column(rb.schema.get_field_index(self.name))

    def _fp_extra(self):
        return self.name


class Literal(Expression):
    def __init__(self, value, dtype: Optional[t.DataType] = None):
        self.value = value
        self.children = ()
        if dtype is None:
            dtype = self._infer(value)
        self.dtype = dtype
        self.nullable = value is None

    @staticmethod
    def _infer(v) -> t.DataType:
        import datetime as pydt
        import decimal as pydec
        if v is None:
            return t.NULL
        if isinstance(v, bool):
            return t.BOOLEAN
        if isinstance(v, int):
            return t.INT if -(2**31) <= v < 2**31 else t.LONG
        if isinstance(v, float):
            return t.DOUBLE
        if isinstance(v, str):
            return t.STRING
        if isinstance(v, pydec.Decimal):
            sign, digits, exp = v.as_tuple()
            scale = max(0, -exp)
            # positive exponents widen the integral part: 1E+2 is 100 ->
            # 3 integral digits, decimal(3, 0)
            integral = len(digits) + max(exp, 0)
            precision = max(integral + scale if exp >= 0 else len(digits),
                            scale + 1)
            return t.DecimalType(min(precision, 38), scale)
        if isinstance(v, pydt.datetime):
            return t.TIMESTAMP
        if isinstance(v, pydt.date):
            return t.DATE
        raise TypeError(f"cannot infer literal type of {v!r}")

    def _physical_value(self):
        """Host value -> device lane value per the storage mapping."""
        import datetime as pydt
        import decimal as pydec
        v, dt = self.value, self.dtype
        if isinstance(dt, t.DecimalType):
            d = v if isinstance(v, pydec.Decimal) else pydec.Decimal(str(v))
            return int(d.scaleb(dt.scale).to_integral_value(
                rounding=pydec.ROUND_HALF_UP))
        if isinstance(dt, t.DateType):
            if isinstance(v, pydt.date):
                return (v - pydt.date(1970, 1, 1)).days
            return int(v)
        if isinstance(dt, t.TimestampType):
            if isinstance(v, pydt.datetime):
                epoch = pydt.datetime(1970, 1, 1,
                                      tzinfo=v.tzinfo and pydt.timezone.utc)
                return int((v - epoch).total_seconds() * 1e6)
            return int(v)
        return v

    def bind(self, schema):
        return self

    def _resolve(self):
        pass

    def lift_type_ok(self) -> bool:
        """Value/dtype half of lift eligibility: a non-null literal with
        one flat numeric device lane.  Strings carry dictionaries (host
        data the program specializes on), wide decimals a second lane,
        nulls an all-false validity shape — all stay baked."""
        if self.value is None:
            return False
        dt = self.dtype
        if isinstance(dt, (t.StringType, t.NullType)):
            return False
        if isinstance(dt, t.DecimalType) and dt.is_wide:
            return False
        return isinstance(dt, (t.ByteType, t.ShortType, t.IntegerType,
                               t.LongType, t.FloatType, t.DoubleType,
                               t.BooleanType, t.DateType, t.TimestampType,
                               t.DecimalType))

    def _lifted(self, pctx: PrepCtx) -> bool:
        if not pctx.lift_literals or not self.lift_type_ok():
            return False
        parent = pctx.current_parent()
        return parent is None or parent.lifts_literal_children

    def _prepare(self, pctx, kids):
        if isinstance(self.dtype, t.StringType) and self.value is not None:
            return HostVal(pa.array([self.value], pa.string()))
        if self._lifted(pctx):
            bound = get_literal_binding(self)
            if bound is None:
                bound = np.asarray(self._physical_value(),
                                   dtype=compute_dtype(self.dtype))
            pctx.add(self, bound)
        return HostVal()

    def _eval_dev(self, ctx, kids):
        cap = ctx.capacity
        slots = ctx.aux_of(self)
        if slots:
            # lifted: the value arrives as a 0-d runtime argument — the
            # broadcast is shape-only, so the compiled program is
            # literal-value-agnostic
            scalar = slots[0].astype(compute_dtype(self.dtype))
            return DevVal(jnp.broadcast_to(scalar, (cap,)), None,
                          self.dtype)
        if self.value is None:
            dt = self.dtype if not isinstance(self.dtype, t.NullType) else t.INT
            data = jnp.zeros((cap,), dtype=compute_dtype(dt))
            return DevVal(data, jnp.zeros((cap,), bool), self.dtype)
        if isinstance(self.dtype, t.StringType):
            data = jnp.zeros((cap,), dtype=jnp.int32)  # code 0 of 1-entry dict
            return DevVal(data, None, self.dtype,
                          pa.array([self.value], pa.string()))
        data = jnp.full((cap,), self._physical_value(),
                        dtype=compute_dtype(self.dtype))
        return DevVal(data, None, self.dtype)

    def canonical_fingerprint(self, lift_ok: bool = True) -> str:
        if lift_ok and self.lift_type_ok():
            return f"Literal(?:{self.dtype.simple_string};)"
        return self.fingerprint()

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        n = rb.num_rows
        if self.value is None:
            return pa.nulls(n, dtype_to_arrow(self.dtype)
                            if not isinstance(self.dtype, t.NullType) else pa.null())
        v = self.value
        if isinstance(self.dtype, t.DecimalType):
            import decimal as pydec
            v = v if isinstance(v, pydec.Decimal) else pydec.Decimal(str(v))
        return pa.array([v] * n, dtype_to_arrow(self.dtype))

    def _fp_extra(self):
        return f"{self.value!r}:{self.dtype.simple_string}"


class Alias(Expression):
    lifts_literal_children = True
    def __init__(self, child: Expression, name: str):
        self.children = (child,)
        self.name = name

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = self.children[0].nullable

    def _prepare(self, pctx, kids):
        return kids[0]          # forward dictionary metadata transparently

    def _eval_dev(self, ctx, kids):
        return kids[0]

    def eval_cpu(self, rb):
        return self.children[0].eval_cpu(rb)

    def _fp_extra(self):
        return self.name


# ---------------------------------------------------------------------------
# Numeric binary arithmetic
# ---------------------------------------------------------------------------

def _promote_binary(a: Expression, b: Expression) -> t.DataType:
    da, db = a.dtype, b.dtype
    if isinstance(da, t.NullType):
        return db
    if isinstance(db, t.NullType):
        return da
    if da == db:
        return da
    return t.numeric_promote(da, db)


def _is_decimal_op(da: t.DataType, db: t.DataType) -> bool:
    return isinstance(da, t.DecimalType) or isinstance(db, t.DecimalType)


def _as_decimal(dt: t.DataType) -> t.DecimalType:
    from ..ops import decimal as D
    if isinstance(dt, t.DecimalType):
        return dt
    return D.integral_as_decimal(dt)


def plain_ref(e: Expression) -> Optional["ColumnRef"]:
    """The column reference that `e` is, bare or under an alias, else
    None."""
    inner = e.children[0] if isinstance(e, Alias) else e
    return inner if isinstance(inner, ColumnRef) else None


def _consumes_wide_host(e: Expression) -> bool:
    """True when `e` reads a wide (p>18) decimal straight off a host column:
    those carry a (lo, hi) two-lane representation the single-lane kernels
    cannot consume.  Device-COMPUTED wide results are single-lane int64 and
    are fine (ops/decimal.py module docs).  Which of the two a column is
    follows from where it comes from in the plan, not from its type: the
    planner marks the references it has traced to an operator that
    computes on the device (`mark_device_decimals`); a reference nobody
    marked is taken for a host column."""
    ref = plain_ref(e)
    return ref is not None and isinstance(ref.dtype, t.DecimalType) \
        and ref.dtype.is_wide and not ref.device_computed


def mark_device_decimals(e: Expression, device_names) -> int:
    """Mark every reference under the bound tree `e` to a column named in
    `device_names` (wide decimals that an operator below computes on the
    device: one int64 unscaled lane) as consumable, and return how many
    expressions of the tree read such a column directly (or through an
    alias)."""
    ref = plain_ref(e)
    if ref is not None:
        if ref.name in device_names:
            ref.device_computed = True
        return 0
    n = sum(mark_device_decimals(c, device_names) for c in e.children)
    refs = (plain_ref(c) for c in e.children)
    return n + any(r is not None and r.device_computed for r in refs)


def _cast_dev(v, src: t.DataType, dst: t.DataType):
    if src == dst:
        return v
    return v.astype(compute_dtype(dst))


def _cpu_promote(arr: pa.Array, dst: t.DataType) -> pa.Array:
    from ..columnar.host import dtype_to_arrow
    want = dtype_to_arrow(dst)
    if arr.type == want:
        return arr
    return arr.cast(want)


class BinaryArithmetic(Expression):
    lifts_literal_children = True
    symbol = "?"
    #: ops/decimal.py result-type rule; None -> decimal unsupported here
    decimal_rule = None
    decimal_kernel = None

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    def _is_decimal(self):
        return _is_decimal_op(self.children[0].dtype, self.children[1].dtype)

    def _resolve(self):
        if self._is_decimal():
            from ..ops import decimal as D
            rule = self.decimal_rule
            if rule is None:
                raise TypeError(
                    f"{type(self).__name__} not defined for decimal")
            self.dtype = rule(_as_decimal(self.children[0].dtype),
                              _as_decimal(self.children[1].dtype))
        else:
            self.dtype = _promote_binary(*self.children)
        self.nullable = True

    def unsupported_reasons(self, conf):
        for c in self.children:
            if not t.is_numeric(c.dtype) and not isinstance(c.dtype, t.NullType):
                return [f"non-numeric operand {c.dtype.simple_string}"]
            if _consumes_wide_host(c):
                return ["128-bit host decimal lane not consumable on device"]
        if self._is_decimal() and self.decimal_kernel is None:
            return [f"decimal {self.symbol} not yet on device"]
        return []

    def _eval_dev(self, ctx, kids):
        l, r = kids
        if self._is_decimal():
            kern = self.decimal_kernel
            sa = _as_decimal(l.dtype).scale
            sb = _as_decimal(r.dtype).scale
            data, ok = kern(l.data.astype(jnp.int64), sa,
                            r.data.astype(jnp.int64), sb, self.dtype)
            return DevVal(data, merge_validity(l.validity, r.validity, ok),
                          self.dtype)
        if l.narrow is not None and r.narrow is not None:
            # FOR-narrowed operands: compute in the EXACT result width
            # (overflow-checked promotion, ops/encodings.py) — promote to
            # the full logical dtype only when the exact width needs it
            op = {"+": "add", "-": "add", "*": "mul"}.get(self.symbol)
            if op is not None:
                from ..ops.encodings import (count_dispatch,
                                             exact_arith_dtype)
                adt = exact_arith_dtype(l.narrow.dtype, r.narrow.dtype,
                                        op, compute_dtype(self.dtype))
                if adt is not None:
                    data, _ = self._op_dev(l.narrow.astype(adt),
                                           r.narrow.astype(adt))
                    count_dispatch("arith_narrow")
                    return DevVal(data.astype(compute_dtype(self.dtype)),
                                  merge_validity(l.validity, r.validity),
                                  self.dtype, narrow=data)
        ld = _cast_dev(l.data, l.dtype, self.dtype)
        rd = _cast_dev(r.data, r.dtype, self.dtype)
        data, extra_valid = self._op_dev(ld, rd)
        valid = merge_validity(l.validity, r.validity, extra_valid)
        return DevVal(data, valid, self.dtype)

    def _eval_cpu(self, rb, kids):
        if self._is_decimal():
            return self._decimal_cpu(kids)
        l = _cpu_promote(kids[0], self.dtype)
        r = _cpu_promote(kids[1], self.dtype)
        return self._op_cpu(l, r)

    def _decimal_cpu(self, kids):
        """Exact decimal arithmetic with Spark result typing.

        Fast path: arrow's decimal128 kernels (vectorized C++, exact) for
        +/-/* with a rescaling cast to the Spark result type; any arrow
        refusal (precision overflow, unsupported pair) falls back to the
        row-wise python-decimal oracle below."""
        import decimal as pydec
        out_t: t.DecimalType = self.dtype
        if type(self).__name__ in ("Add", "Subtract", "Multiply"):
            try:
                def as_dec(a):
                    if pa.types.is_decimal(a.type):
                        return a
                    return a.cast(pa.decimal128(20, 0))
                l, r = as_dec(kids[0]), as_dec(kids[1])
                if type(self).__name__ == "Multiply" and \
                        l.type.precision + r.type.precision + 1 > 38:
                    # arrow needs p1+p2+1 <= 38; shrink declared operand
                    # precisions to the values' actual headroom (the cast
                    # raises if any value doesn't fit -> python fallback)
                    budget = 38 - 1
                    p1 = min(l.type.precision, budget - r.type.precision)
                    if p1 <= l.type.scale:
                        raise pa.ArrowInvalid("no precision headroom")
                    l = l.cast(pa.decimal128(p1, l.type.scale))
                    p2 = min(r.type.precision, budget - p1)
                    if p2 <= r.type.scale:
                        raise pa.ArrowInvalid("no precision headroom")
                    r = r.cast(pa.decimal128(p2, r.type.scale))
                res = self._op_cpu(l, r)
                if isinstance(res, pa.ChunkedArray):
                    res = res.combine_chunks()
                return res.cast(pa.decimal128(out_t.precision, out_t.scale))
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                    pa.ArrowTypeError):
                pass
        quant = pydec.Decimal(1).scaleb(-out_t.scale)
        limit = pydec.Decimal(10) ** (out_t.precision - out_t.scale)
        lv = kids[0].to_pylist()
        rv = kids[1].to_pylist()
        out = []
        with pydec.localcontext() as ctx:
            ctx.prec = 76
            for a, b in zip(lv, rv):
                if a is None or b is None:
                    out.append(None)
                    continue
                try:
                    v = self._py_op(pydec.Decimal(a), pydec.Decimal(b))
                except (pydec.DivisionByZero, pydec.InvalidOperation):
                    out.append(None)
                    continue
                v = v.quantize(quant, rounding=pydec.ROUND_HALF_UP)
                out.append(None if abs(v) >= limit else v)
        return pa.array(out, pa.decimal128(out_t.precision, out_t.scale))

    def _fp_extra(self):
        return self.symbol


def _decimal_rules():
    from ..ops import decimal as D
    return D


class Add(BinaryArithmetic):
    symbol = "+"

    @property
    def decimal_rule(self):
        return _decimal_rules().add_result

    @property
    def decimal_kernel(self):
        return _decimal_rules().add_dev

    def _py_op(self, a, b):
        return a + b

    def _op_dev(self, l, r):
        return l + r, None

    def _op_cpu(self, l, r):
        return pc.add_checked(l, r) if False else pc.add(l, r)


class Subtract(BinaryArithmetic):
    symbol = "-"

    @property
    def decimal_rule(self):
        return _decimal_rules().add_result

    @property
    def decimal_kernel(self):
        return _decimal_rules().sub_dev

    def _py_op(self, a, b):
        return a - b

    def _op_dev(self, l, r):
        return l - r, None

    def _op_cpu(self, l, r):
        return pc.subtract(l, r)


class Multiply(BinaryArithmetic):
    symbol = "*"

    @property
    def decimal_rule(self):
        return _decimal_rules().mul_result

    @property
    def decimal_kernel(self):
        return _decimal_rules().mul_dev

    def _py_op(self, a, b):
        return a * b

    def _op_dev(self, l, r):
        return l * r, None

    def _op_cpu(self, l, r):
        return pc.multiply(l, r)


class Divide(BinaryArithmetic):
    """Spark Divide: DOUBLE result for non-decimal, decimal-rule result for
    decimal (device: CPU fallback — int64 lanes can't hold the scaled
    dividend); x/0 -> NULL."""
    symbol = "/"
    decimal_kernel = None     # tagged off-device; exact python CPU path

    @property
    def decimal_rule(self):
        return _decimal_rules().div_result

    def _py_op(self, a, b):
        return a / b

    def _resolve(self):
        if self._is_decimal():
            self.dtype = self.decimal_rule(
                _as_decimal(self.children[0].dtype),
                _as_decimal(self.children[1].dtype))
            return
        for c in self.children:
            if not (t.is_numeric(c.dtype) or isinstance(c.dtype, t.NullType)):
                raise TypeError(f"divide on {c.dtype}")
        self.dtype = t.DOUBLE

    def _eval_cpu(self, rb, kids):
        if self._is_decimal():
            return self._decimal_cpu(kids)
        return self._float_div_cpu(rb, kids)

    def _eval_dev(self, ctx, kids):
        l, r = kids
        ld = l.data.astype(jnp.float64)
        rd = r.data.astype(jnp.float64)
        safe_r = jnp.where(rd == 0.0, jnp.float64(1.0), rd)
        data = ld / safe_r
        extra = rd != 0.0
        return DevVal(data, merge_validity(l.validity, r.validity, extra),
                      t.DOUBLE)

    def _float_div_cpu(self, rb, kids):
        l = kids[0].cast(pa.float64())
        r = kids[1].cast(pa.float64())
        nz = pc.not_equal(r, pa.scalar(0.0))
        safe_r = pc.if_else(pc.fill_null(nz, False), r, pa.scalar(1.0))
        out = pc.divide(l, safe_r)
        return pc.if_else(pc.fill_null(nz, False), out,
                          pa.nulls(len(out), pa.float64()))


class IntegralDivide(BinaryArithmetic):
    """Spark `div`: long division truncating toward zero; x div 0 -> NULL."""
    symbol = "div"
    decimal_kernel = None

    def _resolve(self):
        self.dtype = t.LONG

    def _eval_cpu(self, rb, kids):
        if self._is_decimal():
            import decimal as pydec
            out = []
            for a, b in zip(kids[0].to_pylist(), kids[1].to_pylist()):
                if a is None or b is None or b == 0:
                    out.append(None)
                else:
                    q = pydec.Decimal(a) / pydec.Decimal(b)
                    out.append(int(q.to_integral_value(
                        rounding=pydec.ROUND_DOWN)))
            return pa.array(out, pa.int64())
        return self._int_div_cpu(rb, kids)

    def unsupported_reasons(self, conf):
        base = super().unsupported_reasons(conf)
        for c in self.children:
            if t.is_floating(c.dtype):
                return base + ["integral divide of floating input"]
        return base

    def _eval_dev(self, ctx, kids):
        l, r = kids
        ld = l.data.astype(jnp.int64)
        rd = r.data.astype(jnp.int64)
        safe_r = jnp.where(rd == 0, jnp.int64(1), rd)
        # Java integer division truncates toward zero; jnp // floors.
        q = jnp.sign(ld) * jnp.sign(safe_r) * (jnp.abs(ld) // jnp.abs(safe_r))
        return DevVal(q, merge_validity(l.validity, r.validity, rd != 0),
                      t.LONG)

    def _int_div_cpu(self, rb, kids):
        l = kids[0].cast(pa.int64())
        r = kids[1].cast(pa.int64())
        nz = pc.not_equal(r, pa.scalar(0, pa.int64()))
        safe_r = pc.if_else(pc.fill_null(nz, False), r, pa.scalar(1, pa.int64()))
        q = pc.divide(l, safe_r)  # arrow int division truncates toward zero
        return pc.if_else(pc.fill_null(nz, False), q, pa.nulls(len(q), pa.int64()))


class Remainder(BinaryArithmetic):
    """Spark %: Java semantics (sign follows dividend); x % 0 -> NULL."""
    symbol = "%"
    decimal_kernel = None

    @property
    def decimal_rule(self):
        def rule(a: t.DecimalType, b: t.DecimalType) -> t.DecimalType:
            s = max(a.scale, b.scale)
            p = min(a.precision - a.scale, b.precision - b.scale) + s
            return t.DecimalType(max(p, 1), s)
        return rule

    def _py_op(self, a, b):
        return a % b        # python Decimal %: sign follows dividend (Java)

    def _eval_dev(self, ctx, kids):
        l, r = kids
        ld = _cast_dev(l.data, l.dtype, self.dtype)
        rd = _cast_dev(r.data, r.dtype, self.dtype)
        if t.is_floating(self.dtype):
            safe_r = jnp.where(rd == 0, jnp.asarray(1, rd.dtype), rd)
            data = jnp.fmod(ld, safe_r)  # C fmod: sign follows dividend
            extra = rd != 0
        else:
            safe_r = jnp.where(rd == 0, jnp.asarray(1, rd.dtype), rd)
            # Java %: sign follows dividend. jnp.remainder follows divisor.
            data = jnp.sign(ld) * (jnp.abs(ld) % jnp.abs(safe_r))
            data = data.astype(ld.dtype)
            extra = rd != 0
        return DevVal(data, merge_validity(l.validity, r.validity, extra),
                      self.dtype)

    def _eval_cpu(self, rb, kids):
        import pandas as pd
        l = _cpu_promote(kids[0], self.dtype)
        r = _cpu_promote(kids[1], self.dtype)
        ln = l.to_numpy(zero_copy_only=False)
        rn = r.to_numpy(zero_copy_only=False)
        valid = np.asarray(pc.and_kleene(pc.is_valid(l), pc.is_valid(r)))
        with np.errstate(all="ignore"):
            rz = np.where(np.asarray(rn == 0) | ~valid, 1, rn)
            out = np.fmod(np.where(valid, ln, 0), rz)
        valid = valid & np.asarray(rn != 0)
        from ..columnar.host import dtype_to_arrow
        return pa.array(out.astype(np.asarray(ln).dtype, copy=False),
                        dtype_to_arrow(self.dtype), mask=~valid)


class UnaryMinus(Expression):
    lifts_literal_children = True
    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = self.children[0].nullable

    def _eval_dev(self, ctx, kids):
        return DevVal(-kids[0].data, kids[0].validity, self.dtype)

    def _eval_cpu(self, rb, kids):
        return pc.negate(kids[0])


class Abs(Expression):
    lifts_literal_children = True
    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = self.children[0].nullable

    def _eval_dev(self, ctx, kids):
        return DevVal(jnp.abs(kids[0].data), kids[0].validity, self.dtype)

    def _eval_cpu(self, rb, kids):
        return pc.abs(kids[0])


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------

class BinaryComparison(Expression):
    lifts_literal_children = True
    symbol = "?"

    def __init__(self, left, right):
        self.children = (left, right)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _string_literal_side(self):
        """Index of a non-null string Literal child whose sibling is a
        plain (possibly aliased) column reference, or None — the shape
        the encoded code-space predicate rewrites cover."""
        for lit_i in (1, 0):
            lit = self.children[lit_i]
            if isinstance(lit, Literal) and \
                    isinstance(lit.dtype, t.StringType) and \
                    lit.value is not None:
                other = self.children[1 - lit_i]
                inner = other.children[0] if isinstance(other, Alias) \
                    else other
                if isinstance(inner, ColumnRef):
                    return lit_i
        return None

    def unsupported_reasons(self, conf):
        l, r = self.children
        if isinstance(l.dtype, t.StringType) or isinstance(r.dtype, t.StringType):
            # String comparisons route through the dictionary machinery in
            # strings.py subclasses; plain comparison handles non-strings.
            if type(self) in (EqualTo, NotEqual, EqualNullSafe):
                return []
            # encoded execution (ops/encodings.py): literal range
            # predicates evaluate in code/rank space on device — against
            # one scalar bound when the dictionary is order-preserving,
            # through a rank table otherwise
            from ..ops.encodings import encoding_policy
            pol = encoding_policy(conf)
            if pol.enabled and pol.dict_predicates and \
                    self._string_literal_side() is not None:
                return []
            return ["string ordering comparison not yet on device"]
        for c in self.children:
            if _consumes_wide_host(c):
                return ["128-bit host decimal lane not consumable on device"]
        return []

    def _common(self):
        l, r = self.children
        if isinstance(l.dtype, t.StringType):
            return t.STRING
        if _is_decimal_op(l.dtype, r.dtype):
            da, db = _as_decimal(l.dtype), _as_decimal(r.dtype)
            s = max(da.scale, db.scale)
            p = max(da.precision - da.scale, db.precision - db.scale) + s
            return t.DecimalType(min(p, 38), s)
        if l.dtype == r.dtype:
            return l.dtype
        return _promote_binary(*self.children)

    def _decimal_lanes(self, kids, common: t.DecimalType):
        """Align both sides to the common scale; overflow -> null (rare:
        only beyond int64's unscaled range, see ops/decimal.py)."""
        from ..ops import decimal as D
        l, r = kids
        sa = _as_decimal(self.children[0].dtype).scale
        sb = _as_decimal(self.children[1].dtype).scale
        ld, ok_a = D.rescale(l.data.astype(jnp.int64), sa, common.scale)
        rd, ok_b = D.rescale(r.data.astype(jnp.int64), sb, common.scale)
        return ld, rd, ok_a & ok_b

    # -- string comparisons: code-space rewrites (ops/encodings.py) with
    # the unified-dictionary remap as the decoded fallback
    def _prepare_string(self, pctx, kids):
        """Choose the string-comparison path and register its aux slots;
        returns the node_info tag _eval_dev follows exactly:

          ("code", lit_i)          equality vs literal: ONE 0-d code aux
                                   (the literal translated through the
                                   column's dictionary) — zero gathers
          ("range_ordered", lit_i) range vs literal, order-preserving
                                   dictionary: two 0-d rank bounds
          ("range_ranks", lit_i)   range vs literal, unordered dict: a
                                   rank table (the decode rung) + bounds
          None                     legacy unified-remap equality
        """
        from ..ops import encodings as ENC
        l, r = kids
        is_eq = type(self) in (EqualTo, NotEqual, EqualNullSafe)
        lit_i = self._string_literal_side()
        pol = ENC.encoding_policy(pctx.conf)
        if pol.enabled and pol.dict_predicates and lit_i is not None:
            d = kids[1 - lit_i].dictionary
            value = self.children[lit_i].value
            if d is not None:
                if is_eq:
                    # code equality == value equality needs a duplicate-
                    # free dictionary (computed dictionaries may repeat)
                    if ENC.is_unique_dict(d) and \
                            ENC.elect_encoded(pctx.conf, "predicate_code"):
                        pctx.add(self, np.int32(ENC.literal_code(d, value)))
                        return ("code", lit_i)
                else:
                    less, leq = ENC.rank_bounds(d, value)
                    if ENC.is_ordered_dict(d) and \
                            ENC.elect_encoded(pctx.conf, "predicate_range"):
                        pctx.add(self, np.int32(less))
                        pctx.add(self, np.int32(leq))
                        return ("range_ordered", lit_i)
                    # decode rung: rank-table gather, still on device
                    ranks = ENC.rank_table(d)
                    ENC.count_decode(
                        "predicate_range",
                        (pctx.batch.capacity if pctx.batch is not None
                         else len(ranks)) * 4)
                    pctx.add(self, ranks)
                    pctx.add(self, np.int32(less))
                    pctx.add(self, np.int32(leq))
                    return ("range_ranks", lit_i)
        if not is_eq:
            # a range comparison only reaches the device behind the
            # encoded policy gate (unsupported_reasons); a dictionary-less
            # column side (a lambda variable) cannot be rank-translated
            raise TypeError("device string ordering comparison needs a "
                            "dictionary column and a string literal")
        dl = l.dictionary if l.dictionary is not None else pa.array([], pa.string())
        dr = r.dictionary if r.dictionary is not None else pa.array([], pa.string())
        combined = pa.concat_arrays([dl.cast(pa.string()), dr.cast(pa.string())])
        enc = pc.dictionary_encode(combined)
        codes = enc.indices.to_numpy(zero_copy_only=False).astype(np.int32)
        map_l = codes[:len(dl)] if len(dl) else np.zeros(1, np.int32)
        map_r = codes[len(dl):] if len(dr) else np.zeros(1, np.int32)
        pctx.add(self, map_l)
        pctx.add(self, map_r)
        return None

    def _prepare(self, pctx, kids):
        if isinstance(self.children[0].dtype, t.StringType) or \
           isinstance(self.children[1].dtype, t.StringType):
            info = self._prepare_string(pctx, kids)
            if info is not None:
                pctx.node_info[id(self)] = info
        return HostVal()

    def _string_op_dev(self, ctx, kids):
        """Traced string comparison following _prepare_string's choice."""
        l, r = kids
        info = ctx.info_of(self)
        if info is None:                      # legacy unified remap
            map_l, map_r = ctx.aux_of(self)
            lc = map_l[jnp.clip(l.data, 0, map_l.shape[0] - 1)]
            rc = map_r[jnp.clip(r.data, 0, map_r.shape[0] - 1)]
            return self._op_dev(lc, rc)
        kind, lit_i = info
        col = kids[1 - lit_i]
        if kind == "code":
            (code,) = ctx.aux_of(self)
            lc, rc = (col.data, code) if lit_i == 1 else (code, col.data)
            return self._op_dev(lc, rc)
        if kind == "range_ordered":
            less, leq = ctx.aux_of(self)
            rank = col.data
        else:                                 # "range_ranks"
            ranks, less, leq = ctx.aux_of(self)
            rank = ranks[jnp.clip(col.data, 0, ranks.shape[0] - 1)]
        # col OP lit in rank space:  col <  lit  <=>  rank <  less
        #                            col <= lit  <=>  rank <  leq
        sym = self.symbol if lit_i == 1 else \
            {"<": ">", "<=": ">=", ">": "<", ">=": "<="}[self.symbol]
        return {"<": rank < less, "<=": rank < leq,
                ">": rank >= leq, ">=": rank >= less}[sym]

    def _eval_dev(self, ctx, kids):
        l, r = kids
        extra = None
        if isinstance(l.dtype, t.StringType) or isinstance(r.dtype, t.StringType):
            data = self._string_op_dev(ctx, kids)
        else:
            common = self._common()
            narrow = self._narrow_op_dev(kids, common)
            if narrow is not None:
                data = narrow
            elif isinstance(common, t.DecimalType):
                ld, rd, extra = self._decimal_lanes(kids, common)
                data = self._op_dev(ld, rd)
            else:
                ld = _cast_dev(l.data, l.dtype, common)
                rd = _cast_dev(r.data, r.dtype, common)
                data = self._op_dev(ld, rd)
        return DevVal(data, merge_validity(l.validity, r.validity, extra),
                      t.BOOLEAN)

    def _narrow_op_dev(self, kids, common):
        """FOR-narrowed comparison (ops/encodings.py): both lanes narrow
        -> compare in their common narrow dtype; one narrow lane vs a
        full-width lane (a literal broadcast, lifted or baked) -> range-
        guarded narrow compare.  None = take the full-width path.
        Decisions depend only on lane dtypes, so compiled programs stay
        literal-value-agnostic (constant lifting holds)."""
        if isinstance(common, (t.DecimalType, t.StringType)) or \
                not isinstance(common, (t.ByteType, t.ShortType,
                                        t.IntegerType, t.LongType,
                                        t.DateType, t.TimestampType)):
            return None
        l, r = kids
        if l.narrow is None and r.narrow is None:
            return None
        from ..ops.encodings import (common_narrow_dtype, count_dispatch,
                                     narrow_compare)
        if l.narrow is not None and r.narrow is not None:
            cdt = common_narrow_dtype(l.narrow.dtype, r.narrow.dtype)
            if cdt is None:
                return None
            count_dispatch("predicate_narrow")
            return self._op_dev(l.narrow.astype(cdt), r.narrow.astype(cdt))
        nar, wide = (l, r) if l.narrow is not None else (r, l)
        sym = self.symbol
        if nar is r:
            sym = {"=": "=", "!=": "!=", "<": ">", "<=": ">=",
                   ">": "<", ">=": "<="}[sym]
        if sym not in ("=", "!=", "<", "<=", ">", ">="):
            return None
        wd = _cast_dev(wide.data, wide.dtype, common)
        if np.dtype(wd.dtype).kind != "i":
            return None
        count_dispatch("predicate_narrow")
        return narrow_compare(sym, nar.narrow, wd)

    def _eval_cpu(self, rb, kids):
        l, r = kids
        common = None
        if not isinstance(self.children[0].dtype, t.StringType):
            common = self._common()
        if isinstance(common, t.DecimalType):
            # arrow compares decimal128 natively once both sides share a
            # scale; rescaling to (38, common.scale) is exact unless a
            # value's integer digits + common scale exceed 38 — only then
            # fall back to the exact row-wise python-decimal oracle
            try:
                want = pa.decimal128(38, common.scale)
                return self._op_cpu(l.cast(want), r.cast(want))
            except pa.ArrowInvalid:
                pass
            import decimal as pydec
            import operator as op
            fn = {"=": op.eq, "!=": op.ne, "<": op.lt, "<=": op.le,
                  ">": op.gt, ">=": op.ge}[self.symbol]
            out = []
            for a, b in zip(l.to_pylist(), r.to_pylist()):
                out.append(None if a is None or b is None
                           else fn(pydec.Decimal(str(a)),
                                   pydec.Decimal(str(b))))
            return pa.array(out, pa.bool_())
        if common is not None:
            l, r = _cpu_promote(l, common), _cpu_promote(r, common)
        return self._op_cpu(l, r)

    def _fp_extra(self):
        return self.symbol


class EqualTo(BinaryComparison):
    symbol = "="

    def _op_dev(self, l, r):
        return l == r

    def _op_cpu(self, l, r):
        return pc.equal(l, r)


class NotEqual(BinaryComparison):
    symbol = "!="

    def _op_dev(self, l, r):
        return l != r

    def _op_cpu(self, l, r):
        return pc.not_equal(l, r)


class LessThan(BinaryComparison):
    symbol = "<"

    def _op_dev(self, l, r):
        return l < r

    def _op_cpu(self, l, r):
        return pc.less(l, r)


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def _op_dev(self, l, r):
        return l <= r

    def _op_cpu(self, l, r):
        return pc.less_equal(l, r)


class GreaterThan(BinaryComparison):
    symbol = ">"

    def _op_dev(self, l, r):
        return l > r

    def _op_cpu(self, l, r):
        return pc.greater(l, r)


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def _op_dev(self, l, r):
        return l >= r

    def _op_cpu(self, l, r):
        return pc.greater_equal(l, r)


class EqualNullSafe(BinaryComparison):
    symbol = "<=>"
    nullable = False

    def _eval_dev(self, ctx, kids):
        l, r = kids
        common = self._common()
        if isinstance(common, t.StringType):
            info = ctx.info_of(self)
            if info is not None and info[0] == "code":
                # code-space equality (ops/encodings.py): the literal's
                # translated code vs the column lane, zero gathers
                kind, lit_i = info
                (code,) = ctx.aux_of(self)
                col = kids[1 - lit_i]
                ld, rd = (col.data, code) if lit_i == 1 \
                    else (code, col.data)
            else:
                map_l, map_r = ctx.aux_of(self)
                ld = map_l[jnp.clip(l.data, 0, map_l.shape[0] - 1)]
                rd = map_r[jnp.clip(r.data, 0, map_r.shape[0] - 1)]
        else:
            ld = _cast_dev(l.data, l.dtype, common)
            rd = _cast_dev(r.data, r.dtype, common)
        from ..ops.kernels import valid_or_true
        lv = valid_or_true(l.validity, ctx.capacity)
        rv = valid_or_true(r.validity, ctx.capacity)
        both_null = (~lv) & (~rv)
        eq = (ld == rd) & lv & rv
        return DevVal(both_null | eq, None, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        l, r = kids
        common = self._common()
        if not isinstance(common, t.StringType):
            l, r = _cpu_promote(l, common), _cpu_promote(r, common)
        eq = pc.fill_null(pc.equal(l, r), False)
        both_null = pc.and_(pc.is_null(l), pc.is_null(r))
        return pc.or_(eq, both_null)


# ---------------------------------------------------------------------------
# Boolean logic (Kleene)
# ---------------------------------------------------------------------------

class And(Expression):
    lifts_literal_children = True
    def __init__(self, l, r):
        self.children = (l, r)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        l, r = kids
        lv = valid_or_true(l.validity, ctx.capacity)
        rv = valid_or_true(r.validity, ctx.capacity)
        ld = l.data & lv   # sanitize: null slots read as False
        rd = r.data & rv
        data = ld & rd
        # Kleene: false AND anything = false (valid); else null if either null
        false_l = lv & ~l.data
        false_r = rv & ~r.data
        valid = (lv & rv) | false_l | false_r
        return DevVal(data, valid, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.and_kleene(kids[0], kids[1])


class Or(Expression):
    lifts_literal_children = True
    def __init__(self, l, r):
        self.children = (l, r)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        l, r = kids
        lv = valid_or_true(l.validity, ctx.capacity)
        rv = valid_or_true(r.validity, ctx.capacity)
        true_l = lv & l.data
        true_r = rv & r.data
        data = true_l | true_r
        valid = (lv & rv) | true_l | true_r
        return DevVal(data, valid, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.or_kleene(kids[0], kids[1])


class Not(Expression):
    lifts_literal_children = True
    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.BOOLEAN
        self.nullable = self.children[0].nullable

    def _eval_dev(self, ctx, kids):
        return DevVal(~kids[0].data, kids[0].validity, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.invert(kids[0])


# ---------------------------------------------------------------------------
# Null predicates & handling
# ---------------------------------------------------------------------------

class IsNull(Expression):
    lifts_literal_children = True
    nullable = False

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _eval_dev(self, ctx, kids):
        v = kids[0].validity
        data = jnp.zeros((ctx.capacity,), bool) if v is None else ~v
        return DevVal(data, None, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.is_null(kids[0])


class IsNotNull(Expression):
    lifts_literal_children = True
    nullable = False

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _eval_dev(self, ctx, kids):
        v = kids[0].validity
        data = jnp.ones((ctx.capacity,), bool) if v is None else v
        return DevVal(data, None, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.is_valid(kids[0])


class IsNaN(Expression):
    nullable = False

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        v = valid_or_true(kids[0].validity, ctx.capacity)
        return DevVal(jnp.isnan(kids[0].data) & v, None, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        return pc.fill_null(pc.is_nan(kids[0]), False)


class Coalesce(Expression):
    lifts_literal_children = True
    def __init__(self, *children):
        self.children = tuple(children)

    def _resolve(self):
        non_null = [c.dtype for c in self.children
                    if not isinstance(c.dtype, t.NullType)]
        self.dtype = non_null[0] if non_null else t.NULL

    def unsupported_reasons(self, conf):
        if isinstance(self.dtype, t.StringType):
            return ["string coalesce not yet on device"]
        return []

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        data = jnp.zeros((ctx.capacity,), compute_dtype(self.dtype))
        valid = jnp.zeros((ctx.capacity,), bool)
        taken = jnp.zeros((ctx.capacity,), bool)
        for k in kids:
            kv = valid_or_true(k.validity, ctx.capacity)
            use = kv & ~taken
            kd = _cast_dev(k.data, k.dtype, self.dtype)
            data = jnp.where(use, kd, data)
            valid = valid | use
            taken = taken | use
        return DevVal(data, valid, self.dtype)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        kids = [k.cast(dtype_to_arrow(self.dtype)) for k in kids]
        return pc.coalesce(*kids)


# ---------------------------------------------------------------------------
# Conditional
# ---------------------------------------------------------------------------

class If(Expression):
    lifts_literal_children = True
    def __init__(self, pred, then, other):
        self.children = (pred, then, other)

    def _resolve(self):
        _, then, other = self.children
        self.dtype = then.dtype if not isinstance(then.dtype, t.NullType) \
            else other.dtype

    def unsupported_reasons(self, conf):
        if isinstance(self.dtype, t.StringType):
            return ["string-valued if not yet on device"]
        return []

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        p, a, b = kids
        pv = valid_or_true(p.validity, ctx.capacity)
        cond = p.data & pv          # null predicate -> else branch (Spark)
        ad = _cast_dev(a.data, a.dtype, self.dtype)
        bd = _cast_dev(b.data, b.dtype, self.dtype)
        data = jnp.where(cond, ad, bd)
        av = valid_or_true(a.validity, ctx.capacity)
        bv = valid_or_true(b.validity, ctx.capacity)
        valid = jnp.where(cond, av, bv)
        return DevVal(data, valid, self.dtype)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        p, a, b = kids
        want = dtype_to_arrow(self.dtype)
        return pc.if_else(pc.fill_null(p, False), a.cast(want), b.cast(want))


class CaseWhen(Expression):
    """CASE WHEN c1 THEN v1 [WHEN c2 THEN v2]* [ELSE e] END."""

    lifts_literal_children = True

    def __init__(self, branches: Sequence[Tuple[Expression, Expression]],
                 otherwise: Optional[Expression] = None):
        flat = []
        for c, v in branches:
            flat += [c, v]
        self.n_branches = len(branches)
        self.has_else = otherwise is not None
        self.children = tuple(flat) + ((otherwise,) if otherwise else ())

    def _branches(self):
        return [(self.children[2 * i], self.children[2 * i + 1])
                for i in range(self.n_branches)]

    def _resolve(self):
        for _, v in self._branches():
            if not isinstance(v.dtype, t.NullType):
                self.dtype = v.dtype
                break
        else:
            self.dtype = self.children[-1].dtype if self.has_else else t.NULL

    def unsupported_reasons(self, conf):
        return []

    def _value_slots(self):
        """Indices of the branch-value (and else) children."""
        out = [2 * i + 1 for i in range(self.n_branches)]
        if self.has_else:
            out.append(len(self.children) - 1)
        return out

    def _prepare(self, pctx, kids):
        """String CASE: unify the branch-value dictionaries on host (the
        engine's string convention — eval-time code remaps ride the aux
        channel, the output dictionary rides HostVal, exactly as In and
        concat do)."""
        if not isinstance(self.dtype, t.StringType):
            return HostVal()
        from ..ops.batch_ops import unify_dictionaries
        slots = self._value_slots()
        for i in slots:
            e, v = self.children[i], kids[i]
            if v.dictionary is None and \
                    not isinstance(e.dtype, t.NullType) and \
                    not (isinstance(e, Literal) and e.value is None):
                raise TypeError(
                    "device CASE over a dictionary-less string value")
        unified, remaps = unify_dictionaries(
            [kids[i].dictionary for i in slots])
        for r in remaps:
            pctx.add(self, r.astype(np.int32))
        if len(unified) == 0:
            # all-null result: codes never read where invalid, but the
            # dictionary must stay indexable
            unified = pa.array([""], pa.string())
        return HostVal(unified)

    def _eval_dev_string(self, ctx, kids):
        """String CASE on device: branch values are dict-encoded, so the
        result is their codes remapped into ONE unified dictionary and
        selected per row (the hierarchy-masking shape rollup/grouping
        queries project — CASE WHEN grouping(c)=0 THEN c END)."""
        from ..ops.kernels import valid_or_true
        cap = ctx.capacity
        vals = [kids[i] for i in self._value_slots()]
        tables = ctx.aux_of(self)
        codes = []
        for v, table in zip(vals, tables):
            codes.append(table[jnp.clip(v.data.astype(jnp.int32), 0,
                                        table.shape[0] - 1)])
        if self.has_else:
            data = codes[-1]
            valid = valid_or_true(vals[-1].validity, cap)
        else:
            data = jnp.zeros((cap,), jnp.int32)
            valid = jnp.zeros((cap,), bool)
        decided = jnp.zeros((cap,), bool)
        for i in range(self.n_branches):
            c, v = kids[2 * i], vals[i]
            cv = valid_or_true(c.validity, cap)
            hit = c.data & cv & ~decided
            data = jnp.where(hit, codes[i], data)
            valid = jnp.where(hit, valid_or_true(v.validity, cap), valid)
            decided = decided | hit
        if not self.has_else:
            valid = valid & decided
        return DevVal(data, valid, self.dtype)

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        if isinstance(self.dtype, t.StringType):
            return self._eval_dev_string(ctx, kids)
        cap = ctx.capacity
        data = jnp.zeros((cap,), compute_dtype(self.dtype))
        valid = jnp.zeros((cap,), bool)
        if self.has_else:
            e = kids[-1]
            data = _cast_dev(e.data, e.dtype, self.dtype)
            valid = valid_or_true(e.validity, cap)
        decided = jnp.zeros((cap,), bool)
        for i in range(self.n_branches):
            c, v = kids[2 * i], kids[2 * i + 1]
            cv = valid_or_true(c.validity, cap)
            hit = c.data & cv & ~decided
            vd = _cast_dev(v.data, v.dtype, self.dtype)
            vv = valid_or_true(v.validity, cap)
            data = jnp.where(hit, vd, data)
            valid = jnp.where(hit, vv, valid)
            decided = decided | hit
        if not self.has_else:
            valid = valid & decided
        return DevVal(data, valid, self.dtype)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        want = dtype_to_arrow(self.dtype)
        n = rb.num_rows
        out = kids[-1].cast(want) if self.has_else else pa.nulls(n, want)
        decided = pa.array([False] * n)
        for i in range(self.n_branches):
            c = pc.fill_null(kids[2 * i], False)
            v = kids[2 * i + 1].cast(want)
            hit = pc.and_(c, pc.invert(decided))
            out = pc.if_else(hit, v, out)
            decided = pc.or_(decided, hit)
        return out


# ---------------------------------------------------------------------------
# In / InSet
# ---------------------------------------------------------------------------

class In(Expression):
    """value IN (literals...). Spark null semantics: null if no match and
    any null present (value null -> null)."""

    def __init__(self, value: Expression, items: Sequence):
        self.items = tuple(items)
        self.children = (value,)

    def _resolve(self):
        self.dtype = t.BOOLEAN

    def _prepare(self, pctx, kids):
        child = self.children[0]
        if isinstance(child.dtype, t.StringType):
            d = kids[0].dictionary
            non_null = [x for x in self.items if x is not None]
            # encoded execution: a small IN-list translates its ITEMS
            # through the dictionary once (host) and ORs per-code
            # equality on device — no per-dictionary membership-mask
            # gather (ops/encodings.py)
            from ..ops import encodings as ENC
            pol = ENC.encoding_policy(pctx.conf)
            if pol.enabled and pol.dict_predicates and d is not None \
                    and len(non_null) <= pol.in_max_codes and \
                    ENC.is_unique_dict(d) and \
                    ENC.elect_encoded(pctx.conf, "in_codes"):
                codes = np.array(
                    sorted(ENC.literal_code(d, x) for x in non_null)
                    or [ENC.ABSENT_CODE], np.int32)
                pctx.add(self, codes)
                pctx.node_info[id(self)] = ("codes",)
                return HostVal()
            d = d.cast(pa.string()) if d is not None else pa.array([], pa.string())
            items = set(non_null)
            mask = np.array([v.as_py() in items for v in d] or [False], bool)
            pctx.add(self, mask)
        return HostVal()

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        v = kids[0]
        has_null_item = any(x is None for x in self.items)
        if isinstance(self.children[0].dtype, t.StringType):
            (aux,) = ctx.aux_of(self)
            info = ctx.info_of(self)
            if info is not None and info[0] == "codes":
                data = jnp.zeros((ctx.capacity,), bool)
                for j in range(aux.shape[0]):
                    data = data | (v.data == aux[j])
            else:
                data = aux[jnp.clip(v.data, 0, aux.shape[0] - 1)]
        else:
            data = jnp.zeros((ctx.capacity,), bool)
            narrow = v.narrow
            for x in self.items:
                if x is not None:
                    if narrow is not None:
                        from ..ops.encodings import narrow_compare
                        data = data | narrow_compare(
                            "=", narrow,
                            jnp.asarray(x, v.data.dtype))
                    else:
                        data = data | (v.data == jnp.asarray(x, v.data.dtype))
        vv = valid_or_true(v.validity, ctx.capacity)
        valid = vv & (data | ~jnp.asarray(has_null_item))
        return DevVal(data & vv, valid if has_null_item else vv, t.BOOLEAN)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        v = kids[0]
        non_null = [x for x in self.items if x is not None]
        has_null = any(x is None for x in self.items)
        vs = pa.array(non_null, dtype_to_arrow(self.children[0].dtype)) \
            if non_null else pa.array([], v.type)
        data = pc.is_in(v, value_set=vs)
        data = pc.if_else(pc.is_valid(v), data, pa.nulls(len(v), pa.bool_()))
        if has_null:
            data = pc.if_else(pc.fill_null(data, False), data,
                              pa.nulls(len(v), pa.bool_()))
        return data

    def _fp_extra(self):
        return repr(self.items)


# ---------------------------------------------------------------------------
# Math functions
# ---------------------------------------------------------------------------

class UnaryMathExpression(Expression):
    fn_dev = None
    fn_cpu_name = None

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.DOUBLE

    def _eval_dev(self, ctx, kids):
        data = type(self).fn_dev(kids[0].data.astype(jnp.float64))
        return DevVal(data, kids[0].validity, t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = type(self).fn_np(x)
        return pa.array(out, pa.float64(), mask=np.asarray(pc.is_null(arr)))


class Sqrt(UnaryMathExpression):
    # XLA's emulated-f64 sqrt returns nan for +inf in this environment;
    # guard the IEEE edge explicitly so device matches CPU/Spark.
    fn_dev = staticmethod(
        lambda x: jnp.where(jnp.isposinf(x), jnp.float64(np.inf), jnp.sqrt(x)))
    fn_np = staticmethod(np.sqrt)


class Exp(UnaryMathExpression):
    # inf guards: see Sqrt note on emulated-f64 transcendentals.
    fn_dev = staticmethod(
        lambda x: jnp.where(jnp.isposinf(x), jnp.float64(np.inf),
                            jnp.where(jnp.isneginf(x), jnp.float64(0.0),
                                      jnp.exp(x))))
    fn_np = staticmethod(np.exp)


class Log(UnaryMathExpression):
    """Spark ln: null for input <= 0 (non-ANSI)."""

    def _eval_dev(self, ctx, kids):
        x = kids[0].data.astype(jnp.float64)
        ok = x > 0
        data = jnp.log(jnp.where(ok, x, 1.0))
        data = jnp.where(jnp.isposinf(x), jnp.float64(np.inf), data)  # Sqrt note
        return DevVal(data, merge_validity(kids[0].validity, ok), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        ok = np.asarray(x > 0) & ~np.asarray(pc.is_null(arr))
        with np.errstate(all="ignore"):
            out = np.log(np.where(ok, x, 1.0))
        return pa.array(out, pa.float64(), mask=~ok)


def _f64_to_long_dev(f):
    """Spark double->long conversion: NaN -> 0, saturate at Long bounds."""
    f = jnp.where(jnp.isnan(f), 0.0, f)
    f = jnp.clip(f, -9.223372036854776e18, 9.223372036854775e18)
    return f.astype(jnp.int64)


def _f64_to_long_np(x):
    x = np.nan_to_num(x, nan=0.0, posinf=9.223372036854775e18,
                      neginf=-9.223372036854776e18)
    return np.clip(x, -9.223372036854776e18, 9.223372036854775e18).astype(np.int64)


class RoundingToLong(Expression):
    """floor/ceil of fractional input -> LONG with Spark .toLong semantics."""
    round_dev = None
    round_np = None

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.LONG

    def _eval_dev(self, ctx, kids):
        if t.is_integral(self.children[0].dtype):
            return DevVal(kids[0].data.astype(jnp.int64), kids[0].validity, t.LONG)
        f = type(self).round_dev(kids[0].data.astype(jnp.float64))
        return DevVal(_f64_to_long_dev(f), kids[0].validity, t.LONG)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = _f64_to_long_np(type(self).round_np(x))
        return pa.array(out, pa.int64(), mask=np.asarray(pc.is_null(arr)))


class Floor(RoundingToLong):
    # inf passthrough: emulated-f64 floor/ceil(inf) yields nan (see Sqrt note)
    round_dev = staticmethod(
        lambda x: jnp.where(jnp.isinf(x), x, jnp.floor(x)))
    round_np = staticmethod(np.floor)


class Ceil(RoundingToLong):
    round_dev = staticmethod(
        lambda x: jnp.where(jnp.isinf(x), x, jnp.ceil(x)))
    round_np = staticmethod(np.ceil)


class Pow(Expression):
    def __init__(self, l, r):
        self.children = (l, r)

    def _resolve(self):
        self.dtype = t.DOUBLE

    def _eval_dev(self, ctx, kids):
        l, r = kids
        data = jnp.power(l.data.astype(jnp.float64), r.data.astype(jnp.float64))
        return DevVal(data, merge_validity(l.validity, r.validity), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        return pc.power(kids[0].cast(pa.float64()), kids[1].cast(pa.float64()))


# ---------------------------------------------------------------------------
# Cast (the compatibility minefield — reference GpuCast.scala, 1903 LoC).
# Round 1 scope: numeric<->numeric, numeric<->bool, date/timestamp widening.
# String casts fall back to CPU (tagged), to be brought on-device later.
# ---------------------------------------------------------------------------

class Cast(Expression):
    lifts_literal_children = True
    def __init__(self, child, to: t.DataType):
        self.children = (child,)
        self.to = to

    def _resolve(self):
        self.dtype = self.to
        self.nullable = self.children[0].nullable

    def unsupported_reasons(self, conf):
        src, dst = self.children[0].dtype, self.to
        if _consumes_wide_host(self.children[0]):
            if t.is_floating(dst):
                return []     # two-lane -> f64 kernel (_eval_dev)
            return ["128-bit host decimal lane not consumable on device"]
        if isinstance(src, t.DecimalType):
            if t.is_numeric(dst) or isinstance(dst, t.BooleanType):
                return []
            return [f"cast {src.simple_string}->{dst.simple_string} "
                    "not yet on device"]
        if isinstance(dst, t.DecimalType):
            if t.is_numeric(src) or isinstance(src, t.StringType):
                return []
            return [f"cast {src.simple_string}->{dst.simple_string} "
                    "not yet on device"]
        ok_num = (t.is_numeric(src) or isinstance(src, t.BooleanType)) and \
                 (t.is_numeric(dst) or isinstance(dst, t.BooleanType))
        if ok_num:
            return []
        if src == dst:
            return []
        if isinstance(src, t.StringType) and (
                t.is_numeric(dst) or isinstance(dst, t.DateType)):
            return []     # dictionary-parse path (_prepare)
        if isinstance(src, t.DateType) and isinstance(dst, t.TimestampType):
            return []
        if isinstance(src, t.TimestampType) and isinstance(dst, t.DateType):
            return []
        return [f"cast {src.simple_string}->{dst.simple_string} not yet on device"]

    # -- string -> X: parse the dictionary host-side, gather by code -------
    @staticmethod
    def _parse_entry(s: Optional[str], dst: t.DataType):
        """Spark non-ANSI string cast: trimmed; invalid -> null."""
        import datetime as pydt
        import decimal as pydec
        if s is None:
            return None
        s = s.strip()
        if not s:
            return None
        try:
            if isinstance(dst, t.DateType):
                parts = s.split("T")[0].split(" ")[0].split("-")
                if len(parts) != 3:
                    return None
                y, m, d = (int(p) for p in parts)
                return (pydt.date(y, m, d) - pydt.date(1970, 1, 1)).days
            if isinstance(dst, t.DecimalType):
                v = pydec.Decimal(s).scaleb(dst.scale).to_integral_value(
                    rounding=pydec.ROUND_HALF_UP)
                iv = int(v)
                if abs(iv) > 10 ** min(dst.precision, 18) - 1:
                    return None
                return iv
            if t.is_floating(dst):
                return float(s)
            if t.is_integral(dst):
                d = pydec.Decimal(s)
                iv = int(d.to_integral_value(rounding=pydec.ROUND_DOWN))
                info = np.iinfo(t.physical_np_dtype(dst))
                if iv < info.min or iv > info.max:
                    return None
                return iv
            if isinstance(dst, t.BooleanType):
                low = s.lower()
                if low in ("t", "true", "y", "yes", "1"):
                    return True
                if low in ("f", "false", "n", "no", "0"):
                    return False
                return None
        except (ValueError, ArithmeticError):
            return None
        return None

    def _prepare(self, pctx, kids):
        src, dst = self.children[0].dtype, self.to
        ts_date_pair = (isinstance(src, t.DateType)
                        and isinstance(dst, t.TimestampType)) or \
                       (isinstance(src, t.TimestampType)
                        and isinstance(dst, t.DateType))
        if ts_date_pair:
            from .datetime import _conf_tz
            tz = _conf_tz(pctx.conf)
            if tz.upper() != "UTC":
                # date->ts uses local midnight (wall->utc table);
                # ts->date uses the local day (utc->local table)
                from ..ops.timezone import transition_table, wall_table
                pts, offs = wall_table(tz) \
                    if isinstance(src, t.DateType) else transition_table(tz)
                pctx.add(self, pts)
                pctx.add(self, offs)
        if isinstance(src, t.StringType) and not isinstance(dst, t.StringType):
            d = kids[0].dictionary
            entries = [v.as_py() for v in d] if d is not None else []
            parsed = [self._parse_entry(s, dst) for s in entries] or [None]
            ok = np.array([p is not None for p in parsed], bool)
            np_dt = t.physical_np_dtype(dst)
            vals = np.array([p if p is not None else 0 for p in parsed],
                            np_dt if not t.is_floating(dst) else np.float64)
            if isinstance(dst, t.DoubleType):
                vals = vals.astype(np.float64).view(np.int64)  # bit-exact lane
            pctx.add(self, vals)
            pctx.add(self, ok)
        return HostVal()

    def _eval_dev(self, ctx, kids):
        from ..ops import decimal as D
        src, dst = self.children[0].dtype, self.to
        x = kids[0].data
        valid = kids[0].validity
        if src == dst:
            return kids[0]
        if isinstance(src, t.StringType):
            vals, ok = ctx.aux_of(self)
            codes = jnp.clip(x, 0, vals.shape[0] - 1)
            data = vals[codes]
            if isinstance(dst, t.DoubleType):
                data = jax.lax.bitcast_convert_type(data, jnp.float64)
            return DevVal(data, merge_validity(valid, ok[codes]), dst)
        if isinstance(src, t.DecimalType):
            if kids[0].hi is not None and t.is_floating(dst):
                # two-lane host decimal128: value = hi*2^64 + unsigned(lo),
                # combined in f64 (32-bit halves — u64->f64 conversion is
                # not portable across backends), then unscaled
                lo = x.astype(jnp.int64)
                hi_f = kids[0].hi.astype(jnp.float64)
                lo_hi32 = ((lo >> 32) & jnp.int64(0xFFFFFFFF)) \
                    .astype(jnp.float64)
                lo_lo32 = (lo & jnp.int64(0xFFFFFFFF)).astype(jnp.float64)
                f = (hi_f * jnp.float64(2.0 ** 64)
                     + lo_hi32 * jnp.float64(2.0 ** 32) + lo_lo32)
                f = f / jnp.float64(10.0 ** src.scale)
                return DevVal(f.astype(compute_dtype(dst)), valid, dst)
            u = x.astype(jnp.int64)
            if isinstance(dst, t.DecimalType):
                data, ok = D.rescale(u, src.scale, dst.scale)
                ok = ok & D.fits_precision(data, dst.precision)
                return DevVal(data, merge_validity(valid, ok), dst)
            if t.is_floating(dst):
                f = D.to_double(u, src.scale)
                return DevVal(f.astype(compute_dtype(dst)), valid, dst)
            if isinstance(dst, t.BooleanType):
                return DevVal(u != 0, valid, dst)
            ints = D.cast_to_integral(u, src.scale)
            info = np.iinfo(t.physical_np_dtype(dst))
            ok = (ints >= info.min) & (ints <= info.max)
            return DevVal(ints.astype(compute_dtype(dst)),
                          merge_validity(valid, ok), dst)
        if isinstance(dst, t.DecimalType):
            if t.is_floating(src):
                data, ok = D.from_double(x.astype(jnp.float64), dst)
            else:
                data, ok = D.from_integral(x, dst)
            return DevVal(data, merge_validity(valid, ok), dst)
        if isinstance(dst, t.BooleanType):
            data = x != 0
        elif t.is_floating(src) and t.is_integral(dst):
            # Spark non-ANSI: truncate toward zero; NaN -> 0; clamp overflow
            # like Java (double->long saturates at Long.MIN/MAX... then
            # narrowing wraps). We saturate at the target bounds (Spark
            # behavior for double->int goes through long then wraps; the
            # common in-range path matches, out-of-range is documented).
            f = x.astype(jnp.float64)
            f = jnp.where(jnp.isnan(f), 0.0, f)
            f = jnp.where(jnp.isinf(f), f, jnp.trunc(f))  # see Sqrt inf note
            # Clamp in integer domain: float-domain clamping is off-by-ulp
            # at INT_MAX under the f32-pair f64 emulation.
            i64 = _f64_to_long_dev(f)
            info = np.iinfo(t.physical_np_dtype(dst))
            i64 = jnp.clip(i64, np.int64(info.min), np.int64(info.max))
            data = i64.astype(compute_dtype(dst))
        elif isinstance(src, t.DateType) and isinstance(dst, t.TimestampType):
            wall = x.astype(jnp.int64) * jnp.int64(86400_000_000)
            aux = ctx.aux_of(self)
            if aux:                       # session tz: local midnight
                from ..ops.timezone import local_to_utc
                wall = local_to_utc(wall, aux[0], aux[1])
            data = wall
        elif isinstance(src, t.TimestampType) and isinstance(dst, t.DateType):
            us = x.astype(jnp.int64)
            aux = ctx.aux_of(self)
            if aux:                       # session tz: local day
                from ..ops.timezone import utc_to_local
                us = utc_to_local(us, aux[0], aux[1])
            days = jnp.where(us >= 0, us // 86400_000_000,
                             -((-us + 86400_000_000 - 1) // 86400_000_000))
            data = days.astype(jnp.int32)
        else:
            data = x.astype(compute_dtype(dst))
        return DevVal(data, valid, dst)

    def _eval_cpu(self, rb, kids):
        import decimal as pydec
        from ..columnar.host import dtype_to_arrow
        src, dst = self.children[0].dtype, self.to
        arr = kids[0]
        if isinstance(src, t.StringType) and not isinstance(dst, t.StringType):
            parsed = [self._parse_entry(v.as_py(), dst)
                      for v in arr.cast(pa.string())]
            if isinstance(dst, t.DecimalType):
                parsed = [None if p is None else
                          pydec.Decimal(p).scaleb(-dst.scale) for p in parsed]
            if isinstance(dst, t.DateType):
                return pa.array([None if p is None else p for p in parsed],
                                pa.int32()).cast(pa.date32())
            return pa.array(parsed, dtype_to_arrow(dst))
        if isinstance(src, t.DecimalType) or isinstance(dst, t.DecimalType):
            out = []
            limit = None
            if isinstance(dst, t.DecimalType):
                quant = pydec.Decimal(1).scaleb(-dst.scale)
                limit = pydec.Decimal(10) ** (dst.precision - dst.scale)
            for v in arr.to_pylist():
                if v is None:
                    out.append(None)
                    continue
                d = v if isinstance(v, pydec.Decimal) \
                    else pydec.Decimal(str(v))
                if isinstance(dst, t.DecimalType):
                    try:
                        q = d.quantize(quant, rounding=pydec.ROUND_HALF_UP)
                    except pydec.InvalidOperation:
                        out.append(None)
                        continue
                    out.append(None if abs(q) >= limit else q)
                elif t.is_floating(dst):
                    out.append(float(d))
                elif isinstance(dst, t.BooleanType):
                    out.append(d != 0)
                else:
                    iv = int(d.to_integral_value(rounding=pydec.ROUND_DOWN))
                    info = np.iinfo(t.physical_np_dtype(dst))
                    out.append(iv if info.min <= iv <= info.max else None)
            return pa.array(out, dtype_to_arrow(dst))
        if t.is_floating(src) and t.is_integral(dst):
            x = arr.cast(pa.float64()).to_numpy(zero_copy_only=False)
            x = np.nan_to_num(x, nan=0.0, posinf=np.inf, neginf=-np.inf)
            info = np.iinfo(t.physical_np_dtype(dst))
            x = np.clip(np.trunc(x), info.min, info.max)
            return pa.array(x.astype(t.physical_np_dtype(dst)),
                            dtype_to_arrow(dst),
                            mask=np.asarray(pc.is_null(arr)))
        ts_date_pair = (isinstance(src, t.DateType)
                        and isinstance(dst, t.TimestampType)) or \
                       (isinstance(src, t.TimestampType)
                        and isinstance(dst, t.DateType))
        if ts_date_pair:
            from .datetime import session_timezone
            tz = session_timezone()
            if tz.upper() != "UTC":
                import jax.numpy as _jnp
                mask = np.asarray(pc.is_null(arr))
                if isinstance(src, t.DateType):
                    from ..ops.timezone import local_to_utc, wall_table
                    days = arr.cast(pa.int32()) \
                        .to_numpy(zero_copy_only=False)
                    wall = days.astype(np.int64) * 86400_000_000
                    pts, offs = wall_table(tz)
                    us = np.asarray(local_to_utc(
                        _jnp.asarray(wall), _jnp.asarray(pts),
                        _jnp.asarray(offs)))
                    return pa.array(us, pa.int64(), mask=mask) \
                        .cast(dtype_to_arrow(dst))
                from ..ops.timezone import transition_table, utc_to_local
                us = arr.cast(pa.timestamp("us", tz="UTC")) \
                    .cast(pa.int64()).to_numpy(zero_copy_only=False)
                pts, offs = transition_table(tz)
                loc = np.asarray(utc_to_local(
                    _jnp.asarray(us), _jnp.asarray(pts),
                    _jnp.asarray(offs)))
                days = np.floor_divide(loc, 86400_000_000)
                return pa.array(days.astype(np.int32), pa.int32(),
                                mask=mask).cast(pa.date32())
        return arr.cast(dtype_to_arrow(dst))

    def _fp_extra(self):
        return self.to.simple_string


# ---------------------------------------------------------------------------
# Math breadth (reference mathExpressions.scala)
# ---------------------------------------------------------------------------

class Sin(UnaryMathExpression):
    fn_dev = staticmethod(jnp.sin)
    fn_np = staticmethod(np.sin)


class Cos(UnaryMathExpression):
    fn_dev = staticmethod(jnp.cos)
    fn_np = staticmethod(np.cos)


class Tan(UnaryMathExpression):
    fn_dev = staticmethod(jnp.tan)
    fn_np = staticmethod(np.tan)


class Asin(UnaryMathExpression):
    fn_dev = staticmethod(jnp.arcsin)
    fn_np = staticmethod(np.arcsin)


class Acos(UnaryMathExpression):
    fn_dev = staticmethod(jnp.arccos)
    fn_np = staticmethod(np.arccos)


class Atan(UnaryMathExpression):
    fn_dev = staticmethod(jnp.arctan)
    fn_np = staticmethod(np.arctan)


class Sinh(UnaryMathExpression):
    fn_dev = staticmethod(jnp.sinh)
    fn_np = staticmethod(np.sinh)


class Cosh(UnaryMathExpression):
    fn_dev = staticmethod(jnp.cosh)
    fn_np = staticmethod(np.cosh)


class Tanh(UnaryMathExpression):
    fn_dev = staticmethod(jnp.tanh)
    fn_np = staticmethod(np.tanh)


class Log10(UnaryMathExpression):
    """Spark log10: null for input <= 0 (shares Log's domain rule)."""

    def _eval_dev(self, ctx, kids):
        x = kids[0].data.astype(jnp.float64)
        ok = x > 0
        data = jnp.log10(jnp.where(ok, x, 1.0))
        data = jnp.where(jnp.isposinf(x), jnp.float64(np.inf), data)
        return DevVal(data, merge_validity(kids[0].validity, ok), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = np.log10(x)
        mask = np.asarray(pc.is_null(arr)) | ~(x > 0)
        return pa.array(out, pa.float64(), mask=mask)


class Log2(Log10):
    def _eval_dev(self, ctx, kids):
        x = kids[0].data.astype(jnp.float64)
        ok = x > 0
        data = jnp.log2(jnp.where(ok, x, 1.0))
        data = jnp.where(jnp.isposinf(x), jnp.float64(np.inf), data)
        return DevVal(data, merge_validity(kids[0].validity, ok), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = np.log2(x)
        mask = np.asarray(pc.is_null(arr)) | ~(x > 0)
        return pa.array(out, pa.float64(), mask=mask)


class Cbrt(UnaryMathExpression):
    fn_dev = staticmethod(jnp.cbrt)
    fn_np = staticmethod(np.cbrt)


class Signum(UnaryMathExpression):
    fn_dev = staticmethod(jnp.sign)
    fn_np = staticmethod(np.sign)


class Atan2(Expression):
    def __init__(self, y, x):
        self.children = (y, x)

    def _resolve(self):
        self.dtype = t.DOUBLE

    def _eval_dev(self, ctx, kids):
        data = jnp.arctan2(kids[0].data.astype(jnp.float64),
                           kids[1].data.astype(jnp.float64))
        return DevVal(data, merge_validity(kids[0].validity,
                                           kids[1].validity), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        a = kids[0].cast(pa.float64()).to_numpy(zero_copy_only=False)
        b = kids[1].cast(pa.float64()).to_numpy(zero_copy_only=False)
        mask = np.asarray(pc.is_null(kids[0])) | np.asarray(
            pc.is_null(kids[1]))
        with np.errstate(all="ignore"):
            return pa.array(np.arctan2(a, b), pa.float64(), mask=mask)


class Greatest(Expression):
    """greatest(...): Spark skips nulls, null only when ALL inputs null;
    NaN is greatest (Java ordering)."""

    lifts_literal_children = True
    _is_greatest = True

    def __init__(self, *items):
        assert len(items) >= 2
        self.children = tuple(items)

    def _resolve(self):
        # first non-NULL-typed child decides the result type (Coalesce
        # pattern): greatest(NULL, x) is x-typed, not NULL-typed
        self.dtype = next((c.dtype for c in self.children
                           if not isinstance(c.dtype, t.NullType)), t.NULL)
        self.nullable = all(c.nullable for c in self.children)

    def unsupported_reasons(self, conf):
        out = []
        for c in self.children:
            if _consumes_wide_host(c):
                out.append("128-bit host decimal lane not consumable "
                           "on device")
        return out

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import valid_or_true
        is_fp = t.is_floating(self.dtype)
        acc_d = kids[0].data
        acc_v = valid_or_true(kids[0].validity, ctx.capacity)
        for k in kids[1:]:
            d, v = k.data, valid_or_true(k.validity, ctx.capacity)
            if is_fp:
                da = acc_d.astype(jnp.float64)
                db = d.astype(jnp.float64)
                # NaN greatest (Java order) with an explicit nan lane so a
                # genuine +inf never ties with NaN
                na, nb = jnp.isnan(da), jnp.isnan(db)
                # Java ordering tiebreak: -0.0 < +0.0 (IEEE == can't see it)
                sa, sb = jnp.signbit(da), jnp.signbit(db)
                zero_tie = (~na & ~nb & (db == da))
                if self._is_greatest:
                    take_b = (nb & ~na) | (~na & ~nb & (db > da)) | \
                        (zero_tie & sa & ~sb)
                else:
                    take_b = (na & ~nb) | (~na & ~nb & (db < da)) | \
                        (zero_tie & ~sa & sb)
            else:
                take_b = d > acc_d if self._is_greatest else d < acc_d
            pick_b = v & (~acc_v | take_b)
            acc_d = jnp.where(pick_b, d, acc_d)
            acc_v = acc_v | v
        return DevVal(acc_d, acc_v, self.dtype)

    def _eval_cpu(self, rb, kids):
        import math
        cols = [k.to_pylist() for k in kids]
        gt = self._is_greatest

        def key(v):
            return ((v != v, v, not math.copysign(1.0, v) < 0)
                    if isinstance(v, float) else (False, v, True))
        out = []
        for row in zip(*cols):
            nn = [v for v in row if v is not None]
            out.append((max(nn, key=key) if gt else min(nn, key=key))
                      if nn else None)
        from ..columnar.host import dtype_to_arrow
        return pa.array(out, dtype_to_arrow(self.dtype))


class Least(Greatest):
    _is_greatest = False


class Round(Expression):
    """round(x, scale) HALF_UP (Spark default).  Decimals round on the
    unscaled int64 lane exactly.  DOUBLE rounds in binary (x*10^s):
    Spark rounds the double's SHORTEST DECIMAL representation through
    BigDecimal, so values sitting on a decimal half-way point that binary
    cannot represent (e.g. 2.675) can differ in the last unit — a
    documented deviation (cf. the reference's float notes in
    docs/compatibility.md); both engine paths here agree with each
    other."""
    _half_even = False

    def __init__(self, child, scale: int = 0):
        self.children = (child,)
        self.scale = scale

    def _fp_extra(self):
        return str(self.scale)

    def _resolve(self):
        dt = self.children[0].dtype
        if isinstance(dt, t.DecimalType):
            # Spark: round(decimal(p,s), d) -> decimal(p-s+max(d,0)+1,
            # max(d,0)); the +1 absorbs the round-up carry (999.99 -> 1000)
            if self.scale >= dt.scale:
                self.dtype = dt
            else:
                self.dtype = t.DecimalType(
                    min(38, dt.precision - dt.scale + max(self.scale, 0)
                        + 1),
                    max(self.scale, 0))
        elif t.is_integral(dt):
            self.dtype = dt
        else:
            self.dtype = t.DOUBLE
        self.nullable = self.children[0].nullable

    def unsupported_reasons(self, conf):
        out = []
        if _consumes_wide_host(self.children[0]):
            out.append("128-bit host decimal lane not consumable on device")
        return out

    def _int_round(self, d, drop: int):
        """Exact integer rounding: divide by 10^drop with HALF_UP or
        HALF_EVEN on the magnitude."""
        p = jnp.int64(10 ** drop)
        mag = jnp.abs(d)
        q = (mag + p // 2) // p
        if self._half_even:
            r = mag - (mag // p) * p
            half = (r * 2 == p)
            qf = mag // p
            q = jnp.where(half, qf + (qf % 2), q)
        return jnp.where(d < 0, -q, q)

    def _eval_dev(self, ctx, kids):
        dt = self.children[0].dtype
        if isinstance(dt, t.DecimalType):
            # drop digits down to the requested scale; a negative scale
            # keeps the decimal's scale at 0 but zeroes integral digits
            drop = dt.scale - self.scale
            d = kids[0].data.astype(jnp.int64)
            if drop <= 0:
                return DevVal(d, kids[0].validity, self.dtype)
            q = self._int_round(d, drop)
            if self.scale < 0:
                q = q * jnp.int64(10 ** (-self.scale))
            return DevVal(q, kids[0].validity, self.dtype)
        if t.is_integral(dt):
            d = kids[0].data.astype(jnp.int64)
            if self.scale >= 0:
                out = d
            else:
                out = self._int_round(d, -self.scale) * \
                    jnp.int64(10 ** (-self.scale))
            return DevVal(out.astype(kids[0].data.dtype),
                          kids[0].validity, self.dtype)
        x = kids[0].data.astype(jnp.float64)
        p = jnp.float64(10.0 ** self.scale)
        if self._half_even:
            out = jnp.round(x * p) / p
        else:
            out = jnp.trunc(x * p + jnp.where(x >= 0, 0.5, -0.5)) / p
        return DevVal(out, kids[0].validity, self.dtype)

    def _eval_cpu(self, rb, kids):
        import decimal as pydec
        dt = self.children[0].dtype
        from ..columnar.host import dtype_to_arrow
        mode = pydec.ROUND_HALF_EVEN if self._half_even \
            else pydec.ROUND_HALF_UP
        if isinstance(dt, t.DecimalType):
            out_q = pydec.Decimal(1).scaleb(-self.dtype.scale)
            rq = pydec.Decimal(1).scaleb(-self.scale)
            out = [None if v is None else
                   v.quantize(rq, rounding=mode).quantize(out_q)
                   for v in kids[0].to_pylist()]
            return pa.array(out, dtype_to_arrow(self.dtype))
        if t.is_integral(dt):
            if self.scale >= 0:
                return kids[0]
            rq = pydec.Decimal(1).scaleb(-self.scale)
            out = [None if v is None else
                   int(pydec.Decimal(v).quantize(rq, rounding=mode))
                   for v in kids[0].to_pylist()]
            return pa.array(out, dtype_to_arrow(self.dtype))
        xs = kids[0].cast(pa.float64()).to_pylist()
        p = 10.0 ** self.scale
        if self._half_even:
            out = [None if v is None else
                   float(np.round(v * p) / p) for v in xs]
        else:
            out = [None if v is None else
                   math_trunc_half_up(v, p) for v in xs]
        return pa.array(out, pa.float64())


def math_trunc_half_up(v: float, p: float) -> float:
    import math
    x = v * p
    return math.floor(x + 0.5) / p if x >= 0 else math.ceil(x - 0.5) / p


class BRound(Round):
    """bround: HALF_EVEN (banker's rounding)."""
    _half_even = True


class RaiseError(Expression):
    """raise_error(msg): CPU-path only — jit programs cannot raise, so the
    expression tags off-device and the CPU operator throws on the first
    evaluated row (reference GpuRaiseError, misc.scala)."""

    def __init__(self, message):
        # accept a plain string or an expression evaluating to one
        if isinstance(message, Expression):
            self.children = (message,)
            self.message = None
        else:
            self.children = ()
            self.message = str(message)

    def _resolve(self):
        self.dtype = t.NULL
        self.nullable = True

    def _fp_extra(self):
        return repr(self.message)

    def unsupported_reasons(self, conf):
        return ["raise_error must run on the CPU path (device programs "
                "cannot throw)"]

    def _eval_cpu(self, rb, kids):
        if rb.num_rows > 0:
            msg = self.message
            if msg is None:
                # the FIRST evaluated row's message, like Spark — not
                # the first non-null one
                v0 = kids[0].to_pylist()[0]
                msg = "" if v0 is None else str(v0)
            raise RuntimeError(msg)
        return pa.nulls(0)


class Murmur3Hash(Expression):
    """hash(...): Spark's murmur3-based hash with seed 42 folded across
    columns — device kernels from ops/hashing (the HashFunctions.scala
    murmur3 role; bit-exact with Spark for the supported lane types)."""

    def __init__(self, *items):
        assert items
        self.children = tuple(items)

    def _resolve(self):
        self.dtype = t.INT
        self.nullable = False

    def _prepare(self, pctx, kids):
        from ..ops.hashing import dict_hash_array
        for k, c in zip(kids, self.children):
            if isinstance(c.dtype, t.StringType):
                d = k.dictionary
                # per-seed string hashes cannot precompute (seed chains);
                # only position-0 style single-column usage precomputes
                pctx.add(self, dict_hash_array(
                    d.cast(pa.string()) if d is not None
                    else pa.array([], pa.string()), 42))
        return HostVal()

    def unsupported_reasons(self, conf):
        out = []
        strings = [c for c in self.children
                   if isinstance(c.dtype, t.StringType)]
        if strings and (len(self.children) > 1 or
                        self.children[0] is not strings[0]):
            out.append("string input to hash() only as the single/first "
                       "column (chained-seed string hashing needs the "
                       "byte-level kernel)")
        for c in self.children:
            if isinstance(c.dtype, (t.ArrayType, t.MapType, t.StructType,
                                    t.BinaryType)):
                out.append(f"hash over {c.dtype.simple_string}")
            if isinstance(c.dtype, t.DoubleType) and \
                    not isinstance(c, ColumnRef):
                out.append("hash over a COMPUTED double (bit-exact f64 "
                           "lanes exist only for scanned columns)")
            if isinstance(c.dtype, t.DecimalType) and c.dtype.is_wide:
                out.append("hash over decimal(>18)")
        return out

    def _eval_dev(self, ctx, kids):
        from ..ops.hashing import hash_column
        from ..ops.kernels import valid_or_true
        aux_iter = iter(ctx.aux_of(self))
        h = jnp.full((ctx.capacity,), 42, jnp.uint32)
        for k, c in zip(kids, self.children):
            if isinstance(c.dtype, t.StringType):
                # single-string-column form only (tagged otherwise): the
                # dict table was hashed against the constant seed 42
                table = next(aux_iter)
                codes = jnp.clip(k.data, 0, table.shape[0] - 1)
                lane = table[codes].astype(jnp.uint32)
                valid = valid_or_true(k.validity, ctx.capacity)
                h = jnp.where(valid, lane, h)   # null: seed passes through
                continue
            data = k.data
            if isinstance(c.dtype, t.DoubleType) and \
                    isinstance(c, ColumnRef):
                # Spark hashes the f64 BIT PATTERN: use the storage lane
                # (int64 bits for scanned columns), not the compute view
                data = ctx.raw.get(c.name, data)
                if data.dtype != jnp.int64:
                    raise TypeError(
                        "hash() over a DOUBLE column whose batch was "
                        "device-computed upstream: the f64 bit pattern "
                        "is unavailable on TPU (no f64->i64 bitcast). "
                        "Disable spark.rapids.tpu.sql.expression."
                        "Murmur3Hash to hash on the CPU path.")
            h = hash_column(data, k.validity, c.dtype, h)
        return DevVal(h.astype(jnp.int32), None, t.INT)

    @staticmethod
    def _cpu_lane(arr: pa.Array, dt: t.DataType):
        """(values list, width) normalized to the exact integers the
        device kernels hash — bit patterns for floats (-0 -> +0, NaN
        canonical), epoch micros/days via arrow casts (no host-timezone
        round trips), unscaled longs for narrow decimals."""
        import struct as _st
        if isinstance(dt, t.BooleanType):
            return [None if v is None else (1 if v else 0)
                    for v in arr.to_pylist()], 32
        if isinstance(dt, (t.ByteType, t.ShortType, t.IntegerType)):
            return arr.cast(pa.int32()).to_pylist(), 32
        if isinstance(dt, t.DateType):
            return arr.cast(pa.int32()).to_pylist(), 32
        if isinstance(dt, t.LongType):
            return arr.to_pylist(), 64
        if isinstance(dt, t.TimestampType):
            return arr.cast(pa.int64()).to_pylist(), 64
        if isinstance(dt, t.FloatType):
            out = []
            for v in arr.to_pylist():
                if v is None:
                    out.append(None)
                    continue
                if v != v:
                    out.append(0x7FC00000)          # canonical NaN bits
                    continue
                if v == 0.0:
                    v = 0.0                          # -0.0 -> +0.0
                out.append(_st.unpack("<i", _st.pack("<f", v))[0])
            return out, 32
        if isinstance(dt, t.DoubleType):
            out = []
            for v in arr.to_pylist():
                if v is None:
                    out.append(None)
                    continue
                if v != v:
                    out.append(0x7FF8000000000000)   # canonical NaN bits
                    continue
                if v == 0.0:
                    v = 0.0                          # -0.0 -> +0.0
                out.append(_st.unpack("<q", _st.pack("<d", v))[0])
            return out, 64
        if isinstance(dt, t.DecimalType):
            return [None if v is None else
                    int(v.scaleb(dt.scale)) for v in arr.to_pylist()], 64
        raise TypeError(f"hash over {dt.simple_string}")

    def _eval_cpu(self, rb, kids):
        from ..ops.hashing import (murmur3_int32_host, murmur3_int64_host,
                                   murmur3_utf8)
        lanes = []
        for k, c in zip(kids, self.children):
            if isinstance(c.dtype, t.StringType):
                lanes.append((k.to_pylist(), "s"))
            else:
                lanes.append(self._cpu_lane(k, c.dtype))
        out = []
        for i in range(rb.num_rows):
            h = 42
            for vals, width in lanes:
                v = vals[i]
                if v is None:
                    continue
                if width == "s":
                    h = murmur3_utf8(v, h)
                elif width == 64:
                    h = murmur3_int64_host(int(v), h)
                else:
                    h = murmur3_int32_host(int(v), h)
            out.append(h - 2**32 if h >= 2**31 else h)
        return pa.array(out, pa.int32())


# ---------------------------------------------------------------------------
# Bitwise family (reference bitwise.scala; device: one VPU op each)
# ---------------------------------------------------------------------------

class _BitwiseBinary(Expression):
    _op = None        # (jnp a, jnp b) -> jnp
    _pyop = None      # (int, int) -> int

    def __init__(self, left, right):
        self.children = (left, right)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = any(c.nullable for c in self.children)

    def unsupported_reasons(self, conf):
        out = []
        for c in self.children:
            if not t.is_integral(c.dtype):
                out.append(f"bitwise over {c.dtype.simple_string}")
        return out

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import merge_validity
        return DevVal(type(self)._op(kids[0].data, kids[1].data),
                      merge_validity(kids[0].validity, kids[1].validity),
                      self.dtype)

    def _eval_cpu(self, rb, kids):
        a, b = kids[0].to_pylist(), kids[1].to_pylist()
        from ..columnar.host import dtype_to_arrow
        bits = 8 * np.dtype(t.physical_np_dtype(self.dtype)).itemsize
        mask = (1 << bits) - 1
        sign = 1 << (bits - 1)
        out = []
        for x, y in zip(a, b):
            if x is None or y is None:
                out.append(None)
                continue
            v = type(self)._pyop(int(x), int(y)) & mask
            out.append(v - (1 << bits) if v & sign else v)
        return pa.array(out, dtype_to_arrow(self.dtype))


class BitwiseAnd(_BitwiseBinary):
    _op = staticmethod(lambda a, b: a & b)
    _pyop = staticmethod(lambda a, b: a & b)


class BitwiseOr(_BitwiseBinary):
    _op = staticmethod(lambda a, b: a | b)
    _pyop = staticmethod(lambda a, b: a | b)


class BitwiseXor(_BitwiseBinary):
    _op = staticmethod(lambda a, b: a ^ b)
    _pyop = staticmethod(lambda a, b: a ^ b)


class BitwiseNot(Expression):
    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = self.children[0].nullable

    def unsupported_reasons(self, conf):
        if not t.is_integral(self.children[0].dtype):
            return [f"bitwise over "
                    f"{self.children[0].dtype.simple_string}"]
        return []

    def _eval_dev(self, ctx, kids):
        return DevVal(~kids[0].data, kids[0].validity, self.dtype)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        bits = 8 * np.dtype(t.physical_np_dtype(self.dtype)).itemsize
        mask = (1 << bits) - 1
        sign = 1 << (bits - 1)
        out = []
        for x in kids[0].to_pylist():
            if x is None:
                out.append(None)
                continue
            v = (~int(x)) & mask
            out.append(v - (1 << bits) if v & sign else v)
        return pa.array(out, dtype_to_arrow(self.dtype))


class _Shift(Expression):
    """Java shift semantics: the shift distance wraps modulo the value
    width (Spark ShiftLeft/ShiftRight/ShiftRightUnsigned)."""
    _kind = "left"

    def __init__(self, child, amount):
        self.children = (child, amount)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = any(c.nullable for c in self.children)

    def unsupported_reasons(self, conf):
        out = []
        if not isinstance(self.children[0].dtype,
                          (t.IntegerType, t.LongType)):
            out.append("shift base must be INT or BIGINT")
        if not t.is_integral(self.children[1].dtype):
            out.append("shift amount must be integral")
        return out

    def _bits(self):
        return 64 if isinstance(self.dtype, t.LongType) else 32

    def _eval_dev(self, ctx, kids):
        import jax.numpy as jnp
        from ..ops.kernels import merge_validity
        bits = self._bits()
        sh = (kids[1].data.astype(jnp.int32) & (bits - 1))
        v = kids[0].data
        if self._kind == "left":
            out = v << sh.astype(v.dtype)
        elif self._kind == "right":
            out = v >> sh.astype(v.dtype)
        else:
            u = v.astype(jnp.uint64 if bits == 64 else jnp.uint32)
            out = (u >> sh.astype(u.dtype)).astype(v.dtype)
        return DevVal(out, merge_validity(kids[0].validity,
                                          kids[1].validity), self.dtype)

    def _eval_cpu(self, rb, kids):
        from ..columnar.host import dtype_to_arrow
        bits = self._bits()
        mask = (1 << bits) - 1
        sign = 1 << (bits - 1)
        out = []
        for x, s in zip(kids[0].to_pylist(), kids[1].to_pylist()):
            if x is None or s is None:
                out.append(None)
                continue
            s = int(s) & (bits - 1)
            x = int(x)
            if self._kind == "left":
                v = (x << s) & mask
            elif self._kind == "right":
                v = (x >> s) & mask   # python >> is already arithmetic
            else:
                v = ((x & mask) >> s) & mask
            out.append(v - (1 << bits) if v & sign else v)
        return pa.array(out, dtype_to_arrow(self.dtype))


class ShiftLeft(_Shift):
    _kind = "left"


class ShiftRight(_Shift):
    _kind = "right"


class ShiftRightUnsigned(_Shift):
    _kind = "unsigned"


class BitCount(Expression):
    """bit_count(x): population count of the two's-complement form."""

    def __init__(self, child):
        self.children = (child,)

    def _resolve(self):
        self.dtype = t.INT
        self.nullable = self.children[0].nullable

    def unsupported_reasons(self, conf):
        dt = self.children[0].dtype
        if not (t.is_integral(dt) or isinstance(dt, t.BooleanType)):
            return [f"bit_count over {dt.simple_string}"]
        return []

    def _eval_dev(self, ctx, kids):
        import jax.numpy as jnp
        from ..ops.kernels import compute_view
        d = kids[0].data
        if d.dtype == jnp.bool_:
            cnt = d.astype(jnp.int32)
        else:
            # Spark counts bits of the SIGN-EXTENDED 64-bit value
            u = d.astype(jnp.int64).astype(jnp.uint64)
            cnt = jax.lax.population_count(u).astype(jnp.int32)
        return DevVal(cnt, kids[0].validity, t.INT)

    def _eval_cpu(self, rb, kids):
        isbool = isinstance(self.children[0].dtype, t.BooleanType)
        mask = (1 << 64) - 1         # sign-extend to 64 bits (Spark)
        out = []
        for x in kids[0].to_pylist():
            if x is None:
                out.append(None)
            elif isbool:
                out.append(1 if x else 0)
            else:
                out.append(bin(int(x) & mask).count("1"))
        return pa.array(out, pa.int32())


class WidthBucket(Expression):
    """width_bucket(v, lo, hi, n) — Spark/ANSI histogram bucket index."""

    def __init__(self, value, lo, hi, nbuckets):
        self.children = (value, lo, hi, nbuckets)

    def _resolve(self):
        self.dtype = t.LONG
        self.nullable = True

    def unsupported_reasons(self, conf):
        out = []
        for c in self.children:
            if not t.is_numeric(c.dtype):
                out.append(f"width_bucket over {c.dtype.simple_string}")
        return out

    @staticmethod
    def _bucket(v, lo, hi, n):
        if n <= 0 or lo == hi or any(
                x != x for x in (v, lo, hi)):      # NaN/degenerate
            return None
        if lo < hi:
            if v < lo:
                return 0
            if v >= hi:
                return n + 1
            return int((v - lo) * n / (hi - lo)) + 1
        if v > lo:
            return 0
        if v <= hi:
            return n + 1
        return int((lo - v) * n / (lo - hi)) + 1

    def _eval_dev(self, ctx, kids):
        import jax.numpy as jnp
        from ..ops.kernels import compute_view, merge_validity
        v = compute_view(kids[0].data, self.children[0].dtype) \
            .astype(jnp.float64)
        lo = compute_view(kids[1].data, self.children[1].dtype) \
            .astype(jnp.float64)
        hi = compute_view(kids[2].data, self.children[2].dtype) \
            .astype(jnp.float64)
        n = kids[3].data.astype(jnp.int64)
        asc = lo < hi
        below = jnp.where(asc, v < lo, v > lo)
        above = jnp.where(asc, v >= hi, v <= hi)
        frac = jnp.where(asc, (v - lo) / (hi - lo),
                         (lo - v) / (lo - hi))
        mid = (frac * n.astype(jnp.float64)).astype(jnp.int64) + 1
        out = jnp.where(below, 0, jnp.where(above, n + 1, mid))
        bad = (n <= 0) | (lo == hi) | jnp.isnan(v) | jnp.isnan(lo) | \
            jnp.isnan(hi)
        valid = merge_validity(kids[0].validity, kids[1].validity,
                               kids[2].validity, kids[3].validity)
        valid = (~bad) if valid is None else (valid & ~bad)
        return DevVal(out, valid, t.LONG)

    def _eval_cpu(self, rb, kids):
        vals = [k.to_pylist() for k in kids]
        out = []
        for v, lo, hi, n in zip(*vals):
            if None in (v, lo, hi, n):
                out.append(None)
            else:
                out.append(self._bucket(float(v), float(lo), float(hi),
                                        int(n)))
        return pa.array(out, pa.int64())


class XxHash64(Expression):
    """xxhash64(...): Spark's 64-bit xxHash with seed 42 chained across
    columns (reference spark-rapids-jni Hash.xxhash64 /
    HashFunctions.scala).  Device kernels in ops/hashing.py; int lanes
    hash via XXH64.hashInt, longs/dates/timestamps via hashLong, string
    columns via a host-hashed dictionary table (single/first column
    only, like Murmur3Hash — chained seeds need the byte kernel)."""

    def __init__(self, *items):
        assert items
        self.children = tuple(items)

    def _resolve(self):
        self.dtype = t.LONG
        self.nullable = False

    def _prepare(self, pctx, kids):
        from ..ops.hashing import dict_xxhash_array
        for k, c in zip(kids, self.children):
            if isinstance(c.dtype, t.StringType):
                d = k.dictionary
                pctx.add(self, dict_xxhash_array(
                    d.cast(pa.string()) if d is not None
                    else pa.array([], pa.string()), 42))
        return HostVal()

    def unsupported_reasons(self, conf):
        out = []
        strings = [c for c in self.children
                   if isinstance(c.dtype, t.StringType)]
        if strings and (len(self.children) > 1 or
                        self.children[0] is not strings[0]):
            out.append("string input to xxhash64() only as the "
                       "single/first column (chained-seed string hashing "
                       "needs the byte-level kernel)")
        for c in self.children:
            if isinstance(c.dtype, (t.ArrayType, t.MapType, t.StructType,
                                    t.BinaryType, t.FloatType)):
                out.append(f"xxhash64 over {c.dtype.simple_string}")
            if isinstance(c.dtype, t.DoubleType):
                out.append("xxhash64 over DOUBLE (bit-exact f64 lane "
                           "widening not wired)")
            if isinstance(c.dtype, t.DecimalType):
                out.append("xxhash64 over decimal")
        return out

    def _eval_dev(self, ctx, kids):
        from ..ops.hashing import xxhash64_int_lane, xxhash64_long_lane
        from ..ops.kernels import valid_or_true
        aux_iter = iter(ctx.aux_of(self))
        h = jnp.full((ctx.capacity,), 42, jnp.uint64)
        for k, c in zip(kids, self.children):
            valid = valid_or_true(k.validity, ctx.capacity)
            if isinstance(c.dtype, t.StringType):
                table = next(aux_iter)
                codes = jnp.clip(k.data, 0, table.shape[0] - 1)
                lane = table[codes].astype(jnp.uint64)
                h = jnp.where(valid, lane, h)
                continue
            dt = c.dtype
            if isinstance(dt, (t.LongType, t.TimestampType)):
                lane = k.data.astype(jnp.uint64)
                nh = xxhash64_long_lane(lane, h)
            elif isinstance(dt, t.BooleanType):
                lane = k.data.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF)
                nh = xxhash64_int_lane(lane, h)
            else:   # byte/short/int/date hash as 32-bit
                lane = k.data.astype(jnp.int32).astype(jnp.uint32) \
                    .astype(jnp.uint64)
                nh = xxhash64_int_lane(lane, h)
            h = jnp.where(valid, nh, h)   # nulls: seed passes through
        return DevVal(h.astype(jnp.int64), None, t.LONG)

    def _eval_cpu(self, rb, kids):
        from ..ops.hashing import (xxhash64_int_host, xxhash64_long_host,
                                   xxhash64_utf8)
        out = []
        cols = [k.to_pylist() for k in kids]
        for i in range(rb.num_rows):
            h = 42
            for vals, c in zip(cols, self.children):
                v = vals[i]
                if v is None:
                    continue
                dt = c.dtype
                if isinstance(dt, t.StringType):
                    h = xxhash64_utf8(v, h)
                elif isinstance(dt, (t.LongType, t.TimestampType)):
                    h = xxhash64_long_host(int(v), h)
                elif isinstance(dt, t.BooleanType):
                    h = xxhash64_int_host(1 if v else 0, h)
                elif isinstance(dt, t.DateType):
                    import datetime as _dt
                    days = (v - _dt.date(1970, 1, 1)).days \
                        if isinstance(v, _dt.date) else int(v)
                    h = xxhash64_int_host(days, h)
                else:
                    h = xxhash64_int_host(int(v), h)
            out.append(h - (1 << 64) if h >= (1 << 63) else h)
        return pa.array(out, pa.int64())


class ToDegrees(UnaryMathExpression):
    fn_dev = staticmethod(jnp.degrees)
    fn_np = staticmethod(np.degrees)


class ToRadians(UnaryMathExpression):
    fn_dev = staticmethod(jnp.radians)
    fn_np = staticmethod(np.radians)


class Expm1(UnaryMathExpression):
    fn_dev = staticmethod(jnp.expm1)
    fn_np = staticmethod(np.expm1)


class Log1p(UnaryMathExpression):
    """log1p: Spark returns null for x <= -1 (ln of non-positive)."""
    fn_dev = staticmethod(jnp.log1p)
    fn_np = staticmethod(np.log1p)

    def _resolve(self):
        self.dtype = t.DOUBLE
        self.nullable = True

    def _eval_dev(self, ctx, kids):
        import jax.numpy as _j
        x = kids[0].data.astype(_j.float64)
        data = _j.log1p(x)
        valid = kids[0].validity
        ok = x > -1.0
        valid = ok if valid is None else (valid & ok)
        return DevVal(data, valid, t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        arr = kids[0].cast(pa.float64())
        x = arr.to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = np.log1p(x)
        mask = np.asarray(pc.is_null(arr)) | ~(x > -1.0)
        return pa.array(out, pa.float64(), mask=mask)


class Rint(UnaryMathExpression):
    fn_dev = staticmethod(jnp.round)
    fn_np = staticmethod(np.rint)


class Cot(UnaryMathExpression):
    fn_dev = staticmethod(lambda x: 1.0 / jnp.tan(x))
    fn_np = staticmethod(lambda x: 1.0 / np.tan(x))


class Sec(UnaryMathExpression):
    fn_dev = staticmethod(lambda x: 1.0 / jnp.cos(x))
    fn_np = staticmethod(lambda x: 1.0 / np.cos(x))


class Csc(UnaryMathExpression):
    fn_dev = staticmethod(lambda x: 1.0 / jnp.sin(x))
    fn_np = staticmethod(lambda x: 1.0 / np.sin(x))


class Hypot(Expression):
    """hypot(a, b)."""

    def __init__(self, left, right):
        self.children = (left, right)

    def _resolve(self):
        self.dtype = t.DOUBLE
        self.nullable = any(c.nullable for c in self.children)

    def _eval_dev(self, ctx, kids):
        from ..ops.kernels import merge_validity
        data = jnp.hypot(kids[0].data.astype(jnp.float64),
                         kids[1].data.astype(jnp.float64))
        return DevVal(data, merge_validity(kids[0].validity,
                                           kids[1].validity), t.DOUBLE)

    def _eval_cpu(self, rb, kids):
        a = kids[0].cast(pa.float64()).to_numpy(zero_copy_only=False)
        b = kids[1].cast(pa.float64()).to_numpy(zero_copy_only=False)
        with np.errstate(all="ignore"):
            out = np.hypot(a, b)
        mask = np.asarray(pc.is_null(kids[0])) | \
            np.asarray(pc.is_null(kids[1]))
        return pa.array(out, pa.float64(), mask=mask)
