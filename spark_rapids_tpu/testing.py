"""Test utilities: the assert_gpu_and_cpu_are_equal analogue.

The reference's entire correctness strategy (SURVEY §4) is "same engine, two
backends, compare" (integration_tests asserts.py:579).  Here the two backends
are the device path (jit-traced eval_dev) and the per-expression CPU fallback
(eval_cpu over pyarrow) — which doubles as the production fallback engine, so
these asserts also exercise the CPU path users hit on unsupported operators.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np
import pyarrow as pa

from .columnar import HostBatch, to_device, to_host
from .config import TpuConf, DEFAULT_CONF
from .exec.evaluator import apply_filter, evaluate_projection
from .plan.expressions import Expression


def _values_equal(a, b, approx_float: bool) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        if approx_float:
            return math.isclose(a, b, rel_tol=1e-12, abs_tol=1e-300)
        return a == b
    return a == b


def assert_columns_equal(got: pa.Array, want: pa.Array, label: str = "",
                         approx_float: bool = False):
    gl, wl = got.to_pylist(), want.to_pylist()
    assert len(gl) == len(wl), f"{label}: row count {len(gl)} != {len(wl)}"
    for i, (g, w) in enumerate(zip(gl, wl)):
        assert _values_equal(g, w, approx_float), \
            f"{label}: row {i}: device={g!r} cpu={w!r}"


def assert_device_cpu_equal(exprs: Sequence[Expression], data: Dict,
                            conf: TpuConf = DEFAULT_CONF,
                            approx_float: bool = False):
    """Evaluate bound-able expressions on device and CPU; compare results."""
    hb = HostBatch.from_pydict(data) if not isinstance(data, HostBatch) else data
    schema = hb.schema
    bound = [e.bind(schema) for e in exprs]
    for e in bound:
        reasons = e.tree_unsupported(conf)
        assert not reasons, f"expression not device-supported: {reasons}"
    db = to_device(hb, conf)
    names = [f"c{i}" for i in range(len(bound))]
    out = to_host(evaluate_projection(bound, names, db, conf))
    for i, e in enumerate(bound):
        want = e.eval_cpu(hb.rb)
        assert_columns_equal(out.rb.column(i), want, label=e.fingerprint(),
                             approx_float=approx_float)
    return out


# ---------------------------------------------------------------------------
# jaxpr program lints: sort-operand budget and scatter census
# ---------------------------------------------------------------------------
# The two compile/runtime cliffs of this platform are directly visible in
# the emitted jaxpr: variadic `sort` equations whose operand count blows
# up XLA compile time, and `scatter*` equations whose outputs land in
# slow S(1)-space buffers.  These walkers turn both
# into assertable numbers for tier-1 tests and bench.py.

_SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                  "scatter-max")


def _iter_eqns(jaxpr):
    """Every equation of a (Closed)Jaxpr, recursing into sub-jaxprs
    (pjit bodies, scan/while/cond branches, custom call wrappers)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in vs:
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def jaxpr_sort_operands(jaxpr) -> int:
    """Largest operand count of any `sort` equation (0 when sort-free)."""
    return max((len(e.invars) for e in _iter_eqns(jaxpr)
                if e.primitive.name == "sort"), default=0)


def jaxpr_sort_operand_total(jaxpr) -> int:
    """TOTAL operands across every `sort` equation — the whole-program
    sort volume proxy (a merge-rank join probe is two 2-operand sorts
    over build+probe rows)."""
    return sum(len(e.invars) for e in _iter_eqns(jaxpr)
               if e.primitive.name == "sort")


def _gather_sizes(eqn):
    """(operand elems, output elems) of a gather equation."""
    import numpy as np
    op_shape = getattr(eqn.invars[0].aval, "shape", ())
    out = 0
    for ov in eqn.outvars:
        shape = getattr(ov.aval, "shape", ())
        out += int(np.prod(shape)) if shape else 1
    return (int(np.prod(op_shape)) if op_shape else 1), out


def jaxpr_decode_count(jaxpr) -> int:
    """Number of DECODE-signature gathers: gather equations whose
    operand is SMALLER than their output — a per-row lookup through a
    table below row count (dictionary remap/rank/membership tables,
    dense direct-address probes).  The encoded-execution layer
    (ops/encodings.py) exists to shrink the dictionary-decode share of
    these: its per-query budget lint asserts the q1/q3/q9-class
    programs emit strictly less decode VOLUME with the feature on."""
    return sum(1 for e in _iter_eqns(jaxpr)
               if e.primitive.name == "gather"
               and _gather_sizes(e)[0] < _gather_sizes(e)[1])


def jaxpr_decode_elems(jaxpr) -> int:
    """Total OUTPUT elements across decode-signature gathers — the
    decode-volume proxy (rows actually expanded through sub-row-count
    tables).  Code-space predicates and order-preserving dictionaries
    remove remap/rank tables outright, so volume strictly drops where
    the rewrites engage while invariant table-gathers (join
    direct-address probes) cancel in the on/off comparison."""
    total = 0
    for e in _iter_eqns(jaxpr):
        if e.primitive.name == "gather":
            osz, out = _gather_sizes(e)
            if osz < out:
                total += out
    return total


def jaxpr_scatter_count(jaxpr) -> int:
    """Number of scatter-family equations in the program."""
    return sum(1 for e in _iter_eqns(jaxpr)
               if e.primitive.name in _SCATTER_PRIMS)


def jaxpr_gather_count(jaxpr) -> int:
    """Number of `gather` equations in the program — the descriptor-
    driven row-gather passes that dominate join-pipeline device time
    (each gathered lane moves at DMA rather than vector bandwidth).  Late materialization (columnar/lanes.py) exists to
    shrink this number: its per-query budget lint asserts the q3/q9/
    q15/q16-class programs emit FEWER gathers with the feature on."""
    return sum(1 for e in _iter_eqns(jaxpr)
               if e.primitive.name == "gather")


def jaxpr_gather_elems(jaxpr) -> int:
    """Total OUTPUT elements across every `gather` equation — the
    volume proxy for row-gather device cost (rows x lanes actually
    moved through descriptor DMA).  Late materialization shrinks this
    even where the equation COUNT ties (a deferred column's sink gather
    replaces a per-join gather 1:1 but the skipped re-gathers of chained
    probe payloads don't), so the per-query budget lint compares
    volume."""
    import numpy as np
    total = 0
    for e in _iter_eqns(jaxpr):
        if e.primitive.name == "gather":
            for ov in e.outvars:
                shape = getattr(ov.aval, "shape", ())
                total += int(np.prod(shape)) if shape else 1
    return total


def plan_program_stats(physical, ctx=None) -> Dict:
    """{'sort_operand_max', 'scatter_op_count'} for a PhysicalQuery's
    device plan traced as ONE whole-plan XLA program
    (exec.compiled.CompiledPlan.make_jaxpr) — the same program shape the
    TPU backend dispatches.  Raises jax tracer errors for plans that
    need host decisions (callers treat those as not-traceable)."""
    from .exec.compiled import CompiledPlan
    from .exec.plan import ExecContext
    ctx = ctx or ExecContext(physical.conf)
    jx = CompiledPlan(physical.root, physical.conf).make_jaxpr(ctx)
    return {"sort_operand_max": jaxpr_sort_operands(jx),
            "sort_operand_total": jaxpr_sort_operand_total(jx),
            "scatter_op_count": jaxpr_scatter_count(jx),
            "gather_op_count": jaxpr_gather_count(jx),
            "gather_out_elems": jaxpr_gather_elems(jx),
            "decode_op_count": jaxpr_decode_count(jx),
            "decode_out_elems": jaxpr_decode_elems(jx)}


# ---------------------------------------------------------------------------
# Compiled-program cache hygiene
# ---------------------------------------------------------------------------
# Every engine module memoizes its jitted kernels in module-level *_CACHE
# dicts, which keep the XLA LoadedExecutables alive for the process
# lifetime.  A long-lived process that compiles many thousands of
# distinct programs (the full tier-1 suite now crosses ~8k with the
# TPC-DS tranche aboard) can exhaust the JIT's executable code space and
# crash inside XLA.  These helpers let harnesses bound that growth.

def compiled_cache_entries() -> int:
    """Total entries across every engine *_CACHE module dict."""
    import sys
    total = 0
    for name, mod in list(sys.modules.items()):
        if not name.startswith("spark_rapids_tpu"):
            continue
        for attr, val in list(vars(mod).items()):
            if attr.endswith("_CACHE") and isinstance(val, dict):
                total += len(val)
    return total


def clear_compiled_caches() -> int:
    """Drop every engine *_CACHE dict and jax's own jit caches, freeing
    the compiled executables they pin.  Returns the number of entries
    released.  Safe at any quiescent point: kernels recompile (or
    reload from the persistent cache) on next use."""
    import sys
    import jax
    released = 0
    for name, mod in list(sys.modules.items()):
        if not name.startswith("spark_rapids_tpu"):
            continue
        for attr, val in list(vars(mod).items()):
            if attr.endswith("_CACHE") and isinstance(val, dict):
                released += len(val)
                val.clear()
    jax.clear_caches()
    return released


def assert_filter_matches(cond: Expression, data: Dict,
                          conf: TpuConf = DEFAULT_CONF):
    """Device filter vs CPU mask-filter row-set comparison."""
    import pyarrow.compute as pc
    hb = HostBatch.from_pydict(data) if not isinstance(data, HostBatch) else data
    bound = cond.bind(hb.schema)
    reasons = bound.tree_unsupported(conf)
    assert not reasons, f"predicate not device-supported: {reasons}"
    db = to_device(hb, conf)
    got = to_host(apply_filter(bound, db, conf))
    mask = pc.fill_null(bound.eval_cpu(hb.rb), False)
    want = hb.rb.filter(mask)
    assert got.num_rows == want.num_rows, \
        f"filter row count {got.num_rows} != {want.num_rows}"
    for i in range(want.num_columns):
        assert_columns_equal(got.rb.column(i), want.column(i),
                             label=f"col {hb.rb.schema.names[i]}")
    return got
