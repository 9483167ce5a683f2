"""Compile-and-run machinery turning expression trees into XLA programs.

The reference evaluates each GpuExpression as a sequence of cuDF kernel
launches (GpuExpressions.scala columnarEval); here an operator's whole
expression list traces into ONE jit-compiled XLA program per
(operator, row-bucket) pair — XLA fuses the elementwise pipeline, which is
the TPU-idiomatic replacement for both columnarEval and the cudf AST
compiler (reference AstUtil.scala / GpuTieredProject common-subexpression
tiers: XLA's CSE does the tier work for free on the traced graph).

Jit caching: keyed on (identity of the bound expression list, capacity,
input physical signature, aux signature).  Batches flowing through the same
physical operator share bound trees, so steady-state execution hits the
cache; the bounded row-bucket set bounds total compiles.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from ..config import TpuConf
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.kernels import compute_view, storage_view, live_mask
from ..plan.expressions import DevVal, EvalCtx, Expression, PrepCtx

_JIT_CACHE = {}


def _input_sig(db: DeviceBatch):
    return tuple((str(c.data.dtype), c.data_hi is not None) for c in db.columns)


_AUX_DEVICE_CACHE = {}
_SMALL_AUX_CACHE = {}
_SCALAR_CACHE = {}


def _upload_aux(a) -> jax.Array:
    """Device copy of a host aux array, cached by content.

    Aux arrays (literal values, dictionary rank tables) repeat identically
    across batches and re-planned queries; uploading them per call costs a
    host->device transfer each — material when the chip sits behind a
    high-latency link.  Tiny scalars (e.g. monotonically_increasing_id's
    per-batch base) churn a DIFFERENT value every batch — they get their
    own small cache so they cannot evict the big shared uploads."""
    if isinstance(a, (jax.Array, jax.core.Tracer)):
        # already on device, or a lifted-literal tracer of the enclosing
        # whole-plan trace: pass through as a positional jit ARGUMENT
        # (caching a tracer would leak it into later eager calls)
        return a
    a = np.asarray(a)
    key = (a.dtype.str, a.shape, a.tobytes())
    cache = _SMALL_AUX_CACHE if a.nbytes <= 16 else _AUX_DEVICE_CACHE
    buf = cache.get(key)
    if buf is None:
        buf = jnp.asarray(a)
        if isinstance(buf, jax.core.Tracer):
            # under whole-plan tracing the "upload" is a traced constant —
            # caching it would leak the tracer into later eager calls
            return buf
        if len(cache) > 4096:
            cache.clear()
        cache[key] = buf
    return buf


def _num_rows_scalar(num_rows) -> jax.Array:
    if not isinstance(num_rows, int):
        return num_rows.astype(jnp.int32)
    buf = _SCALAR_CACHE.get(num_rows)
    if buf is None:
        buf = jnp.int32(num_rows)
        if isinstance(buf, jax.core.Tracer):
            return buf           # whole-plan tracing: never cache tracers
        if len(_SCALAR_CACHE) > 4096:
            _SCALAR_CACHE.clear()
        _SCALAR_CACHE[num_rows] = buf
    return buf


def _lift_enabled(conf: TpuConf) -> bool:
    from ..config import COMPILE_CONST_LIFT
    return bool(conf.get(COMPILE_CONST_LIFT))


def _prepare(exprs: Sequence[Expression], db: DeviceBatch, conf: TpuConf):
    dicts = {n: c.dictionary for n, c in zip(db.names, db.columns)}
    pctx = PrepCtx(conf, dicts, batch=db, lift_literals=_lift_enabled(conf))
    hostvals = [e.prepare(pctx) for e in exprs]
    aux = tuple(_upload_aux(a) for a in pctx.aux)
    return pctx, hostvals, aux


def _batch_meta(db: DeviceBatch):
    """(name, logical dtype, dictionary) per column — all a traced closure
    needs from the batch.  Capturing `db` itself would pin its device
    buffers in the jit cache for process lifetime."""
    return [(n, c.dtype, c.dictionary) for n, c in zip(db.names, db.columns)]


def _col_lanes(db: DeviceBatch):
    """Per-column jit argument: the data lane, (data, hi) for two-lane
    wide-decimal host columns, or (data, offsets, elem_valid) for ragged
    ARRAY columns (pytrees — jit handles the nesting)."""
    out = []
    for c in db.columns:
        if c.offsets is not None:
            out.append((c.data, c.offsets, c.elem_valid))
        elif c.data_hi is not None:
            out.append((c.data, c.data_hi))
        else:
            out.append(c.data)
    return tuple(out)


def _build_inputs(meta, col_data, col_valid):
    import numpy as _np
    from .. import types as t
    inputs = {}
    raw = {}
    for (name, dtype, dictionary), d, v in zip(meta, col_data, col_valid):
        hi = offsets = elem_valid = None
        if isinstance(d, tuple):
            if len(d) == 3:
                d, offsets, elem_valid = d
            else:
                d, hi = d
        view = d if offsets is not None else compute_view(d, dtype)
        narrow = None
        if offsets is None and hi is None and \
                not isinstance(dtype, (t.StringType, t.DoubleType,
                                       t.BooleanType, t.NullType)):
            # FOR-narrowed lane (value-preserving, ops/encodings.py):
            # expose the full-width view for generic consumers — the
            # widen is a fused convert, DCE'd when every consumer stays
            # narrow — and the narrow lane for encoded-aware ones
            phys = _np.dtype(t.physical_np_dtype(dtype))
            lane = _np.dtype(view.dtype)
            if lane.kind == "i" and phys.kind == "i" and \
                    lane.itemsize < phys.itemsize:
                narrow = view
                view = view.astype(phys)
        # a DOUBLE held as its bit pattern keeps it beside the f64 view
        bits = d if isinstance(dtype, t.DoubleType) and \
            d.dtype == jnp.int64 else None
        inputs[name] = DevVal(view, v, dtype, dictionary, hi,
                              offsets=offsets, elem_valid=elem_valid,
                              narrow=narrow, bits=bits)
        raw[name] = d          # storage lane (f64-bits stay int64)
    return inputs, raw


def _expr_fp(e) -> str:
    fp = e.__dict__.get("_fp_cache")
    if fp is None:
        fp = e.fingerprint()
        e.__dict__["_fp_cache"] = fp
    return fp


def _expr_canon_fp(e) -> str:
    fp = e.__dict__.get("_canon_fp_cache")
    if fp is None:
        fp = e.canonical_fingerprint()
        e.__dict__["_canon_fp_cache"] = fp
    return fp


def _jit_key(exprs, db, aux, conf, tag):
    # keyed on expression STRUCTURE (fingerprint), not object identity:
    # re-planned queries (every bench iteration, every AQE re-plan) must hit
    # the compiled program, not re-trace it.  Batch layout (column names,
    # logical dtypes) is part of the key — ColumnRefs resolve positionally
    # at trace time, so same-shaped batches with different layouts must not
    # share a program.  Under constant lifting the CANONICAL fingerprint
    # erases lifted literal values (they are runtime aux arguments), so
    # literal-only-different expressions share one program.
    fp = _expr_canon_fp if _lift_enabled(conf) else _expr_fp
    return (tag, tuple(fp(e) for e in exprs), db.capacity,
            tuple(db.names),
            tuple(c.dtype.simple_string for c in db.columns),
            _input_sig(db), tuple((a.shape, str(a.dtype)) for a in aux),
            conf.ansi)


def evaluate_projection(exprs: Sequence[Expression], names: Sequence[str],
                        db: DeviceBatch, conf: TpuConf,
                        exact_doubles: bool = False) -> DeviceBatch:
    """Project `db` through bound expressions -> new DeviceBatch.

    `exact_doubles`: a DOUBLE whose exact bit pattern is at hand (a host
    column passed through, a decimal cast, a division of such doubles:
    DevVal.bits) leaves as that int64 storage lane, as a scan delivers it,
    and not through the device's f64; for projections whose output goes
    on to other operators (ProjectExec, a window's inputs).  An operator
    that projects its own inputs and reads them as f64 leaves it off."""
    if db.thin is not None:
        # late materialization: referenced deferred columns resolve here
        # (ONE composed gather per lane source); unreferenced ones stay
        # zero-capacity placeholders the traced program never reads
        from ..columnar.lanes import materialize_refs
        db = materialize_refs(db, exprs, conf)
    if db.sel is not None and any(c.offsets is not None
                                  for c in db.columns):
        # ragged kernels bound live VALUES by offsets[num_rows] — a
        # prefix assumption a selection vector violates; materialize
        from ..ops.batch_ops import ensure_prefix
        db = ensure_prefix(db, conf)
    pctx, hostvals, aux = _prepare(exprs, db, conf)
    has_sel = db.sel is not None
    key = _jit_key(exprs, db, aux, conf, ("project", has_sel,
                                          exact_doubles))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        capacity = db.capacity
        node_slots = dict(pctx.node_slots)
        node_info = dict(pctx.node_info)
        exprs_t = tuple(exprs)
        meta = _batch_meta(db)

        def run(col_data, col_valid, num_rows, aux_arrs, *sel_opt):
            inputs, raw = _build_inputs(meta, col_data, col_valid)
            ctx = EvalCtx(capacity, num_rows, inputs, aux_arrs, node_slots,
                          conf, raw, node_info=node_info)
            # a selection vector replaces prefix liveness (lazy join
            # output: live rows are sel-True, not a front prefix)
            live = sel_opt[0] if sel_opt else live_mask(capacity, num_rows)
            outs = []
            for e in exprs_t:
                dv = e.eval_dev(ctx)
                if dv.offsets is not None:
                    data = dv.data
                elif dv.narrow is not None:
                    # FOR-narrowed lane rides through the projection
                    # un-widened (the decode stays sunk downstream)
                    data = dv.narrow
                elif exact_doubles and dv.bits is not None:
                    data = dv.bits
                else:
                    data = storage_view(dv.data, e.dtype)
                valid = dv.validity if dv.validity is not None \
                    else jnp.ones((capacity,), bool)
                # two-lane wide decimals keep their hi lane through the
                # projection (dropping it would corrupt |values| >= 2^63);
                # ragged (ARRAY) results keep offsets + element validity
                outs.append((data, valid & live, dv.hi, dv.offsets,
                             dv.elem_valid))
            return outs

        fn = jax.jit(run)
        _JIT_CACHE[key] = fn

    col_data = _col_lanes(db)
    col_valid = tuple(c.validity for c in db.columns)
    extra = (db.sel,) if has_sel else ()
    outs = fn(col_data, col_valid, _num_rows_scalar(db.num_rows), aux,
              *extra)
    cols = []
    for (data, valid, hi, offsets, ev), e, hv in zip(outs, exprs, hostvals):
        cols.append(DeviceColumn(data, valid, e.dtype, hv.dictionary,
                                 hi, offsets=offsets, elem_valid=ev))
    return DeviceBatch(cols, db.num_rows, list(names), db.origin_file,
                       sel=db.sel)


def project_batch(exprs: Sequence[Expression], names: Sequence[str],
                  db: DeviceBatch, conf: TpuConf) -> DeviceBatch:
    """ProjectExec entry point: evaluate_projection, except deferred
    columns referenced ONLY as plain pass-through refs STAY THIN — the
    placeholder and its lane bookkeeping move to the output position, so
    a projection between two joins doesn't force the materialization the
    join chain deferred.  Computed expressions still materialize exactly
    the columns they reference (early materialization)."""
    if db.thin is None:
        return evaluate_projection(exprs, names, db, conf,
                                   exact_doubles=True)
    if any(c.offsets is not None for c in db.columns):
        # ragged lanes can force an internal prefix compaction whose
        # row order would desync from pass-through lanes — stay dense
        return evaluate_projection(exprs, names, db, conf,
                                   exact_doubles=True)
    from ..columnar.lanes import (ThinState, materialize_refs,
                                  passthrough_positions)
    pass_map = passthrough_positions(db, exprs)
    eval_idx = [i for i in range(len(exprs)) if i not in pass_map]
    db = materialize_refs(db, [exprs[i] for i in eval_idx], conf)
    ts = db.thin
    if ts is not None and pass_map:
        # a computed expr may have materialized a pass-through column too
        pass_map = {oi: p for oi, p in pass_map.items() if p in ts.pending}
    if ts is None or not pass_map:
        return evaluate_projection(exprs, names, db, conf,
                                   exact_doubles=True)
    cols: List[Optional[DeviceColumn]] = [None] * len(exprs)
    if eval_idx:
        ev = evaluate_projection([exprs[i] for i in eval_idx],
                                 [names[i] for i in eval_idx], db, conf,
                                 exact_doubles=True)
        for i, c in zip(eval_idx, ev.columns):
            cols[i] = c
    used: List = []
    src_map: Dict[int, int] = {}
    new_pending: Dict[int, Tuple[int, int]] = {}
    for oi, p in pass_map.items():
        s, c = ts.pending[p]
        if s not in src_map:
            src_map[s] = len(used)
            used.append(ts.sources[s])
        cols[oi] = db.columns[p]          # the zero-capacity placeholder
        new_pending[oi] = (src_map[s], c)
    thin = ThinState(ts.capacity, used, new_pending)
    return DeviceBatch(cols, db.num_rows, list(names), db.origin_file,
                       sel=db.sel, thin=thin)


def compute_predicate(cond: Expression, db: DeviceBatch,
                      conf: TpuConf) -> jax.Array:
    """Evaluate a boolean expression -> keep-mask (False for null/padding)."""
    if db.thin is not None:
        from ..columnar.lanes import materialize_refs
        db = materialize_refs(db, [cond], conf)
    if db.sel is not None and any(c.offsets is not None
                                  for c in db.columns):
        from ..ops.batch_ops import ensure_prefix
        db = ensure_prefix(db, conf)
    pctx, _, aux = _prepare([cond], db, conf)
    has_sel = db.sel is not None
    key = _jit_key([cond], db, aux, conf, ("predicate", has_sel))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        capacity = db.capacity
        node_slots = dict(pctx.node_slots)
        node_info = dict(pctx.node_info)
        meta = _batch_meta(db)

        def run(col_data, col_valid, num_rows, aux_arrs, *sel_opt):
            inputs, raw = _build_inputs(meta, col_data, col_valid)
            ctx = EvalCtx(capacity, num_rows, inputs, aux_arrs, node_slots,
                          conf, raw, node_info=node_info)
            dv = cond.eval_dev(ctx)
            keep = dv.data
            if dv.validity is not None:
                keep = keep & dv.validity
            live = sel_opt[0] if sel_opt else live_mask(capacity, num_rows)
            return keep & live

        fn = jax.jit(run)
        _JIT_CACHE[key] = fn
    extra = (db.sel,) if has_sel else ()
    return fn(_col_lanes(db), tuple(c.validity for c in db.columns),
              _num_rows_scalar(db.num_rows), aux, *extra)


def apply_filter(cond: Expression, db: DeviceBatch, conf: TpuConf) -> DeviceBatch:
    from ..ops.filter import compact_batch
    return compact_batch(db, compute_predicate(cond, db, conf), conf)
