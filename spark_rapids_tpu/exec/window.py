"""WindowExec: partition-sorted window evaluation in one device program.

Reference: GpuWindowExec (window/GpuWindowExec.scala:146) and its batched
variants evaluate window expressions per partition using cuDF rolling /
scan aggregations after the planner guarantees child ordering.

TPU shape: the exec
  1. concatenates the child stream (windows need whole partitions; the
     reference's RequireSingleBatch goal for generic windows —
     GpuWindowExec.scala batching policy),
  2. projects partition keys / order keys / function inputs as appended
     internal columns (one fused projection program),
  3. lexsorts by (partition, order) keys (ops/sort.py),
  4. runs ONE jit window program (ops/window.py) computing every window
     expression, and emits the child columns + window outputs in sorted
     order (Spark's WindowExec also emits child order = sort order).

Inside a whole-plan program (`ExecContext.traced`) the same steps are
traced into the caller's program: no batch is dropped for a count the host
would have to read, the batches stack under their row masks, the sort puts
dead rows last, and the window kernel is traced inline, not jitted apart.

Out-of-core inputs: batches are merged under the memory budget's retry
machinery upstream (exec/plan.py CoalesceBatchesExec); partition-chunked
OOC windows (GpuCachedDoublePassWindowExec analogue) can layer on the same
kernel later without changing it.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.batch_ops import concat_batches
from ..ops.sort import SortKey, sort_batch
from ..ops.window import window_trace
from ..plan import expressions as E
from ..plan.window import (WindowFrame, WindowFunctionSpec, default_frame)
from .evaluator import evaluate_projection
from .plan import ExecContext, PlanNode

_WINDOW_JIT_CACHE = {}


class WindowExec(PlanNode):
    """window_exprs: (WindowFunctionSpec, out_name) pairs.
    partition_keys: expressions; order_keys: (expr, asc, nulls_first)."""

    def __init__(self, window_exprs: Sequence[Tuple[WindowFunctionSpec, str]],
                 partition_keys: Sequence[E.Expression],
                 order_keys: Sequence[Tuple[E.Expression, bool, bool]],
                 child: PlanNode):
        from ..plan.window import check_window_analysis
        super().__init__(child)
        check_window_analysis(window_exprs, order_keys)
        schema = child.output_schema
        self.window_exprs = [(spec.bind(schema), name)
                             for spec, name in window_exprs]
        self.partition_keys = [e.bind(schema) for e in partition_keys]
        self.order_keys = [(e.bind(schema), asc, nf)
                           for e, asc, nf in order_keys]

    @property
    def output_schema(self) -> t.StructType:
        fields = list(self.child.output_schema.fields)
        for spec, name in self.window_exprs:
            fields.append(t.StructField(name, spec.dtype))
        return t.StructType(fields)

    def _resolved_frame(self, spec: WindowFunctionSpec) -> WindowFrame:
        if spec.frame is not None:
            return spec.frame
        if spec.kind in ("row_number", "rank", "dense_rank", "percent_rank",
                         "cume_dist", "ntile", "lead", "lag"):
            return WindowFrame("range", None, None)   # structural; unused
        return default_frame(bool(self.order_keys))

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        if ctx.traced:
            # inside a whole-plan program a row count is a value of the
            # program: every batch rides along, its dead rows under the
            # row mask, stacked as the sorted aggregate stacks them; the
            # sort below puts them last
            batches = list(self.child.execute(ctx))
            if not batches:
                return
            db = concat_batches(batches, ctx.conf, masked=True)
        else:
            batches = [db for db in self.child.execute(ctx)
                       if int(db.num_rows) > 0]
            if not batches:
                return
            db = batches[0] if len(batches) == 1 \
                else concat_batches(batches, ctx.conf)

        child_names = list(db.names)
        n_child = len(child_names)

        # --- 2. append internal key/input columns via one projection ---
        aug_exprs: List[E.Expression] = [
            E.ColumnRef(n).bind(db.schema) for n in child_names]
        aug_names = list(child_names)
        p_idx, o_idx, v_idx = [], [], []
        for i, e in enumerate(self.partition_keys):
            aug_exprs.append(e)
            aug_names.append(f"__w_p{i}")
            p_idx.append(len(aug_exprs) - 1)
        for i, (e, _a, _nf) in enumerate(self.order_keys):
            aug_exprs.append(e)
            aug_names.append(f"__w_o{i}")
            o_idx.append(len(aug_exprs) - 1)
        inputs: List[E.Expression] = []
        spec_input_idx: List[int] = []
        for spec, _name in self.window_exprs:
            if spec.child is None:
                spec_input_idx.append(-1)
                continue
            aug_exprs.append(spec.child)
            aug_names.append(f"__w_v{len(inputs)}")
            inputs.append(spec.child)
            v_idx.append(len(aug_exprs) - 1)
            spec_input_idx.append(len(inputs) - 1)
        aug = evaluate_projection(aug_exprs, aug_names, db, ctx.conf,
                                  exact_doubles=True)

        # --- 3. sort by (partition, order) ---
        sort_keys = [SortKey(i, True, True) for i in p_idx]
        sort_keys += [SortKey(i, asc, nf) for i, (_e, asc, nf)
                      in zip(o_idx, self.order_keys)]
        s = sort_batch(aug, sort_keys, ctx.conf) if sort_keys else aug

        # --- 4. the window program ---
        specs_frames = [(spec, self._resolved_frame(spec), vi)
                        for (spec, _n), vi in zip(self.window_exprs,
                                                  spec_input_idx)]
        part_cols = [s.columns[i] for i in p_idx]
        order_cols = [s.columns[i] for i in o_idx]
        val_cols = [s.columns[i] for i in v_idx]

        # sort directions only shape the traced program for value-offset
        # RANGE frames — keep them out of the cache key otherwise
        has_value_range = any(f.is_value_offset
                              for _s, f, _i in specs_frames)
        order_dirs = tuple((asc, nf) for _e, asc, nf in self.order_keys) \
            if has_value_range else ()
        from .aggregate import _seg_knobs
        scatter_free, max_ops = _seg_knobs(ctx.conf)

        def program():
            return window_trace(
                tuple((c.dtype,) for c in part_cols),
                tuple((c.dtype,) for c in order_cols),
                tuple((c.dtype,) for c in val_cols),
                specs_frames, s.capacity, order_dirs=order_dirs,
                scatter_free=scatter_free, max_sort_operands=max_ops)

        if ctx.traced:
            fn = program()             # traced into the caller's program
            ctx.bump("window.in_program")
        else:
            key = ("window", s.capacity,
                   tuple(sp.fingerprint() for sp, _f, _i in specs_frames),
                   tuple(f.fp() for _s, f, _i in specs_frames),
                   tuple(i for _s, _f, i in specs_frames),
                   order_dirs, scatter_free, max_ops,
                   tuple((c.dtype.simple_string, str(c.data.dtype))
                         for c in part_cols + order_cols + val_cols))
            fn = _WINDOW_JIT_CACHE.get(key)
            if fn is None:
                fn = _WINDOW_JIT_CACHE[key] = jax.jit(program())
        outs = fn(tuple(c.data for c in part_cols),
                  tuple(c.validity for c in part_cols),
                  tuple(c.data for c in order_cols),
                  tuple(c.validity for c in order_cols),
                  tuple(c.data for c in val_cols),
                  tuple(c.validity for c in val_cols),
                  s.row_mask())

        cols = list(s.columns[:n_child])
        names = list(child_names)
        for (spec, name), vi, (data, valid) in zip(self.window_exprs,
                                                   spec_input_idx, outs):
            dictionary = None
            if isinstance(spec.dtype, t.StringType) and vi >= 0:
                # value pass-through functions keep the input dictionary
                dictionary = val_cols[vi].dictionary
            cols.append(DeviceColumn(data, valid, spec.dtype, dictionary))
            names.append(name)
        yield DeviceBatch(cols, s.num_rows, names)

    def describe(self):
        return (f"WindowExec[{[n for _, n in self.window_exprs]}, "
                f"part={len(self.partition_keys)}, "
                f"order={len(self.order_keys)}]")
