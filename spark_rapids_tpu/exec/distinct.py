"""Device count(DISTINCT) aggregation exec (ops/distinct.py runner).

Routing mirrors the percentile exec: an aggregation whose functions are
ALL CountDistinct runs here (one sorted program per distinct input
expression); mixing with streaming aggregates tags to the CPU path.
This is the device rewrite of the reference's per-key dedupe plan.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.batch_ops import (concat_batches, ensure_unique_dict,
                             shrink_to_rows)
from ..ops.distinct import distinct_count_trace
from ..plan import expressions as E
from ..plan.aggregates import CountDistinct
from .evaluator import evaluate_projection
from .plan import ExecContext, PlanNode

_TRACE_CACHE: dict = {}


class DistinctAggregateExec(PlanNode):
    def __init__(self, key_exprs: Sequence[E.Expression],
                 key_names: Sequence[str],
                 aggs: Sequence[Tuple[CountDistinct, str]],
                 child: PlanNode):
        super().__init__(child)
        schema = child.output_schema
        self.key_exprs = [e.bind(schema) for e in key_exprs]
        self.key_names = list(key_names)
        self.aggs = [(fn.bind(schema), name) for fn, name in aggs]
        assert all(isinstance(fn, CountDistinct) for fn, _ in self.aggs)

    @property
    def output_schema(self) -> t.StructType:
        fields = [t.StructField(n, e.dtype)
                  for n, e in zip(self.key_names, self.key_exprs)]
        for _fn, n in self.aggs:
            fields.append(t.StructField(n, t.LONG, False))
        return t.StructType(fields)

    def keys_unique(self, names) -> bool:
        # one output row per group-key tuple
        if not self.key_exprs:
            return True
        return set(self.key_names) <= set(names)

    def static_row_count(self):
        return 1 if not self.key_exprs else None

    def column_range(self, name):
        from .join import key_ref_names
        if name not in self.key_names:
            return None
        ref = key_ref_names([self.key_exprs[self.key_names.index(name)]])
        return None if ref is None else self.child.column_range(ref[0])

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        conf = ctx.conf
        # no per-batch sync: statically-empty batches are dropped, lazy
        # counts flow through (padding rows are masked by `live` below)
        batches = [db for db in self.child.execute(ctx)
                   if not (isinstance(db.num_rows, int)
                           and db.num_rows == 0)]
        if not batches:
            if not self.key_exprs:
                yield self._zero_row(conf)
            return
        merged = concat_batches(batches, conf)

        val_exprs: List[E.Expression] = []
        val_of: List[int] = []
        fps = {}
        for fn, _name in self.aggs:
            fp = repr(fn.child)
            if fp not in fps:
                fps[fp] = len(val_exprs)
                val_exprs.append(fn.child)
            val_of.append(fps[fp])

        nk = len(self.key_exprs)
        proj = evaluate_projection(
            self.key_exprs + val_exprs,
            [f"_k{i}" for i in range(nk)] +
            [f"_v{j}" for j in range(len(val_exprs))], merged, conf)
        key_cols = [ensure_unique_dict(c) for c in proj.columns[:nk]]
        val_cols = [ensure_unique_dict(c) if c.dictionary is not None
                    else c for c in proj.columns[nk:]]
        live = merged.row_mask()
        capacity = merged.capacity

        info = tuple((c.dtype, True, str(c.data.dtype)) for c in key_cols)
        from .aggregate import _seg_knobs, holistic_pack_spec
        from .join import key_ref_names
        pack = holistic_pack_spec(key_cols, self.key_exprs, self.child)
        scatter_free, max_ops = _seg_knobs(conf)
        results: List = [None] * len(self.aggs)
        out_keys = n_groups = None
        for j, vcol in enumerate(val_cols):
            # exact value bounds (dictionary size / scan range stats)
            # let the value lane ride the packed key sort — the whole
            # count-distinct order becomes ONE 2-operand sort
            if vcol.dictionary is not None:
                val_range = (0, max(len(vcol.dictionary) - 1, 0))
            else:
                ref = key_ref_names([val_exprs[j]])
                val_range = None if ref is None \
                    else self.child.column_range(ref[0])
                if val_range is not None and not isinstance(
                        vcol.dtype, (t.DoubleType, t.FloatType)):
                    val_range = (int(val_range[0]), int(val_range[1]))
                else:
                    val_range = None
            sig = (info, capacity, vcol.dtype.simple_string,
                   str(vcol.data.dtype), pack, val_range, scatter_free,
                   max_ops)
            fn = _TRACE_CACHE.get(sig)
            if fn is None:
                fn = jax.jit(distinct_count_trace(
                    list(info), capacity, capacity, pack_spec=pack,
                    val_range=val_range, scatter_free=scatter_free,
                    max_sort_operands=max_ops)(vcol.dtype))
                _TRACE_CACHE[sig] = fn
            ok, (cnt, valid), ng = fn(
                tuple(c.data for c in key_cols),
                tuple(c.validity for c in key_cols),
                vcol.data, vcol.validity, live)
            if out_keys is None:
                out_keys = ok
                n_groups = ng if isinstance(ng, jax.core.Tracer) else int(ng)
            for i, jj in enumerate(val_of):
                if jj == j:
                    results[i] = (cnt, valid)

        cols = []
        for (kd, kv), kc in zip(out_keys, key_cols):
            cols.append(DeviceColumn(kd, kv, kc.dtype, kc.dictionary,
                                     kc.data_hi))
        for cnt, valid in results:
            # count(DISTINCT) is never null: 0 for empty groups
            cols.append(DeviceColumn(
                cnt, jnp.ones(cnt.shape, bool), t.LONG))
        names = self.key_names + [n for _f, n in self.aggs]
        if isinstance(n_groups, int):
            n_out = max(n_groups, 1) if not self.key_exprs else n_groups
            yield shrink_to_rows(DeviceBatch(cols, n_out, names), n_out,
                                 conf)
            return
        n_out = jnp.maximum(n_groups, 1) if not self.key_exprs else n_groups
        yield DeviceBatch(cols, n_out, names)

    def _zero_row(self, conf) -> DeviceBatch:
        from ..columnar.device import bucket_capacity
        cap = bucket_capacity(1, conf)
        cols = [DeviceColumn(jnp.zeros((cap,), jnp.int64),
                             jnp.ones((cap,), bool), t.LONG)
                for _ in self.aggs]
        return DeviceBatch(cols, 1, [n for _f, n in self.aggs])

    def describe(self):
        return (f"DistinctAggregateExec[keys={self.key_names}, "
                f"{[n for _f, n in self.aggs]}]")
