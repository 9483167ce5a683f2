"""Device collect_list / collect_set aggregation exec.

Role of the reference's collect aggregations (GpuAggregateExec.scala +
cuDF collect_list/collect_set ops; windowed forms in
GpuWindowExpression.scala): a group-by whose aggregates are ALL collect
functions runs fully on device via the sort-segment collect kernel
(ops/percentile.py collect_trace), emitting RAGGED result columns over
the values+offsets device layout.  Mixed collect+other aggregations are
tagged to the CPU path by AggregateMeta, like the percentile family.

Collect is holistic (a group's list spans every input batch), so the
exec concatenates the child stream first — the same partial/final
collapse the reference performs when it concatenates partial collect
buffers before the final pass."""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops import percentile as P
from ..ops.batch_ops import concat_batches, ensure_unique_dict
from ..plan import expressions as E
from ..plan.aggregates import CollectList, CollectSet
from .evaluator import evaluate_projection
from .plan import ExecContext, PlanNode

_TRACE_CACHE: dict = {}


class CollectAggregateExec(PlanNode):
    def __init__(self, key_exprs: Sequence[E.Expression],
                 key_names: Sequence[str],
                 aggs: Sequence[Tuple[CollectList, str]],
                 child: PlanNode):
        super().__init__(child)
        schema = child.output_schema
        self.key_exprs = [e.bind(schema) for e in key_exprs]
        self.key_names = list(key_names)
        self.aggs = [(fn.bind(schema), name) for fn, name in aggs]
        assert all(isinstance(fn, CollectList) for fn, _ in self.aggs)

    @property
    def output_schema(self) -> t.StructType:
        fields = [t.StructField(n, e.dtype)
                  for n, e in zip(self.key_names, self.key_exprs)]
        for fn, n in self.aggs:
            fields.append(t.StructField(n, fn.dtype))
        return t.StructType(fields)

    def keys_unique(self, names):
        if not self.key_exprs:
            return True
        return set(self.key_names) <= set(names)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        conf = ctx.conf
        batches = [db for db in self.child.execute(ctx)
                   if int(db.num_rows) > 0]
        if not batches:
            if not self.key_exprs:
                yield self._empty_row(conf)
            return
        merged = concat_batches(batches, conf)

        val_exprs: List[E.Expression] = []
        val_map: List[int] = []     # agg i -> (col j, distinct)
        fps = {}
        for fn, _name in self.aggs:
            fp = (repr(fn.child), isinstance(fn, CollectSet))
            if fp not in fps:
                fps[fp] = len(val_exprs)
                val_exprs.append(fn.child)  # already bound
            val_map.append(fps[fp])

        nk = len(self.key_exprs)
        proj = evaluate_projection(
            self.key_exprs + val_exprs,
            [f"_k{i}" for i in range(nk)] +
            [f"_v{j}" for j in range(len(val_exprs))], merged, conf)
        key_cols = [ensure_unique_dict(c) for c in proj.columns[:nk]]
        # value dictionaries must be duplicate-free too: collect_set
        # dedupes by CODE (same reason as exec/distinct.py)
        val_cols = [ensure_unique_dict(c) if c.dictionary is not None
                    else c for c in proj.columns[nk:]]
        live = merged.row_mask()
        capacity = merged.capacity
        info = tuple((c.dtype, True, str(c.data.dtype)) for c in key_cols)
        from .aggregate import _seg_knobs, holistic_pack_spec
        pack = holistic_pack_spec(key_cols, self.key_exprs, self.child)
        _sf, max_ops = _seg_knobs(ctx.conf)

        results = [None] * len(self.aggs)
        out_keys = n_groups = None
        group_live = None
        flavors = list(fps)          # (child repr, distinct) per val col
        for j, vcol in enumerate(val_cols):
            distinct = flavors[j][1]
            sig = ("collect", info, capacity, distinct,
                   str(vcol.data.dtype), pack, max_ops)
            fn = _TRACE_CACHE.get(sig)
            if fn is None:
                fn = jax.jit(P.collect_trace(
                    list(info), capacity, capacity, distinct,
                    vcol.dtype, pack_spec=pack,
                    max_sort_operands=max_ops), static_argnums=())
                _TRACE_CACHE[sig] = fn
            ok, values, offs, ev, ng, _gl = fn(
                tuple(c.data for c in key_cols),
                tuple(c.validity for c in key_cols),
                vcol.data, vcol.validity, live)
            if out_keys is None:
                out_keys, n_groups = ok, int(ng)
                group_live = _gl
            for i, jj in enumerate(val_map):
                if jj == j:
                    results[i] = (values, offs, ev, vcol)

        cols = []
        for (kd, kv), kc in zip(out_keys, key_cols):
            cols.append(DeviceColumn(kd, kv, kc.dtype, kc.dictionary,
                                     kc.data_hi))
        for (values, offs, ev, vcol), (fn_, _n) in zip(results, self.aggs):
            cols.append(DeviceColumn(
                values, group_live, fn_.dtype,
                vcol.dictionary, offsets=offs, elem_valid=ev))
        n_out = max(n_groups, 1) if not self.key_exprs else n_groups
        db = DeviceBatch(cols, n_out,
                         self.key_names + [n for _f, n in self.aggs])
        yield db

    def _empty_row(self, conf) -> DeviceBatch:
        from ..columnar.device import bucket_capacity
        cap = bucket_capacity(1, conf)
        cols = []
        for fn, _n in self.aggs:
            cols.append(DeviceColumn(
                jnp.zeros((cap,), t.physical_np_dtype(
                    fn.dtype.element_type)),
                jnp.ones((cap,), bool), fn.dtype, None,
                offsets=jnp.zeros((cap + 1,), jnp.int32),
                elem_valid=jnp.zeros((cap,), bool)))
        return DeviceBatch(cols, 1, [n for _f, n in self.aggs])

    def describe(self):
        return (f"CollectAggregateExec[keys={self.key_names}, "
                f"{[n for _f, n in self.aggs]}]")
