"""Device percentile aggregation exec.

Role of the reference's GpuPercentile (Histogram JNI) and
GpuApproximatePercentile (t-digest) execution paths (SURVEY §2.5): an
aggregation whose functions are ALL percentile-family runs fully on
device via the sort-based kernel (ops/percentile.py).  Mixed
percentile+other aggregations stay on the CPU fallback (tagged by
AggregateMeta) — the reference similarly routes percentile through a
dedicated aggregation path.

Percentile is holistic (needs every group row at once), so the exec
concatenates the child stream and runs one traced sort+segment+gather
program per distinct input expression; group segmentation is identical
across runs because lexsort is stable and the group-key lanes agree.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops import percentile as P
from ..ops.batch_ops import (concat_batches, ensure_unique_dict,
                             shrink_to_rows)
from ..plan import expressions as E
from ..plan.aggregates import Percentile, _resolved
from .evaluator import evaluate_projection
from .plan import ExecContext, PlanNode

_TRACE_CACHE: dict = {}


class PercentileAggregateExec(PlanNode):
    def __init__(self, key_exprs: Sequence[E.Expression],
                 key_names: Sequence[str],
                 aggs: Sequence[Tuple[Percentile, str]],
                 child: PlanNode):
        super().__init__(child)
        schema = child.output_schema
        self.key_exprs = [e.bind(schema) for e in key_exprs]
        self.key_names = list(key_names)
        self.aggs = [(fn.bind(schema), name) for fn, name in aggs]
        assert all(isinstance(fn, Percentile) for fn, _ in self.aggs)

    @property
    def output_schema(self) -> t.StructType:
        fields = [t.StructField(n, e.dtype)
                  for n, e in zip(self.key_names, self.key_exprs)]
        for fn, n in self.aggs:
            fields.append(t.StructField(n, t.DOUBLE))
        return t.StructType(fields)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        conf = ctx.conf
        batches = [db for db in self.child.execute(ctx)
                   if int(db.num_rows) > 0]
        if not batches:
            if not self.key_exprs:
                yield self._null_row(conf)
            return
        from ..plan.aggregates import ApproximatePercentile
        if len(batches) > 1 and all(isinstance(fn, ApproximatePercentile)
                                    for fn, _ in self.aggs):
            # PARTIAL/FINAL split: per-partition device sketches merged
            # on host — the distributed shape (each batch = one
            # partition's rows; multi-host shards arrive the same way).
            # Ref: GpuApproximatePercentile.scala t-digest partial/merge.
            yield self._sketched(batches, ctx)
            return
        merged = concat_batches(batches, conf)

        # one value column per DISTINCT input expression; each carries
        # the q list of the aggs that share it
        val_exprs: List[E.Expression] = []
        val_map: List[Tuple[int, float]] = []   # agg i -> (col j, q)
        fps = {}
        for fn, _name in self.aggs:
            fp = repr(fn.child)
            if fp not in fps:
                fps[fp] = len(val_exprs)
                val_exprs.append(_resolved(E.Cast(fn.child, t.DOUBLE)))
            val_map.append((fps[fp], fn.percentage))

        nk = len(self.key_exprs)
        proj = evaluate_projection(
            self.key_exprs + val_exprs,
            [f"_k{i}" for i in range(nk)] +
            [f"_v{j}" for j in range(len(val_exprs))], merged, conf)
        key_cols = [ensure_unique_dict(c) for c in proj.columns[:nk]]
        val_cols = proj.columns[nk:]
        live = merged.row_mask()
        capacity = merged.capacity

        info = tuple((c.dtype, True, str(c.data.dtype)) for c in key_cols)
        from .aggregate import _seg_knobs, holistic_pack_spec
        pack = holistic_pack_spec(key_cols, self.key_exprs, self.child)
        scatter_free, max_ops = _seg_knobs(conf)
        results: List[Tuple] = [None] * len(self.aggs)
        out_keys = n_groups = None
        for j, vcol in enumerate(val_cols):
            qs = sorted({q for (jj, q) in val_map if jj == j})
            sig = (info, tuple(qs), capacity,
                   str(vcol.data.dtype), pack, scatter_free, max_ops)
            fn = _TRACE_CACHE.get(sig)
            if fn is None:
                fn = jax.jit(P.percentile_trace(
                    list(info), qs, capacity, capacity, pack_spec=pack,
                    scatter_free=scatter_free,
                    max_sort_operands=max_ops))
                _TRACE_CACHE[sig] = fn
            from ..ops.kernels import compute_view
            vdata = compute_view(vcol.data, vcol.dtype)
            ok, per_q, ng = fn(
                tuple(c.data for c in key_cols),
                tuple(c.validity for c in key_cols),
                vdata.astype(jnp.float64), vcol.validity, live)
            if out_keys is None:
                out_keys, n_groups = ok, int(ng)
            q_pos = {q: i for i, q in enumerate(qs)}
            for i, (jj, q) in enumerate(val_map):
                if jj == j:
                    results[i] = per_q[q_pos[q]]

        cols = []
        for (kd, kv), kc in zip(out_keys, key_cols):
            cols.append(DeviceColumn(kd, kv, kc.dtype, kc.dictionary,
                                     kc.data_hi))
        for data, valid in results:
            cols.append(DeviceColumn(data, valid, t.DOUBLE))
        n_out = max(n_groups, 1) if not self.key_exprs else n_groups
        db = DeviceBatch(cols, n_out,
                         self.key_names + [n for _f, n in self.aggs])
        yield shrink_to_rows(db, n_out, conf)

    def _sketched(self, batches, ctx: ExecContext) -> DeviceBatch:
        """Device sketch build per input batch (the PARTIAL), host merge
        per group across batches, interpolated FINAL."""
        import numpy as np
        import pyarrow as pa
        from ..columnar.device import to_device
        from ..columnar.host import HostBatch, dtype_to_arrow
        from ..ops.kernels import compute_view
        from ..config import APPROX_PERCENTILE_SKETCH_K
        from ..ops.quantile_sketch import merge_sketches, query_sketch
        conf = ctx.conf
        DEFAULT_K = conf.get(APPROX_PERCENTILE_SKETCH_K)
        nk = len(self.key_exprs)
        val_exprs: List[E.Expression] = []
        val_map: List[Tuple[int, float]] = []
        fps = {}
        for fn, _name in self.aggs:
            fp = repr(fn.child)
            if fp not in fps:
                fps[fp] = len(val_exprs)
                val_exprs.append(_resolved(E.Cast(fn.child, t.DOUBLE)))
            val_map.append((fps[fp], fn.percentage))

        # group key tuple -> per value-expr list of (count, points)
        merged_sketches: dict = {}
        key_dtypes = [e.dtype for e in self.key_exprs]
        for db in batches:
            proj = evaluate_projection(
                self.key_exprs + val_exprs,
                [f"_k{i}" for i in range(nk)] +
                [f"_v{j}" for j in range(len(val_exprs))], db, conf)
            key_cols = [ensure_unique_dict(c) for c in proj.columns[:nk]]
            val_cols = proj.columns[nk:]
            live = db.row_mask()
            capacity = db.capacity
            info = tuple((c.dtype, True, str(c.data.dtype))
                         for c in key_cols)
            from .aggregate import _seg_knobs, holistic_pack_spec
            pack = holistic_pack_spec(key_cols, self.key_exprs,
                                      self.child)
            scatter_free, max_ops = _seg_knobs(conf)
            for j, vcol in enumerate(val_cols):
                sig = ("sketch", info, DEFAULT_K, capacity,
                       str(vcol.data.dtype), pack, scatter_free,
                       max_ops)
                fn = _TRACE_CACHE.get(sig)
                if fn is None:
                    fn = jax.jit(P.sketch_trace(
                        list(info), DEFAULT_K, capacity, capacity,
                        pack_spec=pack, scatter_free=scatter_free,
                        max_sort_operands=max_ops))
                    _TRACE_CACHE[sig] = fn
                vdata = compute_view(vcol.data, vcol.dtype)
                ok, cnt, pts, ng = fn(
                    tuple(c.data for c in key_cols),
                    tuple(c.validity for c in key_cols),
                    vdata.astype(jnp.float64), vcol.validity, live)
                ng = int(ng)
                fetched = jax.device_get(
                    ([(kd[:ng], kv[:ng]) for kd, kv in ok],
                     cnt[:ng], pts[:ng]))
                oks, cnt_h, pts_h = fetched
                for g in range(ng):
                    kt = []
                    for (kd, kv), kc in zip(oks, key_cols):
                        if not kv[g]:
                            kt.append(None)
                        elif kc.dictionary is not None:
                            kt.append(str(kc.dictionary[int(kd[g])]))
                        elif isinstance(kc.dtype, t.DoubleType) and \
                                np.asarray(kd).dtype == np.int64:
                            # host-loaded doubles ride as f64 BIT
                            # PATTERNS in the int64 storage lane
                            kt.append(float(np.int64(kd[g]).view(
                                np.float64)))
                        else:
                            kt.append(kd[g].item())
                    slot = merged_sketches.setdefault(
                        tuple(kt), [[] for _ in val_exprs])
                    slot[j].append((int(cnt_h[g]), pts_h[g]))

        if not merged_sketches and not self.key_exprs:
            merged_sketches[()] = [[] for _ in val_exprs]
        keys_out = sorted(merged_sketches.keys(),
                          key=lambda kt: tuple(
                              (v is None, v) for v in kt))
        arrays = []
        for i in range(nk):
            vals = [kt[i] for kt in keys_out]
            arrays.append(pa.array(vals, dtype_to_arrow(key_dtypes[i])))
        # merge once per (group, value column); percentiles share it
        final = {kt: [merge_sketches(slots[jj], k=DEFAULT_K)
                      for jj in range(len(val_exprs))]
                 for kt, slots in merged_sketches.items()}
        for i, (jj, q) in enumerate(val_map):
            arrays.append(pa.array(
                [query_sketch(*final[kt][jj], q) for kt in keys_out],
                pa.float64()))
        names = self.key_names + [n for _f, n in self.aggs]
        rb = pa.RecordBatch.from_arrays(
            arrays, schema=pa.schema(
                [pa.field(n, a.type) for n, a in zip(names, arrays)]))
        return to_device(HostBatch(rb), conf)

    def _null_row(self, conf) -> DeviceBatch:
        from ..columnar.device import bucket_capacity
        cap = bucket_capacity(1, conf)
        cols = [DeviceColumn(jnp.zeros((cap,), jnp.float64),
                             jnp.zeros((cap,), bool), t.DOUBLE)
                for _ in self.aggs]
        return DeviceBatch(cols, 1, [n for _f, n in self.aggs])

    def describe(self):
        return (f"PercentileAggregateExec[keys={self.key_names}, "
                f"{[n for _f, n in self.aggs]}]")
