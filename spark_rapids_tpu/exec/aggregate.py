"""Hash-aggregate execution: partial -> merge -> final over device batches.

The GpuHashAggregateExec analogue (reference GpuAggregateExec.scala:1711,
call stack SURVEY §3.3): per input batch, project the aggregate inputs and
run the update groupby (partial); accumulated partials are concatenated and
re-grouped with the merge ops; the final projection evaluates each
aggregate's result expression over the merged buffers.

TPU-first deltas from the reference:
  * partial aggregation is sort+segment (ops/groupby.py), not hash tables;
  * merge is concat+regroup in one jit rather than cuDF concatenate+groupby;
  * string group keys ride as unified dictionary codes, so regrouping
    across batches is plain int comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from ..config import TpuConf
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops import groupby as G
from ..ops.batch_ops import concat_batches, ensure_unique_dict, \
    shrink_to_rows
from ..plan import expressions as E
from ..plan.aggregates import AggregateFunction
from .evaluator import evaluate_projection

_GROUPBY_CACHE = {}
_REDUCE_CACHE = {}


_DENSE_DOMAIN_MAX = 4096
_DICT_UNIQUE_CACHE: dict = {}


def _dict_unique(d: pa.Array) -> bool:
    """Duplicate-free dictionary (code equality == value equality), cached
    by identity."""
    import pyarrow.compute as pc
    key = id(d)
    hit = _DICT_UNIQUE_CACHE.get(key)
    if hit is not None and hit[0] is d:
        return hit[1]
    u = len(pc.unique(d.cast(pa.string()))) == len(d)
    if len(_DICT_UNIQUE_CACHE) > 1024:
        _DICT_UNIQUE_CACHE.clear()
    _DICT_UNIQUE_CACHE[key] = (d, u)
    return u


def _dense_domains(key_cols, conf=None) -> "Optional[List[int]]":
    """Static per-key domain sizes when ALL keys are bounded (dictionary
    codes / booleans) and the bucket product stays small — the dense
    no-sort groupby's eligibility (ops/groupby.py dense_groupby_trace).

    The size/budget check runs FIRST: a high-cardinality dictionary must
    bail out before any O(unique) host work."""
    from ..config import DENSE_AGG_DOMAIN_MAX
    limit = conf.get(DENSE_AGG_DOMAIN_MAX) if conf is not None \
        else _DENSE_DOMAIN_MAX
    sizes = []
    total = 1
    for c in key_cols:
        if c.dictionary is not None:
            sizes.append(max(len(c.dictionary), 1))
        elif isinstance(c.dtype, t.BooleanType):
            sizes.append(2)
        else:
            return None
        total *= sizes[-1] + 1
        if total > limit:
            return None
    return sizes


_PACK_BUDGET = 1 << 62


def _key_pack_spec(key_cols: List[DeviceColumn],
                   key_ranges) -> "Optional[tuple]":
    """Per-key (lo, span) for keys with exact static bounds — plan range
    statistics (exec layer) or deduped dictionary domains — greedily
    until the span product budget; None unless >=2 keys pack (one packed
    lane must actually replace lanes to pay for itself)."""
    spec: List[Optional[Tuple[int, int]]] = []
    total = 1
    packed = 0
    for i, c in enumerate(key_cols):
        rng = key_ranges[i] if key_ranges is not None else None
        entry = None
        if isinstance(c.dtype, t.StringType):
            if c.dictionary is not None:
                # pow2-quantized span: the jit signature must not churn
                # with every per-batch dictionary size (a span only
                # needs to be >= the real domain)
                span = max(len(c.dictionary), 1) + 1
                entry = (0, 1 << (span - 1).bit_length())
        elif isinstance(c.dtype, t.DoubleType) or \
                isinstance(c.dtype, t.FloatType):
            entry = None
        elif rng is not None:
            lo, hi = int(rng[0]), int(rng[1])
            entry = (lo, hi - lo + 2)
        elif isinstance(c.dtype, t.BooleanType):
            entry = (0, 3)
        if entry is not None and total * entry[1] <= _PACK_BUDGET:
            total *= entry[1]
            packed += 1
            spec.append(entry)
        else:
            spec.append(None)
    # all keys covered -> the scatter-free single-sort-lane group-by
    # (ops/groupby.py packed_groupby_trace); a partial pack must replace
    # >=2 lanes to pay for itself
    if packed == len(key_cols) and packed >= 1:
        return tuple(spec)
    return tuple(spec) if packed >= 2 else None


def _fused_pack_spec(key_exprs, key_ranges) -> "Optional[tuple]":
    """Pack spec for the fused map-side path: plan ranges only (string
    dictionaries are per-batch host values there)."""
    spec: List[Optional[Tuple[int, int]]] = []
    total = 1
    packed = 0
    for e, rng in zip(key_exprs, key_ranges or []):
        entry = None
        if rng is not None and not isinstance(
                e.dtype, (t.DoubleType, t.FloatType, t.StringType)):
            lo, hi = int(rng[0]), int(rng[1])
            entry = (lo, hi - lo + 2)
        if entry is not None and total * entry[1] <= _PACK_BUDGET:
            total *= entry[1]
            packed += 1
            spec.append(entry)
        else:
            spec.append(None)
    if packed == len(key_exprs) and packed >= 1:
        return tuple(spec)
    return tuple(spec) if packed >= 2 else None


def holistic_pack_spec(key_cols, key_exprs, child):
    """Pack spec for the holistic (sorted_segments) aggregation execs:
    plan range stats via plain column refs + dictionary/bool domains —
    folds every key into ONE sort lane when all are bounded
    (ops/percentile.py sorted_segments packed path)."""
    from .join import key_ref_names
    ranges = []
    for e in key_exprs:
        ref = key_ref_names([e])
        ranges.append(None if ref is None
                      else child.column_range(ref[0]))
    return _key_pack_spec(key_cols, ranges)


def _seg_knobs(conf):
    """(scatter_free, max_sort_operands) statics for the group-by trace
    builders — part of every jit cache key they shape."""
    from ..config import MAX_SORT_OPERANDS, SEG_SCATTER_FREE
    if conf is None:
        return True, 2
    return conf.get(SEG_SCATTER_FREE), conf.get(MAX_SORT_OPERANDS)


#: the strategies whose program sorts its rows by key
_SORT_STRATEGIES = ("packed_sort", "lexsort")


@dataclasses.dataclass(frozen=True)
class AggElection:
    """One aggregate program, as both engines choose it (_run_groupby
    for the eager one, HashAggregate.partial_fused for whole-plan):
    `strategy` is the name `agg.strategy.*` counts; the other fields are
    the statics that shape the trace, so the election itself is the
    part of a jit cache key that tells two programs apart."""
    strategy: str
    domains: Optional[tuple]
    pack: Optional[tuple]
    scatter_free: bool
    max_ops: int
    #: a `packed_sort` may leave each group at its run's last row, under
    #: a mask, where its aggregates allow it (G.in_place_supported)
    in_place: bool = False

    def trace(self, key_info, specs, capacity: int):
        """The keyed group-by trace of this election (a "reduce" has no
        keys and is built by its callers from G.reduce_trace)."""
        if self.domains is not None:
            return G.dense_groupby_trace(list(self.domains), list(specs),
                                         capacity)
        return G.groupby_trace(list(key_info), list(specs), capacity,
                               capacity, pack_spec=self.pack,
                               scatter_free=self.scatter_free,
                               max_sort_operands=self.max_ops,
                               in_place=self.in_place)


def elect_aggregate(num_keys: int, domains, pack, scatter_free: bool,
                    max_ops: int, in_place: bool = False) -> AggElection:
    """THE choice of an aggregate's program from what its caller found:
    no keys reduce; keys with a bounded domain (`domains`: dictionary
    codes, booleans, under agg.denseDomainMax) take the dense group-by,
    built from bucket-masked reductions up to G.MASKED_DOMAIN_MAX
    buckets and from scatters above; the rest sort, on one packed lane
    where `pack` covers every key, else lexicographically."""
    if not num_keys:
        strategy, domains, pack = "reduce", None, None
    elif domains is not None:
        domains, pack = tuple(domains), None
        strategy = "dense_masked" if G.dense_is_masked(domains) \
            else "dense"
    else:
        strategy = "packed_sort" if G.all_keys_pack(pack, num_keys) \
            else "lexsort"
    return AggElection(strategy, domains, pack, scatter_free, max_ops,
                       in_place and strategy == "packed_sort")


def _run_groupby(key_cols: List[DeviceColumn], agg_cols: List[DeviceColumn],
                 specs: List[G.AggSpec], live, capacity: int,
                 key_ranges=None, conf=None, in_place: bool = False):
    """-> (key_cols, out_keys, outs, num_groups, strategy, sel): `sel` is
    None, or with `in_place` the mask of the rows that hold a group each
    (G.packed_groupby_trace)."""
    key_cols = [ensure_unique_dict(c) for c in key_cols]
    if conf is not None and any(c.dictionary is not None for c in key_cols):
        # dictionary group keys aggregate UNDECODED (codes hash/pack/
        # accumulate directly) — count the encoded dispatch so a
        # regression back to decoded keys is visible in the plane
        from ..ops.encodings import count_dispatch, encoding_policy
        if encoding_policy(conf).enabled:
            count_dispatch("groupby_codes")
    info = tuple((c.dtype, True, str(c.data.dtype)) for c in key_cols)
    domains = _dense_domains(key_cols, conf)
    pack = None if domains is not None \
        else _key_pack_spec(key_cols, key_ranges)
    choice = elect_aggregate(len(key_cols), domains, pack,
                             *_seg_knobs(conf), in_place=in_place)
    sig = (info, tuple((s.kind, s.input_idx, s.dtype) for s in specs),
           capacity, tuple(str(c.data.dtype) for c in agg_cols), choice)
    fn = _GROUPBY_CACHE.get(sig)
    if fn is None:
        fn = _GROUPBY_CACHE[sig] = jax.jit(
            choice.trace(info, specs, capacity))
    out_keys, outs, num_groups, *sel = fn(
        tuple(c.data for c in key_cols),
        tuple(c.validity for c in key_cols),
        tuple(c.data for c in agg_cols),
        tuple(c.validity for c in agg_cols),
        live)
    # concrete (eager) group counts coerce to host as before — shrinking
    # to the real bucket keeps downstream sorts small; under whole-plan
    # tracing the count is a Tracer and must stay on device
    if not isinstance(num_groups, jax.core.Tracer):
        num_groups = int(num_groups)
    return (key_cols, out_keys, outs, num_groups, choice.strategy,
            sel[0] if sel else None)


def _run_reduce(agg_cols: List[DeviceColumn], specs: List[G.AggSpec],
                live, capacity: int):
    sig = (tuple((s.kind, s.input_idx, s.dtype) for s in specs), capacity,
           tuple(str(c.data.dtype) for c in agg_cols))
    fn = _REDUCE_CACHE.get(sig)
    if fn is None:
        fn = jax.jit(G.reduce_trace(list(specs), capacity))
        _REDUCE_CACHE[sig] = fn
    return fn(tuple(c.data for c in agg_cols),
              tuple(c.validity for c in agg_cols), live)


def check_agg_inputs_single_lane(input_exprs, db: DeviceBatch) -> None:
    """Decimal buffers ride the single int64 unscaled lane (sums whose
    true value exceeds int64 null out — ops/decimal.py module docs).  Only
    a two-lane 128-bit HOST input is rejected, and the batch itself says
    which a wide column is (`data_hi`): a wide decimal that an operator
    below computed on the device has one lane and aggregates like any
    other.  Plan-time tagging keeps the host ones off the device
    (aggregates.py unsupported_reasons) — this fails fast for direct API
    users."""
    for e in input_exprs:
        ref = E.plain_ref(e)
        if ref is not None and isinstance(ref.dtype, t.DecimalType) \
                and ref.dtype.is_wide \
                and db.column_by_name(ref.name).data_hi is not None:
            raise NotImplementedError(
                f"128-bit host decimal input {ref.name} to an aggregate "
                "not supported on device")


def _storage_zeros(dt: t.DataType, capacity: int):
    if isinstance(dt, t.DoubleType):
        return jnp.zeros((capacity,), jnp.float64)
    return jnp.zeros((capacity,), t.physical_np_dtype(dt))


class HashAggregate:
    """Bound group-by aggregation over a stream of device batches."""

    def __init__(self, key_exprs: Sequence[E.Expression],
                 key_names: Sequence[str],
                 aggs: Sequence[Tuple[AggregateFunction, str]],
                 conf: TpuConf, key_ranges=None, input_ranges=None,
                 bump=None):
        #: the running collect's `ExecContext.bump` (the trace context's,
        #: under whole-plan tracing): every aggregate program run counts
        #: its strategy and its capacity through it (_note)
        self.bump = bump
        self.key_exprs = list(key_exprs)
        self.key_names = list(key_names)
        self.aggs = list(aggs)
        self.conf = conf
        # exact (lo, hi) per key from plan statistics (or None) — lets
        # the group-by pack bounded keys into one sort lane
        self.key_ranges = list(key_ranges) if key_ranges is not None \
            else [None] * len(self.key_exprs)
        # exact (lo, hi) per INPUT expression (plain column refs with
        # scan stats): an int64 lane whose range fits int32 gathers as
        # ONE u32 lane instead of a pair — the permutation gather is the
        # dominant group-by cost at big buckets (~390ms for one 8M int64
        # lane), so halving its width is material
        self._input_ranges_by_expr = input_ranges or {}
        # flatten buffers
        self.update_specs: List[G.AggSpec] = []
        self.merge_specs: List[G.AggSpec] = []
        self.input_exprs: List[Optional[E.Expression]] = []
        self.buffer_slices: List[Tuple[int, int]] = []
        for fn, _name in self.aggs:
            start = len(self.update_specs)
            ins = fn.inputs()
            for (kind, bdt), (mkind, mdt), inp in zip(
                    fn.update_ops(), fn.merge_ops(), ins):
                idx = -1
                if inp is not None:
                    idx = len(self.input_exprs)
                    self.input_exprs.append(inp)
                self.update_specs.append(G.AggSpec(kind, idx, bdt))
            self.buffer_slices.append((start, len(self.update_specs)))
        # merge specs operate on buffer columns positionally
        mi = 0
        for (fn, _name) in self.aggs:
            for (mkind, mdt) in fn.merge_ops():
                self.merge_specs.append(G.AggSpec(mkind, mi, mdt))
                mi += 1

    def _note(self, strategy: str, capacity: int) -> None:
        """Count one aggregate program: `agg.strategy.<strategy>` (and
        `agg.strategy.sorted` where it sorts its rows; `.dense` too
        where it is the dense program built without scatters,
        `dense_masked`), and `agg.capacity_rows` by the padded rows it
        ran at."""
        bump = self.bump
        if bump is None:
            return
        bump(f"agg.strategy.{strategy}")
        if strategy == "dense_masked":
            bump("agg.strategy.dense")
        if strategy in _SORT_STRATEGIES:
            bump("agg.strategy.sorted")
        bump("agg.capacity_rows", int(capacity))

    # ---- phases ----

    _I32_LO, _I32_HI = -(1 << 31), (1 << 31) - 1

    def _narrow_cols(self, agg_cols):
        """Cast int64 agg-input lanes with an int32-fitting known range
        down to int32 (exact; sums re-widen inside the kernel);
        spark.rapids.tpu.sql.agg.inputNarrowing gates it."""
        from ..config import AGG_INPUT_NARROWING
        if not self.conf.get(AGG_INPUT_NARROWING):
            return list(agg_cols)
        out = []
        for c, e in zip(agg_cols, self.input_exprs):
            rng = self._input_ranges_by_expr.get(id(e))
            if rng is not None and c.data.dtype == jnp.int64 and \
                    self._I32_LO <= rng[0] and rng[1] <= self._I32_HI:
                out.append(DeviceColumn(c.data.astype(jnp.int32),
                                        c.validity, c.dtype,
                                        c.dictionary))
            else:
                out.append(c)
        return out

    def partial(self, db: DeviceBatch, live=None) -> DeviceBatch:
        """One input batch -> (keys + buffer columns) partial result.

        `live` (optional bool mask) lets an upstream filter fuse into the
        aggregation: filtered rows simply never contribute — no compaction
        (= no TPU row gather) between filter and agg."""
        check_agg_inputs_single_lane(self.input_exprs, db)
        key_batch = evaluate_projection(self.key_exprs, self.key_names, db,
                                        self.conf) if self.key_exprs else None
        agg_in = evaluate_projection(
            [e for e in self.input_exprs],
            [f"_in{i}" for i in range(len(self.input_exprs))], db, self.conf) \
            if self.input_exprs else None
        agg_cols = self._narrow_cols(agg_in.columns) \
            if agg_in is not None else []
        if live is None:
            live = db.row_mask()
        if not self.key_exprs:
            outs = _run_reduce(agg_cols, self.update_specs, live, db.capacity)
            self._note("reduce", db.capacity)
            return self._reduce_outs_to_batch(outs)
        return self._update(key_batch.columns, agg_cols, live, db.capacity)

    def _update(self, key_cols, agg_cols, live, capacity: int,
                in_place: bool = False) -> DeviceBatch:
        key_cols, out_keys, outs, n_groups, strategy, sel = _run_groupby(
            key_cols, agg_cols, self.update_specs, live, capacity,
            key_ranges=self.key_ranges, conf=self.conf, in_place=in_place)
        self._note(strategy, capacity)
        return self._groupby_outs_to_batch(key_cols, out_keys, outs,
                                           n_groups, sel)

    # ---- one update over the stacked input (whole-plan traces) ----

    def project_inputs(self, db: DeviceBatch, live) -> DeviceBatch:
        """The keys and the aggregates' inputs of one batch, evaluated
        and left where they are under the selection vector `live`: what
        `update_stacked` stacks."""
        check_agg_inputs_single_lane(self.input_exprs, db)
        nk = len(self.key_exprs)
        names = list(self.key_names) + [
            f"_in{i}" for i in range(len(self.input_exprs))]
        out = evaluate_projection(
            list(self.key_exprs) + list(self.input_exprs), names, db,
            self.conf)
        cols = list(out.columns[:nk]) + self._narrow_cols(out.columns[nk:])
        return DeviceBatch(cols, jnp.sum(live, dtype=jnp.int32), names,
                           db.origin_file, sel=live)

    def partials_keep_capacity(self, projected: DeviceBatch) -> bool:
        """Whether a partial aggregate of `projected` (project_inputs)
        would come out at its input's capacity when its group count is a
        value of the program: it sorts its rows by key (no dense domain)
        and states no bound on its groups.  Such partials reduce nothing
        that is static, so merging them sorts every row twice."""
        key_cols = [ensure_unique_dict(c)
                    for c in projected.columns[:len(self.key_exprs)]]
        return _dense_domains(key_cols, self.conf) is None \
            and self._static_group_bound(key_cols) is None

    def update_stacked(self, projected: List[DeviceBatch],
                       in_place: bool = False) -> DeviceBatch:
        """ONE update aggregation over the stacked `project_inputs` of
        every input batch, in place of a partial a batch and their
        merge (keys + buffer columns, as `partial` returns them).
        `in_place`: the consumer reads liveness as a mask, so a sorted
        aggregate may leave each group where its run ended, under a
        selection vector (G.packed_groupby_trace)."""
        stacked = concat_batches(projected, self.conf, masked=True)
        nk = len(self.key_exprs)
        return self._update(stacked.columns[:nk], stacked.columns[nk:],
                            stacked.row_mask(), stacked.capacity, in_place)

    def can_fuse_filter(self, db: "Optional[DeviceBatch]" = None) -> bool:
        """Whether the whole map side (filter mask + projections + update
        groupby) can run as ONE traced program.

        Non-string keys always fuse.  String keys fuse when the batch is
        in hand and every string key is a plain column reference with a
        duplicate-free dictionary whose domain is small: the DENSE
        bounded-domain groupby (ops/groupby.py) then needs no host-side
        dictionary work inside the trace."""
        if not any(isinstance(e.dtype, t.StringType) for e in self.key_exprs):
            return True
        if db is None:
            return False
        return self._fused_dense_domains(db) is not None

    def _fused_dense_domains(self, db: DeviceBatch):
        """Static dense-groupby domain sizes for the fused path, or None.

        Sizes/budget check first; the O(unique) duplicate check only ever
        runs on dictionaries already under the (small) domain budget."""
        from ..config import DENSE_AGG_DOMAIN_MAX
        limit = self.conf.get(DENSE_AGG_DOMAIN_MAX)
        sizes = []
        dicts = []
        total = 1
        for e in self.key_exprs:
            inner = e.children[0] if isinstance(e, E.Alias) else e
            if isinstance(e.dtype, t.BooleanType):
                sizes.append(2)
                dicts.append(None)
            elif isinstance(e.dtype, t.StringType):
                if not isinstance(inner, E.ColumnRef):
                    return None
                try:
                    c = db.column_by_name(inner.name)
                except ValueError:
                    return None
                if c.dictionary is None:
                    return None
                sizes.append(max(len(c.dictionary), 1))
                dicts.append(c.dictionary)
            else:
                return None
            total *= sizes[-1] + 1
            if total > limit:
                return None
        for d in dicts:
            if d is not None and not _dict_unique(d):
                return None
        return sizes

    def partial_fused(self, db: DeviceBatch, conds: Sequence[E.Expression],
                      raw: bool = False):
        """Filter + key/input projection + update groupby in ONE program.

        The whole map-side of an aggregation (predicate, projections,
        sort-segment reduce) is a single XLA program per row bucket: one
        dispatch, full fusion, no intermediate HBM round-trips.  The
        reference runs these as separate cuDF kernel launches
        (GpuFilterExec -> projections -> Table.groupBy); on TPU the fused
        form is both lower-latency and lets XLA share subexpressions."""
        from .evaluator import (_JIT_CACHE, _batch_meta, _build_inputs,
                                _jit_key, _num_rows_scalar, _prepare)
        from ..ops.kernels import live_mask, valid_or_true
        if db.sel is not None and any(c.offsets is not None
                                      for c in db.columns):
            # ragged kernels assume prefix liveness (see evaluator)
            from ..ops.batch_ops import ensure_prefix
            db = ensure_prefix(db, self.conf)
        check_agg_inputs_single_lane(self.input_exprs, db)
        exprs_all = list(conds) + self.key_exprs + self.input_exprs
        pctx, hostvals, aux = _prepare(exprs_all, db, self.conf)
        spec_sig = tuple((s.kind, s.input_idx, str(s.dtype))
                         for s in self.update_specs)
        dense_domains = self._fused_dense_domains(db) \
            if any(isinstance(e.dtype, (t.StringType, t.BooleanType))
                   for e in self.key_exprs) else None
        pack = None if dense_domains is not None \
            else _fused_pack_spec(self.key_exprs, self.key_ranges)
        choice = elect_aggregate(len(self.key_exprs), dense_domains, pack,
                                 *_seg_knobs(self.conf))
        has_sel = db.sel is not None
        from ..config import AGG_INPUT_NARROWING
        _narrow_on = self.conf.get(AGG_INPUT_NARROWING)
        narrow = tuple(
            _narrow_on
            and (rng := self._input_ranges_by_expr.get(id(e))) is not None
            and self._I32_LO <= rng[0] and rng[1] <= self._I32_HI
            for e in self.input_exprs)
        key = _jit_key(exprs_all, db, aux, self.conf,
                       ("fpartial", spec_sig, len(conds),
                        len(self.key_exprs), has_sel, narrow, choice))
        fn = _JIT_CACHE.get(key)
        if fn is None:
            capacity = db.capacity
            node_slots = dict(pctx.node_slots)
            node_info = dict(pctx.node_info)
            conf = self.conf
            conds_t = tuple(conds)
            keys_t = tuple(self.key_exprs)
            ins_t = tuple(self.input_exprs)
            specs = list(self.update_specs)
            meta = _batch_meta(db)

            def run(col_data, col_valid, num_rows, aux_arrs, *sel_opt):
                inputs, raw = _build_inputs(meta, col_data, col_valid)
                ctx = E.EvalCtx(capacity, num_rows, inputs, aux_arrs,
                                node_slots, conf, raw,
                                node_info=node_info)
                # lazy join output: liveness is the selection vector
                live = sel_opt[0] if sel_opt \
                    else live_mask(capacity, num_rows)
                for c in conds_t:
                    dv = c.eval_dev(ctx)
                    k = dv.data.astype(bool)
                    if dv.validity is not None:
                        k = k & dv.validity
                    live = live & k
                agg_data, agg_valid = [], []
                for i, e in enumerate(ins_t):
                    dv = e.eval_dev(ctx)
                    d = dv.data
                    if narrow[i] and d.dtype == jnp.int64:
                        # range-proven int32 fit: halve the permutation
                        # gather width (sums re-widen in the kernel)
                        d = d.astype(jnp.int32)
                    agg_data.append(d)
                    agg_valid.append(valid_or_true(dv.validity, capacity))
                if not keys_t:
                    red = G.reduce_trace(specs, capacity)
                    return (None,
                            red(tuple(agg_data), tuple(agg_valid), live),
                            None)
                kds, kvs, kinfo = [], [], []
                for e in keys_t:
                    dv = e.eval_dev(ctx)
                    kds.append(dv.data)
                    kvs.append(valid_or_true(dv.validity, capacity))
                    kinfo.append((e.dtype, True, str(dv.data.dtype)))
                gb = choice.trace(kinfo, specs, capacity)
                return gb(tuple(kds), tuple(kvs), tuple(agg_data),
                          tuple(agg_valid), live)

            fn = jax.jit(run)
            _JIT_CACHE[key] = fn

        from .evaluator import _col_lanes
        extra = (db.sel,) if has_sel else ()
        out_keys, outs, ng = fn(_col_lanes(db),
                                tuple(c.validity for c in db.columns),
                                _num_rows_scalar(db.num_rows), aux, *extra)
        self._note(choice.strategy, db.capacity)
        if not self.key_exprs:
            return outs if raw else self._reduce_outs_to_batch(outs)
        nconds = len(conds)
        key_cols = []
        for i, e in enumerate(self.key_exprs):
            hv = hostvals[nconds + i]
            key_cols.append(DeviceColumn(
                jnp.zeros((0,)), jnp.zeros((0,), bool), e.dtype,
                hv.dictionary))
        if not isinstance(ng, jax.core.Tracer):
            ng = int(ng)
        return self._groupby_outs_to_batch(key_cols, out_keys, outs, ng)

    def merge_raw(self, partial_outs: List[List]) -> List:
        """Merge per-batch global-agg scalar outputs into final buffer
        scalars — one tiny jit over stacked scalars, no 1-row batches."""
        if len(partial_outs) == 1:
            return partial_outs[0]
        k = len(partial_outs)
        sig = (k, tuple((s.kind, s.input_idx, str(s.dtype))
                        for s in self.merge_specs))
        fn = _REDUCE_CACHE.get(sig)
        if fn is None:
            red = G.reduce_trace(self.merge_specs, k)

            def run(stacks, valids):
                return red(stacks, valids, jnp.ones((k,), bool))

            fn = jax.jit(run)
            _REDUCE_CACHE[sig] = fn
        stacks = tuple(jnp.stack([p[i][0] for p in partial_outs])
                       for i in range(len(self.update_specs)))
        valids = tuple(jnp.stack([p[i][1] for p in partial_outs])
                       for i in range(len(self.update_specs)))
        self._note("reduce", k)
        return list(fn(stacks, valids))

    def final_host(self, outs) -> pa.Table:
        """Finish a global aggregation on host: one D2H fetch of the buffer
        scalars, then the result expressions run via their CPU kernels on a
        1-row Arrow batch (cheaper than dispatching a device program for a
        single row)."""
        fetched = jax.device_get([(d, v) for d, v in outs])
        return self.finalize_fetched(fetched)

    def finalize_fetched(self, fetched) -> pa.Table:
        """Host-side tail of final_host, split out so pipelined callers
        (bench, concurrent-task executor) can batch many queries' D2H
        fetches into one transfer before finalizing each."""
        from ..columnar.host import dtype_to_arrow
        arrays = []
        for (d, v), spec in zip(fetched, self.update_specs):
            val = d.item() if bool(v) else None
            if val is not None and isinstance(spec.dtype, t.DecimalType):
                import decimal as pydec
                val = pydec.Decimal(val).scaleb(-spec.dtype.scale)
            arrays.append(pa.array([val], dtype_to_arrow(spec.dtype)))
        names = self._buffer_names()
        rb = pa.RecordBatch.from_arrays(arrays, names)
        schema = t.StructType([t.StructField(n, s.dtype)
                               for n, s in zip(names, self.update_specs)])
        out_arrays, out_names = [], []
        for (fn, name), (start, end) in zip(self.aggs, self.buffer_slices):
            refs = [E.ColumnRef(f"_buf{j}").bind(schema)
                    for j in range(start, end)]
            expr = fn.evaluate(refs)
            from ..plan.aggregates import _deep_resolved
            expr = _deep_resolved(expr)
            out_arrays.append(expr.eval_cpu(rb))
            out_names.append(name)
        return pa.Table.from_arrays(out_arrays, out_names)

    def merge(self, partials: List[DeviceBatch]) -> DeviceBatch:
        merged = concat_batches(partials, self.conf)
        nkeys = len(self.key_exprs)
        key_cols = merged.columns[:nkeys]
        buf_cols = merged.columns[nkeys:]
        if not self.key_exprs:
            outs = _run_reduce(buf_cols, self.merge_specs, merged.row_mask(),
                               merged.capacity)
            self._note("reduce", merged.capacity)
            return self._reduce_outs_to_batch(outs)
        key_cols, out_keys, outs, n_groups, strategy, _sel = _run_groupby(
            key_cols, buf_cols, self.merge_specs, merged.row_mask(),
            merged.capacity, key_ranges=self.key_ranges, conf=self.conf)
        self._note(strategy, merged.capacity)
        return self._groupby_outs_to_batch(key_cols, out_keys, outs, n_groups)

    def final(self, merged: DeviceBatch) -> DeviceBatch:
        """Evaluate result expressions over (keys + buffers)."""
        nkeys = len(self.key_exprs)
        schema = merged.schema
        out_exprs: List[E.Expression] = []
        out_names: List[str] = []
        for i, name in enumerate(self.key_names):
            out_exprs.append(E.ColumnRef(name).bind(schema))
            out_names.append(name)
        for (fn, name), (start, end) in zip(self.aggs, self.buffer_slices):
            refs = [E.ColumnRef(f"_buf{j}").bind(schema)
                    for j in range(start, end)]
            expr = fn.evaluate(refs)
            from ..plan.aggregates import _deep_resolved
            out_exprs.append(_deep_resolved(expr))
            out_names.append(name)
        return evaluate_projection(out_exprs, out_names, merged, self.conf)

    def execute(self, batches: Iterable[DeviceBatch]) -> DeviceBatch:
        partials = [self.partial(db) for db in batches]
        if not partials:
            raise ValueError("aggregation over zero batches")
        merged = self.merge(partials) if len(partials) > 1 else partials[0]
        return self.final(merged)

    # ---- plumbing ----

    def _buffer_names(self):
        return [f"_buf{i}" for i in range(len(self.update_specs))]

    def _static_group_bound(self, key_cols) -> "Optional[int]":
        """Upper bound on group count from key-domain sizes (dictionary
        lengths, bool), when every key has a bounded domain.  +1 per key
        for the null group.  Lets the output shrink to a tiny bucket with
        NO host sync — the group count itself can stay on device."""
        bound = 1
        for kc in key_cols:
            if kc.dictionary is not None:
                bound *= len(kc.dictionary) + 1
            elif isinstance(kc.dtype, t.BooleanType):
                bound *= 3
            else:
                return None
            if bound > (1 << 22):
                return None
        return bound

    def _groupby_outs_to_batch(self, key_cols, out_keys, outs, n_groups,
                               sel=None):
        cols = []
        for (kd, kv), kc in zip(out_keys, key_cols):
            cols.append(DeviceColumn(kd, kv, kc.dtype, kc.dictionary,
                                     kc.data_hi))
        # update and merge specs share buffer dtypes positionally
        for (data, valid), spec in zip(outs, self.update_specs):
            cols.append(DeviceColumn(data.astype(_storage_zeros(
                spec.dtype, 1).dtype), valid, spec.dtype))
        db = DeviceBatch(cols, n_groups,
                         self.key_names + self._buffer_names(), sel=sel)
        if sel is not None:
            return db          # groups in place, under the mask
        if isinstance(n_groups, int):
            return shrink_to_rows(db, n_groups, self.conf)
        # lazy group count: shrink by the static key-domain bound instead
        # of syncing (whole-plan tracing; a host sync per aggregate)
        bound = self._static_group_bound(key_cols)
        if bound is not None:
            from ..ops.batch_ops import shrink_to_capacity
            return shrink_to_capacity(db, bound, self.conf)
        return db

    def _reduce_outs_to_batch(self, outs) -> DeviceBatch:
        from ..columnar.device import bucket_capacity
        cap = bucket_capacity(1, self.conf)
        cols = []
        for (data, valid), spec in zip(outs, self.update_specs):
            # row 0 by concatenation, not `.at[0].set` — the 1-element
            # scatter that lowers to would be the only scatter left in a
            # global-aggregation program
            sdt = _storage_zeros(spec.dtype, 1).dtype
            d = jnp.concatenate([data.astype(sdt)[None],
                                 jnp.zeros((cap - 1,), sdt)])
            v = jnp.concatenate([valid[None], jnp.zeros((cap - 1,), bool)])
            cols.append(DeviceColumn(d, v, spec.dtype))
        return DeviceBatch(cols, 1, self._buffer_names())
