"""Late-materialization lanes: THIN device batches for join pipelines.

Row gathers are the dominant device cost on TPU (~1.6 GB/s descriptor-
driven DMA per gathered lane), and a join chain classically re-gathers
every payload column of both sides at full batch capacity per join.  The
reference defers this with gather maps (JoinGatherer.scala — a join
yields gather maps, materialization happens when a downstream operator
actually needs columns); "GPU Acceleration of SQL Analytics on
Compressed Data" (PAPERS.md) shows executing *through* encodings rather
than materializing decoded columns is the dominant accelerator win.

The TPU-native realization: a join emits a **thin batch** — its
materialized key/condition columns plus, per deferred payload column, a
pointer into a *lane source*:

  * ``LaneSource``: a fully materialized source batch (a join's build
    side, or a probe batch whose columns pass through) together with an
    int32 **row-id lane** of the output's capacity — the gather indices
    the join computed anyway.  Index < 0 marks a null-extended row
    (outer-join semantics, cuDF OutOfBoundsPolicy.NULLIFY).
  * ``ThinState.pending``: output column position -> (source ordinal,
    column index in the source).

Downstream joins COMPOSE lanes (one int32 take per source per join)
instead of gathering payloads; filters compose their mask into the
batch's selection vector (``DeviceBatch.sel``) instead of compacting; a
pipeline *sink* (aggregate build, sort, exchange, collect — anything
that calls ``materialize_batch``/``ensure_prefix``/``compact_batch``)
resolves each still-needed column with ONE gather through the composed
lane.  Columns nobody references are never gathered at all.

Encodings stay live through the chain: a deferred dictionary-coded
string column materializes as CODES (the dictionary pointer rides on the
placeholder), so strings cross an entire join pipeline without a decode
and with the build-side dictionary remap done once per build
(ops/batch_ops.py remap caches).

Deferred placeholders are ZERO-capacity columns: any path that forgot to
materialize fails loudly on a shape mismatch instead of silently
computing over garbage.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..config import TpuConf, DEFAULT_CONF
from .device import DeviceBatch, DeviceColumn


@dataclasses.dataclass
class LaneSource:
    """A materialized source batch + the row-id lane selecting from it."""
    batch: DeviceBatch
    lane: jax.Array          # (out_capacity,) int32; < 0 => null row

    def nbytes(self) -> int:
        return self.lane.size * 4


@dataclasses.dataclass
class ThinState:
    """Deferred-column bookkeeping attached to a DeviceBatch."""
    capacity: int
    sources: List[LaneSource]
    # output column position -> (source ordinal, source column index)
    pending: Dict[int, Tuple[int, int]]

    def nbytes(self) -> int:
        return sum(s.nbytes() for s in self.sources)

    def select(self, indices: Sequence[int]) -> "Optional[ThinState]":
        """Thin state for DeviceBatch.select(indices): pending positions
        remap to the new column order; sources nobody references drop."""
        new_pending: Dict[int, Tuple[int, int]] = {}
        used: List[LaneSource] = []
        src_map: Dict[int, int] = {}
        for out_i, old_i in enumerate(indices):
            ref = self.pending.get(old_i)
            if ref is None:
                continue
            s, c = ref
            if s not in src_map:
                src_map[s] = len(used)
                used.append(self.sources[s])
            new_pending[out_i] = (src_map[s], c)
        if not new_pending:
            return None
        return ThinState(self.capacity, used, new_pending)

    def pruned(self) -> "ThinState":
        """The same state over sources cut to the columns `pending`
        references: what has to cross a program boundary for the
        deferred columns to be gathered on the other side."""
        refs: List[List[int]] = [[] for _ in self.sources]
        pending: Dict[int, Tuple[int, int]] = {}
        for pos, (s, c) in sorted(self.pending.items()):
            if c not in refs[s]:
                refs[s].append(c)
            pending[pos] = (s, refs[s].index(c))
        sources = [LaneSource(src.batch.select(cols), src.lane)
                   for src, cols in zip(self.sources, refs)]
        return ThinState(self.capacity, sources, pending)


def deferred_column(src_col: DeviceColumn) -> DeviceColumn:
    """Zero-capacity placeholder for a deferred column.  It carries the
    logical dtype AND the source dictionary (schema/encoding fidelity —
    string columns stay code-addressed through the chain) but no data:
    consuming it without materialization is a loud shape error."""
    return DeviceColumn(
        jnp.zeros((0,), src_col.data.dtype),
        jnp.zeros((0,), bool),
        src_col.dtype,
        src_col.dictionary,
        None if src_col.data_hi is None else jnp.zeros((0,), jnp.int64))


def resolved_columns(db: DeviceBatch) -> List[DeviceColumn]:
    """Per output position, the column that says what the position
    holds once resolved (logical dtype, dictionary, lane dtypes, hi
    lane): the column itself, or for a deferred position its lane
    source's column — the placeholder has no capacity to speak of."""
    ts = db.thin
    if ts is None:
        return list(db.columns)
    return [c if i not in ts.pending
            else ts.sources[ts.pending[i][0]].batch
            .columns[ts.pending[i][1]]
            for i, c in enumerate(db.columns)]


def _count_gather(site: str, rows: int, cols: List[DeviceColumn]) -> None:
    """Publish one payload-gather pass into the always-on registry."""
    from ..obs.registry import GATHER_BYTES, GATHER_ROWS
    nbytes = sum(rows * (c.data.dtype.itemsize + 1 +
                         (8 if c.data_hi is not None else 0))
                 for c in cols)
    GATHER_ROWS.inc(rows * len(cols), site=site)
    GATHER_BYTES.inc(nbytes, site=site)


def gather_deferred(src: LaneSource, col_indices: Sequence[int],
                    live: Optional[jax.Array], lane=None
                    ) -> List[DeviceColumn]:
    """Materialize source columns through a row-id lane: one stacked
    gather pass per dtype class (ops/filter.py grouped_take).  Rows with
    lane < 0 / >= source rows come back null; `live` (the output batch's
    row mask) additionally nulls dead output rows."""
    from ..ops.filter import grouped_take
    idx = src.lane if lane is None else lane
    src_rows = jnp.asarray(src.batch.num_rows, jnp.int32)
    in_bounds = (idx >= 0) & (idx < src_rows)
    vmask = in_bounds if live is None else in_bounds & live
    cap = max(src.batch.capacity - 1, 0)
    safe = jnp.clip(idx, 0, cap).astype(jnp.int32)
    cols = [src.batch.columns[i] for i in col_indices]
    lanes, slots = [], []
    for ci, c in enumerate(cols):
        lanes.append(c.data)
        slots.append((ci, "d"))
        lanes.append(c.validity)
        slots.append((ci, "v"))
        if c.data_hi is not None:
            lanes.append(c.data_hi)
            slots.append((ci, "h"))
    moved = grouped_take(lanes, safe)
    got = {slot: arr for slot, arr in zip(slots, moved)}
    out = []
    for ci, c in enumerate(cols):
        out.append(DeviceColumn(got[(ci, "d")], got[(ci, "v")] & vmask,
                                c.dtype, c.dictionary, got.get((ci, "h"))))
    _count_gather("late", idx.shape[0], cols)
    return out


def materialize_batch(db: DeviceBatch, conf: TpuConf = DEFAULT_CONF,
                      positions: Optional[Sequence[int]] = None
                      ) -> DeviceBatch:
    """Resolve deferred columns: one composed gather per lane source.

    positions=None resolves everything (the thin state drops); a subset
    resolves only those columns (mid-pipeline early materialization —
    e.g. a filter referencing a deferred column) and keeps the rest
    thin."""
    ts = db.thin
    if ts is None:
        return db
    want = set(ts.pending) if positions is None \
        else set(positions) & set(ts.pending)
    remaining = {p: r for p, r in ts.pending.items() if p not in want}
    if not want:
        if remaining:
            return db
        return DeviceBatch(list(db.columns), db.num_rows, db.names,
                           db.origin_file, sel=db.sel)
    live = db.row_mask()
    cols = list(db.columns)
    by_src: Dict[int, List[Tuple[int, int]]] = {}
    for pos in want:
        s, c = ts.pending[pos]
        by_src.setdefault(s, []).append((pos, c))
    for s, items in sorted(by_src.items()):
        src = ts.sources[s]
        gathered = gather_deferred(src, [c for _p, c in items], live)
        for (pos, _c), col in zip(items, gathered):
            cols[pos] = col
    new_ts = None
    if remaining:
        # re-pack sources still referenced
        keep_src = sorted({s for s, _c in remaining.values()})
        src_map = {s: i for i, s in enumerate(keep_src)}
        new_ts = ThinState(ts.capacity,
                           [ts.sources[s] for s in keep_src],
                           {p: (src_map[s], c)
                            for p, (s, c) in remaining.items()})
    return DeviceBatch(cols, db.num_rows, db.names, db.origin_file,
                       sel=db.sel, thin=new_ts)


def expr_column_refs(exprs) -> set:
    """Column names referenced anywhere in a set of bound expressions
    (including lambda bodies)."""
    from ..plan import expressions as E
    out: set = set()

    def walk(e):
        if isinstance(e, E.ColumnRef):
            out.add(e.name)
        for c in getattr(e, "children", ()) or ():
            if isinstance(c, E.Expression):
                walk(c)
        body = getattr(e, "body", None)
        if isinstance(body, E.Expression):
            walk(body)
    for e in exprs:
        if isinstance(e, E.Expression):
            walk(e)
    return out


def passthrough_positions(db: DeviceBatch, exprs) -> Dict[int, int]:
    """Output position -> input position for projection expressions that
    are plain (possibly aliased) references to STILL-DEFERRED columns: a
    thin-aware projection passes those through as placeholders with
    remapped lane bookkeeping instead of materializing them.  Duplicate
    input names are ambiguous (column_by_name semantics) and never pass
    through."""
    from ..plan import expressions as E
    ts = db.thin
    out: Dict[int, int] = {}
    if ts is None:
        return out
    counts: Dict[str, int] = {}
    for n in db.names:
        counts[n] = counts.get(n, 0) + 1
    pending_by_name = {db.names[p]: p for p in ts.pending
                       if counts[db.names[p]] == 1}
    for oi, e in enumerate(exprs):
        inner = e.children[0] if isinstance(e, E.Alias) else e
        if isinstance(inner, E.ColumnRef):
            p = pending_by_name.get(inner.name)
            if p is not None:
                out[oi] = p
    return out


def materialize_refs(db: DeviceBatch, exprs, conf: TpuConf = DEFAULT_CONF
                     ) -> DeviceBatch:
    """Materialize exactly the deferred columns the expressions
    reference (forced early materialization of just those columns);
    unreferenced deferred columns stay thin."""
    if db.thin is None:
        return db
    refs = expr_column_refs(exprs)
    positions = [p for p in db.thin.pending if db.names[p] in refs]
    if not positions:
        return db
    return materialize_batch(db, conf, positions)


def materialize_needed(db: DeviceBatch, exprs, conf: TpuConf = DEFAULT_CONF
                       ) -> DeviceBatch:
    """Sink-side materialization that also DROPS dead columns: deferred
    columns the expressions reference materialize through their lanes;
    the rest become all-null dense columns (never gathered) so
    prefix/concat machinery downstream sees a plain dense batch."""
    if db.thin is None:
        return db
    db = materialize_refs(db, exprs, conf)
    ts = db.thin
    if ts is None:
        return db
    cols = list(db.columns)
    for pos, (s, c) in ts.pending.items():
        src_col = ts.sources[s].batch.columns[c]
        cap = ts.capacity
        cols[pos] = DeviceColumn(
            jnp.zeros((cap,), src_col.data.dtype),
            jnp.zeros((cap,), bool), src_col.dtype, src_col.dictionary,
            None if src_col.data_hi is None
            else jnp.zeros((cap,), jnp.int64))
    return DeviceBatch(cols, db.num_rows, db.names, db.origin_file,
                       sel=db.sel)


def compact_thin(db: DeviceBatch, keep: jax.Array,
                 out_capacity: Optional[int] = None) -> DeviceBatch:
    """Compact a THIN batch: materialized columns move through the
    compaction order as usual; each deferred column is gathered ONCE,
    straight from its source into compacted position (the lane composes
    with the order — no materialize-then-compact double pass).

    `out_capacity` cuts the compaction order to its first rows, so every
    gather below runs at that capacity: for a caller that knows the kept
    rows fit (a split-plan seam, after its row-count sync)."""
    from ..ops.filter import compaction_order, grouped_take
    ts = db.thin
    assert ts is not None
    order = compaction_order(keep, out_capacity)
    count = jnp.sum(keep, dtype=jnp.int32)
    if out_capacity is not None:
        order = order[:out_capacity]
        count = jnp.minimum(count, out_capacity)
    live_out = jnp.arange(order.shape[0], dtype=jnp.int32) < count
    out_cols: List[Optional[DeviceColumn]] = [None] * len(db.columns)
    # materialized columns: the ordinary stacked compact gather
    mat = [i for i in range(len(db.columns)) if i not in ts.pending]
    if mat:
        lanes, slots = [], []
        for i in mat:
            c = db.columns[i]
            lanes.append(c.data)
            slots.append((i, "d"))
            lanes.append(c.validity)
            slots.append((i, "v"))
            if c.data_hi is not None:
                lanes.append(c.data_hi)
                slots.append((i, "h"))
        moved = grouped_take(lanes, order)
        got = {slot: arr for slot, arr in zip(slots, moved)}
        for i in mat:
            c = db.columns[i]
            out_cols[i] = DeviceColumn(got[(i, "d")],
                                       got[(i, "v")] & live_out,
                                       c.dtype, c.dictionary,
                                       got.get((i, "h")))
    # deferred columns: compose lane through the order, gather once
    by_src: Dict[int, List[Tuple[int, int]]] = {}
    for pos, (s, c) in ts.pending.items():
        by_src.setdefault(s, []).append((pos, c))
    for s, items in sorted(by_src.items()):
        src = ts.sources[s]
        composed = jnp.where(live_out,
                             jnp.take(src.lane, order), jnp.int32(-1))
        gathered = gather_deferred(src, [c for _p, c in items], live_out,
                                   lane=composed)
        for (pos, _c), col in zip(items, gathered):
            out_cols[pos] = col
    return DeviceBatch(out_cols, count, db.names, db.origin_file)
