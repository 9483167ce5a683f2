"""Spill-partitioned out-of-core group-by aggregation.

Reference: GpuAggregateExec.scala:711 — when merging accumulated
partials stops reducing, the reference re-partitions them by key hash
and merges each bucket independently.  This module is that fallback
grown into a first-class out-of-core tier (ROADMAP item 4):

  * partial aggregates hash-scatter by GROUP KEY into budget-registered
    `Spillable` buckets (`runtime/memory.py`) — key-disjoint partitions
    make the union of per-bucket results EXACT, with the same output
    contracts as the resident path (each bucket finishes on the
    existing dense/sorted group-by paths);
  * the bucket fan-out derives from measured partial BYTES vs the
    out-of-core resident window (`exec/ooc.py`), not just the legacy
    row gate, so a wide-row aggregation degrades before the budget
    OOMs rather than after;
  * a bucket that still exceeds the window re-scatters recursively
    with a re-salted hash (bounded by `sql.ooc.maxDepth`) so key skew
    cannot OOM one bucket; merges inside a bucket are rolling and
    retry-wrapped, holding at most two batches resident;
  * every partition pass fires the `ooc` chaos site after publishing
    its `ooc_state` instant, and the `tpu_ooc_*` families count
    elections/partitions/bytes/recursions (`docs/METRICS.md`).

`HashAggregateExec` (exec/plan.py) owns WHEN to elect this tier (row
gate, byte gate, forced/escalated context); this module owns the
bucket lifecycle, including the idempotent-close cleanup sweep that
early generator abandonment (a LIMIT above the aggregation) relies on.
"""
from __future__ import annotations

from typing import Iterator, List

from ..columnar.device import DeviceBatch
from ..ops.filter import compact_batch
from ..ops.batch_ops import shrink_to_rows
from . import ooc as O
from .plan import ExecContext


class OutOfCoreAggregator:
    """Bucket lifecycle of one spill-partitioned aggregation."""

    def __init__(self, agg, nkeys: int, ctx: ExecContext,
                 policy: "O.OocPolicy", k: int):
        self.agg = agg                       # exec.aggregate.HashAggregate
        self.nkeys = nkeys
        self.ctx = ctx
        self.policy = policy
        self.k = k
        self.buckets: List[list] = [[] for _ in range(k)]
        self._scattered = 0

    # -- scatter -----------------------------------------------------------
    def _scatter(self, pb: DeviceBatch, buckets, nparts: int,
                 salt: int) -> int:
        """Split a partial batch into hash buckets of its group keys
        (value-stable across batches: string keys hash dictionary
        VALUES, not per-batch codes).  Returns spillable bytes added."""
        from ..runtime.memory import Spillable
        from .plan import _agg_partition_ids
        ctx = self.ctx
        ids = _agg_partition_ids(pb, self.nkeys, nparts, salt)
        live = pb.row_mask()
        added = 0
        for p in range(nparts):
            part = compact_batch(pb, (ids == p) & live, ctx.conf)
            part = shrink_to_rows(part, int(part.num_rows), ctx.conf)
            if int(part.num_rows):
                sp = Spillable(part, ctx.budget)
                # live-row-scaled size: recursion decisions must not be
                # inflated by min-bucket capacity padding of tiny slices
                sp.live_nbytes = O.batch_bytes(part)
                buckets[p].append(sp)
                added += sp.live_nbytes
        return added

    def add(self, pb: DeviceBatch) -> None:
        """Scatter one partial into the top-level buckets."""
        self._scattered += self._scatter(pb, self.buckets, self.k, 0)

    # -- finalize ----------------------------------------------------------
    def results(self) -> Iterator[DeviceBatch]:
        ctx = self.ctx
        O.record_partitions(ctx, "agg", self.k, self._scattered)
        try:
            for p, blist in enumerate(self.buckets):
                if not blist:
                    continue
                O.fire(ctx, "agg", bucket=p, k=self.k, depth=0)
                yield from self._finalize(blist, 1)
        finally:
            # early abandonment / errors must release every registered
            # spillable (close is idempotent by contract)
            self.close()

    def _finalize(self, blist, depth: int) -> Iterator[DeviceBatch]:
        """Merge + finalize one bucket.  Oversized buckets re-scatter
        with a different hash salt (bounded depth); merges are rolling
        and retry-wrapped so the working set stays at two batches."""
        from ..config import AGG_FALLBACK_PARTITIONS
        from ..runtime.memory import Spillable
        from ..runtime.retry import with_retry
        ctx, conf, policy = self.ctx, self.ctx.conf, self.policy
        total = sum(sp.num_rows for sp in blist)
        total_bytes = sum(getattr(sp, "live_nbytes", sp.nbytes)
                          for sp in blist)
        # re-scatter only when the bucket's distinct-key bound (its row
        # count) exceeds what one merged batch can hold — the rolling
        # merge below keeps residency at TWO batches regardless of how
        # many spillable slices the bucket accumulated, so byte volume
        # alone never justifies the re-partition churn
        rows_trip = len(blist) > 1 and total > 2 * conf.batch_size_rows
        sub: List[list] = []
        acc = None
        try:
            if depth < policy.max_depth and rows_trip:
                k = conf.get(AGG_FALLBACK_PARTITIONS)
                if policy.bytes_trip(total_bytes):
                    O.record_recursion(ctx, "agg")
                    k = max(k, O.partition_count(total_bytes, policy))
                sub = [[] for _ in range(k)]
                added = 0
                for sp in blist:
                    b = sp.get()
                    sp.close()
                    added += self._scatter(b, sub, k, salt=depth)
                ctx.bump("agg_repartition_fallbacks")
                O.record_partitions(ctx, "agg", k, added)
                for p, sl in enumerate(sub):
                    if sl:
                        O.fire(ctx, "agg", bucket=p, k=k, depth=depth)
                        yield from self._finalize(sl, depth + 1)
                return
            acc = blist[0]
            for sp in blist[1:]:
                # both inputs stay REGISTERED during the merge attempt so
                # the retry's spill_all can actually demote them (the
                # reference's "inputs must be spillable" contract); get()
                # inside the attempt re-materializes after a spill
                a, b = acc, sp
                merged = with_retry(ctx.budget, conf,
                                    lambda: self.agg.merge([a.get(),
                                                            b.get()]))
                nxt = Spillable(merged, ctx.budget)
                a.close()
                b.close()
                acc = nxt
            out = acc.get()
            acc.close()
            yield self.agg.final(out)
        finally:
            # early abandonment / mid-merge failure: release everything
            # still registered (close is idempotent)
            for sp in blist:
                sp.close()
            for sl in sub:
                for sp in sl:
                    sp.close()
            if acc is not None:
                acc.close()

    def close(self) -> None:
        for blist in self.buckets:
            for sp in blist:
                sp.close()
