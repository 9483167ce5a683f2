"""QueryProfile: the offline profiling-tool aggregate over raw spans.

Reference: the RAPIDS Accelerator ships a profiling tool that replays
Spark event logs into per-SQL operator/time breakdowns (SURVEY §5).
`QueryProfile` is that aggregate for one query: the
compile/execute/transition/shuffle wall-time split, a per-node-id
operator table (two `HashAggregateExec`s stay two rows), the fallback
summary, data-movement counters and the memory high-water.  Build it
from a live ExecContext (`from_context`) or a written event log
(`from_event_log`); `scripts/profile_report.py` renders it from disk,
`bench.py` embeds `summary()` per query.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

from .tracer import EventLog, NULL_TRACER, QueryTracer, read_event_log

#: metric keys of the per-node-id operator counters (exec/metrics.py)
_NODE_METRIC_RE = re.compile(
    r"^(?P<name>\w+)#(?P<nid>\d+)\.(?P<field>op_time_ms|total_time_ms|"
    r"output_rows|output_batches)$")

#: metric keys of the per-segment attribution counters (exec/compiled.py
#: _record_segment; populated when spark.rapids.tpu.profile.segments on)
_SEGMENT_METRIC_RE = re.compile(
    r"^segment\.(?P<node>[\w#]+)\.(?P<field>device_ms|rows|out_bytes|"
    r"executions|flops|bytes_accessed|peak_temp_bytes|hbm_bytes|"
    r"hbm_peak_bytes|hbm_resident_pre|dispatch_ms|pad_rows|"
    r"pad_waste_ms)$")

#: span categories that are measured directly; "execute" is the residual
_SPLIT_CATS = ("compile", "transition", "shuffle")


def _union_ms(ivals: List[tuple]) -> float:
    """Total covered milliseconds of possibly-overlapping intervals."""
    if not ivals:
        return 0.0
    ivals = sorted(ivals)
    total, lo, hi = 0.0, ivals[0][0], ivals[0][1]
    for a, b in ivals[1:]:
        if a > hi:
            total += hi - lo
            lo, hi = a, b
        else:
            hi = max(hi, b)
    total += hi - lo
    return total * 1000.0


class QueryProfile:
    def __init__(self, spans, events, counters, metrics, meta,
                 registry=None, truncated=False):
        self.spans = list(spans)
        self.events = list(events)
        self.counters = dict(counters)
        self.metrics = dict(metrics or {})
        self.meta = dict(meta or {})
        #: metrics-plane snapshot from the event log's query_end record
        #: (PR 5); empty for live contexts and truncated logs
        self.registry = dict(registry or {})
        self.truncated = bool(truncated)

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_context(cls, ctx) -> "QueryProfile":
        """From a collected query's ExecContext (tracer may be NULL —
        the metrics-only tables still populate)."""
        tr = getattr(ctx, "tracer", NULL_TRACER)
        if isinstance(tr, QueryTracer):
            return cls(tr.spans, tr.events, tr.counters,
                       tr.metrics if tr.metrics is not None
                       else ctx.metrics, tr.meta)
        return cls([], [], {}, ctx.metrics, {})

    @classmethod
    def from_event_log(cls, path_or_log) -> "QueryProfile":
        log = path_or_log if isinstance(path_or_log, EventLog) \
            else read_event_log(path_or_log)
        return cls(log.spans, log.events, log.counters, log.metrics,
                   log.meta, registry=log.registry,
                   truncated=log.truncated)

    # -- aggregates --------------------------------------------------------
    def wall_ms(self) -> float:
        roots = [s for s in self.spans if s.cat == "query"]
        if roots:
            return sum(s.dur_ms for s in roots)
        if self.spans:
            return (max(s.t1 for s in self.spans) -
                    min(s.t0 for s in self.spans)) * 1000.0
        return 0.0

    def time_split(self) -> Dict[str, float]:
        """compile / execute / transition / shuffle / plan split.

        compile, transition and shuffle sum their spans' interval UNION
        clipped to the query span (nested same-cat spans never double
        count); execute is the residual query wall.  plan covers the
        wrap/tag/convert phases, which run before the query span."""
        roots = [s for s in self.spans if s.cat == "query"]
        q0 = min((s.t0 for s in roots), default=None)
        q1 = max((s.t1 for s in roots), default=None)
        out = {"wall_ms": round(self.wall_ms(), 3),
               "plan_ms": round(sum(s.dur_ms for s in self.spans
                                    if s.cat == "plan"), 3)}
        covered = []
        for cat in _SPLIT_CATS:
            ivals = []
            for s in self.spans:
                if s.cat != cat:
                    continue
                t0, t1 = s.t0, s.t1
                if q0 is not None:
                    t0, t1 = max(t0, q0), min(t1, q1)
                if t1 > t0:
                    ivals.append((t0, t1))
            out[f"{cat}_ms"] = round(_union_ms(ivals), 3)
            covered.extend(ivals)
        out["execute_ms"] = round(
            max(0.0, out["wall_ms"] - _union_ms(covered)), 3)
        return out

    def operators(self) -> List[Dict[str, Any]]:
        """Per-node-id operator table from the instrumented metrics,
        sorted by self time (total minus children) descending."""
        rows: Dict[str, Dict[str, Any]] = {}
        for k, v in self.metrics.items():
            m = _NODE_METRIC_RE.match(k)
            if not m:
                continue
            node = f"{m.group('name')}#{m.group('nid')}"
            row = rows.setdefault(node, {"node": node,
                                         "name": m.group("name"),
                                         "nid": int(m.group("nid"))})
            row[m.group("field")] = v
        children: Dict[Optional[str], List[str]] = {}
        for n in self.meta.get("plan_nodes", []):
            children.setdefault(n.get("parent"), []).append(n["id"])

        def measured_descendants_ms(node: str) -> float:
            """Totals of the nearest MEASURED descendants — skipping
            through unmeasured nodes (fused filters, pass-throughs whose
            metered execute never ran) so their children still subtract
            from this operator's self time."""
            total = 0.0
            stack = list(children.get(node, []))
            while stack:
                c = stack.pop()
                if c in rows:
                    total += float(rows[c].get("total_time_ms", 0.0))
                else:
                    stack.extend(children.get(c, []))
            return total

        for node, row in rows.items():
            total = float(row.get("total_time_ms", 0.0))
            sub = measured_descendants_ms(node) if children else 0.0
            row["self_time_ms"] = round(max(0.0, total - sub), 3)
        return sorted(rows.values(),
                      key=lambda r: (-r["self_time_ms"], r["nid"]))

    # -- the attribution plane (per-segment device time) -------------------
    def segments(self) -> List[Dict[str, Any]]:
        """Per-segment device-time attribution table: one row per
        compiled program segment ({node, device_ms, rows, out_bytes,
        executions, pct, node_lo/node_hi, static cost overlay}), sorted
        by device_ms descending.  Populated only from runs with
        `spark.rapids.tpu.profile.segments` on; merges the segment.*
        metrics with span-level node ranges."""
        rows: Dict[str, Dict[str, Any]] = {}
        for k, v in self.metrics.items():
            m = _SEGMENT_METRIC_RE.match(k)
            if not m or not isinstance(v, (int, float)):
                continue
            row = rows.setdefault(m.group("node"),
                                  {"node": m.group("node")})
            row[m.group("field")] = v
        from_metrics = set(rows)
        for s in self.spans:
            if s.name != "segment" or s.cat != "execute":
                continue
            node = s.node or "?"
            row = rows.setdefault(node, {"node": node})
            if node not in from_metrics:
                # span-only fallback (e.g. a metrics-stripped log):
                # accumulate the per-execution attrs
                row["device_ms"] = row.get("device_ms", 0.0) + \
                    float(s.attrs.get("device_ms", s.dur_ms))
                row["rows"] = row.get("rows", 0) + s.attrs.get("rows", 0)
                row["out_bytes"] = row.get("out_bytes", 0) + \
                    s.attrs.get("out_bytes", 0)
            if "node_lo" not in row and "node_lo" in s.attrs:
                row["node_lo"] = s.attrs["node_lo"]
                row["node_hi"] = s.attrs.get("node_hi")
        total = sum(float(r.get("device_ms", 0.0)) for r in rows.values())
        for r in rows.values():
            r["device_ms"] = round(float(r.get("device_ms", 0.0)), 3)
            r["pct"] = round(100.0 * r["device_ms"] / total, 1) \
                if total else 0.0
        return sorted(rows.values(), key=lambda r: -r["device_ms"])

    def attributed_device_pct(self) -> Optional[float]:
        """Fraction of the measured device wall (the union of
        cat=execute spans) covered by NAMED plan segments (`segment`
        spans carrying a node id) — the explain_analyze attribution
        bar.  None when the run carried no execute spans (eager path,
        or tracing off)."""
        ex = [(s.t0, s.t1) for s in self.spans if s.cat == "execute"]
        total = _union_ms(ex)
        if not total:
            return None
        seg = [(s.t0, s.t1) for s in self.spans
               if s.name == "segment" and s.cat == "execute"
               and (s.node or s.attrs.get("node_lo") is not None)]
        return min(1.0, _union_ms(seg) / total)

    def attributed_wall_pct(self) -> Optional[float]:
        """Fraction of the END-TO-END query wall covered by named
        wall-breakdown categories — the honest attribution bar.
        `attributed_device_pct` divides by the execute-span union only,
        so a fixed-overhead-tail query (q2/q16 class) can report 90%+
        while 99% of its wall is seams and dispatch; this one divides by
        the full query span.  None without a query span."""
        if not any(s.cat == "query" for s in self.spans):
            return None
        bd = self.wall_breakdown()
        return min(1.0, bd["attributed_pct"] / 100.0)

    # -- the overhead attribution plane (wall decomposition) ---------------
    def overheads(self) -> Dict[str, float]:
        """The overhead.* accumulators (exec brackets): seam_ms /
        seam_count / seam_rows / seam_bytes (always-on), dispatch_ms /
        dispatch_floor_ms / pad_rows / pad_waste_ms (profiled runs),
        host_prep_ms, fetch_ms."""
        out: Dict[str, float] = {}
        for k, v in self.metrics.items():
            if k.startswith("overhead.") and isinstance(v, (int, float)):
                out[k.removeprefix("overhead.")] = v
        return out

    def wall_breakdown(self) -> Dict[str, Any]:
        """Decompose the end-to-end query wall into named, summing
        categories (the fixed-overhead-tail view, ROADMAP item 1):

          device_compute_ms  measured wall inside compiled segments,
                             net of the per-dispatch floor
          dispatch_ms        measured per-backend dispatch floor x
                             program launches
          seam_ms            host sync + re-bucket at every
                             SplitCompiledPlan boundary (tpu.seam)
          compile_ms         trace+compile span union (in-wall)
          fetch_ms           d2h/h2d transition span union (seams
                             excluded — they have their own line)
          shuffle_ms         shuffle span union
          host_prep_ms       in-wall setup before execution
                             (tpu.scope_enter)
          prepare_ms         program lookup and cache load, net of
                             compile and upload (tpu.prepare)
          speculate_ms       next-segment speculation (tpu.speculate)
          launch_ms          flatten + enqueue + rebuild, net of the
                             program's own wall (tpu.launch)
          finish_ms          history feed, lazy-metric fetch, registry
                             and scope exit (tpu.finish)
          unattributed_ms    the residual

        `pad_waste_ms`/`pad_rows` ride along as informational fields: the
        bucket-quantization tax is a SLICE of device_compute_ms, not an
        additive category.  Works from a live context or an event log;
        dispatch/pad fields populate only on profiled (profile.segments)
        runs.

        The wall is the query span where the run was traced: the
        `tpu.*` spans of the collect path (obs/tracer.CollectSpan) are
        clipped to it, and `plan_ms` and `semaphore_wait_ms`, which
        happen before it opens, are pre-wall lines.  Without spans
        (tracing off) the wall is `overhead.collect_ms`, the whole of
        DataFrame.collect(): the categories are the span seam's own
        always-on keys, planning is inside it, and the residual is
        `overhead.unattributed_ms`, the `tpu.collect` span's own time."""
        roots = [s for s in self.spans if s.cat == "query"]
        q0 = min((s.t0 for s in roots), default=None)
        q1 = max((s.t1 for s in roots), default=None)
        ov = self.overheads()
        m = self.metrics

        def clipped(pick) -> List[tuple]:
            ivals = []
            for s in self.spans:
                if not pick(s):
                    continue
                t0, t1 = s.t0, s.t1
                if q0 is not None:
                    t0, t1 = max(t0, q0), min(t1, q1)
                if t1 > t0:
                    ivals.append((t0, t1))
            return ivals

        seam_names = ("tpu.seam", "tpu.seam_wait", "tpu.seam_resolve")
        seg_dev = sum(float(r.get("device_ms", 0.0))
                      for r in self.segments())
        dispatch_ms = float(ov.get("dispatch_ms", 0.0))
        if seg_dev <= 0.0:
            # unprofiled run: the program call (`tpu.enqueue`) is the
            # dispatch wall; the measured floor x launch count bounds
            # its overhead share
            seg_dev = float(ov.get("enqueue_ms", 0.0))
            floor = float(ov.get("dispatch_floor_ms", 0.0))
            if not dispatch_ms and floor:
                dispatch_ms = floor * float(m.get("exec_dispatches", 0))
        dispatch_ms = min(dispatch_ms, seg_dev)
        cats = {
            "device_compute_ms": max(seg_dev - dispatch_ms, 0.0),
            "dispatch_ms": dispatch_ms,
            "seam_ms": float(ov.get("seam_ms", 0.0)),
        }
        if roots or "collect_ms" not in ov:
            wall = self.wall_ms()
            by_cat = {
                "compile_ms": clipped(lambda s: s.cat == "compile"),
                "fetch_ms": clipped(lambda s: s.cat == "transition"
                                    and s.name not in seam_names),
                "shuffle_ms": clipped(lambda s: s.cat == "shuffle")}
            cats.update({k: _union_ms(v) for k, v in by_cat.items()})
            # what is counted so far, as intervals; each layer boundary
            # then adds the part of its spans that these leave free
            # (launch last: a single program looks its executable up
            # inside its launch)
            taken = [iv for v in by_cat.values() for iv in v] + clipped(
                lambda s: s.cat == "execute" or s.name in seam_names)
            for key, name in (("host_prep_ms", "tpu.scope_enter"),
                              ("prepare_ms", "tpu.prepare"),
                              ("speculate_ms", "tpu.speculate"),
                              ("finish_ms", "tpu.finish"),
                              ("launch_ms", "tpu.launch")):
                mine = clipped(lambda s: s.name == name)
                cats[key] = _union_ms(mine + taken) - _union_ms(taken)
                taken += mine
            residual = max(wall - sum(cats.values()), 0.0)
            plan_ms = sum(s.dur_ms for s in self.spans if s.cat == "plan")
        else:
            wall = float(ov["collect_ms"])
            compile_ms = min(float(m.get("compile_ms", 0.0)),
                             float(ov.get("prepare_ms", 0.0)))
            cats.update({
                "compile_ms": compile_ms,
                # a mesh's placement (tpu.shard) is an upload: with
                # spans it lies in the `upload` transition, here too
                "fetch_ms": float(ov.get("fetch_ms", 0.0))
                + float(ov.get("shard_ms", 0.0)),
                "shuffle_ms": 0.0,
                "host_prep_ms": float(ov.get("host_prep_ms", 0.0)),
                "prepare_ms": float(ov.get("prepare_ms", 0.0))
                - compile_ms,
                "speculate_ms": float(ov.get("speculate_ms", 0.0)),
                "finish_ms": float(ov.get("finish_ms", 0.0)),
                "launch_ms": max(float(ov.get("launch_ms", 0.0))
                                 - seg_dev, 0.0)})
            residual = float(ov.get("unattributed_ms", 0.0))
            plan_ms = float(ov.get("plan_ms", 0.0))
            cats["plan_ms"] = plan_ms
        named = sum(cats.values())
        out: Dict[str, Any] = {"wall_ms": round(wall, 3)}
        out.update({k: round(v, 3) for k, v in cats.items()})
        out["unattributed_ms"] = round(residual, 3)
        out["attributed_pct"] = round(100.0 * min(named / wall, 1.0), 1) \
            if wall > 0 else 0.0
        out["pad_waste_ms"] = round(float(ov.get("pad_waste_ms", 0.0)), 3)
        for k in ("pad_rows", "seam_count", "seam_rows", "seam_bytes"):
            if ov.get(k):
                out[k] = int(ov[k])
        if ov.get("dispatch_floor_ms"):
            out["dispatch_floor_ms"] = round(
                float(ov["dispatch_floor_ms"]), 4)
        n_disp = m.get("exec_dispatches")
        if n_disp:
            out["dispatches"] = int(n_disp)
        # planning and the device-permit queue wait: pre-wall lines of a
        # traced run (both happen before the query span opens)
        out["plan_ms"] = round(plan_ms, 3)
        sem = m.get("semaphore_wait_ms")
        if sem:
            out["semaphore_wait_ms"] = round(float(sem), 3)
        return out

    def mesh_timeline(self) -> Dict[str, Any]:
        """Per-query mesh/collective timeline from the exchange
        instants (parallel/exchange.py): one record per ragged exchange
        call (round schedule, quotas, wire bytes pre/post compress,
        per-device arrival counts, per-round staging vs collective ms)
        plus one-time dictionary gathers and skew-split events."""
        exchanges: List[Dict[str, Any]] = []
        skew: List[Dict[str, Any]] = []
        cur: Optional[Dict[str, Any]] = None
        org = min([s.t0 for s in self.spans] +
                  [e.t for e in self.events], default=0.0)
        for e in self.events:
            t_ms = round((e.t - org) * 1e3, 3)
            if e.name == "ici_exchange":
                cur = {"kind": "exchange", "t_ms": t_ms, **e.attrs,
                       "round_events": []}
                exchanges.append(cur)
            elif e.name == "exchange_round" and cur is not None:
                cur["round_events"].append({"t_ms": t_ms, **e.attrs})
            elif e.name == "exchange_timing" and cur is not None:
                stage = e.attrs.get("stage_ms") or []
                coll = e.attrs.get("collective_ms") or []
                for rec, sm, cm in zip(cur["round_events"], stage, coll):
                    rec["stage_ms"] = sm
                    rec["collective_ms"] = cm
                cur["stage_ms_total"] = round(sum(stage), 3)
                cur["collective_ms_total"] = round(sum(coll), 3)
            elif e.name == "ici_dict_gather":
                exchanges.append({"kind": "dict_gather", "t_ms": t_ms,
                                  **e.attrs})
            elif e.name == "exchange_skew_split":
                skew.append({"t_ms": t_ms, **e.attrs})
        return {"exchanges": exchanges, "skew_splits": skew}

    def fallbacks(self) -> List[str]:
        return list(self.meta.get("fallbacks", []))

    def compile_stats(self) -> Dict[str, Any]:
        return {
            "cache_misses": int(self.metrics.get("compile_cache_misses",
                                                 0)),
            "cache_hits": int(self.metrics.get("compile_cache_hits", 0)),
            "compile_ms": round(float(self.metrics.get("compile_ms",
                                                       0.0)), 3),
        }

    def data_movement(self) -> Dict[str, int]:
        keys = ("h2d_bytes", "d2h_bytes", "shuffle_bytes_written",
                "shuffle_bytes_read", "ici_exchange_bytes")
        out = {}
        for k in keys:
            v = self.counters.get(k, self.metrics.get(k, 0))
            if v:
                out[k] = int(v)
        for k in ("h2d_rows", "d2h_rows", "shuffle_rows_written",
                  "shuffle_rows_read", "scanned_rows"):
            v = self.metrics.get(k)
            if v:
                out[k] = int(v)
        return out

    def memory(self) -> Dict[str, Any]:
        out = {}
        for k, v in self.metrics.items():
            if k.startswith("memory."):
                out[k.removeprefix("memory.")] = v
        return out

    # -- the memory-attribution plane (obs/memattr.py) ---------------------
    def hbm(self) -> Dict[str, Any]:
        """The query's measured-HBM view: the measured working set
        (memattr query peak / XLA memory_analysis floor), the budget
        peak reservation, residual-leak bytes and the per-segment
        memory table — empty for runs without the plane armed."""
        mem = self.memory()
        out: Dict[str, Any] = {}
        mws = mem.get("hbm_measured_working_set") \
            or self.metrics.get("exec_hbm_bytes")
        if mws:
            out["measured_working_set_bytes"] = int(mws)
        if mem.get("peak_bytes"):
            out["peak_reservation_bytes"] = int(mem["peak_bytes"])
        if mem.get("residual_naked_bytes"):
            out["residual_naked_bytes"] = int(mem["residual_naked_bytes"])
        if mem.get("hbm_census_skipped"):
            out["census_skipped"] = int(mem["hbm_census_skipped"])
        segs = [{k: s[k] for k in ("node", "hbm_bytes", "hbm_peak_bytes",
                                   "hbm_resident_pre") if k in s}
                for s in self.segments() if s.get("hbm_peak_bytes")]
        if segs:
            out["segments"] = segs
        return out

    def hbm_timeline(self) -> List[Dict[str, Any]]:
        """The per-query HBM timeline (reserve/release/spill/OOM
        watermarks + segment brackets with node attribution) embedded
        in the event-log meta by the memattr recorder."""
        tl = self.meta.get("hbm_timeline")
        return list(tl) if isinstance(tl, list) else []

    def incidents(self) -> Dict[str, int]:
        """Instant-event histogram: oom_retry / batch_split / spill /
        whole_plan_fallback / semaphore_wait counts."""
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.name] = out.get(e.name, 0) + 1
        return out

    # -- presentation ------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        out = {"time_split": self.time_split(),
               "wall_breakdown": self.wall_breakdown(),
               "operators": self.operators(),
               "compile": self.compile_stats(),
               "data_movement": self.data_movement(),
               "memory": self.memory(),
               "incidents": self.incidents(),
               "fallbacks": self.fallbacks()}
        segs = self.segments()
        if segs:
            out["segments"] = segs
            pct = self.attributed_device_pct()
            if pct is not None:
                out["attributed_device_pct"] = round(pct * 100, 1)
        wpct = self.attributed_wall_pct()
        if wpct is not None:
            out["attributed_wall_pct"] = round(wpct * 100, 1)
        mesh = self.mesh_timeline()
        if mesh["exchanges"] or mesh["skew_splits"]:
            out["mesh_timeline"] = mesh
        hbm = self.hbm()
        if hbm:
            out["hbm"] = hbm
        tl = self.hbm_timeline()
        if tl:
            out["hbm_timeline"] = tl
        if self.registry:
            out["registry"] = self.registry
        if self.truncated:
            out["truncated"] = True
        return out

    def summary(self, top_n: int = 5) -> Dict[str, Any]:
        """Compact per-query embedding for BENCH_*.json."""
        ops = self.operators()
        out = {"time_split": self.time_split(),
               "wall_breakdown": self.wall_breakdown(),
               "top_operators": [
                   {"node": o["node"],
                    "self_time_ms": o["self_time_ms"],
                    "output_rows": o.get("output_rows", 0)}
                   for o in ops[:top_n]],
               "compile": self.compile_stats(),
               "data_movement": self.data_movement(),
               "memory_peak_bytes": self.memory().get("peak_bytes", 0),
               "incidents": self.incidents(),
               "fallback_count": len(self.fallbacks())}
        segs = self.segments()
        if segs:
            # the segment-level attribution rides into the bench record
            # so profile_diff.py / check_regression.py can cite the
            # regressed SEGMENT, not just the query
            out["segments"] = [
                {k: s[k] for k in ("node", "device_ms", "pct", "rows")
                 if k in s} for s in segs[:top_n]]
            pct = self.attributed_device_pct()
            if pct is not None:
                out["attributed_device_pct"] = round(pct * 100, 1)
        hbm = self.hbm()
        if hbm.get("peak_reservation_bytes"):
            # per-query HBM fields bench.py lifts into BENCH records so
            # check_regression.py can gate HBM-peak regressions
            out["hbm_peak_bytes"] = max(
                hbm["peak_reservation_bytes"],
                hbm.get("measured_working_set_bytes", 0))
        elif hbm.get("measured_working_set_bytes"):
            out["hbm_peak_bytes"] = hbm["measured_working_set_bytes"]
        if hbm.get("measured_working_set_bytes"):
            out["hbm_measured_working_set"] = \
                hbm["measured_working_set_bytes"]
        return out

    def render(self) -> str:
        """The human report: time split, top operators, fallbacks,
        memory high-water — the profiling-tool output."""
        split = self.time_split()
        lines = ["== query profile =="
                 + (" (TRUNCATED log — prefix only)"
                    if self.truncated else ""),
                 f"wall              {split['wall_ms']:.1f} ms",
                 f"  plan (pre-wall) {split['plan_ms']:.1f} ms",
                 f"  compile         {split['compile_ms']:.1f} ms",
                 f"  execute         {split['execute_ms']:.1f} ms",
                 f"  transition      {split['transition_ms']:.1f} ms",
                 f"  shuffle         {split['shuffle_ms']:.1f} ms"]
        cs = self.compile_stats()
        lines.append(f"compile cache     {cs['cache_hits']} hits / "
                     f"{cs['cache_misses']} misses")
        bd = self.wall_breakdown()
        if bd["wall_ms"] > 0:
            lines.extend(render_wall_breakdown(bd))
        if self.meta.get("stitched"):
            # a supervisor-side STITCHED pool record: render the cross-
            # process story — admission -> grant -> each execute attempt
            # (worker-named), with worker_lost instants marking redrives
            lines.append("-- stitched serving record "
                         f"(tenant={self.meta.get('tenant')}, "
                         f"status={self.meta.get('status')}, "
                         f"redrives={self.meta.get('redrives', 0)}) --")
            losses = {(e.attrs or {}).get("attempt"): e.attrs or {}
                      for e in self.events if e.name == "worker_lost"}
            for s in sorted(self.spans, key=lambda s: s.t0):
                if s.cat not in ("serving", "execute"):
                    continue
                extra = ""
                if s.cat == "execute":
                    a = s.attrs or {}
                    if "lost" in a:
                        extra = f"  ! LOST ({a['lost']}) -> redrive"
                    elif a.get("device_us") is not None:
                        extra = f"  device_us={a['device_us']}"
                lines.append(f"  {s.name:<24} {s.dur_ms:>9.1f} ms"
                             f"{extra}")
            if losses:
                lines.append(f"  workers: "
                             f"{self.meta.get('workers')} "
                             f"(answered by {self.meta.get('worker')})")
            wp = self.meta.get("worker_profile") or {}
            if wp:
                hbm = wp.get("hbm") or {}
                lines.append(
                    f"  worker profile: {wp.get('worker')} "
                    f"pid={wp.get('pid')} "
                    f"device_us={wp.get('device_us')} "
                    f"hbm_live={hbm.get('live_bytes', 0)} "
                    f"hbm_peak={hbm.get('peak_bytes', 0)}")
        ops = self.operators()
        if ops:
            lines.append("-- top operators (self time) --")
            for o in ops[:10]:
                lines.append(
                    f"  {o['node']:<32} {o['self_time_ms']:>9.1f} ms  "
                    f"rows={o.get('output_rows', 0)} "
                    f"batches={o.get('output_batches', 0)}")
        segs = self.segments()
        if segs:
            pct = self.attributed_device_pct()
            hdr = "-- segments (measured device time) --"
            if pct is not None:
                hdr += f"  [{pct * 100:.1f}% of device wall attributed]"
            lines.append(hdr)
            for sg in segs[:10]:
                rng = ""
                if sg.get("node_lo") is not None:
                    rng = f" nodes #{sg['node_lo']}-#{sg.get('node_hi')}"
                cost = ""
                if sg.get("flops"):
                    cost = f" flops={sg['flops']:.3g}"
                lines.append(
                    f"  {sg['node']:<32} {sg['device_ms']:>9.1f} ms "
                    f"({sg['pct']:>5.1f}%) rows={sg.get('rows', 0)}"
                    f"{rng}{cost}")
        mesh = self.mesh_timeline()
        if mesh["exchanges"]:
            lines.append("-- mesh timeline --")
            for ex in mesh["exchanges"][:12]:
                if ex.get("kind") == "dict_gather":
                    lines.append(f"  dict_gather bytes={ex.get('bytes', 0)}")
                    continue
                lines.append(
                    f"  exchange rounds={ex.get('rounds', 0)} "
                    f"quota={ex.get('quota', 0)} "
                    f"bytes={ex.get('bytes', 0)} "
                    f"(pre={ex.get('bytes_pre_compress', 0)}) "
                    f"stage={ex.get('stage_ms_total', 0)}ms "
                    f"collective={ex.get('collective_ms_total', 0)}ms")
            if mesh["skew_splits"]:
                lines.append(f"  skew splits: {len(mesh['skew_splits'])}")
        hbm = self.hbm()
        if hbm:
            lines.append("-- hbm (memory attribution) --")
            if hbm.get("measured_working_set_bytes"):
                lines.append(f"  measured working set    "
                             f"{hbm['measured_working_set_bytes']} bytes")
            if hbm.get("peak_reservation_bytes"):
                lines.append(f"  peak budget reservation "
                             f"{hbm['peak_reservation_bytes']} bytes")
            if hbm.get("residual_naked_bytes"):
                lines.append(f"  ! RESIDUAL LEAK         "
                             f"{hbm['residual_naked_bytes']} bytes of "
                             f"naked reservations at query end")
            if hbm.get("census_skipped"):
                lines.append(f"  (census samples skipped: "
                             f"{hbm['census_skipped']})")
            for sg in hbm.get("segments", [])[:10]:
                lines.append(
                    f"  {sg['node']:<32} hbm_peak="
                    f"{sg.get('hbm_peak_bytes', 0)} "
                    f"analysis={sg.get('hbm_bytes', 0)} "
                    f"resident_pre={sg.get('hbm_resident_pre', 0)}")
            tl = self.hbm_timeline()
            if tl:
                by_ev: Dict[str, int] = {}
                for e in tl:
                    by_ev[e.get("ev", "?")] = by_ev.get(
                        e.get("ev", "?"), 0) + 1
                peak_ev = max(tl, key=lambda e: e.get("live", 0))
                lines.append(
                    f"  timeline: {len(tl)} events ("
                    + ", ".join(f"{k}={v}" for k, v in sorted(by_ev.items()))
                    + f"); watermark peak {peak_ev.get('live', 0)} bytes"
                    + (f" at t={peak_ev.get('t_ms', 0)}ms"
                       f" node={peak_ev.get('node')}"
                       if peak_ev.get("node") else ""))
        dm = self.data_movement()
        if dm:
            lines.append("-- data movement --")
            for k, v in dm.items():
                lines.append(f"  {k:<24} {v}")
        mem = self.memory()
        if mem:
            lines.append(f"memory high-water {mem.get('peak_bytes', 0)} "
                         f"bytes; spilled {mem.get('spilled_batches', 0)} "
                         f"batches / {mem.get('spilled_bytes', 0)} bytes")
        inc = self.incidents()
        if inc:
            lines.append("-- incidents --")
            for k, v in sorted(inc.items()):
                lines.append(f"  {k:<24} {v}")
        fb = self.fallbacks()
        lines.append(f"-- fallbacks ({len(fb)}) --")
        for r in fb:
            lines.append(f"  ! {r}")
        if self.registry:
            # the always-on plane's state at log-write time, largest
            # counters first (docs/METRICS.md catalog)
            lines.append("-- metrics registry (process, at log write) --")
            scalars = [(k, v) for k, v in self.registry.items()
                       if isinstance(v, (int, float))]
            for k, v in sorted(scalars, key=lambda kv: -abs(kv[1]))[:12]:
                lines.append(f"  {k:<52} {round(v, 3)}")
            if len(scalars) > 12:
                lines.append(f"  ... {len(scalars) - 12} more series")
        return "\n".join(lines)


#: wall-breakdown category -> report label, render order
_BREAKDOWN_LABELS = (
    ("device_compute_ms", "device compute"),
    ("dispatch_ms", "dispatch overhead"),
    ("seam_ms", "seam time"),
    ("compile_ms", "compile"),
    ("fetch_ms", "fetch/upload"),
    ("shuffle_ms", "shuffle"),
    ("host_prep_ms", "host prep"),
    ("prepare_ms", "program lookup"),
    ("speculate_ms", "speculation"),
    ("launch_ms", "launch (host)"),
    ("finish_ms", "finish"),
    ("unattributed_ms", "unattributed"),
)


def render_wall_breakdown(bd: Dict[str, Any]) -> List[str]:
    """Text lines for one wall_breakdown() dict — shared by
    QueryProfile.render() and EXPLAIN ANALYZE (obs/attribution.py)."""
    wall = bd.get("wall_ms") or 0.0
    lines = [f"-- wall breakdown (end-to-end, {wall:.1f} ms, "
             f"{bd.get('attributed_pct', 0.0):.1f}% attributed) --"]
    for key, label in _BREAKDOWN_LABELS:
        v = float(bd.get(key, 0.0))
        pct = 100.0 * v / wall if wall else 0.0
        extra = ""
        if key == "device_compute_ms" and bd.get("pad_waste_ms"):
            extra = (f"  [pad waste {bd['pad_waste_ms']:.2f} ms over "
                     f"{bd.get('pad_rows', 0)} pad rows]")
        elif key == "dispatch_ms" and bd.get("dispatch_floor_ms"):
            extra = (f"  [floor {bd['dispatch_floor_ms']:.3f} ms x "
                     f"{bd.get('dispatches', 0)} dispatches]")
        elif key == "seam_ms" and bd.get("seam_count"):
            extra = (f"  [{bd['seam_count']} seams, "
                     f"{bd.get('seam_rows', 0)} rows, "
                     f"{bd.get('seam_bytes', 0)} bytes re-bucketed]")
        lines.append(f"  {label:<18} {v:>9.2f} ms ({pct:>5.1f}%){extra}")
    pre = [f"plan {bd.get('plan_ms', 0.0):.1f} ms"]
    if bd.get("semaphore_wait_ms"):
        pre.append(f"queue wait {bd['semaphore_wait_ms']:.1f} ms")
    lines.append("  (pre-wall: " + ", ".join(pre) + ")")
    return lines
