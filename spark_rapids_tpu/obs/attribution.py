"""EXPLAIN ANALYZE — the device-time attribution plane's user surface.

Reference: Spark's SQL UI renders per-operator GPU metrics from the
plugin (GpuExec metric sets, SURVEY §5) so slow plans are diagnosable in
production; Flare (PAPERS.md) argues whole-stage-compiled engines need
compiler-level cost surfaces next to measured time.  This module is the
TPU-native pair of both ideas:

  * `run_explain_analyze(physical_query)` executes ONE profiled collect
    (`trace.enabled` + `profile.segments` forced on — whole-plan
    programs re-split at the seam boundaries the split compiler knows,
    every program dispatch blocks and records measured device wall) and
    renders the physical plan tree annotated with measured ms, rows,
    bytes, gather volume and % of query wall per segment;
  * the XLA static cost overlay (`cost_analysis()`/`memory_analysis()`
    captured at compile time) renders next to measured time, and
    predicted-vs-actual skew (time share wildly off FLOP share) flags
    mis-fused segments.

Surfaced as `DataFrame.explain_analyze()` and
`TpuSession.explain_analyze(df)`; `docs/PROFILING.md` has the
walkthrough.

The ATTRIBUTION_COVERED / ATTRIBUTION_EXEMPT sets below are the lint
contract (`scripts/check_docs.py`): every registered exec node class
must be in one of them, so a new operator cannot ship outside the
attribution plane unnoticed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Attribution coverage contract (linted by scripts/check_docs.py)
# ---------------------------------------------------------------------------

#: exec classes the attribution plane covers by construction: they are
#: instrumented with stable node ids (exec/metrics.py), their time lands
#: in per-node operator metrics, and compiled segments anchor at them
ATTRIBUTION_COVERED = frozenset({
    # device execs
    "HostScanExec", "ProjectExec", "FilterExec", "HashAggregateExec",
    "SortExec", "TopNExec", "GlobalLimitExec", "LocalLimitExec",
    "UnionExec", "CoalesceBatchesExec", "RangeExec", "SampleExec",
    "ExpandExec", "HashJoinExec", "CrossJoinExec",
    "AdaptiveShuffledJoinExec", "BroadcastExchangeExec",
    "ShuffleExchangeExec", "ShuffleReadExec", "CollectAggregateExec",
    "DistinctAggregateExec", "PercentileAggregateExec", "WindowExec",
    "GenerateExec", "ParquetScanExec", "TextScanExec", "OrcScanExec",
    # host execs (eager/CPU path — attributed via per-node metrics)
    "HostSourceExec", "CpuProjectExec", "CpuFilterExec",
    "CpuAggregateExec", "CpuSortExec", "CpuLimitExec", "CpuJoinExec",
    "CpuUnionExec", "CpuRangeExec", "CpuExpandExec", "CpuSampleExec",
    "CpuWindowExec", "CpuGenerateExec", "CpuParquetScanExec",
    "CpuTextScanExec", "CpuOrcScanExec", "HostToDeviceExec",
    "DeviceToHostExec", "CachedHostScan", "MapInPandasExec",
    "ArrowEvalPythonExec", "FlatMapGroupsInPandasExec",
    "FlatMapCoGroupsInPandasExec", "AggregateInPandasExec",
    "WindowInPandasExec",
})

#: exec classes deliberately OUTSIDE per-node attribution, with the
#: reason — the lint accepts these but a reviewer sees why
ATTRIBUTION_EXEMPT: Dict[str, str] = {
    "DeviceResidentScanExec": "split-seam leaf standing in for an "
                              "already-measured upstream segment's "
                              "output; its time IS the seam segment's",
    "_ReplayStage": "adaptive-join internal replay of an already-"
                    "materialized side; its wall lands on the owning "
                    "AdaptiveShuffledJoinExec node",
    "_BloomFilterStage": "adaptive-join internal probe-side stage; "
                         "composed into the owning join's time",
    "PartitionReadExec": "shuffle-manager internal per-partition "
                         "reader; attributed to ShuffleReadExec",
    "_GroupedPandasExec": "python-worker plumbing base; time lands on "
                          "the concrete pandas exec nodes",
    "_FrameSource": "python-worker frame feeder; time lands on the "
                    "cogrouped pandas exec",
}


def registered_exec_classes() -> List[str]:
    """Every concrete exec node class the engine can place in a
    physical plan, discovered from the live class hierarchies (device
    PlanNode + host HostNode subclasses) after importing the exec/io
    modules — the enumeration the attribution lint checks against."""
    # import every module that defines exec classes so the hierarchies
    # are complete (the same trick config's docs lint uses)
    from ..exec import (adaptive, cache, collect, compiled, distinct,  # noqa: F401
                        exchange, generate, host_exec, percentile,
                        plan, python_exec, window)
    from ..io import avro, iceberg, orc, parquet, text  # noqa: F401
    from ..exec.plan import PlanNode
    from ..exec.host_exec import HostNode

    def walk(cls, out):
        for sub in cls.__subclasses__():
            out.add(sub.__name__)
            walk(sub, out)

    names: set = set()
    walk(PlanNode, names)
    walk(HostNode, names)
    # abstract/base helpers that never appear as plan nodes
    names -= {"PlanNode", "HostNode"}
    return sorted(names)


def attribution_coverage_gaps() -> List[str]:
    """Registered exec classes in neither ATTRIBUTION_COVERED nor
    ATTRIBUTION_EXEMPT — must be [] (tier-1 lint via check_docs)."""
    known = ATTRIBUTION_COVERED | set(ATTRIBUTION_EXEMPT)
    return [n for n in registered_exec_classes() if n not in known]


# ---------------------------------------------------------------------------
# The EXPLAIN ANALYZE report
# ---------------------------------------------------------------------------

#: |log2(time share / flop share)| beyond which a segment is flagged as
#: predicted-vs-actual skewed (possible mis-fusion / padding blowup)
_SKEW_LOG2 = 2.0


@dataclasses.dataclass
class ExplainAnalyzeReport:
    """One profiled execution's attribution: the annotated plan tree
    plus the structured tables behind it."""
    tree: str                       # rendered annotated plan tree
    segments: List[Dict[str, Any]]
    attributed_pct: Optional[float]  # 0..100, None when not measurable
    wall_ms: float
    device_ms: float                # union of measured execute spans
    gathers: Dict[str, int]         # gather volume delta over the run
    mesh_timeline: Dict[str, Any]
    metrics: Dict[str, Any]
    profile: object                 # the QueryProfile
    #: the memory-attribution view (obs/memattr.py): measured query
    #: peak, sum of per-segment HBM peaks and the attributed fraction
    #: (the acceptance bar: summed segment peaks account for >=90% of
    #: the measured peak); {} when the plane was off
    hbm: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: admission-style cost-oracle estimate taken BEFORE the profiled
    #: run (obs/estimator.py) — the predicted column next to measured;
    #: None when the history plane is off
    predicted: Optional[Dict[str, Any]] = None
    #: out-of-core tier activity of the profiled run (exec/ooc.py):
    #: per-op election/partition/byte/recursion counters from
    #: ctx.metrics `ooc.*` entries; {} when the tier never engaged
    ooc: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: the wall-decomposition plane (QueryProfile.wall_breakdown): the
    #: end-to-end wall split into named categories — device compute,
    #: dispatch floor, seam time, compile, fetch, host prep — with an
    #: unattributed residual and the pad-waste overlay; {} when the
    #: profile carried no query span
    wall_breakdown: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    #: attributed_wall_pct over the FULL query span (0..100) — the
    #: honest bar next to attributed_pct's execute-span-only view
    attributed_wall_pct: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        return {"tree": self.tree, "segments": self.segments,
                "attributed_device_pct": self.attributed_pct,
                "attributed_wall_pct": self.attributed_wall_pct,
                "wall_ms": self.wall_ms, "device_ms": self.device_ms,
                "wall_breakdown": self.wall_breakdown,
                "gathers": self.gathers,
                "mesh_timeline": self.mesh_timeline,
                "predicted": self.predicted,
                "ooc": self.ooc,
                "hbm": self.hbm}

    def render(self) -> str:
        head = [f"== EXPLAIN ANALYZE ==",
                f"query wall        {self.wall_ms:.1f} ms",
                f"device wall       {self.device_ms:.1f} ms (measured, "
                f"union of program executions)"]
        if self.predicted:
            p = self.predicted
            head.append(
                f"predicted device  {p['device_us'] / 1e3:.1f} ms "
                f"(basis={p['basis']}, confidence="
                f"{p.get('confidence', 0)}, runs={p.get('runs', 0)} — "
                f"the history oracle's admission-time answer)")
        if self.attributed_pct is not None:
            head.append(f"attributed        {self.attributed_pct:.1f}% "
                        f"of device wall to named plan segments")
        if self.attributed_wall_pct is not None:
            head.append(f"attributed (wall) {self.attributed_wall_pct:.1f}"
                        f"% of end-to-end wall to named categories")
        if self.predicted and self.predicted.get("overhead_us"):
            ov_ms = self.predicted["overhead_us"] / 1e3
            head.append(f"predicted overhead {ov_ms:.2f} ms "
                        f"(dispatch+seam+pad, history oracle)")
        if self.wall_breakdown:
            from .profile import render_wall_breakdown
            head.extend(render_wall_breakdown(self.wall_breakdown))
        if self.hbm.get("measured_peak_bytes"):
            h = self.hbm
            head.append(
                f"hbm peak          {h['measured_peak_bytes']} bytes "
                f"measured (segment peaks sum "
                f"{h.get('segment_sum_bytes', 0)}, "
                f"{h.get('attributed_pct', 0):.1f}% attributed)")
        if self.ooc:
            o = self.ooc
            parts = []
            for op in ("join", "agg", "sort"):
                if o.get(f"{op}_elections") or o.get(f"{op}_partitions"):
                    s = f"{op} k={o.get(f'{op}_partitions', 0)}"
                    if o.get(f"{op}_bytes"):
                        s += f" spilled={o[f'{op}_bytes']}B"
                    if o.get(f"{op}_recursions"):
                        s += f" recursions={o[f'{op}_recursions']}"
                    parts.append(s)
            if o.get("query_elections"):
                parts.append("query-escalated")
            head.append("ooc               " + "; ".join(parts) +
                        " (budget-driven out-of-core tier)")
        if self.gathers.get("gather_bytes"):
            head.append(f"gather volume     "
                        f"{self.gathers['gather_bytes']} bytes / "
                        f"{self.gathers.get('gather_rows', 0)} row-gathers"
                        + (f" ({self.gathers['deferred_gathers']} deferred)"
                           if self.gathers.get("deferred_gathers")
                           else ""))
        out = "\n".join(head) + "\n" + self.tree
        mesh = self.mesh_timeline
        if mesh.get("exchanges"):
            lines = ["-- mesh timeline --"]
            for ex in mesh["exchanges"]:
                if ex.get("kind") == "dict_gather":
                    lines.append(f"  dict_gather bytes="
                                 f"{ex.get('bytes', 0)}")
                    continue
                lines.append(
                    f"  exchange rounds={ex.get('rounds', 0)} "
                    f"quota={ex.get('quota', 0)} "
                    f"wire={ex.get('bytes', 0)}B "
                    f"(pre-compress {ex.get('bytes_pre_compress', 0)}B) "
                    f"stage={ex.get('stage_ms_total', 0)}ms "
                    f"collective={ex.get('collective_ms_total', 0)}ms "
                    f"arrivals={ex.get('arrivals', '?')}")
            if mesh.get("skew_splits"):
                lines.append(f"  skew splits: {len(mesh['skew_splits'])}")
            out += "\n" + "\n".join(lines)
        return out

    def __str__(self) -> str:
        return self.render()


def _flag_skew(segments: List[Dict[str, Any]]) -> None:
    """Predicted-vs-actual overlay: a segment whose share of measured
    device time is wildly off its share of static FLOPs gets flagged —
    the mis-fused / padding-bound smell explain_analyze exists to
    surface."""
    import math
    with_flops = [s for s in segments if s.get("flops")]
    tot_ms = sum(s.get("device_ms", 0.0) for s in with_flops)
    tot_fl = sum(s["flops"] for s in with_flops)
    if len(with_flops) < 2 or not tot_ms or not tot_fl:
        return
    for s in with_flops:
        ms_share = s.get("device_ms", 0.0) / tot_ms
        fl_share = s["flops"] / tot_fl
        if not ms_share or not fl_share:
            continue
        ratio = ms_share / fl_share
        if abs(math.log2(ratio)) >= _SKEW_LOG2:
            s["cost_skew"] = round(ratio, 2)


def _render_tree(root, metrics: Dict[str, Any],
                 seg_by_node: Dict[str, Dict[str, Any]],
                 wall_ms: float,
                 pred_segments: Optional[Dict[str, float]] = None) -> str:
    """The annotated physical tree: every node with its measured per-node
    metrics, segment anchors with device time / % of wall / rows /
    bytes / static cost / predicted-from-history ms."""
    from ..exec.metrics import _child_nodes
    pred_segments = pred_segments or {}
    lines: List[str] = []

    def annotate(n) -> str:
        nid = getattr(n, "_node_id", None) or type(n).__name__
        parts = [nid]
        seg = seg_by_node.get(nid)
        if seg is not None:
            rng = ""
            if seg.get("node_lo") is not None:
                rng = f" nodes #{seg['node_lo']}-#{seg.get('node_hi')}"
            s = (f"<segment{rng}: {seg['device_ms']:.1f} ms device"
                 f" ({seg['pct']:.1f}%)")
            if nid in pred_segments:
                s += f", pred={pred_segments[nid]:.1f} ms"
            if seg.get("rows"):
                s += f", rows={seg['rows']}"
            if seg.get("out_bytes"):
                s += f", bytes={seg['out_bytes']}"
            if seg.get("hbm_peak_bytes"):
                # the memory-attribution column: this segment's
                # measured HBM working set; the largest one carries
                # the query's peak flag
                s += f", hbm={int(seg['hbm_peak_bytes'])}"
                if seg.get("hbm_peak_segment"):
                    s += " <-- hbm peak"
            cost = []
            if seg.get("flops"):
                cost.append(f"flops={seg['flops']:.3g}")
            if seg.get("bytes_accessed"):
                cost.append(f"bytes_accessed={seg['bytes_accessed']:.3g}")
            if seg.get("peak_temp_bytes"):
                cost.append(f"peak_temp={seg['peak_temp_bytes']:.3g}")
            if cost:
                s += " | " + " ".join(cost)
            if seg.get("cost_skew"):
                s += (f" | SKEW x{seg['cost_skew']:g} vs predicted "
                      f"(mis-fused?)")
            parts.append(s + ">")
        op_ms = metrics.get(f"{nid}.op_time_ms")
        rows = metrics.get(f"{nid}.output_rows")
        ann = []
        if op_ms is not None:
            ann.append(f"op {float(op_ms):.1f} ms")
            if wall_ms:
                ann.append(f"{100.0 * float(op_ms) / wall_ms:.1f}% of wall")
        if rows is not None:
            ann.append(f"rows={int(rows)}")
        if ann:
            parts.append("[" + ", ".join(ann) + "]")
        return "  ".join(parts)

    def walk(n, depth):
        lines.append("  " * depth + annotate(n))
        for c in _child_nodes(n):
            walk(c, depth + 1)

    walk(root, 0)
    return "\n".join(lines)


def run_explain_analyze(pq, conf_overrides: Optional[dict] = None
                        ) -> ExplainAnalyzeReport:
    """Execute one PROFILED collect of a PhysicalQuery and build the
    attribution report.  The profiled run uses a fresh plan holder so
    whole-plan programs re-split at the known seam boundaries
    (profile.segments) without disturbing the caller's cached plan."""
    from ..config import PROFILE_SEGMENTS, TRACE_ENABLED, TpuConf
    from ..exec.metrics import assign_node_ids
    from ..exec.plan import ExecContext
    from ..obs.profile import QueryProfile
    from ..obs.registry import DEFERRED_GATHERS, GATHER_BYTES, GATHER_ROWS
    from ..plan.overrides import PhysicalQuery

    raw = dict(pq.conf._raw)
    raw[TRACE_ENABLED.key] = True
    raw[PROFILE_SEGMENTS.key] = True
    for k, v in (conf_overrides or {}).items():
        raw[getattr(k, "key", k)] = v
    prof_conf = TpuConf(raw)
    assign_node_ids(pq.root)

    # the history oracle's admission-time answer, taken BEFORE the run
    # so the report shows prediction next to what actually happened
    predicted = None
    try:
        from .estimator import estimate_query
        predicted = estimate_query(pq)
    except Exception:                        # noqa: BLE001
        predicted = None

    def _gather_totals() -> Dict[str, int]:
        out = {}
        for name, fam in (("gather_rows", GATHER_ROWS),
                          ("gather_bytes", GATHER_BYTES),
                          ("deferred_gathers", DEFERRED_GATHERS)):
            out[name] = int(sum(s["value"] for s in fam.series()))
        return out

    q = PhysicalQuery(pq.meta, pq.kind, pq.root, prof_conf)
    q.plan_phases = list(pq.plan_phases)
    ctx = ExecContext(prof_conf)
    g0 = _gather_totals()
    q.collect(ctx)
    g1 = _gather_totals()
    gathers = {k: g1[k] - g0[k] for k in g1 if g1[k] - g0[k]}

    profile = QueryProfile.from_context(ctx)
    segments = profile.segments()
    _flag_skew(segments)
    # memory attribution (obs/memattr.py): flag the peak segment and
    # compute the acceptance ratio — summed per-segment HBM peaks vs
    # the query's measured peak (resident + in-flight program)
    hbm: Dict[str, Any] = {}
    with_hbm = [s for s in segments if s.get("hbm_peak_bytes")]
    if with_hbm:
        max(with_hbm,
            key=lambda s: s["hbm_peak_bytes"])["hbm_peak_segment"] = True
        seg_sum = int(sum(s["hbm_peak_bytes"] for s in with_hbm))
        measured = int(ctx.metrics.get("memory.hbm_measured_working_set")
                       or 0)
        measured = max(measured,
                       int(ctx.metrics.get("memory.peak_bytes") or 0))
        hbm = {"measured_peak_bytes": measured,
               "segment_sum_bytes": seg_sum,
               "attributed_pct": round(
                   min(seg_sum / measured, 1.0) * 100, 1)
               if measured else 0.0}
    seg_by_node = {s["node"]: s for s in segments}
    split = profile.time_split()
    from ..obs.profile import _union_ms
    device_ms = _union_ms([(s.t0, s.t1) for s in profile.spans
                           if s.cat == "execute"])
    pct = profile.attributed_device_pct()
    pred_segments = {}
    if predicted:
        pred_segments = {n: float(v) for n, v in
                         (predicted.get("segments") or {}).items()}
    tree = _render_tree(pq.root, ctx.metrics, seg_by_node,
                        split["wall_ms"], pred_segments=pred_segments)
    # out-of-core tier activity: the ctx.metrics `ooc.*` counters the
    # operators bump (exec/ooc.py) plus the query-rung escalation count
    ooc = {k[len("ooc."):]: v for k, v in ctx.metrics.items()
           if k.startswith("ooc.") and v}
    breakdown = profile.wall_breakdown()
    wpct = profile.attributed_wall_pct()
    return ExplainAnalyzeReport(
        tree=tree, segments=segments,
        attributed_pct=None if pct is None else round(pct * 100, 1),
        wall_ms=split["wall_ms"], device_ms=round(device_ms, 3),
        gathers=gathers, mesh_timeline=profile.mesh_timeline(),
        metrics=dict(ctx.metrics), profile=profile,
        predicted=predicted, hbm=hbm, ooc=ooc,
        wall_breakdown=breakdown if breakdown.get("wall_ms") else {},
        attributed_wall_pct=None if wpct is None
        else round(wpct * 100, 1))
