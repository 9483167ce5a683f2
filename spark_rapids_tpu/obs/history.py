"""Persistent performance history — the structure-keyed cost substrate.

PR 9's attribution plane measures per-segment device time, but every
measurement dies with the process.  ROADMAP 3(a) (predictive, SLA-aware
admission) and 5 (adaptive replanning) both need the opposite: a
*persistent*, structure-keyed history of measured device time the engine
can consult BEFORE running a query — the measured-cost feedback loop
that lets a scheduler place queries by predicted cost instead of
arrival order ("Accelerating Presto with GPUs", PAPERS.md) and schedule
for data movement rather than per-query wall (Theseus, PAPERS.md).

This module is that substrate:

  * `history_key(pq)` — the canonical identity of a query's *work*:
    PR 7's constant-lifted `plan_structure_key` (literal values
    erased, resolved encoding discriminant included) plus the leaf
    shape bucket, with observability-only conf keys (trace, eventLog,
    profile, metrics, history, serving, test) FILTERED OUT so an
    EXPLAIN ANALYZE run, a serving admission and a plain collect of the
    same query all share one history line.  Host-engine plans (no
    canonical key) fall back to a physical-tree digest.
  * `PerfHistoryStore` — a process-wide, on-disk JSONL store under
    `spark.rapids.tpu.history.dir`: one append per completed query
    (measured device wall, per-segment device ms, rows/bytes at seams,
    peak HBM reservation, compile ms), folded into per-structure
    DECAY-WEIGHTED aggregates in memory.  Loads tolerate corrupt or
    truncated lines exactly like `read_event_log` (the intact prefix
    wins; damage is counted, never fatal).  The file is byte/entry
    capped: past `history.maxBytes`/`history.maxEntries` the store
    COMPACTS — aggregates replace raw records and least-recently
    updated structures drop first (LRU) — via an atomic tmp+rename.
  * calibration state — when a record carries an admission-time
    prediction (serving stamps one), the store folds the
    prediction-vs-actual ratio into per-basis calibration curves and
    the `tpu_history_prediction_error_ratio` histogram, so the oracle
    reports how wrong it currently is (`scripts/history_report.py`
    renders the curve; drift >2x from a structure's own history is the
    regression-triage entry point).

Feeding is automatic (exec/metrics.record_history at query end, inside
the crash-capture scope so the `history` chaos site's fatal kind dumps
classified) and near-free when disabled: `get_store(conf)` caches None
on the conf instance, one dict hit per query.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..config import (HISTORY_DECAY, HISTORY_DIR, HISTORY_MAX_BYTES,
                      HISTORY_MAX_ENTRIES, TpuConf)

#: conf keys that change observability, not the traced program — erased
#: from the history key so profiled/traced/serving runs of one query
#: share a single history line with its plain collects
_KEY_NEUTRAL_PREFIXES = (
    "spark.rapids.tpu.trace.",
    "spark.rapids.tpu.eventLog.",
    "spark.rapids.tpu.profile.",
    "spark.rapids.tpu.metrics.",
    "spark.rapids.tpu.history.",
    "spark.rapids.tpu.serving.",
    "spark.rapids.tpu.test.",
    "spark.rapids.tpu.coredump.",
    "spark.rapids.tpu.compile.cacheDir",
)

#: on-disk file name inside spark.rapids.tpu.history.dir
HISTORY_FILE = "perf_history.jsonl"


def _neutral_conf(conf: TpuConf) -> TpuConf:
    raw = {k: v for k, v in conf._raw.items()
           if not k.startswith(_KEY_NEUTRAL_PREFIXES)}
    return TpuConf(raw)


def history_key(pq) -> Optional[str]:
    """Stable 16-hex structure digest of a PhysicalQuery, cached on the
    holder.  None only when the plan cannot be keyed at all."""
    key = pq.__dict__.get("_history_key", False)
    if key is not False:
        return key
    key = compute_history_key(pq.root, pq.conf, pq.kind)
    pq.__dict__["_history_key"] = key
    return key


def compute_history_key(root, conf: TpuConf, kind: str) -> Optional[str]:
    """The structure digest for one physical root: canonical
    plan_structure_key (encoding discriminant included) + leaf shape
    bucket for device plans; a physical-tree digest for host plans."""
    neutral = _neutral_conf(conf)
    parts: List[Any] = [kind]
    skey = None
    if kind == "device":
        try:
            from ..exec.compiled import (_max_leaf_capacity,
                                         plan_structure_key)
            skey = plan_structure_key(root, neutral)
            parts.append(_max_leaf_capacity(root, neutral))
        except Exception:                    # noqa: BLE001
            skey = None
    if skey is not None:
        parts.append(skey)
    else:
        # host engine / uncovered node class: the physical tree is the
        # best stable identity available (literals included)
        try:
            import jax
            parts.append(("tree", root.tree_string(),
                          jax.default_backend(),
                          tuple(sorted((k, str(v))
                                       for k, v in neutral._raw.items()))))
        except Exception:                    # noqa: BLE001
            return None
    return hashlib.sha256(repr(tuple(parts)).encode()).hexdigest()[:16]


def _is_warm(rec: dict) -> bool:
    """A recorded run is WARM when it paid no meaningful compile: cold
    runs carry first-touch costs (XLA compile, first upload, helper-jit
    warmup) that would poison a warm-cost prediction — the oracle
    predicts warm device time and reports compile separately."""
    compile_ms = float(rec.get("compile_ms") or 0.0)
    wall_ms = float(rec.get("wall_ms") or 0.0)
    return compile_ms < max(1.0, 0.05 * wall_ms)


class _Agg:
    """Decay-weighted aggregate of one structure's recorded executions.

    Two device-time tracks: `device_us` folds EVERY run (report
    ranking, the only signal while a structure has never run warm) and
    `warm_device_us` folds only warm runs (`_is_warm`) — the value the
    estimator serves and the drift detector watches, so a process
    restart's cold run can neither inflate predictions nor fake a
    regression."""

    __slots__ = ("runs", "warm_runs", "last_ts", "device_us",
                 "warm_device_us", "prev_warm_us", "last_warm_us",
                 "wall_ms", "compile_ms", "src_bytes", "peak_bytes",
                 "ws_bytes", "ws_runs",
                 "overhead_us", "overhead_runs", "seam_count",
                 "seam_ms", "dispatch_floor_ms",
                 "total_device_us", "segments", "label", "kind",
                 "backend")

    def __init__(self):
        self.runs = 0
        self.warm_runs = 0
        self.last_ts = 0.0
        self.device_us = 0.0        # decayed, all runs
        self.warm_device_us = 0.0   # decayed, warm runs only
        self.prev_warm_us = 0.0     # warm ewma BEFORE the last warm fold
        self.last_warm_us = 0.0     # newest raw warm observation
        self.wall_ms = 0.0
        self.compile_ms = 0.0       # decayed over COLD runs (compile cost)
        self.src_bytes = 0.0
        self.peak_bytes = 0.0
        self.ws_bytes = 0.0         # decayed MEASURED working set
        self.ws_runs = 0            # runs that carried one (memattr /
                                    # XLA memory_analysis — not the
                                    # source-bytes heuristic)
        # the overhead plane (wall decomposition, exec/compiled.py):
        # decayed dispatch+seam+pad overhead of runs that measured it,
        # plus the structure's seam shape and the backend's measured
        # per-dispatch floor — the small-plan fast-path admission signal
        self.overhead_us = 0.0      # decayed, measured runs only
        self.overhead_runs = 0
        self.seam_count = 0         # newest observed seam count
        self.seam_ms = 0.0          # decayed seam wall
        self.dispatch_floor_ms = 0.0  # newest measured backend floor
        self.total_device_us = 0.0  # lifetime sum (report ranking)
        self.segments: Dict[str, float] = {}   # node -> decayed device ms
        self.label: Optional[str] = None
        self.kind: Optional[str] = None
        self.backend: Optional[str] = None

    @staticmethod
    def _ewma(cur: float, obs: float, first: bool, d: float) -> float:
        return obs if first else cur + d * (obs - cur)

    def fold(self, rec: dict, decay: float) -> None:
        dus = float(rec.get("device_us") or 0.0)
        self.total_device_us += dus
        self.device_us = self._ewma(self.device_us, dus,
                                    self.runs == 0, decay)
        self.wall_ms = self._ewma(self.wall_ms,
                                  float(rec.get("wall_ms") or 0.0),
                                  self.runs == 0, decay)
        self.src_bytes = self._ewma(self.src_bytes,
                                    float(rec.get("src_bytes") or 0.0),
                                    self.runs == 0, decay)
        self.peak_bytes = self._ewma(self.peak_bytes,
                                     float(rec.get("peak_bytes") or 0.0),
                                     self.runs == 0, decay)
        ws = float(rec.get("ws_bytes") or 0.0)
        if ws > 0:
            self.ws_bytes = self._ewma(self.ws_bytes, ws,
                                       self.ws_runs == 0, decay)
            self.ws_runs += 1
        ov = float(rec.get("overhead_us") or 0.0)
        if ov > 0:
            self.overhead_us = self._ewma(self.overhead_us, ov,
                                          self.overhead_runs == 0, decay)
            self.overhead_runs += 1
        if rec.get("seam_count"):
            self.seam_count = int(rec["seam_count"])
            self.seam_ms = self._ewma(self.seam_ms,
                                      float(rec.get("seam_ms") or 0.0),
                                      self.seam_ms == 0.0, decay)
        if rec.get("dispatch_floor_ms"):
            self.dispatch_floor_ms = float(rec["dispatch_floor_ms"])
        if _is_warm(rec):
            self.prev_warm_us = self.warm_device_us
            self.last_warm_us = dus
            self.warm_device_us = self._ewma(self.warm_device_us, dus,
                                             self.warm_runs == 0, decay)
            self.warm_runs += 1
        else:
            cms = float(rec.get("compile_ms") or 0.0)
            self.compile_ms = self._ewma(self.compile_ms, cms,
                                         self.compile_ms == 0.0, decay)
        for node, ms in (rec.get("segments") or {}).items():
            try:
                ms = float(ms)
            except (TypeError, ValueError):
                continue
            cur = self.segments.get(node)
            self.segments[node] = ms if cur is None \
                else cur + decay * (ms - cur)
        self.runs += 1
        self.last_ts = float(rec.get("ts") or time.time())
        if rec.get("label"):
            self.label = str(rec["label"])
        if rec.get("kind"):
            self.kind = str(rec["kind"])
        if rec.get("backend"):
            self.backend = str(rec["backend"])

    def predicted_us(self) -> float:
        """The device-us the oracle serves: warm history when any warm
        run exists, else the all-runs decayed value."""
        return self.warm_device_us if self.warm_runs > 0 \
            else self.device_us

    def drift_ratio(self) -> Optional[float]:
        """Newest WARM observation vs the warm history it arrived into
        (>1 = slower than its history).  None below 3 warm runs — cold
        restarts and first measurements are expected, not drift."""
        if self.warm_runs < 3 or self.prev_warm_us <= 0:
            return None
        return self.last_warm_us / self.prev_warm_us

    def to_dict(self) -> dict:
        out = {"runs": self.runs, "warm_runs": self.warm_runs,
               "last_ts": round(self.last_ts, 3),
               "device_us": round(self.device_us, 1),
               "warm_device_us": round(self.warm_device_us, 1),
               "prev_warm_us": round(self.prev_warm_us, 1),
               "last_warm_us": round(self.last_warm_us, 1),
               "wall_ms": round(self.wall_ms, 3),
               "compile_ms": round(self.compile_ms, 3),
               "src_bytes": round(self.src_bytes, 1),
               "peak_bytes": round(self.peak_bytes, 1),
               "ws_bytes": round(self.ws_bytes, 1),
               "ws_runs": self.ws_runs,
               "overhead_us": round(self.overhead_us, 1),
               "overhead_runs": self.overhead_runs,
               "seam_count": self.seam_count,
               "seam_ms": round(self.seam_ms, 3),
               "dispatch_floor_ms": round(self.dispatch_floor_ms, 4),
               "total_device_us": round(self.total_device_us, 1),
               "segments": {n: round(v, 3)
                            for n, v in self.segments.items()}}
        for k in ("label", "kind", "backend"):
            v = getattr(self, k)
            if v:
                out[k] = v
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "_Agg":
        a = cls()
        a.runs = int(d.get("runs") or 0)
        a.warm_runs = int(d.get("warm_runs") or 0)
        a.last_ts = float(d.get("last_ts") or 0.0)
        a.device_us = float(d.get("device_us") or 0.0)
        a.warm_device_us = float(d.get("warm_device_us") or 0.0)
        a.prev_warm_us = float(d.get("prev_warm_us") or a.warm_device_us)
        a.last_warm_us = float(d.get("last_warm_us") or a.warm_device_us)
        a.wall_ms = float(d.get("wall_ms") or 0.0)
        a.compile_ms = float(d.get("compile_ms") or 0.0)
        a.src_bytes = float(d.get("src_bytes") or 0.0)
        a.peak_bytes = float(d.get("peak_bytes") or 0.0)
        a.ws_bytes = float(d.get("ws_bytes") or 0.0)
        a.ws_runs = int(d.get("ws_runs") or 0)
        a.overhead_us = float(d.get("overhead_us") or 0.0)
        a.overhead_runs = int(d.get("overhead_runs") or 0)
        a.seam_count = int(d.get("seam_count") or 0)
        a.seam_ms = float(d.get("seam_ms") or 0.0)
        a.dispatch_floor_ms = float(d.get("dispatch_floor_ms") or 0.0)
        a.total_device_us = float(d.get("total_device_us")
                                  or a.device_us * a.runs)
        a.segments = {str(n): float(v)
                      for n, v in (d.get("segments") or {}).items()}
        a.label = d.get("label")
        a.kind = d.get("kind")
        a.backend = d.get("backend")
        return a


class PerfHistoryStore:
    """One on-disk history file + its in-memory aggregates.

    Thread-safe (the serving plane records from many worker threads);
    process-wide per directory (`get_store`), so hit counters and decay
    state are shared by every conf pointing at the same dir."""

    def __init__(self, path: str, max_bytes: int = 16 << 20,
                 max_entries: int = 4096, decay: float = 0.3):
        self.path = path
        self.max_bytes = int(max_bytes)
        self.max_entries = int(max_entries)
        self.decay = float(decay)
        self._lock = threading.RLock()
        #: key -> _Agg; insertion order IS the LRU order (folds re-insert)
        self._aggs: Dict[str, _Agg] = {}
        #: per-basis calibration: {"n", "sum_ratio", "buckets": {le: n}}
        self._calib: Dict[str, dict] = {}
        #: reservation-vs-actual WORKING-SET calibration, same shape —
        #: how far admission's working_set_bytes predictions land from
        #: the measured HBM footprint (tpu_hbm_prediction_error_ratio)
        self._calib_ws: Dict[str, dict] = {}
        self.corrupt_lines = 0
        self.loaded_records = 0          # raw records replayed from disk
        self.recorded = 0                # records appended live
        self.compactions = 0
        #: continuously-fitted static-cost coefficient (decayed us/byte
        #: over every record with source bytes) — the scale factor the
        #: estimator's static_cost fallback uses for never-seen plans
        self.us_per_byte: Optional[float] = None
        self._fit_n = 0
        self._load()

    # -- load --------------------------------------------------------------
    def _load(self) -> None:
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            return
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                # crash-truncated tails and damaged lines are tolerated
                # (the read_event_log contract): the intact records win
                self.corrupt_lines += 1
                continue
            if not isinstance(rec, dict):
                self.corrupt_lines += 1
                continue
            self._apply(rec)

    def _apply(self, rec: dict) -> None:
        key = rec.get("k")
        if rec.get("fit"):
            fit = rec["fit"]
            if fit.get("us_per_byte"):
                self.us_per_byte = float(fit["us_per_byte"])
                self._fit_n = int(fit.get("n") or 1)
            return
        for field, target in (("calib", self._calib),
                              ("calib_ws", self._calib_ws)):
            if rec.get(field):
                for basis, c in rec[field].items():
                    target[basis] = {
                        "n": int(c.get("n") or 0),
                        "sum_ratio": float(c.get("sum_ratio") or 0.0),
                        "buckets": {int(k): int(v) for k, v in
                                    (c.get("buckets") or {}).items()}}
                return
        if not key:
            return
        if rec.get("agg"):
            # compaction summary: seeds (or replaces) the aggregate
            self._aggs.pop(key, None)
            self._aggs[key] = _Agg.from_dict(rec["agg"])
            return
        agg = self._aggs.pop(key, None)
        if agg is None:
            agg = _Agg()
        agg.fold(rec, self.decay)
        self._aggs[key] = agg                # re-insert: now MRU
        self.loaded_records += 1
        self._fit(rec)
        self._calibrate(rec)

    # -- calibration + static-coefficient fitting --------------------------
    def _fit(self, rec: dict) -> None:
        src = float(rec.get("src_bytes") or 0.0)
        dus = float(rec.get("device_us") or 0.0)
        if src <= 0 or dus <= 0 or not _is_warm(rec):
            return                 # cold runs would inflate the coefficient
        obs = dus / src
        if self.us_per_byte is None:
            self.us_per_byte = obs
        else:
            self.us_per_byte += self.decay * (obs - self.us_per_byte)
        self._fit_n += 1

    def _calibrate(self, rec: dict) -> None:
        from .registry import (HBM_PREDICTION_ERROR,
                               HISTORY_PREDICTION_ERROR, bucket_index)
        basis = str(rec.get("basis") or "?")
        pred = rec.get("predicted_us")
        dus = float(rec.get("device_us") or 0.0)
        if pred and float(pred) > 0 and dus > 0:
            pred = float(pred)
            ratio = max(pred, dus) / min(pred, dus)
            c = self._calib.setdefault(
                basis, {"n": 0, "sum_ratio": 0.0, "buckets": {}})
            c["n"] += 1
            c["sum_ratio"] += ratio
            b = bucket_index(ratio)
            c["buckets"][b] = c["buckets"].get(b, 0) + 1
            HISTORY_PREDICTION_ERROR.observe(ratio, basis=basis)
        # reservation-vs-actual: admission's working-set prediction vs
        # the run's measured HBM footprint (the curve that tells the
        # serving gate how much to trust the oracle's bytes)
        pred_ws = rec.get("predicted_ws")
        meas_ws = float(rec.get("ws_bytes") or rec.get("peak_bytes")
                        or 0.0)
        if pred_ws and float(pred_ws) > 0 and meas_ws > 0:
            pred_ws = float(pred_ws)
            ratio = max(pred_ws, meas_ws) / min(pred_ws, meas_ws)
            ws_basis = str(rec.get("ws_pred_basis") or basis)
            c = self._calib_ws.setdefault(
                ws_basis, {"n": 0, "sum_ratio": 0.0, "buckets": {}})
            c["n"] += 1
            c["sum_ratio"] += ratio
            b = bucket_index(ratio)
            c["buckets"][b] = c["buckets"].get(b, 0) + 1
            HBM_PREDICTION_ERROR.observe(ratio, basis=ws_basis)

    # -- record ------------------------------------------------------------
    def record(self, key: str, rec: dict, conf: Optional[TpuConf] = None
               ) -> bool:
        """Append one execution record and fold it into the aggregates.
        Returns False (entry SKIPPED, store unchanged) on any write
        failure — a history IO problem must never affect the query.
        The `history` chaos site fires on the write path; its `fatal`
        kind propagates (classified upstream), `ioerror` is the skip."""
        from .registry import HISTORY_RECORDS
        rec = {"k": key, "ts": rec.get("ts") or time.time(), **rec}
        line = json.dumps(rec, default=str)
        with self._lock:
            try:
                if conf is not None:
                    from ..runtime.faults import get_injector
                    get_injector(conf).fire("history", path=self.path)
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            except OSError:
                HISTORY_RECORDS.inc(outcome="io_error")
                return False
            self._apply(rec)
            self.loaded_records -= 1         # _apply counted it as loaded
            self.recorded += 1
            HISTORY_RECORDS.inc(outcome="ok")
            self._maybe_compact()
        return True

    def record_query(self, pq, ctx, wall_ms: float) -> None:
        """Build + record one completed query's observation from its
        ExecContext — the automatic feed (exec/metrics.record_history).
        Only host numbers are read (lazy device metrics are skipped)."""
        from .registry import HISTORY_RECORDS
        key = history_key(pq)
        if key is None:
            HISTORY_RECORDS.inc(outcome="unkeyed")
            return
        m = ctx.metrics

        def num(name, default=0.0):
            v = m.get(name, default)
            return float(v) if isinstance(v, (int, float)) \
                and not isinstance(v, bool) else default

        compile_ms = num("compile_ms")
        # the measured device-side wall this structure cost: the query
        # wall net of compile, floored by the accumulated program
        # dispatch wall (exec/compiled.py exec_device_ms — exact when
        # profiling syncs, the dispatch floor otherwise)
        device_ms = max(wall_ms - compile_ms, num("exec_device_ms"), 1e-3)
        segments: Dict[str, dict] = {}
        import re
        seg_re = re.compile(r"^segment\.(?P<node>[\w#]+)\."
                            r"(?P<field>device_ms|rows|out_bytes)$")
        for k, v in m.items():
            sm = seg_re.match(k)
            if sm and isinstance(v, (int, float)):
                segments.setdefault(sm.group("node"), {})[
                    sm.group("field")] = v
        rec = {"kind": pq.kind,
               "wall_ms": round(wall_ms, 3),
               "device_us": round(device_ms * 1e3, 1),
               "compile_ms": round(compile_ms, 3),
               "src_bytes": source_bytes(pq.root),
               "peak_bytes": _peak_bytes(ctx),
               "segments": {n: round(float(f.get("device_ms", 0.0)), 3)
                            for n, f in segments.items()}}
        # the MEASURED working set, when this run produced one: the
        # memattr query peak (profiled runs) or the XLA
        # memory_analysis floor (every compiled run) — max'd with the
        # budget peak so spill-leg reservations count too.  ws_basis
        # marks it measured, the estimator's trust discriminant.
        ws = max(num("memory.hbm_measured_working_set"),
                 num("exec_hbm_bytes"))
        if ws > 0:
            rec["ws_bytes"] = int(max(ws, num("memory.peak_bytes")))
            rec["ws_basis"] = "measured"
        seg_rows = {n: int(f["rows"]) for n, f in segments.items()
                    if isinstance(f.get("rows"), (int, float))}
        if seg_rows:
            rec["segment_rows"] = seg_rows
        # the overhead plane's loop-closer: this structure's measured
        # fixed-overhead tail (dispatch floor x launches + seam wall +
        # pad waste) so the estimator can serve overhead_us next to
        # device_us (the ROADMAP 1(b) fast-path admission signal).
        # seam_ms is always-on; dispatch/pad need a profiled run, but an
        # unprofiled run still prices its launches when the floor has
        # been measured in this process.
        floor = num("overhead.dispatch_floor_ms")
        if not floor:
            try:                             # already-measured cache only:
                import jax                   # never runs the microbench
                from ..exec.compiled import _DISPATCH_FLOOR
                floor = _DISPATCH_FLOOR.get(jax.default_backend(), 0.0)
            except Exception:                # noqa: BLE001
                floor = 0.0
        dispatch_ms = num("overhead.dispatch_ms")
        if not dispatch_ms and floor:
            dispatch_ms = floor * num("exec_dispatches")
        seam_ms = num("overhead.seam_ms")
        overhead_us = (dispatch_ms + seam_ms
                       + num("overhead.pad_waste_ms")) * 1e3
        if overhead_us > 0:
            rec["overhead_us"] = round(overhead_us, 1)
        if num("overhead.seam_count"):
            rec["seam_count"] = int(num("overhead.seam_count"))
            rec["seam_ms"] = round(seam_ms, 3)
        if floor:
            rec["dispatch_floor_ms"] = round(floor, 4)
        try:
            import jax
            rec["backend"] = jax.default_backend()
        except Exception:                    # noqa: BLE001
            pass
        label = m.get("history.label")
        if isinstance(label, str) and label:
            rec["label"] = label
        tenant = m.get("serving.tenant")
        if isinstance(tenant, str) and tenant:
            rec["tenant"] = tenant
        pred = m.get("predicted.device_us")
        if isinstance(pred, (int, float)) and pred > 0:
            rec["predicted_us"] = float(pred)
            rec["basis"] = str(m.get("predicted.basis") or "?")
        pred_ws = m.get("predicted.working_set_bytes")
        if isinstance(pred_ws, (int, float)) and pred_ws > 0:
            rec["predicted_ws"] = float(pred_ws)
            wb = m.get("predicted.ws_basis")
            if isinstance(wb, str) and wb:
                rec["ws_pred_basis"] = wb
        self.record(key, rec, conf=ctx.conf)

    # -- compaction --------------------------------------------------------
    def _maybe_compact(self) -> None:
        over_entries = len(self._aggs) > self.max_entries
        over_bytes = False
        if not over_entries:
            try:
                over_bytes = os.path.getsize(self.path) > self.max_bytes
            except OSError:
                pass
        if over_entries or over_bytes:
            self._compact()

    def checkpoint(self) -> None:
        """Durably checkpoint the store NOW: rewrite the file as one
        atomic aggregate summary (tmp + os.replace, same primitive the
        cap-driven compaction uses).  Graceful drain calls this in
        every serving worker and in the supervisor, so a restart/deploy
        loses no folded history even mid-append."""
        with self._lock:
            self._compact()

    def _compact(self) -> None:
        """Rewrite the file as one aggregate summary per kept structure
        (+ the fit/calibration state), dropping least-recently-updated
        structures past the entry cap and then past the byte cap —
        atomic tmp+rename, fail-soft (the next record retries)."""
        keys = list(self._aggs)              # insertion order = LRU
        if len(keys) > self.max_entries:
            for k in keys[:len(keys) - self.max_entries]:
                self._aggs.pop(k, None)
            keys = list(self._aggs)
        lines = []
        head = []
        if self.us_per_byte is not None:
            head.append(json.dumps(
                {"fit": {"us_per_byte": self.us_per_byte,
                         "n": self._fit_n}}))
        if self._calib:
            head.append(json.dumps({"calib": self._calib}, default=str))
        if self._calib_ws:
            head.append(json.dumps({"calib_ws": self._calib_ws},
                                   default=str))
        for k in keys:
            lines.append(json.dumps({"k": k,
                                     "agg": self._aggs[k].to_dict()}))
        total = sum(len(x) + 1 for x in head + lines)
        while lines and total > self.max_bytes:
            dropped = lines.pop(0)           # oldest (LRU) first
            total -= len(dropped) + 1
            self._aggs.pop(keys.pop(0), None)
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write("\n".join(head + lines)
                        + ("\n" if head or lines else ""))
            os.replace(tmp, self.path)
            self.compactions += 1
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass

    # -- read --------------------------------------------------------------
    def get(self, key: str) -> Optional[_Agg]:
        with self._lock:
            agg = self._aggs.pop(key, None)
            if agg is not None:
                self._aggs[key] = agg        # MRU touch
            return agg

    def aggregates(self) -> Dict[str, _Agg]:
        with self._lock:
            return dict(self._aggs)

    def calibration(self) -> Dict[str, dict]:
        """Per-basis calibration: {basis: {n, mean_ratio, buckets}}."""
        return self._render_calib(self._calib)

    def ws_calibration(self) -> Dict[str, dict]:
        """The reservation-vs-actual working-set curve: per basis, how
        far admission's predicted working_set_bytes landed from the
        measured HBM footprint (the offline
        tpu_hbm_prediction_error_ratio)."""
        return self._render_calib(self._calib_ws)

    def _render_calib(self, calib: Dict[str, dict]) -> Dict[str, dict]:
        with self._lock:
            out = {}
            for basis, c in calib.items():
                out[basis] = {
                    "n": c["n"],
                    "mean_ratio": round(c["sum_ratio"] / c["n"], 3)
                    if c["n"] else None,
                    "buckets": dict(sorted(c["buckets"].items()))}
            return out

    def drifted(self, threshold: float = 2.0) -> List[dict]:
        """Structures whose newest measurement shifted more than
        `threshold`x from their own decayed history (either direction;
        `slower=True` rows are the regression-triage entries)."""
        out = []
        with self._lock:
            items = list(self._aggs.items())
        for key, agg in items:
            r = agg.drift_ratio()
            if r is None:
                continue
            if r >= threshold or r <= 1.0 / threshold:
                out.append({"key": key, "label": agg.label,
                            "runs": agg.runs, "ratio": round(r, 3),
                            "slower": r >= threshold,
                            "history_us": round(agg.prev_warm_us, 1),
                            "last_us": round(agg.last_warm_us, 1)})
        return sorted(out, key=lambda d: -d["ratio"])

    def stats(self) -> dict:
        with self._lock:
            try:
                fsize = os.path.getsize(self.path)
            except OSError:
                fsize = 0
            return {"path": self.path,
                    "structures": len(self._aggs),
                    "records_loaded": self.loaded_records,
                    "records_appended": self.recorded,
                    "corrupt_lines": self.corrupt_lines,
                    "compactions": self.compactions,
                    "file_bytes": fsize,
                    "us_per_byte": round(self.us_per_byte, 6)
                    if self.us_per_byte else None,
                    "calibration": self.calibration(),
                    "ws_calibration": self.ws_calibration()}


def source_bytes(root) -> int:
    """Total host source-table bytes feeding a physical root (0 when
    none are discoverable) — the static working-set proxy."""
    total = 0
    stack, seen = [root], set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        tbl = getattr(n, "_source_table", None)
        if tbl is not None:
            try:
                total += int(tbl.nbytes)
            except Exception:                # noqa: BLE001
                pass
        stack.extend(getattr(n, "children", ()) or ())
        for attr in ("host_child", "device_child"):
            c = getattr(n, attr, None)
            if c is not None:
                stack.append(c)
    return total


def _peak_bytes(ctx) -> int:
    b = getattr(ctx, "_budget", None)
    if b is None:
        return 0
    try:
        return int(b.metrics.get("peak_bytes", 0) or 0)
    except Exception:                        # noqa: BLE001
        return 0


# ---------------------------------------------------------------------------
# The process-wide store registry
# ---------------------------------------------------------------------------

_STORES: Dict[str, PerfHistoryStore] = {}
_STORES_LOCK = threading.Lock()
_MISS = object()


def get_store(conf: TpuConf) -> Optional[PerfHistoryStore]:
    """The history store for this conf, or None when the plane is off
    (spark.rapids.tpu.history.dir unset).  Cached on the conf instance:
    the disabled path is one dict hit per query."""
    st = conf._cache.get("__history_store", _MISS)
    if st is not _MISS:
        return st
    d = str(conf.get(HISTORY_DIR) or "")
    if not d:
        conf._cache["__history_store"] = None
        return None
    path = os.path.join(d, HISTORY_FILE)
    with _STORES_LOCK:
        st = _STORES.get(path)
        if st is None:
            st = _STORES[path] = PerfHistoryStore(
                path,
                max_bytes=conf.get(HISTORY_MAX_BYTES),
                max_entries=conf.get(HISTORY_MAX_ENTRIES),
                decay=conf.get(HISTORY_DECAY))
    conf._cache["__history_store"] = st
    return st


def configure_history(conf: TpuConf) -> Optional[PerfHistoryStore]:
    """Session-init hook (TpuSession.__init__/set_conf): warms the
    store for a conf'd history dir so the first query pays no load."""
    return get_store(conf)
