"""CostEstimator: the admission-time cost oracle over the history store.

`estimate(pq)` answers, BEFORE a query runs, what it will cost:

    {device_us, overhead_us, wall_ms, compile_ms, working_set_bytes,
     confidence, basis, key, runs, segments}

`overhead_us` is the wall-decomposition plane's admission signal: the
structure's measured fixed-overhead tail (dispatch floor x launches +
seam wall + pad waste, obs/history.py overhead fields) — nonzero on
`exact_history` once a run has measured it (`overhead_basis:
"measured"`), always 0.0 with basis "none" for static answers.

Two bases, counted per call in `tpu_history_estimates_total`:

  * `exact_history` — the structure key (obs/history.py: PR 7 canonical
    plan structure + encoding policy + shape bucket) hit the persistent
    store: the answer is the structure's decay-weighted measured
    history, per-segment device ms included.  Confidence grows with
    run count and is cut when the structure's own newest measurement
    drifted >2x from its history (a drifting structure is exactly when
    the oracle should not be trusted blindly).
  * `static_cost` — never-seen structure: the static source-byte cost
    scaled by the store's continuously-fitted us-per-byte coefficient
    (decayed over every recorded execution), falling back to a
    documented default coefficient when the store is empty.  Never
    errors: a cold oracle answers with low confidence, it does not
    block admission.

The serving plane calls this at admission (serving/runtime.py), stamps
the prediction into the ticket / tracer / event log, and the eventual
execution record closes the loop: `tpu_history_prediction_error_ratio`
and the store's per-basis calibration curves report how wrong the
oracle currently is (`scripts/history_report.py`, `stats()`,
heartbeat, Prometheus).
"""
from __future__ import annotations

from typing import Dict, Optional

from ..config import SERVING_ADMIT_WORKING_SET_FACTOR
from .history import PerfHistoryStore, get_store, history_key, source_bytes

#: us/byte used by static_cost when the store has never measured
#: anything (a cold oracle): ~200 MB/s of device progress — deliberately
#: pessimistic so an uncalibrated admission over-reserves rather than
#: over-commits; one recorded run replaces it with the fitted value
DEFAULT_US_PER_BYTE = 5e-3

#: drift beyond which an exact-history estimate loses confidence
DRIFT_CUT = 2.0


class CostEstimator:
    def __init__(self, store: PerfHistoryStore):
        self.store = store

    def estimate(self, pq) -> Dict[str, object]:
        """The oracle's answer for one PhysicalQuery (see module doc)."""
        from .registry import HISTORY_ESTIMATES
        key = history_key(pq)
        agg = self.store.get(key) if key is not None else None
        if agg is not None and agg.runs > 0:
            out = self._from_history(key, agg, pq)
        else:
            out = self._static(key, pq)
        HISTORY_ESTIMATES.inc(basis=out["basis"])
        return out

    def _from_history(self, key, agg, pq) -> Dict[str, object]:
        # warm runs carry the trust: a history of only cold runs still
        # answers (better than static) but at half weight
        if agg.warm_runs > 0:
            confidence = min(1.0, agg.warm_runs / 4.0)
        else:
            confidence = min(0.5, agg.runs / 8.0)
        drift = agg.drift_ratio()
        if drift is not None and (drift >= DRIFT_CUT
                                  or drift <= 1.0 / DRIFT_CUT):
            confidence = min(confidence, 0.25)
        # working set: a MEASURED history (memattr query peaks / XLA
        # memory_analysis floors folded at record time) beats the
        # reserved-peak/source-bytes heuristic — ws_basis tells the
        # serving admission gate which one it is getting
        if agg.ws_runs > 0 and agg.ws_bytes > 0:
            ws = agg.ws_bytes
            ws_basis = "measured"
        else:
            ws = max(agg.peak_bytes, agg.src_bytes)
            ws_basis = "reserved"
        out = {"basis": "exact_history", "key": key,
               "device_us": max(round(agg.predicted_us(), 1), 1.0),
               "wall_ms": round(agg.wall_ms, 3),
               "compile_ms": round(agg.compile_ms, 3),
               "working_set_bytes": int(ws),
               "ws_basis": ws_basis,
               "confidence": round(confidence, 3),
               "runs": agg.runs, "warm_runs": agg.warm_runs,
               "drift_ratio": None if drift is None else round(drift, 3),
               "segments": dict(agg.segments)}
        # the wall-decomposition plane's admission signal (ROADMAP 1b):
        # this structure's measured fixed-overhead tail — dispatch floor
        # x launches + seam wall + pad waste — next to its device_us, so
        # a small-plan fast-path election can see a query that is mostly
        # overhead BEFORE running it.  overhead_basis marks it measured.
        out["overhead_us"] = round(agg.overhead_us, 1) \
            if agg.overhead_runs > 0 else 0.0
        out["overhead_basis"] = "measured" if agg.overhead_runs > 0 \
            else "none"
        if agg.seam_count:
            out["seam_count"] = agg.seam_count
            out["seam_ms"] = round(agg.seam_ms, 3)
        if agg.dispatch_floor_ms:
            out["dispatch_floor_ms"] = round(agg.dispatch_floor_ms, 4)
        return out

    def _static(self, key, pq) -> Dict[str, object]:
        src = source_bytes(pq.root)
        coef = self.store.us_per_byte
        fitted = coef is not None and coef > 0
        if not fitted:
            coef = DEFAULT_US_PER_BYTE
        ws_factor = float(pq.conf.get(SERVING_ADMIT_WORKING_SET_FACTOR))
        return {"basis": "static_cost", "key": key,
                "device_us": max(round(src * coef, 1), 1.0),
                "wall_ms": None,
                "compile_ms": None,
                "working_set_bytes": int(src * ws_factor),
                "ws_basis": "source",
                "confidence": 0.25 if fitted else 0.0,
                "runs": 0,
                "overhead_us": 0.0,
                "overhead_basis": "none",
                "segments": {}}


def estimate_query(pq) -> Optional[Dict[str, object]]:
    """Admission-time estimate for a PhysicalQuery, or None when the
    history plane is disabled (spark.rapids.tpu.history.dir unset) —
    the disabled path is one cached conf check."""
    store = get_store(pq.conf)
    if store is None:
        return None
    return CostEstimator(store).estimate(pq)


def prediction_stats() -> Dict[str, object]:
    """Oracle trustworthiness from the always-on registry: per-basis
    estimate counts + the prediction-error histogram summary — the
    block ServingRuntime.stats() exposes."""
    from .registry import HISTORY_ESTIMATES, HISTORY_PREDICTION_ERROR
    estimates = {}
    for s in HISTORY_ESTIMATES.series():
        basis = s["labels"].get("basis", "?")
        estimates[basis] = estimates.get(basis, 0) + s["value"]
    n = 0
    total = 0.0
    for s in HISTORY_PREDICTION_ERROR.series():
        n += s["count"]
        total += s["sum"]
    return {"estimates": estimates,
            "calibration": {"count": n,
                            "mean_error_ratio": round(total / n, 3)
                            if n else None}}
