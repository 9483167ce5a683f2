"""No hidden fallback: a copy of the assertions of `chip_smoke.py`'s
`Smoke.collect`, turned into reasons.  The cells measure the whole-plan path;
a query answered by the eager engine, by a per-operator CPU fallback, after
an OOM replay or by the out-of-core tier is a failed operation, not a slower
success."""
from __future__ import annotations

from typing import List, Mapping


def plan_faults(df) -> List[str]:
    """Faults visible in the physical plan (asked once per query in
    set-up: planning is deterministic)."""
    q = df.physical()
    faults = []
    if q.kind != "device":
        faults.append(f"plan kind is {q.kind!r}")
    if q.fallback_reasons():
        faults.append(f"per-operator CPU fallback {q.fallback_reasons()}")
    return faults


def collect_faults(metrics: Mapping) -> List[str]:
    """Faults visible in one collect's `ctx.metrics`."""
    faults = []
    if metrics.get("whole_plan_fallbacks", 0):
        faults.append("whole-plan program fell back to the eager engine")
    if metrics.get("whole_plan_compiled_queries", 0) != 1:
        faults.append("whole_plan_compiled_queries="
                      f"{metrics.get('whole_plan_compiled_queries', 0)}")
    if metrics.get("query_oom_replays"):
        faults.append(f"{metrics['query_oom_replays']} OOM replay(s)")
    if metrics.get("query_ooc_escalations"):
        faults.append("escalated to the out-of-core tier")
    return faults


def fallback_instants() -> list:
    """reason + error head of every whole_plan_fallback instant still in
    the engine's always-on flight recorder."""
    from spark_rapids_tpu.obs.recorder import FLIGHT_RECORDER
    return [r.get("attrs") for r in FLIGHT_RECORDER.tail()
            if r.get("name") == "whole_plan_fallback"]
