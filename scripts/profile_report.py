#!/usr/bin/env python
"""Offline query-profile reports from JSONL event logs — the RAPIDS
profiling-tool analogue (SURVEY §5).

Input: one `query_<id>.jsonl` written under `spark.rapids.tpu.eventLog.dir`,
or a directory of them.  For each log it renders the QueryProfile: the
compile/execute/transition/shuffle wall split, the per-node-id operator
table (top operators by self time), per-SEGMENT measured device time
(runs with `spark.rapids.tpu.profile.segments` on), data-movement bytes,
memory high-water, runtime incidents (OOM retries / splits / spills) and
the fallback summary.  The sibling `query_<id>.trace.json` opens directly
in perfetto (https://ui.perfetto.dev) or chrome://tracing.

MULTICHIP/BENCH records (`MULTICHIP_r*.json`, bench final lines, driver
wrappers — including legacy dry-run tails whose last line is a python
repr) are rendered too: the `mc:`-keyed timings, the embedded per-round
exchange timelines and per-query mesh records.

`--mesh` expands the per-round mesh exchange timeline (round quotas,
wire bytes pre/post compress, per-device arrivals, staging vs
collective ms) for every input that carries one.

Usage:
    python scripts/profile_report.py <event_log.jsonl | record.json | dir>
                                     [--json] [--mesh]
"""
import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def log_paths(target: str) -> list:
    if os.path.isdir(target):
        paths = sorted(glob.glob(os.path.join(target, "*.jsonl")) +
                       glob.glob(os.path.join(target, "*.json")))
        paths = [p for p in paths if not p.endswith(".trace.json")]
        if not paths:
            raise SystemExit(f"no *.jsonl / *.json records under {target}")
        return paths
    if not os.path.exists(target):
        raise SystemExit(f"no such file: {target}")
    return [target]


def render_mesh_timeline(tl: dict, indent: str = "  ") -> list:
    """Expanded per-round mesh timeline lines (--mesh)."""
    lines = []
    for ex in tl.get("exchanges", []):
        if ex.get("kind") == "dict_gather":
            lines.append(f"{indent}dict_gather t={ex.get('t_ms', 0)}ms "
                         f"bytes={ex.get('bytes', 0)}")
            continue
        hbm = ""
        if ex.get("slab_bytes") or ex.get("recv_buffer_bytes"):
            hbm = (f" slab={ex.get('slab_bytes', 0)}B "
                   f"recv_buf={ex.get('recv_buffer_bytes', 0)}B")
        lines.append(
            f"{indent}exchange t={ex.get('t_ms', 0)}ms "
            f"rounds={ex.get('rounds', 0)} quota={ex.get('quota', 0)} "
            f"wire={ex.get('bytes', 0)}B "
            f"(pre-compress {ex.get('bytes_pre_compress', 0)}B) "
            f"recv_cap={ex.get('recv_cap', 0)}{hbm} "
            f"arrivals={ex.get('arrivals', '?')}")
        for r in ex.get("round_events", []):
            lines.append(
                f"{indent}  round {r.get('r')}: "
                f"stage={r.get('stage_ms', '?')}ms "
                f"collective={r.get('collective_ms', '?')}ms")
    for sp in tl.get("skew_splits", []):
        lines.append(f"{indent}skew_split t={sp.get('t_ms', 0)}ms "
                     f"per_shard_in={sp.get('per_shard_in', '?')}")
    if tl.get("ici_exchange_bytes"):
        lines.append(f"{indent}ici bytes attributed: "
                     f"{tl['ici_exchange_bytes']}")
    return lines


def try_heartbeat_log(path: str):
    """Parse a .jsonl file as a metrics-heartbeat log (obs/export.py
    Heartbeat) -> list of beat records, or None.  A supervised pool
    writes one such log PER PROCESS into a shared directory (the
    `-w<id>` suffix from `worker_suffixed_path`), so a pool report dir
    mixes query event logs with supervisor + worker heartbeat logs —
    these must render as fleet summaries, not as unreadable queries."""
    if not path.endswith(".jsonl"):
        return None
    beats = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if not isinstance(rec, dict) or \
                        rec.get("type") != "heartbeat":
                    return None
                beats.append(rec)
    except (OSError, json.JSONDecodeError):
        return None
    return beats or None


def render_heartbeat_log(path: str, beats: list, as_json: bool) -> None:
    first, last = beats[0], beats[-1]
    role = last.get("role") or "process"
    worker = last.get("worker")
    who = f"{role} {worker}" if worker else role
    span_s = max(0.0, float(last.get("ts", 0)) - float(first.get("ts", 0)))
    reg = last.get("registry") if isinstance(last.get("registry"),
                                             dict) else {}
    fleet = last.get("fleet") if isinstance(last.get("fleet"), dict) else {}
    if as_json:
        print(json.dumps({"log": path, "heartbeats": len(beats),
                          "role": role, "worker": worker,
                          "span_s": round(span_s, 3),
                          "registry_series": len(reg),
                          "fleet_series": len(fleet)}))
        return
    print(f"### {path}")
    print("== metrics heartbeat log ==")
    print(f"  {who}: {len(beats)} beat(s) over {span_s:.1f}s, "
          f"last registry {len(reg)} series"
          + (f", fleet view {len(fleet)} series" if fleet else ""))
    workers = sorted({k.split("worker=", 1)[1].split(",", 1)[0]
                      .split("}", 1)[0] for k in fleet if "worker=" in k})
    if workers:
        print(f"  fleet workers seen: {', '.join(workers)}")
    print()


def try_multichip_record(path: str):
    """Parse a .json file as a multichip/bench record -> (mc timings
    dict, full doc) or (None, None).  Reuses the regression gate's
    extractor, so driver wrappers and legacy python-repr dry-run tails
    all render."""
    if path.endswith(".jsonl"):
        return None, None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None, None
    from check_regression import extract_multichip
    mc, _backend = extract_multichip(doc)
    if not mc:
        return None, None
    return mc, doc


def render_multichip(path: str, mc: dict, doc: dict, mesh: bool,
                     as_json: bool) -> None:
    inner = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
        else doc
    if as_json:
        out = {"log": path, "multichip_timings_ms": mc}
        for k in ("n_devices", "backend", "multichip_sf", "pcache",
                  "exchange", "primitives_mesh_timeline"):
            if k in inner:
                out[k] = inner[k]
        print(json.dumps(out))
        return
    print(f"### {path}")
    print("== multichip record ==")
    meta = [f"{k}={inner[k]}" for k in ("n_devices", "backend",
                                        "multichip_sf") if k in inner]
    if meta:
        print("  " + " ".join(meta))
    for k in sorted(mc, key=lambda s: (len(s), s)):
        print(f"  {k:<44} {mc[k]:>12.1f} ms")
    prim = inner.get("primitives_mesh_timeline") or {}
    for name, tl in prim.items():
        nex = len(tl.get("exchanges", []))
        print(f"  -- {name}: {nex} exchange call(s)")
        if mesh:
            for line in render_mesh_timeline(tl, indent="     "):
                print(line)
    per_q = inner.get("multichip_suite_queries") or {}
    with_tl = {q: r for q, r in per_q.items()
               if isinstance(r, dict) and r.get("mesh_timeline")}
    for q, r in sorted(with_tl.items()):
        tl = r["mesh_timeline"]
        print(f"  -- {q}: {len(tl.get('exchanges', []))} exchange "
              f"call(s), ici bytes={r.get('ici_exchange_bytes', 0)}")
        if mesh:
            for line in render_mesh_timeline(tl, indent="     "):
                print(line)
    print()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("target", help="event-log .jsonl file, multichip/"
                                   "bench .json record, or directory")
    ap.add_argument("--json", action="store_true",
                    help="emit the full profile dict as JSON instead of "
                         "the text report")
    ap.add_argument("--mesh", action="store_true",
                    help="expand the per-round mesh exchange timeline "
                         "(round quotas, wire bytes pre/post compress, "
                         "arrivals, staging vs collective ms)")
    args = ap.parse_args(argv)

    from spark_rapids_tpu.obs.profile import QueryProfile

    for path in log_paths(args.target):
        # multichip/bench .json records render their own section (the
        # mc:-keyed timings + embedded exchange timelines)
        mc, doc = try_multichip_record(path)
        if mc:
            render_multichip(path, mc, doc, args.mesh, args.json)
            continue
        # pool heartbeat logs (supervisor + per-worker) share the dir
        # with query event logs; render their fleet summary instead of
        # failing them through the query-profile path
        beats = try_heartbeat_log(path)
        if beats:
            render_heartbeat_log(path, beats, args.json)
            continue
        # a directory can hold non-query JSONL (metrics heartbeats),
        # truncated crash-time logs, or logs from fallback-only queries
        # with no spans — none of those may take the report down
        try:
            prof = QueryProfile.from_event_log(path)
        except Exception as e:                   # noqa: BLE001
            if args.json:
                print(json.dumps({"log": path, "error":
                                  f"{type(e).__name__}: {e}"}))
            else:
                print(f"### {path}")
                print(f"  unreadable as a query event log "
                      f"({type(e).__name__}: {e})")
                print()
            continue
        if not prof.spans and not prof.metrics and not prof.events:
            if args.json:
                print(json.dumps({"log": path, "skipped":
                                  "no query trace data"}))
            else:
                print(f"### {path}")
                print("  no query trace data (not an event log, or a "
                      "fallback-only query with tracing off)")
                print()
            continue
        if args.json:
            print(json.dumps({"log": path, **prof.to_dict()}))
        else:
            print(f"### {path}")
            print(prof.render())
            if args.mesh:
                tl = prof.mesh_timeline()
                if tl["exchanges"] or tl["skew_splits"]:
                    print("-- mesh timeline (per round) --")
                    for line in render_mesh_timeline(tl):
                        print(line)
                else:
                    print("(no mesh exchange events in this log)")
            trace = path.removesuffix(".jsonl") + ".trace.json"
            if os.path.exists(trace):
                print(f"perfetto trace: {trace}")
            else:
                print("(no perfetto trace file for this query)")
            print()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
