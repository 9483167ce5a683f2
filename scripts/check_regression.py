#!/usr/bin/env python
"""Bench regression gate: compare a bench.py result against the
committed BENCH_r*/MULTICHIP_r* trajectory and fail on per-query
`device_ms` regressions.

The perf trajectory is the product (ROADMAP north star: as fast as the
hardware allows); a PR that silently doubles a query's device time must
fail CI, not wait for a human to eyeball BENCH_r{N}.json.  This gate:

  1. loads every trajectory file (three accepted shapes: the driver
     wrapper `{parsed, tail, ...}`, a raw bench.py final-line dict, or
     a `{"<suite>_suite_queries": ...}` fragment);
  2. normalizes every timing to NET-OF-FLOOR milliseconds — the
     emitted `device_ms_net` when present, else `device_ms` minus that
     result's own `sync_rtt_ms` — so the harness's one sync round trip
     can neither hide nor manufacture a regression, and builds the
     per-query baseline as the MINIMUM each query ever achieved across
     baseline files from the SAME backend (a cpu-backend run never
     gates against TPU numbers; every record names its `backend`);
  3. compares the current result: a query REGRESSES when its net ms
     exceeds baseline * (1 + threshold) — default threshold 0.25 —
     and exceeds the absolute noise floor (--min-ms, default 50 ms, so
     sub-frame jitter cannot fail the gate).

With no --current, the newest trajectory file that carries per-query
data is the "current" result and the older files are the baseline, so
running the script bare answers "did the latest round regress?" and
exits 0 on a healthy trajectory.

Queries only present on one side are reported but never fail the gate
(coverage growth must not look like a regression).  Exit codes: 0 ok,
1 regressions found, 2 usage/no-data.

Usage:
    python scripts/check_regression.py                  # gate the trajectory
    python scripts/check_regression.py --current out.json [traj.json ...]
    python scripts/check_regression.py --threshold 0.25 --min-ms 50
"""
import argparse
import ast
import glob
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: multichip dry runs force the CPU backend (8 virtual devices) — the
#: MULTICHIP_r01-r05 driver records predate the "backend" field and
#: compare against cpu-backend rounds
_MULTICHIP_BACKEND = "cpu"


def extract_multichip(doc):
    """-> ({'mc:<timing key>': ms}, backend or None) from a multichip
    result: the fused-groupby / ragged / window / mesh-query seconds in
    `multichip_timings_s` become gate-able millisecond entries under an
    `mc:` prefix (never colliding with single-chip qN names).  Accepts
    the suite runner's JSON line, the driver wrapper, and the legacy
    dryrun tail (a python-repr dict — ast.literal_eval parses it)."""
    if not isinstance(doc, dict):
        return {}, None
    tim = doc.get("multichip_timings_s")
    if isinstance(tim, dict):
        out = {f"mc:{k}": float(v) * 1e3 for k, v in tim.items()
               if isinstance(v, (int, float))}
        return out, str(doc.get("backend") or _MULTICHIP_BACKEND)
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        out, backend = extract_multichip(parsed)
        if out:
            return out, backend
    tail = doc.get("tail")
    if isinstance(tail, str) and "multichip_timings_s" in tail:
        for line in reversed(tail.splitlines()):
            if "multichip_timings_s" not in line:
                continue
            try:
                rec = ast.literal_eval(line.strip())
            except (ValueError, SyntaxError):
                try:
                    rec = json.loads(line.strip())
                except json.JSONDecodeError:
                    continue
            if isinstance(rec, dict):
                out, backend = extract_multichip(rec)
                if out:
                    return out, backend
    return {}, None


def extract_serving(doc):
    """-> ({'sv:<entry>': ms}, backend or None) from a bench.py
    --serving result: the `serving_latency_ms` gate dict (per-level
    p99/mean client-observed latency, lower = better) becomes `sv:`-
    prefixed entries that gate like per-query device_ms under the same
    backend-separation rule (never colliding with qN / mc: names).
    Accepts the runner's JSON line, the driver wrapper, and a tail."""
    if not isinstance(doc, dict):
        return {}, None
    lat = doc.get("serving_latency_ms")
    fleet = doc.get("serving_fleet")
    if (isinstance(lat, dict) and lat) or \
            (isinstance(fleet, dict) and fleet):
        out = {f"sv:{k}": float(v)
               for k, v in (lat if isinstance(lat, dict) else {}).items()
               if isinstance(v, (int, float))}
        # cross-process utilization skew from the federated fleet
        # registry (mp levels): gates under the same sv: rules — a skew
        # regression means dispatch stopped spreading work.  Scaled
        # x100 (1.0 -> 100) so a real imbalance clears the --min-ms
        # noise floor, which raw max/min ratios (~1-3) never would.
        out.update({f"sv:{k}": float(v) * 100.0
                    for k, v in (fleet if isinstance(fleet, dict)
                                 else {}).items()
                    if isinstance(v, (int, float))})
        return out, doc.get("backend")
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        out, backend = extract_serving(parsed)
        if out:
            return out, backend
    tail = doc.get("tail")
    if isinstance(tail, str) and ("serving_latency_ms" in tail
                                  or "serving_fleet" in tail):
        for line in reversed(tail.splitlines()):
            if "serving_latency_ms" not in line and \
                    "serving_fleet" not in line:
                continue
            try:
                rec = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out, backend = extract_serving(rec)
                if out:
                    return out, backend
    return {}, None


def extract_encodings(doc):
    """-> ({'en:<entry>': ms}, backend or None) from a bench.py
    --encodings result: the `encoding_timings_ms` A/B dict (encoded vs
    decode-first per encoding family / operator / selectivity, lower =
    better) becomes `en:`-prefixed entries that gate like per-query
    device_ms under the same backend-separation rule (never colliding
    with qN / mc: / sv: names).  Accepts the runner's JSON line,
    the driver wrapper, and a tail."""
    if not isinstance(doc, dict):
        return {}, None
    tim = doc.get("encoding_timings_ms")
    if isinstance(tim, dict) and tim:
        out = {f"en:{k}": float(v) for k, v in tim.items()
               if isinstance(v, (int, float))}
        return out, doc.get("backend")
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        out, backend = extract_encodings(parsed)
        if out:
            return out, backend
    tail = doc.get("tail")
    if isinstance(tail, str) and "encoding_timings_ms" in tail:
        for line in reversed(tail.splitlines()):
            if "encoding_timings_ms" not in line:
                continue
            try:
                rec = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out, backend = extract_encodings(rec)
                if out:
                    return out, backend
    return {}, None


def extract_ooc(doc):
    """-> ({'oc:<entry>': ms}, backend or None) from a bench.py --ooc
    result: the `ooc_timings_ms` dict ({qN}_capped = memory-capped wall
    through the out-of-core tier, {qN}_uncapped = the resident
    baseline, lower = better) becomes `oc:`-prefixed entries that gate
    like per-query device_ms under the same backend-separation rule
    (never colliding with qN / mc: / sv: / en: names).  Accepts
    the runner's JSON line, the driver wrapper, and a tail."""
    if not isinstance(doc, dict):
        return {}, None
    tim = doc.get("ooc_timings_ms")
    if isinstance(tim, dict) and tim:
        out = {f"oc:{k}": float(v) for k, v in tim.items()
               if isinstance(v, (int, float))}
        return out, doc.get("backend")
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        out, backend = extract_ooc(parsed)
        if out:
            return out, backend
    tail = doc.get("tail")
    if isinstance(tail, str) and "ooc_timings_ms" in tail:
        for line in reversed(tail.splitlines()):
            if "ooc_timings_ms" not in line:
                continue
            try:
                rec = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out, backend = extract_ooc(rec)
                if out:
                    return out, backend
    return {}, None


def _rec_ms(rec: dict, rtt_ms: float):
    """Net-of-floor milliseconds for one per-query record: the explicit
    `device_ms_net` when the bench emitted it, else `device_ms` minus
    the result's own sync round trip — so the harness floor can neither
    hide a regression in a fast query nor manufacture one when the
    floor moves."""
    if rec.get("device_ms_net"):
        return float(rec["device_ms_net"])
    if rec.get("device_ms"):
        return max(float(rec["device_ms"]) - rtt_ms, 0.001)
    return None


def extract_compile_ms(doc) -> list:
    """Per-query COLD compile milliseconds (compile_ms_cold) of a
    result document — [] for documents predating the field.  The gate
    compares the MEDIAN, so one pathological query cannot fail it and
    coverage growth cannot hide a fleet-wide compile regression."""
    out = []
    if not isinstance(doc, dict):
        return out
    for key, val in doc.items():
        if key.endswith("_suite_queries") and isinstance(val, dict):
            for rec in val.values():
                if isinstance(rec, dict) and \
                        rec.get("compile_ms_cold") is not None:
                    out.append(float(rec["compile_ms_cold"]))
    if out:
        return out
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return extract_compile_ms(parsed)
    return out


def extract_segments(doc) -> dict:
    """-> {query: {segment node: device_ms}} from the per-query profile
    summaries bench embeds (profile.segments runs, PR 9) — {} for
    records predating the attribution plane.  When the gate fails a
    query, the worst-regressed SEGMENT is cited from these."""
    out = {}
    if not isinstance(doc, dict):
        return out
    for key, val in doc.items():
        if key.endswith("_suite_queries") and isinstance(val, dict):
            for q, rec in val.items():
                prof = rec.get("profile") if isinstance(rec, dict) \
                    else None
                segs = (prof or {}).get("segments") \
                    if isinstance(prof, dict) else None
                if segs:
                    out[q] = {s["node"]: float(s.get("device_ms", 0.0))
                              for s in segs
                              if isinstance(s, dict) and "node" in s}
    if out:
        return out
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return extract_segments(parsed)
    return out


def extract_hbm(doc) -> dict:
    """-> {query: hbm_peak_bytes} from the per-query measured-HBM
    fields bench/multichip records embed (the memory-attribution
    plane, ISSUE 14) — {} for records predating it.  Gated like
    device_ms under the same backend-separation rule: a PR that
    silently doubles a query's working set fails CI even when its
    wall time holds."""
    out = {}
    if not isinstance(doc, dict):
        return out
    for key, val in doc.items():
        if key.endswith("_suite_queries") and isinstance(val, dict):
            for q, rec in val.items():
                if isinstance(rec, dict) and rec.get("hbm_peak_bytes"):
                    out[q] = float(rec["hbm_peak_bytes"])
    if out:
        return out
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return extract_hbm(parsed)
    return out


def load_hbm(path: str) -> dict:
    """{query: hbm_peak_bytes} of one trajectory file ({} on any read
    problem — like segments, absence never fails the gate by itself)."""
    try:
        with open(path) as f:
            return extract_hbm(json.load(f))
    except (OSError, json.JSONDecodeError, ValueError):
        return {}


def extract_overheads(doc) -> dict:
    """-> {query: {seam_count, seam_ms, dispatch_ms, pad_waste_ms,
    pad_waste_share}} from the per-query wall_breakdown embeds bench
    records carry (the wall-decomposition plane, ISSUE 18) — {} for
    records predating it.  seam_count and pad_waste_share gate like
    device_ms under the same backend-separation rule: a PR that adds a
    seam round-trip or blows up bucket padding fails CI even when its
    wall time holds at this scale."""
    out = {}
    if not isinstance(doc, dict):
        return out
    for key, val in doc.items():
        if key.endswith("_suite_queries") and isinstance(val, dict):
            for q, rec in val.items():
                bd = rec.get("wall_breakdown") \
                    if isinstance(rec, dict) else None
                if not isinstance(bd, dict) or not bd.get("wall_ms"):
                    continue
                wall = float(bd["wall_ms"])
                pad = float(bd.get("pad_waste_ms") or 0.0)
                out[q] = {
                    "seam_count": int(bd.get("seam_count") or 0),
                    "seam_ms": float(bd.get("seam_ms") or 0.0),
                    "dispatch_ms": float(bd.get("dispatch_ms") or 0.0),
                    "pad_waste_ms": pad,
                    "pad_waste_share": pad / wall if wall else 0.0,
                }
    if out:
        return out
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return extract_overheads(parsed)
    return out


def load_overheads(path: str) -> dict:
    """{query: overhead fields} of one trajectory file ({} on any read
    problem — like hbm, absence never fails the gate by itself)."""
    try:
        with open(path) as f:
            return extract_overheads(json.load(f))
    except (OSError, json.JSONDecodeError, ValueError):
        return {}


def extract_queries(doc):
    """-> (query name -> net device_ms, backend tag) from any accepted
    result shape; ({}, backend) when the document carries no per-query
    timings."""
    out = {}
    if not isinstance(doc, dict):
        return out, None
    rtt_ms = float(doc.get("sync_rtt_ms") or 0.0)
    for key, val in doc.items():
        if key.endswith("_suite_queries") and isinstance(val, dict):
            for q, rec in val.items():
                if isinstance(rec, dict):
                    ms = _rec_ms(rec, rtt_ms)
                    if ms is not None:
                        out[q] = ms
    if out:
        return out, doc.get("backend")
    # driver wrapper: the parsed final line
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return extract_queries(parsed)
    return out, None


def load_file(path: str):
    with open(path) as f:
        doc = json.load(f)
    qs, backend = extract_queries(doc)
    mc, mc_backend = extract_multichip(doc)
    if mc:
        # multichip timings gate alongside per-query device_ms under
        # their mc: prefix; a pure-multichip file takes the multichip
        # backend tag (cpu for pre-backend dryrun rounds)
        qs = {**qs, **mc}
        backend = backend or mc_backend
    sv, sv_backend = extract_serving(doc)
    if sv:
        # serving latency entries gate under their sv: prefix; a pure
        # serving record carries its own backend tag
        qs = {**qs, **sv}
        backend = backend or sv_backend
    en, en_backend = extract_encodings(doc)
    if en:
        # encoded-execution microbench entries gate under their en:
        # prefix; a pure encodings record carries its own backend tag
        qs = {**qs, **en}
        backend = backend or en_backend
    oc, oc_backend = extract_ooc(doc)
    if oc:
        # memory-capped out-of-core leg entries gate under their oc:
        # prefix; a pure ooc record carries its own backend tag
        qs = {**qs, **oc}
        backend = backend or oc_backend
    return qs, backend, extract_compile_ms(doc)


def load_segments(path: str) -> dict:
    """{query: {segment: device_ms}} of one trajectory file ({} on any
    read problem — segment citation is best-effort color, never a gate
    failure of its own)."""
    try:
        with open(path) as f:
            return extract_segments(json.load(f))
    except (OSError, json.JSONDecodeError, ValueError):
        return {}


def worst_segment_line(q: str, cur_segs: dict, base_segs: dict):
    """The segment-level citation for one regressed query: the segment
    with the largest device_ms growth vs baseline (or the dominant
    current segment when the baseline has no segment data)."""
    cur = cur_segs.get(q) or {}
    if not cur:
        return None
    base = base_segs.get(q) or {}
    shared = set(cur) & set(base)
    if shared:
        node = max(shared, key=lambda n: cur[n] - base[n])
        return (f"    worst segment: {node} "
                f"{base[node]:.1f} -> {cur[node]:.1f} ms "
                f"(+{cur[node] - base[node]:.1f})")
    node = max(cur, key=cur.get)
    return (f"    dominant segment: {node} {cur[node]:.1f} ms "
            f"(no baseline segment data)")


def _median(vals: list):
    vals = sorted(vals)
    return vals[len(vals) // 2] if vals else None


def default_trajectory() -> list:
    return (sorted(glob.glob(os.path.join(_ROOT, "BENCH_r*.json"))) +
            sorted(glob.glob(os.path.join(_ROOT, "MULTICHIP_r*.json"))) +
            sorted(glob.glob(os.path.join(_ROOT, "SERVING_r*.json"))) +
            sorted(glob.glob(os.path.join(_ROOT, "ENCODINGS_r*.json"))) +
            sorted(glob.glob(os.path.join(_ROOT, "OOC_r*.json"))))


def compare(current: dict, baseline: dict, threshold: float,
            min_ms: float) -> dict:
    """-> {regressions, improved, ok, only_current, only_baseline}."""
    regressions, improved, ok = [], [], []
    for q in sorted(set(current) & set(baseline),
                    key=lambda s: (len(s), s)):
        cur, base = current[q], baseline[q]
        ratio = cur / base if base else float("inf")
        row = {"query": q, "device_ms": cur, "baseline_ms": base,
               "ratio": round(ratio, 3)}
        if cur > base * (1.0 + threshold) and cur > min_ms:
            regressions.append(row)
        elif ratio < 1.0:
            improved.append(row)
        else:
            ok.append(row)
    return {"regressions": regressions, "improved": improved, "ok": ok,
            "only_current": sorted(set(current) - set(baseline)),
            "only_baseline": sorted(set(baseline) - set(current))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trajectory", nargs="*",
                    help="baseline result files (default: the committed "
                         "BENCH_r*/MULTICHIP_r* trajectory)")
    ap.add_argument("--current",
                    help="bench result to gate (default: the newest "
                         "trajectory file with per-query data)")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="fractional device_ms regression that fails "
                         "(default 0.25 = +25%%)")
    ap.add_argument("--min-ms", type=float, default=50.0,
                    help="absolute floor below which timings are noise, "
                         "never regressions (default 50)")
    ap.add_argument("--compile-threshold", type=float, default=0.5,
                    help="fractional MEDIAN compile_ms_cold regression "
                         "that fails (default 0.5 = +50%%; compile wall "
                         "is noisier than device wall)")
    ap.add_argument("--compile-min-ms", type=float, default=1000.0,
                    help="median compile floor below which compile "
                         "timings never regress (default 1000)")
    ap.add_argument("--hbm-threshold", type=float, default=0.25,
                    help="fractional per-query hbm_peak_bytes growth "
                         "that fails (default 0.25 = +25%%; the "
                         "memory-attribution plane's measured peaks)")
    ap.add_argument("--hbm-min-bytes", type=float, default=float(1 << 20),
                    help="absolute floor below which HBM peaks are "
                         "noise, never regressions (default 1 MiB)")
    ap.add_argument("--seam-threshold", type=float, default=0.25,
                    help="fractional per-query seam-count growth that "
                         "fails (default 0.25 = +25%%; the "
                         "wall-decomposition plane's seam brackets)")
    ap.add_argument("--seam-min", type=float, default=2,
                    help="seam-count floor below which growth is noise, "
                         "never a regression (default 2: 1 -> 1 never "
                         "fails, 1 -> 2 does)")
    ap.add_argument("--pad-threshold", type=float, default=0.25,
                    help="fractional per-query pad-waste-share growth "
                         "that fails (default 0.25 = +25%%; share = "
                         "pad_waste_ms / profiled wall)")
    ap.add_argument("--pad-min-share", type=float, default=0.05,
                    help="pad-waste share floor below which growth is "
                         "noise, never a regression (default 0.05)")
    ap.add_argument("--history-dir",
                    help="performance-history dir "
                         "(spark.rapids.tpu.history.dir): when the "
                         "gate fails, cite the drifted plan "
                         "STRUCTURES and their measured history "
                         "(scripts/history_report.py drift detection) "
                         "next to the regressed queries")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    args = ap.parse_args(argv)

    paths = args.trajectory or default_trajectory()
    per_file = {}
    backends = {}
    compile_ms = {}
    for p in paths:
        try:
            qs, backend, cms = load_file(p)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# skipping unreadable {p}: {e}", file=sys.stderr)
            continue
        per_file[p] = qs
        backends[p] = backend
        compile_ms[p] = cms
    with_data = [p for p in per_file if per_file[p]]

    if args.current:
        try:
            current, cur_backend, cur_compile = load_file(args.current)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read --current {args.current}: {e}",
                  file=sys.stderr)
            return 2
        current_name = args.current
        baseline_files = with_data
    else:
        if not with_data:
            print("no trajectory file carries per-query device_ms — "
                  "nothing to gate", file=sys.stderr)
            return 2
        current_name = with_data[-1]
        current = per_file[current_name]
        cur_backend = backends[current_name]
        cur_compile = compile_ms[current_name]
        baseline_files = with_data[:-1]
    if not current:
        print(f"{current_name} carries no per-query device_ms",
              file=sys.stderr)
        return 2

    # milliseconds only compare on the SAME hardware: a cpu-backend CI
    # run gating against TPU baselines (or vice versa) would
    # manufacture regressions/improvements out of the platform change
    same_hw = [p for p in baseline_files if backends[p] == cur_backend]
    skipped_hw = [p for p in baseline_files if backends[p] != cur_backend]
    if skipped_hw:
        print(f"# backend={cur_backend}: skipping "
              f"{len(skipped_hw)} baseline file(s) from other backends "
              f"({', '.join(sorted({str(backends[p]) for p in skipped_hw}))})",
              file=sys.stderr)
    baseline_files = same_hw

    baseline = {}
    for p in baseline_files:
        for q, v in per_file[p].items():
            baseline[q] = min(baseline.get(q, v), v)

    # segment-level attribution (best-effort): when a query regresses,
    # cite the worst-regressed SEGMENT from the embedded profiles
    cur_segs = load_segments(current_name) \
        if os.path.exists(current_name) else {}
    base_segs = {}
    for p in baseline_files:
        for q, per in load_segments(p).items():
            tgt = base_segs.setdefault(q, {})
            for n, v in per.items():
                tgt[n] = min(tgt.get(n, v), v)

    res = compare(current, baseline, args.threshold, args.min_ms)
    for row in res["regressions"]:
        cite = worst_segment_line(row["query"], cur_segs, base_segs)
        if cite:
            row["worst_segment"] = cite.strip()
    if args.json:
        print(json.dumps({"current": current_name,
                          "baseline_files": baseline_files,
                          "threshold": args.threshold, **res}))
    else:
        print(f"current:  {current_name} ({len(current)} queries)")
        print(f"baseline: best-of {len(baseline_files)} file(s), "
              f"{len(baseline)} queries; threshold "
              f"+{args.threshold:.0%}, noise floor {args.min_ms:g} ms")
        for row in res["regressions"]:
            print(f"  REGRESSION {row['query']}: {row['device_ms']:.1f} ms"
                  f" vs {row['baseline_ms']:.1f} ms "
                  f"(x{row['ratio']:.2f})")
            if row.get("worst_segment"):
                print(f"    {row['worst_segment']}")
        for row in res["improved"]:
            print(f"  improved   {row['query']}: {row['device_ms']:.1f} ms"
                  f" vs {row['baseline_ms']:.1f} ms "
                  f"(x{row['ratio']:.2f})")
        for row in res["ok"]:
            print(f"  ok         {row['query']}: {row['device_ms']:.1f} ms"
                  f" vs {row['baseline_ms']:.1f} ms "
                  f"(x{row['ratio']:.2f})")
        if res["only_current"]:
            print(f"  new (no baseline): {', '.join(res['only_current'])}")
        if not baseline:
            print("  (empty baseline — nothing to regress against)")
    # -- compile-latency gate: median cold compile_ms, same backend rule
    compile_reg = False
    cur_med = _median(cur_compile)
    base_meds = [_median(compile_ms.get(p) or []) for p in baseline_files]
    base_meds = [m for m in base_meds if m is not None]
    if cur_med is not None and base_meds:
        base_med = min(base_meds)
        if cur_med > base_med * (1.0 + args.compile_threshold) and \
                cur_med > args.compile_min_ms:
            compile_reg = True
            print(f"  COMPILE REGRESSION: median compile_ms_cold "
                  f"{cur_med:.0f} vs {base_med:.0f} "
                  f"(x{cur_med / base_med:.2f}, threshold "
                  f"+{args.compile_threshold:.0%})")
        else:
            print(f"  compile ok: median compile_ms_cold {cur_med:.0f} "
                  f"vs baseline {base_med:.0f}")
    elif cur_med is not None:
        print(f"  compile: median compile_ms_cold {cur_med:.0f} "
              f"(no baseline carries compile data)")

    # -- HBM-peak gate: per-query measured working-set peaks (the
    # memory-attribution plane), best-of baseline, same backend rule
    hbm_regs = []
    cur_hbm = load_hbm(current_name) if os.path.exists(current_name) \
        else {}
    if cur_hbm:
        base_hbm = {}
        for p in baseline_files:
            for q, v in load_hbm(p).items():
                base_hbm[q] = min(base_hbm.get(q, v), v)
        for q in sorted(set(cur_hbm) & set(base_hbm),
                        key=lambda s: (len(s), s)):
            cur_b, base_b = cur_hbm[q], base_hbm[q]
            if cur_b > base_b * (1.0 + args.hbm_threshold) and \
                    cur_b > args.hbm_min_bytes:
                hbm_regs.append((q, cur_b, base_b))
                print(f"  HBM REGRESSION {q}: peak {cur_b:.0f} bytes "
                      f"vs {base_b:.0f} (x{cur_b / base_b:.2f}, "
                      f"threshold +{args.hbm_threshold:.0%})")
        if not hbm_regs and base_hbm:
            print(f"  hbm ok: {len(set(cur_hbm) & set(base_hbm))} "
                  f"query peak(s) within +{args.hbm_threshold:.0%} of "
                  f"baseline")

    # -- overhead gates: per-query seam count and pad-waste share (the
    # wall-decomposition plane), best-of baseline, same backend rule
    overhead_regs = []
    cur_ov = load_overheads(current_name) \
        if os.path.exists(current_name) else {}
    if cur_ov:
        base_ov = {}
        for p in baseline_files:
            for q, rec in load_overheads(p).items():
                tgt = base_ov.get(q)
                if tgt is None:
                    base_ov[q] = dict(rec)
                else:
                    for fk in ("seam_count", "pad_waste_share"):
                        tgt[fk] = min(tgt[fk], rec[fk])
        shared = sorted(set(cur_ov) & set(base_ov),
                        key=lambda s: (len(s), s))
        for q in shared:
            cur_n = cur_ov[q]["seam_count"]
            base_n = base_ov[q]["seam_count"]
            if cur_n > base_n * (1.0 + args.seam_threshold) and \
                    cur_n >= args.seam_min:
                overhead_regs.append((q, "seam_count", cur_n, base_n))
                print(f"  SEAM REGRESSION {q}: {cur_n} seam(s) vs "
                      f"{base_n} baseline (each seam is a host "
                      f"round-trip + re-bucket; threshold "
                      f"+{args.seam_threshold:.0%})")
            cur_s = cur_ov[q]["pad_waste_share"]
            base_s = base_ov[q]["pad_waste_share"]
            if cur_s > base_s * (1.0 + args.pad_threshold) and \
                    cur_s > args.pad_min_share:
                overhead_regs.append((q, "pad_waste_share", cur_s,
                                      base_s))
                print(f"  PAD-WASTE REGRESSION {q}: "
                      f"{cur_s:.1%} of profiled wall vs {base_s:.1%} "
                      f"baseline (bucket-quantization tax; threshold "
                      f"+{args.pad_threshold:.0%})")
        if not overhead_regs and shared:
            print(f"  overhead ok: {len(shared)} query breakdown(s) "
                  f"within +{args.seam_threshold:.0%} seams / "
                  f"+{args.pad_threshold:.0%} pad share of baseline")

    if res["regressions"] or compile_reg or hbm_regs or overhead_regs:
        if res["regressions"]:
            print(f"{len(res['regressions'])} per-query regression(s) "
                  f"beyond +{args.threshold:.0%}")
        _cite_history_drift(args.history_dir)
        return 1
    print("no per-query device_ms regressions")
    return 0


def _cite_history_drift(history_dir) -> None:
    """Gate-failure color from the performance-history plane: name the
    plan structures whose own measured history drifted — a regressed
    query almost always means one of these, and the structure key is
    reproducible triage (best-effort: a missing/empty history never
    changes the exit code)."""
    if not history_dir:
        return
    try:
        sys.path.insert(0, _ROOT)
        from spark_rapids_tpu.obs.history import (HISTORY_FILE,
                                                  PerfHistoryStore)
        path = history_dir if not os.path.isdir(history_dir) \
            else os.path.join(history_dir, HISTORY_FILE)
        if not os.path.exists(path):
            print(f"  (no history file at {path} — drift citation "
                  f"skipped)")
            return
        drifted = PerfHistoryStore(path).drifted(2.0)
        slower = [d for d in drifted if d["slower"]]
        if not slower:
            print("  history: no structure drifted slower than 2x its "
                  "own measured history (regression may be "
                  "environmental)")
            return
        print("  history drift (structures measured >2x slower than "
              "their own history — scripts/history_report.py):")
        for d in slower[:5]:
            name = d["label"] or d["key"]
            print(f"    {name}: {d['history_us'] / 1e3:.1f}ms -> "
                  f"{d['last_us'] / 1e3:.1f}ms (x{d['ratio']:g}, "
                  f"{d['runs']} runs) [{d['key']}]")
    except Exception as e:                   # noqa: BLE001
        print(f"  (history drift citation unavailable: "
              f"{type(e).__name__}: {e})")


if __name__ == "__main__":
    raise SystemExit(main())
