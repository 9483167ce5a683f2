#!/usr/bin/env python
"""Profile diff: compare two profiling records per SEGMENT and per
metric family, and emit the top regressed / improved entries.

The regression gate (check_regression.py) answers "did a query get
slower"; this tool answers the next question — *where*.  It diffs any
two of:

  * query event logs (`query_<id>.jsonl`, written under
    `spark.rapids.tpu.eventLog.dir`) — per-segment measured device ms
    (runs with `spark.rapids.tpu.profile.segments` on), per-node
    operator self time, the compile/execute/transition/shuffle split,
    data-movement counters and incident counts;
  * bench / multichip result JSONs (BENCH_r*/MULTICHIP_r*, raw final
    lines, driver wrappers, legacy python-repr dry-run tails) —
    per-query net device ms, `mc:`-prefixed multichip timings, embedded
    per-query segment summaries and cold compile ms.

Typical uses: A/B two confs from their event logs; r(N) vs r(N-1) from
the committed trajectory (`profile_diff.py MULTICHIP_r05.json
MULTICHIP_r08.json` reproduces the PR 8 fused-groupby win as a
segment-level diff).

Exit codes: 0 ok, 2 usage/no comparable data.

Usage:
    python scripts/profile_diff.py A B [--top N] [--min-ms MS] [--json]
    python scripts/profile_diff.py --self-test
"""
import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# record loading: every input becomes {family: {entry: float}}
# ---------------------------------------------------------------------------

def _eventlog_families(path: str) -> dict:
    from spark_rapids_tpu.obs.profile import QueryProfile
    prof = QueryProfile.from_event_log(path)
    fams = {}
    segs = {s["node"]: float(s.get("device_ms", 0.0))
            for s in prof.segments()}
    if segs:
        fams["segments"] = segs
    ops = {o["node"]: float(o.get("self_time_ms", 0.0))
           for o in prof.operators()}
    if ops:
        fams["operators"] = ops
    split = {k: float(v) for k, v in prof.time_split().items() if v}
    if split:
        fams["time_split"] = split
    dm = {k: float(v) for k, v in prof.data_movement().items()}
    if dm:
        fams["data_movement"] = dm
    inc = {k: float(v) for k, v in prof.incidents().items()}
    if inc:
        fams["incidents"] = inc
    # the wall-decomposition plane: fixed-overhead tail per category
    # (seam wall, dispatch floor x launches, padding waste) — a diff
    # here names the overhead a refactor added or removed even when
    # device ms held still
    bd = prof.wall_breakdown()
    ov = {k: float(bd[k]) for k in ("seam_ms", "dispatch_ms",
                                    "pad_waste_ms", "seam_count")
          if bd.get(k)}
    if ov:
        fams["overhead"] = ov
    return fams


def _bench_families(path: str) -> dict:
    from check_regression import (extract_compile_ms, extract_hbm,
                                  extract_multichip, extract_overheads,
                                  extract_queries, extract_segments,
                                  extract_serving)
    with open(path) as f:
        doc = json.load(f)
    fams = {}
    qs, _backend = extract_queries(doc)
    mc, _ = extract_multichip(doc)
    queries = {**qs, **mc}
    if queries:
        fams["queries"] = queries
    # serving-latency (SERVING_r*.json sv: entries) records diff with
    # the same tool — the regression gate already mines them, so reuse
    # its extractor
    sv, _ = extract_serving(doc)
    if sv:
        fams["serving"] = sv
    segs = extract_segments(doc)
    flat_segs = {f"{q}/{node}": ms for q, per in segs.items()
                 for node, ms in per.items()}
    if flat_segs:
        fams["segments"] = flat_segs
    # per-query measured HBM peaks (memory-attribution plane): diff
    # working sets across bench rounds the same way device time diffs
    hbm = extract_hbm(doc)
    if hbm:
        fams["hbm"] = hbm
    # per-query overhead tails (wall_breakdown embeds): seam/dispatch/
    # pad-waste ms keyed q/field, so "q4 gained a seam" reads directly
    ovs = extract_overheads(doc)
    flat_ov = {f"{q}/{k}": float(v) for q, per in ovs.items()
               for k, v in per.items()
               if k != "pad_waste_share" and v}
    if flat_ov:
        fams["overhead"] = flat_ov
    cms = extract_compile_ms(doc)
    if cms:
        fams["compile"] = {"median_compile_ms":
                           float(sorted(cms)[len(cms) // 2])}
    return fams


def load_families(path: str) -> dict:
    """-> {family: {entry: value}} for an event log or bench record."""
    if path.endswith(".jsonl"):
        return _eventlog_families(path)
    return _bench_families(path)


# ---------------------------------------------------------------------------
# diff
# ---------------------------------------------------------------------------

def diff_families(a: dict, b: dict, min_abs: float = 1.0) -> dict:
    """Per-family entry diff of record A (baseline) vs B (current):
    rows {entry, a, b, delta, ratio}, split into regressed (B worse,
    ratio desc) and improved (B better, improvement desc).  Entries
    below `min_abs` on BOTH sides are noise and skipped."""
    out = {}
    for fam in sorted(set(a) & set(b)):
        rows = []
        ea, eb = a[fam], b[fam]
        for k in sorted(set(ea) & set(eb)):
            va, vb = float(ea[k]), float(eb[k])
            if abs(va) < min_abs and abs(vb) < min_abs:
                continue
            rows.append({"entry": k, "a": round(va, 3),
                         "b": round(vb, 3),
                         "delta": round(vb - va, 3),
                         "ratio": round(vb / va, 4) if va else
                         float("inf")})
        regressed = sorted([r for r in rows if r["delta"] > 0],
                           key=lambda r: -r["delta"])
        improved = sorted([r for r in rows if r["delta"] < 0],
                          key=lambda r: r["delta"])
        out[fam] = {"regressed": regressed, "improved": improved,
                    "only_a": sorted(set(ea) - set(eb)),
                    "only_b": sorted(set(eb) - set(ea))}
    return out


def render(res: dict, name_a: str, name_b: str, top: int) -> str:
    lines = [f"A (baseline): {name_a}", f"B (current):  {name_b}"]
    for fam, d in res.items():
        lines.append(f"-- {fam} --")
        for r in d["regressed"][:top]:
            lines.append(f"  REGRESSED {r['entry']:<44} "
                         f"{r['a']:>12.1f} -> {r['b']:>12.1f}  "
                         f"(x{r['ratio']:.2f}, +{r['delta']:.1f})")
        for r in d["improved"][:top]:
            lines.append(f"  improved  {r['entry']:<44} "
                         f"{r['a']:>12.1f} -> {r['b']:>12.1f}  "
                         f"(x{r['ratio']:.2f}, {r['delta']:.1f})")
        if not d["regressed"] and not d["improved"]:
            lines.append("  (no change above the noise floor)")
        extra = len(d["regressed"]) + len(d["improved"]) - 2 * top
        if extra > 0:
            lines.append(f"  ... {extra} more changed entr"
                         f"{'y' if extra == 1 else 'ies'}")
        if d["only_a"] or d["only_b"]:
            lines.append(f"  (only in A: {len(d['only_a'])}, "
                         f"only in B: {len(d['only_b'])})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# self test (tier-1 via tests/test_explain_analyze.py)
# ---------------------------------------------------------------------------

def self_test() -> int:
    """Built-in proof the diff works end to end: (1) a synthetic A/B
    orders regressions and improvements correctly; (2) a synthetic
    event-log pair diffs per segment; (3) serving (sv:) records load
    and diff as their own family; (3c) a seam-elimination win surfaces
    in the overhead family; (4) the committed MULTICHIP trajectory
    reproduces the PR 8 fused-groupby win (119.4s -> 11.1s) as an
    `mc:`-keyed improvement."""
    import tempfile
    # 1: synthetic family diff
    a = {"segments": {"agg": 100.0, "join": 500.0, "sort": 50.0}}
    b = {"segments": {"agg": 300.0, "join": 50.0, "sort": 55.0}}
    res = diff_families(a, b)
    seg = res["segments"]
    assert seg["regressed"][0]["entry"] == "agg", seg
    assert seg["improved"][0]["entry"] == "join", seg
    assert abs(seg["regressed"][0]["ratio"] - 3.0) < 1e-9

    # 2: event-log pair round trip (segment.* metrics -> segments family)
    def log_lines(join_ms):
        return "\n".join([
            json.dumps({"type": "query_start", "query_id": 1,
                        "wall_start_unix": 0.0}),
            json.dumps({"type": "span", "id": 0, "parent": None,
                        "name": "query", "cat": "query", "t0_ms": 0.0,
                        "dur_ms": join_ms + 10.0}),
            json.dumps({"type": "query_end", "query_id": 1,
                        "metrics": {
                            "segment.HashJoinExec#1.device_ms": join_ms,
                            "segment.HashAggregateExec#0.device_ms": 5.0},
                        "counters": {}, "meta": {}})])
    with tempfile.TemporaryDirectory() as td:
        pa = os.path.join(td, "a.jsonl")
        pb = os.path.join(td, "b.jsonl")
        open(pa, "w").write(log_lines(200.0) + "\n")
        open(pb, "w").write(log_lines(20.0) + "\n")
        res = diff_families(load_families(pa), load_families(pb))
        imp = res["segments"]["improved"]
        assert imp and imp[0]["entry"] == "HashJoinExec#1", res

    # 3: serving records diff through the same loader (the sv: family
    # the regression gate mines)
    def sv_doc(p99_ms):
        return {"backend": "cpu",
                "serving_latency_ms": {"c8_p99": p99_ms,
                                       "c8_mean": p99_ms / 2.0}}
    with tempfile.TemporaryDirectory() as td:
        va = os.path.join(td, "SERVING_a.json")
        vb = os.path.join(td, "SERVING_b.json")
        json.dump(sv_doc(800.0), open(va, "w"))
        json.dump(sv_doc(2400.0), open(vb, "w"))
        res = diff_families(load_families(va), load_families(vb))
        assert res["serving"]["regressed"][0]["entry"] == "sv:c8_p99", \
            res["serving"]
        assert abs(res["serving"]["regressed"][0]["ratio"] - 3.0) < 1e-9

    # 3b: per-query HBM peaks diff as their own family (the memattr
    # plane's bench fields — check_regression gates them, this names
    # the query whose working set moved)
    def hbm_doc(q3_bytes):
        return {"backend": "cpu", "tpch_suite_queries": {
            "q3": {"device_ms_net": 100.0, "hbm_peak_bytes": q3_bytes},
            "q6": {"device_ms_net": 50.0, "hbm_peak_bytes": 1 << 20}}}
    with tempfile.TemporaryDirectory() as td:
        ha = os.path.join(td, "BENCH_a.json")
        hb = os.path.join(td, "BENCH_b.json")
        json.dump(hbm_doc(2 << 20), open(ha, "w"))
        json.dump(hbm_doc(8 << 20), open(hb, "w"))
        res = diff_families(load_families(ha), load_families(hb))
        reg = res["hbm"]["regressed"]
        assert reg and reg[0]["entry"] == "q3", res["hbm"]
        assert abs(reg[0]["ratio"] - 4.0) < 1e-9

    # 3c: seam-elimination win (wall-decomposition plane): B fuses the
    # plan so one row-collapse seam disappears — the overhead family
    # must show q4's seam wall and seam count improving even though
    # net device ms is unchanged
    def seam_doc(seam_count, seam_ms):
        return {"backend": "cpu", "tpch_suite_queries": {
            "q4": {"device_ms_net": 80.0, "wall_breakdown": {
                "wall_ms": 200.0, "seam_ms": seam_ms,
                "seam_count": seam_count, "dispatch_ms": 3.0,
                "pad_waste_ms": 2.0}}}}
    with tempfile.TemporaryDirectory() as td:
        sa = os.path.join(td, "BENCH_a.json")
        sb = os.path.join(td, "BENCH_b.json")
        json.dump(seam_doc(2, 24.0), open(sa, "w"))
        json.dump(seam_doc(1, 6.0), open(sb, "w"))
        res = diff_families(load_families(sa), load_families(sb))
        imp = res["overhead"]["improved"]
        assert imp and imp[0]["entry"] == "q4/seam_ms", res["overhead"]
        assert abs(imp[0]["ratio"] - 0.25) < 1e-9
        assert any(r["entry"] == "q4/seam_count" for r in imp), imp
        assert not res["overhead"]["regressed"], res["overhead"]

    # 4: the committed trajectory reproduces the PR 8 groupby win
    r05 = os.path.join(_ROOT, "MULTICHIP_r05.json")
    r08 = os.path.join(_ROOT, "MULTICHIP_r08.json")
    if os.path.exists(r05) and os.path.exists(r08):
        res = diff_families(load_families(r05), load_families(r08))
        imp = res["queries"]["improved"]
        assert imp, "no improvements between MULTICHIP r05 and r08"
        top = imp[0]
        assert top["entry"] == "mc:groupby_1048576_rows_per_device", imp
        assert top["ratio"] < 0.15, top   # 119.4s -> 11.1s is ~0.093x
    else:
        print("# self-test: committed MULTICHIP records absent, "
              "trajectory leg skipped", file=sys.stderr)
    print("profile_diff self-test OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("a", nargs="?", help="baseline record "
                                         "(.jsonl event log or .json)")
    ap.add_argument("b", nargs="?", help="current record")
    ap.add_argument("--top", type=int, default=5,
                    help="entries shown per direction per family")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="noise floor: entries below this on both "
                         "sides are skipped")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in self test (tier-1 wired)")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    if not args.a or not args.b:
        ap.print_usage()
        return 2
    try:
        fa = load_families(args.a)
        fb = load_families(args.b)
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot read records: {e}", file=sys.stderr)
        return 2
    res = diff_families(fa, fb, args.min_ms)
    if not res:
        print("no comparable metric families between the two records",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({"a": args.a, "b": args.b, **res}))
    else:
        print(render(res, args.a, args.b, args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
