"""One run of one cell: set-up, the timed window, the traced slice, the
comparison with the plain reference, the result line.

Order: device header -> data from the seed -> TpuSession, one DataFrame per
query, a first collect each (upload, compile or cache load) and a second as
warm-up -> `setup_s` ends -> window -> memory peak read -> (traced run: a
short profiled slice) -> reference answers -> comparison.  The reference
runs after the window and is not part of `setup_s`."""
from __future__ import annotations

import collections
import glob
import json
import os
import shutil
import statistics
import sys
import time
from typing import Dict, List

from harness import checks, compare, device, trace_reduce
from harness.columns import merge_columns
from harness.manifest import ROOT, Cell

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def run_cell(args, t0: float, tamper=None, say=None) -> dict:
    """`tamper`: a stand-in from harness/controls.py (the control and the
    planted faults), never given in a benchmark run."""
    prefix = "REHEARSAL platform=cpu " if args.rehearse_cpu else ""

    def _say(msg: str) -> None:
        print(f"{prefix}[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)
    say = say or _say

    cell = Cell(args.workload)
    dev = device.find(cell.chips, args.rehearse_cpu, say)
    peak = None if args.rehearse_cpu else device.peaks(dev["kind"])
    # the system under test; a tree without it ends here, before any data
    from spark_rapids_tpu.exec.compiled import persistent_cache_stats
    from spark_rapids_tpu.session import TpuSession

    scale = cell.config["scale_factor"]
    if args.scale is not None:
        if not args.rehearse_cpu:
            raise SystemExit("--scale is for --rehearse-cpu only: a cell "
                             "runs at its configuration's size")
        scale = args.scale
    t = time.perf_counter()
    wanted = merge_columns(cell.queries[q].SOURCE_COLUMNS
                           for q in cell.query_names)
    tables = cell.generator.gen_tables(scale, args.seed, wanted)
    say(f"data SF{scale:g} seed={args.seed}: {time.perf_counter() - t:.1f}s, "
        + ", ".join(f"{k}={v.num_rows}" for k, v in tables.items())
        + f", {sum(v.nbytes for v in tables.values()) / 1e9:.2f} GB of Arrow")

    # -- the system under test ---------------------------------------------
    import jax
    # The configuration's own conf (empty: the default conf).  Off-TPU AUTO
    # is the eager engine; the dry run has to walk the code the chip run will.
    conf = dict(cell.config["session_conf"])
    if args.rehearse_cpu:
        conf["spark.rapids.tpu.sql.compile.wholePlan"] = "ON"
    session = TpuSession(conf)
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    say(f"sync round trip median {device.measure_rtt() * 1e3:.3f} ms")
    given = tamper.tables(tables) if tamper else tables
    plan_faults: Dict[str, List[str]] = {}
    calls, frames = [], {}
    for q in cell.query_names:
        stand_in = tamper and tamper.replace(q, cell.queries[q], tables)
        if stand_in:
            calls.append((q, stand_in))
            continue
        df = frames[q] = cell.queries[q].build(session, given)
        plan_faults[q] = checks.plan_faults(df)
        # the timed call is the user's entry itself, with nothing around it
        calls.append((q, df.collect if tamper is None else
                      lambda df=df, q=q: tamper.answer(q, df.collect())))

    def inspect(q):
        return frames[q].metrics() if q in frames else None

    for q, call in calls:          # first collect: upload, compile or load
        pc0 = persistent_cache_stats()
        t = time.perf_counter()
        call()
        first = time.perf_counter() - t
        m, pc1 = inspect(q) or {}, persistent_cache_stats()
        say(f"{q}: first collect {first:.2f}s (compile_ms "
            f"{m.get('compile_ms', 0.0):.0f}, cache hits "
            f"{pc1['hits'] - pc0['hits']} misses "
            f"{pc1['misses'] - pc0['misses']}, seams "
            f"{m.get('overhead.seam_count', 0)})")
    service = _compile_service(session)
    _warm_up(calls, service, persistent_cache_stats, say)
    setup_s = time.perf_counter() - t0

    # -- the window ----------------------------------------------------------
    pc_before = persistent_cache_stats()
    window = cell.loop.run(calls, seconds=args.seconds, inspect=inspect)
    _settle(service)
    pc_after = persistent_cache_stats()
    memory_stats = max((d.memory_stats() or {} for d in jax.devices()),
                       key=lambda s: s.get("peak_bytes_in_use", 0))
    say(f"window {window.seconds:.2f}s, {len(window.records)} queries")
    _say_walls(window.records, say)

    records = list(window.records)
    trace = None
    if args.trace:
        trace, sliced = _traced_slice(cell, calls, inspect, args, say)
        records += sliced.records

    # -- failed operations, then the comparison ----------------------------
    reasons = collections.Counter()
    for r in records:
        faults = [r.error] if r.error else (
            plan_faults.get(r.query, []) + checks.collect_faults(r.metrics)
            if r.metrics is not None else [])
        if faults:
            reasons[f"{r.query}: {'; '.join(faults)}"] += 1
    failed = sum(reasons.values())
    for why, n in reasons.items():
        say(f"FAILED x{n}: {why}")
    if any("fell back" in why for why in reasons):
        say(f"whole-plan fallback instants: {checks.fallback_instants()}")

    t = time.perf_counter()
    references = {q: cell.queries[q].reference(tables)
                  for q in cell.query_names}
    verdict = compare.judge(
        [(r.query, r.answer) for r in records if r.error is None],
        references, missing=sum(1 for r in records if r.error))
    say(f"reference + comparison of {verdict['compared']} answers "
        f"{time.perf_counter() - t:.1f}s")

    run = {"window": window, "pcache_before": pc_before,
           "pcache_after": pc_after, "memory_stats": memory_stats,
           "trace": trace}
    if trace is not None:
        needed = {q: cell.queries[q].needed_bytes(tables, references[q])
                  for q in cell.query_names}
        least_s = sum(needed[q] * n for q, n in trace["queries"].items()) \
            / peak["hbm_bytes_per_s"]
        trace["xla_programs_roofline"] = 100.0 * least_s / trace["busy_s"]
        say(f"needed bytes per query {needed}; least time of the slice "
            f"{least_s * 1e3:.4f} ms over busy {trace['busy_s'] * 1e3:.3f} ms")

    if args.trace:
        values = {n: reader.read(spec, run)
                  for n, spec, reader in cell.per_layer}
    else:
        e2e = dict(cell.loop.end_to_end(window), setup_s=setup_s)
        values = {n: e2e.get(n) for n in cell.end_to_end}
    metrics = {n: {"value": v, "unit": cell.units[n]}
               for n, v in values.items() if v is not None}

    dev_out = dict(dev, memory_peak_bytes=int(
        memory_stats.get("peak_bytes_in_use", 0)))
    line = {"correct": verdict["correct"], "attempted": len(records),
            "failed": failed, "metrics": metrics, "device": dev_out}
    if trace is not None:
        dev_out.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if args.rehearse_cpu:
        line["rehearsal"] = "cpu backend: no number here is a device metric"
    line["checks"] = dict(verdict["numbers"], compared=verdict["compared"])
    return line


def _say_walls(records, say) -> None:
    """Per query of the window: how its collects' walls lie, and how many
    of them traced or loaded a program again (`compile_ms` in the engine's
    counters of that collect)."""
    for q in dict.fromkeys(r.query for r in records):
        mine = [r for r in records if r.query == q and r.error is None]
        if not mine:
            continue
        walls = sorted(1e3 * r.wall_s for r in mine)
        again = [r.metrics["compile_ms"] for r in mine
                 if r.metrics and r.metrics.get("compile_ms")]
        say(f"  {q}: {len(walls)} collects, wall ms min {walls[0]:.2f} "
            f"median {statistics.median(walls):.2f} max {walls[-1]:.2f}; "
            f"{len(again)} with compile_ms (sum {sum(again):.0f} ms)")


WARM_UP_ROUNDS = 4


def _compile_service(session):
    from spark_rapids_tpu.runtime.compile_service import (background_enabled,
                                                          get_service)
    return get_service(session.conf) if background_enabled(session.conf) \
        else None


def _settle(service) -> float:
    """Wait until the engine's background compile service is idle.  Its
    threads also count a cache request as a miss until the hit comes in, so
    the compile counters are read only after this."""
    t = time.perf_counter()
    while service is not None and service.pending():
        time.sleep(0.05)
    return time.perf_counter() - t


def _warm_up(calls, service, persistent_cache_stats, say) -> None:
    """Split plans compile candidate programs for their next segment on
    background threads, and with an empty cache that backlog outlives the
    first collects (PERF.md section 6, PR 24: without this wait the cold
    run's window read 2 queries in 196 s).  So: wait until the compile
    service is idle, then collect every query, at least twice round, until
    a whole round compiles nothing.  All of it is set-up."""
    for attempt in range(WARM_UP_ROUNDS):
        waited = _settle(service)
        pc0, walls = persistent_cache_stats(), []
        for _q, call in calls:
            t = time.perf_counter()
            call()
            walls.append(time.perf_counter() - t)
        waited += _settle(service)
        pc1 = persistent_cache_stats()
        say(f"warm-up round {attempt + 1}: waited {waited:.1f}s for "
            f"background compiles, collects "
            f"{[round(w * 1e3, 1) for w in walls]} ms, compile cache hits "
            f"{pc1['hits'] - pc0['hits']} misses "
            f"{pc1['misses'] - pc0['misses']}")
        if attempt and pc1["misses"] <= pc0["misses"]:
            return
    say(f"still compiling after {WARM_UP_ROUNDS} warm-up rounds")


def _traced_slice(cell, calls, inspect, args, say):
    """A short steady slice under the profiler, each collect inside a
    `collect:<query>` annotation; reduced here, raw files deleted."""
    import jax
    count = int(args.trace_queries or cell.spec["loop"]["trace_queries"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0        # keep the host's path as timed
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        sliced = cell.loop.run(
            calls, count=count, inspect=inspect,
            annotate=lambda q: jax.profiler.TraceAnnotation(
                trace_reduce.ANNOTATION + q))
    finally:
        jax.profiler.stop_trace()
    try:
        files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise SystemExit("benchmark FAILED: the profiler wrote no "
                             ".xplane.pb")
        say(f"trace of {count} queries: {os.path.getsize(files[0])} bytes")
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".",
                        exist_ok=True)
            shutil.copyfile(files[0], args.keep_trace)
        trace = trace_reduce.reduce_file(files[0])
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if trace is None:
        if not args.rehearse_cpu:
            raise SystemExit("benchmark FAILED: the trace holds no device "
                             "op inside the collect annotations")
        say("trace holds no device plane (CPU rehearsal): trace metrics "
            "left out")
    return trace, sliced


def print_result(line: dict, prefix: str = "") -> None:
    """The compared numbers as the last lines on stderr, the result as the
    last line on stdout."""
    sys.stdout.flush()
    for name, n in line["checks"].items():
        if isinstance(n, dict):
            print(f"{prefix}check {name}: value {n['value']!r} limit "
                  f"{n['limit']!r}", file=sys.stderr)
    print(f"{prefix}correct {line['correct']} over {line['checks']['compared']} "
          f"answers", file=sys.stderr, flush=True)
    print(prefix + json.dumps(line), flush=True)
