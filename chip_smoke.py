#!/usr/bin/env python
"""Chip smoke: drive the TPC-H main path once on the TPU through the entry
points a user has, and check every answer against the CPU oracle.

One process, default conf (compile.wholePlan=AUTO is what is tested):

  1. single-query leg — TpuSession -> from_arrow -> DataFrame ->
     physical().collect() for one query of each class (q6 filter->agg, q1
     group-by, q3 join+agg+top-N, q5 multi-join, q13 outer join+sort): one
     cold collect (seconds, compile_ms, persistent-cache hits/misses), then
     --warm timed collects, then the oracle comparison outside the timing;
  2. scan leg — lineitem written to Parquet, q6 through read_parquet;
  3. eager leg — q6 with compile.wholePlan=OFF (the engine every memory
     rung lands on);
  4. serving leg — session.serving() in-process, two tenants, every query
     once each, every ticket oracle-equal, close() clean.

It fails (non-zero exit, no result line) on any exception, oracle mismatch,
per-operator CPU fallback, whole-plan fallback, OOM replay, or XLA compile
inside a warm collect.  Nothing is caught and carried on from.

Without a TPU it exits non-zero before generating data.  --rehearse-cpu is
the sandbox dry run (tiny --scale; every line says REHEARSAL platform=cpu);
it is never a fallback.

    python chip_smoke.py                          # SF1, the default legs
    python chip_smoke.py --scale 10 --queries q1,q6,q3
    python chip_smoke.py --mesh 4                 # q1/q6/q12 over 4 chips

The single-query leg times a KEPT `PhysicalQuery` (`dfq.physical()` once,
then `q.collect()` again and again): its warm collects hold on to the
compiled plan and to the plan nodes' device lanes.  `DataFrame.collect()`
planned anew every time until ISSUE 33 (an unchanged DataFrame now keeps
its `PhysicalQuery`, unpinned between collects), so until ISSUE 32 a warm
`--mesh` collect here (q6 10.4 ms at SF1, PR 21) was NOT what
`DataFrame.collect()` paid on a mesh: that traced, lowered and loaded the
program again and put every lane onto the mesh again, in every collect.
The benchmark's cell `tpch-sf10.mesh4` times `DataFrame.collect()` itself.

The last stdout line is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import time

T0 = time.perf_counter()
PREFIX = ""


def say(msg: str) -> None:
    print(f"{PREFIX}[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    """A smoke assertion (not `assert`: -O must not switch it off)."""
    if not cond:
        raise SystemExit(f"{PREFIX}chip_smoke FAILED: {msg}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="TPC-H scale factor (default 1: 6.0M lineitem rows)")
    ap.add_argument("--seed", type=int, default=20240706,
                    help="datagen seed (tpch.gen_tables)")
    ap.add_argument("--queries", default=None,
                    help="comma list (default q6,q1,q3,q5,q13; with --mesh "
                         "q1,q6,q12)")
    ap.add_argument("--warm", type=int, default=5,
                    help="timed warm collects per query (default 5)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="run the single-query leg SPMD over this many "
                         "chips (sql.mesh.enabled); fewer devices is an error")
    ap.add_argument("--out", default=os.path.join("chiprun_out",
                                                  "chip_smoke"),
                    help="output directory (report + the scan leg's Parquet)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox dry run on the CPU backend; never a "
                         "fallback")
    return ap.parse_args(argv)


def device_header(args):
    """First contact with jax: platform, kind, count, versions.  Exits
    non-zero at once when no TPU was found (unless rehearsing)."""
    global PREFIX
    import jax
    if args.rehearse_cpu:
        PREFIX = "REHEARSAL platform=cpu "
        jax.config.update("jax_platforms", "cpu")
        if args.mesh:
            jax.config.update("jax_num_cpu_devices", args.mesh)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    import importlib.metadata as md
    import jaxlib

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"
    say(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={version('libtpu')} "
        f"python={sys.version.split()[0]}")
    if dev["platform"] != "tpu" and not args.rehearse_cpu:
        raise SystemExit(
            f"chip_smoke FAILED: no TPU was found — jax reports "
            f"platform={dev['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). This script proves "
            f"the engine on the chip and has no CPU fallback; "
            f"--rehearse-cpu is the sandbox dry run.")
    check(not args.rehearse_cpu or dev["platform"] == "cpu",
          "--rehearse-cpu did not land on the CPU backend")
    if args.mesh:
        check(dev["count"] >= args.mesh,
              f"--mesh {args.mesh} needs {args.mesh} devices, jax found "
              f"{dev['count']}")
    return dev


class ParquetSource:
    """Hands a tpch query a Parquet scan where it asks the session for the
    in-memory table: the query text stays tpch.py's."""

    def __init__(self, session, path):
        self._session, self._path = session, path

    def from_arrow(self, _table):
        return self._session.read_parquet(self._path)


class Smoke:
    def __init__(self, args, dev):
        from spark_rapids_tpu.session import TpuSession
        self.args = args
        self.report = {"device": dev, "scale": args.scale, "seed": args.seed,
                       "rehearsal": bool(args.rehearse_cpu),
                       "date": time.strftime("%Y-%m-%d"), "queries": {}}
        conf = {}
        if args.mesh:
            conf = {"spark.rapids.tpu.sql.mesh.enabled": True,
                    "spark.rapids.tpu.sql.mesh.devices": args.mesh}
        if args.rehearse_cpu:
            # AUTO is the eager engine off-TPU; the dry run has to walk
            # the code the chip run will
            conf["spark.rapids.tpu.sql.compile.wholePlan"] = "ON"
            say("compile.wholePlan=ON forced for the rehearsal (on the "
                "chip the default conf is what runs)")
        self.dev = TpuSession(conf)
        self.cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
        import jax
        self.report["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
        say(f"compile cache: {self.report['compile_cache_dir']} "
            f"(JAX_COMPILATION_CACHE_DIR="
            f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '')!r})")
        self.oracles = {}

    # -- observations (not metrics) ---------------------------------------
    def platform_probes(self):
        import jax
        import numpy as np
        import bench
        rtt = bench.measure_rtt()
        host = np.arange(8 << 20, dtype=np.int64)           # 64 MiB
        t0 = time.perf_counter()
        on_dev = jax.block_until_ready(jax.device_put(host))
        h2d_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = np.asarray(on_dev)
        d2h_s = time.perf_counter() - t0
        check(back[-1] == host[-1], "64 MiB H2D/D2H round trip corrupted")
        obs = {"sync_rtt_ms_median": rtt * 1e3,
               "h2d_64MiB_s": h2d_s, "h2d_GBps": host.nbytes / h2d_s / 1e9,
               "d2h_64MiB_s": d2h_s, "d2h_GBps": host.nbytes / d2h_s / 1e9}
        self.report["platform"] = obs
        say(f"observation: sync round trip median {rtt * 1e3:.3f} ms; "
            f"64 MiB H2D {h2d_s:.3f}s ({obs['h2d_GBps']:.2f} GB/s), "
            f"D2H {d2h_s:.3f}s ({obs['d2h_GBps']:.2f} GB/s)")

    def upload_probe(self, tables):
        """The engine's own upload of one whole table (`orders`) through
        the ColumnarRdd escape hatch: host encode + H2D, synced."""
        import jax
        tbl = tables["orders"]
        t0 = time.perf_counter()
        dbs = list(self.dev.from_arrow(tbl).device_batches())
        jax.block_until_ready([(c.data, c.validity)
                               for db in dbs for c in db.columns])
        secs = time.perf_counter() - t0
        dev_bytes = sum(db.nbytes() for db in dbs)
        self.report["upload_orders"] = {
            "rows": tbl.num_rows, "arrow_bytes": tbl.nbytes,
            "device_bytes": dev_bytes, "seconds": secs}
        say(f"observation: engine upload of orders ({tbl.num_rows} rows, "
            f"{tbl.nbytes} Arrow B -> {dev_bytes} device B) {secs:.2f}s "
            f"= {tbl.nbytes / secs / 1e6:.0f} MB/s incl. host encode")

    @staticmethod
    def fallback_instants():
        """reason + error head of every whole_plan_fallback instant still
        in the always-on flight recorder."""
        from spark_rapids_tpu.obs.recorder import FLIGHT_RECORDER
        return [r.get("attrs") for r in FLIGHT_RECORDER.tail()
                if r.get("name") == "whole_plan_fallback"]

    @staticmethod
    def moved_bytes():
        from spark_rapids_tpu.obs.registry import DATA_BYTES
        return {s["labels"]["channel"]: int(s["value"])
                for s in DATA_BYTES.series()}

    # -- one checked collect -------------------------------------------------
    def collect(self, q, session, label, whole_plan=True, warm=False):
        """-> (table, seconds, ctx.metrics, persistent-cache delta), with
        every no-hidden-fallback assertion applied."""
        from spark_rapids_tpu.exec.compiled import persistent_cache_stats
        from spark_rapids_tpu.exec.plan import ExecContext
        check(q.kind == "device", f"{label}: plan kind is {q.kind!r}")
        check(q.fallback_reasons() == [],
              f"{label}: per-operator CPU fallback {q.fallback_reasons()}")
        pc0 = persistent_cache_stats()
        ctx = ExecContext(session.conf)
        t0 = time.perf_counter()
        out = q.collect(ctx)
        secs = time.perf_counter() - t0
        pc1 = persistent_cache_stats()
        pc = {k: pc1[k] - pc0[k] for k in pc1}
        m = ctx.metrics
        check(m.get("whole_plan_fallbacks", 0) == 0,
              f"{label}: whole-plan program fell back to the eager engine "
              f"({secs:.1f}s): {self.fallback_instants()}")
        check(m.get("whole_plan_compiled_queries", 0) == int(whole_plan),
              f"{label}: whole_plan_compiled_queries="
              f"{m.get('whole_plan_compiled_queries', 0)}, expected "
              f"{int(whole_plan)}")
        check(not m.get("query_oom_replays"),
              f"{label}: {m.get('query_oom_replays')} OOM replay(s)")
        check(not m.get("query_ooc_escalations"),
              f"{label}: escalated to the out-of-core tier")
        if warm:
            check(pc["misses"] == 0,
                  f"{label}: {pc['misses']} XLA compile(s) inside a warm "
                  f"collect")
        return out, secs, m, pc

    def oracle_equal(self, name, dfq, out, label):
        """CPU oracle (the pyarrow path), outside every timed region."""
        import bench
        from spark_rapids_tpu.session import DataFrame
        if name not in self.oracles:
            t0 = time.perf_counter()
            self.oracles[name] = DataFrame(dfq._plan, self.cpu).collect()
            say(f"{name}: cpu oracle {time.perf_counter() - t0:.1f}s "
                f"({self.oracles[name].num_rows} rows)")
        oracle = self.oracles[name]
        check(out.num_rows == oracle.num_rows and out.num_rows > 0,
              f"{label}: {out.num_rows} rows, oracle {oracle.num_rows}")
        check(bench.approx_equal(out, oracle),
              f"{label}: result differs from the CPU oracle")

    # -- legs -------------------------------------------------------------------
    def single_query_leg(self, tables, names):
        from spark_rapids_tpu import tpch
        self.dfs = {}
        for name in names:
            dfq = self.dfs[name] = tpch.QUERIES[name](self.dev, tables)
            q = dfq.physical()
            b0 = self.moved_bytes()
            out, cold_s, m, pc = self.collect(q, self.dev, f"{name} cold")
            b1 = self.moved_bytes()
            warm = []
            for i in range(self.args.warm):
                out_w, secs, _m, _pc = self.collect(
                    q, self.dev, f"{name} warm#{i}", warm=True)
                warm.append(secs)
            self.oracle_equal(name, dfq, out, f"{name} cold")
            self.oracle_equal(name, dfq, out_w, f"{name} warm")
            rec = {"cold_s": cold_s,
                   "compile_ms": m.get("compile_ms", 0.0),
                   "pcache_cold": pc,
                   "split": bool(m.get("whole_plan_split_queries")),
                   "seams": m.get("overhead.seam_count", 0),
                   "h2d_bytes_cold": b1.get("h2d", 0) - b0.get("h2d", 0),
                   "d2h_bytes_cold": b1.get("d2h", 0) - b0.get("d2h", 0),
                   "warm_s": warm,
                   "warm_median_ms": statistics.median(warm) * 1e3,
                   "warm_min_ms": min(warm) * 1e3,
                   "warm_max_ms": max(warm) * 1e3,
                   "rows": out.num_rows}
            self.report["queries"][name] = rec
            say(f"{name}: cold {cold_s:.1f}s (compile_ms "
                f"{rec['compile_ms']:.0f}, pcache hits {pc['hits']} misses "
                f"{pc['misses']}, h2d {rec['h2d_bytes_cold']} B) | warm "
                f"median {rec['warm_median_ms']:.1f} ms of "
                f"{len(warm)} [min {rec['warm_min_ms']:.1f}, max "
                f"{rec['warm_max_ms']:.1f}] | {out.num_rows} rows, "
                f"oracle-equal, whole-plan"
                f"{' split' if rec['split'] else ''}")

    def scan_leg(self, tables):
        """q6 over a Parquet scan — the reference's task shape."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu import tpch
        path = os.path.join(self.args.out, "scan_leg", "lineitem.parquet")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            pq.write_table(tables["lineitem"], path)
            dfq = tpch.q6(ParquetSource(self.dev, path), tables)
            out, secs, m, pc = self.collect(dfq.physical(), self.dev,
                                            "scan q6")
            self.oracle_equal("q6", tpch.q6(self.cpu, tables), out,
                              "scan q6")
            self.report["scan_q6"] = {
                "seconds": secs, "parquet_bytes": os.path.getsize(path),
                "pcache": pc}
            say(f"scan leg: q6 over read_parquet {secs:.1f}s "
                f"({os.path.getsize(path)} B file, pcache misses "
                f"{pc['misses']}), oracle-equal, whole-plan")
        finally:
            # the output directory travels back from the chip machine:
            # the table does not ride along
            shutil.rmtree(os.path.dirname(path), ignore_errors=True)

    def eager_leg(self, tables):
        """q6 on the eager per-operator engine (compile.wholePlan=OFF)."""
        from spark_rapids_tpu import tpch
        from spark_rapids_tpu.session import TpuSession
        eager = TpuSession({"spark.rapids.tpu.sql.compile.wholePlan": "OFF"})
        dfq = tpch.q6(eager, tables)
        q = dfq.physical()
        out, cold_s, _m, _pc = self.collect(q, eager, "eager q6",
                                            whole_plan=False)
        out, warm_s, _m, _pc = self.collect(q, eager, "eager q6 warm",
                                            whole_plan=False)
        self.oracle_equal("q6", dfq, out, "eager q6")
        self.report["eager_q6"] = {"cold_s": cold_s, "warm_s": warm_s}
        say(f"eager leg: q6 with wholePlan=OFF cold {cold_s:.1f}s, warm "
            f"{warm_s * 1e3:.1f} ms, oracle-equal")

    def serving_leg(self, names):
        """session.serving() in-process: two tenants, every query once
        each, every ticket answered and oracle-equal, close() clean."""
        rt = self.dev.serving()
        t0 = time.perf_counter()
        tickets = [(tenant, name, rt.tenant(tenant).submit(self.dfs[name]))
                   for tenant in ("bi", "etl") for name in names]
        for tenant, name, ticket in tickets:
            out = ticket.result(timeout=600.0)
            self.oracle_equal(name, self.dfs[name], out,
                              f"serving {tenant}/{name}")
        wall = time.perf_counter() - t0
        stats = rt.stats()
        check(stats["completed"] == len(tickets) and stats["inflight"] == 0,
              f"serving: {stats['completed']}/{len(tickets)} completed, "
              f"{stats['inflight']} in flight")
        self.dev.close()                     # closes the runtime it owns
        self.report["serving"] = {
            "tickets": len(tickets), "wall_s": wall,
            "device_slots": stats["device_slots"],
            "result_cache": stats["result_cache"],
            "hbm_limit_bytes": stats["hbm_limit_bytes"]}
        say(f"serving leg: {len(tickets)} tickets from 2 tenants in "
            f"{wall:.1f}s, all oracle-equal; device_slots="
            f"{stats['device_slots']} result_cache={stats['result_cache']}; "
            f"closed clean")

    def memory_peak(self):
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        keep = {k: stats[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "bytes_limit") if k in stats}
        self.report["memory_stats"] = keep
        self.report["moved_bytes"] = self.moved_bytes()
        say(f"observation: memory_stats {keep or 'not reported'}; bytes "
            f"moved {self.report['moved_bytes']}")


def main(argv=None) -> int:
    args = parse_args(argv)
    dev = device_header(args)
    from spark_rapids_tpu import tpch
    names = (args.queries.split(",") if args.queries else
             ["q1", "q6", "q12"] if args.mesh else
             ["q6", "q1", "q3", "q5", "q13"])
    for n in names:
        check(n in tpch.QUERIES, f"unknown query {n!r}")
    smoke = Smoke(args, dev)
    smoke.platform_probes()

    t0 = time.perf_counter()
    tables = tpch.gen_tables(scale=args.scale, seed=args.seed)
    nbytes = sum(t.nbytes for t in tables.values())
    smoke.report["datagen"] = {
        "seconds": time.perf_counter() - t0, "arrow_bytes": nbytes,
        "rows": {k: t.num_rows for k, t in tables.items()}}
    say(f"datagen SF{args.scale:g} seed={args.seed}: "
        f"{time.perf_counter() - t0:.1f}s, lineitem="
        f"{tables['lineitem'].num_rows} orders={tables['orders'].num_rows} "
        f"rows, {nbytes / 1e9:.2f} GB of Arrow")

    smoke.upload_probe(tables)
    smoke.single_query_leg(tables, names)
    if not args.mesh:
        smoke.scan_leg(tables)
        smoke.eager_leg(tables)
        smoke.serving_leg(names)
    smoke.memory_peak()

    smoke.report["elapsed_s"] = time.perf_counter() - T0
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(smoke.report, f, indent=1, default=str)
    say(f"all legs passed in {smoke.report['elapsed_s']:.0f}s; report in "
        f"{os.path.join(args.out, 'chip_smoke.json')}")
    result = {"ok": True, "device": dev}
    if args.rehearse_cpu:
        result["rehearsal"] = PREFIX.strip()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
