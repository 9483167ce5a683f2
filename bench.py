#!/usr/bin/env python
"""Headline benchmark: the TPC-H suite (default) or the TPC-DS tranche
(--suite tpcds) at SF>=1.

Prints a running JSON summary line after EVERY query (flushed), so a
timeout kill at any point still leaves a complete, parseable result as
the last stdout line — a perf harness that can fail to report is itself
a defect (VERDICT r3).  The final line covers every query measured.

Headline metric: geometric-mean speedup of per-query WARM wall time
(device engine / whole-plan XLA compilation) over the SAME queries on
the engine's CPU fallback (vectorized pyarrow kernels — a stronger
stand-in for CPU Spark than Spark itself: columnar C++ kernels, no
JVM/task overhead, so the reported speedup is conservative vs the
BASELINE.md north star).

Methodology.
  * Every query runs BOTH engines from the same in-memory tables and
    results are cross-checked (float tails to 1e-6 relative — reduction
    order differs, as the reference documents for GPU float aggs).
  * Device timing is single-shot warm wall time: one whole-plan XLA
    dispatch + one result fetch, measured after the one-time costs
    (compile — persisted to the jax compilation cache; H2D upload —
    tables are device-resident across queries, the buffer-cache role).
    It INCLUDES one host sync round trip per query; the measured round
    trip is also reported separately so the engine-time floor is
    visible.  CPU timing is the same warm single-shot discipline.
  * Cold numbers (first-run compile or cache load, upload) are reported
    per query and as a median; a persistent-cache hit shows up as a
    small cold time.
  * Time budgets: BENCH_BUDGET_S (default 1800, TOTAL_BUDGET_S below)
    total; queries that don't fit are listed in "skipped" rather than
    silently absent.

Every per-query record embeds a "profile" summary from one extra traced
(untimed) collect — the compile/execute/transition/shuffle wall split,
top operators by self time, data-movement bytes, memory high-water and
runtime incidents (obs/profile.py) — so the JSON explains where each
query's time goes, not just how much there is.

--suite tpcds additionally reports the operator-coverage matrix the
BASELINE.md staged config #2 asks for: per-query fallback reasons (from
the overrides tagger), sort_operand_max and scatter_op_count (jaxpr
lints, testing.py), and a top-level coverage summary splitting queries
into device-clean / with-fallbacks / not-whole-plan-traceable.

Exit code: non-zero when any query recorded an error or an oracle
mismatch, and when the backend is not a TPU unless the CPU was asked for
explicitly (JAX_PLATFORMS=cpu) — the JSON is printed first either way.

Run: python bench.py [scale] [--queries q1,q6,...] [--suite tpch|tpcds]
"""
import json
import os
import sys
import time

import numpy as np

import jax

# Persistent compile cache: cold compiles are paid once per (plan,
# shape); later runs trace + load with ZERO XLA compiles (the hit/miss
# counters below prove it per run).  TpuSession() places it
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).

# With a primed compile cache (same disk), 22 queries need ~10-20 min
# (cache loads + warm timing + the CPU oracle, which alone costs ~70s on
# q21); the incremental JSON emit makes an external kill lossless, so a
# generous default just maximizes what gets measured.
TOTAL_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1800"))
_T0 = time.perf_counter()


def left() -> float:
    return TOTAL_BUDGET_S - (time.perf_counter() - _T0)


def measure_rtt() -> float:
    """Median host<->device sync round trip (a 4-byte fetch of a fresh
    device-computed value) — the floor every host sync pays."""
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((1,), jnp.int32)
    jax.device_get(f(x))
    times = []
    for _ in range(11):
        t0 = time.perf_counter()
        # a fresh device-computed value: the fetch must round-trip
        jax.device_get(f(x))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def approx_equal(a, b) -> bool:
    da, db = a.to_pydict(), b.to_pydict()
    if set(da) != set(db):
        return False
    for k in da:
        if len(da[k]) != len(db[k]):
            return False
        for x, y in zip(da[k], db[k]):
            if x == y:
                continue
            if isinstance(x, float) and isinstance(y, float) and \
                    abs(x - y) <= 1e-6 * max(1.0, abs(x), abs(y)):
                continue
            return False
    return True


def time_warm(fn, iters=3):
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def query_profile(q, conf) -> dict:
    """One traced (untimed) collect -> the compact QueryProfile summary
    embedded per query, so BENCH_*.json explains its own numbers: the
    compile/execute/transition/shuffle split, top operators by self
    time, PER-SEGMENT measured device ms (profile.segments forced on
    for this collect — the attribution check_regression/profile_diff
    cite), data-movement bytes and memory high-water.  Runs AFTER the
    warm timing so span collection can't perturb the headline number."""
    from spark_rapids_tpu.config import (PROFILE_SEGMENTS, TRACE_ENABLED,
                                         TpuConf)
    from spark_rapids_tpu.exec.plan import ExecContext
    from spark_rapids_tpu.obs.profile import QueryProfile
    pctx = ExecContext(TpuConf({**conf._raw, TRACE_ENABLED.key: "true",
                                PROFILE_SEGMENTS.key: "true"}))
    q.collect(pctx)
    return QueryProfile.from_context(pctx).summary()


class Suite:
    def __init__(self, name: str, scale: float, rtt: float):
        self.name = name
        self.scale = scale
        self.rtt = rtt
        self.per_q = {}
        self.skipped = []
        self.compiled_ct = 0
        self.extra_conf = {}
        # metrics-plane A/B: q6 warm wall with the always-on registry +
        # flight recorder active vs spark.rapids.tpu.metrics.enabled=false
        # (the overhead bound the metrics plane claims — docs/METRICS.md)
        self.metrics_overhead = None
        # performance-history store stats when --history-dir recorded
        # this run (structures, records, calibration)
        self.history = None
        # measured per-backend dispatch floor (exec/compiled.py), the
        # irreducible ms one compiled-program launch costs here
        self.dispatch_floor_ms = None

    def overhead_share(self):
        """Suite-level fixed-overhead fraction: dispatch + seam + pad
        waste over the summed profiled walls of queries that carried a
        wall_breakdown embed; None before any did."""
        ov = wall = 0.0
        for v in self.per_q.values():
            bd = v.get("wall_breakdown")
            if isinstance(bd, dict) and bd.get("wall_ms"):
                wall += float(bd["wall_ms"])
                ov += float(bd.get("dispatch_ms", 0.0)) \
                    + float(bd.get("seam_ms", 0.0)) \
                    + float(bd.get("pad_waste_ms", 0.0))
        return round(ov / wall, 4) if wall else None

    def coverage(self) -> dict:
        """Operator-coverage matrix: which queries run device-clean,
        which carry fallbacks (and why), which cannot trace as one
        whole-plan program (per-query stats stay None for those)."""
        clean, with_fb, untraceable = [], {}, []
        for name, v in self.per_q.items():
            fb = v.get("fallback_reasons") or []
            if fb:
                with_fb[name] = fb
            else:
                clean.append(name)
            if v.get("sort_operand_max") is None and "error" not in v:
                untraceable.append(name)
        return {"device_clean": sorted(clean),
                "with_fallbacks": with_fb,
                "not_whole_plan_traceable": sorted(untraceable)}

    def emit(self, final: bool = False):
        speedups = [v["speedup"] for v in self.per_q.values()
                    if v["speedup"] is not None]
        geomean = float(np.exp(np.mean(np.log(speedups)))) \
            if speedups else 0.0
        net = [v["speedup_net"] for v in self.per_q.values()
               if v.get("speedup_net")]
        geomean_net = float(np.exp(np.mean(np.log(net)))) if net else 0.0
        errors = sum(1 for v in self.per_q.values() if "error" in v)
        colds = sorted(v["cold_s"] for v in self.per_q.values()
                       if "error" not in v)
        med_cold = colds[len(colds) // 2] if colds else None
        cms = sorted(v.get("compile_ms_cold") for v in self.per_q.values()
                     if v.get("compile_ms_cold") is not None)
        med_compile_ms = cms[len(cms) // 2] if cms else None
        try:
            from spark_rapids_tpu.exec.compiled import \
                persistent_cache_stats
            pcache = persistent_cache_stats()
        except Exception:                    # noqa: BLE001
            pcache = None
        scale = self.scale
        out = {
            "metric": f"{self.name}_sf{scale:g}_suite_geomean_speedup"
                      f"_vs_cpu",
            "value": round(geomean, 3),
            "unit": "x",
            "vs_baseline": round(geomean, 3),
            "suite": self.name,
            f"{self.name}_suite_scale": scale,
            f"{self.name}_suite_queries": self.per_q,
            f"{self.name}_suite_geomean_speedup": round(geomean, 3),
            f"{self.name}_suite_geomean_speedup_net": round(geomean_net, 3),
            "backend": jax.default_backend(),
            "extra_conf": self.extra_conf,
            "coverage": self.coverage(),
            "queries_measured": len(self.per_q),
            "errors": errors,
            "skipped": self.skipped,
            "final": final,
            "whole_plan_compiled": self.compiled_ct,
            "sort_operand_max": max(
                (v.get("sort_operand_max") or 0
                 for v in self.per_q.values()), default=0),
            "scatter_op_total": sum(
                v.get("scatter_op_count") or 0
                for v in self.per_q.values()),
            "median_cold_s": med_cold,
            "median_compile_ms": med_compile_ms,
            "pcache": pcache,
            "sync_rtt_ms": round(self.rtt * 1e3, 3),
            "metrics_overhead": self.metrics_overhead,
            "dispatch_floor_ms": self.dispatch_floor_ms,
            "overhead_share": self.overhead_share(),
            "history": self.history,
            "elapsed_s": round(time.perf_counter() - _T0, 1),
            "note": "warm single-shot wall per query (one whole-plan XLA "
                    "dispatch + one fetch, device-resident tables, compile "
                    "cached); INCLUDES one host sync per query — "
                    "sync_rtt_ms is that floor and device_ms_net/"
                    "speedup_net subtract it (the engine-controllable "
                    "time; the regression gate compares net values). "
                    "CPU baseline = "
                    "same queries on the engine's vectorized pyarrow "
                    "fallback, warm (arrow decimal128 kernels, no python "
                    "row loops). Incremental line: last stdout line is "
                    "always the complete current result.",
        }
        if final:
            # the always-on metrics-plane snapshot: process-wide data
            # movement / spill / retry / skew telemetry accumulated over
            # the whole run rides with the result (obs/registry.py)
            try:
                from spark_rapids_tpu.obs.export import registry_snapshot
                out["registry"] = registry_snapshot(compact=True)
            except Exception as e:               # noqa: BLE001
                out["registry"] = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps(out), flush=True)


#: --conf key=value session overrides (applied to the DEVICE session
#: only; the CPU oracle baseline never sees them)
EXTRA_CONF = {}


def run_suite(suite_name: str, scale: float, query_names):
    import importlib
    workload = importlib.import_module(f"spark_rapids_tpu.{suite_name}")
    from spark_rapids_tpu.exec.plan import ExecContext
    from spark_rapids_tpu.session import DataFrame, TpuSession

    rtt = measure_rtt()
    print(f"# backend={jax.default_backend()} sync RTT ~{rtt*1e3:.3f}ms "
          f"per host sync", file=sys.stderr)
    # the measured per-backend dispatch floor: header context for every
    # per-query wall_breakdown embed below (fail-soft — its absence
    # loses one report line, never the run)
    try:
        from spark_rapids_tpu.exec.compiled import dispatch_floor_ms
        floor = round(dispatch_floor_ms(), 4)
        print(f"# dispatch floor ~{floor:.3f}ms per compiled-program "
              f"launch on {jax.default_backend()}", file=sys.stderr)
    except Exception:                        # noqa: BLE001
        floor = None

    t0 = time.perf_counter()
    tables = workload.gen_tables(scale=scale)
    gen_s = time.perf_counter() - t0
    biggest = max(tables, key=lambda k: tables[k].num_rows)
    print(f"# datagen {suite_name} SF{scale}: {gen_s:.1f}s "
          f"{biggest}={tables[biggest].num_rows}", file=sys.stderr)

    # whole-plan compile forced ON: the bench methodology IS "one XLA
    # dispatch + one fetch" (docstring), and AUTO would silently fall
    # back to the eager batch engine on non-TPU backends — a different
    # engine than the one the headline number claims to measure
    from spark_rapids_tpu.config import WHOLE_PLAN_COMPILE
    dev = TpuSession({WHOLE_PLAN_COMPILE.key: "ON", **EXTRA_CONF})
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})

    suite = Suite(suite_name, scale, rtt)
    suite.extra_conf = dict(EXTRA_CONF)
    suite.dispatch_floor_ms = floor
    for name in query_names:
        if left() < 20:
            suite.skipped.append(name)
            continue
        try:
            from spark_rapids_tpu.exec.compiled import \
                persistent_cache_stats
            dfq = workload.QUERIES[name](dev, tables)
            q = dfq.physical()
            # cold: compile (or cache load) + device upload + first run;
            # the persistent-cache counter DELTA across it is the proof
            # of a warmed replay (0 misses = zero XLA compiles)
            pc0 = persistent_cache_stats()
            cctx = ExecContext(dev.conf)
            # history-plane label: the recorded structure carries the
            # query name so history_report / drift citations read qN,
            # not a bare digest (no-op when the plane is off)
            cctx.metrics["history.label"] = name
            t0 = time.perf_counter()
            out = q.collect(cctx)
            cold_s = time.perf_counter() - t0
            pc1 = persistent_cache_stats()
            compile_ms_cold = round(cctx.metrics.get("compile_ms", 0.0), 1)
            iters = 3 if left() > 120 else 1
            dt = time_warm(lambda: q.collect(ExecContext(dev.conf)),
                           iters=iters)
            ctx = ExecContext(dev.conf)
            out = q.collect(ctx)
            compile_ms_warm = round(ctx.metrics.get("compile_ms", 0.0), 1)
            compiled = ctx.metrics.get("whole_plan_compiled_queries", 0)
            suite.compiled_ct += compiled

            cq = DataFrame(dfq._plan, cpu).physical()
            oracle = cq.collect()
            ct = time_warm(lambda: cq.collect(), iters=2)

            # regression-surface metrics from the emitted program: the
            # widest sort (compile-time cliff) and the scatter count
            # (runtime cliff).  Tracked per query so
            # the perf trajectory sees the cause, not just wall time.
            try:
                from spark_rapids_tpu.testing import plan_program_stats
                pstats = plan_program_stats(q, ExecContext(dev.conf))
            except Exception:                # noqa: BLE001
                pstats = {"sort_operand_max": None,
                          "scatter_op_count": None}
            # the traced profile run is untimed and budget-gated: its
            # absence loses explanation, never measurement
            try:
                profile = query_profile(q, dev.conf) if left() > 30 \
                    else None
            except Exception as e:           # noqa: BLE001
                profile = {"error": f"{type(e).__name__}: {e}"[:200]}
            match = approx_equal(out, oracle)
            # device_ms_net: the warm wall minus ONE host sync round
            # trip (the single dispatch+fetch every query pays) — the
            # floor-subtracted number is what the engine can actually
            # influence, and the regression gate compares it
            # (scripts/check_regression.py).
            dt_net = max(dt - suite.rtt, 1e-6)
            suite.per_q[name] = {"device_ms": round(dt * 1e3, 1),
                                 "device_ms_net": round(dt_net * 1e3, 1),
                                 "cpu_ms": round(ct * 1e3, 1),
                                 "speedup": round(ct / dt, 2),
                                 "speedup_net": round(ct / dt_net, 2),
                                 "cold_s": round(cold_s, 1),
                                 "compile_ms_cold": compile_ms_cold,
                                 "compile_ms_warm": compile_ms_warm,
                                 "pcache_hits": pc1["hits"] - pc0["hits"],
                                 "pcache_misses":
                                     pc1["misses"] - pc0["misses"],
                                 "compiled": bool(compiled),
                                 "match": match,
                                 "fallback_reasons":
                                     q.fallback_reasons(),
                                 "profile": profile, **pstats}
            # per-query HBM attribution (memattr plane, measured during
            # the profiled collect): top-level so check_regression.py
            # can gate >25% HBM-peak regressions next to device_ms
            if isinstance(profile, dict):
                for hk in ("hbm_peak_bytes", "hbm_measured_working_set"):
                    if profile.get(hk):
                        suite.per_q[name][hk] = int(profile[hk])
                # the wall-decomposition embed: top-level per query so
                # check_regression.py can gate seam-count and
                # pad-waste-share growth next to device_ms and hbm
                bd = profile.get("wall_breakdown")
                if isinstance(bd, dict) and bd.get("wall_ms"):
                    suite.per_q[name]["wall_breakdown"] = bd
            print(f"# {name}: device={dt*1e3:.0f}ms cpu={ct*1e3:.0f}ms "
                  f"x{ct/dt:.2f} cold={cold_s:.1f}s "
                  f"compiled={bool(compiled)} match={match}",
                  file=sys.stderr)
            if not match:
                print(f"# WARNING {name}: device != cpu oracle",
                      file=sys.stderr)
        except Exception as e:               # noqa: BLE001
            # a broken query must not take the whole suite's report down
            print(f"# ERROR {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            suite.per_q[name] = {"device_ms": None, "cpu_ms": None,
                                 "speedup": None, "cold_s": 0.0,
                                 "compiled": False, "match": False,
                                 "error": f"{type(e).__name__}: {e}"[:200]}
        suite.emit()
    suite.metrics_overhead = measure_metrics_overhead(workload, tables,
                                                      suite, dev)
    try:
        from spark_rapids_tpu.obs.history import get_store
        store = get_store(dev.conf)
        if store is not None:
            suite.history = store.stats()
    except Exception:                        # noqa: BLE001
        pass
    return suite


def run_compile_only(suite_name: str, scale: float, query_names):
    """--compile-only: pre-populate the compile caches WITHOUT timing
    anything — the CI warmup mode.  Every query's whole-plan program is
    AOT-compiled (PhysicalQuery.prewarm: trace + lower().compile(), no
    execution) on the background compile service's thread pool, so the
    suite's cold compile wall is max-over-threads instead of a serial
    sum, and the persistent cache ends up holding every program a
    subsequent timed run replays with zero XLA compiles."""
    import importlib
    workload = importlib.import_module(f"spark_rapids_tpu.{suite_name}")
    from spark_rapids_tpu.config import WHOLE_PLAN_COMPILE
    from spark_rapids_tpu.exec.compiled import persistent_cache_stats
    from spark_rapids_tpu.runtime.compile_service import get_service
    from spark_rapids_tpu.session import TpuSession

    tables = workload.gen_tables(scale=scale)
    dev = TpuSession({WHOLE_PLAN_COMPILE.key: "ON", **EXTRA_CONF})
    service = get_service(dev.conf)
    tasks = []
    for name in query_names:
        q = workload.QUERIES[name](dev, tables).physical()

        def thunk(q=q):
            t0 = time.perf_counter()
            ok = q.prewarm()
            return ok, time.perf_counter() - t0

        tasks.append((name, service.submit(
            ("compile-only", suite_name, name), thunk)))
    per_q = {}
    for name, task in tasks:
        try:
            ok, secs = task.wait(timeout=None)
            per_q[name] = {"compiled": bool(ok),
                           "compile_s": round(secs, 2)}
        except Exception as e:               # noqa: BLE001
            per_q[name] = {"compiled": False,
                           "error": f"{type(e).__name__}: {e}"[:200]}
        print(f"# {name}: {per_q[name]}", file=sys.stderr)
    out = {"mode": "compile-only",
           "suite": suite_name,
           f"{suite_name}_suite_scale": scale,
           "backend": jax.default_backend(),
           "queries": per_q,
           "compiled": sum(1 for v in per_q.values() if v["compiled"]),
           "pcache": persistent_cache_stats(),
           "elapsed_s": round(time.perf_counter() - _T0, 1),
           "final": True}
    print(json.dumps(out), flush=True)
    return not any("error" in v for v in per_q.values())


#: --encodings microbench sizes (rows) and selectivities
ENCODING_ROWS = 1 << 20
ENCODING_SELECTIVITIES = {"sel1": 0.01, "sel50": 0.5}


def run_encodings():
    """--encodings: encoded-vs-decode-first A/B microbenchmarks of the
    compressed device-resident execution layer (ISSUE 13) over
    predicate/join/agg x dict/RLE/FOR x 2 selectivities, emitting
    `encoding_timings_ms` entries scripts/check_regression.py gates
    under the `en:` prefix (same backend-separation rule as qN
    device_ms).

    Shapes per encoding:
      * dict — predicate: code-space equality (one scalar compare) vs
        the decode-first per-row remap-table gather; join: probe of
        dictionary-coded keys on codes vs probing decoded rank lanes;
        agg: 32-group code-keyed segment sums vs rank-decoded keys.
      * RLE  — predicate evaluated per RUN + rank-search mask expansion
        (ops/encodings.rle_predicate_mask) vs rle_decode-then-compare.
      * FOR  — predicate/arith on the value-preserving narrow lane
        (range-guarded compare, exact-width add) vs widen-then-compute.
    Selectivity levels move the predicate cut point (sel1 ~1% true,
    sel50 ~50% true) — code/narrow compares are selectivity-invariant,
    the decode-first gathers are too, so the ratio isolates the decode
    cost itself."""
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.bitpack import rle_decode
    from spark_rapids_tpu.ops.encodings import (narrow_compare,
                                                rle_predicate_mask)
    rng = np.random.default_rng(23)
    n = ENCODING_ROWS
    out = {}

    def timed(name, fn):
        jax.block_until_ready(fn())                      # compile+warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        out[name] = round(min(times) * 1e3, 2)
        print(f"# {name}: {out[name]}ms", file=sys.stderr)

    dict_size = 1024
    codes = jnp.asarray(rng.integers(0, dict_size, n), jnp.int32)
    remap = jnp.asarray(rng.permutation(dict_size).astype(np.int32))

    for sname, sel in ENCODING_SELECTIVITIES.items():
        if left() < 45:
            print(f"# budget: skipping encodings level {sname}",
                  file=sys.stderr)
            continue
        cut = max(int(dict_size * sel), 1)

        # -- dict: predicate (code-space vs remap-decode-first)
        @jax.jit
        def dict_pred_encoded(codes):
            return codes < cut                    # ordered dict: code IS rank

        @jax.jit
        def dict_pred_decoded(codes, remap):
            ranks = remap[jnp.clip(codes, 0, remap.shape[0] - 1)]
            return ranks < cut

        timed(f"dict_pred_{sname}_encoded", lambda: dict_pred_encoded(codes))
        timed(f"dict_pred_{sname}_decoded",
              lambda: dict_pred_decoded(codes, remap))

        # -- dict: join probe on codes vs on decoded rank lanes
        from spark_rapids_tpu.ops.join import _merge_rank
        bkeys = jnp.asarray(np.arange(dict_size), jnp.int64)

        @jax.jit
        def dict_join_encoded(codes):
            return _merge_rank(bkeys.astype(jnp.uint64),
                               codes.astype(jnp.uint64), side="left")

        @jax.jit
        def dict_join_decoded(codes, remap):
            lane = remap[jnp.clip(codes, 0, remap.shape[0] - 1)]
            return _merge_rank(jnp.sort(remap.astype(jnp.uint64)),
                               lane.astype(jnp.uint64), side="left")

        timed(f"dict_join_{sname}_encoded", lambda: dict_join_encoded(codes))
        timed(f"dict_join_{sname}_decoded",
              lambda: dict_join_decoded(codes, remap))

        # -- dict: 32-group segment sums keyed by codes vs decoded ranks
        vals = jnp.asarray(rng.integers(0, 1000, n), jnp.int64)

        @jax.jit
        def dict_agg_encoded(codes, vals):
            return jax.ops.segment_sum(vals, codes % 32, num_segments=32)

        @jax.jit
        def dict_agg_decoded(codes, remap, vals):
            lane = remap[jnp.clip(codes, 0, remap.shape[0] - 1)]
            return jax.ops.segment_sum(vals, lane % 32, num_segments=32)

        timed(f"dict_agg_{sname}_encoded",
              lambda: dict_agg_encoded(codes, vals))
        timed(f"dict_agg_{sname}_decoded",
              lambda: dict_agg_decoded(codes, remap, vals))

        # -- RLE: run-domain predicate vs decode-then-compare
        n_runs = n // 64
        run_vals = jnp.asarray(rng.integers(0, 1000, n_runs), jnp.int64)
        run_lens = jnp.asarray(np.full(n_runs, 64), jnp.int32)
        thr = int(1000 * sel)

        @jax.jit
        def rle_encoded(run_vals, run_lens):
            return rle_predicate_mask(run_vals, run_lens, n,
                                      lambda v: v < thr)

        @jax.jit
        def rle_decoded(run_vals, run_lens):
            return rle_decode(run_vals, run_lens, n) < thr

        timed(f"rle_pred_{sname}_encoded",
              lambda: rle_encoded(run_vals, run_lens))
        timed(f"rle_pred_{sname}_decoded",
              lambda: rle_decoded(run_vals, run_lens))

        # -- FOR: narrow-lane predicate + exact-width add vs widened
        narrow = jnp.asarray(rng.integers(-1000, 1000, n), jnp.int16)
        thr16 = jnp.asarray(int(2000 * sel) - 1000, jnp.int64)

        @jax.jit
        def for_encoded(narrow):
            keep = narrow_compare("<", narrow, thr16)
            s = narrow.astype(jnp.int32) + narrow.astype(jnp.int32)
            return keep, s

        @jax.jit
        def for_decoded(narrow):
            wide = narrow.astype(jnp.int64)
            return wide < thr16, wide + wide

        timed(f"for_pred_{sname}_encoded", lambda: for_encoded(narrow))
        timed(f"for_pred_{sname}_decoded", lambda: for_decoded(narrow))

    ratios = {}
    for k in sorted(out):
        if k.endswith("_encoded"):
            base = out.get(k.replace("_encoded", "_decoded"))
            if base:
                ratios[k[:-8]] = round(out[k] / base, 3)
    print(json.dumps({
        "mode": "encodings",
        "metric": "encoding_microbench_encoded_vs_decoded",
        "value": round(float(np.exp(np.mean(np.log(
            [max(r, 1e-6) for r in ratios.values()])))), 3)
        if ratios else None,
        "unit": "x (encoded/decode-first, lower is better)",
        "backend": jax.default_backend(),
        "encoding_timings_ms": out,
        "encoded_over_decoded_ratio": ratios,
        "elapsed_s": round(time.perf_counter() - _T0, 1),
        "final": True}), flush=True)


#: --ooc leg queries: the join+aggregation classes whose working sets
#: the out-of-core tier must carry (ISSUE 15; --queries overrides)
OOC_QUERIES = ["q3", "q9", "q18"]

#: HBM budget for the capped leg = measured peak / this divisor (the
#: budget lands well below the per-operator working sets — a gentler
#: divisor only pressures the staging spill path, never the
#: partition-tier gates)
OOC_CAP_DIVISOR = 16


def run_ooc(suite_name: str, scale: float, query_names):
    """--ooc: memory-capped out-of-core leg (ISSUE 15).  Each query runs
    once UNCAPPED (the resident baseline and the oracle — its measured
    budget peak is the working-set reference) and once with the HBM
    budget forced to peak/OOC_CAP_DIVISOR, floored so a single target
    batch still fits (a budget below one batch is unsatisfiable by ANY
    tier).  The capped run must oracle-match, engage the out-of-core
    tier (`ooc.*` ctx counters / tpu_ooc_* families) and never reach
    the query-level replay rung.  Emits `ooc_timings_ms` entries
    ({qN}_capped / {qN}_uncapped, lower = better) that
    scripts/check_regression.py gates under the `oc:` prefix with the
    same backend-separation rule as qN device_ms."""
    import importlib
    workload = importlib.import_module(f"spark_rapids_tpu.{suite_name}")
    from spark_rapids_tpu.exec.plan import ExecContext
    from spark_rapids_tpu.session import TpuSession

    tables = workload.gen_tables(scale=scale)
    names = [n for n in (query_names or OOC_QUERIES)
             if n in workload.QUERIES]
    # the capped leg runs SMALLER batches (scale-aware) so the
    # unsatisfiable floor — one staged batch must fit the budget —
    # stays far below the cap; the uncapped baseline keeps the default
    base = TpuSession(dict(EXTRA_CONF))
    bsr = base.conf.batch_size_rows
    rows_max = max(t.num_rows for t in tables.values())
    bsr_capped = min(bsr, max(4096, rows_max // 64))
    row_w = max(t.nbytes // max(t.num_rows, 1) for t in tables.values())
    batch_floor = 2 * bsr_capped * max(row_w, 8)
    out = {}
    timings = {}
    all_match = True
    for name in names:
        if left() < 120:
            print(f"# budget: skipping ooc query {name}", file=sys.stderr)
            continue
        # -- uncapped baseline + working-set reference
        s0 = TpuSession(dict(EXTRA_CONF))
        df0 = workload.QUERIES[name](s0, tables)
        t0 = time.perf_counter()
        oracle = df0.collect()
        un_ms = (time.perf_counter() - t0) * 1e3
        m0 = df0.metrics()
        peak = int(m0.get("memory.peak_bytes") or 0)
        src = sum(t.nbytes for t in tables.values())
        cap = max(max(peak, src // 4) // OOC_CAP_DIVISOR, batch_floor)
        # -- capped run: the OOC tier must carry it
        s1 = TpuSession({**EXTRA_CONF,
                         "spark.rapids.tpu.memory.tpu.budgetBytes":
                             str(cap),
                         "spark.rapids.tpu.sql.batchSizeRows":
                             str(bsr_capped)})
        df1 = workload.QUERIES[name](s1, tables)
        t0 = time.perf_counter()
        try:
            capped = df1.collect()
            err = None
        except Exception as e:                       # noqa: BLE001
            capped, err = None, f"{type(e).__name__}: {e}"[:200]
        cap_ms = (time.perf_counter() - t0) * 1e3
        m1 = df1.metrics() if capped is not None else {}
        ooc = {k[4:]: v for k, v in m1.items() if k.startswith("ooc.")}
        match = capped is not None and approx_equal(oracle, capped)
        all_match = all_match and match
        timings[f"{name}_uncapped"] = round(un_ms, 1)
        timings[f"{name}_capped"] = round(cap_ms, 1)
        out[name] = {
            "uncapped_ms": round(un_ms, 1),
            "capped_ms": round(cap_ms, 1),
            "degradation_x": round(cap_ms / un_ms, 2) if un_ms else None,
            "budget_bytes": cap,
            "working_set_peak_bytes": peak,
            "match": match,
            "error": err,
            "ooc": ooc,
            "ooc_engaged": any(k.endswith("_elections") for k in ooc),
            "spilled_batches": m1.get("memory.spilled_batches"),
            "query_oom_replays": m1.get("query_oom_replays", 0),
            "query_ooc_escalations": m1.get("query_ooc_escalations", 0),
        }
        print(f"# ooc {name}: uncapped={un_ms:.0f}ms capped={cap_ms:.0f}ms"
              f" budget={cap} match={match} ooc={ooc}", file=sys.stderr)
        _emit_ooc(suite_name, scale, out, timings, all_match, final=False)
    _emit_ooc(suite_name, scale, out, timings, all_match, final=True)
    return all_match


def _emit_ooc(suite_name, scale, out, timings, all_match, final):
    """Running JSON line after every --ooc query (same lossless-kill
    discipline as the suite runner: the last stdout line is always a
    complete, parseable record covering everything measured)."""
    print(json.dumps({
        "mode": "ooc",
        "metric": f"{suite_name}_sf{scale:g}_ooc_capped_geomean_x",
        "value": round(float(np.exp(np.mean(np.log(
            [max(v["degradation_x"], 1e-6) for v in out.values()
             if v.get("degradation_x")])))), 3)
        if any(v.get("degradation_x") for v in out.values()) else None,
        "unit": "x (capped/uncapped wall, lower is better)",
        "suite": suite_name,
        f"{suite_name}_suite_scale": scale,
        "backend": jax.default_backend(),
        "queries": out,
        "ooc_timings_ms": timings,
        "all_match": all_match,
        "all_engaged": all(v.get("ooc_engaged") for v in out.values())
        if out else False,
        "zero_replay_rung": all(
            not v.get("query_oom_replays") for v in out.values()),
        "extra_conf": dict(EXTRA_CONF),
        "elapsed_s": round(time.perf_counter() - _T0, 1),
        "final": final}), flush=True)


#: default serving mix: a fast, join/agg-diverse TPC-H tranche (clients
#: rotate through it; --queries overrides)
SERVING_MIX = ["q1", "q3", "q6", "q12", "q14", "q19"]

#: closed-loop concurrency levels --serving sweeps
SERVING_LEVELS = (1, 2, 4, 8)


def _pctl(vals, p):
    vs = sorted(vals)
    if not vs:
        return None
    k = max(0, min(len(vs) - 1, int(round(p / 100.0 * (len(vs) - 1)))))
    return vs[k]


def _rows_key(table):
    d = table.to_pydict()
    names = sorted(d)
    return sorted(zip(*(d[n] for n in names))) if names else []


def _fleet_worker_skew(fleet):
    """Utilization skew across pool workers: max/min ratio of summed
    per-worker device-us mined from the federated fleet registry
    (`tpu_fleet_serving_tenant_device_us_total{worker=..,tenant=..}`).
    1.0 = perfectly even dispatch; None when fewer than two workers
    reported work (nothing to compare)."""
    per = {}
    for key, v in (fleet or {}).items():
        if not key.startswith(
                "tpu_fleet_serving_tenant_device_us_total{"):
            continue
        labels = key.split("{", 1)[1].rstrip("}")
        wid = next((p.split("=", 1)[1] for p in labels.split(",")
                    if p.startswith("worker=")), None)
        if wid is not None:
            per[wid] = per.get(wid, 0) + float(v)
    if len(per) < 2 or min(per.values()) <= 0:
        return None
    return round(max(per.values()) / min(per.values()), 3)


def run_serving(suite_name: str, scale: float, query_names):
    """--serving: N concurrent closed-loop clients over a query mix
    through the ServingRuntime, vs the SAME query multiset run serially
    through today's single-query path.

    Per concurrency level: every client is its own tenant and runs the
    mix once (rotated by client index), so level c issues c*len(mix)
    queries — closed-loop repeated dashboard traffic.  The serial
    baseline runs the level-8 multiset sequentially through
    `PhysicalQuery.collect` exactly as today's path would serve it
    (replan per request, no result reuse).  Levels run with the result
    cache ON (it IS the serving architecture for this traffic); a
    `c8_nocache` level isolates pure phase overlap.  NOTE on reading
    the two: on an accelerator the nocache level shows the real
    compile/upload/host-tail overlap win; on a CPU-backend container
    the "device" shares the host cores (this harness runs on ONE core),
    so compute overlap cannot add throughput there by construction and
    nocache QPS ~= serial is the expected reading, with the serving win
    carried by the cache + structure-shared compiles.  Latency is
    client-observed submit->result wall (admission waits included).
    `mp2` / `mp4` levels run the same mix through the SUPERVISED
    WORKER POOL (`serving.pool.processes`, docs/SERVING.md): device
    execution in 2/4 worker processes — the fault-isolation
    architecture's throughput cost (dispatch serialization + per-worker
    warmup; the result cache is bypassed by construction).  `mp2_kill`
    additionally SIGKILLs one worker mid-query (`worker:kill:nth=1`)
    and must stay oracle-matching: the lost query redrives on the
    survivor (docs/ROBUSTNESS.md).
    Gate entries: `serving_latency_ms` (sv:-prefixed in
    scripts/check_regression.py, lower = better, same-backend rule)."""
    import importlib
    import threading
    workload = importlib.import_module(f"spark_rapids_tpu.{suite_name}")
    from spark_rapids_tpu.config import WHOLE_PLAN_COMPILE
    from spark_rapids_tpu.exec.plan import ExecContext
    from spark_rapids_tpu.serving.runtime import ServingRuntime
    from spark_rapids_tpu.session import DataFrame, TpuSession

    rtt = measure_rtt()
    tables = workload.gen_tables(scale=scale)
    dev = TpuSession({WHOLE_PLAN_COMPILE.key: "ON"})
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    mix = [n for n in (query_names or SERVING_MIX)
           if n in workload.QUERIES]

    # warm every mix query once (compile + upload) and oracle-check it
    per_q = {}
    expected = {}
    for name in mix:
        dfq = workload.QUERIES[name](dev, tables)
        q = dfq.physical()
        t0 = time.perf_counter()
        out = q.collect(ExecContext(dev.conf))
        cold_s = time.perf_counter() - t0
        oracle = DataFrame(dfq._plan, cpu).physical().collect()
        expected[name] = _rows_key(out)
        per_q[name] = {"cold_s": round(cold_s, 1),
                       "match": approx_equal(out, oracle)}
        print(f"# warm {name}: cold={cold_s:.1f}s "
              f"match={per_q[name]['match']}", file=sys.stderr)

    # serial baseline: the level-8 multiset through the single-query path
    serial_n = 8 * len(mix)
    t0 = time.perf_counter()
    for i in range(serial_n):
        name = mix[i % len(mix)]
        q = workload.QUERIES[name](dev, tables).physical()
        q.collect(ExecContext(dev.conf))
    serial_s = time.perf_counter() - t0
    serial_qps = serial_n / serial_s
    print(f"# serial baseline: {serial_n} queries in {serial_s:.1f}s "
          f"({serial_qps:.2f} QPS)", file=sys.stderr)

    def run_level(c: int, cache_on: bool, procs: int = 0,
                  faults: str = "") -> dict:
        # workers: 3 pipelines keep one query in a host phase while
        # another executes; more just multiplies GIL-bound planners
        # contending with the executing query (measured — the worker
        # sweep in docs/SERVING.md)
        ov = {
            "spark.rapids.tpu.serving.workers": str(min(3, max(2, c))),
            "spark.rapids.tpu.serving.resultCache.bytes":
                "0" if not cache_on else str(256 << 20)}
        if procs:
            # multi-process pool level: device execution moves into
            # `procs` supervised worker processes (docs/SERVING.md);
            # the result cache is bypassed by construction there
            ov["spark.rapids.tpu.serving.pool.processes"] = str(procs)
        if faults:
            # chaos leg: e.g. worker:kill:nth=1 SIGKILLs one worker
            # mid-query — the level must stay oracle-matching (redrive)
            ov["spark.rapids.tpu.test.faults"] = faults
        rt = ServingRuntime(dev, ov)
        lats, errs, mismatches = [], [], []
        lock = threading.Lock()

        def client(idx: int):
            tenant = rt.tenant(f"client{idx}")
            for j in range(len(mix)):
                name = mix[(j + idx) % len(mix)]
                df = workload.QUERIES[name](dev, tables)
                t0 = time.perf_counter()
                try:
                    out = tenant.collect(df)
                except Exception as e:           # noqa: BLE001
                    with lock:
                        errs.append(f"{name}: {type(e).__name__}: {e}")
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)
                    if _rows_key(out) != expected[name]:
                        mismatches.append(name)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(c)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        stats = rt.stats()
        rt.close()
        n = len(lats)
        level = {"clients": c, "queries": n, "errors": errs,
                 "mismatches": sorted(set(mismatches)),
                 "wall_s": round(wall, 2),
                 "qps": round(n / wall, 3) if wall else None,
                 "p50_ms": round(_pctl(lats, 50) * 1e3, 1) if n else None,
                 "p99_ms": round(_pctl(lats, 99) * 1e3, 1) if n else None,
                 "mean_ms": round(sum(lats) / n * 1e3, 1) if n else None,
                 "device_utilization": stats["device_utilization"],
                 "overlap_observed": stats["overlap_observed"],
                 "max_skips": stats["max_skips"],
                 "result_cache": stats["result_cache"],
                 "cache_on": cache_on}
        if procs:
            pool = stats.get("pool") or {}
            # the federated fleet registry (per-worker-labeled series the
            # supervisor folded from heartbeat telemetry) rides the level
            # record so regression mining sees cross-process utilization
            fleet = stats.get("fleet") or {}
            level.update(
                pool_processes=procs,
                worker_restarts=pool.get("restarts"),
                redrives=pool.get("redrives"),
                faults=faults or None,
                fleet=fleet or None,
                worker_skew=_fleet_worker_skew(fleet))
        print(f"# serving c={c} cache={'on' if cache_on else 'off'}: "
              f"{n} queries {wall:.1f}s qps={level['qps']} "
              f"p50={level['p50_ms']}ms p99={level['p99_ms']}ms "
              f"util={level['device_utilization']}", file=sys.stderr)
        return level

    levels = {}
    for c in SERVING_LEVELS:
        if left() < 45:
            print(f"# budget: skipping serving level c={c}",
                  file=sys.stderr)
            continue
        levels[f"c{c}"] = run_level(c, cache_on=True)
    if left() > 45:
        levels["c8_nocache"] = run_level(8, cache_on=False)
    # multi-process pool levels (docs/SERVING.md): same mix through the
    # supervised worker pool — the fault-isolation architecture's
    # throughput cost vs the in-process path — plus a chaos leg that
    # SIGKILLs one worker mid-query and must stay oracle-matching via
    # redrive.  Pool levels ship source tables over the dispatch socket
    # and pay per-worker session warmup, so they are budget-gated
    # harder than the in-process levels.
    if jax.default_backend() == "tpu":
        # this process holds the chip and a chip belongs to one process:
        # the pool refuses to start on a TPU (serving/workers.py)
        print("# tpu: skipping serving levels mp2/mp4/mp2_kill — the "
              "supervisor holds the chip, worker processes cannot open it",
              file=sys.stderr)
    else:
        for procs in (2, 4):
            if left() < 150:
                print(f"# budget: skipping serving level mp{procs}",
                      file=sys.stderr)
                continue
            levels[f"mp{procs}"] = run_level(4, cache_on=True, procs=procs)
        if left() > 150:
            levels["mp2_kill"] = run_level(4, cache_on=True, procs=2,
                                           faults="worker:kill:nth=1")
        else:
            print("# budget: skipping serving level mp2_kill",
                  file=sys.stderr)

    c8 = levels.get("c8") or {}
    c8_nc = levels.get("c8_nocache") or {}
    gate = {}
    for key, lvl in levels.items():
        if lvl.get("p99_ms"):
            gate[f"{key}_p99"] = lvl["p99_ms"]
        if lvl.get("mean_ms"):
            gate[f"{key}_mean"] = lvl["mean_ms"]
    out = {"mode": "serving",
           "metric": f"{suite_name}_sf{scale:g}_serving_c8_qps",
           "value": c8.get("qps"),
           "unit": "qps",
           "suite": suite_name,
           f"{suite_name}_suite_scale": scale,
           "backend": jax.default_backend(),
           "mix": mix,
           "queries": per_q,
           "serial_n": serial_n,
           "serial_s": round(serial_s, 2),
           "serial_qps": round(serial_qps, 3),
           "serving_levels": levels,
           "serving_latency_ms": gate,
           # per-level worker utilization skew from the federated fleet
           # registry — mined by check_regression under the sv: rules
           "serving_fleet": {f"{key}_skew": lvl["worker_skew"]
                             for key, lvl in levels.items()
                             if lvl.get("worker_skew")},
           "qps_vs_serial": round(c8["qps"] / serial_qps, 3)
           if c8.get("qps") else None,
           "qps_nocache_vs_serial": round(c8_nc["qps"] / serial_qps, 3)
           if c8_nc.get("qps") else None,
           "serving_beats_serial": bool(c8.get("qps") and
                                        c8["qps"] > serial_qps),
           # the crash-containment headline: the kill leg lost one
           # worker mid-query and still matched the oracle everywhere
           "mp_kill_contained": bool(
               (kl := levels.get("mp2_kill"))
               and not kl["errors"] and not kl["mismatches"]
               and (kl.get("worker_restarts") or {}).get("crash")),
           "overlap_observed": bool(c8_nc.get("overlap_observed") or
                                    c8.get("overlap_observed")),
           "all_match": all(v["match"] for v in per_q.values()),
           "sync_rtt_ms": round(rtt * 1e3, 3),
           "elapsed_s": round(time.perf_counter() - _T0, 1),
           "final": True,
           "note": "closed-loop clients, one tenant each, mix rotated "
                   "per client (repeated dashboard traffic); levels run "
                   "the full serving architecture (result cache ON), "
                   "c8_nocache isolates pure phase overlap — on a "
                   "cpu-backend container the engine shares the host "
                   "cores with itself, so nocache ~= serial is the "
                   "expected reading there and the serving win is "
                   "cache + structure-shared compiles; latency = "
                   "client-observed submit->result wall incl. admission "
                   "waits; serial baseline = the same multiset through "
                   "the single-query path (replan per request, no "
                   "result reuse)."}
    print(json.dumps(out), flush=True)
    dev.close()
    return out["all_match"] and not any(
        lvl["errors"] or lvl["mismatches"] for lvl in levels.values())


def measure_metrics_overhead(workload, tables, suite, dev, name="q6"):
    """Re-time one already-measured query with the metrics plane OFF and
    report the delta — the proof the always-on registry + flight
    recorder cost stays within the claimed bound (docs/METRICS.md).
    Budget-gated and fail-soft: its absence loses the overhead line,
    never the benchmark."""
    from spark_rapids_tpu.exec.plan import ExecContext
    from spark_rapids_tpu.session import TpuSession
    on_ms = (suite.per_q.get(name) or {}).get("device_ms")
    if on_ms is None or left() < 60:
        return None
    try:
        from spark_rapids_tpu.config import METRICS_ENABLED
        from spark_rapids_tpu.obs.export import configure_plane
        try:
            off = TpuSession({METRICS_ENABLED.key: "false"})
            q = workload.QUERIES[name](off, tables).physical()
            q.collect(ExecContext(off.conf))         # warm
            t_off = time_warm(lambda: q.collect(ExecContext(off.conf)))
        finally:
            configure_plane(dev.conf)                # plane back ON
        off_ms = t_off * 1e3
        return {"query": name, "on_ms": on_ms,
                "off_ms": round(off_ms, 1),
                "overhead_pct": round((on_ms - off_ms) / off_ms * 100, 2)
                if off_ms else None}
    except Exception as e:                           # noqa: BLE001
        return {"query": name, "error": f"{type(e).__name__}: {e}"[:200]}


def main():
    scale = 1.0
    names = None
    suite_name = "tpch"
    compile_only = False
    serving = False
    encodings = False
    ooc = False
    multichip = False
    multichip_sf = 10.0
    args = list(sys.argv[1:])
    i = 0
    while i < len(args):
        a = args[i]
        if a.startswith("--conf"):
            if a.startswith("--conf="):
                kv = a[len("--conf="):]
            else:
                i += 1
                kv = args[i]
            k, _, v = kv.partition("=")
            EXTRA_CONF[k] = v
        elif a == "--encodings":
            encodings = True
        elif a == "--ooc":
            ooc = True
        elif a.startswith("--history-dir"):
            # persistent performance-history plane: every measured query
            # records its structure-keyed device time (obs/history.py)
            # so later rounds/admissions estimate from measured cost —
            # scripts/history_report.py renders the dir
            if "=" in a:
                hd = a.split("=", 1)[1]
            else:
                i += 1
                hd = args[i]
            EXTRA_CONF["spark.rapids.tpu.history.dir"] = hd
        elif a.startswith("--queries"):
            if "=" in a:
                names = a.split("=", 1)[1].split(",")
            else:
                i += 1
                names = args[i].split(",")
        elif a.startswith("--suite"):
            if "=" in a:
                suite_name = a.split("=", 1)[1]
            else:
                i += 1
                suite_name = args[i]
        elif a == "--compile-only":
            compile_only = True
        elif a == "--serving":
            serving = True
        elif a == "--multichip-suite":
            multichip = True
        elif a.startswith("--multichip-sf"):
            if "=" in a:
                multichip_sf = float(a.split("=", 1)[1])
            else:
                i += 1
                multichip_sf = float(args[i])
        else:
            scale = float(a)
        i += 1
    if multichip:
        # mesh primitives + sharded TPC-H at --multichip-sf over the
        # chips jax finds (8 virtual CPU devices under JAX_PLATFORMS=cpu,
        # which must be configured before any backend init, so the suite
        # owns the whole process) — spark_rapids_tpu/multichip.py
        from spark_rapids_tpu.multichip import run_multichip_suite
        doc = run_multichip_suite(sf=multichip_sf, queries=names,
                                  budget_s=TOTAL_BUDGET_S)
        return not doc["errors"] and not any(
            v.get("match") is False
            for v in doc["multichip_suite_queries"].values())
    if suite_name not in ("tpch", "tpcds"):
        raise SystemExit(f"unknown suite {suite_name!r} "
                         f"(expected tpch or tpcds)")
    import importlib
    workload = importlib.import_module(f"spark_rapids_tpu.{suite_name}")
    query_names = names or sorted(workload.QUERIES,
                                  key=lambda q: int(q[1:]))

    if encodings:
        # encoded-vs-decode-first microbench A/B (ENCODINGS_r*.json)
        run_encodings()
        return True
    if ooc:
        # memory-capped out-of-core leg (OOC_r*.json, oc: gate entries)
        return run_ooc(suite_name, scale, names)
    if serving:
        # concurrent closed-loop serving sweep (names = the mix)
        return run_serving(suite_name, scale, names)
    if compile_only:
        return run_compile_only(suite_name, scale, query_names)
    suite = run_suite(suite_name, scale, query_names)
    suite.emit(final=True)
    return all("error" not in v and v["match"]
               for v in suite.per_q.values())


if __name__ == "__main__":
    # the JSON is out by now; the exit code says whether to believe it
    ok = main()
    if not ok:
        print("# FAILED: a query errored or mismatched its oracle "
              "(see the JSON above)", file=sys.stderr)
        raise SystemExit(1)
    if jax.default_backend() != "tpu" and \
            os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"# FAILED: backend is {jax.default_backend()!r}, not tpu, "
              f"and the CPU was not asked for (JAX_PLATFORMS=cpu)",
              file=sys.stderr)
        raise SystemExit(1)
