"""Multi-chip benchmark suite: mesh primitives + a sharded TPC-H run.

The MULTICHIP_r* trajectory used to be microbenchmarks only; this
module raises it to a real suite (ROADMAP item 4 / Theseus: distributed
engines win or lose on data movement at scale):

  1. **Primitive timings** with the r05-compatible keys — the fused
     distributed groupby at 1M rows/device (now the compressed
     quota-scheduled ragged pipeline), the 65k ragged groupby, the
     distributed window rank — so the regression gate
     (scripts/check_regression.py) compares rounds apples-to-apples;
  2. **Mesh TPC-H microqueries** (q1/q6/q12 at the r05 scale) for the
     same reason;
  3. **The sharded suite**: TPC-H at a real scale factor with fact
     tables *generated in per-shard chunks* (bounded per-chunk datagen,
     globally consistent key spaces), executed SPMD over the mesh
     (`spark.rapids.tpu.sql.mesh.enabled`) with a finite HBM budget so
     the spill tier engages; per-query wall, oracle check (budget
     gated), spill/exchange telemetry from the always-on registry.

Run via `python bench.py --multichip-suite [--multichip-sf N]` — bench
owns the CLI; this module owns the measurement so tests can drive it
at toy scale.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np


def _setup_devices(n_devices: int) -> int:
    """-> the mesh width to run at.  With JAX_PLATFORMS=cpu (tests, dry
    run): secure n virtual CPU devices BEFORE backend init.  Otherwise
    the suite runs on the chips jax finds, all of them."""
    import jax
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return len(jax.devices())
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        pass                    # backend already up: use what exists
    return n_devices


def gen_tables_sharded(scale: float, n_shards: int, seed: int = 20240706
                       ) -> Dict[str, "object"]:
    """TPC-H tables with the FACT volume of `scale`, generated in
    `n_shards` independent per-shard chunks (bounded chunk datagen, the
    sharded-ingest shape of a real cluster load) and re-keyed into one
    coherent key space: shard s owns order keys [s*N, (s+1)*N).  Fact
    foreign keys draw from the shard-scale dimension tables, so every
    join has full referential coverage.  Dimensions come from chunk 0
    (`dims_scale = scale / n_shards` — fact-heavy, the data-movement
    stress shape)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    from . import tpch
    per = scale / n_shards
    shards = [tpch.gen_tables(scale=per, seed=seed + 7919 * s)
              for s in range(n_shards)]
    n_ord_s = shards[0]["orders"].num_rows
    orders, lineitem = [], []
    for s, t in enumerate(shards):
        off = s * n_ord_s
        o, li = t["orders"], t["lineitem"]
        orders.append(o.set_column(
            o.schema.get_field_index("o_orderkey"), "o_orderkey",
            pc.add(o["o_orderkey"], off)))
        lineitem.append(li.set_column(
            li.schema.get_field_index("l_orderkey"), "l_orderkey",
            pc.add(li["l_orderkey"], off)))
    out = dict(shards[0])
    out["orders"] = pa.concat_tables(orders).combine_chunks()
    out["lineitem"] = pa.concat_tables(lineitem).combine_chunks()
    return out


def _timed(timings: dict, name: str):
    class _T:
        def __enter__(self):
            self.t0 = time.perf_counter()

        def __exit__(self, *a):
            timings[name] = round(time.perf_counter() - self.t0, 2)
    return _T()


class _mesh_traced:
    """Collect the mesh exchange timeline of one measured block: a
    QueryTracer is made ACTIVE for the block so every ragged-exchange
    round / dictionary gather / skew split lands in it, and the parsed
    timeline (QueryProfile.mesh_timeline) is stored under `name` —
    the per-round exchange telemetry the MULTICHIP records embed."""

    def __init__(self, timelines: dict, name: str):
        self.timelines = timelines
        self.name = name

    def __enter__(self):
        from .obs.tracer import QueryTracer, set_active
        self.tr = QueryTracer(0)
        set_active(self.tr)
        return self.tr

    def __exit__(self, *a):
        from .obs.profile import QueryProfile
        from .obs.tracer import NULL_TRACER, set_active
        set_active(NULL_TRACER)
        prof = QueryProfile(self.tr.spans, self.tr.events,
                            self.tr.counters, {}, {})
        tl = prof.mesh_timeline()
        tl["ici_exchange_bytes"] = int(
            self.tr.counters.get("ici_exchange_bytes", 0))
        self.timelines[self.name] = tl


def _primitives(mesh, timings: dict, scale: float = 1.0,
                timelines: Optional[dict] = None) -> None:
    """The r05-compatible primitive benchmarks: fused groupby at 1M
    rows/device (the retired bucket stack's headline case), ragged
    groupby + window rank at 64k rows/device."""
    import jax
    import jax.numpy as jnp
    from . import types as t
    from .ops import groupby as G
    from .parallel.exchange import (distributed_groupby_ragged,
                                    distributed_groupby_step,
                                    distributed_window_rank)
    n_devices = mesh.devices.size
    big_cap = max(1024, int((1 << 20) * scale))
    local_cap = max(64, int((1 << 16) * scale))
    rng = np.random.default_rng(3)
    specs = [G.AggSpec(G.SUM, 0, t.LONG), G.AggSpec(G.COUNT, 0, t.LONG)]

    def check(kd, outs, ngroups, keys, key_valid, vals):
        total = int(np.asarray(ngroups).sum())
        distinct = len(set(keys[key_valid].tolist())) + \
            int((~key_valid).any())
        assert total == distinct, (total, distinct)
        sums = np.asarray(outs[0][0])
        ng = np.asarray(ngroups)
        mcap = np.asarray(kd).shape[0] // n_devices
        got = sum(sums[p * mcap: p * mcap + int(ng[p])].sum()
                  for p in range(n_devices))
        assert got == vals.sum(), got

    # fused groupby, 1M rows/device, hot-key skew (the r05 fixture)
    nb = n_devices * big_cap
    bkeys = rng.integers(0, 5000, nb).astype(np.int64)
    bkeys[rng.random(nb) < 0.4] = 3
    bkey_valid = rng.random(nb) < 0.9
    bvals = rng.integers(-10, 10, nb).astype(np.int64)
    timelines = {} if timelines is None else timelines
    fn, shard = distributed_groupby_step(mesh, t.LONG, specs, big_cap)
    with _timed(timings, f"groupby_{big_cap}_rows_per_device"), \
            _mesh_traced(timelines, f"groupby_{big_cap}_rows_per_device"):
        (kd, kv), outs, ngroups = fn(
            jax.device_put(jnp.asarray(bkeys), shard),
            jax.device_put(jnp.asarray(bkey_valid), shard),
            [jax.device_put(jnp.asarray(bvals), shard)],
            [jax.device_put(jnp.ones(nb, bool), shard)])
        jax.block_until_ready((kd, ngroups))
    check(kd, outs, ngroups, bkeys, bkey_valid, bvals)
    del kd, kv, outs, ngroups, bkeys, bkey_valid, bvals

    n = n_devices * local_cap
    keys = rng.integers(0, 7, n).astype(np.int64)
    keys[rng.random(n) < 0.4] = 3
    key_valid = rng.random(n) < 0.9
    vals = rng.integers(-10, 10, n).astype(np.int64)
    run, shard2 = distributed_groupby_ragged(mesh, t.LONG, specs,
                                             local_cap)
    with _timed(timings, f"ragged_groupby_{local_cap}_rows_per_device"), \
            _mesh_traced(timelines,
                         f"ragged_groupby_{local_cap}_rows_per_device"):
        (kd2, _), outs2, ngroups2 = run(
            jax.device_put(jnp.asarray(keys), shard2),
            jax.device_put(jnp.asarray(key_valid), shard2),
            [jax.device_put(jnp.asarray(vals), shard2)],
            [jax.device_put(jnp.ones(n, bool), shard2)])
        jax.block_until_ready((kd2, ngroups2))
    check(kd2, outs2, ngroups2, keys, key_valid, vals)

    wpk = rng.integers(0, 200, n).astype(np.int64)
    wpk[rng.random(n) < 0.4] = 7
    wok = rng.integers(0, 50, n).astype(np.int64)
    wlv = rng.random(n) < 0.9
    with _timed(timings, f"window_rank_{local_cap}_rows_per_device"), \
            _mesh_traced(timelines,
                         f"window_rank_{local_cap}_rows_per_device"):
        _, _, rank, _ = distributed_window_rank(
            mesh, jax.device_put(jnp.asarray(wpk), shard2),
            jax.device_put(jnp.asarray(wok), shard2),
            jax.device_put(jnp.asarray(wlv), shard2))
        jax.block_until_ready(rank)


def _approx_equal(a, b) -> bool:
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


def run_multichip_suite(n_devices: int = 8, sf: float = 10.0,
                        queries: Optional[List[str]] = None,
                        budget_s: float = 1800.0,
                        hbm_budget_bytes: int = 1 << 30,
                        micro_scale: float = 1.0,
                        oracle_budget_s: float = 120.0) -> dict:
    """The full multichip round: primitives + r05 mesh microqueries +
    the sharded TPC-H suite.  Prints a running JSON line after every
    stage (the bench.py lossless-kill discipline) and returns the final
    document."""
    n_devices = _setup_devices(n_devices)
    import jax
    from .config import HBM_BUDGET_BYTES, MESH_DEVICES, MESH_ENABLED
    from .exec.plan import ExecContext
    from .parallel.mesh import make_mesh
    from .session import DataFrame, TpuSession
    from . import tpch

    t_start = time.perf_counter()

    def left():
        return budget_s - (time.perf_counter() - t_start)

    doc: dict = {"suite": "multichip", "n_devices": n_devices,
                 "backend": jax.default_backend(),
                 "multichip_sf": sf, "final": False}
    timings: dict = {}
    doc["multichip_timings_s"] = timings

    def emit(final=False):
        doc["final"] = final
        doc["elapsed_s"] = round(time.perf_counter() - t_start, 1)
        try:
            import resource
            doc["peak_rss_mb"] = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss // 1024
        except Exception:                        # noqa: BLE001
            doc["peak_rss_mb"] = -1
        print(json.dumps(doc), flush=True)

    # persistent compile cache (the bench.py discipline: cold numbers
    # report cache loads; the per-round pcache delta below is the proof
    # of what was compiled vs replayed).  The primitives below jit before
    # any session exists, so place the cache explicitly.
    from .config import DEFAULT_CONF, TpuConf
    from .exec.compiled import (configure_persistent_cache,
                                persistent_cache_stats)
    configure_persistent_cache(DEFAULT_CONF)
    pc0 = persistent_cache_stats()

    mesh = make_mesh(n_devices)
    doc["rows_per_device"] = {
        "fused_groupby": max(1024, int((1 << 20) * micro_scale)),
        "other_primitives": max(64, int((1 << 16) * micro_scale))}
    # per-round exchange timelines (round quotas, wire bytes pre/post
    # compress, arrival counts, staging vs collective ms) ride the
    # record next to the wall timings they explain
    prim_timelines: Dict[str, dict] = {}
    doc["primitives_mesh_timeline"] = prim_timelines
    _primitives(mesh, timings, scale=micro_scale,
                timelines=prim_timelines)
    from .obs.registry import REGISTRY
    doc["exchange"] = {
        k: REGISTRY.get(f"tpu_exchange_wire_bytes_{k}_compress_total")
        .value() for k in ("pre", "post")}
    emit()

    # -- r05-comparable mesh microqueries (tiny SF, same keys) ------------
    micro_tables = tpch.gen_tables(scale=0.002)
    mesh_conf = {MESH_ENABLED.key: True, MESH_DEVICES.key: n_devices}
    s = TpuSession(mesh_conf)
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    for qname in ("q1", "q6", "q12"):
        dfq = tpch.QUERIES[qname](s, micro_tables)
        ctx = ExecContext(s.conf)
        with _timed(timings, f"mesh_query_{qname}"):
            out = dfq.physical().collect(ctx)
        assert ctx.metrics.get("whole_plan_compiled_queries", 0) == 1
        oracle = DataFrame(dfq._plan, cpu).collect()
        assert _approx_equal(out, oracle), f"mesh {qname} oracle mismatch"
    emit()

    # -- the sharded suite ------------------------------------------------
    t0 = time.perf_counter()
    tables = gen_tables_sharded(sf, n_devices)
    doc["datagen_s"] = round(time.perf_counter() - t0, 1)
    doc["lineitem_rows"] = tables["lineitem"].num_rows
    # finite HBM budget so the spill tier engages at suite scale
    suite_conf = dict(mesh_conf)
    suite_conf[HBM_BUDGET_BYTES.key] = hbm_budget_bytes
    sdev = TpuSession(suite_conf)
    scpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    names = queries or sorted(tpch.QUERIES, key=lambda q: int(q[1:]))
    per_q: Dict[str, dict] = {}
    doc["multichip_suite_queries"] = per_q
    spill0 = REGISTRY.get("tpu_spill_batches_total")
    spill_before = sum(s_["value"] for s_ in spill0.series()) \
        if spill0.series() else 0
    for name in names:
        if left() < 30:
            doc.setdefault("skipped", []).append(name)
            continue
        rec: dict = {}
        per_q[name] = rec
        try:
            dfq = tpch.QUERIES[name](sdev, tables)
            q = dfq.physical()
            # cold collect runs TRACED so the record embeds the query's
            # mesh exchange timeline + per-query ICI byte attribution
            # (cold wall includes compile anyway; tracer cost is noise)
            from .config import TRACE_ENABLED
            ctx = ExecContext(TpuConf({**sdev.conf._raw,
                                       TRACE_ENABLED.key: True}))
            t0 = time.perf_counter()
            out = q.collect(ctx)
            rec["cold_s"] = round(time.perf_counter() - t0, 2)
            rec["compiled"] = bool(
                ctx.metrics.get("whole_plan_compiled_queries", 0))
            from .obs.profile import QueryProfile
            prof = QueryProfile.from_context(ctx)
            tl = prof.mesh_timeline()
            if tl["exchanges"] or tl["skew_splits"]:
                rec["mesh_timeline"] = tl
            ici = prof.counters.get("ici_exchange_bytes", 0)
            if ici:
                rec["ici_exchange_bytes"] = int(ici)
            # per-query HBM attribution from the traced cold collect:
            # the budget peak + the XLA memory_analysis working-set
            # floor ride the record so check_regression.py can gate
            # HBM-peak regressions on the mesh suite too
            hbm_peak = max(int(ctx.metrics.get("memory.peak_bytes")
                               or 0),
                           int(ctx.metrics.get("exec_hbm_bytes") or 0))
            if hbm_peak:
                rec["hbm_peak_bytes"] = hbm_peak
            mws = int(ctx.metrics.get("exec_hbm_bytes") or 0)
            if mws:
                rec["hbm_measured_working_set"] = mws
            t0 = time.perf_counter()
            q.collect(ExecContext(sdev.conf))
            warm = time.perf_counter() - t0
            # wall_ms, NOT device_ms: these are mesh-suite timings at
            # --multichip-sf scale — the regression gate compares them
            # via the mc:mesh_sf* keys, never against single-chip qN
            rec["wall_ms"] = round(warm * 1e3, 1)
            timings[f"mesh_sf{sf:g}_{name}"] = round(warm, 2)
            if left() > oracle_budget_s:
                cq = DataFrame(dfq._plan, scpu).physical()
                t0 = time.perf_counter()
                oracle = cq.collect()
                rec["cpu_wall_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)
                rec["match"] = _approx_equal(out, oracle)
            else:
                rec["match"] = None              # oracle budget-gated
        except Exception as e:                   # noqa: BLE001
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        print(f"# multichip {name}: {rec}", file=sys.stderr)
        emit()
    # -- spill leg: the same sharded tables through the eager engine
    # under a finite HBM budget, so the memory-tiering plane is
    # EXERCISED at suite volume (the mesh whole-plan path keeps its
    # working set inside the XLA program and never consults the budget
    # — integrating the two is a ROADMAP item, so the suite proves the
    # tier on the engine that owns it)
    spill_conf = {"spark.rapids.tpu.sql.compile.wholePlan": "OFF",
                  HBM_BUDGET_BYTES.key: min(hbm_budget_bytes, 1 << 23),
                  "spark.rapids.tpu.sql.batchSizeRows": 1 << 16}
    sspill = TpuSession(spill_conf)
    for name in ("q3", "q18"):
        if left() < 60 or name not in tpch.QUERIES:
            continue
        rec = per_q.setdefault(name, {})
        try:
            sctx = ExecContext(sspill.conf)
            t0 = time.perf_counter()
            tpch.QUERIES[name](sspill, tables).physical().collect(sctx)
            rec["spill_leg_wall_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 1)
            # the spill leg's budget peak is the interesting HBM
            # number at suite scale (the eager engine actually
            # reserves): ride it next to the wall
            speak = int(sctx.metrics.get("memory.peak_bytes") or 0)
            if speak:
                rec["spill_leg_hbm_peak_bytes"] = speak
        except Exception as e:                   # noqa: BLE001
            rec["spill_leg_error"] = f"{type(e).__name__}: {e}"[:200]
    spill_after = sum(s_["value"] for s_ in spill0.series()) \
        if spill0.series() else 0
    doc["spill_batches"] = spill_after - spill_before
    doc["exchange"] = {
        k: REGISTRY.get(f"tpu_exchange_wire_bytes_{k}_compress_total")
        .value() for k in ("pre", "post")}
    doc["queries_measured"] = len(per_q)
    doc["errors"] = sum(1 for v in per_q.values() if "error" in v)
    pc1 = persistent_cache_stats()
    doc["pcache"] = {"hits": pc1["hits"] - pc0["hits"],
                     "misses": pc1["misses"] - pc0["misses"]}
    emit(final=True)
    return doc
