"""Wall-clock decomposition plane (ISSUE 18): seam, dispatch, and
padding-waste attribution for the fixed-overhead tail — the
wall_breakdown() categories (obs/profile.py), the EXPLAIN ANALYZE
surface (obs/attribution.py), the dispatch-floor microbenchmark and
seam brackets (exec/compiled.py), the history-fed `overhead_us`
admission signal (obs/history.py + obs/estimator.py), and the
check_regression seam/pad-waste gates."""
import importlib.util
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.session import TpuSession, col, lit

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
PROF = {**WHOLE, "spark.rapids.tpu.profile.segments": "true",
        "spark.rapids.tpu.trace.enabled": "true"}


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpch_tables():
    from spark_rapids_tpu import tpch
    return tpch.gen_tables(scale=0.003)


def _tbl(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({"k": pa.array(rng.integers(0, 8, n), pa.int64()),
                     "v": pa.array(rng.standard_normal(n))})


def _agg_df(s, n=4000):
    return (s.from_arrow(_tbl(n)).filter(col("v") > lit(0.0))
            .group_by("k").agg((Sum(col("v")), "sv"), (Count(None), "c")))


def _seam_df(s, n=4000):
    """Sort over join-under-agg: the row-collapse boundaries (join
    output, then the aggregate itself under the sort) split the
    whole-plan program, so the profiled run crosses seams."""
    rng = np.random.default_rng(11)
    dim = pa.table({"k2": pa.array(np.arange(8), pa.int64()),
                    "w": pa.array(rng.standard_normal(8))})
    return (s.from_arrow(_tbl(n))
            .join(s.from_arrow(dim), left_on=["k"], right_on=["k2"])
            .group_by("k").agg((Sum(col("w")), "sw"), (Count(None), "c"))
            .sort(col("k")))


def _profile(conf, n=4000, df_fn=_agg_df):
    s = TpuSession(conf)
    q = df_fn(s, n).physical()
    ctx = ExecContext(s.conf)
    q.collect(ctx)
    from spark_rapids_tpu.obs.profile import QueryProfile
    return QueryProfile.from_context(ctx), ctx


# ---------------------------------------------------------------------------
# the acceptance bar: multi-seam TPC-H plans attribute >= 90% of the
# END-TO-END wall to named categories
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", ["q2", "q3"])
def test_tpch_wall_attribution_bar(qname, tpch_tables):
    """EXPLAIN ANALYZE on a seam-heavy plan (join-under-agg re-splits
    into multiple programs under profiling) decomposes the end-to-end
    wall into named categories covering >= 90%, with the residual on
    its own `unattributed` line <= 10% (the ISSUE 18 acceptance
    criterion)."""
    from spark_rapids_tpu import tpch
    s = TpuSession(WHOLE)
    rep = tpch.QUERIES[qname](s, tpch_tables).explain_analyze()
    bd = rep.wall_breakdown
    assert bd and bd["wall_ms"] > 0, bd
    for k in ("device_compute_ms", "dispatch_ms", "seam_ms",
              "compile_ms", "fetch_ms", "host_prep_ms",
              "unattributed_ms", "attributed_pct"):
        assert k in bd, (k, bd)
    assert rep.attributed_wall_pct is not None
    assert rep.attributed_wall_pct >= 90.0, bd
    assert bd["unattributed_ms"] <= 0.10 * bd["wall_ms"] + 1e-6, bd
    # the profiled run re-splits at the known seams: the seam brackets
    # measured them with their row/byte volumes
    assert bd["seam_count"] >= 1 and bd["seam_ms"] >= 0.0, bd
    assert bd.get("seam_rows", 0) >= 0
    # dispatch overhead is priced from the measured per-backend floor
    assert bd["dispatch_floor_ms"] > 0 and bd["dispatches"] >= 1, bd
    text = rep.render()
    assert "-- wall breakdown" in text
    assert "unattributed" in text and "seam time" in text
    assert "attributed (wall)" in text


def test_wall_breakdown_categories_sum_and_wall_pct_method():
    """Named categories + residual sum to the wall (the residual is
    never negative), and attributed_wall_pct() divides by the FULL
    query span — the attributed_device_pct fix's companion."""
    prof, ctx = _profile(PROF)
    bd = prof.wall_breakdown()
    named = (bd["device_compute_ms"] + bd["dispatch_ms"] + bd["seam_ms"]
             + bd["compile_ms"] + bd["fetch_ms"] + bd["shuffle_ms"]
             + bd["host_prep_ms"] + bd["prepare_ms"] + bd["speculate_ms"]
             + bd["launch_ms"] + bd["finish_ms"])
    assert bd["unattributed_ms"] >= 0.0
    # categories + residual reconstruct the wall (3-decimal rounding
    # slack; when measured categories slightly overlap the wall the
    # residual clamps at zero and the sum may exceed it)
    total = named + bd["unattributed_ms"]
    assert total >= bd["wall_ms"] - 0.02
    if bd["unattributed_ms"] > 0.0:
        assert total == pytest.approx(bd["wall_ms"], abs=0.02)
    # pad waste is a slice of device compute, not an additive category
    assert bd["pad_waste_ms"] <= bd["device_compute_ms"] + 1e-9
    wpct = prof.attributed_wall_pct()
    assert wpct is not None and 0.0 <= wpct <= 1.0
    assert wpct == pytest.approx(
        min(1.0, bd["attributed_pct"] / 100.0))
    # the bench/per-query embed carries the same dict
    assert prof.summary()["wall_breakdown"]["wall_ms"] == bd["wall_ms"]
    assert prof.to_dict()["wall_breakdown"]["wall_ms"] == bd["wall_ms"]


def test_seam_brackets_always_on():
    """Seam accounting (host sync + re-bucket at SplitCompiledPlan
    boundaries) measures on UNPROFILED runs too — the always-on half
    of the plane — whenever the plan actually splits."""
    prof, ctx = _profile(PROF, df_fn=_seam_df)
    ov = prof.overheads()
    assert ov.get("seam_count", 0) >= 1, ov
    assert ov["seam_ms"] >= 0.0
    assert ov.get("seam_rows", 0) > 0, ov
    assert ov.get("seam_bytes", 0) > 0, ov
    # profiled run: per-dispatch floor + pad accounting rode along
    assert ov.get("dispatch_floor_ms", 0) > 0, ov
    assert ov.get("dispatch_ms", 0) > 0, ov
    assert ctx.metrics.get("exec_dispatches", 0) >= 1


def test_dispatch_floor_measured_and_cached():
    from spark_rapids_tpu.exec import compiled
    f1 = compiled.dispatch_floor_ms()
    f2 = compiled.dispatch_floor_ms()
    assert f1 > 0 and f1 == f2            # measured once, then cached
    import jax
    assert jax.default_backend() in compiled._DISPATCH_FLOOR


# ---------------------------------------------------------------------------
# padding waste responds to bucket granularity
# ---------------------------------------------------------------------------

def test_pad_waste_responds_to_bucket_granularity():
    """A coarse `sql.shape.buckets` set quantizes 4000-row batches onto
    a 65536-row program: the pad-rows accounting must show the
    quantization tax growing vs a fine bucket set."""
    fine_prof, _ = _profile(
        {**PROF, "spark.rapids.tpu.sql.shape.buckets": "4096"})
    coarse_prof, _ = _profile(
        {**PROF, "spark.rapids.tpu.sql.shape.buckets": "65536"})
    fine = fine_prof.overheads()
    coarse = coarse_prof.overheads()
    assert coarse.get("pad_rows", 0) > fine.get("pad_rows", 0), \
        (coarse, fine)
    assert coarse["pad_rows"] >= 65536 - 4000
    assert coarse.get("pad_waste_ms", 0.0) >= 0.0
    assert coarse_prof.wall_breakdown()["pad_rows"] == \
        coarse["pad_rows"]


def test_pad_rows_registry_counter():
    """tpu_pad_rows_total counts padded-minus-live rows at upload and
    per profiled segment dispatch."""
    from spark_rapids_tpu.obs.registry import PAD_ROWS
    before = {s["labels"]["site"]: s["value"] for s in PAD_ROWS.series()}
    _profile({**PROF, "spark.rapids.tpu.sql.shape.buckets": "65536"})
    after = {s["labels"]["site"]: s["value"] for s in PAD_ROWS.series()}
    assert after.get("upload", 0) > before.get("upload", 0), after
    assert after.get("segment", 0) > before.get("segment", 0), after


# ---------------------------------------------------------------------------
# the history-fed admission signal: CostEstimator.estimate() returns a
# measured-basis overhead_us after one warm run
# ---------------------------------------------------------------------------

def test_estimator_returns_measured_overhead_us(tmp_path):
    s = TpuSession({**PROF, "spark.rapids.tpu.history.dir":
                    str(tmp_path / "hist")})
    df = _seam_df(s)
    est0 = s.cost_estimate(df)
    assert est0["overhead_us"] == 0.0
    assert est0["overhead_basis"] == "none"
    q = df.physical()
    q.collect(ExecContext(s.conf))             # cold (recorded)
    q.collect(ExecContext(s.conf))             # warm (recorded)
    est = s.cost_estimate(df)
    assert est["basis"] == "exact_history"
    assert est["overhead_basis"] == "measured"
    assert est["overhead_us"] > 0.0, est       # dispatch+seam+pad tail
    assert est["seam_count"] >= 1 and est["seam_ms"] >= 0.0
    assert est["dispatch_floor_ms"] > 0


def test_history_overhead_fields_round_trip(tmp_path):
    """The overhead fields survive the store's to_dict/from_dict
    compaction round trip."""
    from spark_rapids_tpu.obs.history import _Agg
    a = _Agg()
    a.fold({"device_us": 1000.0, "wall_ms": 5.0, "compile_ms": 0.0,
            "overhead_us": 420.0, "seam_count": 2, "seam_ms": 0.3,
            "dispatch_floor_ms": 0.02}, decay=0.3)
    b = _Agg.from_dict(a.to_dict())
    assert b.overhead_us == pytest.approx(a.overhead_us)
    assert b.overhead_runs == a.overhead_runs == 1
    assert b.seam_count == 2
    assert b.seam_ms == pytest.approx(a.seam_ms)
    assert b.dispatch_floor_ms == pytest.approx(0.02)


# ---------------------------------------------------------------------------
# the CI gates: seam-count and pad-waste-share growth fail, shrink and
# other-backend baselines never cross-gate
# ---------------------------------------------------------------------------

def _bench_doc(seam_count, pad_waste_ms, backend="cpu"):
    return {"backend": backend, "tpch_suite_queries": {
        "q4": {"device_ms_net": 80.0, "wall_breakdown": {
            "wall_ms": 200.0, "seam_ms": 6.0 * seam_count,
            "seam_count": seam_count, "dispatch_ms": 3.0,
            "pad_waste_ms": pad_waste_ms}}}}


def test_check_regression_seam_and_pad_gates(tmp_path, capsys):
    gate = _load_script("check_regression")
    base = tmp_path / "base.json"
    cur = tmp_path / "cur.json"
    base.write_text(json.dumps(_bench_doc(1, 2.0)))
    # seam added (1 -> 2): red
    cur.write_text(json.dumps(_bench_doc(2, 2.0)))
    assert gate.main(["--current", str(cur), str(base)]) == 1
    assert "SEAM REGRESSION q4" in capsys.readouterr().out
    # pad-waste share blown up (1% -> 20% of profiled wall): red
    cur.write_text(json.dumps(_bench_doc(1, 40.0)))
    assert gate.main(["--current", str(cur), str(base)]) == 1
    assert "PAD-WASTE REGRESSION q4" in capsys.readouterr().out
    # unchanged: green, and the gate says it looked
    cur.write_text(json.dumps(_bench_doc(1, 2.0)))
    assert gate.main(["--current", str(cur), str(base)]) == 0
    assert "overhead ok" in capsys.readouterr().out
    # improvement direction (seam eliminated): green
    base.write_text(json.dumps(_bench_doc(2, 40.0)))
    cur.write_text(json.dumps(_bench_doc(1, 2.0)))
    assert gate.main(["--current", str(cur), str(base)]) == 0
    # other-backend baselines never cross-gate overhead fields
    base.write_text(json.dumps(_bench_doc(1, 2.0, backend="tpu")))
    cur.write_text(json.dumps(_bench_doc(3, 80.0)))
    assert gate.main(["--current", str(cur), str(base)]) == 0
    # extractor shape
    ov = gate.extract_overheads(_bench_doc(2, 10.0))
    assert ov["q4"]["seam_count"] == 2
    assert ov["q4"]["pad_waste_share"] == pytest.approx(0.05)


def test_profile_diff_overhead_family(tmp_path):
    """profile_diff surfaces seam/dispatch/pad-waste deltas as their
    own `overhead` family from bench wall_breakdown embeds (the
    seam-elimination-win fixture also runs in its --self-test)."""
    diff = _load_script("profile_diff")
    a = tmp_path / "BENCH_a.json"
    b = tmp_path / "BENCH_b.json"
    a.write_text(json.dumps(_bench_doc(2, 24.0)))
    b.write_text(json.dumps(_bench_doc(1, 24.0)))
    res = diff.diff_families(diff.load_families(str(a)),
                             diff.load_families(str(b)))
    imp = res["overhead"]["improved"]
    assert any(r["entry"] == "q4/seam_ms" for r in imp), res["overhead"]
    assert diff.self_test() == 0
