"""ops/segments.py: the unified segmented-reduction / packed-sort layer.

Covers (1) the kernel primitives against slow references, (2) the
NaN/-0.0/null semantics of the new scatter-free MIN/MAX / FIRST/LAST
group-by reductions against the CPU oracle (the round-5 CollectSet bug
class), and (3) flip-tests proving each new config knob changes the
emitted program but never the results.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.ops.segments import (blocked_seg_scan,
                                           lexsort_capped, matched_flags,
                                           sorted_segments)
from spark_rapids_tpu.session import DataFrame, TpuSession, col
from spark_rapids_tpu.testing import (jaxpr_scatter_count,
                                      jaxpr_sort_operands)

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# kernel primitives
# ---------------------------------------------------------------------------

def _ref_seg_scan(v, b, op):
    out = np.empty_like(v)
    acc = None
    for i in range(len(v)):
        acc = v[i] if (b[i] or acc is None) else op(acc, v[i])
        out[i] = acc
    return out


@pytest.mark.parametrize("n", [17, 512, 4096, 8192])
@pytest.mark.parametrize("op,ref", [(jnp.add, np.add),
                                    (jnp.minimum, np.minimum),
                                    (jnp.maximum, np.maximum)])
def test_blocked_seg_scan_matches_reference(n, op, ref):
    b = RNG.random(n) < 0.1
    b[0] = True
    v = RNG.integers(-50, 50, n).astype(np.int64)
    got = np.asarray(blocked_seg_scan(jnp.asarray(v), jnp.asarray(b), op))
    assert (got == _ref_seg_scan(v, b, ref)).all()


def test_blocked_seg_scan_stacked_and_float():
    n = 4096
    b = RNG.random(n) < 0.05
    b[0] = True
    v2 = RNG.integers(-9, 9, (n, 3)).astype(np.int64)
    got = np.asarray(blocked_seg_scan(jnp.asarray(v2), jnp.asarray(b),
                                      jnp.add))
    for k in range(3):
        assert (got[:, k] == _ref_seg_scan(v2[:, k], b, np.add)).all()
    vf = RNG.random(n)
    gotf = np.asarray(blocked_seg_scan(jnp.asarray(vf), jnp.asarray(b),
                                       jnp.add))
    assert np.allclose(gotf, _ref_seg_scan(vf, b, np.add), rtol=1e-12)


def test_lexsort_capped_equals_lexsort_and_stays_in_budget():
    n = 1000
    lanes = [jnp.asarray(RNG.integers(0, 5, n)),
             jnp.asarray(RNG.integers(0, 3, n)),
             jnp.asarray(RNG.integers(0, 4, n))]
    want = np.asarray(jnp.lexsort(lanes))
    for cap in (2, 3, 4, 10):
        assert (np.asarray(lexsort_capped(lanes, cap)) == want).all()
    jx = jax.make_jaxpr(lambda a, b, c: lexsort_capped([a, b, c], 2))(
        *lanes)
    assert jaxpr_sort_operands(jx) <= 2
    jx3 = jax.make_jaxpr(lambda a, b, c: lexsort_capped([a, b, c], 4))(
        *lanes)
    assert jaxpr_sort_operands(jx3) == 4       # knob actually widens


def test_matched_flags_equals_scatter_reference():
    n, m = 100, 300
    idx = RNG.integers(0, n, m)
    ok = RNG.random(m) < 0.4
    want = np.zeros(n, bool)
    want[idx[ok]] = True
    got = np.asarray(matched_flags(jnp.asarray(idx), jnp.asarray(ok), n))
    assert (got == want).all()
    jx = jax.make_jaxpr(
        lambda i, o: matched_flags(i, o, n))(jnp.asarray(idx),
                                             jnp.asarray(ok))
    assert jaxpr_scatter_count(jx) == 0
    assert jaxpr_sort_operands(jx) <= 2


def test_sorted_segments_fused_pack_single_sort():
    """Bounded keys AND bounded minor lanes fold into ONE lane: the
    whole count-distinct-class ordering is a single 2-operand sort."""
    cap = 64
    info = [(None, True, "int64")]

    def run(k, kv, v, live):
        return sorted_segments(
            info, [k], [kv], live, [v, jnp.zeros((cap,), jnp.int8)],
            cap, cap, pack_spec=((0, 10),),
            minor_spec=[(0, 100), (0, 2)]).perm

    args = (jnp.asarray(RNG.integers(0, 8, cap)),
            jnp.ones((cap,), bool),
            jnp.asarray(RNG.integers(0, 99, cap)),
            jnp.ones((cap,), bool))
    jx = jax.make_jaxpr(run)(*args)
    sorts = [len(e.invars) for e in jx.jaxpr.eqns
             if e.primitive.name == "sort"]
    # one fused (key,value) order sort + one start-compaction sort
    assert max(sorts) <= 2
    assert jaxpr_scatter_count(jx) == 0


# ---------------------------------------------------------------------------
# NaN / -0.0 / null semantics of the scatter-free group-by reductions
# ---------------------------------------------------------------------------

NAN = float("nan")
DOUBLES = [1.5, -0.0, 0.0, NAN, None, -3.25, NAN, 2.5, None, -0.0,
           7.125, -1e300]
# int64 keys: scan range stats pack them into the single-sort-lane
# group-by, so these tests drive the NEW sorted-run reductions, not the
# dense-domain path a low-cardinality string key would select
KEYS = [1, 2, 1, 1, 2, 3, 3, 2, 3, 3, None, None]


def _vals_equal(a, b):
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        # -0.0 vs 0.0 must round-trip exactly
        return a == b and math.copysign(1, a) == math.copysign(1, b)
    return a == b


def _assert_tables_equal(got, want):
    gd, wd = got.to_pydict(), want.to_pydict()
    assert set(gd) == set(wd)
    for k in gd:
        assert len(gd[k]) == len(wd[k]), k
        for x, y in zip(gd[k], wd[k]):
            assert (x is None) == (y is None) and \
                (x is None or _vals_equal(x, y)), (k, x, y)


def _minmax_df(session):
    from spark_rapids_tpu.plan.aggregates import First, Last, Max, Min
    tbl = pa.table({"k": pa.array(KEYS, pa.int64()),
                    "v": pa.array(DOUBLES, pa.float64())})
    return (session.from_arrow(tbl).group_by("k")
            .agg((Min(col("v")), "mn"), (Max(col("v")), "mx"),
                 (First(col("v"), ignore_nulls=True), "fnn"),
                 (Last(col("v"), ignore_nulls=True), "lnn"))
            .sort("k"))


@pytest.mark.parametrize("scatter_free", ["true", "false"])
def test_groupby_minmax_nan_negzero_null_oracle(scatter_free):
    """Java double ordering (NaN greatest, -0.0 < 0.0) and null
    exclusion survive the scatter-free MIN/MAX and ignore-null
    FIRST/LAST kernels — device vs the CPU oracle, both knob states."""
    dev = TpuSession({
        "spark.rapids.tpu.sql.segments.scatterFree.enabled": scatter_free})
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    df = _minmax_df(dev)
    _assert_tables_equal(df.collect(),
                         DataFrame(df._plan, cpu).collect())


def test_scatter_free_emits_no_scatter():
    """The same group-by plan carries scatters exactly when the knob
    says so (both modes must agree on results — previous test)."""
    from spark_rapids_tpu.testing import plan_program_stats
    on = plan_program_stats(_minmax_df(TpuSession()).physical())
    assert on["scatter_op_count"] == 0
    off = plan_program_stats(_minmax_df(TpuSession({
        "spark.rapids.tpu.sql.segments.scatterFree.enabled": "false",
    })).physical())
    assert off["scatter_op_count"] > 0


# ---------------------------------------------------------------------------
# knob flip-tests: every swap is behavior-preserving
# ---------------------------------------------------------------------------

def _join_tables():
    n = 200
    left = pa.table({
        "k1": pa.array(RNG.integers(0, 12, n), pa.int64()),
        "k2": pa.array(RNG.integers(0, 7, n), pa.int64()),
        "lv": pa.array(RNG.integers(0, 1000, n), pa.int64())})
    m = 60
    right = pa.table({
        "r1": pa.array(RNG.integers(0, 12, m), pa.int64()),
        "r2": pa.array(RNG.integers(0, 7, m), pa.int64()),
        "rv": pa.array(RNG.integers(0, 1000, m), pa.int64())})
    return left, right


@pytest.mark.parametrize("knob", [
    "spark.rapids.tpu.sql.join.denseBuildViaSort",
    "spark.rapids.tpu.sql.join.matchedViaMerge"])
@pytest.mark.parametrize("jt", ["inner", "left_outer", "full_outer"])
def test_join_knobs_flip_same_results(knob, jt):
    left, right = _join_tables()

    def run(val):
        s = TpuSession({knob: val})
        out = (s.from_arrow(left)
               .join(s.from_arrow(right), left_on=["k1", "k2"],
                     right_on=["r1", "r2"], how=jt)
               .sort("lv", "rv").collect())
        return out.to_pydict()

    assert run("true") == run("false")


def test_max_sort_operands_flip_same_results():
    tbl = pa.table({"a": pa.array(RNG.integers(0, 4, 100), pa.int64()),
                    "b": pa.array(RNG.integers(0, 4, 100), pa.int64()),
                    "c": pa.array(RNG.integers(0, 99, 100), pa.int64())})

    def run(val):
        s = TpuSession({"spark.rapids.tpu.sql.sort.maxSortOperands": val})
        return (s.from_arrow(tbl).sort("a", "b", "c")
                .collect().to_pydict())

    assert run("2") == run("8")


def test_count_distinct_value_pack_flip():
    """count(DISTINCT) with range-bounded values must agree between the
    fused single-sort-lane path and the scatter (legacy) mode."""
    n = 500
    tbl = pa.table({"g": pa.array(RNG.integers(0, 9, n), pa.int64()),
                    "v": pa.array(RNG.integers(0, 40, n), pa.int64())})
    from spark_rapids_tpu.plan.aggregates import CountDistinct

    def run(conf):
        s = TpuSession(conf)
        return (s.from_arrow(tbl).group_by("g")
                .agg((CountDistinct(col("v")), "dv"))
                .sort("g").collect().to_pydict())

    base = run({})
    assert base == run(
        {"spark.rapids.tpu.sql.segments.scatterFree.enabled": "false"})
    assert base == run({"spark.rapids.tpu.sql.enabled": "false"})
