"""TPC-H Q18 over cached customer, orders and lineitem as a deployment
(ISSUE 35): the benchmark's own Q18 against its plain reference on both
engines, the HAVING's boundary order planted; comparisons of a decimal(22,2)
sum that the device computed, against Python's Decimal; where a wide decimal
still keeps an operator on the CPU; joins whose build side is bounded over
the sub-partition gate inside a whole-plan program; and what the 15-batch
sorted aggregate forced.  CPU backend, small sizes."""
import decimal
import operator
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.plan.aggregates import Max, Sum
from spark_rapids_tpu.session import DataFrame, TpuSession, col

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
ENGINES = {"eager": {"spark.rapids.tpu.sql.compile.wholePlan": "OFF"},
           "whole_plan": WHOLE}
HOST_WIDE = "128-bit host decimal lane not consumable on device"
D = decimal.Decimal


@pytest.fixture
def bench():
    """The benchmark's modules, imported as benchmarks/run.py imports
    them (`benchmarks/` on the path), and forgotten again afterwards."""
    import importlib
    import types
    before = set(sys.modules)
    sys.path[:0] = [_BENCH]
    try:
        yield types.SimpleNamespace(
            gen=importlib.import_module("data.tpch_gen_q18"),
            base=importlib.import_module("data.tpch_gen"),
            q18=importlib.import_module("queries.q18"),
            compare=importlib.import_module("harness.compare"),
            checks=importlib.import_module("harness.checks"),
            columns=importlib.import_module("harness.columns"),
            manifest=importlib.import_module("harness.manifest"))
    finally:
        sys.path.remove(_BENCH)
        for name in set(sys.modules) - before:
            if name.split(".")[0] in ("data", "queries", "harness"):
                del sys.modules[name]


def _plant(bench, tables, cents_of_two_orders):
    """Rewrite the quantities of two seven-line orders so that their lines
    add up to exactly the given cents; -> (tables, the two order keys)."""
    li = tables["lineitem"]
    key = bench.columns.ints(li["l_orderkey"])
    qty = bench.columns.cents(li["l_quantity"]).copy()
    keys, first, lines = np.unique(key, return_index=True,
                                   return_counts=True)
    picked = np.flatnonzero(lines == 7)[[3, 11]]
    for at, total in zip(picked, cents_of_two_orders):
        qty[first[at]:first[at] + 7] = [5000] * 6 + [total - 30000]
    li = li.set_column(li.schema.get_field_index("l_quantity"),
                       "l_quantity", bench.base.money(qty))
    return dict(tables, lineitem=li), [int(k) for k in keys[picked]]


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("seed,planted", [
    (2147486311, None), (3000001291, None), (4294968601, (30000, 30001))])
def test_q18_equals_its_reference_value_by_value_and_in_order(
        bench, engine, seed, planted):
    """Clause 2.4.18 as the cell runs it (decimal HAVING, no cast to
    double) at SF0.02: no operator off the device, no whole-plan fallback,
    every value and the order as the plain reference has them.  The third
    seed has one order whose lines add up to exactly 300.00 (out: the
    HAVING is `>`) and one at 300.01 (in)."""
    tables = bench.gen.gen_tables(0.02, seed, bench.q18.SOURCE_COLUMNS)
    if planted:
        tables, (at_300, over_300) = _plant(bench, tables, planted)
    df = bench.q18.build(TpuSession(ENGINES[engine]), tables)
    assert df.physical().fallback_reasons() == []
    answer = df.collect()
    reference = bench.q18.reference(tables)
    assert reference.num_rows >= (1 if planted else 0)
    verdict = bench.compare.judge([("q18", answer)], {"q18": reference}, 0)
    assert verdict["correct"], verdict["numbers"]
    m = df.metrics()
    assert not m.get("whole_plan_fallbacks")
    if engine == "whole_plan":
        assert bench.checks.collect_faults(m) == []
        assert m["whole_plan_compiled_queries"] == 1
        assert m["expr.wide_decimal_device"] >= 1
        assert m["join.build_bound_rows"] > 0
        assert m["agg.strategy.sorted"] >= 2
    if planted:
        got = dict(zip(answer["o_orderkey"].to_pylist(),
                       answer["sum_qty"].to_pylist()))
        assert at_300 not in got
        assert got[over_300] == D("300.01")


# -- a wide decimal computed on the device is one lane, and consumable ------

_GROUPS = 40


def _sums_table():
    """k, and per k three rows of x (decimal(12,2), some null, group 7 all
    null), z likewise (group 13 all null) and w, a narrow decimal: sum(x)
    and sum(z) are decimal(22,2).  A few groups are set so that every
    comparison has rows on both sides: sum(x) = 1.00 (groups 2, 18),
    = -0.75 (3, 19), = sum(z) (4, 20), = max(w) (5, 21)."""
    rng = np.random.default_rng(35)
    k = np.repeat(np.arange(_GROUPS, dtype=np.int64), 3)
    x = [None if (i % 11 == 0 or g == 7) else D(int(v)).scaleb(-2)
         for i, (g, v) in enumerate(zip(k, rng.integers(-250, 250, len(k))))]
    z = [None if g == 13 else D(int(v)).scaleb(-2)
         for g, v in zip(k, rng.integers(-250, 250, len(k)))]
    w = [D(int(v)).scaleb(-2) for v in rng.integers(-400, 400, len(k))]
    for g in (2, 18):
        x[3 * g:3 * g + 3] = [D("0.50"), D("0.25"), D("0.25")]
    for g in (3, 19):
        x[3 * g:3 * g + 3] = [D("-0.25"), None, D("-0.50")]
    for g in (4, 20):
        x[3 * g:3 * g + 3] = [D("7.10"), D("-2.05"), D("0.01")]
        z[3 * g:3 * g + 3] = [D("5.06"), None, D("0.00")]
    for g in (5, 21):
        x[3 * g:3 * g + 3] = [D("1.11"), D("1.11"), D("1.12")]
        w[3 * g:3 * g + 3] = [D("3.34"), D("-1.00"), D("3.33")]
    dec = pa.decimal128(12, 2)
    return pa.table({"k": k, "x": pa.array(x, dec), "z": pa.array(z, dec),
                     "w": pa.array(w, dec)})


def _py_sums(table, name):
    out = {}
    for g, v in zip(table["k"].to_pylist(), table[name].to_pylist()):
        if v is not None:
            out[g] = out.get(g, D(0)) + v
    return out                                   # absent: the sum is null


COMPARISONS = {"gt": (E.GreaterThan, operator.gt),
               "ge": (E.GreaterThanOrEqual, operator.ge),
               "lt": (E.LessThan, operator.lt),
               "le": (E.LessThanOrEqual, operator.le),
               "eq": (E.EqualTo, operator.eq),
               "ne": (E.NotEqual, operator.ne)}
#: right-hand sides: an integer literal, a decimal literal, another
#: device-computed decimal(22,2), a decimal(12,2) the aggregate hands on
RIGHT = {"int_literal": lambda: E.Literal(1),
         "decimal_literal": lambda: E.Literal(D("-0.75")),
         "wide_column": lambda: col("sz"),
         "narrow_column": lambda: col("mw")}


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("right", sorted(RIGHT))
@pytest.mark.parametrize("op", sorted(COMPARISONS))
def test_a_device_computed_wide_sum_compares_as_python_decimal(
        op, right, engine):
    table = _sums_table()
    expr, py = COMPARISONS[op]
    df = (TpuSession(ENGINES[engine]).from_arrow(table).group_by("k")
          .agg((Sum(col("x")), "sx"), (Sum(col("z")), "sz"),
               (Max(col("w")), "mw"))
          .filter(expr(col("sx"), RIGHT[right]()))
          .sort("k"))
    assert df.schema["sx"].data_type == T.DecimalType(22, 2)
    assert df.physical().fallback_reasons() == []
    sx, sz = _py_sums(table, "x"), _py_sums(table, "z")
    mw = {}
    for g, v in zip(table["k"].to_pylist(), table["w"].to_pylist()):
        mw[g] = max(mw.get(g, v), v)
    other = {"int_literal": lambda g: D(1),
             "decimal_literal": lambda g: D("-0.75"),
             "wide_column": sz.get, "narrow_column": mw.get}[right]
    # a comparison with null is null, and the row is filtered out
    want = [g for g in range(_GROUPS)
            if g in sx and other(g) is not None and py(sx[g], other(g))]
    assert 0 < len(want) < _GROUPS - 2
    got = df.collect()
    assert got["k"].to_pylist() == want
    assert got["sx"].to_pylist() == [sx[g] for g in want]
    m = df.metrics()
    assert not m.get("whole_plan_fallbacks")
    if engine == "whole_plan":
        assert m["whole_plan_compiled_queries"] == 1
        assert m["expr.wide_decimal_device"] == 1


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_arithmetic_casts_and_aggregates_over_a_device_computed_sum(engine):
    """What else guards on the same test: arithmetic, a cast and a second
    aggregate over the decimal(22,2) sum stay on the device and agree with
    the engine's own CPU path."""
    table = _sums_table()

    def build(session):
        sums = (session.from_arrow(table).group_by("k")
                .agg((Sum(col("x")), "sx"), (Sum(col("z")), "sz")))
        return (sums.select(
            E.Alias(E.Remainder(col("k"), E.Literal(4)), "b"),
            E.Alias(E.Add(col("sx"), col("sz")), "both"),
            E.Alias(E.Cast(col("sx"), T.DOUBLE), "as_double"),
            col("sx"), names=["b", "both", "as_double", "sx"])
            .group_by("b").agg((Sum(col("sx")), "ssx"),
                               (Sum(col("both")), "sboth"),
                               (Max(col("as_double")), "top"))
            .sort("b"))
    df = build(TpuSession(ENGINES[engine]))
    assert df.physical().fallback_reasons() == []
    cpu = build(TpuSession({"spark.rapids.tpu.sql.enabled": "false"}))
    got, want = df.collect().to_pydict(), cpu.collect().to_pydict()
    # the device's decimal -> double is a division, the CPU's a parse
    assert got.pop("top") == pytest.approx(want.pop("top"), rel=1e-15)
    assert got == want
    m = df.metrics()
    assert not m.get("whole_plan_fallbacks")
    if engine == "whole_plan":
        assert m["expr.wide_decimal_device"] >= 3


def _wide_host_table():
    wide = pa.decimal128(22, 2)
    return pa.table({
        "k": pa.array([1, 2, 3, 4], pa.int64()),
        "d": pa.array([D("1.50"), D("12345678901234567890.12"), None,
                       D("-3.25")], wide)})


@pytest.mark.parametrize("shape", ["filter", "arithmetic", "aggregate",
                                   "through_a_project", "cpu_aggregate"])
def test_a_wide_decimal_from_the_host_still_plans_onto_the_cpu(shape):
    """A decimal(22,2) that arrives from a scan, or from an operator placed
    on the CPU, is the two-lane value: whatever would compute over it stays
    on the CPU, with the reason it always had, and the answer is the CPU
    engine's."""
    conf = dict(WHOLE)
    if shape == "cpu_aggregate":
        # the sum is computed, but by an aggregate that the conf keeps on
        # the CPU: its result comes up through an upload as two lanes
        conf["spark.rapids.tpu.sql.exec.AggregateExec"] = "false"

    def build(session):
        df = session.from_arrow(_wide_host_table())
        if shape == "filter":
            return df.filter(E.GreaterThan(col("d"), E.Literal(1)))
        if shape == "arithmetic":
            return df.select(col("k"), E.Alias(
                E.Add(col("d"), E.Literal(D("1.00"))), "e"),
                names=["k", "e"])
        if shape == "aggregate":
            return df.group_by("k").agg((Sum(col("d")), "s")).sort("k")
        if shape == "through_a_project":
            return (df.select(col("k"), E.Alias(col("d"), "e"),
                              names=["k", "e"])
                    .filter(E.LessThan(col("e"), E.Literal(2))))
        narrow = session.from_arrow(_sums_table())
        return (narrow.group_by("k").agg((Sum(col("x")), "sx"))
                .filter(E.GreaterThan(col("sx"), E.Literal(1))).sort("k"))
    df = build(TpuSession(conf))
    reasons = df.physical().fallback_reasons()
    assert any(HOST_WIDE in r for r in reasons), reasons
    cpu = build(TpuSession({"spark.rapids.tpu.sql.enabled": "false"}))
    assert df.collect().to_pydict() == cpu.collect().to_pydict()
    assert "expr.wide_decimal_device" not in df.metrics()


def test_a_two_lane_input_to_a_device_aggregate_fails_fast():
    """The exec built by hand over a scan of wide decimals: the batch says
    that the column has two lanes, and the aggregate refuses it."""
    from spark_rapids_tpu.exec.plan import (ExecContext, HashAggregateExec,
                                            HostScanExec)
    scan = HostScanExec.from_table(_wide_host_table(), 1024)
    agg = HashAggregateExec([col("k")], ["k"], [(Sum(col("d")), "s")], scan)
    with pytest.raises(NotImplementedError, match="128-bit host decimal"):
        list(agg.execute(ExecContext()))


# -- joins past the sub-partition gate, inside a whole-plan program -------

@pytest.mark.parametrize("how", ["inner", "left_semi"])
@pytest.mark.parametrize("build", ["unique", "selective"])
def test_a_join_bounded_over_the_gate_runs_whole_plan(how, build):
    """batchSizeRows 1,024 puts the gate (2 x batchSizeRows) at 2,048 rows;
    the build side is bounded at 6,144.  `unique`: a scan of distinct
    keys.  `selective`: a filter leaves 40 of its 6,000 rows live, under
    the capacity of all of them.  The traced join asks the host for no
    count and builds in-program."""
    rng = np.random.default_rng(18)
    n_build, n_probe = 6000, 9000
    bk = np.arange(n_build, dtype=np.int64) * 3
    right = pa.table({"bk": bk, "bv": rng.integers(0, 10**6, n_build)})
    left = pa.table({"pk": rng.integers(0, 3 * n_build, n_probe),
                     "pv": np.arange(n_probe, dtype=np.int64),
                     "g": np.arange(n_probe, dtype=np.int64) % 5})
    conf = dict(WHOLE, **{"spark.rapids.tpu.sql.batchSizeRows": "1024"})

    def build_df(session):
        r = session.from_arrow(right)
        if build == "selective":
            r = r.filter(E.LessThan(col("bv"), E.Literal(6700)))
        j = session.from_arrow(left).join(r, left_on=["pk"],
                                          right_on=["bk"], how=how)
        return (j.group_by("g").agg((Sum(col("pv")), "s"),
                                    (Sum(col("pk")), "t")).sort("g"))
    df = build_df(TpuSession(conf))
    assert df.physical().fallback_reasons() == []
    cpu = build_df(TpuSession({"spark.rapids.tpu.sql.enabled": "false"}))
    assert df.collect().to_pydict() == cpu.collect().to_pydict()
    m = df.metrics()
    assert not m.get("whole_plan_fallbacks")
    assert m["whole_plan_compiled_queries"] == 1
    assert m["join.build_bound_rows"] >= 6 * 1024 > 2 * 1024
    assert not m.get("join_subpartition_fallbacks")


def test_q18_splits_under_its_oversized_build_sides(bench):
    """The cell's plan at a small size (19 / 5 / 1 batches of 16,384 rows,
    the gate at 32,768): the plan splits under the two build sides that
    are real work and bounded over the gate (the HAVING-filtered aggregate,
    524,288 rows of capacity for 4 live; the semi-joined orders, 81,920
    for 4), then at the outer aggregate's input and at that aggregate.
    The filter above the inner aggregate hands its mask to the seam (one
    lazy seam batch more than the 19 probe batches and the 5 of orders);
    the unique customer side builds the join to the few orders left,
    whatever the sizes; no build side is traced over the gate, and
    `join.build_bound_rows` keeps the capacity the seam found."""
    tables = bench.gen.gen_tables(0.05, 2147486311,
                                  bench.q18.SOURCE_COLUMNS)
    conf = dict(WHOLE, **{
        "spark.rapids.tpu.sql.batchSizeRows": "16384",
        "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1024"})
    df = bench.q18.build(TpuSession(conf), tables)
    reference = bench.q18.reference(tables)
    assert reference.num_rows >= 2
    for _ in range(2):            # compiled, then through the kept plan
        verdict = bench.compare.judge([("q18", df.collect())],
                                      {"q18": reference}, 0)
        assert verdict["correct"], verdict["numbers"]
        m = df.metrics()
        assert bench.checks.collect_faults(m) == []
        assert m["overhead.seam_count"] == 4
        assert m["exec_dispatches"] == 5
        assert m["overhead.seam_lazy_count"] == 19 + 5 + 1
        # the bound the seam found the largest build side at (the join
        # itself is traced at the bucket of the 4 rows that are left)
        assert m["join.build_bound_rows"] == 1 << 19
        assert m["join_aligned_fastpath"] >= 1
        assert m["agg.strategy.sorted"] == 2
        assert m["agg.partial_batches"] == 19 + 19
        assert m["expr.wide_decimal_device"] == 1


# -- what the sorted aggregate of many batches forced ----------------------

def test_a_sorted_aggregate_of_many_batches_aggregates_once(bench):
    """Q18's inner aggregate at the cell's batch count: 19 batches whose
    partials would reduce nothing.  Inside a whole-plan program a partial
    that sorts keeps its input's capacity, so merging partials sorts every
    row twice; merged as the batches came (as the eager engine bounds its
    pending set) every merge sorted all rows again at twice the capacity:
    16,384 x 2^17 rows after 19 batches, past what an int32 row id holds
    (`OverflowError` at the capacity's own sentinel).  The traced
    aggregate stacks its batches and runs ONE program at the bucket of the
    stacked capacity; the eager engine keeps its partials and merges."""
    tables = bench.gen.gen_tables(0.05, 2147486311,
                                  bench.q18.SOURCE_COLUMNS)
    keys, sums = bench.columns.group_sum(
        bench.columns.ints(tables["lineitem"]["l_orderkey"]),
        bench.columns.cents(tables["lineitem"]["l_quantity"]))
    batches = -(-tables["lineitem"].num_rows // 16384)
    assert batches == 19
    for engine, programs in (("whole_plan", 1), ("eager", None)):
        conf = dict(ENGINES[engine],
                    **{"spark.rapids.tpu.sql.batchSizeRows": "16384"})
        li = TpuSession(conf).from_arrow(tables["lineitem"])
        df = (li.group_by("l_orderkey").agg((Sum(col("l_quantity")), "q"))
              .filter(E.GreaterThan(col("q"), E.Literal(290)))
              .sort("l_orderkey"))
        got = df.collect()
        assert got["l_orderkey"].to_pylist() == keys[sums > 29000].tolist()
        m = df.metrics()
        assert m["agg.partial_batches"] == batches
        assert not m.get("whole_plan_fallbacks")
        if programs:
            assert m["agg.strategy.packed_sort"] == programs
            assert m["agg.capacity_rows"] == 1 << 19     # of 19 x 16,384
        else:
            assert m["agg.strategy.packed_sort"] > batches


@pytest.mark.parametrize("inputs", ["one_int32_lane", "one_int64_lane",
                                    "two_lanes", "count_only"])
def test_the_in_place_sorted_group_by_agrees_with_the_compacting_one(inputs):
    """packed_groupby_trace's two gather-free realisations against the
    form that sorts group starts to the front and gathers at run ends:
    the payload sort (one input lane of at most 32 bits rides the sort
    with its validity) and `in_place` (each group's result at its run's
    last row, under the returned mask).  Nulls, dead rows and null keys
    included; numpy is the referee."""
    from spark_rapids_tpu.ops import groupby as G
    rng = np.random.default_rng(len(inputs))
    n = 8192
    key = rng.integers(5, 400, n).astype(np.int64)
    key_valid = rng.random(n) > 0.02
    live = rng.random(n) > 0.3
    a = rng.integers(-1000, 1000, n)
    a_valid = rng.random(n) > 0.1
    a_valid[key == 77] = False                    # a group whose sum is null
    b = rng.integers(-10**12, 10**12, n)
    b_valid = rng.random(n) > 0.5
    dec = T.DecimalType(22, 2)
    if inputs == "one_int32_lane":
        data, valid = [a.astype(np.int32)], [a_valid]
        specs = [G.AggSpec(G.SUM, 0, dec), G.AggSpec(G.COUNT, 0, T.LONG),
                 G.AggSpec(G.MIN, 0, T.INT), G.AggSpec(G.MAX, 0, T.INT),
                 G.AggSpec(G.COUNT_ALL, -1, T.LONG)]
    elif inputs == "one_int64_lane":
        data, valid = [b], [b_valid]
        specs = [G.AggSpec(G.SUM, 0, dec), G.AggSpec(G.MAX, 0, T.LONG)]
    elif inputs == "two_lanes":
        data, valid = [a.astype(np.int32), b], [a_valid, b_valid]
        specs = [G.AggSpec(G.SUM, 0, dec), G.AggSpec(G.SUM, 1, dec),
                 G.AggSpec(G.MIN, 1, T.LONG)]
    else:
        data, valid = [], []
        specs = [G.AggSpec(G.COUNT_ALL, -1, T.LONG)]
    assert G.in_place_supported(specs)
    assert not G.in_place_supported([G.AggSpec(G.FIRST, 0, T.LONG)])
    info = [(T.LONG, True, "int64")]
    args = ((key,), (key_valid,), tuple(data), tuple(valid), live)
    forms = {}
    for in_place in (False, True):
        fn = G.groupby_trace(info, specs, n, n, pack_spec=[(0, 401)],
                             in_place=in_place)
        out_keys, outs, count, *sel = fn(*args)
        at = np.asarray(sel[0]) if in_place \
            else np.arange(n) < int(count)
        assert int(count) == at.sum()
        (kd, kv), = out_keys
        rows = {}
        for i in np.flatnonzero(at):
            k = int(kd[i]) if kv[i] else None
            rows[k] = [int(d[i]) if v[i] else None for d, v in outs]
        forms[in_place] = rows
    want = {}
    for k in {int(x) if ok else None
              for x, ok, lv in zip(key, key_valid, live) if lv}:
        mine = live & ((key == k) & key_valid if k is not None
                       else ~key_valid)
        row = []
        for spec in specs:
            if spec.kind == G.COUNT_ALL:
                row.append(int(mine.sum()))
                continue
            v = np.asarray(data[spec.input_idx])[mine & valid[spec.input_idx]]
            row.append({G.SUM: lambda: int(v.sum()) if len(v) else None,
                        G.COUNT: lambda: len(v),
                        G.MIN: lambda: int(v.min()) if len(v) else None,
                        G.MAX: lambda: int(v.max()) if len(v) else None,
                        }[spec.kind]())
        want[k] = row
    assert forms[False] == want
    assert forms[True] == want
    assert None in want and len(want) > 300


@pytest.mark.parametrize("kept,first", [(37, 64), (0, 64), (64, 64),
                                        (900, 1024), (5000, 4096)])
def test_the_first_kept_rows_by_rank_are_the_argsorts(kept, first):
    """compaction_order's two ways to the first `first` kept rows: by rank
    (a cumulative sum and a binary search) where they are at most one in
    256 of the rows, by the stable argsort otherwise; slots past the kept
    rows are the caller's to mask."""
    from spark_rapids_tpu.ops import filter as F
    n = 1 << 16
    rng = np.random.default_rng(kept + first)
    keep = np.zeros(n, bool)
    keep[rng.choice(n, kept, replace=False)] = True
    by_rank = first * F._FIRST_KEPT_SHARE <= n
    assert by_rank == (first <= 256)
    order = np.asarray(F.compaction_order(keep, first))[:first]
    want = np.flatnonzero(keep)[:first]
    assert order[:len(want)].tolist() == want.tolist()
    assert (order < n).all()
    full = np.asarray(F.compaction_order(keep))
    assert full[:kept].tolist() == np.flatnonzero(keep).tolist()


def test_a_sorted_group_by_past_int32_row_ids_fails_with_its_reason():
    from spark_rapids_tpu.ops import groupby as G
    info = [(T.LONG, True, "int64")]
    specs = [G.AggSpec("sum", 0, T.LONG)]
    for pack in (None, [(0, 100)]):
        with pytest.raises(ValueError, match="a row id is an int32"):
            G.groupby_trace(info, specs, 1 << 31, 1 << 31, pack_spec=pack)
    assert callable(G.groupby_trace(info, specs, 1 << 12, 1 << 12))


# -- the cell's files ----------------------------------------------------------

def test_manifest_of_the_q18_cell(bench):
    cell = bench.manifest.Cell("tpch-sf10.q18")
    assert (cell.config_name, cell.chips) == ("tpch-sf10-q18", 1)
    assert cell.query_names == ["q18"]
    assert cell.generator is bench.gen
    assert cell.config["session_conf"] in (
        {}, {"spark.rapids.tpu.sql.batchSizeRows": "16777216"})
    assert cell.config["reduced"] == ["tables", "columns"]
    assert cell.config["rows"] == {"customer": 1_500_000,
                                   "orders": 15_000_000,
                                   "lineitem": 59_986_052}
    q1 = bench.manifest._json(_BENCH, "configs", "tpch-sf10-q1.json",
                              what="the Q1 configuration")
    assert cell.config["guarantees"] == q1["guarantees"]
    assert cell.config["types"] == q1["types"]
    names = {n for n, _spec, _reader in cell.per_layer}
    assert {"sort_pct_of_busy", "join_build_bound_rows_per_query",
            "wide_decimal_device_per_query", "agg_sort_strategies_per_query",
            "xla_programs_roofline", "device_idle_pct"} <= names
    joins = {n for n, _s, _r in
             bench.manifest.Cell("tpch-sf1.joins").per_layer}
    assert {"sort_pct_of_busy", "join_build_bound_rows_per_query"} <= joins
    assert "wide_decimal_device_per_query" not in joins
