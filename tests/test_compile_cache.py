"""Compile-latency plane (exec/compiled.py + runtime/compile_service.py).

Covers the four co-designed mechanisms:
  * constant-lifted canonical cache keys — literal-only query variants
    share ONE executable (whole-plan structure cache + eager jit cache),
    with oracle-checked results and no false sharing across tables;
  * bucket quantization — an explicit shape.buckets set snaps capacities
    onto few compiled shapes and matches the CPU oracle at off-bucket
    row counts;
  * the topology-safe persistent cache — a SECOND PROCESS replays a
    warmed query with zero XLA compiles (subprocess round trip on a
    shared spark.rapids.tpu.compile.cacheDir);
  * background segment compilation — split plans adopt programs the
    compile service AOT-compiled speculatively, bit-identical to the
    inline path.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as t
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.plan.aggregates import Sum
from spark_rapids_tpu.session import DataFrame, TpuSession, col, lit

ON = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
CPU = {"spark.rapids.tpu.sql.enabled": "false"}
LIFT_OFF = {"spark.rapids.tpu.sql.compile.constantLifting": "false"}


def _approx_eq(a: pa.Table, b: pa.Table) -> bool:
    """Row-order-insensitive table equality with a float tail (group-by
    output order is engine-defined)."""
    da, db = a.to_pydict(), b.to_pydict()
    if set(da) != set(db) or a.num_rows != b.num_rows:
        return False
    cols = sorted(da)
    rows_a = sorted(zip(*(da[c] for c in cols)), key=repr)
    rows_b = sorted(zip(*(db[c] for c in cols)), key=repr)
    for ra, rb in zip(rows_a, rows_b):
        for x, y in zip(ra, rb):
            if x == y:
                continue
            if isinstance(x, float) and isinstance(y, float) and \
                    abs(x - y) <= 1e-9 * max(1.0, abs(x), abs(y)):
                continue
            return False
    return True


def _oracle(df):
    return DataFrame(df._plan, TpuSession(CPU)).collect()


# ---------------------------------------------------------------------------
# constant-lifted canonical keys
# ---------------------------------------------------------------------------

def test_literal_variants_share_whole_plan_executable():
    """Two queries differing ONLY in literals compile once: the second
    adopts the first's executable from the process-wide structure cache
    (the acceptance criterion)."""
    rng = np.random.default_rng(11)
    tbl = pa.table({"k": np.arange(400, dtype=np.int64) % 9,
                    "v": rng.random(400)})
    s = TpuSession(ON)

    def q(th):
        return (s.from_arrow(tbl).filter(col("v") > lit(th))
                .group_by("k").agg((Sum(col("v")), "sv")))

    d1, d2 = q(0.25), q(0.75)
    c1, c2 = ExecContext(s.conf), ExecContext(s.conf)
    r1 = d1.physical().collect(c1)
    r2 = d2.physical().collect(c2)
    assert c1.metrics.get("compile_cache_misses") == 1
    assert c1.metrics.get("whole_plan_compiled_queries") == 1
    # the literal-variant query: ZERO compiles, one structure-cache hit
    assert not c2.metrics.get("compile_cache_misses")
    assert c2.metrics.get("whole_plan_structure_hits") == 1
    assert c2.metrics.get("whole_plan_compiled_queries") == 1
    assert _approx_eq(r1, _oracle(d1))
    assert _approx_eq(r2, _oracle(d2))


def test_literal_variants_with_lifting_off_compile_separately():
    rng = np.random.default_rng(12)
    tbl = pa.table({"v": rng.random(300)})
    s = TpuSession({**ON, **LIFT_OFF})

    def q(th):
        return s.from_arrow(tbl).filter(col("v") > lit(th)) \
            .agg((Sum(col("v")), "sv"))

    c1, c2 = ExecContext(s.conf), ExecContext(s.conf)
    r1 = q(0.2).physical().collect(c1)
    r2 = q(0.8).physical().collect(c2)
    assert c1.metrics.get("compile_cache_misses") == 1
    assert c2.metrics.get("compile_cache_misses") == 1
    assert not c2.metrics.get("whole_plan_structure_hits")
    assert _approx_eq(r1, _oracle(q(0.2)))
    assert _approx_eq(r2, _oracle(q(0.8)))


def test_eager_jit_cache_shares_literal_variants():
    """The per-operator jit cache keys canonically too: literal-variant
    filters/projections reuse the same programs on the eager engine."""
    from spark_rapids_tpu.exec import evaluator
    from spark_rapids_tpu.testing import clear_compiled_caches
    tbl = pa.table({"x": list(range(64))})
    s = TpuSession()                   # AUTO on CPU backend -> eager

    def q(a, b):
        return s.from_arrow(tbl).filter(col("x") > lit(a)) \
            .select(col("x") * lit(b), names=["y"])

    clear_compiled_caches()
    r1 = q(5, 3).collect()
    n1 = len(evaluator._JIT_CACHE)
    r2 = q(50, 7).collect()
    assert len(evaluator._JIT_CACHE) == n1
    assert r1.to_pydict()["y"] == [x * 3 for x in range(6, 64)]
    assert r2.to_pydict()["y"] == [x * 7 for x in range(51, 64)]


def test_no_false_sharing_across_tables():
    """Same canonical structure over DIFFERENT tables (different string
    dictionaries) must NOT reuse the other table's executable — the
    identity anchors guard the host data baked at trace time."""
    s = TpuSession(ON)
    t1 = pa.table({"g": ["a", "b", "a", "c"] * 25,
                   "v": np.arange(100, dtype=np.float64)})
    t2 = pa.table({"g": ["x", "y", "z", "x"] * 25,
                   "v": np.arange(100, dtype=np.float64)})

    def q(tbl):
        return s.from_arrow(tbl).filter(col("v") > lit(10.0)) \
            .group_by("g").agg((Sum(col("v")), "sv"))

    r1 = q(t1).collect()
    r2 = q(t2).collect()
    assert set(r1.column("g").to_pylist()) == {"a", "b", "c"}
    assert set(r2.column("g").to_pylist()) == {"x", "y", "z"}
    assert _approx_eq(r1, _oracle(q(t1)))
    assert _approx_eq(r2, _oracle(q(t2)))


def test_canonical_fingerprint_erases_only_lifted_positions():
    schema = t.StructType([t.StructField("x", t.LONG)])
    safe = E.GreaterThan(E.ColumnRef("x"), E.Literal(5)).bind(schema)
    also = E.GreaterThan(E.ColumnRef("x"), E.Literal(9)).bind(schema)
    assert safe.canonical_fingerprint() == also.canonical_fingerprint()
    assert safe.fingerprint() != also.fingerprint()
    # In consumes its items on host -> value-keyed either way
    in5 = E.In(E.ColumnRef("x"), [5]).bind(schema)
    in9 = E.In(E.ColumnRef("x"), [9]).bind(schema)
    assert in5.canonical_fingerprint() != in9.canonical_fingerprint()
    # null / string literals never lift
    s5 = E.EqualTo(E.ColumnRef("x"), E.Literal(None, t.LONG)).bind(schema)
    assert "None" in s5.canonical_fingerprint()


def test_lifted_literal_expressions_match_cpu_oracle():
    """Sweep literal positions under the lift whitelist against the
    per-expression CPU oracle (lifting changes how values enter the
    program, never what they compute)."""
    from spark_rapids_tpu.testing import assert_device_cpu_equal
    data = {"x": [1, 2, None, 4, 5], "f": [0.5, -1.5, 2.5, None, 4.0]}
    exprs = [
        E.Add(E.ColumnRef("x"), E.Literal(7)),
        E.Multiply(E.ColumnRef("f"), E.Literal(2.5)),
        E.GreaterThan(E.ColumnRef("x"), E.Literal(2)),
        E.If(E.LessThan(E.ColumnRef("f"), E.Literal(0.0)),
             E.Literal(-1.0), E.ColumnRef("f")),
        E.Coalesce(E.ColumnRef("x"), E.Literal(99)),
        E.CaseWhen([(E.GreaterThan(E.ColumnRef("x"), E.Literal(3)),
                     E.Literal(1))], E.Literal(0)),
        E.Literal(42),                 # top-level projection scalar
    ]
    assert_device_cpu_equal(exprs, data, approx_float=True)


# ---------------------------------------------------------------------------
# bucket quantization
# ---------------------------------------------------------------------------

def test_explicit_bucket_set_quantizes_capacities():
    from spark_rapids_tpu.columnar.device import bucket_capacity
    conf = TpuConf({"spark.rapids.tpu.sql.shape.buckets": "1024,8192"})
    assert bucket_capacity(1, conf) == 1024
    assert bucket_capacity(1024, conf) == 1024
    assert bucket_capacity(1025, conf) == 8192
    assert bucket_capacity(8192, conf) == 8192
    assert bucket_capacity(8193, conf) == 16384      # doubles past top
    assert bucket_capacity(40000, conf) == 65536


def test_bucket_set_conf_validation():
    for bad in ("8192,1024", "12,12", "a,b", "-4"):
        with pytest.raises(ValueError):
            TpuConf({"spark.rapids.tpu.sql.shape.buckets": bad}) \
                .bucket_set  # noqa: B018


@pytest.mark.parametrize("rows", [1, 1023, 1025, 2999, 9000])
def test_bucket_quantized_execution_matches_oracle(rows):
    """Off-bucket row counts pad onto the quantized set and still match
    the CPU oracle (whole-plan path)."""
    rng = np.random.default_rng(rows)
    tbl = pa.table({"k": (np.arange(rows) % 5).astype(np.int64),
                    "v": rng.random(rows)})
    s = TpuSession({**ON, "spark.rapids.tpu.sql.shape.buckets":
                    "1024,8192"})
    df = s.from_arrow(tbl).filter(col("v") > lit(0.5)) \
        .group_by("k").agg((Sum(col("v")), "sv"))
    ctx = ExecContext(s.conf)
    out = df.physical().collect(ctx)
    assert ctx.metrics.get("whole_plan_compiled_queries") == 1
    got = dict(zip(out.column("k").to_pylist(),
                   out.column("sv").to_pylist()))
    o = _oracle(df)
    want = dict(zip(o.column("k").to_pylist(),
                    o.column("sv").to_pylist()))
    assert set(got) == set(want)
    assert all(abs(got[k] - want[k]) < 1e-9 * max(1.0, abs(want[k]))
               for k in want)


def test_same_bucket_row_counts_share_program():
    """Two tables whose row counts land in ONE explicit bucket produce
    identically-shaped programs — here visible as a second-query
    whole-plan compile that still matches the oracle, and (numeric-only
    columns, no dictionaries) as equal flat input signatures."""
    s = TpuSession({**ON, "spark.rapids.tpu.sql.shape.buckets": "8192"})
    for rows in (2000, 7000):          # both -> capacity 8192
        tbl = pa.table({"v": np.arange(rows, dtype=np.float64)})
        df = s.from_arrow(tbl).filter(col("v") > lit(3.0)) \
            .agg((Sum(col("v")), "sv"))
        out = df.physical().collect(ExecContext(s.conf))
        assert _approx_eq(out, _oracle(df))


# ---------------------------------------------------------------------------
# persistent cache: subprocess round trip (zero XLA compiles on replay)
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import json, sys
import numpy as np, pyarrow as pa
from spark_rapids_tpu.session import TpuSession, col, lit
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.plan.aggregates import Sum
s = TpuSession({"spark.rapids.tpu.sql.compile.wholePlan": "ON",
                "spark.rapids.tpu.compile.cacheDir": sys.argv[1]})
t = pa.table({"k": np.arange(3000) % 7,
              "v": np.arange(3000, dtype=np.float64)})
df = s.from_arrow(t).filter(col("v") > lit(100.0)) \
     .group_by("k").agg((Sum(col("v")), "sv"))
ctx = ExecContext(s.conf)
out = df.physical().collect(ctx)
from spark_rapids_tpu.exec.compiled import persistent_cache_stats
print(json.dumps({"stats": persistent_cache_stats(),
                  "compiled": ctx.metrics.get(
                      "whole_plan_compiled_queries", 0),
                  "sv": sorted(out.column("sv").to_pylist())}))
"""


def test_persistent_cache_second_process_zero_compiles(tmp_path):
    """TPC-H-shaped proof at test scale: process A populates the
    topology-scoped persistent cache; process B replays the same query
    with ZERO XLA compiles (persistent misses == 0, hits > 0) and
    identical results."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "1",
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__)))}
    env.pop("XLA_FLAGS", None)         # single topology for both runs
    # the conf places the cache only when the variable is unset
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run():
        res = subprocess.run(
            [sys.executable, "-c", _SUBPROC, str(tmp_path / "cache")],
            env=env, capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    a = run()
    assert a["compiled"] == 1
    assert a["stats"]["misses"] > 0    # cold: really compiled
    b = run()
    assert b["compiled"] == 1
    assert b["stats"]["misses"] == 0, \
        f"warm replay performed XLA compiles: {b['stats']}"
    assert b["stats"]["hits"] > 0
    assert b["sv"] == a["sv"]
    # entries live flat in exactly the directory the conf named
    entries = os.listdir(tmp_path / "cache")
    assert entries and not any(
        os.path.isdir(tmp_path / "cache" / e) for e in entries)


# ---------------------------------------------------------------------------
# persistent cache: where it lives is decided in one place, from outside
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def test_cache_dir_resolution_order(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and is used exactly (no
    sub-directory, whatever the conf says); unset, the conf names it;
    with neither it is the fixed <checkout>/.jax_cache — no pid, time or
    temp name in the path, because a directory that moves never hits."""
    from spark_rapids_tpu.config import DEFAULT_CONF, TpuConf
    from spark_rapids_tpu.exec.compiled import resolve_cache_dir
    named = TpuConf({"spark.rapids.tpu.compile.cacheDir":
                     str(tmp_path / "conf")})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert resolve_cache_dir(DEFAULT_CONF) == (str(tmp_path / "env"), True)
    assert resolve_cache_dir(named) == (str(tmp_path / "env"), True)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert resolve_cache_dir(named) == (str(tmp_path / "conf"), False)
    fixed = os.path.join(_REPO, ".jax_cache")
    assert resolve_cache_dir(DEFAULT_CONF) == (fixed, False)
    assert resolve_cache_dir(TpuConf({})) == (fixed, False)


_WHERE = r"""
import json, sys
import jax
updates = []
_update = jax.config.update
def spy(name, val):
    updates.append(name)
    return _update(name, val)
jax.config.update = spy
sys.path.insert(0, sys.argv[1])
import conftest                          # tests/conftest.py
after_conftest = jax.config.jax_compilation_cache_dir
from spark_rapids_tpu.session import TpuSession
TpuSession()
print(json.dumps({"conftest": after_conftest,
                  "session": jax.config.jax_compilation_cache_dir,
                  "set_in_code": "jax_compilation_cache_dir" in updates}))
"""


@pytest.mark.parametrize("placed", [True, False])
def test_cache_dir_after_bootstrap_and_session(tmp_path, placed):
    """What jax ends up with after importing tests/conftest.py and after
    a plain TpuSession(): with the variable set, exactly that directory
    and no jax.config.update of it in code; unset, the fixed default."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(_REPO, ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    res = subprocess.run(
        [sys.executable, "-c", _WHERE, os.path.join(_REPO, "tests")],
        env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got == {"conftest": want, "session": want,
                   "set_in_code": not placed}, got


def test_one_call_site_sets_the_cache_dir():
    """Nothing in the tree updates jax's cache-dir config option but the
    resolver (exec/compiled.configure_persistent_cache)."""
    import re
    pat = re.compile(r"""update\(\s*["']jax_compilation_cache_dir["']""")
    hits = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d != "chiprun_out"]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    n = len(pat.findall(fh.read()))
                if n:
                    hits.append((os.path.relpath(path, _REPO), n))
    assert hits == [(os.path.join("spark_rapids_tpu", "exec",
                                  "compiled.py"), 1)], hits


# ---------------------------------------------------------------------------
# background segment compilation
# ---------------------------------------------------------------------------

def _split_conf(extra=None):
    return TpuSession({
        **ON,
        "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1024",
        **(extra or {})})


def _split_tables(n=5000):
    return (pa.table({"k": (np.arange(n) % 50).astype(np.int64),
                      "v": np.random.default_rng(0).random(n)}),
            pa.table({"k": np.arange(50, dtype=np.int64),
                      "w": np.arange(50, dtype=np.float64)}))


def _split_query(s, tables=None, under_join=None):
    """Three segments: the join, the aggregate's 50 groups, the sort.
    `under_join`: the filter's literal, with the filter moved below the
    join, so that the first seam's row count follows it (0.5 leaves
    2,500 of 5,000 rows, the 4,096 bucket; 0.9 leaves 500: 1,024)."""
    t1, t2 = tables or _split_tables()
    left = s.from_arrow(t1)
    if under_join is not None:
        left = left.filter(col("v") > lit(under_join))
    df = left.join(s.from_arrow(t2), on="k")
    if under_join is None:
        df = df.filter(col("v") > lit(0.5))
    return (df.group_by("k").agg((Sum(col("w")), "sw"))
            .sort(("sw", False, False)).limit(10))


def _same_answer(out, df) -> bool:
    o = _oracle(df)
    return out.column("k").to_pylist() == o.column("k").to_pylist() and \
        all(abs(a - b) <= 1e-9 * max(1.0, abs(b))
            for a, b in zip(out.column("sw").to_pylist(),
                            o.column("sw").to_pylist()))


def test_background_segment_compiles_are_adopted_and_correct():
    s = _split_conf()
    df = _split_query(s)
    ctx = ExecContext(s.conf)
    out = df.physical().collect(ctx)
    assert ctx.metrics.get("whole_plan_split_queries") == 1
    # downstream segments came from the background compile service
    assert ctx.metrics.get("compile_background_used", 0) >= 1
    assert _same_answer(out, df)


def test_concurrent_segment_traces_do_not_cross():
    """Speculative candidates for one downstream segment trace on
    several service threads at once (and on the main thread when every
    candidate mispredicted).  A trace installs its leaf batches on the
    shared plan nodes; unserialized, one trace's cleanup made another
    read the seam leaf's still-empty `batches` and bake a ZERO-ROW
    program (about one cold run in three returned an empty table).
    Cold every round: fresh programs, fresh row counts."""
    from spark_rapids_tpu.testing import clear_compiled_caches
    for i in range(8):
        clear_compiled_caches()
        s = _split_conf()
        n = 5000 + i
        t1 = pa.table({"k": (np.arange(n) % 50).astype(np.int64),
                       "v": np.random.default_rng(0).random(n)})
        t2 = pa.table({"k": np.arange(50, dtype=np.int64),
                       "w": np.arange(50, dtype=np.float64)})
        df = (s.from_arrow(t1).join(s.from_arrow(t2), on="k")
              .filter(col("v") > lit(0.5))
              .group_by("k").agg((Sum(col("w")), "sw"))
              .sort(("sw", False, False)).limit(10))
        out = df.physical().collect(ExecContext(s.conf))
        assert out.num_rows == 10, f"round {i}: {out.num_rows} rows"


def test_background_disabled_still_correct():
    s = _split_conf({"spark.rapids.tpu.compile.background.enabled":
                     "false"})
    df = _split_query(s)
    ctx = ExecContext(s.conf)
    out = df.physical().collect(ctx)
    assert ctx.metrics.get("whole_plan_split_queries") == 1
    assert not ctx.metrics.get("compile_background_used")
    o = _oracle(df)
    assert out.column("k").to_pylist() == o.column("k").to_pylist()


_SPEC = ("compile_speculative_submitted", "compile_speculative_cached",
         "compile_background_used", "whole_plan_structure_hits",
         "compile_cache_misses", "plan.reused")


def _collect_counts(df):
    out = df.collect()
    m = df.metrics()
    return out, {k: m.get(k, 0) for k in _SPEC}


#: what a collect of _split_query reads once its three programs are
#: cached and both seams remember their bucket, when its DataFrame was
#: built again and so plans anew
_WARM = {"compile_speculative_submitted": 0, "compile_speculative_cached": 2,
         "compile_background_used": 0, "whole_plan_structure_hits": 3,
         "compile_cache_misses": 0, "plan.reused": 0}
#: and when the same DataFrame is collected again: its kept plan finds
#: every segment among its own programs and asks no cache
_KEPT = {**_WARM, "compile_speculative_cached": 0,
         "whole_plan_structure_hits": 0, "plan.reused": 1}
FRAMES = ["same", "rebuilt"]


@pytest.mark.parametrize("frame", FRAMES)
def test_replanned_split_collects_speculate_only_when_cold(frame):
    """The first collect of a process speculates.  From the second on a
    DataFrame built again over the same tables (a serving ticket,
    bench.py: a new SplitCompiledPlan with no programs of its own) finds
    every seam's next segment in the process-wide cache, submits nothing
    to the compile service and waits for no thread; the same DataFrame
    collected again runs the programs its kept plan holds."""
    from spark_rapids_tpu.testing import clear_compiled_caches
    clear_compiled_caches()
    s = _split_conf()
    tables = _split_tables()
    df = _split_query(s, tables)
    out, cold = _collect_counts(df)
    assert cold["compile_speculative_submitted"] == 4    # 2 seams x 2 guesses
    assert cold["compile_background_used"] == 2
    assert cold["compile_speculative_cached"] == 0
    assert cold["plan.reused"] == 0
    assert _same_answer(out, df)
    for _ in range(2):
        if frame == "rebuilt":
            df = _split_query(s, tables)
        out, warm = _collect_counts(df)
        assert warm == (_KEPT if frame == "same" else _WARM)
        assert df.metrics()["whole_plan_split_queries"] == 1
        assert _same_answer(out, df)


def _successor_keys():
    """Keys of the cached programs that read a seam's output."""
    from spark_rapids_tpu.exec import compiled as C
    return [k for k in C._PLAN_EXEC_CACHE
            if any(name == "DeviceResidentScanExec" for name, _ in k[1])]


@pytest.mark.parametrize("frame", FRAMES)
@pytest.mark.parametrize("case", [
    "cold_process", "new_tables", "bucket_crossing", "evicted_entry",
    "background_off", "compile_fault"])
def test_speculation_keeps_todays_behaviour_off_the_warm_path(case, frame):
    """Where a seam has nothing to go by, or what it remembers no longer
    holds, the collect behaves as it did before seams remembered: it
    speculates (or compiles inline), and the answer is the oracle's.
    `frame`: whether the collects that follow one another are of the same
    DataFrame (its kept plan) or of one built again (plans anew)."""
    from spark_rapids_tpu.exec import compiled as C
    from spark_rapids_tpu.testing import clear_compiled_caches
    clear_compiled_caches()
    conf = {
        "background_off":
            {"spark.rapids.tpu.compile.background.enabled": "false"},
        # one candidate a seam, and inputs of one bucket (1,000 rows),
        # so that the candidate is the program the seam waits for: hit
        # 1 is segment 0's inline compile, hit 2 the background task
        "compile_fault":
            {"spark.rapids.tpu.compile.background.speculateBuckets": "1",
             "spark.rapids.tpu.test.faults": "compile:oom:nth=2"},
    }.get(case)
    s = _split_conf(conf)
    tables = _split_tables(1000 if case == "compile_fault" else 5000)

    query = {"tables": tables,
             "under_join": 0.5 if case == "bucket_crossing" else None}

    def build():
        return _split_query(s, **query)

    def again(df):
        return build() if frame == "rebuilt" else df
    df = build()
    if case not in ("cold_process", "compile_fault"):
        for _ in range(2):
            df = again(df)
            _collect_counts(df)
    # the collect under test runs through a kept plan where it is of the
    # DataFrame just collected: what the caches lost or the conf turned
    # off does not reach the programs that plan holds
    kept = frame == "same" and case in ("evicted_entry", "background_off")
    if case == "new_tables":
        # same shape, other host objects: the anchors differ
        query["tables"] = _split_tables()
        df = build()
    elif case == "bucket_crossing":
        # a lifted literal keeps every key and moves the row count
        # across a bucket boundary: 2,500 survivors -> 500
        assert C._SEAM_BUCKET_CACHE and \
            set(C._SEAM_BUCKET_CACHE.values()) == {(4096,), (1024,)}
        query["under_join"] = 0.9
        df = build()
    elif case == "evicted_entry":
        gone = _successor_keys()
        assert len(gone) >= 2
        for k in gone:
            C._PLAN_EXEC_CACHE.pop(k)
        df = again(df)
    else:
        df = again(df)
    out, got = _collect_counts(df)
    assert _same_answer(out, df)
    want = {
        # nothing remembered: both structural guesses at both seams
        "cold_process": dict(compile_speculative_submitted=4,
                             compile_background_used=2,
                             compile_speculative_cached=0,
                             whole_plan_structure_hits=0),
        # no adoption across tables; segment 0's new entry took the
        # old record with it, so both guesses again
        "new_tables": dict(compile_speculative_submitted=4,
                           compile_background_used=2,
                           compile_speculative_cached=0,
                           whole_plan_structure_hits=0),
        # seam 0 finds the 4,096-row program and submits nothing; the
        # sync says 1,024: segment 1 adopts the program that the cold
        # collect's "full collapse" guess filed, and its seam, seen for
        # the first time, speculates (one guess: both are 1,024)
        "bucket_crossing": dict(compile_speculative_cached=1,
                                compile_speculative_submitted=1,
                                compile_background_used=1,
                                compile_cache_misses=0,
                                whole_plan_structure_hits=2),
        # seam 0 remembers 1,024... and submits that bucket alone; the
        # program it files replaces segment 1's entry and record, so
        # seam 1 guesses twice
        "evicted_entry": dict(compile_speculative_cached=0,
                              compile_speculative_submitted=3,
                              compile_background_used=2,
                              whole_plan_structure_hits=1),
        "background_off": dict(compile_speculative_submitted=0,
                               compile_background_used=0,
                               compile_speculative_cached=0,
                               whole_plan_structure_hits=3),
        # re-raised at the seam, on the collecting thread: the ladder
        # answers with the eager engine
        "compile_fault": dict(compile_speculative_submitted=1,
                              compile_background_used=0),
    }[case]
    if kept:
        want = _KEPT
    assert {k: got[k] for k in want} == want, got
    if case == "compile_fault":
        from spark_rapids_tpu.runtime.faults import get_injector
        assert [(r["site"], r["hit"])
                for r in get_injector(s.conf).log] == [("compile", 2)]
        assert df.metrics().get("whole_plan_fallbacks", 0) >= 1
        return
    if case == "bucket_crossing":
        # the record moved on
        assert set(C._SEAM_BUCKET_CACHE.values()) == {(1024,)}
    # and the collect after it is warm again
    df = again(df)
    out, after = _collect_counts(df)
    assert _same_answer(out, df)
    if frame == "same":
        assert after == _KEPT
    elif case == "background_off":
        assert after == {**_WARM, "compile_speculative_cached": 0}
    else:
        assert after == _WARM


@pytest.mark.parametrize("restore", ["before_the_thunk", "during_its_trace"])
def test_late_speculative_thunk_files_nothing(monkeypatch, restore):
    """A candidate nobody waits for may start after collect has put the
    seams back into the tree, or be overtaken by that while it traces.
    It used to trace the WHOLE plan from the base tables then and file
    that program; now it sees that the tree moved on and files nothing
    (and, started late, traces nothing)."""
    from spark_rapids_tpu.exec import compiled as C
    from spark_rapids_tpu.runtime.compile_service import CompileService
    from spark_rapids_tpu.testing import clear_compiled_caches
    clear_compiled_caches()
    built, results, filed = [], [], []

    def theirs(keys):
        # a last-segment program over a 16,384-row input (other thunks
        # file theirs meanwhile)
        return [k for k in keys if not k[3] and k[2][0][0] == (16384,)]
    real_build, real_submit = C.build_plan, CompileService.submit
    real_aot = C.CompiledPlan.aot_compile

    def build(root, ctx):
        built.append(real_build(root, ctx))
        return built[-1]

    def overtaken(self, *args, **kw):
        real_aot(self, *args, **kw)
        built[-1]._restore_leaves()         # the collect ended meanwhile,
        built[-1]._install_leaves()         # and the next one began

    def submit(self, key, fn):
        if key[1:] != (2, (16384,)):        # the last seam's "no collapse"
            return real_submit(self, key, fn)
        if restore == "before_the_thunk":
            results.append(fn)              # run it after the collect
        else:
            before = set(C._PLAN_EXEC_CACHE)
            monkeypatch.setattr(C.CompiledPlan, "aot_compile", overtaken)
            results.append(fn())
            monkeypatch.setattr(C.CompiledPlan, "aot_compile", real_aot)
            filed.extend(theirs(set(C._PLAN_EXEC_CACHE) - before))
        return real_submit(self, key, lambda: None)
    monkeypatch.setattr(C, "build_plan", build)
    monkeypatch.setattr(CompileService, "submit", submit)
    s = _split_conf()
    df = _split_query(s)
    out, cold = _collect_counts(df)
    assert cold["compile_speculative_submitted"] == 4 and len(results) == 1
    if restore == "before_the_thunk":
        before = set(C._PLAN_EXEC_CACHE)
        results = [results[0]()]
        filed.extend(theirs(set(C._PLAN_EXEC_CACHE) - before))
    assert results == [None] and filed == []
    # no program over the base tables under a last segment's key
    assert not [k for k in C._PLAN_EXEC_CACHE if not k[3]
                and any(name == "HostScanExec" for name, _ in k[1])]
    assert _same_answer(out, df)


def test_compile_service_dedupes_and_reraises():
    from spark_rapids_tpu.config import DEFAULT_CONF
    from spark_rapids_tpu.runtime.compile_service import get_service
    svc = get_service(DEFAULT_CONF)
    t1 = svc.submit(("t", 1), lambda: 41 + 1)
    t1b = svc.submit(("t", 1), lambda: 0)     # deduped: same task
    assert t1 is t1b
    assert t1.wait() == 42

    def boom():
        raise ValueError("injected")

    t2 = svc.submit(("t", 2), boom)
    with pytest.raises(ValueError, match="injected"):
        t2.wait()
    svc.take(("t", 1))
    svc.take(("t", 2))


# ---------------------------------------------------------------------------
# scan-upload LRU (satellite)
# ---------------------------------------------------------------------------

def test_scan_upload_cache_byte_cap_evicts_lru():
    from spark_rapids_tpu.exec import compiled as C
    from spark_rapids_tpu.obs.registry import SCAN_UPLOAD_EVICTIONS
    C._SCAN_UPLOAD_CACHE.clear()
    # cap small enough for ~one table's upload (1000 f64 rows ~ 9KB+)
    s = TpuSession({**ON,
                    "spark.rapids.tpu.sql.scan.uploadCacheBytes":
                    str(32 * 1024)})
    before = SCAN_UPLOAD_EVICTIONS.value() or 0
    tables = [pa.table({"v": np.arange(2000, dtype=np.float64) + i})
              for i in range(4)]
    for tbl in tables:
        df = s.from_arrow(tbl).agg((Sum(col("v")), "sv"))
        df.collect()
    after = SCAN_UPLOAD_EVICTIONS.value() or 0
    assert after > before
    total = sum(e[2] for e in C._SCAN_UPLOAD_CACHE.values())
    assert total <= 32 * 1024 or len(C._SCAN_UPLOAD_CACHE) == 1


def test_prewarm_compiles_without_executing():
    tbl = pa.table({"v": np.arange(500, dtype=np.float64)})
    s = TpuSession(ON)
    df = s.from_arrow(tbl).filter(col("v") > lit(9.0)) \
        .agg((Sum(col("v")), "sv"))
    q = df.physical()
    assert q.prewarm() is True
    ctx = ExecContext(s.conf)
    out = q.collect(ctx)
    # the collect found the program ready: no compile this collect
    assert not ctx.metrics.get("compile_cache_misses")
    assert _approx_eq(out, _oracle(df))


# ---------------------------------------------------------------------------
# CI: the compile-latency regression gate
# ---------------------------------------------------------------------------

def test_check_regression_gates_median_compile_ms(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_regression", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def fixture(name, compile_ms, backend="cpu", device_ms=10.0):
        path = tmp_path / name
        path.write_text(json.dumps({
            "backend": backend,
            "tpch_suite_queries": {
                f"q{i}": {"device_ms_net": device_ms,
                          "compile_ms_cold": compile_ms}
                for i in range(1, 6)}}))
        return str(path)

    base = fixture("base.json", 8000.0)
    ok = fixture("ok.json", 9000.0)          # +12.5% < +50% threshold
    slow = fixture("slow.json", 20000.0)     # 2.5x the baseline median
    assert mod.main(["--current", ok, base]) == 0
    rc = mod.main(["--current", slow, base])
    assert rc == 1
    # backend separation: a tpu baseline never gates a cpu run
    other = fixture("tpu.json", 1000.0, backend="tpu")
    assert mod.main(["--current", slow, other]) == 0


def test_persistent_cache_concurrent_multiprocess_writers(tmp_path):
    """The serving pool's sharing contract: SEVERAL worker processes
    populate one topology-keyed persistent cache dir CONCURRENTLY
    (atomic tmp+rename entry writes — no torn entries, no collisions),
    every writer computes the right answer, and a later process replays
    with zero XLA compiles."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "1",
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__)))}
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cache = str(tmp_path / "cache")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _SUBPROC, cache],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for _ in range(3)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    # every concurrent writer answered correctly
    assert all(o["sv"] == outs[0]["sv"] for o in outs)
    # the cache is intact afterwards: a fresh process is all hits
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC, cache],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    warm = json.loads(res.stdout.strip().splitlines()[-1])
    assert warm["stats"]["misses"] == 0, \
        f"cache torn by concurrent writers: {warm['stats']}"
    assert warm["stats"]["hits"] > 0
    assert warm["sv"] == outs[0]["sv"]
