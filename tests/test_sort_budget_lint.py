"""Suite-wide program lints over every TPC-H and TPC-DS plan (tier-1).

The two platform cliffs are visible in the emitted jaxpr: variadic sorts whose XLA compile time scales brutally with operand
count, and scatters whose outputs land in slow S(1) buffers.  These
tests pin both numbers for all 22 queries, so any kernel change that
re-introduces a wide lexsort or a segment scatter fails tier-1 instead
of silently costing minutes of compile at the next bench round.
"""
import pytest

from spark_rapids_tpu import tpcds, tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.testing import plan_program_stats

ALL_QUERIES = sorted(tpch.QUERIES, key=lambda q: int(q[1:]))
ALL_DS_QUERIES = sorted(tpcds.QUERIES, key=lambda q: int(q[1:]))

# With default knobs the ONLY remaining scatters live in two deliberate
# trades: the dense-domain (no-sort) group-by, which swaps them for zero
# sorts and zero row gathers (low-cardinality dictionary/bool keys), and
# the dense-domain semi/anti PRESENCE bitmap (join.matchedViaPresence —
# one bool scatter replaces the build-sized sort + merge-rank behind the
# offs table, ~10x on q21/q22-class anti joins).  Everything else —
# packed/sorted group-bys, MIN/MAX and ignore-null FIRST/LAST
# reductions, count-distinct, percentile, inner/outer joins, window
# frames — must emit ZERO scatters.
DENSE_GROUPBY_QUERIES = {"q1", "q4", "q5", "q12", "q21", "q22"}
# queries whose plans carry a dense-domain LEFT_SEMI/LEFT_ANTI at lint
# scale (CBO semi rewrites included)
DENSE_MATCHED_JOIN_QUERIES = {"q2", "q3", "q4", "q5", "q8", "q9", "q11",
                              "q16", "q17", "q18", "q20", "q21", "q22"}
SCATTER_ALLOWED = DENSE_GROUPBY_QUERIES | DENSE_MATCHED_JOIN_QUERIES


@pytest.fixture(scope="module")
def tables():
    return tpch.gen_tables(scale=0.001)


@pytest.fixture(scope="module")
def suite_stats(tables):
    s = TpuSession()
    out = {}
    for name in ALL_QUERIES:
        q = tpch.QUERIES[name](s, tables).physical()
        out[name] = plan_program_stats(q)
    return out


def test_sort_operand_budget_suite_wide(suite_stats):
    """No emitted TPC-H program contains a sort with more than 2
    operands (1 key + the payload/iota lane)."""
    wide = {n: st["sort_operand_max"] for n, st in suite_stats.items()
            if st["sort_operand_max"] > 2}
    assert not wide, f"sorts wider than 2 operands: {wide}"


def test_scatter_free_outside_dense_groupby(suite_stats):
    """Group-by MIN/MAX, count-distinct, expand_pairs, window and
    inner/outer join paths emit zero scatters; only the dense-domain
    group-by and dense-matched semi/anti queries may carry them (the
    two no-sort trades)."""
    dirty = {n: st["scatter_op_count"] for n, st in suite_stats.items()
             if st["scatter_op_count"] and n not in SCATTER_ALLOWED}
    assert not dirty, f"unexpected scatters: {dirty}"


def test_small_domain_dense_groupby_lowers_without_scatter(suite_stats,
                                                            monkeypatch):
    """Q1's dense program ((3, 2): 12 buckets with the null slots, its
    own update specs) is bucket-masked reductions: no scatter in the
    lowered text, nor in the whole-plan program; the same specs over a
    domain above ops/groupby.py MASKED_DOMAIN_MAX still scatter."""
    import jax
    from spark_rapids_tpu.ops import groupby as G
    seen = []
    real = G.dense_groupby_trace

    def spy(domain_sizes, agg_specs, capacity):
        fn = real(domain_sizes, agg_specs, capacity)

        def run(*args):
            seen.append((tuple(domain_sizes), list(agg_specs), capacity,
                         jax.tree_util.tree_map(
                             lambda x: jax.ShapeDtypeStruct(x.shape,
                                                            x.dtype),
                             args)))
            return fn(*args)
        return run

    monkeypatch.setattr(G, "dense_groupby_trace", spy)
    # a capacity that no other test of this module traces: the spy sees
    # a trace, not a cached program
    q = tpch.QUERIES["q1"](TpuSession(),
                           tpch.gen_tables(scale=0.005)).physical()
    assert plan_program_stats(q)["scatter_op_count"] == 0
    assert suite_stats["q1"]["scatter_op_count"] == 0
    domains, specs, capacity, shapes = seen[0]      # the update program
    assert domains == (3, 2) and G.dense_is_masked(domains)
    assert len(specs) == 11
    text = jax.jit(real(domains, specs, capacity)).lower(*shapes).as_text()
    assert "scatter" not in text
    wide = (G.MASKED_DOMAIN_MAX, 2)
    assert not G.dense_is_masked(wide)
    text = jax.jit(real(wide, specs, capacity)).lower(*shapes).as_text()
    assert "scatter" in text


# ---------------------------------------------------------------------------
# gather budget: late materialization must keep paying for itself
# ---------------------------------------------------------------------------

# The round-5 tail (q3/q9-class join pipelines at 0.2-0.4x) is gather
# volume: chained joins re-gathering payload columns per join.  Late
# materialization (columnar/lanes.py) defers payloads behind row-id
# lanes and resolves them once at the pipeline sink; these are the
# queries whose programs must emit strictly LESS gather volume with the
# feature on, so the win cannot silently regress.  The volume is rows x
# lanes moved (`gather_lane_elems`): since PR 36 several narrow lanes
# share one 32-bit word of a packed gather, which a count of output
# elements no longer tells apart.
GATHER_BUDGET_QUERIES = ("q3", "q9", "q15", "q16")


def test_late_materialization_gather_budget(tables, suite_stats):
    """Per-query gather budget: the q3/q9/q15/q16 programs move
    strictly fewer gathered lanes x rows (and never MORE gather
    equations) with lateMaterialization on — suite_stats is the default
    (ON) conf, compared here against a fresh OFF trace."""
    off = TpuSession(
        {"spark.rapids.tpu.sql.join.lateMaterialization.enabled":
         "false"})
    for name in GATHER_BUDGET_QUERIES:
        st_on = suite_stats[name]
        st_off = plan_program_stats(tpch.QUERIES[name](off, tables)
                                    .physical())
        assert st_on["gather_lane_elems"] < st_off["gather_lane_elems"], \
            (name, st_on, st_off)
        assert st_on["gather_op_count"] <= st_off["gather_op_count"], \
            (name, st_on, st_off)


# PR 36: grouped_take moves every lane of a call in ONE gather, where the
# parent (commit 560d406) emitted one a dtype class.  Its q3 / q5 programs
# at this scale, read off the parent: (gather equations, bytes the
# gathers write).  Packing must cut the gathers and may not inflate the
# bytes by more than a tenth (a word's unused bits count).
PARENT_GATHERS = {"q3": (19, 1371872), "q5": (38, 452480)}


@pytest.mark.parametrize("name", sorted(PARENT_GATHERS))
def test_one_gather_a_row_move_budget(suite_stats, name):
    ops, nbytes = PARENT_GATHERS[name]
    st = suite_stats[name]
    assert st["gather_op_count"] < ops, st
    assert st["gather_out_bytes"] <= 1.1 * nbytes, st


# ---------------------------------------------------------------------------
# decode budget: encoded execution must keep paying for itself
# ---------------------------------------------------------------------------

# The attribution plane pins residual decode volume on these queries:
# q1 rank-gathers its ORDER BY dictionary keys, q3 remap-gathers the
# c_mktsegment equality per row, q9 pays rank tables on the n_name sort
# and remap tables around its string predicate.  Encoded execution
# (ops/encodings.py: code-space predicates + order-preserving scan
# dictionaries) removes those table gathers, so their programs must
# emit strictly LESS decode volume with the feature on (default).
ENCODED_BUDGET_QUERIES = ("q1", "q3", "q9")


def test_encoded_execution_decode_budget(tables, suite_stats):
    """Per-query decode budget: q1/q3/q9 programs expand strictly fewer
    elements through decode-signature gathers (and never MORE decode
    equations) with encoded execution on — suite_stats is the default
    (ON) conf, compared against a fresh OFF trace."""
    off = TpuSession(
        {"spark.rapids.tpu.sql.encoded.execution.enabled": "false"})
    for name in ENCODED_BUDGET_QUERIES:
        st_on = suite_stats[name]
        st_off = plan_program_stats(tpch.QUERIES[name](off, tables)
                                    .physical())
        assert st_on["decode_out_elems"] < st_off["decode_out_elems"], \
            (name, st_on, st_off)
        assert st_on["decode_op_count"] <= st_off["decode_op_count"], \
            (name, st_on, st_off)


def test_encoded_off_key_discriminant_is_neutral(tables):
    """The off-switch half of the acceptance gate: with the conf off
    the resolved policy is inert — the plan cache key carries NO
    encoding discriminant (byte-identical to pre-encoding builds) and
    no scan is marked for encoded upload."""
    from spark_rapids_tpu.exec.compiled import plan_structure_key
    from spark_rapids_tpu.exec.plan import HostScanExec
    from spark_rapids_tpu.ops.encodings import encoding_discriminant
    off = TpuSession(
        {"spark.rapids.tpu.sql.encoded.execution.enabled": "false"})
    assert encoding_discriminant(off.conf) is None
    for name in ENCODED_BUDGET_QUERIES:
        q = tpch.QUERIES[name](off, tables).physical()
        key = plan_structure_key(q.root, off.conf)
        assert key is None or len(key) == 3, name  # no 4th enc element

        def walk(n):
            if isinstance(n, HostScanExec):
                assert n.encoded_cols is None, name
            for c in n.children:
                walk(c)
        walk(q.root)


# ---------------------------------------------------------------------------
# TPC-DS tranche: the same two budgets over the new workload
# ---------------------------------------------------------------------------

# Dense-domain group-by scatters (the deliberate no-sort trade), hit via
# low-cardinality keys: demographic averages (q7/q26), the day-name
# pivot (q43), and the per-channel union re-aggregations (q56/q60);
# plus the dense-matched semi/anti presence scatters
# (join.matchedViaPresence) in the date_dim semi-filter shapes, and in
# the item and date_dim semi-filters under the window queries that trace
# since their windows do (q12/q20/q98 one each, q70 two: each is
# ops/join.py BuildTable.present; their windows add none).
DS_DENSE_GROUPBY_QUERIES = {"q7", "q26", "q43", "q56", "q60",
                            "q19", "q33", "q55", "q65", "q73", "q96",
                            "q12", "q20", "q70", "q98"}

# Not traceable as ONE whole-plan XLA program yet: q93's join probe
# sizing needs concrete counts (a build side on two keys that is not
# unique).  bench.py --suite tpcds reports it in the coverage matrix;
# its stats stay None.  The window queries (q12/q20/q36/q70/q86/q98)
# trace since windows run inside the program.
DS_UNTRACEABLE = {"q93"}


@pytest.fixture(scope="module")
def ds_tables():
    return tpcds.gen_tables(scale=0.0005)


@pytest.fixture(scope="module")
def ds_suite_stats(ds_tables):
    s = TpuSession()
    out = {}
    for name in ALL_DS_QUERIES:
        q = tpcds.QUERIES[name](s, ds_tables).physical()
        try:
            out[name] = plan_program_stats(q)
        except Exception:            # noqa: BLE001  (host-decision plans)
            out[name] = None
    return out


def test_ds_sort_operand_budget_suite_wide(ds_suite_stats):
    """No traceable TPC-DS program contains a sort wider than 2
    operands — the budget holds across the new workload's rollup,
    union and demographic join shapes."""
    wide = {n: st["sort_operand_max"] for n, st in ds_suite_stats.items()
            if st is not None and st["sort_operand_max"] > 2}
    assert not wide, f"sorts wider than 2 operands: {wide}"


def test_ds_scatter_free_outside_dense_groupby(ds_suite_stats):
    dirty = {n: st["scatter_op_count"] for n, st in ds_suite_stats.items()
             if st is not None and st["scatter_op_count"]
             and n not in DS_DENSE_GROUPBY_QUERIES}
    assert not dirty, f"unexpected scatters: {dirty}"


def test_ds_traceable_set_does_not_shrink(ds_suite_stats):
    """Whole-plan traceability is a capability: queries outside the
    known-untraceable set must keep tracing (regressions here silently
    drop them out of the lint and the bench stats)."""
    broken = {n for n, st in ds_suite_stats.items()
              if st is None and n not in DS_UNTRACEABLE}
    assert not broken, f"queries no longer whole-plan traceable: {broken}"
