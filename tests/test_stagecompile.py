"""Whole-stage tensor compilation (sql/stagecompile.py): the
process-local stage-executable cache, literal-parameterized sharing,
and the fused-stage boundary contract.

The claims under test: repeated structurally-equal queries reuse ONE
compiled stage program (no fresh jax.jit per execution); literal
variants share that program with values riding as runtime arguments;
and a stage whose recorded cut schemas disagree with the unfused physical tree fails
``verify_stage_contract`` loudly, never misexecutes."""

import numpy as np
import pytest

import spark_tpu.config as C
import spark_tpu.types as T
from spark_tpu.analysis import PlanInvariantError, verify_stage_contract
from spark_tpu.sql import stagecompile as SC
from spark_tpu.sql.planner import Planner, QueryExecution


@pytest.fixture()
def sess(spark):
    s = spark.newSession()
    s.conf.set("spark.tpu.mesh.shards", "1")
    return s


def _mk(s, n=200, seed=5):
    rng = np.random.default_rng(seed)
    s.createDataFrame({
        "k": rng.integers(0, 9, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    }).createOrReplaceTempView("scq")


def _planned(s, sql):
    qe = QueryExecution(s, s.sql(sql)._plan)
    return Planner(s).plan(qe.optimized)


# ---------------------------------------------------------------------------
# executable reuse
# ---------------------------------------------------------------------------

def test_repeated_query_reuses_one_stage_executable(sess):
    _mk(sess)
    cache = SC.stage_cache()
    q = "SELECT k, sum(v) AS sv FROM scq GROUP BY k ORDER BY k"
    a1 = [tuple(r) for r in sess.sql(q).collect()]
    s0 = cache.stats()
    a2 = [tuple(r) for r in sess.sql(q).collect()]
    s1 = cache.stats()
    assert a2 == a1
    assert s1["builds"] == s0["builds"], \
        "second run of an identical query must not compile a new stage"
    assert s1["hits"] > s0["hits"]
    assert s1["dispatches"] > s0["dispatches"]


def test_literal_variants_share_one_stage_executable(sess):
    _mk(sess)
    cache = SC.stage_cache()
    sess.sql("SELECT k, v FROM scq WHERE v < 500").collect()
    s0 = cache.stats()
    got = [tuple(r)
           for r in sess.sql("SELECT k, v FROM scq WHERE v < 100"
                             ).collect()]
    s1 = cache.stats()
    assert s1["builds"] == s0["builds"], \
        "a slotted literal variant must reuse the compiled stage"
    assert s1["hits"] > s0["hits"]
    # and the parameterized run uses the NEW literal, not the baked one
    assert got and all(v < 100 for _k, v in got)


def test_stage_fingerprint_separates_structures(sess):
    _mk(sess)
    pq1 = _planned(sess, "SELECT k + 1 AS a FROM scq")
    pq2 = _planned(sess, "SELECT k * 2 AS a FROM scq")
    k1, _ = SC.stage_fingerprint(pq1.physical)
    k2, _ = SC.stage_fingerprint(pq2.physical)
    assert k1 != k2
    # literal-only variants collapse to one key with aligned slots
    pq3 = _planned(sess, "SELECT k + 2 AS a FROM scq")
    k3, slots3 = SC.stage_fingerprint(pq3.physical)
    k1b, slots1 = SC.stage_fingerprint(pq1.physical)
    assert k3 == k1b
    assert [l.value for l in slots1] != [l.value for l in slots3]


def test_stage_cache_entry_bound_is_lru(sess):
    c = SC.StageCache(max_entries=2)
    for i in range(4):
        c.get_or_build(f"k{i}", lambda: ((lambda x: x), None))
    assert len(c) == 2
    assert c.stats()["builds"] == 4


# ---------------------------------------------------------------------------
# fused-stage boundary contract (analysis.verify_stage_contract)
# ---------------------------------------------------------------------------

def test_stage_contract_holds_for_planned_stage(sess):
    _mk(sess)
    pq = _planned(sess, "SELECT k, v * 2 AS w FROM scq WHERE v < 300")
    stage = SC.Stage(pq.physical, [b.schema for b in pq.leaves],
                     pq.physical.schema())
    verify_stage_contract(stage)       # no raise
    assert stage.n_ops == SC.count_ops(pq.physical) >= 3


def test_stage_contract_golden_broken_out_schema(sess):
    _mk(sess)
    pq = _planned(sess, "SELECT k, v FROM scq WHERE v < 300")
    good = pq.physical.schema()
    renamed = T.StructType(
        [T.StructField("WRONG", good.fields[0].dataType)]
        + list(good.fields[1:]))
    stage = SC.Stage(pq.physical, [b.schema for b in pq.leaves], renamed)
    with pytest.raises(PlanInvariantError) as ei:
        verify_stage_contract(stage)
    assert "stage-cut-schema" in str(ei.value)


def test_stage_contract_golden_broken_out_dtype(sess):
    _mk(sess)
    pq = _planned(sess, "SELECT k, v FROM scq WHERE v < 300")
    good = pq.physical.schema()
    retyped = T.StructType(
        [T.StructField(good.fields[0].name, T.float64)]
        + list(good.fields[1:]))
    stage = SC.Stage(pq.physical, [b.schema for b in pq.leaves], retyped)
    with pytest.raises(PlanInvariantError) as ei:
        verify_stage_contract(stage)
    assert "stage-cut-dtype" in str(ei.value)


def test_stage_contract_golden_missing_input_cut(sess):
    _mk(sess)
    pq = _planned(sess, "SELECT k FROM scq")
    stage = SC.Stage(pq.physical, [], pq.physical.schema())
    with pytest.raises(PlanInvariantError) as ei:
        verify_stage_contract(stage)
    assert "stage-scan-leaf" in str(ei.value)


def test_stage_contract_golden_broken_input_cut(sess):
    _mk(sess)
    pq = _planned(sess, "SELECT k, v FROM scq")
    bad_in = [T.StructType([T.StructField("zz", T.int64)])
              for _b in pq.leaves]
    stage = SC.Stage(pq.physical, bad_in, pq.physical.schema())
    with pytest.raises(PlanInvariantError) as ei:
        verify_stage_contract(stage)
    assert "stage-cut" in str(ei.value)


# ---------------------------------------------------------------------------
# observability plumbing
# ---------------------------------------------------------------------------

def test_stage_cache_stats_shape(sess):
    _mk(sess)
    sess.sql("SELECT count(*) AS c FROM scq").collect()
    st = SC.stage_cache().stats()
    for key in ("hits", "misses", "builds", "dispatches", "compile_ms",
                "entries", "stages_fused", "ops_per_stage"):
        assert key in st
    assert st["dispatches"] >= 1 and st["entries"] >= 1
    assert st["ops_per_stage"] >= 1


# ---------------------------------------------------------------------------
# run planes on device (ISSUE 20): the compressed stage-input form
# ---------------------------------------------------------------------------

def _run_leaf(n_runs=16, rep=32, heads=None):
    """A one-batch leaf whose 'ts' column is an unmaterialized run table
    over n_runs*rep rows, plus a dense 'v' column."""
    from spark_tpu.columnar import ColumnBatch, ColumnVector, RunColumnVector
    heads = np.arange(n_runs, dtype=np.int64) if heads is None \
        else np.asarray(heads, np.int64)
    lens = np.full(len(heads), rep, dtype=np.int64)
    cap = int(lens.sum())
    rv = RunColumnVector(heads, lens, T.int64)
    vv = ColumnVector(np.arange(cap, dtype=np.int64) % 7, T.int64)
    return ColumnBatch(["ts", "v"], [rv, vv], None, cap)


def test_plan_leaves_builds_planes_and_signature(sess):
    """An eligible run leaf crosses the boundary as a plane, and the
    leaf signature gains the plane-capacity component that re-keys the
    stage away from the dense form."""
    from spark_tpu.columnar import PlaneColumnVector, RunColumnVector
    b = _run_leaf()
    out = SC.plan_leaves(sess, [b])[0]
    assert isinstance(out.column("ts"), PlaneColumnVector)
    assert not isinstance(out.column("v"), PlaneColumnVector)
    sig = SC.leaf_signature([out])
    assert "~r" in sig and SC.leaf_signature([b]) != sig


def test_plane_signature_stable_within_bucket_replans_past_it(sess):
    """Two leaves whose run counts pad to the SAME plane bucket share a
    signature (one trace serves both); growing the run count past the
    bucket re-keys — a bigger plane is a new stage program, never a
    silent shape mismatch."""
    from spark_tpu.columnar import pad_capacity
    small, bigger = 9, 13          # both pad to pad_capacity(9)?
    if pad_capacity(small) != pad_capacity(bigger):
        bigger = small             # degenerate pad fn: same-count case
    s1 = SC.leaf_signature(SC.plan_leaves(sess, [_run_leaf(small, 64)]))
    s2 = SC.leaf_signature(SC.plan_leaves(sess, [_run_leaf(
        bigger, (small * 64) // bigger if bigger != small else 64,
        heads=np.arange(bigger))]))
    # same dense capacity needed for a fair same-bucket comparison
    grown = 4 * pad_capacity(small)
    s3 = SC.leaf_signature(SC.plan_leaves(sess, [_run_leaf(grown, 64)]))
    assert ("~r%d" % pad_capacity(small)) in s1
    assert s3 != s1 and ("~r%d" % pad_capacity(grown)) in s3


def test_plan_leaves_overflow_falls_back_counted(sess):
    """A run table too large for a winning plane (pad bucket over half
    the dense capacity) stays a lazy run vector — the stage input
    materializes counted, exactly the pre-plane behavior — and the
    overflow gauge records the decision."""
    from spark_tpu import columnar as _col
    from spark_tpu.columnar import PlaneColumnVector, RunColumnVector
    n = 300
    lens = np.ones(n, dtype=np.int64); lens[:212] += 1
    rv = RunColumnVector(np.arange(n, dtype=np.int64), lens, T.int64)
    from spark_tpu.columnar import ColumnBatch
    b = ColumnBatch(["x"], [rv], None, int(lens.sum()))
    before = _col.run_plane_overflows()
    out = SC.plan_leaves(sess, [b])[0]
    assert isinstance(out.column("x"), RunColumnVector)
    assert not isinstance(out.column("x"), PlaneColumnVector)
    assert _col.run_plane_overflows() == before + 1
    # the fallback leaf materializes counted, byte-identical
    mat_before = _col.runs_materialized()
    np.testing.assert_array_equal(
        np.asarray(out.column("x").data),
        np.repeat(np.arange(n, dtype=np.int64), lens))
    assert _col.runs_materialized() > mat_before


def test_run_planes_conf_off_keeps_dense_boundary(sess):
    from spark_tpu.columnar import PlaneColumnVector
    sess.conf.set(C.STAGE_RUN_PLANES.key, "false")
    try:
        out = SC.plan_leaves(sess, [_run_leaf()])[0]
        assert not isinstance(out.column("ts"), PlaneColumnVector)
    finally:
        sess.conf.set(C.STAGE_RUN_PLANES.key, "true")


def test_plane_pytree_roundtrip():
    """flatten → unflatten preserves the plane form: two small leaves on
    the wire, the rebuilt vector still an unexpanded plane with the
    dense capacity and run count intact."""
    import jax
    from spark_tpu.columnar import (PlaneColumnVector, RunColumnVector,
                                    pad_capacity, unexpanded_plane)
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    heads = np.array([5, 3, 9], np.int64)
    lens = np.array([100, 20, 8], np.int64)
    rv = RunColumnVector(heads, lens, T.int64)
    pv = PlaneColumnVector.from_runs(rv, pad_capacity(3))
    dense = ColumnVector(np.arange(128, dtype=np.int64), T.int64)
    b = ColumnBatch(["ts", "v"], [pv, dense], None, 128)
    leaves, tree = jax.tree_util.tree_flatten(b)
    assert len(leaves) == 3          # plane_values, plane_lengths, dense
    rb = jax.tree_util.tree_unflatten(tree, leaves)
    rp = unexpanded_plane(rb.column("ts"))
    assert rp is not None
    assert rp.capacity == 128 and rp.plane_capacity == pad_capacity(3)
    np.testing.assert_array_equal(np.asarray(rp.data),
                                  np.repeat(heads, lens))


def test_plane_stage_runs_filter_agg_without_expansion(sess):
    """The tentpole end to end: an eligible filter+aggregate over a run
    leaf executes through the jitted stage lane with the column NEVER
    expanded — zero in-trace expansions, zero host materializations —
    and the answer is oracle-exact."""
    from spark_tpu import columnar as _col
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame
    b = _run_leaf(32, 16)
    dense = np.repeat(np.arange(32, dtype=np.int64), 16)
    DataFrame(sess, L.LocalRelation(b)).createOrReplaceTempView("rp_ev")
    mat0 = _col.runs_materialized()
    exp0 = _col.run_plane_expansions()
    st0 = _col.run_plane_stages()
    got = sess.sql("SELECT count(*) AS c, sum(ts) AS st FROM rp_ev "
                   "WHERE ts < 20").collect()
    assert got[0]["c"] == int((dense < 20).sum())
    assert got[0]["st"] == int(dense[dense < 20].sum())
    assert _col.run_plane_stages() > st0
    assert _col.run_plane_expansions() == exp0, \
        "eligible filter+agg must never expand the plane"
    assert _col.runs_materialized() == mat0, \
        "the device lane must never charge the host materialization counter"


def test_plane_stage_fallback_matches_plane_result(sess):
    """Planes off vs on over the same run leaf: byte-identical answers
    (the ISSUE's never-wrong contract for the dense fallback)."""
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame
    b = _run_leaf(16, 32, heads=np.arange(16)[::-1].copy())
    DataFrame(sess, L.LocalRelation(b)).createOrReplaceTempView("rp_fb")
    q = ("SELECT count(*) AS c, sum(ts) AS st, min(ts) AS mn, "
         "max(ts) AS mx FROM rp_fb WHERE ts % 3 != 1")
    on = [tuple(r) for r in sess.sql(q).collect()]
    sess.conf.set(C.STAGE_RUN_PLANES.key, "false")
    try:
        off = [tuple(r) for r in sess.sql(q).collect()]
    finally:
        sess.conf.set(C.STAGE_RUN_PLANES.key, "true")
    assert on == off


def test_plane_column_rides_a_join_probe_through_both_paths(sess):
    """A run plane memoizes its dense form at first use.  ``PJoin`` traces
    its unique-build and general paths as the two branches of one
    ``lax.cond``: a plane first expanded inside a branch would hand that
    branch's tracer to the other (``UnexpectedTracerError``), so the join
    expands its probe's planes before the conditional."""
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame
    b = _run_leaf(32, 16)
    DataFrame(sess, L.LocalRelation(b)).createOrReplaceTempView("rp_probe")
    sess.createDataFrame({"k": np.arange(7, dtype=np.int64),
                          "w": np.arange(7, dtype=np.int64) * 10}
                         ).createOrReplaceTempView("rp_dim")
    q = ("SELECT count(*) AS c, sum(ts) AS st, sum(w) AS sw "
         "FROM rp_probe JOIN rp_dim ON v = k WHERE ts < 20")
    dense = np.repeat(np.arange(32, dtype=np.int64), 16)
    v = np.arange(512, dtype=np.int64) % 7
    keep = dense < 20
    got = sess.sql(q).collect()
    assert tuple(got[0]) == (int(keep.sum()), int(dense[keep].sum()),
                             int((v[keep] * 10).sum()))
