"""TPC-DS q67 at its work: an eight-column ROLLUP (nine grouping sets) and a
RANK() over a category partition, through ``POST /sql`` over parquet views
with ``store_sales`` streamed in several batches.

The statement equals the benchmark's pandas reference and runs on the
``stages`` lane: the fact is decoded once, the finest grouping set is
aggregated once from the joined rows and every coarser set from the next
finer one (``grouping.arm``), and the window runs over the materialized
union of the sets.  The rewrite of ROLLUP, CUBE and GROUPING SETS into one
aggregation and re-aggregations is held to the one-aggregation-per-set form
it replaces; an aggregate that does not decompose keeps that form.  The data
comes from the benchmark's generators, small.
"""

import importlib
import json
import logging
import math
import os
import signal
import subprocess
import sys
import urllib.request

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from spark_tpu import kernels as K
from spark_tpu import tracing
from spark_tpu.sql import analyzer as A
from spark_tpu.sql import logical as L
from spark_tpu.sql.planner import QueryExecution
from spark_tpu.tpcds.oracle import norm_value

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import datagen  # noqa: E402

TABLES = ["store_sales", "date_dim", "store", "item"]
ROWS = {"date_dim": 1826, "item": 200, "store": 12, "customer": 2000,
        "customer_demographics": 1920800, "household_demographics": 7200,
        "customer_address": 500, "promotion": 300, "store_sales": 60000}
#: ``store_sales`` is four files of 15,000 rows: four batches a scan
BATCH_ROWS = "16384"
#: the cell's literals (``benchmark/traffic/rollup-rank-http.json``)
LITERALS = {"dms": 1200}
SEED = 2 ** 31 + 67


@pytest.fixture(autouse=True)
def time_limit():
    """A limit of its own for every test of this file (seconds)."""
    def late(_signum, _frame):
        raise TimeoutError("test_rollup_rank: a test passed its 300 s")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _statement():
    with open(os.path.join(ROOT, "benchmark", "statements", "q67.sql")) as f:
        return f.read().strip().format(**LITERALS)


def _reference(tables, **kw):
    return importlib.import_module("benchmark.references.q67") \
        .reference(tables, LITERALS, **kw)


def _same(got, want, rel=1e-9):
    got = [tuple(norm_value(v) for v in r) for r in got]
    want = [tuple(norm_value(v) for v in r) for r in want]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert math.isclose(a, b, rel_tol=rel, abs_tol=1e-9), (g, w)
            else:
                assert a == b, (g, w)


@pytest.fixture(scope="module")
def q67_data(tmp_path_factory):
    """The four tables from the benchmark's generators, as the benchmark
    writes them: the fact in four parquet files, each dimension in one."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    tables = datagen.generate(SEED, ROWS, TABLES)
    base = str(tmp_path_factory.mktemp("q67"))
    for name, cols in tables.items():
        table = pa.Table.from_pandas(pd.DataFrame(cols), preserve_index=False)
        os.makedirs(os.path.join(base, name))
        parts = 4 if datagen.is_fact(name) else 1
        step = (table.num_rows + parts - 1) // parts
        for i in range(parts):
            pq.write_table(table.slice(i * step, step), os.path.join(
                base, name, f"part-{i:04d}.parquet"))
    return tables, base


@pytest.fixture(scope="module")
def streamed(spark, q67_data):
    """The session streams ``store_sales`` while the module's q67 tests
    run."""
    old = spark.conf.get("spark.tpu.scan.maxBatchRows")
    spark.conf.set("spark.tpu.scan.maxBatchRows", BATCH_ROWS)
    yield q67_data
    for name in TABLES:
        spark.catalog.dropTempView(name)
    spark.conf.set("spark.tpu.scan.maxBatchRows", str(old))


class _Http:
    """One server session, as ``benchmark/lib/engine.py`` makes it: every
    view a ``POST /sql`` of the DDL."""

    def __init__(self, spark):
        from spark_tpu.server import SQLServer
        self.srv = SQLServer(spark, port=0).start()
        self.sid = None
        self.sid = self.post("/session")["sessionId"]

    def post(self, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.srv.port}{path}", method="POST",
            data=None if body is None else json.dumps(body).encode())
        req.add_header("Content-Type", "application/json")
        if self.sid:
            req.add_header("X-Session-Id", self.sid)
        with urllib.request.urlopen(req, timeout=280) as resp:
            return json.loads(resp.read().decode())

    def sql(self, text):
        return [tuple(r) for r in self.post("/sql", {"query": text})["rows"]]


@pytest.fixture(scope="module")
def http(spark):
    h = _Http(spark)
    yield h
    h.srv.stop()


def _views(lane, spark, http, base):
    for t in TABLES:
        ddl = (f"CREATE OR REPLACE TEMP VIEW {t} AS "
               f"SELECT * FROM parquet.`{os.path.join(base, t)}`")
        if lane == "http":
            http.post("/sql", {"query": ddl})
        else:
            spark.sql(ddl)


def _run(lane, spark, http, text):
    if lane == "http":
        return http.sql(text)
    return [tuple(r) for r in spark.sql(text).collect()]


# -- the benchmark's statement -------------------------------------------------

@pytest.mark.parametrize("lane", ["session", "http"])
def test_q67_equals_the_reference(spark, streamed, http, lane):
    """The benchmark's q67 at the cell's literals, over parquet views with
    ``store_sales`` streamed: the program's rows are the pandas
    reference's, in its order, a hundred of them, NULL keys among them
    (the grand total and the subtotals) and ranks past 1."""
    tables, base = streamed
    _views(lane, spark, http, base)
    got = _run(lane, spark, http, _statement())
    ref = _reference(tables)
    _same(got, ref)
    assert len(ref) == 100
    assert ref[0][0] is None and ref[0][9] == 1          # the grand total
    assert any(r[1] is None and r[0] is not None for r in ref)
    assert max(r[9] for r in ref) > 10


@pytest.mark.parametrize("lane", ["session", "http"])
def test_q67_streams_once_on_the_stages_lane(spark, streamed, http, lane,
                                             caplog):
    """The statement runs on the ``stages`` lane with no fallback to one
    eager program; ``store_sales`` is decoded once (four files, four
    batches); nine ``grouping.arm`` spans, the finest set read from the
    joined rows and each of the eight others from the next finer set's
    groups; one ``window`` span over every set's rows."""
    _tables, base = streamed
    _views(lane, spark, http, base)
    text = _statement()
    _run(lane, spark, http, text)                 # warm: no trace-time spans
    tracing.reset()
    with caplog.at_level(logging.INFO):
        _run(lane, spark, http, text)
    assert "fallback" not in caplog.text
    spans = tracing.spans()
    root, = [s for s in spans if s.name == "statement"]
    assert root.attrs["path"] == "stages"
    assert sum(s.name == "scan.decode" for s in spans) == 4
    arms = [s.attrs for s in spans if s.name == "grouping.arm"]
    assert [(a["set"], len(a["keys"]), a["from_finer"]) for a in arms] == \
        [(0, 8, False)] + [(i, 8 - i, True) for i in range(1, 9)]
    assert arms[0]["rows_in"] is None
    assert [a["rows_in"] for a in arms[1:]] == \
        [a["rows_out"] for a in arms[:-1]]
    assert arms[-1]["rows_out"] == 1
    window, = [s.attrs for s in spans if s.name == "window"]
    assert window["funcs"] == ["rank()"]
    assert window["partition_keys"] == ["i_category"]
    assert window["rows"] == sum(a["rows_out"] for a in arms)


def test_coarser_sets_run_once_past_the_agg_capacity(spark, streamed):
    """A coarser set's groups are at most its finer set's rows, and its
    aggregate's first output capacity holds them: with
    ``spark.sql.agg.outputCapacity`` far below the sets' group counts no
    program is re-planned, and the answer is the reference's."""
    tables, base = streamed
    _views("session", spark, None, base)
    old = spark.conf.get("spark.sql.agg.outputCapacity")
    spark.conf.set("spark.sql.agg.outputCapacity", "256")
    try:
        tracing.reset()
        got = _run("session", spark, None, _statement())
    finally:
        spark.conf.set("spark.sql.agg.outputCapacity", str(old))
    _same(got, _reference(tables))
    spans = tracing.spans()
    arms = [s.attrs for s in spans if s.name == "grouping.arm"]
    assert sum(a["rows_out"] > 256 for a in arms[1:]) >= 2
    assert not [s for s in spans if s.name == "join.replan"]


@pytest.mark.parametrize("dms", [1200, 1206])
def test_window_input_capacity_is_a_bucket(spark, streamed, dms,
                                           monkeypatch):
    """The window's materialized input is padded to a multiple of a
    sixteenth of its next power of two, so its program does not follow
    the exact row count (which follows the data and the literals): the
    answer is the reference's at either DMS."""
    from spark_tpu.columnar import pad_capacity
    from spark_tpu.sql import stages
    from spark_tpu.sql.window import WindowNode
    tables, base = streamed
    _views("session", spark, None, base)
    seen = []
    eager = stages._eager

    def spy(session, plan, *a, **kw):
        if isinstance(plan, WindowNode):
            b = plan.children[0].batch
            seen.append((b.capacity, int(np.asarray(b.num_rows()))))
        return eager(session, plan, *a, **kw)

    monkeypatch.setattr(stages, "_eager", spy)
    text = _statement().replace(f"{LITERALS['dms']} AND {LITERALS['dms']}",
                                f"{dms} AND {dms}")
    got = _run("session", spark, None, text)
    want = importlib.import_module("benchmark.references.q67") \
        .reference(tables, {"dms": dms})
    _same(got, want)
    (cap, rows), = seen
    step = pad_capacity(rows) // 16
    assert cap % step == 0 and rows <= cap < rows + step


def test_references_fill_the_limit_at_the_configurations_rows():
    """At the configuration's own rows the reference fills its LIMIT 100,
    and the float32 control's sums differ from the float64 ones by more
    than the cell's limit and less than a rounding of float32: the
    comparison can tell them apart."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpcds-sf1-rollup-1chip.json")) as fh:
        rows = json.load(fh)["rows"]
    tables = datagen.generate(SEED, rows, TABLES)
    ref = _reference(tables)
    f32 = _reference(tables, float_dtype="float32")
    assert len(ref) == 100 and len(f32) == 100
    assert [r[:8] for r in f32] == [r[:8] for r in ref]
    gap = max(abs(a[8] - b[8]) / abs(b[8]) for a, b in zip(f32, ref))
    assert 1e-9 < gap < 1e-5


# -- the rewrite: one aggregation, each coarser set from a finer one ------------

@pytest.fixture(scope="module")
def gs(spark):
    """Keys with NULLs of the data (``a`` a string, ``b`` an int), measures
    with NULLs (``v``)."""
    rng = np.random.default_rng(7)
    n = 400
    a = rng.choice(["x", "y", "z", None], n).astype(object)
    b = pd.array(rng.integers(0, 4, n), dtype="Int64")
    b[rng.random(n) < 0.1] = pd.NA
    v = rng.normal(10.0, 3.0, n)
    v[rng.random(n) < 0.1] = np.nan
    frame = pd.DataFrame({"a": a, "b": b, "c": rng.integers(0, 3, n),
                          "v": v, "w": rng.integers(-5, 50, n)})
    spark.createDataFrame(frame).createOrReplaceTempView("gs")
    yield spark
    spark.catalog.dropTempView("gs")


GROUPINGS = {
    "rollup": "SELECT a, b, c, SUM(v) AS s, COUNT(v) AS n, COUNT(*) AS m, "
              "MIN(v) AS lo, MAX(w) AS hi, AVG(v) AS av, AVG(w) AS aw, "
              "grouping(a) AS ga, grouping(c) AS gc, grouping_id() AS gid "
              "FROM gs GROUP BY ROLLUP(a, b, c)",
    "cube": "SELECT a, b, SUM(w) AS s, AVG(v) AS av, MIN(a) AS la, "
            "COUNT(*) AS m, grouping_id() AS gid FROM gs GROUP BY CUBE(a, b)",
    "sets": "SELECT a, b, c, SUM(v) AS s, COUNT(*) AS m, MAX(v) AS hi "
            "FROM gs GROUP BY GROUPING SETS ((a, b), (b, c), (a), ())",
    "repeated": "SELECT a, SUM(w) AS s FROM gs "
                "GROUP BY GROUPING SETS ((a), (a), ())",
    "having": "SELECT a, b, SUM(v) AS s FROM gs GROUP BY ROLLUP(a, b) "
              "HAVING COUNT(*) > 20 AND s > 100",
    "having_key": "SELECT a, b, SUM(w) AS s FROM gs GROUP BY ROLLUP(a, b) "
                  "HAVING b = 1 OR grouping(b) = 1",
    "sum_of_key": "SELECT c, SUM(c) AS s, COUNT(c) AS n FROM gs "
                  "GROUP BY ROLLUP(c)",
    "expression_key": "SELECT c + 1 AS k, SUM(w) AS s FROM gs "
                      "GROUP BY ROLLUP(c + 1)",
    "no_rows": "SELECT a, SUM(v) AS s, COUNT(*) AS m, AVG(w) AS aw "
               "FROM gs WHERE w < -100 GROUP BY ROLLUP(a)",
}


def _rows(spark, text):
    return sorted((tuple(norm_value(v) for v in r)
                   for r in spark.sql(text).collect()),
                  key=lambda r: [(v is None, str(type(v)), v) for v in r])


def _shared_nodes(spark, text):
    plan = QueryExecution(spark, spark.sql(text)._plan).analyzed
    found = []

    def walk(node):
        if isinstance(node, L.Shared):
            found.append(node)
        for c in node.children:
            walk(c)
    walk(plan)
    return found


@pytest.mark.parametrize("case", sorted(GROUPINGS))
def test_rewrite_equals_one_aggregation_per_set(gs, case, monkeypatch):
    """Each grouping set re-aggregated from a finer one (SUM of sums, SUM of
    counts, MIN of mins, MAX of maxes, AVG as a sum over a count) gives the
    rows of one aggregation of the child per set: grouping() and
    grouping_id() per set, HAVING over the sets' aggregates, keys and
    select names, a NULL of the data apart from a rolled-up NULL, a keyless
    set over no rows."""
    text = GROUPINGS[case]
    assert _shared_nodes(gs, text)
    got = _rows(gs, text)
    monkeypatch.setattr(A, "_grouping_sets_from_finest",
                        lambda node, ordinal: None)
    assert not _shared_nodes(gs, text)
    want = _rows(gs, text)
    assert len(got) == len(want) and got
    for g, w in zip(got, want):
        for x, y in zip(g, w):
            if isinstance(x, float) and isinstance(y, float):
                assert math.isclose(x, y, rel_tol=1e-12, abs_tol=1e-12)
            else:
                assert x == y, (g, w)


def test_rollup_keeps_a_null_of_the_data_apart(gs):
    """``a`` NULL in the data under ROLLUP(a): a group of its own (grouping
    0) beside the grand total (grouping 1), each with its own count."""
    rows = gs.sql("SELECT a, grouping(a) AS g, COUNT(*) AS m FROM gs "
                  "GROUP BY ROLLUP(a)").collect()
    nulls = sorted((r["g"], r["m"]) for r in rows if r["a"] is None)
    data_nulls = gs.sql("SELECT COUNT(*) FROM gs WHERE a IS NULL").collect()
    assert nulls == [(0, data_nulls[0][0]), (1, 400)]


def test_distinct_aggregate_keeps_one_aggregation_per_set(gs):
    """COUNT(DISTINCT b) does not decompose: each set aggregates the child
    by itself, and records no ``grouping.arm``."""
    text = ("SELECT a, COUNT(DISTINCT b) AS nb FROM gs "
            "GROUP BY ROLLUP(a)")
    assert not _shared_nodes(gs, text)
    tracing.reset()
    got = dict((r["a"], r["nb"]) for r in gs.sql(text).collect()
               if r["a"] is not None)
    assert not [s for s in tracing.spans() if s.name == "grouping.arm"]
    frame = gs.table("gs").toPandas()
    want = frame.dropna(subset=["a"]).groupby("a")["b"].nunique().to_dict()
    assert got == want


def test_the_child_runs_once_on_the_local_lane(gs):
    """On the local lane too: one ``grouping.arm`` read from the child,
    the others from the sets above them, however many arms read each."""
    tracing.reset()
    gs.sql(GROUPINGS["cube"]).collect()
    arms = [s.attrs for s in tracing.spans() if s.name == "grouping.arm"]
    assert sorted((a["set"], a["from_finer"]) for a in arms) == \
        [(0, False), (1, True), (2, True), (3, True)]


# -- windows over what the stage runner materializes ---------------------------

WINDOW_OVER_AGGREGATES = {
    "aggregate": "SELECT s_store_id, SUM(ss_quantity) AS q, "
                 "RANK() OVER (ORDER BY SUM(ss_quantity) DESC) AS rk "
                 "FROM store_sales, store WHERE ss_store_sk = s_store_sk "
                 "GROUP BY s_store_id",
    "union": "SELECT k, q, RANK() OVER (PARTITION BY t ORDER BY q DESC) AS rk "
             "FROM (SELECT 's' AS t, ss_store_sk AS k, SUM(ss_quantity) AS q "
             "FROM store_sales GROUP BY ss_store_sk UNION ALL "
             "SELECT 'i', i_class_id, SUM(ss_quantity) FROM store_sales, item "
             "WHERE ss_item_sk = i_item_sk GROUP BY i_class_id) u",
}


@pytest.mark.parametrize("case", sorted(WINDOW_OVER_AGGREGATES))
def test_window_over_a_streamed_aggregate_is_a_stage(spark, streamed, case,
                                                     caplog):
    """A window over an aggregate of the streamed fact joined to a
    dimension (or over a UNION ALL of two aggregates) runs on the
    ``stages`` lane over the materialized groups: the same rows as the
    statement over the whole fact in one batch."""
    _tables, base = streamed
    _views("session", spark, None, base)
    text = WINDOW_OVER_AGGREGATES[case]
    tracing.reset()
    with caplog.at_level(logging.INFO):
        got = _rows(spark, text)
    assert "fallback" not in caplog.text
    root, = [s for s in tracing.spans() if s.name == "statement"]
    assert root.attrs["path"] == "stages"
    spark.conf.set("spark.tpu.scan.maxBatchRows", str(1 << 20))
    try:
        want = _rows(spark, text)
    finally:
        spark.conf.set("spark.tpu.scan.maxBatchRows", BATCH_ROWS)
    assert got == want and len(got) > 3


#: a window straight over the streamed join: the stage runner cannot stream it
WINDOW_OVER_A_STREAM = ("SELECT ss_item_sk, RANK() OVER (PARTITION BY i_class "
                        "ORDER BY ss_quantity DESC) AS rk FROM store_sales, "
                        "item WHERE ss_item_sk = i_item_sk")


@pytest.mark.parametrize("mode", ["true", "false", "required"])
def test_stages_mode_decides_the_eager_fallback(spark, streamed, mode,
                                                caplog):
    """``spark.tpu.stages.enabled``: ``true`` falls back to one eager
    program where the stage runner cannot stream a plan, ``false`` never
    asks it, ``required`` fails the statement instead of loading the
    oversized relation whole (the rollup cell's configuration sets it)."""
    from spark_tpu.sql.stages import NotStreamable
    _tables, base = streamed
    _views("session", spark, None, base)
    spark.conf.set("spark.tpu.stages.enabled", mode)
    try:
        with caplog.at_level(logging.INFO):
            if mode == "required":
                with pytest.raises(NotStreamable, match="required"):
                    _rows(spark, WINDOW_OVER_A_STREAM)
                return
            got = _rows(spark, WINDOW_OVER_A_STREAM)
    finally:
        spark.conf.set("spark.tpu.stages.enabled", "true")
    assert ("fallback to eager" in caplog.text) == (mode == "true")
    assert len(got) == int(np.sum(np.isin(
        _tables["store_sales"]["ss_item_sk"],
        _tables["item"]["i_item_sk"])))


@pytest.mark.parametrize("n", [1000, 4096, 1 << 14])
def test_running_max_i32_equals_numpy(n):
    """The two-level running maximum (past 1,024 elements, in whole rows)
    is numpy's ``maximum.accumulate``."""
    x = np.random.default_rng(n).integers(-10 ** 6, 10 ** 6, n) \
        .astype(np.int32)
    want = np.maximum.accumulate(x)
    assert (np.asarray(K.running_max_i32(jnp, jnp.asarray(x))) == want).all()
    assert (K.running_max_i32(np, x) == want).all()


# -- the cell rehearses from the manifest ---------------------------------------

def test_cell_rehearses_on_the_cpu(tmp_path):
    """``sf1-rollup-rank-http`` from ``BENCHMARK.json`` itself, through
    ``POST /sql`` at the configuration's ``rehearse_rows``, to its result
    line with the comparison passed.  From a copy of ``benchmark/`` beside a
    link to the program: a run clears the other cells' data out of its
    ``.work``, which another test file's rehearsal may be reading."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    str(tmp_path / "benchmark"),
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    os.symlink(os.path.join(ROOT, "spark_tpu"), str(tmp_path / "spark_tpu"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--manifest", "BENCHMARK.json", "--workload",
         "sf1-rollup-rank-http", "--seed", str(2 ** 31 + 13),
         "--seconds", "2", "--rehearse", "1", "--control", "1"],
        capture_output=True, text=True, timeout=280, env=env,
        cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()
             if x.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["compared"]["rows_wrong"]["value"] == 0
    assert result["compared"]["references_empty"]["value"] == 0
    control, = [x for x in lines if x.get("phase") == "control"]
    assert control["correct"] is False
    assert control["compared"]["float_rel_gap"]["value"] > 1e-9
