"""TPC-DS q74 and q79 at their work: ``customer`` with business ids, names
and NULLs, GROUP BY three string columns, one CTE read four times and joined
to itself on a string key, the reply ordered by strings.

Over ``CREATE TEMP VIEW t AS SELECT * FROM parquet.`...``` views, as the
benchmark and every HTTP user register tables, with ``store_sales`` streamed
in several batches: the statements equal sqlite (the suite's oracle) and the
benchmark's pandas references, through ``session.sql`` and ``POST /sql``;
another seed's ``customer`` (another dictionary) answers exactly from the
stages the first seed built; the spans and counters the cell's metrics read
are in the ring.  The data comes from the benchmark's generators
(``benchmark/generators/customer.py`` among them), small.
"""

import importlib
import json
import os
import signal
import sqlite3
import subprocess
import sys
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_tpu import tracing
from spark_tpu.tpcds import QUERIES
from spark_tpu.tpcds.oracle import norm_value
from spark_tpu.tpcds.schema import TABLES as SCHEMAS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import datagen  # noqa: E402

TABLES = ["store_sales", "web_sales", "customer", "date_dim", "store",
          "household_demographics"]
ROWS = {"date_dim": 1826, "item": 200, "store": 12, "customer": 2000,
        "customer_demographics": 1920800, "household_demographics": 7200,
        "customer_address": 500, "promotion": 300, "warehouse": 5,
        "web_site": 30, "web_page": 60, "ship_mode": 20,
        "store_sales": 60000, "web_sales": 24000}
SF1 = dict(ROWS, item=18000, customer=100000, customer_address=50000)
#: ``store_sales`` is four files of 15,000 rows: four batches a scan
BATCH_ROWS = "16384"
#: the cell's literals (``benchmark/traffic/yoy-customers-http.json``)
LITERALS = {"q74": {"year": 2000, "year1": 2001}, "q79": {}}
SEEDS = [31, 2 ** 31 + 7]
NP_TYPES = {"bigint": "int64", "int": "int32", "double": "float64"}


@pytest.fixture(autouse=True)
def time_limit():
    """A limit of its own for every test of this file (seconds)."""
    def late(_signum, _frame):
        raise TimeoutError("test_yoy_customers: a test passed its 300 s")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _statement(q):
    with open(os.path.join(ROOT, "benchmark", "statements", q + ".sql")) as f:
        return f.read().strip().format(**LITERALS[q])


def _ddl(base, table):
    return (f"CREATE OR REPLACE TEMP VIEW {table} AS "
            f"SELECT * FROM parquet.`{os.path.join(base, table)}`")


def _write(tables, base):
    """Facts in four files, dimensions in one, as the benchmark writes
    them; a sqlite copy of the same rows."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    con = sqlite3.connect(":memory:")
    for name, cols in tables.items():
        frame = pd.DataFrame(cols)
        os.makedirs(os.path.join(base, name))
        table = pa.Table.from_pandas(frame, preserve_index=False)
        parts = 4 if datagen.is_fact(name) else 1
        step = (table.num_rows + parts - 1) // parts
        for i in range(parts):
            pq.write_table(table.slice(i * step, step), os.path.join(
                base, name, f"part-{i:04d}.parquet"))
        frame.to_sql(name, con, index=False)
    return con


@pytest.fixture(scope="module")
def seeds(spark, tmp_path_factory):
    """{seed: (tables, parquet directory, sqlite connection)}; the session
    streams ``store_sales`` while the module runs."""
    old = spark.conf.get("spark.tpu.scan.maxBatchRows")
    spark.conf.set("spark.tpu.scan.maxBatchRows", BATCH_ROWS)
    made = {}
    for seed in SEEDS:
        tables = datagen.generate(seed, ROWS, TABLES)
        base = str(tmp_path_factory.mktemp(f"yoy{seed}"))
        made[seed] = (tables, base, _write(tables, base))
    yield made
    for _tables, _base, con in made.values():
        con.close()
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
        if lane == "http":
            http.post("/sql", {"query": _ddl(base, t)})
        else:
            spark.sql(_ddl(base, t))


def _run(lane, spark, http, text):
    if lane == "http":
        return http.sql(text)
    return [tuple(r) for r in spark.sql(text).collect()]


def _same(got, want):
    got = [tuple(norm_value(v) for v in r) for r in got]
    want = [tuple(norm_value(v) for v in r) for r in want]
    assert got == want


def _reference(q, tables):
    return importlib.import_module(f"benchmark.references.{q}") \
        .reference(tables, LITERALS[q])


# -- the statements, against sqlite and the pandas references -----------------

@pytest.mark.parametrize("lane", ["session", "http"])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("q", ["q74", "q79"])
def test_equals_sqlite_and_reference(spark, seeds, http, lane, seed, q):
    """The benchmark's statement at the cell's literals: the program, sqlite
    and the pandas reference give the same rows in the same order, and the
    reply is not empty."""
    tables, base, con = seeds[seed]
    _views(lane, spark, http, base)
    text = _statement(q)
    got = _run(lane, spark, http, text)
    oracle = con.execute(text).fetchall()
    ref = _reference(q, tables)
    _same(ref, oracle)              # the reference's reading of the text
    _same(got, oracle)
    assert len(oracle) >= (100 if q == "q79" else 10)
    assert any(isinstance(r[1], str) for r in oracle)  # strings in the reply


def test_the_texts_are_queries_py_s(spark, seeds):
    """The benchmark's q74 and q79 are ``spark_tpu/tpcds/queries.py``'s with
    the year as a parameter."""
    def words(text):
        return text.split()
    assert words(_statement("q74")) == words(QUERIES["q74"])
    assert words(_statement("q79")) == words(QUERIES["q79"])


@pytest.mark.parametrize("lane", ["session", "http"])
def test_another_seeds_customer_builds_no_stage(spark, seeds, http, lane):
    """Another seed's ``customer`` is another dictionary (the names' words
    come from the seed).  Where both sides of a string join carry one
    dictionary nothing of it is baked into the program, and a stage is
    keyed by its plan and its leaves' shapes, never by a dictionary: the
    second seed answers exactly from the stages the first one built."""
    from spark_tpu.sql.stagecompile import stage_cache
    (tables_a, base_a, _ca), (tables_b, base_b, _cb) = \
        (seeds[s] for s in SEEDS)
    names_a = set(tables_a["customer"].c_first_name.dropna())
    names_b = set(tables_b["customer"].c_first_name.dropna())
    assert len(names_a & names_b) < len(names_a) // 10
    text = _statement("q74")
    _views(lane, spark, http, base_a)
    _same(_run(lane, spark, http, text), _reference("q74", tables_a))
    builds = stage_cache().stats()["builds"]
    _views(lane, spark, http, base_b)
    tracing.reset()
    _same(_run(lane, spark, http, text), _reference("q74", tables_b))
    assert stage_cache().stats()["builds"] == builds
    assert not any(s.name in ("stage.build", "dict.unify")
                   for s in tracing.spans())


# -- the spans and counters the cell's metrics read ----------------------------

@pytest.mark.parametrize("lane", ["session", "http"])
def test_cte_bodies_and_string_joins_are_in_the_ring(spark, seeds, http,
                                                     lane):
    """``cte.body``: one record for every copy of ``year_total``'s body the
    statement runs (four: a CTE is substituted where it is named).
    ``join.path``'s ``string``: the three joins on ``customer_id``.
    ``dict.decode``: the reply's three string columns.  The statement
    streams: ``path`` ``stages``, two scans of each fact (the other arm of
    each ``UNION ALL`` is pruned by its constant ``sale_type``)."""
    tables, base, _con = seeds[SEEDS[0]]
    _views(lane, spark, http, base)
    text = _statement("q74")
    _run(lane, spark, http, text)           # warm: no trace-time spans
    tracing.reset()
    rows = _run(lane, spark, http, text)
    spans = tracing.spans()
    bodies = [s.attrs for s in spans if s.name == "cte.body"]
    assert [(b["name"], b["copy"]) for b in bodies] == \
        [("year_total", i) for i in range(4)]
    paths = [s.attrs for s in spans if s.name == "join.path"]
    assert sum(p["string"] for p in paths) == 3
    assert any(not p["string"] for p in paths)
    decodes = [s.attrs for s in spans if s.name == "dict.decode"]
    assert [d["rows"] for d in decodes] == [len(rows)] * 3
    root, = [s for s in spans if s.name == "statement"]
    assert root.attrs["path"] == "stages"
    # 2 scans x 4 files of each streamed fact
    assert sum(s.name == "scan.decode" for s in spans) == 2 * 4 + 2 * 4
    phases = tracing.last_statement()["phases"]
    assert "cte.body" in phases and "dict.decode" in phases


def test_dictionary_spans_of_a_streamed_string_relation(spark, seeds):
    """With ``customer`` itself streamed (a batch of 512 rows) the scan's
    pre-pass builds its dictionaries (``dict.scan``, over the statement's
    pruned columns alone: two of ``customer``'s nine string columns), every
    batch is put onto them (``dict.reencode``), and a join of first names to
    last names, two dictionaries that share a fifth of their words, builds
    one id space for both (``dict.unify``).  Equal to sqlite."""
    tables, base, con = seeds[SEEDS[0]]
    _views("session", spark, None, base)
    text = ("SELECT a.c_first_name, COUNT(*) AS n FROM customer a, customer b "
            "WHERE a.c_first_name = b.c_last_name AND a.c_customer_sk <= 600 "
            "GROUP BY a.c_first_name ORDER BY n DESC, a.c_first_name LIMIT 20")
    old = spark.conf.get("spark.tpu.scan.maxBatchRows")
    spark.conf.set("spark.tpu.scan.maxBatchRows", "512")
    tracing.reset()
    try:
        got = [tuple(r) for r in spark.sql(text).collect()]
    finally:
        spark.conf.set("spark.tpu.scan.maxBatchRows", str(old))
    _same(got, con.execute(text).fetchall())
    assert len(got) == 20
    spans = tracing.spans()
    scans = [s.attrs for s in spans if s.name == "dict.scan"]
    assert scans and all(s["columns"] == 1 for s in scans)
    cu = tables["customer"]
    assert {s["words"] for s in scans} == {
        cu.c_first_name.nunique(), cu.c_last_name.nunique()}
    assert any(s.name == "dict.reencode" for s in spans)
    unify = [s.attrs["words"] for s in spans if s.name == "dict.unify"]
    assert unify and max(unify) > 1000
    assert any(s.attrs["string"] for s in spans if s.name == "join.path")
    assert "dict.scan" in tracing.summary()["spans"]


def test_one_dictionary_on_both_sides_builds_no_table():
    """Two reads of one relation carry one dictionary: their codes already
    compare by word, whatever the dictionary's size."""
    from spark_tpu.columnar import merge_dictionaries
    from spark_tpu.sql.joins import _canonical_ids
    words = tuple(datagen.ids(range(1, 100001)))
    again = tuple(datagen.ids(range(1, 100001)))
    tracing.reset()
    assert _canonical_ids(words, again) == (None, None)
    merged, ra, rb = merge_dictionaries(words, again)
    assert merged is words and (ra == np.arange(100000)).all() and ra is rb
    assert not tracing.spans()
    left, right = _canonical_ids(("a", "c"), ("b", "c", "d"))
    assert left.tolist() == [0, 2] and right.tolist() == [1, 2, 3]
    assert [s.attrs["words"] for s in tracing.spans()] == [5]


# -- the optimizer prunes the arm a constant column rules out ------------------

def test_the_other_arm_of_each_union_is_pruned(spark, seeds):
    """``sale_type = 's'`` over ``... 's' sale_type ... UNION ALL ... 'w'``
    folds to FALSE above the web arm, which has no column to push it by:
    the arm becomes the empty relation and the ``UNION ALL`` its other arm,
    so each copy of ``year_total`` scans one fact, not two."""
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.planner import QueryExecution
    _tables, base, _con = seeds[SEEDS[0]]
    _views("session", spark, None, base)
    plan = QueryExecution(spark, spark.sql(_statement("q74"))._plan).optimized
    scans, unions = [], []

    def walk(node):
        if isinstance(node, L.FileRelation):
            scans.append(os.path.basename(node.paths[0]))
        unions.append(isinstance(node, L.Union))
        for c in node.children:
            walk(c)

    walk(plan)
    assert scans.count("store_sales") == 2 and scans.count("web_sales") == 2
    assert not any(unions)


@pytest.mark.parametrize("keep", ["first", "second", "neither", "both"])
def test_a_pruned_arm_keeps_the_unions_names_and_rows(spark, keep):
    """Whichever arm a constant rules out, the rows left carry the first
    arm's column names and the union's types."""
    spark.createDataFrame(pd.DataFrame({"a": [1, 2, 3], "s": ["x", "y", "z"]})) \
        .createOrReplaceTempView("ints")
    spark.createDataFrame(pd.DataFrame({"b": [1.5, 2.5], "t": ["p", "q"]})) \
        .createOrReplaceTempView("floats")
    where = {"first": "kind = 'i'", "second": "kind = 'f'",
             "neither": "kind = 'n'", "both": "kind <> 'n'"}[keep]
    want = {"first": [(1.0, "x"), (2.0, "y"), (3.0, "z")],
            "second": [(1.5, "p"), (2.5, "q")], "neither": []}
    want["both"] = sorted(want["first"] + want["second"])
    try:
        df = spark.sql(
            "SELECT v, w FROM (SELECT a AS v, s AS w, 'i' AS kind FROM ints "
            "UNION ALL SELECT b, t, 'f' FROM floats) u "
            f"WHERE {where} ORDER BY v")
        assert df.columns == ["v", "w"]
        assert [tuple(r) for r in df.collect()] == want[keep]
    finally:
        spark.catalog.dropTempView("ints")
        spark.catalog.dropTempView("floats")


# -- the generators -------------------------------------------------------------

@pytest.fixture(scope="module", params=[1, 2 ** 31 + 5])
def sf1(request):
    return datagen.generate(request.param, SF1,
                            ["customer", "household_demographics"])


def test_customer_has_the_specs_columns_and_keys(sf1):
    cu = sf1["customer"]
    declared = SCHEMAS["customer"]
    assert list(cu.columns) == [c for c, _t in declared] and len(declared) == 18
    for column, sql_type in declared:
        if sql_type == "string":
            assert not pd.api.types.is_numeric_dtype(cu[column]), column
        else:
            assert str(cu[column].dtype) == NP_TYPES[sql_type], column
    assert len(cu) == 100000
    assert (cu.c_customer_sk == np.arange(1, 100001)).all()
    assert cu.c_customer_id.nunique() == 100000
    assert (cu.c_customer_id.str.len() == 16).all()
    assert cu.c_email_address.nunique() == 100000
    assert cu.c_login.isna().all()
    assert 150 <= cu.c_birth_country.nunique() <= 200


def test_customer_names_are_a_few_thousand_words_some_common(sf1):
    cu = sf1["customer"]
    first, last = cu.c_first_name.dropna(), cu.c_last_name.dropna()
    assert 4500 <= first.nunique() <= 5000 and 4500 <= last.nunique() <= 5000
    assert first.str.len().between(3, 11).all()
    assert last.str.len().between(3, 13).all()
    assert 800 <= len(set(first) & set(last)) <= 1000     # shared words
    for names in (cu.c_first_name, cu.c_last_name):
        assert 0.03 <= names.isna().mean() <= 0.04
        counts = names.value_counts()
        assert counts.iloc[0] >= 20 * counts.iloc[-1]     # not uniform
    assert not (cu.c_first_name.isna() & cu.c_last_name.isna()).any()


def test_another_seed_is_another_dictionary():
    a, b = (datagen.generate(s, SF1, ["customer"])["customer"] for s in (1, 2))
    assert (a.c_customer_id == b.c_customer_id).all()     # the business key
    assert len(set(a.c_first_name.dropna())
               & set(b.c_first_name.dropna())) < 100


def test_household_demographics_is_the_specs_cross_product(sf1):
    hd = sf1["household_demographics"]
    declared = SCHEMAS["household_demographics"]
    assert list(hd.columns) == [c for c, _t in declared]
    for column, sql_type in declared:
        if sql_type != "string":
            assert str(hd[column].dtype) == NP_TYPES[sql_type], column
    assert len(hd) == 7200 and not hd.drop(columns="hd_demo_sk") \
        .duplicated().any()
    assert sorted(hd.hd_dep_count.unique()) == list(range(10))
    assert sorted(hd.hd_vehicle_count.unique()) == list(range(-1, 5))
    assert hd.hd_buy_potential.nunique() == 6
    assert sorted(hd.hd_income_band_sk.unique()) == list(range(1, 21))


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_references_return_100_rows_in_the_cell(seed):
    """At the configuration's own rows and the cell's literals both
    references fill their LIMIT 100 with strings, and the float32 control's
    sums differ from the float64 ones: the comparison can tell them apart
    (by q79's sums; q74's reply holds no number)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpcds-sf1-yoy-1chip.json")) as fh:
        rows = json.load(fh)["rows"]
    tables = datagen.generate(seed, rows, TABLES)
    for q in ("q74", "q79"):
        ref = _reference(q, tables)
        # (a q74 row may lack a first name; q79's first hundred are the
        # NULL last names, which all carry one)
        assert len(ref) == 100, q
        assert sum(isinstance(r[1], str) for r in ref) >= (
            90 if q == "q74" else 100), q
    mod = importlib.import_module("benchmark.references.q79")
    f32 = mod.reference(tables, {}, float_dtype="float32")
    ref = _reference("q79", tables)
    assert [r[:4] for r in f32] == [r[:4] for r in ref]
    gap = max(abs(a[5] - b[5]) / abs(b[5]) for a, b in zip(f32, ref))
    assert 1e-9 < gap < 1e-6


# -- the cell rehearses from the manifest ---------------------------------------

def test_cell_rehearses_on_the_cpu(tmp_path):
    """``sf1-yoy-customers-http`` from ``BENCHMARK.json`` itself, through
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
         "sf1-yoy-customers-http", "--seed", str(2 ** 31 + 11),
         "--seconds", "2", "--rehearse", "1"],
        capture_output=True, text=True, timeout=280, env=env,
        cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["compared"]["rows_wrong"]["value"] == 0
    assert result["compared"]["references_empty"]["value"] == 0
