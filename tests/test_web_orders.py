"""TPC-DS q94 / q95 at their work: the web channel with dsdgen's ORDER
structure (8-16 lines an order), where ``spark_tpu.tpcds.datagen`` gives an
order one line and both queries' self-joins are empty.

Over ``CREATE TEMP VIEW t AS SELECT * FROM parquet.`...``` views, as the
benchmark and every HTTP user register tables: the statements equal sqlite
(the suite's oracle) and the benchmark's pandas references, through
``session.sql`` and ``POST /sql``; a repeated q95 executes once (the join
capacities its first call learned are kept for the statement's shape); a
literal that needs more slots still answers exactly and re-plans once.
The data comes from the benchmark's generators (``benchmark/generators/
web_sales.py``), a few thousand lines.
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import datagen  # noqa: E402

TABLES = ["web_sales", "web_returns", "date_dim", "customer_address",
          "web_site"]
ROWS = {"date_dim": 1826, "item": 200, "customer": 2000,
        "customer_demographics": 1920800, "household_demographics": 7200,
        "customer_address": 500, "promotion": 300, "warehouse": 5,
        "web_site": 30, "web_page": 60, "ship_mode": 20,
        "web_sales": 5000, "web_returns": 499}
SF1 = dict(ROWS, item=18000, customer=100000, customer_address=50000,
           web_sales=719384, web_returns=71763)
#: the cell's literals (``benchmark/traffic/web-orders-http.json``)
CELL = {"date_lo": "1999-02-01", "date_hi": "1999-04-02", "state": "TN",
        "company": "pri"}
#: the five sales years: at a few thousand lines the 60-day window of the
#: templates leaves a handful of lines, this leaves a few hundred
WIDE = dict(CELL, date_lo="1998-01-01", date_hi="2002-12-31")
#: what ``spark_tpu/tpcds/queries.py`` fixes in its texts
IN_QUERIES = {"q95": dict(CELL, date_lo="2000-02-01", date_hi="2000-04-01",
                          state="CA"),
              "q94": CELL}


@pytest.fixture(autouse=True)
def time_limit():
    """A limit of its own for every test of this file (seconds)."""
    def late(_signum, _frame):
        raise TimeoutError("test_web_orders: a test passed its 300 s")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _statement(q, lit):
    with open(os.path.join(ROOT, "benchmark", "statements", q + ".sql")) as f:
        return f.read().strip().format(**lit)


def _ddl(base, table):
    return (f"CREATE OR REPLACE TEMP VIEW {table} AS "
            f"SELECT * FROM parquet.`{os.path.join(base, table)}`")


@pytest.fixture(scope="module")
def web(spark, tmp_path_factory):
    """(tables, parquet directory, sqlite connection); the views are
    registered in the shared session."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    tables = datagen.generate(27, ROWS, TABLES)
    base = str(tmp_path_factory.mktemp("web"))
    con = sqlite3.connect(":memory:")
    for name, cols in tables.items():
        frame = pd.DataFrame(cols)
        os.makedirs(os.path.join(base, name))
        pq.write_table(pa.Table.from_pandas(frame, preserve_index=False),
                       os.path.join(base, name, "part-0000.parquet"))
        frame.to_sql(name, con, index=False)
        spark.sql(_ddl(base, name))
    yield tables, base, con
    con.close()
    for name in tables:
        spark.catalog.dropTempView(name)


class _Http:
    """One server session over the same files, as ``benchmark/lib/engine.py``
    makes it: every view a ``POST /sql`` of the DDL."""

    def __init__(self, spark, base):
        from spark_tpu.server import SQLServer
        self.srv = SQLServer(spark, port=0).start()
        self.sid = None
        self.sid = self.post("/session")["sessionId"]
        for t in TABLES:
            self.post("/sql", {"query": _ddl(base, t)})

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
def http(spark, web):
    h = _Http(spark, web[1])
    yield h
    h.srv.stop()


def _run(lane, spark, http, text):
    if lane == "http":
        return http.sql(text)
    return [tuple(r) for r in spark.sql(text).collect()]


def _same(got, want):
    got = [tuple(norm_value(v) for v in r) for r in got]
    want = [tuple(norm_value(v) for v in r) for r in want]
    assert got == want


# -- the statements, against sqlite and the pandas references -----------------

@pytest.mark.parametrize("lane", ["session", "http"])
@pytest.mark.parametrize("q,lit", [
    ("q95", None), ("q94", None), ("q95", WIDE), ("q94", WIDE)],
    ids=["q95-queries.py", "q94-queries.py", "q95-wide", "q94-wide"])
def test_equals_sqlite_and_reference(spark, web, http, lane, q, lit):
    """``lit`` None: the text of ``spark_tpu/tpcds/queries.py`` as it
    stands; else the benchmark's statement with those literals."""
    tables, _base, con = web
    text = QUERIES[q] if lit is None else _statement(q, lit)
    lit = IN_QUERIES[q] if lit is None else lit
    got = _run(lane, spark, http, text)
    oracle = con.execute(text).fetchall()
    ref = importlib.import_module(f"benchmark.references.{q}") \
        .reference(tables, lit)
    _same(ref, oracle)              # the reference's reading of the text
    _same(got, oracle)
    if lit is WIDE:
        assert oracle[0][0] >= 20   # orders: a reply of zeros cannot pass


def test_self_join_is_not_empty(spark, web):
    """``ws_wh`` has the rows dsdgen's order structure gives it: every
    ordered pair of an order's lines with two non-NULL warehouses that
    differ."""
    tables, _base, con = web
    text = ("SELECT COUNT(*) FROM web_sales ws1, web_sales ws2 "
            "WHERE ws1.ws_order_number = ws2.ws_order_number "
            "AND ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk")
    n = spark.sql(text).collect()[0][0]
    ws = tables["web_sales"]
    w = pd.DataFrame({"o": ws["ws_order_number"],
                      "w": ws["ws_warehouse_sk"]}).dropna()
    pairs = w.merge(w, on="o")
    assert n == int((pairs.w_x != pairs.w_y).sum()) \
        == con.execute(text).fetchall()[0][0]
    assert n > 5 * ROWS["web_sales"]     # the join fans out


# -- a join's output capacity is learned once a statement shape and kept ------

def _spans_of(fn):
    tracing.reset()
    out = fn()
    return out, tracing.spans()


@pytest.mark.parametrize("lane", ["session", "http"])
def test_repeated_q95_executes_once(spark, web, http, lane):
    text = _statement("q95", WIDE).replace("LIMIT 100", "LIMIT 99")
    first, spans = _spans_of(lambda: _run(lane, spark, http, text))
    replans = [s for s in spans if s.name == "join.replan"]
    assert replans, "the self-join did not outgrow its planned capacity"
    assert [s.attrs["attempt"] for s in replans] == \
        list(range(1, len(replans) + 1))
    assert all(s.attrs["ratio"] > 0 and s.attrs["factors"]["join"]
               for s in replans)
    again, spans = _spans_of(lambda: _run(lane, spark, http, text))
    assert again == first
    names = [s.name for s in spans]
    assert "join.replan" not in names
    assert names.count("statement") == 1
    assert names.count("d2h") == 1            # one flag fetch: one execution
    paths = [s.attrs for s in spans if s.name == "join.path"]
    assert paths and all(p["out_cap"] >= 1 and p["probe_cap"] >= 1
                         for p in paths)
    assert any(p["out_cap"] > p["probe_cap"] for p in paths)   # the fan-out
    phases = tracing.last_statement()["phases"]
    assert "join.path" in phases and "join.replan" not in phases


def test_a_literal_that_needs_more_slots_replans_once(spark, web):
    """The capacity kept for the statement's shape is a floor, not a
    promise: a literal in a slot position that matches more rows overflows
    it, answers exactly after ONE re-plan, and the larger capacity replaces
    the kept one."""
    tables, _base, _con = web
    ws = pd.DataFrame({"o": np.asarray(tables["web_sales"]["ws_order_number"]),
                       "q": np.asarray(tables["web_sales"]["ws_quantity"])})
    spark.createDataFrame(ws).createOrReplaceTempView("lines")

    def run(limit):
        text = ("SELECT COUNT(*) AS n FROM lines a, lines b "
                f"WHERE a.o = b.o AND b.q < {limit}")
        (n,), = spark.sql(text).collect()
        pairs = ws.merge(ws[ws.q < limit], on="o")
        assert n == len(pairs)
        return n

    try:
        _n, spans = _spans_of(lambda: run(30))
        assert sum(s.name == "join.replan" for s in spans) >= 1
        _n, spans = _spans_of(lambda: run(30))
        assert not any(s.name == "join.replan" for s in spans)
        small = max(s.attrs["out_cap"] for s in spans
                    if s.name == "join.path")
        _n, spans = _spans_of(lambda: run(101))     # every line matches
        assert sum(s.name == "join.replan" for s in spans) == 1
        _n, spans = _spans_of(lambda: run(101))
        assert not any(s.name == "join.replan" for s in spans)
        assert max(s.attrs["out_cap"] for s in spans
                   if s.name == "join.path") > small
        _n, spans = _spans_of(lambda: run(30))      # the larger one is kept
        assert not any(s.name == "join.replan" for s in spans)
    finally:
        spark.catalog.dropTempView("lines")


def test_growth_is_still_bounded(spark, web):
    """``spark.sql.join.maxOutputRows`` bounds what a re-plan may ask for."""
    from spark_tpu.sql.planner import JoinFanoutError
    text = ("SELECT COUNT(*) FROM web_sales ws1, web_sales ws2 "
            "WHERE ws1.ws_order_number = ws2.ws_order_number "
            "AND ws1.ws_item_sk <> ws2.ws_item_sk")
    old = spark.conf.get("spark.sql.join.maxOutputRows")
    spark.conf.set("spark.sql.join.maxOutputRows", "10000")
    try:
        with pytest.raises(JoinFanoutError):
            spark.sql(text).collect()
    finally:
        spark.conf.set("spark.sql.join.maxOutputRows", str(old))


# -- the generators at SF1's rows ---------------------------------------------

@pytest.fixture(scope="module", params=[1, 2 ** 31 + 5])
def sf1(request):
    return datagen.generate(request.param, SF1, TABLES)


def test_orders_have_8_to_16_lines(sf1):
    ws, wr = sf1["web_sales"], sf1["web_returns"]
    assert len(ws) == 34 and len(wr) == 24          # the spec's columns
    order = np.asarray(ws["ws_order_number"])
    assert len(order) == SF1["web_sales"]
    lines = np.bincount(order)[1:]
    assert lines.min() >= 8 and lines.max() <= 16
    assert 11.5 <= lines.mean() <= 12.5
    key = pd.DataFrame({"o": order, "i": np.asarray(ws["ws_item_sk"])})
    assert not key.duplicated().any()               # distinct items an order
    # what is drawn once an order is one value in all its lines
    per_order = pd.DataFrame({
        "o": order,
        "date": ws["ws_sold_date_sk"].to_numpy(dtype=float, na_value=-1.0),
        "cust": ws["ws_bill_customer_sk"].to_numpy(dtype=float,
                                                    na_value=-1.0),
        "addr": ws["ws_ship_addr_sk"].to_numpy(dtype=float, na_value=-1.0)})
    assert (per_order.groupby("o").nunique() == 1).all().all()
    # what is drawn once a line varies within an order
    wh = pd.DataFrame({"o": order,
                       "w": ws["ws_warehouse_sk"].to_numpy(
                           dtype=float, na_value=np.nan)})
    assert (wh.groupby("o").w.nunique() >= 2).mean() > 0.95
    assert 0.03 < np.isnan(wh.w).mean() < 0.05      # 4% NULL


def test_returns_reference_lines_once(sf1):
    ws, wr = sf1["web_sales"], sf1["web_returns"]
    lines = pd.DataFrame({"o": np.asarray(ws["ws_order_number"]),
                          "i": np.asarray(ws["ws_item_sk"])})
    ret = pd.DataFrame({"o": np.asarray(wr["wr_order_number"]),
                        "i": np.asarray(wr["wr_item_sk"])})
    assert len(ret) == SF1["web_returns"]
    assert not ret.duplicated().any()
    assert len(ret.merge(lines, on=["o", "i"])) == len(ret)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_references_count_tens_of_orders_in_the_cell(seed):
    """At the configuration's own rows and the cell's literals both
    statements count tens of orders: a reply of zeros cannot pass."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpcds-sf1-web-1chip.json")) as fh:
        rows = json.load(fh)["rows"]
    tables = datagen.generate(seed, rows, TABLES)
    for q in ("q95", "q94"):
        ref = importlib.import_module(f"benchmark.references.{q}")
        (orders, shipping, profit), = ref.reference(tables, CELL)
        assert orders >= 20 and shipping > 0 and profit is not None, q


# -- the cell rehearses from the accepted manifest ----------------------------

def test_cell_rehearses_on_the_cpu():
    """``sf1-web-orders-http`` from ``BENCHMARK.json`` itself, through
    ``POST /sql`` at the configuration's ``rehearse_rows``, to its result
    line with the comparison passed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", "BENCHMARK.json", "--workload", "sf1-web-orders-http",
         "--seed", "1", "--seconds", "2", "--rehearse", "1"],
        capture_output=True, text=True, timeout=280, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["compared"]["rows_wrong"]["value"] == 0
