#!/usr/bin/env python3
"""Smoke run of the SQL path on the attached TPU.

    python chip_smoke.py                    # one chip: session + server phases
    python chip_smoke.py --chips 4          # four chips: the mesh phase only
    python chip_smoke.py --rows 20000 --batch-rows 4096     # a tiny rehearsal

One process: it imports JAX, holds the chip and starts no child that
needs it.  TPC-DS tables come from ``spark_tpu.tpcds.generate`` (seeded),
the facts are written once as multi-file parquet under ``--work-dir``,
and every statement goes through ``SparkSession.sql`` or the HTTP
``SQLServer``.  Each result is compared with a plain pandas/numpy
implementation of the same query on the same frames (written below,
independent of ``spark_tpu``): integer and count columns exactly,
float64 sums to 1e-9 relative.

The one-chip run has a cold pass — every statement's first call, each on
an isolated session, side by side, because a first call is minutes of
XLA:TPU compile — and then a warm pass in turn; the server phase reuses
the same executables.  One JSON object is printed per statement and per
phase (rows, ``first_s`` cold and ``wall_s`` warm seconds ending in rows
on the host, compile seconds, ``peak_bytes_in_use``, which aggregate
lowering ran); the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Finding no TPU, a failed phase or a mismatch exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
FACTS = ("store_sales", "store_returns", "catalog_sales", "catalog_returns",
         "web_sales", "web_returns", "inventory")
RTOL = 1e-9
#: ``--rows`` defaults: the real size on one chip; a tenth of it on four,
#: where every second costs four chip-seconds and q17 alone took 686 s cold
#: and 341 s warm at 10M rows (PERF.md, PR 23)
ROWS_ONE_CHIP, ROWS_MESH = 10_000_000, 1_000_000

# ---------------------------------------------------------------------------
# statements (TPC-DS q3/q42/q55 with their literals as parameters, the two
# keyed aggregates) and their pandas references
# ---------------------------------------------------------------------------

Q3 = """
SELECT d_year, i_brand_id, i_brand, SUM(ss_ext_sales_price) AS sum_agg
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manufact_id = {manufact} AND d_moy = {moy}
GROUP BY d_year, i_brand_id, i_brand
ORDER BY d_year, sum_agg DESC, i_brand_id, i_brand
LIMIT 100"""

Q42 = """
SELECT d_year, i_category_id, i_category, SUM(ss_ext_sales_price) AS total
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manager_id = {manager} AND d_moy = {moy} AND d_year = {year}
GROUP BY d_year, i_category_id, i_category
ORDER BY total DESC, d_year, i_category_id, i_category
LIMIT 100"""

Q55 = """
SELECT i_brand_id, i_brand, SUM(ss_ext_sales_price) AS ext_price
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manager_id = {manager} AND d_moy = {moy} AND d_year = {year}
GROUP BY i_brand_id, i_brand
ORDER BY ext_price DESC, i_brand_id, i_brand
LIMIT 100"""

#: key range 1..12 (+NULL) fits ``bucket_cap``: the MXU/Pallas aggregate
AGG_STORE = """
SELECT ss_store_sk, COUNT(*) AS cnt, SUM(ss_quantity) AS qty,
       SUM(ss_ticket_number) AS tickets
FROM store_sales GROUP BY ss_store_sk"""

#: ~26k (customer, store) groups exceed ``bucket_cap`` and the float sum is
#: not MXU-eligible: the sort-based aggregate
AGG_CUSTOMER = """
SELECT ss_customer_sk, ss_store_sk, COUNT(*) AS cnt,
       SUM(ss_quantity) AS qty, SUM(ss_net_paid) AS paid
FROM store_sales GROUP BY ss_customer_sk, ss_store_sk"""

#: (name, template, literals) — the TPC-DS defaults, then the variants the
#: second HTTP session sends
STAR = {
    "q3": (Q3, {"manufact": 28, "moy": 11}, {"manufact": 35, "moy": 12}),
    "q42": (Q42, {"manager": 1, "moy": 11, "year": 2000},
            {"manager": 7, "moy": 12, "year": 2001}),
    "q55": (Q55, {"manager": 28, "moy": 11, "year": 1999},
            {"manager": 13, "moy": 10, "year": 2002}),
}


def _star_ref(frames, name, lit):
    """q3/q42/q55 in pandas: filter the dims, semi-filter the fact to the
    surviving keys, join, group, order, limit."""
    import pandas as pd
    dd, it, ss = frames["date_dim"], frames["item"], frames["store_sales"]
    dd = dd[dd.d_moy == lit["moy"]]
    if "year" in lit:
        dd = dd[dd.d_year == lit["year"]]
    it = it[it.i_manufact_id == lit["manufact"]] if name == "q3" \
        else it[it.i_manager_id == lit["manager"]]
    date = pd.to_numeric(ss.ss_sold_date_sk).to_numpy(float)
    keep = pd.Series(date).isin(dd.d_date_sk.to_numpy()).to_numpy() \
        & ss.ss_item_sk.isin(it.i_item_sk).to_numpy()
    f = pd.DataFrame({
        "d_date_sk": date[keep].astype("int64"),
        "i_item_sk": ss.ss_item_sk.to_numpy()[keep],
        "price": ss.ss_ext_sales_price.to_numpy()[keep]})
    f = f.merge(dd[["d_date_sk", "d_year"]], on="d_date_sk") \
         .merge(it, on="i_item_sk")
    keys, order, asc = {
        "q3": (["d_year", "i_brand_id", "i_brand"],
               ["d_year", "s", "i_brand_id", "i_brand"],
               [True, False, True, True]),
        "q42": (["d_year", "i_category_id", "i_category"],
                ["s", "d_year", "i_category_id", "i_category"],
                [False, True, True, True]),
        "q55": (["i_brand_id", "i_brand"], ["s", "i_brand_id", "i_brand"],
                [False, True, True]),
    }[name]
    g = f.groupby(keys, as_index=False).agg(s=("price", "sum"))
    g = g.sort_values(order, ascending=asc, kind="mergesort").head(100)
    return [tuple(r) for r in g[keys + ["s"]].itertuples(index=False)]


def _nullable(col):
    import pandas as pd
    return pd.to_numeric(col).astype("Int64")


def _agg_store_ref(frames):
    import pandas as pd
    ss = frames["store_sales"]
    f = pd.DataFrame({"k": _nullable(ss.ss_store_sk),
                      "q": ss.ss_quantity.astype("int64"),
                      "t": ss.ss_ticket_number.astype("int64")})
    g = f.groupby("k", dropna=False).agg(
        cnt=("q", "size"), qty=("q", "sum"), tickets=("t", "sum"))
    return [(None if k is pd.NA else int(k), int(r.cnt), int(r.qty),
             int(r.tickets)) for k, r in g.iterrows()]


def _agg_customer_ref(frames):
    import pandas as pd
    ss = frames["store_sales"]
    f = pd.DataFrame({"c": _nullable(ss.ss_customer_sk),
                      "s": _nullable(ss.ss_store_sk),
                      "q": ss.ss_quantity.astype("int64"),
                      "p": ss.ss_net_paid.astype("float64")})
    g = f.groupby(["c", "s"], dropna=False).agg(
        cnt=("q", "size"), qty=("q", "sum"), paid=("p", "sum"))
    return [(None if c is pd.NA else int(c), None if s is pd.NA else int(s),
             int(r.cnt), int(r.qty), float(r.paid))
            for (c, s), r in g.iterrows()]


def _q17_ref(frames):
    """TPC-DS q17 in pandas (NULL keys never join: dropped up front)."""
    import pandas as pd
    dd = frames["date_dim"]
    q1 = dd[dd.d_quarter_name == "2000Q1"].d_date_sk.to_numpy()
    q123 = dd[dd.d_quarter_name.isin(
        ["2000Q1", "2000Q2", "2000Q3"])].d_date_sk.to_numpy()

    def num(df, cols):
        out = pd.DataFrame({c: pd.to_numeric(df[c]) for c in cols})
        return out.dropna()

    ss = num(frames["store_sales"],
             ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
              "ss_customer_sk", "ss_ticket_number", "ss_quantity"])
    ss = ss[ss.ss_sold_date_sk.isin(q1)]
    sr = num(frames["store_returns"],
             ["sr_returned_date_sk", "sr_item_sk", "sr_customer_sk",
              "sr_ticket_number", "sr_return_quantity"])
    sr = sr[sr.sr_returned_date_sk.isin(q123)]
    cs = num(frames["catalog_sales"],
             ["cs_sold_date_sk", "cs_bill_customer_sk", "cs_item_sk",
              "cs_quantity"])
    cs = cs[cs.cs_sold_date_sk.isin(q123)]
    j = ss.merge(sr, left_on=["ss_customer_sk", "ss_item_sk",
                              "ss_ticket_number"],
                 right_on=["sr_customer_sk", "sr_item_sk",
                           "sr_ticket_number"])
    j = j.merge(cs, left_on=["sr_customer_sk", "sr_item_sk"],
                right_on=["cs_bill_customer_sk", "cs_item_sk"])
    j = j.merge(frames["store"][["s_store_sk", "s_state"]],
                left_on="ss_store_sk", right_on="s_store_sk")
    j = j.merge(frames["item"][["i_item_sk", "i_item_id", "i_item_desc"]],
                left_on="ss_item_sk", right_on="i_item_sk")
    keys = ["i_item_id", "i_item_desc", "s_state"]
    spec = {}
    for tag, col in (("ss", "ss_quantity"), ("sr", "sr_return_quantity"),
                     ("cs", "cs_quantity")):
        spec[f"{tag}_n"] = (col, "count")
        spec[f"{tag}_avg"] = (col, "mean")
        spec[f"{tag}_sd"] = (col, lambda v: v.std(ddof=1))
    g = j.groupby(keys, as_index=False).agg(**spec)
    g = g.sort_values(keys, kind="mergesort").head(100)
    return [tuple(r) for r in g.itertuples(index=False)]


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def _norm(v):
    if v is None:
        return None
    if isinstance(v, float) or type(v).__name__.startswith("float"):
        f = float(v)
        return None if math.isnan(f) else f
    if isinstance(v, (bool, str)):
        return v
    if hasattr(v, "__int__"):
        return int(v)
    return v


def compare(name, got, ref, ordered=True, rtol=RTOL):
    """Raise AssertionError unless ``got`` equals ``ref``: ints, strings
    and NULLs exactly, floats to ``rtol`` relative."""
    got = [tuple(_norm(v) for v in r) for r in got]
    ref = [tuple(_norm(v) for v in r) for r in ref]
    assert ref, f"{name}: the reference is empty — a weak check"
    if not ordered:
        def key(r):            # the exact columns: NULL-stable, floats out
            return tuple((x is None, x) for x in r
                         if not isinstance(x, float))
        got, ref = sorted(got, key=key), sorted(ref, key=key)
    assert len(got) == len(ref), f"{name}: {len(got)} rows != {len(ref)}"
    for i, (g, e) in enumerate(zip(got, ref)):
        assert len(g) == len(e), f"{name} row {i}: {g} != {e}"
        for j, (a, b) in enumerate(zip(g, e)):
            if isinstance(a, float) and isinstance(b, float):
                ok = math.isclose(a, b, rel_tol=rtol, abs_tol=0.0)
            else:
                ok = type(a) is type(b) and a == b
            assert ok, f"{name} row {i} col {j}: {a!r} != {b!r}"
    return len(got)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

class Dataset:
    def __init__(self, frames, base, rows, seed):
        self.frames, self.base, self.rows, self.seed = frames, base, rows, seed

    def path(self, table):
        return os.path.join(self.base, table)


def build_dataset(rows: int, seed: int, work_dir: str, emit) -> Dataset:
    """Generate the 24 tables from the seed; write every table as parquet
    under ``work_dir`` (facts in several files) unless the marker there
    already names these rows and seed."""
    from spark_tpu.tpcds import generate
    t0 = time.time()
    frames = generate(rows, seed=seed)
    t_gen = time.time() - t0
    base = os.path.join(work_dir, f"tpcds_{rows}_{seed}")
    marker = os.path.join(base, "_GENERATED")
    reused = os.path.exists(marker)
    t0 = time.time()
    if not reused:
        for name, pdf in frames.items():
            d = os.path.join(base, name)
            os.makedirs(d, exist_ok=True)
            parts = max(4, len(pdf) // (1 << 21) + 1) if name in FACTS else 1
            step = (len(pdf) + parts - 1) // parts
            for i in range(parts):
                pdf.iloc[i * step:(i + 1) * step].to_parquet(
                    os.path.join(d, f"part-{i:04d}.parquet"), index=False)
        with open(marker, "w") as fh:
            json.dump({"rows": rows, "seed": seed}, fh)
    emit({"phase": "data", "rows": rows, "seed": seed,
          "generate_s": round(t_gen, 2), "reused": reused,
          "write_s": round(time.time() - t0, 2),
          "store_sales_bytes": int(
              frames["store_sales"].memory_usage(deep=False).sum())})
    return Dataset(frames, base, rows, seed)


# ---------------------------------------------------------------------------
# what the engine and the device record
# ---------------------------------------------------------------------------

def _peak_bytes():
    import jax
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append(st.get("peak_bytes_in_use"))
    return out


def _compile_ms():
    from spark_tpu.sql.stagecompile import stage_cache
    return stage_cache().stats()["compile_ms"]


def _agg_lowering(spark) -> str:
    """Which keyed-aggregate lowering the last statement RAN, read from the
    program text of the stage-cache entry it dispatched (the jitted callable
    the engine built and called, not a second program): the Mosaic kernel is
    a ``tpu_custom_call``, the portable MXU form a ``dot_general``, the
    sort-based aggregate neither."""
    from spark_tpu.sql import stagecompile as SC
    from spark_tpu.sql.planner import local_stage_key
    key, slots, leaves = local_stage_key(spark, spark._last_qe.planned)
    entry = SC.stage_cache(spark).peek(key)
    assert entry is not None and not entry._first, \
        "the statement did not dispatch its whole-plan stage"
    text = entry.fn.lower(tuple(b.to_device() for b in leaves),
                          SC.param_values(slots)).as_text()
    if "tpu_custom_call" in text:
        return "pallas"
    return "einsum" if "dot_general" in text else "sort"


def _collect(spark, sql):
    """(rows, seconds): one statement through ``spark.sql``, ending in rows
    on the host."""
    t0 = time.time()
    rows = [tuple(r) for r in spark.sql(sql).collect()]
    return rows, time.time() - t0


# ---------------------------------------------------------------------------
# phase `session`
# ---------------------------------------------------------------------------

#: the store_sales columns the statements read: what the device cache holds
_CACHED_COLUMNS = ["ss_sold_date_sk", "ss_item_sk", "ss_customer_sk",
                   "ss_store_sk", "ss_ticket_number", "ss_quantity",
                   "ss_ext_sales_price", "ss_net_paid"]
#: device-cached cold lanes at once: each holds its own copy of the fact
#: (0.72 GB at 10M rows) plus its program's temporaries (1.7 GB for q3)
_CACHED_LANES = 3


def _register(spark, ds, dims_in_memory=False):
    """Every table as a view over its parquet files — what an HTTP session
    can say too (``parquet.`path```), so the server phase runs the session
    phase's programs.  ``dims_in_memory`` registers the dimensions from
    their frames instead."""
    for name, pdf in ds.frames.items():
        if name in FACTS or not dims_in_memory:
            spark.sql(_view_ddl(ds, name))
        else:
            spark.createDataFrame(pdf).createOrReplaceTempView(name)


def _view_ddl(ds, name):
    return (f"CREATE OR REPLACE TEMP VIEW {name} AS "
            f"SELECT * FROM parquet.`{ds.path(name)}`")


def _cache_fact(spark, ds):
    """``store_sales`` cached on the device and registered under its name."""
    cached = spark.read.parquet(ds.path("store_sales")) \
        .select(*_CACHED_COLUMNS).cache()
    cached.createOrReplaceTempView("store_sales")
    return cached


def _session_statements(ds):
    out = [(n, tpl.format(**lit), _star_ref(ds.frames, n, lit), True)
           for n, (tpl, lit, _v) in STAR.items()]
    out.append(("agg_store", AGG_STORE, _agg_store_ref(ds.frames), False))
    out.append(("agg_customer", AGG_CUSTOMER,
                _agg_customer_ref(ds.frames), False))
    return out


def _cold_pass(spark, ds, emit, stmts):
    """Every statement's FIRST call, streamed and device-cached, each on an
    isolated session of its own (``newSession``: the unit the server gives
    each connection) and all at once.  A first call is nearly all XLA:TPU
    compile — minutes per program, one program per statement shape, and the
    compiler is host code that runs beside the others — so ten cold
    statements in turn would not fit the run's 1200 s, while a server's
    sessions compile side by side exactly like this.  The executables land
    in the process-wide stage cache, so the warm pass below reuses them."""
    from concurrent.futures import ThreadPoolExecutor
    t_pass = time.time()

    def lane(variant, name, sql, ref, ordered):
        sess = spark.newSession()
        _register(sess, ds)
        cached = _cache_fact(sess, ds) if variant == "cached" else None
        try:
            rows, first = _collect(sess, sql)
        finally:
            if cached is not None:
                cached.unpersist()
        n = compare(f"session/cold/{variant}/{name}", rows, ref, ordered)
        emit({"phase": "session/cold", "variant": variant, "statement": name,
              "fact_rows": ds.rows, "first_s": round(first, 3),
              "done_at_s": round(time.time() - t_pass, 3),
              "peak_bytes_in_use": _peak_bytes(), "result_rows": n,
              "equal_to_reference": True})
        return first

    lanes = [(v,) + st for v in ("cached", "streamed") for st in stmts]
    n_streamed = max(1, (os.cpu_count() or 4) // 2 - _CACHED_LANES)
    with ThreadPoolExecutor(_CACHED_LANES) as cached_pool, \
            ThreadPoolExecutor(n_streamed) as streamed_pool:
        futures = [(cached_pool if ln[0] == "cached" else streamed_pool)
                   .submit(lane, *ln) for ln in lanes]
        firsts = [f.result() for f in futures]
    emit({"phase": "session/cold", "statement": "all", "lanes": len(lanes),
          "at_once": _CACHED_LANES + n_streamed,
          "host_cpus": os.cpu_count(),
          "wall_s": round(time.time() - t_pass, 3),
          "sum_first_s": round(sum(firsts), 3)})
    return {(ln[0], ln[1]): f for ln, f in zip(lanes, firsts)}


def _warm(spark, ds, emit, variant, name, sql, ref, ordered, firsts,
          lowering=False):
    c0 = _compile_ms()
    rows, wall = _collect(spark, sql)
    first = firsts[variant, name]
    line = {"phase": f"session/{variant}", "statement": name,
            "fact_rows": ds.rows, "wall_s": round(wall, 3),
            "first_s": round(first, 3),
            # the cold call's seconds beyond a warm one: trace + compile
            "compile_s": round(max(first - wall, 0.0), 3),
            "compile_s_in_warm_call": round((_compile_ms() - c0) / 1e3, 3),
            "peak_bytes_in_use": _peak_bytes()}
    if lowering:
        line["agg_lowering"] = _agg_lowering(spark)
    line["result_rows"] = compare(f"session/{variant}/{name}", rows, ref,
                                  ordered)
    line["equal_to_reference"] = True
    emit(line)
    return line


def phase_session(spark, ds, emit, batch_rows=None, require_pallas=True):
    """q3/q42/q55 and the two keyed aggregates through ``spark.sql`` on one
    device: streamed from the parquet files and over the fact cached on the
    device.  A cold pass (first calls, at once) then a warm pass (in turn,
    on ``spark`` itself): each statement reports both."""
    spark.conf.set("spark.tpu.mesh.shards", "1")
    if batch_rows:
        spark.conf.set("spark.tpu.scan.maxBatchRows", str(batch_rows))
    _register(spark, ds)
    stmts = _session_statements(ds)
    firsts = _cold_pass(spark, ds, emit, stmts)

    # the warm pass leaves out what the run's 1200 s cannot hold: streamed
    # q3/q42/q55 are sent again, warm, by the server phase (same
    # executables), and device-cached q42/q55 are q3's class
    for name, sql, ref, ordered in stmts:
        if name.startswith("agg_"):
            _warm(spark, ds, emit, "streamed", name, sql, ref, ordered,
                  firsts)
    t0 = time.time()
    cached = _cache_fact(spark, ds)
    emit({"phase": "session/cached", "statement": "cache",
          "fact_rows": ds.rows, "columns": len(_CACHED_COLUMNS),
          "wall_s": round(time.time() - t0, 3),
          "storage_bytes": int(spark._memory.storage_used),
          "peak_bytes_in_use": _peak_bytes()})
    try:
        for name, sql, ref, ordered in stmts:
            if name in ("q42", "q55"):
                continue
            line = _warm(spark, ds, emit, "cached", name, sql, ref, ordered,
                         firsts, lowering=name.startswith("agg_"))
            if name == "agg_store" and require_pallas:
                assert line["agg_lowering"] == "pallas", \
                    f"GROUP BY ss_store_sk lowered as " \
                    f"{line['agg_lowering']}, not the Pallas kernel"
    finally:
        cached.unpersist()
        spark.sql(_view_ddl(ds, "store_sales"))


# ---------------------------------------------------------------------------
# phase `server`
# ---------------------------------------------------------------------------

def _http(port, path, method="GET", body=None, sid=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                                 method=method)
    req.add_header("Content-Type", "application/json")
    if sid:
        req.add_header("X-Session-Id", sid)
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read().decode())


def phase_server(spark, ds, emit):
    """The same statements, with literal variants, through the HTTP server
    in this process: two sessions — two clients at once, each sending its
    statements in turn — with the worker pool off (the default)."""
    from concurrent.futures import ThreadPoolExecutor
    from spark_tpu.server import SQLServer
    spark.conf.set("spark.tpu.mesh.shards", "1")
    srv = SQLServer(spark, port=0).start()

    def client(i):
        sid = _http(srv.port, "/session", "POST")["sessionId"]
        for name in ds.frames:           # a session sees only its own views
            _http(srv.port, "/sql", "POST", sid=sid,
                  body={"query": _view_ddl(ds, name)})
        lines = []
        for name, (tpl, *literals) in STAR.items():
            use = literals[i]
            t0 = time.time()
            out = _http(srv.port, "/sql", "POST", sid=sid,
                        body={"query": tpl.format(**use)})
            wall = time.time() - t0
            n = compare(f"server/{name}#{i}", out["rows"],
                        _star_ref(ds.frames, name, use))
            lines.append({"phase": "server", "statement": name, "session": i,
                          "literals": use, "fact_rows": ds.rows,
                          "clients_at_once": 2, "wall_s": round(wall, 3),
                          "server_ms": out["durationMs"],
                          "cache_hit": out["cacheHit"],
                          "peak_bytes_in_use": _peak_bytes(),
                          "result_rows": n, "equal_to_reference": True})
        return lines

    try:
        c0, t0 = _compile_ms(), time.time()
        with ThreadPoolExecutor(2) as pool:
            for lines in pool.map(client, range(2)):
                for line in lines:
                    emit(line)
        status = _http(srv.port, "/status")
        assert status["sessions"] == 2, status["sessions"]
        assert "poolActivity" not in status, "the worker pool must be off"
        emit({"phase": "server", "statement": "status",
              "wall_s": round(time.time() - t0, 3),
              # 0 when the session phase already compiled these programs
              "compile_s": round((_compile_ms() - c0) / 1000.0, 3),
              "sessions": status["sessions"],
              "queriesExecuted": status["queriesExecuted"],
              "stageCache": status["stageCache"],
              "admission": status["admission"]})
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# phase `mesh` (--chips N)
# ---------------------------------------------------------------------------

_COLLECTIVE = re.compile(
    r"\b(all-to-all|all-reduce|all-gather|reduce-scatter|"
    r"collective-permute)(?:-start)?\(")


def _dist_collectives(spark, n):
    """Collectives in the compiled text of the shard_map program the last
    statement ran: re-plan it the way ``DistributedExecution`` did (same
    adapted factors, so the same cached jit) and compile for its leaves."""
    from spark_tpu.parallel.executor import DistributedPlanner, shard_leaf
    from spark_tpu.parallel.mesh import get_mesh
    qe = spark._last_qe
    ad = spark._adapted_factors.get(
        f"dist{n}:adapt:" + qe.optimized.tree_string()) or {}
    pq = DistributedPlanner(
        spark, n, skew_override=ad.get("skew"),
        join_factor_override=ad.get("join"),
        agg_shrink_override=ad.get("shrink")).plan(qe.optimized)
    fn = spark._jit_cache[f"dist{n}:" + pq.physical.key()]
    mesh = get_mesh(n)
    leaves = tuple(shard_leaf(mesh, n, b) for b in pq.leaves)
    text = fn.lower(leaves).compile().as_text()
    found = {}
    for m in _COLLECTIVE.finditer(text):
        found[m.group(1)] = found.get(m.group(1), 0) + 1
    return found


def _exchange_check(ds, n, emit, rows_per_sender=1 << 20):
    """``ici.local_device_exchange`` over ``n`` participants against the
    host pack -> slot transpose -> unpack of the same outboxes."""
    import numpy as np
    import pandas as pd
    from spark_tpu import types as T
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.parallel import ici

    ss = ds.frames["store_sales"]
    per = min(rows_per_sender, len(ss) // n)
    kinds = (("ss_ticket_number", T.LongType(), np.int64),
             ("ss_customer_sk", T.LongType(), np.int64),     # has NULLs
             ("ss_net_paid", T.DoubleType(), np.float64),
             ("ss_quantity", T.IntegerType(), np.int32))

    def batch(df):
        vecs = []
        for col, dt, npdt in kinds:
            raw = pd.to_numeric(df[col])
            valid = raw.notna().to_numpy()
            vecs.append(ColumnVector(
                raw.fillna(0).to_numpy().astype(npdt), dt,
                None if valid.all() else valid, None))
        return ColumnBatch([k[0] for k in kinds], vecs, None, len(df))

    outboxes = []
    for s in range(n):
        part = ss.iloc[s * per:(s + 1) * per]
        dest = part.ss_item_sk.to_numpy() % n
        outboxes.append({r: [batch(part[dest == r])] for r in range(n)})
    tpl = batch(ss.iloc[:1])
    cap = ici._pow2(max(b.capacity for ob in outboxes
                        for bs in ob.values() for b in bs))
    members = list(range(n))
    t0 = time.time()
    got = ici.local_device_exchange(outboxes, tpl, max_runs=1, cap=cap)
    first = time.time() - t0
    t0 = time.time()
    ici.local_device_exchange(outboxes, tpl, max_runs=1, cap=cap)
    wall = time.time() - t0

    packs = [ici._pack_outbox(ob, members, tpl, cap, 1) for ob in outboxes]
    n_cols = len(kinds)
    moved = 0
    for r in members:
        cols = [np.stack([packs[s][1][j][r] for s in members])
                for j in range(n_cols)]
        masks = [np.stack([packs[s][2][j][r] for s in members])
                 for j in range(n_cols)]
        rowv = np.stack([packs[s][3][r] for s in members])
        runl = np.stack([packs[s][4][r] for s in members])
        want = ici._unpack_inbox(packs[0][0], tpl, cols, masks, rowv, runl,
                                 members, self_pid=r)
        for s in members:
            if s == r:
                continue
            assert len(got[r][s]) == len(want[s]) == 1, (r, s)
            gb, wb = got[r][s][0], want[s][0]
            assert gb.capacity == wb.capacity, (r, s)
            for gv, wv in zip(gb.vectors, wb.vectors):
                # bit for bit, floats too (a float64 plane crosses the
                # device as its int64 view)
                assert gv.data.dtype == wv.data.dtype, (r, s)
                bits = np.dtype(f"u{wv.data.dtype.itemsize}")
                np.testing.assert_array_equal(gv.data.view(bits),
                                              wv.data.view(bits))
                assert (gv.valid is None) == (wv.valid is None)
                if wv.valid is not None:
                    np.testing.assert_array_equal(gv.valid, wv.valid)
                moved += np.asarray(gv.data).nbytes
    emit({"phase": "mesh", "statement": "ici.local_device_exchange",
          "participants": n, "rows_per_sender": per, "pack_cap": cap,
          "bytes_received_off_device": int(moved),
          "first_s": round(first, 3), "wall_s": round(wall, 3),
          "equal_to_host_pack_unpack": True,
          "peak_bytes_in_use": _peak_bytes()})


def phase_mesh(spark, ds, emit, n=4, require_device_memory=True):
    """q3 and q17 as ONE shard_map program each over ``n`` devices
    (whole-file leaves: the in-slice ``DistributedExecution``), then the
    device exchange step; every device must have held data."""
    from spark_tpu.tpcds import QUERIES
    idle = _peak_bytes()
    spark.conf.set("spark.tpu.mesh.shards", str(n))
    # one batch per file relation: the plan is neither streamed nor staged,
    # so it is DistributedExecution's single program over the mesh
    spark.conf.set("spark.tpu.scan.maxBatchRows", str(1 << 30))
    _register(spark, ds, dims_in_memory=True)
    lit = STAR["q3"][1]
    for name, sql, ref in (
            ("q3", Q3.format(**lit), _star_ref(ds.frames, "q3", lit)),
            ("q17", QUERIES["q17"], _q17_ref(ds.frames))):
        t0 = time.time()
        rows = [tuple(r) for r in spark.sql(sql).collect()]
        first = time.time() - t0
        t0 = time.time()
        spark.sql(sql).collect()
        wall = time.time() - t0
        n_rows = compare(f"mesh/{name}", rows, ref)
        emit({"phase": "mesh", "statement": name, "shards": n,
              "fact_rows": ds.rows, "first_s": round(first, 3),
              "wall_s": round(wall, 3),
              "compile_s": round(max(first - wall, 0.0), 3),
              "collectives": _dist_collectives(spark, n),
              "peak_bytes_in_use": _peak_bytes(),
              "result_rows": n_rows, "equal_to_reference": True})
    _exchange_check(ds, n, emit)
    peaks = _peak_bytes()[:n]
    emit({"phase": "mesh", "statement": "devices", "idle_bytes": idle[:n],
          "peak_bytes_in_use": peaks})
    if require_device_memory:
        for i, (p, base) in enumerate(zip(peaks, idle)):
            assert p is not None and p > (base or 0), \
                f"device {i} stayed at its idle level ({p} bytes)"


# ---------------------------------------------------------------------------

def _emit(obj):
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=0,
                    help="store_sales rows; the other facts scale off it "
                    f"(default {ROWS_ONE_CHIP:,}; {ROWS_MESH:,} with "
                    "--chips 4)")
    ap.add_argument("--seed", type=int, default=20260730)
    ap.add_argument("--work-dir",
                    default=os.path.join(HERE, ".chip_smoke_work"))
    ap.add_argument("--batch-rows", type=int, default=0,
                    help="spark.tpu.scan.maxBatchRows for the one-chip "
                    "phases (0 = the engine's default)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the mesh phase and nothing else")
    args = ap.parse_args(argv)

    import jax
    import spark_tpu  # noqa: F401  (x64 and the compile cache, before any array)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU — jax.devices() is {devs}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax.devices() has "
              f"{len(devs)}", file=sys.stderr)
        return 1

    from spark_tpu.sql.session import SparkSession
    spark = SparkSession.builder.appName("chip_smoke").getOrCreate()
    spark.conf.set("spark.sql.warehouse.dir",
                   os.path.join(args.work_dir, "warehouse"))
    t0 = time.time()
    rows = args.rows or (ROWS_ONE_CHIP if args.chips == 1 else ROWS_MESH)
    ds = build_dataset(rows, args.seed, args.work_dir, _emit)
    if args.chips == 1:
        phase_session(spark, ds, _emit, args.batch_rows or None)
        phase_server(spark, ds, _emit)
    else:
        phase_mesh(spark, ds, _emit, args.chips)
    _emit({"phase": "total", "wall_s": round(time.time() - t0, 2)})
    _emit({"ok": True, "device": {"platform": devs[0].platform,
                                  "kind": devs[0].device_kind,
                                  "count": args.chips}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
