"""spark_tpu.tracing: spans on the profiler's clock, operator/kernel scopes
that change metadata only, counters, trace-time notes, and the two doors
an operator reads them through (``SQLExecutionEnd.phases``, ``GET /status``
``trace``).  CPU, tiny rows."""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_tpu.config as C
from spark_tpu import kernels as K
from spark_tpu import tracing
from spark_tpu.sql import stagecompile as SC
from spark_tpu.sql.planner import QueryExecution, local_stage_key
from spark_tpu.tpcds import QUERIES, generate

BATCH = 4096
ROWS = 3 * BATCH          # store_sales streams in exactly three batches
AGG_CUSTOMER_TOP100 = (
    "SELECT ss_customer_sk, ss_store_sk, COUNT(*) AS cnt, "
    "SUM(ss_quantity) AS qty, SUM(ss_net_paid) AS paid "
    "FROM store_sales GROUP BY ss_customer_sk, ss_store_sk "
    "ORDER BY paid DESC, ss_customer_sk, ss_store_sk LIMIT 100")
FACTS = ("store_sales", "store_returns", "catalog_sales")
DIMS = ("date_dim", "item", "store")


@pytest.fixture(scope="module")
def tables():
    return generate(ROWS)


@pytest.fixture(scope="module")
def fact_dir(tables, tmp_path_factory):
    d = tmp_path_factory.mktemp("tracing") / "store_sales"
    os.makedirs(d)
    tables["store_sales"].to_parquet(d / "part-000.parquet", index=False)
    return str(d)


@pytest.fixture()
def streamed(spark, tables, fact_dir):
    """store_sales as one parquet file read in three batches on a prefetch
    thread; the dimensions q3 joins in memory."""
    spark.read.parquet(fact_dir).createOrReplaceTempView("store_sales")
    for name in DIMS:
        spark.createDataFrame(tables[name]).createOrReplaceTempView(name)
    old = {k: spark.conf.get(k) for k in (C.SCAN_MAX_BATCH_ROWS,
                                          C.SCAN_PREFETCH_BATCHES)}
    spark.conf.set(C.SCAN_MAX_BATCH_ROWS.key, str(BATCH))
    spark.conf.set(C.SCAN_PREFETCH_BATCHES.key, "2")
    yield spark
    for k, v in old.items():
        spark.conf.set(k.key, str(v))
    for name in ("store_sales",) + DIMS:
        spark.catalog.dropTempView(name)


@pytest.fixture()
def in_memory(spark, tables):
    for name in FACTS + DIMS:
        spark.createDataFrame(tables[name]).createOrReplaceTempView(name)
    yield spark
    for name in FACTS + DIMS:
        spark.catalog.dropTempView(name)


def _statement_spans(sid):
    return [s for s in tracing.spans() if s.statement_id == sid]


# -- spans of one streamed statement ----------------------------------------

def test_streamed_q3_ids_parents_self_times(streamed):
    streamed.sql(QUERIES["q3"]).collect()          # warm: builds the steps
    tracing.reset()
    rows = streamed.sql(QUERIES["q3"]).collect()
    assert rows
    last = tracing.last_statement()
    spans = _statement_spans(last["id"])
    names = [s.name for s in spans]
    # one id from parse to the rows, on both threads
    assert {"parse", "analyze", "optimize", "plan", "statement",
            "scan.read", "scan.decode", "scan.prep", "scan.wait", "h2d",
            "stage.dispatch", "d2h", "merge", "collect.rows"} <= set(names)
    assert all(s.statement_id == last["id"] for s in tracing.spans()
               if s.name != "scan.read" or s.statement_id)
    root = [s for s in spans if s.name == "statement"]
    assert root[0].parent is None and root[0].attrs["path"] == "stages"
    # (collect's look at the schema analyzes once more, outside the root)
    assert "statement" in [s.parent for s in spans if s.name == "analyze"]
    assert names.count("scan.decode") == 3 and names.count("scan.prep") == 3
    # the scan runs on the prefetch thread, under the statement's id
    main = root[0].thread
    scan = [s for s in spans if s.name in ("scan.read", "scan.decode",
                                           "scan.prep")]
    assert scan and all(s.thread != main for s in scan)
    assert all(s.thread == main for s in spans if s.name == "scan.wait")
    assert all(s.attrs["rows"] == BATCH for s in scan
               if s.name == "scan.decode")
    # self time: a span's duration less what its children cover
    phases = last["phases"]
    children = sum(s.dur_ns for s in spans
                   if s.parent == "statement" and s.thread == main)
    assert phases["statement"] == pytest.approx(
        (root[0].dur_ns - children) / 1e6, abs=0.01)
    leaf = sum(s.dur_ns for s in spans if s.name == "scan.decode")
    assert phases["scan.decode"] == pytest.approx(leaf / 1e6, abs=0.01)
    assert sum(phases.values()) <= sum(
        s.dur_ns for s in spans if s.parent is None) / 1e6 + 0.01


def test_streamed_statement_span_budget(streamed):
    """At most 80 spans for a warm three-batch streamed statement."""
    streamed.sql(QUERIES["q3"]).collect()
    streamed.sql(QUERIES["q3"]).collect()
    last = tracing.last_statement()
    assert last["spans"] == len(_statement_spans(last["id"]))
    assert 20 <= last["spans"] <= 80, last


def test_ring_and_statements_stay_bounded():
    tracing.reset()
    with tracing.statement() as sid:
        for i in range(tracing.RING_SIZE + 100):
            with tracing.span("tick", i=i):
                pass
    spans = tracing.spans()
    assert len(spans) == tracing.RING_SIZE
    assert spans[0].attrs["i"] == 100 and spans[-1].statement_id == sid
    assert tracing.summary()["spans"]["tick"]["count"] \
        == tracing.RING_SIZE + 100
    for _ in range(tracing.STATEMENTS_KEPT + 10):
        with tracing.statement():
            pass
    assert len(tracing._statements) == tracing.STATEMENTS_KEPT
    assert tracing.statement_phases(sid) == {}       # no longer kept
    lo = spans[10].start_ns
    assert all(s.start_ns + s.dur_ns > lo for s in tracing.spans(lo_ns=lo))


def test_worker_thread_adopts_and_counts():
    tracing.reset()
    seen = []

    def work(sid):
        with tracing.adopt(sid):
            with tracing.span("outer"), tracing.span("inner"):
                seen.append(tracing.current_statement())

    with tracing.statement() as sid:
        th = threading.Thread(target=work, args=(sid,))
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    assert seen == [sid] and tracing.current_statement() == 0
    inner = next(s for s in tracing.spans() if s.name == "inner")
    assert inner.parent == "outer" and inner.statement_id == sid
    with tracing.fresh_jit("here"):
        pass
    tracing.count("jit.fresh", 2)
    assert tracing.summary()["counts"] == {"jit.fresh": 3}
    assert tracing.spans()[-1].attrs == {"site": "here"}


# -- device names ------------------------------------------------------------

def _plan_ops(node):
    yield node
    for c in node.children:
        yield from _plan_ops(c)


def _lowered_text(spark, sql):
    """(debug text of the stage program the statement dispatched, its
    physical plan, stage fingerprint)."""
    df = spark.sql(sql)
    df.collect()
    qe = QueryExecution(spark, df._plan)
    key, slots, leaves = local_stage_key(spark, qe.planned)
    entry = SC.stage_cache(spark).peek(key)
    assert entry is not None, "the statement did not take the local stage"
    text = entry.fn.lower(tuple(b.to_device() for b in leaves),
                          SC.param_values(slots)).as_text(debug_info=True)
    return text, qe.planned.physical, key


@pytest.mark.parametrize("name", ["q3", "q17", "agg_customer_top100"])
def test_operator_scopes_in_lowered_text(in_memory, name, monkeypatch):
    sql = AGG_CUSTOMER_TOP100 if name == "agg_customer_top100" \
        else QUERIES[name]
    text, physical, key = _lowered_text(in_memory, sql)
    ops = [op for op in _plan_ops(physical)
           if type(op).__name__ != "PScan"]       # a leaf emits no op
    assert len(ops) >= 4
    for op in ops:
        assert f"{type(op).__name__}#{op.op_id}" in text, type(op).__name__
    assert "stage.step" in text
    # scopes are metadata only: without them the plan key, the stage
    # fingerprint and the number of programs built are what they were
    builds = SC.stage_cache().stats()["builds"]
    import contextlib
    monkeypatch.setattr(tracing, "scope",
                        lambda _name: contextlib.nullcontext())
    df = in_memory.sql(sql)
    qe = QueryExecution(in_memory, df._plan)
    assert qe.planned.physical.key() == physical.key()
    assert local_stage_key(in_memory, qe.planned)[0] == key
    df.collect()
    assert SC.stage_cache().stats()["builds"] == builds
    assert not any(s in key or s in physical.key()
                   for s in ("stage.step", "join.probe", "agg.sort"))


def test_kernel_scopes_in_lowered_text(in_memory, monkeypatch):
    texts = [_lowered_text(in_memory, QUERIES["q3"])[0],
             _lowered_text(in_memory, AGG_CUSTOMER_TOP100)[0]]
    # the MXU aggregate (portable one-hot form off the chip)
    from spark_tpu.aggregates import CountStar, Sum
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.expressions import Col
    from spark_tpu import types as T
    n = 2048
    batch = ColumnBatch(
        ["k", "v"],
        [ColumnVector(jnp.arange(n) % 7, T.LongType(), None, None),
         ColumnVector(jnp.arange(n), T.LongType(), None, None)], None, n)
    monkeypatch.setattr(K, "MXU_AGG_ENABLED", True)
    aggs = [(Sum(Col("v")), "s"), (CountStar(), "c")]
    texts.append(jax.jit(lambda b: K.grouped_aggregate(
        jnp, b, [Col("k")], aggs)).lower(batch).as_text(debug_info=True))
    monkeypatch.setattr(K, "MXU_AGG_ENABLED", None)
    texts.append(jax.jit(lambda b: K.partition_bucket(
        jnp, b, b.vectors[0].data, 7)[0]).lower(batch)
        .as_text(debug_info=True))
    # the chained single-key sort of the TPU branch
    monkeypatch.setattr(K, "_on_tpu_device", lambda: True)
    texts.append(jax.jit(lambda a, b: K.multi_key_argsort(
        jnp, [a, b], n)).lower(jnp.arange(n), jnp.arange(n))
        .as_text(debug_info=True))
    monkeypatch.undo()
    from spark_tpu import pallas_agg
    texts.append(pallas_agg._accumulate_chunk.lower(
        jnp.zeros(n, jnp.int32), jnp.zeros((n, 4), jnp.bfloat16),
        jnp.int32(1), B=512, L=1024, BB=512, interpret=True)
        .as_text(debug_info=True))
    # the collectives, as the distributed executor's one program
    from spark_tpu.parallel.executor import DistributedPlanner, shard_program
    from spark_tpu.parallel.mesh import get_mesh
    pq = DistributedPlanner(in_memory, 2).plan(
        QueryExecution(in_memory,
                       in_memory.sql(QUERIES["q3"])._plan).optimized)
    from spark_tpu.parallel.executor import shard_leaf
    mesh = get_mesh(2)
    texts.append(jax.jit(shard_program(pq.physical, mesh)).lower(
        tuple(shard_leaf(mesh, 2, b) for b in pq.leaves))
        .as_text(debug_info=True))
    # a sub-plan the stage runner materializes runs under stage.merge
    qe = QueryExecution(in_memory, in_memory.sql(
        "SELECT s_store_sk + 41 AS k FROM store ORDER BY k")._plan)
    qe._stage_scope = "stage.merge"
    qe._execute_inner()
    key, slots, leaves = local_stage_key(in_memory, qe.planned)
    texts.append(SC.stage_cache().peek(key).fn.lower(
        tuple(b.to_device() for b in leaves), SC.param_values(slots))
        .as_text(debug_info=True))
    # a grouping set re-aggregated from a finer one runs under
    # grouping.rollup; a window's phases are scopes of their own
    qe = QueryExecution(in_memory, in_memory.sql(
        "SELECT s_store_sk, COUNT(*) AS n FROM store GROUP BY s_store_sk")
        ._plan)
    qe._stage_scope = "grouping.rollup"
    qe._execute_inner()
    key, slots, leaves = local_stage_key(in_memory, qe.planned)
    texts.append(SC.stage_cache().peek(key).fn.lower(
        tuple(b.to_device() for b in leaves), SC.param_values(slots))
        .as_text(debug_info=True))
    from spark_tpu.sql.logical import SortOrder
    from spark_tpu.sql.window import Rank, WindowSpec, compute_windows
    spec = WindowSpec([Col("k")], [SortOrder(Col("v"), False)])
    texts.append(jax.jit(lambda b: compute_windows(
        jnp, b, spec, [(Rank(), "r"), (Sum(Col("v")), "s")])).lower(batch)
        .as_text(debug_info=True))
    # a join of two string columns with dictionaries of their own gathers
    # each side's codes through the id table the trace built for it
    from spark_tpu.sql.joins import _exact_encode_pair
    from spark_tpu.expressions import EvalContext

    def words(*w):
        return ColumnBatch(["w"], [ColumnVector(
            jnp.arange(n, dtype=jnp.int32) % len(w), T.string, None, w)],
            None, n)
    texts.append(jax.jit(lambda a, b: _exact_encode_pair(
        EvalContext(a, jnp), EvalContext(b, jnp), Col("w"), Col("w"))[0])
        .lower(words("a", "c"), words("b", "c", "d"))
        .as_text(debug_info=True))
    text = "\n".join(texts)
    missing = sorted(s for s in tracing.KERNEL_SCOPES if s not in text)
    assert not missing, missing
    assert "argsort.pass0" in text and "argsort.pass1" in text
    # a scope name holds no literal, dictionary or capacity
    assert all(tracing.named_scope_of(f"jit(run)/{s}/gather:") == s
               for s in tracing.KERNEL_SCOPES)
    assert tracing.named_scope_of(
        "jit(run)/stage.step/PSort#1/PJoin#4/join.probe/while/body/gather:"
    ) == "PJoin#4/join.probe"
    # a join's two paths are the branches of one conditional in the program:
    # the general path keeps join.probe / join.expand, the unique-build
    # path runs under join.unique; the slot-to-probe-row map is a scatter
    # and a running sum, so no loop stands under join.expand (the probe's
    # searches, under join.probe, keep theirs)
    assert "branch_1_fun/join.unique/" in text \
        and "branch_0_fun/join.expand/" in text
    # the probe lookup is a branch too: by table under join.dense, by search
    # under join.probe
    assert re.search(r"branch_\d_fun/join\.dense/", text) \
        and re.search(r"branch_\d_fun/join\.probe/", text)
    # (the lowered text names a loop inside an inner jit relative to it;
    # the compiled program's op names are whole paths)
    assert "join.probe/jit(searchsorted)" in text
    assert not re.search(r'join\.expand/[^"\n]*(while|searchsorted)', text)
    from spark_tpu.sql import physical as P
    fact = in_memory.createDataFrame({"k": np.arange(64, dtype=np.int64) % 16})
    dim = in_memory.createDataFrame({"dk": np.arange(16, dtype=np.int64) // 2})
    pq = QueryExecution(
        in_memory, fact.join(dim, fact["k"] == dim["dk"])._plan).planned
    names = re.findall(r'op_name="([^"]*)"', jax.jit(
        lambda leaves: pq.physical.run(P.ExecContext(jnp, list(leaves))))
        .lower(tuple(b.to_device() for b in pq.leaves)).compile().as_text())
    assert any("join.probe" in n and "/while/" in n for n in names)
    assert any("join.expand" in n for n in names)
    assert not any("join.expand" in n and "while" in n for n in names)
    assert tracing.named_scope_of(
        "jit(step)/stage.step/PJoin#3/cond/branch_1_fun/join.unique/"
        "take_batch/gather"
    ) == "PJoin#3/join.unique/take_batch"
    assert tracing.named_scope_of("jit(run)/jit(main)/mul") is None


def test_agg_lowering_note(streamed):
    """The statement shows which keyed-aggregate lowering its stages took,
    although the stages were traced by an earlier statement."""
    tracing.reset()
    streamed.sql(QUERIES["q3"]).collect()
    notes = tracing.last_statement()["notes"]
    assert notes["agg_lowering"] == ["sort.scan"]
    streamed.sql(AGG_CUSTOMER_TOP100).collect()
    assert tracing.last_statement()["notes"]["agg_lowering"] == ["sort.scan"]


# -- one clock ---------------------------------------------------------------

def test_annotations_share_the_ring_clock(in_memory, tmp_path):
    """A CPU profiler trace holds the ``sql:`` annotations, and each, with
    the trace's ``profile_start_time`` added, lies within 1 ms of its ring
    record."""
    in_memory.sql(AGG_CUSTOMER_TOP100).collect()       # warm
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        in_memory.sql(AGG_CUSTOMER_TOP100).collect()
    finally:
        jax.profiler.stop_trace()
    read = tracing.device_time_by_scope(tracing._xplanes(str(tmp_path))[-1])
    ring = tracing.spans()
    assert read["profile_start_ns"] and all(s.profiled for s in ring)
    assert {"statement", "parse", "h2d", "d2h", "stage.dispatch"} \
        <= set(read["host_spans"])
    assert len(read["annotations"]) == len(ring)
    assert max(tracing.clock_gaps_ms(read["annotations"], ring)) < 1.0
    # with no profiler attached a span opens no annotation
    in_memory.sql(AGG_CUSTOMER_TOP100).collect()
    assert not tracing.spans()[-1].profiled


def test_cli_prints_the_join_paths(in_memory, tmp_path, capsys):
    """``python -m spark_tpu.tracing <trace dir>`` tallies the ``join.path``
    spans by their attributes: which path each traced join took."""
    fact = in_memory.createDataFrame({"k": np.arange(64, dtype=np.int64) % 16})
    dim = in_memory.createDataFrame({"dk": np.arange(16, dtype=np.int64)})
    q = fact.join(dim, fact["k"] == dim["dk"])
    q.collect()                                        # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        q.collect()
    finally:
        jax.profiler.stop_trace()
    read = tracing.device_time_by_scope(tracing._xplanes(str(tmp_path))[-1])
    assert read["join_paths"] == [[{"unique": 1, "dense": 1, "out_cap": 64,
                                    "probe_cap": 64, "string": 0}, 1]]
    assert tracing._main([str(tmp_path)]) == 0
    assert '1 sql:join.path  {"dense": 1, "out_cap": 64, "probe_cap": 64, ' \
        '"string": 0, "unique": 1}' in capsys.readouterr().out


@pytest.mark.parametrize("lane", ["local", "stages"])
def test_scan_rounds_ride_out_as_an_operator_metric(lane, request, tmp_path,
                                                    capsys):
    """The rounds a sort aggregate's segmented scan took leave the program
    as the operator metric ``agg.scan_rounds``: ``SQLExecutionEnd.metrics``
    carries it on the local lane, the local and the ``stages`` lanes write
    one ``agg.scan`` span for each aggregate of each step they fetch, and
    ``python -m spark_tpu.tracing`` tallies them.  The rounds are what the
    input asked for, ``ceil(log2(longest run))``: a handful where the
    aggregate statement's groups hold a handful of rows, never more than
    ``log2(capacity)``."""
    if lane == "local":
        spark, text, most = request.getfixturevalue("in_memory"), \
            AGG_CUSTOMER_TOP100, 6
    else:
        spark, text, most = request.getfixturevalue("streamed"), \
            QUERIES["q3"], 12                          # log2(BATCH)
    events = []
    spark.listenerManager.register(events.append)
    try:
        spark.sql(text).collect()                      # warm
        tracing.reset()
        jax.profiler.start_trace(str(tmp_path))
        try:
            spark.sql(text).collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        spark.listenerManager.unregister(events.append)
    scans = [s.attrs for s in tracing.spans() if s.name == "agg.scan"]
    assert len(scans) >= (1 if lane == "local" else 3)    # one a batch
    assert all(0 <= a["rounds"] <= most for a in scans)
    if lane == "local":
        end = [e for e in events if e["event"] == "SQLExecutionEnd"][-1]
        rounds = [v for k, v in end["metrics"].items()
                  if k.endswith(":agg.scan_rounds")]
        assert rounds == [scans[-1]["rounds"]]
    read = tracing.device_time_by_scope(tracing._xplanes(str(tmp_path))[-1])
    assert sum(n for _attrs, n in read["agg_scans"]) == len(scans)
    assert tracing._main([str(tmp_path)]) == 0
    assert "sql:agg.scan  {" in capsys.readouterr().out


def test_check_clock_cli(tmp_path):
    got = tracing.check_clock(str(tmp_path / "clk"), n=4)
    assert got["annotations"] == got["expected"] == 8
    assert got["max_gap_ms"] < 1.0


# -- the operator's two doors ------------------------------------------------

def test_sql_execution_end_phases(in_memory):
    events = []
    in_memory.listenerManager.register(events.append)
    try:
        in_memory.sql(AGG_CUSTOMER_TOP100).collect()
    finally:
        in_memory.listenerManager.unregister(events.append)
    end = [e for e in events if e["event"] == "SQLExecutionEnd"][-1]
    assert {"statement", "analyze", "optimize", "plan", "h2d", "d2h"} \
        <= set(end["phases"])
    assert sum(end["phases"].values()) <= end["durationMs"] * 1.05 + 50
    json.dumps(end["phases"])


def test_status_trace_and_http_spans(in_memory):
    from spark_tpu.server import SQLServer
    srv = SQLServer(in_memory, port=0).start()
    try:
        def call(path, body=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}{path}",
                data=None if body is None else json.dumps(body).encode(),
                method="GET" if body is None else "POST")
            req.add_header("Content-Type", "application/json")
            with urllib.request.urlopen(req, timeout=120) as resp:
                return json.loads(resp.read().decode())
        tracing.reset()
        out = call("/sql", {"query": "SELECT 1 AS x"})
        assert out["rows"] == [[1]]
        last = tracing.last_statement()
        spans = _statement_spans(last["id"])
        names = {s.name for s in spans}
        assert {"admission.wait", "http.statement", "parse", "statement",
                "http.encode"} <= names
        http = next(s for s in spans if s.name == "http.statement")
        wait = next(s for s in spans if s.name == "admission.wait")
        assert http.thread != wait.thread      # handler and pool thread
        assert next(s for s in spans
                    if s.name == "statement").parent == "http.statement"
        trace = call("/status")["trace"]
        assert trace["spans"]["http.statement"]["count"] == 1
        assert trace["spans"]["statement"]["total_ms"] > 0
    finally:
        srv.stop()


def test_plancache_miss_builds_in_the_stage_cache(in_memory):
    """The serving plan cache keeps plans, not executables: a miss builds
    its program in the stage cache (``stage.lookup`` miss + ``stage.build``,
    no ``jit.fresh``), and the repeat hits both stores."""
    from spark_tpu.serving import PlanCache
    in_memory._plan_cache = PlanCache(in_memory.conf_obj)
    try:
        sql = ("SELECT i_brand_id, COUNT(*) c, SUM(i_item_sk) + 29 s "
               "FROM item WHERE i_manufact_id = 7 GROUP BY i_brand_id")
        tracing.reset()
        first, spans = in_memory.sql(sql).collect(), tracing.spans()
        names = [s.name for s in spans]
        assert "jit.fresh" not in names
        assert "jit.fresh" not in tracing.summary()["counts"]
        assert [s.attrs["hit"] for s in spans
                if s.name == "stage.lookup"] == [False]
        assert names.count("stage.build") == 1
        tracing.reset()
        again, spans = in_memory.sql(sql).collect(), tracing.spans()
        assert again == first
        names = [s.name for s in spans]
        assert "jit.fresh" not in names and "stage.build" not in names
        assert "plan" not in names                 # the hit skipped planning
        assert [s.attrs["hit"] for s in spans
                if s.name == "plancache.lookup"] == [True]
        assert [s.attrs["hit"] for s in spans
                if s.name == "stage.lookup"] == [True]
    finally:
        in_memory._plan_cache = None


# -- the repair in code the spans touch --------------------------------------

def test_plan_cache_key_memo_survives_freed_nodes():
    """``plan_cache_key``'s memo is keyed on ``id(node)``; it keeps the node
    alive beside its key, so a node built where a freed one lay cannot take
    the freed one's key."""
    from spark_tpu.columnar import ColumnBatch
    from spark_tpu.sql import logical as L

    def leaf(n):
        return L.LocalRelation(ColumnBatch.from_arrays(
            {"a": np.arange(n, dtype=np.int64)}))

    memo = {}
    seen = {}
    for i in range(200):
        node = L.Limit(i, leaf(1 + i % 3))
        key = L.plan_cache_key(node, memo)
        assert key == L.plan_cache_key(node), (i, key)
        assert seen.setdefault(key, i) == i
        del node                 # freed: its address may be handed out again
    # a stale entry under a reused address is not trusted
    a = leaf(2)
    memo = {id(a): (leaf(3), "LocalRelation#stale")}
    assert L.plan_cache_key(a, memo) == L.plan_cache_key(a)


# -- the benchmark's reading of the join paths --------------------------------

_STAR_MESH = ["sf1-star-parquet", "mesh4-q3-q17"]
_JOIN_CELLS = ["sf1-star-parquet", "sf1-star-cached", "sf1-web-orders-http",
               "mesh4-q3-q17"]


@pytest.mark.parametrize("metric, cells, ring, want", [
    # three of statement 7's four joins and statement 8's one; the joins of
    # the warm-up before the slice and of the statement after it do not count
    ("join.unique_pct", _STAR_MESH, "ring_join_path.json", 80.0),
    # a program that records no join.path (any tree before PR 26) reads 0
    ("join.unique_pct", _STAR_MESH, "ring_small.json", 0.0),
    # the same spans as PR 30 records them: two of statement 7's joins and
    # statement 8's one read their matches from the table
    ("join.dense_pct", _JOIN_CELLS, "ring_join_dense.json", 60.0),
    ("join.unique_pct", _STAR_MESH, "ring_join_dense.json", 80.0),
    # a program whose join.path lacks the attribute (PR 26 to PR 29) reads 0
    ("join.dense_pct", _JOIN_CELLS, "ring_join_path.json", 0.0),
    ("join.dense_pct", _JOIN_CELLS, "ring_small.json", 0.0),
])
def test_join_path_pct_on_the_recording(metric, cells, ring, want):
    """``join.unique_pct`` / ``join.dense_pct`` as the benchmark computes
    them: the manifest entry, the data file and the accepted
    ``program_spans`` reader, over a small recorded ring laid on the
    harness's recorded trace."""
    import importlib
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    from benchmark.lib import trace as TR
    from benchmark.run import Context
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == metric)
    assert (entry["unit"], entry["better"], entry["layer"], entry["moves"]) \
        == ("%", "higher", "operators", "fact_rows_per_s")
    assert entry["workloads"] == cells
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(bench, "tests", "trace_small.json")) as fh:
        trace = TR.Reduced(json.load(fh))
    with open(os.path.join(bench, "tests", ring)) as fh:
        ctx = Context(trace=trace, ring=json.load(fh)["ring"])
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert reader.read(ctx, **spec["args"]) == pytest.approx(want)


ROLLUP_RANK = (
    "SELECT i_category, i_class, i_brand, s, "
    "RANK() OVER (PARTITION BY i_category ORDER BY s DESC) AS rk "
    "FROM (SELECT i_category, i_class, i_brand, "
    "SUM(ss_ext_sales_price) AS s FROM store_sales, item "
    "WHERE ss_item_sk = i_item_sk "
    "GROUP BY ROLLUP(i_category, i_class, i_brand)) t")


@pytest.mark.parametrize("lane", ["local", "stages"])
def test_grouping_arms_and_windows_are_spans(lane, request, tmp_path,
                                             capsys):
    """A ROLLUP of three keys under a RANK: one ``grouping.arm`` span for
    each of its four sets -- the finest read from the statement's rows, each
    other from the next finer set, whose groups are its ``rows_in`` -- and
    one ``window`` span with the window's functions and keys (and, on the
    ``stages`` lane, the rows of the union it ranks); ``python -m
    spark_tpu.tracing`` tallies both."""
    spark = request.getfixturevalue("in_memory" if lane == "local"
                                    else "streamed")
    spark.sql(ROLLUP_RANK).collect()                   # warm
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        spark.sql(ROLLUP_RANK).collect()
    finally:
        jax.profiler.stop_trace()
    spans = tracing.spans()
    root, = [s for s in spans if s.name == "statement"]
    assert root.attrs["path"] == lane
    arms = [s.attrs for s in spans if s.name == "grouping.arm"]
    keys = ["i_category", "i_class", "i_brand"]
    assert [(a["set"], a["keys"], a["from_finer"]) for a in arms] == \
        [(i, keys[:3 - i], i > 0) for i in range(4)]
    assert arms[0]["rows_in"] is None
    assert [a["rows_in"] for a in arms[1:]] == \
        [a["rows_out"] for a in arms[:-1]]
    window, = [s.attrs for s in spans if s.name == "window"]
    assert window["funcs"] == ["rank()"]
    assert window["partition_keys"] == ["i_category"]
    assert window["order_keys"] == ["s DESC NULLS LAST"]
    assert window["rows"] == (None if lane == "local"
                              else sum(a["rows_out"] for a in arms))
    read = tracing.device_time_by_scope(tracing._xplanes(str(tmp_path))[-1])
    assert sorted(n for _attrs, n in read["grouping_arms"]) == [1, 3]
    assert {json.dumps(a) for a, _n in read["grouping_arms"]} == {
        '{"from_finer": 0}', '{"from_finer": 1}'}
    assert sum(n for _attrs, n in read["windows"]) == 1
    assert tracing._main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert '3 sql:grouping.arm  {"from_finer": 1}' in out
    assert "1 sql:window  {" in out
