"""Multi-tenant serving core: admission control + cross-session plan
cache (serving/admission.py, serving/plancache.py, server.py wiring).

The contract under test: identical (or literal-slotted) statements from
DIFFERENT server sessions share one compiled executable; catalog
mutations and planning-conf changes invalidate affected entries with
oracle-exact results; over-limit submissions fail fast with a structured
429 naming the exhausted limit — never an unbounded queue, never a lost
statement."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from spark_tpu import config as C
from spark_tpu.server import SQLServer
from spark_tpu.serving import (AdmissionController, AdmissionRejected,
                               PlanCache)


@pytest.fixture()
def serve_root(spark, tmp_path):
    """A dedicated root session per test: server-side conf experiments
    (caps, timeouts, warehouse) must not leak into the shared fixture."""
    s = spark.newSession()
    s.conf.set("spark.sql.warehouse.dir", str(tmp_path / "wh"))
    return s


def _req(srv, path, method="GET", body=None, headers=None):
    req = urllib.request.Request(
        f"http://{srv.host}:{srv.port}{path}",
        data=body.encode() if isinstance(body, str) else body,
        method=method, headers=headers or {})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


def _sql(srv, query, sid=None, stmt_id=None):
    body = {"query": query}
    if sid:
        body["session"] = sid
    if stmt_id:
        body["id"] = stmt_id
    return _req(srv, "/sql", "POST", json.dumps(body))[1]


# ---------------------------------------------------------------------------
# plan cache: cross-session sharing + literal slotting
# ---------------------------------------------------------------------------

def test_plan_cache_shared_across_sessions(serve_root):
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s1 = _req(srv, "/session", "POST")
        _, s2 = _req(srv, "/session", "POST")
        q = "SELECT id, id * 2 AS y FROM range(64) ORDER BY id"
        r1 = _sql(srv, q, s1["sessionId"])
        assert r1["cacheHit"] is False
        r2 = _sql(srv, q, s2["sessionId"])
        assert r2["cacheHit"] is True, \
            "session 2 must reuse session 1's compiled plan"
        assert r2["planningSkippedMs"] > 0
        assert r2["rows"] == r1["rows"]
        _, st = _req(srv, "/status")
        assert st["planCache"]["hits"] >= 1
        assert st["planCache"]["entries"] >= 1
        # the gauges ride the session metrics system as a Source
        assert st["metrics"]["serving"]["plan_cache_hits"] >= 1
    finally:
        srv.stop()


def test_literal_variants_share_one_entry(serve_root):
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    r1 = [tuple(r) for r in
          s.sql("SELECT id FROM range(30) WHERE id < 10").collect()]
    r2 = [tuple(r) for r in
          s.sql("SELECT id FROM range(30) WHERE id < 20").collect()]
    assert len(r1) == 10 and len(r2) == 20
    st = cache.stats()
    # the literal is slotted out of the fingerprint: ONE entry, and the
    # second variant is a hit re-executed with a different parameter
    assert st["entries"] == 1, st
    assert st["hits"] == 1 and st["misses"] == 1, st


def test_plan_cache_invalidation_oracle_exact(serve_root):
    cache = PlanCache(serve_root.conf_obj)
    s1 = serve_root.newSession()
    s2 = serve_root.newSession()
    s1._plan_cache = cache
    s2._plan_cache = cache
    s1.sql("CREATE TABLE pcinv_t AS "
           "SELECT id AS k, id * 3 AS v FROM range(50)")
    q = ("SELECT k % 5 AS g, sum(v) AS sv FROM pcinv_t "
         "WHERE v < 120 GROUP BY k % 5 ORDER BY g")
    a1 = [tuple(r) for r in s1.sql(q).collect()]
    a2 = [tuple(r) for r in s2.sql(q).collect()]
    assert a1 == a2 and cache.stats()["hits"] >= 1

    # INSERT must evict entries scanning the table; the next run over
    # the cache must see the new rows, byte-for-byte vs a fresh session
    s2.sql("INSERT INTO pcinv_t SELECT id AS k, id AS v FROM range(5)")
    assert cache.stats()["invalidations"] >= 1
    a3 = [tuple(r) for r in s1.sql(q).collect()]
    oracle = [tuple(r) for r in serve_root.newSession().sql(q).collect()]
    assert a3 == oracle and a3 != a1

    # a planning-relevant conf change evicts entries built under the
    # old value (the fingerprint's conf component is the backstop)
    before = cache.stats()["invalidations"]
    s1.sql("SET spark.tpu.crossproc.autoBroadcastThreshold=12345")
    assert cache.stats()["invalidations"] > before
    a4 = [tuple(r) for r in s1.sql(q).collect()]
    assert a4 == oracle

    s1.sql("DROP TABLE pcinv_t")
    with pytest.raises(Exception):
        s1.sql(q).collect()


def test_run_codes_conf_invalidates_plan_cache(serve_root):
    """``spark.tpu.shuffle.wire.runCodes`` is a planning conf: SET must
    evict cached entries built under the old value (run-encoded and raw
    wire plans are not interchangeable executables), and the re-planned
    run must stay oracle-equal."""
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    s.sql("CREATE TABLE pcrun_t AS "
          "SELECT id % 4 AS k, id AS v FROM range(64)")
    q = ("SELECT k, sum(v) AS sv, count(*) AS c FROM pcrun_t "
         "GROUP BY k ORDER BY k")
    a1 = [tuple(r) for r in s.sql(q).collect()]
    assert [tuple(r) for r in s.sql(q).collect()] == a1
    assert cache.stats()["hits"] >= 1
    before = cache.stats()["invalidations"]
    s.sql("SET spark.tpu.shuffle.wire.runCodes=false")
    assert cache.stats()["invalidations"] > before, \
        "runCodes must be fingerprinted as a planning conf"
    a2 = [tuple(r) for r in s.sql(q).collect()]
    oracle = [tuple(r)
              for r in serve_root.newSession().sql(q).collect()]
    assert a2 == oracle == a1
    s.sql("SET spark.tpu.shuffle.wire.runCodes=true")
    s.sql("DROP TABLE pcrun_t")


def test_run_planes_conf_invalidates_plan_cache(serve_root):
    """``spark.tpu.stage.runPlanes`` is a planning conf: it decides the
    stage-boundary leaf form (compressed plane vs dense materialization)
    and with it the traced stage shapes, so SET must evict entries built
    under the old value — and the re-planned run must stay oracle-equal."""
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    s.sql("CREATE TABLE pcplane_t AS "
          "SELECT id % 8 AS k, id AS v FROM range(128)")
    q = ("SELECT count(*) AS c, sum(v) AS sv FROM pcplane_t "
         "WHERE k < 5")
    a1 = [tuple(r) for r in s.sql(q).collect()]
    assert [tuple(r) for r in s.sql(q).collect()] == a1
    assert cache.stats()["hits"] >= 1
    before = cache.stats()["invalidations"]
    s.sql("SET spark.tpu.stage.runPlanes=false")
    assert cache.stats()["invalidations"] > before, \
        "runPlanes must be fingerprinted as a planning conf"
    a2 = [tuple(r) for r in s.sql(q).collect()]
    oracle = [tuple(r)
              for r in serve_root.newSession().sql(q).collect()]
    assert a2 == oracle == a1
    s.sql("SET spark.tpu.stage.runPlanes=true")
    s.sql("DROP TABLE pcplane_t")


def test_dataframe_write_invalidates_plan_cache(serve_root, tmp_path):
    """Regression: DataFrame-API writes (``df.write...save``) mutate the
    same paths the SQL commands do, but only the SQL commands called the
    cache's path-invalidation hook — a cached plan reading the written
    path replayed STALE rows after an API overwrite.  The writer now
    routes through ``_invalidate_plan_cache``: the entry is evicted and
    the next run matches a fresh-session oracle."""
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    path = str(tmp_path / "pcw.parquet")
    s.sql("SELECT id AS k, id * 3 AS v FROM range(40)").write.parquet(path)
    q = ("SELECT k % 4 AS g, sum(v) AS sv FROM pcw "
         "GROUP BY k % 4 ORDER BY g")
    s.read.parquet(path).createOrReplaceTempView("pcw")
    a1 = [tuple(r) for r in s.sql(q).collect()]
    assert [tuple(r) for r in s.sql(q).collect()] == a1
    assert cache.stats()["hits"] >= 1 and cache.stats()["entries"] >= 1

    # the DataFrame-API overwrite bypasses every SQL command hook — the
    # writer itself must evict entries whose file leaves read this path
    before = cache.stats()["invalidations"]
    s.sql("SELECT id AS k, id AS v FROM range(60)") \
        .write.mode("overwrite").parquet(path)
    assert cache.stats()["invalidations"] > before, \
        "df.write must evict cached plans scanning the written path"
    a2 = [tuple(r) for r in s.sql(q).collect()]
    f = serve_root.newSession()
    f.read.parquet(path).createOrReplaceTempView("pcw")
    oracle = [tuple(r) for r in f.sql(q).collect()]
    assert a2 == oracle and a2 != a1


def test_response_cache_fields_on_repeat(serve_root):
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        sid = s["sessionId"]
        q = "SELECT sum(id) AS s FROM range(100) WHERE id < 77"
        first = _sql(srv, q, sid)
        again = _sql(srv, q, sid)
        assert first["cacheHit"] is False
        assert again["cacheHit"] is True
        assert again["rows"] == first["rows"] == [[sum(range(77))]]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the plan cache keeps plans; every executable is the stage cache's
# ---------------------------------------------------------------------------

def _rows(df):
    return [tuple(r) for r in df.collect()]


def _numpy_lane(serve_root):
    s = serve_root.newSession()
    s.conf.set(C.CODEGEN_ENABLED.key, "false")
    return s


def test_literal_variants_across_sessions_build_one_stage(serve_root):
    """Two sessions, one ``PlanCache``, one statement shape, two literal
    values: ONE program, built by the stage cache (the plan cache makes no
    ``jax.jit`` of its own), and each value answers as the numpy lane."""
    from spark_tpu import tracing
    from spark_tpu.sql.stagecompile import stage_cache
    cache = PlanCache(serve_root.conf_obj)
    s1, s2 = serve_root.newSession(), serve_root.newSession()
    s1._plan_cache = s2._plan_cache = cache
    q = ("SELECT id % 7 AS g, sum(id * 3) AS s, count(*) AS c FROM range(91) "
         "WHERE id < {} GROUP BY id % 7 ORDER BY g")
    tracing.reset()
    builds = stage_cache().stats()["builds"]
    a1 = _rows(s1.sql(q.format(40)))
    a2 = _rows(s2.sql(q.format(77)))
    assert stage_cache().stats()["builds"] == builds + 1
    assert "jit.fresh" not in tracing.summary()["counts"]
    st = cache.stats()
    assert (st["misses"], st["hits"], st["entries"]) == (1, 1, 1), st
    oracle = _numpy_lane(serve_root)
    assert a1 == _rows(oracle.sql(q.format(40)))
    assert a2 == _rows(oracle.sql(q.format(77))) and a2 != a1


def test_literal_outside_the_stage_slots_is_uncacheable(serve_root,
                                                        monkeypatch):
    """An entry is admitted only if every literal the logical fingerprint
    slotted is a runtime parameter of its plan's stage program.  A planner
    that COPIES a filter's condition leaves the fingerprint's literal out of
    the physical plan: cached, the first value would be a constant of the
    trace and answer for the second."""
    import copy

    from spark_tpu.sql.logical import Filter
    from spark_tpu.sql.planner import Planner
    real = Planner._to_physical

    def copying(self, node, leaves):
        if isinstance(node, Filter):
            node = Filter(copy.deepcopy(node.condition), node.child)
        return real(self, node, leaves)

    monkeypatch.setattr(Planner, "_to_physical", copying)
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    q = "SELECT id FROM range(50) WHERE id * 2 < {} ORDER BY id"
    assert _rows(s.sql(q.format(20))) == [(i,) for i in range(10)]
    assert _rows(s.sql(q.format(64))) == [(i,) for i in range(32)]
    st = cache.stats()
    assert st["uncacheable"] == 2 and st["entries"] == 0, st


def test_streamed_join_statement_is_cached_at_its_root_only(serve_root):
    """A statement the stage runner streams enters the plan cache ONCE, at
    its root: the sub-plans it materializes (a new batch every statement,
    so an entry that could never be hit again) go straight to the stage
    cache, where the repeat finds every program."""
    from spark_tpu import tracing
    from spark_tpu.sql.planner import QueryExecution
    from spark_tpu.sql.stages import plan_stages
    serve_root.conf.set(C.SCAN_MAX_BATCH_ROWS.key, "256")
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    s.sql("CREATE TABLE stfact AS SELECT id AS sk, id % 40 AS k, "
          "id % 9 + 1 AS v FROM range(1100)")
    s.sql("CREATE TABLE stdim AS SELECT id AS k, id % 5 AS g, id * 2 AS w "
          "FROM range(40)")
    q = ("SELECT g, sum(v) AS sv FROM stfact f JOIN stdim d "
         "ON f.k = d.k WHERE w + 1 > 20 GROUP BY g ORDER BY g")
    assert plan_stages(s, QueryExecution(s, s.sql(q)._plan).optimized) \
        is not None
    first = _rows(s.sql(q))
    assert s._last_plan_cache_info["hit"] is False
    entries = len(cache)
    for _ in range(2):
        tracing.reset()
        assert _rows(s.sql(q)) == first
        assert s._last_plan_cache_info["hit"] is True
        assert len(cache) == entries
        spans = tracing.spans()
        lookups = [sp.attrs["hit"] for sp in spans
                   if sp.name == "stage.lookup"]
        assert lookups and all(lookups), lookups
        assert not any(sp.name in ("jit.fresh", "stage.build")
                       for sp in spans)
        assert [sp.attrs["hit"] for sp in spans
                if sp.name == "plancache.lookup"] == [True]
    assert first == _rows(_numpy_lane(serve_root).sql(q))
    s.sql("DROP TABLE stfact")
    s.sql("DROP TABLE stdim")


def test_overflowing_cached_run_is_poisoned_and_replans_once(serve_root):
    """A plan-cache run that overflows its planned capacities poisons the
    fingerprint and IS the adaptive loop's first attempt: one re-plan, two
    executions, never a third that repeats the planned capacities."""
    import numpy as np

    from spark_tpu import tracing
    cache = PlanCache(serve_root.conf_obj)
    s = serve_root.newSession()
    s._plan_cache = cache
    o = np.arange(400, dtype=np.int64) % 20
    s.createDataFrame({"o": o, "q": np.arange(400, dtype=np.int64)}) \
        .createOrReplaceTempView("pclines")
    q = ("SELECT count(*) AS n FROM pclines a, pclines b "
         "WHERE a.o = b.o AND b.q < 1000")
    tracing.reset()
    assert _rows(s.sql(q)) == [(400 * 20,)]
    spans = tracing.spans()
    assert [sp.attrs["attempt"] for sp in spans
            if sp.name == "join.replan"] == [1]
    assert sum(sp.name == "d2h" for sp in spans) == 2
    # both attempts are the stage cache's programs
    assert sum(sp.name == "stage.build" for sp in spans) == 2
    assert not any(sp.name == "jit.fresh" for sp in spans)
    st = cache.stats()
    assert (st["misses"], st["hits"], st["entries"]) == (1, 0, 0), st
    assert len(cache._poisoned) == 1
    tracing.reset()                      # the repeat: kept capacities, once
    assert _rows(s.sql(q)) == [(400 * 20,)]
    spans = tracing.spans()
    assert not any(sp.name == "join.replan" for sp in spans)
    assert sum(sp.name == "d2h" for sp in spans) == 1
    assert cache.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_rejects_over_global_cap(serve_root):
    serve_root.conf.set(C.SERVER_MAX_CONCURRENT_STATEMENTS.key, "1")
    srv = SQLServer(serve_root, port=0, workers=2).start()
    try:
        _, sa = _req(srv, "/session", "POST")
        _, sb = _req(srv, "/session", "POST")
        ssa = srv._sessions[sa["sessionId"]]
        ssa.lock.acquire()               # wedge A mid-statement
        try:
            done = {}

            def post_a():
                done["a"] = _sql(srv, "SELECT 1", sa["sessionId"])

            th = threading.Thread(target=post_a)
            th.start()
            time.sleep(0.5)              # let A's statement be admitted
            with pytest.raises(urllib.error.HTTPError) as ei:
                _sql(srv, "SELECT 2", sb["sessionId"])
            assert ei.value.code == 429
            body = json.loads(ei.value.read())
            assert body["limit"] == "maxConcurrentStatements"
            assert body["cap"] == 1 and body["retryAfterSeconds"] >= 1
            assert int(ei.value.headers["Retry-After"]) >= 1
        finally:
            ssa.lock.release()
        th.join(60)
        assert done["a"]["rows"] == [[1]]    # the admitted one completed
        _, st = _req(srv, "/status")
        assert st["admission"]["rejected"] >= 1
        assert st["admission"]["rejectedBy"]["maxConcurrentStatements"] >= 1
        # capacity freed: the next statement is admitted again
        assert _sql(srv, "SELECT 3", sb["sessionId"])["rows"] == [[3]]
    finally:
        srv.stop()


def test_admission_rejects_deep_session_queue(serve_root):
    serve_root.conf.set(C.SERVER_MAX_QUEUED_PER_SESSION.key, "2")
    srv = SQLServer(serve_root, port=0, workers=2).start()
    try:
        _, sa = _req(srv, "/session", "POST")
        sid = sa["sessionId"]
        ssa = srv._sessions[sid]
        ssa.lock.acquire()
        try:
            codes = []

            def post():
                try:
                    _sql(srv, "SELECT 1", sid)
                    codes.append(200)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)

            backlog = [threading.Thread(target=post) for _ in range(2)]
            for t in backlog:
                t.start()
                time.sleep(0.25)         # deterministic queue depths
            with pytest.raises(urllib.error.HTTPError) as ei:
                _sql(srv, "SELECT 9", sid)
            assert ei.value.code == 429
            assert json.loads(ei.value.read())["limit"] == \
                "maxQueuedPerSession"
        finally:
            ssa.lock.release()
        for t in backlog:
            t.join(60)
        assert codes == [200, 200]       # admitted statements all ran
    finally:
        srv.stop()


def test_admission_host_headroom_unit(serve_root):
    class Ledger:
        free = 10

    serve_root.conf.set(C.SERVER_MIN_HOST_HEADROOM.key, "100")
    ac = AdmissionController(serve_root.conf_obj, lambda: Ledger())
    with pytest.raises(AdmissionRejected) as ei:
        ac.admit(0)
    assert ei.value.limit == "hostMemoryHeadroom"
    assert ei.value.observed == 10 and ei.value.cap == 100
    Ledger.free = 1000
    ac.admit(0)                          # headroom restored → admitted
    ac.release(0.01)
    st = ac.stats()
    assert st["admitted"] == 1 and st["rejected"] == 1
    assert st["active"] == 0


# ---------------------------------------------------------------------------
# statement lifecycle: queued cancel, deadlines, idle sessions
# ---------------------------------------------------------------------------

def test_cancel_removes_queued_statement(serve_root):
    srv = SQLServer(serve_root, port=0, workers=2).start()
    try:
        _, sa = _req(srv, "/session", "POST")
        sid = sa["sessionId"]
        ssa = srv._sessions[sid]
        ssa.lock.acquire()               # first statement blocks running
        try:
            codes = {}

            def run(name, stmt_id):
                try:
                    _sql(srv, "SELECT 1", sid, stmt_id)
                    codes[name] = 200
                except urllib.error.HTTPError as e:
                    codes[name] = e.code

            t1 = threading.Thread(target=run, args=("head", "stmt-head"))
            t1.start()
            time.sleep(0.3)
            t2 = threading.Thread(target=run, args=("tail", "stmt-tail"))
            t2.start()
            time.sleep(0.3)              # tail is parked in the FIFO
            _, c = _req(srv, "/cancel", "POST",
                        json.dumps({"id": "stmt-tail"}))
            # a queued statement cancels SYNCHRONOUSLY: status flips
            # in the cancel response, no worker slot is ever spent
            assert c["status"] == "cancelled"
            t2.join(10)
            assert codes["tail"] == 499
            with srv._reg_lock:
                assert all(item[0].id != "stmt-tail"
                           for item in ssa.queue)
        finally:
            ssa.lock.release()
        t1.join(60)
        assert codes["head"] == 200      # the head was untouched
        _, st = _req(srv, "/statement/stmt-tail")
        assert st["status"] == "cancelled"
    finally:
        srv.stop()


def test_statement_deadline_cancels_long_run(serve_root, tmp_path):
    import numpy as np
    import pandas as pd

    p = str(tmp_path / "slow.parquet")
    pd.DataFrame({"x": np.arange(1_500_000, dtype=np.int64)}).to_parquet(
        p, index=False)
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        sid = s["sessionId"]
        _sql(srv, "SET spark.tpu.scan.maxBatchRows=1024", sid)
        _sql(srv, f"CREATE TEMP VIEW slow AS SELECT * FROM parquet.`{p}`",
             sid)
        _sql(srv, "SET spark.tpu.server.statementTimeout=0.3", sid)
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _sql(srv, "SELECT sum(x) FROM slow", sid, "stmt-deadline")
        assert ei.value.code == 499
        assert time.monotonic() - t0 < 45
        _, st = _req(srv, "/statement/stmt-deadline")
        assert st["status"] == "cancelled"
        # the deadline is per-statement: the session still works
        _sql(srv, "SET spark.tpu.server.statementTimeout=0", sid)
        assert _sql(srv, "SELECT 5", sid)["rows"] == [[5]]
    finally:
        srv.stop()


def test_idle_session_ttl_eviction(serve_root):
    serve_root.conf.set(C.SERVER_SESSION_TIMEOUT.key, "10")
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s1 = _req(srv, "/session", "POST")
        _, s2 = _req(srv, "/session", "POST")
        sid1, sid2 = s1["sessionId"], s2["sessionId"]
        _sql(srv, "SELECT 1", sid1)
        # wedge s2 with queued work: busy sessions are never reaped
        ss2 = srv._sessions[sid2]
        ss2.lock.acquire()
        try:
            th = threading.Thread(
                target=lambda: _sql(srv, "SELECT 1", sid2))
            th.start()
            time.sleep(0.3)
            n = srv._expire_idle_sessions(now=time.time() + 60)
            assert n == 1                # only the idle one went
            assert sid2 in srv._sessions
            assert sid1 not in srv._sessions
        finally:
            ss2.lock.release()
        th.join(60)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _sql(srv, "SELECT 1", sid1)
        assert ei.value.code == 404
        _, st = _req(srv, "/status")
        assert st["sessionsExpired"] == 1
        assert st["metrics"]["serving"]["sessions_expired"] == 1
    finally:
        srv.stop()


def _start_json_stream(srv, sid, tmp_path, tag="s"):
    """POST /stream over a one-file json source; returns (streamId, dirs)."""
    import numpy as np
    data = tmp_path / f"{tag}-in"
    data.mkdir(exist_ok=True)
    srv.session.createDataFrame(
        {"x": np.arange(4, dtype=np.int64)}).write.json(
            str(data / "f1"))
    spec = {"session": sid,
            "source": {"format": "json", "path": str(data),
                       "schema": "x bigint"},
            "sink": {"format": "json", "path": str(tmp_path / f"{tag}-out")},
            "checkpoint": str(tmp_path / f"{tag}-ckpt"),
            "interval": 0.1}
    _, r = _req(srv, "/stream", "POST", json.dumps(spec))
    return r["streamId"]


def _wait_stream_commit(srv, stream_id, n=1, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _, st = _req(srv, f"/stream/{stream_id}")
        if st["metrics"]["batches_committed"] >= n:
            return st
        time.sleep(0.05)
    raise AssertionError(f"stream {stream_id} never committed {n} batches")


def test_stream_endpoint_register_status_stop(serve_root, tmp_path):
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        sid = s["sessionId"]
        stream_id = _start_json_stream(srv, sid, tmp_path)
        st = _wait_stream_commit(srv, stream_id)
        assert st["active"] and st["batchId"] >= 1
        assert st["metrics"]["replayed_batches"] == 0
        assert st["lastProgress"]["stageRebuilds"] is not None
        # visible as a serving-tier tenant end to end
        _, status = _req(srv, "/status")
        assert status["standingQueries"][stream_id]["session"] == sid
        assert status["admission"]["standingQueries"] == 1
        assert status["metrics"]["streaming"]["standing_queries"] == 1
        assert status["metrics"]["streaming"]["batches_committed"] >= 1
        # sink really received the batch
        out = tmp_path / "s-out"
        assert any(out.glob("part-*"))
        _, r = _req(srv, f"/stream/{stream_id}", "DELETE")
        assert r["stopped"] == stream_id
        _, status = _req(srv, "/status")
        assert status["admission"]["standingQueries"] == 0
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(srv, f"/stream/{stream_id}")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_session_with_standing_query_never_idle_reaped(serve_root,
                                                       tmp_path):
    """Regression: the idle-TTL reaper must skip a session carrying a
    live standing query, however stale its last statement — reaping it
    would orphan the query's admission slot and kill the stream."""
    serve_root.conf.set(C.SERVER_SESSION_TIMEOUT.key, "10")
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s1 = _req(srv, "/session", "POST")
        _, s2 = _req(srv, "/session", "POST")
        sid1, sid2 = s1["sessionId"], s2["sessionId"]
        stream_id = _start_json_stream(srv, sid1, tmp_path)
        _wait_stream_commit(srv, stream_id)
        n = srv._expire_idle_sessions(now=time.time() + 60)
        assert n == 1                       # only the streamless session
        assert sid1 in srv._sessions and sid2 not in srv._sessions
        _, st = _req(srv, f"/stream/{stream_id}")
        assert st["active"]
        # once the query stops, the session is ordinary idle prey again
        _req(srv, f"/stream/{stream_id}", "DELETE")
        assert srv._expire_idle_sessions(now=time.time() + 60) == 1
        assert sid1 not in srv._sessions
    finally:
        srv.stop()


def test_standing_query_cap_rejects_429_with_retry_after(serve_root,
                                                         tmp_path):
    serve_root.conf.set(C.SERVER_MAX_STANDING_QUERIES.key, "1")
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        sid = s["sessionId"]
        stream_id = _start_json_stream(srv, sid, tmp_path, tag="a")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _start_json_stream(srv, sid, tmp_path, tag="b")
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert "standing" in json.dumps(body).lower()
        # the slot frees on DELETE and the next registration succeeds
        _req(srv, f"/stream/{stream_id}", "DELETE")
        _start_json_stream(srv, sid, tmp_path, tag="c")
    finally:
        srv.stop()


def test_status_exposes_serving_state(serve_root):
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        sid = s["sessionId"]
        _sql(srv, "SELECT 1", sid)
        _, st = _req(srv, "/status")
        assert st["sessionQueues"][sid] == {"queued": 0, "running": False}
        adm = st["admission"]
        assert adm["admitted"] >= 1 and adm["active"] == 0
        assert "rejectedBy" in adm and "avgStatementMs" in adm
        pc = st["planCache"]
        for k in ("hits", "misses", "evictions", "invalidations",
                  "entries", "bytes"):
            assert k in pc
        serving = st["metrics"]["serving"]
        for k in ("plan_cache_hits", "plan_cache_misses",
                  "plan_cache_bytes", "admission_admitted",
                  "admission_rejected", "sessions_open"):
            assert k in serving
    finally:
        srv.stop()


def test_plan_cache_disabled_by_conf(serve_root):
    serve_root.conf.set(C.SERVER_PLAN_CACHE_ENABLED.key, "false")
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        q = "SELECT id FROM range(8)"
        r1 = _sql(srv, q, s["sessionId"])
        r2 = _sql(srv, q, s["sessionId"])
        assert r1["cacheHit"] is False and r2["cacheHit"] is False
        _, st = _req(srv, "/status")
        assert "planCache" not in st
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# stress: small pool + tight caps under many clients
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_admission_stress_bounded_and_conserving(serve_root):
    """16 clients hammer 4 sessions through a 2-worker pool with tight
    caps: every response is 200 or a structured 429, every 200 is
    correct, no statement runs twice or vanishes, and stop() returns."""
    serve_root.conf.set(C.SERVER_MAX_CONCURRENT_STATEMENTS.key, "4")
    serve_root.conf.set(C.SERVER_MAX_QUEUED_PER_SESSION.key, "2")
    srv = SQLServer(serve_root, port=0, workers=2).start()
    try:
        sids = [_req(srv, "/session", "POST")[1]["sessionId"]
                for _ in range(4)]
        lock = threading.Lock()
        outcomes = []                    # (stmt_id, code, value)

        def client(cid):
            for k in range(6):
                stmt_id = f"stress-{cid}-{k}"
                try:
                    r = _sql(srv,
                             f"SELECT sum(id) + {cid} AS s "
                             f"FROM range(2000)",
                             sids[cid % 4], stmt_id)
                    with lock:
                        outcomes.append((stmt_id, 200, r["rows"][0][0]))
                except urllib.error.HTTPError as e:
                    body = json.loads(e.read())
                    with lock:
                        outcomes.append((stmt_id, e.code,
                                         body.get("limit")))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads), "client hung"
        assert len(outcomes) == 16 * 6
        codes = {code for _sid, code, _v in outcomes}
        assert codes <= {200, 429}, codes
        assert 200 in codes
        ok = [(sid, v) for sid, code, v in outcomes if code == 200]
        expect = sum(range(2000))
        for stmt_id, v in ok:
            cid = int(stmt_id.split("-")[1])
            assert v == expect + cid, (stmt_id, v)
        rejected = [(sid, v) for sid, code, v in outcomes if code == 429]
        for _sid, limit in rejected:
            assert limit in ("maxConcurrentStatements",
                             "maxQueuedPerSession"), limit
        # conservation: exactly the admitted statements are registered,
        # each terminal exactly once; rejected ones left no trace
        ok_ids = {sid for sid, _v in ok}
        reg = {s.id: s.status for s in srv._statements.values()
               if s.id.startswith("stress-")}
        assert set(reg) == ok_ids
        assert all(st == "done" for st in reg.values())
        _, st = _req(srv, "/status")
        assert st["admission"]["rejected"] == len(rejected)
        assert st["admission"]["active"] == 0
    finally:
        t0 = time.monotonic()
        srv.stop()
        assert time.monotonic() - t0 < 10, "stop() must not hang"


# ---------------------------------------------------------------------------
# stage-entry caching: distributed/multibatch statements no longer bail
# ---------------------------------------------------------------------------

def test_multibatch_statement_stage_cached_cross_session(serve_root):
    """The lifted bailout: a MULTIBATCH statement (streamed scan wider
    than one device batch) from a SECOND session reports a cache hit —
    the statement-level stage entry is shared via the plan cache while
    the compiled stage executables come from the process stage cache."""
    from spark_tpu.sql.stagecompile import stage_cache
    serve_root.conf.set(C.SCAN_MAX_BATCH_ROWS.key, "256")
    cache = PlanCache(serve_root.conf_obj)
    s1 = serve_root.newSession()
    s2 = serve_root.newSession()
    s1._plan_cache = cache
    s2._plan_cache = cache
    s1.sql("CREATE TABLE mbst AS SELECT id AS k, id % 7 AS g, "
           "id * 3 AS v FROM range(2000)")
    q = "SELECT g, sum(v) AS sv FROM mbst GROUP BY g ORDER BY g"
    # prove the statement actually routes through the multibatch lane
    from spark_tpu.sql.multibatch import plan_multibatch
    from spark_tpu.sql.planner import QueryExecution
    qe = QueryExecution(s1, s1.sql(q)._plan)
    assert plan_multibatch(s1, qe.optimized) is not None

    a1 = [tuple(r) for r in s1.sql(q).collect()]
    assert s1._last_plan_cache_info["hit"] is False
    assert cache.stats()["stage_misses"] >= 1
    sc0 = stage_cache().stats()
    a2 = [tuple(r) for r in s2.sql(q).collect()]
    sc1 = stage_cache().stats()
    assert a2 == a1
    assert s2._last_plan_cache_info["hit"] is True, \
        "second session's multibatch statement must report cacheHit"
    assert cache.stats()["stage_hits"] >= 1
    assert sc1["hits"] > sc0["hits"], \
        "the warm statement must reuse compiled stage executables"
    assert sc1["builds"] == sc0["builds"], \
        "the warm statement must not compile new stages"

    # DML invalidation: INSERT evicts the stage entry; the next run is
    # a miss and matches a fresh-session oracle
    inv0 = cache.stats()["invalidations"]
    s2.sql("INSERT INTO mbst SELECT id AS k, id % 7 AS g, "
           "id AS v FROM range(10)")
    assert cache.stats()["invalidations"] > inv0
    a3 = [tuple(r) for r in s1.sql(q).collect()]
    assert s1._last_plan_cache_info["hit"] is False
    oracle_s = serve_root.newSession()
    oracle = [tuple(r) for r in oracle_s.sql(q).collect()]
    assert a3 == oracle and a3 != a1

    # SET of a planning conf evicts stage entries built under the old
    # value (same hygiene rule as whole-plan entries)
    assert cache.stats()["stage_entries"] >= 1
    inv1 = cache.stats()["invalidations"]
    s1.sql("SET spark.tpu.crossproc.autoBroadcastThreshold=54321")
    assert cache.stats()["invalidations"] > inv1
    s1.sql("DROP TABLE mbst")


def test_status_reports_stage_cache_occupancy(serve_root):
    srv = SQLServer(serve_root, port=0).start()
    try:
        _, s = _req(srv, "/session", "POST")
        _sql(srv, "SELECT sum(id) AS s FROM range(128)", s["sessionId"])
        _, st = _req(srv, "/status")
        assert "stageCache" in st
        for key in ("entries", "hits", "misses", "compile_ms",
                    "stages_fused", "ops_per_stage"):
            assert key in st["stageCache"], key
        assert st["stageCache"]["entries"] >= 1
        # plan-cache stats now carry the stage-entry occupancy too
        assert "stage_entries" in st["planCache"]
        assert st["metrics"]["serving"]["plan_cache_stage_hits"] >= 0
        assert st["metrics"]["compile"]["stage_dispatches"] >= 1
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serving-tier StatsFeedback persistence
# ---------------------------------------------------------------------------

def test_stats_feedback_shared_across_server_sessions(serve_root):
    """Observed exchange cardinalities persist across statements AND
    sessions in the serving tier: the server presets ONE StatsFeedback
    on every session it opens (crossproc's _session_feedback finds it
    instead of creating a per-session empty one)."""
    from spark_tpu.parallel.crossproc import _session_feedback
    srv = SQLServer(serve_root, port=0)
    sid1 = srv._open_session()
    sid2 = srv._open_session()
    s1 = srv._sessions[sid1].session
    s2 = srv._sessions[sid2].session
    assert _session_feedback(s1) is srv._stats_feedback
    assert _session_feedback(s2) is srv._stats_feedback
    assert _session_feedback(serve_root) is srv._stats_feedback
    # recorded in one session, visible in the other
    _session_feedback(s1).record("sigX", 4096, 17, "xq000001")
    assert _session_feedback(s2).peek("sigX") == (4096, 17)


def test_repeated_misestimated_join_broadcasts_on_second_run(serve_root):
    """Regression for the serving-tier feedback loop: the probe
    misestimates both join sides as huge (-> hash/range), the first
    run's adaptive replanner records the right side's true tiny
    cardinality, and the SAME join planned again — from a DIFFERENT
    server session — chooses broadcast_right at plan time."""
    from spark_tpu.parallel.crossproc import (StatsFeedback,
                                              _session_feedback,
                                              choose_join_strategy)
    srv = SQLServer(serve_root, port=0)
    s1 = srv._sessions[srv._open_session()].session
    s2 = srv._sessions[srv._open_session()].session
    sig = StatsFeedback.signature  # structural: same plan -> same key

    import spark_tpu.sql.logical as L
    import spark_tpu.types as T
    from spark_tpu.columnar import ColumnBatch
    import numpy as np
    dim = L.LocalRelation(ColumnBatch.from_arrays(
        {"d": np.arange(8, dtype=np.int64)},
        schema=T.StructType([T.StructField("d", T.int64)])))
    r_sig = sig(dim)

    def plan(session):
        return choose_join_strategy(
            "inner", True, True, True,
            broadcast_threshold=1 << 20, n_procs=2,
            left_bytes=1 << 30, right_bytes=1 << 30,   # the misestimate
            feedback=_session_feedback(session), right_sig=r_sig)

    # first run: no feedback yet -> the probe's estimate stands
    assert plan(s1) != "broadcast_right"
    # the adaptive runtime records the observed tiny right side
    _session_feedback(s1).record(r_sig, 2048, 8, "xq000002")
    # second run, other session: plan-time broadcast, no fragmentation —
    # feedback changes the strategy input, never the plan fingerprint
    assert plan(s2) == "broadcast_right"
