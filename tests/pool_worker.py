"""Worker for the elastic-pool chaos scenarios (not a test module —
launched as a subprocess by test_pool.py and ``bin/chaos --pool``).

argv: <process_id> <n_processes> <shared_root> <mode> [timeout_s]

mode "reap" — scale-down safety mid-fetch (2 processes, the
    ``bs-zero`` join with the retry budget at ZERO):
    pid 1 runs the exchange with a ``drop`` fault on its shipped jR
    block, and the moment its LAST manifest (the ``-gather`` round)
    lands it is cooperatively REAPED: it stops beating (the beat file
    stays behind and goes stale — a reaped worker looks exactly like a
    dead one to the survivor's barrier), hands its block-service lease
    to the pool supervisor (``handoff_lease``) and releases its own,
    then exits 0 printing ``[p1] OK``.  No drain barrier, no goodbye
    round.
    pid 0 must land the EXACT oracle purely by adopting the reaped
    peer's registered blocks: asserts ``stage_retries == 0``,
    ``epoch == 0`` (zero re-executed map tasks — any recovery attempt
    would blow the zero budget), nonzero adoption counters, AND that
    the reaped worker's lease still answers fresh through the heir
    chain — the scale-down-safety invariant (INVARIANTS.md): sealed
    output must stay adoptable before the lease may expire.

mode "spawn-fail" — exec failure converges the pool BELOW target,
    structured, never a hang (1 process): a real
    ``WorkerPoolSupervisor`` with ``FaultInjector().attach_pool`` armed
    from SPARK_TPU_FAULT_PLAN (``spawn_exec_error(after_spawns=1)``).
    Demand wants 2 workers; the second exec raises; the pool settles at
    1 live worker, counts ``spawn_failures`` on every retry tick, and
    the one real worker still serves a spooled statement
    oracle-exactly.  Scale-down then reaps it through hysteresis.

mode "scaleup" — scale-up mid-standing-query is invisible to the
    stream (1 process): a windowed-aggregate standing query processes
    two micro-batches, the pool then spawns a REAL worker (which
    serves a statement to prove it is live), the stream processes two
    more batches over the widened world, and the sink must be
    BYTE-identical to an uninterrupted no-pool oracle lifetime.

Any partial result prints ``[p<pid>] PARTIAL`` and exits 1 — the
launcher greps for it; it must never appear.
"""

import glob
import os
import sys
import time

pid = int(sys.argv[1])
n = int(sys.argv[2])
root = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "reap"
timeout_s = float(sys.argv[5]) if len(sys.argv) > 5 else 20.0

os.environ["JAX_PLATFORMS"] = "cpu"
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from spark_tpu import config as C  # noqa: E402
from spark_tpu.parallel.cluster import HeartbeatMonitor  # noqa: E402
from spark_tpu.parallel.faults import FaultInjector  # noqa: E402
from spark_tpu.serving.admission import DemandSignal  # noqa: E402
from spark_tpu.serving.pool import (  # noqa: E402
    SUPERVISOR_OWNER, WorkerPoolSupervisor)
from spark_tpu.sql.session import SparkSession  # noqa: E402


# ---------------------------------------------------------------------------
# mode "reap": the bs-zero join with a cooperative scale-down victim
# ---------------------------------------------------------------------------

def run_reap():
    from spark_tpu.parallel.hostshuffle import ExchangeFetchFailed

    rng = np.random.default_rng(7)
    N, M = 900, 600
    f_sk = rng.integers(0, 40, N).astype(np.int64)
    f_price = rng.integers(1, 200, N).astype(np.int64)
    k2 = (rng.integers(0, 20, M) * 2).astype(np.int64)
    b2 = rng.integers(1, 100, M).astype(np.int64)
    mine = slice(pid, None, n)

    session = SparkSession.builder.appName(f"pool-{pid}").getOrCreate()

    wr = session.newSession()
    wr.conf.set(C.MESH_SHARDS.key, "1")
    fact_dir = os.path.join(root, "leaves", f"fact-p{pid}")
    fact2_dir = os.path.join(root, "leaves", f"fact2-p{pid}")
    wr.createDataFrame({"sk": f_sk[mine], "price": f_price[mine]}) \
        .write.parquet(fact_dir)
    wr.createDataFrame({"k2": k2[mine], "bonus": b2[mine]}) \
        .write.parquet(fact2_dir)

    xs = session.newSession()
    xs.conf.set(C.MESH_SHARDS.key, "1")
    xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "2048")
    xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
    xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
    xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
    xs.conf.set("spark.tpu.cluster.heartbeatIntervalMs", "100")
    xs.conf.set("spark.tpu.cluster.heartbeatTimeoutMs", "600")
    xs.conf.set(C.BLOCKSERVER_ENABLED.key, "true")
    # the zero-re-execution proof: ANY recovery attempt would blow the
    # zero budget and fail the query, so an oracle-exact OK can only
    # come from adopting the reaped peer's registered output
    xs.conf.set(C.RECOVERY_MAX_STAGE_RETRIES.key, "0")
    hb = HeartbeatMonitor(os.path.join(root, "beats"),
                          host_id=f"host-{pid}", conf=xs.conf_obj)
    hb.start()
    svc = xs.enableHostShuffle(root, process_id=pid, n_processes=n,
                               timeout_s=timeout_s, heartbeat=hb)
    FaultInjector().attach(svc)      # drop rule from SPARK_TPU_FAULT_PLAN

    if pid == 1:
        # arm the cooperative reap: the moment the LAST manifest (the
        # -gather round) lands, this worker is scaled down — it stops
        # beating (the stale beat, not a goodbye, is what the survivor
        # sees), hands its lease to the pool supervisor so its sealed
        # registered output stays adoptable, and leaves.  Wrapping BOTH
        # commit and publish_manifest covers whichever path publishes
        # the trigger round; the injector's wrappers stay underneath.
        store = svc.blockclient.store
        orig_commit = svc.commit
        orig_publish = svc.publish_manifest

        def _maybe_reap(exchange):
            if not exchange.endswith("-gather"):
                return
            hb.stop()                     # beat file STAYS — goes stale
            store.handoff_lease(f"host-{pid}", SUPERVISOR_OWNER)
            store.release_lease(f"host-{pid}")
            print(f"[p{pid}] OK reaped at {exchange} "
                  f"lease->{SUPERVISOR_OWNER}", flush=True)
            os._exit(0)

        def commit(exchange, extra=None):
            orig_commit(exchange, extra=extra)
            _maybe_reap(exchange)

        def publish_manifest(exchange, payload=None):
            out = orig_publish(exchange, payload)
            _maybe_reap(exchange)
            return out

        svc.commit = commit
        svc.publish_manifest = publish_manifest

    xs.read.parquet(fact_dir).createOrReplaceTempView("fact")
    xs.read.parquet(fact2_dir).createOrReplaceTempView("fact2")

    oracle = session.newSession()
    oracle.conf.set(C.MESH_SHARDS.key, "1")
    oracle.createDataFrame({"sk": f_sk, "price": f_price}) \
        .createOrReplaceTempView("fact")
    oracle.createDataFrame({"k2": k2, "bonus": b2}) \
        .createOrReplaceTempView("fact2")

    SQL = ("SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
           "JOIN fact2 ON sk = k2 GROUP BY sk ORDER BY sk")
    exp = [tuple(r) for r in oracle.sql(SQL).collect()]

    t0 = time.time()
    try:
        got = [tuple(r) for r in xs.sql(SQL).collect()]
    except (ExchangeFetchFailed, TimeoutError) as e:
        lost = sorted(getattr(e, "lost_hosts", []) or [])
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} {lost}",
              flush=True)
        os._exit(1)

    if got != exp:
        print(f"[p{pid}] PARTIAL got={len(got)} exp={len(exp)}",
              flush=True)
        os._exit(1)
    gauges = svc.metrics_source().snapshot()
    # zero re-executed map tasks: the recovery machinery never armed —
    # the reaped worker's output came out of block-service custody
    assert svc.counters["stage_retries"] == 0, svc.counters
    assert gauges["epoch"] == 0, gauges
    assert svc.counters["blocks_adopted"] >= 1, svc.counters
    assert svc.counters["blockserver_fallback_reads"] >= 1, svc.counters
    # scale-down safety: the reaped worker's lease must STILL answer
    # fresh — its own lease file is gone, but the heir sidecar chains
    # to the supervisor lease the handoff touched
    store = svc.blockclient.store
    assert store.lease_fresh("host-1", time.time()), \
        "reaped worker's lease went cold before adoption was safe"
    print(f"[p{pid}] OK {len(got)} retries=0 "
          f"adopted={svc.counters['blocks_adopted']}b "
          f"fallback={svc.counters['blockserver_fallback_reads']} "
          f"heir-lease=fresh", flush=True)
    os._exit(0)


# ---------------------------------------------------------------------------
# shared pool scaffolding for the supervisor modes
# ---------------------------------------------------------------------------

def _pool_session():
    """A session whose warehouse lives under the shared root, with one
    persistent table pool workers reach through the filesystem
    catalog."""
    wh = os.path.join(root, "warehouse")
    session = SparkSession.builder.appName(f"pool-{pid}") \
        .config("spark.sql.warehouse.dir", wh).getOrCreate()
    session.conf.set("spark.sql.warehouse.dir", wh)
    df = session.createDataFrame(
        [(1, "a", 10), (2, "b", 20), (3, "c", 30)], ["id", "name", "v"])
    df.write.saveAsTable("pool_t")
    return session, wh


ORACLE_SQL = "SELECT id, name, v FROM pool_t ORDER BY id"
ORACLE_ROWS = [[1, "a", 10], [2, "b", 20], [3, "c", 30]]


def _make_supervisor(session, wh, demand_box):
    conf = session.conf_obj
    conf.set(C.SERVER_POOL_MAX_WORKERS.key, "4")
    conf.set(C.SERVER_POOL_STATEMENTS_PER_WORKER.key, "2")
    conf.set(C.SERVER_POOL_SCALE_DOWN_ROUNDS.key, "2")
    conf.set(C.SERVER_POOL_COOLDOWN.key, "0.0")
    conf.set(C.SERVER_POOL_POLL.key, "0.1")
    sup = WorkerPoolSupervisor(
        os.path.join(root, "_pool"), conf, lambda: demand_box[0],
        warehouse=wh)
    sup.start(reconcile=False)        # chaos drives tick() itself
    return sup


def _serve_one(sup, deadline):
    """One statement through the spool against the live worker; retried
    because a just-spawned worker needs import+session time."""
    while time.monotonic() < deadline:
        res = sup.execute(ORACLE_SQL, timeout_s=15.0)
        if res is not None:
            assert res["rows"] == ORACLE_ROWS, res
            assert res.get("pooled") is True, res
            return res
        time.sleep(0.2)
    print(f"[p{pid}] FAILED pool never served a statement", flush=True)
    os._exit(1)


# ---------------------------------------------------------------------------
# mode "spawn-fail": exec error converges BELOW target, structured
# ---------------------------------------------------------------------------

def run_spawn_fail():
    deadline = time.monotonic() + 3 * timeout_s
    session, wh = _pool_session()
    demand = [DemandSignal(queued=4)]        # wants ceil(4/2) = 2 workers
    sup = _make_supervisor(session, wh, demand)
    FaultInjector().attach_pool(sup)  # plan from SPARK_TPU_FAULT_PLAN

    d = sup.tick()
    assert d.action == "up" and d.target == 2, d
    assert sup.counters["spawn_failures"] >= 1, sup.counters
    assert sup.live == 1 < d.target, (sup.live, d)
    # the pool keeps converging BELOW target on every retry tick —
    # counted, structured, never a hang
    sup.tick()
    assert sup.counters["spawn_failures"] >= 2, sup.counters
    assert sup.live == 1, sup.live

    _serve_one(sup, deadline)         # the one real worker still serves

    demand[0] = DemandSignal()        # idle: hysteresis then reap
    while sup.live > 0:
        if time.monotonic() > deadline:
            print(f"[p{pid}] FAILED reap never converged", flush=True)
            os._exit(1)
        sup.tick()
        time.sleep(0.05)
    assert sup.counters["workers_reaped"] >= 1, sup.counters
    c = dict(sup.counters)
    sup.stop()
    print(f"[p{pid}] OK spawn_failures={c['spawn_failures']} "
          f"spawned={c['workers_spawned']} reaped={c['workers_reaped']} "
          f"served={c['pool_statements_served']}", flush=True)
    os._exit(0)


# ---------------------------------------------------------------------------
# mode "scaleup": pool growth mid-standing-query is invisible downstream
# ---------------------------------------------------------------------------

def run_scaleup():
    from spark_tpu import types as T
    from spark_tpu.sql import functions as F
    from spark_tpu.sql.dataframe import DataFrame
    from spark_tpu.streaming.core import (
        FileSink, FileStreamSource, StreamExecution, StreamingRelation)

    deadline = time.monotonic() + 3 * timeout_s

    def sec(x):
        return int(x * 1_000_000)

    SCHEMA = T.StructType([
        T.StructField("ts", T.timestamp),
        T.StructField("k", T.string),
        T.StructField("v", T.int64),
    ])
    FEEDS = [
        [(sec(1), "a", 1), (sec(9), "b", 2)],
        [(sec(20), "a", 4), (sec(21), "b", 1)],
        [(sec(35), "c", 8), (sec(35), "c", 8)],
        [(sec(50), "a", 3), (sec(51), "d", 9)],
    ]
    in_dir = os.path.join(root, "in")
    os.makedirs(in_dir, exist_ok=True)

    session, wh = _pool_session()

    def feed(i):
        rows = FEEDS[i]
        session.createDataFrame({
            "ts": np.array([r[0] for r in rows], "datetime64[us]"),
            "k": [r[1] for r in rows],
            "v": np.array([r[2] for r in rows], np.int64),
        }).write.parquet(os.path.join(in_dir, f"f{i}"))

    def lifetime(ckpt, out):
        src = FileStreamSource("parquet", in_dir, SCHEMA,
                               {"maxfilespertrigger": "1"})
        df = (DataFrame(session, StreamingRelation(src))
              .withWatermark("ts", "5 seconds")
              .groupBy(F.window("ts", "10 seconds").alias("w"))
              .agg(F.sum("v").alias("s")))
        ex = StreamExecution(session, df._plan, FileSink("json", out, {}),
                             "append", ckpt, 0.1, None)
        ex.process_all_available()
        return ex

    def sink_files(out):
        return {os.path.basename(p): open(p, "rb").read()
                for p in sorted(glob.glob(os.path.join(out, "part-*")))}

    ckpt, out = os.path.join(root, "ckpt"), os.path.join(root, "out")

    # two micro-batches with the pool EMPTY
    feed(0)
    feed(1)
    lifetime(ckpt, out)

    # burst: the pool scales up mid-standing-query — a REAL worker
    # spawns and proves itself by serving a statement
    demand = [DemandSignal(queued=2)]
    sup = _make_supervisor(session, wh, demand)
    d = sup.tick()
    assert d.action == "up", d
    assert sup.counters["workers_spawned"] >= 1, sup.counters
    _serve_one(sup, deadline)

    # the NEXT micro-batches plan over the widened world
    feed(2)
    feed(3)
    lifetime(ckpt, out)
    got = sink_files(out)

    # uninterrupted no-pool oracle over the same feeds
    lifetime(os.path.join(root, "oracle_ckpt"),
             os.path.join(root, "oracle_out"))
    exp = sink_files(os.path.join(root, "oracle_out"))
    if got != exp or not exp:
        print(f"[p{pid}] PARTIAL got={sorted(got)} exp={sorted(exp)}",
              flush=True)
        os._exit(1)

    demand[0] = DemandSignal()
    while sup.live > 0 and time.monotonic() < deadline:
        sup.tick()
        time.sleep(0.05)
    c = dict(sup.counters)
    sup.stop()
    print(f"[p{pid}] OK {len(got)} spawned={c['workers_spawned']} "
          f"reaped={c['workers_reaped']} "
          f"served={c['pool_statements_served']}", flush=True)
    os._exit(0)


if mode == "reap":
    run_reap()
elif mode == "spawn-fail":
    run_spawn_fail()
elif mode == "scaleup":
    run_scaleup()
else:
    print(f"[p{pid}] FAILED unknown mode {mode!r}", flush=True)
    os._exit(2)
