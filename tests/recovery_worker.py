"""Worker for the lineage-recovery tests (not a test module itself —
launched as a subprocess by test_recovery.py and bin/chaos).

argv: <process_id> <n_processes> <shuffle_root> <mode> [timeout_s]

Each process WRITES its strided slice of the join tables to parquet
under the shared root and reads it back through ``read.parquet`` — so
every leaf is a partitioned ``FileRelation`` whose re-read recipe the
digest round publishes to peers (the lineage stage recovery re-executes
from).  A FaultInjector armed from SPARK_TPU_FAULT_PLAN kills the
victim process mid-exchange (it exits 43); a per-process
``HeartbeatMonitor`` converts the silence into a blacklist exclusion
and a structured ``ExchangeFetchFailed`` on the survivor.

mode "recover"   — ``maxStageRetries`` left at its default (1): the
    survivor must run the ``{xid}-recover`` agreement round, adopt the
    dead pid's parquet partitions from its published recipes, re-execute
    under epoch 1, and produce the EXACT full-data oracle rows.  Prints
    ``[p<pid>] OK <rows> retries=<n> recovered=<n> epoch=<e>`` after
    asserting ``stage_retries >= 1``, ``recovered_partitions > 0`` and
    a nonzero epoch gauge.
mode "norecover" — ``maxStageRetries=0``: the pre-recovery contract
    byte-for-byte — the survivor fails BOUNDED with the structured
    error naming the lost host: ``[p<pid>] FAILED <elapsed> <lost>``,
    and the recovery counters stay zero.
mode "grace-recover" — the "recover" contract under a host budget
    CAPPED below the reducers' drained working set: the survivor is
    mid-GRACE (sink re-bucketed into spill files) when the victim's
    death surfaces at the -fin merge, so the recovery epoch must replay
    cleanly over partially-spilled grace state — and the replay, now
    holding the whole data on fewer processes, grace-degrades again.
    Additionally asserts nonzero ``grace_buckets_used`` before OK.
mode "bs-*" — the disaggregated-block-service battery: same query with
    ``spark.tpu.blockserver.enabled`` on.
    "bs-zero"    — retry budget forced to ZERO: the survivor must reach
        the exact oracle purely by adopting the dead peer's registered
        blocks (asserts ``stage_retries == 0``, ``epoch == 0`` and
        nonzero adoption counters — zero re-executed map tasks).
    "bs-adopt"   — victim dies post-seal/pre-marker: the sealed
        manifest adopts, the unfinished downstream stages recover
        (asserts ``manifests_adopted >= 1`` AND ``stage_retries >= 1``).
    "bs-recover" — victim dies pre-seal: nothing adoptable, pure r12
        re-execution (asserts ``manifests_adopted == 0``).
    "bs-unavail" — the SURVIVOR's block service is down: adoption
        degrades to a counted event, recovery still lands the oracle
        (asserts ``blockserver_unavailable >= 1``,
        ``blocks_adopted == 0``).

Any partial result prints ``[p<pid>] PARTIAL`` and exits 1 — the
launcher greps for it; it must never appear.
"""

import os
import sys
import time

pid = int(sys.argv[1])
n = int(sys.argv[2])
root = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "recover"
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
from spark_tpu.parallel.hostshuffle import ExchangeFetchFailed  # noqa: E402
from spark_tpu.sql.session import SparkSession  # noqa: E402

# every process derives the SAME full dataset and owns a strided 1/n
# slice — so the single-process oracle is computable locally, and a
# correct recovery (survivor adopting the victim's partition) yields
# exactly the oracle rows while a silently-partial join cannot
rng = np.random.default_rng(7)
N, M = 900, 600
f_sk = rng.integers(0, 40, N).astype(np.int64)
f_price = rng.integers(1, 200, N).astype(np.int64)
k2 = (rng.integers(0, 20, M) * 2).astype(np.int64)
b2 = rng.integers(1, 100, M).astype(np.int64)
if mode == "grace-recover":
    # 40 distinct keys hash so unevenly across two reducers that one
    # shard stays under any budget the other can survive — widen the
    # key space AND the row counts so EVERY reducer's drained share of
    # EACH side alone overflows the grace-mode cap (the lane trades a
    # side's fetch reservation for its compacted shard, so pressure
    # must arrive within one side's drain)
    N, M = 1500, 1000
    f_sk = rng.integers(0, 200, N).astype(np.int64)
    f_price = rng.integers(1, 200, N).astype(np.int64)
    k2 = (rng.integers(0, 100, M) * 2).astype(np.int64)
    b2 = rng.integers(1, 100, M).astype(np.int64)
mine = slice(pid, None, n)

session = SparkSession.builder.appName(f"recov-{pid}").getOrCreate()

# each process persists ITS OWN partition as parquet on the shared
# filesystem — the leaf files a survivor re-reads for a dead peer
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
# fast failure detection: the victim's silence must become a blacklist
# exclusion well inside one exchange deadline
xs.conf.set("spark.tpu.cluster.heartbeatIntervalMs", "100")
xs.conf.set("spark.tpu.cluster.heartbeatTimeoutMs", "600")
if mode.startswith("bs-"):
    # every process registers its map outputs with the shared block
    # service at manifest-commit time; set BEFORE enableHostShuffle —
    # the client attaches at service construction
    xs.conf.set(C.BLOCKSERVER_ENABLED.key, "true")
    if mode == "bs-zero":
        # the zero-re-execution proof: ANY recovery attempt would blow
        # the zero budget and fail the query, so an oracle-exact OK can
        # only come from adopting the dead peer's registered output
        xs.conf.set(C.RECOVERY_MAX_STAGE_RETRIES.key, "0")
if mode == "norecover":
    xs.conf.set(C.RECOVERY_MAX_STAGE_RETRIES.key, "0")
elif mode == "grace-recover":
    # forced-spill staging plus a budget EVERY reducer's drained share
    # must overflow.  ``plan_reducers`` packs fine buckets greedily to
    # the partition-bytes target, so the 2048 default above would hand
    # reducer 0 a ~2 KiB sliver and the rest to the last reducer —
    # raise the target to ~half the shipped working set (~28 KiB: the
    # fact side prunes to sk at 8 B/row, fact2 ships k2+bonus at
    # 16 B/row) so both reducer shards land near 14 KiB and every
    # per-side drain (~6/8 KiB) alone overflows the 4 KiB budget.  Set
    # BEFORE enableHostShuffle, the ledger reads it at construction.
    # The keys are near-uniform, so grace buckets stay far below the
    # budget in every epoch.
    from spark_tpu.memory import HOST_BUDGET
    xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "14336")
    xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, "1024")
    xs.conf.set(HOST_BUDGET.key, str(4 << 10))
hb = HeartbeatMonitor(os.path.join(root, "beats"),
                      host_id=f"host-{pid}", conf=xs.conf_obj)
hb.start()
svc = xs.enableHostShuffle(root, process_id=pid, n_processes=n,
                           timeout_s=timeout_s, heartbeat=hb)
FaultInjector().attach(svc)          # plan comes from SPARK_TPU_FAULT_PLAN

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
    print(f"[p{pid}] FAILED {time.time() - t0:.2f} {lost}", flush=True)
    os._exit(0)

if got != exp:
    print(f"[p{pid}] PARTIAL got={len(got)} exp={len(exp)}", flush=True)
    os._exit(1)
if mode in ("recover", "grace-recover"):
    gauges = svc.metrics_source().snapshot()
    assert svc.counters["stage_retries"] >= 1, svc.counters
    assert svc.counters["recovered_partitions"] > 0, svc.counters
    assert gauges["epoch"] >= 1, gauges
    if mode == "grace-recover":
        # the capped budget really did force the degraded path (before
        # the loss, after it, or both), and the epoch replay over the
        # partially-spilled grace state still reached the exact oracle
        assert svc.counters["grace_buckets_used"] > 0, svc.counters
        assert svc.counters["grace_spill_bytes"] > 0, svc.counters
        assert 0 < gauges["peak_host_bytes"] \
            <= gauges["host_budget_bytes"], gauges
    print(f"[p{pid}] OK {len(got)} "
          f"retries={svc.counters['stage_retries']} "
          f"recovered={svc.counters['recovered_partitions']} "
          f"epoch={gauges['epoch']} "
          f"grace={svc.counters['grace_buckets_used']}", flush=True)
elif mode.startswith("bs-"):
    gauges = svc.metrics_source().snapshot()
    if mode == "bs-zero":
        # the dead peer's registered output was ADOPTED: exact oracle
        # with the recovery machinery never armed — zero re-executed
        # map tasks, zero epochs, and the adoption counters prove the
        # block really came out of service custody
        assert svc.counters["stage_retries"] == 0, svc.counters
        assert gauges["epoch"] == 0, gauges
        assert svc.counters["blocks_adopted"] >= 1, svc.counters
        assert svc.counters["blockserver_fallback_reads"] >= 1, \
            svc.counters
    elif mode == "bs-adopt":
        # sealed-but-unmarked manifest adopted at the barrier; the
        # victim's unfinished downstream stages still needed recovery
        assert svc.counters["manifests_adopted"] >= 1, svc.counters
        assert svc.counters["stage_retries"] >= 1, svc.counters
    elif mode == "bs-recover":
        # death BEFORE the seal: nothing adoptable, pure re-execution
        assert svc.counters["manifests_adopted"] == 0, svc.counters
        assert svc.counters["stage_retries"] >= 1, svc.counters
    elif mode == "bs-unavail":
        # service down on this side: every adoption attempt degraded to
        # a counted event (no hang, no partial), recovery did the rest
        assert svc.counters["blockserver_unavailable"] >= 1, svc.counters
        assert svc.counters["blocks_adopted"] == 0, svc.counters
        assert svc.counters["stage_retries"] >= 1, svc.counters
    print(f"[p{pid}] OK {len(got)} "
          f"retries={svc.counters['stage_retries']} "
          f"adopted={svc.counters['manifests_adopted']}m"
          f"/{svc.counters['blocks_adopted']}b "
          f"fallback={svc.counters['blockserver_fallback_reads']} "
          f"unavail={svc.counters['blockserver_unavailable']}",
          flush=True)
else:
    # norecover with no fault on this process's path: plain success,
    # and the recovery machinery must not have stirred
    assert svc.counters["stage_retries"] == 0, svc.counters
    print(f"[p{pid}] OK {len(got)} retries=0", flush=True)
os._exit(0)
