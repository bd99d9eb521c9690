"""Worker for the cross-process join parity and fault tests (not a test
module itself — launched as a subprocess by test_shuffled_join.py and
test_faults.py).

argv: <process_id> <n_processes> <shuffle_root> <mode> [timeout_s]

mode "parity": run a battery of equi-join plans (inner / left / semi,
two partitioned leaves, with and without a keyed Aggregate above, with a
deliberately SKEWED hot key) THREE ways — range-partitioned sort-merge
(``spark.tpu.crossproc.sortMergeJoin``), shuffled hash
(``spark.tpu.crossproc.shuffledJoin``), and the generic gather — and
assert every configuration matches a full-data single-process oracle
exactly.  Also asserts each run took the path it was supposed to
(``range_merge_joins`` / ``shuffled_joins`` / ``fast_path_aggs``
counters), that manifest coalescing merged sub-target fine partitions
(``partitions_coalesced``), and that the hot key actually forced a skew
split (``spans_split``).

mode "fault": arm a FaultInjector from SPARK_TPU_FAULT_PLAN and run ONE
shuffled-hash join (sortMergeJoin pinned off so the exchange ids are the
classic ``-jL``/``-jR``).  Prints ``OK <rows>`` when the exchange healed
(result must equal the oracle — never a partial join), or
``FAILED <elapsed> <lost>`` on a structured, bounded failure.

mode "fault-sample": same contract, but the query runs on the RANGE path
(sortMergeJoin on) so the plan can target the manifest-only
``-sample`` coordination round.

mode "spill": the full parity battery again, but with a tiny forced
``spark.tpu.shuffle.spillThresholdBytes`` and a capped host-memory
budget, so every join exchange stages its map output AND its fetched
blocks through the disk-spill path — spilled results must equal the
in-memory results must equal the oracle, spill gauges must be nonzero,
and the ledger's peak must stay under the budget.  Final line
``SPILL-OK ...``.

mode "spill-fault": forced-spill conf plus a ``disk_full`` FaultInjector
rule from SPARK_TPU_FAULT_PLAN: the spill write fails with ENOSPC, and
the query must fail BOUNDED with a structured ``HostMemoryError`` (the
peer fails bounded on its exchange timeout) — never partial results.

mode "ici": the full parity battery with the ICI device-exchange tier
ARMED (enabled, minBytes=0, tierOverride placing every pid in one
domain).  On CPU a cross-process device collective cannot exist
(single-process jax world), so every device attempt must degrade
STRUCTURED to the host tier — results byte-identical to the plain
parity battery, ``dcn_fallback_exchanges`` > 0, ``ici_exchanges`` == 0,
``tier_split_peers`` == n-1, and the decision-trace checks prove the
tier split itself agreed on every replica (divergence = 0).

mode "ici-fault": the ICI confs armed plus a FaultInjector plan from
SPARK_TPU_FAULT_PLAN aimed at the device tier (``ici_unavailable`` at
the attempt point, or ``die_mid_device_copy`` at the copy point); runs
ONE hash-lane join with the "fault" mode's contract — ``OK <rows>``
(oracle-exact) or ``FAILED`` (structured, bounded), never partial.

mode "grace": a host budget CAPPED BELOW the reducers' drained working
set, so fetching a joined shard raises ``HostMemoryPressure`` and the
join lanes must degrade into grace buckets (re-bucket the sink by join
key hash, join bucket-by-bucket under the budget) instead of aborting.
A battery of keyed-aggregate-above-join queries (inner / left / semi,
plus dictionary-coded string keys) runs on BOTH the range and hash
lanes and must equal the uncapped full-data oracle exactly; then a huge
advisory target forces the ELASTIC planner to narrow the reducer set
below the live set (``reducers_elastic``), still oracle-exact.  Asserts
nonzero ``grace_buckets_used`` / ``grace_spill_bytes`` and
``peak_host_bytes <= host_budget_bytes``.  Final line ``GRACE-OK``.

mode "grace-fault": the grace conf plus a ``disk_full`` rule aimed at
the ``<xid>-grace`` exchange: the grace SPILL hits ENOSPC mid-degrade,
and the query must abort bounded with a structured ``HostMemoryError``
whose detail names the failed grace spill — never partial results.

mode "runcodes": run-encoded vs raw wire parity on BOTH exchange lanes
(``spark.tpu.shuffle.wire.runCodes`` flipped per leg) over a
time-series-shaped workload — a sorted key in long runs, a
dictionary+RLE composed status column (codes are int32 runs) — under
the forced-spill conf, so encoded frames also stage through disk
without inflating (the spill-under-budget cell).  Every leg must equal
the full-data oracle exactly; the encoded legs must bump
``rle_columns_encoded`` / ``run_bytes_saved`` and fire the run-aware
operators (``run_aware_op_rows`` / ``runs_materialized``), the raw
legs must not encode.  Final line ``RUNCODES-OK ...``.
"""

import os
import sys
import time

pid = int(sys.argv[1])
n = int(sys.argv[2])
root = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "parity"
timeout_s = float(sys.argv[5]) if len(sys.argv) > 5 else 45.0

os.environ["JAX_PLATFORMS"] = "cpu"
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from spark_tpu import columnar as _col  # noqa: E402
from spark_tpu import config as C  # noqa: E402
from spark_tpu.memory import HOST_BUDGET, HostMemoryError  # noqa: E402
from spark_tpu.parallel.faults import FaultInjector  # noqa: E402
from spark_tpu.parallel.hostshuffle import ExchangeFetchFailed  # noqa: E402
from spark_tpu.sql.session import SparkSession  # noqa: E402

# Both processes draw the SAME full dataset and keep a strided 1/n slice,
# so every process sees every key range (the worst case for a local join:
# without co-partitioning almost every match is cross-process).  Key 8 is
# a deliberately HOT key (~40% of fact rows): under the small advisory
# target below its span exceeds SKEW_FACTOR x median, so the range
# planner must SPLIT it across reducers (and still match the oracle).
rng = np.random.default_rng(7)
N, M = 900, 600
f_sk = rng.integers(0, 40, N).astype(np.int64)
f_sk[rng.random(N) < 0.4] = 8
f_price = rng.integers(1, 200, N).astype(np.int64)
f_g = np.array(["ash", "oak", "fir", "elm"])[f_sk % 4]
k2 = (rng.integers(0, 20, M) * 2).astype(np.int64)   # even keys only →
b2 = rng.integers(1, 100, M).astype(np.int64)        # LEFT join has misses
g2 = np.array(["ash", "oak", "fir", "pine"])[k2 % 4]  # dicts only overlap
d_sk = np.arange(0, 40, 3, dtype=np.int64)           # sparse dim for SEMI
d_year = (1998 + d_sk % 5).astype(np.int64)

mine = slice(pid, None, n)

session = SparkSession.builder.appName(f"sjoin-{pid}").getOrCreate()

xs = session.newSession()
xs.conf.set(C.MESH_SHARDS.key, "1")
if mode in ("spill", "spill-fault", "runcodes"):
    # a threshold far below any join side's bytes forces the map output
    # of EVERY join exchange (and, via the FetchSink's force rule, every
    # fetched block) through the spill files; the budget cap must be set
    # BEFORE enableHostShuffle (the ledger reads it at construction).
    # "runcodes" rides the same forced-spill conf so its whole battery
    # doubles as the spill-under-budget cell: encoded frames must stage
    # through disk WITHOUT inflating and still match the oracle.
    xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, "1024")
    xs.conf.set(HOST_BUDGET.key, str(32 << 20))
elif mode in ("grace", "grace-fault"):
    # same forced-spill staging, but the budget sits BELOW the bytes a
    # reducer drains for one join (each side lands ~3-5 KiB per process
    # here): the second side's drain must overflow the ledger and the
    # lanes must grace-degrade rather than abort.  Single buckets
    # (~1/32nd of a side, plus the whole hot key) still fit.
    xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, "1024")
    xs.conf.set(HOST_BUDGET.key, str(7 << 10))
svc = xs.enableHostShuffle(root, process_id=pid, n_processes=n,
                           timeout_s=timeout_s)
# small advisory target: the test tables are tiny, and with the 4 MiB
# default every fine partition would coalesce onto process 0 — a few KiB
# keeps BOTH processes joining while still exercising the coalescer (and
# makes the hot key's span split into several reducer shares)
xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "2048")
# strategy choice must be pinned per mode below — a tiny side slipping
# under the broadcast threshold would silently change the path under test
xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
# finer quantiles sharpen skew DETECTION: hot-key duplicates collapse
# into one span either way, but more fine spans shrink the median span
# the 5x-median test compares against (8/proc would leave the hot span
# just under threshold on this small table)
xs.conf.set(C.SHUFFLE_FINE_PARTITIONS.key, "32")
if mode in ("ici", "ici-fault"):
    # arm the device tier with every pid in ONE ICI domain and no byte
    # floor: every eligible exchange must ATTEMPT the device tier, and
    # on CPU every attempt must fold back onto the host tier structured
    xs.conf.set(C.SHUFFLE_ICI_ENABLED.key, "true")
    xs.conf.set(C.SHUFFLE_ICI_MIN_BYTES.key, "0")
    xs.conf.set(C.SHUFFLE_ICI_TIER_OVERRIDE.key,
                ",".join(str(p) for p in range(n)))
# tags has a UNIQUE word per row: each process's slice builds a fully
# DISJOINT dictionary, so the cross-process string min/max below can only
# be right if the exchange genuinely unifies the code spaces
t_words = np.array([f"row{i:04d}" for i in range(N)])

xs.createDataFrame({"sk": f_sk[mine], "price": f_price[mine],
                    "g": f_g[mine]}).createOrReplaceTempView("fact")
xs.createDataFrame({"k2": k2[mine], "bonus": b2[mine],
                    "g2": g2[mine]}).createOrReplaceTempView("fact2")
xs.createDataFrame({"sk2": f_sk[mine], "t": t_words[mine]}) \
    .createOrReplaceTempView("tags")
# dim is REPLICATED: every process holds the identical full table
xs.createDataFrame({"d_sk": d_sk, "year": d_year}) \
    .createOrReplaceTempView("dim")

oracle = session.newSession()
oracle.conf.set(C.MESH_SHARDS.key, "1")
oracle.createDataFrame({"sk": f_sk, "price": f_price, "g": f_g}) \
    .createOrReplaceTempView("fact")
oracle.createDataFrame({"k2": k2, "bonus": b2, "g2": g2}) \
    .createOrReplaceTempView("fact2")
oracle.createDataFrame({"sk2": f_sk, "t": t_words}) \
    .createOrReplaceTempView("tags")
oracle.createDataFrame({"d_sk": d_sk, "year": d_year}) \
    .createOrReplaceTempView("dim")

# (name, sql, expected counter per mode).  String keys ride the range
# exchange too: dictionaries are sorted (codes order like words), the
# sample round agrees on cut WORDS, and each process maps them into its
# local code space — so "range" mode takes the sort-merge path for
# string equi-keys exactly like numeric ones.
QUERIES = [
    ("inner-agg",
     "SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
     "JOIN fact2 ON sk = k2 GROUP BY sk ORDER BY sk",
     {"range": "range_merge_joins", "hash": "shuffled_joins"}),
    ("inner-rows",
     "SELECT sk, price, bonus FROM fact JOIN fact2 ON sk = k2 "
     "WHERE bonus > 40 ORDER BY sk, price, bonus",
     {"range": "range_merge_joins", "hash": "shuffled_joins"}),
    ("left-agg",
     "SELECT sk, count(bonus) AS cb, count(*) AS c FROM fact "
     "LEFT JOIN fact2 ON sk = k2 GROUP BY sk ORDER BY sk",
     {"range": "range_merge_joins", "hash": "shuffled_joins"}),
    ("string-key-agg",
     "SELECT g, count(*) AS c, sum(bonus) AS sb FROM fact "
     "JOIN fact2 ON g = g2 GROUP BY g ORDER BY g",
     {"range": "range_merge_joins", "hash": "shuffled_joins"}),
    # lifted string aggregates: min/max/first on a dictionary column whose
    # per-process dictionaries are fully DISJOINT — correct answers require
    # the receiver-side code-space unification, in every exchange mode
    ("string-minmax-fast",
     "SELECT sk2, min(t) AS tlo, max(t) AS thi, count(*) AS c FROM tags "
     "GROUP BY sk2 ORDER BY sk2",
     {"range": "fast_path_aggs", "hash": "fast_path_aggs",
      "gather": "fast_path_aggs"}),
    ("semi-rows",
     "SELECT sk, price FROM fact LEFT SEMI JOIN fact2 ON sk = k2 "
     "ORDER BY sk, price",
     {"range": "range_merge_joins", "hash": "shuffled_joins"}),
    # widened fast-path guard: LEFT SEMI against a REPLICATED build side
    # under a keyed Aggregate stays on the single-exchange fast path in
    # EVERY mode — exchange strategy flags never reach it
    ("semi-replicated-fast",
     "SELECT sk, count(*) AS c FROM fact LEFT SEMI JOIN dim ON sk = d_sk "
     "GROUP BY sk ORDER BY sk",
     {"range": "fast_path_aggs", "hash": "fast_path_aggs",
      "gather": "fast_path_aggs"}),
]

#: mode → (sortMergeJoin, shuffledJoin) conf values
MODES = [("range", "true", "true"),
         ("hash", "false", "true"),
         ("gather", "false", "false")]


def set_mode(m):
    for name, smj, sh in MODES:
        if name == m:
            xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, smj)
            xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, sh)
            return
    raise ValueError(m)


def run(sess, sql):
    return [tuple(r) for r in sess.sql(sql).collect()]


#: dict-free sides (projected to int columns) — the ONLY shape the ICI
#: device tier accepts: dictionary-coded columns are pinned to the host
#: tier, where the code-space unification lives.  The unprojected
#: QUERIES battery above doubles as the dict-code lane: its string
#: columns keep every exchange on the host path even with the tier
#: armed, results still byte-identical.
ICI_QUERIES = [
    ("ici-inner-agg",
     "SELECT sk, count(*) AS c, sum(bonus) AS sb "
     "FROM (SELECT sk FROM fact) f "
     "JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
     "GROUP BY sk ORDER BY sk"),
    ("ici-inner-rows",
     "SELECT sk, price, bonus FROM (SELECT sk, price FROM fact) f "
     "JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
     "WHERE bonus > 40 ORDER BY sk, price, bonus"),
    ("ici-left-agg",
     "SELECT sk, count(bonus) AS cb, count(*) AS c "
     "FROM (SELECT sk FROM fact) f "
     "LEFT JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
     "GROUP BY sk ORDER BY sk"),
]

if mode in ("fault", "fault-sample", "ici-fault"):
    FaultInjector().attach(svc)        # plan comes from SPARK_TPU_FAULT_PLAN
    set_mode("range" if mode == "fault-sample" else "hash")
    join_counter = ("range_merge_joins" if mode == "fault-sample"
                    else "shuffled_joins")
    if mode == "ici-fault":
        # dict-free sides so the device tier genuinely ATTEMPTS (and
        # the armed fault point actually fires) before degrading
        name, sql = ICI_QUERIES[0]
    else:
        name, sql, _ = QUERIES[0]
    exp = run(oracle, sql)
    t0 = time.time()
    try:
        got = run(xs, sql)
    except (ExchangeFetchFailed, TimeoutError) as e:
        lost = sorted(getattr(e, "lost_hosts", []) or [])
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} {lost}", flush=True)
        os._exit(0)
    assert svc.counters[join_counter] > 0, svc.counters
    if got != exp:
        print(f"[p{pid}] PARTIAL got={len(got)} exp={len(exp)}", flush=True)
        os._exit(1)
    print(f"[p{pid}] OK {len(got)}", flush=True)
    os._exit(0)

if mode == "spill-fault":
    FaultInjector().attach(svc)        # disk_full plan from the env
    set_mode("hash")
    _name, sql, _ = QUERIES[0]
    t0 = time.time()
    try:
        got = run(xs, sql)
    except HostMemoryError as e:
        # the faulted process: spill hit injected ENOSPC, and the error
        # names the reserver and the exchange — structured and bounded
        assert e.owner and "spill failed" in str(e), e
        print(f"[p{pid}] FAILED-HOSTMEM {time.time() - t0:.2f} "
              f"{e.owner}", flush=True)
        os._exit(0)
    except (ExchangeFetchFailed, TimeoutError):
        # the healthy peer: its partner aborted mid-exchange, so it
        # fails bounded on the fetch/barrier timeout — never partial
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} []", flush=True)
        os._exit(0)
    print(f"[p{pid}] PARTIAL rows={len(got)}", flush=True)
    os._exit(1)

# keyed aggregates ABOVE the join: the sides are plain leaves, so RAW
# rows ride the join exchange (nothing pushes down) and the pressure
# lands exactly on the reducer's drain — while the merged group states
# keep every post-join exchange far below the capped budget
GRACE_QUERIES = [
    ("grace-inner",
     "SELECT sk, count(*) AS c, sum(bonus) AS sb "
     "FROM (SELECT sk FROM fact) f "
     "JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
     "GROUP BY sk ORDER BY sk"),
    ("grace-left",
     "SELECT sk, count(bonus) AS cb, count(*) AS c "
     "FROM (SELECT sk FROM fact) f "
     "LEFT JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
     "GROUP BY sk ORDER BY sk"),
    ("grace-semi",
     "SELECT sk, count(*) AS c FROM (SELECT sk FROM fact) f "
     "LEFT SEMI JOIN (SELECT k2 FROM fact2) f2 ON sk = k2 "
     "GROUP BY sk ORDER BY sk"),
    ("grace-string",
     "SELECT g, count(*) AS c, sum(bonus) AS sb "
     "FROM (SELECT g FROM fact) f "
     "JOIN (SELECT g2, bonus FROM fact2) f2 ON g = g2 "
     "GROUP BY g ORDER BY g"),
]
#: grace runs BOTH distributed lanes (gather has no reducer drain)
GRACE_MODES = (("range", "range_merge_joins"), ("hash", "shuffled_joins"))

if mode == "grace-fault":
    FaultInjector().attach(svc)    # disk_full on the -grace exchange
    set_mode("hash")
    _name, sql = GRACE_QUERIES[0]
    t0 = time.time()
    try:
        got = run(xs, sql)
    except HostMemoryError as e:
        # the faulted process: the grace SPILL hit injected ENOSPC —
        # the degraded path itself fails structured and bounded
        assert e.owner and "grace spill failed" in str(e), e
        print(f"[p{pid}] FAILED-HOSTMEM {time.time() - t0:.2f} "
              f"{e.owner}", flush=True)
        os._exit(0)
    except (ExchangeFetchFailed, TimeoutError):
        # the healthy peer fails bounded on its exchange timeout
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} []", flush=True)
        os._exit(0)
    print(f"[p{pid}] PARTIAL rows={len(got)}", flush=True)
    os._exit(1)

if mode == "grace":
    for name, sql in GRACE_QUERIES:
        exp = run(oracle, sql)
        for m, want in GRACE_MODES:
            set_mode(m)
            before = dict(svc.counters)
            got = run(xs, sql)
            assert svc.counters[want] > before[want], (
                f"{name}/{m}: expected the {want} path, {svc.counters}")
            if got != exp:
                print(f"[p{pid}] GRACE-PARITY-FAIL {name}/{m} "
                      f"got={got[:4]} exp={exp[:4]}", flush=True)
                os._exit(1)
        print(f"[p{pid}] GRACE-PARITY-OK {name} ({len(exp)} rows)",
              flush=True)
    # elastic narrowing: one reducer's worth of target bytes swallows
    # the whole observed working set, so the plan round must narrow the
    # reducer set below the live set — re-derived deterministically on
    # EVERY process (the runtime invariant cross-checks it against the
    # shared manifests) — and the lone reducer's drain grace-degrades
    xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, str(1 << 20))
    name, sql = GRACE_QUERIES[0]
    exp = run(oracle, sql)
    for m, want in GRACE_MODES:
        set_mode(m)
        before = dict(svc.counters)
        got = run(xs, sql)
        assert svc.counters[want] > before[want], (
            f"elastic/{m}: expected the {want} path, {svc.counters}")
        if got != exp:
            print(f"[p{pid}] GRACE-PARITY-FAIL elastic/{m} "
                  f"got={got[:4]} exp={exp[:4]}", flush=True)
            os._exit(1)
    print(f"[p{pid}] GRACE-PARITY-OK elastic ({len(exp)} rows)",
          flush=True)
    # salted re-split: ONE grace bucket holds a reducer's whole working
    # set, so it cannot fit under the budget and must re-split under a
    # salt — the sub-buckets fit, and results still match the oracle.
    # Two legs so at two processes EACH pressures at least once: at the
    # small advisory target the hot-key owner degrades; at the huge
    # target the elastic plan routes everything to the lone first
    # reducer.  (At other widths a process may own no pressured shard
    # in either leg — the re-split assert then stays with whoever
    # actually graced.)
    xs.conf.set(C.CROSSPROC_GRACE_BUCKETS.key, "1")
    set_mode("hash")
    before = dict(svc.counters)
    for tgt in ("2048", str(1 << 20)):
        xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, tgt)
        got = run(xs, sql)
        if got != exp:
            print(f"[p{pid}] GRACE-PARITY-FAIL resplit@{tgt} "
                  f"got={got[:4]} exp={exp[:4]}", flush=True)
            os._exit(1)
    if n == 2 or svc.counters["grace_buckets_used"] > \
            before["grace_buckets_used"]:
        assert svc.counters["grace_salted_resplits"] > \
            before["grace_salted_resplits"], svc.counters
    print(f"[p{pid}] GRACE-PARITY-OK resplit ({len(exp)} rows)",
          flush=True)
    xs.conf.set(C.CROSSPROC_GRACE_BUCKETS.key,
                str(C.CROSSPROC_GRACE_BUCKETS.default))
    assert svc.counters["reducers_elastic"] > 0, svc.counters
    assert 0 < svc.counters["reducers_observed"] \
        < svc.counters["reducers_planned"], svc.counters
    if n == 2:
        # the budget is tuned so BOTH processes demonstrably grace at
        # two processes; at wider sets a process may own only shards
        # that fit, so the cumulative evidence lives on the pressured
        # peers (parity above still ran everywhere)
        assert svc.counters["grace_buckets_used"] > 0, svc.counters
        assert svc.counters["grace_spill_bytes"] > 0, svc.counters
    gauges = svc.metrics_source().snapshot()
    assert 0 < gauges["peak_host_bytes"] <= gauges["host_budget_bytes"], \
        gauges
    print(f"[p{pid}] GRACE-OK buckets={svc.counters['grace_buckets_used']} "
          f"spill={svc.counters['grace_spill_bytes']} "
          f"resplits={svc.counters['grace_salted_resplits']} "
          f"elastic={svc.counters['reducers_elastic']} "
          f"peak={gauges['peak_host_bytes']}", flush=True)
    os._exit(0)

if mode == "runcodes":
    # run-encoded vs raw wire parity on BOTH exchange lanes.  The
    # workload is time-series shaped: a sorted key in LONG runs, a
    # low-cardinality status string whose dictionary codes are
    # themselves int32 runs (dictionary+RLE composed), and random
    # values.  The strided per-process slice keeps every run shape,
    # just 1/n as long — and the forced-spill conf above makes every
    # exchange stage its encoded frames through disk.
    NRK, REP = 48, 64
    r_ts = np.repeat(np.arange(NRK, dtype=np.int64), REP)
    r_v = rng.integers(1, 100, NRK * REP).astype(np.int64)
    r_s = np.array(["ok", "warn", "err"])[(np.arange(NRK * REP) // 256) % 3]
    r_dk = np.arange(0, NRK, 2, dtype=np.int64)     # even keys → LEFT misses
    r_bonus = (r_dk * 3 + 7).astype(np.int64)
    r_s2 = np.array(["ok", "err", "crit", "ok", "warn", "crit"])
    r_b2 = np.array([11, 23, 37, 5, 41, 2], dtype=np.int64)
    for s, sl in ((xs, mine), (oracle, slice(None))):
        s.createDataFrame({"ts": r_ts[sl], "v": r_v[sl], "s": r_s[sl]}) \
            .createOrReplaceTempView("ev")
        s.createDataFrame({"dk": r_dk[sl], "bonus": r_bonus[sl]}) \
            .createOrReplaceTempView("dm")
        s.createDataFrame({"s2": r_s2[sl], "b2": r_b2[sl]}) \
            .createOrReplaceTempView("dm2")

    RC_QUERIES = [
        ("rc-inner-agg",
         "SELECT ts, count(*) AS c, sum(v) AS sv FROM ev "
         "JOIN dm ON ts = dk GROUP BY ts ORDER BY ts"),
        ("rc-rows-filter",
         "SELECT ts, v, bonus FROM ev JOIN dm ON ts = dk "
         "WHERE bonus > 20 ORDER BY ts, v, bonus"),
        ("rc-left-agg",
         "SELECT ts, count(bonus) AS cb, count(*) AS c FROM ev "
         "LEFT JOIN dm ON ts = dk GROUP BY ts ORDER BY ts"),
        ("rc-dict-rle",
         "SELECT s, count(*) AS c, sum(b2) AS sb FROM ev "
         "JOIN dm2 ON s = s2 GROUP BY s ORDER BY s"),
        # the r20 plane query: filter+agg over the run-shaped key — the
        # reduce-side join shards arrive run-encoded, and on the
        # encoded+jit leg they must cross the stage boundary as device
        # planes, WITHOUT a single host materialization
        ("rc-plane-agg",
         "SELECT ts, count(*) AS c, sum(v) AS sv FROM ev "
         "JOIN dm ON ts = dk WHERE ts < 32 GROUP BY ts ORDER BY ts"),
    ]

    def set_runcodes(on):
        # the service snapshots the conf at construction; the worker
        # flips BOTH (the conf feeds the SpilledRuns constructors, the
        # attribute feeds encode/decode) — identically on every process
        xs.conf.set(C.SHUFFLE_WIRE_RUN_CODES.key,
                    "true" if on else "false")
        svc.run_codes = bool(on)

    # three legs per lane: encoded+jit (eligible run leaves cross the
    # stage boundary as device planes, un-inflated; untaught leaves
    # still materialize counted), encoded+interpreted (the host lane
    # keeps run vectors lazy all the way into the operators — the
    # run-aware join probe and filter paths fire here), and raw+jit
    # (the oracle wire)
    LEGS = (("on", True, True), ("on-host", True, False),
            ("off", False, True))
    for name, sql in RC_QUERIES:
        exp = run(oracle, sql)
        for m, want in (("range", "range_merge_joins"),
                        ("hash", "shuffled_joins")):
            set_mode(m)
            for leg, on, jit in LEGS:
                set_runcodes(on)
                xs.conf.set(C.CODEGEN_ENABLED.key,
                            "true" if jit else "false")
                before = dict(svc.counters)
                mat0 = _col.runs_materialized()
                got = run(xs, sql)
                if name == "rc-plane-agg" and on and jit:
                    # the tentpole acceptance: the fully-eligible
                    # filter+agg pipeline never expands a run on the
                    # host — planes carry the compressed form through
                    # the jitted stage on BOTH exchange lanes
                    assert _col.runs_materialized() == mat0, (
                        f"{name}/{m}/{leg}: runs_materialized moved "
                        f"{_col.runs_materialized() - mat0} on the "
                        "plane leg")
                assert svc.counters[want] > before.get(want, 0), (
                    f"{name}/{m}: expected the {want} path, {svc.counters}")
                if not on:
                    # raw leg: the encoder must not have touched a column
                    assert svc.counters["rle_columns_encoded"] == \
                        before.get("rle_columns_encoded", 0), svc.counters
                if got != exp:
                    print(f"[p{pid}] RC-PARITY-FAIL {name}/{m}/{leg} "
                          f"got={got[:4]} exp={exp[:4]}", flush=True)
                    os._exit(1)
        print(f"[p{pid}] RC-PARITY-OK {name} ({len(exp)} rows)", flush=True)
    xs.conf.set(C.CODEGEN_ENABLED.key, "true")
    set_runcodes(True)
    # the encoded legs demonstrably run-encoded columns and saved bytes
    assert svc.counters["rle_columns_encoded"] > 0, svc.counters
    assert svc.counters["run_bytes_saved"] > 0, svc.counters
    # run-aware operators fired on lazily-decoded run vectors, and the
    # collect() late-materialized at least one of them
    assert _col.run_aware_op_rows() > 0, _col.run_aware_op_rows()
    assert _col.runs_materialized() > 0, _col.runs_materialized()
    # spill-under-budget cell: every exchange staged through disk, the
    # encoded frames never inflated past the capped ledger
    assert svc.counters["spill_bytes"] > 0, svc.counters
    gauges = svc.metrics_source().snapshot()
    assert gauges["rle_columns_encoded"] > 0, gauges
    assert gauges["run_bytes_saved"] > 0, gauges
    assert 0 < gauges["peak_host_bytes"] <= gauges["host_budget_bytes"], \
        gauges
    print(f"[p{pid}] RUNCODES-OK rle={svc.counters['rle_columns_encoded']} "
          f"saved={svc.counters['run_bytes_saved']} "
          f"runaware={_col.run_aware_op_rows()} "
          f"mat={_col.runs_materialized()} "
          f"spill={svc.counters['spill_bytes']}", flush=True)
    os._exit(0)

JOIN_COUNTERS = ("range_merge_joins", "shuffled_joins", "broadcast_joins")
for name, sql, expected in QUERIES:
    exp = run(oracle, sql)
    results = {}
    for m, _smj, _sh in MODES:
        set_mode(m)
        before = dict(svc.counters)
        results[m] = run(xs, sql)
        want = expected.get(m)
        if want is not None:
            assert svc.counters[want] > before[want], (
                f"{name}/{m}: expected the {want} path, {svc.counters}")
        # no OTHER exchange-join path may have run for this query
        for c in JOIN_COUNTERS:
            if c != want:
                assert svc.counters[c] == before[c], (
                    f"{name}/{m}: unexpected {c} bump, {svc.counters}")
    set_mode("range")
    bad = [m for m in results if results[m] != exp]
    if bad:
        print(f"[p{pid}] PARITY-FAIL {name} modes={bad} "
              f"got={results[bad[0]][:4]} exp={exp[:4]}", flush=True)
        os._exit(1)
    print(f"[p{pid}] PARITY-OK {name} ({len(exp)} rows)", flush=True)

# manifest-driven coalescing: the battery above ships tiny fine
# partitions, all far below targetPartitionBytes — the planner must have
# merged them (and the merge demonstrably did not change any result)
assert svc.counters["partitions_coalesced"] > 0, svc.counters
# the hot key forced the range planner to SPLIT its span across reducers
# (the skew mitigation), and the sample round actually moved manifests
assert svc.counters["spans_split"] > 0, svc.counters
assert svc.counters["sample_bytes"] > 0, svc.counters
# per-exchange data-plane accounting: produced >= shipped, and the
# manifest-derived partition-size and cut-point gauges are populated
gauges = svc.metrics_source().snapshot()
assert gauges["bytes_produced_raw"] >= gauges["bytes_shipped_raw"] > 0, gauges
assert gauges["rows_produced"] >= gauges["rows_shipped"] > 0, gauges
assert gauges["partition_bytes_max"] >= gauges["partition_bytes_median"], gauges
assert gauges["range_cutpoints"] > 0, gauges
# encoded execution: dictionary columns crossed the wire as codes with the
# sidecar dedup saving repeat shipments, the disjoint tags dictionaries
# forced receiver-side remaps, and collected strings late-materialized
assert gauges["dict_columns_encoded"] > 0, gauges
assert gauges["dict_bytes_saved"] > 0, gauges
assert gauges["codes_remapped"] > 0, gauges
assert gauges["late_materialized_rows"] > 0, gauges
if mode == "spill":
    # every join exchange was forced through the spill path, results
    # above matched the oracle anyway, and the ledger never exceeded the
    # capped budget
    assert svc.counters["spill_bytes"] > 0, svc.counters
    assert svc.counters["spill_events"] > 0, svc.counters
    assert 0 < gauges["peak_host_bytes"] <= gauges["host_budget_bytes"], \
        gauges
    print(f"[p{pid}] SPILL-OK bytes={svc.counters['spill_bytes']} "
          f"events={svc.counters['spill_events']} "
          f"peak={gauges['peak_host_bytes']}", flush=True)
    os._exit(0)
if mode == "ici":
    # the dict-column battery above kept every exchange on the host
    # path (the code-space gate) — results byte-identical with the
    # tier armed.  Now dict-FREE sides, where the device tier must
    # genuinely attempt every exchange: no CPU process can span the
    # 2-process domain, so each attempt must fold back structured onto
    # the host tier and still match the oracle exactly, on BOTH lanes.
    assert svc.counters["dcn_fallback_exchanges"] == 0, svc.counters
    for name, sql in ICI_QUERIES:
        exp = run(oracle, sql)
        for m, want in (("range", "range_merge_joins"),
                        ("hash", "shuffled_joins")):
            set_mode(m)
            before = dict(svc.counters)
            got = run(xs, sql)
            assert svc.counters[want] > before[want], (
                f"{name}/{m}: expected the {want} path, {svc.counters}")
            assert svc.counters["dcn_fallback_exchanges"] > \
                before["dcn_fallback_exchanges"], (
                f"{name}/{m}: no device-tier attempt, {svc.counters}")
            if got != exp:
                print(f"[p{pid}] ICI-PARITY-FAIL {name}/{m} "
                      f"got={got[:4]} exp={exp[:4]}", flush=True)
                os._exit(1)
        print(f"[p{pid}] ICI-PARITY-OK {name} ({len(exp)} rows)",
              flush=True)
    assert svc.counters["ici_exchanges"] == 0, svc.counters
    assert svc.counters["ici_bytes_moved"] == 0, svc.counters
    assert svc.counters["tier_split_peers"] == n - 1, svc.counters
    print(f"[p{pid}] ICI-FALLBACK-OK "
          f"fallbacks={svc.counters['dcn_fallback_exchanges']} "
          f"peers={svc.counters['tier_split_peers']}", flush=True)
print(f"[p{pid}] ALL-OK range={svc.counters['range_merge_joins']} "
      f"shuffled={svc.counters['shuffled_joins']} "
      f"fast={svc.counters['fast_path_aggs']} "
      f"coalesced={svc.counters['partitions_coalesced']} "
      f"split={svc.counters['spans_split']}", flush=True)
os._exit(0)
