"""Worker for the adaptive-execution parity and fault tests (not a test
module itself — launched as a subprocess by test_adaptive.py and
test_faults.py).

argv: <process_id> <n_processes> <shuffle_root> <mode> [timeout_s]

mode "adaptive": the full adaptive battery against a full-data oracle —
every scenario must match the oracle exactly AND take the path the
observed statistics dictate:

1. demote-to-broadcast (hash lane): both leaves exceed the broadcast
   threshold at plan time, but a selective filter (pushed below the
   join by the optimizer) shrinks one side's OBSERVED map output far
   under it — the stats barrier demotes the frozen hash plan to a
   broadcast before any data block ships (``adaptive_replans`` /
   ``strategy_demotions`` counters, no ``shuffled_joins`` bump);
2. stats-feedback second join: the SAME query again — the recorded
   observed cardinality now decides broadcast at PLAN time
   (``stats_feedback_hits``), gathering the side's executed output;
3. demote-to-broadcast (range lane): a differently-filtered query with
   sortMergeJoin on freezes to range, then demotes at the stats barrier
   (no ``range_merge_joins`` bump);
4. frozen comparison: a second session with adaptiveReplan=false runs
   scenario 1's query through the full hash exchange — same rows, zero
   demotions (adaptive == frozen == oracle);
5. post-sample skew re-split: a probe side whose ROW distribution is
   uniform (the sample round estimates uniform spans) but whose BYTES
   concentrate in one key's fat strings — the observed-size reducer
   plan splits the span the sample could not have flagged
   (``post_sample_skew_splits``);
6. partial-aggregate pushdown: a derived-table keyed aggregate below
   the join ships partial state through the hash exchange
   (``shuffled_joins`` bump) and matches both the oracle and the
   unpushed gather plan.

mode "fault-adapt": arm a FaultInjector from SPARK_TPU_FAULT_PLAN and
run ONE misestimated join (scenario 1's query; first query, so the
stats round is exchange ``xq000001-plan`` and a demotion gather would
be ``xq000001-bcast``).  Prints ``OK ...`` with the path counters when
the query completed (result must equal the oracle — never partial), or
``FAILED <elapsed> <lost>`` on a structured, bounded failure.

mode "trace": the replica-determinism parity run — one full hash
exchange plus one range exchange with the decision-trace runtime check
pinned ON; every process must produce oracle-identical rows and report
``decision_trace_checks > 0`` with ZERO divergence
(``[p<i>] TRACE-OK rows=... checks=... div=0``).

mode "skew-decision": same hash-lane query with a FaultInjector armed
from SPARK_TPU_FAULT_PLAN (the ``skew_decision`` kind): the armed
process's GATHERED view of the ``xq000001-plan`` round is perturbed
while the on-disk manifests stay byte-identical — its adaptive
re-decision diverges from its peers and ``verify_decision_trace`` must
abort it structured (``[p<i>] FAILED-DIVERGED ... prop=decision-trace-
agreement``), never letting a divergently-demoted exchange emit
partial rows; the unarmed peer fails BOUNDED at its data barrier.
"""

import os
import sys
import time

pid = int(sys.argv[1])
n = int(sys.argv[2])
root = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "adaptive"
timeout_s = float(sys.argv[5]) if len(sys.argv) > 5 else 45.0

os.environ["JAX_PLATFORMS"] = "cpu"
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from spark_tpu import config as C  # noqa: E402
from spark_tpu.analysis.errors import PlanInvariantError  # noqa: E402
from spark_tpu.parallel.faults import FaultInjector  # noqa: E402
from spark_tpu.parallel.hostshuffle import ExchangeFetchFailed  # noqa: E402
from spark_tpu.sql.session import SparkSession  # noqa: E402

# Every process draws the SAME full dataset and keeps a strided 1/n
# slice.  fact and fact2 are both far above the broadcast threshold
# below (the plan-time probe sees raw LEAF bytes), but the battery's
# filters cut fact2 to a few dozen rows — the misestimation the
# adaptive stats barrier exists to catch.
rng = np.random.default_rng(11)
NF, NB = 1200, 900
f_sk = rng.integers(0, 48, NF).astype(np.int64)
f_price = rng.integers(1, 500, NF).astype(np.int64)
k2 = rng.integers(0, 48, NB).astype(np.int64)
bonus = rng.integers(0, 100, NB).astype(np.int64)

# skew tables: probe rows are UNIFORM per key (the row-weighted sample
# round estimates uniform spans) but key 3 carries fat unique strings,
# so the observed BYTES of its span dwarf the median — only the
# post-sample size round can see it
NS, NR = 600, 150
s_rk = (np.arange(NS) % 16).astype(np.int64)
s_t = np.array([(f"r{i:04d}" * 56) if s_rk[i] == 3 else f"s{i:04d}"
                for i in range(NS)], dtype=object)
r_rk2 = (np.arange(NR) % 16).astype(np.int64)
r_w2 = rng.integers(1, 50, NR).astype(np.int64)

mine = slice(pid, None, n)

session = SparkSession.builder.appName(f"adapt-{pid}").getOrCreate()


def make_session(shuffle_root, adaptive):
    xs = session.newSession()
    xs.conf.set(C.MESH_SHARDS.key, "1")
    svc = xs.enableHostShuffle(shuffle_root, process_id=pid,
                               n_processes=n, timeout_s=timeout_s)
    xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "2048")
    xs.conf.set(C.SHUFFLE_FINE_PARTITIONS.key, "32")
    xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "2048")
    xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
    xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
    xs.conf.set(C.CROSSPROC_ADAPTIVE_REPLAN.key,
                "true" if adaptive else "false")
    for name, data in (
            ("fact", {"sk": f_sk[mine], "price": f_price[mine]}),
            ("fact2", {"k2": k2[mine], "bonus": bonus[mine]}),
            ("skl", {"rk": s_rk[mine], "t": s_t[mine]}),
            ("skr", {"rk2": r_rk2[mine], "w2": r_w2[mine]})):
        xs.createDataFrame(data).createOrReplaceTempView(name)
    return xs, svc


oracle = session.newSession()
oracle.conf.set(C.MESH_SHARDS.key, "1")
for name, data in (("fact", {"sk": f_sk, "price": f_price}),
                   ("fact2", {"k2": k2, "bonus": bonus}),
                   ("skl", {"rk": s_rk, "t": s_t}),
                   ("skr", {"rk2": r_rk2, "w2": r_w2})):
    oracle.createDataFrame(data).createOrReplaceTempView(name)

# scenario 1/2: misestimated RIGHT side — the optimizer pushes the
# bonus filter below the join, so the observed map output is tiny while
# the plan-time leaf probe still sees all of fact2
Q_DEMOTE = ("SELECT sk, price, bonus FROM fact JOIN fact2 ON sk = k2 "
            "WHERE bonus < 2 ORDER BY sk, price, bonus")
# scenario 3: a different constant → a different plan signature, so the
# range lane freezes from the probe (no feedback shortcut) and the
# demotion happens at the stats barrier
Q_DEMOTE_R = ("SELECT sk, price, bonus FROM fact JOIN fact2 ON sk = k2 "
              "WHERE bonus < 3 ORDER BY sk, price, bonus")
Q_SKEW = ("SELECT rk, count(*) AS c, min(t) AS tlo, sum(w2) AS sw "
          "FROM skl JOIN skr ON rk = rk2 GROUP BY rk ORDER BY rk")
Q_AGG = ("SELECT sk, price, sb FROM fact JOIN "
         "(SELECT k2, sum(bonus) AS sb FROM fact2 GROUP BY k2) a "
         "ON sk = k2 ORDER BY sk, price, sb")
# trace/skew-decision modes: NO filter, so both observed sides stay far
# above the broadcast threshold and the adaptive re-decision keeps the
# frozen hash lane — the only way the armed process can diverge is the
# injected perturbation of its gathered stats view
Q_HASH = ("SELECT sk, price, bonus FROM fact JOIN fact2 ON sk = k2 "
          "ORDER BY sk, price, bonus")


def run(sess, sql):
    return [tuple(r) for r in sess.sql(sql).collect()]


def delta(svc, before):
    return {k: svc.counters[k] - before[k] for k in svc.counters}


if mode == "fault-adapt":
    xs, svc = make_session(root, adaptive=True)
    FaultInjector().attach(svc)       # plan comes from SPARK_TPU_FAULT_PLAN
    exp = run(oracle, Q_DEMOTE)
    t0 = time.time()
    try:
        got = run(xs, Q_DEMOTE)
    except (ExchangeFetchFailed, TimeoutError) as e:
        lost = sorted(getattr(e, "lost_hosts", []) or [])
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} {lost}", flush=True)
        os._exit(0)
    if got != exp:
        print(f"[p{pid}] PARTIAL got={len(got)} exp={len(exp)}", flush=True)
        os._exit(1)
    c = svc.counters
    print(f"[p{pid}] OK rows={len(got)} replans={c['adaptive_replans']} "
          f"demotions={c['strategy_demotions']} "
          f"bcast={c['broadcast_joins']} shuffled={c['shuffled_joins']}",
          flush=True)
    os._exit(0)

if mode in ("trace", "skew-decision"):
    xs, svc = make_session(root, adaptive=True)
    # the decision-trace backstop must run deterministically here,
    # pytest parent or not (bin/chaos launches this worker too)
    xs.conf.set(C.ANALYSIS_VERIFY_PLANS.key, "true")
    if mode == "skew-decision":
        FaultInjector().attach(svc)   # plan from SPARK_TPU_FAULT_PLAN
    exp = run(oracle, Q_HASH)
    t0 = time.time()
    try:
        got = run(xs, Q_HASH)
    except PlanInvariantError as e:
        st = getattr(xs, "_analysis_stats", {})
        print(f"[p{pid}] FAILED-DIVERGED {time.time() - t0:.2f} "
              f"prop={e.property} div="
              f"{st.get('decision_trace_divergence', 0)} detail={e}",
              flush=True)
        os._exit(0)
    except (ExchangeFetchFailed, TimeoutError) as e:
        lost = sorted(getattr(e, "lost_hosts", []) or [])
        print(f"[p{pid}] FAILED {time.time() - t0:.2f} {lost}",
              flush=True)
        os._exit(0)
    if got != exp:
        print(f"[p{pid}] PARTIAL got={len(got)} exp={len(exp)}",
              flush=True)
        os._exit(1)
    if mode == "trace":
        # the range lane's trace (cut points + skew-split estimate)
        # rides the same check: pin the range lane and run the skew join
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "true")
        exp_s = run(oracle, Q_SKEW)
        got_s = run(xs, Q_SKEW)
        if got_s != exp_s:
            print(f"[p{pid}] PARTIAL got={len(got_s)} exp={len(exp_s)}",
                  flush=True)
            os._exit(1)
    st = getattr(xs, "_analysis_stats", {})
    print(f"[p{pid}] TRACE-OK rows={len(got)} "
          f"checks={st.get('decision_trace_checks', 0)} "
          f"div={st.get('decision_trace_divergence', 0)}", flush=True)
    os._exit(0)

xs, svc = make_session(root, adaptive=True)

# -- 1. hash lane demotes to broadcast at the stats barrier -----------------
exp = run(oracle, Q_DEMOTE)
before = dict(svc.counters)
got_adaptive = run(xs, Q_DEMOTE)
d = delta(svc, before)
assert got_adaptive == exp, (len(got_adaptive), len(exp))
assert d["adaptive_replans"] == 1, d
assert d["strategy_demotions"] == 1, d
assert d["broadcast_joins"] == 1 and d["shuffled_joins"] == 0, d
assert len(xs.statsFeedback) >= 2, xs.statsFeedback.snapshot()
print(f"[p{pid}] DEMOTE-OK ({len(exp)} rows)", flush=True)

# -- 2. the recorded cardinality decides broadcast at PLAN time -------------
before = dict(svc.counters)
assert run(xs, Q_DEMOTE) == exp
d = delta(svc, before)
assert d["stats_feedback_hits"] >= 1, d
assert d["broadcast_joins"] == 1 and d["shuffled_joins"] == 0, d
assert d["adaptive_replans"] == 0, d      # no exchange, no stats barrier
print(f"[p{pid}] FEEDBACK-OK ({len(exp)} rows)", flush=True)

# -- 3. range lane demotes too ----------------------------------------------
xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "true")
exp_r = run(oracle, Q_DEMOTE_R)
before = dict(svc.counters)
assert run(xs, Q_DEMOTE_R) == exp_r
d = delta(svc, before)
assert d["adaptive_replans"] == 1, d
assert d["strategy_demotions"] == 1, d
assert d["broadcast_joins"] == 1 and d["range_merge_joins"] == 0, d
xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
print(f"[p{pid}] RANGE-DEMOTE-OK ({len(exp_r)} rows)", flush=True)

# -- 4. frozen comparison: same query, adaptiveReplan off -------------------
fz, fsvc = make_session(root + "-frozen", adaptive=False)
before = dict(fsvc.counters)
got_frozen = run(fz, Q_DEMOTE)
d = delta(fsvc, before)
assert got_frozen == exp == got_adaptive
assert d["shuffled_joins"] == 1 and d["broadcast_joins"] == 0, d
assert d["adaptive_replans"] == 0 and d["strategy_demotions"] == 0, d
print(f"[p{pid}] FROZEN-OK ({len(got_frozen)} rows)", flush=True)

# -- 5. post-sample skew re-split -------------------------------------------
xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")   # pin the range lane
xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "true")
exp_s = run(oracle, Q_SKEW)
before = dict(svc.counters)
assert run(xs, Q_SKEW) == exp_s
d = delta(svc, before)
assert d["range_merge_joins"] == 1, d
assert d["spans_split"] >= 1, d
assert d["post_sample_skew_splits"] >= 1, d
xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
print(f"[p{pid}] SKEW-OK ({len(exp_s)} rows)", flush=True)

# -- 6. partial aggregate pushdown below the join exchange ------------------
exp_a = run(oracle, Q_AGG)
before = dict(svc.counters)
got_pushed = run(xs, Q_AGG)
d = delta(svc, before)
assert got_pushed == exp_a, (len(got_pushed), len(exp_a))
assert d["shuffled_joins"] == 1, d
assert d["strategy_demotions"] == 0, d    # an agg side never demotes
xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "false")
got_unpushed = run(xs, Q_AGG)             # generic gather, same session
xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
assert got_unpushed == exp_a
print(f"[p{pid}] AGGPUSH-OK ({len(exp_a)} rows)", flush=True)

c = svc.counters
print(f"[p{pid}] ADAPT-OK replans={c['adaptive_replans']} "
      f"demotions={c['strategy_demotions']} "
      f"fbhits={c['stats_feedback_hits']} "
      f"postskew={c['post_sample_skew_splits']} "
      f"bcast={c['broadcast_joins']} shuffled={c['shuffled_joins']} "
      f"range={c['range_merge_joins']}", flush=True)
os._exit(0)
