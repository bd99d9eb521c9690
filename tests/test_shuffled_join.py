"""Partitioned shuffled hash join over the DCN exchange (tentpole).

Two layers:

- unit tests (single process, service-level): the manifest-only size
  exchange, the deterministic coalescing reducer planner
  (ExchangeCoordinator analog), the equi-key extractor, and the
  single-process degenerate case (flag on, nothing partitioned → the
  generic path, results unchanged);
- subprocess parity harness (2 and 3 REAL processes,
  ``shuffled_join_worker.py``): randomized-but-seeded plans — inner /
  left / semi joins of two partitioned leaves, with and without a keyed
  Aggregate above, with a deliberately skewed hot key — run through the
  RANGE sort-merge path, the shuffled-hash path AND the forced gather
  path, all byte-identical to a full-data single-process oracle; the
  workers also assert the path counters (``range_merge_joins``,
  ``shuffled_joins``, ``fast_path_aggs``), that coalescing merged
  sub-target fine partitions, and that the hot key forced a skew-span
  split — without changing any result.

The range-specific service machinery (the strict manifest round and the
skew-splitting span→reducer planner) gets direct unit tests here too.
"""

import os

import numpy as np
import pytest

from spark_tpu import config as C
from spark_tpu.parallel.hostshuffle import HostShuffleService
from worker_procs import run_exchange_workers

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "shuffled_join_worker.py")


# ---------------------------------------------------------------------------
# reducer planning: deterministic coalescing from manifest byte counts
# ---------------------------------------------------------------------------

def _svc(tmp_path, pid=0, n=2, **kw):
    kw.setdefault("timeout_s", 5.0)
    kw.setdefault("poll_s", 0.02)
    return HostShuffleService(str(tmp_path), pid, n, **kw)


def test_plan_reducers_static_when_target_zero(tmp_path):
    svc = _svc(tmp_path)
    bounds = svc.plan_reducers(np.array([5] * 16, np.int64), 0)
    assert bounds == [0, 8, 16]
    assert svc.counters["partitions_coalesced"] == 0


def test_plan_reducers_coalesces_tiny_partitions(tmp_path):
    svc = _svc(tmp_path)
    sizes = np.array([10, 10, 10, 10, 500, 10, 10, 10], np.int64)
    bounds = svc.plan_reducers(sizes, 100)
    assert bounds[0] == 0 and bounds[-1] == len(sizes)
    assert len(bounds) - 1 <= svc.n                # never more groups than procs
    assert svc.counters["partitions_coalesced"] > 0
    # group bytes land in the skew gauge inputs
    assert sum(svc.last_partition_bytes) == int(sizes.sum())


def test_plan_reducers_flags_skewed_groups(tmp_path):
    svc = _svc(tmp_path, n=4)
    # one hot key range, three near-empty ones → the hot group exceeds
    # SKEW_FACTOR x median and must be flagged (not silently absorbed)
    sizes = np.array([1, 1, 1, 100000, 1, 1, 1, 1], np.int64)
    svc.plan_reducers(sizes, 2)
    assert svc.counters["partitions_skewed"] >= 1


def test_plan_reducers_deterministic_across_processes(tmp_path):
    sizes = np.array([37, 0, 12, 900, 4, 4, 4, 250, 0, 66], np.int64)
    b0 = _svc(tmp_path / "a", pid=0).plan_reducers(sizes, 200)
    b1 = _svc(tmp_path / "b", pid=1).plan_reducers(sizes, 200)
    assert b0 == b1                      # no driver: same inputs, same plan


def test_publish_and_gather_sizes_roundtrip(tmp_path):
    svc0, svc1 = _svc(tmp_path, 0), _svc(tmp_path, 1)
    svc0.publish_sizes("e", {0: 100, 2: 50})
    svc1.publish_sizes("e", {0: 11, 3: 7})
    t0 = svc0.gather_sizes("e", 4)
    t1 = svc1.gather_sizes("e", 4)
    assert t0.tolist() == t1.tolist() == [111, 0, 50, 7]


def test_publish_sizes_is_single_use(tmp_path):
    svc = _svc(tmp_path)
    svc.publish_sizes("e", {0: 1})
    with pytest.raises(ValueError):
        svc.publish_sizes("e", {0: 1})


# ---------------------------------------------------------------------------
# range exchange coordination: strict manifest rounds + span planning
# ---------------------------------------------------------------------------

def test_publish_and_gather_manifests_roundtrip(tmp_path):
    svc0, svc1 = _svc(tmp_path, 0), _svc(tmp_path, 1)
    n0 = svc0.publish_manifest("e", {"sample": {"points": [1, 2]}})
    n1 = svc1.publish_manifest("e", {"sample": {"points": [9]}})
    mans, total = svc0.gather_manifests("e")
    assert mans[0]["sample"]["points"] == [1, 2]
    assert mans[1]["sample"]["points"] == [9]
    assert total == n0 + n1 > 0


def test_gather_manifests_strict_rejects_unreadable(tmp_path):
    """The coordination-round contract: a committed-but-unparseable
    manifest must FAIL the round (bounded), never be silently skipped —
    skipping would let processes derive DIFFERENT cut points."""
    from spark_tpu.parallel.hostshuffle import ExchangeFetchFailed
    svc0, svc1 = _svc(tmp_path, 0, timeout_s=0.5), _svc(tmp_path, 1)
    svc0.publish_manifest("e")
    svc1.publish_manifest("e", {"sample": {}})
    with open(svc1._done("e", 1), "wb") as f:   # torn write, size intact
        f.write(b"\x82{ not json")
    with pytest.raises(ExchangeFetchFailed) as ei:
        svc0.gather_manifests("e", strict=True)
    assert ei.value.lost_hosts == ["host-1"]
    # non-strict (size rounds): legacy skip-if-unreadable is preserved
    mans, _ = svc0.gather_manifests("e")
    assert 0 in mans and 1 not in mans


def test_plan_range_reducers_splits_skewed_span(tmp_path):
    svc = _svc(tmp_path, n=2)
    probe = np.array([10, 10, 100000, 10, 10], np.int64)
    build = np.array([5, 5, 50, 5, 5], np.int64)
    owners = svc.plan_range_reducers(probe, build, 2048)
    # hot span 2 is split across BOTH processes, others single-owner
    assert sorted(owners[2]) == [0, 1]
    assert all(len(owners[s]) == 1 for s in (0, 1, 3, 4))
    assert svc.counters["spans_split"] == 1
    # load model: split probe halves + build REPLICATED to each owner
    normal = int((probe + build).sum() - probe[2] - build[2])
    assert sum(svc.last_partition_bytes) \
        == normal + 2 * (int(probe[2]) // 2 + int(build[2]))


def test_plan_range_reducers_coalesces_and_is_deterministic(tmp_path):
    probe = np.array([7, 7, 7, 7, 7, 7, 7, 7], np.int64)
    build = np.zeros(8, np.int64)
    o0 = _svc(tmp_path / "a", pid=0).plan_range_reducers(probe, build, 100)
    o1 = _svc(tmp_path / "b", pid=1).plan_range_reducers(probe, build, 100)
    assert o0 == o1                      # no driver: same inputs, same plan
    assert all(len(ps) == 1 for ps in o0)
    svc = _svc(tmp_path / "c")
    svc.plan_range_reducers(probe, build, 100)
    assert svc.counters["partitions_coalesced"] > 0
    assert svc.counters["spans_split"] == 0   # uniform → nothing to split


def test_range_bucket_spans_and_duplicates():
    from spark_tpu.kernels import range_bucket
    cuts = np.array([10, 20], np.int64)
    keys = np.array([-5, 9, 10, 15, 20, 99, 10, 10], np.int64)
    spans = range_bucket(np, keys, cuts)
    assert spans.dtype == np.int32
    assert spans.tolist() == [0, 0, 1, 1, 2, 2, 1, 1]
    # all duplicates of a value land in ONE span (hot-key cohesion)
    assert len({s for k, s in zip(keys.tolist(), spans.tolist())
                if k == 10}) == 1
    # no cuts → everything in span 0 (single-span degenerate case)
    assert range_bucket(np, keys, np.zeros(0, np.int64)).tolist() == [0] * 8


# ---------------------------------------------------------------------------
# equi-key extraction mirrors the join planner
# ---------------------------------------------------------------------------

def test_equi_join_keys_using_and_condition(spark):
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.joins import equi_join_keys

    a = spark.createDataFrame({"k": np.arange(4), "v": np.arange(4)})
    b = spark.createDataFrame({"k2": np.arange(4), "w": np.arange(4)})
    # explicit equi condition → one (left, right) pair
    j = a.join(b, on=a["k"] == b["k2"])._plan
    assert len(equi_join_keys(j)) == 1
    # USING column → Col(name) on both sides
    c = spark.createDataFrame({"k": np.arange(4), "w": np.arange(4)})
    j2 = a.join(c, on="k")._plan
    [(l2, r2)] = equi_join_keys(j2)
    assert isinstance(j2, L.Join) and l2.name == r2.name == "k"
    # cross join: no hash keys → empty (shuffled path must decline)
    j3 = a.crossJoin(b)._plan
    assert equi_join_keys(j3) == []


def test_shuffled_join_flag_is_safe_single_process(spark, tmp_path):
    """n=1: every leaf is trivially 'replicated', so the flag must leave
    results unchanged (generic path) rather than shuffling with itself."""
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        spark.createDataFrame(
            {"k": np.arange(8) % 3, "v": np.arange(8)}
        ).createOrReplaceTempView("ta")
        spark.createDataFrame(
            {"k2": np.arange(6) % 3, "w": np.arange(6) * 10}
        ).createOrReplaceTempView("tb")
        got = [tuple(r) for r in spark.sql(
            "SELECT k, count(*) AS c, sum(w) AS s FROM ta "
            "JOIN tb ON k = k2 GROUP BY k ORDER BY k").collect()]
        assert got == [(0, 6, 90), (1, 6, 150), (2, 4, 140)]
        assert svc.counters["shuffled_joins"] == 0
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


# ---------------------------------------------------------------------------
# the real thing: parity across REAL processes, shuffled vs gather vs oracle
# ---------------------------------------------------------------------------

def _run_parity(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "parity",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        assert f"[p{pid}] ALL-OK" in out, out
        # the battery covered every path and the coalescer + skew
        # splitter both fired
        assert "range=5" in out and "shuffled=5" in out, out
        assert "fast=6" in out, out
    return outs


def test_parity_two_processes(tmp_path):
    _run_parity(tmp_path, 2)


@pytest.mark.slow
def test_parity_three_processes(tmp_path):
    _run_parity(tmp_path, 3)


# ---------------------------------------------------------------------------
# spill parity: the same battery forced through the disk-spill path
# ---------------------------------------------------------------------------

def _run_spill_parity(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "spill",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        # the full battery passed against the oracle AND the spill path
        # demonstrably ran under the capped ledger
        assert f"[p{pid}] SPILL-OK" in out, out
        assert "PARITY-FAIL" not in out, out
    return outs


def test_spill_parity_two_processes(tmp_path):
    _run_spill_parity(tmp_path, 2)


@pytest.mark.slow
def test_spill_parity_three_processes(tmp_path):
    _run_spill_parity(tmp_path, 3)


# ---------------------------------------------------------------------------
# grace parity: a host budget CAPPED below the reducers' drained working
# set — every join must still complete byte-identical to the oracle by
# re-bucketing the sink into spill files and joining bucket-by-bucket
# ---------------------------------------------------------------------------

def _run_grace_parity(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "grace",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        assert f"[p{pid}] GRACE-OK" in out, out
        assert "GRACE-PARITY-FAIL" not in out, out
        # the worker itself asserted elastic narrowing, grace activity
        # (both processes at n=2) and peak <= budget before printing OK
        line = [ln for ln in out.splitlines()
                if f"[p{pid}] GRACE-OK" in ln][-1]
        if n == 2:
            assert "buckets=0" not in line, out
            assert "resplits=0" not in line, out
    return outs


def test_grace_parity_two_processes(tmp_path):
    _run_grace_parity(tmp_path, 2)


@pytest.mark.slow
def test_grace_parity_three_processes(tmp_path):
    _run_grace_parity(tmp_path, 3)


# ---------------------------------------------------------------------------
# run-codes parity: run-encoded vs raw wire on BOTH exchange lanes over a
# time-series-shaped workload (sorted key runs + a dictionary+RLE composed
# status column), under the forced-spill conf so encoded frames also stage
# through disk without inflating — every leg oracle-exact
# ---------------------------------------------------------------------------

def _run_runcodes_parity(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "runcodes",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        # the worker asserted the gauge side (rle_columns_encoded,
        # run_bytes_saved, run_aware_op_rows, runs_materialized, spill
        # under the capped ledger) before printing its OK line
        assert f"[p{pid}] RUNCODES-OK" in out, out
        assert "RC-PARITY-FAIL" not in out, out
        line = [ln for ln in out.splitlines()
                if f"[p{pid}] RUNCODES-OK" in ln][-1]
        assert "rle=0" not in line and "runaware=0" not in line, out
    return outs


def test_runcodes_parity_two_processes(tmp_path):
    _run_runcodes_parity(tmp_path, 2)


@pytest.mark.slow
def test_runcodes_parity_three_processes(tmp_path):
    _run_runcodes_parity(tmp_path, 3)
