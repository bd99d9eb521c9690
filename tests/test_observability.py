"""Per-operator SQL metrics + listener bus + event log
(SQLMetrics.scala:34 / LiveListenerBus / EventLoggingListener analogs)."""

import json
import os
import time

import numpy as np
import pandas as pd
import pytest

import spark_tpu.config as C
from spark_tpu.sql import functions as F


@pytest.fixture()
def mdf(spark):
    return spark.createDataFrame(pd.DataFrame({
        "k": np.arange(100, dtype=np.int64) % 7,
        "v": np.arange(100, dtype=np.float64)}))


def test_operator_metrics(spark, mdf):
    spark.conf.set(C.METRICS_ENABLED.key, "true")
    try:
        mdf.filter(F.col("v") < 50).groupBy("k").agg(
            F.sum("v").alias("s")).collect()
        m = spark._last_qe.metrics
    finally:
        spark.conf.set(C.METRICS_ENABLED.key, "false")
    by_label = {}
    for (oid, label), v in m.items():
        by_label.setdefault(label, []).append(v)
    assert by_label["Filter"] == [50]
    assert by_label["Aggregate"] == [7]
    assert "Scan[0]" in by_label or any(
        lbl.startswith("Scan") for lbl in by_label)


def test_metrics_interpreted_lane(spark, mdf):
    spark.conf.set(C.METRICS_ENABLED.key, "true")
    spark.conf.set(C.CODEGEN_ENABLED.key, "false")
    try:
        mdf.filter(F.col("v") < 10).collect()
        m = spark._last_qe.metrics
    finally:
        spark.conf.set(C.CODEGEN_ENABLED.key, "true")
        spark.conf.set(C.METRICS_ENABLED.key, "false")
    assert any(lbl == "Filter" and v == 10 for (_o, lbl), v in m.items())


def test_listener_bus(spark, mdf):
    events = []
    spark.listenerManager.register(events.append)
    try:
        mdf.count()
    finally:
        spark.listenerManager.unregister(events.append)
    kinds = [e["event"] for e in events]
    assert "SQLExecutionStart" in kinds and "SQLExecutionEnd" in kinds
    end = [e for e in events if e["event"] == "SQLExecutionEnd"][-1]
    assert end["durationMs"] >= 0


def test_listener_failure_does_not_break_query(spark, mdf):
    def bad(_e):
        raise RuntimeError("boom")
    spark.listenerManager.register(bad)
    try:
        assert mdf.count() == 100
    finally:
        spark.listenerManager.unregister(bad)


def test_event_log(spark, mdf, tmp_path):
    d = str(tmp_path / "evlog")
    spark.conf.set(C.EVENT_LOG_DIR.key, d)
    try:
        mdf.filter(F.col("v") > 90).count()
    finally:
        spark.conf.set(C.EVENT_LOG_DIR.key, "")
    lines = [json.loads(x) for x in
             open(os.path.join(d, "eventlog.jsonl"))]
    assert any(e["event"] == "SQLExecutionStart" for e in lines)
    assert any(e["event"] == "SQLExecutionEnd" for e in lines)


def test_history_html_renderer(spark, mdf, tmp_path):
    """FsHistoryProvider analog: the JSON event log replays into one
    static HTML page with query durations, plans, and operator metrics."""
    d = str(tmp_path / "evlog2")
    spark.conf.set(C.EVENT_LOG_DIR.key, d)
    spark.conf.set(C.METRICS_ENABLED.key, "true")
    try:
        mdf.filter(F.col("v") > 50).count()
    finally:
        spark.conf.set(C.EVENT_LOG_DIR.key, "")
        spark.conf.set(C.METRICS_ENABLED.key, "false")
    # a failed execution's Start/End-with-error pair (runtime failures
    # post these through execute(); synthesized here to pin the format)
    with open(os.path.join(d, "eventlog.jsonl"), "a") as f:
        f.write(json.dumps({"event": "SQLExecutionStart", "time": 1.0,
                            "plan": "Project [boom]"}) + "\n")
        f.write(json.dumps({"event": "SQLExecutionEnd", "time": 2.0,
                            "durationMs": 1000.0,
                            "error": "RuntimeError: boom"}) + "\n")
    from spark_tpu.ui import render_history, write_history
    html_text = render_history(d)
    assert "FINISHED" in html_text
    assert "FAILED" in html_text
    assert "metrics" in html_text          # per-operator row counts block
    out = write_history(d)
    assert os.path.exists(out)
    assert open(out).read().startswith("<!doctype html>")


def test_history_cli_main(spark, mdf, tmp_path, capsys):
    d = str(tmp_path / "evlog3")
    spark.conf.set(C.EVENT_LOG_DIR.key, d)
    try:
        mdf.count()
    finally:
        spark.conf.set(C.EVENT_LOG_DIR.key, "")
    from spark_tpu import ui
    assert ui.main([d]) == 0
    printed = capsys.readouterr().out.strip()
    assert printed.endswith("history.html") and os.path.exists(printed)


def test_metrics_system_sources_and_sinks(spark, mdf, tmp_path):
    """MetricsSystem analog: process gauges snapshot on demand
    (`metrics/MetricsSystem.scala`); a failing gauge reads None and does
    not take the snapshot down."""
    from spark_tpu.metrics import Source
    ms = spark.metricsSystem
    before = ms.snapshots().get("queries", {}).get("executed", 0)
    mdf.count()
    snaps = ms.snapshots()
    assert snaps["queries"]["executed"] >= before + 1
    assert snaps["memory"]["hbm_budget_bytes"] > 0
    # custom source
    ms.register_source(Source("custom", {"answer": lambda: 42,
                                         "broken": lambda: 1 // 0}))
    try:
        assert ms.snapshots()["custom"] == {"answer": 42, "broken": None}
    finally:
        ms._sources = [s for s in ms._sources if s.name != "custom"]


def test_shuffle_range_gauges_exported(spark, tmp_path):
    """The range-exchange coordination plane is observable: cut-point
    count, skew-span splits, and sample-round manifest bytes surface as
    gauges on the session's shuffle metrics source."""
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        svc.publish_manifest("s", {"sample": {"points": [1, 2]}})
        _mans, nbytes = svc.gather_manifests("s")
        svc.counters["sample_bytes"] += nbytes
        svc.last_range_cutpoints = [10, 20]
        svc.plan_range_reducers(np.array([1, 1, 1000, 1], np.int64),
                                np.zeros(4, np.int64), 10)
        snap = ms.snapshots()["shuffle"]
        assert snap["range_cutpoints"] == 2
        assert snap["spans_split"] == 1          # the hot span was split
        assert snap["sample_bytes"] == nbytes > 0
        assert snap["partition_bytes_max"] >= snap["partition_bytes_median"]
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_adaptive_replan_gauges_exported(spark, tmp_path):
    """The adaptive execution plane is observable: stats-barrier
    re-decisions, strategy demotions, skew splits only the observed
    sizes revealed, and feedback-driven plan-time decisions all surface
    as gauges on the shuffle metrics source (zero until the counters
    move, so dashboards can alert on first divergence from the frozen
    plan)."""
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        snap0 = ms.snapshots()["shuffle"]
        for g in ("adaptive_replans", "strategy_demotions",
                  "post_sample_skew_splits", "stats_feedback_hits"):
            assert snap0[g] == 0, (g, snap0)
        svc.counters["adaptive_replans"] += 2
        svc.counters["strategy_demotions"] += 1
        svc.counters["post_sample_skew_splits"] += 3
        svc.counters["stats_feedback_hits"] += 4
        snap = ms.snapshots()["shuffle"]
        assert snap["adaptive_replans"] == 2
        assert snap["strategy_demotions"] == 1
        assert snap["post_sample_skew_splits"] == 3
        assert snap["stats_feedback_hits"] == 4
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_shuffle_dict_gauges_exported(spark, tmp_path):
    """Encoded execution is observable: dictionary columns framed as
    codes, sidecar bytes saved by the dedup, receiver-side code remaps,
    and output-boundary late materializations all surface as gauges on
    the shuffle metrics source."""
    from spark_tpu.columnar import ColumnBatch
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        snap0 = ms.snapshots()["shuffle"]
        assert snap0["dict_columns_encoded"] == 0
        assert snap0["dict_bytes_saved"] == 0
        assert snap0["codes_remapped"] == 0
        assert snap0["late_materialized_rows"] == 0
        # two blocks sharing one dictionary: the second frame dedups it
        b = ColumnBatch.from_arrays({"s": ["ash", "oak", "ash"]})
        svc.put("dg1", 0, [b])
        svc.put("dg1", 0, [b])
        svc.commit("dg1")
        # an exchange whose own batches disagree on the dictionary:
        # the receiver unifies into one sorted code space
        ba = ColumnBatch.from_arrays({"s": ["ash", "oak"]})
        bb = ColumnBatch.from_arrays({"s": ["fir", "oak"]})
        out = svc.exchange("dg2", {0: [ba, bb]})
        dicts = {v.dictionary for r in out for v in r.vectors}
        assert dicts == {("ash", "fir", "oak")}
        # late materialization: decoding codes to words at the boundary
        out[0].to_pylist()
        snap = ms.snapshots()["shuffle"]
        assert snap["dict_columns_encoded"] == 2
        assert snap["dict_bytes_saved"] > 0
        assert snap["codes_remapped"] > 0
        assert snap["late_materialized_rows"] > 0
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_shuffle_run_gauges_exported(spark, tmp_path):
    """Run-length execution is observable: columns shipped as run/delta
    codes, wire bytes saved, rows the run-aware operators processed
    without expansion, and rows re-inflated at materialization
    boundaries all surface as gauges on the shuffle metrics source."""
    from spark_tpu import types as T
    from spark_tpu.columnar import ColumnBatch, RunColumnVector
    from spark_tpu.expressions import Col, GT, Literal
    from spark_tpu.kernels import apply_filter
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        assert svc.run_codes                       # default-on conf
        snap0 = ms.snapshots()["shuffle"]
        for g in ("rle_columns_encoded", "run_bytes_saved",
                  "run_aware_op_rows", "runs_materialized"):
            assert snap0[g] == 0, (g, snap0)
        # a run-shaped block RLE-encodes on the put path
        b = ColumnBatch.from_arrays(
            {"v": np.repeat(np.arange(4, dtype=np.int64), 64)})
        svc.put("rg", 0, [b])
        svc.commit("rg")
        # a run-aware filter over a lazy run vector, then the explicit
        # materialization boundary
        rv = RunColumnVector(np.asarray([1, 2], np.int64),
                             np.asarray([32, 32], np.int64), T.int64)
        rb = ColumnBatch(["x"], [rv], None, 64)
        apply_filter(np, rb, GT(Col("x"), Literal(1, T.int64)))
        np.asarray(rv.data)
        snap = ms.snapshots()["shuffle"]
        assert snap["rle_columns_encoded"] >= 1
        assert snap["run_bytes_saved"] > 0
        assert snap["run_aware_op_rows"] == 64
        assert snap["runs_materialized"] == 64
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_run_activity_in_status(spark, tmp_path):
    """/status surfaces per-session run-length execution activity the
    same way it surfaces ICI/grace: {} while quiet, live gauges once
    columns ship encoded or run-aware operators fire."""
    import urllib.request

    from spark_tpu import columnar as _col
    from spark_tpu.server import SQLServer
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    srv = None
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        srv = SQLServer(spark, port=0).start()

        def status():
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/status",
                    timeout=30) as r:
                return json.loads(r.read())

        st = status()
        assert st["runActivity"] == {}            # codes never engaged
        svc.counters["rle_columns_encoded"] += 3
        svc.counters["run_bytes_saved"] += 2048
        _col.bump_run_aware(128)
        st = status()
        got = st["runActivity"]["default"]
        assert got["rle_columns_encoded"] == 3
        assert got["run_bytes_saved"] == 2048
        assert got["run_aware_op_rows"] == 128
    finally:
        if srv is not None:
            srv.stop()
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_spill_and_ledger_gauges_exported(spark, tmp_path):
    """Memory-pressure handling is observable: spill bytes/events, fetch
    backpressure waits, and the host ledger's peak/budget surface as
    gauges on the shuffle source — and the session memory source mirrors
    the same ledger."""
    import threading

    from spark_tpu.parallel.hostshuffle import _InflightGate
    prev = getattr(spark, "_crossproc_svc", None)
    prev_ledger = getattr(spark, "_host_ledger", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        snap0 = ms.snapshots()["shuffle"]
        assert snap0["spill_bytes"] == 0
        assert snap0["spill_events"] == 0
        assert snap0["fetch_backpressure_waits"] == 0
        assert snap0["host_budget_bytes"] > 0
        # a spill write counts bytes and events
        svc.spill_write(str(tmp_path / "r.spill"), b"z" * 2048)
        # a ledger reservation moves the peak (and releases cleanly)
        svc.ledger.reserve("shuffle:test", 4096)
        svc.ledger.release("shuffle:test")
        # the in-flight gate reports each wait through the service hook
        gate = _InflightGate(16, on_wait=svc._count_backpressure)
        gate.acquire(10)
        t = threading.Timer(0.05, lambda: gate.release(10))
        t.start()
        gate.acquire(10)                   # must wait for the release
        gate.release(10)
        t.join()
        snap = ms.snapshots()["shuffle"]
        assert snap["spill_bytes"] == 2048
        assert snap["spill_events"] == 1
        assert snap["fetch_backpressure_waits"] == 1
        assert snap["peak_host_bytes"] >= 4096
        # the session memory source reads the SAME ledger
        memsnap = ms.snapshots()["memory"]
        assert memsnap["host_budget_bytes"] == snap["host_budget_bytes"]
        assert memsnap["host_peak_bytes"] == snap["peak_host_bytes"]
        assert memsnap["host_used_bytes"] == 0
    finally:
        spark._crossproc_svc = prev
        spark._host_ledger = prev_ledger
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_memory_leak_check_releases(spark, mdf):
    """Executor.scala's 'managed memory leak detected' idiom: a leaked
    execution reservation is detected and released after the query."""
    from spark_tpu.sql.planner import QueryExecution
    qe = QueryExecution(spark, mdf._plan)
    spark._memory.acquire_execution(f"query:{id(qe)}", 1234)
    qe.execute()
    assert f"query:{id(qe)}" not in spark._memory._execution


def test_analysis_verifier_gauges(spark, mdf):
    """The plan verifier's accounting rides the session metrics system:
    plans_verified increments per verified plan (verifyPlans=auto is ON
    under pytest) and plan_verify_ms accumulates wall time."""
    ms = spark.metricsSystem
    before = ms.snapshots()["analysis"]
    mdf.filter(F.col("v") < 10).count()
    after = ms.snapshots()["analysis"]
    assert after["plans_verified"] > before["plans_verified"]
    assert after["plan_verify_ms"] >= before["plan_verify_ms"]
    assert after["plan_verify_ms"] < 60_000  # sanity: ms, not seconds


def test_decision_trace_gauges_exported(spark):
    """The replica-determinism backstop's accounting rides the same
    analysis Source: every verify_decision_trace call bumps
    decision_trace_checks, a caught divergence bumps
    decision_trace_divergence — the gauge an operator alarms on."""
    from spark_tpu import types as T
    from spark_tpu.analysis import PlanInvariantError
    from spark_tpu.analysis import runtime as az_rt
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.expressions import Col
    from spark_tpu.sql import logical as L

    ms = spark.metricsSystem
    before = ms.snapshots()["analysis"]
    assert before["decision_trace_divergence"] == 0
    inputs = {"frozen": "hash", "epoch": 0, "live": [0, 1], "adopt": []}
    arr = np.asarray([1], dtype=np.int64)
    rel = L.LocalRelation(ColumnBatch(
        ["k"], [ColumnVector(arr, T.LongType())], np.ones(1, bool), 1))
    join = L.Join(rel, rel, "inner", on=Col("k") == Col("k"))
    mans = {0: {"dtrace": {"h": az_rt.decision_trace(inputs),
                           "c": inputs}}}
    az_rt.verify_decision_trace(spark, join, None, "xq000001-plan",
                                mans, inputs)
    theirs = dict(inputs, epoch=1)
    mans[1] = {"dtrace": {"h": az_rt.decision_trace(theirs),
                          "c": theirs}}
    with pytest.raises(PlanInvariantError):
        az_rt.verify_decision_trace(spark, join, None, "xq000001-plan",
                                    mans, inputs)
    after = ms.snapshots()["analysis"]
    assert after["decision_trace_checks"] == \
        before["decision_trace_checks"] + 2
    assert after["decision_trace_divergence"] == 1


def test_stage_compile_gauges_exported(spark, mdf):
    """ISSUE 11 observability: the process stage-executable cache rides
    the session metrics system as the 'compile' Source — compile cost,
    hit/miss counters, fusion width (ops_per_stage) all live gauges."""
    ms = spark.metricsSystem
    before = ms.snapshots()["compile"]
    for key in ("stage_compile_ms", "stage_cache_hits",
                "stage_cache_misses", "stage_cache_entries",
                "stage_dispatches", "stages_fused", "ops_per_stage"):
        assert key in before, key
    mdf.groupBy("k").agg(F.sum("v")).collect()
    mdf.groupBy("k").agg(F.sum("v")).collect()   # second run: warm
    after = ms.snapshots()["compile"]
    assert after["stage_dispatches"] > before["stage_dispatches"]
    assert after["stage_cache_hits"] > before["stage_cache_hits"]
    assert after["stages_fused"] >= 1
    assert after["ops_per_stage"] >= 1.0
    assert after["stage_compile_ms"] >= 0.0
    # warm reuse must not have built a new executable for the repeat
    assert after["stage_cache_entries"] >= 1


def test_grace_and_elastic_gauges_exported(spark, tmp_path):
    """ISSUE 13 observability: graceful-degradation and elastic-reducer
    activity ride the shuffle Source as live gauges — grace bucket
    count, grace spill bytes, salted re-splits, and the planned vs
    observed vs narrowed reducer tallies."""
    prev = getattr(spark, "_crossproc_svc", None)
    prev_ledger = getattr(spark, "_host_ledger", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        snap0 = ms.snapshots()["shuffle"]
        for key in ("grace_buckets_used", "grace_spill_bytes",
                    "grace_salted_resplits", "reducers_planned",
                    "reducers_observed", "reducers_elastic"):
            assert key in snap0, key
            assert snap0[key] == 0, (key, snap0[key])
        svc.counters["grace_buckets_used"] += 3
        svc.counters["grace_spill_bytes"] += 4096
        svc.counters["grace_salted_resplits"] += 1
        svc.counters["reducers_planned"] += 4
        svc.counters["reducers_observed"] += 2
        svc.counters["reducers_elastic"] += 1
        snap = ms.snapshots()["shuffle"]
        assert snap["grace_buckets_used"] == 3
        assert snap["grace_spill_bytes"] == 4096
        assert snap["grace_salted_resplits"] == 1
        assert snap["reducers_planned"] == 4
        assert snap["reducers_observed"] == 2
        assert snap["reducers_elastic"] == 1
    finally:
        spark._crossproc_svc = prev
        spark._host_ledger = prev_ledger
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_ici_tier_gauges_exported(spark, tmp_path):
    """The two-tier exchange is observable: device-tier exchange count
    and HBM bytes moved, host-tier fallbacks, and the agreed tier
    split's peer count all ride the shuffle Source as live gauges —
    zero until the tier engages, so dashboards can alert on the first
    fallback (ICI degraded to DCN) the moment it happens."""
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        snap0 = ms.snapshots()["shuffle"]
        for key in ("ici_exchanges", "ici_bytes_moved",
                    "dcn_fallback_exchanges", "tier_split_peers"):
            assert key in snap0, key
            assert snap0[key] == 0, (key, snap0[key])
        svc.counters["ici_exchanges"] += 5
        svc.counters["ici_bytes_moved"] += 1 << 20
        svc.counters["dcn_fallback_exchanges"] += 1
        svc.counters["tier_split_peers"] = 3
        snap = ms.snapshots()["shuffle"]
        assert snap["ici_exchanges"] == 5
        assert snap["ici_bytes_moved"] == 1 << 20
        assert snap["dcn_fallback_exchanges"] == 1
        assert snap["tier_split_peers"] == 3
    finally:
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_ici_activity_in_status(spark, tmp_path):
    """/status surfaces per-session device-tier activity the same way
    it surfaces grace degradation: {} while quiet, live counters once
    the tier moves bytes or folds back."""
    import urllib.request

    from spark_tpu.server import SQLServer
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    srv = None
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        srv = SQLServer(spark, port=0).start()

        def status():
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/status",
                    timeout=30) as r:
                return json.loads(r.read())

        st = status()
        assert st["iciActivity"] == {}            # tier never engaged
        svc.counters["ici_exchanges"] += 2
        svc.counters["ici_bytes_moved"] += 4096
        svc.counters["dcn_fallback_exchanges"] += 1
        st = status()
        got = st["iciActivity"]["default"]
        assert got["ici_exchanges"] == 2
        assert got["ici_bytes_moved"] == 4096
        assert got["dcn_fallback_exchanges"] == 1
    finally:
        if srv is not None:
            srv.stop()
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


def test_grace_activity_in_status_and_admission(spark, tmp_path):
    """/status surfaces per-session grace activity, and the admission
    controller both reports the cluster-wide degraded-event total and
    widens its memory headroom floor while degradation is live."""
    import urllib.request

    from spark_tpu.server import SQLServer
    prev = getattr(spark, "_crossproc_svc", None)
    prev_ledger = getattr(spark, "_host_ledger", None)
    ms = spark.metricsSystem
    srv = None
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        srv = SQLServer(spark, port=0).start()

        def status():
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/status",
                    timeout=30) as r:
                return json.loads(r.read())

        st = status()
        assert st["graceActivity"] == {}          # quiet cluster
        assert st["admission"]["graceDegraded"] == 0
        svc.counters["grace_buckets_used"] += 2
        svc.counters["grace_spill_bytes"] += 8192
        st = status()
        got = st["graceActivity"]["default"]
        assert got["grace_buckets_used"] == 2
        assert got["grace_spill_bytes"] == 8192
        assert st["admission"]["graceDegraded"] == 2
        ac = srv._admission
        assert ac._grace() == 2
        assert ac.GRACE_HEADROOM_FACTOR > 1.0
    finally:
        if srv is not None:
            srv.stop()
        spark._crossproc_svc = prev
        spark._host_ledger = prev_ledger
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]


# ---------------------------------------------------------------------------
# ISSUE 15 observability: standing-query state/recovery gauges on the
# `streaming` Source — state residency in the host ledger, watermark
# progress, eviction counts, and wire-format spill under a capped budget
# with byte parity against the uncapped run
# ---------------------------------------------------------------------------

@pytest.fixture()
def _single_shard(spark):
    """Streaming micro-batches run local single-shard; pin the shared
    session in case an earlier module leaked a wider mesh conf."""
    prev = spark.conf.get("spark.tpu.mesh.shards")
    spark.conf.set("spark.tpu.mesh.shards", "1")
    yield spark
    spark.conf.set("spark.tpu.mesh.shards", str(prev))


def _stream_feeds(spark, in_dir):
    def s(n):
        return int(n * 1_000_000)
    feeds = [[(s(1), "a", 1), (s(9), "b", 2)],
             [(s(20), "a", 4), (s(21), "b", 1)],
             [(s(35), "c", 8)],
             [(s(50), "a", 3), (s(51), "d", 9)]]
    os.makedirs(in_dir, exist_ok=True)
    for i, rows in enumerate(feeds):
        spark.createDataFrame({
            "ts": np.array([r[0] for r in rows], "datetime64[us]"),
            "k": [r[1] for r in rows],
            "v": np.array([r[2] for r in rows], np.int64),
        }).write.parquet(os.path.join(in_dir, f"f{i}"))


def _stream_lifetime(spark, in_dir, ckpt, out):
    from spark_tpu import types as T
    from spark_tpu.sql.dataframe import DataFrame
    from spark_tpu.streaming.core import (
        FileSink, FileStreamSource, StreamExecution, StreamingRelation)
    schema = T.StructType([
        T.StructField("ts", T.timestamp),
        T.StructField("k", T.string),
        T.StructField("v", T.int64)])
    src = FileStreamSource("parquet", in_dir, schema,
                          {"maxfilespertrigger": "1"})
    df = (DataFrame(spark, StreamingRelation(src))
          .withWatermark("ts", "5 seconds")
          .groupBy(F.window("ts", "10 seconds").alias("w"))
          .agg(F.sum("v").alias("s")))
    return StreamExecution(spark, df._plan, FileSink("json", out, {}),
                           "append", ckpt, 0.1, None)


def test_streaming_gauges_and_ledger_tenancy(_single_shard, spark, tmp_path):
    from spark_tpu.memory import HostMemoryLedger
    prev_ledger = getattr(spark, "_host_ledger", None)
    ms = spark.metricsSystem
    spark._host_ledger = HostMemoryLedger(budget=64 << 20)
    try:
        in_dir = str(tmp_path / "in")
        _stream_feeds(spark, in_dir)
        ex = _stream_lifetime(spark, in_dir, str(tmp_path / "ckpt"),
                              str(tmp_path / "out"))
        ex.process_all_available()
        snap = ms.snapshots()["streaming"]
        assert snap["standing_queries"] == 1
        assert snap["batches_committed"] == 4
        assert snap["replayed_batches"] == 0
        assert snap["stage_rebuilds_last"] == 0    # batch 4 ran cached
        assert snap["state_bytes"] > 0
        assert snap["state_rows"] > 0
        # watermark advanced to max_event - 5s of the last feed
        assert snap["watermark_us"] == 51_000_000 - 5_000_000
        # append mode finalized + evicted the closed windows
        assert snap["evicted_rows"] > 0
        assert snap["spill_events"] == 0           # budget was ample
        assert "state_versions_spilled" in snap
        # the resident state is a ledger tenant under the stream's owner
        owner = f"stream:{ex.id[:8]}:state"
        assert spark._host_ledger.held(owner) == snap["state_bytes"]
        ex.stop()
        # stop() releases the whole tenancy prefix and leaves the Source
        assert spark._host_ledger.held(owner) == 0
        snap = ms.snapshots()["streaming"]
        assert snap["standing_queries"] == 0
        assert snap["state_bytes"] == 0
    finally:
        spark._host_ledger = prev_ledger


def test_streaming_state_spills_under_capped_ledger_with_parity(
        _single_shard, spark, tmp_path):
    """Capping the host ledger BELOW the streaming working set forces
    the state between micro-batches into wire-format spill files — the
    spill gauges light up, and the sink stays byte-identical to the
    uncapped run."""
    import glob

    from spark_tpu.memory import HostMemoryLedger
    prev_ledger = getattr(spark, "_host_ledger", None)
    try:
        in_dir = str(tmp_path / "in")
        _stream_feeds(spark, in_dir)

        def run(tag, budget):
            spark._host_ledger = HostMemoryLedger(budget=budget)
            ex = _stream_lifetime(spark, in_dir,
                                  str(tmp_path / f"{tag}-ckpt"),
                                  str(tmp_path / f"{tag}-out"))
            ex.process_all_available()
            metrics = dict(ex.metrics)
            ex.stop()
            files = {os.path.basename(p): open(p, "rb").read()
                     for p in sorted(glob.glob(
                         os.path.join(tmp_path, f"{tag}-out", "part-*")))}
            return metrics, files

        free_metrics, free_files = run("free", 64 << 20)
        capped_metrics, capped_files = run("capped", 256)  # < working set
        assert free_metrics["spill_events"] == 0
        assert capped_metrics["spill_events"] > 0
        assert capped_metrics["spill_bytes"] > 0
        # pressure changed WHERE state lived, never WHAT was emitted
        assert capped_files == free_files and free_files
    finally:
        spark._host_ledger = prev_ledger


# ---------------------------------------------------------------------------
# elastic-pool observability: the `pool` Source gauges + /status
# poolActivity (spawn/reap/target/live/decisions/failures)
# ---------------------------------------------------------------------------

POOL_GAUGES = ("workers_spawned", "workers_reaped", "pool_target",
               "pool_live", "scale_decisions", "spawn_failures")


def test_pool_source_registered_and_zero_when_pool_off(spark):
    """The `pool` Source exists on every server (gauges read through
    the supervisor handle, 0 until one attaches) and /status carries no
    poolActivity while the pool is disabled."""
    import urllib.request

    from spark_tpu.server import SQLServer
    ms = spark.metricsSystem
    srv = None
    try:
        srv = SQLServer(spark, port=0).start()
        snap = ms.snapshots()["pool"]
        for g in POOL_GAUGES:
            assert snap[g] == 0, (g, snap)
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/status", timeout=30) as r:
            st = json.loads(r.read())
        assert "poolActivity" not in st
        assert "pool" in st["metrics"]
    finally:
        if srv is not None:
            srv.stop()
        ms._sources = [s for s in ms._sources
                       if s.name not in ("serving", "pool")]


def test_pool_gauges_and_status_activity(spark, tmp_path):
    """With the pool enabled the server starts a real supervisor; its
    counters flow through the `pool` Source gauges live, and /status
    surfaces the full poolActivity block (live set, counters, last
    decision)."""
    import urllib.request

    from spark_tpu.server import SQLServer
    ms = spark.metricsSystem
    prev_wh = spark.conf.get("spark.sql.warehouse.dir")
    spark.conf.set("spark.sql.warehouse.dir", str(tmp_path / "wh"))
    spark.conf.set(C.SERVER_POOL_ENABLED.key, "true")
    spark.conf.set(C.SERVER_POOL_POLL.key, "0.05")
    srv = None
    try:
        srv = SQLServer(spark, port=0).start()
        sup = srv._pool_supervisor
        assert sup is not None
        deadline = time.time() + 10
        while sup._last_decision is None and time.time() < deadline:
            time.sleep(0.02)                  # first reconcile tick
        # an idle server: the reconcile loop holds the pool at zero
        snap = ms.snapshots()["pool"]
        assert snap["pool_live"] == 0 and snap["workers_spawned"] == 0
        # counters flow through the gauges with no re-registration
        sup.counters["workers_spawned"] = 3
        sup.counters["workers_reaped"] = 2
        sup.counters["spawn_failures"] = 1
        snap = ms.snapshots()["pool"]
        assert snap["workers_spawned"] == 3
        assert snap["workers_reaped"] == 2
        assert snap["spawn_failures"] == 1
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/status", timeout=30) as r:
            st = json.loads(r.read())
        pa = st["poolActivity"]
        assert pa["live"] == 0 and pa["workers"] == []
        assert pa["counters"]["workers_spawned"] == 3
        assert "lastDecision" in pa           # the loop has ticked
        assert pa["lastDecision"]["action"] == "hold"
        # the admission stats carry the non-consuming demand view the
        # supervisor's signal samples from
        assert st["admission"]["demand"]["running"] == 0
    finally:
        if srv is not None:
            srv.stop()
        spark.conf.set("spark.sql.warehouse.dir", prev_wh)
        spark.conf_obj.unset(C.SERVER_POOL_ENABLED.key)
        spark.conf_obj.unset(C.SERVER_POOL_POLL.key)
        ms._sources = [s for s in ms._sources
                       if s.name not in ("serving", "pool")]


def test_run_plane_gauges_exported(spark):
    """ISSUE 20 observability: run-plane activity rides the compile
    Source — stages entered compressed, dense rows the planes stood in
    for, overflow fallbacks, and in-trace expansions all live gauges
    that move when an eligible run leaf crosses the stage boundary."""
    import spark_tpu.types as T
    from spark_tpu.columnar import ColumnBatch, ColumnVector, RunColumnVector
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame
    ms = spark.metricsSystem
    before = ms.snapshots()["compile"]
    for key in ("run_plane_stages", "run_plane_rows",
                "run_plane_overflows", "run_plane_expansions"):
        assert key in before, key
    s = spark.newSession()
    s.conf.set("spark.tpu.mesh.shards", "1")
    heads = np.arange(16, dtype=np.int64)
    rv = RunColumnVector(heads, np.full(16, 32, np.int64), T.int64)
    vv = ColumnVector(np.arange(512, dtype=np.int64), T.int64)
    b = ColumnBatch(["ts", "v"], [rv, vv], None, 512)
    DataFrame(s, L.LocalRelation(b)).createOrReplaceTempView("obs_rp")
    got = s.sql("SELECT count(*) AS c, sum(ts) AS st FROM obs_rp "
                "WHERE ts < 9").collect()
    dense = np.repeat(heads, 32)
    assert got[0]["c"] == int((dense < 9).sum())
    assert got[0]["st"] == int(dense[dense < 9].sum())
    after = ms.snapshots()["compile"]
    assert after["run_plane_stages"] > before["run_plane_stages"]
    assert after["run_plane_rows"] >= before["run_plane_rows"] + 512
    assert after["run_plane_overflows"] >= before["run_plane_overflows"]
    # the eligible filter+agg stage never expanded its plane
    assert after["run_plane_expansions"] == before["run_plane_expansions"]


def test_run_plane_activity_in_status(spark, tmp_path):
    """/status runActivity carries the plane gauges next to the run-code
    gauges, diffed against the shuffle service's birth snapshot."""
    import urllib.request

    from spark_tpu import columnar as _col
    from spark_tpu.server import SQLServer
    prev = getattr(spark, "_crossproc_svc", None)
    ms = spark.metricsSystem
    srv = None
    try:
        svc = spark.enableHostShuffle(str(tmp_path), process_id=0,
                                      n_processes=1, timeout_s=5.0)
        srv = SQLServer(spark, port=0).start()

        def status():
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/status",
                    timeout=30) as r:
                return json.loads(r.read())

        _col.bump_plane_stage()
        _col.bump_plane_rows(4096)
        _col.bump_plane_overflow()
        st = status()
        got = st["runActivity"]["default"]
        assert got["run_plane_stages"] >= 1
        assert got["run_plane_rows"] >= 4096
        assert got["run_plane_overflows"] >= 1
        # and the shuffle Source mirrors the same diffed gauges
        snap = ms.snapshots()["shuffle"]
        assert snap["run_plane_stages"] >= 1
        assert snap["run_plane_rows"] >= 4096
    finally:
        if srv is not None:
            srv.stop()
        spark._crossproc_svc = prev
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]
