"""Metrics system: named gauge sources, read on demand.

The analog of the reference's Dropwizard pipeline
(`core/src/main/scala/org/apache/spark/metrics/MetricsSystem.scala`,
`metrics/source/` per-component gauges like `DAGSchedulerSource`):
components register SOURCES (a name + a dict of gauge callables) and
``snapshots()`` reads them — ``GET /status`` serves it as ``metrics``.
Query-level metrics stay on the listener-bus/event-log pipeline
(`session._post_event`), spans and counters in ``spark_tpu.tracing``; this
system is for PROCESS gauges — memory pools, cache occupancy, query
counters — the things an operator watches over time.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

__all__ = ["MetricsSystem", "Source"]


class Source:
    """A named set of gauges (callables returning numbers/strings)."""

    def __init__(self, name: str, gauges: Dict[str, Callable[[], Any]]):
        self.name = name
        self.gauges = dict(gauges)

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for g, fn in self.gauges.items():
            try:
                out[g] = fn()
            except Exception:
                out[g] = None
        return out


class MetricsSystem:
    def __init__(self):
        self._sources: List[Source] = []

    def register_source(self, source: Source) -> None:
        self._sources.append(source)

    def snapshots(self) -> Dict[str, Dict[str, Any]]:
        return {s.name: s.snapshot() for s in self._sources}


def default_sources(session) -> List[Source]:
    """Built-in process gauges (the `*Source.scala` set, TPU-shaped)."""
    mem = getattr(session, "_memory", None)
    cache = getattr(session, "_cache", None)
    srcs: List[Source] = []
    if mem is not None:
        def _ledger_gauge(attr):
            # resolved per read: the host ledger appears only when a
            # host shuffle is enabled, possibly after source setup
            def g():
                ledger = getattr(session, "_host_ledger", None)
                return int(getattr(ledger, attr)) if ledger is not None \
                    else 0
            return g
        srcs.append(Source("memory", {
            "hbm_budget_bytes": lambda: mem.budget,
            "execution_used_bytes": lambda: mem.execution_used,
            "storage_used_bytes": lambda: mem.storage_used,
            "free_bytes": lambda: mem.free,
            # host-RAM side of the ledger pair (0s until a host shuffle
            # is enabled and a ledger exists)
            "host_budget_bytes": _ledger_gauge("budget"),
            "host_used_bytes": _ledger_gauge("used"),
            "host_peak_bytes": _ledger_gauge("peak"),
        }))
    if cache is not None:
        srcs.append(Source("cache", {
            "entries": lambda: len(cache._entries),
            "device_entries": lambda: sum(
                1 for e in cache._entries.values()
                if e.level == "DEVICE"),
        }))
    srcs.append(Source("queries", {
        "executed": lambda: getattr(session, "_query_count", 0),
    }))
    srcs.append(Source("analysis", {
        # plan-invariant verifier accounting (analysis.maybe_verify_*)
        "plans_verified": lambda: getattr(
            session, "_analysis_stats", {}).get("plans_verified", 0),
        "plan_verify_ms": lambda: getattr(
            session, "_analysis_stats", {}).get("plan_verify_ms", 0.0),
        # replica-determinism backstop (analysis.runtime.
        # verify_decision_trace): checks run / divergences caught — any
        # nonzero divergence means a process's decision pipeline split
        # from its peers and the exchange was aborted structured
        "decision_trace_checks": lambda: getattr(
            session, "_analysis_stats", {}).get(
                "decision_trace_checks", 0),
        "decision_trace_divergence": lambda: getattr(
            session, "_analysis_stats", {}).get(
                "decision_trace_divergence", 0),
    }))
    from .sql.stagecompile import metrics_source as _stage_gauges
    # whole-stage compilation: the process stage-executable cache
    # (compile cost, hit ratio, fusion width — CodegenMetrics analog)
    srcs.append(Source("compile", _stage_gauges()))
    def _stream_sum(key):
        # resolved per read: standing queries register themselves on
        # session._stream_execs at construction and leave on stop()
        def g():
            return sum(int(ex.metrics.get(key, 0))
                       for ex in getattr(session, "_stream_execs", []))
        return g

    srcs.append(Source("streaming", {
        # standing-query health: commits vs replays (recovery activity),
        # stage rebuilds (0 after the first batch when the stage cache
        # holds), state residency vs spill (ledger pressure), watermark
        # progress + rows evicted past it
        "standing_queries": lambda: len(
            getattr(session, "_stream_execs", [])),
        "batches_committed": _stream_sum("batches_committed"),
        "replayed_batches": _stream_sum("replayed_batches"),
        "stage_rebuilds_last": _stream_sum("stage_rebuilds_last"),
        "state_bytes": _stream_sum("state_bytes"),
        "state_rows": _stream_sum("state_rows"),
        "spill_bytes": _stream_sum("spill_bytes"),
        "spill_events": _stream_sum("spill_events"),
        "evicted_rows": _stream_sum("evicted_rows"),
        "watermark_us": lambda: max(
            [int(ex.metrics.get("watermark_us", 0))
             for ex in getattr(session, "_stream_execs", [])] or [0]),
        "admission_deferred": _stream_sum("admission_deferred"),
        "state_versions_spilled": lambda: sum(
            int(getattr(ex, "_fmgws_provider", None) and
                ex._fmgws_provider.versions_spilled or 0)
            for ex in getattr(session, "_stream_execs", [])),
    }))
    svc = getattr(session, "_crossproc_svc", None)
    if svc is not None and hasattr(svc, "metrics_source"):
        # DCN exchange retry/blacklist counters (RetryingBlockReader +
        # peer blacklist; the shuffle-metrics Source of the reference's
        # ExternalShuffleServiceSource) plus the lineage-recovery gauges
        # an operator alarms on: stage_retries / recovered_partitions /
        # recovery_ms / epoch / recovered_peers — a nonzero epoch means
        # the process set shrank and stayed shrunk
        srcs.append(svc.metrics_source())
    store = getattr(getattr(svc, "blockclient", None), "store", None)
    if store is not None:
        # disaggregated block service hygiene: what the store currently
        # holds (exchanges awaiting adoption/cleanup, owner leases,
        # registered state dirs) and the orphan reaper's lifetime
        # reclaim total — all read live off the shared store
        srcs.append(Source("blockstore", {
            "available": lambda: int(store.available),
            "exchanges_held": lambda: store.stats()["exchangesHeld"],
            "leases": lambda: store.stats()["leases"],
            "state_registrations": lambda: store.stats()[
                "stateRegistrations"],
            "orphaned_blocks_reclaimed": lambda: store.reclaimed_total(),
        }))
    return srcs
