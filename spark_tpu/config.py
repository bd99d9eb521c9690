"""Typed configuration registry.

Re-design of the reference's layered config system:
``core/src/main/scala/org/apache/spark/SparkConf.scala`` (string k/v map) +
``internal/config/ConfigBuilder.scala`` / ``ConfigEntry.scala`` (typed entries
with defaults, validators, fallbacks) + the session-mutable
``sql/catalyst/.../internal/SQLConf.scala``.

One mechanism serves both roles here: a global registry of ``ConfigEntry``
objects, with ``Conf`` instances (per-session) holding string overrides.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: Dict[str, "ConfigEntry"] = {}


class ConfigEntry(Generic[T]):
    def __init__(self, key: str, default: T, value_type: type,
                 doc: str = "", validator: Optional[Callable[[T], bool]] = None,
                 fallback: Optional["ConfigEntry"] = None):
        self.key = key
        self.default = default
        self.value_type = value_type
        self.doc = doc
        self.validator = validator
        self.fallback = fallback
        if key in _REGISTRY:
            raise ValueError(f"duplicate config key {key}")
        _REGISTRY[key] = self

    def parse(self, raw: Any) -> T:
        if isinstance(raw, str):
            if self.value_type is bool:
                low = raw.strip().lower()
                if low in ("true", "1", "yes"):
                    v = True
                elif low in ("false", "0", "no"):
                    v = False
                else:
                    raise ValueError(f"invalid boolean {raw!r} for config {self.key}")
            elif self.value_type in (int, float):
                v = self.value_type(raw.strip())
            else:
                v = raw
        else:
            v = self.value_type(raw) if raw is not None else raw
        if self.validator is not None and not self.validator(v):
            raise ValueError(f"invalid value {v!r} for config {self.key}")
        return v  # type: ignore[return-value]


class ConfigBuilder:
    """Fluent builder mirroring ``ConfigBuilder.scala``."""

    def __init__(self, key: str):
        self.key = key
        self._doc = ""
        self._validator: Optional[Callable] = None
        self._fallback: Optional[ConfigEntry] = None

    def doc(self, text: str) -> "ConfigBuilder":
        self._doc = text
        return self

    def check(self, fn: Callable[[Any], bool]) -> "ConfigBuilder":
        self._validator = fn
        return self

    def fallback(self, entry: ConfigEntry) -> "ConfigBuilder":
        self._fallback = entry
        return self

    def _make(self, default, value_type) -> ConfigEntry:
        return ConfigEntry(self.key, default, value_type, self._doc,
                           self._validator, self._fallback)

    def boolean(self, default: bool) -> ConfigEntry:
        return self._make(default, bool)

    def int(self, default: int) -> ConfigEntry:
        return self._make(default, int)

    def float(self, default: float) -> ConfigEntry:
        return self._make(default, float)

    def string(self, default: Optional[str]) -> ConfigEntry:
        return self._make(default, str)


def conf(key: str) -> ConfigBuilder:
    return ConfigBuilder(key)


class Conf:
    """A mutable configuration: overrides on top of registered defaults.

    Plays both the ``SparkConf`` role (cloned into the session) and the
    ``SQLConf``/``RuntimeConfig`` role (``session.conf.set(...)``).
    """

    def __init__(self, overrides: Optional[Dict[str, Any]] = None):
        self._overrides: Dict[str, Any] = dict(overrides or {})

    def clone(self) -> "Conf":
        return Conf(self._overrides)

    def set(self, key_or_entry, value: Any) -> "Conf":
        key = key_or_entry.key if isinstance(key_or_entry, ConfigEntry) else key_or_entry
        self._overrides[key] = value
        return self

    def unset(self, key: str) -> None:
        self._overrides.pop(key, None)

    def get(self, key_or_entry, default: Any = None) -> Any:
        if isinstance(key_or_entry, ConfigEntry):
            entry = key_or_entry
        else:
            entry = _REGISTRY.get(key_or_entry)
            if entry is None:
                return self._overrides.get(key_or_entry, default)
        if entry.key in self._overrides:
            return entry.parse(self._overrides[entry.key])
        if entry.fallback is not None and entry.fallback.key in self._overrides:
            return self.get(entry.fallback)
        return entry.default

    def __getitem__(self, entry: ConfigEntry) -> Any:
        return self.get(entry)

    def items(self):
        return dict(self._overrides).items()


def registered_entries() -> List[ConfigEntry]:
    return list(_REGISTRY.values())


# ---------------------------------------------------------------------------
# Core entries (analogs of internal/config/package.scala + SQLConf.scala)
# ---------------------------------------------------------------------------

APP_NAME = conf("spark.app.name").doc("Application name.").string("spark-tpu")

MASTER = conf("spark.master").doc(
    "Execution target: local[*] (host CPU backend), tpu (single process, all "
    "local devices in one mesh)."
).string("tpu")

DEFAULT_PARALLELISM = conf("spark.default.parallelism").doc(
    "Default number of partitions for RDDs and shuffles."
).int(8)

SHUFFLE_PARTITIONS = conf("spark.sql.shuffle.partitions").doc(
    "Number of logical shuffle buckets for exchanges (SQLConf analog)."
).int(8)

BATCH_CAPACITY = conf("spark.sql.execution.batch.capacity").doc(
    "Default device batch row capacity (padded, static shape). Analog of "
    "spark.sql.inMemoryColumnarStorage.batchSize / ColumnarBatch capacity."
).int(1 << 16)

AUTO_BROADCAST_JOIN_THRESHOLD = conf("spark.sql.autoBroadcastJoinThreshold").doc(
    "Max estimated row count of a relation that will be broadcast for joins "
    "(reference uses bytes, SQLConf autoBroadcastJoinThreshold; rows here "
    "because columnar batches make row counts the natural stat)."
).int(1 << 22)

JOIN_OUTPUT_FACTOR = conf("spark.sql.join.outputCapacityFactor").doc(
    "Static output capacity of an equi-join as a multiple of the probe-side "
    "capacity; overflow is detected and reported (dynamic-shape escape hatch)."
).float(1.0)

AGG_OUTPUT_ROWS = conf("spark.sql.agg.outputCapacity").doc(
    "Static output capacity of keyed aggregate/distinct results when the "
    "input batch is larger: the group table is sliced to this many rows "
    "so a downstream sort/join does not pay full-input-capacity work for "
    "a handful of live groups (q3: 64 brands in a 4M-row batch).  Safe "
    "by construction — the sorted path emits groups as a prefix and the "
    "MXU path confines them to the first bucket_cap slots — and a traced "
    "overflow flag + adaptive retry grows it when the true group count "
    "exceeds it (the join-output-factor discipline)."
).int(1 << 16)

JOIN_OUTPUT_MAX_ROWS = conf("spark.sql.join.maxOutputRows").doc(
    "Upper bound on an ADAPTIVELY GROWN join output allocation (probe "
    "capacity x grown factor, in rows): beyond it the query fails with "
    "an actionable error instead of attempting an allocation that "
    "exhausts memory (hot-key fanout joins belong on the out-of-core "
    "grace path).  A small factor on a big batch and a huge factor on a "
    "tiny batch are both fine — absolute size is what kills."
).int(1 << 27)

EXCHANGE_SKEW_FACTOR = conf("spark.sql.exchange.skewFactor").doc(
    "Per-destination bucket capacity of an all_to_all exchange as a multiple "
    "of the even split (capacity/num_shards); overflow detected at runtime."
).float(4.0)

MESH_SHARDS = conf("spark.tpu.mesh.shards").doc(
    "Number of mesh shards for distributed execution. 0 = auto (all local "
    "devices); 1 = single-device local execution."
).int(0)

ADAPTIVE_ENABLED = conf("spark.sql.adaptive.enabled").doc(
    "Adaptive exchanges (ExchangeCoordinator analog, in-program): hash "
    "exchanges route through a measured balanced fine-bucket→shard "
    "assignment (coalescing + balancing), and shuffled joins split hot "
    "keys (probe rows spread, build rows replicate)."
).boolean(True)

EXCHANGE_FINE_BUCKETS = conf("spark.tpu.exchange.fineBucketsPerShard").doc(
    "Fine buckets PER SHARD for adaptive hash exchanges; their psum'd "
    "counts drive the balanced bucket→shard assignment.  More buckets = "
    "flatter balance, slightly more assignment work."
).int(32)

EXCHANGE_SPREAD_FRAC = conf("spark.tpu.exchange.spreadThreshold").doc(
    "A fine bucket whose probe-side row count exceeds this fraction of "
    "the per-shard even share is HOT in a shuffled join: its probe rows "
    "spread round-robin and its build rows replicate to every shard."
).float(0.5)

ANALYSIS_VERIFY_PLANS = conf("spark.tpu.analysis.verifyPlans").doc(
    "Plan-invariant verification (analysis.verify_plan) plus the "
    "crossproc exchange runtime checks. auto = on under pytest (tier-1 "
    "suites and the subprocess parity harnesses), off otherwise; "
    "on/off = explicit."
).string("auto")

CODEGEN_ENABLED = conf("spark.sql.codegen.wholeStage").doc(
    "Fuse operator pipelines into a single jitted XLA program (WholeStage"
    "Codegen analog). Off = eager per-op numpy execution (debug path)."
).boolean(True)

CASE_SENSITIVE = conf("spark.sql.caseSensitive").boolean(False)

SESSION_TIME_ZONE = conf("spark.sql.session.timeZone").string("UTC")

SPECULATION = conf("spark.speculation").boolean(False)

MAX_RESULT_ROWS = conf("spark.driver.maxResultRows").doc(
    "Safety cap on collect() row counts (maxResultSize analog)."
).int(1 << 26)

EAGER_EVAL = conf("spark.sql.repl.eagerEval.enabled").boolean(False)

CROSS_JOIN_ENABLED = conf("spark.sql.crossJoin.enabled").boolean(True)

MULTIBATCH_ENABLED = conf("spark.tpu.multibatch.enabled").doc(
    "Stream file scans larger than maxBatchRows through a jitted per-batch "
    "step with cross-batch merge (FileScanRDD + ExternalSorter analog): HBM "
    "holds one batch at a time, intermediates accumulate host-side."
).boolean(True)

SCAN_MAX_BATCH_ROWS = conf("spark.tpu.scan.maxBatchRows").doc(
    "Row count per streamed scan batch; file relations above this row count "
    "take the multi-batch path instead of one eager device batch. 2^20 "
    "measured ~20% faster than 2^21 on the streamed scan lane (smaller "
    "working set, more read/compute overlap) and halves HBM per batch."
).int(1 << 20)

SCAN_PREFETCH_BATCHES = conf("spark.tpu.scan.prefetchBatches").doc(
    "How many scan batches a background thread reads/decodes/transfers "
    "ahead of the device step (double-buffering of the "
    "VectorizedParquetRecordReader pipeline, SURVEY §7 hard-part 4). "
    "0 disables the prefetch thread (fully synchronous scan); -1 = auto: "
    "prefetch on an accelerator, synchronous when the step itself runs "
    "on the host CPU (where the decode thread would compete with XLA:CPU "
    "for the same cores — measured ~3% loss, vs overlap win on TPU)."
).int(-1)

CROSSPROC_DEDUP_REPLICATED = conf("spark.tpu.crossproc.dedupReplicated").doc(
    "On the cross-process generic path, collapse leaf relations that are "
    "byte-identical across processes to ONE copy (replicated broadcast "
    "tables need no annotation). Set false when partitions may be "
    "legitimately duplicate data, to force union semantics."
).boolean(True)

SPILL_MEMORY_ROWS = conf("spark.tpu.spill.hostMemoryRows").doc(
    "Host-RAM row budget for multi-batch intermediates (sorted runs, "
    "concatenated spine output); beyond it, runs spill to disk under "
    "spark.tpu.spill.dir (Spillable threshold analog)."
).int(1 << 24)

SPILL_DIR = conf("spark.tpu.spill.dir").doc(
    "Directory for spilled intermediate runs; empty = a fresh temp dir."
).string("")

METRICS_ENABLED = conf("spark.sql.metrics.enabled").doc(
    "Record per-operator output row counts (SQLMetrics analog). Adds one "
    "fetched scalar per operator to every query; off by default."
).boolean(False)

EVENT_LOG_DIR = conf("spark.eventLog.dir").doc(
    "Directory for JSON-lines query event logs (EventLoggingListener "
    "analog); empty = disabled."
).string("")

COLLECT_MAX_LEN = conf("spark.tpu.collect.maxArrayLen").doc(
    "Static element capacity of collect_list/collect_set output arrays; "
    "larger groups truncate (static shapes need a bound)."
).int(128)

WAREHOUSE_DIR = conf("spark.sql.warehouse.dir").doc(
    "Root directory for persistent (non-temp) tables and databases "
    "(CREATE TABLE ... USING, saveAsTable)."
).string("spark-warehouse")

AGG_FOLD_ROWS = conf("spark.tpu.multibatch.aggFoldRows").doc(
    "Accumulated partial-aggregate rows that trigger an intermediate "
    "buffer-merge fold during a multi-batch aggregation."
).int(1 << 18)

SHUFFLE_IO_MAX_RETRIES = conf("spark.tpu.shuffle.io.maxRetries").doc(
    "Re-read attempts for a missing/partial DCN host-shuffle block before "
    "it is declared lost (spark.shuffle.io.maxRetries analog).  Shared "
    "filesystems lose block visibility transiently (list-after-write "
    "consistency, NFS attribute caches); a bounded retry rides those out "
    "without hanging a dead peer's query."
).check(lambda v: v >= 0).int(3)

SHUFFLE_IO_RETRY_WAIT_MS = conf("spark.tpu.shuffle.io.retryWaitMs").doc(
    "Base wait between block re-read attempts; grows exponentially per "
    "attempt with deterministic per-block jitter so a pod's readers do "
    "not stampede the filesystem in lockstep (spark.shuffle.io.retryWait "
    "analog)."
).check(lambda v: v >= 0).int(100)

SHUFFLE_IO_ATTEMPT_TIMEOUT_MS = conf(
    "spark.tpu.shuffle.io.attemptTimeoutMs").doc(
    "Cap on a SINGLE block retry cycle (backoff + re-read); the "
    "exponential backoff never sleeps longer than this, so late attempts "
    "still poll often enough to see a block heal before the total "
    "deadline."
).check(lambda v: v > 0).int(2000)

SHUFFLE_WIRE_CODEC = conf("spark.tpu.shuffle.wire.codec").doc(
    "Per-column byte codec for the framed columnar shuffle wire format "
    "(and SpilledRuns spill files): one of codec.CODECS ('none', 'zlib', "
    "'lzma', 'bz2', plus lz4/zstd when their wheels are importable).  "
    "Applied per column buffer above compressThreshold, kept only when "
    "it actually shrinks the buffer (spark.shuffle.compress analog)."
).string("zlib")

SHUFFLE_WIRE_COMPRESS_THRESHOLD = conf(
    "spark.tpu.shuffle.wire.compressThreshold").doc(
    "Column buffers at or above this many bytes are candidates for wire "
    "compression; smaller ones skip the codec call entirely — zlib-1 "
    "moves ~100 MB/s while the local filesystem moves GB/s, so "
    "compression only pays once a buffer is large enough that DCN/"
    "shared-fs bandwidth (not codec CPU) is the bottleneck "
    "(spark.shuffle.spill.compress threshold role).  The 1 MiB default "
    "keeps typical exchange blocks raw → zero-copy decode."
).check(lambda v: v >= 0).int(1 << 20)

SHUFFLE_WIRE_DICT_CODES = conf("spark.tpu.shuffle.wire.dictCodes").doc(
    "Ship each dictionary ONCE per (exchange, sender) in a framed "
    "sidecar and stamp blocks with an 8-byte fingerprint instead of "
    "repeating the word list in every block header; receivers cache the "
    "sidecar and operate on int32 codes, late-materializing words only "
    "at the output boundary.  Off = legacy per-block inline "
    "dictionaries (still always decodable)."
).boolean(True)

SHUFFLE_WIRE_RUN_CODES = conf("spark.tpu.shuffle.wire.runCodes").doc(
    "Run-length/delta encode eligible shuffle wire columns (per-column "
    "sampled-benefit probe; presorted range-lane spans tag their runs "
    "for free) and keep RLE columns as lazy run vectors on decode, so "
    "run-aware operators (filter, count/sum, hash-join probe) work at "
    "run granularity and expansion happens only where a dense array is "
    "genuinely needed.  Off = raw columns (legacy frames always decode "
    "either way)."
).boolean(True)

SHUFFLE_IO_ASYNC_WRITE = conf("spark.tpu.shuffle.io.asyncWrite").doc(
    "Stage shuffle blocks through a background writer thread so encode+"
    "disk I/O overlaps the device's next exchange step; commit() drains "
    "the queue before publishing the manifest, so the protocol's "
    "atomic-rename/commit-marker ordering is unchanged.  Off = every "
    "put() writes synchronously (the pre-overlap behavior)."
).boolean(True)

SHUFFLE_IO_FETCH_THREADS = conf("spark.tpu.shuffle.io.fetchThreads").doc(
    "Concurrent block fetch+decode workers per exchange read: blocks "
    "from multiple senders stream through a small thread pool instead "
    "of a serial loop (zlib/file I/O release the GIL, so decode "
    "genuinely parallelizes).  1 = serial reads."
).check(lambda v: v >= 1).int(4)

SHUFFLE_SPILL_THRESHOLD = conf("spark.tpu.shuffle.spillThresholdBytes").doc(
    "Map-side bucketed join output at or above this many raw bytes per "
    "side spills its fine-partition slices to disk in the wire format "
    "and ships receivers their byte spans straight from the spill file "
    "(ExternalSorter spill analog for the exchange).  0 = spill only "
    "when the host-memory ledger (spark.tpu.memory.hostBudget) cannot "
    "reserve the side."
).check(lambda v: v >= 0).int(0)

SHUFFLE_IO_MAX_INFLIGHT = conf("spark.tpu.shuffle.io.maxInFlightBytes").doc(
    "Bound on the total encoded bytes the fetch/decode pool may hold in "
    "flight at once (spark.reducer.maxSizeInFlight analog): fetch "
    "workers wait for room instead of queueing every sender's block in "
    "host RAM.  A single block larger than the bound still proceeds "
    "alone (no deadlock).  0 = unbounded."
).check(lambda v: v >= 0).int(64 << 20)

SHUFFLE_FETCH_RETRY_ENABLED = conf(
    "spark.tpu.shuffle.fetchRetryEnabled").doc(
    "Allow the keyed-aggregate fast path to re-request a lost peer's "
    "partials once after a re-barrier (the peer may have committed "
    "before dying — filesystem blocks survive process death).  Off = "
    "every lost block fails the query immediately with "
    "ExchangeFetchFailed."
).boolean(True)

CROSSPROC_SHUFFLED_JOIN = conf("spark.tpu.crossproc.shuffledJoin").doc(
    "Cross-process shuffled hash join (ShuffledHashJoinExec placement "
    "analog): an equi-join whose two sides BOTH hold partitioned leaves "
    "co-partitions both sides by join-key hash through the host shuffle "
    "service and joins each disjoint key range locally, instead of "
    "centralizing every leaf to every process (the generic-path "
    "O(total-data x processes) gather).  Off = always gather."
).boolean(True)

CROSSPROC_SORT_MERGE_JOIN = conf("spark.tpu.crossproc.sortMergeJoin").doc(
    "Cross-process range-partitioned sort-merge join (SortMergeJoinExec "
    "analog): eligible equi-joins sample their join keys, agree on "
    "global cut points through a manifest-only sample round, exchange "
    "rows by key RANGE instead of key hash, and join each contiguous "
    "key span locally as a streaming sorted merge.  Spans whose sampled "
    "weight exceeds SKEW_FACTOR x median are split across several "
    "reducers with the build side replicated only for that span.  "
    "Requires a single orderable (non-string) equi key; other joins "
    "fall back to the shuffled hash path.  Off = hash or gather."
).boolean(True)

CROSSPROC_AUTO_BROADCAST = conf(
    "spark.tpu.crossproc.autoBroadcastThreshold").doc(
    "Cross-process broadcast join threshold in bytes "
    "(spark.sql.autoBroadcastJoinThreshold analog for the DCN layer): "
    "when the digest probe shows one partitioned join side's global "
    "size at or below this AND much smaller than the other side's "
    "per-process share, every process gathers just that side and joins "
    "locally, skipping the co-partitioning exchange entirely.  "
    "0 = never broadcast."
).check(lambda v: v >= 0).int(1 << 20)

CROSSPROC_ADAPTIVE_REPLAN = conf(
    "spark.tpu.crossproc.adaptiveReplan").doc(
    "Adaptive re-planning of the cross-process join strategy from "
    "OBSERVED exchange statistics: after both map sides are bucketed "
    "(and before any data block ships), the size-manifest round also "
    "carries each side's observed byte/row totals, every process re-runs "
    "choose_join_strategy against them, and a hash/range plan whose "
    "small side's real volume contradicts the digest probe demotes to "
    "broadcast (the small side ships ONCE instead of co-partitioning "
    "both sides).  Observed cardinalities are also recorded in the "
    "session's StatsFeedback and consulted by later plan-time decisions "
    "of the same query sequence.  Demotion additionally requires a "
    "positive autoBroadcastThreshold; a lost or corrupt stats round "
    "falls back to the frozen plan-time strategy.  Off = strategies "
    "freeze at plan time (the digest probe alone decides)."
).boolean(True)

CROSSPROC_GRACE_BUCKETS = conf("spark.tpu.crossproc.graceBuckets").doc(
    "Grace-partition fan-out for the distributed join lanes' degraded "
    "mode: when a reducer cannot reserve its drained post-exchange shard "
    "(or the joined output) under the host-memory ledger, the probe and "
    "build runs re-bucket by join-key hash into this many wire-framed "
    "spill files and the join runs bucket-by-bucket through the "
    "stage-compiled join step, keeping peak ledger bytes to roughly "
    "1/buckets of the shard (the local stage grace path's distributed "
    "twin).  A single key overflowing its bucket falls back to a salted "
    "re-split.  0 = disabled: post-exchange memory pressure stays a "
    "bounded HostMemoryError."
).check(lambda v: v >= 0).int(32)

SHUFFLE_RANGE_SAMPLE_SIZE = conf("spark.tpu.shuffle.rangeSampleSize").doc(
    "Per-process, per-side number of join-key sample points published "
    "in the range-partitioning sample round.  Larger = tighter cut "
    "points and better balance, linearly larger sample manifests."
).check(lambda v: v >= 8).int(256)

SHUFFLE_TARGET_PARTITION_BYTES = conf(
    "spark.tpu.shuffle.targetPartitionBytes").doc(
    "Advisory reduce-partition size for cross-process shuffles "
    "(spark.sql.adaptive.advisoryPartitionSizeInBytes analog): after "
    "map-side size manifests are published, adjacent fine partitions "
    "below this byte count coalesce into one reducer, chosen adaptively "
    "per exchange.  0 = static contiguous assignment, no coalescing."
).check(lambda v: v >= 0).int(1 << 22)

SHUFFLE_FINE_PARTITIONS = conf("spark.tpu.shuffle.finePartitionsPerProc").doc(
    "Fine hash partitions PER PROCESS for cross-process shuffled joins; "
    "the manifest-driven coordinator coalesces these into at most "
    "n_processes contiguous reducer ranges.  More = finer coalescing/"
    "skew resolution, slightly larger size manifests."
).check(lambda v: v >= 1).int(8)

SHUFFLE_BLACKLIST_ENABLED = conf("spark.tpu.shuffle.blacklistEnabled").doc(
    "Exclude heartbeat-confirmed-dead peers from exchange barriers and "
    "remember them for the rest of the query (scheduler/HealthTracker "
    "executor-blacklist analog): later steps fail fast with the lost "
    "hosts named instead of re-paying the barrier timeout."
).boolean(True)

RECOVERY_MAX_STAGE_RETRIES = conf("spark.tpu.recovery.maxStageRetries").doc(
    "Lineage-based stage recovery budget (the DAGScheduler resubmit "
    "analog): when a cross-process exchange loses a peer past its block "
    "retry budget, surviving processes agree on the loss through an "
    "epoch-tagged {xid}-recover manifest round, re-plan reducer "
    "ownership over the live set, and deterministically re-execute the "
    "statement's map stages from leaf recipes under a fresh epoch — up "
    "to this many times per statement before the structured "
    "ExchangeFetchFailed propagates.  0 = the pre-recovery contract: "
    "every exhausted fetch aborts the statement bounded."
).check(lambda v: v >= 0).int(1)

SHUFFLE_ICI_ENABLED = conf("spark.tpu.shuffle.ici.enabled").doc(
    "Two-tier exchange: ship bucketed join columns HBM→HBM over ICI "
    "(lax.all_to_all under shard_map) "
    "between peers the topology probe places in one ICI domain, keeping "
    "the wire-format host shuffle as the cross-pod DCN tier and the "
    "fault-tolerant fallback.  ALL control-plane rounds ({xid}-plan "
    "manifests, adaptive stats, decision traces, recovery agreement) "
    "stay on the host path regardless; where the device tier is "
    "unavailable (no spanning device world, too few devices) the spans "
    "fold back onto the host tier, counted, never partial."
).boolean(False)

SHUFFLE_ICI_MIN_BYTES = conf("spark.tpu.shuffle.ici.minBytes").doc(
    "Smallest AGREED side byte total (summed over the gathered plan-"
    "round manifests, so every replica derives the same verdict) that "
    "takes the ICI device tier; smaller sides stay on the host path "
    "where the fixed collective cost would dominate.  The gate reads "
    "shared manifest totals, never local sizes — asymmetric tier "
    "participation would hang a device collective."
).check(lambda v: v >= 0).int(1 << 16)

SHUFFLE_ICI_TIER_OVERRIDE = conf("spark.tpu.shuffle.ici.tierOverride").doc(
    "Manual ICI domain map overriding the topology probe: pipe-"
    "separated comma groups of process ids ('0,1|2,3' = two 2-chip "
    "pods).  Pids left unmentioned form singleton (host-tier-only) "
    "domains.  Empty = probe the jax world (peers sharing a TPU slice "
    "in a multi-controller world share a domain; anything else — "
    "including CPU — yields singleton domains and the host tier)."
).string("")

BLOCKSERVER_ENABLED = conf("spark.tpu.blockserver.enabled").doc(
    "Disaggregated block service (the external-shuffle-service analog): "
    "the shuffle service hard-links every committed map output, spill "
    "frame, and dict sidecar into a <root>/_blockstore/ area it OWNS and "
    "seals a per-sender registration record at manifest-commit time.  A "
    "survivor whose peer died after registering ADOPTS the materialized "
    "output from the store (zero map re-execution) instead of paying the "
    "r12 re-plan/re-execute epoch; when the service is down the client "
    "degrades to peer-direct reads and lineage recovery.  Off by "
    "default: registration doubles directory entries per exchange."
).boolean(False)

BLOCKSERVER_ORPHAN_TTL = conf("spark.tpu.blockserver.orphanTtlSeconds").doc(
    "TTL for the orphaned-block reaper: an exchange whose every owner "
    "lease has been silent this long (and whose files are equally stale) "
    "is reclaimed, as are raw exchange dirs under swept shuffle roots.  "
    "Registered STATE dirs (streaming checkpoints) are reclaimed only "
    "after explicit ownership release PLUS this TTL — a crashed owner's "
    "checkpoint is never reaped, restart recovery needs it."
).check(lambda v: v >= 0).int(3600)

BLOCKSERVER_GC_INTERVAL = conf("spark.tpu.blockserver.gcIntervalSeconds").doc(
    "Period of the block-service reaper thread the SQL server runs while "
    "started (serving-tier lifecycle: elastic worker reap/spawn leaves "
    "orphans only the service may delete).  0 = reaper disabled."
).check(lambda v: v >= 0).int(60)

DEBUG_NANS = conf("spark.tpu.debug.nanChecks").doc(
    "Enable jax_debug_nans for the session's process: XLA computations "
    "fail loudly on NaN/Inf production instead of propagating them — the "
    "numeric-debugging layer SURVEY §5 notes the reference lacks. Off by "
    "default (SQL semantics legitimately produce NaN, e.g. 0.0/0.0)."
).boolean(False)

# -- multi-tenant serving (spark_tpu.serving: admission + plan cache) -------

SERVER_MAX_CONCURRENT_STATEMENTS = conf(
    "spark.tpu.server.maxConcurrentStatements").doc(
    "Global cap on statements admitted and not yet finished (queued + "
    "running) across ALL server sessions (the thriftserver's session-pool "
    "backpressure role).  Over the cap, POST /sql fails fast with a "
    "structured 429 + Retry-After instead of queueing unboundedly.  "
    "0 = unlimited."
).int(0)

SERVER_MAX_QUEUED_PER_SESSION = conf(
    "spark.tpu.server.maxQueuedPerSession").doc(
    "Cap on statements waiting on ONE server session's FIFO (running + "
    "queued).  A client hammering a single busy session gets 429s once "
    "its backlog is this deep, instead of growing an unbounded queue.  "
    "0 = unlimited."
).int(64)

SERVER_MIN_HOST_HEADROOM = conf(
    "spark.tpu.server.admission.minHostHeadroomBytes").doc(
    "Host-memory-aware admission: when the session has a HostMemoryLedger "
    "(enableHostShuffle) and its free budget is below this many bytes, new "
    "statements are rejected with 429 until pressure clears.  0 = off."
).int(0)

SERVER_MAX_STANDING_QUERIES = conf(
    "spark.tpu.server.maxStandingQueries").doc(
    "Cap on STANDING (streaming) queries registered across all server "
    "sessions.  A standing query is a long-lived tenant: it holds its "
    "admission slot from registration until stop, and each of its "
    "micro-batches passes a non-blocking headroom gate (deferred batches "
    "retry at the trigger interval).  Over the cap, POST /stream fails "
    "fast with 429 + Retry-After.  0 = unlimited."
).int(16)

SERVER_STATEMENT_TIMEOUT = conf("spark.tpu.server.statementTimeout").doc(
    "Per-statement deadline in SECONDS, riding the cooperative cancel "
    "machinery: a statement still queued past its deadline is dropped, a "
    "running one is cancelled at its next cancellation checkpoint "
    "(between streamed batches).  0 = no deadline."
).float(0.0)

SERVER_SESSION_TIMEOUT = conf("spark.tpu.server.sessionTimeout").doc(
    "Idle server-session TTL in SECONDS: sessions with no activity for "
    "this long are closed by the reaper so abandoned clients cannot "
    "exhaust max_sessions.  0 = sessions never expire."
).float(3600.0)

SERVER_PLAN_CACHE_ENABLED = conf("spark.tpu.server.planCache.enabled").doc(
    "Cross-session plan→executable cache for the SQL server: optimized "
    "logical plans are fingerprinted (literals slotted out) and their "
    "compiled jit executables shared across ALL server sessions — the "
    "serving analog of the reference's Janino codegen cache "
    "(CodeGenerator.compile's Guava cache)."
).boolean(True)

SERVER_PLAN_CACHE_MAX_ENTRIES = conf(
    "spark.tpu.server.planCache.maxEntries").doc(
    "Entry bound of the serving plan cache (LRU beyond it)."
).int(256)

SERVER_PLAN_CACHE_MAX_BYTES = conf("spark.tpu.server.planCache.maxBytes").doc(
    "Byte bound of the serving plan cache: estimated held bytes (pinned "
    "local input batches + per-entry executable overhead) stay under this "
    "via LRU eviction."
).int(256 << 20)

# -- elastic worker pool (spark_tpu.serving.pool) ---------------------------

SERVER_POOL_ENABLED = conf("spark.tpu.server.pool.enabled").doc(
    "Elastic worker pool: the SQL server runs a supervisor that derives "
    "a target pool size from the admission demand signal (running + "
    "queued depth, cost-EWMA backlog, host headroom) and reconciles it "
    "by fork/exec'ing real worker processes against the shared root — "
    "the dynamic-allocation analog (ExecutorAllocationManager over the "
    "external shuffle service).  Scale-down is 'stop heartbeating and "
    "hand off the lease': sealed-block adoption plus the TTL reaper "
    "absorb the rest, never a drain barrier.  Off by default."
).boolean(False)

SERVER_POOL_MIN_WORKERS = conf("spark.tpu.server.pool.minWorkers").doc(
    "Floor of the elastic pool: the supervisor never reaps below this "
    "many live workers (0 = the pool may drain completely when idle)."
).check(lambda v: v >= 0).int(0)

SERVER_POOL_MAX_WORKERS = conf("spark.tpu.server.pool.maxWorkers").doc(
    "Ceiling of the elastic pool: the supervisor never spawns above "
    "this many live workers regardless of demand."
).check(lambda v: v >= 1).int(4)

SERVER_POOL_STATEMENTS_PER_WORKER = conf(
    "spark.tpu.server.pool.statementsPerWorker").doc(
    "Demand divisor of the pool policy: target = "
    "ceil((running + queued + recently-rejected) / this), clamped to "
    "[minWorkers, maxWorkers].  Lower = more aggressive scale-up."
).check(lambda v: v >= 1).int(2)

SERVER_POOL_SCALE_DOWN_ROUNDS = conf(
    "spark.tpu.server.pool.scaleDownRounds").doc(
    "Hysteresis: the policy must observe demand below the current pool "
    "size for this many CONSECUTIVE evaluations before it scales down "
    "(one transient idle poll never reaps a warm worker)."
).check(lambda v: v >= 1).int(3)

SERVER_POOL_COOLDOWN = conf("spark.tpu.server.pool.cooldownSeconds").doc(
    "Minimum seconds between pool scale DECISIONS (up or down): after "
    "any resize the policy holds for this long so spawn cost is "
    "amortized and flapping demand cannot thrash the pool."
).check(lambda v: v >= 0).float(2.0)

SERVER_POOL_POLL = conf("spark.tpu.server.pool.pollSeconds").doc(
    "Period of the supervisor's reconcile loop (demand sample -> policy "
    "-> spawn/reap)."
).check(lambda v: v > 0).float(0.25)

SERVER_POOL_HEADROOM = conf(
    "spark.tpu.server.pool.minHostHeadroomBytes").doc(
    "Host-memory clamp on scale-up: when the demand signal reports free "
    "host budget below this many bytes, the policy never raises the "
    "target above the live count (spawning under memory pressure only "
    "deepens it).  0 = off."
).check(lambda v: v >= 0).int(0)

SERVER_POOL_OFFLOAD = conf("spark.tpu.server.pool.offload").doc(
    "Route eligible admitted statements (SELECTs against persistent "
    "tables, no session temp views) to pool workers through the shared "
    "filesystem spool instead of the session FIFO.  Any offload miss — "
    "no live worker, timeout, worker error — falls back silently to the "
    "local path, so results are never worse than pool-off."
).boolean(True)

STAGE_CACHE_MAX_ENTRIES = conf("spark.tpu.stage.cacheMaxEntries").doc(
    "Entry bound of the process-local stage-executable cache (LRU "
    "beyond it).  The cache is per PROCESS, not per session: subprocess "
    "reducers reuse compiled stages across queries within a worker."
).int(256)

STAGE_RUN_PLANES = conf("spark.tpu.stage.runPlanes").doc(
    "Run planes through the jitted stage lane: an eligible lazy run "
    "column (no NULLs, run table at most half the dense capacity after "
    "pow-2 padding) crosses the pytree boundary as a fixed-capacity "
    "(run_values, run_lengths) device plane instead of materializing "
    "dense.  Taught kernels — segmented filter, keyless count/sum/min/"
    "max, bare-column project — work at run granularity; every untaught "
    "operator expands in-trace via a searchsorted gather, byte-"
    "identical.  Off restores the pre-r20 counted materialization at "
    "the boundary."
).boolean(True)
