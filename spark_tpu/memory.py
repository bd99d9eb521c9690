"""HBM memory accounting and the device cache manager.

The reference splits a fixed heap between EXECUTION (shuffle/sort/join
working memory) and STORAGE (cached blocks), with storage evictable down
to a protected floor — ``UnifiedMemoryManager.scala:47`` — and tracks
cached relations in ``CacheManager.scala`` / ``InMemoryRelation.scala``
with compressed column blocks and LRU-style eviction via the
``BlockManager``/``MemoryStore``.

TPU translation:

- the accounted resource is device HBM.  The budget comes from the live
  device (``Device.memory_stats()['bytes_limit']``) when the backend
  exposes it, else ``spark.tpu.memory.hbmBudget``.
- EXECUTION reservations are made by the planner for a query's leaf
  batches + operator working set *before* dispatch, so an impossible
  query fails with an honest ``HBMOutOfMemoryError`` naming the reserver
  instead of an opaque XLA allocation crash.
- STORAGE holds cached relations as device-resident ColumnBatches.
  Under pressure they demote: DEVICE -> HOST (numpy) -> HOST_COMPRESSED
  (columnar RLE/dict/byte-codec blocks — ``codec.py``), mirroring the
  reference's MEMORY_ONLY -> MEMORY_AND_DISK ladder with the host RAM
  playing the disk role (HBM:host ~ heap:disk in bandwidth ratio).
- eviction is LRU over cached entries.  Demotion is safe mid-query: a
  reader holds a reference to the decompressed/materialized batch it got
  from ``get``, so the entry's storage can change underneath it freely.

Single-controller scope: accounting covers this process's session (the
reference's per-executor MemoryManager scope; multi-host counterparts
each run their own).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import codec as codec_mod
from . import config as C
from .columnar import ColumnBatch, ColumnVector

HBM_BUDGET = C.conf("spark.tpu.memory.hbmBudget").doc(
    "Device HBM budget in bytes for execution+storage accounting; 0 = "
    "discover from device memory_stats (16 GiB on the CPU backend, which "
    "reports none)."
).int(0)

STORAGE_FRACTION = C.conf("spark.tpu.memory.storageFraction").doc(
    "Fraction of the HBM budget protected for the device cache before "
    "execution reservations may force eviction (UnifiedMemoryManager's "
    "spark.memory.storageFraction analog)."
).float(0.3)

CACHE_CODEC = C.conf("spark.tpu.cache.codec").doc(
    "Byte codec for HOST_COMPRESSED cache blocks: one of codec.CODECS "
    "(zlib/lzma/bz2 always; lz4/zstd when their wheels are present)."
).string("zlib")

HOST_BUDGET = C.conf("spark.tpu.memory.hostBudget").doc(
    "Host-RAM budget in bytes for the shuffle path's exchange staging "
    "(bucketed map output, fetched blocks, drained shards); 0 = discover "
    "physical RAM via psutil or os.sysconf (fallback 16 GiB).  Sides "
    "that cannot reserve spill to disk instead of growing unbounded."
).check(lambda v: v >= 0).int(0)


class HBMOutOfMemoryError(MemoryError):
    """Execution reservation cannot fit even after evicting all unpinned
    storage (SparkOutOfMemoryError analog)."""


class HostMemoryError(MemoryError):
    """Host-RAM staging can proceed NEITHER in memory nor via spill
    (disk error, or the ledger exhausted by concurrent reservers): the
    query fails bounded with the reserver and exchange named, never
    partial results (the spill ladder's SparkOutOfMemoryError rung)."""

    def __init__(self, owner: str, requested: int, budget: int,
                 holders: Optional[Dict[str, int]] = None,
                 exchange: str = "", detail: str = ""):
        self.owner = owner
        self.requested = requested
        self.budget = budget
        self.holders = dict(holders or {})
        self.exchange = exchange
        self.detail = detail
        held = sum(self.holders.values())
        msg = (f"{owner}: cannot stage {requested} B"
               f"{' for exchange ' + exchange if exchange else ''} "
               f"(host budget {budget} B, held {held} B by "
               f"{len(self.holders)} reserver(s))")
        if detail:
            msg += f": {detail}"
        super().__init__(msg)


class HostMemoryPressure(HostMemoryError):
    """A ledger reservation failed at a point where a DEGRADED mode can
    still complete the query (the drained post-exchange shard of a
    distributed join, which the crossproc grace path can re-bucket to
    disk and join piecewise).  Raisers guarantee the underlying state is
    intact and re-consumable; callers with no grace path installed may
    treat it exactly as its ``HostMemoryError`` base — bounded, never
    partial."""


def batch_nbytes(batch: ColumnBatch) -> int:
    from .columnar import unmaterialized_runs
    total = 0
    for v in batch.vectors:
        runs = unmaterialized_runs(v)
        if runs is not None:
            # lazy run vector: the ledger charges what is actually held
            # (run values + int64 lengths), not the inflated row count
            total += int(np.asarray(runs.run_values).nbytes
                         + np.asarray(runs.run_lengths).nbytes)
        else:
            total += np.dtype(v.dtype.np_dtype).itemsize * batch.capacity
        if v.valid is not None:
            total += batch.capacity
    if batch.row_valid is not None:
        total += batch.capacity
    return total


def _device_budget(conf) -> int:
    fixed = conf.get(HBM_BUDGET)
    if fixed:
        return fixed
    import jax
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']; set "
            f"{HBM_BUDGET.key} to its HBM size in bytes")
    # the CPU backend reports no limit; tests still need a budget
    return 16 << 30


class MemoryManager:
    """Execution/storage split over one HBM budget with storage eviction."""

    def __init__(self, conf):
        self._conf = conf
        self._lock = threading.RLock()
        self.budget = _device_budget(conf)
        self.storage_floor = int(self.budget *
                                 conf.get(STORAGE_FRACTION))
        self._execution: Dict[str, int] = {}
        self._storage: Dict[str, int] = {}
        self._evict_cb = None            # set by the cache manager

    # -- introspection ------------------------------------------------------
    @property
    def execution_used(self) -> int:
        return sum(self._execution.values())

    @property
    def storage_used(self) -> int:
        return sum(self._storage.values())

    @property
    def free(self) -> int:
        return self.budget - self.execution_used - self.storage_used

    def set_eviction_callback(self, cb) -> None:
        """cb(nbytes_needed) -> bytes actually released."""
        self._evict_cb = cb

    # -- execution pool -----------------------------------------------------
    def acquire_execution(self, owner: str, nbytes: int) -> None:
        with self._lock:
            if nbytes > self.free and self._evict_cb is not None:
                # evict storage above the protected floor
                evictable = max(0, self.storage_used - self.storage_floor)
                want = min(nbytes - self.free, evictable)
                if want > 0:
                    self._evict_cb(want)
            if nbytes > self.free:
                raise HBMOutOfMemoryError(
                    f"{owner}: need {nbytes} B, free {self.free} B of "
                    f"{self.budget} B (execution {self.execution_used} B, "
                    f"storage {self.storage_used} B)")
            self._execution[owner] = self._execution.get(owner, 0) + nbytes

    def release_execution(self, owner: str) -> None:
        with self._lock:
            self._execution.pop(owner, None)

    def execution_held(self, owner: str) -> int:
        """Bytes an owner still holds (0 = clean) — the post-task leak
        check's locked accessor."""
        with self._lock:
            return self._execution.get(owner, 0)

    # -- storage pool -------------------------------------------------------
    def try_acquire_storage(self, key: str, nbytes: int) -> bool:
        with self._lock:
            if nbytes > self.free and self._evict_cb is not None:
                self._evict_cb(nbytes - self.free)
            if nbytes > self.free:
                return False
            self._storage[key] = self._storage.get(key, 0) + nbytes
            return True

    def release_storage(self, key: str) -> None:
        with self._lock:
            self._storage.pop(key, None)


def discover_host_budget() -> int:
    """Physical host RAM in bytes: psutil when its wheel is present, else
    ``os.sysconf`` (absent on some platforms), else a 16 GiB guess."""
    try:
        import psutil
        return int(psutil.virtual_memory().total)
    except Exception:
        pass
    try:
        return int(os.sysconf("SC_PAGE_SIZE")) * int(os.sysconf("SC_PHYS_PAGES"))
    except Exception:
        pass
    return 16 << 30


class HostMemoryLedger:
    """Owner-keyed host-RAM reservations for the shuffle staging path.

    The host twin of ``MemoryManager``'s execution pool, minus eviction:
    there is no storage to demote, so over-budget reservers either spill
    (``try_reserve`` returns False) or fail structured (``reserve``
    raises ``HostMemoryError``).  ``peak`` records the high-water mark of
    accounted bytes for the peak_host_bytes gauge."""

    def __init__(self, conf=None, budget: Optional[int] = None):
        if budget is None:
            fixed = conf.get(HOST_BUDGET) if conf is not None else 0
            budget = fixed or discover_host_budget()
        self.budget = int(budget)
        self._lock = threading.Lock()
        self._held: Dict[str, int] = {}
        self.peak = 0

    @property
    def used(self) -> int:
        with self._lock:
            return sum(self._held.values())

    @property
    def free(self) -> int:
        return self.budget - self.used

    def held(self, owner: str) -> int:
        with self._lock:
            return self._held.get(owner, 0)

    def owners(self) -> List[str]:
        """Snapshot of every owner currently holding a reservation (the
        analysis ledger-scope check diffs this across a query)."""
        with self._lock:
            return list(self._held)

    def try_reserve(self, owner: str, nbytes: int) -> bool:
        nbytes = int(nbytes)
        with self._lock:
            used = sum(self._held.values())
            if used + nbytes > self.budget:
                return False
            self._held[owner] = self._held.get(owner, 0) + nbytes
            self.peak = max(self.peak, used + nbytes)
            return True

    def reserve(self, owner: str, nbytes: int, exchange: str = "") -> None:
        if not self.try_reserve(owner, nbytes):
            with self._lock:
                holders = dict(self._held)
            raise HostMemoryError(owner, int(nbytes), self.budget,
                                  holders=holders, exchange=exchange)

    def release(self, owner: str, nbytes: Optional[int] = None) -> None:
        with self._lock:
            if nbytes is None:
                self._held.pop(owner, None)
                return
            left = self._held.get(owner, 0) - int(nbytes)
            if left > 0:
                self._held[owner] = left
            else:
                self._held.pop(owner, None)

    def release_prefix(self, prefix: str) -> int:
        """Drop every reservation whose owner starts with ``prefix`` —
        the query-exit safety net against leaks on error paths, and the
        epoch-abort sweep lineage recovery runs BEFORE re-executing a
        statement (a dead epoch's map staging must not shrink the
        re-run's budget).  Returns the number of bytes freed so callers
        can account the sweep (0 = nothing was held under the scope)."""
        freed = 0
        with self._lock:
            for owner in [o for o in self._held if o.startswith(prefix)]:
                freed += self._held.pop(owner)
        return freed


# ---------------------------------------------------------------------------
# storage levels & cached entries
# ---------------------------------------------------------------------------

class StorageLevel:
    DEVICE = "DEVICE"                      # HBM-resident (MEMORY_ONLY)
    HOST = "HOST"                          # numpy (MEMORY_AND_DISK's disk)
    HOST_COMPRESSED = "HOST_COMPRESSED"    # codec blocks (compressed cache)


class _Entry:
    __slots__ = ("key", "level", "requested", "batch", "blocks", "nbytes",
                 "last_used", "uid")

    def __init__(self, key, level, requested, batch, nbytes):
        self.key = key
        self.level = level
        self.requested = requested
        self.batch = batch            # device or host ColumnBatch
        self.blocks = None            # HOST_COMPRESSED payload
        self.nbytes = nbytes
        self.last_used = time.monotonic()
        self.uid = None               # stable plan-key identity (see get())


def _compress_batch(batch: ColumnBatch, codec_name: str):
    host = batch.to_host()
    cols = []
    for v in host.vectors:
        enc = codec_mod.encode_column(np.asarray(v.data), codec_name)
        validity = (None if v.valid is None
                    else np.packbits(np.asarray(v.valid, bool)))
        cols.append((enc, validity, v.dtype, v.dictionary))
    rv = (None if host.row_valid is None
          else np.packbits(np.asarray(host.row_valid, bool)))
    return (host.names, cols, rv, host.capacity)


def _decompress_batch(blocks) -> ColumnBatch:
    names, cols, rv, capacity = blocks
    vectors = []
    for enc, validity, dt, dictionary in cols:
        data = codec_mod.decode_column(enc)
        valid = (None if validity is None
                 else np.unpackbits(validity)[:capacity].astype(bool))
        vectors.append(ColumnVector(data, dt, valid, dictionary))
    row_valid = (None if rv is None
                 else np.unpackbits(rv)[:capacity].astype(bool))
    return ColumnBatch(names, vectors, row_valid, capacity)


class DeviceCacheManager:
    """Plan-keyed cached relations with demotion + LRU eviction."""

    def __init__(self, memory: MemoryManager, conf):
        self._memory = memory
        self._conf = conf
        self._entries: Dict[str, _Entry] = {}
        # ONE lock with the memory manager: the eviction callback runs
        # under it, and a second lock here would order-invert (cache.put ->
        # memory.try_acquire_storage vs memory.acquire_execution -> _evict)
        self._lock = memory._lock
        memory.set_eviction_callback(self._evict)

    # -- public -------------------------------------------------------------
    def put(self, key: str, batch: ColumnBatch,
            level: str = StorageLevel.DEVICE) -> None:
        if level not in (StorageLevel.DEVICE, StorageLevel.HOST,
                         StorageLevel.HOST_COMPRESSED):
            raise ValueError(
                f"unknown storage level {level!r}; expected one of "
                f"StorageLevel.DEVICE/HOST/HOST_COMPRESSED")
        nbytes = batch_nbytes(batch)
        with self._lock:
            self.remove(key)
            entry = _Entry(key, level, level, batch, nbytes)
            from .sql.logical import _batch_uid
            entry.uid = _batch_uid(batch)
            if level == StorageLevel.DEVICE:
                if self._memory.try_acquire_storage(key, nbytes):
                    entry.batch = batch.to_device()
                else:                      # no room: demote on entry
                    entry.level = StorageLevel.HOST
                    entry.batch = batch.to_host()
            elif level == StorageLevel.HOST:
                entry.batch = batch.to_host()
            else:
                entry.blocks = _compress_batch(
                    batch, self._conf.get(CACHE_CODEC))
                entry.batch = None
                entry.nbytes = sum(c[0].nbytes for c in entry.blocks[1])
            self._entries[key] = entry

    def get(self, key: str) -> Optional[ColumnBatch]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            entry.last_used = time.monotonic()
            if entry.level == StorageLevel.HOST_COMPRESSED:
                if entry.batch is None:       # decompress ONCE; keep the
                    entry.batch = _decompress_batch(entry.blocks)  # host copy
                batch = entry.batch
            else:
                batch = entry.batch
            # promote back toward the requested level opportunistically —
            # BOTH for decompressed blocks and for entries that were put()
            # straight to HOST because HBM was full at the time
            if entry.level != StorageLevel.DEVICE \
                    and entry.requested == StorageLevel.DEVICE \
                    and self._memory.try_acquire_storage(
                        key, batch_nbytes(batch)):
                entry.batch = batch.to_device()
                entry.blocks = None
                entry.level = StorageLevel.DEVICE
                entry.nbytes = batch_nbytes(batch)
                batch = entry.batch
            # every object served under this key carries the SAME uid, so
            # plan keys built over a cached batch (cache-on-cache) stay
            # stable across demote/decompress/promote cycles
            if entry.uid is not None:
                try:
                    batch._cache_uid = entry.uid
                except Exception:
                    pass
            return batch

    def remove(self, key: str) -> bool:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return False
            if entry.level == StorageLevel.DEVICE:
                self._memory.release_storage(key)
            return True

    def clear(self) -> None:
        with self._lock:
            for key in list(self._entries):
                self.remove(key)

    def entries(self) -> List[dict]:
        with self._lock:
            return [{"key": e.key, "level": e.level, "nbytes": e.nbytes}
                    for e in self._entries.values()]

    # -- eviction (called under memory pressure) ----------------------------
    def _evict(self, nbytes_needed: int) -> int:
        """Demote least-recently-used DEVICE entries to HOST_COMPRESSED
        until ``nbytes_needed`` device bytes are free."""
        released = 0
        with self._lock:
            device_entries = sorted(
                (e for e in self._entries.values()
                 if e.level == StorageLevel.DEVICE),
                key=lambda e: e.last_used)
            for entry in device_entries:
                if released >= nbytes_needed:
                    break
                host = entry.batch.to_host()
                entry.blocks = _compress_batch(
                    host, self._conf.get(CACHE_CODEC))
                entry.batch = None        # dropped to free host refs too;
                entry.level = StorageLevel.HOST_COMPRESSED  # get() re-caches
                self._memory.release_storage(entry.key)
                released += entry.nbytes
                entry.nbytes = sum(c[0].nbytes for c in entry.blocks[1])
        return released
