"""Out-of-core multi-batch execution: the host-side stage runner.

Datasets larger than one device batch stream through a jitted per-batch
step (compiled ONCE — every scan batch is padded to one shared capacity
with fixed string dictionaries), and a host-side merger folds per-batch
results across batches.  This is the TPU answer to the reference's
multi-stage machinery:

- streamed file splits  → ``FileScanRDD.scala`` (one split at a time)
- cross-batch aggregate → partial/final split of ``AggUtils.scala``:
  the device step emits RAW mergeable buffers (DPartialAggregate), the
  host merges sum-of-sums/min-of-mins and finishes once at the end
- sorted-run spill      → ``ExternalSorter.scala:89`` /
  ``UnsafeExternalSorter.java``: per-batch device-sorted runs accumulate
  under a host-RAM budget, overflow goes to disk, one final merge
- the stage pipeline    → ``DAGScheduler.scala:114`` collapsed to a
  scan-stage + merge-stage pair (all in-batch operator fusion is XLA)

HBM only ever holds one input batch and one partial result at a time; the
host (RAM, then disk) is the spill hierarchy.
"""

from __future__ import annotations

import logging
import os
import pickle
import tempfile
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from .. import config as C
from .. import tracing
from .. import types as T
from .. import wire
from ..aggregates import First, Max, Min
from ..columnar import (
    ColumnBatch, ColumnVector, normalize_valids, pad_capacity,
    pad_to_capacity,
)
from ..expressions import Col, EvalContext
from ..kernels import (
    compact, distinct as k_distinct, union_all,
)
from . import logical as L
from . import physical as P
from .planner import Planner, _leaves_nbytes, _slice_to_host
from .window import WindowNode

_log = logging.getLogger("spark_tpu.multibatch")

MULTIBATCH_CKPT = C.conf("spark.tpu.multibatch.checkpointDir").doc(
    "Directory for multi-batch run checkpoints (merger state + scan "
    "cursor); empty = no checkpointing.  A rerun of the same query over "
    "unchanged files resumes at the last checkpointed batch."
).string("")

MULTIBATCH_CKPT_INTERVAL = C.conf("spark.tpu.multibatch.checkpointInterval"
                                  ).doc(
    "Scan batches between checkpoints when checkpointDir is set."
).int(32)

GRACE_AGG_BUCKETS = C.conf("spark.tpu.graceAgg.buckets").doc(
    "Key-hash spill buckets for grace hash aggregation (collect_list/"
    "collect_set/percentile over a streamed scan).  Expected per-bucket "
    "size is total rows / buckets; each bucket is aggregated eagerly "
    "host-side at finish."
).int(32)


# ---------------------------------------------------------------------------
# plan decomposition
# ---------------------------------------------------------------------------

class _Decomposed(NamedTuple):
    rel: L.FileRelation
    spine: List[L.LogicalPlan]        # streamable ops, bottom-up
    breaker: Optional[L.LogicalPlan]  # Aggregate | Sort | Distinct | Limit
    topk: Optional[int]               # Limit fused into a Sort breaker
    above: List[L.LogicalPlan]        # ops above the breaker, top-down
    grace: bool = False               # Aggregate breaker w/o mergeable
                                      # partial: grace hash aggregation


def _with_child(op: L.LogicalPlan, child: L.LogicalPlan):
    """Rebuild a single-child logical node over a new child (logical nodes
    are immutable; the runner re-roots subtrees over materialized results)."""
    if isinstance(op, L.Project):
        return L.Project(op.exprs, child)
    if isinstance(op, L.Filter):
        return L.Filter(op.condition, child)
    if isinstance(op, L.Aggregate):
        return L.Aggregate(op.keys, op.aggs, child)
    if isinstance(op, L.Sort):
        return L.Sort(op.orders, child, op.is_global)
    if isinstance(op, L.Limit):
        return L.Limit(op.n, child)
    if isinstance(op, L.Distinct):
        return L.Distinct(child)
    if isinstance(op, WindowNode):
        return WindowNode(op.wexprs, child)
    if isinstance(op, L.Sample):
        return L.Sample(op.fraction, op.seed, child)
    return None


def _spine_ok(op: L.LogicalPlan) -> bool:
    # nondeterministic expressions (Rand/RowIndex offsets are per-program)
    # would CORRELATE draws/ids across batches if the same program replayed
    # per batch — such plans keep the eager single-batch path
    from .optimizer import is_deterministic
    if isinstance(op, L.Project):
        return all(is_deterministic(e) for e in op.exprs)
    if isinstance(op, L.Filter):
        return is_deterministic(op.condition)
    return False


def _decompose(optimized: L.LogicalPlan) -> Optional[_Decomposed]:
    chain: List[L.LogicalPlan] = []
    node = optimized
    while True:
        if isinstance(node, L.SubqueryAlias):
            node = node.children[0]
            continue
        chain.append(node)
        if not node.children:
            break
        if len(node.children) != 1:
            return None
        node = node.children[0]
    leaf = chain[-1]
    if not isinstance(leaf, L.FileRelation):
        return None
    ops = chain[:-1]                      # root .. just-above-leaf
    i = len(ops)
    while i > 0 and _spine_ok(ops[i - 1]):
        i -= 1
    spine = ops[i:][::-1]                 # bottom-up
    rest = ops[:i]                        # root .. breaker
    breaker: Optional[L.LogicalPlan] = None
    topk: Optional[int] = None
    above: List[L.LogicalPlan] = []
    grace = False
    if rest:
        cand = rest[-1]
        if not isinstance(cand, (L.Aggregate, L.Sort, L.Distinct, L.Limit)):
            return None
        breaker = cand
        above = rest[:-1]
        if isinstance(cand, L.Sort) and above \
                and isinstance(above[-1], L.Limit):
            topk = above[-1].n
            above = above[:-1]
        if isinstance(breaker, L.Aggregate):
            # ONE classification shared with the stage runner (stages.py)
            # so the two paths can never route the same aggregate
            # differently: None = raw distinct (eager only — its partial
            # would silently drop distinctness), 'grace' = bucket-spill +
            # eager per bucket, 'partial' = mergeable buffers
            from .stages import _agg_mode
            mode = _agg_mode(breaker)
            if mode is None:
                return None
            grace = mode == "grace"
        for op in above:
            if _with_child(op, leaf) is None:
                return None
    return _Decomposed(leaf, spine, breaker, topk, above, grace)


def default_spill_dir(conf) -> str:
    """The one definition of where mergers spill (configured dir, or a
    per-process tmp dir) — shared by the linear runner and the stage
    runner so every spill store lands in the same place."""
    return conf.get(C.SPILL_DIR) or os.path.join(
        tempfile.gettempdir(), f"spark_tpu_spill_{os.getpid()}")


# ---------------------------------------------------------------------------
# spill-backed run accumulator
# ---------------------------------------------------------------------------

class SpilledRuns:
    """Run batches held in host RAM up to a row budget, then on disk.

    The ``Spillable`` threshold idiom (`util/collection/Spillable.scala`)
    with the columnar wire format (``wire.py``) as the spill format: the
    same framed raw-buffer + checksum encoding shuffle blocks use, so a
    torn spill is detected on read instead of deserializing garbage.
    Pre-wire pickle spill files still load (magic-byte sniff)."""

    def __init__(self, budget_rows: int, spill_dir: str,
                 budget_bytes: int = 0, run_codes: bool = False):
        self.budget_rows = budget_rows
        # run/delta codes on the spill wire: sealed runs keep encoded
        # frames on disk and reload as lazy run vectors — never inflate
        self.run_codes = run_codes
        # optional second trigger: raw bytes held in RAM (the host-memory
        # ledger's unit), so wide rows spill before the row budget trips
        self.budget_bytes = budget_bytes
        # a fresh subdirectory per accumulator: concurrent queries (or two
        # mergers in one query) must never collide on run file names
        os.makedirs(spill_dir, exist_ok=True)
        self._dir = tempfile.mkdtemp(prefix="runs-", dir=spill_dir)
        self._mem: List[ColumnBatch] = []
        self._disk: List[str] = []
        self.total_rows = 0
        self._mem_rows = 0
        self._mem_bytes = 0
        self._n_spilled = 0

    def add(self, batch: ColumnBatch) -> None:
        rows = int(np.asarray(batch.num_rows()))
        self.total_rows += rows
        self._mem.append(batch)
        self._mem_rows += rows
        if self.budget_bytes > 0:
            self._mem_bytes += wire.raw_nbytes([batch])
        if (self._mem_rows > self.budget_rows
                or 0 < self.budget_bytes < self._mem_bytes):
            self._spill()

    def _spill(self) -> None:
        path = os.path.join(self._dir, f"run-{self._n_spilled:05d}.spill")
        self._n_spilled += 1
        with open(path, "wb") as f:
            f.write(wire.encode_batches([b.to_host() for b in self._mem],
                                        run_codes=self.run_codes))
        _log.info("spilled %d rows in %d runs to %s",
                  self._mem_rows, len(self._mem), path)
        self._disk.append(path)
        self._mem = []
        self._mem_rows = 0
        self._mem_bytes = 0

    def drain(self) -> List[ColumnBatch]:
        """All runs (disk runs loaded back); clears the accumulator."""
        runs: List[ColumnBatch] = []
        for path in self._disk:
            with open(path, "rb") as f:
                data = f.read()
            if data[:4] == wire.MAGIC:
                runs.extend(wire.decode_batches(data,
                                                keep_runs=self.run_codes))
            else:                      # legacy pickle spill
                runs.extend(pickle.loads(data))
            os.remove(path)
        runs.extend(self._mem)
        self._disk = []
        self._mem = []
        self._mem_rows = 0
        self._mem_bytes = 0
        self.total_rows = 0
        return runs

    def replace(self, batches: List[ColumnBatch]) -> None:
        for b in batches:
            self.add(b)

    def close(self) -> None:
        """Remove all spill files and the run directory (crash cleanup)."""
        for path in self._disk:
            try:
                os.remove(path)
            except OSError:
                pass
        self._disk = []
        self._mem = []
        try:
            os.rmdir(self._dir)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# cross-batch mergers
# ---------------------------------------------------------------------------

class _ConcatMerger:
    """Map-only spine (or plain Limit): concatenate per-batch outputs."""

    def __init__(self, spill: SpilledRuns, limit: Optional[int] = None):
        self.spill = spill
        self.limit = limit

    def add(self, batch: ColumnBatch) -> bool:
        self.spill.add(batch)
        if self.limit is not None and self.spill.total_rows >= self.limit:
            return False                       # early-exit the scan
        return True

    def finish(self) -> ColumnBatch:
        runs = self.spill.drain()
        if not runs:
            raise RuntimeError("no scan batches produced")
        out = union_all(runs) if len(runs) > 1 else runs[0]
        if self.limit is not None:
            phys = P.PLimit(self.limit, P.PScan(0, out.schema))
            out = phys.run(P.ExecContext(np, [out]))
        return compact(np, out)


class _SortMerger:
    """Sorted-run accumulation + one final host merge; a fused Limit (the
    ORDER BY ... LIMIT k top-k pattern) keeps the accumulation bounded by
    folding whenever it exceeds a few multiples of k."""

    def __init__(self, spill: SpilledRuns, orders, topk: Optional[int]):
        self.spill = spill
        self.orders = orders                  # [(expr, asc, nulls_first)]
        self.topk = topk

    def _sort_limit(self, batch: ColumnBatch) -> ColumnBatch:
        phys: P.PhysicalPlan = P.PSort(self.orders, P.PScan(0, batch.schema))
        if self.topk is not None:
            phys = P.PLimit(self.topk, phys)
        return compact(np, phys.run(P.ExecContext(np, [batch])))

    def add(self, batch: ColumnBatch) -> bool:
        self.spill.add(batch)
        if self.topk is not None and \
                self.spill.total_rows > max(4 * self.topk, 1 << 16):
            runs = self.spill.drain()
            folded = self._sort_limit(
                union_all(runs) if len(runs) > 1 else runs[0])
            self.spill.add(folded)
        return True

    def _native_merge(self, runs: List[ColumnBatch]) -> Optional[ColumnBatch]:
        """k-way merge of the sorted runs with the native heap kernel —
        applies when the sort is a single plain integral/timestamp column
        with nulls grouped at one end (the common ORDER BY <key> case)."""
        if len(self.orders) != 1 or self.topk is not None:
            return None
        expr, asc, _nf = self.orders[0]
        if not isinstance(expr, Col):
            return None
        from ..native import merge_sorted_runs

        def live_prefix(b: ColumnBatch) -> ColumnBatch:
            # compacted runs hold live rows as a prefix; drop the padding
            # so run offsets line up with the concatenation
            n = int(np.asarray(b.num_rows()))
            if n == b.capacity and b.row_valid is None:
                return b
            vecs = [ColumnVector(np.asarray(v.data)[:n], v.dtype,
                                 None if v.valid is None
                                 else np.asarray(v.valid)[:n], v.dictionary)
                    for v in b.vectors]
            return ColumnBatch(list(b.names), vecs, None, n)

        runs = [live_prefix(r) for r in runs]
        key_arrays = []
        for r in runs:
            try:
                vec = r.column(expr.name)
            except ValueError:
                return None
            if vec.dictionary is not None or vec.valid is not None:
                return None
            data = np.asarray(vec.data)
            if not np.issubdtype(data.dtype, np.signedinteger):
                return None           # uint64 > int64max would wrap
            data = data.astype(np.int64)
            if not asc and len(data) \
                    and data.min() == np.iinfo(np.int64).min:
                return None           # -INT64_MIN overflows: fall back
            key_arrays.append(data if asc else -data)
        perm = merge_sorted_runs(key_arrays)
        cat = union_all(runs) if len(runs) > 1 else runs[0]
        vectors = [
            ColumnVector(np.asarray(v.data)[perm], v.dtype,
                         None if v.valid is None
                         else np.asarray(v.valid)[perm], v.dictionary)
            for v in cat.vectors
        ]
        rv = None if cat.row_valid is None \
            else np.asarray(cat.row_valid)[perm]
        return ColumnBatch(list(cat.names), vectors, rv, cat.capacity)

    def finish(self) -> ColumnBatch:
        runs = self.spill.drain()
        if not runs:
            raise RuntimeError("no scan batches produced")
        runs = [compact(np, r) for r in runs]
        merged = self._native_merge(runs)
        if merged is not None:
            return merged
        return self._sort_limit(union_all(runs) if len(runs) > 1 else runs[0])


class _DistinctMerger:
    """Per-batch distincts re-distincted whenever the accumulation exceeds
    a budget that grows if the true distinct count is legitimately larger."""

    def __init__(self, spill: SpilledRuns, fold_rows: int):
        self.spill = spill
        self.fold_rows = fold_rows

    def _fold(self) -> None:
        runs = self.spill.drain()
        folded = compact(
            np, k_distinct(np, union_all(runs) if len(runs) > 1 else runs[0]))
        self.spill.add(folded)
        got = self.spill.total_rows
        if got > self.fold_rows:
            self.fold_rows = 2 * got          # avoid quadratic refolding

    def add(self, batch: ColumnBatch) -> bool:
        self.spill.add(batch)
        if self.spill.total_rows > self.fold_rows:
            self._fold()
        return True

    def finish(self) -> ColumnBatch:
        self._fold()
        runs = self.spill.drain()
        return runs[0] if runs else ColumnBatch.empty(T.StructType([]))


class _AggMerger:
    """Accumulates DPartialAggregate outputs (keys + raw buffer columns),
    folds them with per-buffer-kind re-reduction (sum-of-sums, min-of-mins),
    and finishes once via DFinalAggregate — the exact merge contract the
    distributed layer uses across shards, reused across scan batches."""

    def __init__(self, keys, slots, child_schema: T.StructType,
                 fold_rows: int, str_minmax_dicts):
        from ..parallel.dist import DPartialAggregate
        self.keys = list(keys)
        self.slots = list(slots)
        self.child_schema = child_schema
        self.partial = DPartialAggregate(
            self.keys, self.slots, P.PScan(0, child_schema))
        self.fold_rows = fold_rows
        self._acc: List[ColumnBatch] = []
        self._rows = 0
        # slot_idx -> dictionary for string-typed min/max/first value buffers
        self._str_dicts = str_minmax_dicts
        self._first_slots = [i for i, (f, _n) in enumerate(self.slots)
                             if isinstance(f, First)]
        self._batch_ord = -1   # bumped by next_batch() before each scan batch

    def __setstate__(self, state):
        # checkpoints pickled by builds that predate the first/last rank
        # rebase lack these fields; default them (such checkpoints cannot
        # contain First slots — the old guard excluded them)
        self.__dict__.update(state)
        self.__dict__.setdefault("_first_slots", [
            i for i, (f, _n) in enumerate(self.slots)
            if isinstance(f, First)])
        self.__dict__.setdefault("_batch_ord", -1)

    def next_batch(self) -> None:
        """Called once per scan batch (before its runs are added): advances
        the scan ordinal used to rebase first/last ranks across batches."""
        self._batch_ord += 1

    def _attach_dicts(self, pbatch: ColumnBatch) -> ColumnBatch:
        if not self._str_dicts:
            return pbatch
        vectors = list(pbatch.vectors)
        for i, d in self._str_dicts.items():
            func = self.slots[i][0]
            # First/Last carry (rank, value, valid): the VALUE buffer is
            # index 1; min/max value buffers are index 0
            bidx = 1 if isinstance(func, First) else 0
            bname = self.partial.buffer_names(i, func)[bidx]
            j = pbatch.names.index(bname)
            v = vectors[j]
            # typed as STRING (codes + dictionary) so union_all's fold path
            # carries the dictionary through intermediate merges
            vectors[j] = ColumnVector(v.data.astype(np.int32), T.string,
                                      v.valid, d)
        return ColumnBatch(list(pbatch.names), vectors, pbatch.row_valid,
                           pbatch.capacity)

    def _rebase_ranks(self, pbatch: ColumnBatch) -> ColumnBatch:
        """Re-encode first/last rank buffers from per-batch coordinates
        (shard << 48 | row) into scan-global (batch_ord, shard, row)
        lexicographic int64s, so the cross-batch min/max picks the
        scan-order-first (or -last) contributing row — the determinism the
        single-batch path already provides."""
        if not self._first_slots:
            return pbatch
        if self._batch_ord >= (1 << 29):
            raise RuntimeError("first/last rank rebase overflow: > 2^29 "
                               "scan batches")
        live = np.asarray(pbatch.row_valid_or_true())
        names = list(pbatch.names)
        vectors = list(pbatch.vectors)
        for i in self._first_slots:
            func = self.slots[i][0]
            is_last = getattr(func, "ARGREDUCE", "first") == "last"
            dead = np.int64(-1) if is_last else np.int64(1 << 62)
            bname = self.partial.buffer_names(i, func)[0]
            j = names.index(bname)
            v = vectors[j]
            rank = np.asarray(v.data).astype(np.int64)
            mask = live & (rank != dead)
            shard = rank >> np.int64(48)
            row = rank & np.int64((1 << 48) - 1)
            # bounds on the OBSERVED fields (the scan-batch capacity the
            # row indices were drawn from is bigger than this compacted
            # partial batch — checking pbatch.capacity would pass silently)
            if mask.any():
                if int(row[mask].max()) >= (1 << 24):
                    raise RuntimeError(
                        "first/last rank rebase requires scan batches "
                        "<= 2^24 rows")
                if int(shard[mask].max()) >= 256:
                    raise RuntimeError(
                        "first/last rank rebase supports at most 256 "
                        "shards per batch")
            enc = (np.int64(self._batch_ord) << np.int64(32)) \
                | (shard << np.int64(24)) | row
            vectors[j] = ColumnVector(np.where(mask, enc, dead), v.dtype,
                                      v.valid, v.dictionary)
        return ColumnBatch(names, vectors, pbatch.row_valid, pbatch.capacity)

    def _fold(self) -> None:
        if len(self._acc) <= 1:
            return
        from ..parallel.dist import DMergePartial
        allp = union_all(self._acc)
        merge = DMergePartial(self.keys, self.slots, self.partial,
                              P.PScan(0, allp.schema))
        folded = compact(np, merge.run(P.ExecContext(np, [allp])))
        self._acc = [folded]
        self._rows = int(np.asarray(folded.num_rows()))

    def add(self, pbatch: ColumnBatch) -> bool:
        pbatch = self._rebase_ranks(self._attach_dicts(pbatch))
        self._acc.append(pbatch)
        self._rows += int(np.asarray(pbatch.num_rows()))
        if self._rows > self.fold_rows:
            self._fold()
        return True

    def finish(self) -> ColumnBatch:
        from ..parallel.dist import DFinalAggregate
        if not self._acc:
            raise RuntimeError("no scan batches produced")
        self._fold()
        state = self._acc[0]
        final = DFinalAggregate(self.keys, self.slots, self.partial,
                                P.PScan(0, state.schema))
        return compact(np, final.run(P.ExecContext(np, [state])))


class _GraceAggMerger:
    """Grace hash aggregation for aggregates with no fixed-width mergeable
    partial (collect_list/collect_set, percentile — and any mix of them
    with ordinary slots): raw spine rows stream into spill buckets by
    group-key hash (the grace join's ``_BucketStore``: shared RAM budget,
    native counting-sort partitioner), and each bucket is aggregated
    EAGERLY host-side at finish.  Groups never straddle buckets, so
    per-bucket results are exact and disjoint — the
    ``ObjectHashAggregateExec`` + ``SortAggregateExec`` fallback role
    (``ObjectHashAggregateExec.scala``)."""

    def __init__(self, session, agg, spine_schema: T.StructType,
                 n_buckets: int, budget_rows: int, spill_dir: str):
        from .stages import _BucketStore
        self.session = session
        self.keys = list(agg.keys)
        self.aggs = list(agg.aggs)
        self.spine_schema = spine_schema
        self.n_buckets = max(1, n_buckets) if self.keys else 1
        self.store = _BucketStore(self.n_buckets, budget_rows, spill_dir)

    def __getstate__(self):
        # the session holds locks and is process-local; a resumed merger
        # reattaches to the active session at finish time
        d = dict(self.__dict__)
        d["session"] = None
        return d

    def add(self, batch: ColumnBatch) -> bool:
        from .stages import _live
        live = _live(compact(np, batch.to_host()))
        if live.capacity == 0:
            return True
        if self.n_buckets == 1:
            bucket = np.zeros(live.capacity, np.int64)
        else:
            from ..expressions import Hash64
            ectx = EvalContext(live, np)
            h = ectx.broadcast(Hash64(*self.keys).eval(ectx)).data
            bucket = (np.asarray(h).astype(np.uint64)
                      % np.uint64(self.n_buckets)).astype(np.int64)
        self.store.add(live, bucket)
        return True

    def _eager_agg(self, bucket_batch: ColumnBatch) -> ColumnBatch:
        session = self.session
        if session is None:
            from .session import SparkSession
            session = SparkSession.getActiveSession()
            if session is None:
                raise RuntimeError(
                    "grace aggregation resumed without an active session")
        node = L.Aggregate(self.keys, self.aggs,
                           L.LocalRelation(bucket_batch))
        # shrink_aggs=False: this call site never inspects ctx.flags, and
        # the shrink's overflow flag is its only correctness escape hatch
        planner = Planner(session, shrink_aggs=False)
        leaves: List[ColumnBatch] = []
        phys = planner._to_physical(node, leaves)
        planner._assign_op_ids(phys, [1])
        out = phys.run(P.ExecContext(np, [b.to_host() for b in leaves]))
        return compact(np, out.to_host())

    def finish(self) -> ColumnBatch:
        outs: List[ColumnBatch] = []
        for b in range(self.n_buckets):
            runs = self.store.load(b)
            if not runs:
                continue
            out = self._eager_agg(
                union_all(runs) if len(runs) > 1 else runs[0])
            if int(np.asarray(out.num_rows())):
                outs.append(out)
        self.close_spills()
        if not outs:
            # zero input rows: aggregate an empty relation so a global
            # aggregate still produces its single (empty/NULL) row
            return self._eager_agg(ColumnBatch.empty(self.spine_schema))
        return union_all(outs) if len(outs) > 1 else outs[0]

    def close_spills(self) -> None:
        self.store.close()


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------

def _prefix_live(phys: P.PhysicalPlan) -> bool:
    """True when `phys`'s output provably carries all live rows in a
    prefix, so the per-batch step can skip the sort-based ``compact``:

    - the sort-grouped aggregation stages scatter groups to slots
      0..k-1 (``parallel/dist.py`` rv = arange < num_groups);
    - PSort pushes dead rows past the end (leading dead-key);
    - scan pieces arrive compacted+padded (``_emit_pieces``);
    - projects/limits preserve a prefix-live child.

    PDistinct is NOT prefix-live: its MXU bucket path leaves holes in
    the bucket table (grow mask).  Default to False when unsure —
    compact is correct either way, just slower."""
    from ..parallel.dist import (DFinalAggregate, DMergePartial,
                                 DPartialAggregate)
    if isinstance(phys, (DPartialAggregate, DFinalAggregate,
                         DMergePartial, P.PSort)):
        return True
    if isinstance(phys, (P.PScan, P.PRange)):
        return True
    if isinstance(phys, (P.PProject, P.PLimit)):
        return _prefix_live(phys.children[0])
    return False


class MultiBatchExecution:
    def __init__(self, session, dec: _Decomposed, batch_rows: int):
        self.session = session
        self.dec = dec
        self.batch_rows = batch_rows
        self.capacity = pad_capacity(batch_rows)

    # -- per-batch device step -------------------------------------------
    def _step_physical(self, template: ColumnBatch
                       ) -> Tuple[P.PhysicalPlan, T.StructType]:
        """Physical spine + breaker-partial for one scan batch — ONE
        definition shared by the local and sharded steps so the two paths
        cannot diverge in breaker mapping."""
        planner = Planner(self.session)
        node: L.LogicalPlan = L.LocalRelation(template)
        for op in self.dec.spine:
            node = _with_child(op, node)
        leaves: List[ColumnBatch] = []
        phys = planner._to_physical(node, leaves)
        spine_schema = phys.schema()
        breaker = self.dec.breaker
        if isinstance(breaker, L.Aggregate):
            if self.dec.grace:
                pass   # grace hash agg: stream raw spine rows; the merger
                       # buckets them host-side by key hash
            else:
                from ..parallel.dist import DPartialAggregate
                phys = DPartialAggregate(breaker.keys, breaker.aggs, phys)
        elif isinstance(breaker, L.Sort):
            orders = [(o.child, o.ascending, o.nulls_first)
                      for o in breaker.orders]
            phys = P.PSort(orders, phys)
            if self.dec.topk is not None:
                phys = P.PLimit(self.dec.topk, phys)
        elif isinstance(breaker, L.Distinct):
            phys = P.PDistinct(phys)
        elif isinstance(breaker, L.Limit):
            phys = P.PLimit(breaker.n, phys)
        planner._assign_op_ids(phys, [1])
        return phys, spine_schema

    def _build_step(self, template: ColumnBatch):
        """(jitted step fn, spine output schema) for one padded scan batch.

        The jitted step is one fused STAGE (scan→spine→breaker-partial,
        the map side of the exchange) and lives in the PROCESS-LOCAL
        stage-executable cache (``sql/stagecompile.py``), keyed by the
        structural fingerprint with filter/projection literals slotted
        out as runtime arguments: a fresh ``jax.jit`` object per
        execution would re-trace the identical program for every run of the same
        query, and a per-SESSION cache would still re-compile it once
        per server session."""
        from . import stagecompile as SC
        phys, spine_schema = self._step_physical(template)
        cache = SC.stage_cache(self.session)
        skey, slots = SC.stage_fingerprint(phys)
        skey = (f"mb|{skey}|{SC.leaf_signature([template])}"
                f"|{SC._conf_component(self.session)}")
        skip_compact = _prefix_live(phys)

        def make():
            from ..analysis import maybe_verify_stage_contract
            maybe_verify_stage_contract(
                self.session, SC.Stage(phys, [template.schema],
                                       phys.schema(), skey))
            entry_slots = slots          # entry owns THIS plan's literals

            def step(leaf, params):
                from .. import expressions as E
                E._slot_bindings.map = {
                    id(l): p for l, p in zip(entry_slots, params)}
                try:
                    with tracing.scope("stage.step"):
                        ctx = P.ExecContext(jnp, [leaf])
                        out = phys.run(ctx)
                        # compact = a full sort; skip it when the spine
                        # provably emits live rows as a prefix already
                        # (aggregation stages scatter groups to slots
                        # 0..k-1; sorted/limited outputs are prefix-
                        # compacted by construction) — on TPU this sort
                        # was the single largest cost of every streamed
                        # step
                        c = out if skip_compact else compact(jnp, out)
                        return c, c.num_rows()
                finally:
                    E._slot_bindings.map = None

            return step, None

        entry = cache.get_or_build(skey, make, n_ops=SC.count_ops(phys),
                                   session=self.session)
        params = SC.param_values(slots)

        def jitted(leaf):
            return cache.dispatch(entry, leaf, params)

        # introspection contract: the compiled stage program stays
        # reachable through .lower() exactly like a bare jit object
        # (program-cost tests read its HLO/cost_analysis)
        jitted.lower = lambda leaf: entry.fn.lower(leaf, params)
        return jitted, spine_schema

    # -- per-batch transfer + host-ification (overridden when sharded) ---
    def _place(self, b: ColumnBatch):
        """Device placement for one prepared scan batch.  Runs on the
        prefetch thread so the H2D copy overlaps the previous batch's
        device step."""
        with tracing.span("h2d", bytes=_leaves_nbytes([b])):
            return b.to_device()

    def _run_batch(self, jstep, leaf) -> List[ColumnBatch]:
        out_dev, n = jstep(leaf)
        with tracing.span("d2h"):        # the row count waits for the step
            return [_slice_to_host(out_dev, int(np.asarray(n)))]

    # -- merger selection ------------------------------------------------
    def _make_merger(self, spine_schema: T.StructType,
                     template: ColumnBatch):
        conf = self.session.conf
        breaker = self.dec.breaker
        spill_dir = default_spill_dir(conf)
        if isinstance(breaker, L.Aggregate):
            if self.dec.grace:
                return _GraceAggMerger(
                    self.session, breaker, spine_schema,
                    conf.get(GRACE_AGG_BUCKETS),
                    conf.get(C.SPILL_MEMORY_ROWS), spill_dir)
            str_dicts = self._string_minmax_dicts(
                breaker, spine_schema, template)
            return _AggMerger(breaker.keys, breaker.aggs, spine_schema,
                              conf.get(C.AGG_FOLD_ROWS), str_dicts)
        spill = SpilledRuns(
            conf.get(C.SPILL_MEMORY_ROWS), spill_dir,
            budget_bytes=conf.get(C.SHUFFLE_SPILL_THRESHOLD),
            run_codes=conf.get(C.SHUFFLE_WIRE_RUN_CODES))
        if isinstance(breaker, L.Sort):
            orders = [(o.child, o.ascending, o.nulls_first)
                      for o in breaker.orders]
            return _SortMerger(spill, orders, self.dec.topk)
        if isinstance(breaker, L.Distinct):
            return _DistinctMerger(spill, conf.get(C.AGG_FOLD_ROWS))
        if isinstance(breaker, L.Limit):
            return _ConcatMerger(spill, limit=breaker.n)
        return _ConcatMerger(spill)

    def _string_minmax_dicts(self, agg: L.Aggregate,
                             spine_schema: T.StructType,
                             template: ColumnBatch):
        """Dictionary per slot for min/max over STRING inputs: the partial's
        value buffer holds dictionary CODES, and the dictionary itself is
        dropped by the buffer vector — probe it host-side once on a tiny
        slice (dictionaries are trace-time-static: they depend only on the
        input dictionaries, which streamed scans fix globally, never on the
        rows)."""
        needed = [
            i for i, (f, _n) in enumerate(agg.aggs)
            if isinstance(f, (Min, Max, First)) and f.children
            and f.children[0].data_type(spine_schema).is_string
        ]
        if not needed:
            return {}
        from ..io import _slice_rows
        probe_in = _slice_rows(template.to_host(), 0,
                               min(8, template.capacity))
        probe = self._host_spine_probe(probe_in)
        ectx = EvalContext(probe, np)
        return {i: agg.aggs[i][0].children[0].eval(ectx).dictionary
                for i in needed}

    # -- main loop -------------------------------------------------------
    # -- checkpoint/restart (fault tolerance, DAGScheduler-retry analog) --
    #
    # A multi-batch run over a huge dataset is the one execution in the
    # engine long enough to be worth resuming: every CKPT_INTERVAL scan
    # batches the merger (host numpy state + spill-file references) and the
    # batch cursor are pickled atomically; a rerun of the same query over
    # the same files resumes at the cursor instead of rescanning.  Scan
    # order is deterministic (sorted files, fixed batch_rows), which is
    # what makes the cursor meaningful.  The reference's lineage-based
    # per-task retry has no SPMD analog — checkpoint/resume is the TPU
    # answer (SURVEY §2.14).
    def _ckpt_path(self) -> Optional[str]:
        import hashlib
        ckpt_dir = self.session.conf.get(MULTIBATCH_CKPT)
        if not ckpt_dir:
            return None
        rel = self.dec.rel
        ident = [repr(self.dec.spine), str(self.batch_rows)]
        for p in sorted(rel.paths):
            ident.append(p)
            try:
                ident.append(str(os.stat(p).st_mtime_ns))
            except OSError:
                pass
        key = hashlib.sha1("|".join(ident).encode()).hexdigest()[:16]
        os.makedirs(ckpt_dir, exist_ok=True)
        return os.path.join(ckpt_dir, f"mb-{key}.ckpt")

    def _ckpt_save(self, path: str, n_batches: int, merger) -> None:
        try:
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump({"n": n_batches, "merger": merger}, f,
                            protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, path)
        except Exception as e:   # a failed checkpoint must not fail the run
            _log.warning("multi-batch checkpoint to %s failed: %s", path, e)

    def _ckpt_load(self, path: Optional[str]):
        if not path or not os.path.exists(path):
            return 0, None
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            spill = getattr(payload["merger"], "spill", None)
            if spill is not None:
                for run in spill._disk:
                    if not os.path.exists(run):   # spill files vanished
                        raise FileNotFoundError(run)
            _log.info("resuming multi-batch run at batch %d from %s",
                      payload["n"], path)
            return payload["n"], payload["merger"]
        except Exception as e:           # torn/stale checkpoint: start over
            _log.warning("ignoring unusable checkpoint %s: %s", path, e)
            return 0, None

    def execute(self) -> ColumnBatch:
        from ..io import (
            prefetch_iter, reencode_strings, scan_file_batches,
            scan_prefetch_depth, scan_string_dictionaries,
        )
        rel = self.dec.rel
        fixed_dicts = scan_string_dictionaries(rel, self.batch_rows)
        ckpt = self._ckpt_path()
        interval = self.session.conf.get(MULTIBATCH_CKPT_INTERVAL)
        skip, merger = self._ckpt_load(ckpt)
        jstep = None
        n_batches = 0
        completed = False

        prep_idx = [0]

        def _prep(raw):
            # runs on the prefetch thread: Arrow decode → re-encode → pad
            # → H2D, overlapped with the consumer's device step.  Only the
            # first batch's host form is kept (step build + merger
            # template); checkpoint-skipped batches don't pay the device
            # transfer (scan order is deterministic, idx == n_batches-1).
            idx = prep_idx[0]
            prep_idx[0] += 1
            with tracing.span("scan.prep", rows=raw.capacity):
                b = normalize_valids(pad_to_capacity(
                    reencode_strings(raw, fixed_dicts), self.capacity))
            return (b if idx == 0 else None,
                    self._place(b) if idx >= skip else None)

        try:
            for b, leaf in prefetch_iter(
                    scan_file_batches(rel, self.batch_rows), _prep,
                    scan_prefetch_depth(self.session.conf)):
                self.session.raise_if_cancelled()
                if jstep is None:
                    jstep, spine_schema = self._build_step(b)
                    if merger is None:
                        merger = self._make_merger(spine_schema, b)
                n_batches += 1
                if n_batches <= skip:
                    continue             # already folded into the merger
                if hasattr(merger, "next_batch"):
                    merger.next_batch()
                runs = self._run_batch(jstep, leaf)
                with tracing.span("merge", runs=len(runs)):
                    more = all(merger.add(host) for host in runs)
                if not more:
                    _log.info("multi-batch scan early exit after %d batches",
                              n_batches)
                    break
                if ckpt and interval > 0 and n_batches % interval == 0:
                    self._ckpt_save(ckpt, n_batches, merger)
            if merger is None:
                raise RuntimeError(f"empty file relation {rel!r}")
            _log.info("multi-batch scan: %d batches of <=%d rows merged",
                      n_batches, self.batch_rows)
            with tracing.span("merge", finish=True):
                result = merger.finish()
            completed = True
        finally:
            # with checkpointing ON, spill run files referenced by the
            # checkpoint must SURVIVE a crash — that is the whole point;
            # they are cleaned on successful completion (below) or by the
            # next run's resume/restart
            spill = getattr(merger, "spill", None)
            if spill is not None and (not ckpt or completed):
                spill.close()          # crash-safe: no leaked run files
            if not completed and hasattr(merger, "close_spills") \
                    and (not ckpt):
                merger.close_spills()  # grace buckets: same crash cleanup
        if ckpt and os.path.exists(ckpt):
            try:
                os.remove(ckpt)        # completed: cursor is obsolete
            except OSError:
                pass
        return self._run_above(result)

    def _host_spine_probe(self, template: ColumnBatch) -> ColumnBatch:
        """Run the spine interpreted on the (host) template batch — used
        only to discover trace-time-static string dictionaries."""
        planner = Planner(self.session)
        node: L.LogicalPlan = L.LocalRelation(template)
        for op in self.dec.spine:
            node = _with_child(op, node)
        leaves: List[ColumnBatch] = []
        phys = planner._to_physical(node, leaves)
        planner._assign_op_ids(phys, [1])
        return phys.run(P.ExecContext(np, [template]))

    def _run_above(self, result: ColumnBatch) -> ColumnBatch:
        """Ops above the breaker run on the merged result — interpreted
        (host numpy): post-breaker data is usually tiny, and a huge
        Sort/concat result must not be forced back into HBM whole."""
        if not self.dec.above:
            return compact(np, result.to_host())
        # shrink_aggs=False: flags are not inspected here (see _eager_agg)
        planner = Planner(self.session, shrink_aggs=False)
        node: L.LogicalPlan = L.LocalRelation(result)
        for op in reversed(self.dec.above):
            node = _with_child(op, node)
        leaves: List[ColumnBatch] = []
        phys = planner._to_physical(node, leaves)
        planner._assign_op_ids(phys, [1])
        out = phys.run(P.ExecContext(np, [b.to_host() for b in leaves]))
        return compact(np, out.to_host())


class DistributedMultiBatchExecution(MultiBatchExecution):
    """Multi-batch streaming COMPOSED with the data mesh: every scan batch
    is row-sharded over the mesh and runs the spine + breaker-partial step
    as one ``shard_map`` program; per-shard results merge across batches
    through the same host mergers.

    The reference analog is a ``ShuffledRowRDD`` stage that is
    simultaneously out-of-core and distributed
    (``execution/exchange/ShuffleExchange.scala:38`` over
    ``ShuffledRowRDD:113``): here the scan streams (out-of-core), the
    per-batch compute is SPMD over the mesh, and the cross-batch merge
    happens in host memory.  Per-shard breaker outputs (sorted runs,
    partial-agg buffers, per-shard distincts/limits) are added to the
    merger as INDEPENDENT runs, which every merger already supports."""

    def __init__(self, session, dec: _Decomposed, batch_rows: int, mesh):
        super().__init__(session, dec, batch_rows)
        from ..parallel.mesh import mesh_shards
        self.mesh = mesh
        self.n = mesh_shards(mesh)

    def _build_step(self, template: ColumnBatch):
        from . import stagecompile as SC

        phys, spine_schema = self._step_physical(template)
        cache = SC.stage_cache(self.session)
        skey, slots = SC.stage_fingerprint(phys)
        skey = (f"mbdist{self.n}|{skey}|{SC.leaf_signature([template])}"
                f"|{SC._conf_component(self.session)}")
        skip_compact = _prefix_live(phys)

        def make():
            from jax.sharding import PartitionSpec
            from jax import shard_map
            from ..analysis import maybe_verify_stage_contract
            from ..parallel.mesh import DATA_AXIS
            maybe_verify_stage_contract(
                self.session, SC.Stage(phys, [template.schema],
                                       phys.schema(), skey))
            entry_slots = slots

            def shard_fn(leaf, params):
                from .. import expressions as E
                E._slot_bindings.map = {
                    id(l): p for l, p in zip(entry_slots, params)}
                try:
                    with tracing.scope("stage.step"):
                        ctx = P.ExecContext(jnp, [leaf])
                        out = phys.run(ctx)
                        # same skip as the local step: per-shard outputs
                        # of the aggregation stages are prefix-live by
                        # construction, and _run_batch passes whole shard
                        # slices (mergers consume row_valid), so layout
                        # requirements are unchanged
                        return out if skip_compact else compact(jnp, out)
                finally:
                    E._slot_bindings.map = None

            wrapped = shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(PartitionSpec(DATA_AXIS), PartitionSpec()),
                out_specs=PartitionSpec(DATA_AXIS),
                check_vma=False,
            )
            return wrapped, None

        entry = cache.get_or_build(skey, make, n_ops=SC.count_ops(phys),
                                   session=self.session)
        params = SC.param_values(slots)

        def jitted(leaf):
            return cache.dispatch(entry, leaf, params)

        # introspection contract: the compiled stage program stays
        # reachable through .lower() exactly like a bare jit object
        # (program-cost tests read its HLO/cost_analysis)
        jitted.lower = lambda leaf: entry.fn.lower(leaf, params)
        return jitted, spine_schema

    def _place(self, b: ColumnBatch):
        from ..parallel.executor import shard_leaf
        return shard_leaf(self.mesh, self.n, b)

    def _run_batch(self, jstep, leaf) -> List[ColumnBatch]:
        from ..io import _slice_rows
        out = jstep(leaf)
        with tracing.span("d2h"):        # the fetch waits for the step
            out = out.to_host()
        per = out.capacity // self.n
        runs = []
        for i in range(self.n):
            run = _slice_rows(out, i * per, (i + 1) * per)
            if int(np.asarray(run.num_rows())):
                runs.append(run)
        return runs


def plan_multibatch(session, optimized: L.LogicalPlan, mesh=None
                    ) -> Optional[MultiBatchExecution]:
    """Decide whether a query takes the multi-batch path.

    Conditions: enabled, the plan decomposes into scan→spine→breaker→above
    over a single FileRelation, and the file exceeds one batch.  With a
    ``mesh``, the per-batch step runs sharded over it."""
    if not session.conf.get(C.MULTIBATCH_ENABLED):
        return None
    dec = _decompose(optimized)
    if dec is None:
        return None
    batch_rows = session.conf.get(C.SCAN_MAX_BATCH_ROWS)
    from ..io import file_row_count
    try:
        total = file_row_count(dec.rel)
    except Exception:
        return None
    if total is None or total <= batch_rows:
        return None
    _log.info("multi-batch path: %d rows > %d rows/batch (%s)%s",
              total, batch_rows, dec.rel,
              "" if mesh is None else f" sharded over {mesh}")
    if mesh is not None:
        return DistributedMultiBatchExecution(session, dec, batch_rows, mesh)
    return MultiBatchExecution(session, dec, batch_rows)
