"""Static-shape operator kernels over ColumnBatch.

These replace the reference's Tungsten execution layer — ``BytesToBytesMap``
hash aggregation (``unsafe/map/BytesToBytesMap.java:66``), radix sort
(``collection/unsafe/sort/RadixSort.java``), and the iterator-chain operators
— with XLA-friendly primitives:

* group-by is SORT-BASED: multi-key ``lax.sort`` → runs of equal keys → a
  segmented scan within the runs, read at each run's end (``sorted_runs``,
  ``reduce_runs``).  Scatter-heavy hash maps fit TPUs poorly, and so does a
  scatter-reduce over rows that are already sorted; sorting rides the
  hardware sort and keeps shapes static (Spark itself falls back to
  sort-based aggregation when its hash map fills —
  ``TungstenAggregationIterator.scala``).
* filter never compacts — it ANDs the row mask; ``compact`` is explicit.
* every kernel is pure and shape-static, so whole pipelines trace into one
  XLA program (the WholeStageCodegen analog).

All kernels take ``xp`` (numpy | jax.numpy) — the dual-path contract.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import tracing
from . import types as T
from .aggregates import AggregateFunction, First, IDENTITY
from .columnar import (ColumnBatch, ColumnVector, PlaneColumnVector,
                       RunColumnVector, bump_run_aware, merge_dictionaries,
                       unexpanded_plane, unmaterialized_runs)
from .expressions import (Col, EvalContext, Expression, ExprValue, Rand,
                          RowIndex, SparkPartitionId)

Array = Any


def _is_np(xp) -> bool:
    return xp is np


def _scope(xp, name: str):
    """``tracing.scope(name)`` on the traced lane, nothing on the numpy
    lane: the device op names of PERF.md section 3."""
    return contextlib.nullcontext() if xp is np else tracing.scope(name)


def _scoped(name: str):
    """A kernel ``fn(xp, ...)`` under ``_scope(xp, name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def kernel(xp, *args, **kw):
            if xp is np:
                return fn(xp, *args, **kw)
            with tracing.scope(name):
                return fn(xp, *args, **kw)
        return kernel
    return deco


# ---------------------------------------------------------------------------
# sorting primitives
# ---------------------------------------------------------------------------

@_scoped("argsort")
def multi_key_argsort(xp, keys: Sequence[Array], capacity: int) -> Array:
    """Stable lexicographic argsort by keys[0], then keys[1], ...

    numpy path: ``np.lexsort`` (reversed key order).  jax path off-TPU: ONE
    variadic ``lax.sort`` over operands + iota.  On a TPU: a chain of
    single-key stable sorts, least-significant key first (LSD) — the same
    permutation, but XLA:TPU's COMPILE time of one variadic sort grows
    steeply with its operand count (2^20 rows, compiled for a v5e in PR 23:
    7 keys + iota over 430 s as one sort, 88 s as seven single-key sorts;
    3 int64 keys 389 s), and every keyed aggregate, join build and ORDER BY
    goes through here.  The chain pays at run time — each later pass gathers
    its key through the running permutation: on a v5e at 2^22 rows 0.046 s
    against 0.0105 s (2 int32 keys) and 0.090 s against 0.0121 s (int8, int8,
    int32), for 24 s against 34 s and 27 s against 59 s of compile; on the
    CPU backend, where compile is free, the variadic sort is 1.3-1.6x faster
    (``tools/prof_sort.py``; PERF.md, PR 23).  Each chained pass is its own
    scope (``argsort.pass<i>``, least-significant key first).
    """
    if _is_np(xp):
        return np.lexsort(tuple(reversed([np.asarray(k) for k in keys])))
    import jax
    iota = xp.arange(capacity, dtype=np.int32)
    if len(keys) > 1 and _on_tpu_device():
        perm = None
        for i, k in enumerate(reversed(keys)):
            with tracing.scope(f"argsort.pass{i}"):
                kp, ip = (k, iota) if perm is None else (k[perm], perm)
                _, perm = jax.lax.sort((kp, ip), num_keys=1, is_stable=True)
        return perm
    out = jax.lax.sort(tuple(keys) + (iota,), num_keys=len(keys),
                       is_stable=True)
    return out[-1]


def searchsorted(xp, a: Array, v: Array, side: str = "left") -> Array:
    """Searchsorted for both lanes: ``np.searchsorted`` on the numpy lane,
    jnp's default while-loop ``scan`` on the jax lane.  (An unrolled
    lowering was once forced on TPU on a guess; on a v5e both compile, run
    and take the same 0.36 s at a 2048-row build and a 2^21-row probe —
    PERF.md, PR 23 — and on CPU the scan is 2.3x faster.)  Every round of
    the loop is an int64 gather over all of ``v``: where the keys of ``a``
    span few integers (``keys_span_under``) a join asks ``table_search``
    instead, which reads each answer from a table indexed by the key."""
    if _is_np(xp):
        return np.searchsorted(np.asarray(a), np.asarray(v), side=side)
    return xp.searchsorted(a, v, side=side)


def keys_span_under(xp, a: Array, m: Array, size: int) -> Array:
    """True where the first ``m`` entries of the sorted int64 ``a`` hold at
    least one key and span fewer than ``size`` integers (``a[m-1] - a[0] <
    size``): a table of ``size`` entries indexed by ``key - a[0]`` then has
    a place for every one of them.  The difference is taken in uint64
    (Python integers on the numpy lane), where it cannot wrap: a build that
    holds both a very negative and a very positive key reads "not dense"."""
    if _is_np(xp):
        m = int(m)
        return m > 0 and int(a[m - 1]) - int(a[0]) < size
    first, last = a[0], a[xp.maximum(m - 1, 0)]
    span = last.astype(np.uint64) - first.astype(np.uint64)
    return (m > 0) & (span < np.uint64(size))


def table_search(xp, a: Array, m: Array, v: Array, size: int
                 ) -> Tuple[Array, Array]:
    """``(lo, n_eq)`` of every ``v`` in the first ``m`` entries of the sorted
    int64 ``a``: ``lo = searchsorted(a[:m], v, "left")`` and ``lo + n_eq``
    the ``"right"`` one, where ``keys_span_under(a, m, size)`` holds.  jax
    lane: a direct-address table in place of the searches.  ONE scatter-add
    of a 1 at ``key - a[0]`` for each of the ``m`` keys (a histogram of
    ``size`` int32 entries; ``size`` a power of two) and ONE int32 running
    sum over it; a key's count is its histogram entry and the keys at or
    below it the running sum there, so each ``v`` pays ONE int32 gather of
    the two stacked as a ``[2, size]`` plane where a search pays
    ``log2(len(a))`` rounds of an int64 one, twice (on a v5e at 2^22 rows:
    26 ms for the plane, 129 for the two tables apart, 1,617 for the
    searches — PERF.md, PR 30).  Below ``a[0]`` the answer is 0, above
    ``a[m-1]`` it is ``m``.  (XLA:TPU takes an int32 running sum inside a
    conditional's branch at every power of two, as for ``slot_owner``.)
    numpy lane: the searches themselves, the tests' independent form of the
    same lookup."""
    if _is_np(xp):
        head = np.asarray(a)[:int(m)]
        lo = np.searchsorted(head, np.asarray(v), side="left")
        return lo, np.searchsorted(head, np.asarray(v), side="right") - lo
    first, last = a[0], a[xp.maximum(m - 1, 0)]
    at = xp.where(xp.arange(a.shape[0], dtype=np.int32) < m, a - first, size)
    hist = xp.zeros(size, dtype=np.int32).at[at.astype(np.int32)].add(
        1, mode="drop", indices_are_sorted=True)
    plane = xp.stack([hist, xp.cumsum(hist, dtype=np.int32)])
    inside = (v >= first) & (v <= last)
    count, upto = plane[:, xp.where(inside, v - first, 0).astype(np.int32)]
    n_eq = xp.where(inside, count, 0)
    lo = xp.where(inside, upto - n_eq,
                  xp.where(v < first, 0, m).astype(np.int32))
    return lo, n_eq


def slot_owner(xp, ends: Array, n_slots: int) -> Array:
    """For slots ``0..n_slots-1`` the number of entries of the non-decreasing
    ``ends`` at or below the slot: ``searchsorted(ends, arange(n_slots),
    side="right")``, the inverse of a running sum (output slot → the row
    whose run of slots holds it).  jax lane: ONE scatter-add of a mark at
    every ``ends[k]`` (sorted indices; an entry at or past ``n_slots``
    drops; rows with no slot repeat their end and add up there) and ONE
    inclusive int32 running sum over the slots, as ``jnp.repeat`` does inside:
    no loop, where the search from every slot is log2(len(ends)) rounds of an
    int64 gather over ``n_slots`` (PERF.md, PR 28).  The sum never passes
    ``len(ends)``, so int32 holds it, and XLA:TPU takes an int32 running sum
    inside a conditional's branch where it refuses an int64 one.  numpy
    lane: the search itself, the tests' independent form of the same map."""
    if _is_np(xp):
        return np.searchsorted(np.asarray(ends),
                               np.arange(n_slots, dtype=np.int64),
                               side="right")
    at = xp.minimum(ends, n_slots).astype(np.int32)
    marks = xp.zeros(n_slots, dtype=np.int32).at[at].add(
        1, mode="drop", indices_are_sorted=True)
    return xp.cumsum(marks, dtype=np.int32)


def radix_argsort(xp, keys: Array, bits: int = 4) -> Array:
    """Stable LSD radix argsort of int64 keys — the TPU-native candidate
    replacement for the bitonic ``lax.sort`` (`SortBenchmark.scala:120`
    radix baseline role).

    Per digit pass: a (n, 2^bits) one-hot, column sums for the global
    digit starts, an exclusive cumsum down the rows for stable
    within-digit ranks, and one scatter to invert the placement — all
    dense, fusable ops (the one-hot contraction is MXU-shaped), no
    compare network.  ``bits=4`` keeps the per-pass working set at
    n x 16 x 4B; 16 passes cover 64 bits.  CPU lane: np.argsort
    (XLA:CPU executes the dense formulation slower than its built-in
    sort — this path exists for TPU and takes over no default before a
    chip trace, read by ``python -m spark_tpu.tracing``, says it should)."""
    if _is_np(xp):
        return np.argsort(np.asarray(keys), kind="stable")
    if 64 % bits != 0:
        raise ValueError(f"radix_argsort bits={bits} must divide 64 "
                         "(uncovered top bits would silently mis-sort)")
    import jax
    import jax.numpy as jnp
    n = keys.shape[0]
    R = 1 << bits
    k = keys.astype(jnp.uint64) ^ jnp.uint64(1 << 63)   # signed → biased
    perm = jnp.arange(n, dtype=jnp.int32)
    for p in range(64 // bits):
        digit = ((k >> jnp.uint64(p * bits))
                 & jnp.uint64(R - 1)).astype(jnp.int32)
        oh = jax.nn.one_hot(digit, R, dtype=jnp.int32)          # (n, R)
        counts = oh.sum(axis=0)
        starts = jnp.cumsum(counts) - counts                    # (R,)
        ranks = jnp.cumsum(oh, axis=0) - oh                     # exclusive
        pos = starts[digit] + jnp.take_along_axis(
            ranks, digit[:, None], axis=1)[:, 0]
        inv = jnp.zeros(n, jnp.int32).at[pos].set(
            jnp.arange(n, dtype=jnp.int32))
        k = k[inv]
        perm = perm[inv]
    return perm


def sort_key_transform(xp, data: Array, valid: Optional[Array], dtype: T.DataType,
                       ascending: bool, nulls_first: bool) -> List[Array]:
    """Turn one sort column into (null_rank, comparable_key) arrays.

    Dead rows (row_valid=False) are pushed to the very end by the caller's
    leading dead-key.  Descending order flips integer bits (``~x``) /
    negates floats, mirroring the prefix trick of ``PrefixComparators.java``.
    """
    np_dt = np.asarray(data).dtype if _is_np(xp) else data.dtype
    if np_dt == np.bool_:
        data = data.astype(np.int8)
        np_dt = np.dtype(np.int8)
    if ascending:
        key = data
    else:
        if np.issubdtype(np_dt, np.floating):
            key = -data
        else:
            key = ~data
    if valid is None:
        null_rank = xp.zeros(data.shape[0], np.int8)
    else:
        # null_rank orders: nulls_first → nulls get -1 else +1
        rank_null = np.int8(-1) if nulls_first else np.int8(1)
        null_rank = xp.where(valid, np.int8(0), rank_null)
        ident = IDENTITY["min"](np_dt) if nulls_first else IDENTITY["max"](np_dt)
        key = xp.where(valid, key, np.asarray(ident, np_dt))
    return [null_rank, key]


@_scoped("sort_batch")
def sort_batch(xp, batch: ColumnBatch,
               keys: Sequence[Tuple[Array, Optional[Array], T.DataType, bool, bool]],
               ) -> ColumnBatch:
    """Sort live rows by the given key specs; dead rows sink to the end.

    keys: (data, valid, dtype, ascending, nulls_first) per sort column.
    """
    dead = ~batch.row_valid_or_true()
    sort_cols: List[Array] = [dead.astype(np.int8)]
    for data, valid, dtype, asc, nf in keys:
        sort_cols += sort_key_transform(xp, data, valid, dtype, asc, nf)
    perm = multi_key_argsort(xp, sort_cols, batch.capacity)
    return take_batch(xp, batch, perm)


def range_bucket(xp, keys: Array, cuts: Array) -> Array:
    """Map orderable int64 join keys to contiguous span ids by binary
    search against shared cut points (RangePartitioner.getPartition
    analog, jittable).

    ``cuts`` are the ``n_spans - 1`` strictly-increasing EXCLUSIVE upper
    bounds every process derived identically from the sample round: span
    id = number of cut points ≤ the key (``side="right"``), so every
    duplicate of a value — hot keys included — lands in ONE span on
    every process.  Composes with ``partition_bucket``: the returned
    int32 span ids are that kernel's ``part_ids``.
    """
    return searchsorted(xp, cuts, keys, side="right").astype(np.int32)


@_scoped("partition_bucket")
def partition_bucket(xp, batch: ColumnBatch, part_ids: Array,
                     n_parts: int,
                     tie_keys: Optional[Sequence[Array]] = None,
                     ) -> Tuple[ColumnBatch, Array, Array]:
    """Bucket rows by partition id in ONE device sort (the exchange-side
    replacement for per-receiver host mask/compact passes).

    Dead rows fold into a virtual partition ``n_parts`` so a single-key
    stable sort (riding ``multi_key_argsort``'s lax.sort path) groups
    live rows contiguously by destination with padding at the tail.
    Returns ``(bucketed, offsets, counts)``: partition ``p``'s rows are
    ``bucketed[offsets[p] : offsets[p] + counts[p]]``, so the sender
    does one compacted D2H transfer and slices per-receiver host VIEWS
    out of it — padding never crosses DCN.  ``tie_keys`` appends extra
    sort keys AFTER the partition id, ordering rows WITHIN each bucket
    (the range exchange ships key-sorted runs this way — same single
    sort, no extra pass).  Jittable on the jnp path (``n_parts``
    static); numpy path is the host fallback.
    """
    live = batch.row_valid_or_true()
    pid = xp.where(live, xp.asarray(part_ids).astype(np.int32),
                   np.int32(n_parts))
    sort_keys = [pid] + [xp.asarray(k) for k in (tie_keys or [])]
    perm = multi_key_argsort(xp, sort_keys, batch.capacity)
    bucketed = take_batch(xp, batch, perm)
    if _is_np(xp):
        counts = np.bincount(np.asarray(pid)[np.asarray(live)],
                             minlength=n_parts).astype(np.int32)
    else:
        # dead rows carry pid == n_parts; out-of-bounds scatter adds drop
        counts = xp.zeros(n_parts, np.int32).at[pid].add(
            np.int32(1), mode="drop")
    offsets = xp.concatenate(
        [xp.zeros(1, np.int32), xp.cumsum(counts)[:-1].astype(np.int32)])
    return bucketed, offsets, counts


def partition_host_slices(xp, batch: ColumnBatch, part_ids: Array,
                          n_parts: int,
                          tie_keys: Optional[Sequence[Array]] = None,
                          ) -> Tuple[ColumnBatch, Array, Array]:
    """``partition_bucket`` + one D2H transfer + host offset/count arrays.

    The shared front half of every DCN route (aggregate-state exchange,
    shuffled-join co-partitioning): callers carve zero-copy per-receiver
    views out of the returned host batch with ``slice_rows``.  Because
    the bucketing sort is stable and partition ids ascend, any CONTIGUOUS
    range of partitions is itself one contiguous slice — which is what
    lets the manifest coordinator coalesce adjacent fine partitions into
    a single receiver block without re-bucketing.
    """
    bucketed, offsets, counts = partition_bucket(xp, batch, part_ids,
                                                 n_parts, tie_keys)
    return (bucketed.to_host(), np.asarray(offsets), np.asarray(counts))


def slice_rows(batch: ColumnBatch, start: int, count: int) -> ColumnBatch:
    """A zero-copy HOST view of rows ``[start, start + count)`` — numpy
    basic slicing, every column shares the parent's buffers.  Rows in the
    window are assumed live (``partition_bucket`` guarantees it), so the
    view drops the row mask."""
    vectors = [
        ColumnVector(np.asarray(v.data)[start:start + count], v.dtype,
                     None if v.valid is None
                     else np.asarray(v.valid)[start:start + count],
                     v.dictionary)
        for v in batch.vectors
    ]
    return ColumnBatch(list(batch.names), vectors, None, count)


@_scoped("take_batch")
def take_batch(xp, batch: ColumnBatch, perm: Array) -> ColumnBatch:
    """Gather all columns (and masks) through an index array.

    ``perm`` may be longer/shorter than the input capacity (join expansion);
    the output capacity is ``len(perm)``.
    """
    out_cap = int(perm.shape[0])
    vectors = []
    for v in batch.vectors:
        data = v.data[perm]
        valid = None if v.valid is None else v.valid[perm]
        vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
    rv = None if batch.row_valid is None else batch.row_valid[perm]
    return ColumnBatch(batch.names, vectors, rv, out_cap)


@_scoped("compact")
def compact(xp, batch: ColumnBatch) -> ColumnBatch:
    """Move live rows to the front, preserving order (stable).

    Device path: ONE single-operand uint32 sort — the dead flag rides
    the iota's top bit (capacity < 2^31 always), so the sorted values
    ARE the permutation: live rows (bit clear) sort first in original
    order, dead rows after.  Half the comparator/permute work of the
    two-operand (flag, iota) formulation on the TPU's bitonic sort."""
    if batch.row_valid is None:
        return batch
    if _is_np(xp):
        dead = (~batch.row_valid).astype(np.int8)
        perm = multi_key_argsort(xp, [dead], batch.capacity)
        return take_batch(xp, batch, perm)
    import jax
    dead = ~batch.row_valid
    iota = xp.arange(batch.capacity, dtype=np.uint32)
    packed = iota | (dead.astype(np.uint32) << np.uint32(31))
    (packed_s,) = jax.lax.sort((packed,), num_keys=1, is_stable=False)
    perm = (packed_s & np.uint32(0x7FFFFFFF)).astype(np.int32)
    return take_batch(xp, batch, perm)


# ---------------------------------------------------------------------------
# run-length / delta codecs (the wire.py "enc" tags; see RunColumnVector)
# ---------------------------------------------------------------------------

def rle_encode(data: Array) -> Tuple[np.ndarray, np.ndarray]:
    """Run-length encode a 1-D host array into ``(run_values, run_lengths)``.

    Run detection is ONE vectorized diff + nonzero — no Python loop."""
    data = np.asarray(data)
    n = len(data)
    if n == 0:
        return data[:0], np.zeros(0, np.int64)
    change = np.nonzero(data[1:] != data[:-1])[0] + 1
    starts = np.concatenate([np.zeros(1, np.int64), change])
    lengths = np.diff(np.concatenate([starts, np.asarray([n], np.int64)]))
    return data[starts], lengths.astype(np.int64)


def rle_expand(xp, run_values: Array, run_lengths: Array) -> Array:
    """Expand a run table back to the dense array (cumsum/repeat only, so
    the jax lane traces when the output length is static)."""
    return xp.repeat(xp.asarray(run_values), xp.asarray(run_lengths))


def run_row_ids(xp, plane_lengths: Array, capacity: int) -> Array:
    """Row → run-index map for a fixed-capacity run plane (shape-stable,
    jittable): inclusive-cumsum the zero-padded lengths into run END
    offsets, then binary-search each row position right of its end.
    Zero-length (padded) runs collapse to repeated ends that the
    ``side="right"`` search skips, so every row lands on a REAL run.
    O(capacity · log planes) compares, no scatter."""
    ends = xp.cumsum(xp.asarray(plane_lengths).astype(np.int64))
    rows = xp.arange(capacity, dtype=np.int64)
    ids = searchsorted(xp, ends, rows, side="right")
    # rows past sum(lengths) (never produced by a well-formed plane) clamp
    # into range instead of indexing out of bounds
    return xp.clip(ids, 0, max(int(plane_lengths.shape[0]) - 1, 0))


def run_expand(xp, plane_values: Array, plane_lengths: Array,
               capacity: int) -> Array:
    """Searchsorted-gather expansion of a run plane to its dense array —
    the jit-lane analog of ``rle_expand`` (whose ``repeat`` needs a data-
    dependent output length).  numpy lane: plain repeat (exact and
    cheaper on host)."""
    if _is_np(xp):
        return np.repeat(np.asarray(plane_values),
                         np.asarray(plane_lengths))[:capacity]
    return xp.asarray(plane_values)[run_row_ids(xp, plane_lengths, capacity)]


def delta_encode(data: Array) -> Optional[Tuple[int, np.ndarray]]:
    """Delta / frame-of-reference encode a 1-D signed-int host array as
    ``(base, diffs)`` with diffs downcast to the narrowest of
    int8/int16/int32 that bounds them.  Diffs are taken in int64 modular
    arithmetic, so ``delta_decode``'s cumsum reconstruction is exact even
    across wraparound.  Returns None when no strictly narrower diff dtype
    exists (encoding would not shrink the column)."""
    data = np.asarray(data)
    if len(data) < 2:
        return None
    d64 = np.diff(data.astype(np.int64))
    lo, hi = int(d64.min()), int(d64.max())
    for cand in (np.int8, np.int16, np.int32):
        if np.dtype(cand).itemsize >= data.dtype.itemsize:
            break
        info = np.iinfo(cand)
        if info.min <= lo and hi <= info.max:
            return int(data[0]), d64.astype(cand)
    return None


def delta_decode(xp, base: int, diffs: Array, np_dtype, n: int) -> Array:
    """cumsum reconstruction of a delta-encoded column; exact under int64
    modular arithmetic regardless of the original dtype's wraparound."""
    if n == 0:
        return xp.zeros(0, np_dtype)
    d = xp.asarray(diffs).astype(np.int64)
    prefix = xp.concatenate([xp.zeros(1, np.int64), xp.cumsum(d)])
    return (np.int64(base) + prefix).astype(np_dtype)


# ---------------------------------------------------------------------------
# row-mask operators
# ---------------------------------------------------------------------------

#: expression classes whose value depends on the row's POSITION rather than
#: the row's data — a run head cannot stand in for its whole run under these
#: (Randn subclasses Rand; all four read ``ctx.row_offset``)
_POSITIONAL_EXPRS = (Rand, RowIndex, SparkPartitionId)


def _run_aware_filter(batch: ColumnBatch,
                      pred: Expression) -> Optional[ColumnBatch]:
    """Evaluate ``pred`` once per run head and expand the selection mask by
    run length.  Applies when the predicate references exactly one column,
    that column is an unexpanded run vector covering the batch, and the
    predicate is data-deterministic (no positional expressions).  Returns
    None to fall back to the dense path."""
    refs = pred.references()
    if len(refs) != 1:
        return None
    name = next(iter(refs))
    if name not in batch.names:
        return None
    rv = unmaterialized_runs(batch.column(name))
    if rv is None or rv.valid is not None or rv.capacity != batch.capacity:
        return None
    stack = [pred]
    while stack:
        e = stack.pop()
        if isinstance(e, _POSITIONAL_EXPRS):
            return None
        stack.extend(e.children)
    n_runs = len(rv.run_values)
    head = ColumnBatch(
        [name], [ColumnVector(rv.run_values, rv.dtype, None, rv.dictionary)],
        None, n_runs)
    v = pred.eval(EvalContext(head, np))
    keep = np.broadcast_to(np.asarray(v.data), (n_runs,))
    if v.valid is not None:
        keep = keep & np.broadcast_to(np.asarray(v.valid), (n_runs,))
    keep = np.repeat(keep.astype(bool), rv.run_lengths)
    bump_run_aware(batch.capacity)
    out_rv = np.asarray(batch.row_valid_or_true()) & keep
    return ColumnBatch(batch.names, batch.vectors, out_rv, batch.capacity)


def _plane_filter(xp, batch: ColumnBatch,
                  pred: Expression) -> Optional[ColumnBatch]:
    """Jit-lane twin of ``_run_aware_filter``: evaluate ``pred`` once per
    run HEAD of a device plane, then expand only the boolean keep mask
    through ``run_row_ids`` — the data column never expands.  Applies
    when the predicate references exactly one column, that column is an
    unexpanded run plane covering the batch, and the predicate is
    data-deterministic (no positional expressions).  Returns None to
    fall back to the dense path (which expands in-trace, counted)."""
    refs = pred.references()
    if len(refs) != 1:
        return None
    name = next(iter(refs))
    if name not in batch.names:
        return None
    pv = unexpanded_plane(batch.column(name))
    if pv is None or pv.valid is not None or pv.capacity != batch.capacity:
        return None
    stack = [pred]
    while stack:
        e = stack.pop()
        if isinstance(e, _POSITIONAL_EXPRS):
            return None
        stack.extend(e.children)
    plane_cap = pv.plane_capacity
    head = ColumnBatch(
        [name],
        [ColumnVector(pv.plane_values, pv.dtype, None, pv.dictionary)],
        None, plane_cap)
    v = pred.eval(EvalContext(head, xp))
    keep = xp.broadcast_to(v.data, (plane_cap,))
    if v.valid is not None:
        keep = keep & xp.broadcast_to(v.valid, (plane_cap,))
    keep_rows = keep.astype(bool)[run_row_ids(xp, pv.plane_lengths,
                                              batch.capacity)]
    out_rv = batch.row_valid_or_true() & keep_rows
    return ColumnBatch(batch.names, batch.vectors, out_rv, batch.capacity)


def apply_filter(xp, batch: ColumnBatch, pred: Expression,
                 row_offset: int = 0) -> ColumnBatch:
    if _is_np(xp) and row_offset == 0:
        out = _run_aware_filter(batch, pred)
        if out is not None:
            return out
    if not _is_np(xp):
        # no row_offset gate: the offset only feeds positional
        # expressions, which _plane_filter already refuses
        out = _plane_filter(xp, batch, pred)
        if out is not None:
            return out
    ctx = EvalContext(batch, xp, row_offset)
    v = pred.eval(ctx)
    keep = v.data
    if v.valid is not None:
        keep = keep & v.valid          # NULL predicate → drop (SQL WHERE)
    rv = batch.row_valid_or_true() & keep
    return ColumnBatch(batch.names, batch.vectors, rv, batch.capacity)


def apply_project(xp, batch: ColumnBatch, exprs: Sequence[Expression],
                  row_offset: int = 0) -> ColumnBatch:
    ctx = EvalContext(batch, xp, row_offset)
    names, vectors = [], []
    schema = batch.schema
    for e in exprs:
        if isinstance(e, Col) and e._name in batch.names:
            # bare column select keeps run forms (plane or host run table)
            # un-inflated — evaluating through EvalContext would expand
            src = batch.column(e._name)
            if (unexpanded_plane(src) is not None
                    or unmaterialized_runs(src) is not None) \
                    and src.dtype.np_dtype == e.data_type(schema).np_dtype:
                names.append(e.name)
                vectors.append(src)
                continue
        v = ctx.broadcast(e.eval(ctx))
        dt = e.data_type(schema)
        names.append(e.name)
        vectors.append(ColumnVector(v.data.astype(dt.np_dtype), dt, v.valid,
                                    v.dictionary))
    return ColumnBatch(names, vectors, batch.row_valid, batch.capacity)


def apply_limit(xp, batch: ColumnBatch, n: int) -> ColumnBatch:
    rv = batch.row_valid_or_true()
    keep = xp.cumsum(rv.astype(np.int64)) <= n
    return ColumnBatch(batch.names, batch.vectors, rv & keep, batch.capacity)


# ---------------------------------------------------------------------------
# segment reductions
# ---------------------------------------------------------------------------

def _np_segment_reduce(data: np.ndarray, seg: np.ndarray, num: int, kind: str,
                       ident) -> np.ndarray:
    out = np.full(num, ident, dtype=data.dtype)
    if kind == "sum":
        np.add.at(out, seg, data)
    elif kind == "min":
        np.minimum.at(out, seg, data)
    else:
        np.maximum.at(out, seg, data)
    return out


def _global_reduce(xp, data: Array, kind: str, capacity: int) -> Array:
    """One-segment reduction: the whole (already contribute-masked)
    buffer collapses to slot 0; remaining slots hold the identity, as
    segment_reduce would leave them.  No sort, no scatter."""
    np_dt = np.asarray(data).dtype if _is_np(xp) else np.dtype(str(data.dtype))
    ident = IDENTITY[kind](np_dt)
    if capacity == 0:
        # capacity-0 host batches: segment_reduce returned shape (0,)
        return xp.zeros(0, np_dt)
    if kind == "sum":
        val = data.sum()
    elif kind == "min":
        val = data.min()
    else:
        val = data.max()
    rest = xp.full(capacity - 1, ident, np_dt)
    return xp.concatenate([xp.asarray(val).reshape(1).astype(np_dt), rest])


def segment_reduce(xp, data: Array, seg_ids: Array, num_segments: int,
                   kind: str) -> Array:
    """``out[s] = reduce(data[seg_ids == s])``, the kind's identity where no
    row has ``s``; ``seg_ids`` in any order.  numpy lane: ``np.add.at`` and
    its kin, the form every keyed aggregate's numpy lane reduces by (the
    tests' independent form of ``reduce_runs``).  jax lane: a scatter-reduce
    that does not know whether its ids are sorted (68 ns an element with a
    64-bit combiner on a v5e, 55 with int32 ids and ``indices_are_sorted``;
    PERF.md, PR 32).  Since PR 32 the keyed sum / min / max path is off it
    (``reduce_runs``); left on it, jax lane, are the callers whose ids are
    NOT runs of sorted rows or that no cell reaches: ``_plane_global_
    aggregate`` (live rows by run-plane slot), ``_percentile_groups`` and
    ``_collect_into_arrays`` (their own value-sorted segments, int64 ids)
    and ``sql/window.py``'s frame reduction (ids by partition, unsorted)."""
    np_dt = np.asarray(data).dtype if _is_np(xp) else np.dtype(str(data.dtype))
    ident = IDENTITY[kind](np_dt)
    if _is_np(xp):
        return _np_segment_reduce(np.asarray(data), np.asarray(seg_ids),
                                  num_segments, kind, ident)
    import jax
    if kind == "sum":
        return jax.ops.segment_sum(data, seg_ids, num_segments=num_segments)
    if kind == "min":
        return jax.ops.segment_min(data, seg_ids, num_segments=num_segments)
    return jax.ops.segment_max(data, seg_ids, num_segments=num_segments)


# ---------------------------------------------------------------------------
# runs of sorted rows: what the sort aggregate does after its argsort
# ---------------------------------------------------------------------------
#
# Rows sorted by group are reduced where they lie.  A scatter-reduce
# (``segment_reduce``) does not know its ids are sorted and pays a serial
# 64-bit combine an element on a TPU (68 ns; PERF.md, PR 28); on sorted rows
# the same reduction is a segmented scan, and a group's result is the scan
# at its run's last row.  Everything here is int32 (a capacity is static and
# under 2^31) and moves columns through an index as ONE plane
# (``gather_columns``): a gather's cost on the chip is its index, not its
# width (a plane of 5, 8 or 13 words through 2^22 indices takes 105-115 ms
# on a v5e, one int32 column 36-47; PERF.md, PR 32).

_RSUM_ROW = 1024


def running_sum_i32(xp, x: Array) -> Array:
    """Inclusive int32 running sum of ``x`` (jax lane).  Past ``_RSUM_ROW``
    elements in two levels (running sums along rows of ``_RSUM_ROW``, the
    running sum of the row totals added back): XLA:TPU's own ``cumsum`` of
    2^22 elements is one reduce-window of 4-19 s of compile and over a
    megabyte of code, this form 0.2-0.5 s, at the same speed (PERF.md
    section 7), and the compiler takes it inside a conditional's branch."""
    n = int(x.shape[0])
    x = x.astype(np.int32)
    if n <= _RSUM_ROW or n % _RSUM_ROW:
        return xp.cumsum(x, dtype=np.int32)
    within = xp.cumsum(x.reshape(n // _RSUM_ROW, _RSUM_ROW), axis=1,
                       dtype=np.int32)
    totals = within[:, -1]
    before = xp.cumsum(totals, dtype=np.int32) - totals
    return (within + before[:, None]).reshape(n)


def running_max_i32(xp, x: Array) -> Array:
    """Inclusive int32 running maximum of ``x``, in ``running_sum_i32``'s
    two levels past ``_RSUM_ROW`` elements: XLA:TPU compiles one
    ``cummax`` of 2^20 elements in 25 s (64 s in int64), the two levels in
    1.6 s (described v5e; PERF.md section 7)."""
    x = x.astype(np.int32)
    if _is_np(xp):
        return np.maximum.accumulate(x)
    import jax
    n = int(x.shape[0])
    if n <= _RSUM_ROW or n % _RSUM_ROW:
        return jax.lax.cummax(x)
    within = jax.lax.cummax(x.reshape(n // _RSUM_ROW, _RSUM_ROW), axis=1)
    rows = jax.lax.cummax(within[:, -1])
    before = xp.concatenate([xp.full((1,), np.iinfo(np.int32).min,
                                     np.int32), rows[:-1]])
    return xp.maximum(within, before[:, None]).reshape(n)


def gather_columns(xp, cols: Sequence[Array], idx: Array) -> List[Array]:
    """``[c[idx] for c in cols]`` for 1-D columns of one length, each back
    in its own dtype, bit for bit.  jax lane: every column but a float64
    one rides ONE ``[k, n]`` plane of uint32 words through ONE gather
    (8-byte integers as two words by ``bitcast_convert_type``, 4-byte ones
    as one, 2-byte ones sign-extended, bools / int8 packed four to a word);
    float64 columns ride a second, float64 plane, because XLA:TPU has no
    bitcast out of its emulated float64 (and a cast would not keep the
    bits).  ``idx`` must be in bounds."""
    if _is_np(xp):
        idx = np.asarray(idx)
        return [np.asarray(c)[idx] for c in cols]
    import jax
    bitcast = jax.lax.bitcast_convert_type
    words: List[Array] = []       # rows of the uint32 plane
    floats: List[Array] = []      # rows of the float64 plane
    small: List[Array] = []       # 1-byte columns, packed after the loop
    back = []                     # (plane, fplane, bytes' first row) -> column
    for c in cols:
        at, dt = len(words), c.dtype
        if dt == np.float64:
            back.append(lambda w, f, b, at=len(floats): f[at])
            floats.append(c)
        elif dt.itemsize == 8:
            pair = bitcast(c, np.uint32)
            words += [pair[..., 0], pair[..., 1]]
            back.append(lambda w, f, b, at=at, dt=dt: bitcast(
                xp.stack([w[at], w[at + 1]], axis=-1), dt))
        elif dt.itemsize == 4:
            words.append(bitcast(c, np.uint32))
            back.append(lambda w, f, b, at=at, dt=dt: bitcast(w[at], dt))
        elif dt.itemsize == 2:
            words.append(bitcast(c.astype(np.int32), np.uint32))
            back.append(lambda w, f, b, at=at, dt=dt: bitcast(
                w[at], np.int32).astype(dt))
        else:
            back.append(lambda w, f, b, at=len(small), dt=dt: (
                (w[b + at // 4] >> np.uint32(8 * (at % 4))) & np.uint32(0xFF)
            ).astype(np.uint8).astype(dt))
            small.append(c.astype(np.uint8).astype(np.uint32))
    first_small = len(words)
    for i in range(0, len(small), 4):
        word = small[i]
        for j, byte in enumerate(small[i + 1:i + 4], 1):
            word = word | (byte << np.uint32(8 * j))
        words.append(word)
    plane = xp.stack(words)[:, idx] if words else None
    fplane = xp.stack(floats)[:, idx] if floats else None
    return [get(plane, fplane, first_small) for get in back]


class SortedRuns(NamedTuple):
    """The rows of a keyed aggregate once sorted by (liveness, keys): runs
    of equal keys, live rows first.  Group ``g`` is the ``g``-th run."""
    perm: Array          # sorted position -> row
    seg_ids: Array       # sorted position -> group; a dead row: capacity-1
    is_start: Array      # sorted position opens a live run
    live_s: Array        # sorted position holds a live row
    num_groups: Array
    # jax lane only (the numpy lane scatters and has no use for them):
    start_of: Optional[Array] = None   # group -> sorted position of its first
    end_of: Optional[Array] = None     # and of its last row (in bounds)
    longest: Optional[Array] = None    # rows of the longest live run


def group_sort_columns(xp, key_vals: Sequence[ExprValue], live: Array
                       ) -> List[Array]:
    """The sort columns of a keyed aggregate: liveness first (dead rows
    last), then each key as (null rank, value); NULL is a group of its own
    and ranks before every value.  A key with no validity has no null
    rank: a constant column changes no stable order, and costs a TPU a sort
    pass and a gather."""
    sort_cols: List[Array] = [(~live).astype(np.int8)]
    for v in key_vals:
        data = v.data
        if (np.asarray(data).dtype if _is_np(xp) else data.dtype) == np.bool_:
            data = data.astype(np.int8)
        if v.valid is None:
            sort_cols.append(data)
        else:
            sort_cols += [xp.where(v.valid, np.int8(0), np.int8(-1)),
                          xp.where(v.valid, data, xp.zeros((), data.dtype))]
    return sort_cols


def _run_starts(xp, sorted_cols: Sequence[Array], live_s: Array,
                capacity: int) -> Array:
    """Sorted positions that open a live run: some sort column differs from
    the row before (position 0 always does)."""
    change = xp.zeros(capacity, bool)
    for c in sorted_cols:
        change = change | (c != xp.concatenate([c[:1], c[:-1]]))
    if _is_np(xp):
        change[:1] = True          # a capacity-0 host batch has no row 0
    else:
        change = change.at[0].set(True)
    return change & live_s


def sorted_runs(xp, sort_cols: Sequence[Array], live: Array, capacity: int,
                carry: Sequence[Array] = ()
                ) -> Tuple[SortedRuns, List[Array]]:
    """Sort rows by ``sort_cols`` (``group_sort_columns``: liveness first)
    and lay out their runs; ``carry`` (the aggregate's buffers) comes back in
    sorted order.  THE grouping prologue of every keyed sort aggregate
    (``_sorted_grouped_aggregate``, ``parallel/dist.py``'s partial, merge and
    final stages), so that their grouping cannot drift apart.

    jax lane, all int32: the sort columns and the carry go through ``perm``
    ONCE, as one plane; the liveness column does not go at all (dead rows
    sort last, so ``live_s`` is ``arange < n_live``); ``seg_ids`` is one
    running sum; ``start_of`` ONE scatter of the start positions (distinct
    slots, the rest dropped) and ``end_of`` its shift.  numpy lane:
    ``lexsort`` and an int64 ``cumsum``, the tests' independent form."""
    with _scope(xp, "agg.sort.argsort"):
        perm = multi_key_argsort(xp, sort_cols, capacity)
    if _is_np(xp):
        live_s = np.asarray(live)[perm]
        is_start = _run_starts(xp, [np.asarray(c)[perm] for c in sort_cols],
                               live_s, capacity)
        seg_ids = np.cumsum(is_start.astype(np.int64)) - 1
        seg_ids = np.where(live_s, seg_ids, np.int64(capacity - 1))
        runs = SortedRuns(perm, seg_ids, is_start, live_s,
                          np.sum(is_start.astype(np.int64)))
        return runs, gather_columns(xp, carry, perm)
    keys = list(sort_cols[1:])
    with _scope(xp, "agg.sort.permute"):
        moved = gather_columns(xp, keys + list(carry), perm)
    with _scope(xp, "agg.sort.segment"):
        pos = xp.arange(capacity, dtype=np.int32)
        n_live = xp.sum(live, dtype=np.int32)
        live_s = pos < n_live
        is_start = _run_starts(xp, moved[:len(keys)], live_s, capacity)
        seg_ids = xp.where(live_s, running_sum_i32(xp, is_start) - 1,
                           np.int32(capacity - 1))
        num_groups = xp.sum(is_start, dtype=np.int32)
        # one slot a start, every other row's write dropped: 25 ms at 2^22
        # on a v5e.  No hint: ``unique_indices`` buys nothing (25.0 ms) and
        # ``indices_are_sorted``, which the dropped rows make untrue, is 4
        # ms faster and WRONG (PERF.md, PR 32)
        start_of = xp.full(capacity, capacity, np.int32).at[
            xp.where(is_start, seg_ids, np.int32(capacity))].set(
                pos, mode="drop")
        next_start = xp.concatenate(
            [start_of[1:], xp.full(1, capacity, np.int32)])
        end_of = xp.where(pos == num_groups - 1, n_live, next_start) - 1
        start_of = xp.minimum(start_of, np.int32(capacity - 1))
        longest = xp.max(xp.where(pos < num_groups, end_of - start_of + 1,
                                  np.int32(0)))
    runs = SortedRuns(perm, seg_ids, is_start, live_s, num_groups,
                      start_of, end_of, longest)
    return runs, moved[len(keys):]


def segmented_scan(xp, seg_ids: Array, bufs: Sequence[Array],
                   kinds: Sequence[str], longest: Array
                   ) -> Tuple[List[Array], Array]:
    """Inclusive scan of every buffer within runs of equal ``seg_ids``
    (sorted: equal ids ARE one run, so no flag is carried), all buffers in
    one loop: rounds of stride ``d = 1, 2, 4, ...``, ``v[i] = op(v[i-d],
    v[i])`` where ``seg_ids[i-d] == seg_ids[i]``, while ``d < longest``.
    The loop reads how long it must run from its input: ``ceil(log2(
    longest))`` rounds, which it returns beside the scans.  After it the
    last row of a run of at most ``longest`` rows holds the run's reduction:
    integer sums exact mod 2^64, ``min`` / ``max`` exact, a float sum added
    as a balanced tree (its error bound is below the serial sum's, and does
    not grow with the rows before the run, as a differenced running sum's
    would)."""
    import jax
    n = int(seg_ids.shape[0])
    ops = {"sum": xp.add, "min": xp.minimum, "max": xp.maximum}
    # position i - d of the ids, with "no run" before position 0
    padded_ids = xp.concatenate([xp.full(n, -1, seg_ids.dtype), seg_ids])

    def more(state):
        return state[0] < longest

    def round_(state):
        d, rounds, vals = state
        same = jax.lax.dynamic_slice(padded_ids, (n - d,), (n,)) == seg_ids
        # position i - d of a buffer: the buffer rolled by d (what wraps
        # around lies under ``same`` false).  NOT a padded loop carry
        # updated in place by ``dynamic_update_slice``: XLA:TPU reads that
        # one AFTER it has written it (wrong sums from position 2^17 on at
        # 14 rounds and more with a float64 buffer beside an int64 one, on
        # a v5e; PERF.md, PR 32; ``tools/prof_aggscan.py`` keeps the form)
        return d * 2, rounds + 1, tuple(
            xp.where(same, ops[kind](jax.lax.dynamic_slice(
                xp.concatenate([v, v]), (n - d,), (n,)), v), v)
            for v, kind in zip(vals, kinds))

    _, rounds, vals = jax.lax.while_loop(
        more, round_, (np.int32(1), np.int32(0), tuple(bufs)))
    return list(vals), rounds


def reduce_runs(xp, runs: SortedRuns, sorted_bufs: Sequence[Array],
                kinds: Sequence[str], capacity: int
                ) -> Tuple[List[Array], Optional[Array]]:
    """Each buffer (in SORTED order, dead rows holding its kind's identity)
    reduced by group: slot ``g`` holds group ``g``'s reduction, every slot
    past the groups the identity.  jax lane: ``segmented_scan`` and ONE
    gather of the scans, stacked, at each run's last row; also returns the
    scan's rounds.  numpy lane: ``np.add.at`` and its kin, rounds None."""
    if _is_np(xp):
        return [segment_reduce(xp, b, runs.seg_ids, capacity, k)
                for b, k in zip(sorted_bufs, kinds)], None
    if not sorted_bufs:                       # DISTINCT: keys alone
        return [], xp.zeros((), np.int32)
    as_bool = [b.dtype == np.bool_ for b in sorted_bufs]
    bufs = [b.astype(np.int8) if flag else b
            for b, flag in zip(sorted_bufs, as_bool)]
    scans, rounds = segmented_scan(xp, runs.seg_ids, bufs, kinds,
                                   runs.longest)
    at_end = gather_columns(xp, scans, runs.end_of)
    is_group = xp.arange(capacity, dtype=np.int32) < runs.num_groups
    out = []
    for got, kind, flag in zip(at_end, kinds, as_bool):
        ident = IDENTITY[kind](np.dtype(bool) if flag
                               else np.dtype(str(got.dtype)))
        red = xp.where(is_group, got, xp.asarray(ident, got.dtype))
        out.append(red.astype(bool) if flag else red)
    return out, rounds


def run_keys(xp, runs: SortedRuns, key_vals: Sequence[ExprValue],
             capacity: int) -> List[Tuple[Array, Optional[Array]]]:
    """(data, validity) of every key at each group's slot: the value of the
    group's first row, zero (False) past the groups.  jax lane: keys are
    READ, not moved: ``perm[start_of]`` names each group's first row, and
    every key's data and validity come from the UNSORTED columns there, as
    one plane.  numpy lane: ``_scatter_starts`` over the sorted columns."""
    if not key_vals:
        return []
    if _is_np(xp):
        out = []
        for v in key_vals:
            out.append((
                _scatter_starts(xp, np.asarray(v.data)[runs.perm],
                                runs.seg_ids, runs.is_start, capacity),
                None if v.valid is None else _scatter_starts(
                    xp, np.asarray(v.valid)[runs.perm], runs.seg_ids,
                    runs.is_start, capacity)))
        return out
    cols = [v.data for v in key_vals] \
        + [v.valid for v in key_vals if v.valid is not None]
    first_row = runs.perm[runs.start_of]
    got = gather_columns(xp, cols, first_row)
    is_group = xp.arange(capacity, dtype=np.int32) < runs.num_groups
    got = [xp.where(is_group, c, xp.zeros((), c.dtype)) for c in got]
    valids = iter(got[len(key_vals):])
    return [(d, None if v.valid is None else next(valids))
            for d, v in zip(got, key_vals)]


# ---------------------------------------------------------------------------
# grouped aggregation (sort-based HashAggregateExec replacement)
# ---------------------------------------------------------------------------

#: the one-hot-matmul aggregation only wins where a systolic array exists.
#: None = auto (on when the process runs on a TPU); tests force True to
#: exercise the MXU formulation on the virtual CPU mesh.
MXU_AGG_ENABLED: "bool | None" = None


def _on_tpu_device() -> bool:
    """THE device gate: the MXU aggregate, its Mosaic kernel and the
    chained single-key sort are chosen where the process runs on a TPU,
    and nowhere else."""
    import jax
    return jax.default_backend() == "tpu"


def _mxu_agg_on() -> bool:
    if MXU_AGG_ENABLED is not None:
        return MXU_AGG_ENABLED
    return _on_tpu_device()


def grouped_aggregate(
    xp,
    batch: ColumnBatch,
    key_exprs: Sequence[Expression],
    agg_slots: Sequence[Tuple[AggregateFunction, str]],
    bucket_cap: int = 4096,
    scan_rounds: Optional[List[Array]] = None,
) -> ColumnBatch:
    """GROUP BY keys with aggregate outputs; one batch in, one batch out.

    With keys, output capacity equals input capacity (worst case: every live
    row its own group) and ``row_valid`` marks real groups.  NULL is a group
    key value (SQL semantics).  With no keys, the single global-aggregate
    row comes back as a capacity-1 batch (see `_sorted_grouped_aggregate`).

    Device path: when keys are integral and the key range fits ``bucket_cap``
    buckets, aggregation runs on the MXU (one-hot matmul over 8-bit limb
    planes — see ``_mxu_grouped_aggregate``); a runtime ``lax.cond`` falls
    back to the sort-based path otherwise.

    ``scan_rounds``, where given, receives the rounds the sort path's
    segmented scan took (a traced scalar; 0 where the MXU took the rows),
    the operator's ``agg.scan_rounds`` metric; nothing on the numpy lane.
    """
    if _mxu_agg_on() and not _is_np(xp) and key_exprs \
            and _mxu_applicable(batch.schema, key_exprs, agg_slots):
        return _mxu_grouped_aggregate(xp, batch, key_exprs, agg_slots,
                                      bucket_cap, scan_rounds)
    if _is_np(xp) and not key_exprs:
        out = _run_aware_global_aggregate(batch, agg_slots)
        if out is not None:
            return out
    if not _is_np(xp) and not key_exprs:
        out = _plane_global_aggregate(xp, batch, agg_slots)
        if out is not None:
            return out
    if not _is_np(xp) and key_exprs:
        # which lowering a keyed aggregate took, noted at trace time with
        # the stage being built: tracing.last_statement()["notes"]
        tracing.note("agg_lowering", "sort.scan")
    return _sorted_grouped_aggregate(xp, batch, key_exprs, agg_slots,
                                     scan_rounds)


def _run_aware_global_aggregate(
    batch: ColumnBatch,
    agg_slots: Sequence[Tuple[AggregateFunction, str]],
) -> Optional[ColumnBatch]:
    """Keyless count/sum over run-encoded columns without expansion: a run
    contributes ``value × length`` with one multiply.  Fires only when the
    result is provably byte-identical to the dense path — every slot is
    count(*)/count/sum (non-distinct) over a bare column whose vector is an
    unexpanded run table with no NULLs covering a fully-live batch; integer
    sums match the dense path exactly because int64 products and sums both
    wrap mod 2^64 (floats are excluded: their addition is not associative).
    Returns None to fall back to the general path."""
    from .aggregates import Count, CountStar, Sum
    if batch.row_valid is not None or batch.capacity == 0 or not agg_slots:
        return None
    cap = batch.capacity
    plans = []
    for func, name in agg_slots:
        if getattr(func, "is_distinct", False):
            return None
        if isinstance(func, CountStar):
            plans.append((func, name, None))
            continue
        if type(func) not in (Count, Sum):
            return None
        child = func.children[0]
        if not isinstance(child, Col) or child.name not in batch.names:
            return None
        rv = unmaterialized_runs(batch.column(child.name))
        if rv is None or rv.valid is not None or rv.capacity != cap:
            return None
        if isinstance(func, Sum) \
                and np.asarray(rv.run_values).dtype.kind not in "iub":
            return None
        plans.append((func, name, rv))
    if all(rv is None for _, _, rv in plans):
        return None  # nothing run-encoded: nothing to claim credit for
    schema = batch.schema
    names: List[str] = []
    vectors: List[ColumnVector] = []
    for func, name, rv in plans:
        dt = func.data_type(schema)
        if rv is None or isinstance(func, (CountStar, Count)):
            # no NULLs and no dead rows ⇒ count == capacity
            out = ColumnVector(np.asarray([cap], dt.np_dtype), dt, None, None)
        else:
            out_np = dt.np_dtype
            total = (np.asarray(rv.run_values).astype(out_np)
                     * rv.run_lengths.astype(out_np)).sum(dtype=out_np)
            out = ColumnVector(np.asarray([total], out_np), dt,
                               np.asarray([True]), None)
        names.append(name)
        vectors.append(out)
    bump_run_aware(cap)
    return ColumnBatch(names, vectors, None, 1)


def _plane_global_aggregate(
    xp,
    batch: ColumnBatch,
    agg_slots: Sequence[Tuple[AggregateFunction, str]],
) -> Optional[ColumnBatch]:
    """Jit-lane twin of ``_run_aware_global_aggregate`` over run PLANES,
    extended with min/max and a dense row mask: keyless count/sum reduce
    ``run_values × per-run-live-counts`` (the live counts come from one
    ``segment_sum`` of the row mask over ``run_row_ids``), min/max reduce
    the run VALUES under a per-run any-live mask — no arithmetic on
    expanded rows, so exact for every dtype.  Fires only when provably
    byte-identical to the dense path: every slot is count(*)/count/sum/
    min/max (non-distinct) over a bare column whose vector is an
    unexpanded plane with no NULLs covering the batch; integer-only sums
    (int64 products and sums both wrap mod 2^64; float addition is not
    associative).  Returns None to fall back (in-trace expansion,
    counted)."""
    from .aggregates import Count, CountStar, Max, Min, Sum
    if batch.capacity == 0 or not agg_slots:
        return None
    cap = batch.capacity
    plans = []
    for func, name in agg_slots:
        if getattr(func, "is_distinct", False):
            return None
        if isinstance(func, CountStar):
            plans.append((func, name, None))
            continue
        if type(func) not in (Count, Sum, Min, Max):
            return None
        child = func.children[0]
        if not isinstance(child, Col) or child._name not in batch.names:
            return None
        pv = unexpanded_plane(batch.column(child._name))
        if pv is None or pv.valid is not None or pv.capacity != cap:
            return None
        if isinstance(func, Sum) \
                and np.dtype(pv.dtype.np_dtype).kind not in "iub":
            return None
        plans.append((func, name, pv))
    if all(pv is None for _, _, pv in plans):
        return None  # nothing plane-encoded: nothing to claim credit for

    live = batch.row_valid  # row masks are always dense, never planes
    n_live = np.int64(cap) if live is None else xp.sum(live.astype(np.int64))
    counts_cache: dict = {}

    def run_live_counts(pv: PlaneColumnVector) -> Array:
        """Live-row count per run slot (zero on padded slots)."""
        if id(pv) not in counts_cache:
            if live is None:
                c = xp.asarray(pv.plane_lengths).astype(np.int64)
            else:
                ids = run_row_ids(xp, pv.plane_lengths, cap)
                c = segment_reduce(xp, live.astype(np.int64), ids,
                                   pv.plane_capacity, "sum")
            counts_cache[id(pv)] = c
        return counts_cache[id(pv)]

    def as1(val, np_dt):
        return xp.asarray(val).reshape(1).astype(np_dt)

    schema = batch.schema
    names: List[str] = []
    vectors: List[ColumnVector] = []
    for func, name, pv in plans:
        dt = func.data_type(schema)
        if pv is None or isinstance(func, (CountStar, Count)):
            # no NULLs ⇒ count == number of live rows
            out = ColumnVector(as1(n_live, dt.np_dtype), dt, None, None)
        elif isinstance(func, Sum):
            out_np = dt.np_dtype
            total = (xp.asarray(pv.plane_values).astype(out_np)
                     * run_live_counts(pv).astype(out_np)).sum()
            out = ColumnVector(as1(total, out_np), dt,
                               as1(n_live > 0, np.bool_), None)
        else:  # Min / Max
            red_dt = np.dtype(np.int8) if dt.np_dtype == np.bool_ \
                else np.dtype(dt.np_dtype)
            ident = IDENTITY[func.kind](red_dt)
            run_live = run_live_counts(pv) > 0
            buf = xp.where(run_live,
                           xp.asarray(pv.plane_values).astype(red_dt),
                           xp.asarray(ident, red_dt))
            val = buf.min() if func.kind == "min" else buf.max()
            out = ColumnVector(as1(val, dt.np_dtype), dt,
                               as1(n_live > 0, np.bool_), pv.dictionary)
        names.append(name)
        vectors.append(out)
    return ColumnBatch(names, vectors, None, 1)


@_scoped("agg.sort")
def _sorted_grouped_aggregate(
    xp,
    batch: ColumnBatch,
    key_exprs: Sequence[Expression],
    agg_slots: Sequence[Tuple[AggregateFunction, str]],
    scan_rounds: Optional[List[Array]] = None,
) -> ColumnBatch:
    """Sort-based grouping: multi-key sort -> runs of equal keys -> each
    buffer reduced within its run (the general path; also the numpy
    oracle).  jax lane: ``sorted_runs`` / ``reduce_runs`` / ``run_keys`` (one
    plane through ``perm``, a segmented scan, reads at the runs' ends);
    ``scan_rounds``, where given, receives the scan's rounds (a traced
    scalar: the operator's ``agg.scan_rounds`` metric)."""
    if not key_exprs and batch.capacity == 0:
        # the global row exists even over an empty input (COUNT=0, SUM
        # NULL); pad to one all-dead row so the ordinary no-live-rows
        # machinery produces it (a capacity-0 batch cannot hold it)
        from .columnar import pad_to_capacity
        batch = pad_to_capacity(batch, 1)
    ctx = EvalContext(batch, xp)
    capacity = batch.capacity
    live = batch.row_valid_or_true()
    schema = batch.schema

    key_vals: List[ExprValue] = [ctx.broadcast(k.eval(ctx)) for k in key_exprs]
    sort_cols = group_sort_columns(xp, key_vals, live)

    # every plain slot's buffers, built before the sort so that all of them
    # go through ``perm`` in one plane and reduce in one scan
    specs = {i: f.make_buffers(ctx, live)
             for i, (f, _n) in enumerate(agg_slots)
             if not getattr(f, "is_percentile", False)
             and not getattr(f, "is_collect", False)}
    flat = [s for ss in specs.values() for s in ss]

    # keyless (global) aggregation needs NO sort: every buffer reduces
    # over one segment, and the reductions are order-independent (First
    # reduces original-row indices).  The sort was the dominant cost of
    # every global aggregate — a full O(n log^2 n) bitonic pass on TPU
    # for a single output row.
    if key_exprs:
        runs, sorted_bufs = sorted_runs(xp, sort_cols, live, capacity,
                                        [s.data for s in flat])
        with _scope(xp, "agg.sort.segment"):
            reduced, rounds = reduce_runs(xp, runs, sorted_bufs,
                                          [s.kind for s in flat], capacity)
            keys_out = run_keys(xp, runs, key_vals, capacity)
        if scan_rounds is not None and rounds is not None:
            scan_rounds.append(rounds)
        seg_ids, is_start, live_s = runs.seg_ids, runs.is_start, runs.live_s
        if not _is_np(xp):
            # the percentile and collect slots keep ``segment_reduce`` and
            # its int64 ids (no cell reaches them)
            seg_ids = seg_ids.astype(np.int64)
        perm = runs.perm
    else:
        reduced = [_global_reduce(xp, s.data, s.kind, capacity) for s in flat]
        keys_out = []
        seg_ids = xp.zeros(capacity, np.int64)
        perm, is_start, live_s = None, None, live
    reduced = iter(reduced)
    reduced_of = {i: [next(reduced) for _s in ss] for i, ss in specs.items()}

    out_names: List[str] = []
    out_vectors: List[ColumnVector] = []
    for k, v, (kdata, kvalid) in zip(key_exprs, key_vals, keys_out):
        dt = k.data_type(schema)
        out_names.append(k.name)
        out_vectors.append(ColumnVector(kdata.astype(dt.np_dtype), dt, kvalid,
                                        v.dictionary))

    group_pos = xp.arange(capacity, dtype=np.int64)
    # all percentile slots over one child share ONE value-sort
    pct_slots = [(f, n) for f, n in agg_slots
                 if getattr(f, "is_percentile", False)]
    pct_results = {}
    if pct_slots:
        by_child = {}
        for f, n in pct_slots:
            by_child.setdefault(repr(f.children[0]), []).append((f, n))
        for group in by_child.values():
            pct_results.update(_percentile_groups(
                xp, ctx, group, sort_cols, live, capacity))
    for i, (func, name) in enumerate(agg_slots):
        if getattr(func, "is_percentile", False):
            out_names.append(name)
            out_vectors.append(pct_results[name])
            continue
        if getattr(func, "is_collect", False):
            cperm = perm if perm is not None \
                else xp.arange(capacity, dtype=np.int64)
            out_names.append(name)
            out_vectors.append(_collect_into_arrays(
                xp, ctx, func, cperm, sort_cols, seg_ids, is_start,
                group_pos, live_s, capacity))
            continue
        red = reduced_of[i]
        dt = func.data_type(schema)
        if isinstance(func, First):
            # argmin/argmax of row index → gather the value column
            v = ctx.broadcast(func.children[0].eval(ctx))
            idx = xp.clip(red[0], 0, capacity - 1).astype(np.int64)
            # reduced index is in PRE-sort coordinates (buffers built pre-sort
            # then permuted; values stored are original indices)
            data = v.data[idx]
            got = (red[0] >= 0) & (red[0] < np.int64(1 << 62))
            valid = got if v.valid is None else (got & v.valid[idx])
            out = ExprValue(data, valid, v.dictionary)
        else:
            out = func.finish(xp, red)
        dictionary = out.dictionary if out.dictionary is not None \
            else func.output_dictionary(ctx)
        data = out.data.astype(dt.np_dtype) if dt.np_dtype != np.bool_ \
            else out.data.astype(np.bool_)
        out_names.append(name)
        out_vectors.append(ColumnVector(data, dt, out.valid, dictionary))

    # ---- output row mask -------------------------------------------------
    if key_exprs:
        out_rv = group_pos < runs.num_groups
        return ColumnBatch(out_names, out_vectors, out_rv, capacity)
    # keyless (global) aggregation: exactly ONE row, so emit capacity 1 —
    # cross joins of scalar subquery blocks (TPC-DS q88/q90) stay tiny
    # instead of multiplying input capacities
    out_vectors = [
        ColumnVector(v.data[:1], v.dtype,
                     None if v.valid is None else v.valid[:1], v.dictionary)
        for v in out_vectors
    ]
    return ColumnBatch(out_names, out_vectors, None, 1)


def _percentile_groups(xp, ctx, slots, sort_cols, live, capacity: int
                       ) -> dict:
    """Exact nearest-rank percentiles per group, ONE value-sort for every
    requested percentage over the same child: re-sort by (keys, value) so
    each group's values are ordered, then gather the row whose
    position-in-group equals floor(p * (n_valid - 1)).  Returns
    {slot_name: ColumnVector}."""
    func = slots[0][0]
    v = ctx.broadcast(func.children[0].eval(ctx))
    vdata = v.data
    np_dt = np.asarray(vdata).dtype if _is_np(xp) else \
        np.dtype(str(vdata.dtype))
    if np_dt == np.bool_:
        vdata = vdata.astype(np.int8)
        np_dt = np.dtype(np.int8)
    keep = live if v.valid is None else (live & v.valid)
    # NULL/dead values sort to the end of their group (max-identity key)
    ident = IDENTITY["max"](np_dt)
    vkey = xp.where(keep, vdata, np.asarray(ident, vdata.dtype))
    vnull = xp.where(keep, np.int8(0), np.int8(1))
    perm = multi_key_argsort(xp, sort_cols + [vnull, vkey], capacity)
    live_s = live[perm]
    keep_s = keep[perm]
    # recompute segments over the value-sorted order
    change = xp.zeros(capacity, bool)
    for c0 in sort_cols:
        c = c0[perm]
        shifted = xp.concatenate([c[:1], c[:-1]])
        change = change | (c != shifted)
    if _is_np(xp):
        change = change.copy()
        change[0] = True
    else:
        change = change.at[0].set(True)
    is_start = change & live_s
    seg_ids = xp.cumsum(is_start.astype(np.int64)) - 1
    seg_ids = xp.where(live_s, seg_ids, np.int64(capacity - 1))
    n_valid = segment_reduce(xp, keep_s.astype(np.int64), seg_ids,
                             capacity, "sum")
    ck = xp.cumsum(keep_s.astype(np.int64))
    seg_base = segment_reduce(xp, xp.where(keep_s, ck - 1,
                                           np.int64(1 << 62)),
                              seg_ids, capacity, "min")
    pos = ck - 1 - seg_base[seg_ids]
    got = n_valid > 0
    vdata_s = vdata[perm]
    out = {}
    for f, name in slots:
        target = xp.floor(np.float64(f.percentage)
                          * (n_valid - 1).astype(np.float64)
                          ).astype(np.int64)
        win = keep_s & (pos == target[seg_ids])
        # max over exactly-one-winner IS the gather; empty groups -> NULL
        masked = xp.where(win, vdata_s, np.asarray(ident, vdata.dtype))
        red = segment_reduce(xp, masked, seg_ids, capacity, "max")
        dt = f.data_type(ctx.batch.schema)
        data = red.astype(np.bool_) if np.dtype(dt.np_dtype) == np.bool_ \
            else red.astype(dt.np_dtype)
        out[name] = ColumnVector(data, dt, got, v.dictionary)
    return out


def _collect_into_arrays(xp, ctx, func, perm, sort_cols, seg_ids, is_start,
                         group_pos, live_s, capacity: int) -> ColumnVector:
    """collect_list/collect_set inside the sort-based group path: scatter
    each group's (optionally deduplicated) values into a fixed-width
    ``(groups, Lmax)`` array — position-within-segment is the column, a
    trash row swallows dead/overflow/NULL slots.  The static bound comes
    from ``spark.tpu.collect.maxArrayLen``."""
    from . import config as C
    dt = func.data_type(ctx.batch.schema)
    ed = dt.element_type
    sent = dt.element_sentinel()
    lmax = C.COLLECT_MAX_LEN.default
    try:
        from .sql.session import SparkSession
        s = SparkSession.getActiveSession()
        if s is not None:
            lmax = s.conf.get(C.COLLECT_MAX_LEN)
    except Exception:
        pass

    v = ctx.broadcast(func.children[0].eval(ctx))
    if func.distinct_elements:
        # per-slot re-sort including the value: equal values in a group
        # become adjacent so first-occurrence positions dedupe them
        vdata = v.data
        if (np.asarray(vdata).dtype if _is_np(xp) else vdata.dtype) \
                == np.bool_:
            vdata = vdata.astype(np.int8)
        vnull = xp.zeros(capacity, np.int8) if v.valid is None else \
            xp.where(v.valid, np.int8(0), np.int8(1))
        perm = multi_key_argsort(xp, sort_cols + [vnull, vdata], capacity)
        live_s = ctx.batch.row_valid_or_true()[perm]
        if is_start is not None:
            change = xp.zeros(capacity, bool)
            for c in [c0[perm] for c0 in sort_cols]:
                shifted = xp.concatenate([c[:1], c[:-1]])
                change = change | (c != shifted)
            if _is_np(xp):
                change = change.copy()
                change[0] = True
            else:
                change = change.at[0].set(True)
            is_start = change & live_s
            seg_ids = xp.cumsum(is_start.astype(np.int64)) - 1
            seg_ids = xp.where(live_s, seg_ids, np.int64(capacity - 1))

    value_s = v.data[perm]
    valid_s = None if v.valid is None else v.valid[perm]
    keep = live_s if valid_s is None else (live_s & valid_s)
    if func.distinct_elements:
        prev_v = xp.concatenate([value_s[:1], value_s[:-1]])
        prev_seg = xp.concatenate([seg_ids[:1] - 1, seg_ids[:-1]])
        first = (value_s != prev_v) | (seg_ids != prev_seg)
        keep = keep & first
    # position among KEPT rows of the same segment (cumsum minus the
    # segment's running total at its start)
    ck = xp.cumsum(keep.astype(np.int64))
    seg_base = segment_reduce(xp, xp.where(keep, ck - 1, np.int64(1 << 62)),
                              seg_ids, capacity, "min")
    pos = ck - 1 - seg_base[seg_ids]
    row = xp.where(keep & (pos >= 0) & (pos < lmax), seg_ids,
                   np.int64(capacity))
    col = xp.clip(pos, 0, lmax - 1)
    np_ed = ed.np_dtype
    if _is_np(xp):
        out = np.full((capacity + 1, lmax), sent, np_ed)
        out[np.asarray(row), np.asarray(col)] = np.asarray(value_s
                                                           ).astype(np_ed)
    else:
        out = xp.full((capacity + 1, lmax), sent, np_ed)
        out = out.at[row, col].set(value_s.astype(np_ed))
    return ColumnVector(out[:capacity], dt, None, v.dictionary)


def _scatter_starts(xp, sorted_data: Array, seg_ids: Array, is_start: Array,
                    capacity: int) -> Array:
    """out[g] = sorted_data[first row of segment g] (scatter at starts).
    Since PR 32 only ``run_keys``'s numpy lane calls it (the tests'
    independent form); the jax lane reads a group's key at ``perm[
    start_of[g]]`` and scatters nothing."""
    if _is_np(xp):
        out = np.zeros(capacity, dtype=np.asarray(sorted_data).dtype)
        idx = np.asarray(seg_ids)[np.asarray(is_start)]
        out[idx] = np.asarray(sorted_data)[np.asarray(is_start)]
        return out
    target = xp.where(is_start, seg_ids, np.int64(capacity))  # capacity = drop
    out = xp.zeros(capacity, dtype=sorted_data.dtype)
    return out.at[target].set(sorted_data, mode="drop")


# ---------------------------------------------------------------------------
# MXU grouped aggregation (the BytesToBytesMap replacement that actually
# fits the hardware: aggregation as matrix multiplication)
# ---------------------------------------------------------------------------
#
# Spark's fast hash aggregate is a scatter-heavy open-addressing map
# (`unsafe/map/BytesToBytesMap.java:66`).  Scatters are the worst primitive
# on a TPU; matmuls are the best.  This path computes
#
#     sums[b, p] = Σ_rows  one_hot(bucket[row], B) · plane[row, p]
#
# on the MXU, where the planes are 8-bit limbs of the (offset-shifted)
# values plus count masks.  Per-tile f32 accumulations of ≤2048 limbs are
# exact (< 2^19 < 2^24); cross-tile accumulation is int64; limb
# recombination is mod-2^64 two's-complement — so integer sums are
# BIT-EXACT, including overflow wraparound, matching Java long semantics.
#
# Buckets come from composite key codes (key - min, mixed-radix over
# multiple keys, NULL = slot 0).  A runtime `lax.cond` checks that the key
# ranges fit the static bucket capacity and otherwise falls back to the
# sort-based path, so the operator is total.

_MXU_TILE = 2048


def _integral_key(dt: T.DataType) -> bool:
    return (dt.is_integral or isinstance(dt, (T.BooleanType, T.DateType,
                                              T.TimestampType, T.DecimalType))
            or dt.is_string)  # strings group by dictionary code


def _mxu_applicable(schema: T.StructType, key_exprs, agg_slots) -> bool:
    from .aggregates import Avg, Count, CountStar, Sum
    try:
        for k in key_exprs:
            if not _integral_key(k.data_type(schema)):
                return False
        for f, _ in agg_slots:
            if getattr(f, "is_distinct", False):
                return False
            if isinstance(f, (Count, CountStar)):
                continue
            if isinstance(f, (Sum, Avg)):
                src = f.children[0].data_type(schema)
                if src.is_integral or isinstance(src, (T.BooleanType,
                                                       T.DecimalType)):
                    continue
                return False
            return False
    except Exception:
        return False
    return True


def _limb_plan(np_dtype) -> Tuple[int, int]:
    """(n_limbs, offset) for a value dtype: offset shifts the value into
    [0, 2^(8·n_limbs)) so limbs are unsigned; int64 uses the full width
    (offset 2^63 ≡ sign-bit flip, mod-2^64 arithmetic)."""
    dt = np.dtype(np_dtype)  # bool inputs are cast to int8 by the caller
    bits = dt.itemsize * 8
    return dt.itemsize, 1 << (bits - 1)


# TPU VPUs have no 64-bit lanes — XLA emulates every int64 op with a
# multi-op 32-bit expansion, which made the O(n) prep (bucket codes, limb
# extraction) dominate the whole aggregation.  The helpers below keep all
# O(n) arithmetic in native 32-bit: int64 columns are split into (lo, hi)
# uint32 halves by bitcast (XLA defines minor index 0 = least-significant
# word), min/max are two-pass lexicographic reductions, and in-range codes
# come from low-half arithmetic alone (exact whenever the range fits the
# bucket table — `fits` guards it; the sort-based branch owns the rest).

def _i64_halves(xp, data):
    """(lo, hi) uint32 halves of ``data`` sign-extended to int64."""
    import jax
    import jax.numpy as jnp
    if data.dtype.itemsize == 8:
        pair = jax.lax.bitcast_convert_type(data.astype(jnp.int64),
                                            jnp.uint32)
        return pair[..., 0], pair[..., 1]
    w = data.astype(jnp.int32)
    return w.astype(jnp.uint32), (w >> 31).astype(jnp.uint32)


def _masked_minmax64(xp, lo, hi, mask):
    """(kmin_i64, kmax_i64, kmin_lo_u32) over rows where mask, via int32
    lexicographic (hi signed, lo unsigned) two-pass reductions.  Empty mask
    yields (INT64_MAX, INT64_MIN, UINT32_MAX) — the sort-branch sentinels."""
    import jax.numpy as jnp
    hi_s = hi.astype(jnp.int32)
    min_hi = xp.min(xp.where(mask, hi_s, np.int32(np.iinfo(np.int32).max)))
    min_lo = xp.min(xp.where(mask & (hi_s == min_hi), lo,
                             np.uint32(0xFFFFFFFF)))
    max_hi = xp.max(xp.where(mask, hi_s, np.int32(np.iinfo(np.int32).min)))
    max_lo = xp.max(xp.where(mask & (hi_s == max_hi), lo, np.uint32(0)))

    def comb(h, l):
        return (h.astype(jnp.int64) << np.int64(32)) | l.astype(jnp.int64)

    return comb(min_hi, min_lo), comb(max_hi, max_lo), min_lo


@_scoped("agg.mxu")
def _mxu_grouped_aggregate(xp, batch, key_exprs, agg_slots, bucket_cap,
                           scan_rounds=None):
    import jax
    import jax.numpy as jnp
    from . import pallas_agg
    from .aggregates import Avg, Count, CountStar, Sum

    ctx = EvalContext(batch, xp)
    capacity = batch.capacity
    live = xp.broadcast_to(batch.row_valid_or_true(), (capacity,))
    schema = batch.schema

    B = int(min(bucket_cap, capacity))
    L = int(min(_MXU_TILE, capacity))
    n_pad = ((capacity + L - 1) // L) * L

    with tracing.scope("agg.mxu.limbs"):
        # ---- composite bucket codes (mixed radix over keys, NULL = 0) -------
        # All O(n) arithmetic is 32-bit native (see _i64_halves): codes come
        # from low-half differences, exact whenever `fits` holds; the slow
        # branch owns every other execution, so garbage codes are harmless.
        key_vals: List[ExprValue] = [ctx.broadcast(k.eval(ctx)) for k in key_exprs]
        key_dts = [k.data_type(schema) for k in key_exprs]
        codes = []          # per-key (code32 in [0, r), r32, kmin_i64, nullable)
        prod = xp.ones((), np.float64)   # overflow-safe fit check in f64
        for v in key_vals:
            data = v.data
            if data.dtype == np.bool_:
                data = data.astype(np.int8)
            lo, hi = _i64_halves(xp, data)
            mask = live if v.valid is None else (live & v.valid)
            kmin, kmax, kmin_lo = _masked_minmax64(xp, lo, hi, mask)
            # the authoritative range estimate is f64 (int64 spans can exceed
            # any 32-bit arithmetic); only trusted when `fits` proves it small
            rangef = xp.maximum(kmax.astype(np.float64) - kmin.astype(np.float64)
                                + 1.0, 0.0)
            r32 = xp.clip(rangef, 0.0, np.float64(B + 2)).astype(np.int32)
            diff = (lo - kmin_lo).astype(np.int32)   # mod-2^32; exact iff fits
            nullable = v.valid is not None
            if nullable:
                code = xp.where(mask, diff + 1, 0)
                r32 = r32 + 1
                prod = prod * (rangef + 1.0)
            else:
                code = diff
                r32 = xp.maximum(r32, 1)
                prod = prod * xp.maximum(rangef, 1.0)
            codes.append((code, r32, kmin, nullable))

        bucket = xp.zeros(capacity, np.int32)
        for code, r32, _, _ in codes:
            bucket = bucket * r32 + code   # wraps only when not fits
        fits = prod <= np.float64(B)
        bucket32 = xp.clip(bucket, 0, B - 1)

    def fast_branch(_):
        with tracing.scope("agg.mxu.limbs"):
            # ---- plane assembly (fast branch only: fallback executions must
            # not pay the O(n·P) limb extraction) ------------------------------
            # plane 0: live-row count; per Sum/Avg: limb planes + own count
            # plane; per Count: count plane.  All bf16 {0..255}-valued.
            planes: List[Array] = [live.astype(jnp.bfloat16)]
            agg_plane_info = []  # (func, name, kind, first_plane, offset, n_limbs)
            for func, name in agg_slots:
                if isinstance(func, CountStar):
                    agg_plane_info.append((func, name, "countstar", None, 0, 0))
                    continue
                v = ctx.broadcast(func.children[0].eval(ctx))
                m = live if v.valid is None else (live & v.valid)
                if isinstance(func, Count):
                    start = len(planes)
                    planes.append(m.astype(jnp.bfloat16))
                    agg_plane_info.append((func, name, "count", start, 0, 0))
                    continue
                # Sum / Avg over integral input
                data = v.data
                if data.dtype == np.bool_:
                    data = data.astype(np.int8)
                n_limbs, offset = _limb_plan(data.dtype)
                # 32-bit-native limb extraction: the +offset sign shift is a
                # top-bit flip for 8-byte values (no carry: 2^63 IS the top
                # bit) and a mod-2^32 low-word add for narrower ones (only the
                # low 8*n_limbs bits are read, which the wrap cannot touch)
                lo, hi = _i64_halves(xp, data)
                if n_limbs == 8:
                    words = (lo, hi ^ np.uint32(0x80000000))
                else:
                    words = (lo + np.uint32(offset),)
                start = len(planes)
                for i in range(n_limbs):
                    w = words[i // 4]
                    limb = (w >> np.uint32(8 * (i % 4))) & np.uint32(0xFF)
                    limb = xp.where(m, limb, np.uint32(0))
                    planes.append(limb.astype(jnp.bfloat16))
                planes.append(m.astype(jnp.bfloat16))   # per-agg count
                agg_plane_info.append((func, name, "sum", start, offset, n_limbs))

            P = len(planes)
            plane_mat = xp.stack(planes, axis=-1)                # (n, P)

        if pallas_agg.supported(B) and _on_tpu_device():
            # Pallas accumulate: one-hot tiles built in VMEM, (B, P) int32
            # accumulator in scratch, bucket chunks beyond the runtime key
            # range skipped — HBM traffic is one pass over the planes
            n_active = pallas_agg.n_active_chunks(xp, prod, B)
            tracing.note("agg_lowering", "pallas")
            tot = pallas_agg.grouped_accumulate(bucket32, plane_mat,
                                                n_active, B)
        else:
            tracing.note("agg_lowering", "einsum")
            with tracing.scope("agg.onehot"):
                bucket_pad = bucket32
                if n_pad != capacity:
                    plane_mat = xp.concatenate(
                        [plane_mat, xp.zeros((n_pad - capacity, P), jnp.bfloat16)])
                    bucket_pad = xp.concatenate(
                        [bucket32, xp.zeros(n_pad - capacity, np.int32)])
                T_tiles = n_pad // L

                bb = bucket_pad.reshape(T_tiles, L)
                pp = plane_mat.reshape(T_tiles, L, P)
                oh = jax.nn.one_hot(bb, B, dtype=jnp.bfloat16)        # (T, L, B)
                per_tile = jnp.einsum("tlb,tlp->tbp", oh, pp,
                                      preferred_element_type=jnp.float32)
                # exact integer accumulation across tiles; int32 is enough while
                # total counts/limb-sums stay < 2^31 (n·255), halving HBM traffic
                acc_dt = jnp.int32 if n_pad * 255 < (1 << 31) else jnp.int64
                tot = per_tile.astype(acc_dt).sum(0).astype(jnp.int64)  # (B, P)
        live_count = tot[:, 0]
        grow = live_count > 0                                 # real groups

        out_datas: List[Array] = []
        out_valids: List[Array] = []
        # decode keys from bucket index (mixed radix, most-significant first)
        rem = xp.arange(B, dtype=np.int64)
        strides = []
        s = xp.ones((), np.int64)
        for _, r, _, _ in reversed(codes):
            strides.append(s)
            s = s * r
        strides.reverse()
        for (code, r, kmin, nullable), stride, v, dt in zip(
                codes, strides, key_vals, key_dts):
            digit = (rem // stride) % xp.maximum(r, 1)
            if nullable:
                kdata = kmin + digit - 1
                kvalid = grow & (digit > 0)
            else:
                kdata = kmin + digit
                kvalid = grow
            np_dt = dt.np_dtype
            out_datas.append(kdata.astype(np_dt))
            out_valids.append(kvalid)

        for func, name, kind, start, offset, n_limbs in agg_plane_info:
            if kind == "countstar":
                out_datas.append(live_count)
                out_valids.append(grow)
                continue
            if kind == "count":
                out_datas.append(tot[:, start])
                out_valids.append(grow)
                continue
            cnt = tot[:, start + n_limbs]
            acc = xp.zeros(B, jnp.uint64)
            for i in range(n_limbs):
                acc = acc + (tot[:, start + i].astype(jnp.uint64)
                             << jnp.uint64(8 * i))
            total = (acc - cnt.astype(jnp.uint64) * jnp.uint64(offset)
                     ).astype(jnp.int64)
            if isinstance(func, Avg):
                src = func.children[0].data_type(schema)
                f = total.astype(np.float64)
                if isinstance(src, T.DecimalType):
                    f = f / (10 ** src.scale)
                safe = xp.where(cnt > 0, cnt, 1)
                out_datas.append(f / safe)
            else:
                out_dt = func.data_type(schema).np_dtype
                out_datas.append(total.astype(out_dt))
            out_valids.append(grow & (cnt > 0))

        def pad(a):
            if B == capacity:
                return a
            fill = xp.zeros(capacity - B, a.dtype)
            return xp.concatenate([a, fill])

        return (tuple(pad(d) for d in out_datas),
                tuple(pad(v) for v in out_valids),
                pad(grow), xp.zeros((), np.int32))

    def slow_branch(_):
        rounds: List[Array] = []
        cb = _sorted_grouped_aggregate(xp, batch, key_exprs, agg_slots,
                                       rounds)
        datas = tuple(v.data for v in cb.vectors)
        valids = tuple(
            xp.broadcast_to(v.valid, (capacity,)) if v.valid is not None
            else xp.ones(capacity, bool) for v in cb.vectors)
        return datas, valids, xp.broadcast_to(cb.row_valid_or_true(),
                                              (capacity,)), rounds[0]

    datas, valids, row_valid, rounds = jax.lax.cond(
        fits, fast_branch, slow_branch, None)
    if scan_rounds is not None:
        scan_rounds.append(rounds)

    # ---- assemble (names/dtypes/dictionaries are host-static) -----------
    out_names: List[str] = []
    out_vectors: List[ColumnVector] = []
    i = 0
    for k, v, dt in zip(key_exprs, key_vals, key_dts):
        out_names.append(k.name)
        out_vectors.append(ColumnVector(datas[i], dt, valids[i], v.dictionary))
        i += 1
    for func, name in agg_slots:
        dt = func.data_type(schema)
        out_names.append(name)
        out_vectors.append(ColumnVector(datas[i], dt, valids[i],
                                        func.output_dictionary(ctx)))
        i += 1
    return ColumnBatch(out_names, out_vectors, row_valid, capacity)


# ---------------------------------------------------------------------------
# distinct / union
# ---------------------------------------------------------------------------

def distinct(xp, batch: ColumnBatch) -> ColumnBatch:
    """Deduplicate live rows (group by all columns, keep firsts)."""
    from .expressions import Col
    keys = [Col(n) for n in batch.names]
    out = grouped_aggregate(xp, batch, keys, [])
    return out


def remap_codes(xp, codes, table):
    """Gather dictionary codes into a merged code space (jittable).

    ``table[old_code] -> new_code`` must be monotone — engine
    dictionaries are sorted, so ``merge_dictionaries`` remaps are —
    which keeps sorted runs sorted across the remap (the range-merge
    path depends on this).  Sentinel-preserving, unlike a clipping
    gather: codes at or above ``len(table)`` (the min-buffer identity
    INT32_MAX on a live all-NULL aggregate row) stay INT32_MAX, and
    negative codes (NULL -1, the max-buffer / first-value identity
    INT32_MIN) pass through unchanged, so a reduction identity is still
    an identity after the hop instead of aliasing onto a real word."""
    codes = xp.asarray(codes)
    table = xp.asarray(table)
    dt = codes.dtype
    n = int(table.shape[0])
    if n:
        gathered = table[xp.clip(codes, 0, n - 1)].astype(dt)
    else:
        gathered = codes
    hi = np.asarray(np.iinfo(np.int32).max, dt)
    out = xp.where(codes >= n, hi, gathered)
    return xp.where(codes < 0, codes, out).astype(dt)


def union_all(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """Concatenate batches (host-side shape change; capacity = sum).

    String columns re-encode onto a merged dictionary via
    ``remap_codes``; identical dictionaries (the post-exchange common
    case — the hop already unified code spaces) skip the remap.
    """
    assert batches
    names = batches[0].names
    capacity = sum(b.capacity for b in batches)
    vectors: List[ColumnVector] = []
    for ci, name in enumerate(names):
        vecs = [b.vectors[ci] for b in batches]
        dtype = vecs[0].dtype
        dicts = [v.dictionary for v in vecs]
        runs = [unmaterialized_runs(v) for v in vecs]
        if (any(r is not None for r in runs)
                and all((r.valid is None and r.capacity == b.capacity)
                        if r is not None else v.valid is None
                        for r, v, b in zip(runs, vecs, batches))
                and len({d or () for d in dicts}) == 1):
            # at least one piece is still run-encoded over one shared
            # code space: concatenate the run TABLES and stay lazy
            # (adjacent equal values across a seam are two runs — still
            # a valid table).  A DENSE sibling piece — typically the
            # reducer's own map output, which short-circuits the wire
            # and so was never run-detected — is re-encoded here IF it
            # compresses (one vectorized diff); a piece that doesn't
            # falls through to the dense concat, inflating the encoded
            # pieces exactly as before
            tables = []
            for r, v, b in zip(runs, vecs, batches):
                if r is not None:
                    tables.append(
                        (np.asarray(r.run_values, dtype.np_dtype),
                         r.run_lengths))
                    continue
                vals, lens = rle_encode(np.asarray(v.data,
                                                   dtype.np_dtype))
                if len(vals) * 2 > b.capacity:
                    tables = None
                    break
                tables.append((vals, lens))
            if tables is not None:
                rvals = np.concatenate([t[0] for t in tables])
                rlens = np.concatenate([t[1] for t in tables])
                vectors.append(RunColumnVector(rvals, rlens, dtype, None,
                                               dicts[0]))
                continue
        if dtype.is_string or isinstance(dtype, T.BinaryType):
            if len({d or () for d in dicts}) == 1:
                data = np.concatenate([np.asarray(v.data) for v in vecs])
                dictionary = dicts[0] or ()
            else:
                merged = dicts[0] or ()
                remaps = [None] * len(vecs)
                for i in range(1, len(vecs)):
                    merged, ra, rb = merge_dictionaries(merged, dicts[i] or ())
                    # ra remaps everything merged so far; fold into
                    # earlier remaps
                    for j in range(i):
                        remaps[j] = ra if remaps[j] is None else ra[remaps[j]]
                    remaps[i] = rb
                datas = []
                for v, rm in zip(vecs, remaps):
                    d = np.asarray(v.data)
                    datas.append(remap_codes(np, d, rm)
                                 if rm is not None else d)
                data = np.concatenate(datas)
                dictionary = merged
        else:
            data = np.concatenate([np.asarray(v.data, dtype.np_dtype) for v in vecs])
            dictionary = None
        valids = [v.valid for v in vecs]
        if any(vl is not None for vl in valids):
            valid = np.concatenate([
                np.asarray(vl) if vl is not None else np.ones(b.capacity, bool)
                for vl, b in zip(valids, batches)])
        else:
            valid = None
        vectors.append(ColumnVector(data, dtype, valid, dictionary))
    rv = np.concatenate([np.asarray(b.row_valid_or_true()) for b in batches])
    return ColumnBatch(names, vectors, rv, capacity)


def align_string_columns(a: ColumnBatch, a_col: str, b: ColumnBatch, b_col: str
                         ) -> Tuple[ColumnBatch, ColumnBatch]:
    """Re-encode two string columns onto a shared dictionary (host-side prep
    before joins/set-ops compare them on device)."""
    va, vb = a.column(a_col), b.column(b_col)
    if va.dictionary == vb.dictionary:
        return a, b
    merged, ra, rb = merge_dictionaries(va.dictionary or (), vb.dictionary or ())

    def remap(batch, name, vec, rm):
        new = remap_codes(np, np.asarray(vec.data), rm)
        i = batch.names.index(name)
        vecs = list(batch.vectors)
        vecs[i] = ColumnVector(new.astype(np.int32), vec.dtype, vec.valid, merged)
        return ColumnBatch(batch.names, vecs, batch.row_valid, batch.capacity)

    return remap(a, a_col, va, ra), remap(b, b_col, vb, rb)
