"""Collective data-movement kernels (run INSIDE shard_map).

These are the engine's data plane — what ``ShuffleExchange.scala:38`` +
``UnsafeShuffleWriter.java`` + ``ShuffleBlockFetcherIterator`` +
Netty chunk streams do in the reference, collapsed into XLA collectives:

* ``hash_exchange``: bucket rows by hash, pack per-destination send buffers
  (static per-bucket capacity = skew factor × even split), ONE
  ``lax.all_to_all`` over ICI, unpack.  Overflowing a bucket is detected and
  reported (the skew escape hatch — Spark's answer is spilling; ours is
  retry with a bigger factor, and later adaptive re-bucketing).
* ``broadcast_all``: ``all_gather`` the build side to every shard
  (``BroadcastExchangeExec`` without the driver round-trip).
* ``psum_batch``: merge global aggregation buffers across shards
  (``RDD.treeAggregate``'s reduction tree, done by the ICI allreduce).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import tracing
from ..columnar import ColumnBatch, ColumnVector
from ..kernels import multi_key_argsort, searchsorted, take_batch
from .mesh import DATA_AXIS

Array = Any


def shard_count(axis: str = DATA_AXIS) -> int:
    return lax.axis_size(axis)


def hash_exchange(batch: ColumnBatch, bucket: Array, n_shards: int,
                  cap_out: int, axis: str = DATA_AXIS,
                  ) -> Tuple[ColumnBatch, Array]:
    """Repartition rows so shard d receives every row with ``bucket == d``.

    Returns (received batch with capacity n_shards*cap_out, overflow count).
    Rows beyond a destination's ``cap_out`` are dropped and counted.
    """
    xp = jnp
    with tracing.scope("exchange.pack"):
        C = batch.capacity
        live = batch.row_valid_or_true()
        b = xp.where(live, bucket.astype(np.int32), np.int32(n_shards))

        perm = multi_key_argsort(xp, [b], C)
        bs = b[perm]
        sorted_batch = take_batch(xp, batch, perm)

        starts = searchsorted(xp, bs, xp.arange(n_shards, dtype=np.int32))
        slot = xp.arange(C) - starts[xp.clip(bs, 0, n_shards - 1)]
        ok = (bs < n_shards) & (slot < cap_out)
        overflow = xp.sum((bs < n_shards).astype(np.int64)) - xp.sum(ok.astype(np.int64))

        dest = xp.where(ok, bs, np.int32(n_shards))      # n_shards row → dropped
        slot_c = xp.clip(slot, 0, cap_out - 1)

        def scatter(data, fill):
            buf = xp.full((n_shards, cap_out), fill, dtype=data.dtype)
            return buf.at[dest, slot_c].set(data, mode="drop")

        vectors: List[Tuple[Array, Optional[Array], ColumnVector]] = []
        for v in sorted_batch.vectors:
            data2 = scatter(v.data, 0)
            valid2 = None if v.valid is None else scatter(v.valid, False)
            vectors.append((data2, valid2, v))
        rv_live = sorted_batch.row_valid_or_true() & ok
        rv2 = scatter(rv_live, False)

    with tracing.scope("exchange.all_to_all"):
        # ONE all_to_all moves every bucket to its destination over ICI
        received = []
        for data2, valid2, v in vectors:
            rd = lax.all_to_all(data2, axis, split_axis=0, concat_axis=0, tiled=True)
            rvd = None if valid2 is None else lax.all_to_all(
                valid2, axis, split_axis=0, concat_axis=0, tiled=True)
            received.append(ColumnVector(rd.reshape(-1), v.dtype,
                                         None if rvd is None else rvd.reshape(-1),
                                         v.dictionary))
        rv_recv = lax.all_to_all(rv2, axis, split_axis=0, concat_axis=0,
                                 tiled=True).reshape(-1)
    out = ColumnBatch(batch.names, received, rv_recv, n_shards * cap_out)
    return out, overflow


def fine_bucket_histogram(h: Array, live: Array, n_fine: int,
                          axis: str = DATA_AXIS) -> Tuple[Array, Array]:
    """(fine bucket id per row, GLOBAL live-row count per fine bucket).

    The measurement half of the adaptive exchange (the role of the
    reference's ``MapOutputStatistics`` feeding ``ExchangeCoordinator``):
    per-shard counts scatter-add locally, one ``psum`` makes them global —
    no host round-trip, the whole measurement stays inside the program."""
    xp = jnp
    fine = (h.astype(np.uint64) % np.uint64(n_fine)).astype(np.int32)
    local = xp.zeros(n_fine, np.int64).at[fine].add(
        live.astype(np.int64), mode="drop")
    with tracing.scope("exchange.psum"):
        return fine, lax.psum(local, axis)


def balanced_assignment(counts: Array, n_shards: int) -> Tuple[Array, Array]:
    """Greedy LPT packing of fine buckets onto shards: heaviest bucket
    first, always onto the least-loaded shard.  Pure function of the
    (psum'd, therefore shard-identical) counts, so every shard computes
    the SAME assignment with no extra collective.  Returns
    (assignment (B,) int32, predicted per-shard loads (n_shards,)).

    This subsumes both halves of ``ExchangeCoordinator.scala:85,118``:
    undersized buckets coalesce onto the same shard, oversized ones get a
    shard (nearly) to themselves."""
    order = jnp.argsort(-counts)                    # heavy first

    def body(i, carry):
        loads, assign = carry
        j = order[i]
        s = jnp.argmin(loads).astype(np.int32)
        return loads.at[s].add(counts[j]), assign.at[j].set(s)

    loads0 = jnp.zeros(n_shards, counts.dtype)
    assign0 = jnp.zeros(counts.shape[0], np.int32)
    loads, assign = lax.fori_loop(0, counts.shape[0], body, (loads0, assign0))
    return assign, loads


def replicate_selected(batch: ColumnBatch, mask: Array, hot_cap: int,
                       axis: str = DATA_AXIS) -> Tuple[ColumnBatch, Array]:
    """Every shard receives ALL rows where ``mask`` (from every shard):
    selected rows pack into a ``hot_cap`` send buffer, one ``all_gather``
    replicates them.  Returns (batch of capacity n_shards*hot_cap,
    overflow count of selected rows beyond hot_cap)."""
    xp = jnp
    C = batch.capacity
    hot_cap = min(hot_cap, C)       # a slice can't exceed the source batch
    live = batch.row_valid_or_true()
    sel = mask & live
    perm = multi_key_argsort(xp, [xp.where(sel, np.int8(0), np.int8(1))], C)
    sb = take_batch(xp, batch, perm)
    sel_s = sel[perm]
    n_sel = xp.sum(sel.astype(np.int64))
    overflow = xp.maximum(n_sel - np.int64(hot_cap), np.int64(0))

    def cut(a):
        return a[:hot_cap]

    vectors = [ColumnVector(cut(v.data), v.dtype,
                            None if v.valid is None else cut(v.valid),
                            v.dictionary) for v in sb.vectors]
    packed = ColumnBatch(batch.names, vectors, cut(sel_s), hot_cap)
    return broadcast_all(packed, axis), overflow


def round_robin_exchange(batch: ColumnBatch, n_shards: int,
                         axis: str = DATA_AXIS) -> ColumnBatch:
    """Spread rows evenly round-robin (RoundRobinPartitioning analog).

    Used before a range exchange: when input order correlates with the sort
    key (very common), whole shards map to one range bucket and the
    per-(source,dest) all_to_all capacity explodes; a round-robin pass makes
    every source hold a representative slice, bounding per-pair traffic at
    ~C/n.  Capacity is exact — this exchange cannot overflow.
    """
    from ..columnar import pad_capacity
    xp = jnp
    C = batch.capacity
    bucket = (xp.arange(C, dtype=np.int32) % n_shards)
    cap_out = pad_capacity(-(-C // n_shards))
    out, _ = hash_exchange(batch, bucket, n_shards, cap_out, axis)
    return out


def broadcast_all(batch: ColumnBatch, axis: str = DATA_AXIS) -> ColumnBatch:
    """Every shard receives the concatenation of all shards' rows."""
    n = lax.axis_size(axis)

    def gather(x):
        return lax.all_gather(x, axis, tiled=True)

    with tracing.scope("exchange.all_gather"):
        vectors = []
        for v in batch.vectors:
            data = gather(v.data)
            valid = None if v.valid is None else gather(v.valid)
            vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
        rv = gather(batch.row_valid_or_true())
    return ColumnBatch(batch.names, vectors, rv, batch.capacity * n)


def psum_arrays(arrays: List[Array], axis: str = DATA_AXIS) -> List[Array]:
    with tracing.scope("exchange.psum"):
        return [lax.psum(a, axis) for a in arrays]


def _extreme(op: str, x: Array, axis: str) -> Array:
    # XLA:TPU rewrites 64-bit element types away and implements that
    # rewrite for SUM all-reduces only ("Supported lowering only of Sum
    # all reduce", first seen on four v5e chips in PR 23): a 64-bit max/min
    # rides an all_gather and reduces locally — same value on every shard
    with tracing.scope("exchange.psum"):       # the all-reduce class
        if np.dtype(x.dtype).itemsize == 8:
            return getattr(jnp, op)(lax.all_gather(x, axis), axis=0)
        return (lax.pmax if op == "max" else lax.pmin)(x, axis)


def pmax(x: Array, axis: str = DATA_AXIS) -> Array:
    """``lax.pmax`` that also compiles on a TPU for int64/float64."""
    return _extreme("max", x, axis)


def pmin(x: Array, axis: str = DATA_AXIS) -> Array:
    """``lax.pmin`` that also compiles on a TPU for int64/float64."""
    return _extreme("min", x, axis)


def sampled_splitters_multi(keys: List[Array], live: Array, n_shards: int,
                            samples_per_shard: int = 64,
                            axis: str = DATA_AXIS) -> List[Array]:
    """Lexicographic multi-key range splitters (RangePartitioner over the
    FULL sort key, not just the first column — r1 weak #6): stratified
    sample of key TUPLES per shard → all_gather → lexsort → quantile
    tuples.  Returns one (n_shards-1,) array per key column, identical on
    every shard.  First-key-only splitting is already order-correct
    (equal first keys co-locate); refining by the remaining keys splits
    heavy first-key runs across shards instead of hotspotting one."""
    xp = jnp
    C = keys[0].shape[0]
    stride = max(C // samples_per_shard, 1)
    idx = xp.arange(samples_per_shard) * stride % C
    cols = []
    for k in keys:
        # dead rows sample as the key dtype's maximum (int64 or float64)
        big = np.float64(np.inf) if str(k.dtype).startswith("float") \
            else np.int64(np.iinfo(np.int64).max)
        sample = k[idx]
        sample = xp.where(live[idx], sample, big)
        cols.append(lax.all_gather(sample, axis, tiled=True))
    # lexicographic sort of the gathered tuples
    order = jax.lax.sort(tuple(cols) + (xp.arange(cols[0].shape[0],
                                                  dtype=np.int32),),
                         num_keys=len(cols), is_stable=True)[-1]
    total = samples_per_shard * n_shards
    pos = (xp.arange(1, n_shards) * total) // n_shards
    return [c[order][pos] for c in cols]


def lex_bucket(keys: List[Array], splitters: List[Array]) -> Array:
    """bucket[row] = number of splitter tuples <= row's key tuple
    (lexicographic searchsorted, vectorized over (capacity, n-1))."""
    xp = jnp
    n1 = splitters[0].shape[0]
    gt = xp.zeros((keys[0].shape[0], n1), bool)
    eq = xp.ones((keys[0].shape[0], n1), bool)
    for k, s in zip(keys, splitters):
        kv = k[:, None]
        sv = s[None, :]
        gt = gt | (eq & (kv > sv))
        eq = eq & (kv == sv)
    ge = gt | eq                      # tuple >= splitter → to its right
    return ge.sum(axis=1).astype(np.int32)


def sampled_splitters(key: Array, live: Array, n_shards: int,
                      samples_per_shard: int = 64,
                      axis: str = DATA_AXIS) -> Array:
    """Single-key convenience wrapper over sampled_splitters_multi."""
    return sampled_splitters_multi([key], live, n_shards,
                                   samples_per_shard, axis)[0]
