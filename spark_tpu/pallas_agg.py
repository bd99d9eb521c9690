"""Pallas TPU kernel for grouped aggregation (the MXU hash-map).

The grouped-aggregate hot loop is a one-hot matmul: for each row tile,
``one_hot(bucket) @ planes`` scatters each row's 8-bit limb planes into its
bucket row.  Formulated in plain XLA the one-hot tile round-trips through
HBM (N x B bf16 — tens of GB at bench sizes) and fusion decisions are
fragile; this kernel builds each ``(L, BB)`` one-hot tile in VMEM from an
iota compare, feeds the MXU directly, and accumulates an int32 ``(B, P)``
result in VMEM scratch across the whole grid — HBM traffic is ONE pass
over the inputs.

Runtime bucket-chunk skipping: buckets are processed in ``BB``-wide
chunks, and a scalar-prefetch argument ``n_active`` (derived from the
actual key range, a traced value) lets the kernel skip chunks that cannot
contain a live bucket — the common "1k distinct keys in a 4k-bucket
table" case does 1/8th of the matmul work without recompiling.

Exactness: one-hot entries are {0,1} bf16, plane values are {0..255}
bf16 (both exact); each per-tile f32 dot accumulates at most
255*L < 2^24 so f32 is exact; the cross-tile int32 accumulator is exact
while 255*N < 2^31 (the wrapper chunks input batches above that).

Reference parity: this is the TPU replacement for the Tungsten vectorized
hash map (`sql/core/.../aggregate/VectorizedHashMapGenerator.scala`,
`AggregateBenchmark.scala:125-131` "codegen = T hashmap = T").
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = Any

# Tile sizes: L rows per tile (sublane-dim of the one-hot, multiple of 8),
# BB buckets per chunk (lane-dim, multiple of 128).  VMEM at the defaults:
# one-hot (L, BB) bf16 = 1 MB, acc (B<=8192, P->128 lanes) i32 <= 4 MB.
_L = 1024
_BB = 512
_MAX_B = 8192          # full-accumulator variant cap (acc must fit VMEM)
_MAX_CHUNK_ROWS = 1 << 23    # 255 * 2^23 < 2^31: int32 accumulator exact


def _kernel(nact_ref, bucket_ref, planes_ref, out_ref, acc_ref, *, T, BCH, L,
            BB, P):
    t = pl.program_id(0)
    bj = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[pl.ds(bj * BB, BB), :] = jnp.zeros((BB, P), jnp.int32)

    @pl.when(bj < nact_ref[0])
    def _active():
        b = bucket_ref[0, :]                                   # (L,) int32
        iota = jax.lax.broadcasted_iota(jnp.int32, (L, BB), 1) + bj * BB
        oh = (b[:, None] == iota).astype(jnp.bfloat16)         # (L, BB)
        pt = jax.lax.dot_general(
            oh, planes_ref[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (BB, P)
        acc_ref[pl.ds(bj * BB, BB), :] += pt.astype(jnp.int32)

    @pl.when((t == T - 1) & (bj == BCH - 1))
    def _fin():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("B", "L", "BB", "interpret"))
def _accumulate_chunk(bucket32: Array, planes: Array, n_active: Array, *,
                      B: int, L: int, BB: int, interpret: bool) -> Array:
    n = bucket32.shape[0]
    P = planes.shape[1]
    n_pad = ((n + L - 1) // L) * L
    if n_pad != n:
        # zero planes contribute nothing regardless of bucket value
        bucket32 = jnp.concatenate(
            [bucket32, jnp.zeros(n_pad - n, jnp.int32)])
        planes = jnp.concatenate(
            [planes, jnp.zeros((n_pad - n, P), planes.dtype)])
    B_pad = ((B + BB - 1) // BB) * BB
    T = n_pad // L
    BCH = B_pad // BB

    # index maps must stay i32: under jax_enable_x64 a bare Python 0
    # lowers as an i64 constant, which Mosaic refuses to legalize
    # ("failed to legalize operation 'func.func'", first seen on real
    # v5e hardware 2026-07-31 — interpret mode never catches this)
    zero = np.int32(0)          # numpy scalar: untraced, keeps i32 dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, BCH),
        in_specs=[
            pl.BlockSpec((1, L), lambda t, bj, n: (zero, t)),
            pl.BlockSpec((L, P), lambda t, bj, n: (t, zero)),
        ],
        out_specs=pl.BlockSpec((B_pad, P), lambda t, bj, n: (zero, zero)),
        scratch_shapes=[pltpu.VMEM((B_pad, P), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, T=T, BCH=BCH, L=L, BB=BB, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B_pad, P), jnp.int32),
        interpret=interpret,
        name="pallas_agg",
    )(n_active.reshape(1).astype(jnp.int32),
      bucket32.reshape(1, n_pad),
      planes.astype(jnp.bfloat16))
    return out[:B]


def supported(B: int) -> bool:
    return B <= _MAX_B


def n_active_chunks(xp, prod, B: int):
    """Traced int32 chunk count covering buckets [0, prod) — the kernel
    skips chunks >= this.  Owned here so the chunk width stays private."""
    return xp.clip(xp.ceil(prod / np.float64(_BB)), 1.0,
                   float(-(-B // _BB))).astype(np.int32)


def grouped_accumulate(bucket32: Array, planes: Array, n_active: Array,
                       B: int, *, interpret: bool = False) -> Array:
    """Per-bucket column sums: out[b, p] = sum(planes[i, p] for bucket[i]==b).

    bucket32: (N,) int32 in [0, B); rows whose planes are all-zero may carry
    any bucket value.  planes: (N, P) with values in {0..255}.  n_active: a
    traced int32 scalar — number of leading ceil(B/BB) bucket chunks that can
    contain a live bucket (pass B//BB rounded up to skip nothing).
    Returns (B, P) int64, bit-exact.
    """
    n = bucket32.shape[0]
    if n <= _MAX_CHUNK_ROWS:
        return _accumulate_chunk(bucket32, planes, n_active, B=B, L=_L,
                                 BB=_BB, interpret=interpret).astype(jnp.int64)
    tot = jnp.zeros((B, planes.shape[1]), jnp.int64)
    for s in range(0, n, _MAX_CHUNK_ROWS):
        e = min(s + _MAX_CHUNK_ROWS, n)
        tot = tot + _accumulate_chunk(
            bucket32[s:e], planes[s:e], n_active, B=B, L=_L, BB=_BB,
            interpret=interpret).astype(jnp.int64)
    return tot
