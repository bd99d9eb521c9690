"""The engine's aggregate and sort kernels COMPILE for a TPU v5e.

This file holds the kernels at the bench's shapes: the Pallas aggregate, the
fused MXU aggregate step, the sort aggregate and what it does after its
sorts, the chain of single-key sorts, the join probe's ``searchsorted`` and
the run planes.  Whole plans, on one chip and on four, are in
``test_tpu_compile_plans.py``.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so what it
refuses — a Mosaic kernel with a misaligned slice, a dtype the chip cannot
carry through a collective, a name the installed JAX no longer has — fails
in tier-1 at no chip time.  Nothing runs: these tests say nothing about
results or speed (``chip_smoke.py`` does, on the chip).

The topology is described inside the module-scoped fixture ``topo``
(``conftest.py``) and nowhere else, so only a module that asks for it loads
the TPU's library; describing it at import or in a ``skipif`` would load it
in every xdist worker.  Two workers may hold the library at once because
``conftest.py`` sets ``ALLOW_MULTIPLE_LIBTPU_LOAD``.  Code that asks
``jax.default_backend()`` still sees the CPU here, so the device gate
(``kernels._on_tpu_device``) is steered with ``monkeypatch``, in the test
(``on_tpu``).

A new compile test goes into the file of its theme.  A third file is added
only when one of the two passes about 700 s on a full tier-1 run, and it
should hold at least about 20 tests: ``--dist loadfile`` hands files out by
their number of tests, most first, so a small file starts late and ends the
run late.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_tpu import kernels as K
from spark_tpu import pallas_agg
from spark_tpu import types as T
from spark_tpu.aggregates import CountStar, Sum
from spark_tpu.columnar import ColumnBatch, ColumnVector
from spark_tpu.expressions import Col
from tpu_compile_shapes import _spec

N = 1 << 22              # the bench shape: rows per batch


def _kv_batch(n):
    return ColumnBatch(
        ["k", "v"],
        [ColumnVector(np.zeros(n, np.int64), T.LongType(), None, None),
         ColumnVector(np.zeros(n, np.int64), T.LongType(), None, None)],
        None, n)


# -- the aggregate kernel -------------------------------------------------

@pytest.mark.parametrize("B", [1024, 8192])
def test_pallas_agg_kernel_compiles(one_chip, B):
    P = 18                                   # live + 2 x (8 limbs) + count
    compiled = pallas_agg._accumulate_chunk.lower(
        jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((N, P), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        B=B, L=pallas_agg._L, BB=pallas_agg._BB, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "pallas_agg" in compiled.as_text()      # the kernel's trace name


def test_fused_mxu_aggregate_step_compiles(one_chip, on_tpu):
    """``kernels.grouped_aggregate`` at the bench shape: bucket prep, limb
    planes, the Mosaic kernel and the key decode as ONE program."""
    aggs = [(Sum(Col("v")), "s"), (CountStar(), "c")]

    def step(batch):
        out = K.grouped_aggregate(jnp, batch, [Col("k")], aggs,
                                  bucket_cap=4096)
        return K.compact(jnp, out)

    compiled = jax.jit(step).lower(
        _spec(_kv_batch(N), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the keyed aggregate did not lower to the Pallas kernel"


def test_sorted_grouped_aggregate_compiles(one_chip, on_tpu):
    """The ``lax.sort``-based aggregate at 2^22 int64 keys — every GROUP BY
    whose key range exceeds ``bucket_cap`` (on the TPU branch of
    ``multi_key_argsort``: the variadic sort it replaces takes the TPU
    compiler four times as long here)."""
    aggs = [(Sum(Col("v")), "s"), (CountStar(), "c")]

    def step(batch):
        return K._sorted_grouped_aggregate(jnp, batch, [Col("k")], aggs)

    compiled = jax.jit(step).lower(_spec(_kv_batch(N), one_chip)).compile()
    assert "sort" in compiled.as_text()


def _runs_batch(n):
    """Two nullable int64 keys, an int64 and a float64 value: what the
    aggregate cell's statement groups and sums."""
    ones = np.ones(n, bool)
    return ColumnBatch(
        ["k", "j", "v", "f"],
        [ColumnVector(np.zeros(n, np.int64), T.LongType(), ones, None),
         ColumnVector(np.zeros(n, np.int64), T.LongType(), ones, None),
         ColumnVector(np.zeros(n, np.int64), T.LongType(), None, None),
         ColumnVector(np.zeros(n, np.float64), T.DoubleType(), ones, None)],
        ones, n)


@pytest.mark.parametrize("form,log_rows", [("whole", 20), ("whole", 22)] + [
    ("branch", k) for k in range(10, 23)])
def test_sorted_runs_scan_and_reads_compile(one_chip, on_tpu, monkeypatch,
                                            form, log_rows):
    """What the sort aggregate does after its argsort (``kernels.
    sorted_runs``, ``reduce_runs``, ``run_keys``: one plane through ``perm``,
    the int32 running sum in two levels, the scatter of start positions,
    the segmented scan's ``while_loop``, the reads at the runs' ends)
    compiles for a v5e: ``whole`` as ``_sorted_grouped_aggregate`` with its
    sorts, and ``branch`` INSIDE a ``lax.cond`` branch, where the MXU
    aggregate's fallback puts it and where XLA:TPU refuses an int64
    ``cumsum`` at 9 of 16 lengths, at every power of two (the sorts are
    left out of the branch cases: their compile is minutes, and a sort in a
    branch is what ``test_fused_mxu_aggregate_step_compiles`` holds)."""
    n = 1 << log_rows
    keys = [Col("k"), Col("j")]
    aggs = [(CountStar(), "c"), (Sum(Col("v")), "s"), (Sum(Col("f")), "t")]

    def whole(batch):
        return K._sorted_grouped_aggregate(jnp, batch, keys, aggs)

    def branch(batch, fits):
        def slow(_):
            out = K._sorted_grouped_aggregate(jnp, batch, keys, aggs)
            return tuple(v.data for v in out.vectors), out.row_valid

        def fast(_):
            return tuple(jnp.zeros(n, d) for d in (
                np.int64, np.int64, np.int64, np.int64, np.float64)), \
                jnp.zeros(n, bool)

        return jax.lax.cond(fits, fast, slow, None)

    if form == "whole":
        compiled = jax.jit(whole).lower(
            _spec(_runs_batch(n), one_chip)).compile()
        assert "sort" in compiled.as_text()
    else:
        monkeypatch.setattr(
            K, "multi_key_argsort",
            lambda xp, cols, capacity: xp.arange(capacity, dtype=np.int32))
        compiled = jax.jit(branch).lower(
            _spec(_runs_batch(n), one_chip),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "while" in text and "scatter" in text


def test_tpu_sort_chain_equals_lexsort(on_tpu):
    """On a TPU ``multi_key_argsort`` is a chain of single-key stable sorts
    (a variadic sort costs the TPU compiler minutes); the permutation must
    be np.lexsort's, ties, int8 flags, int64 and float64 keys included."""
    rng = np.random.default_rng(3)
    n = 4096
    keys = [rng.integers(0, 2, n).astype(np.int8),
            rng.integers(-1, 2, n).astype(np.int8),
            rng.integers(-5, 5, n).astype(np.int64),
            rng.normal(size=n).round(0),
            rng.integers(0, 3, n).astype(np.int32)]
    want = np.lexsort(tuple(reversed(keys)))
    fn = jax.jit(lambda *k: K.multi_key_argsort(jnp, list(k), n))
    text = fn.lower(*keys).as_text()
    assert text.count("stablehlo.sort") == len(keys)
    np.testing.assert_array_equal(np.asarray(fn(*keys)), want)


def test_tpu_sort_chain_compiles(one_chip, on_tpu):
    n = 1 << 14
    sig = [np.int8, np.int8, np.int64, np.int8, np.float64]
    jax.jit(lambda *k: K.multi_key_argsort(jnp, list(k), n)).lower(
        *[jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
          for dt in sig]).compile()


# -- the join probe's search -------------------------------------------------

@pytest.mark.parametrize("method", ["scan", "scan_unrolled"])
def test_searchsorted_lowerings_compile(one_chip, method):
    """Both ``jnp.searchsorted`` lowerings the join probe can take, int64
    keys at the bench's q3 shape (2048-row build, 2^21-row probe)."""
    fn = jax.jit(lambda a, v: jnp.searchsorted(a, v, side="left",
                                               method=method))
    fn.lower(jax.ShapeDtypeStruct((2048,), jnp.int64, sharding=one_chip),
             jax.ShapeDtypeStruct((1 << 21,), jnp.int64,
                                  sharding=one_chip)).compile()


# -- run planes -------------------------------------------------------------

def test_run_plane_kernels_compile(one_chip, on_tpu):
    """Row -> run map, gather expansion and the per-run segment sum of a
    row mask, at a 2^16-run plane over a 2^22-row batch."""
    planes = 1 << 16

    def step(values, lengths, mask):
        ids = K.run_row_ids(jnp, lengths, N)
        dense = K.run_expand(jnp, values, lengths, N)
        live = jax.ops.segment_sum(mask.astype(jnp.int64), ids,
                                   num_segments=planes)
        return dense, (values * live).sum()

    jax.jit(step).lower(
        jax.ShapeDtypeStruct((planes,), jnp.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((planes,), jnp.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=one_chip)).compile()

