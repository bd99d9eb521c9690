"""The main path's kernels and programs COMPILE for a TPU v5e.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so what it
refuses — a Mosaic kernel with a misaligned slice, a dtype the chip cannot
carry through a collective, a name the installed JAX no longer has — fails
in tier-1 at no chip time.  Nothing runs: these tests say nothing about
results or speed (``chip_smoke.py`` does, on the chip).

The topology is described inside a module-scoped fixture and nowhere else:
only one process at a time may load the TPU's library, so describing it at
import, in a ``skipif`` or in ``conftest.py`` would break the other xdist
workers.  All such tests live in THIS file for the same reason.  Code that
asks ``jax.default_backend()`` still sees the CPU here, so the device gate
(``kernels._on_tpu_device``) is steered with ``monkeypatch``, in the test.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from spark_tpu import kernels as K
from spark_tpu import pallas_agg
from spark_tpu import types as T
from spark_tpu.aggregates import CountStar, Sum
from spark_tpu.columnar import ColumnBatch, ColumnVector
from spark_tpu.expressions import Col

N = 1 << 22              # the bench shape: rows per batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """Steer the engine's device gate to its TPU branch for one test."""
    monkeypatch.setattr(K, "_on_tpu_device", lambda: True)
    monkeypatch.setattr(K, "MXU_AGG_ENABLED", None)


def _spec(tree, sharding):
    """The pytree with every array leaf replaced by its shape on the
    described device (there is no device to hold an array)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


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


# -- the q3-class fused plan ---------------------------------------------

def test_q3_class_fused_plan_compiles(one_chip, on_tpu, spark):
    """join + filter + project + aggregate + sort over int64/float64
    columns (``__graft_entry__.entry``), the program the old records said
    had never compiled for a TPU."""
    import __graft_entry__ as G
    run, (example,) = G.entry()
    compiled = jax.jit(run).lower(_spec(example, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("rows", [500, 30_000, 130_000, 250_000])
def test_join_with_both_paths_compiles(one_chip, on_tpu, spark, rows):
    """A join chooses between its two paths in conditionals.  Probe
    capacities 512, 32768 and 131072: three of the lengths at which the TPU
    compiler refuses an int64 ``cumsum`` (a ``reduce-window``) inside a branch —
    which is why the int64 running sum of the match counts (``ends``) sits
    BETWEEN the join's two conditionals and in neither.  The general
    branch of the second holds ``slot_owner``'s running sum of the marks,
    an int32 one, which the compiler takes there at every power of two from
    2^7 to 2^22 (PERF.md, PR 28); 262144 is the web cell's probe."""
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import QueryExecution
    fact = spark.createDataFrame(
        {"k": np.arange(rows, dtype=np.int64) % 100,
         "v": np.arange(rows, dtype=np.int64)})
    dim = spark.createDataFrame(
        {"dk": np.arange(100, dtype=np.int64),
         "w": np.arange(100, dtype=np.float64)})
    q = fact.join(dim, fact["k"] == dim["dk"], "left")
    pq = QueryExecution(spark, q._plan).planned

    def step(leaves):
        ctx = P.ExecContext(jnp, list(leaves))
        return K.compact(jnp, pq.physical.run(ctx)), ctx.flags

    text = jax.jit(step).lower(
        _spec(tuple(b.to_device() for b in pq.leaves), one_chip)) \
        .compile().as_text()
    assert "conditional" in text
    assert "join.unique" in text and "join.expand" in text
    assert "join.dense" in text and "join.probe" in text


@pytest.mark.parametrize("how,factor,out_cap", [
    ("inner", 16.0, 1 << 21), ("left_semi", 1.0, 1 << 17),
    ("left_anti", 1.0, 1 << 17)])
def test_fanout_join_compiles(one_chip, on_tpu, spark, how, factor, out_cap):
    """The general path at a real fan-out, as TPC-DS q95 / q94 run it: a
    self-join on the order number with a ``<>`` residual, the build repeating
    every key (12 lines an order).  Inner: a 2^17-row probe into 2^21
    output slots (the capacity a re-plan chooses: ``join_factor_override``);
    semi / anti: the residual decides existence, so the pairs are expanded
    there too."""
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import Planner, QueryExecution
    n = 100_000                               # pads to 2^17
    spark.createDataFrame(
        {"o": np.arange(n, dtype=np.int64) // 12,
         "w": np.arange(n, dtype=np.int64) % 5}) \
        .createOrReplaceTempView("fanout_lines")
    on = "a.o = b.o AND a.w <> b.w"
    q = spark.sql({
        "inner": "SELECT a.o, b.w FROM fanout_lines a, fanout_lines b "
                 f"WHERE {on}",
        "left_semi": "SELECT a.o FROM fanout_lines a WHERE EXISTS "
                     f"(SELECT * FROM fanout_lines b WHERE {on})",
        "left_anti": "SELECT a.o FROM fanout_lines a WHERE NOT EXISTS "
                     f"(SELECT * FROM fanout_lines b WHERE {on})"}[how])
    pq = Planner(spark, join_factor_override=[factor]).plan(
        QueryExecution(spark, q._plan).optimized)
    spark.catalog.dropTempView("fanout_lines")
    assert f"HashJoin {how}" in pq.physical.tree_string()
    caps = []

    def step(leaves):
        ctx = P.ExecContext(jnp, list(leaves))
        out = K.compact(jnp, pq.physical.run(ctx))
        caps.extend(c for k, c in zip(ctx.flag_kinds, ctx.flag_caps)
                    if k == P.JOIN_PATH)
        return out, ctx.flags

    text = jax.jit(step).lower(
        _spec(tuple(b.to_device() for b in pq.leaves), one_chip)) \
        .compile().as_text()
    assert caps == [(out_cap, 1 << 17, False)]
    assert "join.expand" in text and "join.gather" in text
    assert "join.dense" in text and "join.probe" in text


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


# -- four chips ---------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["int64", "float32", "int32", "bool"])
def test_exchange_step_compiles_on_four_chips(topo, dtype):
    """The device exchange's step (``ici._exchange_stage``'s traceable)
    on a mesh of the four described chips, for every plane dtype the pack
    produces (a float64 plane ships as its int64 view), at (4 peers x 4
    slots, 2^18 rows)."""
    from spark_tpu.parallel import ici
    mesh = Mesh(np.asarray(topo.devices[:4]), (ici.ICI_AXIS,))
    spec = PartitionSpec(ici.ICI_AXIS)
    fn = jax.jit(jax.shard_map(ici._a2a_step, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False))
    x = jax.ShapeDtypeStruct((16, 1 << 18), np.dtype(dtype),
                             sharding=NamedSharding(mesh, spec))
    text = fn.lower(x).compile().as_text()
    assert "all-to-all" in text


@pytest.mark.parametrize("dtype", ["int64", "float64", "float32"])
def test_mesh_extremes_compile_on_four_chips(topo, dtype):
    """XLA:TPU lowers 64-bit all-reduces for SUM only; ``collective.pmax``
    / ``pmin`` must compile for every dtype the executor and the global
    aggregate reduce (the first four-chip run died on ``lax.pmax`` of an
    int64 scalar)."""
    from spark_tpu.parallel.collective import pmax, pmin
    from spark_tpu.parallel.mesh import DATA_AXIS
    mesh = Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))
    fn = jax.jit(jax.shard_map(
        lambda x: (pmax(x.max()), pmin(x.min())), mesh=mesh,
        in_specs=PartitionSpec(DATA_AXIS),
        out_specs=(PartitionSpec(), PartitionSpec()), check_vma=False))
    fn.lower(jax.ShapeDtypeStruct(
        (1 << 12,), np.dtype(dtype),
        sharding=NamedSharding(mesh, PartitionSpec(DATA_AXIS)))).compile()


def test_distributed_program_compiles_on_four_chips(topo, on_tpu, spark):
    """``executor.shard_program`` — the ONE program ``DistributedExecution``
    runs — for the planner's q3-class distributed plan (exchange, shuffled
    join, partial/final aggregate, range sort on a float key, overflow
    readings reduced over the mesh), on the four described chips."""
    import __graft_entry__ as G
    from spark_tpu.parallel.executor import DistributedPlanner, shard_program
    from spark_tpu.parallel.mesh import DATA_AXIS
    from spark_tpu.sql.planner import QueryExecution

    df = G._q3_plan(spark)
    pq = DistributedPlanner(spark, 4).plan(
        QueryExecution(spark, df._plan).optimized)
    mesh = Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))
    leaves = tuple(
        _spec(b.to_device(), NamedSharding(mesh, PartitionSpec(DATA_AXIS)))
        for b in pq.leaves)
    text = jax.jit(shard_program(pq.physical, mesh)) \
        .lower(leaves).compile().as_text()
    assert "all-to-all" in text or "all-gather" in text


# -- a lowering error is an error, not "unavailable" -----------------------

def test_exchange_lowering_error_propagates(monkeypatch):
    """A failure inside the device exchange's program must surface as
    itself: turning it into ``IciUnavailable`` would send every exchange
    down the host tier in silence."""
    import collections
    import threading
    import types

    from spark_tpu.parallel import ici

    class Boom(RuntimeError):
        pass

    def broken(*_a, **_k):
        raise Boom("lowering failed")

    monkeypatch.setattr(ici, "_exchange_stage", broken)
    # past the "no spanning world" check, which IS unavailability
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    data = np.arange(8, dtype=np.int64)
    b = ColumnBatch(["k"], [ColumnVector(data, T.LongType(), None, None)],
                    None, len(data))
    svc = types.SimpleNamespace(pid=0, _lock=threading.Lock(),
                                counters=collections.Counter())
    plan = ici.SidePlan(ici.TierSplit(0, ((0,),)), True, 8, 1, 64)
    with pytest.raises(Boom):
        ici.device_exchange(svc, None, plan, "xq-test", {0: [b]}, b)
    assert svc.counters["ici_exchanges"] == 0


# -- q67's rollup and window ------------------------------------------------

def test_window_rank_compiles(one_chip, on_tpu):
    """``compute_windows``: RANK() OVER (PARTITION BY a string ORDER BY a
    float64 DESC) at 2^20 rows, the size of TPC-DS q67's union of grouping
    sets at SF1 (789,028 rows); the positions are int32 running maxima."""
    from spark_tpu.sql.logical import SortOrder
    from spark_tpu.sql.window import Rank, WindowSpec, compute_windows
    n = 1 << 20
    words = tuple(f"category{i}" for i in range(10))
    batch = ColumnBatch(["c", "s"], [
        ColumnVector(np.zeros(n, np.int32), T.StringType(),
                     np.ones(n, bool), words),
        ColumnVector(np.zeros(n, np.float64), T.DoubleType(),
                     np.ones(n, bool), None)], np.ones(n, bool), n)
    spec = WindowSpec([Col("c")], [SortOrder(Col("s"), False)])
    text = jax.jit(lambda b: compute_windows(jnp, b, spec, [(Rank(), "rk")])) \
        .lower(_spec(batch, one_chip)).compile().as_text()
    assert "window.sort" in text and "window.segments" in text
    assert "window.rank" in text


def test_rollup_coarser_set_compiles(one_chip, on_tpu, spark):
    """One grouping set re-aggregated from the next finer one, as q67's
    ROLLUP runs it at SF1: GROUP BY category, class, brand, product name and
    year over the 18,000 groups of the set below (2^15 rows), the SUM of its
    sums, under ``grouping.rollup``."""
    from spark_tpu import tracing
    from spark_tpu.sql import logical as L
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import Planner
    n = 1 << 15
    strings = ["i_category", "i_class", "i_brand", "i_product_name"]
    batch = ColumnBatch(strings + ["d_year", "__gs_0"], [
        ColumnVector(np.zeros(n, np.int32), T.StringType(),
                     np.ones(n, bool), tuple(f"{c}{i}" for i in range(50)))
        for c in strings] + [
        ColumnVector(np.zeros(n, np.int32), T.IntegerType(),
                     np.ones(n, bool), None),
        ColumnVector(np.zeros(n, np.float64), T.DoubleType(),
                     np.ones(n, bool), None)], np.ones(n, bool), n)
    plan = L.Aggregate([Col(c) for c in strings + ["d_year"]],
                       [(Sum(Col("__gs_0")), "__gs_0")], L.LocalRelation(batch))
    pq = Planner(spark).plan(plan)

    def step(leaves):
        with tracing.scope("grouping.rollup"):
            ctx = P.ExecContext(jnp, list(leaves))
            return K.compact(jnp, pq.physical.run(ctx)), ctx.flags

    text = jax.jit(step).lower(
        _spec(tuple(b.to_device() for b in pq.leaves), one_chip)) \
        .compile().as_text()
    assert "grouping.rollup" in text and "agg.sort" in text
