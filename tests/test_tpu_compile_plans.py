"""Whole plans COMPILE for a TPU v5e, on one chip and on four.

This file holds programs the planner builds: the q3-class fused plan, the
join with both of its paths and at a real fan-out, q67's window and rollup,
the device exchange's step and the mesh's reductions on four described
chips, and the distributed program.  The kernels alone, at the bench's
shapes, are in ``test_tpu_compile_kernels.py``.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from spark_tpu import kernels as K
from spark_tpu import types as T
from spark_tpu.aggregates import Sum
from spark_tpu.columnar import ColumnBatch, ColumnVector
from spark_tpu.expressions import Col
from tpu_compile_shapes import _spec


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

