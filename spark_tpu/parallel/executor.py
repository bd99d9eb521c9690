"""Distributed planner + shard_map executor.

Builds the SPMD program for a whole query and runs it as ONE shard_map over
the data mesh (the reference's DAGScheduler stage pipeline collapses into a
single XLA program whose collectives are the stage boundaries).
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

from .. import config as C
from .. import tracing
from ..columnar import ColumnBatch, ColumnVector, pad_capacity
from ..expressions import Col
from ..kernels import compact
from ..memory import batch_nbytes
from ..sql import physical as P
from ..sql.joins import PJoin, plan_join_raw, _JoinOutput
from ..sql.logical import Aggregate, Distinct, FileRelation, Filter, Join, Limit, LocalRelation, LogicalPlan, Project, RangeRelation, Sample, Sort, SubqueryAlias
from ..sql.planner import ADAPT_MAX_RETRIES, Planner, check_planned_join_capacities, grow_capacity_factor
from . import dist as D
from .mesh import DATA_AXIS, mesh_shards

_log = logging.getLogger("spark_tpu.execution")


class DistributedPlanner(Planner):
    """Planner emitting exchange-aware physical plans (EnsureRequirements)."""

    def __init__(self, session, n_shards: int,
                 skew_override: Optional[float] = None,
                 join_factor_override: Optional[float] = None,
                 agg_shrink_override: Optional[int] = None, shared=None):
        super().__init__(session, join_factor_override,
                         agg_shrink_override=agg_shrink_override,
                         shared=shared)
        self.n_shards = n_shards
        self.skew_override = skew_override

    @property
    def skew(self) -> float:
        if self.skew_override is not None:
            return self.skew_override
        return self.session.conf.get(C.EXCHANGE_SKEW_FACTOR)

    @property
    def fine(self) -> int:
        """Fine buckets for adaptive exchanges (0 = static hash%n)."""
        if not self.session.conf.get(C.ADAPTIVE_ENABLED):
            return 0
        return self.n_shards * self.session.conf.get(C.EXCHANGE_FINE_BUCKETS)

    def _to_physical(self, node: LogicalPlan, leaves) -> P.PhysicalPlan:
        n = self.n_shards
        if isinstance(node, RangeRelation):
            return D.DRange(node.start, node.end, node.step, node.name,
                            node.num_rows(), n)
        if isinstance(node, Aggregate):
            child = self._to_physical(node.child, leaves)
            if any(getattr(f, "is_collect", False)
                   or getattr(f, "is_percentile", False)
                   for f, _n in node.aggs):
                # no fixed-width mergeable partial form: gather rows to one
                # shard and aggregate there (the reference's
                # ObjectHashAggregate runs such aggs on a single partition
                # after the shuffle) — everything BELOW stays sharded.
                # Keyless aggregation emits an always-valid global row on
                # EVERY shard, so mask the result to shard 0
                agg = P.PAggregate(node.keys, node.aggs,
                                   D.DGatherOne(child))
                return agg if node.keys else D.DKeepShardZero(agg)
            if not node.keys:
                return D.DGlobalAggregate(node.aggs, child)
            partial_agg = D.DPartialAggregate(node.keys, node.aggs, child)
            key_refs = [Col(k.name) for k in node.keys]
            exchanged = D.DExchangeHash(key_refs, n, self.skew, partial_agg,
                                        fine_buckets=self.fine)
            # per-shard group tables are prefix-live (rv = arange <
            # num_groups), so the eager shrink applies per shard; its
            # overflow flag rides the shard_map's shrink channel
            return self._shrunk(D.DFinalAggregate(
                node.keys, node.aggs, partial_agg, exchanged))
        if isinstance(node, Distinct):
            child = self._to_physical(node.child, leaves)
            keys = [Col(nm) for nm in node.child.schema().names]
            partial_agg = D.DPartialAggregate(keys, [], child)
            exchanged = D.DExchangeHash(keys, n, self.skew, partial_agg,
                                        fine_buckets=self.fine)
            return self._shrunk(D.DFinalAggregate(
                keys, [], partial_agg, exchanged))
        if isinstance(node, Sort):
            child = self._to_physical(node.child, leaves)
            orders = [(o.child, o.ascending, o.nulls_first) for o in node.orders]
            ex = D.DExchangeRange(orders, n, self.skew, child)
            return D.DShardSort(orders, ex)
        if isinstance(node, Limit):
            return D.DLimit(node.n, self._to_physical(node.child, leaves))
        if isinstance(node, Join):
            return self._plan_dist_join(node, leaves)
        from ..sql.window import WindowNode
        if isinstance(node, WindowNode):
            return self._plan_dist_window(node, leaves)
        return super()._to_physical(node, leaves)

    def _plan_dist_window(self, node, leaves) -> P.PhysicalPlan:
        """Windows need all rows of a partition on one shard
        (WindowExec.requiredChildDistribution: ClusteredDistribution on
        partitionBy, SinglePartition when empty — `EnsureRequirements.scala:33`).
        Group the window expressions by partition keys; each group gets a
        hash exchange (or a gather-to-one-shard for empty partitionBy)
        before the per-shard window kernel."""
        child = self._to_physical(node.child, leaves)
        # the analyzer emits one WindowNode per distinct window spec, so
        # all wexprs here share one partitionBy — one exchange suffices
        pb = node.wexprs[0][0].spec.partition_by
        if pb:
            exchanged = D.DExchangeHash(list(pb), self.n_shards, self.skew,
                                        child, fine_buckets=self.fine)
        else:
            exchanged = D.DGatherOne(child)
        return P.PWindow(node.wexprs, exchanged)

    def _plan_dist_join(self, node: Join, leaves) -> P.PhysicalPlan:
        n = self.n_shards
        threshold = self.session.conf.get(C.AUTO_BROADCAST_JOIN_THRESHOLD)
        # estimate build size by logical row estimate (capacity-based)
        right_rows = _estimate_rows(node.right)
        raw = plan_join_raw(self, node if node.how != "right" else
                            Join(node.right, node.left, "left", node.on, node.using),
                            leaves)
        inner = raw
        if isinstance(raw, PJoin):
            build_small = right_rows is not None and right_rows <= threshold \
                and node.how in ("inner", "left", "left_semi", "left_anti", "cross")
            if build_small or raw.how == "cross":
                # broadcast hash join: build side replicated to all shards
                inner = PJoin(raw.children[0], D.DBroadcast(raw.children[1]),
                              raw.how, raw.key_pairs, raw.residual,
                              raw._schema, raw.factor)
            elif self.fine > 0:
                # adaptive shuffled hash join: one balanced assignment for
                # both sides; hot probe buckets spread + build replicate
                # (only where build-side unmatched rows are never emitted)
                allow_spread = raw.how in ("inner", "left", "left_semi",
                                           "left_anti")
                inner = D.DSkewJoin(
                    raw.children[0], raw.children[1], raw.how,
                    raw.key_pairs, raw.residual, raw._schema, raw.factor,
                    n, self.skew, self.fine,
                    self.session.conf.get(C.EXCHANGE_SPREAD_FRAC),
                    allow_spread)
            else:
                # shuffled hash join: co-partition both sides on key hash
                # (pairs normalized so a mixed int/float pair routes both
                # sides identically)
                lkeys, rkeys = D._routing_key_pairs(
                    raw.key_pairs, raw.children[0].schema(),
                    raw.children[1].schema())
                ex_l = D.DExchangeHash(lkeys, n, self.skew, raw.children[0])
                ex_r = D.DExchangeHash(rkeys, n, self.skew, raw.children[1])
                inner = PJoin(ex_l, ex_r, raw.how, raw.key_pairs, raw.residual,
                              raw._schema, raw.factor)
        if node.how in ("left_semi", "left_anti"):
            return inner
        ls, rs = node.left.schema(), node.right.schema()
        if node.how == "right":
            return _JoinOutput(node.schema(), ls.names, rs.names,
                               left_base=len(rs.names), right_base=0,
                               using=node.using or [], how="right", child=inner)
        return _JoinOutput(node.schema(), ls.names, rs.names,
                           left_base=0, right_base=len(ls.names),
                           using=node.using or [], how=node.how, child=inner)


def _estimate_rows(node: LogicalPlan) -> Optional[int]:
    if isinstance(node, LocalRelation):
        return node.batch.capacity
    if isinstance(node, RangeRelation):
        return node.num_rows()
    from ..sql.logical import FileRelation
    if isinstance(node, FileRelation):
        # datasource stats (SparkStrategies.scala:116): a small parquet
        # dimension table must take the broadcast path, not a shuffle —
        # parquet answers from metadata without loading data
        from ..io import file_row_count
        try:
            return file_row_count(node)
        except Exception:
            return None
    if isinstance(node, (Project, SubqueryAlias, Filter, Sample)):
        return _estimate_rows(node.children[0])
    if isinstance(node, Limit):
        child = _estimate_rows(node.children[0])
        return min(node.n, child) if child is not None else node.n
    return None


# ---------------------------------------------------------------------------

class DistributedExecution:
    """Runs a planned query as one shard_map program over the mesh."""

    def __init__(self, session, mesh: Mesh):
        self.session = session
        self.mesh = mesh
        self.n = mesh_shards(mesh)

    MAX_ADAPT = ADAPT_MAX_RETRIES

    def live_view(self):
        """The post-failure process topology this executor would serve:
        ``cluster.live_view`` over the session's heartbeat verdicts and
        the exchange plane's agreed-lost set.  Purely observational here
        — the shard_map program itself cannot drop a participant
        mid-collective (XLA restarts from checkpoint); the DCN exchange
        lanes in ``crossproc`` are the layer that actually re-plans over
        this set."""
        from .cluster import live_view as _lv
        svc = getattr(self.session, "_crossproc_svc", None)
        hb = getattr(svc, "heartbeat", None) if svc is not None else None
        dead = hb.dead_hosts() if hb is not None else ()
        gone = sorted(svc.recovered_pids) if svc is not None else ()
        return _lv(self.n, dead, gone)

    def execute(self, optimized: LogicalPlan) -> ColumnBatch:
        """Run with adaptive capacity retry: when an exchange bucket or a
        join output overflows its static capacity, replan with factors
        sized from the MEASURED worst-shard overflow and rerun — the
        static-shape answer to `ExchangeCoordinator.scala:85`-style
        adaptation (which coalesces partitions; here capacities grow)."""
        # same adapted-parameter dict shape as the local executor
        base_key = f"dist{self.n}:adapt:" + optimized.tree_string()
        adapted = self.session._adapted_factors.get(base_key) or {}
        skew, jf = adapted.get("skew"), adapted.get("join")
        shrink = adapted.get("shrink")
        grew = False
        ex_ratio = join_ratio = 0.0
        shared: dict = {}          # the statement's Shared results
        for attempt in range(self.MAX_ADAPT + 1):
            with tracing.replan(attempt, max(ex_ratio, join_ratio),
                                {"skew": skew, "join": jf,
                                 "shrink": shrink}):
                result, ex_ratio, join_ratio, shrink_need = self._run_once(
                    optimized, skew, jf, shrink, check_caps=grew,
                    shared=shared)
            if ex_ratio <= 0.0 and join_ratio <= 0.0 and shrink_need <= 0:
                if skew is not None or jf is not None or shrink is not None:
                    self.session._adapted_factors[base_key] = {
                        "skew": skew, "join": jf, "shrink": shrink}
                return result
            base_skew = skew if skew is not None \
                else self.session.conf.get(C.EXCHANGE_SKEW_FACTOR)
            base_jf = jf if jf is not None \
                else self.session.conf.get(C.JOIN_OUTPUT_FACTOR)
            if attempt == self.MAX_ADAPT:
                raise RuntimeError(
                    f"exchange/join/agg still overflows after {attempt} "
                    f"adaptive retries (skew={base_skew}, join "
                    f"factor={base_jf}, agg capacity={shrink}); raise "
                    f"{C.EXCHANGE_SKEW_FACTOR.key} / "
                    f"{C.JOIN_OUTPUT_FACTOR.key} / "
                    f"{C.AGG_OUTPUT_ROWS.key} explicitly")
            if ex_ratio > 0.0:
                skew = grow_capacity_factor(base_skew, ex_ratio)
            if join_ratio > 0.0:
                jf = grow_capacity_factor(base_jf, join_ratio)
                grew = True
            if shrink_need > 0:
                from ..columnar import pad_capacity
                base_s = shrink if shrink is not None \
                    else self.session.conf.get(C.AGG_OUTPUT_ROWS)
                shrink = pad_capacity(
                    max(int(shrink_need * 1.25), 2 * int(base_s)))
            _log.warning(
                "capacity overflow (exchange %.0f%%, join %.0f%%, agg "
                "need %d); replanning with skew=%s join_factor=%s "
                "agg_capacity=%s", ex_ratio * 100, join_ratio * 100,
                shrink_need, skew, jf, shrink)

    def _run_once(self, optimized: LogicalPlan, skew: Optional[float],
                  jf: Optional[float], shrink: Optional[int] = None,
                  check_caps: bool = False, shared=None
                  ) -> Tuple[ColumnBatch, float, float, int]:
        planner = DistributedPlanner(self.session, self.n,
                                     skew_override=skew,
                                     join_factor_override=jf,
                                     agg_shrink_override=shrink,
                                     shared=shared)
        with tracing.span("plan"):
            pq = planner.plan(optimized)
        if check_caps:
            # exact per-join allocation guard after growth in THIS
            # execution (attributes the violation to the join owning the
            # buffer); cached factors already proved they fit
            check_planned_join_capacities(pq, self.session,
                                          "distributed join")
        key = f"dist{self.n}:" + pq.physical.key()

        # the distributed jit cache, and beside it what each trace noted
        with tracing.span("stage.lookup", hit=True) as sp:
            fn = self.session._jit_cache.get(key)
            sp.attrs["hit"] = fn is not None
        notes = self.session._jit_notes.setdefault(key, {})
        dev_leaves = tuple(self._shard_leaf(b) for b in pq.leaves)
        if fn is None:
            fn = jax.jit(shard_program(pq.physical, self.mesh))
            self.session._jit_cache[key] = fn
            timed = tracing.fresh_jit("executor.jit_cache")
        else:
            timed = tracing.span("stage.dispatch")
        with timed, tracing.collecting(notes):
            result, n_rows, ex_r, join_r, shr_need, paths = fn(dev_leaves)
        with tracing.span("d2h") as sp:  # the ratio fetch waits for the step
            ex_ratio = float(np.asarray(ex_r))
            join_ratio = float(np.asarray(join_r))
            shrink_need = int(np.asarray(shr_need))
            paths = [int(np.asarray(p)) for p in paths]
            if ex_ratio > 0.0 or join_ratio > 0.0 or shrink_need > 0:
                return result, ex_ratio, join_ratio, shrink_need
            host = result.to_host()
            sp.attrs["bytes"] = batch_nbytes(host)
        P.record_join_paths(paths, [P.JOIN_PATH] * len(paths),
                            (notes.get("join.caps") or [()])[-1])
        return compact(np, host), 0.0, 0.0, 0


    def _shard_leaf(self, batch: ColumnBatch) -> ColumnBatch:
        return shard_leaf(self.mesh, self.n, batch)


def shard_program(physical, mesh: Mesh):
    """The ONE ``shard_map`` program a distributed query runs:
    ``leaves -> (result, n_rows, exchange ratio, join ratio, agg need,
    join paths)`` with the three overflow readings and each join's path
    (``ExecContext.add_join_path``) reduced over the mesh.  Module-level
    so a test can compile exactly this for a described mesh."""
    from .collective import pmax

    def shard_fn(leaves):
        with tracing.scope("stage.step"):
            ctx = P.ExecContext(jnp, list(leaves))
            ctx.shard_offset = lax.axis_index(DATA_AXIS).astype(np.int64) << 48
            out = physical.run(ctx)
            out = compact(jnp, out)
            n_rows = lax.psum(out.num_rows(), DATA_AXIS)
            # per-kind worst overflow RATIO (lost rows / capacity),
            # pmax'd over shards — sizes the adaptive retry
            ex_r = jnp.zeros((), jnp.float32)
            join_r = jnp.zeros((), jnp.float32)
            # agg-shrink: absolute NEEDED capacity (lost + bound), 0
            # when nothing overflowed — growth is a row count, not a
            # factor
            shr_need = jnp.zeros((), jnp.int64)
            paths = []
            # each join's static (output slots, probe capacity) a shard:
            # a trace-time fact, kept with the program's notes
            tracing.note("join.caps", [
                cap for kind, cap in zip(ctx.flag_kinds, ctx.flag_caps)
                if kind == P.JOIN_PATH])
            for f, kind, cap in zip(ctx.flags, ctx.flag_kinds,
                                    ctx.flag_caps):
                if kind == P.JOIN_PATH:
                    paths.append(P.all_shards_path(f, pmax))
                    continue
                if kind == "shrink":
                    lost = f.astype(jnp.int64)
                    shr_need = jnp.maximum(
                        shr_need,
                        jnp.where(lost > 0, lost + np.int64(cap),
                                  np.int64(0)))
                    continue
                r = f.astype(jnp.float32) / np.float32(max(cap, 1))
                if kind == "exchange":
                    ex_r = jnp.maximum(ex_r, r)
                else:
                    join_r = jnp.maximum(join_r, r)
            return (out, n_rows, pmax(ex_r), pmax(join_r), pmax(shr_need),
                    paths)

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PartitionSpec(DATA_AXIS),),
        out_specs=(PartitionSpec(DATA_AXIS), PartitionSpec(),
                   PartitionSpec(), PartitionSpec(), PartitionSpec(),
                   PartitionSpec()),
        check_vma=False,
    )


def shard_leaf(mesh: Mesh, n: int, batch: ColumnBatch) -> ColumnBatch:
    """Pad a host batch so rows split evenly over shards, then device_put
    with row sharding."""
    with tracing.span("h2d", bytes=batch_nbytes(batch)):
        return _shard_leaf(mesh, n, batch)


def _shard_leaf(mesh: Mesh, n: int, batch: ColumnBatch) -> ColumnBatch:
    per = pad_capacity(max(-(-batch.capacity // n), 1))
    total = per * n
    sharding = NamedSharding(mesh, PartitionSpec(DATA_AXIS))

    def pad_and_put(arr, fill=0):
        a = np.asarray(arr)
        if len(a) < total:
            # arrays may be 2-D (ArrayType element planes): pad rows only
            pad = np.full((total - len(a),) + a.shape[1:], fill,
                          dtype=a.dtype)
            a = np.concatenate([a, pad])
        return jax.device_put(a, sharding)

    vectors = []
    for v in batch.vectors:
        data = pad_and_put(v.data)
        valid = None if v.valid is None else pad_and_put(v.valid, False)
        vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
    rv = pad_and_put(np.asarray(batch.row_valid_or_true()), False)
    return ColumnBatch(batch.names, vectors, rv, total)
