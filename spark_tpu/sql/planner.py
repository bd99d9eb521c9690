"""Planner + executor: logical plan → physical plan → compiled XLA program.

The compressed analog of the reference pipeline
``QueryExecution.scala:67-92`` (analyzed → optimized → sparkPlan →
executedPlan → toRdd): here the "executedPlan" is a pure function over the
prepared input batches, and "codegen" is ``jax.jit`` of that function,
cached per plan fingerprint (jax itself retraces when batch treedefs —
capacities, dictionaries, schemas — change).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import config as C
from .. import tracing
from .. import types as T
from ..columnar import ColumnBatch
from ..expressions import AnalysisException
from ..kernels import compact
from .logical import (
    Aggregate, Distinct, FileRelation, Filter, Join, Limit, LocalRelation,
    LogicalPlan, Project, RangeRelation, Sample, Shared, Sort, SubqueryAlias,
    Union, cte_copies,
)
from . import physical as P

_log = logging.getLogger("spark_tpu.execution")

#: adaptive capacity retry policy — ONE definition shared by the local and
#: distributed executors so overflow behavior cannot diverge
ADAPT_MAX_RETRIES = 4


def grow_capacity_factor(base: float, ratio: float) -> float:
    """Next capacity factor after an overflow of `ratio` (lost/capacity):
    at least 2× so pathological distributions converge in few retries."""
    return base * max(2.0, (1.0 + ratio) * 1.25)


class JoinFanoutError(RuntimeError):
    """An adaptive join-capacity growth asked for an output buffer beyond
    ``spark.sql.join.maxOutputRows``.  Typed so the stage builder can
    catch it and re-route the offending join through the grace spill
    path (where per-bucket capacities stay small) instead of dying."""


def _fanout_error(where: str, est_rows: float, factor: float,
                  probe_rows: int, cap: int) -> JoinFanoutError:
    """The ONE failure message for every fanout guard, so the guidance
    cannot drift between the eager, streamed and distributed sites."""
    return JoinFanoutError(
        f"{where} output needs ~{est_rows:,.0f} rows of static capacity "
        f"(factor {factor:.2f}x over {probe_rows:,} probe rows; > "
        f"{C.JOIN_OUTPUT_MAX_ROWS.key}={cap}): the join fans out too "
        "much for eager in-memory execution.  Route it out-of-core "
        f"(file-backed inputs larger than {C.SCAN_MAX_BATCH_ROWS.key} "
        "stream through the grace-join stage runner), reduce the "
        "hot-key fanout, or raise the cap explicitly")


def check_factor_cap(factor: float, probe_rows: int, session,
                     where: str = "join") -> None:
    """Fanout guard for growth sites where the probe capacity is known
    directly (the streamed step passes each join's OWN static probe base;
    planned queries use ``check_planned_join_capacities`` instead): an
    output allocation beyond spark.sql.join.maxOutputRows means the join
    fans out into something that would exhaust memory long before the
    retry loop gives up (the q14-under-skew failure asked XLA for
    ~275 GB) — fail with the actionable story instead.  The bound is
    ABSOLUTE rows: a huge factor on a tiny batch (grace-join chunk skew)
    is fine."""
    cap = session.conf.get(C.JOIN_OUTPUT_MAX_ROWS)
    est = factor * max(probe_rows, 1)
    if est > cap:
        raise _fanout_error(where, est, factor, probe_rows, cap)


def _overflow_ratio(flags: List[int], caps: List[int]) -> float:
    """Worst lost-rows / static-capacity ratio across all overflow flags.

    A missing capacity (shouldn't happen) degrades to cap=1 so a positive
    flag is NEVER silently ignored."""
    ratio = 0.0
    for i, f in enumerate(flags):
        if f > 0:
            c = caps[i] if i < len(caps) else 1
            ratio = max(ratio, f / max(c, 1))
    return ratio


def _slice_to_host(result: ColumnBatch, n: int) -> ColumnBatch:
    """Transfer only the live prefix of a COMPACTED device batch to host.

    collect() of a few rows from a padded million-row batch must not ship
    the padding over PCIe; slicing on device first costs one tiny dispatch.
    """
    from ..columnar import ColumnVector, pad_capacity
    cap = min(pad_capacity(max(n, 1)), result.capacity)
    if cap == result.capacity:
        return result.to_host()
    vectors = []
    for v in result.vectors:
        data = np.asarray(v.data[:cap])
        valid = None if v.valid is None else np.asarray(v.valid[:cap])
        vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
    rv = None if result.row_valid is None else np.asarray(result.row_valid[:cap])
    return ColumnBatch(result.names, vectors, rv, cap)


def _leaves_nbytes(batches) -> int:
    """Bytes of the batches as the memory ledger counts them (a span's
    ``bytes``)."""
    from ..memory import batch_nbytes
    return sum(batch_nbytes(b) for b in batches)


def _row_nbytes(schema: T.StructType) -> int:
    """Device bytes per row of one materialized batch of this schema
    (data + validity + row mask)."""
    total = 2
    for f in schema.fields:
        try:
            total += np.dtype(f.dataType.np_dtype).itemsize + 1
        except Exception:
            total += 9
    return total


def _walk_plan_caps(pq: PlannedQuery):
    """(root_cap, extra_bytes, join_caps) over the physical plan's STATIC
    output capacities — exact arithmetic, not a heuristic: join output
    capacity is ``pad_capacity(probe × factor)`` by construction
    (joins.py).  ``join_caps`` lists ``(PJoin, probe_rows, out_rows)``
    for every join with an adaptive (factor-sized) output buffer."""
    from ..columnar import pad_capacity
    from .joins import PJoin

    extra = 0
    join_caps: List[tuple] = []

    def cap(node: P.PhysicalPlan) -> int:
        nonlocal extra
        if isinstance(node, P.PScan):
            return pq.leaves[node.index].capacity
        if isinstance(node, P.PRange):
            return node.capacity
        ch = [cap(c) for c in node.children]
        if isinstance(node, P.PAggregate) and not node.keys:
            return 1            # global aggregate: capacity-1 output
        if isinstance(node, P.PAggShrink):
            return min(ch[0] if ch else 1, node.out_rows)
        if isinstance(node, PJoin):
            probe = ch[0] if ch else 1
            build = ch[1] if len(ch) > 1 else 1
            if node.how == "cross" or not node.key_pairs:
                # joins.py takes the all-pairs path for ANY join without
                # equi keys (pure non-equi residual), not just CROSS
                out = probe * build
            elif node.how in ("left_semi", "left_anti"):
                return probe                     # probe-shaped, no buffer
            else:
                out = pad_capacity(int(probe * max(node.factor, 0.1)))
                if node.how == "full":
                    out += build
                join_caps.append((node, probe, out))
            extra += out * _row_nbytes(node.schema())
            return out
        if isinstance(node, P.PUnion):
            out = sum(ch) if ch else 1
            extra += out * _row_nbytes(node.schema())
            return out
        return max(ch) if ch else 1

    root_cap = cap(pq.physical)
    extra += root_cap * _row_nbytes(pq.physical.schema())
    return root_cap, extra, join_caps


def check_planned_join_capacities(pq: PlannedQuery, session,
                                  where: str = "join") -> None:
    """EXACT successor of the factor-x-probe estimate for planned
    queries: walk the physical plan and fail any join whose STATIC output
    buffer exceeds ``spark.sql.join.maxOutputRows`` — attributing the
    violation to the join that owns the allocation, not to whichever
    leaf happens to be largest."""
    cap = session.conf.get(C.JOIN_OUTPUT_MAX_ROWS)
    try:
        join_caps = _walk_plan_caps(pq)[2]
    except Exception:
        return                  # estimation must never sink a query
    for node, probe, out in join_caps:
        if out > cap:
            raise _fanout_error(where, out, node.factor, probe, cap)


def _plan_reserve_bytes(pq: PlannedQuery) -> int:
    """Upper-bound device bytes for one execution attempt: the leaf
    working set (input + one fused intermediate) plus the STATIC output
    buffers of every capacity-growing operator (``_walk_plan_caps``)."""
    from ..memory import batch_nbytes
    try:
        _root, extra, _joins = _walk_plan_caps(pq)
        return 2 * sum(batch_nbytes(b) for b in pq.leaves) + extra
    except Exception:
        # estimation must never sink a runnable query
        return 2 * sum(batch_nbytes(b) for b in pq.leaves)


def _needs_local_fallback(plan: LogicalPlan) -> bool:
    """Plans the distributed executor cannot shard yet: ArrayType columns
    feeding an EXCHANGE-inducing operator (exchanges are 1-D today).

    collect/percentile aggregates no longer force a fallback — the
    distributed planner gathers their input to one shard (PAggregate over
    DGatherOne) and keeps everything below sharded.  Arrays they PRODUCE
    above all exchanges ride the shard_map output fine; arrays at LEAVES
    (2-D element planes + element-validity masks through row sharding) or
    feeding an exchange still fall back."""
    from .window import WindowNode
    found = []

    def has_arrays(node: LogicalPlan) -> bool:
        try:
            return any(isinstance(f.dataType, T.ArrayType)
                       for f in node.schema().fields)
        except Exception:
            return False

    def walk(node: LogicalPlan):
        if not node.children and has_arrays(node):
            found.append("array-leaf")
        exchange_like = isinstance(
            node, (Aggregate, Distinct, Join, Union, Sort, WindowNode))
        for c in node.children:
            if exchange_like and has_arrays(c):
                found.append("array-into-exchange")
            walk(c)

    walk(plan)
    return bool(found)


def _refuse_eager_fallback(session, exc: Exception) -> None:
    """Under ``spark.tpu.stages.enabled=required`` a plan the stage runner
    cannot stream fails here instead of loading its oversized relation
    onto the device whole."""
    from .stages import NotStreamable, stages_mode
    if stages_mode(session) == "required":
        raise NotStreamable(f"{exc}; spark.tpu.stages.enabled=required "
                            "refuses the eager fallback") from exc


def _record_windows(plan: LogicalPlan) -> None:
    """One zero-length ``window`` span for each window a one-device program
    computes: its functions, partition and order keys and, where it reads
    a materialized batch (the stage runner's window over an aggregate or a
    union of them), that batch's rows."""
    from .window import WindowNode
    if isinstance(plan, WindowNode):
        spec = plan.wexprs[0][0].spec
        child = plan.children[0]
        rows = int(np.asarray(child.batch.num_rows())) \
            if isinstance(child, LocalRelation) else None
        with tracing.span(
                "window", funcs=[repr(we.func) for we, _n in plan.wexprs],
                partition_keys=[repr(e) for e in spec.partition_by],
                order_keys=[repr(o) for o in spec.order_by], rows=rows):
            pass
    for c in plan.children:
        _record_windows(c)


class PlannedQuery:
    def __init__(self, physical: P.PhysicalPlan, leaves: List[ColumnBatch],
                 leaf_recipes=None):
        self.physical = physical
        self.leaves = leaves
        #: how each leaf batch was obtained, in PScan index order:
        #: ("local", LocalRelation) | ("file", FileRelation) |
        #: ("opaque", None) — the serving plan cache re-materializes
        #: leaves from these on a hit (files re-read → data freshness);
        #: any opaque leaf (side-effecting source) makes the plan
        #: uncacheable.  None when the planner predates recipe capture
        #: (callers constructing PlannedQuery directly).
        self.leaf_recipes = leaf_recipes


class Planner:
    """Logical → physical (``SparkPlanner.strategies`` analog)."""

    def __init__(self, session, join_factor_override=None,
                 for_execution: bool = True, agg_shrink_override=None,
                 shrink_aggs: bool = True, shared=None):
        #: None | float (every join) | list (per join construction index —
        #: chained joins must not COMPOUND one overflowing join's growth)
        self.session = session
        self.join_factor_override = join_factor_override
        #: None | int rows: adaptively grown keyed-agg output capacity
        #: (replaces spark.sql.agg.outputCapacity after a shrink overflow)
        self.agg_shrink_override = agg_shrink_override
        #: False for call sites that execute plans WITHOUT inspecting
        #: ctx.flags: the shrink's overflow flag is its only correctness
        #: escape hatch, so flag-blind execution must not shrink
        self.shrink_aggs = shrink_aggs
        #: False for explain/inspection: planning must not run side
        #: effects (lazy-checkpoint materialization)
        self.for_execution = for_execution
        #: the statement's ``Shared`` results where it runs the plan:
        #: each ``Shared`` node is computed once and enters the plan as an
        #: opaque leaf (``stages.materialize_shared``); without it a
        #: ``Shared`` node is planned in place, once for each parent
        self.shared = shared
        self._join_seq = 0
        self._leaf_recipes: list = []

    def _shrunk(self, agg: "P.PhysicalPlan") -> "P.PhysicalPlan":
        from ..columnar import pad_capacity
        if not self.shrink_aggs:
            return agg
        rows = self.agg_shrink_override
        if rows is None:
            rows = self.session.conf.get(C.AGG_OUTPUT_ROWS)
        return P.PAggShrink(pad_capacity(int(rows)), agg)

    def next_join_factor(self) -> float:
        """Output capacity factor for the NEXT join constructed — an
        EXPLICIT method (not a property) because each call consumes one
        position; list overrides are positional by join construction
        order, which matches flag (execution) order for the plans the
        planner builds.  ``plan()`` resets the sequence."""
        i = self._join_seq
        self._join_seq += 1
        o = self.join_factor_override
        if isinstance(o, (list, tuple)):
            if i < len(o) and o[i] is not None:
                return o[i]
            return self.session.conf.get(C.JOIN_OUTPUT_FACTOR)
        if o is not None:
            return o
        return self.session.conf.get(C.JOIN_OUTPUT_FACTOR)

    def plan(self, logical: LogicalPlan) -> PlannedQuery:
        self._join_seq = 0            # positional factors restart per plan
        self._leaf_recipes = []
        leaves: List[ColumnBatch] = []
        phys = self._to_physical(logical, leaves)
        self._assign_op_ids(phys, [1])
        if self.session.conf.get(C.METRICS_ENABLED):
            phys = self._wrap_metrics(phys)
        return PlannedQuery(phys, leaves, leaf_recipes=self._leaf_recipes)

    def _wrap_metrics(self, node: P.PhysicalPlan) -> P.PhysicalPlan:
        node.children = tuple(self._wrap_metrics(c) for c in node.children)
        return P.PMetric(node)

    def _assign_op_ids(self, node: P.PhysicalPlan, counter: List[int]) -> None:
        node.op_id = counter[0]
        counter[0] += 1
        for c in node.children:
            self._assign_op_ids(c, counter)

    def _scan(self, batch: ColumnBatch, leaves: List[ColumnBatch],
              source=None) -> P.PScan:
        leaves.append(batch)
        # leaf provenance for the serving plan cache: a re-materializable
        # source node, or opaque (side-effecting producers — cache hits
        # must NOT skip re-running those)
        if isinstance(source, (LocalRelation, FileRelation)):
            kind = "local" if isinstance(source, LocalRelation) else "file"
            self._leaf_recipes.append((kind, source))
        else:
            self._leaf_recipes.append(("opaque", None))
        return P.PScan(len(leaves) - 1, batch.schema)

    def _to_physical(self, node: LogicalPlan, leaves) -> P.PhysicalPlan:
        if isinstance(node, LocalRelation):
            return self._scan(node.batch, leaves, source=node)
        if isinstance(node, RangeRelation):
            return P.PRange(node.start, node.end, node.step, node.name,
                            node.num_rows())
        if isinstance(node, FileRelation):
            from ..io import read_file_relation
            batch = read_file_relation(node, self.session)
            return self._scan(batch, leaves, source=node)
        if isinstance(node, Shared) and self.shared is not None \
                and self.for_execution:
            # computed once a statement, its rows a leaf of no recipe: the
            # plan cache keeps no rows one statement computed
            from .stages import _eager, materialize_shared
            return self._scan(materialize_shared(
                self.session, node, self.shared,
                lambda child: _eager(self.session, child, "stage.step")),
                leaves)
        if isinstance(node, (SubqueryAlias, Shared)):
            return self._to_physical(node.child, leaves)
        from .logical import FlatMapGroupsWithState
        if isinstance(node, FlatMapGroupsWithState):
            # host-side user function: the child sub-plan runs as its own
            # query, the function runs per group with a fresh batch-mode
            # state, and the result enters THIS plan as a scanned leaf
            # (FlatMapGroupsWithStateExec batch semantics)
            from ..streaming.groupstate import run_flat_map_groups
            child = QueryExecution(self.session, node.child).execute()
            out, _states, _ch, _rm = run_flat_map_groups(
                node.func, node.key_names, child, node.out_schema, {},
                watermark_us=None, timeout_conf=node.timeout_conf)
            return self._scan(out, leaves)
        from .logical import EventTimeWatermark
        if isinstance(node, EventTimeWatermark):
            return self._to_physical(node.children[0], leaves)  # batch no-op
        if isinstance(node, Project):
            return P.PProject(node.exprs, self._to_physical(node.child, leaves))
        if isinstance(node, Filter):
            return P.PFilter(node.condition, self._to_physical(node.child, leaves))
        if isinstance(node, Aggregate):
            agg = P.PAggregate(node.keys, node.aggs,
                               self._to_physical(node.child, leaves))
            return self._shrunk(agg) if node.keys else agg
        if isinstance(node, Sort):
            orders = [(o.child, o.ascending, o.nulls_first) for o in node.orders]
            return P.PSort(orders, self._to_physical(node.child, leaves))
        if isinstance(node, Limit):
            return P.PLimit(node.n, self._to_physical(node.child, leaves))
        if isinstance(node, Distinct):
            return self._shrunk(
                P.PDistinct(self._to_physical(node.child, leaves)))
        from .window import WindowNode
        if isinstance(node, WindowNode):
            return P.PWindow(node.wexprs,
                             self._to_physical(node.child, leaves))
        if isinstance(node, Union):
            return P.PUnion([self._to_physical(c, leaves) for c in node.children],
                            node.schema())
        if isinstance(node, Sample):
            return P.PSample(node.fraction, node.seed,
                             self._to_physical(node.child, leaves))
        from .logical import LazyCheckpoint
        if isinstance(node, LazyCheckpoint):
            if not node.state["done"]:
                if not self.for_execution:
                    # explain/inspection is not an action: show the plan
                    # WITHOUT materializing the checkpoint
                    return self._to_physical(node.child, leaves)
                from .dataframe import DataFrame as _DF
                _DF(self.session, node.child).write.parquet(node.path)
                node.state["done"] = True
            from ..io import read_file_relation
            rel = self.session.read.parquet(node.path)._plan
            batch = read_file_relation(rel, self.session)
            # deliberately opaque to the plan cache: the checkpoint node's
            # mutable done-state would churn fingerprints, and correctness
            # requires the materialization side effect to run
            return self._scan(batch, leaves)
        from .logical import Explode
        if isinstance(node, Explode):
            return P.PExplode(node.pre_exprs, node.array_expr, node.out_name,
                              node.with_pos, node.pos_name,
                              self._to_physical(node.child, leaves),
                              insert_at=node.insert_at)
        if isinstance(node, Join):
            from .joins import plan_join
            return plan_join(self, node, leaves)
        raise AnalysisException(f"no physical plan for {node!r}")


def local_stage_key(session, pq):
    """(stage-cache key, literal slots, stage leaves) of the one-device
    whole-plan stage ``QueryExecution`` dispatches for ``pq``."""
    from . import stagecompile as SC
    stage_leaves = SC.plan_leaves(session, pq.leaves)
    skey, slots = SC.stage_fingerprint(pq.physical)
    skey = (f"local|{skey}|{SC.leaf_signature(stage_leaves)}"
            f"|{SC._conf_component(session)}")
    return skey, slots, stage_leaves


class QueryExecution:
    """Carries one query through analyze → optimize → plan → execute."""

    def __init__(self, session, logical: LogicalPlan):
        self.session = session
        self.logical = logical
        self._analyzed: Optional[LogicalPlan] = None
        self._optimized: Optional[LogicalPlan] = None
        self._planned: Optional[PlannedQuery] = None
        #: the statement this execution belongs to (``tracing``), set by
        #: ``execute``, and its root span while it runs
        self.statement_id = 0
        self._root_span: Optional[tracing.span] = None
        #: the scope of what the stage program does outside any operator:
        #: ``stage.merge`` where the stage runner materializes a sub-plan
        self._stage_scope = "stage.step"
        #: the keyed aggregates' first output capacity where the caller
        #: knows a bound on their groups (None: spark.sql.agg.outputCapacity)
        self._agg_rows: Optional[int] = None
        #: per-operator metrics of the last execution:
        #: {(op_id, operator label): output row count}
        self.metrics: Dict[Tuple[int, str], int] = {}
        #: the worst overflow ratio of the last attempt (``read_flags``)
        self._last_ratio = 0.0
        self._fingerprint = False        # not worked out yet (None: no key)
        #: the statement's ``Shared`` results (``Planner.shared``)
        self._shared: Dict = {}

    @property
    def analyzed(self) -> LogicalPlan:
        if self._analyzed is None:
            from .analyzer import Analyzer
            with tracing.span("analyze"):
                plan = Analyzer(self.session.catalog).analyze(self.logical)
                self._analyzed = self._use_cached_data(plan)
        return self._analyzed

    def _use_cached_data(self, plan: LogicalPlan) -> LogicalPlan:
        """Replace subtrees a DataFrame.cache() materialized with their
        cached batches (CacheManager.useCachedData on the analyzed plan)."""
        cache = getattr(self.session, "_cache", None)
        if cache is None or not cache._entries:
            return plan
        from .logical import plan_cache_key
        memo: dict = {}               # one memo across the walk: O(n) keys

        def sub(node: LogicalPlan) -> LogicalPlan:
            if isinstance(node, LocalRelation):
                return node           # never probe: not substitutable, and
            hit = cache.get(plan_cache_key(node, memo))  # get() bumps LRU
            if hit is not None:
                return LocalRelation(hit)
            return node

        return plan.transform_up(sub)

    @property
    def optimized(self) -> LogicalPlan:
        if self._optimized is None:
            from .optimizer import Optimizer
            analyzed = self.analyzed
            with tracing.span("optimize"):
                self._optimized = Optimizer(self.session.conf).optimize(
                    analyzed)
        return self._optimized

    @property
    def planned(self) -> PlannedQuery:
        if self._planned is None:
            optimized = self.optimized
            with tracing.span("plan"):
                self._planned = Planner(self.session,
                                        shared=self._shared).plan(optimized)
        return self._planned

    # ------------------------------------------------------------------
    MAX_ADAPT = ADAPT_MAX_RETRIES

    def execute(self) -> ColumnBatch:
        """Run the query; returns a COMPACTED host batch.

        Capacity overflow (a join producing more rows than its static
        output buffer) triggers an automatic replan with a factor sized
        from the MEASURED overflow, instead of erroring — the dynamic-shape
        answer to ExchangeCoordinator-style adaptation."""
        with tracing.statement() as sid:
            self.statement_id = sid
            # the root span: ``path`` is the executor ``_execute_inner``
            # takes (local / multibatch / stages / dist / crossproc)
            with tracing.span("statement", path="local") as root:
                self._root_span = root
                try:
                    result, end_event = self._execute_posting()
                finally:
                    self._root_span = None
            end_event["phases"] = tracing.statement_phases(sid)
            end_event["notes"] = tracing.statement_notes(sid)
            self.session._post_event(end_event)
            return result

    def _execute_posting(self):
        """(result, the ``SQLExecutionEnd`` event still to post); a failed
        execution posts its own and raises."""
        import time as _time
        t0 = _time.time()
        self.session._post_event({
            "event": "SQLExecutionStart", "time": t0,
            "plan": repr(self.optimized)[:500]})
        self.session._query_count = \
            getattr(self.session, "_query_count", 0) + 1
        # the EXECUTING session is the active one for the duration of the
        # query (SparkSession.setActiveSession in the reference): kernels
        # that read conf via getActiveSession (e.g. the collect_list cap)
        # must see THIS session's conf, not whichever session was created
        # last in the process
        cls = type(self.session)
        prev_active = getattr(cls._tls, "active", None)
        cls._set_thread_active(self.session)
        try:
            # every copy of a CTE's body in the statement is planned and
            # run by itself: one zero-length record each
            for name, copy in cte_copies(self.analyzed):
                with tracing.span("cte.body", name=name, copy=copy):
                    pass
            result = self._execute_inner()
        except BaseException as e:
            self.session._post_event({
                "event": "SQLExecutionEnd", "time": _time.time(),
                "durationMs": (_time.time() - t0) * 1000,
                "error": f"{type(e).__name__}: {e}"[:300]})
            raise
        finally:
            cls._set_thread_active(prev_active)
            self._leak_check()
        return result, {
            "event": "SQLExecutionEnd", "time": _time.time(),
            "durationMs": (_time.time() - t0) * 1000,
            "metrics": {f"{oid}:{lbl}": v
                        for (oid, lbl), v in self.metrics.items()}}

    def _leak_check(self) -> None:
        """Post-query reservation leak check (`Executor.scala:342-357`
        "Managed memory leak detected" idiom): every execution reservation
        this query made must be released by now; a leak is released
        loudly rather than starving later queries."""
        mem = getattr(self.session, "_memory", None)
        if mem is None:
            return
        owner = f"query:{id(self)}"
        leaked = mem.execution_held(owner)
        if leaked:
            _log.warning("managed HBM leak detected: %s held %d B after "
                         "execution; releasing", owner, leaked)
            mem.release_execution(owner)

    def _staged(self, kind: str, thunk):
        """Route one distributed/multibatch execution through the serving
        plan cache's STAGE-ENTRY bookkeeping (r8 lifted): the statement's
        optimized-plan fingerprint is recorded so a repeat — from ANY
        server session — reports ``cacheHit`` and skips the stage
        compiles (the executables live in the process-local stage
        cache).  Without an attached plan cache this is the thunk."""
        if self._root_span is not None:
            self._root_span.attrs["path"] = kind
        plan_cache = self._statement_cache()
        if plan_cache is None:
            return thunk()
        return plan_cache.run_staged(self, kind, thunk)

    def _statement_cache(self):
        """The session's serving plan cache where this execution is a
        statement's ROOT, else None: a sub-plan the stage runner
        materializes (``stages._eager``) is a new batch every statement,
        so its entry could never be hit again and would pin that batch;
        it goes straight to the adaptive loop and the stage cache, which
        keys leaves by shape."""
        if self._root_span is None:
            return None
        return getattr(self.session, "_plan_cache", None)

    def _execute_inner(self) -> ColumnBatch:
        self.session._last_qe = self      # metrics/explain introspection
        from ..analysis import maybe_verify_plan
        maybe_verify_plan(self.session, self.optimized)
        svc = getattr(self.session, "_crossproc_svc", None)
        if svc is not None:
            # the session's registered DCN data plane makes the exchange a
            # planner decision: the hop is placed here, on the normal
            # session.sql path (ShuffleExchangeExec placement role)
            from ..parallel.crossproc import crossproc_execute
            return self._staged(
                "crossproc",
                lambda: crossproc_execute(self.session, self.optimized,
                                          svc))
        n_shards = self.session.conf.get(C.MESH_SHARDS)
        if n_shards == 0:
            n_shards = len(jax.devices())
        if n_shards > 1 and _needs_local_fallback(self.optimized):
            # collect aggregates have no fixed-width mergeable partial
            # form, and array columns don't ride the 1-D exchanges yet —
            # run single-shard (the reference's objectHashAggregate also
            # falls back rather than spilling through the shuffle)
            _log.info("collect/array plan: falling back to single-shard")
            n_shards = 1
        if n_shards > 1:
            from ..parallel.executor import DistributedExecution
            from ..parallel.mesh import get_mesh
            mesh = get_mesh(n_shards)
            # out-of-core × distributed: oversized linear file chains
            # stream per-batch through a shard_map step (ShuffledRowRDD
            # stages are simultaneously out-of-core and distributed)
            from .multibatch import plan_multibatch
            mb = plan_multibatch(self.session, self.optimized, mesh=mesh)
            if mb is not None:
                return self._staged("multibatch", mb.execute)
            # join plans over oversized files: streamed stage DAG with the
            # per-batch step sharded over the mesh (bucket joins inside
            # the grace phase re-enter this executor and run distributed)
            from .stages import NotStreamable, plan_stages
            st = plan_stages(self.session, self.optimized, mesh=mesh)
            if st is not None:
                try:
                    return self._staged("stages", st.execute)
                except NotStreamable as e:
                    _refuse_eager_fallback(self.session, e)
                    _log.info("stage runner fallback to distributed "
                              "eager: %s", e)
            return self._staged(
                "dist",
                lambda: DistributedExecution(
                    self.session, mesh).execute(self.optimized))

        # out-of-core path: file scans larger than one device batch stream
        # through the multi-batch stage runner (FileScanRDD/ExternalSorter
        # analog) instead of one eager batch
        from .multibatch import plan_multibatch
        mb = plan_multibatch(self.session, self.optimized)
        if mb is not None:
            return self._staged("multibatch", mb.execute)

        # multi-relation out-of-core path: plans with joins over oversized
        # file relations stream through the stage DAG (grace hash joins +
        # broadcast-fused streams); non-streamable shapes fall back here
        from .stages import NotStreamable, plan_stages
        st = plan_stages(self.session, self.optimized)
        if st is not None:
            try:
                return self._staged("stages", st.execute)
            except NotStreamable as e:
                _refuse_eager_fallback(self.session, e)
                _log.info("stage runner fallback to eager: %s", e)

        _record_windows(self.optimized)
        # serving plan cache (spark_tpu.serving.plancache): attached to
        # server sessions, shared across all of them, asked at a
        # statement's root.  A usable entry skips planning and runs its
        # plan through ``_run_planned``; None falls through to the normal
        # adaptive path (uncacheable plan, overflow, jit off).
        plan_cache = self._statement_cache()
        if plan_cache is not None:
            cached_out = plan_cache.try_execute(self)
            if cached_out is not None:
                return cached_out
        # (where the plan cache ran the planned capacities and they
        # overflowed, that WAS the loop's first attempt: ``_last_ratio``)

        # ONE adapted-parameter shape for every executor:
        # {"skew": float|None, "join": factors|None, "shrink": rows|None},
        # kept under the statement's SHAPE, so that a repeat (a new literal
        # in a slot position included) starts from what the shape learned
        # and executes once
        base_key = self._capacity_key()
        adapted = self.session._adapted_factors.get(base_key) or {}
        factors = adapted.get("join")
        shrink = adapted.get("shrink", self._agg_rows)
        grew = False
        ratio = self._last_ratio if not adapted else 0.0
        for attempt in range(self.MAX_ADAPT + 1):
            if attempt or ratio <= 0.0:
                with tracing.replan(attempt, ratio,
                                    {"join": factors, "shrink": shrink}):
                    result, ratio = self._attempt(factors, shrink, grew)
            if ratio <= 0.0:
                if factors is not None or shrink is not None:
                    self.session._adapted_factors[base_key] = {
                        "join": factors, "shrink": shrink}
                return result
            if attempt == self.MAX_ADAPT:
                raise RuntimeError(
                    f"join/agg output still overflows after {attempt} "
                    f"adaptive retries (factors {factors}, agg capacity "
                    f"{shrink}); raise {C.JOIN_OUTPUT_FACTOR.key} / "
                    f"{C.AGG_OUTPUT_ROWS.key} explicitly (join growth is "
                    f"bounded by {C.JOIN_OUTPUT_MAX_ROWS.key})")
            # grow ONLY the joins that overflowed (positional): a chained
            # plan must not compound one hot join's factor into every join
            base_f = self.session.conf.get(C.JOIN_OUTPUT_FACTOR)
            join_ratios = getattr(self, "_last_join_ratios", [])
            cur = list(factors) if isinstance(factors, (list, tuple)) \
                else [None] * len(join_ratios)
            while len(cur) < len(join_ratios):
                cur.append(None)
            for i, r in enumerate(join_ratios):
                if r > 0:
                    prev = cur[i] if cur[i] is not None else base_f
                    cur[i] = grow_capacity_factor(prev, r)
            factors = cur
            # grow the keyed-agg output capacity past the measured group
            # count (ONE bound for all aggs in the plan: capacity growth
            # cannot corrupt results, only spend memory)
            lost = getattr(self, "_last_shrink", [])
            if any(l > 0 for l, _c in lost):
                from ..columnar import pad_capacity
                # 2x floor: MXU bucket tables can spread live groups
                # across [0, bucket_cap), so growth must make geometric
                # progress even when the measured lost count is small
                need = max(max(c + l, 2 * c) for l, c in lost if l > 0)
                shrink = pad_capacity(int(need * 1.25))
                _log.warning("agg output capacity overflowed; growing to "
                             "%d rows", shrink)
            grew = True
            _log.warning(
                "join/agg output overflowed its static capacity by "
                "%.0f%%; replanning with per-join factors %s, agg "
                "capacity %s", ratio * 100,
                ["%.2f" % f if f else "-" for f in factors], shrink)

    def _capacity_key(self) -> str:
        """Where ``session._adapted_factors`` keeps the capacities this
        statement's shape learned: the optimized plan with the literals in
        slot positions and the identity of in-memory leaves left out
        (``plancache.fingerprint``; the plan's own text where that cannot
        key it), so a new literal and the same plan over another batch (a
        grace bucket, a cross-process lane's partition) start from them.
        Not the physical plan: a statement with kept capacities is planned
        once, with them."""
        from ..serving.plancache import fingerprint
        fp = fingerprint(self.session, self.optimized, leaf_identity=False)
        return "local:" + (self.optimized.tree_string() if fp is None
                           else fp.key)

    def fingerprint(self):
        """``plancache.fingerprint`` of the optimized plan (None where it
        cannot be keyed), worked out once a statement."""
        if self._fingerprint is False:
            from ..serving.plancache import fingerprint
            self._fingerprint = fingerprint(self.session, self.optimized)
        return self._fingerprint

    def _attempt(self, factors, shrink, grew: bool
                 ) -> Tuple[ColumnBatch, float]:
        """One attempt of the adaptive loop: plan (with the capacities
        chosen, where any are) and run."""
        if factors is None and shrink is None:
            pq = self.planned
        else:
            with tracing.span("plan"):
                pq = Planner(self.session, join_factor_override=factors,
                             agg_shrink_override=shrink,
                             shared=self._shared).plan(self.optimized)
        if grew:
            # exact per-join allocation guard (replaces the old factor x
            # max-leaf estimate, which mis-blamed small joins in plans
            # with one large leaf).  Only GROWTH in THIS execution is
            # guarded — factors kept from a previous successful run
            # already proved they fit.
            check_planned_join_capacities(pq, self.session)
        return self._run_planned(pq)

    def _run_planned(self, pq: PlannedQuery, bindings=None
                     ) -> Tuple[ColumnBatch, float]:
        """One execution attempt → (host result, worst overflow ratio).
        ``bindings`` ({id(Literal of ``pq.physical``): value}) replaces those
        literals' own values as the stage's runtime parameters: how a
        plan-cache hit runs the entry's plan with this statement's values.

        Before dispatch the query's device working set is reserved with
        the HBM memory manager (UnifiedMemoryManager's
        acquireExecutionMemory): cached relations evict/demote to make
        room, and a query that cannot fit raises HBMOutOfMemoryError
        naming itself instead of dying inside XLA's allocator.  The
        reservation pre-flights the TRUE static output allocations of
        capacity-growing operators (join/cross/union buffers, whose sizes
        are compile-time constants) on top of the leaf working set, so a
        join whose output buffer cannot fit fails BEFORE dispatch (r2
        weak #5: estimate-based accounting was not enforcement)."""
        from ..analysis import maybe_verify_physical
        maybe_verify_physical(self.session, pq)
        mem = getattr(self.session, "_memory", None)
        owner = f"query:{id(self)}"
        if mem is not None:
            mem.acquire_execution(owner, _plan_reserve_bytes(pq))
        try:
            return self._run_planned_inner(pq, bindings or {})
        finally:
            if mem is not None:
                mem.release_execution(owner)

    def compiles(self) -> bool:
        """Whether this query runs as a compiled stage program (else the
        interpreted numpy lane, which the plan cache has nothing to keep
        for)."""
        if not self.session.conf.get(C.CODEGEN_ENABLED):
            return False
        from .udf import backend_supports_callbacks, plan_has_slow_udf
        if plan_has_slow_udf(self.optimized) \
                and not backend_supports_callbacks():
            # per-row Python UDFs need pure_callback; on backends
            # without host callbacks (some TPU runtimes) the query
            # drops to the interpreted host lane — the price the
            # reference pays per-UDF-operator, paid per-query here.
            # vectorized=True UDFs stay on the device path.
            _log.info("slow-lane Python UDF on a backend without host "
                      "callbacks: running interpreted")
            return False
        return True

    def _run_planned_inner(self, pq: PlannedQuery, bindings: Dict[int, Any]
                           ) -> Tuple[ColumnBatch, float]:
        if not self.compiles():
            # the numpy lane reads a literal's own value: a plan-cache hit
            # (the one source of bindings) never comes here
            assert not bindings
            ctx = P.ExecContext(np, [b.to_host() for b in pq.leaves])
            out = pq.physical.run(ctx)
            ratio = self.read_flags([int(f) for f in ctx.flags],
                                    ctx.flag_caps, ctx.flag_kinds)
            self.metrics = {(oid, lbl): int(v)
                            for oid, lbl, v in ctx.metrics}
            return compact(np, out.to_host()), ratio

        # the whole-plan step IS one exchange-bounded stage: compiled
        # executables live in the PROCESS-LOCAL stage cache
        # (sql/stagecompile.py), keyed on the structural fingerprint
        # plus the leaf shape/dtype signature, with int/float/bool
        # literals in arithmetic/comparison positions slotted out as
        # runtime arguments — crossproc lane sub-plans, grace-join
        # bucket pairs and repeated server statements all reuse ONE
        # compiled program per stage shape
        from . import stagecompile as SC
        cache = SC.stage_cache(self.session)
        # run-plane decision BEFORE the key: eligible lazy run columns
        # cross the boundary as fixed-capacity planes, and the plane
        # markers in leaf_signature re-key the stage (a run-count bucket
        # overflow re-plans to a larger plane; an oversized run table
        # falls back to the counted to_device materialization below)
        skey, slots, stage_leaves = local_stage_key(self.session, pq)

        def make():
            from ..analysis import maybe_verify_stage_contract
            physical = pq.physical
            stage_scope = self._stage_scope
            entry_slots = slots          # entry owns THIS plan's literals
            maybe_verify_stage_contract(
                self.session, SC.Stage(
                    physical, [b.schema for b in stage_leaves],
                    physical.schema(), skey))
            meta: Dict[Tuple, List] = {}

            def run(leaves, params):
                from .. import expressions as E
                E._slot_bindings.map = {
                    id(l): p for l, p in zip(entry_slots, params)}
                try:
                    with tracing.scope(stage_scope):
                        ctx = P.ExecContext(jnp, list(leaves))
                        out = physical.run(ctx)
                        c = compact(jnp, out)
                    # host-side capture at trace time, KEYED BY INPUT
                    # SHAPE: different leaf capacities retrace and may
                    # produce different static flag caps / metric keys
                    shape_key = tuple(b.capacity for b in leaves)
                    meta[shape_key] = (list(ctx.flag_caps),
                                       list(ctx.flag_kinds),
                                       [(oid, lbl)
                                        for oid, lbl, _v in ctx.metrics])
                    return c, c.num_rows(), ctx.flags, \
                        [v for _o, _l, v in ctx.metrics]
                finally:
                    E._slot_bindings.map = None

            return run, meta

        entry = cache.get_or_build(skey, make,
                                   n_ops=SC.count_ops(pq.physical),
                                   session=self.session)
        meta = entry.aux
        with tracing.span("h2d", bytes=_leaves_nbytes(stage_leaves)):
            dev_leaves = tuple(b.to_device() for b in stage_leaves)
        params = tuple(bindings.get(id(l), v)
                       for l, v in zip(slots, SC.param_values(slots)))
        result, n_rows, flags, metric_vals = cache.dispatch(
            entry, dev_leaves, params)
        shape_key = tuple(b.capacity for b in stage_leaves)
        flag_caps, flag_kinds, metric_keys = meta.get(shape_key,
                                                      ([], [], []))
        with tracing.span("d2h") as sp:  # the flag fetch waits for the step
            int_flags = [int(np.asarray(f)) for f in flags]
            self.metrics = {k: int(np.asarray(v))
                            for k, v in zip(metric_keys, metric_vals)}
            P.record_scan_rounds(self.metrics)
            host = _slice_to_host(result, int(np.asarray(n_rows)))
            sp.attrs["bytes"] = _leaves_nbytes([host])
        return host, self.read_flags(int_flags, flag_caps, flag_kinds)

    def read_flags(self, int_flags, caps, kinds) -> float:
        """What an attempt's fetched flags say: the ``join.path`` spans, the
        worst overflow ratio (0.0: everything fitted) and, kept for the
        adaptive loop's next choice, each join's ratio and each shrunk
        aggregate's (lost rows, capacity)."""
        P.record_join_paths(int_flags, kinds, caps)
        self._last_join_ratios = [
            f / max(c, 1)
            for f, c, k in zip(int_flags, caps, kinds) if k == "join"]
        self._last_shrink = [
            (f, c) for f, c, k in zip(int_flags, caps, kinds)
            if k == "shrink"]
        self._last_ratio = _overflow_ratio(int_flags, caps)
        return self._last_ratio

    def planned_preview(self) -> PlannedQuery:
        """Side-effect-free plan for explain(): lazy checkpoints are NOT
        materialized (uncached — execution re-plans normally)."""
        return Planner(self.session, for_execution=False).plan(self.optimized)

    def explain_string(self) -> str:
        s = "== Analyzed Logical Plan ==\n" + self.analyzed.tree_string()
        s += "== Optimized Logical Plan ==\n" + self.optimized.tree_string()
        s += "== Physical Plan ==\n" + \
            self.planned_preview().physical.tree_string()
        return s
