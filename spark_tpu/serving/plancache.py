"""Cross-session plan cache for the SQL server.

The reference amortizes query compilation twice: Janino bytecode is
cached process-wide in ``CodeGenerator.compile``'s Guava cache
(``codegen/CodeGenerator.scala:1415``), and the thriftserver keeps one
compiled plan serving many sessions.  Here the two are two stores with
one job each: the process ``StageCache`` keeps every compiled one-device
program (the trace + XLA compile), and this module keeps the PLANNED
statement, so a repeat from any server session skips analysis-to-plan
work too.  Flare and TQP (PAPERS.md) both locate a compiled engine's
serving throughput in exactly this amortization.

This module provides it:

* ``fingerprint(session, plan)`` — a stable string key over the
  OPTIMIZED logical plan: node structure, every non-child field,
  expression trees, leaf identities (LocalRelation batch uids, file
  paths + schemas), and the planning-relevant conf values.  Literals in
  arithmetic/comparison positions are SLOTTED OUT — replaced by typed
  ``?i`` markers — so ``WHERE v < 10`` and ``WHERE v < 20`` share one
  entry; their values ride into the compiled program as runtime scalar
  ARGUMENTS (see ``expressions._slot_bindings``), never baked
  constants.  Anything the serializer cannot PROVE stable (opaque
  objects, host callbacks' side outputs) makes the plan uncacheable
  rather than wrongly shared.
* ``PlanCache`` — a thread-safe, entry- and byte-bounded LRU from
  fingerprint → (physical plan, leaf recipes, slot literals): what lets
  a repeat skip PLANNING.  The compiled program is not kept here: every
  one-device executable lives in the process ``StageCache``
  (``sql/stagecompile.py``), reached through
  ``QueryExecution._run_planned``, which a hit calls with the entry's
  plan and this statement's literal values.  ``try_execute(qe)`` is the
  whole integration surface for ``QueryExecution``: it returns a
  finished host batch on a usable entry (building one on a miss) or
  ``None`` to fall through to the normal adaptive path.

Safety properties (the invalidation rules, see docs/DECISIONS.md):

* value-dependent PLANNING is covered by fingerprinting AFTER the
  optimizer: constant folding, CBO join reordering and filter pushdown
  have already consumed literal values, so variants that optimized
  differently get different fingerprints (including pushed-down scan
  predicates, serialized as FileRelation fields).
* file leaves are re-read on every hit (``read_file_relation`` has no
  data cache), so a hit always computes over CURRENT table data; the
  catalog hooks (CREATE/INSERT/DROP/ANALYZE → ``invalidate_paths``,
  SET of a planning conf → ``invalidate_conf``) evict entries whose
  PLAN may be stale, and the fingerprint's conf/schema components are
  the correctness backstop for sessions the hooks cannot see.
* a cached plan's static output capacities may not fit another
  literal variant's data: overflow flags are checked by the normal
  path's own code, and an overflowing fingerprint is POISONED (excluded
  from caching) and re-run through the adaptive replan loop.
* a literal the fingerprint slotted out must be a runtime PARAMETER of
  the plan's stage program, never a constant of its trace: an entry is
  admitted only if every slot of the logical fingerprint is (by object
  identity) a slot of its physical plan's stage fingerprint.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import config as C
from .. import expressions as E
from .. import tracing
from .. import types as T

__all__ = ["PlanCache", "PlanFingerprint", "fingerprint"]


class _Unfingerprintable(Exception):
    """Plan contains a field the serializer cannot key soundly."""


class _StaleEntry(Exception):
    """A hit's re-materialized leaves no longer match the compiled plan
    (e.g. a table's schema changed underneath the cache without a
    catalog hook firing)."""


# Literal parents whose eval() consumes the literal ONLY through
# Literal.eval (vectorized, dtype-stable): safe positions to replace the
# value with a runtime parameter.  Everything else (In/Between bounds,
# string ops, function args that read .value host-side) keeps the value
# in the fingerprint.
_SLOT_PARENTS = (E.Add, E.Sub, E.Mul, E.Div, E.IntDiv, E.Mod, E.Pow,
                 E.EQ, E.NE, E.LT, E.LE, E.GT, E.GE)

# dtypes whose Literal.eval is a pure asarray (no host-side string /
# decimal / datetime conversion): eligible for slotting
_SLOT_DTYPES = (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
                T.LongType, T.FloatType, T.DoubleType)

#: conf entries that change what the planner/optimizer would build; their
#: values are part of every fingerprint, and SET of one evicts entries
#: built under the old value (session._run_command hook)
PLANNING_CONF_ENTRIES = (
    C.CODEGEN_ENABLED, C.MESH_SHARDS, C.BATCH_CAPACITY,
    C.AUTO_BROADCAST_JOIN_THRESHOLD, C.JOIN_OUTPUT_FACTOR,
    C.AGG_OUTPUT_ROWS, C.JOIN_OUTPUT_MAX_ROWS, C.SHUFFLE_PARTITIONS,
    C.SCAN_MAX_BATCH_ROWS, C.MULTIBATCH_ENABLED, C.CASE_SENSITIVE,
    C.SESSION_TIME_ZONE, C.COLLECT_MAX_LEN, C.CROSSPROC_AUTO_BROADCAST,
    C.CROSSPROC_SHUFFLED_JOIN, C.CROSSPROC_SORT_MERGE_JOIN,
    C.ADAPTIVE_ENABLED, C.METRICS_ENABLED, C.WAREHOUSE_DIR,
    C.AGG_FOLD_ROWS, C.CROSS_JOIN_ENABLED, C.EXCHANGE_SKEW_FACTOR,
    # crossproc exchange shaping: fine-partition count, reducer
    # coalescing target and range-sample density move work between
    # processes; dedupReplicated changes the gather plan
    C.SHUFFLE_FINE_PARTITIONS, C.SHUFFLE_TARGET_PARTITION_BYTES,
    C.SHUFFLE_RANGE_SAMPLE_SIZE, C.CROSSPROC_DEDUP_REPLICATED,
    # adaptive replanning changes which exchange lane a join takes
    C.CROSSPROC_ADAPTIVE_REPLAN,
    # exchange tiering: which peers (if any) take the ICI device tier,
    # and the agreed byte floor below which a side stays on the host
    # path, both feed the tier-split decision the lanes replicate
    C.SHUFFLE_ICI_ENABLED, C.SHUFFLE_ICI_MIN_BYTES,
    C.SHUFFLE_ICI_TIER_OVERRIDE,
    # run-length/delta wire encoding flips which operator fast paths the
    # executed plan takes (run-aware vs dense)
    C.SHUFFLE_WIRE_RUN_CODES,
    # run planes flip the stage-boundary leaf form (compressed plane vs
    # dense materialization) and with it the traced stage shapes
    C.STAGE_RUN_PLANES,
)

PLANNING_CONF_KEYS = frozenset(e.key for e in PLANNING_CONF_ENTRIES)


class PlanFingerprint:
    """Key + the slotted Literal objects of THIS query's plan (positional;
    the serialization is deterministic, so slot i in any fingerprint-equal
    plan denotes the same parameter)."""

    def __init__(self, key: str, slots: List[E.Literal]):
        self.key = key
        self.slots = slots

    def bindings(self, entry_slots: List[E.Literal]) -> Dict[int, Any]:
        """THIS plan's slot values under the identity of a fingerprint-equal
        entry's ``Literal`` objects: what ``QueryExecution._run_planned``
        takes to run the entry's plan for this statement."""
        return {id(ref): np.asarray(s.value, dtype=ref.dtype.np_dtype)
                for s, ref in zip(self.slots, entry_slots)}


def _ser_expr(e: E.Expression, slots: List[E.Literal],
              slot_ok: bool) -> str:
    if type(e) is E.Literal:
        if slot_ok and e.value is not None \
                and isinstance(e.dtype, _SLOT_DTYPES):
            slots.append(e)
            return f"?{len(slots) - 1}:{e.dtype.simpleString()}"
        return f"lit[{e.value!r}:{e.dtype.simpleString()}]"
    child_ok = isinstance(e, _SLOT_PARENTS)
    fields = []
    if isinstance(e, (E.Col, E.Alias, E.LambdaVar)):
        # the identity of these leaves/binders lives in a PRIVATE field
        # the vars() walk below skips — without it `sum(a)` and `sum(b)`
        # serialize identically and two different plans share one
        # fingerprint (and, downstream, one compiled stage executable)
        fields.append(f"name={e.name!r}")
    for name in sorted(vars(e)):
        if name == "children" or name.startswith("_"):
            continue
        v = vars(e)[name]
        fields.append(f"{name}={_ser_val(v, slots)}")
    inner = ",".join(_ser_expr(c, slots, child_ok) for c in e.children)
    return f"{type(e).__name__}[{';'.join(fields)}]({inner})"


def _ser_val(v: Any, slots: List[E.Literal]) -> str:
    if isinstance(v, E.Expression):
        return _ser_expr(v, slots, False)
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return repr(v)
    if isinstance(v, T.DataType):
        return v.simpleString()
    from ..sql.logical import SortOrder, _batch_uid
    if isinstance(v, SortOrder):
        return (f"SortOrder[{int(v.ascending)}{int(v.nulls_first)}]"
                f"({_ser_expr(v.child, slots, False)})")
    if isinstance(v, (list, tuple)):
        inner = ",".join(_ser_val(x, slots) for x in v)
        return ("L(" if isinstance(v, list) else "T(") + inner + ")"
    if isinstance(v, dict):
        items = sorted(((repr(k), _ser_val(x, slots))
                        for k, x in v.items()))
        return "{" + ",".join(f"{k}:{x}" for k, x in items) + "}"
    if callable(v) and not isinstance(v, type):
        # identity-keyed (uid survives address recycling): same function
        # object = same behavior; a re-created lambda keys fresh
        return f"fn#{_batch_uid(v)}"
    raise _Unfingerprintable(f"{type(v).__name__} in plan fields")


def _ser_plan(node, slots: List[E.Literal], leaf_identity=True) -> str:
    from ..sql import logical as L
    if isinstance(node, L.LocalRelation):
        # batch identity, not content hash: uid is monotonic per batch
        # object, so two sessions' same-shaped temp views never collide
        # (a statement SHAPE leaves it out: ``fingerprint``)
        uid = f"#{L._batch_uid(node.batch)}" if leaf_identity else ""
        return f"Local{uid}:{node.batch.schema.simpleString()}"
    fields = []
    for name in sorted(vars(node)):
        if name in ("children", "child"):
            continue
        v = vars(node)[name]
        if name.startswith("_"):
            # private fields are planner memos EXCEPT the file schema,
            # which decides scan column layout and must key the entry
            if name == "_schema" and isinstance(v, T.StructType):
                fields.append(f"schema={v.simpleString()}")
            continue
        if isinstance(v, L.LogicalPlan) or (
                isinstance(v, (list, tuple)) and v
                and isinstance(v[0], L.LogicalPlan)):
            continue
        fields.append(f"{name}={_ser_val(v, slots)}")
    inner = ",".join(_ser_plan(c, slots, leaf_identity)
                     for c in node.children)
    return f"{type(node).__name__}[{';'.join(fields)}]({inner})"


def fingerprint(session, plan, leaf_identity=True
                ) -> Optional[PlanFingerprint]:
    """Fingerprint an OPTIMIZED plan, or None if it cannot be keyed.
    ``leaf_identity`` False keys the statement's SHAPE: in-memory leaves by
    their schema alone, so the same plan over another batch (a grace
    bucket, a cross-process lane's partition) has the same key."""
    slots: List[E.Literal] = []
    try:
        body = _ser_plan(plan, slots, leaf_identity)
    except (_Unfingerprintable, RecursionError):
        return None
    conf = ";".join(f"{e.key}={session.conf.get(e)!r}"
                    for e in PLANNING_CONF_ENTRIES)
    return PlanFingerprint(f"{body}|{conf}", slots)


class _Entry:
    """One cached plan: the physical plan and how to re-materialize its
    leaves (its executable is the stage cache's)."""

    __slots__ = ("key", "physical", "recipes", "leaf_schemas", "slots",
                 "paths", "conf_snapshot", "nbytes", "planning_ms", "hits",
                 "built_at")

    def __init__(self, key: str, physical, recipes, leaf_schemas, slots,
                 paths, conf_snapshot, nbytes):
        self.key = key
        self.physical = physical
        self.recipes = recipes          # [("local", node) | ("file", node)]
        self.leaf_schemas = leaf_schemas  # [StructType] in planner order
        self.slots = slots              # entry-owned Literal objects
        self.paths = paths              # abs file paths of file leaves
        self.conf_snapshot = conf_snapshot
        self.nbytes = nbytes
        self.planning_ms = 0.0
        self.hits = 0
        self.built_at = time.time()


class _StageEntry:
    """One cached DISTRIBUTED/MULTIBATCH statement: bookkeeping only.

    These shapes cannot be one host-callable executable (they stream
    batches, fork subprocesses, run shard_map collectives), so what the
    plan cache stores for them is the STATEMENT-level record — its
    fingerprint, the file paths and conf snapshot invalidation needs,
    and the stage trace+compile cost the first run paid.  The compiled
    stage executables themselves live in the process-local
    ``sql.stagecompile.StageCache`` (where subprocess reducers and every
    session share them); a hit here means the statement's whole
    stage-executable SET is known-warm, so the server reports
    ``cacheHit`` and skips nothing but re-proving it."""

    __slots__ = ("key", "kind", "paths", "conf_snapshot", "planning_ms",
                 "hits", "built_at")

    def __init__(self, key: str, kind: str, paths, conf_snapshot,
                 planning_ms: float):
        self.key = key
        self.kind = kind                # crossproc | dist | multibatch | …
        self.paths = paths
        self.conf_snapshot = conf_snapshot
        self.planning_ms = planning_ms
        self.hits = 0
        self.built_at = time.time()


#: fixed per-entry cost estimate for the plan objects; the dominant
#: VARIABLE cost (pinned LocalRelation inputs) is measured
_ENTRY_OVERHEAD_BYTES = 64 << 10


class PlanCache:
    """Thread-safe LRU: fingerprint → planned statement, shared across
    every ``_ServerSession`` (attach via ``session._plan_cache``)."""

    def __init__(self, conf):
        self._conf = conf
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _Entry]" = \
            collections.OrderedDict()
        self._bytes = 0
        # fingerprints whose cached run overflowed its static capacities:
        # they need the adaptive replan loop, so caching would thrash
        self._poisoned: set = set()
        # per-fingerprint single-flight build locks: N sessions missing
        # the same plan at once must pay ONE trace+compile, not N
        self._building: Dict[str, threading.Lock] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.uncacheable = 0
        # distributed/multibatch statements: bookkeeping entries whose
        # executables live in the process StageCache (see _StageEntry)
        self._stage_entries: "collections.OrderedDict[str, _StageEntry]" \
            = collections.OrderedDict()
        self.stage_hits = 0
        self.stage_misses = 0

    # -- stats ---------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "uncacheable": self.uncacheable,
                "entries": len(self._entries), "bytes": self._bytes,
                "stage_entries": len(self._stage_entries),
                "stage_hits": self.stage_hits,
                "stage_misses": self.stage_misses,
            }

    @property
    def bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- bounded LRU mechanics ----------------------------------------
    def _get(self, key: str) -> Optional[_Entry]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
            return entry

    def _put(self, entry: _Entry) -> None:
        max_entries = int(self._conf.get(C.SERVER_PLAN_CACHE_MAX_ENTRIES))
        max_bytes = int(self._conf.get(C.SERVER_PLAN_CACHE_MAX_BYTES))
        if entry.nbytes > max_bytes:
            with self._lock:
                self.uncacheable += 1
            return
        with self._lock:
            old = self._entries.pop(entry.key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[entry.key] = entry
            self._bytes += entry.nbytes
            while self._entries and (
                    len(self._entries) > max_entries
                    or self._bytes > max_bytes):
                _k, victim = self._entries.popitem(last=False)
                self._bytes -= victim.nbytes
                self.evictions += 1

    def _drop(self, key: str, count_invalidation: bool = False) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._bytes -= entry.nbytes
                if count_invalidation:
                    self.invalidations += 1

    def _poison(self, key: str) -> None:
        with self._lock:
            if len(self._poisoned) > 1024:
                self._poisoned.clear()
            self._poisoned.add(key)

    # -- invalidation --------------------------------------------------
    def invalidate_paths(self, path: str) -> int:
        """Evict every entry reading under/above ``path`` (a table or
        database directory a DDL/DML just mutated)."""
        import os
        p = os.path.abspath(path)

        def overlaps(entry):
            for leaf in entry.paths:
                if leaf == p or leaf.startswith(p + os.sep) \
                        or p.startswith(leaf + os.sep):
                    return True
            return False

        victims = []
        with self._lock:
            for key, entry in self._entries.items():
                if overlaps(entry):
                    victims.append(key)
            for key in victims:
                entry = self._entries.pop(key, None)
                if entry is not None:
                    self._bytes -= entry.nbytes
            stage_victims = [k for k, e in self._stage_entries.items()
                             if overlaps(e)]
            for k in stage_victims:
                self._stage_entries.pop(k, None)
            self.invalidations += len(victims) + len(stage_victims)
        return len(victims) + len(stage_victims)

    def invalidate_conf(self, key: str, old: Any, new: Any) -> int:
        """A planning-relevant conf changed in SOME session: evict
        entries built under the session's old value.  (The fingerprint's
        conf component already guarantees correctness — this is hygiene,
        freeing entries the setting session can no longer hit.)"""
        if key not in PLANNING_CONF_KEYS or old == new:
            return 0
        victims = []
        with self._lock:
            for k, entry in self._entries.items():
                if entry.conf_snapshot.get(key) == old:
                    victims.append(k)
            for k in victims:
                entry = self._entries.pop(k, None)
                if entry is not None:
                    self._bytes -= entry.nbytes
            stage_victims = [k for k, e in self._stage_entries.items()
                             if e.conf_snapshot.get(key) == old]
            for k in stage_victims:
                self._stage_entries.pop(k, None)
            self.invalidations += len(victims) + len(stage_victims)
        return len(victims) + len(stage_victims)

    def invalidate_all(self) -> None:
        with self._lock:
            n = len(self._entries) + len(self._stage_entries)
            self._entries.clear()
            self._stage_entries.clear()
            self._bytes = 0
            self.invalidations += n

    # -- execution integration ----------------------------------------
    def try_execute(self, qe) -> Optional[Any]:
        """The QueryExecution hook: run ``qe`` through the cache.

        Returns the finished host ColumnBatch, or None to fall through
        to the normal adaptive execution path (uncacheable plan, the
        interpreted lane, poisoned fingerprint, or capacity overflow)."""
        session = qe.session
        info = {"hit": False, "skippedMs": 0.0}
        session._last_plan_cache_info = info
        if not qe.compiles():
            return None
        with tracing.span("plancache.lookup", hit=False) as sp:
            fp = qe.fingerprint()
            entry = None if fp is None else self._get(fp.key)
            sp.attrs["hit"] = entry is not None
        if fp is None:
            with self._lock:
                self.uncacheable += 1
            return None
        if entry is None:
            with self._lock:
                if fp.key in self._poisoned:
                    self.misses += 1
                    return None
                build_lock = self._building.setdefault(
                    fp.key, threading.Lock())
            # single-flight: the herd blocks here while one thread
            # builds, then re-checks and takes the hit path
            with build_lock:
                entry = self._get(fp.key)
                if entry is None:
                    with self._lock:
                        self.misses += 1
                    try:
                        return self._build_and_run(qe, fp)
                    finally:
                        with self._lock:
                            self._building.pop(fp.key, None)
        try:
            out = self._run_entry(qe, entry, fp)
        except _StaleEntry:
            self._drop(fp.key, count_invalidation=True)
            with self._lock:
                self.misses += 1
            return self._build_and_run(qe, fp)
        if out is None:                  # overflow under THIS data shape
            self._drop(fp.key)
            self._poison(fp.key)
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            self.hits += 1
        entry.hits += 1
        info["hit"] = True
        info["skippedMs"] = entry.planning_ms
        return out

    def run_staged(self, qe, kind: str, thunk) -> Any:
        """The cache hook for DISTRIBUTED / MULTIBATCH statements, the
        shapes ``try_execute`` used to bail on.  Execution always goes
        through ``thunk`` (these lanes stream, fork and shard — there is
        no single host callable to store); what is cached cross-session
        is the statement-level ``_StageEntry``, with the compiled stage
        executables living in the process ``StageCache`` keyed by stage
        fingerprint.  A hit reports ``cacheHit``/``planningSkippedMs``
        to the server; literals in slot positions share one entry by
        the same fingerprint slotting as the local path."""
        session = qe.session
        info = {"hit": False, "skippedMs": 0.0}
        session._last_plan_cache_info = info
        if not session.conf.get(C.CODEGEN_ENABLED):
            return thunk()
        with tracing.span("plancache.lookup", hit=False) as sp:
            fp = qe.fingerprint()
            entry = None
            if fp is not None:
                key = f"stage|{kind}|{fp.key}"
                with self._lock:
                    entry = self._stage_entries.get(key)
                    if entry is not None:
                        self._stage_entries.move_to_end(key)
            sp.attrs["hit"] = entry is not None
        if fp is None:
            with self._lock:
                self.uncacheable += 1
            return thunk()
        if entry is not None:
            out = thunk()
            with self._lock:
                self.stage_hits += 1
            entry.hits += 1
            info["hit"] = True
            info["skippedMs"] = entry.planning_ms
            return out
        # miss: run the statement, charging it the stage trace+compile
        # cost the process StageCache pays during this execution — the
        # cost every later fingerprint-equal statement skips
        from ..sql.stagecompile import stage_cache
        sc = stage_cache(session)
        ms0 = sc.stats()["compile_ms"]
        out = thunk()                    # exceptions propagate unrecorded
        from ..sql.logical import FileRelation
        import os

        paths: List[str] = []

        def walk(node):
            if isinstance(node, FileRelation):
                paths.extend(os.path.abspath(p) for p in node.paths)
            for c in node.children:
                walk(c)

        walk(qe.optimized)
        conf_snapshot = {e.key: session.conf.get(e)
                         for e in PLANNING_CONF_ENTRIES}
        planning_ms = round(max(sc.stats()["compile_ms"] - ms0, 0.0), 1)
        entry = _StageEntry(key, kind, paths, conf_snapshot, planning_ms)
        max_entries = int(self._conf.get(C.SERVER_PLAN_CACHE_MAX_ENTRIES))
        with self._lock:
            self.stage_misses += 1
            self._stage_entries[key] = entry
            while len(self._stage_entries) > max(max_entries, 1):
                self._stage_entries.popitem(last=False)
                self.evictions += 1
        return out

    def _build_and_run(self, qe, fp: PlanFingerprint) -> Optional[Any]:
        from ..memory import batch_nbytes
        from ..sql.stagecompile import stage_fingerprint

        t0 = time.perf_counter()
        pq = qe.planned                  # Planner records leaf recipes
        recipes = getattr(pq, "leaf_recipes", None)
        # every literal the fingerprint slotted must reach the stage
        # program as a parameter: one the planner copied or consumed
        # would be a constant of the trace and serve the next value wrong
        stage_slots = {id(l) for l in stage_fingerprint(pq.physical)[1]}
        if recipes is None or len(recipes) != len(pq.leaves) \
                or any(kind == "opaque" for kind, _n in recipes) \
                or not all(id(l) in stage_slots for l in fp.slots):
            with self._lock:
                self.uncacheable += 1
            return None
        import os
        paths = []
        for kind, node in recipes:
            if kind == "file":
                paths.extend(os.path.abspath(p) for p in node.paths)
        pinned = sum(batch_nbytes(node.batch)
                     for kind, node in recipes if kind == "local")
        conf_snapshot = {e.key: qe.session.conf.get(e)
                         for e in PLANNING_CONF_ENTRIES}
        entry = _Entry(fp.key, pq.physical, recipes,
                       [b.schema for b in pq.leaves], fp.slots, paths,
                       conf_snapshot, _ENTRY_OVERHEAD_BYTES + pinned)
        out = self._run_entry(qe, entry, fp, first_leaves=pq.leaves)
        if out is None:
            self._poison(fp.key)
            return None
        # first-build cost ≈ what every later hit skips (plan + trace +
        # compile dominate the first run for cached-shape workloads)
        entry.planning_ms = round((time.perf_counter() - t0) * 1000, 1)
        self._put(entry)
        return out

    def _materialize(self, recipe, session):
        kind, node = recipe
        if kind == "local":
            return node.batch
        from ..io import read_file_relation
        return read_file_relation(node, session)

    def _run_entry(self, qe, entry: _Entry, fp: PlanFingerprint,
                   first_leaves=None) -> Optional[Any]:
        """Run the entry's plan with THIS statement's literal values
        through ``qe._run_planned`` (the one place that reserves memory,
        dispatches the stage cache's executable and reads its flags).
        None: the run overflowed and needs the adaptive loop, which takes
        it as its first attempt (``qe._last_ratio``)."""
        from ..sql.planner import PlannedQuery
        if first_leaves is not None:
            leaves = first_leaves
        else:
            leaves = [self._materialize(r, qe.session)
                      for r in entry.recipes]
            for batch, want in zip(leaves, entry.leaf_schemas):
                if batch.schema.simpleString() != want.simpleString():
                    raise _StaleEntry(
                        f"leaf schema drifted: {batch.schema.simpleString()}"
                        f" != {want.simpleString()}")
        host, ratio = qe._run_planned(
            PlannedQuery(entry.physical, list(leaves)),
            fp.bindings(entry.slots))
        return None if ratio > 0.0 else host

    def metrics_source(self):
        """Gauges for the metrics system ('serving' Source half; the
        server merges admission gauges in)."""
        return {
            "plan_cache_hits": lambda: self.stats()["hits"],
            "plan_cache_misses": lambda: self.stats()["misses"],
            "plan_cache_evictions": lambda: self.stats()["evictions"],
            "plan_cache_invalidations":
                lambda: self.stats()["invalidations"],
            "plan_cache_bytes": lambda: self.stats()["bytes"],
            "plan_cache_entries": lambda: self.stats()["entries"],
            "plan_cache_stage_entries":
                lambda: self.stats()["stage_entries"],
            "plan_cache_stage_hits": lambda: self.stats()["stage_hits"],
            "plan_cache_stage_misses":
                lambda: self.stats()["stage_misses"],
        }
