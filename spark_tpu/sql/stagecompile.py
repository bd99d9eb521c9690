"""Whole-stage tensor compilation with a process-local executable cache.

The physical tree between two exchange/breaker boundaries — one STAGE —
already executes as a single ``jax.jit`` trace (XLA fusion is the
WholeStageCodegen analog, ``physical.py`` header).  What the engine was
missing is ONE owner for those compiled stage programs: the eager
executor, the multi-batch streamers, the stage-DAG mapped streams and
every crossproc lane sub-plan each kept (or worse, rebuilt) private jit
objects, so a subprocess reducer recompiled the identical stage for
every query and every ``_MappedStream`` instance re-traced per stream.

``StageCache`` is that owner: a process-local, thread-safe LRU from a
STRUCTURAL stage fingerprint — ``PhysicalPlan.key()`` semantics grown
with literal slotting, leaf batch-shape/dtype signatures and the
planning-conf values that leak into traces (``getActiveSession`` reads
like the collect cap) — to the compiled executable.  Builds are
single-flight per fingerprint; literals in arithmetic/comparison
positions ride in as runtime scalar ARGUMENTS (the serving plan cache's
``expressions._slot_bindings`` protocol), so ``WHERE v < 10`` and
``WHERE v < 20`` share one stage executable.

The cache is deliberately per PROCESS, not per session: the serving
tier's sessions and the crossproc subprocess reducers are exactly the
places where per-session ``_jit_cache`` dicts made compile cost
O(sessions x queries) instead of O(distinct stage shapes).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import config as C
from .. import tracing

__all__ = [
    "Stage", "StageCache", "stage_cache", "stage_fingerprint",
    "leaf_signature", "count_ops", "metrics_source", "plan_leaves",
]


# ---------------------------------------------------------------------------
# stage fingerprints
# ---------------------------------------------------------------------------

def count_ops(physical) -> int:
    """Number of physical operators fused into one stage program."""
    return 1 + sum(count_ops(c) for c in physical.children)


def leaf_signature(leaves) -> str:
    """Batch-shape/dtype signature of a stage's input leaves: the part
    of the key ``PhysicalPlan.key()`` cannot see (capacities and vector
    dtypes decide the traced program's shapes).

    A run-plane vector signs as ``dtype~r{plane_capacity}``: the plane
    capacity is a ``pad_capacity`` bucket of the run count (the
    ``PJoin.factor`` discipline), so a run-count overflow past the
    bucket re-keys the stage and re-plans to a larger plane instead of
    feeding a stale trace the wrong shapes."""
    from ..columnar import unexpanded_plane
    parts = []
    for b in leaves:
        dts = ",".join(
            f"{v.dtype}~r{p.plane_capacity}"
            if (p := unexpanded_plane(v)) is not None else str(v.dtype)
            for v in b.vectors)
        parts.append(f"{b.capacity}[{dts}]")
    return "x".join(parts)


def _ser_physical(node, slots: List) -> str:
    """Slot-aware structural serialization of a physical tree.

    Same discipline as the serving plan cache's ``_ser_plan`` but over
    PHYSICAL operators: every non-child field is serialized, expression
    fields reuse ``plancache._ser_expr`` so int/float/bool literals in
    arithmetic/comparison positions slot out as ``?i`` markers (their
    values become runtime arguments of the stage executable)."""
    from ..serving.plancache import _ser_val
    fields = []
    for name in sorted(vars(node)):
        if name == "children":
            continue
        v = vars(node)[name]
        if name.startswith("_"):
            # private fields are planner memos EXCEPT the scan schema,
            # which decides the leaf layout the trace was built for
            from .. import types as T
            if name == "_schema" and isinstance(v, T.StructType):
                fields.append(f"schema={v.simpleString()}")
            continue
        fields.append(f"{name}={_ser_val(v, slots)}")
    inner = ",".join(_ser_physical(c, slots) for c in node.children)
    return f"{type(node).__name__}[{';'.join(fields)}]({inner})"


def stage_fingerprint(physical) -> Tuple[str, List]:
    """(structural key, slotted Literal objects) for one stage tree.

    Falls back to the un-slotted ``physical.key()`` (literal values
    inlined, no parameters) when a field defeats the serializer —
    degraded sharing, never wrong sharing."""
    from ..serving.plancache import _Unfingerprintable
    slots: List = []
    try:
        body = _ser_physical(physical, slots)
    except (_Unfingerprintable, RecursionError):
        return physical.key(), []
    return body, slots


def _conf_component(session) -> str:
    """Planning-conf values that can leak into a trace through
    ``getActiveSession`` reads (collect cap, time zone, metrics flag):
    sessions with different values must not share a stage executable."""
    if session is None:
        return ""
    from ..serving.plancache import PLANNING_CONF_ENTRIES
    return ";".join(f"{e.key}={session.conf.get(e)!r}"
                    for e in PLANNING_CONF_ENTRIES)


def param_values(slots) -> Tuple:
    """Runtime argument tuple for one execution of a slotted stage —
    positionally aligned with any fingerprint-equal plan's slots."""
    return tuple(np.asarray(l.value, dtype=l.dtype.np_dtype)
                 for l in slots)


# ---------------------------------------------------------------------------
# run planes at the stage boundary
# ---------------------------------------------------------------------------

def plan_leaves(session, leaves):
    """Decide, per leaf vector, how a lazy run column crosses the jit
    boundary: as a fixed-capacity run PLANE (compressed, two small pytree
    leaves) or materialized dense (counted, exactly as before r20).

    Eligibility is strict compression — the padded plane must be at most
    half the dense capacity (``pad_capacity(n_runs) * 2 <= capacity``) —
    because a plane that barely compresses pays searchsorted overhead in
    every untaught operator for nothing.  Run vectors that fail the test
    bump ``run_plane_overflows`` and fall through to the existing
    ``to_device`` materialization (byte-identical, never wrong).  Called
    BEFORE the stage key is computed: conversion changes
    ``leaf_signature``, so a plane-shaped input can never hit a
    dense-shaped trace or vice versa.  Returns the (possibly rebuilt)
    leaf list; callers on mesh paths must not call this for sharded
    leaves (planes do not slice along rows)."""
    from ..columnar import (ColumnBatch, PlaneColumnVector,
                            bump_plane_overflow, bump_plane_rows,
                            bump_plane_stage, pad_capacity,
                            unmaterialized_runs)
    if session is None or not session.conf.get(C.STAGE_RUN_PLANES):
        return list(leaves)
    checks = None  # resolved lazily, only if a candidate shows up
    out, any_planes = [], False
    for b in leaves:
        vecs = None
        for i, v in enumerate(b.vectors):
            rv = unmaterialized_runs(v)
            if rv is None or rv.valid is not None \
                    or rv.capacity != b.capacity:
                continue
            plane_cap = pad_capacity(len(rv.run_values))
            if plane_cap * 2 > b.capacity:
                bump_plane_overflow()
                continue
            if checks is None:
                from ..analysis import runtime_checks_enabled
                checks = runtime_checks_enabled(session)
            if checks:
                from ..analysis.runtime import verify_run_plane
                verify_run_plane(rv, b.capacity)
            if vecs is None:
                vecs = list(b.vectors)
            vecs[i] = PlaneColumnVector.from_runs(rv, plane_cap,
                                                  device=False)
            bump_plane_rows(b.capacity)
            any_planes = True
        out.append(b if vecs is None
                   else ColumnBatch(b.names, vecs, b.row_valid, b.capacity))
    if any_planes:
        bump_plane_stage()
    return out


# ---------------------------------------------------------------------------
# stage record (the verifier's contract surface)
# ---------------------------------------------------------------------------

class Stage:
    """One compiled stage: the fused physical tree plus the input/output
    schemas at its cut points, recorded AT COMPILE TIME so
    ``analysis.verify_stage_contract`` can re-derive them bottom-up and
    prove fusion changed dispatch structure, never semantics."""

    __slots__ = ("physical", "in_schemas", "out_schema", "key", "n_ops")

    def __init__(self, physical, in_schemas, out_schema, key: str = "",
                 n_ops: int = 0):
        self.physical = physical
        self.in_schemas = list(in_schemas)   # [StructType] in leaf order
        self.out_schema = out_schema         # StructType at the out cut
        self.key = key
        self.n_ops = n_ops or count_ops(physical)


# ---------------------------------------------------------------------------
# the process-local executable cache
# ---------------------------------------------------------------------------

class _CachedStage:
    """Payload of one cache entry: the jitted callable (built ONCE by
    the cache, the only ``jax.jit`` construction site on the execution
    paths — HZ108) plus whatever entry-owned state the builder returned
    (shape-keyed trace metadata, slot literals)."""

    __slots__ = ("fn", "aux", "n_ops", "compile_ms", "hits", "built_at",
                 "notes", "_first", "_lock")

    def __init__(self, fn, aux, n_ops: int):
        self.fn = fn
        self.aux = aux
        self.n_ops = n_ops
        #: trace-time facts of this stage (``tracing.note``), shown with
        #: every statement that dispatches it
        self.notes: Dict[str, List] = {}
        self.compile_ms = 0.0
        self.hits = 0
        self.built_at = time.time()
        self._first = True
        self._lock = threading.Lock()


class StageCache:
    """Thread-safe process-local LRU: stage fingerprint → executable."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _CachedStage]" = \
            collections.OrderedDict()
        # per-fingerprint single-flight build locks (plan cache idiom):
        # N threads missing one stage pay ONE trace+compile, not N
        self._building: Dict[str, threading.Lock] = {}
        self.hits = 0
        self.misses = 0
        self.builds = 0
        self.dispatches = 0
        self.compile_ms = 0.0
        self.total_ops = 0

    # -- lookup / build ------------------------------------------------
    def get_or_build(self, key: str, make_fn: Callable[[], Tuple],
                     n_ops: int = 1, session=None) -> _CachedStage:
        """The single integration surface for every execution path.

        ``make_fn`` returns ``(traceable, aux)`` — the pure step
        function to compile and any entry-owned metadata; the cache
        jits it, so call sites never construct jit objects themselves
        (a fresh ``jax.jit`` per execution re-traces the identical program)."""
        if session is not None:
            try:
                self.max_entries = int(
                    session.conf.get(C.STAGE_CACHE_MAX_ENTRIES))
            except Exception:
                pass
        with tracing.span("stage.lookup", hit=True, n_ops=n_ops,
                          key=hash(key) & 0xFFFFFFFF) as sp:
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    entry.hits += 1
                    return entry
                build_lock = self._building.setdefault(key, threading.Lock())
            sp.attrs["hit"] = False
        with build_lock:
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:      # lost the build race: a hit
                    self._entries.move_to_end(key)
                    self.hits += 1
                    entry.hits += 1
                    return entry
            import jax
            with tracing.span("stage.build", n_ops=n_ops):
                fn, aux = make_fn()
                entry = _CachedStage(jax.jit(fn), aux, n_ops)
            with self._lock:
                self.misses += 1
                self.builds += 1
                self.total_ops += n_ops
                self._entries[key] = entry
                while len(self._entries) > max(self.max_entries, 1):
                    self._entries.popitem(last=False)
                self._building.pop(key, None)
            return entry

    def dispatch(self, entry: _CachedStage, *args):
        """Invoke one compiled stage, counting the dispatch; the first
        invocation per entry is timed as the stage's trace+compile cost
        (jax traces lazily at first call)."""
        with self._lock:
            self.dispatches += 1
        # a jitted stage traces at its first call and at each new input
        # shape: what the trace notes is the stage's, whichever call it is
        with tracing.collecting(entry.notes):
            if entry._first:
                with entry._lock:
                    if entry._first:
                        t0 = time.perf_counter()
                        with tracing.span("stage.first_call",
                                          n_ops=entry.n_ops):
                            out = entry.fn(*args)
                        ms = (time.perf_counter() - t0) * 1000.0
                        entry.compile_ms = round(ms, 2)
                        with self._lock:
                            self.compile_ms += ms
                        entry._first = False
                        return out
            with tracing.span("stage.dispatch", n_ops=entry.n_ops):
                return entry.fn(*args)

    # -- introspection -------------------------------------------------
    def peek(self, key: str) -> Optional[_CachedStage]:
        """The entry under ``key`` or None; counts nothing, moves nothing."""
        with self._lock:
            return self._entries.get(key)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            n = len(self._entries)
            return {
                "hits": self.hits, "misses": self.misses,
                "builds": self.builds, "dispatches": self.dispatches,
                "compile_ms": round(self.compile_ms, 2),
                "entries": n, "max_entries": self.max_entries,
                "stages_fused": self.builds,
                "ops_per_stage": round(
                    self.total_ops / self.builds, 2) if self.builds else 0.0,
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._building.clear()
            self.hits = self.misses = self.builds = 0
            self.dispatches = 0
            self.compile_ms = 0.0
            self.total_ops = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: THE process-local cache (one per worker process by construction —
#: subprocess reducers each get their own on first import)
_CACHE: Optional[StageCache] = None
_CACHE_LOCK = threading.Lock()


def stage_cache(session=None) -> StageCache:
    global _CACHE
    if _CACHE is None:
        with _CACHE_LOCK:
            if _CACHE is None:
                _CACHE = StageCache()
    return _CACHE


def metrics_source() -> Dict[str, Callable]:
    """Gauges for the 'compile' metrics Source (ISSUE 11 observability):
    resolved per read so a source registered before the first stage
    compile still reports live numbers."""
    def g(key, default=0):
        def read():
            return stage_cache().stats().get(key, default)
        return read
    from .. import columnar as _col
    return {
        "stage_compile_ms": g("compile_ms", 0.0),
        "stage_cache_hits": g("hits"),
        "stage_cache_misses": g("misses"),
        "stage_cache_entries": g("entries"),
        "stage_dispatches": g("dispatches"),
        "stages_fused": g("stages_fused"),
        "ops_per_stage": g("ops_per_stage", 0.0),
        # run planes at the stage boundary (ISSUE 20): how often the
        # jit lane ran compressed, and both fallback counters
        "run_plane_stages": _col.run_plane_stages,
        "run_plane_rows": _col.run_plane_rows,
        "run_plane_overflows": _col.run_plane_overflows,
        "run_plane_expansions": _col.run_plane_expansions,
    }
