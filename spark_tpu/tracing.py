"""Spans, scopes and counters of the program itself.

One module beside the listener bus and ``GET /status``: no second system,
no conf key, no exporter, no switch.  "Off" is no profiler attached; the
ring's cost is in every run.

* ``span(name, **attrs)`` -- a context manager around HOST work at a layer
  boundary.  While a profiler is attached it also opens
  ``jax.profiler.TraceAnnotation("sql:" + name)``, so the span is in the
  trace on the profiler's own clock.  Every span is appended to ONE bounded
  ring as ``Span(name, start_ns, dur_ns, statement_id, parent, thread,
  attrs, profiled)``; ``start_ns`` is ``time.time_ns()``, and a trace's
  event times are relative to the ``profile_start_time`` stat (epoch ns)
  of its ``Task Environment`` plane, so the two clocks are one up to that
  constant.
* ``scope(name)`` -- ``jax.named_scope`` for code that runs under ``jit``:
  trace time only, nothing per dispatch, metadata only (a scope never
  enters ``PhysicalPlan.key()``, a stage fingerprint or any cache key).
* ``count(name, n)`` -- integer counters at the same boundaries.
* ``note(key, value)`` -- a trace-time fact (which lowering a kernel took),
  kept with the stage being built and shown with every statement that
  dispatches it.

Readers: ``spans()``, ``summary()``, ``last_statement()``,
``statement_phases()``; ``reset()`` for tests.  A span's SELF time is its
duration less what its children on the same thread cover.

``device_time_by_scope(xplane_path)`` and ``python -m spark_tpu.tracing
<trace dir>`` reduce a profiler trace with nothing but
``jax.profiler.ProfileData``: device self seconds by scope on the busiest
device, host seconds by ``sql:`` span -- the operator's EXPLAIN ANALYZE.

The span, scope and counter names are fixed; PERF.md section 3 lists each
with the metric it is for.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

__all__ = [
    "Span", "span", "scope", "count", "fresh_jit", "replan", "note",
    "statement",
    "adopt", "current_statement", "collecting", "spans", "summary",
    "last_statement", "statement_phases", "reset", "device_time_by_scope",
    "KERNEL_SCOPES",
]

RING_SIZE = 2 ** 16
#: statements whose phases and notes are kept (the ring keeps the spans)
STATEMENTS_KEPT = 1024
SPAN_PREFIX = "sql:"

#: the kernel scopes by their fixed names (operators are
#: ``<PhysicalPlan class>#<op_id>``; ``argsort.pass<i>`` is one chained pass)
KERNEL_SCOPES = frozenset({
    "stage.step", "stage.merge",
    "join.keys", "join.keys.remap", "join.build_sort", "join.probe",
    "join.dense",
    "join.expand", "join.gather", "join.unique",
    "agg.onehot", "agg.mxu", "agg.mxu.limbs", "agg.sort", "agg.sort.argsort",
    "agg.sort.permute", "agg.sort.segment", "pallas_agg",
    "sort_batch", "argsort", "take_batch", "compact", "partition_bucket",
    "exchange.pack", "exchange.all_to_all", "exchange.all_gather",
    "exchange.psum",
    "window.sort", "window.segments", "window.rank", "window.agg",
    "grouping.rollup",
})
#: stage scopes that say what their whole program is for: kept in front of
#: the operator a device op came from (``named_scope_of``)
STAGE_NAMES = frozenset({"grouping.rollup"})
#: the zero-length spans ``device_time_by_scope`` tallies: span -> (the
#: key of its tally, the attributes it is tallied by; None: all of them)
TALLIED = {"join.path": ("join_paths", None),
           "agg.scan": ("agg_scans", None),
           "grouping.arm": ("grouping_arms", ("from_finer",)),
           "window": ("windows", ("funcs", "partition_keys", "order_keys"))}


class Span(NamedTuple):
    name: str
    start_ns: int
    dur_ns: int
    statement_id: int        # 0: outside any statement
    parent: Optional[str]    # the enclosing span of that thread
    thread: int
    attrs: Dict[str, Any]
    profiled: bool           # a profiler was attached at the span's start


class _Statement:
    __slots__ = ("id", "phases", "notes", "n_spans")

    def __init__(self, sid: int):
        self.id = sid
        self.phases: Dict[str, int] = {}     # span name -> self ns
        self.notes: Dict[str, List] = {}
        self.n_spans = 0

    def phases_ms(self) -> Dict[str, float]:
        return {n: round(ns / 1e6, 3) for n, ns in self.phases.items()}


_ring: "collections.deque[tuple]" = collections.deque(maxlen=RING_SIZE)
_statements: "collections.OrderedDict[int, _Statement]" = \
    collections.OrderedDict()
_totals: Dict[str, List[int]] = {}           # name -> [count, total, max] ns
_counts: Dict[str, int] = {}
_ids = itertools.count(1)
_lock = threading.Lock()
_tls = threading.local()


# -- statements ---------------------------------------------------------------

def current_statement() -> int:
    """The id of the statement this thread works for (0: none)."""
    return getattr(_tls, "sid", 0)


@contextlib.contextmanager
def adopt(sid: int):
    """This thread works for statement ``sid`` (0: for none): a worker
    thread takes over the statement of the thread that started it."""
    prev = current_statement()
    _tls.sid = sid
    try:
        yield sid
    finally:
        _tls.sid = prev


def statement(sid: int = 0):
    """``adopt`` ``sid``; with no ``sid`` keep the thread's current statement
    or allot the next id of the process-wide counter.  Called where a
    statement first enters the program (``session.sql``, the server's
    ``_run_sql``, else ``QueryExecution.execute``)."""
    sid = sid or current_statement()
    if not sid:
        sid = next(_ids)
        with _lock:
            _statements[sid] = _Statement(sid)
            while len(_statements) > STATEMENTS_KEPT:
                _statements.popitem(last=False)
    return adopt(sid)


# -- spans --------------------------------------------------------------------

class span:
    """``with span("scan.read", rows=n) as sp:`` -- ``sp.attrs`` may be
    filled in before the exit (the ring keeps them; the trace annotation
    has what was known at the start)."""

    __slots__ = ("name", "attrs", "_ann", "_t0", "_children_ns")

    def __init__(self, name: str, /, **attrs):    # ``name=`` is an attr
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        try:
            _tls.stack.append(self)
        except AttributeError:           # the thread's first span
            _tls.stack, _tls.sid = [self], current_statement()
        self._children_ns = 0
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name, **self.attrs)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        dur = time.time_ns() - t0
        ann = self._ann
        if ann is not None:
            ann.__exit__(*exc)
        tls = _tls
        stack = tls.stack
        stack.pop()
        if stack:
            parent = stack[-1]
            parent._children_ns += dur
            parent = parent.name
        else:
            parent = None
        sid, name = tls.sid, self.name
        _ring.append((name, t0, dur, sid, parent, threading.get_ident(),
                      self.attrs, ann is not None))
        with _lock:
            tot = _totals.get(name)
            if tot is None:
                _totals[name] = [1, dur, dur]
            else:
                tot[0] += 1
                tot[1] += dur
                if dur > tot[2]:
                    tot[2] = dur
            if sid:
                st = _statements.get(sid)
                if st is not None:
                    st.n_spans += 1
                    st.phases[name] = st.phases.get(name, 0) + dur \
                        - self._children_ns
        return False


def scope(name: str):
    """A named scope for traced (jitted) code: an HLO op's ``op_name``
    reads ``.../PJoin#4/join.probe/...``."""
    return jax.named_scope(name)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def fresh_jit(site: str) -> span:
    """Around every ``jax.jit(...)`` object built outside the stage cache
    on the statement path, and its first call (trace and compile): the
    span and the counter ``jit.fresh``, with the call site."""
    count("jit.fresh")
    return span("jit.fresh", site=site)


def replan(attempt: int, ratio: float, factors):
    """Around every attempt AFTER the first of an adaptive capacity loop
    (``QueryExecution._execute_inner``, ``DistributedExecution.execute``):
    the span ``join.replan`` with the attempt's number, the overflow ratio
    (lost rows over capacity) that asked for it and the capacities chosen.
    A statement whose first attempt fits records none."""
    if attempt == 0:
        return contextlib.nullcontext()
    return span("join.replan", attempt=attempt, ratio=round(ratio, 4),
                factors=factors)


# -- notes --------------------------------------------------------------------

def note(key: str, value) -> None:
    """A trace-time fact.  Inside ``collecting(notes)`` (a stage being
    traced) it is kept with that stage; else with the current statement."""
    sink = getattr(_tls, "notes", None)
    if sink is None:
        st = _statements.get(getattr(_tls, "sid", 0))
        if st is None:
            return
        sink = st.notes
    with _lock:
        values = sink.setdefault(key, [])
        if value not in values:
            values.append(value)


@contextlib.contextmanager
def collecting(notes: Dict[str, List]):
    """Around a call of a compiled stage: what its trace notes goes into
    ``notes`` (the stage's own), and the current statement shows it."""
    prev = getattr(_tls, "notes", None)
    _tls.notes = notes
    try:
        yield
    finally:
        _tls.notes = prev
        st = _statements.get(getattr(_tls, "sid", 0)) if notes else None
        if st is not None:
            with _lock:
                for key, values in notes.items():
                    have = st.notes.setdefault(key, [])
                    have.extend(v for v in values if v not in have)


# -- readers ------------------------------------------------------------------

def spans(lo_ns: Optional[int] = None, hi_ns: Optional[int] = None
          ) -> List[Span]:
    """The ring's spans that overlap [lo_ns, hi_ns), oldest first."""
    return [Span._make(s) for s in list(_ring)
            if (hi_ns is None or s[1] < hi_ns)
            and (lo_ns is None or s[1] + s[2] > lo_ns)]


def summary() -> dict:
    """Per span name the count, total and max ms since the process started
    (or ``reset``), and the counters: ``GET /status`` ``trace``."""
    with _lock:
        return {
            "spans": {n: {"count": c, "total_ms": round(t / 1e6, 3),
                          "max_ms": round(m / 1e6, 3)}
                      for n, (c, t, m) in sorted(_totals.items())},
            "counts": dict(sorted(_counts.items())),
        }


def statement_phases(sid: int) -> Dict[str, float]:
    """Span name -> self ms of the statement so far (``SQLExecutionEnd``
    ``phases``); empty for a statement no longer kept."""
    with _lock:
        st = _statements.get(sid)
        return {} if st is None else st.phases_ms()


def last_statement() -> Optional[dict]:
    """The statement allotted last: its id, phase self times (ms), span
    count and notes (``notes["agg_lowering"]``: the keyed-aggregate
    lowerings its stages took, e.g. ``["sort"]``)."""
    with _lock:
        if not _statements:
            return None
        st = next(reversed(_statements.values()))
        return {"id": st.id, "spans": st.n_spans, "phases": st.phases_ms(),
                "notes": {k: list(v) for k, v in st.notes.items()}}


def reset() -> None:
    """Forget every span, statement, total and counter (tests)."""
    with _lock:
        _ring.clear()
        _statements.clear()
        _totals.clear()
        _counts.clear()


# -- the program's own reading of a trace -------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPERATOR = re.compile(r"^[A-Z_]\w*#\d+$")
_ARGSORT_PASS = re.compile(r"^argsort\.pass\d+$")
#: the stat of an ``XLA Ops`` event's METADATA that carries the HLO
#: ``op_name`` on a TPU (a fusion carries its root's), e.g. ``jit(run)/
#: stage.step/PSort#2/PAggregate#5/agg.sort/agg.sort.permute/gather:``.
#: ``ProfileData`` shows an event's own stats only, so ``_op_names`` reads
#: the metadata straight from the file.
_SCOPE_STAT = "tf_op"


def named_scope_of(op_name: str) -> Optional[str]:
    """``PJoin#4/join.probe`` from ``jit(run)/stage.step/PSort#1/PJoin#4/
    join.probe/gather:``: the innermost operator and every kernel scope
    this module names below it, after the program's ``STAGE_NAMES`` scope
    where it has one (``grouping.rollup/PAggregate#1/agg.sort``); None
    where the path holds neither."""
    named: List[str] = []
    for part in op_name.split("/"):
        if _OPERATOR.match(part):
            named = [p for p in named if p in STAGE_NAMES] + [part]
        elif part in KERNEL_SCOPES or _ARGSORT_PASS.match(part):
            named.append(part)
    return "/".join(named) or None


def _varint(buf: bytes, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``: an
    int for a varint, (start, end) for a length-delimited field."""
    while lo < hi:
        key, lo = _varint(buf, lo)
        wire = key & 7
        if wire == 0:
            value, lo = _varint(buf, lo)
        elif wire == 2:
            n, lo = _varint(buf, lo)
            value, lo = (lo, lo + n), lo + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, lo = (lo, lo + n), lo + n
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield key >> 3, value


def _op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: its metadata's ``tf_op``}} of an
    ``.xplane.pb``, by the wire format (tsl ``xplane.proto``: XSpace.planes
    1; XPlane name 2, event_metadata 4, stat_metadata 5; XEventMetadata name
    2, stats 5; XStat metadata_id 1, str_value 5, ref_value 7); a plane's
    lines are skipped by their length."""
    with open(xplane_path, "rb") as fh:
        buf = fh.read()

    def text(span_):
        return buf[span_[0]:span_[1]].decode("utf-8", "replace")

    def entry(span_):                       # a map entry: key 1, value 2
        return dict(_fields(buf, *span_)).get(2)

    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = text(v)
            elif f == 4:
                events.append(entry(v))
            elif f == 5:
                meta = dict(_fields(buf, *entry(v)))
                stat_names[meta.get(1, 0)] = text(meta[2]) if 2 in meta \
                    else ""
        if not _DEVICE_PLANE.match(name):
            continue
        names = out.setdefault(name, {})
        for ev in events:
            ev_name, op = "", ""
            for f, v in _fields(buf, *ev):
                if f == 2:
                    ev_name = text(v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == _SCOPE_STAT:
                        op = text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            names[ev_name] = op
    return out


def _self_times(events):
    """[(event, self_ns)]: an event's duration less what the events nested
    inside it cover, so one line's self times partition its busy time."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out, stack = [], []                    # stack of [end, payload, self]
    for s, e, payload in evs:
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, payload, e - s])
    out.extend((top[1], top[2]) for top in stack)
    return out


def device_time_by_scope(xplane_path: str, top: int = 10) -> dict:
    """Reduce one ``.xplane.pb``:

    ``{"device", "busy_s", "unnamed_s", "unnamed_pct", "by_scope": [[scope,
    seconds], ...], "top_ops": [[instruction, scope, seconds], ...] (``top``),
    "unnamed_top": [[instruction, seconds], ...], "host_spans": {name:
    [count, seconds]}, "join_paths": [[{unique, dense, out_cap, probe_cap,
    string}, count], ...] (the ``join.path`` spans by their attributes),
    "agg_scans": [[{op, rounds}, count], ...] (the ``agg.scan`` spans: the
    rounds a sort aggregate's segmented scan took), "grouping_arms":
    [[{from_finer}, count], ...] (the ``grouping.arm`` spans: sets read
    from a finer set or from the statement's rows), "windows": [[{funcs,
    partition_keys, order_keys}, count], ...] (the ``window`` spans),
    "profile_start_ns", "annotations": [[name, start_ns (epoch), dur_ns],
    ...]}``

    Device seconds are self times on the busiest device's ``XLA Ops``
    line; ``unnamed`` is the part under no scope this module names."""
    data = jax.profiler.ProfileData.from_file(xplane_path)
    op_names = _op_names(xplane_path)
    devices, host, annotations, start_ns = {}, {}, [], None
    records = {name: collections.Counter() for name in TALLIED}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time", start_ns)
        elif _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                names = op_names.get(plane.name, {})
                devices[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns,
                     (e.name, names.get(e.name, "")))
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        name = e.name[len(SPAN_PREFIX):]
                        got = host.setdefault(name, [0, 0.0])
                        got[0] += 1
                        got[1] += e.duration_ns / 1e9
                        annotations.append([name, e.start_ns, e.duration_ns])
                        if name in TALLIED:
                            stats, keep = dict(e.stats), TALLIED[name][1]
                            if keep is not None:
                                stats = {k: stats.get(k) for k in keep}
                            records[name][json.dumps(
                                stats, sort_keys=True, default=str)] += 1
    if start_ns is not None:
        for a in annotations:
            a[1] = int(a[1] + start_ns)
    out = {"device": None, "busy_s": 0.0, "unnamed_s": 0.0,
           "unnamed_pct": None, "by_scope": [], "top_ops": [],
           "unnamed_top": [],
           "host_spans": host, "profile_start_ns": start_ns,
           "annotations": annotations}
    for name, got in records.items():
        out[TALLIED[name][0]] = [[json.loads(a), n]
                                 for a, n in got.most_common()]
    if not devices:
        return out
    selfs = {d: _self_times(evs) for d, evs in devices.items()}
    dev = max(selfs, key=lambda d: sum(ns for _p, ns in selfs[d]))
    by, unnamed, ops = {}, {}, {}
    for (name, path), ns in selfs[dev]:
        scope_name = named_scope_of(path)
        head = name.split(" = ")[0].lstrip("%")
        ops[head, scope_name] = ops.get((head, scope_name), 0) + ns
        if scope_name is None:
            unnamed[head] = unnamed.get(head, 0) + ns
        else:
            by[scope_name] = by.get(scope_name, 0) + ns
    busy = sum(by.values()) + sum(unnamed.values())
    out.update(
        device=dev, busy_s=busy / 1e9, unnamed_s=sum(unnamed.values()) / 1e9,
        unnamed_pct=100.0 * sum(unnamed.values()) / busy if busy else None,
        by_scope=[[n, ns / 1e9] for n, ns in
                  sorted(by.items(), key=lambda kv: -kv[1])],
        top_ops=[[head, sc, ns / 1e9] for (head, sc), ns in
                 sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        unnamed_top=[[n, ns / 1e9] for n, ns in
                     sorted(unnamed.items(), key=lambda kv: -kv[1])[:10]])
    return out


def clock_gaps_ms(annotations, ring: List[Span]) -> List[float]:
    """For each ``sql:`` annotation of a trace (``device_time_by_scope``'s
    ``annotations``, already shifted by ``profile_start_time``) the distance
    in ms to the nearest ring record of the same name: the shared-clock
    check."""
    by_name: Dict[str, List[int]] = {}
    for s in ring:
        by_name.setdefault(s.name, []).append(s.start_ns)
    return [min((abs(start - t) for t in by_name.get(name, ())),
                default=float("inf")) / 1e6
            for name, start, _dur in annotations]


def _xplanes(path: str) -> List[str]:
    return [path] if os.path.isfile(path) else sorted(
        os.path.join(d, f) for d, _s, fs in os.walk(path)
        for f in fs if f.endswith(".xplane.pb"))


def check_clock(trace_dir: str, n: int = 32) -> dict:
    """Trace ``n`` nested spans and one small device program in THIS
    process, then read the trace back: every ``sql:`` annotation, with the
    trace's ``profile_start_time`` added, against its ring record.  The
    shared-clock check, for the backend JAX has here."""
    import jax.numpy as jnp
    jax.profiler.start_trace(trace_dir)
    try:
        with statement():
            for i in range(n):
                with span("clock.outer", i=i), span("clock.inner"):
                    jnp.arange(4096.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    read = device_time_by_scope(_xplanes(trace_dir)[-1])
    gaps = clock_gaps_ms(read["annotations"], spans())
    return {"platform": jax.default_backend(),
            "annotations": len(gaps), "expected": 2 * n,
            "max_gap_ms": max(gaps, default=None),
            "profile_start_ns": read["profile_start_ns"]}


def _main(argv) -> int:
    if len(argv) == 2 and argv[0] == "--check-clock":
        got = check_clock(argv[1])
        print(json.dumps(got))
        return 0 if got["annotations"] == got["expected"] \
            and got["max_gap_ms"] < 1.0 else 1
    if len(argv) != 1:
        print("usage: python -m spark_tpu.tracing <trace dir or .xplane.pb>"
              " | --check-clock <new trace dir>")
        return 2
    paths = _xplanes(argv[0])
    if not paths:
        print(f"no .xplane.pb under {argv[0]}")
        return 1
    for path in paths:
        r = device_time_by_scope(path)
        print(f"# {path}")
        print(f"device {r['device']}  busy {r['busy_s']:.3f} s  under no "
              f"named scope {r['unnamed_s']:.3f} s "
              f"({r['unnamed_pct'] or 0.0:.2f}%)")
        for name, s in r["by_scope"][:30]:
            print(f"  {s:10.4f} s  {name}")
        print("top device ops:")
        for head, sc, s in r["top_ops"]:
            print(f"  {s:10.4f} s  {head}  {sc or '(unnamed)'}")
        for name, s in r["unnamed_top"][:5]:
            print(f"  {s:10.4f} s  (unnamed) {name}")
        print("host spans (count, seconds):")
        for name, (n, s) in sorted(r["host_spans"].items(),
                                   key=lambda kv: -kv[1][1])[:24]:
            print(f"  {n:6d} {s:10.4f} s  sql:{name}")
        for name, (key, _attrs) in TALLIED.items():
            for attrs, n in r[key]:
                print(f"  {n:6d} sql:{name}  {json.dumps(attrs)}")
        print(json.dumps({"profile_start_ns": r["profile_start_ns"],
                          "annotations": len(r["annotations"])}))
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
