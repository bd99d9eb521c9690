"""From a profiler trace to numbers: the reduction every PR uses.

``load(path)`` turns an ``.xplane.pb`` into the plain form the rest works
on (and the tests keep a small recording of):

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "spans":   [[name, start_ns, dur_ns, {stat: value}], ...]}

``devices`` holds the events of each device plane's ``XLA Ops`` line, by
the names XLA prints; ``spans`` the harness's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``bench:``), which
the profiler writes on the same clock.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
#: the profiler's own host events while the TPU compiler works
COMPILE_PREFIX = "XLA::TPU"
COMPILE_SPAN = "host:xla_compile"
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def load(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans, compiling = {}, [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      {k: v for k, v in e.stats}])
                    elif e.name.startswith(COMPILE_PREFIX):
                        compiling.append((float(e.start_ns),
                                          float(e.start_ns + e.duration_ns)))
    spans += [[COMPILE_SPAN, s, e - s, {}] for s, e in union(compiling)]
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Merged, sorted [start, end] list of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def gaps(merged, lo, hi):
    """The idle intervals of [lo, hi] that ``merged`` leaves."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def op_parts(text: str):
    """(instance, class, shape) of an ``XLA Ops`` event, whose name is the
    HLO instruction as XLA prints it, e.g.

        %fusion.239 = u32[1048576]{0:T(1024)} fusion(...), kind=kCustom, ...

    -> ("fusion.239", "fusion:kCustom", "u32[1048576]").  The class is the
    opcode, with a fusion's kind or a custom call's target."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), text.lstrip("%"), ""
    m = _OPCODE.search(" " + rest)
    cls = m.group(1) if m else "?"
    if cls == "fusion":
        k = _KIND.search(rest)
        cls += ":" + k.group(1) if k else ""
    elif cls == "custom-call":
        t = _TARGET.search(rest)
        cls += ":" + t.group(1) if t else ""
    shape = _SHAPE.match(rest)
    return head.strip().lstrip("%"), cls, shape.group(1) if shape else ""


def op_class(text: str) -> str:
    return op_parts(text)[1]


def self_times(events):
    """[(name, self_ns)] for one line's events: an event's duration minus
    the part its children (events nested inside it) cover, so that the
    self times of a line partition its busy time."""
    evs = sorted(([s, s + d, n] for n, s, d in events),
                 key=lambda x: (x[0], -x[1]))
    out, stack = [], []       # stack of [end, name, self]
    for s, e, n in evs:
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    while stack:
        top = stack.pop()
        out.append((top[1], top[2]))
    return out


# -- the reduction -----------------------------------------------------------

class Reduced:
    """A trace cut to the traced slice (the ``bench:slice`` span)."""

    def __init__(self, trace: dict):
        self.spans = [tuple(s) for s in trace["spans"]]
        slices = [s for s in self.spans if s[0] == SPAN_PREFIX + "slice"]
        if len(slices) != 1:
            raise ValueError(f"the trace holds {len(slices)} bench:slice "
                             "spans, not one")
        self.lo = slices[0][1]
        self.hi = self.lo + slices[0][2]
        self.events = {}
        self.busy = {}
        for dev, evs in sorted(trace["devices"].items()):
            inside = [(n, s, d) for n, s, d in evs
                      if s + d > self.lo and s < self.hi]
            self.events[dev] = inside
            self.busy[dev] = clip(union((s, s + d) for _n, s, d in inside),
                                  self.lo, self.hi)
        if not self.busy or not any(self.busy.values()):
            raise ValueError("no device operation ran inside the traced "
                             "slice (are the spans on the device's clock?)")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds a device operation ran, averaged over the devices."""
        return sum(total(b) for b in self.busy.values()) \
            / len(self.busy) / 1e9

    def busiest(self) -> str:
        return max(self.busy, key=lambda d: total(self.busy[d]))

    def idle_pct(self) -> float:
        """Idle share of the slice on the least idle device."""
        return 100.0 * (1.0 - total(self.busy[self.busiest()])
                        / (self.hi - self.lo))

    def class_share_pct(self, classes) -> float | None:
        """Device time of the operations whose opcode is in ``classes`` over
        the device's busy time, on the busiest device; None where no such
        operation ran."""
        dev = self.busiest()
        hit = union((s, s + d) for n, s, d in self.events[dev]
                    if op_class(n).split(":")[0] in classes)
        hit = clip(hit, self.lo, self.hi)
        if not hit:
            return None
        return 100.0 * total(hit) / total(self.busy[dev])

    def named(self, name: str, **stats):
        """The harness spans called ``bench:<name>`` whose stats match."""
        return [s for s in self.spans if s[0] == SPAN_PREFIX + name
                and all(s[3].get(k) == v for k, v in stats.items())]

    def statement_spans(self, statements=None):
        """The ``bench:statement`` spans (of the named statements only)."""
        return [s for s in self.named("statement")
                if statements is None or s[3].get("stmt") in statements]

    def device_s_in(self, span) -> float:
        """Busy seconds of the busiest device inside one span."""
        lo, hi = span[1], span[1] + span[2]
        return total(clip(self.busy[self.busiest()], lo, hi)) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        dev = self.busiest()
        by = {}
        for name, ns in self_times(self.events[dev]):
            inst, cls, shape = op_parts(name)
            label = f"{cls} {inst} {shape}".strip()
            by[label] = by.get(label, 0.0) + ns
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        # the idle time, by the innermost host span over each piece of it
        # (a gap is cut where a span starts or ends)
        host = [s for s in self.spans if s[0] != SPAN_PREFIX + "slice"]
        cuts = sorted({t for h in host for t in (h[1], h[1] + h[2])})
        idle = {}
        for s, e in gaps(self.busy[dev], self.lo, self.hi):
            edges = [s] + [t for t in cuts if s < t < e] + [e]
            for a, b in zip(edges, edges[1:]):
                mid = (a + b) / 2
                cover = [h for h in host if h[1] <= mid < h[1] + h[2]]
                what = min(cover, key=lambda h: h[2])[0].split(":", 1)[1] \
                    if cover else "between_statements"
                idle[what] = idle.get(what, 0.0) + (b - a)
        idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[n, ns / 1e9] for n, ns in idle]}
