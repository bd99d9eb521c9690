"""The program's own spans (``spark_tpu.tracing``) over the traced slice:
self milliseconds of the named spans per statement or per batch, a count
per statement, or the share of one span's records whose attribute is true.

The harness's trace reduction keeps only ``bench:`` spans and the trace is
deleted before the readers run, so this reads the program's RING and puts
it on the trace's clock:

* a statement's extent is the earliest start to the latest end of the
  spans under its id; the slice's statements are those whose first span
  was recorded with a profiler attached, in order, and they must number
  the slice's ``bench:statement`` spans (else this raises, as it does
  where the ring has already dropped them);
* the ring's clock is ``time.time_ns()`` and the trace's is relative to
  its start: each extent lies inside its ``bench:statement``, so each pair
  bounds the constant between the clocks from both sides.  The bounds
  must intersect (else this raises); the midpoint is used.

A program without ``spark_tpu.tracing`` has nothing to read: None, and the
line leaves the metric out.  Where statements were traced and no such
span ran the value is 0.0.
"""

import sys


def ring_of(ctx):
    """The program's ring as Span-like tuples (name, start_ns, dur_ns,
    statement_id, parent, thread, attrs, profiled), or None where the
    program has none.  A test hands a recording in as ``ctx.ring``."""
    recorded = getattr(ctx, "ring", None)
    if recorded is not None:
        return [tuple(s) for s in recorded]
    try:
        from spark_tpu import tracing
    except ImportError:
        return None
    return [tuple(s) for s in tracing.spans()]


def slice_statements(ctx, ring):
    """[(bench:statement span, [ring spans of that statement])] in order,
    and the clock constant (ring clock minus trace clock) with the width
    of the interval the pairs leave it, in ns.  Worked out once a run."""
    if getattr(ctx, "_program_slice", None) is None:
        ctx._program_slice = _slice_statements(ctx, ring)
    return ctx._program_slice


def _slice_statements(ctx, ring):
    by_id = {}
    for s in ring:
        if s[3]:
            by_id.setdefault(s[3], []).append(s)
    traced = []
    for sid, spans in by_id.items():
        first = min(spans, key=lambda s: s[1])
        if first[7]:
            traced.append((first[1], sid))
    traced = [by_id[sid] for _t, sid in sorted(traced)]
    bench = sorted(ctx.trace.statement_spans(), key=lambda s: s[1])
    if len(traced) != len(bench):
        raise ValueError(
            f"program_spans: the slice holds {len(bench)} bench:statement "
            f"spans and the ring {len(traced)} profiled statements (has the "
            "ring dropped them?)")
    if not traced:
        return [], 0, 0
    # whole nanoseconds: the ring's epoch times are past what a float holds
    los, his = [], []
    for b, spans in zip(bench, traced):
        e0 = min(s[1] for s in spans)
        e1 = max(s[1] + s[2] for s in spans)
        los.append(e1 - int(b[1] + b[2]))
        his.append(e0 - int(b[1]))
    lo, hi = max(los), min(his)
    if lo > hi:
        raise ValueError(
            f"program_spans: the statements' extents do not fit their "
            f"bench:statement spans under one clock constant (bounds "
            f"{lo} > {hi} ns)")
    print(f"program_spans: clock constant within {(hi - lo) / 1e6:.3f} ms "
          f"over {len(traced)} statements", file=sys.stderr)
    return list(zip(bench, traced)), (lo + hi) // 2, hi - lo


def self_ns(spans):
    """[(span, self ns)]: a span's duration less what the spans nested
    inside it on the same thread cover."""
    out = []
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s[5], []).append(s)
    for evs in by_thread.values():
        evs.sort(key=lambda s: (s[1], -s[2]))
        stack = []                    # [end, span, self]
        for s in evs:
            while stack and stack[-1][0] <= s[1]:
                top = stack.pop()
                out.append((top[1], top[2]))
            if stack:
                stack[-1][2] -= min(s[1] + s[2], stack[-1][0]) - s[1]
            stack.append([s[1] + s[2], s, s[2]])
        out.extend((top[1], top[2]) for top in stack)
    return out


def read(ctx, spans=None, per="statement", count=None, ratio=None, **_):
    """``spans``: self ms of those names, per statement or (``per`` a span
    name, e.g. ``scan.decode``) per record of that span.  ``count``:
    records of those names per statement.  ``ratio``: ``{"span", "attr"}``
    -> percent of that span's records whose attribute is true."""
    ring = ring_of(ctx)
    if ring is None:
        return None
    pairs, _c, _w = slice_statements(ctx, ring)
    if not pairs:
        return None
    mine = [s for _b, ss in pairs for s in ss]
    if ratio is not None:
        of = [s for s in mine if s[0] == ratio["span"]]
        return 100.0 * sum(1 for s in of if s[6].get(ratio["attr"])) \
            / len(of) if of else 0.0
    if count is not None:
        return sum(1 for s in mine if s[0] in count) / len(pairs)
    total = sum(ns for s, ns in self_ns(mine) if s[0] in spans)
    over = len(pairs) if per == "statement" \
        else sum(1 for s in mine if s[0] == per)
    return total / 1e6 / over if over else 0.0
