"""Share of the slice's device-idle time that lies under a program span
other than the roots: the idle intervals of the busiest device, each piece
given to the innermost (shortest) program span over it, whichever thread
recorded it.  What is left is the roots' own time (``statement``,
``http.statement``) and the time outside every statement."""

from benchmark.lib import trace as TR
from benchmark.readers import program_spans as PS


def innermost(spans):
    """Disjoint [start, end, name] pieces: over each, the shortest of
    ``spans`` ((name, start, dur)) that covers it."""
    cuts = sorted({t for _n, s, d in spans for t in (s, s + d)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        over = [(d, n) for n, s, d in spans if s <= mid < s + d]
        if over:
            out.append([a, b, min(over)[1]])
    return out


def read(ctx, roots=("statement", "http.statement"), **_):
    ring = PS.ring_of(ctx)
    if ring is None:
        return None
    pairs, const, _w = PS.slice_statements(ctx, ring)
    if not pairs:
        return None
    t = ctx.trace
    idle = TR.gaps(t.busy[t.busiest()], t.lo, t.hi)
    pieces = innermost([(s[0], s[1] - const, s[2])
                        for _b, ss in pairs for s in ss])
    named = 0.0
    at = 0
    for a, b in idle:
        while at < len(pieces) and pieces[at][1] <= a:
            at += 1
        k = at
        while k < len(pieces) and pieces[k][0] < b:
            if pieces[k][2] not in roots:
                named += min(b, pieces[k][1]) - max(a, pieces[k][0])
            k += 1
    total = TR.total(idle)
    return 100.0 * named / total if total else 0.0
