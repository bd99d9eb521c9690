"""The two readers of the program's own spans, on a small recorded ring
(``ring_small.json``) laid over the recorded trace (``trace_small.json``):
one statement over HTTP streamed in three batches with its scan on a
prefetch thread, one through the session entry, a warm-up statement before
the slice and one after it with no profiler attached.  The ring's clock is
``clock_constant_ns`` ahead of the trace's."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark.lib import trace as TR
from benchmark.readers import idle_named, program_spans as PS
from benchmark.run import Context

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _load(name):
    with open(os.path.join(HERE, name)) as fh:
        return json.load(fh)


@pytest.fixture()
def ctx():
    return Context(trace=TR.Reduced(_load("trace_small.json")),
                   ring=_load("ring_small.json")["ring"])


def _metric(name, ctx):
    with open(os.path.join(HERE, "..", "layer_metrics", name + ".json")) as fh:
        spec = json.load(fh)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("name, want", [
    # parse+analyze+optimize+plan+plancache.lookup: 9 ms and 4 ms
    ("plan.ms_per_stmt", 6.5),
    # one in the slice (the warm-up's is outside it), two statements
    ("compile.fresh_jits_per_stmt", 0.5),
    # stage.lookup: miss, hit, hit; hit
    ("compile.stage_hit_pct", 75.0),
    # read 20 + decode 30 + prep 10 a batch
    ("scan.busy_ms_per_batch", 60.0),
    ("scan.wait_ms_per_stmt", 15.0),
    ("xfer.h2d_ms_per_stmt", 8.0),
    # d2h 40 (inside jit.fresh) + 600, merge 12, rows 2, encode 3; 160 + 1
    ("fetch.d2h_ms_per_stmt", 409.0),
])
def test_span_metrics_on_the_recording(ctx, name, want):
    assert _metric(name, ctx) == pytest.approx(want, abs=1e-9)


def test_self_time_excludes_children_per_thread(ctx):
    # jit.fresh is 100 ms with a 40-ms d2h inside it
    assert PS.read(ctx, spans=["jit.fresh"]) == pytest.approx(30.0)
    # the root's self time: 900 ms less its children on ITS thread (the
    # scan on the prefetch thread overlaps it and takes nothing away)
    ring = PS.ring_of(ctx)
    pairs, _c, _w = PS.slice_statements(ctx, ring)
    root = {s[0]: ns for s, ns in PS.self_ns(pairs[0][1])
            if s[0] == "statement"}
    assert root["statement"] == (900 - 7 - 100 - 3 * 222) * MS
    assert PS.read(ctx, spans=["no.such.span"]) == 0.0


def test_statements_and_clock_constant(ctx, capsys):
    ring = PS.ring_of(ctx)
    pairs, const, width = PS.slice_statements(ctx, ring)
    assert [b[3]["stmt"] for b, _ss in pairs] == ["q3", "q42"]
    assert [ss[0][3] for _b, ss in pairs] == [7, 8]     # not 5, not 9
    true = _load("ring_small.json")["clock_constant_ns"]
    # the HTTP pair leaves 55.5 ms, the session pair 7.65: they intersect
    assert width == pytest.approx(7_654_026)
    assert abs(const - true) <= width / 2
    assert "clock constant within 7.654 ms over 2" in capsys.readouterr().err
    for b, ss in pairs:                       # each extent inside its span
        assert b[1] <= min(s[1] for s in ss) - const
        assert max(s[1] + s[2] for s in ss) - const <= b[1] + b[2]


def test_a_ring_that_does_not_fit_raises(ctx):
    ring = [list(s) for s in ctx.ring]
    ctx.ring = [s for s in ring if s[3] != 8]            # dropped by the ring
    ctx._program_slice = None
    with pytest.raises(ValueError, match="1 profiled statements"):
        PS.read(ctx, spans=["h2d"])
    # statement 8 moved 300 ms early: no one constant fits both pairs
    ctx._program_slice = None
    ctx.ring = [s[:1] + [s[1] - 300 * MS] + s[2:] if s[3] == 8 else s
                for s in ring]
    with pytest.raises(ValueError, match="one clock constant"):
        idle_named.read(ctx)


def test_a_program_without_tracing_reads_nothing(ctx, monkeypatch):
    import builtins
    del ctx.ring
    real = builtins.__import__

    def no_tracing(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "spark_tpu" and "tracing" in (fromlist or ()):
            raise ImportError("no tracing in this program")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_tracing)
    assert PS.read(ctx, spans=["h2d"]) is None
    assert idle_named.read(ctx) is None


def test_idle_named_against_a_brute_force_timeline(ctx):
    """One flag per 100 ns of the slice: idle, and idle under a span that
    is not a root (the innermost span over a point is the shortest)."""
    got = _metric("device.idle_named_pct", ctx)
    t = ctx.trace
    ring = PS.ring_of(ctx)
    pairs, const, _w = PS.slice_statements(ctx, ring)
    step = 100
    n = int((t.hi - t.lo) // step)
    at = t.lo + step * (np.arange(n) + 0.5)
    busy = np.zeros(n, bool)
    for _name, s, d in t.events[t.busiest()]:
        busy |= (at >= s) & (at < s + d)
    best = np.full(n, np.inf)
    named = np.zeros(n, bool)
    for _b, ss in pairs:
        for s in ss:
            over = (at >= s[1] - const) & (at < s[1] - const + s[2])
            take = over & (s[2] < best)
            best[take] = s[2]
            named[take] = s[0] not in ("statement", "http.statement")
    want = 100.0 * (named & ~busy).sum() / (~busy).sum()
    assert 0 < want < 100
    assert got == pytest.approx(want, abs=0.05)
    # every span a root: nothing is named
    assert idle_named.read(ctx, roots=sorted({s[0] for s in ring})) == 0.0
