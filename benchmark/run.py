#!/usr/bin/env python3
"""One run of one cell of the benchmark, in one process that holds the
cell's chips.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data the harness finds by name from
the cell's entry in ``BENCHMARK.json``: ``configs/<configuration>.json``,
``traffic/<mix>.json`` -> ``loops/<loop>.py``, ``statements/<name>.sql``
(+ ``.json``), ``references/<name>.py``, ``generators/<table>.py``,
``limits/<cell>.json``, ``end_to_end/<metric>.py``,
``layer_metrics/<metric>.json`` -> ``readers/<reader>.py``, ``peaks.json``.

Set-up (import, data from the seed, views, device cache, one warm call of
every statement shape — compile included) ends where the window starts.
The window is the mix's loop (``loops/closed.py``: ONE closed loop over
whole passes of the mix's order; at ``--seconds`` the client stops issuing
once the pass in flight is complete, and everything sent completes and
counts).  Once it has closed the peak
memory is read, the program's state is freed, and every reply is compared
with the plain pandas reference.  The LAST line of standard output is the
result object; a run that finds no TPU (or fewer chips than the cell
asks for) exits non-zero and prints none.

``--rehearse 1`` runs the same path at the configuration's
``rehearse_rows`` on whatever backend JAX has (the CPU here): its line says
``"rehearsal": true`` and carries no metric at all.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import gc            # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _emit(obj, device):
    """A progress line on standard output; every line names the device."""
    print(json.dumps(dict(obj, device=device)), flush=True)


def _percentile(values, q):
    """Linear-interpolated percentile of all the values (numpy's rule)."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def end_to_end(name, records, window_s, setup_s):
    """An end-to-end metric, by the file of its own that computes it over
    ALL the statements and ALL the time of the window."""
    mod = importlib.import_module(f"benchmark.end_to_end.{name}")
    return mod.value(records, window_s, setup_s)


def _latency_summary(records):
    """Per statement name: how many, the median, the slowest and which —
    where a window's time went when a rate reads far off."""
    out = {}
    for name in dict.fromkeys(r["name"] for r in records):
        lat = [(r["latency_ms"], r["k"]) for r in records
               if r["name"] == name]
        worst = max(lat)
        out[name] = {"n": len(lat), "p50": round(_percentile(
            [v for v, _k in lat], 50), 3), "max": round(worst[0], 3),
            "max_k": worst[1]}
    return out


class Context:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _metrics_of(manifest, group, cell):
    """The metrics of ``group`` this cell reports (a metric with no
    ``workloads`` key belongs to every cell that reports what it moves)."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def _load_cell(args):
    """(manifest, cell, configuration, row counts) of ``--workload``; a
    rehearsal takes the configuration's tiny rows and conf, on the CPU."""
    with open(os.path.join(ROOT, args.manifest)) as fh:
        manifest = json.load(fh)
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                         f"{args.manifest}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    rows = dict(config["rows"])
    if args.rehearse:
        rows.update(config["rehearse_rows"])
        config["conf"] = dict(config["conf"], **config["rehearse_conf"])
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell["chips"] > 1 and os.environ["JAX_PLATFORMS"] == "cpu":
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={cell['chips']}").strip()
    return manifest, cell, config, rows


def _prepare_data(E, datagen, args, config, rows, mix, device):
    """The cell's tables from the seed; the facts (and the dimensions, where
    the configuration keeps them in files) as parquet under ``.work``, once
    per configuration and seed.  Other seeds' files are removed."""
    t0 = time.time()
    tables = datagen.generate(args.seed, rows, mix.tables(),
                              config.get("dimension_seed"))
    mix.bind(tables)
    t_gen = time.time() - t0
    tag = f"{config['name']}_{args.seed}" + \
        ("_rehearse" if args.rehearse else "")
    data_root = os.path.join(WORK, "data")
    if os.path.isdir(data_root):
        for other in os.listdir(data_root):
            if other != tag:
                shutil.rmtree(os.path.join(data_root, other),
                              ignore_errors=True)
    base = os.path.join(data_root, tag)
    on_disk = {t: c for t, c in tables.items()
               if datagen.is_fact(t) or config["dimensions"] == "parquet"}
    t0 = time.time()
    wrote = E.write_parquet(on_disk, base,
                            {"seed": int(args.seed), "rows": rows},
                            int(config["fact_files"]))
    _emit({"phase": "data", "generate_s": round(t_gen, 3), "wrote": wrote,
           "write_s": round(time.time() - t0, 3),
           "tables": {t: len(next(iter(c.values()))) if isinstance(c, dict)
                      else len(c) for t, c in tables.items()}}, device)
    return tables, base


def _warm_up(E, eng, mix, rehearse, device):
    """One call of every statement shape (its first in a checkout compiles),
    with what the traffic file asks to be asserted of it."""
    expect = mix.spec.get("expect_lowering", {})
    for name, lit, sql in mix.warm_up():
        c0 = E.counters()
        t0 = time.time()
        got, _ms = eng.run(sql)
        first = time.time() - t0
        c1 = E.counters()
        line = {"phase": "warm_up", "statement": name, "literals": lit,
                "first_s": round(first, 3), "rows": len(got),
                "xla_compiles": c1["xla_compiles"] - c0["xla_compiles"],
                "xla_compile_s": round(
                    c1["xla_compile_s"] - c0["xla_compile_s"], 3),
                "stage_builds": c1["builds"] - c0["builds"]}
        if name in expect and not rehearse:
            line["agg_lowering"] = eng.agg_lowering()
            if line["agg_lowering"] not in (expect[name], "unreadable"):
                raise SystemExit(f"benchmark: {name} lowered as "
                                 f"{line['agg_lowering']}, not {expect[name]}")
        if mix.spec.get("assert_redispatch"):
            # a repeated statement must run on the device again (no result
            # cache in the way): the stage cache dispatches once more
            eng.run(sql)
            again = E.counters()["dispatches"] - c1["dispatches"]
            line["dispatches_on_repeat"] = again
            if again < 1:
                raise SystemExit(f"benchmark: a repeated {name} did not "
                                 "dispatch its stage again")
        _emit(line, device)


class Tracer:
    """The traced slice: with a ``trace_dir`` the window's first ``n``
    statements run inside the profiler, under a ``bench:slice`` span, and
    every call into the system under a ``bench:<name>`` span."""

    def __init__(self, trace_dir, n):
        self.dir, self.n = trace_dir, int(n)
        self.open = False
        self._slice = None

    def span(self, name, **kw):
        if self.dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name, **kw)

    def start(self):
        if self.dir is None:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opt = jax.profiler.ProfileOptions()
        opt.python_tracer_level = 0
        opt.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opt)
        self._slice = self.span("slice")
        self._slice.__enter__()
        self.open = True

    def statement_done(self, k):
        if self.open and k >= self.n:
            import jax
            self._slice.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.open = False


class _GcClock:
    """Seconds the collector ran inside the window (a stall's first
    suspect): set-up's survivors are frozen out of its reach first."""

    def __init__(self):
        self.seconds, self._t0 = 0.0, None

    def __call__(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        gc.unfreeze()


def _judge(compare, T, args, cell, records, tables, device):
    """Every reply against the plain reference of its statement and
    literals; with ``--control`` the float32 reference too, put in the
    program's place (an earlier line)."""
    refs, control = {}, {}
    for r in records:
        key = (r["name"], r["literals_key"])
        if key not in refs:
            mod = importlib.import_module(f"benchmark.references.{r['name']}")
            refs[key] = mod.reference(tables, r["literals"])
            if args.control:
                control[key] = mod.reference(tables, r["literals"],
                                             float_dtype="float32")
    limits = T.load_json("limits", cell["name"] + ".json")
    if args.control:
        stand_ins = [dict(r, rows=control[r["name"], r["literals_key"]])
                     for r in records]
        c_ok, c_cmp = compare.judge(stand_ins, refs, limits)
        _emit({"phase": "control", "what": "the float32 reference in the "
               "program's place", "correct": c_ok, "compared": c_cmp}, device)
    return compare.judge(records, refs, limits)


def _per_layer(T, args, manifest, cell, ctx, trace_dir, result):
    """Reduce the traced slice and let each of the cell's per-layer metrics'
    readers read it; a reader that finds nothing leaves its metric out."""
    from benchmark.lib import trace as TR
    t0 = time.time()
    files = [os.path.join(d, f) for d, _s, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(files) != 1:
        raise SystemExit(f"benchmark: {len(files)} trace files")
    raw = TR.load(files[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.trace = TR.Reduced(raw)
    for m in _metrics_of(manifest, "per_layer", cell):
        spec = T.load_json("layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec["args"])
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"]["busy_s"] = ctx.trace.busy_s()
    result["device"]["window_s"] = ctx.trace.window_s
    result["breakdown"] = ctx.trace.breakdown()
    result["traced"] = {"statements": len(ctx.records),
                        "reduce_s": round(time.time() - t0, 3)}


def run_cell(args) -> int:
    manifest, cell, config, rows = _load_cell(args)
    chips, rehearse = int(cell["chips"]), bool(args.rehearse)

    import jax
    import spark_tpu  # noqa: F401  (x64 and the compile cache, before any array)
    from benchmark.lib import compare, datagen, engine as E, roofline, \
        traffic as T
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        print(f"benchmark: no TPU — jax.devices() is {devs}", file=sys.stderr)
        return 1
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips and "
              f"jax.devices() has {len(devs)}", file=sys.stderr)
        return 1
    device = E.device_info(chips)
    peaks = T.load_json("peaks.json")
    pk = None if rehearse else roofline.peak(peaks, device["kind"])

    # -- set-up ---------------------------------------------------------------
    mix = T.Traffic(cell["traffic"], args.seed)
    tables, base = _prepare_data(E, datagen, args, config, rows, mix, device)
    E.counters()                        # starts counting XLA compiles
    eng = E.Engine(config, mix.spec, tables, base, WORK)
    eng.start()
    _warm_up(E, eng, mix, rehearse, device)

    # -- the window -----------------------------------------------------------
    tracing = bool(args.trace) and not rehearse
    trace_dir = os.path.join(WORK, "trace", cell["name"]) if tracing else None
    loop = importlib.import_module(
        "benchmark.loops." + mix.spec.get("loop", "closed"))
    tracer = Tracer(trace_dir, mix.spec["trace_statements"])
    with _GcClock() as gc_clock:
        tracer.start()
        before = E.counters()
        setup_s = time.time() - T_START
        records, window_s = loop.drive(eng, mix, rows, args.seconds, tracer)

    # -- the window has closed: read the peak, free the state, compare --------
    after = E.counters()
    peak = E.peak_bytes(chips)
    eng.stop()
    stmt_log = os.path.join(WORK, f"statements_{cell['name']}.jsonl")
    with open(stmt_log, "w") as fh:
        for r in records:
            fh.write(json.dumps({
                "k": r["k"], "statement": r["name"], "literals": r["literals"],
                "start_s": r["start_s"], "latency_ms": r["latency_ms"],
                "server_ms": r["server_ms"], "error": r.get("error"),
                "rows": None if r["rows"] is None else len(r["rows"]),
                "device": device}) + "\n")
    t0 = time.time()
    correct, compared = _judge(compare, T, args, cell, records, tables,
                               device)
    _emit({"phase": "compare", "reference_s": round(time.time() - t0, 3),
           "window_s": round(window_s, 3), "statements": len(records),
           "xla_compiles_in_window":
               after["xla_compiles"] - before["xla_compiles"],
           "stage_builds_in_window": after["builds"] - before["builds"],
           "gc_s_in_window": round(gc_clock.seconds, 4),
           "latency_ms": _latency_summary(records),
           "per_statement": os.path.relpath(stmt_log, ROOT)}, device)

    # -- the result -----------------------------------------------------------
    done = [r for r in records if r["rows"] is not None]
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": len(records) - len(done), "metrics": {},
              "device": dict(device, memory_peak_bytes=peak)}
    if rehearse:
        result["rehearsal"] = True
    elif not tracing:
        for m in _metrics_of(manifest, "end_to_end", cell):
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], records, window_s, setup_s),
                "unit": m["unit"]}
    else:
        n_traced = int(mix.spec["trace_statements"])
        ctx = Context(
            trace=None, records=records[:n_traced], peak_bytes=peak,
            counters_before=before, counters_after=after,
            least_bytes=lambda st: roofline.least_bytes(
                mix.statements[st].meta, rows),
            hbm_roofline_pct=lambda b, s: roofline.hbm_roofline_pct(
                b / chips, s, pk))
        _per_layer(T, args, manifest, cell, ctx, trace_dir, result)
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct = {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="the manifest, relative to the checkout")
    ap.add_argument("--rehearse", type=int, default=0, choices=(0, 1),
                    help="tiny sizes on any backend; prints no metric")
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="also judge the float32 reference put in the "
                    "program's place (an earlier line; not a benchmark run)")
    return run_cell(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
