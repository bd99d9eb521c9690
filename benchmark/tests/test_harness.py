"""The benchmark's harness, checked without the chip.

    python -m pytest benchmark/tests -q

Every cell's traffic is rehearsed at a tiny size through ``--rehearse 1``
(which a real measurement refuses and which prints no metric); the control
(the float32 reference in the program's place) and each fault the cells can
have must come out as not correct; the roofline's bytes are checked by
hand; the trace reduction is checked on a small recording of a chip trace.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join("benchmark", "tests", "manifest_rehearsal.json")
CELLS = ["sf1-star-parquet", "sf1-aggsort-cached", "mesh4-q3-q17",
         "sf1-star-cached", "sf1-aggstore-cached"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def _run_sub(args, cwd=ROOT, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py")] + args,
        cwd=cwd, env=e, capture_output=True, text=True, timeout=600)


def _run_here(capsys, cell, *extra, seconds="2"):
    from benchmark import run
    rc = run.main(["--workload", cell, "--seed", "2147483777", "--seconds",
                   seconds, "--trace", "0", "--rehearse", "1",
                   "--manifest", MANIFEST] + list(extra))
    out = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.out.strip().splitlines()
             if ln.startswith("{")]
    return rc, lines, out.err


# -- every cell's traffic, rehearsed ------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    """Each cell end to end in a process of its own, a seed past 2**31; the
    last line has the contract's keys, ``compared`` last, says it is a
    rehearsal and carries no metric."""
    p = _run_sub(["--workload", cell, "--seed", str(2**31 + 12345),
                  "--seconds", "3", "--trace", "0", "--rehearse", "1",
                  "--manifest", MANIFEST])
    assert p.returncode == 0, p.stderr[-2000:]
    res = _last_json(p.stdout)
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]
    # every line of standard output names the device
    for ln in p.stdout.strip().splitlines():
        assert set(json.loads(ln)["device"]) >= {"platform", "kind", "count"}
    # the numbers compared are the last lines of standard error
    tail = p.stderr.strip().splitlines()[-5:]
    assert tail[-1] == "correct = True"
    assert all(t.startswith("compared ") for t in tail[:-1])


def test_measurement_refuses_the_cpu():
    """Without ``--rehearse`` a run that finds no TPU exits non-zero and
    prints no result."""
    p = _run_sub(["--workload", "sf1-aggsort-cached", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_bare_checkout_exits_nonzero(tmp_path):
    """Only ``BENCHMARK.json`` and ``benchmark/``: no program, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        ".work", "__pycache__"))
    p = _run_sub(["--workload", "sf1-aggsort-cached", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], cwd=str(tmp_path),
                 env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(ln.startswith('{"correct"') for ln in p.stdout.splitlines())


# -- the control and the faults come out as not correct -----------------------

@pytest.mark.parametrize("cell", CELLS[:3])
def test_control_is_not_correct(capsys, cell):
    """The reference computed in float32 and put in the program's place
    fails ``float_rel_gap``; the program itself passes in the same run."""
    rc, lines, _err = _run_here(capsys, cell, "--control", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    control = [ln for ln in lines if ln.get("phase") == "control"][0]
    assert control["correct"] is False
    gap = control["compared"]["float_rel_gap"]
    assert gap["value"] > 10 * gap["limit"]
    assert lines[-1]["compared"]["float_rel_gap"]["value"] < gap["limit"] / 10


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: the last column of the first
    row of every reply moves by one part in a million (or by one)."""
    from benchmark.lib import engine as E
    real = E.Engine.run

    def run(self, sql):
        rows, ms = real(self, sql)
        if rows and not sql.lstrip().upper().startswith("CREATE"):
            v = rows[0][-1]
            rows[0] = rows[0][:-1] + (v * (1 + 1e-6) if isinstance(v, float)
                                      else v + 1,)
        return rows, ms
    monkeypatch.setattr(E.Engine, "run", run)


def _half_the_fact(monkeypatch):
    """Half of the batch left out: every fact view keeps its even rows."""
    from benchmark.lib import engine as E
    keys = {"store_sales": "ss_ticket_number",
            "store_returns": "sr_ticket_number",
            "catalog_sales": "cs_order_number"}
    real = E.Engine._view_ddl

    def ddl(self, table):
        text = real(self, table)
        if table in keys:
            text += f" WHERE {keys[table]} % 2 = 0"
        return text
    monkeypatch.setattr(E.Engine, "_view_ddl", ddl)


def _no_exchange(monkeypatch):
    """The exchange between chips left out: ``all_to_all`` hands every
    shard its own send buffer back."""
    import jax
    monkeypatch.setattr(
        jax.lax, "all_to_all",
        lambda x, axis_name, split_axis, concat_axis, **kw: x)


FAULTS = [("sf1-star-parquet", _alter_answer),
          ("sf1-star-parquet", _half_the_fact),
          ("sf1-aggsort-cached", _alter_answer),
          ("mesh4-q3-q17", _alter_answer),
          ("mesh4-q3-q17", _half_the_fact),
          ("mesh4-q3-q17", _no_exchange)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(capsys, monkeypatch, cell, fault):
    """The harness past its look for a chip, the timed path broken
    underneath: ``correct`` comes out false."""
    fault(monkeypatch)
    if fault is _no_exchange:
        # a program compiled with the exchange in it must not be reused:
        # a stopped session's programs go with it
        from spark_tpu.sql.session import SparkSession
        SparkSession.builder.getOrCreate().stop()
    rc, lines, err = _run_here(capsys, cell)
    assert rc == 0
    assert lines[-1]["correct"] is False
    assert "correct = False" in err
    bad = [k for k, c in lines[-1]["compared"].items()
           if c["value"] > c["limit"]]
    assert bad, lines[-1]["compared"]
    if fault is _no_exchange:
        SparkSession.builder.getOrCreate().stop()


# -- the roofline's bytes, by hand ---------------------------------------------

def test_roofline_bytes_by_hand():
    from benchmark.lib import roofline, traffic
    rows = traffic.load_json("configs", "tpcds-sf1-1chip.json")["rows"]
    assert rows["store_sales"] == 2_880_404
    # agg_store: ss_store_sk bigint + ss_quantity int + ss_ticket_number
    # bigint = 20 B a row, and 13 result rows of 4 x 8 B
    meta = traffic.Statement("agg_store").meta
    assert roofline.least_bytes(meta, rows) == 2_880_404 * 20 + 13 * 32 \
        == 57_608_496
    # agg_customer_top100: two bigint keys, an int and a double = 28 B a
    # row, and 100 result rows of 5 x 8 B
    meta = traffic.Statement("agg_customer_top100").meta
    assert roofline.least_bytes(meta, rows) == 2_880_404 * 28 + 100 * 40 \
        == 80_655_312
    # q3: two bigint keys and one double = 24 B a row, and 100 result rows
    # of int + int + 16-byte brand + double = 32 B
    meta = traffic.Statement("q3").meta
    assert roofline.least_bytes(meta, rows) == 2_880_404 * 24 + 100 * 32 \
        == 69_132_896
    peaks = traffic.load_json("peaks.json")
    pk = roofline.peak(peaks, "TPU v5 lite")
    # 69 MB at 819 GB/s is 0.0844 ms: over 1 s of device time, 0.00844 %
    assert roofline.hbm_roofline_pct(69_132_896, 1.0, pk) == \
        pytest.approx(0.0084411, rel=1e-4)
    with pytest.raises(KeyError):
        roofline.peak(peaks, "TPU v9")


def test_manifest_names_files_that_exist():
    """Every name in ``BENCHMARK.json`` resolves to its data file."""
    from benchmark.lib import traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in m["workloads"]:
        mix = traffic.Traffic(w["traffic"], 1)
        assert os.path.exists(os.path.join(
            BENCH, "loops", mix.spec["loop"] + ".py"))
        for st in mix.statements:
            assert os.path.exists(
                os.path.join(BENCH, "references", st + ".py"))
        for t in mix.tables():
            assert os.path.exists(
                os.path.join(BENCH, "generators", t + ".py"))
        assert os.path.exists(
            os.path.join(BENCH, "limits", w["name"] + ".json"))
    e2e = {x["name"] for x in m["end_to_end"]}
    for name in e2e:
        assert os.path.exists(os.path.join(BENCH, "end_to_end", name + ".py"))
    for x in m["per_layer"]:
        spec = traffic.load_json("layer_metrics", x["name"] + ".json")
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert x["moves"] in e2e


# -- the trace reduction, on a small recording of a chip trace ----------------

@pytest.fixture(scope="module")
def recording():
    """600 ``XLA Ops`` events of ``sf1-star-parquet`` on a TPU v5 lite (PR
    24's first traced run: ``lib.trace.load``'s plain form of the
    ``.xplane.pb``, cut to 600 events), shifted to start near 0, with a
    slice span, two statement spans and one compile span laid over them by
    hand."""
    with open(os.path.join(HERE, "trace_small.json")) as fh:
        return json.load(fh)


def _timeline(events, lo, hi):
    """Busy nanoseconds by brute force: one flag per nanosecond."""
    import numpy as np
    busy = np.zeros(int(hi - lo), bool)
    for _n, s, d in events:
        a, b = int(max(s, lo) - lo), int(min(s + d, hi) - lo)
        if b > a:
            busy[a:b] = True
    return busy


def test_trace_reduction_on_the_recording(recording):
    from benchmark.lib import trace as TR
    r = TR.Reduced(recording)
    dev = "/device:TPU:0"
    events = recording["devices"][dev]
    lo, hi = r.lo, r.hi
    busy = _timeline(events, lo, hi)
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_s() * 1e9 == pytest.approx(busy.sum(), abs=len(events))
    assert r.idle_pct() == pytest.approx(100 * (1 - busy.mean()), abs=1e-3)
    # the self times of the line partition its busy time
    assert sum(ns for _n, ns in TR.self_times(events)) == \
        pytest.approx(busy.sum(), abs=len(events))
    # an opcode's share: the sort ops of the recording
    sorts = [e for e in events if TR.op_class(e[0]) == "sort"]
    assert sorts and all(" sort(" in e[0] for e in sorts)
    want = 100 * _timeline(sorts, lo, hi).sum() / busy.sum()
    assert r.class_share_pct({"sort"}) == pytest.approx(want, rel=1e-6)
    assert r.class_share_pct({"all-to-all"}) is None     # nothing to read
    # device time inside each statement span; the two spans cover every op
    spans = r.named("statement")
    assert [s[3]["stmt"] for s in spans] == ["q3", "q42"]
    inside = [r.device_s_in(s) for s in spans]
    for s, got in zip(spans, inside):
        a, b = int(s[1] - lo), int(s[1] + s[2] - lo)
        assert got * 1e9 == pytest.approx(busy[a:b].sum(), abs=len(events))
    # (an op 500 ns long straddles the gap left between the two spans)
    assert sum(inside) == pytest.approx(r.busy_s(), rel=1e-5)
    assert r.named("statement", stmt="q42") == [spans[1]]
    # the breakdown: ten entries at most, idle gaps named by the innermost
    # harness span over each gap's middle
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("fusion:kCustom fusion.229")
    idle = dict(b["idle_gaps"])
    assert set(idle) <= {"statement", "xla_compile", "between_statements"}
    assert idle["xla_compile"] == pytest.approx(5000e-9)
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - busy.sum()) / 1e9, rel=1e-6)


def test_op_parts_reads_xla_printed_names():
    from benchmark.lib import trace as TR
    assert TR.op_parts(
        "%fusion.239 = u32[1048576]{0:T(1024)} fusion(u32[8]{0} %a), "
        "kind=kCustom, calls=%fused_computation.3") == \
        ("fusion.239", "fusion:kCustom", "u32[1048576]")
    assert TR.op_parts(
        '%custom-call.1 = u32[2048]{0:T(1024)S(1)} custom-call(s64[2048]{0} '
        '%x), custom_call_target="X64SplitLow"')[1] == \
        "custom-call:X64SplitLow"
    assert TR.op_parts(
        "%copy-start = (s32[2048]{0:T(1024)S(1)}, s32[2048]{0:T(1024)}, "
        "u32[]{:S(2)}) copy-start(s32[2048]{0:T(1024)} %l)")[1] == \
        "copy-start"
    assert TR.op_class("%all-to-all.3 = s64[4,8]{1,0} all-to-all(s64[4,8] "
                       "%p), replica_groups={}") == "all-to-all"


def test_a_trace_with_no_device_op_is_refused(recording):
    from benchmark.lib import trace as TR
    empty = {"devices": {"/device:TPU:0": []}, "spans": recording["spans"]}
    with pytest.raises(ValueError):
        TR.Reduced(empty)
