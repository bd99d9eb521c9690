"""CPU rehearsal of ``chip_smoke.py``: its phases in-process at a few
thousand rows, against the script's own pandas reference.

The phase functions are called directly, so the device check in the
script's ``main`` stays strict (no TPU -> non-zero exit, tested below) and
its control flow — data set-up, streamed and cached runs, the HTTP server,
the mesh phase and the comparisons — is guarded at no chip time.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

ROWS = 20000
BATCH = 4096             # store_sales streams in 5 batches


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    lines = []
    d = cs.build_dataset(ROWS, 20260730, str(tmp_path_factory.mktemp("smoke")),
                         lines.append)
    assert lines[0]["phase"] == "data" and not lines[0]["reused"]
    again = []
    cs.build_dataset(ROWS, 20260730, os.path.dirname(d.base), again.append)
    assert again[0]["reused"], "the marker must spare the second write"
    return d


@pytest.fixture()
def smoke_session(spark):
    """The shared session with the confs and views the phases touch put
    back afterwards."""
    keys = ("spark.tpu.mesh.shards", "spark.tpu.scan.maxBatchRows")
    old = {k: spark.conf._overrides.get(k) for k in keys}
    yield spark
    for k, v in old.items():
        if v is None:
            spark.conf.unset(k)
        else:
            spark.conf.set(k, v)
    for name in list(spark.catalog.listTables()):
        if name in cs.FACTS or name in ("date_dim", "item", "store"):
            spark.catalog.dropTempView(name)


def test_session_phase_matches_reference(smoke_session, ds):
    lines = []
    # the Pallas requirement is the chip's: here the MXU formulation the
    # suite forces on lowers as the portable einsum
    cs.phase_session(smoke_session, ds, lines.append, batch_rows=BATCH,
                     require_pallas=False)
    names = ("q3", "q42", "q55", "agg_store", "agg_customer")
    cold = {(ln["variant"], ln["statement"]) for ln in lines
            if ln["phase"] == "session/cold" and "variant" in ln}
    assert cold == {(v, n) for v in ("streamed", "cached") for n in names}
    warm = {(ln["phase"], ln["statement"]) for ln in lines}
    assert {("session/streamed", "agg_store"), ("session/cached", "q3"),
            ("session/cached", "agg_customer")} <= warm
    # the warm pass reuses the cold pass's executables
    assert all(ln["compile_s_in_warm_call"] == 0.0 for ln in lines
               if "compile_s_in_warm_call" in ln)
    assert all(ln["equal_to_reference"] for ln in lines
               if "equal_to_reference" in ln)
    by = {(ln["phase"], ln["statement"]): ln for ln in lines}
    assert by["session/cached", "agg_store"]["agg_lowering"] == "einsum"
    assert by["session/cached", "agg_customer"]["agg_lowering"] == "sort"
    assert by["session/cached", "cache"]["storage_bytes"] > 0
    json.dumps(lines)                       # every line is JSON


def test_server_phase_matches_reference(smoke_session, ds):
    smoke_session.conf.set("spark.tpu.scan.maxBatchRows", str(BATCH))
    lines = []
    cs.phase_server(smoke_session, ds, lines.append)
    served = [ln for ln in lines if ln["statement"] in cs.STAR]
    assert len(served) == 6 and {ln["session"] for ln in served} == {0, 1}
    assert all(ln["equal_to_reference"] for ln in served)
    assert lines[-1]["statement"] == "status" and lines[-1]["sessions"] == 2


def test_mesh_phase_matches_reference(smoke_session, ds):
    if len(jax.devices()) < 4:
        pytest.skip("needs the suite's virtual devices")
    lines = []
    # the CPU backend reports no memory_stats: the per-device proof is the
    # chip's
    cs.phase_mesh(smoke_session, ds, lines.append, n=4,
                  require_device_memory=False)
    by = {ln["statement"]: ln for ln in lines}
    assert by["q3"]["equal_to_reference"] and by["q17"]["equal_to_reference"]
    assert by["q3"]["collectives"].get("all-to-all", 0) > 0
    assert by["ici.local_device_exchange"]["equal_to_host_pack_unpack"]


def test_a_mismatch_fails():
    with pytest.raises(AssertionError):
        cs.compare("t", [(1, 2.0)], [(1, 2.0 * (1 + 1e-8))])
    with pytest.raises(AssertionError):
        cs.compare("t", [(1, 2.0)], [(2, 2.0)])
    with pytest.raises(AssertionError):
        cs.compare("t", [], [])             # an empty reference is no check
    assert cs.compare("t", [(None, 2.0)], [(None, 2.0 * (1 + 1e-12))]) == 1


def test_no_tpu_is_a_failure(tmp_path):
    """Without a TPU the script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        "--rows", "1000", "--work-dir", str(tmp_path)],
                       capture_output=True, text=True, timeout=240, env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr
