"""The ICI device-exchange tier (spark_tpu.parallel.ici).

Three rings, innermost out:

* pure units — ``probe_topology`` (the replica-deterministic tier
  split), ``plan_side`` (agreed-inputs activation), ``schema_eligible``
  (the dictionary pin), and a numpy-only pack→transpose→unpack
  round-trip that models exactly what the all-to-all does to the slots;
* a FORCED multi-device CPU mesh (``--xla_force_host_platform_device_
  count``, so a subprocess): ``local_device_exchange`` moves real
  buckets through the real shard_map collective and must return every
  span byte-identical, runs and masks intact, with the second exchange
  of the same shape a StageCache HIT;
* two REAL processes (worker mode ``ici`` from shuffled_join_worker):
  the full parity battery with the tier armed — dict-coded queries stay
  pinned to the host tier, dict-free queries genuinely attempt the
  device tier on BOTH lanes and (no cross-process device world on CPU)
  fold back structured, every result byte-identical to the oracle.

The fault matrix for this tier (injected ``ici_unavailable``, death at
the copy point) lives in chaos_matrix.py like every other fault kind.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from spark_tpu import types as T  # noqa: E402
from spark_tpu.columnar import ColumnBatch, ColumnVector  # noqa: E402
from spark_tpu.parallel import ici  # noqa: E402
from worker_procs import run_exchange_workers  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "shuffled_join_worker.py")


# ---------------------------------------------------------------------------
# probe_topology: the replica-deterministic tier split
# ---------------------------------------------------------------------------

def test_probe_cpu_world_is_all_singletons():
    # no override + single-process jax world (the CPU test reality):
    # every pid is host-tier-only
    t = ici.probe_topology("", 0, 3, [0, 1, 2])
    assert t.domains == ((0,), (1,), (2,))
    assert t.peers() == []


def test_probe_override_groups_and_singleton_rest():
    t = ici.probe_topology("0,1|2,3", 1, 5, [0, 1, 2, 3, 4])
    assert t.domains == ((0, 1), (2, 3), (4,))
    assert t.domain() == (0, 1)
    assert t.peers() == [0]
    assert t.same_domain(0) and not t.same_domain(2)


def test_probe_override_drops_dead_and_out_of_range():
    # pid 9 is out of [0, n); pid 2 is dead → both silently dropped and
    # the dead pid does NOT reappear as a singleton (it is not live)
    t = ici.probe_topology("0,1,9|2", 0, 4, [0, 1, 3])
    assert t.domains == ((0, 1), (3,))


def test_probe_override_duplicate_keeps_first_group():
    t = ici.probe_topology("0,1|1,2", 2, 3, [0, 1, 2])
    assert t.domains == ((0, 1), (2,))
    assert t.peers() == []


def test_probe_malformed_override_degrades_to_singletons():
    # misconfiguration must degrade (host tier everywhere), never abort
    t = ici.probe_topology("0,banana|2", 0, 3, [0, 1, 2])
    assert t.domains == ((0,), (1,), (2,))


def test_probe_fingerprint_identical_across_replicas():
    # the property decision_inputs relies on: every pid derives the
    # SAME fingerprint from the same replicated inputs
    fps = {tuple(ici.probe_topology("1,0|3,2", p, 4, [0, 1, 2, 3])
                 .fingerprint()) for p in range(4)}
    assert fps == {("0,1", "2,3")}


# ---------------------------------------------------------------------------
# plan_side: agreed-inputs activation
# ---------------------------------------------------------------------------

def _mans(l_bytes, l_rows, r_bytes=0, r_rows=0):
    # one plan-round manifest per process, halving the side between them
    return {0: {"sides": {"l": [l_bytes // 2, l_rows],
                          "r": [r_bytes // 2, r_rows]}},
            1: {"sides": {"l": [l_bytes - l_bytes // 2, l_rows // 2],
                          "r": [r_bytes - r_bytes // 2, r_rows]}}}


def test_plan_side_requires_a_tier_with_peers():
    assert ici.plan_side(None, _mans(1 << 20, 100), "l", 0) is None
    solo = ici.probe_topology("", 0, 2, [0, 1])     # all singletons
    assert ici.plan_side(solo, _mans(1 << 20, 100), "l", 0) is None


def test_plan_side_byte_floor_and_pow2_capacity():
    tier = ici.probe_topology("0,1", 0, 2, [0, 1])
    p = ici.plan_side(tier, _mans(4096, 100), "l", 65536)
    assert p is not None and not p.active          # below the floor
    p = ici.plan_side(tier, _mans(70000, 100), "l", 65536, max_runs=7)
    assert p.active and p.agreed_bytes == 70000
    assert p.cap_rows == 128 and p.max_runs == 7   # pow2(max over procs)


def test_plan_side_zero_rows_never_activates():
    tier = ici.probe_topology("0,1", 0, 2, [0, 1])
    p = ici.plan_side(tier, _mans(1 << 20, 0), "l", 0)
    assert p is not None and not p.active


# ---------------------------------------------------------------------------
# schema gate + pack/unpack round-trip (numpy only — models the a2a's
# slot transpose without a device world)
# ---------------------------------------------------------------------------

def _batch(vals, valid=None, row_valid=None, dictionary=None):
    data = np.asarray(vals, np.int64)
    vec = ColumnVector(data, T.LongType(), valid, dictionary)
    return ColumnBatch(["k"], [vec], row_valid, len(data))


def test_schema_eligible_pins_dictionary_columns():
    assert ici.schema_eligible(_batch([1, 2]))
    assert not ici.schema_eligible(_batch([0, 1], dictionary=("a", "b")))
    assert not ici.schema_eligible(None)


def test_pack_transpose_unpack_round_trip():
    members = [0, 1, 2]
    # sender → receiver → runs (run boundaries must survive)
    outboxes = [
        {1: [_batch([10, 11]), _batch([12])], 2: [_batch([13])]},
        {0: [_batch([20], valid=[np.array([False])][0])],
         2: [_batch([21, 22, 23])]},
        {0: [], 1: [_batch([30, 31],
                           row_valid=np.array([True, False]))]},
    ]
    tpl = _batch([0])
    packs = [ici._pack_outbox(ob, members, tpl, cap=4, max_runs=2)
             for ob in outboxes]
    # the all-to-all's observable: receiver r's slot s = sender s's slot r
    for r in members:
        names = packs[0][0]
        cols = [np.stack([packs[s][1][0][r] for s in members])]
        masks = [np.stack([packs[s][2][0][r] for s in members])]
        rowv = np.stack([packs[s][3][r] for s in members])
        runl = np.stack([packs[s][4][r] for s in members])
        inbox = ici._unpack_inbox(names, tpl, cols, masks, rowv, runl,
                                  members, self_pid=r)
        for s in members:
            want = [b for b in (outboxes[s].get(r) or [])
                    if b.capacity > 0]
            if s == r or not want:
                assert s not in inbox
                continue
            got = inbox[s]
            assert len(got) == len(want)           # run boundaries kept
            for gb, wb in zip(got, want):
                assert gb.capacity == wb.capacity
                np.testing.assert_array_equal(gb.vectors[0].data,
                                              wb.vectors[0].data)
                gv, wv = gb.vectors[0].valid, wb.vectors[0].valid
                assert (gv is None) == (wv is None)
                if wv is not None:
                    np.testing.assert_array_equal(gv, wv)
                assert (gb.row_valid is None) == (wb.row_valid is None)
                if wb.row_valid is not None:
                    np.testing.assert_array_equal(gb.row_valid,
                                                  wb.row_valid)


def test_float64_plane_ships_as_its_int64_bits():
    """A TPU emulates float64, so a float plane crosses the device as its
    int64 view and comes back bit for bit: -0.0, a NaN payload and a
    denormal included."""
    odd = np.array([-0.0, 1.0 / 3.0, 5e-324, np.inf], np.float64)
    odd = np.append(odd, np.array([0x7FF8DEADBEEF0001], np.uint64)
                    .view(np.float64))

    def fbatch(vals):
        vec = ColumnVector(np.asarray(vals, np.float64), T.DoubleType(),
                           None, None)
        return ColumnBatch(["v"], [vec], None, len(vals))

    tpl = fbatch([0.0])
    _names, cols, masks, rowv, runl = ici._pack_outbox(
        {1: [fbatch(odd)]}, [0, 1], tpl, cap=8, max_runs=1)
    assert cols[0].dtype == np.int64
    assert [dt for dt, _ in ici._plane_shapes(cols, masks, rowv, runl)[1]] \
        == ["int64", "bool", "bool", "int32"]
    # receiver 1's slot 0 is sender 0's slot 1
    swap = lambda p: p[::-1]
    inbox = ici._unpack_inbox(_names, tpl, [swap(cols[0])], [swap(masks[0])],
                              swap(rowv), swap(runl), [0, 1], self_pid=1)
    got = inbox[0][0].vectors[0].data
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got.view(np.uint64), odd.view(np.uint64))


def test_pack_overflow_degrades_structured():
    tpl = _batch([0])
    with pytest.raises(ici.IciUnavailable):
        ici._pack_outbox({1: [_batch([1, 2, 3])]}, [0, 1], tpl,
                         cap=2, max_runs=2)
    with pytest.raises(ici.IciUnavailable):
        ici._pack_outbox({1: [_batch([1]), _batch([2])]}, [0, 1], tpl,
                         cap=8, max_runs=1)


# ---------------------------------------------------------------------------
# the real collective on a forced multi-device CPU mesh (subprocess:
# XLA_FLAGS must be set before jax initializes)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import numpy as np
    from spark_tpu import types as T
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.parallel import ici
    from spark_tpu.sql.stagecompile import stage_cache

    def batch(vals, valid=None, row_valid=None):
        data = np.asarray(vals, np.int64)
        return ColumnBatch(["k"], [ColumnVector(data, T.LongType(),
                                                valid, None)],
                           row_valid, len(data))

    rng = np.random.default_rng(11)
    n = 4
    outboxes = []
    for s in range(n):
        ob = {}
        for r in range(n):
            runs = []
            for _ in range(int(rng.integers(0, 3))):
                m = int(rng.integers(1, 9))
                vals = rng.integers(-99, 99, m)
                valid = (rng.random(m) < 0.8) if m % 2 else None
                runs.append(batch(vals, valid))
            ob[r] = runs
        outboxes.append(ob)
    tpl = batch([0])

    cache = stage_cache(None)
    inboxes = ici.local_device_exchange(outboxes, tpl, max_runs=2)
    assert cache.misses >= 1
    h0 = cache.hits
    again = ici.local_device_exchange(outboxes, tpl, max_runs=2)
    assert cache.hits > h0, "same shape must be a StageCache HIT"

    for got in (inboxes, again):
        for r in range(n):
            for s in range(n):
                want = [b for b in outboxes[s][r] if b.capacity > 0]
                if not want:
                    assert s not in got[r] or s == r
                    continue
                runs = got[r][s]
                assert len(runs) == len(want)
                for gb, wb in zip(runs, want):
                    np.testing.assert_array_equal(
                        gb.vectors[0].data, wb.vectors[0].data)
                    # None == all-true: the unpack canonicalizes an
                    # all-true mask back to None, so compare effective
                    m = wb.capacity
                    gv, wv = gb.vectors[0].valid, wb.vectors[0].valid
                    gm = np.ones(m, bool) if gv is None else gv
                    wm = np.ones(m, bool) if wv is None else wv
                    np.testing.assert_array_equal(gm, wm)
    print("MESH-PARITY-OK")
""")


def test_local_device_exchange_mesh_parity(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(HERE, ".."))
    p = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "MESH-PARITY-OK" in p.stdout, p.stdout + p.stderr


def test_local_device_exchange_needs_enough_devices():
    # in-process jax world: default CPU has one device — structured
    with pytest.raises(ici.IciUnavailable):
        ici.local_device_exchange([{}, {}, {}, {}, {}, {}, {}, {}, {}],
                                  _batch([0]))


# ---------------------------------------------------------------------------
# two REAL processes: the armed tier against the full battery
# ---------------------------------------------------------------------------

def _run_ici_parity(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "ici",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        # dict-coded battery pinned to host, still byte-identical
        assert f"[p{pid}] ALL-OK" in out, out
        # dict-free queries attempted the device tier on both lanes and
        # every attempt folded back structured (CPU: no spanning world)
        assert f"[p{pid}] ICI-FALLBACK-OK" in out, out
        assert out.count(f"[p{pid}] ICI-PARITY-OK") == 3, out
    return outs


def test_ici_parity_two_processes(tmp_path):
    _run_ici_parity(tmp_path, 2)


@pytest.mark.slow
def test_ici_parity_three_processes(tmp_path):
    _run_ici_parity(tmp_path, 3)
