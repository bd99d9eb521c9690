"""The decision-trace golden divergence battery across REAL processes.

Spawns ``adaptive_worker.py`` in two modes:

* mode "trace" (2 procs tier-1, 3 procs slow): unperturbed parity —
  one full hash exchange and one range exchange with the decision-trace
  runtime check pinned on.  Every process must report oracle-identical
  rows, ``decision_trace_checks > 0`` and ZERO divergence; the row
  counts must agree across processes (byte-identical results — each
  worker already compares its rows tuple-for-tuple against the oracle).

* mode "skew-decision": one process's gathered view of the
  ``xq000001-plan`` stats round is perturbed by the ``skew_decision``
  fault kind while the on-disk manifests stay byte-identical — the
  classic silent replica-determinism violation.  The armed process must
  abort STRUCTURED via ``verify_decision_trace`` (property
  ``decision-trace-agreement``, naming the diverging exchange), never
  emit partial rows; the unarmed peer fails bounded at its data
  barrier.  Without the trace check this run would demote one process
  to broadcast while the other ships hash buckets — rows silently lost.
"""

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from spark_tpu.parallel.faults import (  # noqa: E402
    FAULT_PLAN_ENV, FaultPlan)
from worker_procs import run_workers  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "adaptive_worker.py")


def _spawn(tmp_path, n, mode, timeout_s, plans=None):
    root = str(tmp_path / "shuf")
    envs = []
    for pid in range(n):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop(FAULT_PLAN_ENV, None)
        build = (plans or {}).get(pid)
        if build is not None:
            env[FAULT_PLAN_ENV] = build().to_env()
        envs.append(env)
    procs, outs = run_workers(
        [[sys.executable, WORKER, str(pid), str(n), root, mode,
          str(timeout_s)] for pid in range(n)], envs, tmp_path, 420)
    return outs, procs


def _run_trace_parity(tmp_path, n):
    outs, procs = _spawn(tmp_path, n, "trace", 45.0)
    rows = set()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        assert "PARTIAL" not in out, out
        m = re.search(rf"\[p{pid}\] TRACE-OK rows=(\d+) checks=(\d+) "
                      r"div=(\d+)", out)
        assert m, out
        rows.add(int(m.group(1)))
        assert int(m.group(2)) > 0, f"no decision-trace checks ran:\n{out}"
        assert int(m.group(3)) == 0, f"unexpected divergence:\n{out}"
    # every process produced the same (oracle-verified) result set
    assert len(rows) == 1, rows


def test_trace_parity_two_processes(tmp_path):
    _run_trace_parity(tmp_path, 2)


@pytest.mark.slow
def test_trace_parity_three_processes(tmp_path):
    _run_trace_parity(tmp_path, 3)


def test_skew_decision_divergence_aborts_structured(tmp_path):
    """The armed process must abort via the decision-trace check —
    naming the diverging exchange and decision — and NEVER produce
    partial rows; the peer fails bounded, not hanging."""
    outs, procs = _spawn(
        tmp_path, 2, "skew-decision", 8.0,
        plans={1: lambda: FaultPlan().skew_decision("xq000001-plan")})
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        assert "PARTIAL" not in out, out
        assert "TRACE-OK" not in out, out
    # armed process: structured divergence abort naming the round
    assert "[p1] FAILED-DIVERGED" in outs[1], outs[1]
    assert "prop=decision-trace-agreement" in outs[1], outs[1]
    assert "xq000001-plan" in outs[1], outs[1]
    assert "div=1" in outs[1], outs[1]
    # unarmed peer: bounded structured failure at its data barrier
    assert "[p0] FAILED" in outs[0], outs[0]
