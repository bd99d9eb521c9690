"""Adaptive query execution across REAL processes.

Spawns ``adaptive_worker.py`` under 2 (tier-1) and 3 (slow) processes.
The worker batters the adaptive re-planning layer against a full-data
oracle: hash→broadcast demotion at the stats barrier, the
stats-feedback plan-time shortcut on a repeated query, range→broadcast
demotion, a frozen-plan control session, the post-sample skew
re-split, and partial-aggregate pushdown — every scenario must return
oracle-identical rows AND take the path the observed statistics
dictate (asserted inside the worker via path counters; this spawner
checks the per-scenario OK markers and exit codes).

Fault-injection coverage for the stats round itself lives in
test_faults.py.
"""

import os

import pytest

from worker_procs import run_exchange_workers

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "adaptive_worker.py")

MARKERS = ("DEMOTE-OK", "FEEDBACK-OK", "RANGE-DEMOTE-OK", "FROZEN-OK",
           "SKEW-OK", "AGGPUSH-OK", "ADAPT-OK")


def _run_adaptive(tmp_path, n, timeout_s=90.0):
    procs, outs = run_exchange_workers(WORKER, tmp_path, n, "adaptive",
                                       timeout_s)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out}"
        for m in MARKERS:
            assert f"[p{pid}] {m}" in out, (m, out)
        # one demotion per lane (hash + range), the repeat answered
        # from feedback, and the skew span re-split from observed bytes
        assert "demotions=2" in out, out
        assert "fbhits=" in out and "fbhits=0" not in out, out
        assert "postskew=" in out and "postskew=0" not in out, out
    return outs


def test_adaptive_parity_two_processes(tmp_path):
    _run_adaptive(tmp_path, 2)


@pytest.mark.slow
def test_adaptive_parity_three_processes(tmp_path):
    _run_adaptive(tmp_path, 3)
