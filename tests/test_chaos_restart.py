"""Chaos harness: supervised gang restart + checkpoint resume.

The reference's `FaultToleranceTest.scala` kills cluster members and
asserts recovery; the analog here is the launcher's --max-restarts
supervision (`spark-submit --supervise`, `deploy/Client.scala` role):
a worker SIGKILLed mid-scan is relaunched as a whole gang and the
checkpointed multibatch query resumes from its saved cursor instead of
restarting from row zero."""

import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "chaos_worker.py")


@pytest.mark.timeout(300)
def test_supervised_restart_resumes_from_checkpoint(tmp_path):
    rng = np.random.default_rng(21)
    n = 2000                                  # 8 scan batches of 256
    pdf = pd.DataFrame({
        "k": rng.integers(0, 20, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64)})
    data = tmp_path / "chaos.parquet"
    data.mkdir()
    pdf.to_parquet(data / "part-0.parquet", index=False)
    ckpt = tmp_path / "ckpt"
    marker = tmp_path / "died.marker"
    out = tmp_path / "result.csv"

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "spark_tpu.cli", "launch",
         "--processes", "1", "--max-restarts", "2",
         _WORKER, str(data), str(ckpt), str(marker), str(out)],
        capture_output=True, text=True, timeout=280, env=env,
        cwd=os.path.dirname(os.path.dirname(_WORKER)))
    log = proc.stdout + proc.stderr
    assert proc.returncode == 0, log[-3000:]
    # attempt 1 died after its 2nd checkpoint...
    assert "CHAOS-KILL" in log
    assert "restart 1/2" in log
    # ...and attempt 2 RESUMED (skip > 0) rather than rescanning
    assert "CKPT-SKIP 2" in log
    assert "CHAOS-QUERY-OK" in log
    # the resumed result is exact
    got = [tuple(int(x) for x in line.split(","))
           for line in out.read_text().splitlines()]
    exp = (pdf.groupby("k").agg(s=("v", "sum"), c=("v", "size"))
           .reset_index().sort_values("k"))
    assert got == list(zip(exp.k, exp.s, exp.c))
