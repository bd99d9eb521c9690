"""Start the worker processes of a multi-process test and collect what they
print, without letting a worker block on its own output.

A ``subprocess.PIPE`` holds 64 KiB.  The helpers used to drain the workers
one after another (``[p.communicate() for p in procs]``), so while worker 0
was read, worker 1 wrote into a pipe nobody emptied.  A worker that loads
programs from a warm XLA:CPU compile cache logs two 3 KB lines a load: about
ten loads fill the pipe, the worker stops inside ``write``, and its peer
waits at the next shuffle barrier until ``senders [1] did not commit within
90.0s``.  That was the one cause of the six ``*_parity_two_processes``
failures (alone, with a warm ``.jax_cache/`` and no load at all,
``test_adaptive_parity_two_processes`` failed in 185 s of which 12 were CPU
time).  Files have no such limit.
"""

import os
import subprocess
import sys


def run_workers(cmds, env, tmp_path, wait_s):
    """Run one process per command line of ``cmds`` (``env``: one
    environment for all, or a list of one each), each writing stdout and
    stderr to a file of its own under ``tmp_path``; wait up to ``wait_s``
    seconds for each and return ``(procs, outputs)``.  A worker that outlives
    its wait is killed and ``subprocess.TimeoutExpired`` raised."""
    logs = [tmp_path / f"worker{i}.out" for i in range(len(cmds))]
    envs = env if isinstance(env, list) else [env] * len(cmds)
    procs = []
    try:
        for cmd, log, e in zip(cmds, logs, envs):
            with open(log, "w") as f:
                procs.append(subprocess.Popen(
                    cmd, stdout=f, stderr=subprocess.STDOUT, env=e))
        for p in procs:
            p.wait(timeout=wait_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return procs, [log.read_text(errors="replace") for log in logs]


def run_exchange_workers(worker, tmp_path, n, mode, timeout_s, wait_s=420):
    """The n-process exchange batteries' launch: ``worker <pid> <n> <shuffle
    root> <mode> <barrier timeout>`` on the CPU backend with no fault plan;
    ``(procs, outputs)`` as ``run_workers``."""
    root = str(tmp_path / "shuf")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SPARK_TPU_FAULT_PLAN", None)
    return run_workers(
        [[sys.executable, worker, str(pid), str(n), root, mode,
          str(timeout_s)] for pid in range(n)], env, tmp_path, wait_s)
