"""Subprocess worker for the kill-a-peer-mid-exchange chaos test.

argv: <pid> <shuffle_root> <beat_dir>
The fault plan arrives via SPARK_TPU_FAULT_PLAN (env transport), so the
victim and the survivor run the SAME code; only the plan differs.

Protocol printed on stdout (one line):
    OK <sorted values received>          exchange completed
    FAILED <elapsed_s> <lost hosts>      structured ExchangeFetchFailed
Anything else (traceback, timeout) fails the parent's assertions.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import numpy as np  # noqa: E402

from spark_tpu import config as C  # noqa: E402
from spark_tpu.columnar import ColumnBatch  # noqa: E402
from spark_tpu.parallel.cluster import HeartbeatMonitor  # noqa: E402
from spark_tpu.parallel.faults import FaultInjector  # noqa: E402
from spark_tpu.parallel.hostshuffle import (  # noqa: E402
    ExchangeFetchFailed, HostShuffleService,
)

TIMEOUT_S = 8.0


def main() -> None:
    pid, root, beats = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    conf = (C.Conf()
            .set("spark.tpu.cluster.heartbeatIntervalMs", "100")
            .set("spark.tpu.cluster.heartbeatTimeoutMs", "500"))
    # time.time, not monotonic: beats are compared ACROSS processes
    hb = HeartbeatMonitor(beats, host_id=f"host-{pid}", conf=conf,
                          clock=time.time)
    hb.beat()
    svc = HostShuffleService(root, pid, 2, timeout_s=TIMEOUT_S,
                             poll_s=0.05, conf=conf, heartbeat=hb)
    FaultInjector().attach(svc)          # plan comes from the env

    # wait for the peer's first beat so its death is later OBSERVABLE as
    # a stale beat (a peer that never beat at all is just a straggler)
    peer = 1 - pid
    t_end = time.time() + 5
    while not os.path.exists(os.path.join(beats, f"beat_host-{peer}.json")):
        if time.time() > t_end:
            print("NO_PEER_BEAT", flush=True)
            sys.exit(2)
        time.sleep(0.02)

    rows = np.arange(pid * 100, pid * 100 + 10, dtype=np.int64)
    per = {r: [ColumnBatch.from_arrays({"v": rows[rows % 2 == r]})]
           for r in (0, 1)}
    t0 = time.time()
    try:
        mine = svc.exchange("ex", per)
    except ExchangeFetchFailed as e:
        print(f"FAILED {time.time() - t0:.2f} {e.lost_hosts}", flush=True)
        return
    got = sorted(int(x) for b in mine
                 for x, ok in zip(np.asarray(b.column("v").data),
                                  np.asarray(b.row_valid_or_true()))
                 if ok)
    print(f"OK {got}", flush=True)


if __name__ == "__main__":
    main()
