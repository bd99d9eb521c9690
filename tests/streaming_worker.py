"""Worker for the streaming chaos scenarios (not a test module itself —
launched as a subprocess by bin/chaos --streaming and test_recovery.py).

argv: <process_id> <n_processes> <shared_root> <mode> [timeout_s]

A 2-process supervised kill/restart pair over ONE shared checkpoint:

pid 1 (victim)     — writes its OS pid to ``root/victim.pid``, runs the
    standing query over the shared inputs with the ``FaultInjector``
    armed from SPARK_TPU_FAULT_PLAN (``die_after_state_commit`` or
    ``torn_checkpoint(..., die=True)``), and REALLY dies: exit 43 via
    ``os._exit`` at the planned commit phase.
pid 0 (supervisor) — writes the input feeds + a ready sentinel, waits
    for the victim process to disappear, then (a) runs an uninterrupted
    ORACLE lifetime against private ckpt/out dirs and (b) a RECOVERY
    lifetime over the victim's checkpoint and sink.  Prints
    ``[p0] OK <files> replayed=<n>`` only if the recovered sink is
    BYTE-identical to the oracle's and at least one batch was replayed;
    a mismatch prints ``[p0] PARTIAL`` (grepped out of every run).

mode "wagg"  — windowed aggregate (watermark + tumbling-window sum);
mode "dedup" — stateful dropDuplicates over (k, ts).
"""

import glob
import os
import sys
import time

pid = int(sys.argv[1])
n = int(sys.argv[2])
root = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "wagg"
timeout_s = float(sys.argv[5]) if len(sys.argv) > 5 else 30.0

os.environ["JAX_PLATFORMS"] = "cpu"
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from spark_tpu import types as T  # noqa: E402
from spark_tpu.parallel.faults import FaultInjector  # noqa: E402
from spark_tpu.sql import functions as F  # noqa: E402
from spark_tpu.sql.dataframe import DataFrame  # noqa: E402
from spark_tpu.sql.session import SparkSession  # noqa: E402
from spark_tpu.streaming.core import (  # noqa: E402
    FileSink, FileStreamSource, StreamExecution, StreamingRelation)


def sec(x):
    return int(x * 1_000_000)


SCHEMA = T.StructType([
    T.StructField("ts", T.timestamp),
    T.StructField("k", T.string),
    T.StructField("v", T.int64),
])
FEEDS = [
    [(sec(1), "a", 1), (sec(9), "b", 2)],
    [(sec(20), "a", 4), (sec(21), "b", 1)],
    [(sec(35), "c", 8), (sec(35), "c", 8)],
    [(sec(50), "a", 3), (sec(51), "d", 9)],
]

in_dir = os.path.join(root, "in")
ready = os.path.join(root, "inputs_ready")
pidfile = os.path.join(root, "victim.pid")

spark = SparkSession.builder.appName(f"stream-chaos-{pid}").getOrCreate()


def shape(df):
    if mode == "dedup":
        return (df.withWatermark("ts", "5 seconds")
                .dropDuplicates(["k", "ts"]))
    return (df.withWatermark("ts", "5 seconds")
            .groupBy(F.window("ts", "10 seconds").alias("w"))
            .agg(F.sum("v").alias("s")))


def lifetime(ckpt, out, arm=False):
    src = FileStreamSource("parquet", in_dir, SCHEMA,
                          {"maxfilespertrigger": "1"})
    df = shape(DataFrame(spark, StreamingRelation(src)))
    ex = StreamExecution(spark, df._plan, FileSink("json", out, {}),
                         "append", ckpt, 0.1, None)
    if arm:
        FaultInjector().attach_stream(ex)   # plan from SPARK_TPU_FAULT_PLAN
    ex.process_all_available()
    return ex


def sink_files(out):
    return {os.path.basename(p): open(p, "rb").read()
            for p in sorted(glob.glob(os.path.join(out, "part-*")))}


deadline = time.monotonic() + timeout_s

if pid == 1:                                             # -- victim --
    os.makedirs(root, exist_ok=True)
    with open(pidfile, "w") as f:
        f.write(str(os.getpid()))
    while not os.path.exists(ready):
        if time.monotonic() > deadline:
            print("[p1] FAILED inputs never appeared", flush=True)
            os._exit(1)
        time.sleep(0.05)
    # the armed plan kills this process (os._exit(43)) mid-protocol;
    # reaching the end means the plan never fired — that is a failure
    lifetime(os.path.join(root, "ckpt"), os.path.join(root, "out"),
             arm=True)
    print("[p1] FAILED planned kill never fired", flush=True)
    os._exit(1)

# -- supervisor (pid 0) --
os.makedirs(in_dir, exist_ok=True)
for i, rows in enumerate(FEEDS):
    spark.createDataFrame({
        "ts": np.array([r[0] for r in rows], "datetime64[us]"),
        "k": [r[1] for r in rows],
        "v": np.array([r[2] for r in rows], np.int64),
    }).write.parquet(os.path.join(in_dir, f"f{i}"))
open(ready, "w").close()

def _dead(p):
    # the victim is the RUNNER's child, not ours: after the kill it
    # lingers as a zombie until the runner reaps it, so liveness has to
    # come from /proc state, not os.kill(p, 0)
    try:
        with open(f"/proc/{p}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


victim = None
while time.monotonic() < deadline:
    if victim is None and os.path.exists(pidfile):
        victim = int(open(pidfile).read())
    if victim is not None and _dead(victim):
        break                               # the kill landed
    time.sleep(0.05)
else:
    print("[p0] FAILED victim never died", flush=True)
    os._exit(1)

oracle_out = os.path.join(root, "oracle_out")
lifetime(os.path.join(root, "oracle_ckpt"), oracle_out)
oracle = sink_files(oracle_out)

ex = lifetime(os.path.join(root, "ckpt"), os.path.join(root, "out"))
got = sink_files(os.path.join(root, "out"))
if got != oracle or not oracle:
    print(f"[p0] PARTIAL got={sorted(got)} exp={sorted(oracle)}",
          flush=True)
    os._exit(1)
if ex.metrics["replayed_batches"] < 1:
    print(f"[p0] FAILED nothing replayed: {ex.metrics}", flush=True)
    os._exit(1)
print(f"[p0] OK {len(got)} replayed={ex.metrics['replayed_batches']}",
      flush=True)
os._exit(0)
