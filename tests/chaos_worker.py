"""Chaos worker: a checkpointed multibatch aggregation that SIGKILLs
itself mid-scan on the first gang attempt (marker file absent), then —
relaunched by the supervising launcher — resumes from the multibatch
checkpoint and completes.  Driven by tests/test_chaos_restart.py."""
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

data_dir, ckpt_dir, marker, out_path = sys.argv[1:5]

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# persistent jit cache (the directory is spark_tpu's own default inside the
# checkout; same policy as conftest.py): worker subprocesses otherwise
# recompile every program on every test run
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
from spark_tpu.sql.session import SparkSession          # noqa: E402
from spark_tpu.sql import functions as F                # noqa: E402
from spark_tpu.sql import multibatch as mb              # noqa: E402

first_attempt = not os.path.exists(marker)

# instrument checkpoint save/load so the harness can assert the resume
orig_save = mb.MultiBatchExecution._ckpt_save
orig_load = mb.MultiBatchExecution._ckpt_load
saves = {"n": 0}


def save(self, path, n_batches, merger):
    orig_save(self, path, n_batches, merger)
    saves["n"] += 1
    print(f"CKPT-SAVE {n_batches}", flush=True)
    if first_attempt and saves["n"] >= 2:
        open(marker, "w").close()
        print("CHAOS-KILL", flush=True)
        os.kill(os.getpid(), signal.SIGKILL)


def load(self, ckpt):
    skip, merger = orig_load(self, ckpt)
    print(f"CKPT-SKIP {skip}", flush=True)
    return skip, merger


mb.MultiBatchExecution._ckpt_save = save
mb.MultiBatchExecution._ckpt_load = load

spark = SparkSession.builder.appName("chaos").getOrCreate()
spark.conf.set("spark.tpu.scan.maxBatchRows", "256")
spark.conf.set("spark.tpu.multibatch.checkpointDir", ckpt_dir)
spark.conf.set("spark.tpu.multibatch.checkpointInterval", "1")

df = (spark.read.parquet(data_dir).groupBy("k")
      .agg(F.sum("v").alias("s"), F.count("*").alias("c")))
rows = sorted((r["k"], r["s"], r["c"]) for r in df.collect())
with open(out_path, "w") as f:
    for k, s, c in rows:
        f.write(f"{k},{s},{c}\n")
print("CHAOS-QUERY-OK", flush=True)
