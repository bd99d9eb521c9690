"""Profile the run-plane device lane on whatever backend jax gives
(run by hand; bench.py no longer schedules probes).

Three groups, each isolated so one compile failure cannot abort the
rest of the profile:

1. plane expansion: the shape-stable searchsorted-gather
   (``run_expand``, the jit-lane form an untaught operator triggers)
   vs ``jnp.repeat(total_repeat_length=...)`` (the ``to_device`` form)
   vs the counted host ``np.repeat`` baseline — the figure that says
   what an in-trace expansion costs when a stage is NOT fully taught;
2. the keyless plane aggregate (segment-sum of a row mask over
   ``run_row_ids``, then values × live-counts — no arithmetic on
   expanded rows) vs the same masked sum over the expanded dense
   column, at plane shapes the distrle bench ships;
3. the stage lane end to end: an eligible filter+aggregate SQL query
   over a run leaf with ``spark.tpu.stage.runPlanes`` on vs off —
   the single-process twin of the distrleplane bench pair.
"""
import sys, time
sys.path.insert(0, "/root/repo")
import numpy as np
import spark_tpu  # noqa
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), "backend:", jax.default_backend())

ITERS = 20
CAP = 1 << 18
rng = np.random.default_rng(11)

from spark_tpu import kernels as K
from spark_tpu import types as T
from spark_tpu.columnar import (ColumnBatch, ColumnVector, RunColumnVector,
                                PlaneColumnVector, pad_capacity)


def timed(name, fn, *args):
    """Compile+warm once, then ITERS dispatches with one scalar fetch."""
    try:
        _ = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _i in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / ITERS
        print(f"{name:44s} {dt*1e3:9.3f} ms/iter", flush=True)
        return dt
    except Exception as e:
        print(f"{name:44s} FAILED: {str(e)[:300]}", flush=True)
        import traceback
        traceback.print_exc(limit=3)
        return None


def plane(n_runs):
    """A full-capacity plane: n_runs values, equal lengths summing to
    CAP, zero-padded to the pad_capacity bucket."""
    pc = pad_capacity(n_runs)
    vals = np.zeros(pc, np.int64)
    vals[:n_runs] = rng.integers(0, 1 << 20, n_runs)
    lens = np.zeros(pc, np.int64)
    lens[:n_runs] = CAP // n_runs
    return jnp.asarray(vals), jnp.asarray(lens)


# 1. expansion forms at run counts the distrle shape actually ships
for n_runs in (256, 4096):
    pv, pl = plane(n_runs)

    @jax.jit
    def gather_expand(v, l):
        return K.run_expand(jnp, v, l, CAP)

    @jax.jit
    def repeat_expand(v, l):
        return jnp.repeat(v, l, total_repeat_length=CAP)

    timed(f"searchsorted-gather expand runs={n_runs}", gather_expand, pv, pl)
    timed(f"jnp.repeat expand      runs={n_runs}", repeat_expand, pv, pl)
    hv, hl = np.asarray(pv), np.asarray(pl)
    t0 = time.perf_counter()
    for _i in range(ITERS):
        _ = np.repeat(hv, hl)
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{'host np.repeat expand  runs=' + str(n_runs):44s} "
          f"{dt*1e3:9.3f} ms/iter", flush=True)


# 2. the keyless plane aggregate vs the expanded dense sum, both under
#    a data-dependent row mask (the post-filter shape in the stage lane)
for n_runs in (256, 4096):
    pv, pl = plane(n_runs)
    mask = jnp.asarray(rng.random(CAP) < 0.5)

    @jax.jit
    def plane_sum(v, l, m):
        ids = K.run_row_ids(jnp, l, CAP)
        live = jax.ops.segment_sum(m.astype(jnp.int64), ids,
                                   num_segments=int(v.shape[0]))
        return jnp.sum(v * live), jnp.sum(live)

    @jax.jit
    def dense_sum(v, l, m):
        d = jnp.repeat(v, l, total_repeat_length=CAP)
        return jnp.sum(jnp.where(m, d, 0)), jnp.sum(m.astype(jnp.int64))

    timed(f"plane segsum agg       runs={n_runs}", plane_sum, pv, pl, mask)
    timed(f"expand-then-sum agg    runs={n_runs}", dense_sum, pv, pl, mask)


# 3. the stage lane end to end: runPlanes on vs off over one run leaf
try:
    import spark_tpu.config as C
    from spark_tpu.sql.session import SparkSession
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame

    N_RUNS, REP = 256, CAP // 256
    heads = np.arange(N_RUNS, dtype=np.int64)
    rv = RunColumnVector(heads, np.full(N_RUNS, REP, np.int64), T.int64)
    vv = ColumnVector(np.arange(CAP, dtype=np.int64) % 7, T.int64)
    leaf = ColumnBatch(["ts", "v"], [rv, vv], None, CAP)
    q = (f"SELECT count(*) AS c, sum(ts) AS st FROM pr_ev "
         f"WHERE ts < {N_RUNS // 2}")

    s = SparkSession.builder.appName("prof_runs").getOrCreate()
    s.conf.set("spark.tpu.mesh.shards", "1")
    DataFrame(s, L.LocalRelation(leaf)).createOrReplaceTempView("pr_ev")
    for mode, on in (("planes-on", "true"), ("planes-off", "false")):
        s.conf.set(C.STAGE_RUN_PLANES.key, on)
        _ = s.sql(q).collect()                        # compile+warm
        t0 = time.perf_counter()
        for _i in range(max(3, ITERS // 4)):
            rows = s.sql(q).collect()
        dt = (time.perf_counter() - t0) / max(3, ITERS // 4)
        print(f"{'stage lane filter+agg ' + mode:44s} {dt*1e3:9.3f} ms/iter"
              f"  (c={rows[0]['c']}, st={rows[0]['st']})", flush=True)
    s.conf.set(C.STAGE_RUN_PLANES.key, "true")
except Exception as e:
    print(f"{'stage lane filter+agg':44s} FAILED: {str(e)[:300]}", flush=True)
    import traceback
    traceback.print_exc(limit=3)

print("done")
