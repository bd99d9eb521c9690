"""Profile TPU agg pieces with the bench's honest methodology:
ITERS inside one fori_loop with carried dependency, one scalar fetch."""
import sys, time
sys.path.insert(0, "/root/repo")
import numpy as np
import spark_tpu  # noqa
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), "backend:", jax.default_backend())

N = 1 << 22
GROUPS = 1024
B = 4096
ITERS = 20

rng = np.random.default_rng(7)
kd = jnp.asarray(rng.integers(0, GROUPS, N).astype(np.int64))
vd = jnp.asarray(rng.integers(0, 100, N).astype(np.int64))


def loop_time(name, step, *args, iters=None):
    """step(i, *args) -> scalar contribution; fori_loop of ITERS.

    Each variant is isolated: a compile failure (e.g. a Mosaic
    regression in the Pallas step) must not abort the remaining
    measurements — one run has to yield the full profile."""
    it = iters or ITERS

    def run(args):
        def body(i, acc):
            return acc + step(i.astype(jnp.int64), *args)
        return jax.lax.fori_loop(0, it, body, jnp.int64(0))
    try:
        f = jax.jit(run)
        _ = int(np.asarray(f(args)))          # compile+warm
        t0 = time.perf_counter()
        acc = int(np.asarray(f(args)))
        dt = (time.perf_counter() - t0) / it
        print(f"{name:44s} {dt*1e3:9.2f} ms/iter {N/dt/1e6:9.1f} Mrows/s",
              flush=True)
        return dt
    except Exception as e:
        print(f"{name:44s} FAILED: {str(e)[:300]}", flush=True)
        import traceback
        traceback.print_exc(limit=3)
        return None


from spark_tpu import pallas_agg, kernels
from spark_tpu.columnar import ColumnBatch, ColumnVector
from spark_tpu.expressions import Col
from spark_tpu.aggregates import Sum, CountStar

# 1. perturb only (baseline: the bench's input mutation)
def perturb(i, k, v):
    k2 = k ^ (i & jnp.int64(GROUPS - 1))
    v2 = v + i
    return (k2.sum() & jnp.int64(1)) + (v2.sum() & jnp.int64(1))

loop_time("perturb + 2 sums (baseline)", perturb, kd, vd)

# 2. plane assembly + pallas accumulate
def pal_step(i, k, v):
    k2 = k ^ (i & jnp.int64(GROUPS - 1))
    v2 = v + i
    b32 = jnp.clip(k2.astype(jnp.int32), 0, B - 1)
    lo = (v2 & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    planes = jnp.stack([jnp.ones(N, jnp.bfloat16)] +
                       [((lo >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
                         ).astype(jnp.bfloat16) for j in range(4)], axis=-1)
    tot = pallas_agg.grouped_accumulate(b32, planes, jnp.int32(B // 512), B)
    return tot.sum() & jnp.int64(1)

loop_time("assemble + pallas accumulate", pal_step, kd, vd)

# 3. full kernels.grouped_aggregate (MXU/pallas path)
def full_step(i, k, v):
    k2 = k ^ (i & jnp.int64(GROUPS - 1))
    v2 = v + i
    batch = ColumnBatch(
        ["k", "v"],
        [ColumnVector(k2, spark_tpu.types.int64, None, None),
         ColumnVector(v2, spark_tpu.types.int64, None, None)], None, N)
    out = kernels.grouped_aggregate(
        jnp, batch, [Col("k")], [(Sum(Col("v")), "s"), (CountStar(), "c")],
        bucket_cap=B)
    return out.vectors[1].data.sum() & jnp.int64(1)

loop_time("kernels.grouped_aggregate (auto path)", full_step, kd, vd)

# 4. sorted path
kernels.MXU_AGG_ENABLED = False
loop_time("kernels.grouped_aggregate (sorted)", full_step, kd, vd)
kernels.MXU_AGG_ENABLED = None

# 5. primitives under the same loop
loop_time("lax.sort int64",
          lambda i, k, v: jax.lax.sort(v + i)[0] & jnp.int64(1), kd, vd)
loop_time("lax.sort int32",
          lambda i, k, v: jax.lax.sort(
              (v + i).astype(jnp.int32))[0].astype(jnp.int64) & jnp.int64(1),
          kd, vd)
loop_time("argsort int64",
          lambda i, k, v: jnp.argsort(v + i)[0] & jnp.int64(1), kd, vd)
loop_time("2-col sort (key+perm) int64",
          lambda i, k, v: jax.lax.sort((v + i, k))[1][0] & jnp.int64(1),
          kd, vd)

# 6. radix argsort candidate vs the bitonic (the sort-lane decision
# point: 0.22x baseline today; radix is dense one-hot/cumsum/scatter)
loop_time("radix_argsort bits=4",
          lambda i, k, v: kernels.radix_argsort(
              jnp, v + i).astype(jnp.int64)[0] & jnp.int64(1), kd, vd,
          iters=3)
loop_time("radix_argsort bits=8",
          lambda i, k, v: kernels.radix_argsort(
              jnp, v + i, bits=8).astype(jnp.int64)[0] & jnp.int64(1),
          kd, vd, iters=3)
loop_time("lax.sort argsort baseline (2-op)",
          lambda i, k, v: jax.lax.sort(
              (v + i, jnp.arange(N, dtype=jnp.int32)),
              num_keys=1)[1][0].astype(jnp.int64) & jnp.int64(1), kd, vd)
print("done")
