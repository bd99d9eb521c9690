"""Profile the device-side pieces of the cross-process JOIN lanes with
the bench's honest methodology (ITERS inside one fori_loop with a
carried dependency, one scalar fetch): the hash-bucket and range-span
routers, the (null_flag, key) tie sort that makes span slices sorted
runs, the build-side sort the presorted-merge path skips, and the
probe searchsorted + output gather that both local joins share.

Run by hand on whatever backend jax gives (bench.py no longer
schedules probes)."""
import sys, time
sys.path.insert(0, "/root/repo")
import numpy as np
import spark_tpu  # noqa
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), "backend:", jax.default_backend())

N = 1 << 21          # probe rows
M = 1 << 19          # build rows
N_FINE = 64          # fine hash partitions (8/proc x 8 procs)
N_CUTS = 63          # range cut points (64 spans)
ITERS = 20

rng = np.random.default_rng(7)
pk = jnp.asarray(rng.integers(0, 1 << 20, N).astype(np.int64))
bk = jnp.asarray(np.sort(rng.integers(0, 1 << 20, M)).astype(np.int64))
cuts = jnp.asarray(np.linspace(0, 1 << 20, N_CUTS).astype(np.int64))


def loop_time(name, step, *args, iters=None):
    """step(i, *args) -> scalar contribution; fori_loop of ITERS.
    Variants are isolated: one Mosaic/compile failure must not abort
    the rest of the profile."""
    it = iters or ITERS

    def run(args):
        def body(i, acc):
            return acc + step(i.astype(jnp.int64), *args)
        return jax.lax.fori_loop(0, it, body, jnp.int64(0))
    try:
        f = jax.jit(run)
        _ = int(np.asarray(f(args)))          # compile+warm
        t0 = time.perf_counter()
        _ = int(np.asarray(f(args)))
        dt = (time.perf_counter() - t0) / it
        print(f"{name:44s} {dt*1e3:9.2f} ms/iter {N/dt/1e6:9.1f} Mrows/s",
              flush=True)
        return dt
    except Exception as e:
        print(f"{name:44s} FAILED: {str(e)[:300]}", flush=True)
        import traceback
        traceback.print_exc(limit=3)
        return None


from spark_tpu import kernels
from spark_tpu.expressions import Hash64

# 1. baseline: input perturbation only (subtract from everything else)
loop_time("perturb + sum (baseline)",
          lambda i, p, b: ((p ^ i).sum() & jnp.int64(1)), pk, bk)

# 2. routers: hash bucketing vs range span assignment (searchsorted)
loop_time("hash bucket (Hash64 mix %% n_fine)",
          lambda i, p, b: (Hash64._mix(jnp, p ^ i).astype(jnp.uint64)
                           % jnp.uint64(N_FINE)).astype(jnp.int32)
          .sum().astype(jnp.int64) & jnp.int64(1), pk, bk)
loop_time("range_bucket (searchsorted vs cuts)",
          lambda i, p, b: kernels.range_bucket(jnp, p ^ i, cuts)
          .sum().astype(jnp.int64) & jnp.int64(1), pk, bk)

# 3. the routing sort: 1-key (hash path) vs 3-key tie sort (range path:
# pid + null_flag + encoded key -> per-span SORTED runs, one device sort)
loop_time("argsort 1 key (span id)",
          lambda i, p, b: kernels.multi_key_argsort(
              jnp, [kernels.range_bucket(jnp, p ^ i, cuts)], N)[0]
          .astype(jnp.int64) & jnp.int64(1), pk, bk)
loop_time("argsort 3 keys (span,flag,key tie sort)",
          lambda i, p, b: kernels.multi_key_argsort(
              jnp, [kernels.range_bucket(jnp, p ^ i, cuts),
                    (p & jnp.int64(1)).astype(jnp.int8), p ^ i], N)[0]
          .astype(jnp.int64) & jnp.int64(1), pk, bk)

# 4. the build-side sort PMergeJoin SKIPS (presorted runs merge on host):
# what the hash join pays per local join to order its build side
loop_time("build argsort 2 keys (what merge skips)",
          lambda i, p, b: kernels.multi_key_argsort(
              jnp, [(b & jnp.int64(1)).astype(jnp.int8), b ^ i], M)[0]
          .astype(jnp.int64) & jnp.int64(1), pk, bk, iters=ITERS)

# 5. shared local-join core: probe searchsorted + first-match gather
def probe_step(i, p, b):
    lo = kernels.searchsorted(jnp, b, p + i, side="left")
    return lo.sum().astype(jnp.int64) & jnp.int64(1)

loop_time("probe searchsorted (sorted build)", probe_step, pk, bk)
loop_time("output gather (take rows)",
          lambda i, p, b: p[jnp.clip(p ^ i, 0, N - 1) % N]
          .sum() & jnp.int64(1), pk, bk)
print("done")
