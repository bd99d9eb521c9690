"""Profile the ICI device-exchange tier's pieces on whatever devices
jax gives (2+ needed).

Three groups, each isolated so one failure cannot abort the rest of
the profile:

1. the collective over the exchange axis at pack-plane shapes —
   ``lax.all_to_all`` under ``shard_map``, the exchange's own step;
2. the end-to-end ``local_device_exchange`` (pack → stage-cached
   collective → unpack) in host wall-clock MB/s — the figure the
   distici bench lane's forced-CPU mesh approximates;
3. the host wire plane (encode + decode of identical outboxes) as the
   DCN-tier baseline the device tier is meant to beat.

Multi-device on a single host: the collective crosses the chips' ICI
links even though every participant is one process — exactly the
intra-pod data plane, minus process boundaries.
"""
import sys, time
sys.path.insert(0, "/root/repo")
import numpy as np
import spark_tpu  # noqa
import jax
import jax.numpy as jnp

print("devices:", jax.devices(), "backend:", jax.default_backend())

DEVS = jax.local_devices()
N_M = min(4, len(DEVS))
ITERS = 20

if N_M < 2:
    print(f"only {len(DEVS)} device(s): the exchange collective needs "
          "2+; nothing to profile")
    sys.exit(0)

from jax.sharding import PartitionSpec
from spark_tpu import types as T
from spark_tpu import wire
from spark_tpu.columnar import ColumnBatch, ColumnVector
from spark_tpu.parallel import ici
from spark_tpu.parallel.mesh import Mesh

mesh = Mesh(np.asarray(DEVS[:N_M]), (ici.ICI_AXIS,))
sharding = jax.sharding.NamedSharding(mesh, PartitionSpec(ici.ICI_AXIS))
rng = np.random.default_rng(7)


def coll_time(name, rows):
    """One packed data plane ((n_m*n_m, rows) int64, device i holding
    its (n_m, rows) outbound block), ITERS exchanges inside a fori_loop
    with a carried perturbation, one scalar fetch."""
    try:
        def body(x):
            def it(i, carry):
                moved, = ici._a2a_step(carry + i)
                return moved
            return jax.lax.fori_loop(0, ITERS, it, x)[0, 0]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=PartitionSpec(ici.ICI_AXIS),
            out_specs=PartitionSpec(), check_vma=False))
        x = jax.device_put(
            rng.integers(-99, 99, (N_M * N_M, rows)).astype(np.int64),
            sharding)
        _ = int(np.asarray(fn(x)))            # compile+warm
        t0 = time.perf_counter()
        _ = int(np.asarray(fn(x)))
        dt = (time.perf_counter() - t0) / ITERS
        mb = N_M * N_M * rows * 8 / 1e6
        print(f"{name:44s} {dt*1e3:9.3f} ms/iter {mb/dt/1e3:9.2f} GB/s",
              flush=True)
        return dt
    except Exception as e:
        print(f"{name:44s} FAILED: {str(e)[:300]}", flush=True)
        import traceback
        traceback.print_exc(limit=3)
        return None


# 1. the collective at the pack-plane sizes the exchange actually ships
for rows in (1 << 10, 1 << 14, 1 << 18):
    coll_time(f"lax.all_to_all  rows/peer={rows}", rows)


# 2/3. end-to-end exchange vs the host wire plane on identical outboxes
def batch(m):
    vals = rng.integers(-(1 << 40), 1 << 40, m)
    return ColumnBatch(["k"], [ColumnVector(vals, T.LongType(), None,
                                            None)], None, m)


for per in (1 << 12, 1 << 15):
    outboxes = [{r: [batch(per)] for r in range(N_M)}
                for _s in range(N_M)]
    tpl = batch(1)
    total = sum(wire.raw_nbytes(bs) for ob in outboxes
                for bs in ob.values())
    try:
        ici.local_device_exchange(outboxes, tpl)          # warm
        t0 = time.perf_counter()
        for _ in range(max(3, ITERS // 4)):
            ici.local_device_exchange(outboxes, tpl)
        dt = (time.perf_counter() - t0) / max(3, ITERS // 4)
        print(f"{'local_device_exchange rows/span=' + str(per):44s} "
              f"{dt*1e3:9.2f} ms/iter {total/dt/1e6:9.1f} MB/s",
              flush=True)
    except Exception as e:
        print(f"{'local_device_exchange rows/span=' + str(per):44s} "
              f"FAILED: {str(e)[:300]}", flush=True)
    try:
        t0 = time.perf_counter()
        for _ in range(max(3, ITERS // 4)):
            for ob in outboxes:
                for bs in ob.values():
                    wire.decode_batches(wire.encode_batches(bs))
        dt = (time.perf_counter() - t0) / max(3, ITERS // 4)
        print(f"{'wire encode+decode rows/span=' + str(per):44s} "
              f"{dt*1e3:9.2f} ms/iter {total/dt/1e6:9.1f} MB/s",
              flush=True)
    except Exception as e:
        print(f"{'wire encode+decode rows/span=' + str(per):44s} "
              f"FAILED: {str(e)[:300]}", flush=True)

print("done")
