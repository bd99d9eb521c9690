"""The sort aggregate's steps after its argsort, each timed alone on whatever
backend jax gives (ISSUE 32, steps 0-4): today's ``segment_sum`` and its
five-minute repairs, the run layout (running sums, the scatter of start
positions), the segmented scan in its three forms and the read at the
runs' ends, the keys' read, and the permutation as one plane against one
gather a column.  One JSON line a primitive: compile seconds, median run ms.

    python tools/prof_aggscan.py [log2 rows ...]        # default 20 22

Two run shapes a size: ``many`` (2.5 rows a run, the aggregate cell's) and
``few`` (300 runs, a star statement's).  Run by hand (through the builder's
tool for the chip); PERF.md carries the readings."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import spark_tpu  # noqa: F401  (x64)
import jax
import jax.numpy as jnp
from jax import lax
from spark_tpu import kernels as K

jax.config.update("jax_enable_compilation_cache", False)
rng = np.random.default_rng(0)
OUT = []


def timed(name, rows, fn, *args, check=None, **note):
    t0 = time.time()
    try:
        c = jax.jit(fn).lower(*args).compile()
    except Exception as e:                      # what the compiler refuses
        line = {"step": name, "rows": rows, "error": str(e)[:200]}
        print(json.dumps(line), flush=True)
        OUT.append(line)
        return None
    compile_s = time.time() - t0
    out = c(*args)
    jax.block_until_ready(out)
    runs = []
    for _ in range(5):
        t0 = time.time()
        jax.block_until_ready(c(*args))
        runs.append((time.time() - t0) * 1e3)
    line = {"step": name, "rows": rows, "compile_s": round(compile_s, 2),
            "run_ms": round(float(np.median(runs)), 3),
            "kind": jax.devices()[0].device_kind, **note}
    if check is not None:
        line["same"] = bool(check(out))
    print(json.dumps(line), flush=True)
    OUT.append(line)
    return out


def padded_scan(seg_ids, bufs, kinds, longest):
    """``kernels.segmented_scan`` with each buffer behind ``n`` slots of
    padding in the loop carry, read by ONE dynamic slice and written back in
    place: the form that is WRONG on a v5e from stride 2^17 on (``int_equal``
    false below: XLA:TPU's in-place ``dynamic_update_slice`` reads what it
    has written), kept here so that a newer compiler can be asked again."""
    n = seg_ids.shape[0]
    ops = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    padded_ids = jnp.concatenate([jnp.full(n, -1, seg_ids.dtype), seg_ids])

    def round_(state):
        d, rounds, padded = state
        same = lax.dynamic_slice(padded_ids, (n - d,), (n,)) == seg_ids
        return d * 2, rounds + 1, tuple(
            lax.dynamic_update_slice(p, jnp.where(same, ops[k](
                lax.dynamic_slice(p, (n - d,), (n,)), p[n:]), p[n:]), (n,))
            for p, k in zip(padded, kinds))

    _, rounds, padded = lax.while_loop(
        lambda st: st[0] < longest, round_,
        (jnp.int32(1), jnp.int32(0), tuple(
            jnp.concatenate([jnp.zeros(n, b.dtype), b]) for b in bufs)))
    return [p[n:] for p in padded], rounds


def static_scan(seg_ids, bufs, kinds, longest):
    """``kernels.segmented_scan`` as a chain of conditionals, one a power of
    two, each with a STATIC shift (which a fusion can read in place)."""
    n = seg_ids.shape[0]
    ops = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    vals, rounds, d = tuple(bufs), jnp.int32(0), 1
    while d < n:
        def step(vs, d=d):
            same = jnp.concatenate([jnp.zeros(d, bool),
                                    seg_ids[d:] == seg_ids[:-d]])
            return tuple(
                jnp.where(same, ops[k](jnp.concatenate([v[:d], v[:-d]]), v), v)
                for v, k in zip(vs, kinds))
        go = d < longest
        vals = lax.cond(go, step, lambda vs: vs, vals)
        rounds = rounds + go.astype(jnp.int32)
        d *= 2
    return list(vals), rounds


for lg in [int(a) for a in sys.argv[1:]] or [20, 22]:
    n = 1 << lg
    n_live = int(n * 0.69)                     # 2,880,404 of 4,194,304
    perm = jax.device_put(rng.permutation(n).astype(np.int32))
    i64 = [jax.device_put(rng.integers(-1 << 40, 1 << 40, n))
           for _ in range(3)]
    f64 = [jax.device_put(rng.random(n) * 1e4) for _ in range(2)]
    i8 = [jax.device_put(rng.integers(-1, 1, n).astype(np.int8))
          for _ in range(2)]
    for shape, n_runs in (("many", int(n_live / 2.5)), ("few", 300)):
        starts = np.zeros(n, bool)
        starts[np.sort(rng.choice(n_live, n_runs, replace=False))] = True
        starts[0] = True
        seg64 = np.where(np.arange(n) < n_live, np.cumsum(starts) - 1, n - 1)
        is_start = jax.device_put(starts)
        ids64 = jax.device_put(seg64.astype(np.int64))
        ids32 = jax.device_put(seg64.astype(np.int32))
        groups = int(starts.sum())
        tag = dict(shape=shape, groups=groups)
        bufs = [i64[0], f64[0], i64[1], f64[1], i64[2]]
        kinds = ["sum"] * 5

        # -- step 0: today's reduction, and its five-minute repairs -------
        ref_i = timed("0.segment_sum.i64.ids64", n,
                      lambda v, s: jax.ops.segment_sum(v, s, num_segments=n),
                      i64[0], ids64, **tag)
        ref_f = timed("0.segment_sum.f64.ids64", n,
                      lambda v, s: jax.ops.segment_sum(v, s, num_segments=n),
                      f64[0], ids64, **tag)
        timed("0.segment_sum.i64.ids32.sorted", n,
              lambda v, s: jax.ops.segment_sum(
                  v, s, num_segments=n, indices_are_sorted=True),
              i64[0], ids32, check=lambda o: (o == ref_i).all(), **tag)
        timed("0.segment_sum.f64.ids32.sorted", n,
              lambda v, s: jax.ops.segment_sum(
                  v, s, num_segments=n, indices_are_sorted=True),
              f64[0], ids32,
              check=lambda o: np.allclose(o, ref_f, rtol=1e-12), **tag)

        # -- step 1: the layout ------------------------------------------
        pos = jnp.arange(n, dtype=jnp.int32)
        ref_s = timed("1.start_of.set.drop", n,
                      lambda st, s: jnp.full(n, n, jnp.int32).at[
                          jnp.where(st, s, n)].set(pos, mode="drop"),
                      is_start, ids32, **tag)
        timed("1.start_of.set.drop.unique", n,
              lambda st, s: jnp.full(n, n, jnp.int32).at[
                  jnp.where(st, s, n + pos)].set(
                      pos, mode="drop", unique_indices=True),
              is_start, ids32, check=lambda o: (o == ref_s).all(), **tag)
        timed("1.start_of.set.drop.unique.sorted_claimed", n,
              lambda st, s: jnp.full(n, n, jnp.int32).at[
                  jnp.where(st, s, n)].set(
                      pos, mode="drop", unique_indices=True,
                      indices_are_sorted=True),
              is_start, ids32, check=lambda o: (o == ref_s).all(), **tag)
        timed("1.start_of.min.sorted", n,
              lambda s: jnp.full(n, n, jnp.int32).at[s].min(
                  pos, indices_are_sorted=True),
              ids32,
              check=lambda o: (np.asarray(o)[:groups]
                               == np.asarray(ref_s)[:groups]).all(), **tag)
        if shape == "many":
            want = np.cumsum(starts).astype(np.int32)
            timed("1.cumsum.i64", n,
                  lambda st: jnp.cumsum(st.astype(jnp.int64)), is_start)
            timed("1.cumsum.i32", n,
                  lambda st: jnp.cumsum(st, dtype=jnp.int32), is_start,
                  check=lambda o: (np.asarray(o) == want).all())
            timed("1.running_sum_i32.two_level", n,
                  lambda st: K.running_sum_i32(jnp, st), is_start,
                  check=lambda o: (np.asarray(o) == want).all())

        # -- step 2: the scan and the read at the runs' ends ---------------
        runs_np = np.diff(np.append(np.flatnonzero(starts), n_live))
        longest = jnp.int32(runs_np.max())
        tag2 = dict(tag, longest=int(runs_np.max()))
        ref_scan = timed("2.scan.while.rolled", n,
                         lambda s, *b: K.segmented_scan(jnp, s, b, kinds,
                                                        longest),
                         ids32, *bufs, **tag2)
        timed("2.scan.while.padded_carry", n,
              lambda s, *b: padded_scan(s, b, kinds, longest), ids32, *bufs,
              check=lambda o: all(
                  (np.asarray(a) == np.asarray(b)).all()
                  for a, b in zip(o[0], ref_scan[0])), **tag2)
        timed("2.scan.cond_chain.static_shift", n,
              lambda s, *b: static_scan(s, b, kinds, longest), ids32, *bufs,
              check=lambda o: all(
                  (np.asarray(a) == np.asarray(b)).all()
                  for a, b in zip(o[0], ref_scan[0])), **tag2)
        end_of = jax.device_put(np.minimum(np.append(
            np.append(np.flatnonzero(starts)[1:], n_live) - 1,
            np.full(n - groups, n - 1)), n - 1).astype(np.int32))
        if ref_scan is not None:
            at_end = timed("2.read_at_end.one_plane", n,
                           lambda e, *b: K.gather_columns(jnp, b, e),
                           end_of, *ref_scan[0], **tag)
            timed("2.read_at_end.u32_plane.plain", n,
                  lambda e, *b: jnp.stack(b)[:, e], end_of,
                  *[x.astype(jnp.uint32) for x in i64 + i64], **tag)
            timed("2.read_at_end.u32_plane.sorted_in_bounds", n,
                  lambda e, *b: jnp.stack(b).at[:, e].get(
                      indices_are_sorted=True, mode="promise_in_bounds"),
                  end_of, *[x.astype(jnp.uint32) for x in i64 + i64], **tag)
            timed("2.read_at_end.a_gather_a_buffer", n,
                  lambda e, *b: [x[e] for x in b], end_of, *ref_scan[0],
                  **tag)
            same_i = (np.asarray(at_end[0])[:groups]
                      == np.asarray(ref_i)[:groups]).all()
            gap = np.max(np.abs(np.asarray(at_end[1])[:groups]
                                - np.asarray(ref_f)[:groups])
                         / np.abs(np.asarray(ref_f)[:groups]))
            print(json.dumps({"step": "2.scan_vs_segment_sum", "rows": n,
                              "int_equal": bool(same_i),
                              "float_rel_gap": float(gap), **tag}),
                  flush=True)

        # -- step 3: the keys, read at each group's first row --------------
        start_of = jnp.minimum(ref_s, n - 1)
        valid = [x == 0 for x in i8]
        timed("3.keys.read_one_plane", n,
              lambda p, s, *c: K.gather_columns(jnp, c, p[s]),
              perm, start_of, i64[0], i64[1], *valid, **tag)
        timed("3.keys.permute_then_scatter", n,
              lambda p, st, s, *c: [
                  jnp.zeros(n, x.dtype).at[jnp.where(st, s, n)].set(
                      x[p], mode="drop") for x in c],
              perm, is_start, ids64, i64[0], i64[1], *valid, **tag)

    # -- step 4: what goes through ``perm`` ------------------------------
    cols = i8 + i64[:2] + i64 + f64              # the aggregate cell's
    timed("4.permute.one_plane", n,
          lambda p, *c: K.gather_columns(jnp, c, p), perm, *cols,
          columns=len(cols))
    timed("4.permute.a_gather_a_column", n,
          lambda p, *c: [x[p] for x in c], perm, *cols, columns=len(cols))
    timed("4.permute.one_plane.ints_only", n,
          lambda p, *c: K.gather_columns(jnp, c, p), perm, *cols[:-2],
          columns=len(cols) - 2)
    timed("4.permute.one_plane.f64_only", n,
          lambda p, *c: K.gather_columns(jnp, c, p), perm, *f64, columns=2)

os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/prof_aggscan.jsonl", "w") as f:
    for line in OUT:
        f.write(json.dumps(line) + "\n")
