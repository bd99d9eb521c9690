"""Headline benchmarks; needs a TPU and fails without one.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Primary metric — hash aggregate with grouping keys, rows/sec.  Reference
baseline: Spark Tungsten "codegen + vectorized hashmap" at 93.5 M rows/s
(`sql/core/src/test/.../benchmark/AggregateBenchmark.scala:125-131`,
i7-4960HQ) — see BASELINE.md.  Same workload shape: N rows, grouped
sum/count over a keyed column, executed through the planner as one fused
XLA program; the aggregation runs on the MXU
(`kernels._mxu_grouped_aggregate`: one-hot matmul over 8-bit limb planes,
bit-exact int64 sums).

Secondary metric (reported in the same JSON object) — a TPC-DS q3-shaped
pipeline: fact⋈dim broadcast join → filter → grouped sum → sort, vs the
Spark broadcast-hash-join baseline of 65.3 M rows/s
(`JoinBenchmark.scala:42-47`).

Timing methodology: the per-batch step runs ITERS times inside one
`lax.fori_loop` with a carried dependency on both the row count and the
aggregated values (nothing can be hoisted or dead-code-eliminated), and
one scalar is fetched at the end — dispatch and host-link round-trips are
amortized the way a real pipeline amortizes them over a stream of batches.
Inputs are perturbed per iteration from the carried index.

One process: it imports jax, holds the chip and runs every device lane
in-process.  ``jax.devices()[0].platform != "tpu"`` is an error (exit 1),
and so is a lane that raises — there is no CPU attempt, no shrunken shape
and no zero-valued result line.  The two-process distributed lanes launch
their workers under ``JAX_PLATFORMS=cpu`` by design; they never need the
chip.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time

BASELINE_AGG_ROWS_PER_S = 93.5e6    # AggregateBenchmark.scala:125-131
BASELINE_JOIN_ROWS_PER_S = 65.3e6   # JoinBenchmark.scala:42-47
BASELINE_SORT_ROWS_PER_S = 188.4e6  # SortBenchmark.scala:120-128 (radix)
BASELINE_SCAN_ROWS_PER_S = 73.0e6   # ParquetReadBenchmark.scala:140-143

N = 1 << 22          # rows per iteration for the agg bench (static batch)
ITERS = 20
GROUPS = 1024
RESULT_CAP = 8192    # static result capacity (>= bucket cap of MXU path)

J_FACT = 1 << 21     # q3-shape: fact rows per iteration
J_DIM = 2048         # q3-shape: dimension rows (broadcast side)
J_BRANDS = 64
J_ITERS = 10

S_ROWS = 1 << 22     # sort lane: rows per iteration (25M-longs baseline shape)
S_ITERS = 10

P_ROWS = 1 << 22     # parquet scan lane: rows in the generated file
P_COLS = 10          # wide file; pruning must read only the summed column
P_REPS = 4

SH_CAP = 1 << 18     # shuffle lane: rows per source batch
SH_BATCHES = 8       # source batches per exchange pass
SH_RECEIVERS = 8     # fan-out (the repo's 8-process world)
SH_THREADS = 4       # fetch-pool width (shuffle.io.fetchThreads default)

DJ_ROWS = 1 << 17    # distributed-join lane: rows per table (full dataset)
DJ_KEYS = 1 << 14    # join-key cardinality (multiplicity 8 per side)
DS_ROWS = 1 << 18    # distsort lane: probe rows (full dataset, SKEWED keys)
DS_BUILD = 1 << 16   # distsort lane: build rows (uniform, multiplicity 16)
DS_KEYS = 1 << 12    # distsort key cardinality; half the probe mass sits
DS_HOT = 77          # on this ONE hot key (the skew under test)
DD_ROWS = 24000      # distdict lane: rows per table (low-cardinality keys)
DD_KEYS = 2500       # distinct fat words (~30 B each: dict ~75 KiB/column)
DR_ROWS = 1 << 18    # distrle lane: time-series rows (full dataset) —
                     # sized so the exchange dwarfs the barrier overhead
DR_KEYS = 256        # distinct timestamps — each repeats 1024x, so the
                     # sorted spans carry long runs in ts/sensor/status
DA_ROWS = 1 << 20    # distadapt lane: rows per table (full dataset)
DA_KEYS = 1 << 13    # join-key cardinality
DA_CUT = 3           # right-side filter: bonus < 3 keeps ~2% of rows, a
                     # ~50x misestimate vs the plan-time raw-leaf probe
DA_PAY = 12          # left payload columns: the mass the frozen hash
                     # shuffle ships and the demoted broadcast never does
SC_ROWS = 1 << 14    # stagecache lane: fact rows (full dataset) — sized
                     # for compile-vs-dispatch accounting, not throughput
SC_KEYS = 1 << 10    # dim-key cardinality (dim side UNIQUE: fanout 1, so
                     # the per-op baseline replays without overflow retry)
GG_ROWS = 1 << 15    # distgrace lane: rows per table (full dataset)
GG_KEYS = 1 << 11    # join-key cardinality (multiplicity 16 on the right)
GG_BUDGET = 96 << 10  # host budget: below EVERY reducer's drained share
                      # (~128 KiB/side at 2 procs) but above each of the
                      # 32 grace buckets (~24 KiB both sides)

#: per-lane worker-process timeout (the two-process CPU lanes)
CHILD_TIMEOUT_S = int(os.environ.get("SPARK_TPU_BENCH_CHILD_TIMEOUT", "900"))
#: timed repetitions per lane; the reported figure is the MEDIAN of the
#: runs, which shields the tracked metric from one-off host stalls
#: (GC pause, cron neighbor) that a single sample eats
BENCH_RUNS = max(3, int(os.environ.get("SPARK_TPU_BENCH_RUNS", "3")))
#: pinned BLAS/OpenMP pool width: unpinned pools size to
#: the container's nproc, making run-to-run numbers depend on co-tenant
#: load; the pin is recorded in the output JSON for comparability
BENCH_THREADS = int(os.environ.get("SPARK_TPU_BENCH_THREADS", "4"))
_THREAD_ENV_VARS = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                    "MKL_NUM_THREADS", "NUMEXPR_NUM_THREADS")


def _slice_batch(batch, cap: int):
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    vecs = [ColumnVector(v.data[:cap], v.dtype,
                         None if v.valid is None else v.valid[:cap],
                         v.dictionary) for v in batch.vectors]
    rv = None if batch.row_valid is None else batch.row_valid[:cap]
    return ColumnBatch(batch.names, vecs, rv, cap)


def _median_rate(timed_fn, work_items: int) -> float:
    """One warm call (compile/populate caches), then ``BENCH_RUNS`` timed
    calls; returns the MEDIAN rows/sec so a single stalled run cannot
    move the tracked metric."""
    timed_fn()
    rates = []
    for _ in range(BENCH_RUNS):
        t0 = time.perf_counter()
        timed_fn()
        rates.append(work_items / (time.perf_counter() - t0))
    return statistics.median(rates)


def _bench_hash_agg(jax, jnp, np, session):
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.kernels import compact
    from spark_tpu.sql import functions as F
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import QueryExecution

    rng = np.random.default_rng(7)
    keys = rng.integers(0, GROUPS, N).astype(np.int64)
    vals = rng.integers(0, 100, N).astype(np.int64)
    df = session.createDataFrame({"k": keys, "v": vals})
    q = df.groupBy("k").agg(F.sum("v").alias("s"), F.count("*").alias("c"))
    pq = QueryExecution(session, q._plan).planned
    physical = pq.physical

    def step(leaves, bump):
        # BOTH columns depend on the carried index — keys via an XOR that
        # preserves [0, GROUPS) — so nothing is loop-invariant.
        perturbed = []
        for b in leaves:
            vecs = []
            for name, v in zip(b.names, b.vectors):
                if name == "v":
                    data = v.data + bump
                elif name == "k":
                    data = v.data ^ (bump & jnp.int64(GROUPS - 1))
                else:
                    data = v.data
                vecs.append(ColumnVector(data, v.dtype, v.valid, v.dictionary))
            perturbed.append(ColumnBatch(b.names, vecs, b.row_valid,
                                         b.capacity))
        ctx = P.ExecContext(jnp, perturbed)
        out = physical.run(ctx)
        c = compact(jnp, _slice_batch(out, RESULT_CAP))
        return c, c.num_rows()

    def run_loop(leaves):
        def body(i, acc):
            c, nr = step(leaves, i.astype(jnp.int64))
            s_dep = c.vectors[1].data.sum()
            return acc + nr + (s_dep & jnp.int64(1))
        return jax.lax.fori_loop(0, ITERS, body, jnp.int64(0))

    dev_leaves = tuple(b.to_device() for b in pq.leaves)

    # correctness gate: one un-perturbed run vs the numpy oracle
    c0, nr0 = jax.jit(lambda l: step(l, jnp.int64(0)))(dev_leaves)
    assert int(np.asarray(nr0)) == GROUPS, int(np.asarray(nr0))
    got_k = np.asarray(c0.vectors[0].data)[:GROUPS]
    got_s = np.asarray(c0.vectors[1].data)[:GROUPS]
    expect = np.zeros(GROUPS, np.int64)
    np.add.at(expect, keys, vals)
    order = np.argsort(got_k)
    assert np.array_equal(got_s[order], expect), "sum mismatch vs oracle"

    loop = jax.jit(run_loop)

    def timed():
        acc = int(np.asarray(loop(dev_leaves)))    # one fetch syncs all iters
        assert acc >= GROUPS * ITERS, acc
    return _median_rate(timed, N * ITERS)


def _bench_q3_join(jax, jnp, np, session):
    """TPC-DS q3 shape: fact ⋈ dim (broadcast) → filter → group-sum → sort."""
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.kernels import compact
    from spark_tpu.sql import functions as F
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import QueryExecution

    rng = np.random.default_rng(11)
    f_sk = rng.integers(0, J_DIM, J_FACT).astype(np.int64)
    f_price = rng.integers(1, 1000, J_FACT).astype(np.int64)
    d_sk = np.arange(J_DIM, dtype=np.int64)
    d_brand = rng.integers(0, J_BRANDS, J_DIM).astype(np.int64)
    d_year = rng.integers(1998, 2003, J_DIM).astype(np.int64)

    fact = session.createDataFrame({"sk": f_sk, "price": f_price})
    dim = session.createDataFrame({"d_sk": d_sk, "brand": d_brand,
                                   "year": d_year})
    q = (fact.join(dim, fact["sk"] == dim["d_sk"])
             .filter(dim["year"] == 2000)
             .groupBy("brand").agg(F.sum("price").alias("rev"))
             .orderBy(F.col("rev").desc()))
    pq = QueryExecution(session, q._plan).planned
    physical = pq.physical

    def step(leaves, bump):
        # fact keys AND values depend on the carried index (key XOR
        # preserves [0, J_DIM)) so the join build/probe cannot be hoisted
        # out of the timing loop as loop-invariant code.
        perturbed = []
        for b in leaves:
            vecs = []
            for name, v in zip(b.names, b.vectors):
                if name == "price":
                    data = v.data + bump
                elif name == "sk":
                    data = v.data ^ (bump & jnp.int64(J_DIM - 1))
                else:
                    data = v.data
                vecs.append(ColumnVector(data, v.dtype, v.valid, v.dictionary))
            perturbed.append(ColumnBatch(b.names, vecs, b.row_valid,
                                         b.capacity))
        ctx = P.ExecContext(jnp, perturbed)
        out = physical.run(ctx)
        c = compact(jnp, _slice_batch(out, RESULT_CAP))
        return c, c.num_rows()

    def run_loop(leaves):
        def body(i, acc):
            c, nr = step(leaves, i.astype(jnp.int64))
            s_dep = c.vectors[1].data.sum()
            return acc + nr + (s_dep & jnp.int64(1))
        return jax.lax.fori_loop(0, J_ITERS, body, jnp.int64(0))

    dev_leaves = tuple(b.to_device() for b in pq.leaves)

    # correctness gate vs numpy oracle
    c0, nr0 = jax.jit(lambda l: step(l, jnp.int64(0)))(dev_leaves)
    sel = d_year[f_sk] == 2000
    expect = np.zeros(J_BRANDS, np.int64)
    np.add.at(expect, d_brand[f_sk[sel]], f_price[sel])
    # prices are >= 1, so sum > 0 iff the brand matched any fact row
    n_expected = int((expect > 0).sum())
    got_n = int(np.asarray(nr0))
    got_rev = np.asarray(c0.vectors[1].data)[:got_n]
    exp_rev = np.sort(expect[expect > 0])[::-1]
    assert got_n == n_expected, (got_n, n_expected)
    assert np.array_equal(np.sort(got_rev)[::-1], exp_rev), "q3 rev mismatch"

    loop = jax.jit(run_loop)
    return _median_rate(lambda: int(np.asarray(loop(dev_leaves))),
                        J_FACT * J_ITERS)


def _bench_sort(jax, jnp, np, session):
    """Global sort of S_ROWS random int64 keys through the planner, vs the
    reference radix sort at 188.4 M rows/s (`SortBenchmark.scala:120-128`).
    """
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.sql import functions as F
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import QueryExecution

    rng = np.random.default_rng(13)
    xs = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                      S_ROWS, dtype=np.int64)
    df = session.createDataFrame({"x": xs}).orderBy(F.col("x"))
    pq = QueryExecution(session, df._plan).planned
    physical = pq.physical

    def step(leaves, bump):
        perturbed = []
        for b in leaves:
            vecs = [ColumnVector(v.data ^ bump, v.dtype, v.valid,
                                 v.dictionary) for v in b.vectors]
            perturbed.append(ColumnBatch(b.names, vecs, b.row_valid,
                                         b.capacity))
        ctx = P.ExecContext(jnp, perturbed)
        out = physical.run(ctx)
        return out.vectors[0].data

    def run_loop(leaves):
        def body(i, acc):
            s = step(leaves, i.astype(jnp.int64))
            # every 64k-th element of the SORTED output feeds the carry:
            # the whole permutation is live, nothing hoists
            return acc + s[:: 1 << 16].sum() + s[0] + s[-1]
        return jax.lax.fori_loop(0, S_ITERS, body, jnp.int64(0))

    dev_leaves = tuple(b.to_device() for b in pq.leaves)

    # correctness gate
    s0 = np.asarray(jax.jit(lambda l: step(l, jnp.int64(0)))(dev_leaves))
    assert np.array_equal(s0, np.sort(xs)), "sort mismatch vs numpy"

    loop = jax.jit(run_loop)
    return _median_rate(lambda: int(np.asarray(loop(dev_leaves))),
                        S_ROWS * S_ITERS)


def _bench_parquet_scan(np, session):
    """End-to-end parquet scan+sum of one int column out of a P_COLS-wide
    file (pruned read), vs the vectorized reader at 73 M rows/s
    (`ParquetReadBenchmark.scala:140-143`).  Wall-clock includes file IO —
    the relation cache is cleared per repetition."""
    import pandas as pd

    from spark_tpu import io as tio
    from spark_tpu.sql import functions as F

    path = os.path.join(tempfile.gettempdir(),
                        f"spark_tpu_bench_scan_{P_ROWS}x{P_COLS}.parquet")
    marker = os.path.join(path, "_SUCCESS")
    if not os.path.exists(marker):
        rng = np.random.default_rng(17)
        cols = {"x": rng.integers(0, 1 << 30, P_ROWS).astype(np.int64)}
        for i in range(P_COLS - 1):
            cols[f"pad{i}"] = rng.integers(0, 1000, P_ROWS).astype(np.int64)
        os.makedirs(path, exist_ok=True)
        pd.DataFrame(cols).to_parquet(
            os.path.join(path, "part-000.parquet"), index=False,
            row_group_size=1 << 20)
        open(marker, "w").close()

    df = session.read.parquet(path).agg(F.sum("x").alias("s"))
    tio._relation_cache.clear()
    (expect,), = df.collect()               # warm-up + self-consistency

    def timed():
        for _ in range(P_REPS):
            tio._relation_cache.clear()
            (s,), = df.collect()
            assert s == expect
    return _median_rate(timed, P_ROWS * P_REPS)


def _bench_shuffle(np):
    """Shuffle data-plane lane: one routed exchange, new plane vs seed.

    SH_BATCHES source batches route to SH_RECEIVERS receivers.  The NEW
    plane buckets each source batch once (``kernels.partition_bucket``,
    untimed here — it rides the device exchange step in production),
    then times encode→write→read→decode of the compact slices through
    a ``SH_THREADS``-wide pool (the wire codec + fetch-pool path of
    ``hostshuffle``).  The SEED plane is timed over the SAME logical
    rows the way the old ``put()``/``collect()`` shipped them: pickle
    of fully-padded static-capacity batches, written and read serially.
    Rows/sec counts live rows for both, so the ratio is a pure
    data-plane speedup for identical exchange content."""
    import pickle
    import shutil
    from concurrent.futures import ThreadPoolExecutor

    from spark_tpu import kernels, types as T, wire
    from spark_tpu.columnar import ColumnBatch, ColumnVector

    rng = np.random.default_rng(23)
    routed, padded = [], []
    for _ in range(SH_BATCHES):
        vecs = [
            ColumnVector(rng.integers(0, 1024, SH_CAP).astype(np.int64),
                         T.int64, None, None),
            ColumnVector(rng.integers(0, 100, SH_CAP).astype(np.int64),
                         T.int64, None, None),
            ColumnVector(rng.random(SH_CAP), T.float64, None, None),
            ColumnVector(rng.integers(0, 8, SH_CAP).astype(np.int32),
                         T.string, None,
                         tuple(f"cat{j}" for j in range(8))),
        ]
        src = ColumnBatch(["k", "v", "f", "s"], vecs, None, SH_CAP)
        pids = (np.asarray(src.vectors[0].data)
                % SH_RECEIVERS).astype(np.int32)
        b, off, cnt = kernels.partition_bucket(np, src, pids, SH_RECEIVERS)
        b = b.to_host()
        for r in range(SH_RECEIVERS):
            sl = kernels.slice_rows(b, int(off[r]), int(cnt[r]))
            routed.append(sl)
            # the same rows as the seed plane shipped them: padded back
            # to the full static capacity with a row-validity mask
            rv = np.zeros(SH_CAP, bool)
            rv[: int(cnt[r])] = True
            pv = [ColumnVector(np.resize(np.asarray(v.data), SH_CAP),
                               v.dtype, None, v.dictionary)
                  for v in sl.vectors]
            padded.append(ColumnBatch(list(sl.names), pv, rv, SH_CAP))
    live = sum(b.capacity for b in routed)
    raw_bytes = wire.raw_nbytes(routed)

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_shuffle_")
    pool = ThreadPoolExecutor(SH_THREADS)
    try:
        def wire_write(i):
            buf = wire.encode_batches([wire.trim_host(routed[i])])
            path = os.path.join(d, f"w{i:03d}.blk")
            with open(path, "wb") as f:
                f.write(buf)
            return path, len(buf)

        def wire_read(path):
            with open(path, "rb") as f:
                data = f.read()
            return wire.decode_batches(data)

        def wire_pass():
            written = list(pool.map(wire_write, range(len(routed))))
            for out in pool.map(wire_read, (p for p, _ in written)):
                assert out[0].capacity >= 0
            return sum(n for _, n in written)

        def pickle_pass():
            for i, b in enumerate(padded):
                with open(os.path.join(d, f"p{i:03d}.blk"), "wb") as f:
                    pickle.dump([b], f, protocol=pickle.HIGHEST_PROTOCOL)
            for i in range(len(padded)):
                with open(os.path.join(d, f"p{i:03d}.blk"), "rb") as f:
                    pickle.load(f)

        wire_bytes = wire_pass()            # also the warm-up
        pickle_pass()
        pickle_bytes = sum(
            os.path.getsize(os.path.join(d, f"p{i:03d}.blk"))
            for i in range(len(padded)))
        wire_rate = _median_rate(wire_pass, live)
        pickle_rate = _median_rate(pickle_pass, live)
    finally:
        pool.shutdown()
        shutil.rmtree(d, ignore_errors=True)
    return {
        "shuffle_rows_per_sec": round(wire_rate, 1),
        "shuffle_bytes_per_sec": round(wire_rate * wire_bytes / live, 1),
        "shuffle_vs_scan_baseline": round(
            wire_rate / BASELINE_SCAN_ROWS_PER_S, 3),
        "shuffle_pickle_rows_per_sec": round(pickle_rate, 1),
        "shuffle_vs_pickle": round(wire_rate / pickle_rate, 2),
        "shuffle_wire_bytes": wire_bytes,
        "shuffle_pickle_bytes": pickle_bytes,
        "shuffle_wire_vs_pickle_bytes": round(
            pickle_bytes / max(1, wire_bytes), 2),
        "shuffle_compression_ratio": round(raw_bytes / max(1, wire_bytes),
                                           3),
    }


def _bench_dist_join() -> dict:
    """Distributed-join lane: a 2-process equi-join + group-by through the
    host-shuffle data plane, shuffled hash join vs the forced gather path.

    Two REAL worker processes (``--distjoin-worker``) share one shuffle
    root; each holds a strided half of both fact tables and runs the same
    query twice — ``spark.tpu.crossproc.shuffledJoin`` on, then off on a
    fresh exchange root.  Each worker reports warm-run wall time and its
    service's DCN byte/row counters; this parent sums bytes across both
    workers and cross-checks that the two paths produced identical
    aggregates.  The byte reduction is structural: the shuffled path runs
    each side's subtree (pushed-down filters, pruned columns) BEFORE
    shipping and keeps its own key range in memory, while the gather path
    ships raw leaves."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_dj_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distjoin-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distjoin worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # both paths, both processes: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("shuffled",
                                                         "gather")}
        if len(sums) != 1:
            raise RuntimeError(f"shuffled/gather results diverge: {objs}")
        if not all(o["shuffled"]["shuffled_joins"] > 0 for o in objs):
            raise RuntimeError(f"shuffled path did not run: {objs}")
        if any(o["gather"]["shuffled_joins"] > 0 for o in objs):
            raise RuntimeError(f"gather run took the shuffled path: {objs}")
        rows = objs[0]["rows_total"]
        sh_s = max(o["shuffled"]["seconds"] for o in objs)
        ga_s = max(o["gather"]["seconds"] for o in objs)
        sh_b = sum(o["shuffled"]["bytes_written"] for o in objs)
        ga_b = sum(o["gather"]["bytes_written"] for o in objs)
        return {
            "distjoin_rows_per_sec": round(rows / sh_s, 1),
            "distjoin_gather_rows_per_sec": round(rows / ga_s, 1),
            "distjoin_speedup_vs_gather": round(ga_s / sh_s, 3),
            "distjoin_dcn_bytes": sh_b,
            "distjoin_gather_dcn_bytes": ga_b,
            "distjoin_dcn_byte_reduction": round(ga_b / max(1, sh_b), 2),
            "distjoin_rows_shipped": sum(
                o["shuffled"]["rows_shipped"] for o in objs),
            "distjoin_gather_rows_shipped": sum(
                o["gather"]["rows_shipped"] for o in objs),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distjoin_worker_main() -> None:
    """One process of the distributed-join lane (see ``_bench_dist_join``).

    argv: --distjoin-worker <pid> <root>.  Prints ONE JSON line with warm
    wall-clock and service counters for the shuffled and gather modes."""
    i = sys.argv.index("--distjoin-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    # both workers draw the SAME dataset, keep a strided half: every key
    # range lives on both processes (worst case for a local join)
    rng = np.random.default_rng(31)
    sk = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    price = rng.integers(1, 201, DJ_ROWS).astype(np.int64)
    k2 = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    bonus = rng.integers(1, 101, DJ_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
         "JOIN fact2 ON sk = k2 WHERE price < 100 AND bonus < 50 "
         "GROUP BY sk")

    session = SparkSession.builder.appName(f"bench-dj-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * DJ_ROWS)}
    for mode in ("shuffled", "gather"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key,
                    "true" if mode == "shuffled" else "false")
        # this lane measures hash-vs-gather; the range sort-merge and
        # broadcast planners must not preempt it (distsort lane covers
        # range-vs-hash)
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"sk": sk[mine], "price": price[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        base_bytes = int(svc.counters["bytes_written"])
        base_rows = int(svc.counters["rows_shipped"])
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        elapsed = time.perf_counter() - t0
        out[mode] = {
            "seconds": round(elapsed, 3),
            "bytes_written": int(svc.counters["bytes_written"]) - base_bytes,
            "rows_shipped": int(svc.counters["rows_shipped"]) - base_rows,
            "groups": len(rows),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) for r in rows)),
            "shuffled_joins": int(svc.counters["shuffled_joins"]),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_ici() -> dict:
    """Distici lane: the two-tier exchange (ICI device tier over the
    host/DCN wire tier).

    Phase one, 2 REAL worker processes (``--distici-worker``): the
    dict-free distjoin workload runs with the device tier armed (one
    ICI domain spanning both pids, zero byte floor) and then disarmed
    on a fresh root.  jax CPU backends cannot span two OS processes, so
    every armed attempt must fold back structured onto the host tier —
    the lane pins that ladder: fallbacks counted in tiered mode, zero
    in host mode, aggregates byte-identical, and the fallback overhead
    (pack + probe per exchange) measured as a wall-clock ratio.

    Phase two, one forced 4-device CPU mesh (``--distici-mesh``): the
    SAME pack/collective/unpack that ships HBM→HBM moves real bucketed
    spans device-to-device and is timed against the host wire plane
    (encode + decode of identical outboxes) — the structural number the
    tier exists for, portable to real chips unchanged."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_di_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distici-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distici worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        sums = {o[m]["checksum"] for o in objs for m in ("tiered",
                                                         "host")}
        if len(sums) != 1:
            raise RuntimeError(f"tiered/host results diverge: {objs}")
        if not all(o["tiered"]["dcn_fallbacks"] > 0 for o in objs):
            raise RuntimeError(f"armed tier never attempted: {objs}")
        if any(o["host"]["dcn_fallbacks"] > 0 for o in objs):
            raise RuntimeError(f"disarmed tier attempted: {objs}")
        ti_s = max(o["tiered"]["seconds"] for o in objs)
        ho_s = max(o["host"]["seconds"] for o in objs)
        res = {
            "distici_fallback_rows_per_sec": round(
                objs[0]["rows_total"] / ti_s, 1),
            "distici_host_rows_per_sec": round(
                objs[0]["rows_total"] / ho_s, 1),
            # armed-but-degraded vs never-armed: the price of probing
            # the device tier when it cannot serve (should stay ~1.0)
            "distici_fallback_overhead": round(ti_s / ho_s, 3),
            "distici_dcn_fallbacks": sum(
                o["tiered"]["dcn_fallbacks"] for o in objs),
        }
        mesh_env = dict(env,
                        XLA_FLAGS="--xla_force_host_platform_device_count=4")
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--distici-mesh"],
            capture_output=True, text=True, env=mesh_env,
            timeout=CHILD_TIMEOUT_S)
        if p.returncode != 0:
            raise RuntimeError(
                f"distici mesh rc={p.returncode}: "
                f"{(p.stderr or p.stdout).strip().splitlines()[-3:]}")
        line = [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")][-1]
        res.update(json.loads(line))
        return res
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distici_worker_main() -> None:
    """One process of the distici lane's 2-process phase (see
    ``_bench_dist_ici``).

    argv: --distici-worker <pid> <root>.  Prints ONE JSON line with
    warm wall-clock and tier counters for the armed (tiered) and
    disarmed (host) modes."""
    i = sys.argv.index("--distici-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    rng = np.random.default_rng(47)
    sk = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    k2 = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    bonus = rng.integers(1, 101, DJ_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    # projected int-only sides: the shape the device tier accepts (a
    # dictionary column would pin the exchange to the host tier)
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb "
         "FROM (SELECT sk FROM fact) f "
         "JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
         "GROUP BY sk")

    session = SparkSession.builder.appName(f"bench-di-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * DJ_ROWS)}
    for mode in ("tiered", "host"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        if mode == "tiered":
            xs.conf.set(C.SHUFFLE_ICI_ENABLED.key, "true")
            xs.conf.set(C.SHUFFLE_ICI_MIN_BYTES.key, "0")
            xs.conf.set(C.SHUFFLE_ICI_TIER_OVERRIDE.key, "0,1")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"sk": sk[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        base_fb = int(svc.counters["dcn_fallback_exchanges"])
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        elapsed = time.perf_counter() - t0
        out[mode] = {
            "seconds": round(elapsed, 3),
            "dcn_fallbacks": int(svc.counters["dcn_fallback_exchanges"])
            - base_fb,
            "ici_exchanges": int(svc.counters["ici_exchanges"]),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) for r in rows)),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def distici_mesh_main() -> None:
    """The distici lane's forced-mesh phase: device all-to-all vs the
    host wire plane over identical bucketed spans.

    argv: --distici-mesh (XLA_FLAGS forces a 4-device CPU world).
    Prints ONE JSON line: MB/s through ``local_device_exchange`` (pack
    + collective + unpack, warm stage cache) and through wire encode +
    decode of the same outboxes, plus the ratio."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import types as T
    from spark_tpu import wire
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.parallel import ici

    n = 4
    per = 1 << 13                        # rows per sender→receiver span
    rng = np.random.default_rng(53)

    def batch(m):
        vals = rng.integers(-(1 << 40), 1 << 40, m)
        return ColumnBatch(
            ["k"], [ColumnVector(vals, T.LongType(), None, None)],
            None, m)

    outboxes = [{r: [batch(per)] for r in range(n)} for _s in range(n)]
    tpl = batch(1)
    total = sum(wire.raw_nbytes(bs) for ob in outboxes
                for bs in ob.values())

    ici.local_device_exchange(outboxes, tpl)       # warm: trace+compile
    t0 = time.perf_counter()
    for _ in range(BENCH_RUNS):
        ici.local_device_exchange(outboxes, tpl)
    dev_s = (time.perf_counter() - t0) / BENCH_RUNS

    def wire_pass():
        for ob in outboxes:
            for bs in ob.values():
                wire.decode_batches(wire.encode_batches(bs))

    wire_pass()                                    # warm codec paths
    t0 = time.perf_counter()
    for _ in range(BENCH_RUNS):
        wire_pass()
    host_s = (time.perf_counter() - t0) / BENCH_RUNS

    print(json.dumps({
        "distici_mesh_device_mb_per_s": round(total / dev_s / 1e6, 1),
        "distici_mesh_wire_mb_per_s": round(total / host_s / 1e6, 1),
        "distici_mesh_device_vs_wire": round(host_s / dev_s, 3),
        "distici_mesh_bytes": int(total),
    }))
    sys.stdout.flush()


def _bench_stagecache() -> dict:
    """Stagecache lane: whole-stage compilation vs per-operator dispatch,
    and cold vs warm stage-executable cache, on a 2-process join + agg.

    Two REAL worker processes (``--stagecache-worker``) share a shuffle
    root and run the same fact⋈dim + group-by statement cold (first
    execution: every stage traces and compiles through the process
    StageCache) and then warm three times (median; executables must come
    back as cache hits with ZERO new builds).  Worker 0 additionally
    replays the same planned shape single-process both ways: fused (one
    jitted program per stage, dispatch count from StageCache counters)
    and per-operator (``stagecompile.run_per_op``, one device dispatch
    per physical operator — the pre-fusion baseline).  The parent pins
    checksum parity across processes and across dispatch modes, requires
    the >=3x dispatch reduction, and reports compile-ms / hit-count /
    wall-clock figures."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_sc_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--stagecache-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"stagecache worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # distributed statement: byte-identical aggregates on both
        # processes, cold and warm
        sums = {o["dist"]["checksum"] for o in objs}
        if len(sums) != 1:
            raise RuntimeError(f"worker results diverge: {objs}")
        if not all(o["dist"]["warm_hits"] > 0 for o in objs):
            raise RuntimeError(f"warm runs never hit the stage cache: "
                               f"{objs}")
        if any(o["dist"]["warm_builds"] > 0 for o in objs):
            raise RuntimeError(
                f"warm runs recompiled stages (stale cache key?): {objs}")
        cold_s = max(o["dist"]["cold_s"] for o in objs)
        warm_s = max(o["dist"]["warm_s"] for o in objs)
        if warm_s >= cold_s:
            raise RuntimeError(
                f"warm stage cache not faster than cold: {cold_s=} "
                f"{warm_s=}")
        # dispatch-mode comparison (worker 0's local replay)
        lo = objs[0]["local"]
        if lo["fused_checksum"] != lo["per_op_checksum"]:
            raise RuntimeError(f"fused/per-op results diverge: {lo}")
        if lo["per_op_overflow"]:
            raise RuntimeError(f"per-op baseline overflowed: {lo}")
        reduction = lo["per_op_dispatches"] / max(1,
                                                  lo["fused_dispatches"])
        if reduction < 3.0:
            raise RuntimeError(
                f"dispatch reduction {reduction:.2f}x < 3x: {lo}")
        return {
            "stagecache_cold_s": cold_s,
            "stagecache_warm_s": warm_s,
            "stagecache_warm_vs_cold_speedup": round(cold_s / warm_s, 3),
            "stagecache_compile_ms": round(
                sum(o["dist"]["compile_ms"] for o in objs), 1),
            "stagecache_stage_builds": sum(
                o["dist"]["builds"] for o in objs),
            "stagecache_warm_hits": sum(
                o["dist"]["warm_hits"] for o in objs),
            "stagecache_fused_dispatches": lo["fused_dispatches"],
            "stagecache_per_op_dispatches": lo["per_op_dispatches"],
            "stagecache_dispatch_reduction": round(reduction, 2),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def stagecache_worker_main() -> None:
    """One process of the stagecache lane (see ``_bench_stagecache``).

    argv: --stagecache-worker <pid> <root>.  Prints ONE JSON line with
    cold/warm wall clocks + StageCache counter deltas for the 2-process
    statement, and (worker 0) fused-vs-per-op dispatch counts with
    checksums on a single-process replay of the same shape."""
    i = sys.argv.index("--stagecache-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.sql import stagecompile as SC
    from spark_tpu.sql.session import SparkSession

    # both workers draw the SAME dataset, keep a strided half; the dim
    # side is UNIQUE-keyed so join fanout is exactly 1 and the per-op
    # replay cannot overflow the planned capacities
    rng = np.random.default_rng(47)
    sk = rng.integers(0, SC_KEYS, SC_ROWS).astype(np.int64)
    price = rng.integers(1, 201, SC_ROWS).astype(np.int64)
    k2 = np.arange(SC_KEYS, dtype=np.int64)
    bonus = rng.integers(1, 101, SC_KEYS).astype(np.int64)
    mine = slice(pid, None, 2)
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
         "JOIN dim ON sk = k2 WHERE price < 100 GROUP BY sk")

    def _ck(rows):
        return int(sum(int(r[1]) * 7 + int(r[2]) for r in rows))

    session = SparkSession.builder.appName(f"bench-sc-{pid}").getOrCreate()
    cache = SC.stage_cache()
    out = {"pid": pid, "rows_total": int(SC_ROWS)}

    xs = session.newSession()
    xs.conf.set(C.MESH_SHARDS.key, "1")
    xs.enableHostShuffle(os.path.join(root, "x"), process_id=pid,
                         n_processes=2, timeout_s=300.0)
    xs.createDataFrame({"sk": sk[mine], "price": price[mine]}) \
        .createOrReplaceTempView("fact")
    xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
        .createOrReplaceTempView("dim")

    s0 = cache.stats()
    t0 = time.perf_counter()
    rows = xs.sql(Q).collect()
    cold_s = time.perf_counter() - t0
    s1 = cache.stats()
    checksum = _ck(rows)
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        warm.append(time.perf_counter() - t0)
        if _ck(rows) != checksum:
            raise RuntimeError("warm run diverged from cold result")
    s2 = cache.stats()
    warm.sort()
    out["dist"] = {
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm[len(warm) // 2], 3),
        "checksum": checksum,
        "builds": s1["builds"] - s0["builds"],
        "compile_ms": round(s1["compile_ms"] - s0["compile_ms"], 1),
        "warm_hits": s2["hits"] - s1["hits"],
        "warm_builds": s2["builds"] - s1["builds"],
    }

    if pid == 0:
        # single-process replay of the same shape: fused dispatch count
        # (StageCache counters) vs the per-operator baseline
        from spark_tpu.sql.planner import (Planner, QueryExecution,
                                           _slice_to_host)
        ls = session.newSession()
        ls.conf.set(C.MESH_SHARDS.key, "1")
        ls.createDataFrame({"sk": sk, "price": price}) \
            .createOrReplaceTempView("fact")
        ls.createDataFrame({"k2": k2, "bonus": bonus}) \
            .createOrReplaceTempView("dim")
        b0 = cache.stats()
        fused_ck = _ck(ls.sql(Q).collect())
        b1 = cache.stats()
        pq = Planner(ls).plan(QueryExecution(ls, ls.sql(Q)._plan)
                              .optimized)
        dev, n_rows, n_disp, flags, _caps, _kinds = SC.run_per_op(
            pq.physical, pq.leaves)
        host = _slice_to_host(dev, n_rows)
        cols = [np.asarray(v.data)[:n_rows] for v in host.vectors]
        out["local"] = {
            "fused_dispatches": b1["dispatches"] - b0["dispatches"],
            "fused_checksum": fused_ck,
            "per_op_dispatches": n_disp,
            "per_op_checksum": _ck(list(zip(*cols))),
            "per_op_overflow": bool(any(f > 0 for f in flags)),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_adapt() -> dict:
    """Distadapt lane: adaptive re-planning from observed exchange stats.

    A 2-process join whose RIGHT side the plan-time probe misestimates
    by ~20x: the leaf is ~5 MB raw, but a selective pushed-down filter
    keeps ~5% of its rows, far under the broadcast threshold.  Each
    worker runs the same query with ``adaptiveReplan`` off (frozen: the
    full hash shuffle ships the fat left side) and on (the stats
    barrier demotes to a broadcast before any data block ships).  The
    parent cross-checks byte-identical aggregates, that the adaptive
    run actually demoted (and the frozen run actually shuffled), and
    reports wall-clock speedup + DCN byte reduction."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_da_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distadapt-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distadapt worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        sums = {o[m]["checksum"] for o in objs for m in ("adaptive",
                                                         "frozen")}
        if len(sums) != 1:
            raise RuntimeError(f"adaptive/frozen results diverge: {objs}")
        if not all(o["adaptive"]["strategy_demotions"] > 0 for o in objs):
            raise RuntimeError(f"adaptive run did not demote: {objs}")
        if not all(o["frozen"]["shuffled_joins"] > 0
                   and o["frozen"]["strategy_demotions"] == 0
                   for o in objs):
            raise RuntimeError(f"frozen run did not hash-shuffle: {objs}")
        rows = objs[0]["rows_total"]
        ad_s = max(o["adaptive"]["seconds"] for o in objs)
        fz_s = max(o["frozen"]["seconds"] for o in objs)
        ad_b = sum(o["adaptive"]["bytes_written"] for o in objs)
        fz_b = sum(o["frozen"]["bytes_written"] for o in objs)
        return {
            "distadapt_rows_per_sec": round(rows / ad_s, 1),
            "distadapt_frozen_rows_per_sec": round(rows / fz_s, 1),
            "distadapt_speedup_vs_frozen": round(fz_s / ad_s, 3),
            "distadapt_dcn_bytes": ad_b,
            "distadapt_frozen_dcn_bytes": fz_b,
            "distadapt_dcn_byte_reduction": round(fz_b / max(1, ad_b), 2),
            "distadapt_demotions": sum(
                o["adaptive"]["strategy_demotions"] for o in objs),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distadapt_worker_main() -> None:
    """One process of the distadapt lane (see ``_bench_dist_adapt``).

    argv: --distadapt-worker <pid> <root>.  Prints ONE JSON line with
    warm wall-clock and service counters for the adaptive and frozen
    modes.  The measured adaptive run must exercise the DEMOTION (the
    stats barrier), not the feedback shortcut, so the warm run's
    recorded cardinalities are cleared before timing."""
    i = sys.argv.index("--distadapt-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    # both workers draw the SAME dataset, keep a strided half.  The left
    # side is WIDE (five payload columns, all live in the output) — the
    # mass the frozen hash shuffle ships and the demoted broadcast keeps
    # local.  The right side's filter keeps ~5% of its rows.
    rng = np.random.default_rng(47)
    sk = rng.integers(0, DA_KEYS, DA_ROWS).astype(np.int64)
    pay = [rng.integers(1, 201, DA_ROWS).astype(np.int64)
           for _ in range(DA_PAY)]
    k2 = rng.integers(0, DA_KEYS, DA_ROWS).astype(np.int64)
    bonus = rng.integers(1, 101, DA_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    spay = " + ".join(f"p{j}" for j in range(DA_PAY))
    Q = ("SELECT sk, count(*) AS c, "
         f"sum({spay}) AS sp, sum(bonus) AS sb "
         f"FROM fact JOIN fact2 ON sk = k2 WHERE bonus < {DA_CUT} "
         "GROUP BY sk")

    session = SparkSession.builder.appName(f"bench-da-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * DA_ROWS)}
    for mode in ("adaptive", "frozen"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
        # between the observed right side (~5% of the leaf) and the
        # plan-time probe (the raw leaf): freeze hash, observe broadcast
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, str(1 << 20))
        xs.conf.set(C.CROSSPROC_ADAPTIVE_REPLAN.key,
                    "true" if mode == "adaptive" else "false")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame(dict(
            {"sk": sk[mine]},
            **{f"p{j}": p[mine] for j, p in enumerate(pay)})) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        xs.statsFeedback.clear()             # measure the demotion path
        base_bytes = int(svc.counters["bytes_written"])
        base_rows = int(svc.counters["rows_shipped"])
        base_dem = int(svc.counters["strategy_demotions"])
        base_shj = int(svc.counters["shuffled_joins"])
        # median-of-3: filesystem-barrier jitter dominates run-to-run
        # variance, and both processes must repeat in lockstep anyway
        # (every iteration is a fresh exchange round)
        iters = []
        for _ in range(3):
            xs.statsFeedback.clear()         # re-demote, don't shortcut
            it_bytes = int(svc.counters["bytes_written"])
            it_rows = int(svc.counters["rows_shipped"])
            t0 = time.perf_counter()
            rows = xs.sql(Q).collect()
            iters.append((time.perf_counter() - t0,
                          int(svc.counters["bytes_written"]) - it_bytes,
                          int(svc.counters["rows_shipped"]) - it_rows))
        elapsed, it_bytes, it_rows = sorted(iters)[1]
        out[mode] = {
            "seconds": round(elapsed, 3),
            "bytes_written": it_bytes,
            "rows_shipped": it_rows,
            "groups": len(rows),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) * 3 + int(r[3])
                                for r in rows)),
            "strategy_demotions":
                int(svc.counters["strategy_demotions"]) - base_dem,
            "shuffled_joins": int(svc.counters["shuffled_joins"]) - base_shj,
            "adaptive_replans": int(svc.counters["adaptive_replans"]),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_dict() -> dict:
    """Distdict lane: encoded execution over the DCN exchange.  A
    2-process low-cardinality string-key join + group-by runs twice with
    only ``spark.tpu.shuffle.wire.dictCodes`` toggled: "codes" ships each
    fat dictionary ONCE per (exchange, sender) in the framed sidecar and
    the blocks carry int32 codes + an 8-byte fingerprint, "words" inlines
    the full dictionary into EVERY block frame (the legacy wire).  Same
    shuffled-hash path, identical results cross-checked; the byte
    reduction is the dictionary dedup, measured end to end."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_dd_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distdict-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distdict worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # both wire formats, both processes: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("codes", "words")}
        if len(sums) != 1:
            raise RuntimeError(f"codes/words results diverge: {objs}")
        if not all(o["codes"]["dict_columns_encoded"] > 0 for o in objs):
            raise RuntimeError(f"codes run never framed a dictionary: {objs}")
        rows = objs[0]["rows_total"]
        co_s = max(o["codes"]["seconds"] for o in objs)
        wo_s = max(o["words"]["seconds"] for o in objs)
        co_b = sum(o["codes"]["bytes_written"] for o in objs)
        wo_b = sum(o["words"]["bytes_written"] for o in objs)
        return {
            "distdict_rows_per_sec": round(rows / co_s, 1),
            "distdict_words_rows_per_sec": round(rows / wo_s, 1),
            "distdict_speedup_vs_words": round(wo_s / co_s, 3),
            "distdict_dcn_bytes": co_b,
            "distdict_words_dcn_bytes": wo_b,
            "distdict_dcn_byte_reduction": round(wo_b / max(1, co_b), 2),
            "distdict_dict_bytes_saved": sum(
                o["codes"]["dict_bytes_saved"] for o in objs),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distdict_worker_main() -> None:
    """One process of the distdict lane (see ``_bench_dist_dict``).

    argv: --distdict-worker <pid> <root>.  Prints ONE JSON line with warm
    wall-clock and service counters for the codes and words wire modes."""
    i = sys.argv.index("--distdict-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import zlib

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    # fat words, low cardinality: the per-column dictionary (~75 KiB)
    # dwarfs a fine partition's code payload, so inlining it per block
    # frame vs once per sender is the measured difference
    words = np.array([f"sku-{j:06d}-lot-{j % 97:02d}-aisle-{j % 13:02d}"
                      for j in range(DD_KEYS)])
    rng = np.random.default_rng(53)
    g = words[rng.integers(0, DD_KEYS, DD_ROWS)]
    v = rng.integers(1, 100, DD_ROWS).astype(np.int64)
    g2 = words[rng.integers(0, DD_KEYS, DD_ROWS)]
    w = rng.integers(1, 100, DD_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    Q = ("SELECT g, count(*) AS c, sum(w) AS sw FROM fact "
         "JOIN fact2 ON g = g2 GROUP BY g ORDER BY g")

    session = SparkSession.builder.appName(f"bench-dd-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * DD_ROWS)}
    for mode in ("codes", "words"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.SHUFFLE_WIRE_DICT_CODES.key,
                    "true" if mode == "codes" else "false")
        # pin the range sort-merge path both runs (string keys are
        # range-eligible now): this lane measures the WIRE format, not a
        # join-strategy difference.  Range routing ships one batch frame
        # PER SPAN per receiver — the words wire pays the dictionary in
        # each frame, the codes wire once per sender in the sidecar.
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "false")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        xs.conf.set(C.SHUFFLE_FINE_PARTITIONS.key, "32")
        xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "4096")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"g": g[mine], "v": v[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"g2": g2[mine], "w": w[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        base_bytes = int(svc.counters["bytes_written"])
        base_rows = int(svc.counters["rows_shipped"])
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        elapsed = time.perf_counter() - t0
        chk = 0
        for r in rows:                       # order pinned by ORDER BY g
            chk = (chk * 1000003 + zlib.crc32(str(r[0]).encode())
                   + 7 * int(r[1]) + int(r[2])) & 0xFFFFFFFF
        out[mode] = {
            "seconds": round(elapsed, 3),
            "bytes_written": int(svc.counters["bytes_written"]) - base_bytes,
            "rows_shipped": int(svc.counters["rows_shipped"]) - base_rows,
            "groups": len(rows),
            "checksum": chk,
            "dict_columns_encoded": int(
                svc.counters["dict_columns_encoded"]),
            "dict_bytes_saved": int(svc.counters["dict_bytes_saved"]),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_rle() -> dict:
    """Distrle lane: run-length/delta encoded execution over the DCN
    exchange.  A 2-process time-series join + group-by runs twice with
    only ``spark.tpu.shuffle.wire.runCodes`` toggled: "runs" lets the
    sampled-benefit probe RLE/delta-encode each block column (and the
    range sort-merge path emit its presorted span slices as free runs),
    "raw" ships every column dense (the legacy wire).  Same range
    sort-merge path, identical results cross-checked; the byte
    reduction is the run compression of the sorted ts/sensor/status
    planes, measured end to end against an incompressible payload
    column that ships dense in both modes.

    Acceptance (raises into ``distrle_error`` when missed): >=2x DCN
    byte reduction, runs wall clock <= 1.1x the raw wall, checksums
    byte-identical across modes and processes."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_dr_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distrle-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distrle worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # both wire formats, both processes: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("runs", "raw")}
        if len(sums) != 1:
            raise RuntimeError(f"runs/raw results diverge: {objs}")
        # the plane pair runs its own (filter+agg) query: planes on vs
        # off must be byte-identical across modes AND processes
        psums = {o[m]["checksum"] for o in objs
                 for m in ("plane", "noplane")}
        if len(psums) != 1:
            raise RuntimeError(f"plane/noplane results diverge: {objs}")
        # the r20 contract: with runPlanes on the jitted stage lane ran
        # the eligible query compressed — stages entered as planes, and
        # not one run expanded on the host during the timed iterations
        if sum(o["plane"]["run_plane_stages"] for o in objs) == 0:
            raise RuntimeError(
                f"plane run never entered a stage compressed: {objs}")
        mat = sum(o["plane"]["runs_materialized_delta"] for o in objs)
        if mat != 0:
            raise RuntimeError(
                f"plane run materialized {mat} run rows on the host "
                f"(want 0): {objs}")
        pl_s = max(o["plane"]["seconds"] for o in objs)
        npl_s = max(o["noplane"]["seconds"] for o in objs)
        plane_ratio = pl_s / max(1e-9, npl_s)
        if plane_ratio > 1.1:
            raise RuntimeError(
                f"plane wall {pl_s:.3f}s is {plane_ratio:.2f}x the "
                f"materializing path {npl_s:.3f}s (> 1.1x budget)")
        # span ownership need not balance, so a process that keeps its
        # shard local frames nothing — the EXCHANGE must run-encode
        if sum(o["runs"]["rle_columns_encoded"] for o in objs) == 0:
            raise RuntimeError(f"runs run never run-encoded a column: {objs}")
        if not all(o["raw"]["rle_columns_encoded"] == 0 for o in objs):
            raise RuntimeError(f"raw run framed run codes: {objs}")
        rows = objs[0]["rows_total"]
        ru_s = max(o["runs"]["seconds"] for o in objs)
        ra_s = max(o["raw"]["seconds"] for o in objs)
        ru_b = sum(o["runs"]["bytes_written"] for o in objs)
        ra_b = sum(o["raw"]["bytes_written"] for o in objs)
        reduction = ra_b / max(1, ru_b)
        wall_ratio = ru_s / max(1e-9, ra_s)
        if reduction < 2.0:
            raise RuntimeError(
                f"DCN byte reduction {reduction:.2f}x < 2x "
                f"(runs {ru_b} B vs raw {ra_b} B)")
        if wall_ratio > 1.1:
            raise RuntimeError(
                f"runs wall {ru_s:.3f}s is {wall_ratio:.2f}x raw "
                f"{ra_s:.3f}s (> 1.1x budget)")
        return {
            "distrle_rows_per_sec": round(rows / ru_s, 1),
            "distrle_raw_rows_per_sec": round(rows / ra_s, 1),
            "distrle_wall_vs_raw": round(wall_ratio, 3),
            "distrle_dcn_bytes": ru_b,
            "distrle_raw_dcn_bytes": ra_b,
            "distrle_dcn_byte_reduction": round(reduction, 2),
            "distrle_run_bytes_saved": sum(
                o["runs"]["run_bytes_saved"] for o in objs),
            "distrleplane_wall_vs_dense": round(plane_ratio, 3),
            "distrleplane_rows_per_sec": round(rows / pl_s, 1),
            "distrleplane_stages": sum(
                o["plane"]["run_plane_stages"] for o in objs),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distrle_worker_main() -> None:
    """One process of the distrle lane (see ``_bench_dist_rle``).

    argv: --distrle-worker <pid> <root>.  Prints ONE JSON line with warm
    wall-clock and service counters for the runs and raw wire modes."""
    i = sys.argv.index("--distrle-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import zlib

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    # time-series shape: ts repeats in long blocks, sensor and status
    # follow ts (long runs after the range sort), v is an incompressible
    # random payload that ships dense in both modes — the honest floor
    rep = DR_ROWS // DR_KEYS
    ts = np.repeat(np.arange(DR_KEYS, dtype=np.int64), rep)
    sensor = (ts // 4).astype(np.int64)
    status = np.array(["ok", "warn", "err"])[
        (np.arange(DR_ROWS) // 512) % 3]
    rng = np.random.default_rng(59)
    v = rng.integers(1, 1 << 30, DR_ROWS).astype(np.int64)
    dk = np.arange(DR_KEYS, dtype=np.int64)
    bonus = (dk * 3 + 7).astype(np.int64)
    mine = slice(pid, None, 2)
    Q = ("SELECT status, count(*) AS c, sum(v) AS sv, "
         "sum(sensor) AS ss, sum(bonus) AS sb FROM ev "
         "JOIN dm ON ts = dk GROUP BY status ORDER BY status")
    # the plane modes run the eligible filter+agg shape over the sorted
    # key: on the encoded wire the reduce-side shards arrive run-encoded,
    # and with runPlanes on the jitted stage lane must execute this query
    # without materializing a single run on the host
    QP = (f"SELECT ts, count(*) AS c, sum(v) AS sv FROM ev "
          f"JOIN dm ON ts = dk WHERE ts < {DR_KEYS // 2} "
          f"GROUP BY ts ORDER BY ts")

    from spark_tpu import columnar as _col
    session = SparkSession.builder.appName(f"bench-dr-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(DR_ROWS)}
    for mode in ("runs", "raw", "plane", "noplane"):
        q = QP if mode in ("plane", "noplane") else Q
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.SHUFFLE_WIRE_RUN_CODES.key,
                    "false" if mode == "raw" else "true")
        xs.conf.set(C.STAGE_RUN_PLANES.key,
                    "false" if mode == "noplane" else "true")
        # runs/raw pin the range sort-merge path: the sorted spans are
        # where presorted-slice RLE is free, and that pair measures the
        # WIRE format, not a join-strategy difference.  The plane pair
        # pins the shuffled hash path instead — under the presorted
        # merge ev never leaves the process (only dm is gathered), so
        # only a real shuffle makes the run-shaped probe side cross the
        # encoded wire and arrive at the reduce-side stage as run
        # vectors, the boundary the planes compress
        smj = mode in ("runs", "raw")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key,
                    "true" if smj else "false")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key,
                    "false" if smj else "true")
        if not smj:
            # the reducer's own map output normally short-circuits the
            # wire as a dense slice, and one dense piece in the drain
            # union forces the whole column dense — the forced-spill
            # threshold stages EVERY piece through the encoded frames
            # (the parity battery's configuration), so the reduce-side
            # union stays run-encoded and the stage boundary sees run
            # vectors.  The small advisory target keeps both processes
            # reducing instead of coalescing every fine partition onto
            # process 0 (the filtered side is ~2 MiB, under the 4 MiB
            # default)
            xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, "1024")
            xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, "65536")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        xs.conf.set(C.SHUFFLE_FINE_PARTITIONS.key, "16")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"ts": ts[mine], "sensor": sensor[mine],
                            "status": status[mine], "v": v[mine]}) \
            .createOrReplaceTempView("ev")
        xs.createDataFrame({"dk": dk[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("dm")
        xs.sql(q).collect()                  # warm: compile + caches
        # median-of-3: filesystem-barrier jitter dominates run-to-run
        # variance, and both processes must repeat in lockstep anyway
        iters = []
        mat0 = _col.runs_materialized()
        stages0 = _col.run_plane_stages()
        for _ in range(3):
            it_bytes = int(svc.counters["bytes_written"])
            it_rows = int(svc.counters["rows_shipped"])
            t0 = time.perf_counter()
            rows = xs.sql(q).collect()
            iters.append((time.perf_counter() - t0,
                          int(svc.counters["bytes_written"]) - it_bytes,
                          int(svc.counters["rows_shipped"]) - it_rows))
        elapsed, it_bytes, it_rows = sorted(iters)[1]
        chk = 0
        for r in rows:                 # order pinned by the ORDER BY
            chk = (chk * 1000003 + zlib.crc32(str(r[0]).encode())
                   + sum((3 + 2 * i) * int(r[i])
                         for i in range(1, len(r)))) & 0xFFFFFFFF
        out[mode] = {
            "seconds": round(elapsed, 3),
            "bytes_written": it_bytes,
            "rows_shipped": it_rows,
            "groups": len(rows),
            "checksum": chk,
            "rle_columns_encoded": int(
                svc.counters["rle_columns_encoded"]),
            "run_bytes_saved": int(svc.counters["run_bytes_saved"]),
            "runs_materialized_delta": int(
                _col.runs_materialized() - mat0),
            "run_plane_stages": int(_col.run_plane_stages() - stages0),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_sort() -> dict:
    """Distsort lane: the SKEWED 2-process equi-join, range-partitioned
    sort-merge (with skew-span splitting) vs the shuffled hash path.

    Half the probe mass sits on one hot key.  Under hash partitioning
    that key's fine partition is indivisible — one reducer does all the
    hot join work while its peer idles.  The range planner detects the
    hot span from the sample round and SPLITS its probe rows across both
    reducers (build replicated for that span), so the work balances.

    The headline figure is the CRITICAL PATH: max over the two workers
    of per-process CPU seconds in the timed run.  On a real multi-host
    pod that IS the exchange's wall clock; on this single-host CI
    simulator the two workers timeshare the same cores, so raw
    end-to-end wall clock only measures TOTAL work (the idle hash peer
    donates its core to the hot one) and is reported separately.  The
    lane also reports the reducer-balance evidence (max/median partition
    bytes of the range data plan, captured at plan time)."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_ds_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distsort-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distsort worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # both paths, both processes: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("range", "hash")}
        if len(sums) != 1:
            raise RuntimeError(f"range/hash results diverge: {objs}")
        if not all(o["range"]["range_merge_joins"] > 0 for o in objs):
            raise RuntimeError(f"range path did not run: {objs}")
        if not all(o["range"]["spans_split"] > 0 for o in objs):
            raise RuntimeError(f"hot span was not split: {objs}")
        if not all(o["hash"]["shuffled_joins"] > 0 for o in objs):
            raise RuntimeError(f"hash path did not run: {objs}")
        # reducer balance: the range DATA plan (captured at plan time,
        # before the agg round overwrites the gauge) must not hand any
        # reducer more than 2x the median partition bytes
        loads = sorted(objs[0]["range"]["partition_bytes"])
        p_max = loads[-1]
        mid = len(loads) // 2
        p_med = float(loads[mid]) if len(loads) % 2 \
            else (loads[mid - 1] + loads[mid]) / 2.0
        if p_max > 2 * p_med:
            raise RuntimeError(f"skew survived the split: {loads}")
        rows = objs[0]["rows_total"]
        # critical path: the slowest reducer's CPU time = multi-host wall
        # clock; barrier sleeps (waiting for the peer) cost no CPU
        rg_s = max(o["range"]["cpu_seconds"] for o in objs)
        ha_s = max(o["hash"]["cpu_seconds"] for o in objs)
        return {
            "distsort_rows_per_sec": round(rows / rg_s, 1),
            "distsort_hash_rows_per_sec": round(rows / ha_s, 1),
            "distsort_speedup_vs_hash": round(ha_s / rg_s, 3),
            "distsort_wall_seconds": max(
                o["range"]["seconds"] for o in objs),
            "distsort_hash_wall_seconds": max(
                o["hash"]["seconds"] for o in objs),
            "distsort_dcn_bytes": sum(
                o["range"]["bytes_written"] for o in objs),
            "distsort_hash_dcn_bytes": sum(
                o["hash"]["bytes_written"] for o in objs),
            "distsort_spans_split": objs[0]["range"]["spans_split"],
            "distsort_partition_bytes_max": int(p_max),
            "distsort_partition_bytes_median": int(p_med),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distsort_worker_main() -> None:
    """One process of the distsort lane (see ``_bench_dist_sort``).

    argv: --distsort-worker <pid> <root>.  Prints ONE JSON line with warm
    wall-clock, service counters, and the range data plan's per-reducer
    byte loads for the range and hash modes."""
    i = sys.argv.index("--distsort-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.sql.session import SparkSession

    # same full dataset on both workers, strided halves; HALF the probe
    # mass on one hot key — the indivisible-under-hash partition
    rng = np.random.default_rng(47)
    sk = rng.integers(0, DS_KEYS, DS_ROWS).astype(np.int64)
    sk[rng.random(DS_ROWS) < 0.5] = DS_HOT
    price = rng.integers(1, 201, DS_ROWS).astype(np.int64)
    k2 = rng.integers(0, DS_KEYS, DS_BUILD).astype(np.int64)
    k2[:96] = DS_HOT        # hot key matches ~112 build rows: the join
    bonus = rng.integers(1, 101, DS_BUILD).astype(np.int64)  # OUTPUT skews
    mine = slice(pid, None, 2)
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
         "JOIN fact2 ON sk = k2 GROUP BY sk")

    session = SparkSession.builder.appName(f"bench-ds-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(DS_ROWS + DS_BUILD)}
    for mode in ("range", "hash"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key,
                    "true" if mode == "range" else "false")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        # small advisory target: non-hot spans spread over many runs
        # (balance) and the hot span's bytes far exceed it (split k=2)
        xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key, str(1 << 16))
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        # tight barrier polling: this lane measures partitioning quality,
        # and the default 50ms poll quantum would swamp the compute delta
        svc.poll_s = 0.005
        # capture the DATA-plan reducer loads at plan time — the keyed
        # aggregate's later size round overwrites the shared gauge
        plan_loads: list = []

        def prr(probe, build, target, _svc=svc,
                _orig=svc.plan_range_reducers, _sink=plan_loads):
            owners = _orig(probe, build, target)
            _sink.append([int(b) for b in (_svc.last_partition_bytes or [])])
            return owners
        svc.plan_range_reducers = prr
        xs.createDataFrame({"sk": sk[mine], "price": price[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        base_bytes = int(svc.counters["bytes_written"])
        t0 = time.perf_counter()
        c0 = time.process_time()
        rows = xs.sql(Q).collect()
        cpu = time.process_time() - c0
        elapsed = time.perf_counter() - t0
        out[mode] = {
            "seconds": round(elapsed, 3),
            "cpu_seconds": round(cpu, 3),
            "bytes_written": int(svc.counters["bytes_written"]) - base_bytes,
            "groups": len(rows),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) for r in rows)),
            "range_merge_joins": int(svc.counters["range_merge_joins"]),
            "shuffled_joins": int(svc.counters["shuffled_joins"]),
            "spans_split": int(svc.counters["spans_split"]),
            "partition_bytes": plan_loads[-1] if plan_loads else [],
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_spill() -> dict:
    """Distspill lane: the memory-pressure path of the distributed join.

    The distjoin workload reruns with the host budget capped BELOW the
    input working set and a tiny spill threshold, so map output and
    fetched blocks take the wire-format spill files instead of RAM.  The
    lane pins the robustness contract as a number: the capped run must
    COMPLETE with the same aggregates as the uncapped run, report
    nonzero spill bytes, keep its ledger peak under the cap — and the
    wall-clock overhead of spilling is the tracked figure."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_dspill_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distspill-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distspill worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # under pressure or not: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("uncapped",
                                                         "capped")}
        if len(sums) != 1:
            raise RuntimeError(f"capped/uncapped results diverge: {objs}")
        if not all(o["capped"]["spill_bytes"] > 0 for o in objs):
            raise RuntimeError(f"capped run did not spill: {objs}")
        for o in objs:
            if o["capped"]["peak_host_bytes"] > o["capped"]["budget_bytes"]:
                raise RuntimeError(f"ledger peak blew the cap: {objs}")
        rows = objs[0]["rows_total"]
        cap_s = max(o["capped"]["seconds"] for o in objs)
        unc_s = max(o["uncapped"]["seconds"] for o in objs)
        return {
            "distspill_rows_per_sec": round(rows / cap_s, 1),
            "distspill_overhead_vs_uncapped": round(cap_s / unc_s, 3),
            "distspill_bytes": sum(
                o["capped"]["spill_bytes"] for o in objs),
            "distspill_events": sum(
                o["capped"]["spill_events"] for o in objs),
            "distspill_peak_host_bytes": max(
                o["capped"]["peak_host_bytes"] for o in objs),
            "distspill_budget_bytes": objs[0]["capped"]["budget_bytes"],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distspill_worker_main() -> None:
    """One process of the distspill lane (see ``_bench_dist_spill``).

    argv: --distspill-worker <pid> <root>.  Runs the distjoin query
    uncapped, then with the host budget capped below the input working
    set and a tiny spill threshold; prints ONE JSON line with both warm
    wall-clocks and the capped run's spill/ledger figures."""
    i = sys.argv.index("--distspill-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.memory import HOST_BUDGET
    from spark_tpu.sql.session import SparkSession

    rng = np.random.default_rng(31)
    sk = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    price = rng.integers(1, 201, DJ_ROWS).astype(np.int64)
    k2 = rng.integers(0, DJ_KEYS, DJ_ROWS).astype(np.int64)
    bonus = rng.integers(1, 101, DJ_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb FROM fact "
         "JOIN fact2 ON sk = k2 WHERE price < 100 AND bonus < 50 "
         "GROUP BY sk")
    # below the per-process input working set (2 tables x 2 int64 cols),
    # above the post-filter resident shards the join must hold to finish
    budget = DJ_ROWS * 20

    session = SparkSession.builder.appName(
        f"bench-dspill-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * DJ_ROWS)}
    for mode in ("uncapped", "capped"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        if mode == "capped":
            xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, str(64 << 10))
            xs.conf.set(HOST_BUDGET.key, str(budget))
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"sk": sk[mine], "price": price[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        xs.sql(Q).collect()                  # warm: compile + caches
        base_spill = int(svc.counters["spill_bytes"])
        base_events = int(svc.counters["spill_events"])
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        elapsed = time.perf_counter() - t0
        out[mode] = {
            "seconds": round(elapsed, 3),
            "groups": len(rows),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) for r in rows)),
            "spill_bytes": int(svc.counters["spill_bytes"]) - base_spill,
            "spill_events": int(svc.counters["spill_events"]) - base_events,
            "peak_host_bytes": int(svc.ledger.peak),
            "budget_bytes": int(svc.ledger.budget),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_grace() -> dict:
    """Distgrace lane: graceful degradation past the exchange.

    A 2-process join+group-by runs with the host budget capped below
    EVERY reducer's drained working set — a budget the plain spill path
    cannot absorb, because the fetched shard itself does not fit.  With
    grace buckets enabled the reducers re-bucket the drained runs into
    spill files and join bucket-by-bucket: the lane pins that the capped
    run COMPLETES byte-identical to the uncapped run, reports nonzero
    grace buckets/spill, keeps the ledger peak under the cap — and the
    wall-clock overhead of degrading is the tracked figure.  With
    ``graceBuckets=0`` the same query must abort with the structured
    ``HostMemoryError`` (the pre-grace contract), never a wrong
    answer."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_dgrace_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distgrace-worker", str(pid), d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in (0, 1)]
        outs = [p.communicate(timeout=CHILD_TIMEOUT_S) for p in procs]
        objs = []
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"distgrace worker rc={p.returncode}: "
                    f"{(err or out).strip().splitlines()[-3:]}")
            line = [ln for ln in out.splitlines()
                    if ln.strip().startswith("{")][-1]
            objs.append(json.loads(line))
        # degraded or not: byte-identical aggregates
        sums = {o[m]["checksum"] for o in objs for m in ("uncapped",
                                                         "grace")}
        if len(sums) != 1:
            raise RuntimeError(f"grace/uncapped results diverge: {objs}")
        for o in objs:
            if o["grace"]["grace_buckets_used"] <= 0:
                raise RuntimeError(f"capped run never graced: {objs}")
            if o["grace"]["peak_host_bytes"] > o["grace"]["budget_bytes"]:
                raise RuntimeError(f"ledger peak blew the cap: {objs}")
            if not o["nograce"]["aborted"]:
                raise RuntimeError(
                    f"graceBuckets=0 run did not abort bounded: {objs}")
        rows = objs[0]["rows_total"]
        gra_s = max(o["grace"]["seconds"] for o in objs)
        unc_s = max(o["uncapped"]["seconds"] for o in objs)
        return {
            "distgrace_rows_per_sec": round(rows / gra_s, 1),
            "distgrace_overhead_vs_uncapped": round(gra_s / unc_s, 3),
            "distgrace_buckets": sum(
                o["grace"]["grace_buckets_used"] for o in objs),
            "distgrace_spill_bytes": sum(
                o["grace"]["grace_spill_bytes"] for o in objs),
            "distgrace_peak_host_bytes": max(
                o["grace"]["peak_host_bytes"] for o in objs),
            "distgrace_budget_bytes": objs[0]["grace"]["budget_bytes"],
            "distgrace_nograce_aborts": sum(
                1 for o in objs if o["nograce"]["aborted"]),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distgrace_worker_main() -> None:
    """One process of the distgrace lane (see ``_bench_dist_grace``).

    argv: --distgrace-worker <pid> <root>.  Runs the join uncapped,
    then capped below the reducers' drained working set with grace
    buckets on (must complete via grace), then the same cap with
    ``graceBuckets=0`` (must abort with the structured HostMemoryError);
    prints ONE JSON line."""
    i = sys.argv.index("--distgrace-worker")
    pid, root = int(sys.argv[i + 1]), sys.argv[i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from spark_tpu import config as C
    from spark_tpu.memory import HOST_BUDGET, HostMemoryError
    from spark_tpu.sql.session import SparkSession

    rng = np.random.default_rng(47)
    sk = rng.integers(0, GG_KEYS, GG_ROWS).astype(np.int64)
    price = rng.integers(1, 201, GG_ROWS).astype(np.int64)
    k2 = rng.integers(0, GG_KEYS, GG_ROWS).astype(np.int64)
    bonus = rng.integers(1, 101, GG_ROWS).astype(np.int64)
    mine = slice(pid, None, 2)
    # projection subqueries: sides ship ONLY the joined/aggregated
    # columns, so the shipped working set (and the grace buckets) stay
    # deliberately sized against GG_BUDGET
    Q = ("SELECT sk, count(*) AS c, sum(bonus) AS sb "
         "FROM (SELECT sk FROM fact) f "
         "JOIN (SELECT k2, bonus FROM fact2) f2 ON sk = k2 "
         "GROUP BY sk")

    session = SparkSession.builder.appName(
        f"bench-dgrace-{pid}").getOrCreate()
    out = {"pid": pid, "rows_total": int(2 * GG_ROWS)}
    for mode in ("uncapped", "grace", "nograce"):
        xs = session.newSession()
        xs.conf.set(C.MESH_SHARDS.key, "1")
        xs.conf.set(C.CROSSPROC_SHUFFLED_JOIN.key, "true")
        xs.conf.set(C.CROSSPROC_SORT_MERGE_JOIN.key, "false")
        xs.conf.set(C.CROSSPROC_AUTO_BROADCAST.key, "0")
        # balance the two reducer shards: greedy span packing to half
        # the shipped working set (fact ships sk at 8 B/row, fact2
        # ships k2+bonus at 16 B/row)
        xs.conf.set(C.SHUFFLE_TARGET_PARTITION_BYTES.key,
                    str(GG_ROWS * 24 // 2))
        if mode != "uncapped":
            xs.conf.set(C.SHUFFLE_SPILL_THRESHOLD.key, str(8 << 10))
            xs.conf.set(HOST_BUDGET.key, str(GG_BUDGET))
        if mode == "nograce":
            xs.conf.set(C.CROSSPROC_GRACE_BUCKETS.key, "0")
        svc = xs.enableHostShuffle(os.path.join(root, mode),
                                   process_id=pid, n_processes=2,
                                   timeout_s=300.0)
        xs.createDataFrame({"sk": sk[mine], "price": price[mine]}) \
            .createOrReplaceTempView("fact")
        xs.createDataFrame({"k2": k2[mine], "bonus": bonus[mine]}) \
            .createOrReplaceTempView("fact2")
        if mode == "nograce":
            # the pre-grace contract: a shard that cannot be staged is a
            # STRUCTURED bounded failure, never a wrong answer
            t0 = time.perf_counter()
            try:
                xs.sql(Q).collect()
                aborted, detail = False, ""
            except HostMemoryError as e:
                aborted, detail = True, str(e)[:200]
            out[mode] = {
                "seconds": round(time.perf_counter() - t0, 3),
                "aborted": aborted,
                "error": detail,
            }
            continue
        xs.sql(Q).collect()                  # warm: compile + caches
        base_gb = int(svc.counters["grace_buckets_used"])
        base_gs = int(svc.counters["grace_spill_bytes"])
        t0 = time.perf_counter()
        rows = xs.sql(Q).collect()
        elapsed = time.perf_counter() - t0
        out[mode] = {
            "seconds": round(elapsed, 3),
            "groups": len(rows),
            "checksum": int(sum(int(r[1]) * 7 + int(r[2]) for r in rows)),
            "grace_buckets_used":
                int(svc.counters["grace_buckets_used"]) - base_gb,
            "grace_spill_bytes":
                int(svc.counters["grace_spill_bytes"]) - base_gs,
            "peak_host_bytes": int(svc.ledger.peak),
            "budget_bytes": int(svc.ledger.budget),
        }
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_servebench() -> dict:
    """Servebench lane: multi-tenant serving throughput, plan cache on/off.

    One CPU worker process runs an in-process SQL server twice — plan
    cache disabled, then enabled — with 4 concurrent HTTP sessions each
    replaying the same mix of parameterized query variants.  Cache off,
    every (session, literal-variant) pays its own trace+compile; cache
    on, literal slotting folds all variants of a template into ONE
    shared executable, so the first session's compile serves everyone.
    The lane pins result equality across modes and reports the
    throughput/latency delta the cache buys."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_serve_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--servebench-worker", d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        out, err = p.communicate(timeout=CHILD_TIMEOUT_S)
        if p.returncode != 0:
            raise RuntimeError(
                f"servebench worker rc={p.returncode}: "
                f"{(err or out).strip().splitlines()[-3:]}")
        o = json.loads([ln for ln in out.splitlines()
                        if ln.strip().startswith("{")][-1])
        if o["off"]["checksum"] != o["on"]["checksum"]:
            raise RuntimeError(f"cache on/off results diverge: {o}")
        if o["on"]["cache_hits"] <= 0:
            raise RuntimeError(f"plan cache never hit: {o}")
        mb = o["multibatch"]
        if not mb["checksum_equal"]:
            raise RuntimeError(f"multibatch sessions diverge: {mb}")
        if mb["first_cache_hit"] or not mb["second_cache_hit"]:
            raise RuntimeError(
                f"multibatch statement not cached cross-session: {mb}")
        if mb["stage_cache_hits"] <= 0 or mb["stage_builds"] > 0:
            raise RuntimeError(
                f"second session recompiled multibatch stages: {mb}")
        return {
            "servebench_sessions": o["sessions"],
            "servebench_statements": o["off"]["statements"],
            "servebench_stmts_per_sec_cache_off":
                o["off"]["stmts_per_sec"],
            "servebench_stmts_per_sec_cache_on":
                o["on"]["stmts_per_sec"],
            "servebench_cache_speedup": round(
                o["on"]["stmts_per_sec"]
                / max(o["off"]["stmts_per_sec"], 1e-9), 3),
            "servebench_p50_ms_cache_off": o["off"]["p50_ms"],
            "servebench_p95_ms_cache_off": o["off"]["p95_ms"],
            "servebench_p50_ms_cache_on": o["on"]["p50_ms"],
            "servebench_p95_ms_cache_on": o["on"]["p95_ms"],
            "servebench_cache_hits": o["on"]["cache_hits"],
            "servebench_multibatch_second_session_hit":
                mb["second_cache_hit"],
            "servebench_multibatch_stage_hits": mb["stage_cache_hits"],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def servebench_worker_main() -> None:
    """The servebench lane's single worker (see ``_bench_servebench``).

    argv: --servebench-worker <root>.  Starts an in-process SQLServer on
    a loopback port, opens 4 HTTP sessions, and replays 2 query
    templates x 3 literal variants per session, cache off then on.
    Prints ONE JSON line with per-mode throughput, latency percentiles,
    a result checksum, and the cache-on hit count."""
    import urllib.request

    i = sys.argv.index("--servebench-worker")
    root = sys.argv[i + 1]
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
    import spark_tpu  # noqa: F401
    # no persistent compile cache in this worker: it would hand cache-off
    # its compiles back and fake the comparison
    jax.config.update("jax_enable_compilation_cache", False)

    from spark_tpu.server import SQLServer
    from spark_tpu.sql.session import SparkSession

    def _http(port, method, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=(json.dumps(body).encode() if body is not None else None),
            method=method)
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read().decode())

    N_SESSIONS, N_VARIANTS = 4, 3
    TEMPLATES = [
        "SELECT k % 10 AS g, sum(v) AS sv, count(*) AS c FROM f "
        "WHERE v < {lit} GROUP BY k % 10 ORDER BY g",
        "SELECT count(*) AS c, sum(v) AS sv FROM f WHERE k % 7 = {lit}",
    ]
    base = SparkSession.builder.appName("servebench").getOrCreate()
    out = {"sessions": N_SESSIONS}
    for mode in ("off", "on"):
        srv_sess = base.newSession()
        srv_sess.conf.set("spark.tpu.mesh.shards", "1")
        srv_sess.conf.set("spark.sql.warehouse.dir",
                          os.path.join(root, f"wh_{mode}"))
        srv_sess.conf.set("spark.tpu.server.planCache.enabled",
                          "true" if mode == "on" else "false")
        srv_sess.sql("CREATE TABLE f AS SELECT id AS k, "
                     "(id * 7) % 1000 AS v FROM range(65536)")
        srv = SQLServer(srv_sess, port=0, workers=N_SESSIONS).start()
        try:
            lat_ms, sums, errs = [], [], []
            lock = threading.Lock()

            def client(_cid):
                try:
                    sid = _http(srv.port, "POST", "/session")["sessionId"]
                    for rep in range(N_VARIANTS):
                        for t_i, tpl in enumerate(TEMPLATES):
                            q = tpl.format(lit=101 + 13 * rep + t_i)
                            t0 = time.perf_counter()
                            r = _http(srv.port, "POST", "/sql",
                                      {"query": q, "session": sid})
                            dt = (time.perf_counter() - t0) * 1000
                            s = sum(c for row in r["rows"] for c in row
                                    if isinstance(c, int))
                            with lock:
                                lat_ms.append(dt)
                                sums.append(s)
                    _http(srv.port, "DELETE", f"/session/{sid}")
                except Exception as e:   # noqa: BLE001 — report, not hang
                    with lock:
                        errs.append(f"{type(e).__name__}: {e}")

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(N_SESSIONS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errs:
                raise RuntimeError(f"servebench {mode}: {errs[:3]}")
            lat_ms.sort()
            pc = srv._plan_cache.stats() if srv._plan_cache else {}
            out[mode] = {
                "statements": len(lat_ms),
                "stmts_per_sec": round(len(lat_ms) / wall, 2),
                "p50_ms": round(lat_ms[len(lat_ms) // 2], 1),
                "p95_ms": round(lat_ms[int(len(lat_ms) * 0.95)
                                       - 1], 1),
                "checksum": int(sum(sums)),
                "cache_hits": int(pc.get("hits", 0)),
            }
        finally:
            srv.stop()

    # cross-session STAGE cache: a multibatch statement (scan split into
    # device batches — previously a plan-cache bailout) repeated from a
    # SECOND session must report cacheHit with the stage executables
    # served from the shared stage cache, not recompiled
    mb_sess = base.newSession()
    mb_sess.conf.set("spark.tpu.mesh.shards", "1")
    mb_sess.conf.set("spark.sql.warehouse.dir", os.path.join(root, "wh_mb"))
    mb_sess.conf.set("spark.tpu.server.planCache.enabled", "true")
    mb_sess.conf.set("spark.tpu.scan.maxBatchRows", "256")
    mb_sess.sql("CREATE TABLE mb AS SELECT id AS k, (id * 13) % 997 AS v "
                "FROM range(2000)")
    MQ = ("SELECT k % 8 AS g, sum(v) AS sv, count(*) AS c FROM mb "
          "GROUP BY k % 8 ORDER BY g")
    srv = SQLServer(mb_sess, port=0, workers=2).start()
    try:
        runs, stats = [], []
        for _ in range(2):
            sid = _http(srv.port, "POST", "/session")["sessionId"]
            runs.append(_http(srv.port, "POST", "/sql",
                              {"query": MQ, "session": sid}))
            stats.append(_http(srv.port, "GET", "/status"))
            _http(srv.port, "DELETE", f"/session/{sid}")
        sc0 = stats[0]["stageCache"]
        sc1 = stats[1]["stageCache"]
        out["multibatch"] = {
            "first_cache_hit": bool(runs[0].get("cacheHit")),
            "second_cache_hit": bool(runs[1].get("cacheHit")),
            "checksum_equal": runs[0]["rows"] == runs[1]["rows"],
            "stage_entries": int(
                stats[1]["planCache"].get("stage_entries", 0)),
            # second-session deltas: executables must come back as stage
            # cache hits, never fresh builds
            "stage_cache_hits": int(sc1["hits"]) - int(sc0["hits"]),
            "stage_builds": int(sc1["builds"]) - int(sc0["builds"]),
        }
    finally:
        srv.stop()
    print(json.dumps(out))
    sys.stdout.flush()


def _bench_dist_pool() -> dict:
    """Distpool lane: burst admission, fixed server vs elastic pool.

    One CPU worker process runs an in-process SQL server twice under the
    same burst — 6 concurrent HTTP clients hammering SELECTs through a
    maxConcurrentStatements=4 admission cap with a single local executor
    thread.  Fixed mode has only that thread, so the burst piles up
    behind admission and clients eat 429 + retry; elastic mode lets the
    supervisor spawn real pool workers off the demand signal and offload
    admitted SELECTs to them, so slots drain faster.  The lane pins
    result equality across modes and proves the whole elastic loop in
    one number set: workers spawned under burst, statements served by
    the pool, and the idle pool reaped back to zero."""
    import shutil

    d = tempfile.mkdtemp(prefix="spark_tpu_bench_pool_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SPARK_TPU_FAULT_PLAN", None)
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distpool-worker", d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        out, err = p.communicate(timeout=CHILD_TIMEOUT_S)
        if p.returncode != 0:
            raise RuntimeError(
                f"distpool worker rc={p.returncode}: "
                f"{(err or out).strip().splitlines()[-3:]}")
        o = json.loads([ln for ln in out.splitlines()
                        if ln.strip().startswith("{")][-1])
        if o["fixed"]["checksum"] != o["elastic"]["checksum"]:
            raise RuntimeError(f"fixed/elastic results diverge: {o}")
        el = o["elastic"]
        if el["workers_spawned"] <= 0:
            raise RuntimeError(f"pool never spawned under burst: {o}")
        if el["pool_served"] <= 0:
            raise RuntimeError(f"pool served no statements: {o}")
        # self-exited workers are collected without a reap count, so
        # reaped==spawned is not guaranteed — but an idle pool must
        # shed at least one worker and end empty
        if el["workers_reaped"] <= 0 or el["pool_live_end"] != 0:
            raise RuntimeError(f"idle pool never reaped: {o}")
        return {
            "distpool_clients": o["clients"],
            "distpool_statements": o["fixed"]["statements"],
            "distpool_stmts_per_sec_fixed": o["fixed"]["stmts_per_sec"],
            "distpool_stmts_per_sec_elastic": el["stmts_per_sec"],
            "distpool_p95_ms_fixed": o["fixed"]["p95_ms"],
            "distpool_p95_ms_elastic": el["p95_ms"],
            "distpool_429_rate_fixed": o["fixed"]["rate_429"],
            "distpool_429_rate_elastic": el["rate_429"],
            "distpool_workers_spawned": el["workers_spawned"],
            "distpool_workers_reaped": el["workers_reaped"],
            "distpool_pool_served": el["pool_served"],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def distpool_worker_main() -> None:
    """The distpool lane's single worker (see ``_bench_dist_pool``).

    argv: --distpool-worker <root>.  Starts an in-process SQLServer
    twice — pool off then pool on — with 6 concurrent HTTP clients each
    replaying the same SELECT burst through a tight admission cap.
    Clients retry on 429 and count every rejection; latency is measured
    end to end INCLUDING retry waits, because that is what a
    backpressured client actually experiences.  Prints ONE JSON line
    with per-mode latency/throughput/429 stats, a result checksum, and
    the elastic mode's pool counters."""
    import urllib.error
    import urllib.request

    i = sys.argv.index("--distpool-worker")
    root = sys.argv[i + 1]
    os.environ["JAX_PLATFORMS"] = "cpu"

    # pool WORKER processes share this process's compile cache by the
    # program's own rule (spark_tpu/__init__.py): a compile done by any
    # process serves the rest
    import jax
    jax.config.update("jax_platforms", "cpu")

    from spark_tpu.server import SQLServer
    from spark_tpu.sql.session import SparkSession

    def _http(port, method, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=(json.dumps(body).encode() if body is not None else None),
            method=method)
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read().decode())

    N_CLIENTS, N_STMTS = 6, 6
    QUERY = ("SELECT k % 16 AS g, sum(v) AS sv, count(*) AS c "
             "FROM pool_f GROUP BY k % 16 ORDER BY g")
    base = SparkSession.builder.appName("distpool").getOrCreate()
    out = {"clients": N_CLIENTS}
    for mode in ("fixed", "elastic"):
        srv_sess = base.newSession()
        srv_sess.conf.set("spark.tpu.mesh.shards", "1")
        srv_sess.conf.set("spark.sql.warehouse.dir",
                          os.path.join(root, f"wh_{mode}"))
        # tight global admission cap + ONE local executor thread: the
        # fixed server's whole capacity.  The elastic pool's workers are
        # the only way mode two gets more parallelism.
        srv_sess.conf.set("spark.tpu.server.maxConcurrentStatements", "4")
        if mode == "elastic":
            srv_sess.conf.set("spark.tpu.server.pool.enabled", "true")
            srv_sess.conf.set("spark.tpu.server.pool.maxWorkers", "3")
            srv_sess.conf.set(
                "spark.tpu.server.pool.statementsPerWorker", "1")
            srv_sess.conf.set("spark.tpu.server.pool.cooldownSeconds", "0")
            srv_sess.conf.set("spark.tpu.server.pool.pollSeconds", "0.05")
            # 2s of continuous idle before the first reap: long enough
            # to survive the gap between the warm-up and measured
            # bursts, short enough to drain well inside the post-run
            # reap wait below
            srv_sess.conf.set(
                "spark.tpu.server.pool.scaleDownRounds", "40")
        srv_sess.sql("CREATE TABLE pool_f AS SELECT id AS k, "
                     "(id * 7) % 1000 AS v FROM range(120000)")
        srv = SQLServer(srv_sess, port=0, workers=1).start()
        try:
            def burst():
                lat_ms, sums, errs = [], [], []
                n429 = [0]
                lock = threading.Lock()

                def client(_cid):
                    try:
                        sid = _http(srv.port, "POST",
                                    "/session")["sessionId"]
                        for _rep in range(N_STMTS):
                            t0 = time.perf_counter()
                            for _attempt in range(400):
                                try:
                                    r = _http(srv.port, "POST", "/sql",
                                              {"query": QUERY,
                                               "session": sid})
                                    break
                                except urllib.error.HTTPError as e:
                                    if e.code != 429:
                                        raise
                                    with lock:
                                        n429[0] += 1
                                    time.sleep(0.05)
                            else:
                                raise RuntimeError(
                                    "429 retry budget exhausted")
                            dt = (time.perf_counter() - t0) * 1000
                            s = sum(c for row in r["rows"] for c in row
                                    if isinstance(c, int))
                            with lock:
                                lat_ms.append(dt)
                                sums.append(s)
                        _http(srv.port, "DELETE", f"/session/{sid}")
                    except Exception as e:   # noqa: BLE001 — report
                        with lock:
                            errs.append(f"{type(e).__name__}: {e}")

                t0 = time.perf_counter()
                threads = [threading.Thread(target=client, args=(c,))
                           for c in range(N_CLIENTS)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                if errs:
                    raise RuntimeError(f"distpool {mode}: {errs[:3]}")
                return lat_ms, sums, n429[0], wall

            # warm-up burst (unmeasured): pays the first-compile in
            # both modes, and in elastic mode gives the supervisor a
            # demand spike to scale up on so the MEASURED burst hits a
            # warm pool — steady-state elasticity, not boot cost
            burst()
            lat_ms, sums, n429, wall = burst()
            lat_ms.sort()
            spawned = reaped = served = live_end = 0
            sup = srv._pool_supervisor
            if sup is not None:
                # demand is gone; give the reconcile loop time to walk
                # the pool back down so the lane can report a full
                # spawn->serve->reap cycle
                deadline = time.time() + 20.0
                while time.time() < deadline:
                    c = sup.counters
                    if int(c["workers_spawned"]) > 0 \
                            and sup.stats()["live"] == 0:
                        break
                    time.sleep(0.1)
                c = sup.counters
                spawned = int(c["workers_spawned"])
                reaped = int(c["workers_reaped"])
                served = int(c["pool_statements_served"])
                live_end = int(sup.stats()["live"])
            out[mode] = {
                "statements": len(lat_ms),
                "stmts_per_sec": round(len(lat_ms) / wall, 2),
                "p50_ms": round(lat_ms[len(lat_ms) // 2], 1),
                "p95_ms": round(lat_ms[int(len(lat_ms) * 0.95) - 1], 1),
                "rate_429": round(n429 / max(n429 + len(lat_ms), 1), 3),
                "checksum": int(sum(sums)),
                "workers_spawned": spawned,
                "workers_reaped": reaped,
                "pool_served": served,
                "pool_live_end": live_end,
            }
        finally:
            srv.stop()
    print(json.dumps(out))
    sys.stdout.flush()


def main() -> int:
    # before numpy loads its BLAS: pinned pool width, recorded below
    for var in _THREAD_ENV_VARS:
        os.environ.setdefault(var, str(BENCH_THREADS))

    import numpy as np
    import jax
    import jax.numpy as jnp

    import spark_tpu  # noqa: F401  (x64 and THE compile-cache directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devices = jax.devices()
    print(f"[bench] devices: {devices}", file=sys.stderr)
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: jax.devices()[0].platform is "
              f"{devices[0].platform!r}; a CPU timing is never reported "
              "under a device metric's name", file=sys.stderr)
        return 1

    from spark_tpu.sql.session import SparkSession
    session = SparkSession.builder.appName("bench").getOrCreate()
    session.conf.set("spark.tpu.mesh.shards", "1")

    agg_rows_per_s = _bench_hash_agg(jax, jnp, np, session)

    extras = {}

    def lane(rps, baseline, value_key, ratio_key):
        extras[value_key] = round(rps, 1)
        extras[ratio_key] = round(rps / baseline, 3)

    # a lane that raises fails the run (non-zero exit): no *_error key
    lane(_bench_q3_join(jax, jnp, np, session), BASELINE_JOIN_ROWS_PER_S,
         "q3_join_agg_sort_rows_per_sec", "q3_vs_join_baseline")
    lane(_bench_sort(jax, jnp, np, session), BASELINE_SORT_ROWS_PER_S,
         "sort_rows_per_sec", "sort_vs_baseline")
    lane(_bench_parquet_scan(np, session), BASELINE_SCAN_ROWS_PER_S,
         "parquet_scan_rows_per_sec", "scan_vs_baseline")
    # host-side data plane: wire vs the seed pickle plane in the same run
    extras.update(_bench_shuffle(np))
    # the two-process lanes: real worker processes pinned to the CPU
    # backend (they must not contend for the accelerator)
    for dist_lane in (_bench_dist_join, _bench_dist_sort, _bench_dist_adapt,
                      _bench_dist_dict, _bench_dist_rle, _bench_dist_spill,
                      _bench_dist_grace, _bench_dist_ici, _bench_stagecache,
                      _bench_servebench, _bench_dist_pool):
        extras.update(dist_lane())

    try:
        load_1m = round(os.getloadavg()[0], 2)
    except OSError:
        load_1m = None
    print(json.dumps({
        "metric": "hash_agg_keys_rows_per_sec",
        "value": round(agg_rows_per_s, 1),
        "unit": "rows/s",
        "vs_baseline": round(agg_rows_per_s / BASELINE_AGG_ROWS_PER_S, 3),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
        # measurement conditions: median-of-N protocol, pinned host
        # thread pools, and ambient load at report time — so two BENCH
        # lines are comparable before their values are
        "runs_per_lane": BENCH_RUNS,
        "threads_pinned": int(os.environ.get("OMP_NUM_THREADS", 0)
                              or BENCH_THREADS),
        "loadavg_1m": load_1m,
        **extras,
    }))
    return 0


if __name__ == "__main__":
    if "--distjoin-worker" in sys.argv:
        distjoin_worker_main()
    elif "--distadapt-worker" in sys.argv:
        distadapt_worker_main()
    elif "--distsort-worker" in sys.argv:
        distsort_worker_main()
    elif "--distdict-worker" in sys.argv:
        distdict_worker_main()
    elif "--distrle-worker" in sys.argv:
        distrle_worker_main()
    elif "--distspill-worker" in sys.argv:
        distspill_worker_main()
    elif "--distgrace-worker" in sys.argv:
        distgrace_worker_main()
    elif "--distici-worker" in sys.argv:
        distici_worker_main()
    elif "--distici-mesh" in sys.argv:
        distici_mesh_main()
    elif "--stagecache-worker" in sys.argv:
        stagecache_worker_main()
    elif "--servebench-worker" in sys.argv:
        servebench_worker_main()
    elif "--distpool-worker" in sys.argv:
        distpool_worker_main()
    else:
        sys.exit(main())
