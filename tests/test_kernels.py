"""Operator kernel tests: numpy path vs pandas oracle, plus one fused jit
pipeline cross-check (filter → project → group-agg in a single XLA program)."""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_tpu import types as T
from spark_tpu.aggregates import (
    Avg, Count, CountStar, First, Last, Max, Min, StddevSamp, Sum, VarSamp,
)
from spark_tpu.columnar import ColumnBatch
from spark_tpu.expressions import Col, col, lit
from spark_tpu.kernels import (
    apply_filter, apply_limit, apply_project, compact, distinct,
    grouped_aggregate, sort_batch, union_all,
)


def make_batch(n=20, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 4, n)
    keys = np.array(["a", "b", "c", "d"])[k]
    vals = rng.normal(size=n) * 10
    nulls = rng.random(n) < 0.25
    v2 = [None if nulls[i] else int(rng.integers(0, 100)) for i in range(n)]
    return ColumnBatch.from_arrays({
        "k": list(keys), "v": vals, "c": v2,
        "i": rng.integers(-50, 50, n).astype(np.int64),
    }), pd.DataFrame({"k": keys, "v": vals,
                      "c": [np.nan if x is None else x for x in v2],
                      "i": np.arange(0)[0:0] if False else rng.integers(0, 0, 0)}) if False else None


def to_df(batch):
    return batch.to_pandas()


def test_filter_then_compact():
    b = ColumnBatch.from_arrays({"x": np.arange(10, dtype=np.int64)})
    f = apply_filter(np, b, (col("x") % 2) == 0)
    assert int(np.asarray(f.num_rows())) == 5
    c = compact(np, f)
    assert c.to_pylist()[:5] == [(0,), (2,), (4,), (6,), (8,)]
    # compaction preserved mask count
    assert int(np.asarray(c.num_rows())) == 5


def test_filter_null_pred_drops():
    b = ColumnBatch.from_arrays({"x": [1, None, 3]})
    f = apply_filter(np, b, col("x") > 0)
    assert [r[0] for r in compact(np, f).to_pylist()] == [1, 3]


def test_project():
    b = ColumnBatch.from_arrays({"x": np.arange(5, dtype=np.int64)})
    p = apply_project(np, b, [(col("x") * 2).children and (col("x") * 2), lit(7)])
    rows = p.to_pylist()
    assert rows[0] == (0, 7) and rows[4] == (8, 7)


def test_limit():
    b = ColumnBatch.from_arrays({"x": np.arange(10, dtype=np.int64)})
    f = apply_filter(np, b, col("x") >= 4)
    l = apply_limit(np, f, 3)
    assert [r[0] for r in compact(np, l).to_pylist()] == [4, 5, 6]


def test_sort_asc_desc_nulls():
    b = ColumnBatch.from_arrays({"x": [3, None, 1, None, 2], "y": [1, 2, 3, 4, 5]})
    vec = b.column("x")
    s = sort_batch(np, b, [(vec.data, vec.valid, T.int32, True, True)])
    assert [r[0] for r in s.to_pylist()] == [None, None, 1, 2, 3]
    s2 = sort_batch(np, b, [(vec.data, vec.valid, T.int32, False, False)])
    assert [r[0] for r in s2.to_pylist()] == [3, 2, 1, None, None]


def test_sort_multi_key_stable():
    b = ColumnBatch.from_arrays({
        "a": [1, 2, 1, 2, 1], "b": [9, 8, 7, 6, 5]})
    va, vb = b.column("a"), b.column("b")
    s = sort_batch(np, b, [(va.data, va.valid, T.int32, True, True),
                           (vb.data, vb.valid, T.int32, False, True)])
    assert [r for r in s.to_pylist()] == [(1, 9), (1, 7), (1, 5), (2, 8), (2, 6)]


def test_sort_strings_and_floats():
    b = ColumnBatch.from_arrays({"s": ["pear", "fig", "apple"], "f": [2.5, -1.0, 3.5]})
    vs = b.column("s")
    s = sort_batch(np, b, [(vs.data, vs.valid, T.string, True, True)])
    assert [r[0] for r in s.to_pylist()] == ["apple", "fig", "pear"]
    vf = b.column("f")
    s2 = sort_batch(np, b, [(vf.data, vf.valid, T.float64, False, True)])
    assert [r[1] for r in s2.to_pylist()] == [3.5, 2.5, -1.0]


def agg_oracle(df, group, aggs):
    """pandas oracle for grouped aggregation."""
    g = df.groupby(group, dropna=False)
    out = g.agg(**aggs).reset_index()
    return out.sort_values(group).reset_index(drop=True)


def test_grouped_aggregate_against_pandas():
    rng = np.random.default_rng(7)
    n = 50
    keys = np.array(["a", "b", "c", "d"])[rng.integers(0, 4, n)]
    vals = rng.normal(size=n) * 10
    batch = ColumnBatch.from_arrays({"k": list(keys), "v": vals})
    out = grouped_aggregate(np, batch, [Col("k")], [
        (Sum(Col("v")), "sum_v"), (Count(Col("v")), "n"),
        (Avg(Col("v")), "avg_v"), (Min(Col("v")), "min_v"),
        (Max(Col("v")), "max_v"), (VarSamp(Col("v")), "var_v"),
    ])
    got = compact(np, out).to_pandas().sort_values("k").reset_index(drop=True)
    df = pd.DataFrame({"k": keys, "v": vals})
    exp = agg_oracle(df, "k", dict(
        sum_v=("v", "sum"), n=("v", "count"), avg_v=("v", "mean"),
        min_v=("v", "min"), max_v=("v", "max"), var_v=("v", "var")))
    assert got["k"].tolist() == exp["k"].tolist()
    for c_ in ["sum_v", "avg_v", "min_v", "max_v", "var_v"]:
        np.testing.assert_allclose(got[c_].to_numpy(), exp[c_].to_numpy(), rtol=1e-10)
    np.testing.assert_array_equal(got["n"].to_numpy(), exp["n"].to_numpy())


def test_grouped_aggregate_null_keys_and_values():
    batch = ColumnBatch.from_arrays({
        "k": ["x", None, "x", None, "y"],
        "v": [1, 2, None, 4, 5],
    })
    out = grouped_aggregate(np, batch, [Col("k")], [
        (Sum(Col("v")), "s"), (Count(Col("v")), "n"), (CountStar(), "all")])
    rows = sorted(compact(np, out).to_pylist(),
                  key=lambda r: (r[0] is None, r[0] or ""))
    # NULL key forms its own group (SQL GROUP BY semantics)
    assert rows == [("x", 1, 1, 2), ("y", 5, 1, 1), (None, 6, 2, 2)]


def test_global_aggregate_no_keys():
    batch = ColumnBatch.from_arrays({"v": [1.0, 2.0, 3.0, 4.0]})
    f = apply_filter(np, batch, col("v") > 1.5)
    out = grouped_aggregate(np, f, [], [(Sum(Col("v")), "s"), (CountStar(), "n")])
    assert compact(np, out).to_pylist() == [(9.0, 3)]


def test_global_aggregate_empty_input():
    batch = ColumnBatch.from_arrays({"v": [1.0, 2.0]})
    f = apply_filter(np, batch, col("v") > 100)
    out = grouped_aggregate(np, f, [], [(Sum(Col("v")), "s"), (CountStar(), "n"),
                                        (Min(Col("v")), "m")])
    assert compact(np, out).to_pylist() == [(None, 0, None)]


def test_first_last():
    batch = ColumnBatch.from_arrays({
        "k": ["a", "a", "b", "b", "b"],
        "v": [None, 10, 20, None, 30],
    })
    out = grouped_aggregate(np, batch, [Col("k")], [
        (First(Col("v")), "f"), (Last(Col("v")), "l")])
    rows = sorted(compact(np, out).to_pylist())
    assert rows == [("a", 10, 10), ("b", 20, 30)]


def test_min_max_strings():
    batch = ColumnBatch.from_arrays({
        "k": [1, 1, 2], "s": ["pear", "apple", "fig"]})
    out = grouped_aggregate(np, batch, [Col("k")], [
        (Min(Col("s")), "lo"), (Max(Col("s")), "hi")])
    rows = sorted(compact(np, out).to_pylist())
    assert rows == [(1, "apple", "pear"), (2, "fig", "fig")]


def test_distinct():
    batch = ColumnBatch.from_arrays({
        "a": [1, 1, 2, 2, 1], "b": ["x", "x", "y", "y", "z"]})
    out = compact(np, distinct(np, batch))
    assert sorted(out.to_pylist()) == [(1, "x"), (1, "z"), (2, "y")]


def test_union_all_merges_dictionaries():
    b1 = ColumnBatch.from_arrays({"s": ["b", "a"], "x": [1, 2]})
    b2 = ColumnBatch.from_arrays({"s": ["c", "a", None], "x": [3, 4, 5]})
    u = union_all([b1, b2])
    rows = compact(np, u).to_pylist()
    assert rows == [("b", 1), ("a", 2), ("c", 3), ("a", 4), (None, 5)]
    assert u.column("s").dictionary == ("a", "b", "c")


def test_fused_pipeline_jit_matches_numpy():
    """filter → project → group agg fused under ONE jit — WholeStageCodegen."""
    rng = np.random.default_rng(3)
    n = 64
    keys = np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
    vals = rng.normal(size=n)
    batch = ColumnBatch.from_arrays({"k": list(keys), "v": vals})

    def pipeline(xp, b):
        f = apply_filter(xp, b, col("v") > 0)
        p = apply_project(xp, f, [Col("k"), (col("v") * 2).children and (col("v") * 2)])
        # rename: projected expr name is the repr; use Col on it via index
        p.names = ["k", "v2"]
        return grouped_aggregate(xp, p, [Col("k")], [
            (Sum(Col("v2")), "s"), (CountStar(), "n"), (Max(Col("v2")), "mx")])

    ref = compact(np, pipeline(np, batch.to_host()))

    jitted = jax.jit(lambda b: pipeline(jnp, b))
    out = compact(np, jitted(batch.to_device()).to_host())
    rref = sorted(ref.to_pylist())
    rout = sorted(out.to_pylist())
    assert len(rref) == len(rout)
    for a, b2 in zip(rref, rout):
        assert a[0] == b2[0]
        np.testing.assert_allclose(a[1], b2[1], rtol=1e-12)
        assert a[2] == b2[2]
        np.testing.assert_allclose(a[3], b2[3], rtol=1e-12)


def test_sort_jit_matches_numpy():
    rng = np.random.default_rng(5)
    vals = rng.normal(size=32)
    nulls = rng.random(32) < 0.2
    b = ColumnBatch.from_arrays({"v": [None if nulls[i] else vals[i] for i in range(32)],
                                 "i": np.arange(32, dtype=np.int64)})

    def do_sort(xp, bt):
        vec = bt.column("v")
        return sort_batch(xp, bt, [(vec.data, vec.valid, T.float64, True, False)])

    ref = do_sort(np, b.to_host()).to_pylist()
    out = jax.jit(lambda bt: do_sort(jnp, bt))(b.to_device()).to_host().to_pylist()
    assert ref == out


def test_keyless_agg_capacity_zero():
    """Keyless aggregation over a capacity-0 batch (empty streamed
    source): the no-sort global path must behave like segment_reduce did
    — shape-(0,) buffers, one all-NULL/zero output row after finish."""
    import numpy as np
    from spark_tpu import types as T
    from spark_tpu.aggregates import Min, Sum, CountStar
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.expressions import Col
    from spark_tpu.kernels import grouped_aggregate
    empty = ColumnBatch(
        ["v"], [ColumnVector(np.zeros(0, np.int64), T.int64, None, None)],
        np.zeros(0, bool), 0)
    out = grouped_aggregate(np, empty, [],
                            [(Sum(Col("v")), "s"), (Min(Col("v")), "m"),
                             (CountStar(), "c")])
    assert out.capacity == 1
    assert int(np.asarray(out.column("c").data)[0]) == 0
    sv = out.column("s")
    assert sv.valid is not None and not bool(np.asarray(sv.valid)[0])


def test_keyless_first_last_capacity_zero():
    """Keyless first/last partials over a capacity-0 batch (empty shard
    slice) must not crash in the global reduce path."""
    import numpy as np
    from spark_tpu import types as T
    from spark_tpu.aggregates import First
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.expressions import Col
    from spark_tpu.parallel.dist import DPartialAggregate
    from spark_tpu.sql import physical as P

    class _Leaf(P.PhysicalPlan):
        def __init__(self, b):
            self.b = b
            self.children = ()

        def run(self, ctx):
            return self.b

    empty = ColumnBatch(
        ["v"], [ColumnVector(np.zeros(0, np.int64), T.int64, None, None)],
        np.zeros(0, bool), 0)
    node = DPartialAggregate([], [(First(Col("v")), "f")], _Leaf(empty))
    out = node.run(P.ExecContext(np, []))
    assert out.capacity == 0


def test_compact_jax_path_matches_numpy():
    """The DEVICE compact (single-operand bit-packed uint32 sort) must
    agree row-for-row with the numpy reference, including all-dead,
    all-live and interleaved masks."""
    import numpy as np
    import jax.numpy as jnp
    from spark_tpu import types as T
    from spark_tpu.columnar import ColumnBatch, ColumnVector
    from spark_tpu.kernels import compact
    rng = np.random.default_rng(13)
    for mask in (rng.random(257) < 0.4,
                 np.zeros(257, bool),
                 np.ones(257, bool)):
        data = rng.integers(0, 1000, 257).astype(np.int64)
        valid = rng.random(257) < 0.9
        b = ColumnBatch(["x"],
                        [ColumnVector(data, T.int64, valid, None)],
                        mask.copy(), 257)
        ref = compact(np, b)
        dev = compact(jnp, ColumnBatch(
            ["x"], [ColumnVector(jnp.asarray(data), T.int64,
                                 jnp.asarray(valid), None)],
            jnp.asarray(mask), 257))
        n = int(np.asarray(ref.num_rows()))
        assert int(np.asarray(dev.num_rows())) == n
        np.testing.assert_array_equal(
            np.asarray(dev.vectors[0].data)[:n],
            np.asarray(ref.vectors[0].data)[:n])
        np.testing.assert_array_equal(
            np.asarray(dev.vectors[0].valid)[:n],
            np.asarray(ref.vectors[0].valid)[:n])
        np.testing.assert_array_equal(
            np.asarray(dev.row_valid_or_true())[:n],
            np.asarray(ref.row_valid_or_true())[:n])


def test_radix_argsort_matches_lax_sort():
    """Stable LSD radix argsort (the TPU sort-lane candidate): exact
    permutation equality with the stable reference argsort across sign,
    duplicates, and extremes."""
    import jax.numpy as jnp
    from spark_tpu.kernels import radix_argsort
    rng = np.random.default_rng(3)
    for n in (1, 7, 1024, 5000):
        xs = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                          n, dtype=np.int64)
        xs[rng.integers(0, n, n // 3 or 1)] = 42       # duplicates
        got = np.asarray(radix_argsort(jnp, jnp.asarray(xs)))
        exp = np.argsort(xs, kind="stable")
        np.testing.assert_array_equal(got, exp)
    # numpy lane
    xs = np.array([3, -1, 3, np.iinfo(np.int64).min,
                   np.iinfo(np.int64).max, 0], np.int64)
    np.testing.assert_array_equal(
        np.asarray(radix_argsort(np, xs)), np.argsort(xs, kind="stable"))


def test_partition_bucket_numpy_oracle():
    from spark_tpu.kernels import partition_bucket, slice_rows
    rng = np.random.default_rng(9)
    cap, n_parts = 64, 5
    vals = rng.integers(-100, 100, cap).astype(np.int64)
    rv = rng.random(cap) < 0.6
    pids = rng.integers(0, n_parts, cap).astype(np.int32)
    b = ColumnBatch.from_arrays({"v": vals})
    b = ColumnBatch(b.names, b.vectors, rv, b.capacity)
    bucketed, off, cnt = partition_bucket(np, b, pids, n_parts)
    off, cnt = np.asarray(off), np.asarray(cnt)
    assert cnt.sum() == rv.sum()
    assert off[0] == 0
    np.testing.assert_array_equal(off[1:], np.cumsum(cnt)[:-1])
    data = np.asarray(bucketed.vectors[0].data)
    for p in range(n_parts):
        # partition p's window holds exactly the live rows routed to p,
        # in original order (stable sort)
        want = vals[rv & (pids == p)]
        got = data[off[p]: off[p] + cnt[p]]
        np.testing.assert_array_equal(got, want)
        sl = slice_rows(bucketed, int(off[p]), int(cnt[p]))
        assert sl.capacity == cnt[p] and sl.row_valid is None
        np.testing.assert_array_equal(np.asarray(sl.vectors[0].data), want)
    # everything past the live region is dead padding
    assert np.asarray(bucketed.row_valid)[: cnt.sum()].all()
    assert not np.asarray(bucketed.row_valid)[cnt.sum():].any()


def test_partition_bucket_jit_matches_numpy():
    from spark_tpu.kernels import partition_bucket
    rng = np.random.default_rng(11)
    cap, n_parts = 32, 4
    vals = rng.integers(0, 50, cap).astype(np.int64)
    rv = rng.random(cap) < 0.5
    pids = (vals % n_parts).astype(np.int32)
    host = ColumnBatch.from_arrays({"v": vals})
    host = ColumnBatch(host.names, host.vectors, rv, host.capacity)
    nb, noff, ncnt = partition_bucket(np, host, pids, n_parts)

    dev = host.to_device()
    f = jax.jit(lambda b, p: partition_bucket(jnp, b, p, n_parts))
    jb, joff, jcnt = f(dev, jnp.asarray(pids))
    np.testing.assert_array_equal(np.asarray(jcnt), np.asarray(ncnt))
    np.testing.assert_array_equal(np.asarray(joff), np.asarray(noff))
    live = int(np.asarray(ncnt).sum())
    np.testing.assert_array_equal(
        np.asarray(jb.vectors[0].data)[:live],
        np.asarray(nb.vectors[0].data)[:live])


def test_slice_rows_is_zero_copy_view():
    from spark_tpu.kernels import slice_rows
    b = ColumnBatch.from_arrays({"v": np.arange(16, dtype=np.int64)})
    sl = slice_rows(b, 4, 8)
    assert np.shares_memory(np.asarray(sl.vectors[0].data),
                            np.asarray(b.vectors[0].data))
    assert sl.capacity == 8
    np.testing.assert_array_equal(np.asarray(sl.vectors[0].data),
                                  np.arange(4, 12))


# ---------------------------------------------------------------------------
# remap_codes + code-space range_bucket (encoded execution)
# ---------------------------------------------------------------------------

def test_remap_codes_basic_and_dtype():
    from spark_tpu.kernels import remap_codes
    codes = np.array([0, 2, 1, 0], np.int32)
    table = np.array([3, 5, 9], np.int32)     # monotone merge remap
    out = remap_codes(np, codes, table)
    np.testing.assert_array_equal(out, [3, 9, 5, 3])
    assert out.dtype == np.int32


def test_remap_codes_preserves_null_and_oob_sentinels():
    from spark_tpu.kernels import remap_codes
    hi = np.iinfo(np.int32).max
    codes = np.array([-1, 0, hi, 1, -7], np.int32)
    out = remap_codes(np, codes, np.array([4, 6], np.int32))
    # negatives (NULL) pass through; >= len(table) folds to INT32_MAX
    np.testing.assert_array_equal(out, [-1, 4, hi, 6, -7])


def test_remap_codes_empty_inputs():
    from spark_tpu.kernels import remap_codes
    hi = np.iinfo(np.int32).max
    # empty codes
    out = remap_codes(np, np.zeros(0, np.int32), np.array([1], np.int32))
    assert out.shape == (0,) and out.dtype == np.int32
    # empty table: every non-negative code is out of range
    out = remap_codes(np, np.array([-1, 0, 3], np.int32),
                      np.zeros(0, np.int32))
    np.testing.assert_array_equal(out, [-1, hi, hi])


def test_remap_codes_jit_matches_numpy():
    from spark_tpu.kernels import remap_codes
    codes = np.array([2, -1, 0, 1, 2], np.int32)
    table = np.array([1, 4, 7], np.int32)
    want = remap_codes(np, codes, table)
    got = jax.jit(lambda c, t: remap_codes(jnp, c, t))(codes, table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the join's direct-address probe lookup against the searches it stands for

_I64 = np.iinfo(np.int64)


def _table_cases():
    rng = np.random.default_rng(30)
    runs = np.sort(rng.integers(-40, 60, 200))       # every key about twice
    return {
        # name: (matchable keys, table size)
        "runs_from_negative_min": (runs, 256),
        "distinct": (np.arange(7, 107), 128),
        "one_key_many_times": (np.full(50, 12), 64),
        "one_key_once": (np.array([-9]), 8),
        "span_is_size_minus_1": (np.array([5, 5, 9, 5 + 63]), 64),
        "int64_max_is_a_key": (np.array([_I64.max - 3, _I64.max, _I64.max]),
                               8),
        "int64_min_is_a_key": (np.array([_I64.min, _I64.min + 2]), 8),
    }


@pytest.mark.parametrize("case", sorted(_table_cases()))
def test_table_search_is_both_searches(case):
    """``table_search`` gives ``searchsorted(a[:m], v, "left")`` and, with
    its count, the ``"right"`` one, for keys in the build, between its keys,
    below its first and above its last, the int64 extremes among them; the
    entries past ``m`` (the join's NULL / dead rows) are never counted."""
    from spark_tpu import kernels as K
    keys, size = _table_cases()[case]
    keys = np.sort(keys).astype(np.int64)
    m = len(keys)
    a = np.concatenate([keys, np.full(5, _I64.max)])  # the dead suffix
    assert bool(K.keys_span_under(np, a, np.int32(m), size))
    v = np.unique(np.concatenate([
        keys, keys[keys < _I64.max] + 1, keys[keys > _I64.min] - 1,
        [_I64.min, _I64.max, 0]])).astype(np.int64)
    want_lo = np.searchsorted(keys, v, side="left")
    want_hi = np.searchsorted(keys, v, side="right")
    for xp, fn in ((np, K.table_search), (jnp, K.table_search),
                   (jnp, jax.jit(K.table_search, static_argnums=(0, 4)))):
        lo, n_eq = fn(xp, xp.asarray(a), xp.asarray(np.int32(m)),
                      xp.asarray(v), size)
        np.testing.assert_array_equal(np.asarray(lo), want_lo)
        np.testing.assert_array_equal(np.asarray(lo) + np.asarray(n_eq),
                                      want_hi)
        if xp is jnp:
            assert lo.dtype == n_eq.dtype == jnp.int32


@pytest.mark.parametrize("lane", ["traced", "numpy"])
def test_keys_span_under_does_not_wrap(lane):
    """The span is read from the first and the last of the ``m`` keys, in
    uint64: a build holding both ends of int64 is not dense, a span of
    exactly ``size`` is not, ``size - 1`` is, no key at all is not."""
    from spark_tpu import kernels as K
    xp = np if lane == "numpy" else jnp

    def dense(keys, m, size):
        return bool(K.keys_span_under(
            xp, xp.asarray(np.array(keys, np.int64)),
            xp.asarray(np.int32(m)), size))

    assert dense([3, 4, 10, _I64.max], 3, 8)
    assert not dense([3, 4, 11, _I64.max], 3, 8)
    assert dense([3, 4, 11, _I64.max], 2, 8)         # the dead row's key
    assert not dense([_I64.min, _I64.max], 2, 1 << 22)
    assert not dense([_I64.min, 0], 2, 1 << 22)
    assert not dense([-1, _I64.max], 2, 1 << 22)
    assert dense([_I64.max - 1, _I64.max], 2, 8)
    assert dense([_I64.min, _I64.min + 7], 2, 8)
    assert dense([-4, 3], 2, 8)
    assert not dense([_I64.max, _I64.max], 0, 8)     # nothing matchable


def test_union_all_identical_dictionaries_fast_path():
    # all senders share one dictionary: codes concatenate untouched
    words = ("a", "b")
    b1 = ColumnBatch.from_arrays({"s": ["b", "a"]})
    b2 = ColumnBatch.from_arrays({"s": ["a", "b"]})
    assert b1.column("s").dictionary == words
    u = union_all([b1, b2])
    assert u.column("s").dictionary == words
    rows = compact(np, u).to_pylist()
    assert rows == [("b",), ("a",), ("a",), ("b",)]


def test_range_bucket_code_space_matches_word_space():
    """Mapping shared cut WORDS into each local code space via
    searchsorted(dict, cut, "left") buckets a row by its WORD alone —
    identical spans across processes whose dictionaries differ."""
    from spark_tpu.kernels import range_bucket
    cuts_w = np.asarray(["dd", "mm"], object)          # shared word cuts
    dict_a = ("aa", "cc", "dd", "zz")                  # process A
    dict_b = ("bb", "dd", "ee", "mm", "qq")            # process B
    for kdict in (dict_a, dict_b):
        local_cuts = np.searchsorted(
            np.asarray(kdict, object), cuts_w, side="left").astype(np.int64)
        codes = np.arange(len(kdict), dtype=np.int64)
        spans = range_bucket(np, codes, local_cuts)
        want = [int(np.searchsorted(cuts_w, w, side="right"))
                for w in kdict]
        np.testing.assert_array_equal(spans, want)


def test_range_bucket_code_space_nonmember_and_empty_cuts():
    from spark_tpu.kernels import range_bucket
    kdict = ("ash", "oak")
    # cut word outside the local dictionary's range → all rows one side
    local_cuts = np.searchsorted(np.asarray(kdict, object),
                                 np.asarray(["zzz"], object),
                                 side="left").astype(np.int64)
    spans = range_bucket(np, np.array([0, 1], np.int64), local_cuts)
    np.testing.assert_array_equal(spans, [0, 0])
    # zero cuts: the single span 0
    spans = range_bucket(np, np.array([0, 1], np.int64),
                         np.zeros(0, np.int64))
    np.testing.assert_array_equal(spans, [0, 0])


# ---------------------------------------------------------------------------
# run planes on device (ISSUE 20): segment-scan kernels vs dense oracle
# ---------------------------------------------------------------------------

def _plane_batch(heads, lengths, extra=None, device=True, pad_to=None):
    """A ColumnBatch whose 'ts' column is a run plane over the given run
    table, plus an optional dense int column 'v'."""
    from spark_tpu.columnar import PlaneColumnVector, RunColumnVector
    from spark_tpu.columnar import ColumnVector, pad_capacity
    heads = np.asarray(heads, np.int64)
    lengths = np.asarray(lengths, np.int64)
    cap = int(lengths.sum())
    rv = RunColumnVector(heads, lengths, T.int64)
    pv = PlaneColumnVector.from_runs(
        rv, pad_to or pad_capacity(len(heads)), device=device)
    names, vecs = ["ts"], [pv]
    if extra is not None:
        arr = np.asarray(extra, np.int64)
        assert arr.shape[0] == cap
        from spark_tpu.columnar import ColumnVector as CV
        data = jnp.asarray(arr) if device else arr
        names.append("v")
        vecs.append(CV(data, T.int64))
    return ColumnBatch(names, vecs, None, cap), np.repeat(heads, lengths)


def test_run_expand_matches_repeat_oracle():
    """The searchsorted-gather expansion decodes a zero-padded plane to
    exactly np.repeat(values, lengths) — including single-run, padded
    (zero-length) tails, and a full plane with no padding."""
    from spark_tpu.kernels import run_expand
    cases = [
        ([3, 1, 4, 1, 5], [2, 3, 1, 4, 2], 8),       # padded tail
        ([7], [12], 4),                              # single run
        ([5, 6, 7, 8], [1, 1, 1, 1], 4),             # capacity edge: full
        ([0, -3, 2], [5, 1, 10], 4),                 # negatives, long runs
    ]
    for heads, lens, plane_cap in cases:
        heads = np.asarray(heads, np.int64)
        lens = np.asarray(lens, np.int64)
        cap = int(lens.sum())
        pv = np.zeros(plane_cap, np.int64); pv[:len(heads)] = heads
        pl = np.zeros(plane_cap, np.int64); pl[:len(lens)] = lens
        oracle = np.repeat(heads, lens)
        np.testing.assert_array_equal(run_expand(np, pv, pl, cap), oracle)
        np.testing.assert_array_equal(
            np.asarray(run_expand(jnp, jnp.asarray(pv), jnp.asarray(pl),
                                  cap)), oracle)


def test_plane_filter_matches_dense_oracle_unexpanded():
    """A single-column predicate over a run plane filters by run HEAD —
    same surviving rows as the dense path, and the plane's dense form is
    never built (the data column crossed the stage compressed)."""
    from spark_tpu.columnar import unexpanded_plane
    b, dense = _plane_batch([4, 9, 2, 9, 7], [3, 1, 6, 2, 4])
    out = apply_filter(jnp, b, (col("ts") % 2) == 1)
    keep = np.asarray(out.row_valid_or_true())
    np.testing.assert_array_equal(keep, (dense % 2) == 1)
    assert unexpanded_plane(out.column("ts")) is not None, \
        "plane filter must not expand the data column"
    # and the filtered batch still aggregates exactly
    agg = grouped_aggregate(jnp, out, [], [(CountStar(), "c")])
    assert int(np.asarray(agg.column("c").data)[0]) == int(
        ((dense % 2) == 1).sum())


def test_plane_filter_empty_and_total_survivors():
    b, dense = _plane_batch([1, 2, 3], [4, 4, 4])
    none = apply_filter(jnp, b, col("ts") > 100)
    assert int(np.asarray(none.num_rows())) == 0
    all_ = apply_filter(jnp, b, col("ts") >= 0)
    assert int(np.asarray(all_.num_rows())) == dense.shape[0]


def test_plane_global_aggregate_matches_dense_oracle():
    """Keyless count/sum/min/max over a run plane reduce over
    run_values x run_lengths — value-exact against the dense oracle,
    plane never expanded."""
    from spark_tpu.columnar import unexpanded_plane
    b, dense = _plane_batch([11, -2, 40, 7], [5, 2, 9, 3])
    out = grouped_aggregate(jnp, b, [], [
        (CountStar(), "c"), (Count(col("ts")), "ct"),
        (Sum(col("ts")), "s"), (Min(col("ts")), "mn"),
        (Max(col("ts")), "mx")])
    assert unexpanded_plane(b.column("ts")) is not None
    got = {n: int(np.asarray(out.column(n).data)[0])
           for n in ("c", "ct", "s", "mn", "mx")}
    assert got == {"c": dense.shape[0], "ct": dense.shape[0],
                   "s": int(dense.sum()), "mn": int(dense.min()),
                   "mx": int(dense.max())}


def test_plane_global_aggregate_respects_row_mask():
    """With a dense row mask (a prior filter), the plane aggregate
    segments the LIVE mask per run — masked rows drop from count/sum and
    min/max, exactly as the dense path drops them."""
    b, dense = _plane_batch([11, -2, 40, 7], [5, 2, 9, 3])
    fb = apply_filter(jnp, b, col("ts") != 40)
    out = grouped_aggregate(jnp, fb, [], [
        (CountStar(), "c"), (Sum(col("ts")), "s"),
        (Min(col("ts")), "mn"), (Max(col("ts")), "mx")])
    live = dense[dense != 40]
    got = {n: int(np.asarray(out.column(n).data)[0])
           for n in ("c", "s", "mn", "mx")}
    assert got == {"c": live.shape[0], "s": int(live.sum()),
                   "mn": int(live.min()), "mx": int(live.max())}


def test_plane_global_aggregate_all_dead_is_null():
    """Zero surviving rows: sum/min/max come back NULL (valid false),
    count 0 — same null semantics as the dense keyless kernel."""
    b, _ = _plane_batch([1, 2], [4, 4])
    fb = apply_filter(jnp, b, col("ts") > 10)
    out = grouped_aggregate(jnp, fb, [], [
        (CountStar(), "c"), (Sum(col("ts")), "s"), (Min(col("ts")), "mn")])
    assert int(np.asarray(out.column("c").data)[0]) == 0
    for n in ("s", "mn"):
        v = out.column(n)
        assert v.valid is not None and not bool(np.asarray(v.valid)[0])


def test_plane_project_bare_col_stays_unexpanded():
    """SELECT of a bare plane column re-emits the plane itself; a
    computed expression over it expands in-trace (counted per trace in
    run_plane_expansions, never in runs_materialized)."""
    from spark_tpu import columnar as _col
    from spark_tpu.columnar import unexpanded_plane
    b, dense = _plane_batch([4, 9, 2], [3, 5, 8])
    p = apply_project(jnp, b, [col("ts")])
    assert unexpanded_plane(p.column("ts")) is not None
    before_host = _col.runs_materialized()
    before_exp = _col.run_plane_expansions()
    p2 = apply_project(jnp, b, [col("ts") * 2])
    np.testing.assert_array_equal(np.asarray(p2.vectors[0].data),
                                  dense * 2)
    assert _col.run_plane_expansions() == before_exp + 1
    assert _col.runs_materialized() == before_host, \
        "in-trace plane expansion must not charge the host counter"


def test_plane_capacity_edge_full_plane():
    """A run table that exactly fills its pad bucket (no zero padding at
    all) filters and aggregates exactly."""
    from spark_tpu.columnar import pad_capacity
    n = pad_capacity(6)
    heads = np.arange(n, dtype=np.int64)
    lens = np.full(n, 3, dtype=np.int64)
    b, dense = _plane_batch(heads, lens, pad_to=n)
    fb = apply_filter(jnp, b, col("ts") >= 2)
    out = grouped_aggregate(jnp, fb, [], [(Sum(col("ts")), "s")])
    assert int(np.asarray(out.column("s").data)[0]) == \
        int(dense[dense >= 2].sum())


def test_plane_kernels_jit_match_eager():
    """The segmented filter+aggregate composes under jax.jit with the
    plane riding the pytree: jitted result equals eager equals dense
    oracle."""
    b, dense = _plane_batch([5, 1, 8, 1], [7, 2, 4, 3])

    def prog(batch):
        fb = apply_filter(jnp, batch, col("ts") > 1)
        return grouped_aggregate(jnp, fb, [], [
            (CountStar(), "c"), (Sum(col("ts")), "s")])

    eager = prog(b)
    jitted = jax.jit(prog)(b)
    want_c = int((dense > 1).sum())
    want_s = int(dense[dense > 1].sum())
    for out in (eager, jitted):
        assert int(np.asarray(out.column("c").data)[0]) == want_c
        assert int(np.asarray(out.column("s").data)[0]) == want_s


# ---------------------------------------------------------------------------
# the sort aggregate after its argsort: runs, the segmented scan, the reads
# ---------------------------------------------------------------------------

def _run_shapes():
    """name -> (run lengths of the live rows, dead rows after them)."""
    powers = [n for k in range(1, 7) for n in (2 ** k - 1, 2 ** k, 2 ** k + 1)]
    return {
        "one_run": ([200], 0),
        "singletons": ([1] * 130, 0),
        "dead_tail": ([5, 1, 9, 2], 47),
        "no_live": ([], 64),
        "capacity_1": ([1], 0),
        "powers_of_two": (powers, 11),
        # a long run that starts at an odd position, between short ones:
        # every stride's i - d falls inside it, before it and on its edge
        "straddles": ([3, 333, 1, 2, 97, 1], 5),
    }


_RUN_CASES = [(kind, dt, shape) for kind in ("sum", "min", "max")
              for dt in ("int64", "float64", "int8")
              for shape in sorted(_run_shapes())] \
    + [("sum", "float64", "mixed_65536")]


@pytest.mark.parametrize("kind,dtype,shape", _RUN_CASES)
def test_reduce_runs_equals_np_segment_reduce(kind, dtype, shape):
    """``kernels.reduce_runs`` on the traced lane (the segmented scan and
    the read at each run's end) against ``_np_segment_reduce`` over the
    numpy lane's layout of the same rows: integers and min / max bit for
    bit in EVERY slot (the identity past the groups), a float sum within
    1e-12 of ``math.fsum``; and the rounds are ``ceil(log2(longest live
    run))``."""
    import math
    from spark_tpu import kernels as K
    from spark_tpu.aggregates import IDENTITY
    from spark_tpu.expressions import ExprValue

    rng = np.random.default_rng(abs(hash((kind, dtype, shape))) % (1 << 31))
    if shape == "mixed_65536":
        lengths, dead = [], 1 << 16
        while dead > 4096:                    # runs of 1..1500 rows
            lengths.append(int(rng.integers(1, 1500)))
            dead -= lengths[-1]
    else:
        lengths, dead = _run_shapes()[shape]
    n_live = sum(lengths)
    capacity = n_live + dead
    np_dt = np.dtype(dtype)
    if np_dt.kind == "f":
        data = rng.normal(size=capacity) * 10.0 ** rng.integers(
            -6, 9, capacity)
    else:
        info = np.iinfo(np_dt)
        data = rng.integers(info.min // 4, info.max // 4, capacity
                            ).astype(np_dt)
    ident = IDENTITY[kind](np_dt)
    # rows in a shuffled order, so that the layout's perm does work
    order = rng.permutation(capacity)
    key = np.empty(capacity, np.int64)
    live = np.empty(capacity, bool)
    key[order] = np.concatenate([np.repeat(np.arange(len(lengths)), lengths),
                                 rng.integers(0, 3, dead)]).astype(np.int64)
    live[order] = np.arange(capacity) < n_live
    buf = np.where(live, data, np.asarray(ident, np_dt)).astype(np_dt)

    def reduce(xp, key, live, buf):
        cols = K.group_sort_columns(xp, [ExprValue(key, None, None)], live)
        runs, (moved,) = K.sorted_runs(xp, cols, live, capacity, [buf])
        (red,), rounds = K.reduce_runs(xp, runs, [moved], [kind], capacity)
        return red, runs.num_groups, rounds

    want, n_groups, no_rounds = reduce(np, key, live, buf)
    got, got_groups, rounds = jax.jit(
        lambda *a: reduce(jnp, *a))(key, live, buf)
    got = np.asarray(got)
    assert no_rounds is None and int(got_groups) == int(n_groups) \
        == len(lengths)
    assert got.dtype == np_dt and got.shape == (capacity,)
    longest = max(lengths, default=0)
    assert int(rounds) == (math.ceil(math.log2(longest)) if longest > 1
                           else 0)
    if np_dt.kind == "f" and kind == "sum":
        rows = np.argsort(key[live], kind="stable")
        exact = np.asarray([math.fsum(r) for r in np.split(
            data[live][rows], np.cumsum(lengths)[:-1])]) if lengths \
            else np.zeros(0)
        scale = np.asarray([math.fsum(np.abs(r)) for r in np.split(
            data[live][rows], np.cumsum(lengths)[:-1])]) if lengths \
            else np.zeros(0)
        assert np.all(np.abs(got[:len(lengths)] - exact) <= 1e-12 * scale)
        assert np.all(got[len(lengths):] == 0.0)
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        assert np.array_equal(got, want)


def test_gather_columns_keeps_every_bit():
    """``kernels.gather_columns`` on the traced lane returns ``c[idx]`` for
    every dtype a column or a buffer has, bit for bit: 8-byte words split
    and rejoined, small integers and bools packed four to a word, float64
    on its own plane (-0.0, NaN payloads and infinities included)."""
    from spark_tpu import kernels as K
    rng = np.random.default_rng(5)
    n = 257
    f = rng.normal(size=n)
    f[:6] = [-0.0, 0.0, np.inf, -np.inf, np.nan, 5e-324]
    cols = [rng.integers(-1 << 62, 1 << 62, n), f,
            rng.integers(-128, 128, n).astype(np.int8), rng.random(n) < 0.5,
            rng.integers(-1 << 30, 1 << 30, n).astype(np.int32),
            rng.integers(-1 << 14, 1 << 14, n).astype(np.int16),
            rng.normal(size=n).astype(np.float32),
            rng.integers(0, 256, n).astype(np.uint8), rng.random(n) < 0.5,
            rng.integers(-128, 128, n).astype(np.int8),
            rng.integers(0, 1 << 63, n).astype(np.uint64)]
    idx = rng.integers(0, n, 400).astype(np.int32)
    got = jax.jit(lambda i, *c: K.gather_columns(jnp, c, i))(idx, *cols)
    for c, g in zip(cols, got):
        g = np.asarray(g)
        assert g.dtype == c.dtype
        assert g.tobytes() == c[idx].tobytes()


def _lane_cases():
    """name -> (keys, slots, sorted path only)."""
    from spark_tpu.aggregates import CollectList, PercentileApprox
    plain = [(Sum(Col("v")), "s"), (CountStar(), "n"), (Min(Col("i")), "lo"),
             (Max(Col("v")), "hi"), (Avg(Col("i")), "av")]
    return {
        "null_keys": ([Col("a")], plain, False),
        "dictionary_key": ([Col("w")], plain, False),
        "two_keys": ([Col("a"), Col("b")], plain, False),
        "no_validity_key": ([Col("b")], plain, False),
        "first_last": ([Col("a")], [(First(Col("c")), "f"),
                                    (Last(Col("c")), "l"),
                                    (Sum(Col("v")), "s")], False),
        "percentile": ([Col("a")], [(PercentileApprox(Col("v"), 0.5), "p"),
                                    (Sum(Col("v")), "s")], True),
        "collect": ([Col("b")], [(CollectList(Col("i")), "xs"),
                                 (Sum(Col("v")), "s")], True),
    }


def _lane_batch():
    rng = np.random.default_rng(11)
    n = 300
    a = [None if rng.random() < 0.15 else int(rng.integers(-3, 9))
         for _ in range(n)]
    c = [None if rng.random() < 0.3 else int(rng.integers(0, 50))
         for _ in range(n)]
    batch = ColumnBatch.from_arrays({
        "a": a, "b": rng.integers(0, 4, n).astype(np.int64),
        "w": list(np.array(["pear", "fig", "kiwi", "plum"])[
            rng.integers(0, 4, n)]),
        "v": rng.normal(size=n) * 100, "c": c,
        "i": rng.integers(-50, 50, n).astype(np.int64)})
    live = np.asarray(batch.row_valid_or_true()) \
        & (rng.random(batch.capacity) < 0.8)
    return ColumnBatch(batch.names, batch.vectors, live, batch.capacity)


def _rows_of(batch):
    rows = compact(np, batch.to_host()).to_pylist()
    return rows[:int(np.asarray(batch.num_rows()))]


@pytest.mark.parametrize("case,operator", [
    (case, operator) for case, (_k, _s, sorted_only) in sorted(
        _lane_cases().items())
    for operator in ("sorted", "dist") if not (sorted_only
                                               and operator == "dist")])
def test_sort_aggregate_lanes_agree(case, operator):
    """``_sorted_grouped_aggregate`` and ``parallel/dist.py``'s partial ->
    merge -> final stages give on the traced lane (runs, scan, reads) what
    they give on the numpy lane (``lexsort``, ``np.add.at``, scatters):
    NULL keys, a dictionary key, two keys, a key with no validity, first /
    last, and a percentile and a collect slot beside a sum."""
    from spark_tpu import kernels as K
    from spark_tpu.parallel.dist import (DFinalAggregate, DMergePartial,
                                         DPartialAggregate)
    from spark_tpu.sql import physical as P
    keys, slots, _sorted_only = _lane_cases()[case]
    batch = _lane_batch()

    def run(xp, b):
        if operator == "sorted":
            return K._sorted_grouped_aggregate(xp, b, keys, slots)
        partial = DPartialAggregate(keys, slots, P.PScan(0, b.schema))
        parts = partial.run(P.ExecContext(xp, [b]))
        merged = DMergePartial(keys, slots, partial, P.PScan(0, parts.schema)
                               ).run(P.ExecContext(xp, [parts]))
        return DFinalAggregate(keys, slots, partial,
                               P.PScan(0, merged.schema)
                               ).run(P.ExecContext(xp, [merged]))

    want = _rows_of(run(np, batch))
    got = _rows_of(jax.jit(lambda b: run(jnp, b))(batch.to_device()))
    assert len(got) == len(want) and len(want) > 2
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for x, y in zip(g, w):
            if isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-12, abs=1e-12)
            else:
                assert x == y
