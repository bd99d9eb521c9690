"""Exact join semantics (`SortMergeJoinExec.scala:36` parity).

Joins must be EXACT, not hash-probabilistic: single-key joins search on
exact value encodings; every candidate pair is verified by value; semi/
anti existence and outer null-extension derive from verified pairs.
"""

import numpy as np
import pandas as pd
import pytest

import spark_tpu.sql.functions as F


I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min


def rows(df):
    def key(t):
        return tuple((v is None, 0 if v is None else v) for v in t)
    return sorted((tuple(r) for r in df.collect()), key=key)


def test_extreme_int64_keys(spark):
    """INT64_MAX collides with the null/dead sentinel suffix of the exact
    search path; verification must still produce the exact answer."""
    left = spark.createDataFrame(
        {"k": np.array([I64_MAX, I64_MIN, 0, 7], np.int64),
         "l": np.array([1, 2, 3, 4], np.int64)})
    right = spark.createDataFrame(
        {"k": np.array([I64_MAX, 5, I64_MIN], np.int64),
         "r": np.array([10, 20, 30], np.int64)})
    got = rows(left.join(right, "k"))
    assert got == [(I64_MIN, 2, 30), (I64_MAX, 1, 10)]


def test_negative_zero_normalization_and_nan_as_null(spark):
    """-0.0 == 0.0 on join keys (NormalizeFloatingNumbers contract).
    NaN is NULL in this engine's ingestion semantics (columnar.py NaN→NULL
    by design), so NaN-keyed rows never match — like NULL keys."""
    left = spark.createDataFrame(
        {"k": np.array([np.nan, -0.0, 1.5], np.float64),
         "l": np.array([1, 2, 3], np.int64)})
    right = spark.createDataFrame(
        {"k": np.array([np.nan, 0.0], np.float64),
         "r": np.array([10, 20], np.int64)})
    out = rows(left.join(right, "k").select("l", "r"))
    assert out == [(2, 20)]


def test_string_join_disjoint_dictionaries(spark):
    """Each side dictionary-encodes independently; equality must compare
    word VALUES through the canonical id space, not codes."""
    left = spark.createDataFrame(
        [("zebra", 1), ("apple", 2), ("mango", 3)], ["k", "l"])
    right = spark.createDataFrame(
        [("apple", 10), ("zebra", 20), ("kiwi", 30)], ["k", "r"])
    got = rows(left.join(right, "k").select("k", "l", "r"))
    assert got == [("apple", 2, 10), ("zebra", 1, 20)]


def test_null_keys_never_match(spark):
    left = spark.createDataFrame([(None, 1), (5, 2)], ["k", "l"])
    right = spark.createDataFrame([(None, 10), (5, 20)], ["k", "r"])
    assert rows(left.join(right, "k").select("l", "r")) == [(2, 20)]
    # left outer: null-key row null-extends
    got = rows(left.join(right, "k", "left").select("l", "r"))
    assert got == [(1, None), (2, 20)]
    # semi/anti exact
    assert rows(left.join(right, "k", "left_semi").select("l")) == [(2,)]
    assert rows(left.join(right, "k", "left_anti").select("l")) == [(1,)]


def test_semi_anti_with_duplicate_build_keys(spark):
    """The old dup-range shortcut trusted hashA alone when the build range
    had duplicates; existence must come from verified pairs."""
    left = spark.createDataFrame(
        {"k": np.array([1, 2, 3], np.int64), "l": np.array([1, 2, 3], np.int64)})
    right = spark.createDataFrame(
        {"k": np.array([2, 2, 2, 9, 9], np.int64),
         "r": np.arange(5, dtype=np.int64)})
    assert rows(left.join(right, "k", "left_semi").select("l")) == [(2,)]
    assert rows(left.join(right, "k", "left_anti").select("l")) == [(1,), (3,)]


@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_property_vs_pandas(spark, how):
    rng = np.random.default_rng(hash(how) % 2**31)
    n, m = 300, 200
    lk = rng.integers(0, 50, n).astype(np.int64)
    rk = rng.integers(25, 75, m).astype(np.int64)
    lv = rng.integers(0, 1000, n).astype(np.int64)
    rv = rng.integers(0, 1000, m).astype(np.int64)
    left = spark.createDataFrame({"k": lk, "l": lv})
    right = spark.createDataFrame({"k2": rk, "r": rv})
    got = rows(left.join(right, left["k"] == right["k2"], how)
               .select("l", "r"))
    pdf = pd.DataFrame({"k": lk, "l": lv}).merge(
        pd.DataFrame({"k": rk, "r": rv}), on="k",
        how={"inner": "inner", "left": "left", "right": "right",
             "full": "outer"}[how])
    def key(t):
        return tuple((v is None, 0 if v is None else v) for v in t)
    exp = sorted(((None if pd.isna(a) else int(a),
                   None if pd.isna(b) else int(b))
                  for a, b in zip(pdf["l"], pdf["r"])), key=key)
    assert got == exp


def test_property_multi_key_vs_pandas(spark):
    rng = np.random.default_rng(99)
    n, m = 250, 250
    lk1 = rng.integers(0, 10, n).astype(np.int64)
    lk2 = rng.integers(0, 10, n).astype(np.int64)
    rk1 = rng.integers(0, 10, m).astype(np.int64)
    rk2 = rng.integers(0, 10, m).astype(np.int64)
    lv = np.arange(n, dtype=np.int64)
    rv = np.arange(m, dtype=np.int64)
    left = spark.createDataFrame({"a": lk1, "b": lk2, "l": lv})
    right = spark.createDataFrame({"c": rk1, "d": rk2, "r": rv})
    cond = (left["a"] == right["c"]) & (left["b"] == right["d"])
    got = rows(left.join(right, cond).select("l", "r"))
    pdf = pd.DataFrame({"k1": lk1, "k2": lk2, "l": lv}).merge(
        pd.DataFrame({"k1": rk1, "k2": rk2, "r": rv}), on=["k1", "k2"])
    exp = sorted((int(a), int(b)) for a, b in zip(pdf["l"], pdf["r"]))
    assert got == exp


def test_dist_join_exact_matches_local(spark):
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rng = np.random.default_rng(7)
    n = 2000
    lk = rng.integers(0, 100, n).astype(np.int64)
    rk = rng.integers(50, 150, n).astype(np.int64)
    left_d = {"k": lk, "l": np.arange(n, dtype=np.int64)}
    right_d = {"k2": rk, "r": np.arange(n, dtype=np.int64)}

    def run():
        left = spark.createDataFrame(left_d)
        right = spark.createDataFrame(right_d)
        return rows(left.join(right, left["k"] == right["k2"], "left")
                    .select("l", "r"))

    spark.conf.set("spark.tpu.mesh.shards", "8")
    try:
        got = run()
    finally:
        spark.conf.set("spark.tpu.mesh.shards", "1")
    assert got == run()


def test_residual_condition_in_semi_anti(spark):
    """Non-equi ON conjuncts are part of the MATCH condition: semi/anti
    existence must respect them, not just the equi keys."""
    left = spark.createDataFrame([(1, 5), (2, 50)], ["k", "v"])
    right = spark.createDataFrame([(1, 10), (2, 10)], ["k2", "w"])
    cond = (left["k"] == right["k2"]) & (left["v"] < right["w"])
    assert rows(left.join(right, cond, "left_semi").select("k")) == [(1,)]
    assert rows(left.join(right, cond, "left_anti").select("k")) == [(2,)]


def test_residual_condition_null_extends_outer(spark):
    """A probe row whose only equi-match fails the residual is UNMATCHED:
    it must appear null-extended in a left join, not be dropped."""
    left = spark.createDataFrame([(1, 5), (2, 50)], ["k", "v"])
    right = spark.createDataFrame([(1, 10), (2, 10)], ["k2", "w"])
    cond = (left["k"] == right["k2"]) & (left["v"] < right["w"])
    got = rows(left.join(right, cond, "left").select("k", "v", "w"))
    assert got == [(1, 5, 10), (2, 50, None)]
    # full outer: the refused build row appears null-extended too
    got_full = rows(left.join(right, cond, "full").select("k", "v", "k2", "w"))
    assert got_full == [(1, 5, 1, 10), (2, 50, None, None),
                        (None, None, 2, 10)]


def test_multikey_join_mixed_int_float_keys(spark):
    """int64=float64 key pairs must match cross-typed values (review find:
    the combined hash hashed raw bits per side, dropping every match)."""
    import numpy as np
    import pandas as pd
    a = spark.createDataFrame(pd.DataFrame({
        "k1": np.array([1, 2, 3], np.int64),
        "k2": np.array([10, 20, 30], np.int64)}))
    b = spark.createDataFrame(pd.DataFrame({
        "j1": np.array([1.0, 2.0, 9.0], np.float64),
        "j2": np.array([10.0, 20.0, 90.0], np.float64),
        "v": np.array([100, 200, 900], np.int64)}))
    a.createOrReplaceTempView("mixa")
    b.createOrReplaceTempView("mixb")
    rows = spark.sql(
        "SELECT k1, v FROM mixa JOIN mixb ON k1 = j1 AND k2 = j2 "
        "ORDER BY k1").collect()
    assert [(r["k1"], r["v"]) for r in rows] == [(1, 100), (2, 200)]


def test_literal_equality_is_filter_not_join_key(spark):
    """`col = -7` in an ON clause is a filter conjunct; it must not become
    a constant 'join key' (review find via TPC-DS q91)."""
    import numpy as np
    import pandas as pd
    a = spark.createDataFrame(pd.DataFrame({"x": np.arange(4, dtype=np.int64)}))
    b = spark.createDataFrame(pd.DataFrame({
        "y": np.arange(4, dtype=np.int64),
        "g": np.array([-7.0, -7.0, -5.0, -5.0])}))
    a.createOrReplaceTempView("lita")
    b.createOrReplaceTempView("litb")
    rows = spark.sql(
        "SELECT x FROM lita JOIN litb ON x = y AND g = -7 ORDER BY x"
    ).collect()
    assert [r["x"] for r in rows] == [0, 1]


# -- the unique-build path (PJoin: chosen in the program from the sorted
# -- build keys) against the general path and a pandas reference -----------

def _join_paths(attr="unique"):
    from spark_tpu import tracing
    return [s.attrs if attr is None else s.attrs[attr]
            for s in tracing.spans() if s.name == "join.path"]


def _path_case(build, two_key, seed):
    """Probe and build frames with NULL probe keys, NULL and dead build rows
    (``live`` 0: filtered off inside the program, so they reach the join as
    dead rows), and values a residual refutes some matches by.  ``build``:
    ``unique`` (every matchable key once), ``one_dup`` (one key twice),
    ``all_dup`` (every key twice)."""
    rng = np.random.default_rng(seed)
    n = 90
    lk = rng.integers(0, 24, n).astype(float)
    lk[rng.random(n) < 0.1] = np.nan                   # NULL probe keys
    keys = np.arange(4, 28)
    if build == "one_dup":
        keys = np.append(keys, 9)
    elif build == "all_dup":
        keys = np.repeat(keys, 2)
    rk = np.concatenate([keys.astype(float),
                         [np.nan, np.nan],              # NULL build keys
                         [5.0, 5.0, 11.0]])             # dead, equal keys
    live = np.ones(len(rk), np.int64)
    live[-3:] = 0
    left = pd.DataFrame({"lid": np.arange(n), "lk": lk,
                         "lv": rng.integers(0, 10, n)})
    right = pd.DataFrame({"rid": np.arange(len(rk)), "rk": rk,
                          "rv": rng.integers(0, 10, len(rk)), "live": live})
    if two_key:
        left["lk2"] = left["lid"] % 2
        right["rk2"] = right["rid"] % 2 if build == "unique" else 0
        if build == "all_dup":
            right["rk2"] = (right["rid"] // 2) % 2
    return left, right


def _path_reference(left, right, two_key, how):
    """(lid, rid) pairs by a pandas merge of the non-NULL, live keys, the
    residual ``lv != rv`` part of the match condition."""
    on_l, on_r = (["lk", "lk2"], ["rk", "rk2"]) if two_key \
        else (["lk"], ["rk"])
    rl = right[right["live"] == 1]
    pairs = left.dropna(subset=on_l).merge(
        rl.dropna(subset=on_r), left_on=on_l, right_on=on_r)
    pairs = pairs[pairs["lv"] != pairs["rv"]]
    hit_l, hit_r = set(pairs["lid"]), set(pairs["rid"])
    inner = [(int(a), int(b)) for a, b in zip(pairs["lid"], pairs["rid"])]
    lone_l = [(int(a), None) for a in left["lid"] if a not in hit_l]
    if how == "inner":
        return sorted(inner)
    if how == "left_semi":
        return sorted((int(a),) for a in left["lid"] if a in hit_l)
    if how == "left_anti":
        return sorted((a,) for a, _ in lone_l)
    out = inner + lone_l
    if how == "full":
        out += [(None, int(b)) for b in rl["rid"] if b not in hit_r]
    return sorted(out, key=lambda t: tuple((v is None, v or 0) for v in t))


def _to_df(spark, pdf):
    cols = list(pdf.columns)
    data = [tuple(None if pd.isna(v) else int(v) for v in r)
            for r in pdf.itertuples(index=False)]
    return spark.createDataFrame(data, cols)


@pytest.mark.parametrize("lane", ["traced", "numpy"])
@pytest.mark.parametrize("two_key", [False, True],
                         ids=["exact_key", "two_key_hash"])
@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti",
                                 "full"])
@pytest.mark.parametrize("build", ["unique", "one_dup", "all_dup"])
def test_unique_build_path_parity(spark, build, how, two_key, lane):
    """The same rows whichever path ran: against the pandas reference, and
    — the unique build again with a duplicated key that no probe row has,
    which forces the general path — against the general path itself."""
    from spark_tpu import tracing
    left_p, right_p = _path_case(build, two_key, seed=17)

    def run(right_pdf):
        left, right = _to_df(spark, left_p), _to_df(spark, right_pdf)
        cond = (left["lk"] == right["rk"]) & (left["lv"] != right["rv"])
        if two_key:
            cond = cond & (left["lk2"] == right["rk2"])
        out = left.join(right.filter(right["live"] == 1), cond, how)
        tracing.reset()
        got = rows(out.select("lid") if how in ("left_semi", "left_anti")
                   else out.select("lid", "rid"))
        return got, _join_paths()

    spark.conf.set("spark.sql.codegen.wholeStage",
                   "true" if lane == "traced" else "false")
    try:
        got, paths = run(right_p)
        assert got == _path_reference(left_p, right_p, two_key, how)
        # full joins keep the general path; else the build decides (an
        # overflowing join runs, and reports, once more at a grown capacity)
        assert set(paths) == {build == "unique" and how != "full"}
        if build == "unique" and how != "full":
            extra = right_p.iloc[[0, 0]].assign(rk=1000, rid=[900, 901])
            forced, paths = run(pd.concat([right_p, extra]))
            assert set(paths) == {False}
            assert forced == got
    finally:
        spark.conf.set("spark.sql.codegen.wholeStage", "true")


@pytest.mark.parametrize("lane", ["traced", "numpy"])
def test_build_unique_reads_matchable_neighbours_only(lane):
    """Equal neighbours that are both live and valid: not unique.  Equal
    neighbours that are the NULL / dead sentinels (hash path) or flagged
    rows (exact path): still unique."""
    import jax.numpy as jnp
    from spark_tpu.sql import joins as J
    xp = np if lane == "numpy" else jnp
    dead, null = J._DEAD_BUILD, J._NULL_BUILD

    def unique(keys, flags=None):
        return bool(J._build_unique(
            xp, xp.asarray(np.array(keys, np.int64)),
            None if flags is None else xp.asarray(np.array(flags, np.int8))))

    # hash path: sentinels outside the 62-bit hash range
    assert unique([null, null, 3, 8, 9, dead, dead])
    assert not unique([null, 3, 8, 8, dead])
    assert unique([7])
    # exact path: (flag, key) order, flagged rows carry the dead sentinel
    assert unique([1, 2, 5, dead, dead], [0, 0, 0, 1, 1])
    assert not unique([1, 5, 5, dead], [0, 0, 0, 1])
    # a live key that IS int64 max beside a flagged row is one key, once
    assert unique([1, dead, dead], [0, 0, 1])


def test_join_path_spans_and_overflow_flag(spark):
    """Each executed join reports its path beside the overflow flags: the
    flag reads 0 on the unique path (no output slot past the probe's
    capacity by construction), and no overflow test sees the path flag."""
    import jax.numpy as jnp
    from spark_tpu import tracing
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import QueryExecution, _overflow_ratio
    fact = spark.createDataFrame(
        {"k": np.arange(64, dtype=np.int64) % 16,
         "v": np.arange(64, dtype=np.int64)})
    for dim_keys, want in ((np.arange(16), True),
                           (np.arange(16) // 2, False)):
        dim = spark.createDataFrame(
            {"dk": dim_keys.astype(np.int64),
             "w": np.arange(16, dtype=np.int64)})
        q = fact.join(dim, fact["k"] == dim["dk"])
        pq = QueryExecution(spark, q._plan).planned
        for xp, leaves in ((np, [b.to_host() for b in pq.leaves]),
                           (jnp, [b.to_device() for b in pq.leaves])):
            ctx = P.ExecContext(xp, leaves)
            pq.physical.run(ctx)
            assert ctx.flag_kinds == ["join", P.JOIN_PATH]
            flags = [int(f) for f in ctx.flags]
            # keys 0..15: the traced lane reads them from the table, the
            # numpy lane keeps its searches
            assert flags[1] == -(want * P.PATH_UNIQUE
                                 + (xp is jnp) * P.PATH_DENSE)
            assert _overflow_ratio(flags[1:], ctx.flag_caps[1:]) == 0.0
            if want:
                assert flags[0] == 0 and not any(f > 0 for f in flags)
        tracing.reset()
        q.collect()
        assert _join_paths()[-1] is want
    # over the mesh a path reads taken only where every shard took it
    for shards, all_took in (([3, 2], 2), ([1, 2], 0), ([3, 3, 3], 3),
                             ([3, 1, 0], 0), ([1, 3], 1)):
        assert P.all_shards_path(-np.array(shards, np.int32),
                                 lambda x: x.max()) == -all_took


# -- the probe lookup by table (kernels.table_search: taken in the program
# -- where the build's keys span fewer integers than the join's larger side
# -- has rows) against the numpy lane's searches and a plain reference ------

_DENSE_KEYS = {
    # kind: (the build's distinct keys, probe keys the build lacks: inside
    #        its span, below its first, above its last; reads dense)
    "negative_min": (lambda t: ([k for k in range(-12, 12)
                                 if k not in (-3, 4)], [-3, 4, -13, 12, 900]),
                     True),
    "span_t_minus_1": (lambda t: ([100, 101, 103, 110, 100 + t - 1],
                                  [102, 99, 100 + t, 100 + t + 1]), True),
    "span_t": (lambda t: ([100, 101, 103, 110, 100 + t],
                          [102, 99, 100 + t - 1, 100 + t + 1]), False),
    "int64_extremes": (lambda t: ([I64_MIN, -5, 0, 7, I64_MAX - 1],
                                  [I64_MIN + 1, 3, I64_MAX]), False),
    "float": (lambda t: ([0.5, 1.5, 2.5, 3.0, 4.0], [0.75, -1.0, 99.0]),
              False),
    "two_key": (lambda t: (list(range(-12, 12)), [-13, 12, 900]), False),
    "string": (lambda t: (list("bcefghkmnpq"), ["d", "a", "z"]), True),
    "boolean": (lambda t: ([False, True], []), True),
}


def _dense_case(kind, build, seed=30):
    """Probe rows (lid, lk, lv) and build rows (rid, rk, rv, live): the probe
    holds every build key, keys the build lacks on each side of and inside
    its span, and NULLs; the build NULL keys and dead rows, one of them far
    outside the span (a flagged row is no key)."""
    from spark_tpu.columnar import pad_capacity
    rng = np.random.default_rng(seed)
    n_probe = 56
    table = pad_capacity(n_probe)                  # the build is smaller
    distinct, lacking = _DENSE_KEYS[kind][0](table)
    reps = {"unique": [1] * len(distinct),
            "one_dup": [1, 2] + [1] * (len(distinct) - 2),
            "all_dup": [2] * len(distinct)}[build]
    rk = [k for k, r in zip(distinct, reps) for _ in range(r)]
    far = {"float": 1e12, "string": "zz", "boolean": True}.get(
        kind, 10 ** 15)
    rk += [None, None, distinct[0], distinct[-1], far]
    live = [1] * (len(rk) - 3) + [0, 0, 0]
    order = rng.permutation(len(rk))
    right = [(int(i), rk[j], int(rng.integers(0, 4)), live[j])
             for i, j in enumerate(order)]
    lk = list(distinct) + list(lacking) + [None, None, None]
    lk += [lk[j] for j in rng.integers(0, len(lk), n_probe - len(lk))]
    left = [(int(i), lk[j], int(rng.integers(0, 4)))
            for i, j in enumerate(rng.permutation(n_probe))]
    assert pad_capacity(len(right)) <= table
    return left, right, table


def _dense_reference(left, right, how):
    """The join by two loops: equal non-NULL keys of a live build row, the
    residual ``lv <> rv`` part of the match condition."""
    pairs = [(a, b) for a, lk, lv in left for b, rk, rv, live in right
             if live and lk is not None and rk is not None
             and lk == rk and lv != rv]
    hit_l, hit_r = {a for a, _ in pairs}, {b for _, b in pairs}
    if how == "left_semi":
        return sorted((a,) for a, _k, _v in left if a in hit_l)
    if how == "left_anti":
        return sorted((a,) for a, _k, _v in left if a not in hit_l)
    out = list(pairs)
    if how in ("left", "full"):
        out += [(a, None) for a, _k, _v in left if a not in hit_l]
    if how == "full":
        out += [(None, b) for b, _k, _v, live in right
                if live and b not in hit_r]
    return sorted(out, key=lambda t: tuple((v is None, v or 0) for v in t))


def _dense_run(spark, left, right, how, two_key=False):
    """The join's rows and its ``join.path`` spans' attributes on each lane:
    ``{lane: (rows, [attrs])}``."""
    from spark_tpu import tracing, types as T
    key = next(k for _i, k, _v in left if k is not None)
    key_t = {bool: T.boolean, float: T.float64, str: T.string}.get(
        type(key), T.int64)

    def frame(data, names):
        return spark.createDataFrame(data, T.StructType([
            T.StructField(n, key_t if n in ("lk", "rk") else T.int64, True)
            for n in names]))
    ldf = frame(left, ["lid", "lk", "lv"])
    rdf = frame(right, ["rid", "rk", "rv", "live"])
    if two_key:
        ldf = ldf.withColumn("lk2", F.lit(1))
        rdf = rdf.withColumn("rk2", F.lit(1))
    cond = (ldf["lk"] == rdf["rk"]) & (ldf["lv"] != rdf["rv"])
    if two_key:
        cond = cond & (ldf["lk2"] == rdf["rk2"])
    out = ldf.join(rdf.filter(rdf["live"] == 1), cond, how)
    out = out.select("lid") if how in ("left_semi", "left_anti") \
        else out.select("lid", "rid")
    got = {}
    try:
        for lane in ("traced", "numpy"):
            spark.conf.set("spark.sql.codegen.wholeStage",
                           "true" if lane == "traced" else "false")
            tracing.reset()
            got[lane] = (rows(out), _join_paths(None))
    finally:
        spark.conf.set("spark.sql.codegen.wholeStage", "true")
    return got


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti",
                                 "full"])
@pytest.mark.parametrize("build", ["unique", "one_dup", "all_dup"])
@pytest.mark.parametrize("kind", sorted(_DENSE_KEYS))
def test_dense_table_path_parity(spark, kind, build, how):
    """The same rows whether the probe's matches are read from the table or
    searched for: the traced lane against the numpy lane (which keeps the
    searches) and against two plain loops, with the path each join reports.
    The table is taken exactly where one key pair has an int64 encoding and
    the build's matchable keys span fewer integers than the table has
    entries: up to ``T - 1`` and not ``T``, never by wrapping, never for a
    float's bit pattern or a hash."""
    from spark_tpu.columnar import pad_capacity
    left, right, table = _dense_case(kind, build)
    got = _dense_run(spark, left, right, how, two_key=kind == "two_key")
    want = _dense_reference(left, right, how)
    assert got["traced"][0] == want and got["numpy"][0] == want
    for lane, dense in (("traced", _DENSE_KEYS[kind][1]), ("numpy", False)):
        for path in got[lane][1]:
            assert path["probe_cap"] == pad_capacity(len(left)) == table
            assert path["dense"] is dense
            # (the session keeps a statement shape's output capacity: after
            # a build that repeated its keys the output outgrows the probe)
            assert path["unique"] is (build == "unique" and how != "full"
                                      and path["out_cap"] == table)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti",
                                 "full"])
@pytest.mark.parametrize("build", ["no_live_row", "all_null_keys"])
def test_dense_table_path_needs_a_key(spark, build, how):
    """A build with no matchable key has no span: the search path, and no
    probe row matches."""
    left, right, _t = _dense_case("negative_min", "unique")
    right = [(rid, rk if build == "no_live_row" else None, rv,
              0 if build == "no_live_row" else live)
             for rid, rk, rv, live in right]
    got = _dense_run(spark, left, right, how)
    want = _dense_reference(left, right, how)
    assert got["traced"][0] == want and got["numpy"][0] == want
    assert {p["dense"] for p in got["traced"][1]} == {False}
    assert len(want) == {"inner": 0, "left_semi": 0}.get(how, len(want))


@pytest.mark.parametrize("build", ["unique", "all_dup"])
def test_dense_table_path_takes_a_run_plane_probe(spark, build):
    """A probe key that arrives as a run plane (runs of equal keys, unexpanded
    at the stage boundary) is looked up in the table like a dense column."""
    from spark_tpu import tracing, types as T
    from spark_tpu.columnar import ColumnBatch, ColumnVector, RunColumnVector
    from spark_tpu.sql import logical as L
    from spark_tpu.sql.dataframe import DataFrame
    heads = np.array([3, 9, 4, 40, -2, 9, 5, 6], np.int64)   # 40, -2: absent
    lens = np.full(8, 16, np.int64)
    probe = ColumnBatch(
        ["ts", "v"],
        [RunColumnVector(heads, lens, T.int64),
         ColumnVector(np.arange(128, dtype=np.int64) % 5, T.int64)],
        None, 128)
    DataFrame(spark, L.LocalRelation(probe)).createOrReplaceTempView("dp_probe")
    k = np.repeat(np.arange(3, 11), 2 if build == "all_dup" else 1)
    spark.createDataFrame({"k": k.astype(np.int64),
                           "w": np.arange(len(k), dtype=np.int64)}
                          ).createOrReplaceTempView("dp_dim")
    try:
        tracing.reset()
        got = spark.sql("SELECT count(*) AS c, sum(ts) AS st, sum(w) AS sw, "
                        "sum(v) AS sv FROM dp_probe JOIN dp_dim ON ts = k"
                        ).collect()
        dense, unique = _join_paths("dense"), _join_paths()
    finally:
        spark.catalog.dropTempView("dp_probe")
        spark.catalog.dropTempView("dp_dim")
    ts, v = np.repeat(heads, lens), np.arange(128) % 5
    pairs = [(t, x, w) for t, x in zip(ts, v)
             for kk, w in zip(k, range(len(k))) if kk == t]
    assert tuple(got[0]) == (len(pairs), sum(p[0] for p in pairs),
                             sum(p[2] for p in pairs),
                             sum(p[1] for p in pairs))
    assert set(dense) == {True} and set(unique) == {build == "unique"}


# -- the general path's slot-to-probe-row map (kernels.slot_owner: one
# -- scatter of marks and one running sum) against the search it replaced ---

def _slot_cases():
    rng = np.random.default_rng(28)
    mixed = rng.integers(0, 5, 64)
    left = np.maximum(rng.integers(0, 3, 64), 1)     # a left join's counts_eff
    left[rng.random(64) < 0.2] = 0                   # ... of dead probe rows
    return {
        "zeros_at_head": (np.r_[np.zeros(5, int), mixed], 256),
        "zeros_in_middle": (np.r_[mixed[:20], np.zeros(9, int), mixed[20:]],
                            256),
        "zeros_at_tail": (np.r_[mixed, np.zeros(7, int)], 256),
        "every_count_zero": (np.zeros(32, int), 64),
        "one_row_owns_every_slot": (np.r_[0, 0, 128, 0], 128),
        "overflow_total_past_slots": (mixed * 4, 128),
        "overflow_one_end_at_n_slots": (np.r_[3, 61, 5], 64),
        "slots_16x_rows": (rng.integers(0, 30, 64), 1024),
        "slots_equal_rows": (rng.integers(0, 3, 128), 128),
        "slots_a_tenth_of_rows": (rng.random(160) < 0.05, 16),
        "left_join_counts_eff": (left, 256),
    }


@pytest.mark.parametrize("case", list(_slot_cases()))
def test_slot_owner_is_the_right_search(case):
    """``slot_owner`` on the jax lane (eager and inside ``jit``) is
    ``np.searchsorted(ends, arange(n), "right")`` slot for slot — also
    where the join overflows: marks at or past ``n_slots`` drop, the slots
    below agree."""
    import jax
    import jax.numpy as jnp
    from spark_tpu import kernels as K
    counts, n_slots = _slot_cases()[case]
    ends = np.cumsum(np.asarray(counts).astype(np.int64))
    want = np.searchsorted(ends, np.arange(n_slots), "right")
    if case.startswith("overflow"):
        assert ends[-1] > n_slots
    np.testing.assert_array_equal(K.slot_owner(np, ends, n_slots), want)
    for fn in (K.slot_owner,
               jax.jit(K.slot_owner, static_argnums=(0, 2))):
        got = fn(jnp, jnp.asarray(ends), n_slots)
        assert got.dtype == jnp.int32 and got.shape == (n_slots,)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("how", ["inner", "left_semi", "left_anti"])
def test_fanout_self_join_lanes_agree(spark, how):
    """TPC-DS q95 / q94's shape: orders of 12 lines joined to themselves on
    the order number with a ``<>`` residual, 16 output slots a probe row
    (``join_factor_override``).  The jax lane's rows are the numpy lane's,
    and the pairs a plain count says there must be."""
    import jax.numpy as jnp
    from spark_tpu import kernels as K
    from spark_tpu.sql import physical as P
    from spark_tpu.sql.planner import Planner, QueryExecution
    n = 1000                                           # pads to 1024
    order, wh = np.arange(n) // 12, (np.arange(n) * 7 // 3) % 5
    spark.createDataFrame(
        {"o": order.astype(np.int64), "w": wh.astype(np.int64),
         "line": np.arange(n, dtype=np.int64)}) \
        .createOrReplaceTempView("fanout_lines")
    on = "a.o = b.o AND a.w <> b.w"
    q = spark.sql({
        "inner": "SELECT a.line, b.line AS other FROM fanout_lines a, "
                 f"fanout_lines b WHERE {on}",
        "left_semi": "SELECT a.line FROM fanout_lines a WHERE EXISTS "
                     f"(SELECT * FROM fanout_lines b WHERE {on})",
        "left_anti": "SELECT a.line FROM fanout_lines a WHERE NOT EXISTS "
                     f"(SELECT * FROM fanout_lines b WHERE {on})"}[how])
    pq = Planner(spark, join_factor_override=[16.0]).plan(
        QueryExecution(spark, q._plan).optimized)
    spark.catalog.dropTempView("fanout_lines")
    assert f"HashJoin {how}" in pq.physical.tree_string()

    def run(xp, leaves):
        ctx = P.ExecContext(xp, leaves)
        out = K.compact(xp, pq.physical.run(ctx))
        caps = [c for k, c in zip(ctx.flag_kinds, ctx.flag_caps)
                if k == P.JOIN_PATH]
        flags = [int(f) for f in ctx.flags]
        assert caps == [(1 << 14, 1 << 10, False)] \
            and not any(f > 0 for f in flags)
        return sorted(out.to_host().to_pylist())

    got = run(jnp, [b.to_device() for b in pq.leaves])
    assert got == run(np, [b.to_host() for b in pq.leaves])
    same = order[:, None] == order[None, :]
    pairs = same & (wh[:, None] != wh[None, :])
    if how == "inner":
        a, b = np.nonzero(pairs)
        assert got == sorted(zip(a.tolist(), b.tolist()))
    else:
        keep = pairs.any(axis=1) == (how == "left_semi")
        assert got == [(int(i),) for i in np.nonzero(keep)[0]]
