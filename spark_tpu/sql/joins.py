"""Join execution.

Replaces the reference join zoo (``execution/joins/``: BroadcastHashJoinExec
on ``BytesToBytesMap``, SortMergeJoinExec's codegen merge loop
``SortMergeJoinExec.scala:36``) with ONE static-shape device algorithm,
sorted-build + binary-search probe:

1. single-key joins search on an EXACT order-consistent int64 encoding of
   the key value itself (ints directly; floats via NaN/-0.0-normalizing
   bitcast, the ``NormalizeFloatingNumbers`` analog; dictionary strings via
   a host-canonicalized shared id space) — no hashing, collisions
   impossible by construction.  Multi-key joins search on a 62-bit-masked
   combined hash with NULL/dead sentinels outside the hash range.
2. the build side sorts by search key (dead rows sentineled to the end);
3. each probe row binary-searches its match range [lo, hi) —
   ``searchsorted`` is the TPU-friendly stand-in for hash-table lookup.
   Where the search key is the value itself (1.) and the build's matchable
   keys span fewer integers than the larger of the two sides has rows — a
   surrogate key's do — the range is READ, not searched for: a
   direct-address table indexed by ``key - first`` (``kernels.table_search``:
   one scatter over the build, two int32 gathers a probe row, where a search
   is log2(build) rounds of an int64 gather).  The program decides from the
   sorted build keys, like 4.'s choice; hashes, floats' bit patterns and
   sparse ids fail the test and are searched;
4. duplicate expansion uses the counts-cumsum-gather pattern into a STATIC
   output capacity (``spark.sql.join.outputCapacityFactor`` × probe
   capacity): the running sum of the match counts gives each probe row's
   run of output slots, and ``kernels.slot_owner`` inverts it (a mark at
   every run's end and one running sum over the slots — no search from
   every slot); the true total is returned as an overflow flag that triggers
   the executor's adaptive capacity retry — the honest dynamic-shape
   escape hatch.  A build side whose matchable keys are all distinct —
   a dimension's — needs none of that: the program reads it from the
   sorted build keys (``_build_unique``) and, where the output has the
   probe's capacity, takes output slot ``j`` for probe row ``j`` — no
   second search, no slot map, no gather through the probe side;
5. every candidate pair is verified by EXACT per-key value comparison
   (null-aware), so result rows are exact even on the hash search path;
   existence for semi/anti and outer null-extension derives from a
   scatter-OR of verified pairs, never from hash-range counts alone.

Outer joins append null-padded unmatched rows.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from .. import tracing
from .. import types as T
from ..columnar import (ColumnBatch, ColumnVector, PlaneColumnVector,
                        bump_run_aware, pad_capacity, unmaterialized_runs)
from ..expressions import AnalysisException, Col, EQ, EvalContext, Expression, Hash64
from ..kernels import (_POSITIONAL_EXPRS, _scope, keys_span_under,
                       multi_key_argsort, searchsorted, slot_owner,
                       table_search, take_batch)
from .logical import Join
from . import physical as P

Array = Any


def split_equi_condition(
    on: Optional[Expression], left_cols: set, right_cols: set,
) -> Tuple[List[Tuple[Expression, Expression]], List[Expression]]:
    """Split a join condition into equi-key pairs and residual conjuncts
    (the extraction half of ``ExtractEquiJoinKeys``)."""
    from .optimizer import split_conjuncts
    if on is None:
        return [], []
    keys, residual = [], []
    for c in split_conjuncts(on):
        if isinstance(c, EQ):
            l, r = c.children
            lr, rr = l.references(), r.references()
            # BOTH sides must reference columns: `lit = col` is a FILTER,
            # not a join key (a constant 'key' would force cross-side
            # encoding of unrelated types — and the reference routes such
            # conjuncts through PushPredicateThroughJoin as filters)
            if lr and rr:
                if lr <= left_cols and rr <= right_cols:
                    keys.append((l, r))
                    continue
                if lr <= right_cols and rr <= left_cols:
                    keys.append((r, l))
                    continue
        residual.append(c)
    return keys, residual


def equi_join_keys(node: Join
                   ) -> List[Tuple[Expression, Expression]]:
    """Equi-key pairs of a LOGICAL join, oriented (left_expr, right_expr)
    — the same extraction ``plan_join_raw`` performs, exposed for
    planners that must decide PLACEMENT before planning (the
    cross-process shuffled join hashes these on each side to
    co-partition).  Empty when the join has no equi keys and therefore
    cannot be hash-partitioned (cross / pure-theta joins)."""
    if node.using:
        return [(Col(n), Col(n)) for n in node.using]
    keys, _residual = split_equi_condition(
        node.on, set(node.left.schema().names),
        set(node.right.schema().names))
    return keys


# second, independent mixing constants for match verification
class _Hash64B(Hash64):
    @staticmethod
    def _mix(xp, x):
        c1 = np.uint64(0x9E3779B97F4A7C15)
        c2 = np.uint64(0xBF58476D1CE4E5B9)
        x = xp.asarray(x).astype(np.uint64)
        x = x ^ (x >> np.uint64(31))
        x = x * c1
        x = x ^ (x >> np.uint64(29))
        x = x * c2
        x = x ^ (x >> np.uint64(32))
        return x.astype(np.int64)

    @staticmethod
    def _string_hash_table(dictionary):
        import hashlib
        out = np.empty(max(len(dictionary), 1), np.int64)
        out[:] = 0
        with tracing.span("dict.unify", words=len(dictionary)):
            for i, w in enumerate(dictionary):
                data = w if isinstance(w, bytes) else str(w).encode("utf-8")
                h = hashlib.blake2b(data, digest_size=8,
                                    key=b"spark-tpu-joinB").digest()
                out[i] = np.frombuffer(h, np.int64)[0]
        return out


# primary hash keys are masked to 62 bits (range [0, 2^62)) so the sentinels
# below are STRICTLY outside the hash range — sort/searchsorted invariants
# must hold for arbitrary hash values
_HASH_MASK = np.int64((1 << 62) - 1)
_NULL_PROBE = np.int64(-3)
_NULL_BUILD = np.int64(-5)
_DEAD_BUILD = np.int64(np.iinfo(np.int64).max)


def _bitcast_f64(xp, x):
    import jax.numpy as jnp
    from jax import lax
    if xp is np:
        return np.ascontiguousarray(np.asarray(x, np.float64)).view(np.int64)
    return lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)


_CANON_NAN = np.float64(np.nan).view(np.int64) if hasattr(np.float64(0), "view") \
    else np.int64(0x7FF8000000000000)

# NULL/dead sentinel for RANGE routing keys — only determinism matters
# (a genuine INT64_MIN key sharing the sentinel's span is harmless: span
# assignment never decides matches, the local exact join does)
_RANGE_NULL = np.int64(np.iinfo(np.int64).min)


def _orderable_f64(xp, x):
    """Total-order monotonic int64 encoding of float64 (IEEE-754 sign
    flip): -0.0 folds to +0.0 and every NaN to one canonical positive
    pattern (above +inf — Spark's NaN-greatest sort order), then
    negative bit patterns flip their magnitude bits so the int64s ascend
    exactly as the floats do.  An equality-preserving bijection, so the
    exact-join search contract is unchanged; the added monotonicity is
    what lets range cut points, sender sorts, and the local merge all
    share one encoding."""
    x = xp.where(x == 0.0, np.float64(0.0), x)   # -0.0 → +0.0
    bits = _bitcast_f64(xp, x)
    bits = xp.where(xp.isnan(x), np.int64(_CANON_NAN), bits)
    return xp.where(bits < 0, bits ^ np.int64(0x7FFFFFFFFFFFFFFF), bits)


def range_encode_key(ctx: EvalContext, expr: Expression,
                     as_float: bool = False):
    """Monotonic int64 encoding of one join-key column for range
    partitioning, or None when no such encoding exists — see
    ``range_encode_key_ex`` (this wrapper drops the dictionary)."""
    r = range_encode_key_ex(ctx, expr, as_float)
    return None if r is None else r[:2]


def range_encode_key_ex(ctx: EvalContext, expr: Expression,
                        as_float: bool = False):
    """Monotonic int64 encoding of one join-key column for range
    partitioning, or None when no such encoding exists.

    Ints/bools pass through; floats take the ``_orderable_f64`` sign-flip
    bitcast — the SAME normalization ``_exact_encode_pair`` applies, so
    span routing and the local exact merge agree on every value.  Pass
    ``as_float=True`` on the integer side of a mixed int/float pair so
    both sides encode through float64.  NULL-key and dead rows fold to
    ``_RANGE_NULL`` (span 0 on every process — deterministic routing;
    they can never match, the local join's null masks handle them).

    Dictionary strings encode as their int32 CODES: dictionaries are
    SORTED (code order == lex order), so codes are monotone in the words
    — locally orderable, but NOT comparable across processes or sides
    until the caller maps shared cut WORDS into each local code space
    (``_range_merge_join_shards``) and the exchange unifies the
    dictionaries after the hop.  The dictionary rides along in the third
    tuple slot for exactly that purpose.

    Returns ``(enc, ok, dictionary)``: routing keys, the
    live-and-non-null mask, and the column's dictionary (None for
    non-string keys)."""
    xp = ctx.xp
    v = ctx.broadcast(expr.eval(ctx))
    ok = ctx.batch.row_valid_or_true()
    if v.valid is not None:
        ok = ok & xp.broadcast_to(v.valid, (ctx.capacity,))
    if v.dictionary is not None:
        ok = ok & (v.data >= 0)            # NULL code sentinel (-1)
        enc = v.data.astype(np.int64)
        return xp.where(ok, enc, _RANGE_NULL), ok, v.dictionary
    dt = np.dtype(str(v.data.dtype))
    if as_float or np.issubdtype(dt, np.floating):
        enc = _orderable_f64(xp, v.data.astype(np.float64))
    elif dt == np.bool_ or np.issubdtype(dt, np.integer):
        enc = v.data.astype(np.int64)
    else:
        return None
    return xp.where(ok, enc, _RANGE_NULL), ok, None


def range_key_spec(node: Join, left_schema: T.StructType,
                   right_schema: T.StructType):
    """Eligibility gate for the range-partitioned merge join: exactly ONE
    equi-key pair whose two sides are both orderable types — numeric, or
    string-vs-string (dictionaries are sorted, so codes order like
    words; cut points travel as WORDS and map into each local code
    space).  Returns ``(l_expr, r_expr, l_as_float, r_as_float,
    is_string)`` or None.  Right/full joins are excluded — the skew
    mitigation replicates the build side per split span, which would
    double-count build-side null-extension."""
    if node.how not in ("inner", "left", "left_semi", "left_anti"):
        return None
    keys = equi_join_keys(node)
    if len(keys) != 1:
        return None
    l, r = keys[0]

    def _kind(e, schema):
        try:
            dt = e.data_type(schema)
        except Exception:
            return None
        if isinstance(dt, T.BooleanType) or dt.is_integral:
            return "int"
        if dt.is_fractional:
            return "float"
        if dt.is_string:
            return "str"                   # dictionary codes, word cuts
        return None                        # dates, binary, complex types

    lk = _kind(l, left_schema)
    rk = _kind(r, right_schema)
    if lk is None or rk is None:
        return None
    if (lk == "str") != (rk == "str"):
        return None                        # str never coerces to numeric
    mixed = lk != rk
    return (l, r, mixed and lk == "int", mixed and rk == "int",
            lk == "str")


def _canonical_ids(left: tuple, right: tuple):
    """(left table, right table): each dictionary's codes -> ids in ONE id
    space, the sorted union of both sides' words, so that ids compare by
    word across sides.  (None, None) where the two sides carry one
    dictionary (two reads of one relation, the arms of a self-join): their
    codes already are such ids, and nothing is built or baked into the
    program.  Host work at trace time, once a compile."""
    if left is right or left == right:
        return None, None
    with tracing.span("dict.unify", words=len(left) + len(right)):
        lw = [w if isinstance(w, str) else str(w) for w in left]
        rw = [w if isinstance(w, str) else str(w) for w in right]
        pos = {w: i for i, w in enumerate(sorted(set(lw) | set(rw)))}
        return (np.array([pos[w] for w in lw] or [0], np.int64),
                np.array([pos[w] for w in rw] or [0], np.int64))


def _exact_encode_pair(pctx: EvalContext, bctx: EvalContext,
                       l: Expression, r: Expression):
    """Exact int64 encodings of one equi-key pair, value-comparable across
    sides; None when the pair's type has no exact 64-bit encoding (then
    verification for this pair falls back to the second hash).

    Floats are normalized so NaN == NaN and -0.0 == 0.0 — the join-key
    contract of the reference's NormalizeFloatingNumbers / Spark NaN
    grouping semantics.  Dictionary strings map through a HOST-side
    canonical id space built from both dictionaries at trace time (static
    metadata), so codes compare by word value across sides."""
    xp = pctx.xp
    lv = pctx.broadcast(l.eval(pctx))
    rv = bctx.broadcast(r.eval(bctx))

    def enc(side_ctx, v, table):
        if v.dictionary is not None:
            codes = xp.clip(v.data.astype(np.int64), 0,
                            max(len(v.dictionary) - 1, 0))
            if table is None:            # one dictionary: codes ARE ids
                return codes
            with _scope(xp, "join.keys.remap"):
                return xp.asarray(table)[codes]
        dt = np.dtype(str(v.data.dtype))
        if np.issubdtype(dt, np.floating):
            return _orderable_f64(xp, v.data.astype(np.float64))
        if dt == np.bool_ or np.issubdtype(dt, np.integer):
            return v.data.astype(np.int64)
        return None

    ld = np.dtype(str(lv.data.dtype))
    rd = np.dtype(str(rv.data.dtype))
    has_dict = lv.dictionary is not None or rv.dictionary is not None
    if has_dict and (lv.dictionary is None or rv.dictionary is None):
        return None                      # string vs non-dict string
    if not has_dict and (np.issubdtype(ld, np.floating)
                         != np.issubdtype(rd, np.floating)):
        # mixed int/float pair: compare both as float64
        from ..expressions import ExprValue
        lv = ExprValue(lv.data.astype(np.float64), lv.valid, None)
        rv = ExprValue(rv.data.astype(np.float64), rv.valid, None)
    l_ids, r_ids = _canonical_ids(lv.dictionary, rv.dictionary) \
        if has_dict else (None, None)
    p_enc = enc(pctx, lv, l_ids)
    b_enc = enc(bctx, rv, r_ids)
    if p_enc is None or b_enc is None:
        return None
    p_val = None if lv.valid is None \
        else xp.broadcast_to(lv.valid, (pctx.capacity,))
    b_val = None if rv.valid is None \
        else xp.broadcast_to(rv.valid, (bctx.capacity,))
    return p_enc, p_val, b_enc, b_val


def _scatter_or(xp, size: int, idx, values):
    """out[j] = OR of values where idx == j (bounded scatter)."""
    if xp is np:
        out = np.zeros(size, bool)
        np.logical_or.at(out, np.asarray(idx), np.asarray(values))
        return out
    import jax.numpy as jnp
    return jnp.zeros(size, bool).at[idx].max(values, mode="drop")


def _build_unique(xp, ba_s, b_flag_s):
    """True where no two MATCHABLE entries of the sorted build search keys
    are equal: every probe row then has at most one candidate.  Exact path
    (``b_flag_s`` given): the rows with flag 0.  Hash path: the rows that
    carry neither the NULL nor the dead sentinel — a hash-A collision of
    two live build rows reads as "not unique"."""
    nxt = ba_s[1:]
    matchable = (b_flag_s[1:] == 0) if b_flag_s is not None \
        else ((nxt != _NULL_BUILD) & (nxt != _DEAD_BUILD))
    return ~xp.any((nxt == ba_s[:-1]) & matchable)


def _join_keys(ctx: EvalContext, exprs: Sequence[Expression],
               null_sentinel: np.int64, dead_sentinel: Optional[np.int64]
               ) -> Tuple[Array, Array]:
    """(hashA, hashB) int64 keys for one side; NULL/dead rows sentineled."""
    xp = ctx.xp
    ha = ctx.broadcast(Hash64(*exprs).eval(ctx))
    hb = ctx.broadcast(_Hash64B(*exprs).eval(ctx))
    all_valid = None
    for e in exprs:
        v = e.eval(ctx)
        if v.valid is not None:
            nn = xp.broadcast_to(v.valid, (ctx.capacity,))
            all_valid = nn if all_valid is None else (all_valid & nn)
    ka, kb = ha.data & _HASH_MASK, hb.data
    if all_valid is not None:
        ka = xp.where(all_valid, ka, null_sentinel)
    live = ctx.batch.row_valid_or_true()
    if dead_sentinel is not None:
        ka = xp.where(live, ka, dead_sentinel)
    else:
        ka = xp.where(live, ka, null_sentinel)
    return ka, kb


class PJoin(P.PhysicalPlan):
    #: build side already arrives globally (null_flag, key)-sorted —
    #: PMergeJoin skips the build sort (the merge-join contract)
    presorted_build = False

    def __init__(self, left: P.PhysicalPlan, right: P.PhysicalPlan, how: str,
                 key_pairs: Sequence[Tuple[Expression, Expression]],
                 residual: Optional[Expression],
                 schema: T.StructType, out_capacity_factor: float = 1.0):
        self.children = (left, right)
        self.how = how
        self.key_pairs = list(key_pairs)
        self.residual = residual
        self._schema = schema
        self.factor = out_capacity_factor

    def schema(self):
        return self._schema

    # ------------------------------------------------------------------
    def run(self, ctx: P.ExecContext) -> ColumnBatch:
        left = self.children[0].run(ctx)
        right = self.children[1].run(ctx)
        return self._run_on(ctx, left, right)

    def _string_keyed(self, probe: ColumnBatch, build: ColumnBatch) -> bool:
        """Whether a key pair is dictionary-coded (``join.path``'s
        ``string``)."""
        for l, r in self.key_pairs:
            try:
                if l.data_type(probe.schema).is_string \
                        or r.data_type(build.schema).is_string:
                    return True
            except Exception:
                pass
        return False

    # ------------------------------------------------------------------
    def _run_on(self, ctx: P.ExecContext, probe: ColumnBatch,
                build: ColumnBatch) -> ColumnBatch:
        xp = ctx.xp
        how = self.how

        if how == "cross" or not self.key_pairs:
            return self._cross(ctx, probe, build)

        pctx = EvalContext(probe, xp)
        bctx = EvalContext(build, xp)
        probe_live = probe.row_valid_or_true()
        build_live = build.row_valid_or_true()

        # the phases below are the device scopes of a join (tracing.py):
        # join.keys, join.build_sort, join.probe (the searches and the match
        # counts) or join.dense (the table in their place), then join.expand
        # + join.gather on the general path or join.unique on the
        # unique-build path
        with _scope(xp, "join.keys"):
            # exact int64 encodings per key pair (None → hashB fallback for
            # that pair's verification).  A single probe key riding an
            # unmaterialized run vector encodes at RUN-HEAD granularity —
            # one binary search per run of identical keys, expanded below.
            run_rid = None
            encs = None
            if xp is np and len(self.key_pairs) == 1:
                rh = self._run_head_encode(probe, bctx)
                if rh is not None:
                    encs, run_rid = rh
            if encs is None:
                encs = [_exact_encode_pair(pctx, bctx, l, r)
                        for l, r in self.key_pairs]

            exact = len(encs) == 1 and encs[0] is not None
            if exact:
                # EXACT search path: sort/search the encoded value itself —
                # no hash, collisions impossible by construction
                p_enc, p_val, b_enc, b_val = encs[0]
                b_ok = build_live if b_val is None else (build_live & b_val)
                # lexicographic (flag, key) sort puts valid keys first
                # sorted by value; null/dead rows sink into an
                # INT64_MAX-keyed suffix
                b_flag = xp.where(b_ok, np.int8(0), np.int8(1))
                sort_keys = [b_flag, b_enc]
            else:
                # multi-key / unencodable: combined-hash search with
                # sentinels.  Mixed int/float pairs hash BOTH sides as
                # float64 — int64(-7) and float64(-7.0) have different
                # hashes otherwise, silently dropping every cross-typed
                # match
                from ..expressions import Cast
                from .. import types as _T
                lks, rks = [], []
                for l, r in self.key_pairs:
                    try:
                        ldt = l.data_type(probe.schema)
                        rdt = r.data_type(build.schema)
                        if ldt.is_numeric and rdt.is_numeric \
                                and ldt.is_fractional != rdt.is_fractional:
                            l, r = Cast(l, _T.float64), Cast(r, _T.float64)
                    except Exception:
                        pass
                    lks.append(l)
                    rks.append(r)
                pa, _pb = _join_keys(pctx, lks, _NULL_PROBE, None)
                ba, _bb = _join_keys(bctx, rks, _NULL_BUILD, _DEAD_BUILD)
                sort_keys = [ba]

        with _scope(xp, "join.build_sort"):
            if exact and self.presorted_build:
                # range exchange delivered the build side already merged
                # into (flag, key) order — identity perm, no device sort
                perm = xp.arange(build.capacity, dtype=np.int32)
            else:
                perm = multi_key_argsort(xp, sort_keys, build.capacity)
            if exact:
                b_flag_s = b_flag[perm]
                ba_s = xp.where(b_flag_s == 0, b_enc[perm], _DEAD_BUILD)
            else:
                ba_s = ba[perm]
            build_s = take_batch(xp, build, perm)

        out_cap = pad_capacity(int(probe.capacity * max(self.factor, 0.1)))
        # the slot map below exists for builds that repeat a key; where
        # the output has the probe's capacity the program reads from the
        # sorted build keys whether it needs it (full's unmatched-build
        # append and a grown or shrunk output keep the general path)
        skippable = out_cap == probe.capacity \
            and how in ("inner", "left", "left_semi", "left_anti")
        with _scope(xp, "join.keys"):
            if exact:
                pa = p_enc
                if run_rid is not None and p_val is not None:
                    p_val = p_val[run_rid]       # head-sized → row-sized
                p_ok = probe_live if p_val is None else (probe_live & p_val)
            else:
                p_ok = probe_live
            if run_rid is not None:
                # the verification arrays at row granularity: every row of
                # a run shares its key, so the gather reproduces dense
                # execution exactly
                pe0, pv0, be0, bv0 = encs[0]
                encs[0] = (pe0[run_rid],
                           None if pv0 is None else pv0[run_rid], be0, bv0)
            hashb = None
            if any(e is None for e in encs):
                # unencodable pairs: fall back to the independent second
                # hash over exactly those pairs (collision ~2^-64,
                # documented)
                exprs_l = [l for (l, _), e in zip(self.key_pairs, encs)
                           if e is None]
                exprs_r = [r for (_, r), e in zip(self.key_pairs, encs)
                           if e is None]
                hashb = (pctx.broadcast(_Hash64B(*exprs_l).eval(pctx)).data,
                         bctx.broadcast(
                             _Hash64B(*exprs_r).eval(bctx)).data[perm])
            build_live_s = build_live[perm]
            build_unique = _build_unique(
                xp, ba_s, b_flag_s if exact else None) if skippable else False
            # an exact build whose matchable keys span fewer integers than
            # the join's larger side has rows (surrogate keys do) is asked
            # through a table of that many entries, not searched: read in
            # the program from the sorted keys, as ``build_unique`` is.  The
            # numpy lane keeps the searches, the tests' independent form
            tabled = exact and xp is not np
            dense = False
            if tabled:
                table_size = pad_capacity(max(probe.capacity, build.capacity))
                n_keys = xp.sum(b_flag_s == 0, dtype=np.int32)
                dense = keys_span_under(xp, ba_s, n_keys, table_size)

        def choose(unique_fn, general_fn):
            """The unique-build path or the general one, by what the sorted
            build keys say: a ``lax.cond`` on the traced lane (the program
            decides, no host sync), a Python branch on the numpy lane."""
            if not skippable:
                return general_fn()
            if xp is np:
                return unique_fn() if build_unique else general_fn()
            from jax import lax
            # a run plane memoizes its dense form at first use: expand the
            # probe's here, in the enclosing trace, or the tracer of the
            # branch traced first would be handed to the other
            for v in probe.vectors:
                if isinstance(v, PlaneColumnVector):
                    v.data
            return lax.cond(build_unique, unique_fn, general_fn)

        # each probe row's match range [lo, lo + n_eq).  By search: one
        # search says where it starts; how many build rows it holds takes a
        # second one only where a matchable build key can repeat.  By table:
        # both are read at ``key - first`` (``kernels.table_search``)
        def by_search(unique: bool):
            with _scope(xp, "join.probe"):
                lo = searchsorted(xp, ba_s, pa, side="left")
                if not unique:
                    return lo, searchsorted(xp, ba_s, pa, side="right") - lo
            with _scope(xp, "join.unique"):
                at_lo = ba_s[xp.clip(lo, 0, build.capacity - 1)]
                return lo, (at_lo == pa).astype(lo.dtype)

        def by_table():
            with _scope(xp, "join.dense"):
                return table_search(xp, ba_s, n_keys, pa, table_size)

        lookups = [lambda: by_search(False)]
        lookup = 0
        if skippable:
            lookups.append(lambda: by_search(True))
            lookup = xp.asarray(build_unique).astype(np.int32)
        if tabled:
            lookups.append(by_table)
            lookup = xp.where(dense, len(lookups) - 1, lookup)
        if xp is np:
            lo, n_eq = lookups[int(lookup)]()
        else:
            from jax import lax
            lo, n_eq = lax.switch(lookup, lookups)

        # (the running sum of the counts stays BETWEEN the two conditionals:
        # it lowers to an int64 reduce-window, which the TPU compiler refuses
        # inside a conditional's branch at most lengths under 2^18 — "ran
        # out of memory in memory space vmem while allocating on stack" —
        # and an associative scan in its place costs 70 MB of code a join)
        with _scope(xp, "join.probe"):
            if run_rid is not None:
                # per-run search results to row granularity
                lo, n_eq = lo[run_rid], n_eq[run_rid]
            counts = xp.where(p_ok, n_eq.astype(np.int64), 0)
            matched_hash = counts > 0
            if how in ("left", "full"):
                counts_eff = xp.where(probe_live, xp.maximum(counts, 1), 0)
            else:
                counts_eff = counts
            ends = xp.cumsum(counts_eff)
            total = ends[-1]

        def rows(unique: bool):
            """Output slots, exact verification and the joined rows (for a
            semi / anti join the keep mask).  ``unique``: every match count
            is 0 or 1 and the output has the probe's capacity, so output
            slot ``j`` IS probe row ``j`` — no slot map, and no gather
            through the probe side."""
            def phase(name):
                return _scope(xp, "join.unique" if unique else name)

            with phase("join.expand"):
                # output slot j → probe row i and duplicate index d
                if unique:
                    def at(a):               # i is the identity
                        return a
                    in_range = counts_eff > 0
                    first = in_range         # d is 0 in every slot
                    b_row = xp.clip(lo, 0, build.capacity - 1)
                else:
                    offsets = ends - counts_eff     # exclusive prefix
                    slot = xp.arange(out_cap, dtype=np.int64)
                    i = slot_owner(xp, ends, out_cap)
                    i = xp.clip(i, 0, probe.capacity - 1)

                    def at(a):
                        return a[i]
                    d = slot - offsets[i]
                    in_range = slot < total
                    first = d == 0
                    b_row = xp.clip(lo[i] + d, 0, build.capacity - 1)
                has_match = at(matched_hash)

                # EXACT per-pair verification (null-aware): a pair survives
                # only if every key column compares equal with both sides
                # valid
                verify = in_range & has_match & build_live_s[b_row]
                for e in encs:
                    if e is not None:
                        pe, pv, be, bv = e
                        be_s = be[perm]
                        ok = at(pe) == be_s[b_row]
                        if pv is not None:
                            ok = ok & at(pv)
                        if bv is not None:
                            ok = ok & bv[perm][b_row]
                        verify = verify & ok
                if hashb is not None:
                    verify = verify & (at(hashb[0]) == hashb[1][b_row])

            with phase("join.gather"):
                # assemble the combined (probe row, build row) batch for
                # each slot; needed before existence when a residual ON
                # conjunct participates in the match decision
                left_vectors = [
                    ColumnVector(v.data, v.dtype, v.valid, v.dictionary)
                    for v in probe.vectors] if unique \
                    else take_batch(xp, probe, i).vectors
                right_out = take_batch(xp, build_s, b_row)
                names: List[str] = list(probe.names) + list(right_out.names)
                raw_vectors: List[ColumnVector] = \
                    list(left_vectors) + list(right_out.vectors)

                if self.residual is not None:
                    # non-equi ON conjuncts are part of the MATCH CONDITION
                    # (ExtractEquiJoinKeys keeps them as the join's
                    # `condition`): a pair that fails them is not a match —
                    # it does not satisfy semi-existence and DOES
                    # null-extend in outer joins
                    rctx = EvalContext(
                        ColumnBatch(names, raw_vectors, verify, out_cap), xp)
                    rv_res = rctx.broadcast(self.residual.eval(rctx))
                    res_ok = rv_res.data.astype(bool)
                    if rv_res.valid is not None:
                        res_ok = res_ok & rv_res.valid   # NULL → no match
                    verify = verify & res_ok

                # exact existence per probe row — drives semi/anti and
                # outer null-extension (never hash-range counts alone)
                exact_m = verify if unique \
                    else _scatter_or(xp, probe.capacity, i, verify)

            if how in ("left_semi", "left_anti"):
                return exact_m if how == "left_semi" \
                    else (probe_live & ~exact_m)

            if how in ("left", "full"):
                # probe rows with zero VERIFIED matches emit one
                # null-extended row on their first slot (covers
                # zero-hash-match rows, all-pairs-refuted collisions, and
                # residual-refuted matches)
                null_slot = in_range & first & ~at(exact_m) & at(probe_live)
                pair_ok = verify | null_slot
                null_right = verify
            else:
                pair_ok = verify
                null_right = None

            vectors: List[ColumnVector] = []
            for idx, v in enumerate(raw_vectors):
                if null_right is not None and idx >= len(left_vectors):
                    base = v.valid if v.valid is not None \
                        else xp.ones(out_cap, bool)
                    v = ColumnVector(v.data, v.dtype, base & null_right,
                                     v.dictionary)
                vectors.append(v)

            out = ColumnBatch(names, vectors, pair_ok, out_cap)

            if how == "full":
                hit_b = _scatter_or(xp, build.capacity, b_row, verify)
                unmatched_b = build_live_s & ~hit_b
                out = self._append_unmatched_build(ctx, out, build_s,
                                                   unmatched_b)
            return out

        out = choose(lambda: rows(True), lambda: rows(False))

        if hasattr(ctx, "add_flag"):
            ctx.add_flag(xp.maximum(total - out_cap, 0), "join", out_cap)
            ctx.add_join_path(build_unique, dense, out_cap, probe.capacity,
                              self._string_keyed(probe, build))

        if how in ("left_semi", "left_anti"):
            return ColumnBatch(probe.names, probe.vectors,
                               probe.row_valid_or_true() & out,
                               probe.capacity)
        return out

    # ------------------------------------------------------------------
    def _run_head_encode(self, probe: ColumnBatch, bctx: EvalContext):
        """Encode the single probe-side key at RUN-HEAD granularity when
        it rides an unmaterialized run vector.  Returns ``(encs,
        run_rid)`` — head-sized probe arrays plus the per-row run-id
        gather that expands them — or None when ineligible (the caller
        then takes the ordinary dense encode).  Sound because every row
        of a run shares its key value: the encoding and both binary
        search bounds are constant within the run, so the expanded
        results are identical to dense execution."""
        l, r = self.key_pairs[0]
        refs = l.references()
        if len(refs) != 1:
            return None
        name = next(iter(refs))
        if name not in probe.names:
            return None
        rv = unmaterialized_runs(probe.vectors[probe.names.index(name)])
        if rv is None or rv.valid is not None \
                or int(rv.capacity) != int(probe.capacity):
            return None
        stack: List[Expression] = [l]
        while stack:
            e = stack.pop()
            if isinstance(e, _POSITIONAL_EXPRS):
                return None          # key depends on row position
            stack.extend(e.children)
        run_values = np.asarray(rv.run_values)
        head = ColumnBatch([name],
                           [ColumnVector(run_values, rv.dtype, None,
                                         rv.dictionary)],
                           None, len(run_values))
        enc0 = _exact_encode_pair(EvalContext(head, np), bctx, l, r)
        if enc0 is None:
            return None
        run_rid = np.repeat(np.arange(len(run_values), dtype=np.int64),
                            np.asarray(rv.run_lengths))
        bump_run_aware(int(probe.capacity))
        return [enc0], run_rid

    # ------------------------------------------------------------------
    def _append_unmatched_build(self, ctx, inner_out: ColumnBatch,
                                build_s: ColumnBatch, unmatched):
        """FULL OUTER: append build rows with no VERIFIED match,
        null-extended on the left side (exact — derived from the per-pair
        verification scatter, not hash-range hit spans)."""
        xp = ctx.xp
        cap_b = build_s.capacity

        names = inner_out.names
        left_n = len(names) - len(build_s.names)
        vectors: List[ColumnVector] = []
        for idx, (n, v) in enumerate(zip(names, inner_out.vectors)):
            if idx < left_n:
                pad_data = xp.zeros(cap_b, dtype=v.data.dtype)
                pad_valid = xp.zeros(cap_b, dtype=bool)
                data = xp.concatenate([v.data, pad_data])
                valid = xp.concatenate([
                    v.valid if v.valid is not None else xp.ones(inner_out.capacity, bool),
                    pad_valid])
            else:
                bv = build_s.vectors[idx - left_n]
                data = xp.concatenate([v.data, bv.data])
                valid = xp.concatenate([
                    v.valid if v.valid is not None else xp.ones(inner_out.capacity, bool),
                    bv.valid if bv.valid is not None else xp.ones(cap_b, bool)])
            vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
        rv = xp.concatenate([inner_out.row_valid_or_true(), unmatched])
        return ColumnBatch(names, vectors, rv, inner_out.capacity + cap_b)

    # ------------------------------------------------------------------
    def _cross(self, ctx, probe: ColumnBatch, build: ColumnBatch) -> ColumnBatch:
        """Cartesian product: all-pairs expansion (CartesianProductExec)."""
        xp = ctx.xp
        np_, nb = probe.capacity, build.capacity
        out_cap = np_ * nb
        slot = xp.arange(out_cap, dtype=np.int64)
        i = slot // nb
        j = slot % nb
        left_out = take_batch(xp, probe, i)
        right_out = take_batch(xp, build, j)
        rv = probe.row_valid_or_true()[i] & build.row_valid_or_true()[j]
        names = left_out.names + right_out.names
        vectors = left_out.vectors + right_out.vectors
        out = ColumnBatch(names, vectors, rv, out_cap)
        if self.residual is not None:
            from ..kernels import apply_filter
            out = apply_filter(xp, out, self.residual)
        return out

    def __repr__(self):
        ks = ", ".join(f"{l!r}={r!r}" for l, r in self.key_pairs)
        return f"HashJoin {self.how} keys=[{ks}] residual={self.residual!r} f={self.factor}"


class PMergeJoin(PJoin):
    """Merge join over a pre-sorted build side (SortMergeJoinExec's
    streaming-merge role, static-shape): the cross-process range exchange
    ships key-sorted runs and the receiver k-way-merges them
    (``native/merge.py``), so the per-process build sort — the O(n log n)
    device step of every PJoin — is already done.  Probe rows
    binary-search the merged build directly; everything downstream
    (expansion, exact verification, existence) is inherited unchanged."""

    presorted_build = True

    def __repr__(self):
        ks = ", ".join(f"{l!r}={r!r}" for l, r in self.key_pairs)
        return (f"MergeJoin {self.how} keys=[{ks}] "
                f"residual={self.residual!r} f={self.factor}")


def plan_join(planner, node: Join, leaves) -> P.PhysicalPlan:
    ls, rs = node.left.schema(), node.right.schema()

    if node.how == "right":
        # right outer = left outer with sides swapped; _JoinOutput restores
        # column order and picks key values from the correct side
        swapped_on = node.on
        swapped = Join(node.right, node.left, "left", swapped_on, node.using)
        inner = plan_join_raw(planner, swapped, leaves)
        rl, ll = len(rs.names), len(ls.names)
        return _JoinOutput(node.schema(), ls.names, rs.names,
                           left_base=rl, right_base=0,
                           using=node.using or [], how="right", child=inner)

    inner = plan_join_raw(planner, node, leaves)
    if inner is None:
        raise AnalysisException(f"cannot plan join {node!r}")
    if node.how in ("left_semi", "left_anti"):
        return inner
    return _JoinOutput(node.schema(), ls.names, rs.names,
                       left_base=0, right_base=len(ls.names),
                       using=node.using or [], how=node.how, child=inner)


def plan_join_raw(planner, node: Join, leaves) -> P.PhysicalPlan:
    """Physical join emitting [all left cols + all right cols] (or probe-only
    for semi/anti); duplicate names allowed internally."""
    left_p = planner._to_physical(node.left, leaves)
    right_p = planner._to_physical(node.right, leaves)
    ls, rs = node.left.schema(), node.right.schema()

    overlap = set(ls.names) & set(rs.names)
    if node.using:
        key_pairs = [(Col(n), Col(n)) for n in node.using]
        residual_list: List[Expression] = []
        overlap -= set(node.using)
    else:
        key_pairs, residual_list = split_equi_condition(
            node.on, set(ls.names), set(rs.names))
    if overlap and node.how not in ("left_semi", "left_anti"):
        raise AnalysisException(
            f"ambiguous join output columns {sorted(overlap)}; rename before "
            f"joining (select/withColumnRenamed) or join with using=[...]")

    residual = None
    if residual_list:
        from .optimizer import join_conjuncts
        residual = join_conjuncts(residual_list)

    raw_schema = T.StructType(
        [T.StructField(f.name, f.dataType, True) for f in ls.fields]
        + [T.StructField(f.name, f.dataType, True) for f in rs.fields])

    if not key_pairs:
        if node.how not in ("cross", "inner"):
            raise AnalysisException(f"{node.how} join requires equi-join keys")
        return PJoin(left_p, right_p, "cross", [], residual, raw_schema, 1.0)

    cls = PMergeJoin if getattr(node, "_presorted_build", False) else PJoin
    return cls(left_p, right_p, node.how, key_pairs, residual, raw_schema,
               planner.next_join_factor())


class _JoinOutput(P.PhysicalPlan):
    """Assembles the user-visible join output: drops duplicate USING key
    columns, restores left-then-right column order after a right-join swap,
    and coalesces key values across sides for FULL OUTER (Spark's USING
    semantics)."""

    def __init__(self, schema: T.StructType, left_names, right_names,
                 left_base: int, right_base: int, using: List[str], how: str,
                 child: P.PhysicalPlan):
        self._schema = schema
        self.left_names = list(left_names)
        self.right_names = list(right_names)
        self.left_base = left_base
        self.right_base = right_base
        self.using = list(using)
        self.how = how
        self.children = (child,)

    def schema(self):
        return self._schema

    def _left_idx(self, name: str) -> int:
        return self.left_base + self.left_names.index(name)

    def _right_idx(self, name: str) -> int:
        return self.right_base + self.right_names.index(name)

    def run(self, ctx):
        xp = ctx.xp
        batch = self.children[0].run(ctx)
        names: List[str] = []
        vectors: List[ColumnVector] = []
        for f in self._schema.fields:
            n = f.name
            if n in self.using:
                lv = batch.vectors[self._left_idx(n)]
                rv = batch.vectors[self._right_idx(n)]
                if self.how == "full":
                    vec = _coalesce_vectors(xp, lv, rv)
                elif self.how == "right":
                    vec = rv
                else:
                    vec = lv
            elif n in self.left_names:
                vec = batch.vectors[self._left_idx(n)]
            else:
                vec = batch.vectors[self._right_idx(n)]
            names.append(n)
            vectors.append(vec)
        return ColumnBatch(names, vectors, batch.row_valid, batch.capacity)

    def __repr__(self):
        return f"JoinOutput how={self.how} using={self.using}"


def _coalesce_vectors(xp, a: ColumnVector, b: ColumnVector) -> ColumnVector:
    """a if valid else b — merging string dictionaries when needed."""
    av = a.valid if a.valid is not None else xp.ones(a.data.shape[0], bool)
    bv = b.valid if b.valid is not None else xp.ones(b.data.shape[0], bool)
    if a.dictionary is not None or b.dictionary is not None:
        from ..columnar import merge_dictionaries
        merged, ra, rb = merge_dictionaries(a.dictionary or (), b.dictionary or ())
        ad = xp.asarray(ra)[xp.clip(a.data, 0, None)] if len(ra) else a.data
        bd = xp.asarray(rb)[xp.clip(b.data, 0, None)] if len(rb) else b.data
        data = xp.where(av, ad, bd).astype(np.int32)
        return ColumnVector(data, a.dtype, av | bv, merged)
    data = xp.where(av, a.data, b.data)
    return ColumnVector(data, a.dtype, av | bv, None)
