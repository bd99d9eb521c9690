"""Window functions (`sql/core/.../execution/window/` +
`expressions/windowExpressions.scala` analog).

Design: one sort of (partition keys, order keys) per window spec, then every
window function is computed with vectorized prefix scans over the sorted
space — position arithmetic for row_number/rank/lag, prefix-sum differences
for running and bounded aggregate frames, segment totals for whole-partition
frames — and scattered back to the original row order through the inverse
permutation.  No per-partition loops: a window over 10M rows is one sort +
O(1) scans, all jit-traceable (dual-path numpy/jax like every kernel).

Frames: the Spark defaults are honored — with ORDER BY the frame is RANGE
UNBOUNDED PRECEDING..CURRENT ROW (peers included via value-group ends),
without ORDER BY it is the whole partition; explicit rowsBetween gives
row-based frames (prefix differences with segment clamping).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..aggregates import AggregateFunction, Avg, Count, CountStar, Max, Min, Sum
from ..columnar import ColumnBatch, ColumnVector
from ..expressions import AnalysisException, Col, EvalContext, Expression
from .. import tracing
from ..kernels import (
    multi_key_argsort, running_max_i32, running_sum_i32, sort_key_transform,
)
from .logical import LogicalPlan, SortOrder

__all__ = [
    "Window", "WindowSpec", "WindowExpression", "RowNumber", "Rank",
    "DenseRank", "PercentRank", "CumeDist", "NTile", "Lag", "Lead",
    "WindowNode", "compute_windows",
]


class WindowSpec:
    def __init__(self, partition_by: Sequence[Expression] = (),
                 order_by: Sequence[SortOrder] = (),
                 frame: Optional[Tuple[Optional[int], Optional[int]]] = None,
                 frame_type: str = "range"):
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        # frame bounds in rows; None = unbounded on that side
        self.frame = frame
        self.frame_type = frame_type   # 'rows' | 'range'

    def partitionBy(self, *cols) -> "WindowSpec":
        return WindowSpec([_expr(c) for c in cols], self.order_by,
                          self.frame, self.frame_type)

    def orderBy(self, *cols) -> "WindowSpec":
        orders = [_order(c) for c in cols]
        return WindowSpec(self.partition_by, orders, self.frame,
                          self.frame_type)

    def rowsBetween(self, start: int, end: int) -> "WindowSpec":
        lo = None if start <= Window.unboundedPreceding else start
        hi = None if end >= Window.unboundedFollowing else end
        return WindowSpec(self.partition_by, self.order_by, (lo, hi), "rows")

    def rangeBetween(self, start: int, end: int) -> "WindowSpec":
        if start > Window.unboundedPreceding or end < Window.unboundedFollowing:
            raise AnalysisException(
                "bounded rangeBetween is not supported; use rowsBetween")
        return WindowSpec(self.partition_by, self.order_by, None, "range")

    def _key(self):
        return (tuple(repr(e) for e in self.partition_by),
                tuple(repr(o) for o in self.order_by))

    def __repr__(self):
        return (f"WindowSpec(partitionBy={self.partition_by}, "
                f"orderBy={self.order_by}, frame={self.frame})")


def _expr(c) -> Expression:
    from .column import Column
    if isinstance(c, Column):
        return c._e
    if isinstance(c, str):
        return Col(c)
    return c


def _order(c) -> SortOrder:
    from ..logicalutils import _SortOrderHandle
    if isinstance(c, SortOrder):
        return c
    if isinstance(c, _SortOrderHandle):
        return SortOrder(c.expr, c.ascending, c.nulls_first)
    return SortOrder(_expr(c), True)


class Window:
    """Static builder (`expressions/Window.scala`)."""

    unboundedPreceding = -(1 << 62)
    unboundedFollowing = 1 << 62
    currentRow = 0

    @staticmethod
    def partitionBy(*cols) -> WindowSpec:
        return WindowSpec().partitionBy(*cols)

    @staticmethod
    def orderBy(*cols) -> WindowSpec:
        return WindowSpec().orderBy(*cols)

    @staticmethod
    def rowsBetween(start: int, end: int) -> WindowSpec:
        return WindowSpec().rowsBetween(start, end)


# ---------------------------------------------------------------------------
# window functions
# ---------------------------------------------------------------------------

class WindowFunction(Expression):
    """Rank-family functions; only meaningful under a WindowExpression."""

    requires_order = True
    children: Tuple[Expression, ...] = ()

    def data_type(self, schema) -> T.DataType:
        return T.int64

    def eval(self, ctx):
        raise AnalysisException(f"{self!r} must be used with .over(window)")

    def __repr__(self):
        return f"{type(self).__name__.lower()}()"


class RowNumber(WindowFunction):
    pass


class Rank(WindowFunction):
    pass


class DenseRank(WindowFunction):
    pass


class PercentRank(WindowFunction):
    def data_type(self, schema):
        return T.float64


class CumeDist(WindowFunction):
    def data_type(self, schema):
        return T.float64


class NTile(WindowFunction):
    def __init__(self, n: int):
        self.n = n
        self.children = ()


class _OffsetFunction(WindowFunction):
    def __init__(self, child: Expression, offset: int = 1, default=None):
        self.children = (child,)
        self.offset = offset
        self.default = default

    def data_type(self, schema):
        return self.children[0].data_type(schema)


class Lag(_OffsetFunction):
    pass


class Lead(_OffsetFunction):
    pass


class WindowExpression(Expression):
    """func OVER spec.  func is a WindowFunction or AggregateFunction."""

    def __init__(self, func, spec: WindowSpec):
        self.func = func
        self.spec = spec
        self.children = ()

    @property
    def name(self) -> str:
        return repr(self)

    def data_type(self, schema):
        return self.func.data_type(schema)

    # children stays () deliberately: generic aggregate-extraction must NOT
    # slot-ify the window function itself.  Passes that do need to see
    # inside (UDF resolution, traversal checks) use these two hooks.
    def sub_expressions(self):
        return (self.func, *self.spec.partition_by,
                *(o.child for o in self.spec.order_by))

    def map_parts(self, fn) -> "WindowExpression":
        spec = WindowSpec(
            [fn(p) for p in self.spec.partition_by],
            [type(o)(fn(o.child), o.ascending, o.nulls_first)
             for o in self.spec.order_by],
            self.spec.frame, self.spec.frame_type)
        return WindowExpression(fn(self.func), spec)

    def eval(self, ctx):
        raise AnalysisException(
            "window expressions are computed by the Window operator")

    def __repr__(self):
        return f"{self.func!r} OVER {self.spec!r}"


def contains_window(e: Expression) -> bool:
    if isinstance(e, WindowExpression):
        return True
    return any(contains_window(c) for c in e.children)


# ---------------------------------------------------------------------------
# logical node
# ---------------------------------------------------------------------------

class WindowNode(LogicalPlan):
    """Appends computed window columns to the child's output."""

    def __init__(self, wexprs: Sequence[Tuple[WindowExpression, str]],
                 child: LogicalPlan):
        self.wexprs = list(wexprs)
        self.children = (child,)

    @property
    def child(self):
        return self.children[0]

    def schema(self) -> T.StructType:
        cs = self.child.schema()
        fields = list(cs.fields)
        for we, name in self.wexprs:
            fields.append(T.StructField(name, we.data_type(cs), True))
        return T.StructType(fields)

    def __repr__(self):
        return f"Window [{', '.join(n for _, n in self.wexprs)}]"


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _next_flag_idx(xp, flags, idx, cap):
    """For each row: smallest j >= i with flags[j] (a running maximum of the
    negated positions, from the end)."""
    marked = xp.where(flags, -idx, np.int32(-cap))
    return -running_max_i32(xp, marked[::-1])[::-1]


def _segment_scan_base(xp, values, is_start):
    """For each row (sorted space): value at its segment's start row."""
    n = values.shape[0]
    idx = xp.arange(n, dtype=np.int32)
    start_idx = running_max_i32(xp, xp.where(is_start, idx, 0))
    return values[start_idx], start_idx


def compute_windows(xp, batch: ColumnBatch,
                    spec: WindowSpec,
                    funcs: Sequence[Tuple[Any, str]]) -> ColumnBatch:
    """Append window columns (same capacity, original row order).

    Positions (the permutation, segment and peer-group bounds, ranks) are
    int32 (a capacity is under 2^31): XLA:TPU's int64 scans are emulated
    in pairs of words, and its compiler refuses an int64 cumsum in some
    places (PERF.md section 7).  The device scopes ``window.sort``,
    ``window.segments``, ``window.rank`` and ``window.agg`` name the
    phases."""
    ctx = EvalContext(batch, xp)
    cap = batch.capacity
    live = xp.broadcast_to(batch.row_valid_or_true(), (cap,))
    schema = batch.schema

    # ---- sort by (dead-last, partition keys, order keys) ----------------
    with tracing.scope("window.sort"):
        sort_cols: List[Any] = [(~live).astype(np.int8)]
        part_vals = [ctx.broadcast(e.eval(ctx)) for e in spec.partition_by]
        for e, v in zip(spec.partition_by, part_vals):
            dt = e.data_type(schema)
            sort_cols += sort_key_transform(xp, v.data, v.valid, dt, True,
                                            True)
        for o in spec.order_by:
            v = ctx.broadcast(o.child.eval(ctx))
            dt = o.child.data_type(schema)
            sort_cols += sort_key_transform(xp, v.data, v.valid, dt,
                                            o.ascending, o.nulls_first)
        perm = multi_key_argsort(xp, sort_cols, cap).astype(np.int32)
        inv = _invert_perm(xp, perm, cap)
        live_s = live[perm]
        idx = xp.arange(cap, dtype=np.int32)

    with tracing.scope("window.segments"):
        # ---- segment starts (partition boundaries) in sorted space ------
        n_part_cols = 1 + 2 * len(spec.partition_by)
        part_sorted = [c[perm] for c in sort_cols[:n_part_cols]]
        is_start = xp.zeros(cap, bool)
        for c in part_sorted:
            shifted = xp.concatenate([c[:1], c[:-1]])
            is_start = is_start | (c != shifted)
        is_start = _set0_true(xp, is_start)

        seg_start_idx = running_max_i32(xp, xp.where(is_start, idx, 0))
        pos = idx - seg_start_idx                   # 0-based row in partition

        # seg_end_idx[i] = index of last row of i's segment (reverse scan
        # to the nearest following boundary)
        next_start = xp.concatenate([is_start[1:], xp.ones(1, bool)])
        seg_end_idx = _next_flag_idx(xp, next_start, idx, cap)
        seg_len = seg_end_idx - seg_start_idx + 1

        # ---- order-key value groups (peers) -----------------------------
        order_sorted = [c[perm] for c in sort_cols[n_part_cols:]]
        if order_sorted:
            vg_change = is_start
            for c in order_sorted:
                shifted = xp.concatenate([c[:1], c[:-1]])
                vg_change = vg_change | (c != shifted)
            vg_change = _set0_true(xp, vg_change)
            vg_start_idx = running_max_i32(
                xp, xp.where(vg_change, idx, 0))
            next_vg = xp.concatenate([vg_change[1:], xp.ones(1, bool)])
            vg_end_idx = _next_flag_idx(xp, next_vg, idx, cap)
        else:
            vg_change = is_start
            vg_start_idx, vg_end_idx = seg_start_idx, seg_end_idx

    names = list(batch.names)
    vectors = list(batch.vectors)

    for func, out_name in funcs:
        if isinstance(func, WindowFunction):
            with tracing.scope("window.rank"):
                data_s, valid_s, dt = _rank_family(
                    xp, func, ctx, perm, pos, seg_len, seg_start_idx,
                    seg_end_idx, vg_change, vg_start_idx, vg_end_idx, idx,
                    live_s, schema, cap)
                data, valid = _unsorted(data_s, valid_s, inv, live)
        elif isinstance(func, AggregateFunction):
            with tracing.scope("window.agg"):
                data_s, valid_s, dt = _window_aggregate(
                    xp, func, ctx, spec, perm, pos, seg_start_idx,
                    seg_end_idx, vg_end_idx, idx, live_s, schema, cap)
                data, valid = _unsorted(data_s, valid_s, inv, live)
        else:
            raise AnalysisException(f"not a window function: {func!r}")
        names.append(out_name)
        dictionary = None
        if isinstance(func, (Lag, Lead)) or (isinstance(func, (Min, Max))
                                             and dt.is_string):
            v0 = func.children[0].eval(ctx)
            dictionary = v0.dictionary
        vectors.append(ColumnVector(data.astype(dt.np_dtype)
                                    if dt.np_dtype != np.bool_
                                    else data.astype(np.bool_),
                                    dt, valid, dictionary))
    return ColumnBatch(names, vectors, batch.row_valid, cap)


def _unsorted(data_s, valid_s, inv, live):
    """A sorted-space result back in the rows' own order."""
    data = data_s[inv]
    return data, live if valid_s is None else valid_s[inv]


def _set0_true(xp, arr):
    if xp is np:
        out = arr.copy()
        out[0] = True
        return out
    return arr.at[0].set(True)


def _invert_perm(xp, perm, cap):
    idx = xp.arange(cap, dtype=np.int32)
    if xp is np:
        inv = np.empty(cap, np.int32)
        inv[perm] = idx
        return inv
    return xp.zeros(cap, np.int32).at[perm].set(idx)


def _rank_family(xp, func, ctx, perm, pos, seg_len, seg_start_idx,
                 seg_end_idx, vg_change, vg_start_idx, vg_end_idx, idx,
                 live_s, schema, cap):
    if isinstance(func, RowNumber):
        return pos + 1, live_s, T.int64
    if isinstance(func, Rank):
        return vg_start_idx - seg_start_idx + 1, live_s, T.int64
    if isinstance(func, DenseRank):
        cs = running_sum_i32(xp, vg_change)
        base, _ = _segment_scan_base(xp, cs, _first_flag(xp, seg_start_idx,
                                                         idx))
        return cs - base + 1, live_s, T.int64
    if isinstance(func, PercentRank):
        rank = vg_start_idx - seg_start_idx + 1
        denom = xp.maximum(seg_len - 1, 1)
        out = (rank - 1).astype(np.float64) / denom.astype(np.float64)
        return xp.where(seg_len > 1, out, 0.0), live_s, T.float64
    if isinstance(func, CumeDist):
        covered = vg_end_idx - seg_start_idx + 1
        return (covered.astype(np.float64)
                / seg_len.astype(np.float64)), live_s, T.float64
    if isinstance(func, NTile):
        n = np.int32(func.n)
        # Spark: first `rem` buckets get (len/n)+1 rows
        base = seg_len // n
        rem = seg_len % n
        big = (base + 1) * rem
        in_big = pos < big
        tile = xp.where(in_big,
                        pos // xp.maximum(base + 1, 1),
                        rem + (pos - big) // xp.maximum(base, 1))
        return tile + 1, live_s, T.int64
    if isinstance(func, (Lag, Lead)):
        v = ctx.broadcast(func.children[0].eval(ctx))
        dt = func.children[0].data_type(schema)
        data_s = v.data[perm]
        valid_s = None if v.valid is None else v.valid[perm]
        off = func.offset if isinstance(func, Lag) else -func.offset
        src = idx - off
        in_seg = (src >= seg_start_idx) & (src <= seg_end_idx)
        src_c = xp.clip(src, 0, cap - 1)
        src_valid = xp.ones(cap, bool) if valid_s is None else valid_s[src_c]
        if func.default is not None:
            dv = np.asarray(func.default).astype(dt.np_dtype)
            out = xp.where(in_seg, data_s[src_c].astype(dt.np_dtype), dv)
            ok = live_s & xp.where(in_seg, src_valid, True)
        else:
            out = xp.where(in_seg, data_s[src_c],
                           xp.zeros((), data_s.dtype))
            ok = in_seg & live_s & src_valid
        return out, ok, dt
    raise AnalysisException(f"unsupported window function {func!r}")


def _first_flag(xp, seg_start_idx, idx):
    return seg_start_idx == idx


def _segmented_running_scan(xp, buf, seg_id, kind: str, cap: int):
    """Inclusive running min/max within segments, vectorized.

    Hillis-Steele doubling: after pass k, out[i] covers the last 2^k rows
    of its segment; log2(cap) passes total.  Works identically under numpy
    and traced jax (static trip count)."""
    op = xp.minimum if kind == "min" else xp.maximum
    out = buf
    shift = 1
    while shift < cap:
        prev = xp.concatenate([out[:shift], out[:-shift]])
        seg_prev = xp.concatenate([seg_id[:shift], seg_id[:-shift]])
        idx = xp.arange(cap)
        same = (seg_id == seg_prev) & (idx >= shift)
        out = xp.where(same, op(out, prev), out)
        shift <<= 1
    return out


def _minmax_identity(kind: str, np_dtype):
    """Scan identity for min/max in the accumulator's OWN dtype.

    Integer min/max must stay integer (Spark's are exact); ±inf only for
    floats; bool handled (no np.iinfo)."""
    from ..aggregates import IDENTITY
    dt = np.dtype(np_dtype)
    return dt.type(IDENTITY[kind](dt))


def _window_aggregate(xp, func, ctx, spec, perm, pos, seg_start_idx,
                      seg_end_idx, vg_end_idx, idx, live_s, schema, cap):
    """sum/count/avg/min/max over partition frames via prefix scans."""
    if isinstance(func, CountStar):
        buf = live_s.astype(np.int64)
        valid_in = live_s
        dt_out = T.int64
        kind = "sum"
    else:
        v = ctx.broadcast(func.children[0].eval(ctx))
        data_s = v.data[perm]
        valid_in = live_s if v.valid is None else (live_s & v.valid[perm])
        dt_out = func.data_type(schema)
        if isinstance(func, Count):
            buf = valid_in.astype(np.int64)
            dt_out = T.int64
            kind = "sum"
        elif isinstance(func, (Sum, Avg)):
            # accumulate in the OUTPUT dtype: int64 prefix sums stay exact
            acc_np = np.float64 if isinstance(func, Avg) else dt_out.np_dtype
            buf = xp.where(valid_in, data_s.astype(acc_np),
                           xp.zeros((), acc_np))
            kind = "sum"
        elif isinstance(func, (Min, Max)):
            kind = "min" if isinstance(func, Min) else "max"
            buf = xp.where(valid_in, data_s.astype(dt_out.np_dtype),
                           _minmax_identity(kind, dt_out.np_dtype))
        else:
            raise AnalysisException(
                f"unsupported window aggregate {func!r}")
    cnt_buf = valid_in

    has_order = bool(spec.order_by)
    frame = spec.frame

    def prefix(a):
        return xp.cumsum(a)

    if kind in ("sum",) or isinstance(func, (Sum, Avg, Count, CountStar)):
        cs = prefix(buf)
        ccnt = running_sum_i32(xp, cnt_buf)
        # sentinel in the ACCUMULATOR dtype: a float64 zero would promote
        # the whole prefix array and lose int64 exactness beyond 2^53
        cs0 = xp.concatenate([xp.zeros(1, cs.dtype), cs])  # sum of rows < i
        ccnt0 = xp.concatenate([xp.zeros(1, ccnt.dtype), ccnt])

        if frame is None and not has_order:
            lo_idx, hi_idx = seg_start_idx, seg_end_idx
        elif frame is None:
            lo_idx, hi_idx = seg_start_idx, vg_end_idx   # range: incl. peers
        else:
            # offsets past the capacity clamp alike, and fit int32
            lo, hi = (None if b is None else max(-cap, min(cap, b))
                      for b in frame)
            lo_idx = seg_start_idx if lo is None else \
                xp.clip(idx + lo, seg_start_idx, seg_end_idx + 1)
            hi_idx = seg_end_idx if hi is None else \
                xp.clip(idx + hi, seg_start_idx - 1, seg_end_idx)
        total = cs0[hi_idx + 1] - cs0[lo_idx]
        count = ccnt0[hi_idx + 1] - ccnt0[lo_idx]
        if isinstance(func, (Count, CountStar)):
            return count.astype(np.int64), live_s, T.int64
        if isinstance(func, Avg):
            safe = xp.where(count > 0, count, 1.0)
            return total / safe, live_s & (count > 0), T.float64
        out_valid = live_s & (count > 0)
        return total, out_valid, dt_out

    # min/max: running or whole-partition frames only
    if frame is not None and frame != (None, 0) and frame != (None, None):
        raise AnalysisException(
            "min/max window frames support only UNBOUNDED PRECEDING")
    base_flag = seg_start_idx == idx
    if frame == (None, 0) or (frame is None and has_order):
        # running min/max with per-segment reset: vectorized Hillis-Steele
        # segmented scan (log2(cap) doubling passes; same code on numpy and
        # jax — no sequential lax.scan, no per-row Python)
        seg_id = running_sum_i32(xp, base_flag) - 1
        run = _segmented_running_scan(xp, buf, seg_id, kind, cap)
        cnt_run = running_sum_i32(xp, cnt_buf)
        c0 = xp.concatenate([xp.zeros(1, cnt_run.dtype), cnt_run])
        if frame is None:
            # default RANGE frame: the current row's ORDER BY peers are IN
            # the frame — read the running value at the peer-group end
            # (consistent with the sum/count path's vg_end_idx)
            run = run[vg_end_idx]
            count = c0[vg_end_idx + 1] - c0[seg_start_idx]
        else:
            count = c0[idx + 1] - c0[seg_start_idx]
        return run, live_s & (count > 0), dt_out
    # whole partition
    from ..kernels import segment_reduce
    seg_id = running_sum_i32(xp, base_flag) - 1
    reduced = segment_reduce(xp, buf, seg_id, cap, kind)
    cnts = segment_reduce(xp, cnt_buf.astype(np.int32), seg_id, cap, "sum")
    out = reduced[seg_id]
    count = cnts[seg_id]
    return out, live_s & (count > 0), dt_out
