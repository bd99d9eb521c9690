"""Physical operators.

The analog of ``sql/core/.../execution/SparkPlan.scala`` operators, with one
deep difference: operators do not produce iterators — each node's ``run`` is
a PURE ARRAY FUNCTION over ColumnBatches, and the whole tree executes inside
one ``jax.jit`` trace.  XLA fusing that trace is the WholeStageCodegen
analog (``WholeStageCodegenExec.scala:312``), with none of the produce/
consume protocol: function composition does it.

Host-only metadata (string dictionaries) is static under jit, so even
dictionary merging for Union/Join key alignment happens "inside" the traced
function — it runs at trace time on the host, the resulting remap tables are
baked into the program as constants.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from .. import tracing
from .. import types as T
from ..aggregates import AggregateFunction
from ..columnar import ColumnBatch, ColumnVector, merge_dictionaries, pad_capacity
from ..expressions import EvalContext, Expression, LT, Rand
from ..kernels import (
    apply_filter, apply_limit, apply_project, distinct as k_distinct,
    grouped_aggregate, sort_batch,
)

Array = Any


#: the flag kind of ``ExecContext.add_join_path``, and the host span that
#: ``record_join_paths`` writes for it
JOIN_PATH = "join.path"
#: the bits of that flag's negated value
PATH_UNIQUE, PATH_DENSE = 1, 2


def record_join_paths(int_flags, kinds, caps=()) -> None:
    """One ``join.path`` span for each join of the step whose flags were
    just fetched: ``unique`` and ``dense`` (the paths it took: the rows and
    the probe lookup) and, from the trace's static facts where the lane
    kept them, ``out_cap`` and ``probe_cap`` (the join's fan-out) and
    ``string`` (a key pair is dictionary-coded).  ``python
    -m spark_tpu.tracing`` and the benchmark's ``join.unique_pct`` /
    ``join.dense_pct`` read them."""
    for n, (f, k) in enumerate(zip(int_flags, kinds)):
        if k == JOIN_PATH:
            attrs = {"unique": bool(-f & PATH_UNIQUE),
                     "dense": bool(-f & PATH_DENSE)}
            if n < len(caps):
                attrs["out_cap"], attrs["probe_cap"], attrs["string"] = \
                    caps[n]
            with tracing.span(JOIN_PATH, **attrs):
                pass


#: the operator metric a sort aggregate adds (``ExecContext.add_metric``):
#: the rounds its segmented scan took, ``ceil(log2(longest live run))``
SCAN_ROUNDS = "agg.scan_rounds"
#: and the zero-length host span ``record_scan_rounds`` writes for it
AGG_SCAN = "agg.scan"


def record_scan_rounds(metrics) -> None:
    """One ``agg.scan`` span (``op``, ``rounds``) for each ``agg.scan_rounds``
    among the operator metrics just fetched (``{(op_id, label): value}``):
    ``python -m spark_tpu.tracing`` tallies them beside the join paths."""
    for (op_id, label), rounds in metrics.items():
        if label == SCAN_ROUNDS:
            with tracing.span(AGG_SCAN, op=op_id, rounds=rounds):
                pass


def all_shards_path(flag, pmax):
    """A ``JOIN_PATH`` flag over the mesh: a path reads taken only where
    every shard took it, so each bit is the minimum over shards."""
    return sum(pmax(-(-flag & bit)) for bit in (PATH_UNIQUE, PATH_DENSE))


class ExecContext:
    def __init__(self, xp, leaves: List[ColumnBatch]):
        self.xp = xp
        self.leaves = leaves
        # traced scalars checked host-side after execution (join/exchange
        # overflow accounting — the dynamic-shape escape hatch); kinds and
        # static capacities let the executor adapt the right factor and
        # size the retry from the measured overflow
        self.flags: List[Array] = []
        self.flag_kinds: List[str] = []
        self.flag_caps: List[int] = []
        # per-operator metrics (SQLMetrics.scala:34 analog): traced row
        # counts keyed by (op_id, label), fetched with the result
        self.metrics: List[Tuple[int, str, Array]] = []

    def add_flag(self, value: Array, kind: str, cap: int) -> None:
        self.flags.append(value)
        self.flag_kinds.append(kind)
        self.flag_caps.append(cap)

    def add_join_path(self, unique, dense, out_cap: int,
                      probe_cap: int, string: bool = False) -> None:
        """Which paths a join ran (``joins.PJoin``: the unique-build rows or
        the general ones; the probe lookup by table or by search), beside
        the overflow flags so that it comes back in their fetch: kind
        ``JOIN_PATH``, minus the sum of ``PATH_UNIQUE`` and ``PATH_DENSE``
        where taken.  Never positive, so no overflow test (each reads ``f >
        0``) sees it; over shards each bit is reduced apart
        (``all_shards_path``).  Its static "capacity" is the triple (output
        slots, probe capacity, whether a key pair is dictionary-coded),
        which ``record_join_paths`` puts on the span."""
        xp = self.xp
        bits = xp.asarray(unique).astype(np.int32) * PATH_UNIQUE \
            + xp.asarray(dense).astype(np.int32) * PATH_DENSE
        self.add_flag(-bits, JOIN_PATH, (out_cap, probe_cap, bool(string)))

    def add_metric(self, op_id: int, label: str, value: Array) -> None:
        self.metrics.append((op_id, label, value))


def _under_operator_scope(run):
    """``run`` under ``scope("<class>#<op_id>")`` on the traced lane, so an
    HLO op's ``op_name`` names the operator it came from (metadata only:
    nothing of it enters ``key()`` or a stage fingerprint).  An operator
    whose ``run`` calls its base class's opens one scope, not two."""
    @functools.wraps(run)
    def scoped(self, ctx):
        if ctx.xp is np or getattr(ctx, "_scoped_op", None) is self:
            return run(self, ctx)
        outer, ctx._scoped_op = getattr(ctx, "_scoped_op", None), self
        try:
            with tracing.scope(f"{type(self).__name__}#{self.op_id}"):
                return run(self, ctx)
        finally:
            ctx._scoped_op = outer
    return scoped


class PhysicalPlan:
    children: Tuple["PhysicalPlan", ...] = ()
    #: stable preorder position, assigned by the planner; shifted into the
    #: upper bits of RowIndex/Rand offsets so non-deterministic expressions
    #: decorrelate across operators (MonotonicallyIncreasingID's partition-id
    #: trick, reapplied to operator identity)
    op_id: int = 0

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "run" in cls.__dict__:
            cls.run = _under_operator_scope(cls.__dict__["run"])

    @property
    def row_offset(self) -> int:
        return self.op_id << 33

    def offset_in(self, ctx: "ExecContext"):
        """Operator offset + shard offset (traced under shard_map)."""
        shard = getattr(ctx, "shard_offset", 0)
        return self.row_offset + shard

    def schema(self) -> T.StructType:
        raise NotImplementedError

    def run(self, ctx: ExecContext) -> ColumnBatch:
        raise NotImplementedError

    def key(self) -> str:
        """Structural fingerprint for the jit cache (data-independent parts;
        dictionaries/capacities live in the pytree treedef and are handled
        by jax's own retrace logic)."""
        inner = ",".join(c.key() for c in self.children)
        return f"{self!r}({inner})"

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + "*- " + repr(self) + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def __repr__(self):  # pragma: no cover
        return type(self).__name__


class PMetric(PhysicalPlan):
    """Transparent wrapper recording the child's output row count
    (`SQLMetrics` numOutputRows); inserted by the planner when
    spark.sql.metrics.enabled is on."""

    def __init__(self, child: PhysicalPlan):
        self.children = (child,)

    @property
    def label(self) -> str:
        return repr(self.children[0]).split("(")[0].split(" ")[0]

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx: ExecContext) -> ColumnBatch:
        out = self.children[0].run(ctx)
        ctx.add_metric(self.children[0].op_id, self.label, out.num_rows())
        return out

    def key(self):
        return f"M({self.children[0].key()})"

    def __repr__(self):
        return "Metric"


class PScan(PhysicalPlan):
    """Leaf: reads the i-th prepared input batch (device-resident under jit).

    Plays the role of scan + ``InputAdapter``; columnar by construction
    (reference ``ColumnarBatchScan.scala``)."""

    def __init__(self, index: int, schema: T.StructType):
        self.index = index
        self._schema = schema

    def schema(self):
        return self._schema

    def run(self, ctx: ExecContext) -> ColumnBatch:
        return ctx.leaves[self.index]

    def __repr__(self):
        return f"Scan[{self.index}] {self._schema.simpleString()}"


class PRange(PhysicalPlan):
    """range() generated directly on device (no host transfer) —
    ``RangeExec`` (codegen'd in the reference)."""

    def __init__(self, start: int, end: int, step: int, name: str, num_rows: int):
        self.start, self.end, self.step = start, end, step
        self.name = name
        self.num_rows = num_rows
        self.capacity = pad_capacity(num_rows)

    def schema(self):
        return T.StructType([T.StructField(self.name, T.int64, False)])

    def run(self, ctx: ExecContext) -> ColumnBatch:
        xp = ctx.xp
        idx = xp.arange(self.capacity, dtype=np.int64)
        data = idx * self.step + self.start
        rv = idx < self.num_rows
        return ColumnBatch([self.name], [ColumnVector(data, T.int64)], rv,
                           self.capacity)

    def __repr__(self):
        return f"Range({self.start},{self.end},{self.step})"


class PProject(PhysicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: PhysicalPlan):
        self.exprs = list(exprs)
        self.children = (child,)

    def schema(self):
        cs = self.children[0].schema()
        return T.StructType([T.StructField(e.name, e.data_type(cs)) for e in self.exprs])

    def run(self, ctx):
        batch = self.children[0].run(ctx)
        out = apply_project(ctx.xp, batch, self.exprs, self.offset_in(ctx))
        out.names = [e.name for e in self.exprs]
        return out

    def __repr__(self):
        return f"Project [{', '.join(repr(e) for e in self.exprs)}]"


class PFilter(PhysicalPlan):
    def __init__(self, cond: Expression, child: PhysicalPlan):
        self.cond = cond
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        return apply_filter(ctx.xp, self.children[0].run(ctx), self.cond,
                            self.offset_in(ctx))

    def __repr__(self):
        return f"Filter ({self.cond!r})"


class PAggregate(PhysicalPlan):
    """Sort-based aggregation (HashAggregateExec replacement, §kernels)."""

    def __init__(self, keys: Sequence[Expression],
                 slots: Sequence[Tuple[AggregateFunction, str]],
                 child: PhysicalPlan):
        self.keys = list(keys)
        self.slots = list(slots)
        self.children = (child,)

    def schema(self):
        cs = self.children[0].schema()
        fields = [T.StructField(k.name, k.data_type(cs)) for k in self.keys]
        fields += [T.StructField(n, f.data_type(cs)) for f, n in self.slots]
        return T.StructType(fields)

    def run(self, ctx):
        batch = self.children[0].run(ctx)
        rounds: List[Array] = []
        out = grouped_aggregate(ctx.xp, batch, self.keys, self.slots,
                                scan_rounds=rounds)
        if rounds:
            # the rounds the sort path's segmented scan took: what the
            # program read off its input (kernels.segmented_scan)
            ctx.add_metric(self.op_id, SCAN_ROUNDS, rounds[0])
        return out

    def __repr__(self):
        return (f"Aggregate keys=[{', '.join(repr(k) for k in self.keys)}] "
                f"aggs=[{', '.join(f'{f!r} AS {n}' for f, n in self.slots)}]")


class PAggShrink(PhysicalPlan):
    """Slice a keyed aggregate/distinct output to a bounded static
    capacity (``spark.sql.agg.outputCapacity``).

    Keyed aggregation keeps the INPUT capacity (worst case: every live
    row its own group), so a downstream sort/join pays full-capacity
    work for a handful of live groups.  The slice is lossless whenever
    the true group count fits: the sorted path emits groups at slots
    0..k-1 and the MXU path confines live buckets to the first
    bucket_cap (< out_rows) slots.  A traced flag reports any groups
    lost past the bound; the executor's adaptive retry then grows the
    capacity, exactly like join-output factors.  Reference analog:
    `HashAggregateExec` outputs are naturally |groups|-sized; static
    shapes force the bound-and-grow formulation."""

    def __init__(self, out_rows: int, child: PhysicalPlan):
        self.out_rows = int(out_rows)
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        xp = ctx.xp
        b = self.children[0].run(ctx)
        S = self.out_rows
        if S >= b.capacity:
            return b
        live = b.row_valid_or_true()
        total = xp.sum(live.astype(np.int64))
        kept = xp.sum(live[:S].astype(np.int64))
        ctx.add_flag(total - kept, "shrink", S)
        vecs = [ColumnVector(v.data[:S], v.dtype,
                             None if v.valid is None else v.valid[:S],
                             v.dictionary) for v in b.vectors]
        return ColumnBatch(b.names, vecs, live[:S], S)

    def __repr__(self):
        return f"AggShrink({self.out_rows})"


class PSort(PhysicalPlan):
    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 child: PhysicalPlan):
        self.orders = list(orders)
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        batch = self.children[0].run(ctx)
        ectx = EvalContext(batch, ctx.xp)
        schema = batch.schema
        keys = []
        for e, asc, nf in self.orders:
            v = ectx.broadcast(e.eval(ectx))
            keys.append((v.data, v.valid, e.data_type(schema), asc, nf))
        return sort_batch(ctx.xp, batch, keys)

    def __repr__(self):
        parts = [f"{e!r} {'ASC' if a else 'DESC'} {'NF' if n else 'NL'}"
                 for e, a, n in self.orders]
        return f"Sort [{', '.join(parts)}]"


class PLimit(PhysicalPlan):
    def __init__(self, n: int, child: PhysicalPlan):
        self.n = n
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        return apply_limit(ctx.xp, self.children[0].run(ctx), self.n)

    def __repr__(self):
        return f"Limit {self.n}"


class PWindow(PhysicalPlan):
    """Window operator: one sort per spec + vectorized prefix scans
    (`execution/window/WindowExec.scala` analog, without per-group loops)."""

    def __init__(self, wexprs, child: PhysicalPlan):
        self.wexprs = list(wexprs)     # [(WindowExpression, out_name)]
        self.children = (child,)

    def schema(self):
        cs = self.children[0].schema()
        fields = list(cs.fields)
        for we, name in self.wexprs:
            fields.append(T.StructField(name, we.data_type(cs), True))
        return T.StructType(fields)

    def run(self, ctx):
        from .window import compute_windows
        batch = self.children[0].run(ctx)
        spec = self.wexprs[0][0].spec
        funcs = [(we.func, name) for we, name in self.wexprs]
        return compute_windows(ctx.xp, batch, spec, funcs)

    def __repr__(self):
        return f"Window [{', '.join(n for _, n in self.wexprs)}]"


class PDistinct(PhysicalPlan):
    def __init__(self, child: PhysicalPlan):
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        return k_distinct(ctx.xp, self.children[0].run(ctx))

    def __repr__(self):
        return "Distinct"


class PUnion(PhysicalPlan):
    """Concatenate children on device; string columns re-encode onto merged
    dictionaries via trace-time remap tables."""

    def __init__(self, children: Sequence[PhysicalPlan], schema: T.StructType):
        self.children = tuple(children)
        self._schema = schema

    def schema(self):
        return self._schema

    def run(self, ctx):
        xp = ctx.xp
        batches = [c.run(ctx) for c in self.children]
        out_fields = self._schema.fields
        names = self._schema.names
        capacity = sum(b.capacity for b in batches)
        vectors: List[ColumnVector] = []
        for i, f in enumerate(out_fields):
            vecs = [b.vectors[i] for b in batches]
            dt = f.dataType
            if dt.is_string or isinstance(dt, T.BinaryType):
                merged: tuple = ()
                remaps: List[Optional[np.ndarray]] = [None] * len(vecs)
                for j, v in enumerate(vecs):
                    merged_new, r_old, r_new = merge_dictionaries(merged, v.dictionary or ())
                    for k in range(j):
                        if remaps[k] is not None:
                            remaps[k] = r_old[remaps[k]]
                        elif len(r_old):
                            remaps[k] = r_old
                    remaps[j] = r_new
                    merged = merged_new
                datas = []
                for v, rm in zip(vecs, remaps):
                    d = v.data
                    if rm is not None and len(rm):
                        d = xp.asarray(rm)[xp.clip(d, 0, None)]
                    datas.append(d.astype(np.int32))
                data = xp.concatenate(datas)
                dictionary = merged
            else:
                data = xp.concatenate([v.data.astype(dt.np_dtype) for v in vecs])
                dictionary = None
            valids = [v.valid for v in vecs]
            if any(x is not None for x in valids):
                valid = xp.concatenate([
                    x if x is not None else xp.ones(b.capacity, dtype=bool)
                    for x, b in zip(valids, batches)])
            else:
                valid = None
            vectors.append(ColumnVector(data, dt, valid, dictionary))
        rv = xp.concatenate([b.row_valid_or_true() for b in batches])
        return ColumnBatch(list(names), vectors, rv, capacity)

    def __repr__(self):
        return f"Union({len(self.children)})"


class PSample(PhysicalPlan):
    def __init__(self, fraction: float, seed: int, child: PhysicalPlan):
        self.fraction = fraction
        self.seed = seed
        self.children = (child,)

    def schema(self):
        return self.children[0].schema()

    def run(self, ctx):
        from ..expressions import Literal
        cond = LT(Rand(self.seed), Literal(float(self.fraction)))
        return apply_filter(ctx.xp, self.children[0].run(ctx), cond,
                            self.offset_in(ctx))

    def __repr__(self):
        return f"Sample({self.fraction}, seed={self.seed})"


class PExplode(PhysicalPlan):
    """Static row generation: ``(capacity, L)`` arrays flatten to
    ``capacity*L`` rows; companion columns repeat; dead element slots
    join the row mask."""

    def __init__(self, pre_exprs, array_expr, out_name, with_pos, pos_name,
                 child, insert_at=None):
        self.pre_exprs = list(pre_exprs)
        self.array_expr = array_expr
        self.out_name = out_name
        self.with_pos = with_pos
        self.pos_name = pos_name
        self.insert_at = len(self.pre_exprs) if insert_at is None \
            else int(insert_at)
        self.children = (child,)

    def schema(self):
        cs = self.children[0].schema()
        gen = []
        if self.with_pos:
            gen.append(T.StructField(self.pos_name, T.int32, False))
        at = self.array_expr.data_type(cs)
        gen.append(T.StructField(self.out_name, at.element_type))
        fields = [T.StructField(e.name, e.data_type(cs))
                  for e in self.pre_exprs]
        i = min(self.insert_at, len(fields))
        return T.StructType(fields[:i] + gen + fields[i:])

    def run(self, ctx):
        from ..expressions import EvalContext, _array_elem_mask
        import numpy as _np
        xp = ctx.xp
        batch = self.children[0].run(ctx)
        ectx = EvalContext(batch, xp, self.offset_in(ctx))
        cap = batch.capacity
        at = self.array_expr.data_type(batch.schema)
        av = ectx.broadcast(self.array_expr.eval(ectx))
        if getattr(av.data, "ndim", 2) == 1:
            # array literal / scalar-derived array: one row's elements —
            # broadcast to every row (ExprValue.broadcast only knows rank 0)
            from ..expressions import ExprValue as _EV
            av = _EV(xp.broadcast_to(av.data, (cap,) + av.data.shape),
                     av.valid, av.dictionary)
        L = int(av.data.shape[-1])
        emask = _array_elem_mask(xp, at, av.data)        # (cap, L)
        pre_cols = []
        for e in self.pre_exprs:
            v = ectx.broadcast(e.eval(ectx))
            dt = e.data_type(batch.schema)
            data = xp.repeat(v.data, L, axis=0)
            valid = None if v.valid is None else xp.repeat(v.valid, L)
            pre_cols.append((e.name, ColumnVector(data, dt, valid,
                                                  v.dictionary)))
        gen_cols = []
        if self.with_pos:
            pos = xp.broadcast_to(xp.arange(L, dtype=_np.int32), (cap, L))
            gen_cols.append((self.pos_name,
                             ColumnVector(pos.reshape(cap * L), T.int32,
                                          None, None)))
        elem = av.data.reshape(cap * L)
        gen_cols.append((self.out_name,
                         ColumnVector(elem, at.element_type, None,
                                      av.dictionary)))
        i = min(self.insert_at, len(pre_cols))
        ordered = pre_cols[:i] + gen_cols + pre_cols[i:]
        names = [n for n, _v in ordered]
        vectors = [v for _n, v in ordered]
        rv = batch.row_valid_or_true()
        if av.valid is not None:
            rv = rv & av.valid
        out_rv = xp.repeat(rv, L) & emask.reshape(cap * L)
        return ColumnBatch(names, vectors, out_rv, cap * L)

    def __repr__(self):
        pos = f" POS {self.pos_name}" if self.with_pos else ""
        pre = ", ".join(repr(e) for e in self.pre_exprs)
        return (f"Explode[{pre} | {self.array_expr!r} AS "
                f"{self.out_name}{pos} @{self.insert_at}]")
