"""Expression IR with dual-path evaluation.

The analog of Catalyst's expression tree
(``sql/catalyst/.../expressions/Expression.scala``), redesigned for XLA:

* every expression evaluates VECTORIZED over a whole ColumnBatch — there is
  no row-at-a-time path at all;
* ``eval(ctx)`` is written against an array-module ``ctx.xp`` that is either
  numpy (interpreted/host path) or jax.numpy (traced path).  Running the same
  code under ``jax.jit`` IS the codegen path — XLA plays Janino
  (``codegen/CodeGenerator.scala:905``) — and the numpy run is the
  interpreted oracle, preserving the reference's dual-path testing pattern
  (``ExpressionEvalHelper`` cross-checks eval vs codegen);
* NULLs are validity masks threaded through every operator, with Kleene
  three-valued logic for AND/OR (reference ``expressions/predicates.scala``);
* string expressions are DICTIONARY transforms: the host rewrites the (small)
  sorted dictionary and the device only gathers/remaps int32 codes.  This is
  the TPU replacement for ``UTF8String.java`` byte-twiddling.

Aggregate functions live in ``spark_tpu.aggregates``.
"""

from __future__ import annotations

import math
import threading
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import types as T
from .columnar import ColumnBatch

__all__ = [
    "ExprValue", "EvalContext", "Expression", "Col", "Literal", "Alias",
    "Cast", "Add", "Sub", "Mul", "Div", "IntDiv", "Mod", "Pow", "Neg",
    "UnaryMath", "RoundExpr", "EQ", "NE", "LT", "LE", "GT", "GE", "EqNullSafe",
    "And", "Or", "Not", "IsNull", "IsNotNull", "IsNaN", "Coalesce", "If",
    "CaseWhen", "In", "Between", "StringPredicate", "StringTransform",
    "StringLength", "Concat", "Substring", "ExtractDatePart", "Hash64",
    "Greatest", "Least", "RowIndex", "Rand", "lit", "col", "AnalysisException",
    "TimeWindow", "parse_duration",
]


class AnalysisException(Exception):
    """Resolution/type error (reference ``sql/AnalysisException.scala``)."""


class ExprValue(NamedTuple):
    """A vectorized value: data array (+ scalar broadcastable), optional
    validity mask (None = no NULLs), optional string dictionary."""

    data: Any
    valid: Optional[Any]
    dictionary: Optional[Tuple] = None


def and_valid(xp, a: Optional[Any], b: Optional[Any]) -> Optional[Any]:
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class EvalContext:
    """Evaluation environment: a ColumnBatch plus the array module.

    ``xp`` is numpy for the interpreted path, jax.numpy inside jit traces.
    ``row_offset`` decorrelates RowIndex/Rand across operators/partitions
    (the upper-bits analog of MonotonicallyIncreasingID's partition id).
    """

    def __init__(self, batch: ColumnBatch, xp, row_offset: int = 0):
        self.batch = batch
        self.xp = xp
        self.capacity = batch.capacity
        self.row_offset = row_offset

    def col(self, name: str) -> ExprValue:
        vec = self.batch.column(name)
        return ExprValue(vec.data, vec.valid, vec.dictionary)

    def broadcast(self, value: ExprValue) -> ExprValue:
        """Materialize scalars to full capacity (project output)."""
        data = value.data
        if getattr(data, "shape", ()) == ():
            data = self.xp.broadcast_to(data, (self.capacity,))
        elif not hasattr(data, "shape"):
            data = self.xp.full((self.capacity,), data)
        valid = value.valid
        if valid is not None and getattr(valid, "shape", ()) == ():
            valid = self.xp.broadcast_to(valid, (self.capacity,))
        return ExprValue(data, valid, value.dictionary)


class Expression:
    """Base expression node: typed, vectorized, rewritable."""

    children: Tuple["Expression", ...] = ()

    # -- analysis ---------------------------------------------------------
    def data_type(self, schema: T.StructType) -> T.DataType:
        raise NotImplementedError

    def references(self) -> set:
        out = set()
        for c in self.children:
            out |= c.references()
        return out

    @property
    def foldable(self) -> bool:
        return bool(self.children) and all(c.foldable for c in self.children)

    def map_children(self, fn: Callable[["Expression"], "Expression"]) -> "Expression":
        """Rebuild this node with transformed children (rule rewrites)."""
        if not self.children:
            return self
        import copy
        new = copy.copy(self)
        new.children = tuple(fn(c) for c in self.children)
        return new

    def transform_up(self, fn) -> "Expression":
        node = self.map_children(lambda c: c.transform_up(fn))
        return fn(node)

    # -- execution --------------------------------------------------------
    def eval(self, ctx: EvalContext) -> ExprValue:
        raise NotImplementedError

    # -- display ----------------------------------------------------------
    @property
    def name(self) -> str:
        """Auto-generated output column name (Catalyst ``toString``)."""
        return repr(self)

    def __repr__(self) -> str:  # pragma: no cover
        args = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__.lower()}({args})"

    # -- sugar (the user-facing Column API builds on these) ---------------
    def __add__(self, o): return Add(self, _wrap(o))
    def __radd__(self, o): return Add(_wrap(o), self)
    def __sub__(self, o): return Sub(self, _wrap(o))
    def __rsub__(self, o): return Sub(_wrap(o), self)
    def __mul__(self, o): return Mul(self, _wrap(o))
    def __rmul__(self, o): return Mul(_wrap(o), self)
    def __truediv__(self, o): return Div(self, _wrap(o))
    def __rtruediv__(self, o): return Div(_wrap(o), self)
    def __mod__(self, o): return Mod(self, _wrap(o))
    def __neg__(self): return Neg(self)
    def __eq__(self, o): return EQ(self, _wrap(o))  # type: ignore[override]
    def __ne__(self, o): return NE(self, _wrap(o))  # type: ignore[override]
    def __lt__(self, o): return LT(self, _wrap(o))
    def __le__(self, o): return LE(self, _wrap(o))
    def __gt__(self, o): return GT(self, _wrap(o))
    def __ge__(self, o): return GE(self, _wrap(o))
    def __and__(self, o): return And(self, _wrap(o))
    def __or__(self, o): return Or(self, _wrap(o))
    def __invert__(self): return Not(self)
    def __hash__(self):  # __eq__ is overloaded; identity hash keeps sets working
        return id(self)


def _wrap(v: Any) -> Expression:
    return v if isinstance(v, Expression) else Literal(v)


def lit(v: Any) -> Expression:
    return _wrap(v)


def col(name: str) -> "Col":
    return Col(name)


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class Col(Expression):
    """Column reference (``AttributeReference`` after resolution)."""

    def __init__(self, name: str):
        self._name = name

    @property
    def name(self) -> str:
        return self._name

    @property
    def foldable(self) -> bool:
        return False

    def data_type(self, schema: T.StructType) -> T.DataType:
        try:
            return schema[self._name].dataType
        except KeyError:
            raise AnalysisException(
                f"cannot resolve column '{self._name}' among ({', '.join(schema.names)})")

    def references(self) -> set:
        return {self._name}

    def eval(self, ctx: EvalContext) -> ExprValue:
        return ctx.col(self._name)

    def __repr__(self) -> str:
        return self._name


class _SlotBindings(threading.local):
    """Per-thread Literal→parameter bindings for the serving plan cache.

    Parameterized plan sharing (serving/plancache.py) traces ONE jit
    program per plan SHAPE and feeds literal values in as runtime scalar
    arguments.  The binding is thread-local and keyed by Literal object
    identity — never object mutation — so a concurrent execution of a
    plan that happens to share Literal objects (optimizer rules reuse
    untouched subtrees) can never observe another thread's tracers."""

    map: Optional[dict] = None


_slot_bindings = _SlotBindings()


class Literal(Expression):
    def __init__(self, value: Any, dtype: Optional[T.DataType] = None):
        self.value = value
        self.dtype = dtype or T.infer_type(value)

    @property
    def foldable(self) -> bool:
        return True

    def data_type(self, schema: T.StructType) -> T.DataType:
        return self.dtype

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        bindings = _slot_bindings.map
        if bindings is not None:
            bound = bindings.get(id(self))
            if bound is not None:
                # slotted parameter: the VALUE arrives as a traced scalar
                # argument of the cached executable, not a baked constant
                return ExprValue(xp.asarray(bound), None)
        if self.value is None:
            return ExprValue(xp.zeros((), self.dtype.np_dtype),
                             xp.zeros((), bool))
        if self.dtype.is_string:
            # a lone string literal: single-entry dictionary, code 0
            return ExprValue(xp.zeros((), np.int32), None, (str(self.value),))
        if isinstance(self.dtype, T.DecimalType):
            scaled = int(round(float(self.value) * 10 ** self.dtype.scale))
            return ExprValue(xp.asarray(scaled, dtype=np.int64), None)
        if isinstance(self.dtype, T.DateType):
            return ExprValue(xp.asarray(np.datetime64(self.value, "D").astype(np.int32)), None)
        if isinstance(self.dtype, T.TimestampType):
            return ExprValue(xp.asarray(np.datetime64(self.value, "us").astype(np.int64)), None)
        return ExprValue(xp.asarray(self.value, dtype=self.dtype.np_dtype), None)

    def __repr__(self) -> str:
        return repr(self.value)


class Alias(Expression):
    def __init__(self, child: Expression, alias: str):
        self.children = (child,)
        self._alias = alias

    @property
    def name(self) -> str:
        return self._alias

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval(self, ctx):
        return self.children[0].eval(ctx)

    def __repr__(self) -> str:
        return f"{self.children[0]!r} AS {self._alias}"


# ---------------------------------------------------------------------------
# Arithmetic (reference expressions/arithmetic.scala)
# ---------------------------------------------------------------------------

class BinaryArithmetic(Expression):
    op_name = "?"

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    def data_type(self, schema):
        lt_, rt = (c.data_type(schema) for c in self.children)
        if isinstance(lt_, T.NullType):
            return rt
        if isinstance(rt, T.NullType):
            return lt_
        return T.numeric_promote(lt_, rt)

    def _compute(self, xp, a, b):
        raise NotImplementedError

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        dt = self.data_type(ctx.batch.schema)
        a = l.data.astype(dt.np_dtype)
        b = r.data.astype(dt.np_dtype)
        return ExprValue(self._compute(xp, a, b), and_valid(xp, l.valid, r.valid))

    def __repr__(self) -> str:
        return f"({self.children[0]!r} {self.op_name} {self.children[1]!r})"


class Add(BinaryArithmetic):
    op_name = "+"
    def _compute(self, xp, a, b): return a + b


class Sub(BinaryArithmetic):
    op_name = "-"
    def _compute(self, xp, a, b): return a - b


class Mul(BinaryArithmetic):
    op_name = "*"
    def _compute(self, xp, a, b): return a * b


class Div(BinaryArithmetic):
    """True division; x/0 → NULL (ANSI-off Spark semantics)."""

    op_name = "/"

    def data_type(self, schema):
        dt = super().data_type(schema)
        return dt if dt.is_fractional else T.float64

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        dt = self.data_type(ctx.batch.schema)
        zero = r.data == 0
        a = l.data.astype(dt.np_dtype)
        b = xp.where(zero, xp.ones((), r.data.dtype), r.data).astype(dt.np_dtype)
        valid = and_valid(xp, and_valid(xp, l.valid, r.valid), ~zero)
        return ExprValue(a / b, valid)


class IntDiv(Div):
    op_name = "div"

    def data_type(self, schema):
        return T.int64

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        zero = r.data == 0
        b = xp.where(zero, xp.ones((), r.data.dtype), r.data)
        valid = and_valid(xp, and_valid(xp, l.valid, r.valid), ~zero)
        return ExprValue((l.data // b).astype(np.int64), valid)


class Mod(BinaryArithmetic):
    op_name = "%"

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        dt = self.data_type(ctx.batch.schema)
        zero = r.data == 0
        a = l.data.astype(dt.np_dtype)
        b = xp.where(zero, xp.ones((), r.data.dtype), r.data).astype(dt.np_dtype)
        valid = and_valid(xp, and_valid(xp, l.valid, r.valid), ~zero)
        # Spark % keeps the sign of the dividend (Java semantics), i.e. fmod —
        # not numpy's floored mod.
        if dt.is_fractional:
            res = xp.fmod(a, b)
        else:
            res = (xp.sign(a) * (xp.abs(a) % xp.abs(b))).astype(dt.np_dtype)
        return ExprValue(res, valid)


class Pow(BinaryArithmetic):
    op_name = "pow"

    def data_type(self, schema):
        return T.float64

    def _compute(self, xp, a, b):
        return xp.power(a, b)


class Neg(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)

    def data_type(self, schema):
        return self.children[0].data_type(schema)

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        return ExprValue(-v.data, v.valid)

    def __repr__(self):
        return f"(- {self.children[0]!r})"


class UnaryMath(Expression):
    """sqrt/exp/log/sin/... — float64 elementwise fns (mathExpressions.scala).

    Domain errors (log of ≤0, sqrt of <0) produce NULL like Spark's NaN→null
    behavior is emulated by masking.
    """

    FNS = {
        "sqrt": (lambda xp, x: xp.sqrt(xp.maximum(x, 0.0)), lambda xp, x: x >= 0),
        "exp": (lambda xp, x: xp.exp(x), None),
        "ln": (lambda xp, x: xp.log(xp.where(x > 0, x, 1.0)), lambda xp, x: x > 0),
        "log10": (lambda xp, x: xp.log10(xp.where(x > 0, x, 1.0)), lambda xp, x: x > 0),
        "log2": (lambda xp, x: xp.log2(xp.where(x > 0, x, 1.0)), lambda xp, x: x > 0),
        "sin": (lambda xp, x: xp.sin(x), None),
        "cos": (lambda xp, x: xp.cos(x), None),
        "tan": (lambda xp, x: xp.tan(x), None),
        "asin": (lambda xp, x: xp.arcsin(xp.clip(x, -1, 1)), lambda xp, x: xp.abs(x) <= 1),
        "acos": (lambda xp, x: xp.arccos(xp.clip(x, -1, 1)), lambda xp, x: xp.abs(x) <= 1),
        "atan": (lambda xp, x: xp.arctan(x), None),
        "sinh": (lambda xp, x: xp.sinh(x), None),
        "cosh": (lambda xp, x: xp.cosh(x), None),
        "tanh": (lambda xp, x: xp.tanh(x), None),
        "floor": (lambda xp, x: xp.floor(x), None),
        "ceil": (lambda xp, x: xp.ceil(x), None),
        "abs": (lambda xp, x: xp.abs(x), None),
        "sign": (lambda xp, x: xp.sign(x), None),
        "radians": (lambda xp, x: x * (math.pi / 180.0), None),
        "degrees": (lambda xp, x: x * (180.0 / math.pi), None),
        "log1p": (lambda xp, x: xp.log1p(xp.where(x > -1, x, 0.0)),
                  lambda xp, x: x > -1),
        "expm1": (lambda xp, x: xp.expm1(x), None),
        "cbrt": (lambda xp, x: xp.cbrt(x), None),
        "rint": (lambda xp, x: xp.round(x), None),
    }

    def __init__(self, fn: str, child: Expression):
        assert fn in self.FNS, fn
        self.fn = fn
        self.children = (child,)

    def data_type(self, schema):
        if self.fn in ("floor", "ceil"):
            return T.int64
        if self.fn in ("abs", "sign"):
            return self.children[0].data_type(schema)
        return T.float64

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        if self.fn in ("abs", "sign"):
            return ExprValue(xp.abs(v.data) if self.fn == "abs" else xp.sign(v.data), v.valid)
        x = v.data.astype(np.float64)
        fn, domain = self.FNS[self.fn]
        out = fn(xp, x)
        valid = v.valid
        if domain is not None:
            valid = and_valid(xp, valid, domain(xp, x))
        if self.fn in ("floor", "ceil"):
            out = out.astype(np.int64)
        return ExprValue(out, valid)

    def __repr__(self):
        return f"{self.fn}({self.children[0]!r})"


class RoundExpr(Expression):
    def __init__(self, child: Expression, scale: int = 0):
        self.children = (child,)
        self.scale = scale

    def data_type(self, schema):
        dt = self.children[0].data_type(schema)
        return dt if dt.is_numeric else T.float64

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        if not np.issubdtype(np.asarray(v.data).dtype if ctx.xp is np else v.data.dtype, np.floating):
            return v
        factor = 10.0 ** self.scale
        # HALF_UP like Spark, not banker's rounding
        out = xp.floor(xp.abs(v.data) * factor + 0.5) / factor * xp.sign(v.data)
        return ExprValue(out, v.valid)

    def __repr__(self):
        return f"round({self.children[0]!r}, {self.scale})"


# ---------------------------------------------------------------------------
# Comparisons & boolean logic (reference expressions/predicates.scala)
# ---------------------------------------------------------------------------

def _comparison_operands(ctx: EvalContext, le: Expression, re_: Expression):
    """Evaluate both sides coerced to a common comparable representation.

    Strings compare by dictionary code, which is order-correct only when both
    sides share a dictionary; a string literal vs a column is rewritten into
    code space via searchsorted on the host dictionary (static under jit).
    """
    xp = ctx.xp
    l, r = le.eval(ctx), re_.eval(ctx)
    if l.dictionary is not None or r.dictionary is not None:
        if l.dictionary is not None and r.dictionary is not None:
            if l.dictionary == r.dictionary:
                return l, r, True
            if len(r.dictionary) == 1:  # literal side
                word = r.dictionary[0]
                idx = int(np.searchsorted(np.array(l.dictionary, dtype=object), word))
                exact = idx < len(l.dictionary) and l.dictionary[idx] == word
                # map literal into left's code space: for exact match use the
                # code; otherwise use idx-0.5 boundary → encode by doubling
                return (ExprValue(l.data * 2, l.valid, None),
                        ExprValue(xp.asarray(idx * 2 if exact else idx * 2 - 1, np.int64),
                                  r.valid, None), True)
            if len(l.dictionary) == 1:
                word = l.dictionary[0]
                idx = int(np.searchsorted(np.array(r.dictionary, dtype=object), word))
                exact = idx < len(r.dictionary) and r.dictionary[idx] == word
                return (ExprValue(xp.asarray(idx * 2 if exact else idx * 2 - 1, np.int64),
                                  l.valid, None),
                        ExprValue(r.data * 2, r.valid, None), True)
            # two dictionary-coded columns: dictionaries are trace-time
            # static, so align by merging them and remapping both code
            # spaces (the remap tables bake into the program as constants)
            from .columnar import merge_dictionaries
            _merged, ra, rb = merge_dictionaries(l.dictionary, r.dictionary)
            ldata, rdata = l.data, r.data
            if len(ra):
                ldata = xp.asarray(ra)[xp.clip(ldata, 0, len(ra) - 1)]
            if len(rb):
                rdata = xp.asarray(rb)[xp.clip(rdata, 0, len(rb) - 1)]
            return (ExprValue(ldata, l.valid, None),
                    ExprValue(rdata, r.valid, None), True)
        raise AnalysisException("cannot compare string with non-string")
    return l, r, False


class BinaryComparison(Expression):
    op_name = "?"

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    def data_type(self, schema):
        lt_, rt = (c.data_type(schema) for c in self.children)
        if T.common_type(lt_, rt) is None and not (lt_ == rt):
            raise AnalysisException(f"cannot compare {lt_} and {rt}")
        return T.boolean

    def _compute(self, xp, a, b):
        raise NotImplementedError

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r, is_str = _comparison_operands(ctx, *self.children)
        if not is_str:
            ct = T.common_type(self.children[0].data_type(ctx.batch.schema),
                               self.children[1].data_type(ctx.batch.schema))
            np_dt = (ct or T.float64).np_dtype
            a, b = l.data.astype(np_dt), r.data.astype(np_dt)
        else:
            a, b = l.data, r.data
        return ExprValue(self._compute(xp, a, b), and_valid(xp, l.valid, r.valid))

    def __repr__(self):
        return f"({self.children[0]!r} {self.op_name} {self.children[1]!r})"


class EQ(BinaryComparison):
    op_name = "="
    def _compute(self, xp, a, b): return a == b


class NE(BinaryComparison):
    op_name = "!="
    def _compute(self, xp, a, b): return a != b


class LT(BinaryComparison):
    op_name = "<"
    def _compute(self, xp, a, b): return a < b


class LE(BinaryComparison):
    op_name = "<="
    def _compute(self, xp, a, b): return a <= b


class GT(BinaryComparison):
    op_name = ">"
    def _compute(self, xp, a, b): return a > b


class GE(BinaryComparison):
    op_name = ">="
    def _compute(self, xp, a, b): return a >= b


class EqNullSafe(BinaryComparison):
    """<=> : NULL-safe equality, never NULL itself."""

    op_name = "<=>"

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        l, r, _ = _comparison_operands(ctx, *self.children)
        lv = l.valid if l.valid is not None else xp.ones((), bool)
        rv = r.valid if r.valid is not None else xp.ones((), bool)
        eq = (l.data == r.data) & lv & rv
        both_null = ~lv & ~rv
        return ExprValue(eq | both_null, None)


class And(Expression):
    """Kleene AND: F & NULL = F, T & NULL = NULL."""

    def __init__(self, left, right):
        self.children = (left, right)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        lv = l.valid if l.valid is not None else xp.ones((), bool)
        rv = r.valid if r.valid is not None else xp.ones((), bool)
        data = (l.data | ~lv) & (r.data | ~rv)  # null treated true, then masked
        valid = (lv & rv) | (lv & ~l.data) | (rv & ~r.data)
        if l.valid is None and r.valid is None:
            valid = None
        return ExprValue(data & (valid if valid is not None else True), valid)

    def __repr__(self):
        return f"({self.children[0]!r} AND {self.children[1]!r})"


class Or(Expression):
    """Kleene OR: T | NULL = T, F | NULL = NULL."""

    def __init__(self, left, right):
        self.children = (left, right)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        l, r = (c.eval(ctx) for c in self.children)
        lv = l.valid if l.valid is not None else xp.ones((), bool)
        rv = r.valid if r.valid is not None else xp.ones((), bool)
        data = (l.data & lv) | (r.data & rv)
        valid = (lv & rv) | (lv & l.data) | (rv & r.data)
        if l.valid is None and r.valid is None:
            valid = None
        return ExprValue(data, valid)

    def __repr__(self):
        return f"({self.children[0]!r} OR {self.children[1]!r})"


class Not(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        return ExprValue(~v.data, v.valid)

    def __repr__(self):
        return f"(NOT {self.children[0]!r})"


# ---------------------------------------------------------------------------
# Null handling & conditionals (nullExpressions.scala, conditionalExpressions.scala)
# ---------------------------------------------------------------------------

class IsNull(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        if v.valid is None:
            return ExprValue(xp.zeros((), bool), None)
        return ExprValue(~v.valid, None)

    def __repr__(self):
        return f"({self.children[0]!r} IS NULL)"


class IsNotNull(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        if v.valid is None:
            return ExprValue(xp.ones((), bool), None)
        return ExprValue(v.valid, None)

    def __repr__(self):
        return f"({self.children[0]!r} IS NOT NULL)"


class IsNaN(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        d = v.data
        if not np.issubdtype(np.dtype(str(d.dtype)), np.floating):
            return ExprValue(xp.zeros((), bool), None)
        return ExprValue(xp.isnan(d), None)


def _align_value_dicts(xp, vals):
    """Re-encode ExprValues that carry different string dictionaries onto one
    merged dictionary (host-merged, device-gathered; static under jit).
    Returns (vals, merged_dictionary_or_None)."""
    dicts = [v.dictionary for v in vals if v.dictionary is not None]
    if not dicts:
        return vals, None
    if all(d == dicts[0] for d in dicts):
        return vals, dicts[0]
    merged = tuple(sorted(set().union(*[set(d) for d in dicts])))
    lookup = {w: i for i, w in enumerate(merged)}
    out = []
    for v in vals:
        if v.dictionary is None:
            out.append(v)
            continue
        remap = xp.asarray(
            np.fromiter((lookup[w] for w in v.dictionary), np.int32,
                        count=len(v.dictionary)))
        out.append(ExprValue(remap[xp.clip(v.data, 0, None)], v.valid, merged))
    return out, merged


class Coalesce(Expression):
    def __init__(self, *children):
        self.children = tuple(children)

    def data_type(self, schema):
        out = T.null_type
        for c in self.children:
            nxt = T.common_type(out, c.data_type(schema))
            if nxt is None:
                raise AnalysisException("incompatible coalesce branches")
            out = nxt
        return out

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.data_type(ctx.batch.schema)
        vals = [c.eval(ctx) for c in self.children]
        vals, merged = _align_value_dicts(xp, vals)
        dicts = [merged] if merged is not None else []
        out = ExprValue(vals[-1].data.astype(dt.np_dtype), vals[-1].valid,
                        dicts[0] if dicts else None)
        for v in reversed(vals[:-1]):
            if v.valid is None:
                out = ExprValue(v.data.astype(dt.np_dtype), None, out.dictionary)
            else:
                taken_valid = out.valid if out.valid is not None else xp.ones((), bool)
                out = ExprValue(
                    xp.where(v.valid, v.data.astype(dt.np_dtype), out.data),
                    v.valid | taken_valid, out.dictionary)
        return out

    def __repr__(self):
        return f"coalesce({', '.join(map(repr, self.children))})"


class If(Expression):
    def __init__(self, pred, then, otherwise):
        self.children = (pred, then, otherwise)

    def data_type(self, schema):
        t = T.common_type(self.children[1].data_type(schema),
                          self.children[2].data_type(schema))
        if t is None:
            raise AnalysisException("IF branches have incompatible types")
        return t

    def eval(self, ctx):
        xp = ctx.xp
        p, a, b = (c.eval(ctx) for c in self.children)
        dt = self.data_type(ctx.batch.schema)
        (a, b), merged = _align_value_dicts(xp, [a, b])
        dicts = [merged] if merged is not None else []
        cond = p.data & (p.valid if p.valid is not None else True)
        data = xp.where(cond, a.data.astype(dt.np_dtype), b.data.astype(dt.np_dtype))
        av = a.valid if a.valid is not None else xp.ones((), bool)
        bv = b.valid if b.valid is not None else xp.ones((), bool)
        valid = None if (a.valid is None and b.valid is None) else xp.where(cond, av, bv)
        return ExprValue(data, valid, dicts[0] if dicts else None)

    def __repr__(self):
        p, a, b = self.children
        return f"if({p!r}, {a!r}, {b!r})"


class CaseWhen(Expression):
    """CASE WHEN p1 THEN v1 ... ELSE d END — desugars to nested If at eval."""

    def __init__(self, branches: Sequence[Tuple[Expression, Expression]],
                 otherwise: Optional[Expression] = None):
        self.branches = [(p, v) for p, v in branches]
        self.otherwise = otherwise if otherwise is not None else Literal(None)
        flat: List[Expression] = []
        for p, v in self.branches:
            flat += [p, v]
        flat.append(self.otherwise)
        self.children = tuple(flat)

    def map_children(self, fn):
        new_branches = [(fn(p), fn(v)) for p, v in self.branches]
        return CaseWhen(new_branches, fn(self.otherwise))

    def _as_if(self) -> Expression:
        node: Expression = self.otherwise
        for p, v in reversed(self.branches):
            node = If(p, v, node)
        return node

    def data_type(self, schema):
        return self._as_if().data_type(schema)

    def eval(self, ctx):
        return self._as_if().eval(ctx)

    def __repr__(self):
        parts = " ".join(f"WHEN {p!r} THEN {v!r}" for p, v in self.branches)
        return f"CASE {parts} ELSE {self.otherwise!r} END"


class In(Expression):
    """`x IN (lit, lit, ...)` — ORs of equality, vectorized as isin."""

    def __init__(self, child: Expression, values: Sequence[Any]):
        self.children = (child,)
        self.values = [v.value if isinstance(v, Literal) else v for v in values]

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        if v.dictionary is not None:
            member = np.array([w in set(self.values) for w in v.dictionary], bool)
            member = xp.asarray(member)
            data = xp.where(v.data >= 0, member[xp.clip(v.data, 0, None)], False)
            return ExprValue(data, v.valid)
        acc = xp.zeros((), bool)
        for val in self.values:
            acc = acc | (v.data == val)
        return ExprValue(acc, v.valid)

    def __repr__(self):
        return f"({self.children[0]!r} IN {tuple(self.values)!r})"


class Between(Expression):
    def __repr__(self):
        c = self.children
        return f"({c[0]!r} BETWEEN {c[1]!r} AND {c[2]!r})"

    def __init__(self, child, low, high):
        self.children = (child, _wrap(low), _wrap(high))

    def data_type(self, schema):
        return T.boolean

    def eval(self, ctx):
        c, lo, hi = self.children
        return And(GE(c, lo), LE(c, hi)).eval(ctx)


class Greatest(Expression):
    def __init__(self, *children):
        self.children = tuple(children)

    def data_type(self, schema):
        out = self.children[0].data_type(schema)
        for c in self.children[1:]:
            out = T.numeric_promote(out, c.data_type(schema))
        return out

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.data_type(ctx.batch.schema)
        vals = [c.eval(ctx) for c in self.children]
        out = vals[0].data.astype(dt.np_dtype)
        valid = vals[0].valid
        for v in vals[1:]:
            out = xp.maximum(out, v.data.astype(dt.np_dtype))
            valid = and_valid(xp, valid, v.valid)
        return ExprValue(out, valid)


class Least(Greatest):
    def eval(self, ctx):
        xp = ctx.xp
        dt = self.data_type(ctx.batch.schema)
        vals = [c.eval(ctx) for c in self.children]
        out = vals[0].data.astype(dt.np_dtype)
        valid = vals[0].valid
        for v in vals[1:]:
            out = xp.minimum(out, v.data.astype(dt.np_dtype))
            valid = and_valid(xp, valid, v.valid)
        return ExprValue(out, valid)


# ---------------------------------------------------------------------------
# Cast (reference expressions/Cast.scala)
# ---------------------------------------------------------------------------

class Cast(Expression):
    def __init__(self, child: Expression, to: T.DataType):
        self.children = (child,)
        self.to = to

    def data_type(self, schema):
        return self.to

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        src = self.children[0].data_type(ctx.batch.schema)
        to = self.to
        if src == to:
            return v
        if v.dictionary is not None:
            # string → X: parse the dictionary on host, gather on device
            if to.is_string:
                return v
            def parse(fn, default):
                arr = []
                ok = []
                for w in v.dictionary:
                    try:
                        arr.append(fn(w)); ok.append(True)
                    except (ValueError, TypeError):
                        arr.append(default); ok.append(False)
                return (xp.asarray(np.array(arr, to.np_dtype)),
                        xp.asarray(np.array(ok, bool)))
            if to.is_numeric:
                if isinstance(to, T.DecimalType):
                    table, ok = parse(lambda w: int(round(float(w) * 10 ** to.scale)), 0)
                else:
                    table, ok = parse(float if to.is_fractional else (lambda w: int(float(w))), 0)
            elif isinstance(to, T.DateType):
                table, ok = parse(lambda w: np.datetime64(w, "D").astype(np.int32), 0)
            elif isinstance(to, T.TimestampType):
                table, ok = parse(lambda w: np.datetime64(w, "us").astype(np.int64), 0)
            elif isinstance(to, T.BooleanType):
                table, ok = parse(lambda w: w.strip().lower() in ("true", "t", "1", "yes", "y"), False)
            else:
                raise AnalysisException(f"unsupported cast string→{to}")
            codes = xp.clip(v.data, 0, None)
            return ExprValue(table[codes], and_valid(xp, v.valid, ok[codes]))
        if to.is_string:
            raise AnalysisException(
                "cast to string requires host materialization (non-jittable); "
                "wrap in a HostCast at planning time")
        if isinstance(src, T.DecimalType):
            f = v.data.astype(np.float64) / (10 ** src.scale)
            if isinstance(to, T.DecimalType):
                return ExprValue(xp.round(f * 10 ** to.scale).astype(np.int64), v.valid)
            return ExprValue(f.astype(to.np_dtype), v.valid)
        if isinstance(to, T.DecimalType):
            return ExprValue(xp.round(v.data.astype(np.float64) * 10 ** to.scale).astype(np.int64), v.valid)
        if isinstance(src, T.DateType) and isinstance(to, T.TimestampType):
            return ExprValue(v.data.astype(np.int64) * 86_400_000_000, v.valid)
        if isinstance(src, T.TimestampType) and isinstance(to, T.DateType):
            return ExprValue(xp.floor_divide(v.data, 86_400_000_000).astype(np.int32), v.valid)
        if isinstance(to, T.BooleanType):
            return ExprValue(v.data != 0, v.valid)
        # float → integral needs JVM-exact semantics on BOTH lanes
        # ((long)f: truncate toward zero, saturate at long bounds, NaN→0;
        # then mod-wrap into the narrow type) — numpy's direct astype of
        # out-of-range floats is platform UB and diverges from XLA
        if np.issubdtype(np.dtype(getattr(v.data, "dtype", np.float64)),
                         np.floating) and to.is_integral:
            f = v.data.astype(np.float64)
            t = xp.trunc(xp.where(xp.isnan(f), 0.0, f))
            if np.dtype(to.np_dtype).itemsize >= 8:
                # largest float64 strictly below 2^63 — clipping to
                # float(2^63-1) would round UP to 2^63 and wrap
                lo, hi = float(np.iinfo(np.int64).min), \
                    float(np.nextafter(2.0 ** 63, 0.0))
                sat = np.int64(np.iinfo(np.int64).max)
            else:
                # JVM narrows through int: saturate at int32, then the
                # astype below mod-wraps into short/byte exactly like
                # (short)(int)f / (byte)(int)f
                lo, hi = float(np.iinfo(np.int32).min), \
                    float(np.iinfo(np.int32).max)
                sat = np.int64(np.iinfo(np.int32).max)
            out = xp.clip(t, lo, hi).astype(np.int64)
            # ONLY above-range values saturate: hi itself (e.g. the
            # exactly-representable nextafter(2^63) for int64) converts
            # exactly via astype, matching JVM (long)f
            out = xp.where(t > hi, sat, out)
            return ExprValue(out.astype(to.np_dtype), v.valid)
        # numeric/bool → numeric: plain astype (truncating float→int like Spark)
        return ExprValue(v.data.astype(to.np_dtype), v.valid)

    def __repr__(self):
        return f"CAST({self.children[0]!r} AS {self.to!r})"


# ---------------------------------------------------------------------------
# String expressions — dictionary transforms (stringExpressions.scala)
# ---------------------------------------------------------------------------

def _dict_gather(xp, table: np.ndarray, codes, valid):
    t = xp.asarray(table)
    return t[xp.clip(codes, 0, None)]


class StringTransform(Expression):
    """upper/lower/trim/reverse/...: host rewrites the dictionary, device
    remaps codes.  The output dictionary is re-sorted so downstream
    comparisons stay order-correct."""

    FNS = {
        "upper": str.upper,
        "lower": str.lower,
        "trim": str.strip,
        "ltrim": str.lstrip,
        "rtrim": str.rstrip,
        "reverse": lambda s: s[::-1],
        "initcap": lambda s: s.title(),
    }

    def __init__(self, fn: str, child: Expression):
        assert fn in self.FNS
        self.fn = fn
        self.children = (child,)

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not ct.is_string:
            raise AnalysisException(f"{self.fn} expects string, got {ct}")
        return T.string

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        return _rewrite_dictionary(ctx.xp, v, self.FNS[self.fn])

    def __repr__(self):
        return f"{self.fn}({self.children[0]!r})"


def _rewrite_dictionary(xp, v: ExprValue, fn) -> ExprValue:
    """Shared host-rewrites-dictionary/device-remaps-codes contract for
    every string→string transform (StringTransform + the parameterized
    family)."""
    transformed = [fn(w) for w in (v.dictionary or ())]
    new_dict = tuple(sorted(set(transformed))) or ("",)
    pos = {w: i for i, w in enumerate(new_dict)}
    remap = np.array([pos[w] for w in transformed], np.int32) \
        if transformed else np.zeros(1, np.int32)
    return ExprValue(_dict_gather(xp, remap, v.data, v.valid), v.valid,
                     new_dict)


class Substring(Expression):
    """substring(s, pos, len) with static pos/len (1-based, Spark semantics)."""

    def __init__(self, child: Expression, pos: int, length: int):
        self.children = (child,)
        self.pos = pos
        self.length = length

    def data_type(self, schema):
        return T.string

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        start = self.pos - 1 if self.pos > 0 else self.pos
        transformed = []
        for w in v.dictionary:
            s = w[start:] if start >= 0 else w[len(w) + start:]
            transformed.append(s[:self.length])
        new_dict = tuple(sorted(set(transformed)))
        pos = {w: i for i, w in enumerate(new_dict)}
        remap = np.array([pos[w] for w in transformed], np.int32) if transformed else np.zeros(1, np.int32)
        return ExprValue(_dict_gather(xp, remap, v.data, v.valid), v.valid, new_dict)

    def __repr__(self):
        return f"substring({self.children[0]!r}, {self.pos}, {self.length})"


class StringLength(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self, schema):
        return T.int32

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        lens = np.array([len(w) for w in v.dictionary], np.int32) if v.dictionary else np.zeros(1, np.int32)
        return ExprValue(_dict_gather(xp, lens, v.data, v.valid), v.valid)

    def __repr__(self):
        return f"length({self.children[0]!r})"


class StringPredicate(Expression):
    """LIKE / startswith / endswith / contains / rlike: host evaluates the
    predicate over the dictionary, device gathers a boolean."""

    def __init__(self, kind: str, child: Expression, pattern: str):
        assert kind in ("like", "startswith", "endswith", "contains", "rlike")
        self.kind = kind
        self.children = (child,)
        self.pattern = pattern

    def data_type(self, schema):
        return T.boolean

    def _matcher(self) -> Callable[[str], bool]:
        import re as _re
        if self.kind == "like":
            # translate SQL LIKE to regex (% → .*, _ → .)
            out = []
            i = 0
            p = self.pattern
            while i < len(p):
                ch = p[i]
                if ch == "\\" and i + 1 < len(p):
                    out.append(_re.escape(p[i + 1])); i += 2; continue
                if ch == "%":
                    out.append(".*")
                elif ch == "_":
                    out.append(".")
                else:
                    out.append(_re.escape(ch))
                i += 1
            rx = _re.compile("^" + "".join(out) + "$", _re.DOTALL)
            return lambda s: rx.match(s) is not None
        if self.kind == "rlike":
            rx = _re.compile(self.pattern)
            return lambda s: rx.search(s) is not None
        if self.kind == "startswith":
            return lambda s: s.startswith(self.pattern)
        if self.kind == "endswith":
            return lambda s: s.endswith(self.pattern)
        return lambda s: self.pattern in s

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        m = self._matcher()
        table = np.array([m(w) for w in v.dictionary], bool) if v.dictionary else np.zeros(1, bool)
        return ExprValue(_dict_gather(xp, table, v.data, v.valid), v.valid)

    def __repr__(self):
        return f"({self.children[0]!r} {self.kind} {self.pattern!r})"


class Concat(Expression):
    """concat of string columns/literals.

    The output dictionary is the cross product of input dictionaries — fine
    for low-cardinality columns, rejected above a size limit (the honest
    dynamic-shape boundary; high-cardinality concat belongs on the host).
    """

    MAX_DICT = 1 << 20

    def __init__(self, *children):
        self.children = tuple(children)

    def data_type(self, schema):
        return T.string

    def eval(self, ctx):
        xp = ctx.xp
        vals = [c.eval(ctx) for c in self.children]
        dicts = [v.dictionary if v.dictionary is not None else ("",) for v in vals]
        size = 1
        for d in dicts:
            size *= max(len(d), 1)
        if size > self.MAX_DICT:
            raise AnalysisException(
                f"concat dictionary blowup ({size}); use host path")
        # pairwise fold: combine two dictionary-coded values at a time
        cur = vals[0]
        cur_dict = dicts[0]
        for v, d in zip(vals[1:], dicts[1:]):
            combined = [a + b for a in cur_dict for b in d]
            new_dict = tuple(sorted(set(combined)))
            pos = {w: i for i, w in enumerate(new_dict)}
            remap = np.array([[pos[a + b] for b in d] for a in cur_dict], np.int32)
            remap = remap if remap.size else np.zeros((1, 1), np.int32)
            table = xp.asarray(remap)
            code = table[xp.clip(cur.data, 0, None), xp.clip(v.data, 0, None)]
            cur = ExprValue(code, and_valid(xp, cur.valid, v.valid), new_dict)
            cur_dict = new_dict
        return cur

    def __repr__(self):
        return f"concat({', '.join(map(repr, self.children))})"


# ---------------------------------------------------------------------------
# Datetime extraction (datetimeExpressions.scala)
# ---------------------------------------------------------------------------

def parse_duration(text) -> int:
    """'10 seconds' / '5 minutes' / '1 hour' / '2 days' -> microseconds.

    The CalendarInterval subset event-time windows and watermarks need
    (reference `unsafe/types/CalendarInterval.java` parsing, fixed-length
    units only — months/years are not fixed durations)."""
    if isinstance(text, (int, float)):
        return int(text)
    parts = str(text).strip().lower().split()
    if len(parts) != 2:
        raise AnalysisException(
            f"cannot parse duration {text!r}: expected '<n> <unit>'")
    try:
        n = float(parts[0])
    except ValueError:
        raise AnalysisException(f"cannot parse duration {text!r}")
    unit = parts[1].rstrip("s")
    scale = {"microsecond": 1, "millisecond": 1_000, "second": 1_000_000,
             "minute": 60_000_000, "hour": 3_600_000_000,
             "day": 86_400_000_000, "week": 7 * 86_400_000_000}.get(unit)
    if scale is None:
        raise AnalysisException(f"unknown duration unit {parts[1]!r}")
    return int(n * scale)


class TimeWindow(Expression):
    """Tumbling event-time bucket (`expressions/TimeWindow.scala`):
    start = floor(ts / duration) * duration; `field` picks start or end.

    Nested struct output (Spark's window.start/.end) is flattened into the
    field choice — sliding windows (slide < duration) need row expansion
    (Expand) and are not supported yet."""

    def __init__(self, child: Expression, duration_us: int,
                 slide_us: Optional[int] = None, field: str = "start"):
        if int(duration_us) <= 0:
            raise AnalysisException(
                f"window duration must be positive, got {duration_us}us")
        slide = int(slide_us) if slide_us is not None else int(duration_us)
        if slide <= 0 or int(duration_us) % slide != 0:
            raise AnalysisException(
                "window slide must be positive and divide the duration "
                f"evenly; got duration={duration_us}us slide={slide}us")
        if int(duration_us) // slide > 512:
            # each event expands into duration/slide rows (static shapes);
            # an unbounded ratio would explode analysis and batch capacity
            raise AnalysisException(
                f"window duration/slide ratio {duration_us // slide} "
                "exceeds the supported maximum of 512 windows per event")
        assert field in ("start", "end"), field
        self.duration_us = int(duration_us)
        self.slide_us = slide
        self.field = field
        self.children = (child,)

    @property
    def is_sliding(self) -> bool:
        return self.slide_us != self.duration_us

    def map_children(self, fn):
        return TimeWindow(fn(self.children[0]), self.duration_us,
                          self.slide_us, self.field)

    @property
    def name(self):
        return "window" if self.field == "start" else "window_end"

    def data_type(self, schema):
        src = self.children[0].data_type(schema)
        if not (isinstance(src, T.TimestampType) or src.is_integral):
            raise AnalysisException(
                f"window() needs a timestamp/integral column, got {src}")
        return T.timestamp

    def eval(self, ctx):
        if self.is_sliding:
            raise AnalysisException(
                "sliding window() must be a grouping key (the analyzer "
                "expands events into their windows); it cannot be "
                "evaluated as a plain expression")
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        d = np.int64(self.duration_us)
        start = xp.floor_divide(v.data.astype(np.int64), d) * d
        out = start if self.field == "start" else start + d
        return ExprValue(out, v.valid)

    def __repr__(self):
        return (f"window({self.children[0]!r}, {self.duration_us}us"
                + (f", slide={self.slide_us}us" if self.is_sliding else "")
                + f").{self.field}")


class ExtractDatePart(Expression):
    """year/month/day/... from date (days) or timestamp (micros) columns,
    via Hinnant's civil-from-days integer algorithm — pure elementwise int
    ops, so it fuses into the surrounding XLA program."""

    PARTS = ("year", "month", "day", "dayofweek", "dayofyear", "quarter",
             "hour", "minute", "second", "weekofyear")

    def __init__(self, part: str, child: Expression):
        assert part in self.PARTS, part
        self.part = part
        self.children = (child,)

    def data_type(self, schema):
        return T.int32

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        src = self.children[0].data_type(ctx.batch.schema)
        if isinstance(src, T.TimestampType):
            days = xp.floor_divide(v.data, 86_400_000_000)
            micros_in_day = v.data - days * 86_400_000_000
        elif isinstance(src, T.DateType):
            days = v.data.astype(np.int64)
            micros_in_day = xp.zeros((), np.int64)
        else:
            raise AnalysisException(f"cannot extract {self.part} from {src}")

        if self.part == "hour":
            return ExprValue((micros_in_day // 3_600_000_000).astype(np.int32), v.valid)
        if self.part == "minute":
            return ExprValue(((micros_in_day // 60_000_000) % 60).astype(np.int32), v.valid)
        if self.part == "second":
            return ExprValue(((micros_in_day // 1_000_000) % 60).astype(np.int32), v.valid)
        if self.part == "dayofweek":
            # Spark: 1 = Sunday. 1970-01-01 was a Thursday.
            return ExprValue(((days + 4) % 7 + 1).astype(np.int32), v.valid)

        # civil_from_days (Howard Hinnant, public domain algorithm)
        z = days + 719_468
        era = xp.floor_divide(z, 146_097)
        doe = z - era * 146_097
        yoe = (doe - doe // 1460 + doe // 36_524 - doe // 146_096) // 365
        y = yoe + era * 400
        doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
        mp = (5 * doy + 2) // 153
        d = doy - (153 * mp + 2) // 5 + 1
        m = xp.where(mp < 10, mp + 3, mp - 9)
        y = xp.where(m <= 2, y + 1, y)
        if self.part == "year":
            return ExprValue(y.astype(np.int32), v.valid)
        if self.part == "month":
            return ExprValue(m.astype(np.int32), v.valid)
        if self.part == "day":
            return ExprValue(d.astype(np.int32), v.valid)
        if self.part == "quarter":
            return ExprValue(((m - 1) // 3 + 1).astype(np.int32), v.valid)
        if self.part == "dayofyear":
            jan1 = _days_from_civil(xp, y, 1, 1)
            return ExprValue((days - jan1 + 1).astype(np.int32), v.valid)
        if self.part == "weekofyear":
            # ISO week number
            dow = (days + 3) % 7  # 0 = Monday
            thursday = days - dow + 3
            z2 = thursday + 719_468
            era2 = xp.floor_divide(z2, 146_097)
            doe2 = z2 - era2 * 146_097
            yoe2 = (doe2 - doe2 // 1460 + doe2 // 36_524 - doe2 // 146_096) // 365
            iso_year = yoe2 + era2 * 400
            doy2 = doe2 - (365 * yoe2 + yoe2 // 4 - yoe2 // 100)
            mp2 = (5 * doy2 + 2) // 153
            m2 = xp.where(mp2 < 10, mp2 + 3, mp2 - 9)
            iso_year = xp.where(m2 <= 2, iso_year + 1, iso_year)
            jan4 = _days_from_civil(xp, iso_year, 1, 4)
            week1_mon = jan4 - (jan4 + 3) % 7
            return ExprValue(((days - week1_mon) // 7 + 1).astype(np.int32), v.valid)
        raise AssertionError(self.part)

    def __repr__(self):
        return f"{self.part}({self.children[0]!r})"


def _days_from_civil(xp, y, m: int, d: int):
    """Inverse of civil_from_days for an array of years y and static month/day."""
    y = y - (1 if m <= 2 else 0)
    era = xp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = (m + 9) % 12
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146_097 + doe - 719_468


# ---------------------------------------------------------------------------
# Hashing — bit-exact across hosts/devices for shuffle partitioning
# ---------------------------------------------------------------------------

class Hash64(Expression):
    """Deterministic 64-bit mix hash (splitmix64 finalizer) of one or more
    columns.  The role of ``Murmur3_x86_32`` (reference
    ``unsafe/hash/Murmur3_x86_32.java``): agreement between partitioners on
    every host/device, here guaranteed by identical integer ops in XLA/numpy.
    NULL hashes to a fixed constant; string columns hash their dictionary
    WORDS (host-side stable hash of the bytes), not codes, so the value is
    independent of the batch dictionary."""

    NULL_HASH = np.int64(0x9E3779B97F4A7C15 - (1 << 64))

    def __init__(self, *children):
        self.children = tuple(children)

    def data_type(self, schema):
        return T.int64

    @staticmethod
    def _mix(xp, x):
        # murmur3/splitmix finalizer in uint64 (wraparound, logical shifts)
        c1 = np.uint64(0xFF51AFD7ED558CCD)
        c2 = np.uint64(0xC4CEB9FE1A85EC53)
        x = xp.asarray(x).astype(np.uint64)
        x = x ^ (x >> np.uint64(33))
        x = x * c1
        x = x ^ (x >> np.uint64(33))
        x = x * c2
        x = x ^ (x >> np.uint64(33))
        return x.astype(np.int64)

    @staticmethod
    def _string_hash_table(dictionary: Tuple[str, ...]) -> np.ndarray:
        import hashlib
        from . import tracing
        out = np.empty(max(len(dictionary), 1), np.int64)
        out[:] = 0
        with tracing.span("dict.unify", words=len(dictionary)):
            for i, w in enumerate(dictionary):
                data = w if isinstance(w, bytes) else str(w).encode("utf-8")
                h = hashlib.blake2b(data, digest_size=8).digest()
                out[i] = np.frombuffer(h, np.int64)[0]
        return out

    def eval(self, ctx):
        xp = ctx.xp
        acc = xp.asarray(np.int64(42))
        for c in self.children:
            v = c.eval(ctx)
            if v.dictionary is not None:
                # clip BOTH ends: NULL (-1) codes and out-of-dictionary
                # sentinels (e.g. a remap's INT32_MAX) must gather in
                # bounds; both are masked/never-match downstream
                table = xp.asarray(self._string_hash_table(v.dictionary))
                h = table[xp.clip(v.data, 0, max(len(v.dictionary) - 1, 0))]
            else:
                bits = v.data
                if np.issubdtype(np.dtype(str(bits.dtype)), np.floating):
                    # normalize -0.0 → 0.0 then bitcast
                    bits = xp.where(bits == 0, xp.zeros((), bits.dtype), bits)
                    bits = bits.astype(np.float64).view(np.int64) if xp is np \
                        else _jax_bitcast(bits)
                h = self._mix(xp, bits.astype(np.int64))
            if v.valid is not None:
                h = xp.where(v.valid, h, self.NULL_HASH)
            combined = (xp.asarray(acc).astype(np.uint64) * np.uint64(31)
                        + xp.asarray(h).astype(np.uint64))
            acc = self._mix(xp, combined)
        return ExprValue(acc, None)

    def __repr__(self):
        return f"hash64({', '.join(map(repr, self.children))})"


def _jax_bitcast(x):
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)


class RowIndex(Expression):
    """Global row id: batch-local index + the context's partition offset
    (``monotonically_increasing_id`` analog — reference
    ``expressions/MonotonicallyIncreasingID.scala`` packs partition id in the
    upper bits; here the offset is provided by the executing operator)."""

    def data_type(self, schema):
        return T.int64

    @property
    def foldable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        offset = getattr(ctx, "row_offset", 0)
        return ExprValue(xp.arange(ctx.capacity, dtype=np.int64) + offset, None)

    def __repr__(self):
        return "monotonically_increasing_id()"


class Rand(Expression):
    """Deterministic per-row uniform [0,1): counter-based (hash of row index
    and seed), so it is reproducible and identical between the interpreted
    and compiled paths — unlike Spark's stateful XORShiftRandom
    (``expressions/randomExpressions.scala``), which is seeded per-partition.
    """

    def __init__(self, seed: int = 0):
        self.seed = seed

    def data_type(self, schema):
        return T.float64

    @property
    def foldable(self) -> bool:
        return False

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        offset = getattr(ctx, "row_offset", 0)
        idx = xp.arange(ctx.capacity, dtype=np.int64) + offset
        seed_mix = np.uint64((self.seed * 2654435761 + 1) & 0xFFFFFFFFFFFFFFFF)
        mixed = Hash64._mix(xp, (idx.astype(np.uint64)
                                 * np.uint64(0x9E3779B97F4A7C15)
                                 + seed_mix))
        u = (mixed.astype(np.uint64) >> np.uint64(11)).astype(np.float64)
        return ExprValue(u * (1.0 / (1 << 53)), None)

    def __repr__(self):
        return f"rand({self.seed})"


# ---------------------------------------------------------------------------
# Expression breadth: parameterized string transforms, date arithmetic,
# binary math (the long tail of `stringExpressions.scala`,
# `datetimeExpressions.scala`, `mathExpressions.scala`)
# ---------------------------------------------------------------------------

def _civil_ymd_vec(xp, days):
    """(y, m, d) int arrays from day numbers (civil_from_days, vectorized)."""
    z = days + 719_468
    era = xp.floor_divide(z, 146_097)
    doe = z - era * 146_097
    yoe = (doe - doe // 1460 + doe // 36_524 - doe // 146_096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = xp.where(mp < 10, mp + 3, mp - 9)
    y = xp.where(m <= 2, y + 1, y)
    return y, m, d


def _days_from_civil_vec(xp, y, m, d):
    """day numbers from (y, m, d) int arrays (days_from_civil, vectorized)."""
    y = xp.where(m <= 2, y - 1, y)
    era = xp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = xp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146_097 + doe - 719_468


def _month_len_vec(xp, y, m):
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    # Jan..Dec lengths, Feb patched by leapness
    table = xp.asarray(np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31,
                                 30, 31], np.int64))
    base = table[xp.clip(m - 1, 0, 11)]
    return xp.where((m == 2) & leap, 29, base)


def _as_days(xp, v: ExprValue, dt) -> Any:
    if isinstance(dt, T.TimestampType):
        return xp.floor_divide(v.data, 86_400_000_000).astype(np.int64)
    if isinstance(dt, T.DateType) or dt.is_integral:
        return v.data.astype(np.int64)
    raise AnalysisException(f"expected a date/timestamp, got {dt}")


class DateArith(Expression):
    """date_add/date_sub/datediff/add_months/months_between/last_day —
    pure elementwise integer calendar math (Hinnant algorithms), so every
    date function fuses into the surrounding XLA program instead of
    round-tripping through host datetime objects."""

    KINDS = ("date_add", "date_sub", "datediff", "add_months",
             "months_between", "last_day")

    def __init__(self, kind: str, *children: Expression):
        assert kind in self.KINDS, kind
        self.kind = kind
        self.children = tuple(children)

    def map_children(self, fn):
        return DateArith(self.kind, *[fn(c) for c in self.children])

    def data_type(self, schema):
        if self.kind == "datediff":
            return T.int32
        if self.kind == "months_between":
            return T.float64
        return T.date

    def eval(self, ctx):
        xp = ctx.xp
        schema = ctx.batch.schema
        a = ctx.broadcast(self.children[0].eval(ctx))
        da = _as_days(xp, a, self.children[0].data_type(schema))
        if self.kind == "last_day":
            y, m, _d = _civil_ymd_vec(xp, da)
            out = _days_from_civil_vec(xp, y, m, _month_len_vec(xp, y, m))
            return ExprValue(out.astype(np.int32), a.valid)
        b = ctx.broadcast(self.children[1].eval(ctx))
        valid = and_valid(xp, a.valid, b.valid)
        if self.kind in ("date_add", "date_sub"):
            n = b.data.astype(np.int64)
            out = da + (n if self.kind == "date_add" else -n)
            return ExprValue(out.astype(np.int32), valid)
        if self.kind == "datediff":
            db = _as_days(xp, b, self.children[1].data_type(schema))
            return ExprValue((da - db).astype(np.int32), valid)
        if self.kind == "add_months":
            y, m, d = _civil_ymd_vec(xp, da)
            total = (y * 12 + (m - 1)) + b.data.astype(np.int64)
            ny = xp.floor_divide(total, 12)
            nm = total - ny * 12 + 1
            nd = xp.minimum(d, _month_len_vec(xp, ny, nm))
            out = _days_from_civil_vec(xp, ny, nm, nd)
            return ExprValue(out.astype(np.int32), valid)
        # months_between (Spark's rule: integer when same day-of-month or
        # both month ends; else day difference / 31, rounded to 8 digits)
        db = _as_days(xp, b, self.children[1].data_type(schema))
        y1, m1, d1 = _civil_ymd_vec(xp, da)
        y2, m2, d2 = _civil_ymd_vec(xp, db)
        whole = ((y1 - y2) * 12 + (m1 - m2)).astype(np.float64)
        last1 = d1 == _month_len_vec(xp, y1, m1)
        last2 = d2 == _month_len_vec(xp, y2, m2)
        frac = (d1 - d2).astype(np.float64) / 31.0
        out = xp.where((d1 == d2) | (last1 & last2), whole, whole + frac)
        return ExprValue(xp.round(out * 1e8) / 1e8, valid)

    def __repr__(self):
        return f"{self.kind}({', '.join(map(repr, self.children))})"


class NextDay(Expression):
    """next_day(date, 'Mon'): the first date later than `date` falling on
    the given weekday (datetimeExpressions.scala NextDay)."""

    DOW = {"sun": 0, "mon": 1, "tue": 2, "wed": 3, "thu": 4, "fri": 5,
           "sat": 6}

    def __init__(self, child: Expression, day_name: str):
        key = str(day_name).strip().lower()[:3]
        if key not in self.DOW:
            raise AnalysisException(f"unknown weekday {day_name!r}")
        self.day_name = key
        self.children = (child,)

    def map_children(self, fn):
        return NextDay(fn(self.children[0]), self.day_name)

    def data_type(self, schema):
        return T.date

    def eval(self, ctx):
        xp = ctx.xp
        v = ctx.broadcast(self.children[0].eval(ctx))
        days = _as_days(xp, v, self.children[0].data_type(ctx.batch.schema))
        # 1970-01-01 was Thursday; dow 0 = Sunday
        cur = (days + 4) % 7
        target = np.int64(self.DOW[self.day_name])
        delta = (target - cur + 7) % 7
        delta = xp.where(delta == 0, 7, delta)
        return ExprValue((days + delta).astype(np.int32), v.valid)

    def __repr__(self):
        return f"next_day({self.children[0]!r}, {self.day_name!r})"


class TruncDate(Expression):
    """trunc(date, 'year'|'month'|'week'|'quarter') -> date."""

    def __init__(self, child: Expression, fmt: str):
        key = str(fmt).strip().lower()
        aliases = {"yy": "year", "yyyy": "year", "mm": "month",
                   "mon": "month"}
        key = aliases.get(key, key)
        if key not in ("year", "month", "week", "quarter"):
            raise AnalysisException(f"unknown trunc unit {fmt!r}")
        self.fmt = key
        self.children = (child,)

    def map_children(self, fn):
        return TruncDate(fn(self.children[0]), self.fmt)

    def data_type(self, schema):
        return T.date

    def eval(self, ctx):
        xp = ctx.xp
        v = ctx.broadcast(self.children[0].eval(ctx))
        days = _as_days(xp, v, self.children[0].data_type(ctx.batch.schema))
        if self.fmt == "week":      # Monday start
            out = days - (days + 3) % 7
        else:
            y, m, _d = _civil_ymd_vec(xp, days)
            if self.fmt == "year":
                m = xp.ones_like(m)
            elif self.fmt == "quarter":
                m = ((m - 1) // 3) * 3 + 1
            out = _days_from_civil_vec(xp, y, m, xp.ones_like(days))
        return ExprValue(out.astype(np.int32), v.valid)

    def __repr__(self):
        return f"trunc({self.children[0]!r}, {self.fmt!r})"


class UnixTimestamp(Expression):
    """unix_timestamp(ts) -> seconds since epoch (int64); from_unixtime
    (`FromUnixTime`) is the inverse returning a TIMESTAMP (deviation: the
    reference formats to string; string materialization is host-side)."""

    def __init__(self, child: Expression, inverse: bool = False):
        self.inverse = inverse
        self.children = (child,)

    def map_children(self, fn):
        return UnixTimestamp(fn(self.children[0]), self.inverse)

    def data_type(self, schema):
        return T.timestamp if self.inverse else T.int64

    def eval(self, ctx):
        xp = ctx.xp
        v = ctx.broadcast(self.children[0].eval(ctx))
        dt = self.children[0].data_type(ctx.batch.schema)
        if self.inverse:
            return ExprValue(v.data.astype(np.int64) * 1_000_000, v.valid)
        if isinstance(dt, T.DateType):
            return ExprValue(v.data.astype(np.int64) * 86_400, v.valid)
        return ExprValue(xp.floor_divide(v.data.astype(np.int64),
                                         1_000_000), v.valid)

    def __repr__(self):
        op = "from_unixtime" if self.inverse else "unix_timestamp"
        return f"{op}({self.children[0]!r})"


class BinaryMath(Expression):
    """hypot/atan2/nanvl — float64 elementwise binaries."""

    FNS = {
        "hypot": lambda xp, a, b: xp.hypot(a, b),
        "atan2": lambda xp, a, b: xp.arctan2(a, b),
        "nanvl": lambda xp, a, b: xp.where(xp.isnan(a), b, a),
    }

    def __init__(self, fn: str, left: Expression, right: Expression):
        assert fn in self.FNS, fn
        self.fn = fn
        self.children = (left, right)

    def map_children(self, fn):
        return BinaryMath(self.fn, fn(self.children[0]), fn(self.children[1]))

    def data_type(self, schema):
        return T.float64

    def eval(self, ctx):
        xp = ctx.xp
        a = ctx.broadcast(self.children[0].eval(ctx))
        b = ctx.broadcast(self.children[1].eval(ctx))
        out = self.FNS[self.fn](xp, a.data.astype(np.float64),
                                b.data.astype(np.float64))
        return ExprValue(out, and_valid(xp, a.valid, b.valid))

    def __repr__(self):
        return f"{self.fn}({self.children[0]!r}, {self.children[1]!r})"


def _soundex(word: str) -> str:
    codes = {"b": "1", "f": "1", "p": "1", "v": "1",
             "c": "2", "g": "2", "j": "2", "k": "2", "q": "2", "s": "2",
             "x": "2", "z": "2", "d": "3", "t": "3", "l": "4",
             "m": "5", "n": "5", "r": "6"}
    w = "".join(c for c in word.upper() if c.isalpha())
    if not w:
        return word
    out = [w[0]]
    prev = codes.get(w[0].lower(), "")
    for c in w[1:]:
        code = codes.get(c.lower(), "")
        if code and code != prev:
            out.append(code)
        if c.lower() not in ("h", "w"):
            prev = code
    return (out[0] + "".join(out[1:]) + "000")[:4]


class ParamStringTransform(Expression):
    """String→string transforms with STATIC parameters (regexp_replace,
    lpad, translate, md5, ...): the host rewrites the dictionary once per
    trace, the device only remaps int32 codes — same contract as
    StringTransform."""

    @staticmethod
    def _make(kind, params):
        import base64 as b64
        import hashlib
        import re as re_mod
        if kind == "regexp_replace":
            pat, repl = params
            rx = re_mod.compile(pat)
            return lambda s: rx.sub(repl, s)
        if kind == "regexp_extract":
            pat, idx = params
            rx = re_mod.compile(pat)

            def ex(s):
                m = rx.search(s)
                return m.group(idx) if m else ""
            return ex
        if kind == "lpad":
            n, pad = params
            return lambda s: s.rjust(n, pad)[:n] if pad else s[:n]
        if kind == "rpad":
            n, pad = params
            return lambda s: s.ljust(n, pad)[:n] if pad else s[:n]
        if kind == "translate":
            frm, to = params
            table = str.maketrans(frm[:len(to)], to[:len(frm)],
                                  frm[len(to):])
            return lambda s: s.translate(table)
        if kind == "repeat":
            (n,) = params
            return lambda s: s * n
        if kind == "soundex":
            return _soundex
        if kind == "md5":
            return lambda s: hashlib.md5(s.encode()).hexdigest()
        if kind == "sha1":
            return lambda s: hashlib.sha1(s.encode()).hexdigest()
        if kind == "sha2":
            (bits,) = params
            return lambda s: hashlib.new(f"sha{bits}",
                                         s.encode()).hexdigest()
        if kind == "base64":
            return lambda s: b64.b64encode(s.encode()).decode()
        if kind == "unbase64":
            return lambda s: b64.b64decode(s.encode()).decode("utf-8",
                                                              "replace")
        if kind == "hex":
            return lambda s: s.encode().hex().upper()
        raise AnalysisException(f"unknown string transform {kind}")

    def __init__(self, kind: str, child: Expression, params: tuple = ()):
        self.kind = kind
        self.params = tuple(params)
        self._fn = self._make(kind, self.params)
        self.children = (child,)

    def map_children(self, fn):
        return ParamStringTransform(self.kind, fn(self.children[0]),
                                    self.params)

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not ct.is_string:
            raise AnalysisException(f"{self.kind} expects string, got {ct}")
        return T.string

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        return _rewrite_dictionary(ctx.xp, v, self._fn)

    def __repr__(self):
        return f"{self.kind}({self.children[0]!r}, {self.params})"


class StringToInt(Expression):
    """String→int64 via a host-computed dictionary table (instr/locate/
    levenshtein-vs-literal/crc32)."""

    @staticmethod
    def _make(kind, params):
        import zlib
        if kind == "instr":
            (sub,) = params
            return lambda s: s.find(sub) + 1
        if kind == "locate":
            sub, start = params
            return lambda s: s.find(sub, max(start - 1, 0)) + 1
        if kind == "levenshtein":
            (other,) = params

            def lev(s):
                a, b = s, other
                if len(a) < len(b):
                    a, b = b, a
                prev = list(range(len(b) + 1))
                for i, ca in enumerate(a, 1):
                    cur = [i]
                    for j, cb in enumerate(b, 1):
                        cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                                       prev[j - 1] + (ca != cb)))
                    prev = cur
                return prev[-1]
            return lev
        if kind == "crc32":
            return lambda s: zlib.crc32(s.encode()) & 0xFFFFFFFF
        raise AnalysisException(f"unknown string→int transform {kind}")

    def __init__(self, kind: str, child: Expression, params: tuple = ()):
        self.kind = kind
        self.params = tuple(params)
        self._fn = self._make(kind, self.params)
        self.children = (child,)

    def map_children(self, fn):
        return StringToInt(self.kind, fn(self.children[0]), self.params)

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not ct.is_string:
            raise AnalysisException(f"{self.kind} expects string, got {ct}")
        return T.int64

    def eval(self, ctx):
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        table = np.array([self._fn(w) for w in v.dictionary] or [0],
                         np.int64)
        codes = xp.clip(v.data, 0, None)
        return ExprValue(xp.asarray(table)[codes], v.valid)

    def __repr__(self):
        return f"{self.kind}({self.children[0]!r}, {self.params})"


class Randn(Rand):
    """Standard-normal draws (randn): Box-Muller over two Rand streams —
    deterministic per (seed, row index) like Rand."""

    def eval(self, ctx: EvalContext) -> ExprValue:
        xp = ctx.xp
        u1 = Rand(self.seed).eval(ctx).data
        u2 = Rand(self.seed + 0x5DEECE66D).eval(ctx).data
        u1 = xp.maximum(u1, 1e-12)
        out = xp.sqrt(-2.0 * xp.log(u1)) * xp.cos(2.0 * math.pi * u2)
        return ExprValue(out, None)

    def __repr__(self):
        return f"randn({self.seed})"


class SparkPartitionId(Expression):
    """spark_partition_id(): the mesh shard index in distributed execution;
    0 on the single-chip path (set via ExecContext.partition_id)."""

    children = ()

    def data_type(self, schema):
        return T.int32

    @property
    def name(self):
        return "SPARK_PARTITION_ID()"

    def eval(self, ctx):
        xp = ctx.xp
        # distributed execution encodes the mesh shard in the high bits of
        # the row offset (executor.py: shard_offset = axis_index << 48);
        # single-chip offsets stay below 2^48 → partition 0
        offset = getattr(ctx, "row_offset", 0)
        if isinstance(offset, int):
            pid = np.int32(offset >> 48)
            return ExprValue(xp.asarray(pid), None)
        return ExprValue((offset >> 48).astype(np.int32), None)

    def __repr__(self):
        return "spark_partition_id()"


# ---------------------------------------------------------------------------
# Array expressions (`complexTypeCreator.scala`, `collectionOperations.scala`)
#
# Layout contract: see T.ArrayType — (capacity, max_len) element-dtype data
# with trailing sentinel padding; element order is position order.
# ---------------------------------------------------------------------------

def _array_elem_mask(xp, dt: "T.ArrayType", data):
    s = dt.element_sentinel()
    if dt.element_type.is_fractional:
        return ~xp.isnan(data)
    return data != s


class MakeArray(Expression):
    """array(e1, e2, ...): fixed-length array from scalar expressions."""

    def __init__(self, *children: Expression):
        if not children:
            raise AnalysisException("array() needs at least one element")
        self.children = tuple(children)

    def map_children(self, fn):
        return MakeArray(*[fn(c) for c in self.children])

    @property
    def name(self):
        return f"array({', '.join(c.name for c in self.children)})"

    def data_type(self, schema):
        et = self.children[0].data_type(schema)
        for c in self.children[1:]:
            et = T.numeric_promote(et, c.data_type(schema)) \
                if et != c.data_type(schema) else et
        return T.ArrayType(et)

    def eval(self, ctx):
        from .columnar import merge_dictionaries
        xp = ctx.xp
        dt = self.data_type(ctx.batch.schema)
        ed = dt.element_type.np_dtype
        vals = [ctx.broadcast(c.eval(ctx)) for c in self.children]
        sent = dt.element_sentinel()
        out_dict = None
        if dt.element_type.is_string:
            # merge each element's dictionary into one shared code space
            merged = vals[0].dictionary or ("",)
            remaps = [np.arange(len(merged), dtype=np.int32)]
            for v in vals[1:]:
                merged, ra, rb = merge_dictionaries(
                    merged, v.dictionary or ("",))
                remaps = [ra[r] for r in remaps] + [rb]
            vals = [ExprValue(xp.asarray(r)[xp.clip(v.data, 0, None)],
                              v.valid, merged)
                    for v, r in zip(vals, remaps)]
            out_dict = merged
        cols = []
        masks = []
        any_null = any(v.valid is not None for v in vals)
        for v in vals:
            d = v.data.astype(ed)
            if v.valid is not None:          # NULL element -> sentinel slot
                d = xp.where(v.valid, d, sent)
                masks.append(v.valid)
            else:
                masks.append(None)
            cols.append(d)
        data = xp.stack(cols, axis=-1)
        if any_null:
            # pack live elements to the FRONT: the ArrayType layout is
            # position-packed with trailing sentinels (ElementAt/size
            # depend on it).  Deviation: NULL elements are dropped, not
            # kept in place — interior nulls are unrepresentable here.
            k = len(cols)
            mask = xp.stack(
                [m if m is not None
                 else xp.ones(data.shape[0], bool) for m in masks], axis=-1)
            order = xp.argsort(~mask, axis=-1, stable=True)
            data = xp.take_along_axis(data, order, axis=-1)
        return ExprValue(data, None, out_dict)

    def __repr__(self):
        return f"array({', '.join(map(repr, self.children))})"


class SplitStr(Expression):
    """split(str, regex[, limit]) -> array<string>: the dictionary is
    split on host once per trace; the device gathers per-row element-code
    vectors from a (dict_size, max_len) table."""

    def __init__(self, child: Expression, pattern: str, limit: int = -1):
        self.pattern = pattern
        self.limit = limit
        self.children = (child,)

    def map_children(self, fn):
        return SplitStr(fn(self.children[0]), self.pattern, self.limit)

    @property
    def name(self):
        return f"split({self.children[0].name}, {self.pattern!r})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not ct.is_string:
            raise AnalysisException(f"split expects string, got {ct}")
        return T.ArrayType(T.string)

    def eval(self, ctx):
        import re as re_mod
        xp = ctx.xp
        v = self.children[0].eval(ctx)
        rx = re_mod.compile(self.pattern)
        # re.split maxsplit: 0 = unlimited; Spark limit<=0 = split fully
        maxsplit = 0 if self.limit <= 0 else self.limit - 1
        parts_per_word = [rx.split(w, maxsplit)
                          for w in (v.dictionary or ("",))]
        elem_dict = tuple(sorted({p for parts in parts_per_word
                                  for p in parts}))
        pos = {w: i for i, w in enumerate(elem_dict)}
        L = max(max((len(p) for p in parts_per_word), default=1), 1)
        table = np.full((len(parts_per_word), L), -1, np.int32)
        for i, parts in enumerate(parts_per_word):
            for j, p in enumerate(parts):
                table[i, j] = pos[p]
        codes = xp.clip(v.data, 0, None)
        return ExprValue(xp.asarray(table)[codes], v.valid, elem_dict)

    def __repr__(self):
        return f"split({self.children[0]!r}, {self.pattern!r})"


class ArraySize(Expression):
    """size(arr): element count (0 for empty; NULL row follows row mask)."""

    def __init__(self, child: Expression):
        self.children = (child,)

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if isinstance(ct, T.MapType):
            return T.int32        # size(map): rewritten to its keys plane
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"size expects an array, got {ct}")
        return T.int32

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        return ExprValue(mask.sum(axis=-1).astype(np.int32), v.valid)

    def __repr__(self):
        return f"size({self.children[0]!r})"


def _gather_1based_plane(xp, dt, v, idx, capacity, out_np_dtype):
    """ONE definition of the 1-based (negative = from-the-end, 0/out of
    bounds = NULL) array-plane gather, shared by ElementAt (static index)
    and ArrayGather (dynamic index) so their semantics cannot diverge.
    Returns (gathered data, ok mask)."""
    if v.data.shape[-1] == 0:        # all-empty plane: nothing to gather
        return xp.zeros(capacity, out_np_dtype), xp.zeros(capacity, bool)
    mask = _array_elem_mask(xp, dt, v.data)
    lengths = mask.sum(axis=-1)
    eff = xp.where(idx > 0, idx - 1, lengths + idx)
    ok = (idx != 0) & (eff >= 0) & (eff < lengths)
    gathered = xp.take_along_axis(
        v.data, xp.clip(eff, 0, v.data.shape[-1] - 1)[..., None],
        axis=-1)[..., 0]
    return gathered, ok


class ElementAt(Expression):
    """element_at(arr, i): 1-based; negative indexes from the end; out of
    bounds -> NULL (Spark's non-ANSI behavior)."""

    def __init__(self, child: Expression, index: int):
        if index == 0:
            raise AnalysisException("element_at index is 1-based; got 0")
        self.index = int(index)
        self.children = (child,)

    def map_children(self, fn):
        return ElementAt(fn(self.children[0]), self.index)

    @property
    def name(self):
        return f"element_at({self.children[0].name}, {self.index})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if isinstance(ct, T.MapType):
            return ct.value_type  # element_at(map, k): rewritten to MapGet
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"element_at expects an array, got {ct}")
        return ct.element_type

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        out_dt = self.data_type(ctx.batch.schema).np_dtype
        gathered, ok = _gather_1based_plane(
            xp, dt, v, np.int64(self.index), ctx.capacity, out_dt)
        return ExprValue(gathered, and_valid(xp, v.valid, ok),
                         v.dictionary)

    def __repr__(self):
        return f"element_at({self.children[0]!r}, {self.index})"


class ArrayReduce(Expression):
    """array_max / array_min: sentinel-aware reduction over the plane."""

    def __init__(self, child: Expression, op: str):
        self.children = (child,)
        self.op = op                      # "max" | "min"

    def map_children(self, fn):
        return ArrayReduce(fn(self.children[0]), self.op)

    @property
    def name(self):
        return f"array_{self.op}({self.children[0].name})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(
                f"array_{self.op} expects an array, got {ct}")
        return ct.element_type

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        et = dt.element_type
        if et.is_fractional:
            lo, hi = -np.inf, np.inf
        else:
            info = np.iinfo(et.np_dtype)
            lo, hi = info.min, info.max
        fill = lo if self.op == "max" else hi
        red = xp.max if self.op == "max" else xp.min
        out = red(xp.where(mask, v.data, fill), axis=-1)
        nonempty = mask.any(axis=-1)
        return ExprValue(out, and_valid(xp, v.valid, nonempty),
                         v.dictionary)

    def __repr__(self):
        return f"array_{self.op}({self.children[0]!r})"


class SortArray(Expression):
    """sort_array(arr[, asc]): per-row element sort, dead slots kept as a
    trailing sentinel block (live-prefix layout contract)."""

    def __init__(self, child: Expression, asc: bool = True):
        self.children = (child,)
        self.asc = bool(asc)

    def map_children(self, fn):
        return SortArray(fn(self.children[0]), self.asc)

    @property
    def name(self):
        return f"sort_array({self.children[0].name}, {self.asc})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"sort_array expects an array, got {ct}")
        return ct

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        et = dt.element_type
        # string codes sort lexicographically BY CONSTRUCTION (sorted
        # dictionaries).  Ascending: dead slots carry the MAX extreme so
        # they sink; descending: dead slots carry the MIN extreme, sort
        # ascending, then flip the row — dead slots land last either way
        # with no negation (which would overflow int64 / lose exactness).
        if et.is_fractional:
            info_lo, info_hi = -np.inf, np.inf
        else:
            info = np.iinfo(et.np_dtype)
            info_lo, info_hi = info.min, info.max
        fill = info_hi if self.asc else info_lo
        order = xp.argsort(xp.where(mask, v.data, fill), axis=-1,
                           stable=True)
        if not self.asc:
            order = xp.flip(order, axis=-1)
        data = xp.take_along_axis(v.data, order, axis=-1)
        smask = xp.take_along_axis(mask, order, axis=-1)
        data = xp.where(smask, data, dt.element_sentinel())
        return ExprValue(data, v.valid, v.dictionary)

    def __repr__(self):
        return f"sort_array({self.children[0]!r}, asc={self.asc})"


class ArrayDistinct(Expression):
    """array_distinct(arr): first occurrence of each element kept, order
    preserved, result compacted to the live prefix."""

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def name(self):
        return f"array_distinct({self.children[0].name})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(
                f"array_distinct expects an array, got {ct}")
        return ct

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        # first-occurrence: element j survives iff no earlier equal live
        # element exists — O(L^2) pairwise plane, L is small and static
        eq = v.data[..., :, None] == v.data[..., None, :]
        earlier = xp.tril(xp.ones(eq.shape[-2:], bool), k=-1)
        dup = (eq & earlier & mask[..., None, :]
               & mask[..., :, None]).any(axis=-1)
        keep = mask & ~dup
        order = xp.argsort(~keep, axis=-1, stable=True)
        data = xp.take_along_axis(v.data, order, axis=-1)
        kept = xp.take_along_axis(keep, order, axis=-1)
        data = xp.where(kept, data, dt.element_sentinel())
        return ExprValue(data, v.valid, v.dictionary)

    def __repr__(self):
        return f"array_distinct({self.children[0]!r})"


class ArraySlice(Expression):
    """slice(arr, start, length): 1-based, negative start from the end."""

    def __init__(self, child: Expression, start: int, length: int):
        if start == 0:
            raise AnalysisException("slice start is 1-based; got 0")
        if length < 0:
            raise AnalysisException("slice length must be >= 0")
        self.children = (child,)
        self.start = int(start)
        self.length = int(length)

    def map_children(self, fn):
        return ArraySlice(fn(self.children[0]), self.start, self.length)

    @property
    def name(self):
        return f"slice({self.children[0].name}, {self.start}, {self.length})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"slice expects an array, got {ct}")
        return ct

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        lengths = mask.sum(axis=-1)
        width = v.data.shape[-1]
        begin = np.int64(self.start)
        eff = xp.where(begin > 0, begin - 1, lengths + begin)
        # Spark: a negative start reaching before element 0 yields the
        # EMPTY array (never a partial tail), and live elements must land
        # on the output PREFIX (layout contract)
        valid_start = (eff >= 0) & (eff < lengths)
        pos = xp.arange(width, dtype=np.int64)
        idx = eff[..., None] + pos
        in_range = valid_start[..., None] & (pos < self.length) \
            & (idx < lengths[..., None])
        gathered = xp.take_along_axis(
            v.data, xp.clip(idx, 0, width - 1), axis=-1)
        data = xp.where(in_range, gathered, dt.element_sentinel())
        return ExprValue(data, v.valid, v.dictionary)

    def __repr__(self):
        return f"slice({self.children[0]!r}, {self.start}, {self.length})"


class ArrayPosition(Expression):
    """array_position(arr, value): 1-based first index, 0 when absent."""

    def __init__(self, child: Expression, value: Any):
        self.children = (child,)
        self.value = value

    def map_children(self, fn):
        return ArrayPosition(fn(self.children[0]), self.value)

    @property
    def name(self):
        return f"array_position({self.children[0].name}, {self.value!r})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(
                f"array_position expects an array, got {ct}")
        return T.int64

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        if dt.element_type.is_string:
            if v.dictionary is None or self.value not in v.dictionary:
                hit = xp.zeros(v.data.shape, bool)
            else:
                hit = v.data == v.dictionary.index(self.value)
        else:
            hit = v.data == np.asarray(self.value).astype(
                dt.element_type.np_dtype)
        hit = hit & mask
        width = v.data.shape[-1]
        first = xp.where(hit, xp.arange(width, dtype=np.int64),
                         np.int64(width)).min(axis=-1)
        pos = xp.where(first < width, first + 1, 0)
        return ExprValue(pos, v.valid)

    def __repr__(self):
        return f"array_position({self.children[0]!r}, {self.value!r})"


class LambdaVar(Expression):
    """Lambda placeholder bound by a higher-order array function to the
    ELEMENT PLANE (`higherOrderFunctions.scala`'s NamedLambdaVariable).

    Evaluates to the whole ``(capacity, max_len)`` plane — element-wise
    lambdas become plain vectorized ops over it, which is exactly the
    TPU-friendly shape.  ``dtype`` is bound by the enclosing function at
    type-resolution time (deterministic, planning-only mutation)."""

    _counter = [0]

    def __init__(self, name: str = "x"):
        self.children = ()
        LambdaVar._counter[0] += 1
        self._name = f"{name}#{LambdaVar._counter[0]}"
        self.dtype: Optional[T.DataType] = None
        self.dictionary = None

    @property
    def name(self):
        return self._name

    def references(self) -> set:
        return set()                   # bound, not a column reference

    def data_type(self, schema):
        if self.dtype is None:
            raise AnalysisException(
                f"lambda variable {self._name} used outside its "
                "higher-order function")
        return self.dtype

    def eval(self, ctx):
        bound = getattr(ctx, "lambda_bindings", {}).get(self._name)
        if bound is None:
            raise AnalysisException(
                f"lambda variable {self._name} evaluated without a "
                "binding")
        return bound

    def __repr__(self):
        return self._name.split("#")[0]


class _HigherOrder(Expression):
    """Shared machinery: bind the element plane, evaluate the body
    vectorized over it."""

    def __init__(self, child: Expression, var: LambdaVar, body: Expression):
        self.children = (child,)
        self.var = var
        self.body = body
        extra = body.references()
        if extra:
            raise AnalysisException(
                f"lambda body may reference only the lambda variable and "
                f"literals in this engine (vectorized element-plane "
                f"evaluation); found column refs {sorted(extra)}")

    def map_children(self, fn):
        return type(self)(fn(self.children[0]), self.var, self.body)

    def _array_type(self, schema) -> "T.ArrayType":
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(
                f"{type(self).__name__} expects an array, got {ct}")
        self.var.dtype = ct.element_type
        return ct

    def _plane(self, ctx):
        """(value ExprValue over the plane, element mask, array ExprValue)."""
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        self.var.dtype = dt.element_type
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        bound = ExprValue(v.data, None, v.dictionary)
        bindings = dict(getattr(ctx, "lambda_bindings", {}))
        bindings[self.var._name] = bound
        sub = EvalContext(ctx.batch, xp)
        sub.lambda_bindings = bindings
        out = self.body.eval(sub)
        return out, mask, v


class ArrayTransform(_HigherOrder):
    """transform(arr, x -> expr): elementwise map over the plane."""

    @property
    def name(self):
        return f"transform({self.children[0].name}, " \
               f"{self.var!r} -> {self.body.name})"

    def data_type(self, schema):
        self._array_type(schema)
        et = self.body.data_type(schema)
        if et.is_string:
            raise AnalysisException(
                "transform to string elements is not supported yet")
        if isinstance(et, T.BooleanType):
            et = T.int32           # bool arrays have no sentinel; widen
        return T.ArrayType(et)

    def eval(self, ctx):
        xp = ctx.xp
        out, mask, v = self._plane(ctx)
        odt = self.data_type(ctx.batch.schema)
        sent = odt.element_sentinel()
        data = xp.asarray(out.data).astype(odt.element_type.np_dtype)
        ok = mask if out.valid is None else (mask & out.valid)
        data = xp.where(ok, data, sent)
        return ExprValue(data, v.valid)

    def __repr__(self):
        return f"transform({self.children[0]!r}, {self.var!r} -> " \
               f"{self.body!r})"


class ArrayFilterFn(_HigherOrder):
    """filter(arr, x -> pred): keep matching elements, COMPACTED to a
    prefix (positional ops like element_at assume live-prefix layout)."""

    @property
    def name(self):
        return f"filter({self.children[0].name}, " \
               f"{self.var!r} -> {self.body.name})"

    def data_type(self, schema):
        ct = self._array_type(schema)
        bt = self.body.data_type(schema)
        if not isinstance(bt, T.BooleanType):
            raise AnalysisException(
                f"filter lambda must return boolean, got {bt} "
                f"({self.body!r})")
        return ct

    def eval(self, ctx):
        xp = ctx.xp
        out, mask, v = self._plane(ctx)
        dt = self.children[0].data_type(ctx.batch.schema)
        sent = dt.element_sentinel()
        pred = xp.asarray(out.data).astype(bool)
        if out.valid is not None:
            pred = pred & out.valid
        keep = mask & pred
        # stable compaction: live elements first, original order kept
        # (same idiom as MakeArray's null compaction)
        order = xp.argsort(~keep, axis=-1, stable=True)
        data = xp.take_along_axis(v.data, order, axis=-1)
        kept = xp.take_along_axis(keep, order, axis=-1)
        data = xp.where(kept, data, sent)
        return ExprValue(data, v.valid, v.dictionary)

    def __repr__(self):
        return f"filter({self.children[0]!r}, {self.var!r} -> " \
               f"{self.body!r})"


class ArrayExists(_HigherOrder):
    """exists(arr, x -> pred) / forall(arr, x -> pred)."""

    def __init__(self, child, var, body, require_all: bool = False):
        super().__init__(child, var, body)
        self.require_all = require_all

    def map_children(self, fn):
        return ArrayExists(fn(self.children[0]), self.var, self.body,
                           self.require_all)

    @property
    def name(self):
        kind = "forall" if self.require_all else "exists"
        return f"{kind}({self.children[0].name}, " \
               f"{self.var!r} -> {self.body.name})"

    def data_type(self, schema):
        self._array_type(schema)
        bt = self.body.data_type(schema)
        if not isinstance(bt, T.BooleanType):
            kind = "forall" if self.require_all else "exists"
            raise AnalysisException(
                f"{kind} lambda must return boolean, got {bt} "
                f"({self.body!r})")
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        out, mask, v = self._plane(ctx)
        pred = xp.asarray(out.data).astype(bool)
        if out.valid is not None:
            pred = pred & out.valid
        if self.require_all:
            res = xp.all(pred | ~mask, axis=-1)
        else:
            res = xp.any(pred & mask, axis=-1)
        return ExprValue(res, v.valid)

    def __repr__(self):
        kind = "forall" if self.require_all else "exists"
        return f"{kind}({self.children[0]!r}, {self.var!r} -> " \
               f"{self.body!r})"


class ArrayAggregate(Expression):
    """aggregate(arr, init, (acc, x) -> merge[, acc -> finish]): fold over
    the element plane.  The fold unrolls over the STATIC max_len (one
    masked select per slot — compiler-friendly, no data-dependent loop)."""

    def __init__(self, child: Expression, init: Expression,
                 acc_var: "LambdaVar", x_var: "LambdaVar",
                 merge: Expression,
                 finish_var: Optional["LambdaVar"] = None,
                 finish: Optional[Expression] = None):
        self.children = (child, init)
        self.acc_var = acc_var
        self.x_var = x_var
        self.merge = merge
        self.finish_var = finish_var
        self.finish = finish
        for body in (merge, finish):
            if body is not None and body.references():
                raise AnalysisException(
                    "lambda body may reference only its lambda variables "
                    "and literals in this engine; found column refs "
                    f"{sorted(body.references())}")

    def map_children(self, fn):
        return ArrayAggregate(fn(self.children[0]), fn(self.children[1]),
                              self.acc_var, self.x_var, self.merge,
                              self.finish_var, self.finish)

    @property
    def name(self):
        return f"aggregate({self.children[0].name})"

    def _bind_types(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"aggregate expects an array, got {ct}")
        self.x_var.dtype = ct.element_type
        self.acc_var.dtype = self.children[1].data_type(schema)
        return ct

    def data_type(self, schema):
        self._bind_types(schema)
        if self.acc_var.dtype.is_string:
            raise AnalysisException(
                "aggregate with a string accumulator is not supported "
                "yet (dictionary state cannot thread through the fold)")
        mt = self.merge.data_type(schema)
        if mt.is_string:
            raise AnalysisException(
                "aggregate merge producing strings is not supported yet")
        if self.finish is not None:
            self.finish_var.dtype = mt
            return self.finish.data_type(schema)
        return mt

    def eval(self, ctx):
        xp = ctx.xp
        dt = self._bind_types(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        init = ctx.broadcast(self.children[1].eval(ctx))
        acc_data = init.data
        acc_valid = init.valid
        width = v.data.shape[-1]
        for i in range(width):
            sub = EvalContext(ctx.batch, xp)
            sub.lambda_bindings = dict(getattr(ctx, "lambda_bindings", {}))
            sub.lambda_bindings[self.acc_var._name] = \
                ExprValue(acc_data, acc_valid)
            sub.lambda_bindings[self.x_var._name] = \
                ExprValue(v.data[..., i], None, v.dictionary)
            merged = sub.broadcast(self.merge.eval(sub))
            live = mask[..., i]
            acc_data = xp.where(live, merged.data, acc_data)
            if merged.valid is not None or acc_valid is not None:
                mv = merged.valid if merged.valid is not None \
                    else xp.ones_like(live)
                av = acc_valid if acc_valid is not None \
                    else xp.ones_like(live)
                acc_valid = xp.where(live, mv, av)
        out = ExprValue(acc_data, and_valid(xp, v.valid, acc_valid)
                        if acc_valid is not None else v.valid)
        if self.finish is not None:
            self.finish_var.dtype = self.merge.data_type(ctx.batch.schema)
            sub = EvalContext(ctx.batch, xp)
            sub.lambda_bindings = {self.finish_var._name: out}
            fin = sub.broadcast(self.finish.eval(sub))
            out = ExprValue(fin.data,
                            and_valid(xp, out.valid, fin.valid)
                            if fin.valid is not None else out.valid)
        return out

    def __repr__(self):
        fin = f", {self.finish_var!r} -> {self.finish!r}" \
            if self.finish is not None else ""
        return (f"aggregate({self.children[0]!r}, {self.children[1]!r}, "
                f"({self.acc_var!r}, {self.x_var!r}) -> "
                f"{self.merge!r}{fin})")


class ZipWith(Expression):
    """zip_with(a, b, (x, y) -> expr): elementwise combine of two arrays.
    The shorter side's missing tail enters the lambda as NULL (validity
    propagation), matching the reference's null-padded zip."""

    def __init__(self, left: Expression, right: Expression,
                 x_var: "LambdaVar", y_var: "LambdaVar", body: Expression):
        self.children = (left, right)
        self.x_var = x_var
        self.y_var = y_var
        self.body = body
        if body.references():
            raise AnalysisException(
                "lambda body may reference only its lambda variables and "
                f"literals; found column refs {sorted(body.references())}")

    def map_children(self, fn):
        return ZipWith(fn(self.children[0]), fn(self.children[1]),
                       self.x_var, self.y_var, self.body)

    @property
    def name(self):
        return f"zip_with({self.children[0].name}, {self.children[1].name})"

    def _bind_types(self, schema):
        lt = self.children[0].data_type(schema)
        rt = self.children[1].data_type(schema)
        if not isinstance(lt, T.ArrayType) or not isinstance(rt, T.ArrayType):
            raise AnalysisException(
                f"zip_with expects two arrays, got {lt} and {rt}")
        self.x_var.dtype = lt.element_type
        self.y_var.dtype = rt.element_type
        return lt, rt

    def data_type(self, schema):
        self._bind_types(schema)
        et = self.body.data_type(schema)
        if et.is_string:
            raise AnalysisException(
                "zip_with to string elements is not supported yet")
        if isinstance(et, T.BooleanType):
            et = T.int32
        return T.ArrayType(et)

    def eval(self, ctx):
        xp = ctx.xp
        lt, rt = self._bind_types(ctx.batch.schema)
        a = self.children[0].eval(ctx)
        b = self.children[1].eval(ctx)
        am = _array_elem_mask(xp, lt, a.data)
        bm = _array_elem_mask(xp, rt, b.data)
        wa, wb = a.data.shape[-1], b.data.shape[-1]
        w = max(wa, wb)

        def widen(data, mask, width, fill):
            if width == w:
                return data, mask
            pad = [(0, 0)] * (data.ndim - 1) + [(0, w - width)]
            return (xp.pad(data, pad, constant_values=fill),
                    xp.pad(mask, pad, constant_values=False))

        ad, am = widen(a.data, am, wa, 0)
        bd, bm = widen(b.data, bm, wb, 0)
        sub = EvalContext(ctx.batch, xp)
        sub.lambda_bindings = dict(getattr(ctx, "lambda_bindings", {}))
        sub.lambda_bindings[self.x_var._name] = \
            ExprValue(ad, am, a.dictionary)
        sub.lambda_bindings[self.y_var._name] = \
            ExprValue(bd, bm, b.dictionary)
        out = self.body.eval(sub)
        odt = self.data_type(ctx.batch.schema)
        sent = odt.element_sentinel()
        live = am | bm
        ok = live if out.valid is None else (live & out.valid)
        data = xp.where(ok, xp.asarray(out.data).astype(
            odt.element_type.np_dtype), sent)
        return ExprValue(data, and_valid(xp, a.valid, b.valid))

    def __repr__(self):
        return (f"zip_with({self.children[0]!r}, {self.children[1]!r}, "
                f"({self.x_var!r}, {self.y_var!r}) -> {self.body!r})")


class ArrayContains(Expression):
    """array_contains(arr, literal)."""

    def __init__(self, child: Expression, value: Any):
        self.value = value
        self.children = (child,)

    def map_children(self, fn):
        return ArrayContains(fn(self.children[0]), self.value)

    @property
    def name(self):
        return f"array_contains({self.children[0].name}, {self.value!r})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(
                f"array_contains expects an array, got {ct}")
        return T.boolean

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        mask = _array_elem_mask(xp, dt, v.data)
        if dt.element_type.is_string:
            words = np.array(v.dictionary or (), dtype=object)
            idx = int(np.searchsorted(words, self.value)) if len(words) \
                else 0
            if idx >= len(words) or words[idx] != self.value:
                zero = xp.zeros(v.data.shape[0], bool)
                return ExprValue(zero, v.valid)
            target = np.int32(idx)
        else:
            ed = np.dtype(dt.element_type.np_dtype)
            if np.issubdtype(ed, np.integer) and \
                    float(self.value) != int(self.value):
                # 1.5 can never equal an integer element; casting would
                # truncate and false-positive
                return ExprValue(xp.zeros(v.data.shape[0], bool), v.valid)
            target = np.asarray(self.value, ed)
        hit = ((v.data == target) & mask).any(axis=-1)
        return ExprValue(hit, v.valid)

    def __repr__(self):
        return f"array_contains({self.children[0]!r}, {self.value!r})"


class ExplodeMarker(Expression):
    """Marker for explode()/posexplode() in a select list; the DataFrame/
    analyzer layer rewrites it into the Explode logical operator (the
    reference's `Generate` + `GeneratorOuter` machinery collapsed to the
    one generator the columnar engine supports)."""

    def __init__(self, child: Expression, with_pos: bool = False):
        self.with_pos = with_pos
        self.children = (child,)

    def map_children(self, fn):
        return ExplodeMarker(fn(self.children[0]), self.with_pos)

    @property
    def name(self):
        return "col" if not self.with_pos else "posexplode"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"explode expects an array, got {ct}")
        return ct.element_type

    def eval(self, ctx):
        raise AnalysisException(
            "explode() is only supported as a top-level select expression")

    def __repr__(self):
        return f"explode({self.children[0]!r})"


class GroupingCall(Expression):
    """grouping(col) / grouping_id() inside GROUP BY ROLLUP/CUBE/GROUPING
    SETS — resolved to per-branch literals by the analyzer's grouping-sets
    rewrite (`grouping__id` in the reference's Expand output)."""

    def __init__(self, child: Optional[Expression]):
        self.children = (child,) if child is not None else ()

    @property
    def name(self):
        return "grouping_id()" if not self.children \
            else f"grouping({self.children[0].name})"

    def data_type(self, schema):
        return T.int64 if not self.children else T.int32

    def eval(self, ctx):
        raise AnalysisException(
            "grouping()/grouping_id() are only valid with GROUP BY "
            "ROLLUP/CUBE/GROUPING SETS")

    def __repr__(self):
        return self.name


# ---------------------------------------------------------------------------
# complex types: struct + map (the object layer)
# ---------------------------------------------------------------------------
#
# Maps and structs are OBJECT-LAYER values, exactly as in the reference
# (`complexTypeCreator.scala:164` CreateMap/CreateNamedStruct never got a
# Tungsten-vectorized layout): every consumer is rewritten by the optimizer
# into flat array/scalar expressions (`SimplifyExtractValueOps`-style,
# `complexTypeExtractors.scala`), so nothing below ever materializes a
# nested value on device.  Only a COLLECTED map/struct column materializes,
# as its flat planes (docs/DECISIONS.md pair-of-planes design), zipped into
# Python dicts/Rows host-side by the DataFrame layer.

_COMPLEX_EVAL_HINT = (
    " survived to execution: complex values are consumed via "
    "getField/map_keys/map_values/element_at/size (rewritten to flat "
    "columns by the optimizer) or collected at the top level.  A map/"
    "struct flowing through an operator that is neither is unsupported — "
    "as are maps/structs read from files (docs/DECISIONS.md)."
)


class CreateStruct(Expression):
    """struct(...) / named_struct(...) — `complexTypeCreator.scala:164`."""

    def __init__(self, field_names, *children: Expression):
        if not children or len(field_names) != len(children):
            raise AnalysisException("struct() needs one name per field")
        self.field_names = tuple(field_names)
        self.children = tuple(children)

    def map_children(self, fn):
        return CreateStruct(self.field_names,
                            *[fn(c) for c in self.children])

    @property
    def name(self):
        return f"struct({', '.join(c.name for c in self.children)})"

    def data_type(self, schema):
        return T.StructType([T.StructField(n, c.data_type(schema))
                             for n, c in zip(self.field_names,
                                             self.children)])

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        parts = [f"{n}={c!r}" for n, c in zip(self.field_names,
                                              self.children)]
        return f"named_struct({', '.join(parts)})"


class GetField(Expression):
    """struct.field — `complexTypeExtractors.scala` GetStructField."""

    def __init__(self, child: Expression, field: str):
        self.children = (child,)
        self.field = field

    def map_children(self, fn):
        return GetField(fn(self.children[0]), self.field)

    @property
    def name(self):
        return self.field

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.StructType):
            raise AnalysisException(
                f"getField expects a struct, got {ct}")
        for f in ct.fields:
            if f.name == self.field:
                return f.dataType
        raise AnalysisException(
            f"no field {self.field!r} in {ct.names}")

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return f"{self.children[0]!r}.{self.field}"


class CreateMap(Expression):
    """map(k1, v1, k2, v2, ...) — `complexTypeCreator.scala` CreateMap."""

    def __init__(self, *children: Expression):
        if not children or len(children) % 2:
            raise AnalysisException(
                "map() needs an even, positive number of arguments "
                "(alternating keys and values)")
        self.children = tuple(children)

    def map_children(self, fn):
        return CreateMap(*[fn(c) for c in self.children])

    @property
    def keys(self):
        return self.children[0::2]

    @property
    def values(self):
        return self.children[1::2]

    @property
    def name(self):
        return f"map({', '.join(c.name for c in self.children)})"

    def _common(self, exprs, schema, what):
        dt = exprs[0].data_type(schema)
        for e in exprs[1:]:
            nxt = T.common_type(dt, e.data_type(schema))
            if nxt is None:
                raise AnalysisException(
                    f"map {what} types are incompatible: {dt} vs "
                    f"{e.data_type(schema)}")
            dt = nxt
        return dt

    def data_type(self, schema):
        return T.MapType(self._common(self.keys, schema, "key"),
                         self._common(self.values, schema, "value"))

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return f"map({', '.join(repr(c) for c in self.children)})"


class MapFromArrays(Expression):
    """map_from_arrays(keys_array, values_array)."""

    def __init__(self, keys: Expression, values: Expression):
        self.children = (keys, values)

    def map_children(self, fn):
        return MapFromArrays(fn(self.children[0]), fn(self.children[1]))

    @property
    def name(self):
        return (f"map_from_arrays({self.children[0].name}, "
                f"{self.children[1].name})")

    def data_type(self, schema):
        kt = self.children[0].data_type(schema)
        vt = self.children[1].data_type(schema)
        if not isinstance(kt, T.ArrayType) or not isinstance(vt, T.ArrayType):
            raise AnalysisException(
                f"map_from_arrays expects two arrays, got {kt}, {vt}")
        return T.MapType(kt.element_type, vt.element_type)

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return (f"map_from_arrays({self.children[0]!r}, "
                f"{self.children[1]!r})")


class _MapExtract(Expression):
    """Shared shape of map_keys/map_values."""

    WHICH = "keys"

    def __init__(self, child: Expression):
        self.children = (child,)

    def map_children(self, fn):
        return type(self)(fn(self.children[0]))

    @property
    def name(self):
        return f"map_{self.WHICH}({self.children[0].name})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.MapType):
            raise AnalysisException(
                f"map_{self.WHICH} expects a map, got {ct}")
        return T.ArrayType(ct.key_type if self.WHICH == "keys"
                           else ct.value_type)

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return f"map_{self.WHICH}({self.children[0]!r})"


class MapKeys(_MapExtract):
    WHICH = "keys"


class MapValues(_MapExtract):
    WHICH = "values"


class MapGet(Expression):
    """map[key] / element_at(map, key) — GetMapValue: NULL when absent."""

    def __init__(self, child: Expression, key: Expression):
        self.children = (child, key)

    def map_children(self, fn):
        return MapGet(fn(self.children[0]), fn(self.children[1]))

    @property
    def name(self):
        return f"element_at({self.children[0].name}, {self.children[1].name})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if isinstance(ct, T.ArrayType):
            return ct.element_type    # dynamic element_at(arr, expr):
        if not isinstance(ct, T.MapType):  # rewritten to ArrayGather
            raise AnalysisException(f"element_at on {ct} needs a map")
        return ct.value_type

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return f"element_at({self.children[0]!r}, {self.children[1]!r})"


class GetItem(Expression):
    """Column.getItem(key): 0-based position for arrays, key for maps —
    `complexTypeExtractors.scala` ExtractValue dispatch, resolved by the
    optimizer's complex-type rewrite once the child's type is known."""

    def __init__(self, child: Expression, key):
        self.children = (child,)
        self.key = key

    def map_children(self, fn):
        return GetItem(fn(self.children[0]), self.key)

    @property
    def name(self):
        return f"{self.children[0].name}[{self.key!r}]"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if isinstance(ct, T.ArrayType):
            return ct.element_type
        if isinstance(ct, T.MapType):
            return ct.value_type
        if isinstance(ct, T.StructType) and isinstance(self.key, str):
            return GetField(self.children[0], self.key).data_type(schema)
        raise AnalysisException(f"getItem on {ct} is not supported")

    def eval(self, ctx):
        raise AnalysisException(f"{self!r}" + _COMPLEX_EVAL_HINT)

    def __repr__(self):
        return f"{self.children[0]!r}[{self.key!r}]"


class ArrayGather(Expression):
    """1-based dynamic-position gather from an array plane; position 0 or
    out of bounds -> NULL.  The flat form MapGet(map_from_arrays(k, v), x)
    rewrites into (via array_position) — and a real dual-path eval, since
    it is what actually executes."""

    def __init__(self, arr: Expression, pos: Expression):
        self.children = (arr, pos)

    def map_children(self, fn):
        return ArrayGather(fn(self.children[0]), fn(self.children[1]))

    @property
    def name(self):
        return f"element_at({self.children[0].name}, {self.children[1].name})"

    def data_type(self, schema):
        ct = self.children[0].data_type(schema)
        if not isinstance(ct, T.ArrayType):
            raise AnalysisException(f"array gather expects an array, got {ct}")
        return ct.element_type

    def eval(self, ctx):
        xp = ctx.xp
        dt = self.children[0].data_type(ctx.batch.schema)
        v = self.children[0].eval(ctx)
        p = ctx.broadcast(self.children[1].eval(ctx))
        out_dt = self.data_type(ctx.batch.schema).np_dtype
        gathered, ok = _gather_1based_plane(
            xp, dt, v, p.data.astype(np.int64), ctx.capacity, out_dt)
        valid = and_valid(xp, and_valid(xp, v.valid, p.valid), ok)
        return ExprValue(gathered, valid, v.dictionary)

    def __repr__(self):
        return f"array_gather({self.children[0]!r}, {self.children[1]!r})"
