"""DataFrame: the user-facing lazy relational API.

The analog of ``sql/core/.../Dataset.scala`` (DataFrame = Dataset[Row]) with
pyspark's surface.  A DataFrame is (session, logical plan); every method
builds a new plan, and actions run it through QueryExecution
(``Dataset.withAction`` → ``QueryExecution`` in the reference).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple, Union

from .. import tracing
from .. import types as T
from ..aggregates import Avg, Count, CountStar, Max, Min, Sum
from ..columnar import ColumnBatch
from ..expressions import (
    Alias, AnalysisException, Col, Expression, IsNotNull, Literal,
)
from ..logicalutils import _SortOrderHandle
from . import logical as L
from .column import Column
from .row import Row

ColumnOrName = Union[Column, str]


def _to_expr(c: ColumnOrName) -> Expression:
    if isinstance(c, Column):
        return c._e
    if isinstance(c, str):
        return Col(c)
    if isinstance(c, Expression):
        return c
    raise TypeError(f"expected Column or str, got {type(c)}")


class DataFrame:
    def __init__(self, session, plan: L.LogicalPlan):
        self.session = session
        self._plan = plan
        self._cached: Optional[str] = None   # device-cache key
        #: the statement ``session.sql`` parsed this plan under; the first
        #: action takes it (0: the QueryExecution allots one)
        self._statement_id = 0

    def _take_statement(self) -> int:
        sid, self._statement_id = self._statement_id, 0
        return sid

    # -- metadata ---------------------------------------------------------
    @property
    def schema(self) -> T.StructType:
        return self._qe_analyzed().schema()

    def _qe_analyzed(self) -> L.LogicalPlan:
        from .analyzer import Analyzer
        with tracing.span("analyze"):
            return Analyzer(self.session.catalog).analyze(self._plan)

    @property
    def columns(self) -> List[str]:
        return self.schema.names

    @property
    def dtypes(self) -> List[Tuple[str, str]]:
        return [(f.name, f.dataType.simpleString()) for f in self.schema.fields]

    def printSchema(self) -> None:
        print("root")
        for f in self.schema.fields:
            print(f" |-- {f.name}: {f.dataType.simpleString()} "
                  f"(nullable = {str(f.nullable).lower()})")

    def explain(self, extended: bool = False) -> None:
        from .planner import QueryExecution
        qe = QueryExecution(self.session, self._plan)
        print(qe.explain_string() if extended else
              "== Physical Plan ==\n"
              + qe.planned_preview().physical.tree_string())

    def __getitem__(self, item) -> Column:
        if isinstance(item, str):
            return Column(Col(item))
        raise TypeError(item)

    def __getattr__(self, name: str) -> Column:
        if name.startswith("_"):
            raise AttributeError(name)
        if name in self.schema.names:
            return Column(Col(name))
        raise AttributeError(name)

    def alias(self, name: str) -> "DataFrame":
        return DataFrame(self.session, L.SubqueryAlias(name, self._plan))

    # -- transformations --------------------------------------------------
    def select(self, *cols: ColumnOrName) -> "DataFrame":
        if not cols:
            cols = ("*",)
        exprs: List[Expression] = []
        for c in cols:
            if isinstance(c, str) and c == "*":
                exprs += [Col(n) for n in self.schema.names]
            else:
                exprs.append(_to_expr(c))
        # explode()/posexplode() flows through the plain Project — the
        # analyzer's _rewrite_explode turns it into the Explode operator
        # (ONE rewrite shared with the SQL path)
        from ..expressions import ExplodeMarker

        def _has_marker(e):
            base = e.children[0] if isinstance(e, Alias) else e
            return isinstance(base, ExplodeMarker)
        if any(_has_marker(e) for e in exprs):
            return DataFrame(self.session, L.Project(exprs, self._plan))
        # select with aggregates and no grouping is a global aggregation
        # (Dataset.select's ungrouped-agg path): df.select(avg(x)) works;
        # mixing plain columns in raises like the reference does
        from .analyzer import build_aggregate, contains_aggregate
        if any(contains_aggregate(e) for e in exprs):
            for e in exprs:
                base = e.children[0] if isinstance(e, Alias) else e
                if not contains_aggregate(e) \
                        and not isinstance(base, Literal):
                    raise AnalysisException(
                        f"expression {e!r} is neither an aggregate nor "
                        "grouped; add it to groupBy() or aggregate it")
            return DataFrame(self.session,
                             build_aggregate([], exprs, self._plan))
        return DataFrame(self.session, L.Project(exprs, self._plan))

    def selectExpr(self, *exprs: str) -> "DataFrame":
        from .parser import parse_expression
        return self.select(*[Column(parse_expression(e)) for e in exprs])

    def filter(self, condition: Union[Column, str]) -> "DataFrame":
        if isinstance(condition, str):
            from .parser import parse_expression
            cond = parse_expression(condition)
        else:
            cond = condition._e
        return DataFrame(self.session, L.Filter(cond, self._plan))

    where = filter

    def withColumn(self, name: str, col: Column) -> "DataFrame":
        exprs: List[Expression] = []
        replaced = False
        for n in self.schema.names:
            if n == name:
                exprs.append(Alias(col._e, name))
                replaced = True
            else:
                exprs.append(Col(n))
        if not replaced:
            exprs.append(Alias(col._e, name))
        return DataFrame(self.session, L.Project(exprs, self._plan))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = [Alias(Col(n), new) if n == old else Col(n)
                 for n in self.schema.names]
        return DataFrame(self.session, L.Project(exprs, self._plan))

    def drop(self, *names: str) -> "DataFrame":
        keep = [Col(n) for n in self.schema.names if n not in names]
        return DataFrame(self.session, L.Project(keep, self._plan))

    def groupBy(self, *cols: ColumnOrName) -> "GroupedData":
        return GroupedData(self, [_to_expr(c) for c in cols])

    groupby = groupBy

    def agg(self, *cols: Column) -> "DataFrame":
        return self.groupBy().agg(*cols)

    def orderBy(self, *cols, ascending: Optional[Any] = None) -> "DataFrame":
        orders: List[L.SortOrder] = []
        for i, c in enumerate(cols):
            if isinstance(c, _SortOrderHandle):
                orders.append(L.SortOrder(c.expr, c.ascending, c.nulls_first))
            else:
                asc = True
                if ascending is not None:
                    asc = ascending[i] if isinstance(ascending, (list, tuple)) \
                        else bool(ascending)
                orders.append(L.SortOrder(_to_expr(c), asc))
        return DataFrame(self.session, L.Sort(orders, self._plan))

    sort = orderBy

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, L.Limit(n, self._plan))

    def withWatermark(self, eventTime: str, delayThreshold: str) -> "DataFrame":
        """Event-time watermark (`Dataset.withWatermark`); no-op in batch."""
        from ..expressions import AnalysisException, parse_duration
        if eventTime not in self.schema.names:
            raise AnalysisException(
                f"watermark column {eventTime!r} not found among "
                f"{self.schema.names}")
        delay = parse_duration(delayThreshold)
        if delay < 0:
            raise AnalysisException(
                f"watermark delay must be >= 0, got {delayThreshold!r}")
        return DataFrame(self.session, L.EventTimeWatermark(
            eventTime, delay, self._plan))

    def distinct(self) -> "DataFrame":
        return DataFrame(self.session, L.Distinct(self._plan))

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        if not subset:
            return self.distinct()
        # keep first row per subset-key: group by subset, first() the rest
        from ..aggregates import First
        keys = [Col(n) for n in subset]
        aggs = [(First(Col(n)), n) for n in self.schema.names if n not in subset]
        out_order = [n for n in self.schema.names]
        agg_plan = L.Aggregate(keys, aggs, self._plan)
        return DataFrame(self.session,
                         L.Project([Col(n) for n in out_order], agg_plan))

    drop_duplicates = dropDuplicates

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, L.Union([self._plan, other._plan]))

    unionAll = union

    def unionByName(self, other: "DataFrame") -> "DataFrame":
        reordered = other.select(*[Col(n) for n in self.schema.names])
        return self.union(reordered)

    def join(self, other: "DataFrame",
             on: Union[str, List[str], Column, None] = None,
             how: str = "inner") -> "DataFrame":
        using = None
        cond = None
        if isinstance(on, str):
            using = [on]
        elif isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            using = list(on)
        elif isinstance(on, Column):
            cond = on._e
        elif on is None:
            how = "cross" if how == "inner" else how
        return DataFrame(self.session,
                         L.Join(self._plan, other._plan, how, cond, using))

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session,
                         L.Join(self._plan, other._plan, "cross", None, None))

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        return DataFrame(self.session, L.Sample(fraction, seed, self._plan))

    def dropna(self, how: str = "any", subset: Optional[List[str]] = None
               ) -> "DataFrame":
        names = subset or self.schema.names
        preds = [IsNotNull(Col(n)) for n in names]
        if how == "any":
            cond = preds[0]
            for p in preds[1:]:
                from ..expressions import And
                cond = And(cond, p)
        else:
            from ..expressions import Or
            cond = preds[0]
            for p in preds[1:]:
                cond = Or(cond, p)
        return DataFrame(self.session, L.Filter(cond, self._plan))

    na = property(lambda self: _NAFunctions(self))

    def fillna(self, value: Any, subset: Optional[List[str]] = None) -> "DataFrame":
        from ..expressions import Coalesce
        names = subset or self.schema.names
        schema = self.schema
        exprs = []
        for f in schema.fields:
            if f.name in names and _fill_compatible(f.dataType, value):
                exprs.append(Alias(Coalesce(Col(f.name), Literal(value)), f.name))
            else:
                exprs.append(Col(f.name))
        return DataFrame(self.session, L.Project(exprs, self._plan))

    def repartition(self, num: int, *cols) -> "DataFrame":
        # local single-stage execution: logical no-op recorded for the
        # distributed planner (parallel/ uses it to pick shard counts)
        return self

    def coalesce(self, num: int) -> "DataFrame":
        return self

    def checkpoint(self, eager: bool = True) -> "DataFrame":
        """Truncate lineage by materializing to reliable storage
        (``Dataset.checkpoint`` / ReliableRDDCheckpointData): parquet under
        ``spark.tpu.checkpoint.dir`` (falls back to the warehouse dir);
        the result reads back from the files, so a driver restart can
        resume from them.  ``eager=False`` defers the write to the first
        action, matching the reference's lazy-checkpoint contract."""
        import os
        import uuid
        from .. import config as C
        base = self.session.conf.get("spark.tpu.checkpoint.dir", None) or \
            os.path.join(self.session.conf.get(C.WAREHOUSE_DIR),
                         "_checkpoints")
        path = os.path.join(base, uuid.uuid4().hex[:12])
        if eager:
            self.write.parquet(path)
            return self.session.read.parquet(path)
        return DataFrame(self.session,
                         L.LazyCheckpoint(self._plan, path))

    def localCheckpoint(self, eager: bool = True) -> "DataFrame":
        return self.checkpoint(eager)

    def cache(self, level: Optional[str] = None) -> "DataFrame":
        """Materialize and register in the session's device cache manager
        (``CacheManager.cacheQuery``); other queries containing this exact
        subtree read the cached batch instead of recomputing.  ``level`` is
        a ``memory.StorageLevel`` (default DEVICE; demotes under HBM
        pressure)."""
        from ..memory import StorageLevel
        from .planner import QueryExecution
        # key on the SUBSTITUTED analyzed plan: _use_cached_data rewrites
        # bottom-up, so a cache-on-cache plan must be keyed the way other
        # queries' rewritten trees will actually look
        qe = QueryExecution(self.session, self._plan)
        key = L.plan_cache_key(qe.analyzed)
        batch = qe.execute()
        self.session._cache.put(key, batch, level or StorageLevel.DEVICE)
        self._cached = key
        return self

    def persist(self, level: Optional[str] = None) -> "DataFrame":
        return self.cache(level)

    def unpersist(self) -> "DataFrame":
        if self._cached is not None:
            self.session._cache.remove(self._cached)
            self._cached = None
        return self

    # -- actions ----------------------------------------------------------
    def _execute(self) -> ColumnBatch:
        if self._cached is not None:
            hit = self.session._cache.get(self._cached)
            if hit is not None:
                return hit
        from .planner import QueryExecution
        with tracing.statement(self._take_statement()):
            return QueryExecution(self.session, self._plan).execute()

    # -- complex-type output (maps/structs) -------------------------------
    def _flatten_complex(self):
        """(flat DataFrame, assembly spec | None).

        Top-level map/struct output columns cannot materialize on device
        (object-layer contract, docs/DECISIONS.md): they are replaced by
        their PLANE columns (map → keys/values arrays via the pair-of-
        planes layout; struct → one column per field) for execution, and
        the spec rebuilds Python dicts / Rows per row at collect."""
        try:
            # API-built plans answer schema() directly (fast path, no
            # second analysis); raw SQL plans hold unresolved relations
            # whose schema() raises — analyze only then
            try:
                schema = self._plan.schema()
            except Exception:
                schema = self._qe_analyzed().schema()
        except Exception:
            return self, None
        if not any(isinstance(f.dataType, (T.MapType, T.StructType))
                   for f in schema.fields):
            return self, None
        from ..expressions import GetField, MapKeys, MapValues
        exprs: List[Any] = []
        spec: List[tuple] = []

        def flatten(expr, dtype, prefix, name):
            """Recursive spec node: structs flatten per field, maps emit
            their two planes; complex-typed map keys/values have no plane
            representation — loud error, not silent wrongness."""
            if isinstance(dtype, T.MapType):
                if isinstance(dtype.key_type, (T.MapType, T.StructType)) \
                        or isinstance(dtype.value_type,
                                      (T.MapType, T.StructType)):
                    raise AnalysisException(
                        "maps with map/struct keys or values cannot be "
                        "collected (no plane layout — docs/DECISIONS.md)")
                ki, vi = len(exprs), len(exprs) + 1
                exprs.append(Alias(MapKeys(expr), f"{prefix}__mkeys"))
                exprs.append(Alias(MapValues(expr), f"{prefix}__mvals"))
                return ("map", ki, vi, name)
            if isinstance(dtype, T.StructType):
                subs = [flatten(GetField(expr, sf.name), sf.dataType,
                                f"{prefix}__{sf.name}", sf.name)
                        for sf in dtype.fields]
                return ("struct", subs, name)
            idx = len(exprs)
            exprs.append(Alias(expr, f"{prefix}__v")
                         if prefix.startswith("__") else expr)
            return ("plain", idx, name)

        for f in schema.fields:
            if isinstance(f.dataType, (T.MapType, T.StructType)):
                spec.append(flatten(Col(f.name), f.dataType,
                                    f"__{f.name}", f.name))
            else:
                spec.append(("plain", len(exprs), f.name))
                exprs.append(Col(f.name))
        flat = DataFrame(self.session, L.Project(exprs, self._plan))
        return flat, spec

    @staticmethod
    def _assemble_rows(rows, spec) -> List[Row]:
        def build(s, r):
            if s[0] == "plain":
                return r[s[1]]
            if s[0] == "map":
                ks, vs = r[s[1]], r[s[2]]
                if ks is None:
                    return None
                # reversed so the FIRST occurrence of a duplicate key wins
                # — consistent with element_at's GetMapValue scan order
                return dict(zip(reversed(ks), reversed(vs or [])))
            return Row([build(sub, r) for sub in s[1]],
                       [sub[-1] for sub in s[1]])

        names = [s[-1] for s in spec]
        return [Row([build(s, r) for s in spec], names) for r in rows]

    def collect(self) -> List[Row]:
        with tracing.statement(self._take_statement()):
            flat, spec = self._flatten_complex()
            batch = flat._execute()
            with tracing.span("collect.rows") as sp:
                if spec is None:
                    rows = [Row(r, batch.names) for r in batch.to_pylist()]
                else:
                    rows = self._assemble_rows(batch.to_pylist(), spec)
                sp.attrs["rows"] = len(rows)
            return rows

    def count(self) -> int:
        agg = L.Aggregate([], [(CountStar(), "count")], self._plan)
        from .planner import QueryExecution
        out = QueryExecution(self.session, agg).execute()
        return int(out.to_pylist()[0][0])

    def first(self) -> Optional[Row]:
        rows = self.limit(1).collect()
        return rows[0] if rows else None

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        return rows[0] if n == 1 and rows else rows

    def take(self, n: int) -> List[Row]:
        return self.limit(n).collect()

    def toPandas(self):
        flat, spec = self._flatten_complex()
        if spec is None:
            return flat._execute().to_pandas()
        import pandas as pd
        rows = self._assemble_rows(flat._execute().to_pylist(), spec)
        return pd.DataFrame([list(r) for r in rows],
                            columns=[s[-1] for s in spec])

    def toLocalIterator(self):
        return iter(self.collect())

    def show(self, n: int = 20, truncate: bool = True) -> None:
        flat, spec = self.limit(n)._flatten_complex()
        batch = flat._execute()
        if spec is None:
            names = batch.names
            rows = batch.to_pylist()
        else:
            names = [s[-1] for s in spec]
            rows = [list(r) for r in
                    self._assemble_rows(batch.to_pylist(), spec)]
        cells = [[_fmt(v, truncate) for v in r] for r in rows]
        widths = [max([len(nm)] + [len(c[i]) for c in cells])
                  for i, nm in enumerate(names)]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        print(sep)
        print("|" + "|".join(f" {nm:<{w}} " for nm, w in zip(names, widths)) + "|")
        print(sep)
        for c in cells:
            print("|" + "|".join(f" {v:<{w}} " for v, w in zip(c, widths)) + "|")
        print(sep)

    def createOrReplaceTempView(self, name: str) -> None:
        self.session.catalog.register(name, self._plan)

    createTempView = createOrReplaceTempView

    @property
    def write(self):
        from ..io import DataFrameWriter
        return DataFrameWriter(self)

    @property
    def writeStream(self):
        from ..streaming.api import DataStreamWriter
        return DataStreamWriter(self)

    @property
    def isStreaming(self) -> bool:
        from ..streaming.core import StreamingRelation
        found = []

        def walk(n):
            if isinstance(n, StreamingRelation):
                found.append(n)
            for c in n.children:
                walk(c)
        walk(self._plan)
        return bool(found)

    @property
    def rdd(self):
        rows = self.collect()
        return self.session.sparkContext.parallelize(rows)

    def __repr__(self):
        cols = ", ".join(f"{f.name}: {f.dataType.simpleString()}"
                         for f in self.schema.fields)
        return f"DataFrame[{cols}]"


def _fmt(v, truncate) -> str:
    if v is None:
        return "null"
    if isinstance(v, bool):
        return str(v).lower()
    if isinstance(v, float):
        s = f"{v}"
    else:
        s = str(v)
    if truncate and len(s) > 20:
        s = s[:17] + "..."
    return s


def _fill_compatible(dt: T.DataType, value: Any) -> bool:
    if isinstance(value, bool):
        return isinstance(dt, T.BooleanType)
    if isinstance(value, (int, float)):
        return dt.is_numeric
    if isinstance(value, str):
        return dt.is_string
    return False


class _NAFunctions:
    def __init__(self, df: DataFrame):
        self._df = df

    def drop(self, how: str = "any", subset=None) -> DataFrame:
        return self._df.dropna(how, subset)

    def fill(self, value, subset=None) -> DataFrame:
        return self._df.fillna(value, subset)


class GroupedData:
    """Result of groupBy() (``RelationalGroupedDataset`` analog)."""

    def __init__(self, df: DataFrame, keys: List[Expression]):
        self._df = df
        self._keys = keys

    def agg(self, *cols, **named) -> DataFrame:
        from .analyzer import build_aggregate
        exprs: List[Expression] = []
        if len(cols) == 1 and isinstance(cols[0], dict):
            for name, fn in cols[0].items():
                exprs.append(Alias(_AGG_BY_NAME[fn](Col(name)),
                                   f"{fn}({name})"))
        else:
            exprs = [c._e if isinstance(c, Column) else c for c in cols]
        for out_name, c in named.items():
            exprs.append(Alias(c._e if isinstance(c, Column) else c, out_name))
        plan = build_aggregate(self._keys, exprs, self._df._plan)
        return DataFrame(self._df.session, plan)

    def flatMapGroupsWithState(self, func, outputStructType,
                               outputMode: str = "append",
                               timeoutConf: str = "NoTimeout") -> DataFrame:
        """Arbitrary stateful per-group processing
        (``flatMapGroupsWithState`` / pyspark's applyInPandasWithState).

        ``func(key_tuple, rows, state)`` → iterable of output tuples.  On a
        stream, ``state`` persists across micro-batches (versioned state
        store) and, with ``timeoutConf='EventTimeTimeout'``, times out by
        watermark; in batch mode each group sees one fresh state."""
        if timeoutConf not in ("NoTimeout", "EventTimeTimeout"):
            raise AnalysisException(
                f"unsupported timeoutConf {timeoutConf!r}; processing-time "
                "timeouts do not replay deterministically — use "
                "EventTimeTimeout")
        if outputMode not in ("append", "update"):
            raise AnalysisException(
                "flatMapGroupsWithState supports append/update output modes")
        key_names = []
        for k in self._keys:
            base = k.children[0] if isinstance(k, Alias) else k
            if not isinstance(base, Col):
                raise AnalysisException(
                    "flatMapGroupsWithState grouping keys must be plain "
                    "columns")
            key_names.append(k.name)
        return DataFrame(self._df.session, L.FlatMapGroupsWithState(
            func, key_names, outputStructType, outputMode, timeoutConf,
            self._df._plan))

    applyInPandasWithState = flatMapGroupsWithState

    def count(self) -> DataFrame:
        return self.agg(Column(Alias(CountStar(), "count")))

    def sum(self, *names: str) -> DataFrame:
        return self.agg(*[Column(Alias(Sum(Col(n)), f"sum({n})")) for n in names])

    def avg(self, *names: str) -> DataFrame:
        return self.agg(*[Column(Alias(Avg(Col(n)), f"avg({n})")) for n in names])

    mean = avg

    def min(self, *names: str) -> DataFrame:
        return self.agg(*[Column(Alias(Min(Col(n)), f"min({n})")) for n in names])

    def max(self, *names: str) -> DataFrame:
        return self.agg(*[Column(Alias(Max(Col(n)), f"max({n})")) for n in names])


_AGG_BY_NAME = {
    "sum": Sum, "count": Count, "avg": Avg, "mean": Avg, "min": Min, "max": Max,
}
