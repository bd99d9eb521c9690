"""SparkSession: the entry point (``sql/SparkSession.scala:77`` analog).

One process = driver + executor: the SPMD mesh replaces the task-scheduler
split, so the session directly owns the conf, catalog, jit cache, and (in
distributed mode) the device mesh (see ``spark_tpu.parallel``).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Union


from .. import config as C
from .. import tracing
from .. import types as T
from ..columnar import ColumnBatch
from ..expressions import AnalysisException
from . import logical as L
from .dataframe import DataFrame


class QueryCancelled(Exception):
    """Raised inside a streamed execution loop after
    ``session.cancelAllQueries()`` — the cooperative analog of the
    reference's ``SparkContext.cancelJobGroup`` task interruption."""


class _ListenerManager:
    """Query-event fan-out (`LiveListenerBus` in miniature): listeners are
    callables receiving event dicts; failures are swallowed."""

    def __init__(self):
        self._listeners: List[Any] = []

    def register(self, fn) -> None:
        self._listeners.append(fn)

    def unregister(self, fn) -> None:
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass


class Catalog:
    """Temp views + functions + PERSISTENT databases/tables
    (``SessionCatalog`` + ``InMemoryCatalog``): the filesystem IS the
    external catalog — ``<warehouse>/<db>.db/<table>/`` holds the data
    files plus a ``_meta.json`` (format/schema/options), so there is no
    separate metastore process to run or corrupt."""

    def __init__(self, session=None):
        self._session = session
        self._views: Dict[str, L.LogicalPlan] = {}
        self._functions: Dict[str, Any] = {}
        self.current_database = "default"

    # -- functions ---------------------------------------------------------
    def register_function(self, name: str, wrapper) -> None:
        self._functions[name.lower()] = wrapper

    def lookup_function(self, name: str):
        return self._functions.get(name.lower())

    def listFunctions(self) -> List[str]:
        return sorted(self._functions)

    # -- temp views ----------------------------------------------------------
    def register(self, name: str, plan: L.LogicalPlan) -> None:
        self._views[name.lower()] = plan

    def drop(self, name: str) -> bool:
        return self._views.pop(name.lower(), None) is not None

    dropTempView = drop

    # -- persistent layer ---------------------------------------------------
    def _warehouse(self) -> str:
        if self._session is not None:
            return self._session.conf.get(C.WAREHOUSE_DIR)
        return C.WAREHOUSE_DIR.default

    def _db_dir(self, db: str) -> str:
        import os
        wh = self._warehouse()
        return wh if db == "default" else os.path.join(wh, f"{db}.db")

    def _split(self, name: str):
        parts = name.split(".")
        if len(parts) == 2:
            return parts[0].lower(), parts[1].lower()
        return self.current_database, parts[0].lower()

    def table_path(self, name: str) -> str:
        import os
        db, tbl = self._split(name)
        return os.path.join(self._db_dir(db), tbl)

    def create_database(self, name: str, if_not_exists: bool = False) -> None:
        import os
        if name.lower() == "default":
            if if_not_exists:
                return
            raise AnalysisException("database default already exists")
        d = self._db_dir(name.lower())
        if os.path.isdir(d):
            if if_not_exists:
                return
            raise AnalysisException(f"database {name} already exists")
        os.makedirs(d, exist_ok=True)

    def drop_database(self, name: str, if_exists: bool = False) -> None:
        import os
        import shutil
        if name.lower() == "default":
            raise AnalysisException("cannot drop the default database")
        d = self._db_dir(name.lower())
        if not os.path.isdir(d):
            if if_exists:
                return
            raise AnalysisException(f"database not found: {name}")
        shutil.rmtree(d)

    def list_databases(self) -> List[str]:
        import os
        wh = self._warehouse()
        out = ["default"]
        if os.path.isdir(wh):
            out += sorted(f[:-3] for f in os.listdir(wh)
                          if f.endswith(".db")
                          and os.path.isdir(os.path.join(wh, f)))
        return out

    listDatabases = list_databases

    def setCurrentDatabase(self, name: str) -> None:
        if name.lower() not in self.list_databases():
            raise AnalysisException(f"database not found: {name}")
        self.current_database = name.lower()

    def save_table(self, name: str, df, fmt: str = "parquet",
                   mode: str = "error", options: Optional[dict] = None,
                   partition_by: Optional[List[str]] = None) -> None:
        """CTAS / saveAsTable: write data files + _meta.json."""
        import json
        import os
        path = self.table_path(name)
        from ..io import DataFrameWriter
        w = DataFrameWriter(df).format(fmt).mode(mode)
        if partition_by:
            w = w.partitionBy(*partition_by)
        for k, v in (options or {}).items():
            w = w.option(k, v)
        w.save(path)
        meta = {"format": fmt, "options": options or {},
                "schema": [[f.name, f.dataType.simpleString()]
                           for f in df.schema.fields]}
        with open(os.path.join(path, "_meta.json"), "w") as f:
            json.dump(meta, f)

    def save_table_stats(self, name: str, stats: dict) -> bool:
        """Persist ANALYZE TABLE results into the table's _meta.json.
        Returns False when `name` is not a persistent table (temp views
        keep session-only stats)."""
        import json
        import os
        path = self.table_path(name)
        meta_p = os.path.join(path, "_meta.json")
        if not os.path.isfile(meta_p):
            return False
        with open(meta_p) as f:
            meta = json.load(f)
        meta["stats"] = stats
        with open(meta_p, "w") as f:
            json.dump(meta, f, default=str)
        return True

    def create_empty_table(self, name: str, schema: T.StructType,
                           fmt: str = "parquet") -> None:
        import json
        import os
        path = self.table_path(name)
        if os.path.isdir(path):
            raise AnalysisException(f"table {name} already exists")
        os.makedirs(path)
        meta = {"format": fmt, "options": {},
                "schema": [[f.name, f.dataType.simpleString()]
                           for f in schema.fields]}
        with open(os.path.join(path, "_meta.json"), "w") as f:
            json.dump(meta, f)

    def drop_table(self, name: str, if_exists: bool = False) -> None:
        import os
        import shutil
        path = self.table_path(name)
        if not os.path.isdir(path):
            if if_exists:
                return
            raise AnalysisException(f"table not found: {name}")
        shutil.rmtree(path)

    def _persistent_plan(self, name: str) -> Optional[L.LogicalPlan]:
        import glob as _glob
        import json
        import os
        path = self.table_path(name)
        meta_p = os.path.join(path, "_meta.json")
        if not os.path.isfile(meta_p):
            return None
        with open(meta_p) as f:
            meta = json.load(f)
        schema = T.StructType([
            T.StructField(n, T.type_for_name(t)) for n, t in meta["schema"]])
        pats = {"parquet": "*.parquet", "csv": "*.csv", "json": "*.json",
                "text": "*.txt"}
        fmt = meta["format"]
        has_data = _glob.glob(os.path.join(
            path, "**", pats.get(fmt, "*"), ), recursive=True)
        has_data = [p for p in has_data if not os.path.basename(p).startswith(
            ("_", "."))]
        if not has_data:
            return L.LocalRelation(ColumnBatch.empty(schema))
        rel = L.FileRelation(fmt, [path], schema,
                             dict(meta.get("options") or {}))
        if meta.get("stats"):
            # ANALYZE TABLE results persisted with the table: re-register
            # ONLY if the files are unchanged since ANALYZE (the stats
            # carry the files+mtimes key they were gathered under; an
            # append/rewrite makes them stale and they are dropped)
            from .. import io as _tio
            if meta["stats"].get("key") == _tio.stats_key_token(rel):
                _tio.register_analyzed_stats(rel, meta["stats"])
        return rel

    # -- unified lookup -----------------------------------------------------
    def lookup(self, name: str) -> L.LogicalPlan:
        key = name.lower()
        if key in self._views:
            return self._views[key]
        plan = self._persistent_plan(name)
        if plan is not None:
            return plan
        plan = self._file_format_plan(name)
        if plan is not None:
            return plan
        raise AnalysisException(f"Table or view not found: {name}")

    def _file_format_plan(self, name: str) -> Optional[L.LogicalPlan]:
        """``SELECT * FROM parquet.`/path``` — querying a file directly by
        format-qualified path (`rules/ResolveSQLOnFile.scala:44` analog).
        The parser delivers the identifier as ``<format>.<path>``."""
        import os
        fmt, dot, path = name.partition(".")
        fmt = fmt.lower()
        if not dot or fmt not in ("parquet", "orc", "csv", "json", "text"):
            return None
        if not os.path.exists(path):
            return None
        from ..io import DataFrameReader
        return DataFrameReader(self._session).format(fmt).load(path)._plan

    def list_persistent_tables(self, db: Optional[str] = None) -> List[str]:
        import os
        d = self._db_dir((db or self.current_database).lower())
        if not os.path.isdir(d):
            return []
        return sorted(
            t for t in os.listdir(d)
            if os.path.isfile(os.path.join(d, t, "_meta.json")))

    def listTables(self) -> List[str]:
        return sorted(set(self._views) | set(self.list_persistent_tables()))


class RuntimeConfig:
    def __init__(self, conf: C.Conf):
        self._conf = conf

    def set(self, key: str, value: Any) -> None:
        self._conf.set(key, value)

    def get(self, key: str, default: Any = None) -> Any:
        return self._conf.get(key, default)

    def unset(self, key: str) -> None:
        self._conf.unset(key)


class Builder:
    def __init__(self):
        self._options: Dict[str, Any] = {}

    def appName(self, name: str) -> "Builder":
        self._options["spark.app.name"] = name
        return self

    def master(self, master: str) -> "Builder":
        self._options["spark.master"] = master
        return self

    def config(self, key: str, value: Any = None) -> "Builder":
        self._options[key] = value
        return self

    def enableHiveSupport(self) -> "Builder":
        return self

    def getOrCreate(self) -> "SparkSession":
        import os
        opts = dict(self._options)
        if SparkSession._active is None:
            # --conf pairs handed down by bin/spark-tpu-launch ride the
            # environment (the launcher must not build a session itself:
            # backend init would break the worker's init_cluster).  They
            # SEED the session only — re-applying them on later
            # getOrCreate() calls would silently revert runtime
            # conf.set overrides.
            launch_conf = os.environ.get("SPARK_TPU_LAUNCH_CONF")
            if launch_conf:
                for pair in launch_conf.split("\x1f"):
                    k, _, v = pair.partition("=")
                    opts.setdefault(k, v)
            SparkSession._active = SparkSession(C.Conf(opts))
        else:
            for k, v in opts.items():
                SparkSession._active.conf.set(k, v)
        return SparkSession._active


class SparkSession:
    _active: Optional["SparkSession"] = None
    _tls = threading.local()         # per-thread executing session

    class _BuilderAccessor:
        def __get__(self, obj, objtype=None) -> Builder:
            return Builder()

    builder = _BuilderAccessor()

    def __init__(self, conf: Optional[C.Conf] = None):
        self.conf_obj = conf or C.Conf()
        self.conf = self.conf_obj  # Conf has get/set directly
        self.catalog = Catalog(self)
        self._listener_manager = _ListenerManager()
        self._last_qe = None              # most recent QueryExecution
        self._jit_cache: Dict[str, Any] = {}
        self._jit_notes: Dict[str, dict] = {}     # tracing.note, by key
        # learned capacity factors from adaptive overflow retries, keyed by
        # the pre-adaptation plan key — later executions of the same query
        # shape start at the factor that worked (no repeat overflow+recompile)
        self._adapted_factors: Dict[str, Any] = {}
        self._sc = None
        from ..memory import DeviceCacheManager, MemoryManager
        self._memory = MemoryManager(self.conf_obj)
        self._cache = DeviceCacheManager(self._memory, self.conf_obj)
        self._query_count = 0
        from ..metrics import MetricsSystem, default_sources
        self._metrics_system = MetricsSystem()
        for src in default_sources(self):
            self._metrics_system.register_source(src)
        if self.conf_obj.get(C.DEBUG_NANS):
            import jax
            jax.config.update("jax_debug_nans", True)
        # pyspark semantics: constructing a session makes it the active one
        SparkSession._active = self

    @property
    def memoryManager(self):
        """HBM execution/storage accounting (UnifiedMemoryManager analog)."""
        return self._memory

    @property
    def metricsSystem(self):
        """Process-gauge sources (`metrics/MetricsSystem.scala` analog);
        `snapshots()` reads them on demand."""
        return self._metrics_system

    @property
    def cacheManager(self):
        """Device cache of materialized relations (CacheManager analog)."""
        return self._cache

    @property
    def udf(self):
        """`spark.udf.register(name, fn, returnType)` (UDFRegistration)."""
        from .udf import UDFRegistration
        return UDFRegistration(self)

    # -- observability (LiveListenerBus + EventLoggingListener analogs) ---
    @property
    def listenerManager(self):
        return self._listener_manager

    def _post_event(self, event: Dict[str, Any]) -> None:
        for fn in list(self._listener_manager._listeners):
            try:
                fn(event)
            except Exception:
                pass                       # listeners never fail the query
        log_dir = self.conf.get(C.EVENT_LOG_DIR)
        if log_dir:
            import json
            import os
            os.makedirs(log_dir, exist_ok=True)
            path = os.path.join(log_dir, "eventlog.jsonl")
            with open(path, "a") as f:
                f.write(json.dumps(event, default=str) + "\n")

    @classmethod
    def getActiveSession(cls) -> Optional["SparkSession"]:
        # the EXECUTING session on this thread wins (set per query by
        # QueryExecution): with the server's worker pool running DIFFERENT
        # sessions concurrently, a process-global here would hand kernel
        # conf reads (collect_list cap, multibatch fallback) to whichever
        # session started a query last on ANY thread
        tls = getattr(cls._tls, "active", None)
        return tls if tls is not None else cls._active

    @classmethod
    def _set_thread_active(cls, session) -> None:
        cls._tls.active = session

    # -- cooperative statement cancellation (cancelJobGroup analog) ------
    #
    # XLA programs are uninterruptible once dispatched, exactly like a
    # running Spark task; cancellation lands at the same granularity the
    # reference's does — between units of scheduled work.  Long queries
    # are streamed (multibatch / stage runner), and those loops call
    # raise_if_cancelled() between batches.
    def cancelAllQueries(self) -> None:
        self._cancel_requested = True

    def clear_cancel(self) -> None:
        self._cancel_requested = False

    def raise_if_cancelled(self) -> None:
        if getattr(self, "_cancel_requested", False):
            raise QueryCancelled("query cancelled by user request")

    @property
    def sparkContext(self):
        if self._sc is None:
            from ..rdd.context import SparkContext
            self._sc = SparkContext(conf=self.conf_obj, session=self)
        return self._sc

    @property
    def version(self) -> str:
        from .. import __version__
        return __version__

    def stop(self) -> None:
        SparkSession._active = None
        self._jit_cache.clear()
        self._jit_notes.clear()
        self._adapted_factors.clear()
        self._cache.clear()

    # ------------------------------------------------------------------
    def range(self, start: int, end: Optional[int] = None, step: int = 1
              ) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.RangeRelation(start, end, step))

    def createDataFrame(self, data, schema: Union[None, List[str], T.StructType] = None,
                        ) -> DataFrame:
        """Rows (list of tuples/dicts/Rows), pandas DataFrame, or dict of
        columns → DataFrame (``SparkSession.createDataFrame`` analog)."""
        import pandas as pd

        struct: Optional[T.StructType] = None
        names: Optional[List[str]] = None
        if isinstance(schema, T.StructType):
            struct = schema
            names = schema.names
        elif isinstance(schema, (list, tuple)):
            names = list(schema)

        if isinstance(data, pd.DataFrame):
            batch = ColumnBatch.from_pandas(data)
            if names:
                batch.names = list(names)
            return DataFrame(self, L.LocalRelation(batch))

        if isinstance(data, dict):
            batch = ColumnBatch.from_arrays(data, schema=struct)
            return DataFrame(self, L.LocalRelation(batch))

        rows = list(data)
        if not rows:
            if struct is None:
                raise AnalysisException("cannot infer schema from empty data")
            return DataFrame(self, L.LocalRelation(ColumnBatch.empty(struct)))

        first = rows[0]
        if isinstance(first, dict):
            names = names or list(first.keys())
            cols = {n: [r.get(n) for r in rows] for n in names}
        elif hasattr(first, "__fields__"):
            names = names or list(first.__fields__)
            cols = {n: [r[i] for r in rows] for i, n in enumerate(names)}
        elif isinstance(first, (tuple, list)):
            names = names or [f"_{i + 1}" for i in range(len(first))]
            cols = {n: [r[i] for r in rows] for i, n in enumerate(names)}
        else:  # scalars → single column
            names = names or ["value"]
            cols = {names[0]: rows}
        batch = ColumnBatch.from_arrays(cols, schema=struct)
        return DataFrame(self, L.LocalRelation(batch))

    def sql(self, query: str) -> DataFrame:
        from . import parser as P
        # where a statement first enters the program: its id goes with
        # the DataFrame to the action that runs it
        with tracing.statement() as sid:
            with tracing.span("parse"):
                st = P.parse_statement(query)
            if not isinstance(st, P.Command):
                df = DataFrame(self, st)
                df._statement_id = sid
                return df
            return self._run_command(st)

    @staticmethod
    def _unwrap_aliases(node):
        while isinstance(node, L.SubqueryAlias):
            node = node.children[0]
        return node

    def _analyze_table(self, cmd, string_df) -> DataFrame:
        """ANALYZE TABLE … COMPUTE STATISTICS [FOR COLUMNS …]: gather
        row count and per-column min/max/null_count/NDV through the
        engine's own (streamed, if oversized) scan, register them for
        the CBO, and persist them with catalog tables.  The analog of
        `AnalyzeTableCommand` / `AnalyzeColumnCommand` — the reference
        stores these in the metastore; here they complete the stats
        story for formats without free parquet footers (csv/json/orc/
        text/jdbc)."""
        from .. import io as tio
        from . import functions as F
        df = self.table(cmd.name)
        node = self._unwrap_aliases(self.catalog.lookup(cmd.name))
        if not isinstance(node, L.FileRelation):
            raise AnalysisException(
                f"ANALYZE TABLE {cmd.name}: only file- or jdbc-backed "
                "tables/views carry statistics (views over computed "
                "plans re-derive them at query time)")
        rows = df.count()
        stats: dict = {"rows": int(rows), "columns": {},
                       "key": tio.stats_key_token(node)}
        if cmd.columns is None:
            # rows-only refresh PRESERVES previously gathered column
            # stats (the reference's AnalyzeTableCommand does the same)
            prev = tio.analyzed_stats(node)
            if prev:
                stats["columns"] = prev.get("columns", {})
        if cmd.columns is not None:
            names = [f.name for f in node.schema().fields]
            selected = names if cmd.columns == [] else list(cmd.columns)
            aggs = []
            for c in selected:
                if c not in names:
                    raise AnalysisException(
                        f"ANALYZE TABLE: no such column {c!r}")
                aggs += [F.min(c).alias(f"__mn_{c}"),
                         F.max(c).alias(f"__mx_{c}"),
                         F.count(c).alias(f"__ct_{c}")]
            row = df.agg(*aggs).collect()[0]
            # NDV separately per column: one aggregate may carry only
            # one distinct column (engine limitation; the reference's
            # AnalyzeColumnCommand likewise scans per column set)
            ndvs = {}
            for c in selected:
                ndvs[c] = float(df.agg(
                    F.approx_count_distinct(c).alias("nd")).collect()[0]["nd"])

            def plain(v):
                # only JSON-native types survive: stringified timestamps/
                # decimals would change type across a persist/reload and
                # silently alter selectivity estimation between sessions
                v = v.item() if hasattr(v, "item") else v
                return v if isinstance(v, (int, float, str, bool)) \
                    or v is None else None

            for c in selected:
                stats["columns"][c] = {
                    "min": plain(row[f"__mn_{c}"]),
                    "max": plain(row[f"__mx_{c}"]),
                    "null_count": int(rows) - int(row[f"__ct_{c}"]),
                    "total": int(rows),
                    "ndv": ndvs[c],
                }
        tio.register_analyzed_stats(node, stats)
        # persist ONLY when the name resolves to the persistent table —
        # a temp view shadowing a same-named table must not plant its
        # stats in the table's _meta.json
        persisted = False
        if cmd.name.lower() not in self.catalog._views:
            persisted = self.catalog.save_table_stats(cmd.name, stats)
        return string_df({
            "table": [cmd.name],
            "rows": [str(rows)],
            "columns_analyzed": [str(len(stats["columns"]))],
            "persisted": [str(persisted).lower()],
        })

    def _invalidate_plan_cache(self, path: Optional[str] = None,
                               conf_key: Optional[str] = None,
                               old: Any = None, new: Any = None) -> None:
        """Serving plan-cache hook (spark_tpu.serving.plancache): catalog
        mutations evict entries reading the mutated table/database path;
        a SET of a planning-relevant conf evicts entries built under this
        session's old value.  No-op outside a serving deployment."""
        cache = getattr(self, "_plan_cache", None)
        if cache is None:
            return
        if path is not None:
            cache.invalidate_paths(path)
        if conf_key is not None:
            cache.invalidate_conf(conf_key, old, new)

    def _run_command(self, cmd) -> DataFrame:
        from . import parser as P
        from ..columnar import ColumnBatch

        def string_df(cols: dict) -> DataFrame:
            names = list(cols)
            struct = T.StructType(
                [T.StructField(n, T.string) for n in names])
            vals = list(cols.values())
            if vals and len(vals[0]) == 0:
                return DataFrame(self, L.LocalRelation(ColumnBatch.empty(struct)))
            return DataFrame(
                self, L.LocalRelation(ColumnBatch.from_arrays(cols, schema=struct)))

        if isinstance(cmd, P.AnalyzeTableCommand):
            out = self._analyze_table(cmd, string_df)
            # fresh stats change what the planner would build (CBO sides,
            # capacities): entries over this table are stale plans now
            try:
                self._invalidate_plan_cache(
                    path=self.catalog.table_path(cmd.name))
            except Exception:
                pass                   # path-based targets have no entry
            return out
        if isinstance(cmd, P.CreateViewCommand):
            # conflict-check TEMP VIEWS only: a temp view may shadow a
            # persistent table of the same name
            if not cmd.replace and cmd.name.lower() in self.catalog._views:
                raise AnalysisException(f"temp view {cmd.name} already exists")
            self.catalog.register(cmd.name, cmd.query)
            return string_df({})
        if isinstance(cmd, P.DropViewCommand):
            found = self.catalog.drop(cmd.name)
            if not found and not cmd.if_exists:
                raise AnalysisException(f"view not found: {cmd.name}")
            return string_df({})
        if isinstance(cmd, P.DropTableCommand):
            # a temp view may shadow a table of the same name (Spark drops
            # the view first)
            if self.catalog.drop(cmd.name):
                return string_df({})
            self.catalog.drop_table(cmd.name, cmd.if_exists)
            self._invalidate_plan_cache(
                path=self.catalog.table_path(cmd.name))
            return string_df({})
        if isinstance(cmd, P.CreateDatabaseCommand):
            self.catalog.create_database(cmd.name, cmd.if_not_exists)
            return string_df({})
        if isinstance(cmd, P.DropDatabaseCommand):
            db_dir = self.catalog._db_dir(cmd.name.lower())
            self.catalog.drop_database(cmd.name, cmd.if_exists)
            self._invalidate_plan_cache(path=db_dir)
            return string_df({})
        if isinstance(cmd, P.UseDatabaseCommand):
            self.catalog.setCurrentDatabase(cmd.name)
            return string_df({})
        if isinstance(cmd, P.ShowDatabasesCommand):
            return string_df({"namespace": self.catalog.list_databases()})
        if isinstance(cmd, P.CreateTableCommand):
            import os
            exists = os.path.isdir(self.catalog.table_path(cmd.name))
            if exists:
                if cmd.if_not_exists:
                    return string_df({})
                if cmd.replace:
                    self.catalog.drop_table(cmd.name)
                else:
                    raise AnalysisException(
                        f"table {cmd.name} already exists")
            if cmd.query is not None:
                df = DataFrame(self, cmd.query)
                self.catalog.save_table(cmd.name, df, cmd.fmt)
            else:
                schema = T.StructType([
                    T.StructField(n, T.type_for_name(t))
                    for n, t in cmd.columns])
                self.catalog.create_empty_table(cmd.name, schema, cmd.fmt)
            self._invalidate_plan_cache(
                path=self.catalog.table_path(cmd.name))
            return string_df({})
        if isinstance(cmd, P.InsertIntoCommand):
            import json
            import os
            path = self.catalog.table_path(cmd.name)
            meta_p = os.path.join(path, "_meta.json")
            if not os.path.isfile(meta_p):
                raise AnalysisException(f"table not found: {cmd.name}")
            with open(meta_p) as f:
                meta = json.load(f)
            # MATERIALIZE the query before touching the table directory:
            # INSERT OVERWRITE t SELECT ... FROM t must read the old data,
            # and a failing query must not destroy it.  Inserts bind by
            # POSITION against the table schema (Spark semantics), so
            # validate arity and rename.
            src = DataFrame(self, cmd.query)
            table_schema = [n for n, _t in meta["schema"]]
            if len(src.schema.names) != len(table_schema):
                raise AnalysisException(
                    f"INSERT into {cmd.name}: query produces "
                    f"{len(src.schema.names)} columns, table has "
                    f"{len(table_schema)}")
            batch = src._execute()
            batch = ColumnBatch(list(table_schema), batch.vectors,
                                batch.row_valid, batch.capacity)
            materialized = DataFrame(self, L.LocalRelation(batch))
            from ..io import DataFrameWriter
            mode = "overwrite" if cmd.overwrite else "append"
            DataFrameWriter(materialized).format(meta["format"]) \
                .mode(mode).save(path)
            if cmd.overwrite:
                # overwrite clears the dir, including the metadata: rewrite
                with open(meta_p, "w") as f:
                    json.dump(meta, f)
            self._invalidate_plan_cache(path=path)
            return string_df({})
        if isinstance(cmd, P.ShowTablesCommand):
            persistent = set(self.catalog.list_persistent_tables())
            names = self.catalog.listTables()
            return string_df({
                "tableName": names,
                "isTemporary": ["false" if n in persistent else "true"
                                for n in names]})
        if isinstance(cmd, P.DescribeCommand):
            plan = self.catalog.lookup(cmd.name)
            schema = DataFrame(self, plan).schema
            if not cmd.extended:
                return string_df({
                    "col_name": [f.name for f in schema.fields],
                    "data_type": [f.dataType.simpleString()
                                  for f in schema.fields],
                    "comment": [""] * len(schema.fields)})
            # DESCRIBE EXTENDED: append ANALYZE TABLE statistics when
            # registered (DescribeTableCommand's stats section)
            from .. import io as tio
            node = self._unwrap_aliases(plan)
            st = tio.analyzed_stats(node) \
                if isinstance(node, L.FileRelation) else None
            cols = st.get("columns", {}) if st else {}

            def fmt_stats(name):
                rec = cols.get(name)
                if not rec:
                    return ""
                return (f"min={rec.get('min')} max={rec.get('max')} "
                        f"nulls={rec.get('null_count')} "
                        f"ndv={rec.get('ndv')}")

            names = [f.name for f in schema.fields] + ["# rows"]
            dts = [f.dataType.simpleString() for f in schema.fields] + [""]
            comments = [fmt_stats(f.name) for f in schema.fields] + [
                str(st["rows"]) if st else "<not analyzed>"]
            return string_df({"col_name": names, "data_type": dts,
                              "comment": comments})
        if isinstance(cmd, P.SetCommand):
            if cmd.key is not None and cmd.value is not None:
                old = self.conf.get(cmd.key, None)
                self.conf.set(cmd.key, cmd.value)
                new = self.conf.get(cmd.key, None)
                if new != old:
                    self._invalidate_plan_cache(conf_key=cmd.key,
                                                old=old, new=new)
            key = cmd.key if cmd.key is not None else ""
            value = str(self.conf.get(cmd.key, "<undefined>")) \
                if cmd.key is not None else ""
            return string_df({"key": [key], "value": [value]})
        if isinstance(cmd, P.ExplainCommand):
            from .planner import QueryExecution
            qe = QueryExecution(self, cmd.query)
            text = qe.explain_string() if cmd.extended else \
                "== Physical Plan ==\n" + qe.planned.physical.tree_string()
            return string_df({"plan": [text]})
        raise AnalysisException(f"unsupported command {type(cmd).__name__}")

    def table(self, name: str) -> DataFrame:
        return DataFrame(self, L.UnresolvedRelation(name))

    @property
    def read(self):
        from ..io import DataFrameReader
        return DataFrameReader(self)

    @property
    def readStream(self):
        from ..streaming.api import DataStreamReader
        return DataStreamReader(self)

    @property
    def streams(self):
        from ..streaming.api import StreamingQueryManager
        return StreamingQueryManager.get(self)

    def newSession(self) -> "SparkSession":
        """A sibling session: same conf VALUES and warehouse (persistent
        tables are shared through the filesystem catalog, like sessions
        sharing one SparkContext), but isolated temp views, conf object,
        jit/plan caches, and cancellation state
        (`SparkSession.scala:236 newSession`)."""
        return SparkSession(self.conf_obj.clone())

    def enableHostShuffle(self, root: str, process_id: Optional[int] = None,
                          n_processes: Optional[int] = None,
                          timeout_s: float = 120.0, heartbeat=None):
        """Register the DCN host-shuffle data plane on this session: from
        now on every query PLANS its cross-process exchange through a
        ``HostShuffleService`` at ``root`` (the planner-citizen form of
        the reference's external shuffle service registration,
        `ExternalShuffleBlockResolver.java:57`).  Leaf DataFrames/scans
        are per-process partitions; byte-identical leaves are detected as
        replicated.  Defaults identify the process via jax.distributed.

        ``heartbeat`` (a ``parallel.cluster.HeartbeatMonitor``) arms the
        exchange's failure detector: confirmed-dead peers are excluded
        from barriers and blacklisted for the rest of the query instead
        of timing every step out.  Retry knobs come from this session's
        conf (``spark.tpu.shuffle.io.*``); the service's retry/blacklist
        counters register as the ``shuffle`` metrics source."""
        from ..parallel.hostshuffle import HostShuffleService
        if process_id is None or n_processes is None:
            import jax
            process_id = jax.process_index() if process_id is None \
                else process_id
            n_processes = jax.process_count() if n_processes is None \
                else n_processes
        if getattr(self, "_host_ledger", None) is None:
            # one ledger per session-process: re-enabling the shuffle
            # (fault recovery, reconfiguration) keeps the same budget
            # accounting instead of forgetting what is already held
            from ..memory import HostMemoryLedger
            self._host_ledger = HostMemoryLedger(self.conf_obj)
        self._crossproc_svc = HostShuffleService(
            root, process_id=process_id, n_processes=n_processes,
            timeout_s=timeout_s, conf=self.conf_obj, heartbeat=heartbeat,
            ledger=self._host_ledger)
        ms = self.metricsSystem
        ms._sources = [s for s in ms._sources if s.name != "shuffle"]
        ms.register_source(self._crossproc_svc.metrics_source())
        return self._crossproc_svc

    def disableHostShuffle(self) -> None:
        svc = getattr(self, "_crossproc_svc", None)
        bc = getattr(svc, "blockclient", None)
        if bc is not None:
            # orderly departure: release this process's block-service
            # lease so the orphan reaper's TTL clock starts on whatever
            # the process leaves registered (a crash skips this and the
            # lease simply goes stale — same clock, later start)
            bc.expire_owner(bc.owner)
        self._crossproc_svc = None

    @property
    def statsFeedback(self):
        """The session's adaptive-execution ``StatsFeedback``: observed
        per-side cardinalities the cross-process replanner recorded at
        exchange stats barriers, consulted by later plan-time join
        decisions and exposed here for inspection (``snapshot()``,
        ``hits``, ``clear()``).  Lazily created so sessions that never
        touch the adaptive path pay nothing."""
        from ..parallel.crossproc import _session_feedback
        return _session_feedback(self)
