"""Datasource IO: DataFrameReader / DataFrameWriter + file relation loading.

The analog of `sql/core/.../execution/datasources/` (`DataSource.scala`
resolution, `FileFormat.scala` implementations, `PartitioningUtils` partition
discovery, `FileFormatWriter.scala`) re-based on Arrow:

* parquet/csv/json decode through pyarrow's C++ readers straight into
  columnar host memory — the role `VectorizedParquetRecordReader.java` plays
  in the reference — then transfer to device as SoA arrays.
* partition discovery parses `key=value` directory components
  (`PartitioningUtils.parsePathFragment` analog) and materializes partition
  columns.
* writers emit Spark-compatible directory layouts: `part-*` files inside the
  target directory, `key=value` subdirectories under `partitionBy`, and a
  `_SUCCESS` marker.

Reads are eager at plan time (a FileRelation resolves to one host batch,
cached by path+mtime); the scan operator streams it to device.  Multi-batch
streaming scans arrive with the multi-stage runner.
"""

from __future__ import annotations

import glob as _glob
import json as _json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from . import tracing
from . import types as T
from .columnar import ColumnBatch, ColumnVector, PrebuiltColumn as \
    _PrebuiltColumn
from .expressions import AnalysisException
from .sql import logical as L

__all__ = ["DataFrameReader", "DataFrameWriter", "read_file_relation"]

_DATA_EXTS = {".parquet", ".csv", ".json", ".txt", ".text"}


# ---------------------------------------------------------------------------
# schema mapping (arrow <-> engine types)
# ---------------------------------------------------------------------------

def _arrow_to_engine(at) -> T.DataType:
    import pyarrow as pa
    if pa.types.is_boolean(at):
        return T.boolean
    if pa.types.is_int8(at):
        return T.int8
    if pa.types.is_int16(at):
        return T.int16
    if pa.types.is_int32(at):
        return T.int32
    if pa.types.is_int64(at) or pa.types.is_unsigned_integer(at):
        return T.int64
    if pa.types.is_float32(at):
        return T.float32
    if pa.types.is_floating(at):
        return T.float64
    if pa.types.is_decimal(at):
        return T.DecimalType(at.precision, at.scale)
    if pa.types.is_date(at):
        return T.date
    if pa.types.is_timestamp(at):
        return T.timestamp
    if pa.types.is_string(at) or pa.types.is_large_string(at) \
            or pa.types.is_binary(at) or pa.types.is_large_binary(at):
        return T.string
    if pa.types.is_null(at):
        return T.string
    raise AnalysisException(f"unsupported arrow type for TPU engine: {at}")


def _engine_to_arrow(dt: T.DataType):
    import pyarrow as pa
    if isinstance(dt, T.BooleanType):
        return pa.bool_()
    if isinstance(dt, T.ByteType):
        return pa.int8()
    if isinstance(dt, T.ShortType):
        return pa.int16()
    if isinstance(dt, T.IntegerType):
        return pa.int32()
    if isinstance(dt, T.LongType):
        return pa.int64()
    if isinstance(dt, T.FloatType):
        return pa.float32()
    if isinstance(dt, T.DoubleType):
        return pa.float64()
    if isinstance(dt, T.DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, T.DateType):
        return pa.date32()
    if isinstance(dt, T.TimestampType):
        return pa.timestamp("us")
    if isinstance(dt, T.StringType):
        return pa.string()
    raise AnalysisException(f"cannot write type {dt}")


def _table_to_batch(table, extra_cols: Optional[Dict[str, Any]] = None
                    ) -> ColumnBatch:
    """Arrow table → host ColumnBatch (+appended partition columns).

    Numeric/temporal columns convert VECTORIZED (arrow fill_null + numpy
    view), including nullable ones — the per-value pylist lane is only
    for strings (dictionary encoding needs the words) and decimals.
    This is the `VectorizedParquetRecordReader.java` half of the scan
    hot path; the pylist fallback was 10× the whole scan cost at 2M+
    rows."""
    import pyarrow as pa
    data: Dict[str, Any] = {}
    fields: List[T.StructField] = []
    n = table.num_rows
    for col_name, col in zip(table.column_names, table.columns):
        at = col.type
        dt = _arrow_to_engine(at)
        arr = col.combine_chunks()
        if dt.is_string:
            data[col_name] = arr.to_pylist()
        elif isinstance(dt, T.DecimalType):
            scaled = [None if v is None else int(v.scaled_value)
                      for v in arr.to_pylist()]
            data[col_name] = np.array(
                [0 if v is None else v for v in scaled], np.int64)
            # nulls handled below via pylist path when present
            if arr.null_count:
                data[col_name] = scaled
        else:
            if isinstance(dt, T.DateType):
                arr = arr.cast(pa.date32()).cast(pa.int32())
                np_dtype = np.int32
            elif isinstance(dt, T.TimestampType):
                arr = arr.cast(pa.timestamp("us")).cast(pa.int64())
                np_dtype = np.int64
            else:
                np_dtype = np.dtype(dt.np_dtype)
            valid = None
            if arr.null_count:
                valid = ~np.asarray(arr.is_null())
                fill = pa.scalar(False) if np_dtype == np.bool_ \
                    else pa.scalar(0, arr.type)
                arr = arr.fill_null(fill)
            vals = arr.to_numpy(zero_copy_only=False).astype(np_dtype,
                                                             copy=False)
            data[col_name] = _PrebuiltColumn(vals, dt, valid)
        fields.append(T.StructField(col_name, dt, True))
    if extra_cols:
        for k, v in extra_cols.items():
            data[k] = v
            if isinstance(v, np.ndarray):
                dt = T.np_dtype_to_engine(v.dtype)
            else:
                dt = T.string
            fields.append(T.StructField(k, dt, True))
    schema = T.StructType(fields)
    if n == 0 and not extra_cols:
        return ColumnBatch.empty(schema)
    return ColumnBatch.from_arrays(data, schema=schema)


# ---------------------------------------------------------------------------
# path resolution + partition discovery
# ---------------------------------------------------------------------------

def _resolve_paths(path_or_paths) -> List[str]:
    paths = ([path_or_paths] if isinstance(path_or_paths, str)
             else list(path_or_paths))
    out: List[str] = []
    for p in paths:
        if any(ch in p for ch in "*?["):
            out += sorted(_glob.glob(p))
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if not d.startswith(("_", ".")))
                for f in sorted(files):
                    if f.startswith(("_", ".")):
                        continue
                    out.append(os.path.join(root, f))
        elif os.path.exists(p):
            out.append(p)
        else:
            raise AnalysisException(f"Path does not exist: {p}")
    if not out:
        raise AnalysisException(f"no input files found in {path_or_paths}")
    return out


def _partition_values(file_path: str, base: str) -> Dict[str, str]:
    """Parse `key=value` directory components below `base`."""
    rel = os.path.relpath(os.path.dirname(file_path), base)
    vals: Dict[str, str] = {}
    if rel == ".":
        return vals
    for comp in rel.split(os.sep):
        if "=" in comp:
            k, v = comp.split("=", 1)
            vals[k] = v
    return vals


def _infer_partition_column(raw: List[str]):
    """Spark infers partition value types (int, double, string)."""
    try:
        return np.array([int(v) for v in raw], np.int64)
    except ValueError:
        pass
    try:
        return np.array([float(v) for v in raw], np.float64)
    except ValueError:
        return raw


# ---------------------------------------------------------------------------
# format readers (host side, arrow-backed)
# ---------------------------------------------------------------------------

#: observable scan counters (ParquetReadBenchmark-style evidence that
#: pruning/pushdown actually narrowed the read); reset freely in tests
SCAN_STATS = {"files": 0, "row_groups": 0, "row_groups_skipped": 0,
              "rows": 0, "columns_read": 0}


def _rg_keep(pf, pushed: Optional[List[tuple]]) -> Optional[List[int]]:
    """Row groups that MAY contain matching rows, by footer min/max stats.

    ``pushed`` holds advisory ``(col, op, value)`` conjuncts; a row group
    is skipped only when its stats PROVE no row satisfies a conjunct
    (``ParquetFilters.scala`` + ``VectorizedParquetRecordReader`` role).
    Returns None when nothing can be skipped (avoids the per-group read
    path)."""
    if not pushed:
        return None
    md = pf.metadata
    name_to_idx = {md.schema.column(i).path: i
                   for i in range(md.num_columns)}
    keep: List[int] = []
    skipped = 0
    for rg in range(md.num_row_groups):
        alive = True
        for col, op, val in pushed:
            ci = name_to_idx.get(col)
            if ci is None:
                continue
            st = md.row_group(rg).column(ci).statistics
            if st is None or not st.has_min_max:
                continue
            try:
                lo, hi = st.min, st.max
                if isinstance(val, str) and isinstance(lo, bytes):
                    lo, hi = lo.decode("utf-8", "replace"), \
                        hi.decode("utf-8", "replace")
                if type(lo) is not type(val) and not (
                        isinstance(lo, (int, float))
                        and isinstance(val, (int, float))):
                    continue
                if (op == "==" and (val < lo or val > hi)) \
                        or (op == "<" and lo >= val) \
                        or (op == "<=" and lo > val) \
                        or (op == ">" and hi <= val) \
                        or (op == ">=" and hi < val):
                    alive = False
                    break
            except Exception:
                continue
        if alive:
            keep.append(rg)
        else:
            skipped += 1
    SCAN_STATS["row_groups_skipped"] += skipped
    return keep if skipped else None


def _open_pruned(path: str, columns, pushed):
    """Open one parquet file for a pruned/pushed read: returns
    ``(pf, present, keep)`` and updates SCAN_STATS — the single definition
    behind both the eager and streaming scan paths."""
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(path)
    present = None
    if columns is not None:
        names = set(pf.schema_arrow.names)
        present = [c for c in columns if c in names]
    SCAN_STATS["files"] += 1
    SCAN_STATS["columns_read"] += len(present) if present is not None \
        else pf.metadata.num_columns
    keep = _rg_keep(pf, pushed)
    SCAN_STATS["row_groups"] += pf.metadata.num_row_groups
    return pf, present, keep


def _read_parquet(paths: List[str], options, columns=None,
                  pushed=None) -> "Any":
    import pyarrow.parquet as pq
    import pyarrow as pa
    tables = []
    for p in paths:
        pf, present, keep = _open_pruned(p, columns, pushed)
        if keep is None:
            t = pq.read_table(p, columns=present)
        elif keep:
            t = pf.read_row_groups(keep, columns=present)
        else:
            t = pf.schema_arrow.empty_table()
            if present is not None:
                t = t.select(present)
        SCAN_STATS["rows"] += t.num_rows
        tables.append(t)
    return pa.concat_tables(tables, promote_options="permissive")


def _read_csv(paths: List[str], options) -> "Any":
    import pyarrow as pa
    import pyarrow.csv as pacsv
    header = str(options.get("header", "false")).lower() == "true"
    sep = options.get("sep", options.get("delimiter", ","))
    infer = str(options.get("inferschema", "false")).lower() == "true"
    null_value = options.get("nullvalue", "")
    tables = []
    for p in paths:
        read_opts = pacsv.ReadOptions(autogenerate_column_names=not header)
        parse_opts = pacsv.ParseOptions(delimiter=sep)
        conv = pacsv.ConvertOptions(null_values=[null_value, "null"])
        t = pacsv.read_csv(p, read_options=read_opts,
                           parse_options=parse_opts, convert_options=conv)
        if not header:
            t = t.rename_columns([f"_c{i}" for i in range(t.num_columns)])
        if not infer:
            t = t.cast(pa.schema([pa.field(f.name, pa.string())
                                  for f in t.schema]))
        tables.append(t)
    return pa.concat_tables(tables, promote_options="permissive")


def _read_json(paths: List[str], options) -> "Any":
    import pyarrow as pa
    import pyarrow.json as pajson
    tables = [pajson.read_json(p) for p in paths]
    return pa.concat_tables(tables, promote_options="permissive")


def _read_text(paths: List[str], options) -> "Any":
    import pyarrow as pa
    lines: List[str] = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as f:
            lines += [ln.rstrip("\n") for ln in f]
    return pa.table({"value": pa.array(lines, pa.string())})


def _read_orc(paths: List[str], options, columns=None) -> "Any":
    """ORC via pyarrow.orc (`sql/hive/.../orc/OrcFileFormat.scala` role):
    column pruning pushes into the stripe reader; stats-based stripe
    skipping stays parquet-only (documented)."""
    import pyarrow as pa
    import pyarrow.orc as paorc
    tables = []
    strip = False
    for p in paths:
        f = paorc.ORCFile(p)
        cols = None if columns is None else \
            [c for c in columns if c in f.schema.names]
        if cols == []:
            # partition-dir-only projection: both ORCFile.read(columns=[])
            # and concat_tables of 0-column tables DROP the row count, so
            # carry one narrow column through the concat and strip after
            cols = [f.schema.names[0]]
            strip = True
        tables.append(f.read(columns=cols))
    out = pa.concat_tables(tables, promote_options="permissive")
    return out.select([]) if strip else out


_READERS = {
    "parquet": _read_parquet,
    "csv": _read_csv,
    "json": _read_json,
    "text": _read_text,
    "orc": _read_orc,
}


def _parquet_schema(raw_paths: List[str]) -> T.StructType:
    """Engine schema from parquet FOOTERS + partition directories — no data
    pages are read (the lazy half of ``DataSource.resolveRelation``)."""
    import pyarrow.parquet as pq
    files = _resolve_paths(raw_paths)
    base = raw_paths[0] if isinstance(raw_paths, list) else raw_paths
    base = base if os.path.isdir(base) else os.path.dirname(base)
    fields: List[T.StructField] = []
    seen: set = set()
    for f in files:
        for af in pq.ParquetFile(f).schema_arrow:
            if af.name not in seen:
                seen.add(af.name)
                fields.append(T.StructField(af.name,
                                            _arrow_to_engine(af.type), True))
    _append_partition_fields(files, base, seen, fields)
    return T.StructType(fields)


def _append_partition_fields(files, base, seen: set,
                             fields: List["T.StructField"]) -> None:
    """Partition-directory (k=v) columns, shared by every metadata-only
    schema reader (parquet footers, ORC metadata)."""
    part_vals: Dict[str, List[str]] = {}
    for f in files:
        for k, v in _partition_values(f, base).items():
            part_vals.setdefault(k, []).append(v)
    for k, vals in part_vals.items():
        if k in seen:
            continue
        inferred = _infer_partition_column(vals)
        dt = T.np_dtype_to_engine(inferred.dtype) \
            if isinstance(inferred, np.ndarray) else T.string
        fields.append(T.StructField(k, dt, True))


def _orc_schema(raw_paths: List[str]) -> T.StructType:
    """Engine schema from ORC file metadata — no stripes read."""
    import pyarrow.orc as paorc
    files = _resolve_paths(raw_paths)
    base = raw_paths[0] if isinstance(raw_paths, list) else raw_paths
    base = base if os.path.isdir(base) else os.path.dirname(base)
    fields: List[T.StructField] = []
    seen: set = set()
    for f in files:
        for af in paorc.ORCFile(f).schema:
            if af.name not in seen:
                seen.add(af.name)
                fields.append(T.StructField(af.name,
                                            _arrow_to_engine(af.type), True))
    _append_partition_fields(files, base, seen, fields)
    return T.StructType(fields)


_relation_cache: Dict[Any, ColumnBatch] = {}


def _load_batch(fmt: str, raw_paths: List[str], options: Dict[str, str],
                columns: Optional[List[str]] = None,
                pushed: Optional[List[tuple]] = None,
                engine_schema: Optional[T.StructType] = None) -> ColumnBatch:
    if fmt == "jdbc":
        # database relations: no filesystem paths, and NEVER cached (a
        # mutable store has no mtime-like invalidation token).  The
        # relation's resolved engine schema (user-declared or
        # sample-inferred) is the scan's cast target.
        import pyarrow as pa
        from . import jdbc as _jdbc
        urls = [raw_paths] if isinstance(raw_paths, str) else list(raw_paths)
        target = None
        if engine_schema is not None:
            target = pa.schema([pa.field(f.name,
                                         _engine_to_arrow(f.dataType))
                                for f in engine_schema.fields])
        return _table_to_batch(_jdbc.read_table(urls, options,
                                                columns=columns,
                                                pushed=pushed,
                                                target=target))
    files = _resolve_paths(raw_paths)
    key = (fmt, tuple(files), tuple(sorted(options.items())),
           tuple(os.path.getmtime(f) for f in files),
           None if columns is None else tuple(columns),
           None if pushed is None else tuple(pushed))
    if key in _relation_cache:
        return _relation_cache[key]
    base_reader = _READERS.get(fmt)
    if base_reader is None:
        raise AnalysisException(f"unsupported format: {fmt}")
    if fmt == "parquet":
        def reader(paths, opts):
            return _read_parquet(paths, opts, columns=columns, pushed=pushed)
    elif fmt == "orc" and columns is not None:
        def reader(paths, opts):
            return _read_orc(paths, opts, columns=columns)
    elif columns is not None:
        def reader(paths, opts):
            t = base_reader(paths, opts)
            sel = [c for c in columns if c in t.column_names]
            return t.select(sel)
    else:
        reader = base_reader
    # group files by partition values (from the first existing base dir)
    base = raw_paths[0] if isinstance(raw_paths, list) else raw_paths
    base = base if os.path.isdir(base) else os.path.dirname(base)
    part_of = {f: _partition_values(f, base) for f in files}
    part_keys: List[str] = []
    for f in files:
        for k in part_of[f]:
            if k not in part_keys and (columns is None or k in columns):
                part_keys.append(k)
    table = reader(files, options)
    extra = None
    if part_keys:
        # re-read per file to align partition values with row counts
        import pyarrow as pa
        per_file = [reader([f], options) for f in files]
        cols: Dict[str, List[str]] = {k: [] for k in part_keys}
        for f, t in zip(files, per_file):
            for k in part_keys:
                cols[k] += [part_of[f].get(k, "")] * t.num_rows
        table = pa.concat_tables(per_file, promote_options="permissive")
        extra = {k: _infer_partition_column(v) for k, v in cols.items()}
    batch = _table_to_batch(table, extra)
    _relation_cache[key] = batch
    if len(_relation_cache) > 64:
        _relation_cache.pop(next(iter(_relation_cache)))
    return batch


def read_file_relation(rel: L.FileRelation, session) -> ColumnBatch:
    return _load_batch(rel.fmt, rel.paths, rel.options,
                       columns=getattr(rel, "columns", None),
                       pushed=getattr(rel, "pushed_filters", None),
                       engine_schema=getattr(rel, "_schema", None))


# ---------------------------------------------------------------------------
# streamed (multi-batch) scans — FileScanRDD.scala analog
# ---------------------------------------------------------------------------

_ROW_COUNT_CACHE: dict = {}


def file_row_count(rel: L.FileRelation) -> Optional[int]:
    """Total rows WITHOUT loading data when possible (parquet metadata);
    other formats load (host-cached) and count.  Memoized per resolved
    file list + mtimes — multi-join planning probes the same dimension
    files repeatedly."""
    import os
    if rel.fmt == "jdbc":
        from . import jdbc as _jdbc
        return _jdbc.count_rows(rel.paths[0], rel.options)  # never cached
    try:
        files = _resolve_paths(rel.paths)
    except AnalysisException:
        return None
    key = tuple((f, os.path.getmtime(f)) for f in files)
    if key in _ROW_COUNT_CACHE:
        return _ROW_COUNT_CACHE[key]
    if rel.fmt == "parquet":
        import pyarrow.parquet as pq
        n = sum(pq.ParquetFile(f).metadata.num_rows for f in files)
    else:
        st = analyzed_stats(rel)
        if st and st.get("rows") is not None:
            n = int(st["rows"])     # ANALYZE result: no data load needed
        else:
            batch = _load_batch(rel.fmt, rel.paths, rel.options)
            n = int(np.asarray(batch.num_rows()))
    _ROW_COUNT_CACHE[key] = n
    return n


#: ANALYZE TABLE results, keyed by the relation's identity at ANALYZE
#: time (files+mtimes, or the jdbc url+table).  The CBO fallback for
#: formats without free footer statistics (csv/json/text/orc/jdbc) —
#: parquet keeps its exact, always-fresh footer path.  Mirrors the
#: reference's ANALYZE-gathered `statsEstimation/` stats, including
#: their staleness model (here: invalidated when file mtimes change).
_ANALYZED_STATS: Dict[Any, dict] = {}


def _rel_stats_key(rel: L.FileRelation):
    """Identity of a relation FOR STATS PURPOSES: format + read options
    (header/schema options change the logical table over the same bytes)
    + files with mtimes (staleness token); jdbc: url + table/query."""
    opts = tuple(sorted((str(k), str(v))
                        for k, v in (rel.options or {}).items()))
    if rel.fmt == "jdbc":
        return ("jdbc", rel.paths[0], rel.options.get("dbtable"),
                rel.options.get("query"))
    try:
        files = _resolve_paths(rel.paths)
    except AnalysisException:
        return None
    return (rel.fmt, opts) + tuple(
        (f, os.path.getmtime(f)) for f in files)


def stats_key_token(rel: L.FileRelation):
    """JSON-round-tripped form of the stats key, captured at ANALYZE
    time and persisted with the stats: a catalog load re-registers them
    ONLY when the current key still matches — the staleness gate."""
    import json as _json
    k = _rel_stats_key(rel)
    return None if k is None else _json.loads(_json.dumps(k))


def register_analyzed_stats(rel: L.FileRelation, stats: dict) -> None:
    """Install ANALYZE TABLE results for this relation's current files."""
    key = _rel_stats_key(rel)
    if key is not None:
        _ANALYZED_STATS[key] = stats


def analyzed_stats(rel: L.FileRelation) -> Optional[dict]:
    key = _rel_stats_key(rel)
    return None if key is None else _ANALYZED_STATS.get(key)


_COLUMN_STATS_CACHE: dict = {}


def file_column_stats(rel: L.FileRelation) -> Dict[str, dict]:
    """Per-column {min, max, null_count, total} from parquet FOOTERS — the
    free column statistics the reference's CBO keeps in
    `catalyst/.../plans/logical/statsEstimation/` (there gathered by
    ANALYZE TABLE; here always available because parquet already wrote
    them).  Non-parquet formats fall back to ANALYZE TABLE results
    (``analyzed_stats``); memoized per file list + mtimes."""
    if rel.fmt != "parquet":
        st = analyzed_stats(rel)
        return st.get("columns", {}) if st else {}
    try:
        files = _resolve_paths(rel.paths)
    except AnalysisException:
        return {}
    key = tuple((f, os.path.getmtime(f)) for f in files)
    if key in _COLUMN_STATS_CACHE:
        return _COLUMN_STATS_CACHE[key]
    import pyarrow.parquet as pq
    out: Dict[str, dict] = {}
    for f in files:
        md = pq.ParquetFile(f).metadata
        names = {md.schema.column(i).path: i
                 for i in range(md.num_columns)}
        for name, ci in names.items():
            rec = out.setdefault(name, {"min": None, "max": None,
                                        "null_count": 0, "total": 0})
            rec["total"] += md.num_rows
            for rg in range(md.num_row_groups):
                st = md.row_group(rg).column(ci).statistics
                if st is None:
                    continue
                if st.null_count is not None:
                    rec["null_count"] += st.null_count
                if not st.has_min_max:
                    continue
                lo, hi = st.min, st.max
                if isinstance(lo, bytes):
                    lo = lo.decode("utf-8", "replace")
                    hi = hi.decode("utf-8", "replace")
                try:
                    if rec["min"] is None or lo < rec["min"]:
                        rec["min"] = lo
                    if rec["max"] is None or hi > rec["max"]:
                        rec["max"] = hi
                except TypeError:
                    pass
    _COLUMN_STATS_CACHE[key] = out
    return out


_NDV_CACHE: Dict[Any, Dict[str, float]] = {}


def file_column_ndv(rel: L.FileRelation, columns) -> Dict[str, float]:
    """Estimated distinct-value counts for ``columns`` (the NDV half of
    the reference's CBO statistics, `statsEstimation/` — gathered there
    by ANALYZE TABLE, here by a one-row-group sample at plan time).

    Estimator: distinct count over the first row group of the first
    file; if the sample's distinct ratio is saturated (<90% unique) the
    domain is assumed reached (dimension keys, enums), otherwise the
    count scales linearly with the table (near-unique keys).  Memoized
    per (files, mtimes, columns).  Non-parquet formats use ANALYZE TABLE
    results when present."""
    if rel.fmt != "parquet":
        st = analyzed_stats(rel)
        if not st:
            return {}
        return {c: rec["ndv"] for c, rec in st.get("columns", {}).items()
                if c in columns and rec.get("ndv") is not None}
    try:
        files = _resolve_paths(rel.paths)
    except AnalysisException:
        return {}
    # ONE cache entry per file set, extended per newly-requested column —
    # reorder_joins probes one key column at a time, and per-(files,
    # column) keys would re-open footers for every probe
    key = tuple((f, os.path.getmtime(f)) for f in files)
    cached = _NDV_CACHE.setdefault(key, {})
    missing = [c for c in columns if c not in cached]
    if not missing:
        return cached
    import pyarrow.parquet as pq
    try:
        pf = pq.ParquetFile(files[0])
        present = [c for c in missing if c in pf.schema_arrow.names]
        if present:
            sample = pf.read_row_group(0, columns=present)
            total = file_row_count(rel) or sample.num_rows  # memoized sum
            n = max(sample.num_rows, 1)
            for c in present:
                uniq = len(sample.column(c).unique())
                if uniq < 0.9 * n:
                    cached[c] = float(uniq)            # saturated domain
                else:
                    cached[c] = float(uniq) * total / n  # near-unique key
    except Exception:
        pass
    return cached


def scan_file_batches(rel: L.FileRelation, batch_rows: int):
    """Yield host ColumnBatches of ≤ batch_rows rows each.

    Parquet streams record batches straight off the file (the
    VectorizedParquetRecordReader path — bounded host memory); other
    formats slice the host-cached table.  Partition-directory columns are
    appended per file."""
    columns = getattr(rel, "columns", None)
    pushed = getattr(rel, "pushed_filters", None)
    if rel.fmt == "jdbc":
        # database relation: one partitioned read (WHERE pushdown + column
        # pruning applied in SQL), sliced host-side like csv/json
        whole = _load_batch(rel.fmt, rel.paths, rel.options,
                            columns=columns, pushed=pushed,
                            engine_schema=getattr(rel, "_schema", None))
        n = int(np.asarray(whole.num_rows()))
        for start in range(0, max(n, 1), batch_rows):
            yield _slice_rows(whole, start, min(start + batch_rows, n))
        return
    files = _resolve_paths(rel.paths)
    base = rel.paths[0] if isinstance(rel.paths, list) else rel.paths
    base = base if os.path.isdir(base) else os.path.dirname(base)
    if rel.fmt == "parquet":
        import pyarrow as pa
        import pyarrow.parquet as pq
        yielded = False
        for f in files:
            pvals = _partition_values(f, base)
            if columns is not None:
                pvals = {k: v for k, v in pvals.items() if k in columns}
            pf, present, keep = _open_pruned(f, columns, pushed)
            kw = {} if keep is None else {"row_groups": keep}
            if keep == []:
                continue
            record_batches = pf.iter_batches(batch_size=batch_rows,
                                             columns=present, **kw)
            while True:
                with tracing.span("scan.read") as sp:
                    rb = next(record_batches, None)
                    if rb is None:
                        break
                    sp.attrs.update(rows=rb.num_rows, bytes=rb.nbytes)
                with tracing.span("scan.decode", rows=rb.num_rows):
                    table = pa.Table.from_batches([rb])
                    SCAN_STATS["rows"] += table.num_rows
                    extra = {k: _infer_partition_column([v] * table.num_rows)
                             for k, v in pvals.items()} or None
                    batch = _table_to_batch(table, extra)
                yielded = True
                yield batch
        if not yielded:
            # every row group was skipped: emit one empty batch so stage
            # runners still see the (pruned) schema
            yield ColumnBatch.empty(rel.schema())
        return
    whole = _load_batch(rel.fmt, rel.paths, rel.options, columns=columns)
    n = int(np.asarray(whole.num_rows()))
    # the cached batch is compacted on load (row_valid all-true prefix)
    for start in range(0, max(n, 1), batch_rows):
        stop = min(start + batch_rows, n)
        yield _slice_rows(whole, start, stop)


def scan_prefetch_depth(conf) -> int:
    """Resolve ``spark.tpu.scan.prefetchBatches``: -1 (auto) prefetches
    only when the per-batch step runs on an accelerator — on host-CPU
    XLA the decode thread competes with the step for the same cores."""
    from . import config as C
    d = conf.get(C.SCAN_PREFETCH_BATCHES)
    if d >= 0:
        return d
    try:
        import jax
        accel = jax.devices()[0].platform != "cpu"
    except Exception:
        accel = False
    return 2 if accel else 0


def prefetch_iter(inner, prep=None, depth: int = 2):
    """Iterate ``inner`` through a bounded background pipeline thread.

    The worker pulls items from ``inner`` and applies ``prep`` (string
    re-encode / pad / device transfer) up to ``depth`` items ahead of the
    consumer, so the host-side Arrow read + H2D copy of batch N+1 overlap
    the device step of batch N — the double-buffered scan pipeline of the
    reference's vectorized reader
    (`parquet/VectorizedParquetRecordReader.java:147`, which decodes the
    next page while the consuming operator drains the current batch;
    SURVEY §7 hard-part 4).  ``depth <= 0`` degrades to synchronous
    iteration.  Worker exceptions re-raise at the consuming site; early
    termination (break / generator close) stops the worker and closes
    ``inner`` so parquet file handles are released promptly."""
    if depth <= 0:
        for item in inner:
            yield prep(item) if prep is not None else item
        return
    import queue as _qmod
    import threading

    q: "_qmod.Queue" = _qmod.Queue(maxsize=depth)
    stop = threading.Event()
    # the worker takes the constructing thread's statement over, so scan
    # spans belong to their statement
    sid = tracing.current_statement()

    def _put(msg) -> None:
        # bounded put that aborts when the consumer has gone away
        while not stop.is_set():
            try:
                q.put(msg, timeout=0.2)
                return
            except _qmod.Full:
                continue

    def worker() -> None:
        try:
            try:
                with tracing.adopt(sid):
                    for item in inner:
                        out = prep(item) if prep is not None else item
                        _put(("item", out))
                        if stop.is_set():
                            return
            finally:
                close = getattr(inner, "close", None)
                if close is not None:
                    close()
        except BaseException as e:  # noqa: BLE001 — re-raised by consumer
            _put(("raise", e))
        else:
            _put(("end", None))

    th = threading.Thread(target=worker, daemon=True, name="scan-prefetch")
    th.start()
    try:
        while True:
            with tracing.span("scan.wait"):    # what the step waits for
                kind, payload = q.get()
            if kind == "item":
                yield payload
            elif kind == "raise":
                raise payload
            else:
                return
    finally:
        stop.set()
        try:                       # unblock a worker stuck on a full queue
            while True:
                q.get_nowait()
        except _qmod.Empty:
            pass
        th.join(timeout=5)


def scan_string_dictionaries(rel: L.FileRelation,
                             batch_rows: int) -> Dict[str, tuple]:
    """One cheap pre-pass over a file relation collecting the GLOBAL sorted
    dictionary of every string column.

    Streamed scans encode every batch onto these fixed dictionaries so the
    per-batch jitted step never retraces on dictionary changes, and sort
    order on codes stays globally consistent (sorted-dictionary invariant
    of ``encode_strings``).  For parquet only the string columns are read."""
    schema = rel.schema()
    str_cols = [f.name for f in schema.fields if f.dataType.is_string]
    if not str_cols:
        return {}
    with tracing.span("dict.scan", columns=len(str_cols)) as sp:
        out = _scan_string_dictionaries(rel, batch_rows, str_cols)
        sp.attrs["words"] = sum(len(d) for d in out.values())
    return out


def _scan_string_dictionaries(rel: L.FileRelation, batch_rows: int,
                              str_cols: List[str]) -> Dict[str, tuple]:
    uniques: Dict[str, set] = {c: set() for c in str_cols}
    files = [] if rel.fmt == "jdbc" else _resolve_paths(rel.paths)
    if rel.fmt == "parquet":
        import pyarrow.compute as pc
        import pyarrow.parquet as pq
        for f in files:
            pf = pq.ParquetFile(f)
            present = [c for c in str_cols if c in pf.schema_arrow.names]
            if not present:
                continue
            for rb in pf.iter_batches(batch_size=batch_rows, columns=present):
                for c in present:
                    col = rb.column(rb.schema.get_field_index(c))
                    # dedup in native code; only the per-batch uniques
                    # become Python objects
                    uniques[c].update(
                        v for v in pc.unique(col).to_pylist()
                        if v is not None)
    else:
        # jdbc: prune the (uncached) SELECT to the string columns only
        cols = str_cols if rel.fmt == "jdbc" else None
        whole = _load_batch(rel.fmt, rel.paths, rel.options, columns=cols,
                            engine_schema=getattr(rel, "_schema", None)
                            if rel.fmt == "jdbc" else None)
        for c in str_cols:
            if c in whole.names:
                vec = whole.column(c)
                if vec.dictionary:
                    uniques[c].update(vec.dictionary)
    # partition-directory columns (string-typed) also need fixed dicts
    base = rel.paths[0] if isinstance(rel.paths, list) else rel.paths
    base = base if os.path.isdir(base) else os.path.dirname(base)
    for f in files:
        for k, v in _partition_values(f, base).items():
            if k in uniques:
                uniques[k].add(v)
    return {c: tuple(sorted(s)) for c, s in uniques.items()}


def reencode_strings(batch: ColumnBatch,
                     fixed_dicts: Dict[str, tuple]) -> ColumnBatch:
    """Remap per-batch string codes onto fixed global dictionaries.

    Both dictionaries are sorted, so the remap table is one searchsorted."""
    if not fixed_dicts:
        return batch
    vectors = []
    with tracing.span("dict.reencode", words=0) as sp:
        for name, v in zip(batch.names, batch.vectors):
            target = fixed_dicts.get(name)
            if target is None or v.dictionary is None or \
                    v.dictionary is target:
                vectors.append(v)
                continue
            if v.dictionary == target:
                # the SAME tuple on every batch: a compiled step then knows
                # its input's dictionary by identity, not word by word
                vectors.append(ColumnVector(v.data, v.dtype, v.valid, target))
                continue
            sp.attrs["words"] += len(v.dictionary)
            tarr = np.asarray(target, dtype=object)
            local = np.asarray(v.dictionary, dtype=object)
            remap = np.searchsorted(tarr, local).astype(np.int32) \
                if len(local) else np.zeros(0, np.int32)
            codes = np.asarray(v.data).astype(np.int64)
            new_codes = remap[np.clip(codes, 0, max(len(local) - 1, 0))] \
                if len(local) else np.zeros_like(codes, np.int32)
            new_codes = np.where(codes < 0, -1, new_codes).astype(np.int32)
            vectors.append(ColumnVector(new_codes, v.dtype, v.valid, target))
    return ColumnBatch(list(batch.names), vectors, batch.row_valid,
                       batch.capacity)


def _slice_rows(batch: ColumnBatch, start: int, stop: int) -> ColumnBatch:
    from .columnar import ColumnVector as CV
    vectors = []
    for v in batch.vectors:
        data = np.asarray(v.data)[start:stop]
        valid = None if v.valid is None else np.asarray(v.valid)[start:stop]
        vectors.append(CV(data, v.dtype, valid, v.dictionary))
    rv = None if batch.row_valid is None \
        else np.asarray(batch.row_valid)[start:stop]
    out = ColumnBatch(batch.names, vectors, rv, stop - start)
    from .columnar import pad_capacity, pad_to_capacity
    return pad_to_capacity(out, pad_capacity(stop - start))


# ---------------------------------------------------------------------------
# DataFrameReader (`sql/DataFrameReader.scala` analog)
# ---------------------------------------------------------------------------

class DataFrameReader:
    def __init__(self, session):
        self._session = session
        self._fmt = "parquet"
        self._options: Dict[str, str] = {}
        self._schema: Optional[T.StructType] = None

    def format(self, source: str) -> "DataFrameReader":
        self._fmt = source.lower()
        return self

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[str(key).lower()] = str(value)
        return self

    def options(self, **opts) -> "DataFrameReader":
        for k, v in opts.items():
            self.option(k, v)
        return self

    def schema(self, s) -> "DataFrameReader":
        if isinstance(s, str):
            fields = []
            for part in s.split(","):
                name, tname = part.strip().rsplit(" ", 1)
                fields.append(T.StructField(name.strip(),
                                            T.type_for_name(tname)))
            s = T.StructType(fields)
        self._schema = s
        return self

    def load(self, path=None) -> "Any":
        from .sql.dataframe import DataFrame
        if path is None:
            raise AnalysisException("load() requires a path")
        paths = [path] if isinstance(path, str) else list(path)
        if self._schema is not None:
            schema = self._schema
        elif self._fmt == "parquet":
            # schema from footers only — a wide table must not be READ to
            # be *referenced*; pruning decides what the query's scan loads
            schema = _parquet_schema(paths)
        elif self._fmt == "orc":
            schema = _orc_schema(paths)
        elif self._fmt == "jdbc":
            from . import jdbc as _jdbc
            schema = _jdbc.table_schema(paths[0], self._options)
        else:
            schema = _load_batch(self._fmt, paths, self._options).schema
        rel = L.FileRelation(self._fmt, paths, schema, self._options)
        return DataFrame(self._session, rel)

    def parquet(self, *paths) -> "Any":
        return self.format("parquet").load(list(paths) if len(paths) > 1
                                           else paths[0])

    def orc(self, *paths) -> "Any":
        return self.format("orc").load(list(paths) if len(paths) > 1
                                       else paths[0])

    def csv(self, path, header=None, sep=None, inferSchema=None,
            nullValue=None) -> "Any":
        if header is not None:
            self.option("header", header)
        if sep is not None:
            self.option("sep", sep)
        if inferSchema is not None:
            self.option("inferschema", inferSchema)
        if nullValue is not None:
            self.option("nullvalue", nullValue)
        return self.format("csv").load(path)

    def json(self, path) -> "Any":
        return self.format("json").load(path)

    def text(self, path) -> "Any":
        return self.format("text").load(path)

    def jdbc(self, url: str, table: str = None, column: str = None,
             lowerBound=None, upperBound=None, numPartitions=None,
             predicates=None, properties=None) -> "Any":
        """Relational source over DB-API connections
        (`DataFrameReader.jdbc`, `JDBCRelation.columnPartition` stride
        partitioning).  `predicates` is a list of SQL strings, one read
        partition each; or (`column`, `lowerBound`, `upperBound`,
        `numPartitions`) stride-partitions a numeric column."""
        self.format("jdbc").option("url", url)
        if table is not None:
            self.option("dbtable", table)
        if column is not None:
            if lowerBound is None or upperBound is None \
                    or numPartitions is None:
                raise AnalysisException(
                    "jdbc partitioning requires column, lowerBound, "
                    "upperBound and numPartitions together")
            self.option("partitioncolumn", column)
            self.option("lowerbound", int(lowerBound))
            self.option("upperbound", int(upperBound))
            self.option("numpartitions", int(numPartitions))
        if predicates:
            self.option("predicates", "\x1f".join(predicates))
        for k, v in (properties or {}).items():
            self.option(k, v)
        return self.load(url)

    def table(self, name: str) -> "Any":
        return self._session.table(name)


# ---------------------------------------------------------------------------
# DataFrameWriter (`sql/DataFrameWriter.scala` analog)
# ---------------------------------------------------------------------------

class DataFrameWriter:
    def __init__(self, df):
        self._df = df
        self._fmt = "parquet"
        self._mode = "errorifexists"
        self._options: Dict[str, str] = {}
        self._partition_by: List[str] = []

    def format(self, source: str) -> "DataFrameWriter":
        self._fmt = source.lower()
        return self

    def mode(self, m: str) -> "DataFrameWriter":
        m = m.lower()
        if m not in ("overwrite", "append", "ignore", "error", "errorifexists"):
            raise AnalysisException(f"unknown save mode: {m}")
        self._mode = "errorifexists" if m == "error" else m
        return self

    def option(self, key: str, value) -> "DataFrameWriter":
        self._options[str(key).lower()] = str(value)
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    # -- save paths -------------------------------------------------------
    def _arrow_table(self, df):
        import pyarrow as pa
        batch = df._execute()
        schema = batch.schema
        rows = batch.to_pylist()
        cols = list(zip(*rows)) if rows else [[] for _ in schema.fields]
        arrays = []
        for field, col in zip(schema.fields, cols):
            arrays.append(pa.array(list(col), _engine_to_arrow(field.dataType)))
        return pa.table(dict(zip(schema.names, arrays)))

    def _prepare_dir(self, path: str) -> bool:
        """Returns False if the write should be skipped (ignore mode)."""
        if os.path.exists(path) and os.listdir(path):
            if self._mode == "errorifexists":
                raise AnalysisException(f"path {path} already exists")
            if self._mode == "ignore":
                return False
            if self._mode == "overwrite":
                import shutil
                shutil.rmtree(path)
        os.makedirs(path, exist_ok=True)
        return True

    def _next_part(self, path: str, ext: str) -> str:
        existing = len([f for f in os.listdir(path)
                        if f.startswith("part-")]) if os.path.exists(path) else 0
        return os.path.join(path, f"part-{existing:05d}{ext}")

    def _write_table(self, table, path: str, ext: str,
                     out: Optional[str] = None) -> None:
        import pyarrow as pa
        os.makedirs(path, exist_ok=True)
        if out is None:
            # batch writes pick the next free part slot; streaming sinks
            # pass an explicit deterministic target instead (idempotent
            # replay must overwrite, not append a new part)
            out = self._next_part(path, ext)
        if self._fmt == "parquet":
            import pyarrow.parquet as pq
            pq.write_table(table, out)
        elif self._fmt == "csv":
            import pyarrow.csv as pacsv
            header = str(self._options.get("header", "false")).lower() == "true"
            opts = pacsv.WriteOptions(include_header=header)
            pacsv.write_csv(table, out, opts)
        elif self._fmt == "json":
            with open(out, "w", encoding="utf-8") as f:
                for row in table.to_pylist():
                    f.write(_json.dumps(row, default=str) + "\n")
        elif self._fmt == "text":
            if table.num_columns != 1:
                raise AnalysisException("text format writes exactly 1 column")
            with open(out, "w", encoding="utf-8") as f:
                for v in table.columns[0].to_pylist():
                    f.write(("" if v is None else str(v)) + "\n")
        elif self._fmt == "orc":
            import pyarrow.orc as paorc
            paorc.write_table(table, out)
        else:
            raise AnalysisException(f"unsupported format: {self._fmt}")

    def save(self, path: str) -> None:
        ext = {"parquet": ".parquet", "csv": ".csv",
               "json": ".json", "text": ".txt", "orc": ".orc"}[self._fmt]
        if not self._prepare_dir(path):
            return
        table = self._arrow_table(self._df)
        if self._partition_by:
            import pyarrow as pa
            names = table.column_names
            for p in self._partition_by:
                if p not in names:
                    raise AnalysisException(f"partition column {p} not found")
            keep = [n for n in names if n not in self._partition_by]
            pydict = table.to_pydict()
            rows = list(zip(*[pydict[n] for n in names])) if table.num_rows \
                else []
            groups: Dict[tuple, List[tuple]] = {}
            for r in rows:
                key = tuple(r[names.index(p)] for p in self._partition_by)
                groups.setdefault(key, []).append(r)
            for key, grp in groups.items():
                sub = path
                for p, v in zip(self._partition_by, key):
                    sub = os.path.join(sub, f"{p}={v}")
                cols = list(zip(*grp))
                sub_table = pa.table({
                    n: pa.array(list(cols[names.index(n)]),
                                table.schema.field(n).type) for n in keep})
                self._write_table(sub_table, sub, ext)
        else:
            self._write_table(table, path, ext)
        open(os.path.join(path, "_SUCCESS"), "w").close()
        # DataFrame-API writes mutate the same paths the SQL commands do
        # (CREATE TABLE AS / INSERT INTO route through this writer): a
        # serving plan cache holding entries that READ this path would
        # replay stale capacities/CBO sides, so the write goes through
        # the same invalidation hook the SQL commands use
        session = getattr(self._df, "session", None)
        invalidate = getattr(session, "_invalidate_plan_cache", None)
        if invalidate is not None:
            invalidate(path=os.path.abspath(path))

    def parquet(self, path: str) -> None:
        self.format("parquet").save(path)

    def orc(self, path: str) -> None:
        self.format("orc").save(path)

    def csv(self, path: str, header=None) -> None:
        if header is not None:
            self.option("header", header)
        self.format("csv").save(path)

    def json(self, path: str) -> None:
        self.format("json").save(path)

    def text(self, path: str) -> None:
        self.format("text").save(path)

    def jdbc(self, url: str, table: str, mode: str = None,
             properties=None) -> None:
        """Write into a relational table over a DB-API connection
        (`DataFrameWriter.jdbc` / `JdbcUtils.saveTable`): DDL derived
        from the schema, rows in one batched-INSERT transaction."""
        from . import jdbc as _jdbc
        if mode is not None:
            self.mode(mode)
        opts = dict(self._options)
        for k, v in (properties or {}).items():
            opts[str(k).lower()] = str(v)
        _jdbc.write_table(self._arrow_table(self._df), url, table,
                          self._mode, opts)

    def saveAsTable(self, name: str) -> None:
        """Persist as a catalog table under the warehouse dir
        (`DataFrameWriter.saveAsTable`)."""
        self._df.session.catalog.save_table(
            name, self._df, self._fmt, self._mode, self._options,
            self._partition_by)
