"""Columnar batches: the device-native data representation.

This replaces the reference's row format stack — ``UnsafeRow.java:62``,
``ColumnarBatch.java:46`` / ``ColumnVector.java:60`` — with a
structure-of-arrays layout designed for XLA:

* every column is ONE flat device array of a fixed-width dtype, padded to a
  static ``capacity`` (power of two) so shapes never depend on data;
* row existence (``row_valid``) and per-column NULLs (``ColumnVector.valid``)
  are separate boolean masks (Arrow-style validity);
* strings/binary are dictionary codes (``int32``) into a host-side,
  lexicographically sorted dictionary, so all device ops on strings are
  integer ops (see ``types.StringType``);
* a ``ColumnBatch`` is a registered JAX pytree, so whole operator pipelines
  over batches trace into a single XLA program (the WholeStageCodegen analog).

Filtering does NOT compact (it just ANDs ``row_valid``); ``compact`` is an
explicit operator applied only where order/size matters (exchange, limit).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import tracing
from . import types as T

Array = Any  # np.ndarray | jax.Array

MIN_CAPACITY = 8


def pad_capacity(n: int) -> int:
    """Round row count up to the static batch capacity (next power of two)."""
    c = MIN_CAPACITY
    while c < n:
        c <<= 1
    return c


def _xp(arr: Array):
    return jnp if isinstance(arr, jax.Array) else np


def pad_to_capacity(batch: "ColumnBatch", cap: int) -> "ColumnBatch":
    """Grow a HOST batch to a larger static capacity.

    Streamed scans pad every batch to ONE shared capacity so the per-batch
    jitted step compiles once (the multi-batch analog of the reference's
    fixed ColumnarBatch capacity, `ColumnarBatch.java:46`)."""
    if cap < batch.capacity:
        raise ValueError(f"cannot shrink batch {batch.capacity} -> {cap}")
    if cap == batch.capacity:
        return batch
    pad = cap - batch.capacity
    vectors = []
    for v in batch.vectors:
        data = np.concatenate(
            [np.asarray(v.data), np.zeros(pad, np.asarray(v.data).dtype)])
        valid = None
        if v.valid is not None:
            valid = np.concatenate(
                [np.asarray(v.valid), np.zeros(pad, bool)])
        vectors.append(ColumnVector(data, v.dtype, valid, v.dictionary))
    rv = np.zeros(cap, bool)
    rv[:batch.capacity] = np.asarray(batch.row_valid_or_true())
    return ColumnBatch(list(batch.names), vectors, rv, cap)


def normalize_valids(batch: "ColumnBatch") -> "ColumnBatch":
    """Materialize every implicit (None) validity mask as an explicit array.

    Validity masks live in the pytree STRUCTURE (None vs array), so two scan
    batches that differ only in "column happened to contain a null" would
    retrace the jitted per-batch step; normalizing makes the treedef stable
    across a streamed scan."""
    vectors = [
        v if v.valid is not None else
        ColumnVector(v.data, v.dtype,
                     np.ones(batch.capacity, bool), v.dictionary)
        for v in batch.vectors
    ]
    rv = batch.row_valid
    if rv is None:
        rv = np.ones(batch.capacity, bool)
    return ColumnBatch(list(batch.names), vectors, rv, batch.capacity)


#: running total of dictionary codes decoded back into Python words —
#: the "late materialization" boundary.  Codes that stay codes through
#: exchange/join/group never show up here; only collect()-style output
#: does.  Plain module int: metrics-grade accuracy is enough.
_LATE_MATERIALIZED_ROWS = 0


def late_materialized_rows() -> int:
    """Total dictionary-encoded values decoded to Python objects so far
    (process-wide; gauge consumers diff against a baseline)."""
    return _LATE_MATERIALIZED_ROWS


#: running total of run-encoded values expanded to dense arrays — the run
#: analog of ``_LATE_MATERIALIZED_ROWS``.  Columns that stay run-encoded
#: through filter/aggregate/join never show up here; only operators that
#: genuinely need the dense form (or ``to_pylist``) do.
_RUNS_MATERIALIZED = 0

#: running total of rows whose operator work was done at run granularity
#: (one predicate eval / one probe / one multiply per run instead of per
#: row) — proof the run-aware fast paths actually fired.
_RUN_AWARE_OP_ROWS = 0


def runs_materialized() -> int:
    """Total run-encoded values expanded to dense arrays so far
    (process-wide; gauge consumers diff against a baseline)."""
    return _RUNS_MATERIALIZED


def run_aware_op_rows() -> int:
    """Total rows served by run-aware operator fast paths so far
    (process-wide; gauge consumers diff against a baseline)."""
    return _RUN_AWARE_OP_ROWS


def bump_run_aware(n: int) -> None:
    """Credit ``n`` rows to the run-aware fast-path counter."""
    global _RUN_AWARE_OP_ROWS
    _RUN_AWARE_OP_ROWS += int(n)


#: run-plane activity — the device-side close of the run line.  A "plane"
#: is the fixed-capacity pytree form of a run table (see
#: ``PlaneColumnVector``): stages count stage entries that carried at
#: least one plane input, rows count the dense rows those planes stood in
#: for, overflows count run tables too large to compress (fell back to
#: counted materialization at the boundary), and expansions count
#: in-TRACE dense expansions (an untaught operator read ``.data`` inside
#: a jitted stage — paid in device gathers, never host inflation, and
#: counted once per trace, not per dispatch).
_RUN_PLANE_STAGES = 0
_RUN_PLANE_ROWS = 0
_RUN_PLANE_OVERFLOWS = 0
_RUN_PLANE_EXPANSIONS = 0


def run_plane_stages() -> int:
    """Stage dispatches that carried at least one run-plane input
    (process-wide; gauge consumers diff against a baseline)."""
    return _RUN_PLANE_STAGES


def run_plane_rows() -> int:
    """Dense rows that crossed the jit boundary as run planes instead of
    materialized arrays (process-wide)."""
    return _RUN_PLANE_ROWS


def run_plane_overflows() -> int:
    """Run vectors whose run count was too large for a compressing plane
    — materialized counted at the boundary instead (process-wide)."""
    return _RUN_PLANE_OVERFLOWS


def run_plane_expansions() -> int:
    """In-trace searchsorted-gather expansions of a plane by an untaught
    operator — once per trace, not per dispatch (process-wide)."""
    return _RUN_PLANE_EXPANSIONS


def bump_plane_stage() -> None:
    global _RUN_PLANE_STAGES
    _RUN_PLANE_STAGES += 1


def bump_plane_rows(n: int) -> None:
    global _RUN_PLANE_ROWS
    _RUN_PLANE_ROWS += int(n)


def bump_plane_overflow() -> None:
    global _RUN_PLANE_OVERFLOWS
    _RUN_PLANE_OVERFLOWS += 1


def encode_strings(values: Sequence[Optional[str]]) -> Tuple[np.ndarray, Tuple[str, ...]]:
    """Dictionary-encode strings: codes into a SORTED dictionary.

    Sorted dictionaries make code order == lexicographic order, so device
    sorts/compares on codes are string-correct (the UTF8String replacement).
    Returns (int32 codes with -1 for None, dictionary tuple).
    """
    present = sorted({v for v in values if v is not None})
    lookup = {v: i for i, v in enumerate(present)}
    codes = np.fromiter(
        (lookup[v] if v is not None else -1 for v in values),
        dtype=np.int32, count=len(values),
    )
    return codes, tuple(present)


def merge_dictionaries(
    a: Tuple[str, ...], b: Tuple[str, ...]
) -> Tuple[Tuple[str, ...], np.ndarray, np.ndarray]:
    """Merge two sorted dictionaries; return (merged, remap_a, remap_b).

    ``remap_x[old_code] -> new_code``. Needed when two independently encoded
    string columns meet (union, join keys, comparisons).
    """
    if a is b or a == b:
        # one dictionary on both sides (two reads of one relation, the arms
        # of a self-join): nothing to merge, whatever its size
        same = np.arange(len(a), dtype=np.int32)
        return a, same, same
    with tracing.span("dict.unify", words=len(a) + len(b)):
        merged = tuple(sorted(set(a) | set(b)))
        lookup = {v: i for i, v in enumerate(merged)}
        remap_a = np.fromiter((lookup[v] for v in a), dtype=np.int32,
                              count=len(a))
        remap_b = np.fromiter((lookup[v] for v in b), dtype=np.int32,
                              count=len(b))
    return merged, remap_a, remap_b


class ColumnVector:
    """One column: data array + optional validity mask (+ string dictionary).

    ``valid is None`` means "no NULLs". The dictionary is host metadata
    (static under jit); data/valid may be numpy (host) or jax.Array (device).
    """

    __slots__ = ("data", "valid", "dtype", "dictionary")

    def __init__(self, data: Array, dtype: T.DataType,
                 valid: Optional[Array] = None,
                 dictionary: Optional[Tuple[str, ...]] = None):
        self.data = data
        self.dtype = dtype
        self.valid = valid
        self.dictionary = dictionary

    def __repr__(self) -> str:  # pragma: no cover
        return f"ColumnVector({self.dtype!r}, shape={getattr(self.data, 'shape', None)})"

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def with_data(self, data: Array, valid: Union[Array, None, type(...)] = ...) -> "ColumnVector":
        """New vector with replaced data; ``valid=...`` keeps the old mask."""
        v = self.valid if valid is ... else valid
        return ColumnVector(data, self.dtype, v, self.dictionary)

    def valid_or_true(self) -> Array:
        if self.valid is not None:
            return self.valid
        return _xp(self.data).ones(self.data.shape[0], dtype=bool)

    # ---- host/device movement ------------------------------------------
    def to_device(self) -> "ColumnVector":
        return ColumnVector(jnp.asarray(self.data), self.dtype,
                            None if self.valid is None else jnp.asarray(self.valid),
                            self.dictionary)

    def to_host(self) -> "ColumnVector":
        return ColumnVector(np.asarray(self.data), self.dtype,
                            None if self.valid is None else np.asarray(self.valid),
                            self.dictionary)

    def to_pylist(self, row_valid: Optional[Array] = None) -> List[Any]:
        """Decode to Python objects (None for NULL); for collect()."""
        data = np.asarray(self.data)
        valid = np.ones(len(data), bool) if self.valid is None else np.asarray(self.valid)
        if row_valid is not None:
            sel = np.asarray(row_valid)
            data, valid = data[sel], valid[sel]
        if self.dictionary is None:
            return self._to_pylist(data, valid)
        global _LATE_MATERIALIZED_ROWS
        _LATE_MATERIALIZED_ROWS += len(data)
        with tracing.span("dict.decode", rows=len(data)):
            return self._to_pylist(data, valid)

    def _to_pylist(self, data: np.ndarray, valid: np.ndarray) -> List[Any]:
        out: List[Any] = []
        dt = self.dtype
        for i in range(len(data)):
            if not valid[i]:
                out.append(None)
            elif isinstance(dt, T.ArrayType):
                ed = dt.element_type
                row = data[i]
                if ed.is_fractional:
                    live = row[~np.isnan(row.astype(np.float64))]
                    out.append([float(x) for x in live])
                elif ed.is_string:
                    codes = row[row >= 0]
                    out.append([
                        self.dictionary[int(c)] if self.dictionary is not None
                        and 0 <= int(c) < len(self.dictionary) else None
                        for c in codes])
                else:
                    sent = dt.element_sentinel()
                    live = row[row != sent]
                    out.append([int(x) for x in live])
            elif dt.is_string or isinstance(dt, T.BinaryType):
                code = int(data[i])
                out.append(self.dictionary[code] if (self.dictionary is not None and 0 <= code < len(self.dictionary)) else None)
            elif isinstance(dt, T.BooleanType):
                out.append(bool(data[i]))
            elif isinstance(dt, T.DecimalType):
                out.append(float(data[i]) / (10 ** dt.scale))
            elif isinstance(dt, T.DateType):
                out.append(np.datetime64(int(data[i]), "D").astype("datetime64[D]").item())
            elif isinstance(dt, T.TimestampType):
                out.append(np.datetime64(int(data[i]), "us").item())
            elif dt.is_fractional:
                out.append(float(data[i]))
            else:
                out.append(int(data[i]))
        return out


class ColumnBatch:
    """A fixed-capacity batch of columns plus a row-existence mask.

    Registered as a JAX pytree: arrays are leaves; names/dtypes/dictionaries/
    capacity are static aux data, so operator pipelines jit cleanly.
    """

    # _cache_uid: lazily-assigned identity for plan cache keys
    # (memory.py) -- id() could be recycled after GC
    __slots__ = ("names", "vectors", "row_valid", "capacity",
                 "_cache_uid")

    def __init__(self, names: Sequence[str], vectors: Sequence[ColumnVector],
                 row_valid: Optional[Array], capacity: int):
        assert len(names) == len(vectors)
        self.names = list(names)
        self.vectors = list(vectors)
        self.row_valid = row_valid
        self.capacity = capacity

    # -- constructors -----------------------------------------------------
    @staticmethod
    def from_arrays(data: Dict[str, Any], num_rows: Optional[int] = None,
                    capacity: Optional[int] = None,
                    schema: Optional[T.StructType] = None) -> "ColumnBatch":
        """Build from host arrays / lists; pads to a static capacity."""
        names = list(data.keys())
        if num_rows is None:
            num_rows = len(next(iter(data.values()))) if names else 0
        cap = capacity or pad_capacity(num_rows)
        if cap < num_rows:
            raise ValueError(f"capacity {cap} < num_rows {num_rows}")
        vectors: List[ColumnVector] = []
        for name in names:
            raw = data[name]
            dt = schema[name].dataType if schema is not None else None
            vec = _ingest_column(raw, num_rows, cap, dt)
            vectors.append(vec)
        row_valid = None
        if cap != num_rows:
            rv = np.zeros(cap, dtype=bool)
            rv[:num_rows] = True
            row_valid = rv
        return ColumnBatch(names, vectors, row_valid, cap)

    @staticmethod
    def from_pandas(df, capacity: Optional[int] = None) -> "ColumnBatch":
        import pandas as pd
        data = {}
        for name in df.columns:
            s = df[name]
            if s.dtype == object or str(s.dtype) in ("string", "str"):
                na = s.isna().to_numpy()
                data[str(name)] = [None if na[i] else v for i, v in enumerate(s.tolist())]
            elif str(s.dtype).startswith(("Int", "Float", "boolean")):  # nullable ext dtypes
                na = s.isna().to_numpy()
                data[str(name)] = [None if na[i] else v for i, v in enumerate(s.tolist())]
            else:
                data[str(name)] = s.to_numpy()
        return ColumnBatch.from_arrays(data, num_rows=len(df), capacity=capacity)

    @staticmethod
    def empty(schema: T.StructType, capacity: int = MIN_CAPACITY) -> "ColumnBatch":
        vectors = []
        for f in schema.fields:
            arr = np.zeros(capacity, dtype=f.dataType.np_dtype)
            d = () if (f.dataType.is_string or isinstance(f.dataType, T.BinaryType)) else None
            vectors.append(ColumnVector(arr, f.dataType, None, d))
        return ColumnBatch(schema.names, vectors, np.zeros(capacity, bool), capacity)

    # -- schema & access --------------------------------------------------
    @property
    def schema(self) -> T.StructType:
        return T.StructType([
            T.StructField(n, v.dtype, v.valid is not None)
            for n, v in zip(self.names, self.vectors)
        ])

    def column(self, name: str) -> ColumnVector:
        return self.vectors[self.names.index(name)]

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def with_columns(self, names: Sequence[str], vectors: Sequence[ColumnVector]) -> "ColumnBatch":
        return ColumnBatch(list(names), list(vectors), self.row_valid, self.capacity)

    def row_valid_or_true(self) -> Array:
        if self.row_valid is not None:
            return self.row_valid
        # probe device residency without touching .data — that would
        # materialize a lazy RunColumnVector (host) or expand a
        # PlaneColumnVector (device) before any operator asked for rows
        def _probe(v):
            if isinstance(v, PlaneColumnVector):
                return v.plane_values if v._dense is None else v._dense
            if isinstance(v, RunColumnVector):
                return v._dense
            return v.data
        xp = jnp if any(isinstance(_probe(v), jax.Array)
                        for v in self.vectors) else np
        return xp.ones(self.capacity, dtype=bool)

    def num_rows(self):
        """Number of live rows — a traced scalar under jit, int on host."""
        if self.row_valid is None:
            return self.capacity
        xp = _xp(self.row_valid)
        return xp.sum(self.row_valid)

    # -- movement ---------------------------------------------------------
    def to_device(self) -> "ColumnBatch":
        rv = None if self.row_valid is None else jnp.asarray(self.row_valid)
        return ColumnBatch(self.names, [v.to_device() for v in self.vectors], rv, self.capacity)

    def to_host(self) -> "ColumnBatch":
        rv = None if self.row_valid is None else np.asarray(self.row_valid)
        return ColumnBatch(self.names, [v.to_host() for v in self.vectors], rv, self.capacity)

    # -- output -----------------------------------------------------------
    def to_pylist(self) -> List[tuple]:
        """Rows as tuples (collect() decode path)."""
        rv = None if self.row_valid is None else np.asarray(self.row_valid)
        cols = [v.to_pylist(rv) for v in self.vectors]
        if not cols:
            n = int(rv.sum()) if rv is not None else self.capacity
            return [() for _ in range(n)]
        return list(zip(*cols))

    def to_pandas(self):
        import pandas as pd
        rows = self.to_pylist()
        return pd.DataFrame(rows, columns=self.names)

    def __repr__(self) -> str:  # pragma: no cover
        return f"ColumnBatch({self.schema.simpleString()}, capacity={self.capacity})"


class RunColumnVector(ColumnVector):
    """Run-length encoded column: ``(run_values, run_lengths)`` standing in
    for a dense array of ``sum(run_lengths)`` elements.

    The dense form is produced lazily on the first ``.data`` access (counted
    in ``runs_materialized``); run-aware operators read ``run_values`` /
    ``run_lengths`` directly and never pay the expansion.  Everything else —
    validity, dtype, dictionary, pytree participation — behaves exactly like
    a dense ``ColumnVector``, so the lazy form is a drop-in safety net: any
    code path that was not taught about runs simply materializes."""

    __slots__ = ("run_values", "run_lengths", "_n", "_dense")

    def __init__(self, run_values: Array, run_lengths: Array,
                 dtype: T.DataType, valid: Optional[Array] = None,
                 dictionary: Optional[Tuple[str, ...]] = None):
        self.run_values = np.asarray(run_values)
        self.run_lengths = np.asarray(run_lengths, dtype=np.int64)
        self._n = int(self.run_lengths.sum())
        self._dense = None
        self.dtype = dtype
        self.valid = valid
        self.dictionary = dictionary

    def __repr__(self) -> str:  # pragma: no cover
        return (f"RunColumnVector({self.dtype!r}, n={self._n}, "
                f"runs={len(self.run_values)}, "
                f"materialized={self._dense is not None})")

    @property
    def is_materialized(self) -> bool:
        return self._dense is not None

    @property
    def data(self) -> Array:
        # shadows the parent's `data` slot: expansion happens here, once
        if self._dense is None:
            global _RUNS_MATERIALIZED
            _RUNS_MATERIALIZED += self._n
            self._dense = np.repeat(self.run_values, self.run_lengths)
        return self._dense

    @property
    def capacity(self) -> int:
        return self._n

    def valid_or_true(self) -> Array:
        if self.valid is not None:
            return self.valid
        return np.ones(self._n, dtype=bool)

    def to_host(self) -> "ColumnVector":
        return self  # run tables are always host arrays

    def to_device(self) -> "ColumnVector":
        if self._dense is None:
            # expand ON DEVICE: the run table crosses as two small
            # arrays and the repeat runs compiled (shape-static via
            # total_repeat_length) — the counted host expansion in
            # ``.data`` is reserved for operators that genuinely need
            # dense HOST rows
            data = jnp.repeat(jnp.asarray(self.run_values),
                              jnp.asarray(self.run_lengths),
                              total_repeat_length=self._n)
        else:
            data = jnp.asarray(self._dense)
        return ColumnVector(data, self.dtype,
                            None if self.valid is None
                            else jnp.asarray(self.valid),
                            self.dictionary)

    def with_run_values(self, run_values: Array,
                        dictionary: Union[Tuple[str, ...], None,
                                          type(...)] = ...) -> "RunColumnVector":
        """New run vector with remapped run values (same run structure) —
        the seam dictionary-code remapping uses to stay run-preserving."""
        d = self.dictionary if dictionary is ... else dictionary
        return RunColumnVector(run_values, self.run_lengths, self.dtype,
                               self.valid, d)


def unmaterialized_runs(v: ColumnVector) -> Optional[RunColumnVector]:
    """``v`` if it is a run-encoded column whose dense form was never built
    (so run-granularity work is still a win), else None."""
    if isinstance(v, RunColumnVector) and not v.is_materialized:
        return v
    return None


class PlaneColumnVector(ColumnVector):
    """Fixed-capacity DEVICE form of a run table — the shape-stable pytree
    citizen that lets compressed columns cross the jit boundary.

    ``plane_values`` (run values zero-padded to the plane capacity, a
    ``pad_capacity`` bucket of the run count) and ``plane_lengths``
    (int64 run lengths, zero-padded) are the two pytree leaves; the dense
    capacity they stand in for is static aux.  Real runs are exactly the
    ``lengths > 0`` prefix — RLE never emits zero-length runs, so the
    zero padding is unambiguous.  Taught jit-lane kernels (segmented
    filter, keyless count/sum/min/max, bare-column project) read the
    plane directly; any untaught operator that asks for ``.data`` gets a
    memoized in-trace searchsorted-gather expansion (counted in
    ``run_plane_expansions``) — byte-identical, fused and dead-code
    eliminated by XLA when unused, and it never touches the host
    ``runs_materialized`` counter.  Planes are a LOCAL stage form: mesh
    (shard_map) stages never receive them, because slicing a plane along
    the run axis would not slice the rows it encodes."""

    __slots__ = ("plane_values", "plane_lengths", "n_runs", "_capacity",
                 "_dense")

    def __init__(self, plane_values: Array, plane_lengths: Array,
                 dtype: T.DataType, capacity: int,
                 valid: Optional[Array] = None,
                 dictionary: Optional[Tuple[str, ...]] = None,
                 n_runs: Optional[int] = None):
        self.plane_values = plane_values
        self.plane_lengths = plane_lengths
        self.n_runs = None if n_runs is None else int(n_runs)
        self._capacity = int(capacity)
        self._dense = None
        self.dtype = dtype
        self.valid = valid
        self.dictionary = dictionary

    @classmethod
    def from_runs(cls, rv: RunColumnVector,
                  plane_cap: int, device: bool = True) -> "PlaneColumnVector":
        """Pad a host run table into a plane of capacity ``plane_cap``
        (a ``pad_capacity`` bucket ≥ the run count)."""
        nr = len(rv.run_values)
        values = np.zeros(plane_cap, rv.run_values.dtype)
        values[:nr] = rv.run_values
        lengths = np.zeros(plane_cap, np.int64)
        lengths[:nr] = rv.run_lengths
        if device:
            values, lengths = jnp.asarray(values), jnp.asarray(lengths)
        return cls(values, lengths, rv.dtype, rv.capacity, rv.valid,
                   rv.dictionary, n_runs=nr)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"PlaneColumnVector({self.dtype!r}, capacity={self._capacity},"
                f" plane={int(self.plane_values.shape[0])},"
                f" runs={self.n_runs}, expanded={self._dense is not None})")

    @property
    def plane_capacity(self) -> int:
        return int(self.plane_values.shape[0])

    @property
    def is_expanded(self) -> bool:
        return self._dense is not None

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def data(self) -> Array:
        # shadows the parent's `data` slot: the untaught-operator safety
        # net — one memoized in-trace expansion per trace, never counted
        # as host materialization
        if self._dense is None:
            global _RUN_PLANE_EXPANSIONS
            _RUN_PLANE_EXPANSIONS += 1
            from . import kernels
            xp = jnp if isinstance(self.plane_values, jax.Array) else np
            self._dense = kernels.run_expand(
                xp, self.plane_values, self.plane_lengths, self._capacity)
        return self._dense

    def valid_or_true(self) -> Array:
        if self.valid is not None:
            return self.valid
        xp = jnp if isinstance(self.plane_values, jax.Array) else np
        return xp.ones(self._capacity, dtype=bool)

    def to_device(self) -> "ColumnVector":
        if isinstance(self.plane_values, jax.Array):
            return self
        return PlaneColumnVector(
            jnp.asarray(self.plane_values), jnp.asarray(self.plane_lengths),
            self.dtype, self._capacity,
            None if self.valid is None else jnp.asarray(self.valid),
            self.dictionary, n_runs=self.n_runs)

    def to_host(self) -> "ColumnVector":
        # leaving the device lane: hand back a dense host vector (planes
        # have no host consumers; the expansion is the memoized one)
        return ColumnVector(np.asarray(self.data), self.dtype,
                            None if self.valid is None
                            else np.asarray(self.valid),
                            self.dictionary)


def unexpanded_plane(v: ColumnVector) -> Optional[PlaneColumnVector]:
    """``v`` if it is a run plane whose dense form was never demanded (so
    plane-granularity work is still a win), else None."""
    if isinstance(v, PlaneColumnVector) and v._dense is None:
        return v
    return None


class PrebuiltColumn:
    """Already-decoded column (data array + engine type + validity) — the
    vectorized readers hand these to ``from_arrays`` so nullable numeric
    columns never round-trip through Python objects."""

    __slots__ = ("data", "dtype", "valid")

    def __init__(self, data: np.ndarray, dtype: T.DataType,
                 valid: Optional[np.ndarray]):
        self.data = data
        self.dtype = dtype
        self.valid = valid

    def __len__(self):
        return len(self.data)


def _ingest_column(raw: Any, num_rows: int, cap: int,
                   dtype: Optional[T.DataType]) -> ColumnVector:
    """Convert one host column (list/ndarray) into a padded ColumnVector."""
    dictionary: Optional[Tuple[str, ...]] = None
    valid: Optional[np.ndarray] = None

    if isinstance(raw, PrebuiltColumn):
        data = raw.data
        valid = raw.valid
        if len(data) < cap:
            data = np.concatenate(
                [data, np.zeros(cap - len(data), data.dtype)])
            if valid is not None:
                valid = np.concatenate(
                    [valid, np.zeros(cap - len(raw.valid), bool)])
        return ColumnVector(data, raw.dtype, valid, None)

    # fixed-width vector column (ML feature vectors): 2D data, ArrayType
    if isinstance(raw, np.ndarray) and raw.ndim == 2:
        dt = dtype if isinstance(dtype, T.ArrayType) else T.ArrayType(T.float64)
        data = raw.astype(dt.element_type.np_dtype)
        if len(data) < cap:
            pad = np.zeros((cap - len(data),) + data.shape[1:], data.dtype)
            data = np.concatenate([data, pad])
        return ColumnVector(data, dt, None, None)
    if (not isinstance(raw, np.ndarray) and len(raw)
            and isinstance(next((v for v in raw if v is not None), None),
                           (list, tuple, np.ndarray))):
        values = [([] if v is None else list(v)) for v in raw]
        width = max((len(v) for v in values), default=1) or 1
        nulls = np.fromiter((v is None for v in raw), bool, count=len(values))
        if isinstance(dtype, T.ArrayType):
            ed = dtype.element_type
        else:
            all_int = all(
                isinstance(x, (int, np.integer))
                and not isinstance(x, bool)
                for v in values for x in v if x is not None)
            ed = T.int64 if all_int and any(len(v) for v in values) \
                else T.float64
        dt = dtype if isinstance(dtype, T.ArrayType) else T.ArrayType(ed)
        # ragged tails / None elements carry the ELEMENT SENTINEL (NaN for
        # fractional, element_sentinel() for integral) — the device layout
        # to_pylist/array kernels treat as dead, never silent zeros
        sent = np.nan if ed.is_fractional else dt.element_sentinel()
        mat = np.full((len(values), width), sent, ed.np_dtype)
        for i, v in enumerate(values):
            for j, x in enumerate(v):
                if x is not None and not (isinstance(x, float)
                                          and np.isnan(x)):
                    mat[i, j] = x
        if len(mat) < cap:
            mat = np.concatenate(
                [mat, np.full((cap - len(mat), width), sent, ed.np_dtype)])
        valid = None if not nulls.any() else np.concatenate(
            [~nulls, np.zeros(cap - len(values), bool)])
        return ColumnVector(mat, dt, valid, None)

    if isinstance(raw, np.ndarray) and raw.dtype.kind not in ("O", "U", "S"):
        if raw.dtype.kind == "M":  # datetime64
            if isinstance(dtype, T.DateType):
                data = raw.astype("datetime64[D]").astype(np.int32)
                dt = dtype
            else:
                data = raw.astype("datetime64[us]").astype(np.int64)
                dt = dtype or T.timestamp
        elif isinstance(dtype, T.DecimalType):
            dt = dtype
            fl = raw.astype(np.float64)
            nan = np.isnan(fl)
            data = np.round(np.where(nan, 0.0, fl) * 10 ** dt.scale).astype(np.int64)
            if nan.any():
                valid = ~nan
        elif raw.dtype.kind == "f":
            dt = dtype or T.np_dtype_to_engine(raw.dtype)
            nan = np.isnan(raw)
            data = np.where(nan, 0.0, raw).astype(dt.np_dtype)
            if nan.any():
                valid = ~nan
        else:
            dt = dtype or T.np_dtype_to_engine(raw.dtype)
            data = raw.astype(dt.np_dtype)
    else:
        values = list(raw)
        nulls = np.fromiter((v is None or (isinstance(v, float) and np.isnan(v)) for v in values),
                            dtype=bool, count=len(values))
        sample = next((v for v in values if v is not None), None)
        dt = dtype or (T.infer_type(sample) if sample is not None else T.null_type)
        if dt.is_string or isinstance(dt, T.BinaryType):
            # binary keeps bytes in the dictionary; strings coerce via str()
            conv = (lambda v: v) if isinstance(dt, T.BinaryType) else str
            codes, dictionary = encode_strings(
                [None if nulls[i] else conv(values[i]) for i in range(len(values))])
            data = np.where(codes < 0, 0, codes).astype(np.int32)
            if (codes < 0).any():
                valid = codes >= 0
        elif isinstance(dt, T.DecimalType):
            scale = 10 ** dt.scale
            data = np.fromiter(
                (0 if nulls[i] else int(round(float(values[i]) * scale)) for i in range(len(values))),
                dtype=np.int64, count=len(values))
            if nulls.any():
                valid = ~nulls
        elif isinstance(dt, T.DateType):
            data = np.fromiter(
                (0 if nulls[i] else np.datetime64(values[i], "D").astype(np.int32) for i in range(len(values))),
                dtype=np.int32, count=len(values))
            if nulls.any():
                valid = ~nulls
        elif isinstance(dt, T.TimestampType):
            data = np.fromiter(
                (0 if nulls[i] else np.datetime64(values[i], "us").astype(np.int64) for i in range(len(values))),
                dtype=np.int64, count=len(values))
            if nulls.any():
                valid = ~nulls
        else:
            data = np.fromiter(
                (dt.null_sentinel() if nulls[i] else values[i] for i in range(len(values))),
                dtype=dt.np_dtype, count=len(values))
            if nulls.any():
                valid = ~nulls

    if len(data) < cap:
        pad = np.zeros(cap - len(data), dtype=data.dtype)
        data = np.concatenate([data, pad])
        if valid is not None:
            valid = np.concatenate([valid, np.zeros(cap - len(valid), bool)])
    return ColumnVector(data, dt, valid, dictionary)


# ---------------------------------------------------------------------------
# pytree registration — makes ColumnBatch traceable end-to-end
# ---------------------------------------------------------------------------

def _batch_flatten(b: ColumnBatch):
    # a run plane contributes its (values, lengths) pair as the data child
    # (tuples are pytrees, so both pad to leaves); the per-vector plane
    # marker in aux carries n_runs (-1 when unknown) so unflatten rebuilds
    # the plane instead of a dense vector
    datas, planes = [], []
    for v in b.vectors:
        if isinstance(v, PlaneColumnVector):
            datas.append((v.plane_values, v.plane_lengths))
            planes.append(-1 if v.n_runs is None else v.n_runs)
        else:
            datas.append(v.data)
            planes.append(None)
    children = (datas, [v.valid for v in b.vectors], b.row_valid)
    aux = (tuple(b.names),
           tuple(v.dtype for v in b.vectors),
           tuple(v.dictionary for v in b.vectors),
           b.capacity,
           tuple(planes))
    return children, aux


def _batch_unflatten(aux, children):
    if len(aux) == 5:
        names, dtypes, dicts, capacity, planes = aux
    else:  # pre-plane aux (serialized treedefs): no plane vectors
        names, dtypes, dicts, capacity = aux
        planes = (None,) * len(names)
    datas, valids, row_valid = children
    # Inside shard_map/vmap the leaves are per-shard slices whose length
    # differs from the stored aux capacity — trust the arrays when possible.
    # Plane children are (values, lengths) tuples: their length is the
    # plane capacity, not the dense capacity, so they never vote here.
    for leaf in list(datas) + [row_valid]:
        if isinstance(leaf, tuple):
            continue
        shape = getattr(leaf, "shape", None)
        if shape is not None and len(shape) >= 1:
            capacity = int(shape[0])
            break
    vectors = []
    for d, v, t, dic, pl in zip(datas, valids, dtypes, dicts, planes):
        if pl is not None:
            pv, plen = d
            vectors.append(PlaneColumnVector(
                pv, plen, t, capacity, v, dic,
                n_runs=None if pl < 0 else pl))
        else:
            vectors.append(ColumnVector(d, t, v, dic))
    b = ColumnBatch.__new__(ColumnBatch)
    b.names = list(names)
    b.vectors = vectors
    b.row_valid = row_valid
    b.capacity = capacity
    return b


jax.tree_util.register_pytree_node(ColumnBatch, _batch_flatten, _batch_unflatten)
