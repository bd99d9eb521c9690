"""The only file of the benchmark that touches the program: it starts the
system under test as the configuration and the traffic file say, sends it
one statement at a time, and reads its counters.  Everything it measures
with is elsewhere in ``benchmark/`` and imports nothing of ``spark_tpu``.
"""

from __future__ import annotations

import json
import os
import shutil
import urllib.request

from . import datagen


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": int(chips)}


def peak_bytes(chips: int):
    """Highest ``peak_bytes_in_use`` over the cell's devices (None where
    the backend reports none, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = {"count": 0, "seconds": 0.0, "listening": False}


def _on_duration(event, seconds, **_kw):
    if event == _COMPILE_EVENT:
        _compiles["count"] += 1
        _compiles["seconds"] += seconds


def counters() -> dict:
    """The program's stage-cache counters, and JAX's own count of programs
    handed to the backend compiler (whichever of the program's caches —
    stage cache, distributed jit cache, streamed steps — asked for them)."""
    import jax.monitoring
    from spark_tpu.sql.stagecompile import stage_cache
    if not _compiles["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compiles["listening"] = True
    out = stage_cache().stats()
    out["xla_compiles"] = _compiles["count"]
    out["xla_compile_s"] = _compiles["seconds"]
    return out


def write_parquet(tables: dict, base: str, marker: dict, fact_files: int):
    """Every table as parquet under ``base`` (facts in ``fact_files`` files
    each), once: a marker beside a table's files that names the same seed
    and row counts spares its write.  Returns the tables written."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    wrote = []
    for name, cols in tables.items():
        d = os.path.join(base, name)
        path = os.path.join(base, name + "._GENERATED.json")
        if os.path.exists(path):
            with open(path) as fh:
                if json.load(fh) == marker:
                    continue
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        table = pa.table(cols) if isinstance(cols, dict) \
            else pa.Table.from_pandas(cols, preserve_index=False)
        parts = fact_files if datagen.is_fact(name) else 1
        step = (table.num_rows + parts - 1) // parts
        for i in range(parts):
            pq.write_table(table.slice(i * step, step),
                           os.path.join(d, f"part-{i:04d}.parquet"))
        with open(path, "w") as fh:
            json.dump(marker, fh)
        wrote.append(name)
    return wrote


class Engine:
    """The system under test, started for one cell.

    ``entry`` ``http``: an in-process ``SQLServer`` and ONE server session,
    every statement a ``POST /sql``.  ``entry`` ``session``:
    ``SparkSession.sql(...).collect()``.  ``fact_source`` ``parquet``: every
    fact a view over its files.  ``cached``: the listed columns of the fact
    ``.cache()``d on the device and registered under the table's name (the
    session entry only: HTTP has no ``CACHE TABLE``)."""

    def __init__(self, config: dict, traffic: dict, tables: dict, base: str,
                 work_dir: str):
        self.config, self.traffic = config, traffic
        self.tables, self.base = tables, base
        self.entry = traffic["entry"]
        self.fact_source = traffic["fact_source"]
        if self.entry not in ("http", "session"):
            raise ValueError(f"entry {self.entry!r}: http or session")
        if self.fact_source not in ("parquet", "cached"):
            raise ValueError(f"fact_source {self.fact_source!r}")
        if self.entry == "http" and self.fact_source == "cached":
            raise ValueError("the HTTP entry cannot cache a table")
        self._server = self._sid = None
        self._cached = []
        from spark_tpu.sql.session import SparkSession
        self.spark = SparkSession.builder.appName("benchmark").getOrCreate()
        self.spark.conf.set("spark.sql.warehouse.dir",
                            os.path.join(work_dir, "warehouse"))
        self._conf_before = {k: self.spark.conf.get(k)
                             for k in config["conf"]}
        for k, v in config["conf"].items():
            self.spark.conf.set(k, str(v))

    # -- set-up ------------------------------------------------------------
    def _path(self, table):
        return os.path.join(self.base, table)

    def _view_ddl(self, table):
        return (f"CREATE OR REPLACE TEMP VIEW {table} AS "
                f"SELECT * FROM parquet.`{self._path(table)}`")

    def start(self):
        in_memory = self.config["dimensions"] == "memory"
        if self.entry == "http":
            from spark_tpu.server import SQLServer
            self._server = SQLServer(self.spark, port=0).start()
            self._sid = self._http("/session", "POST")["sessionId"]
            if in_memory:
                raise ValueError("an HTTP session sees only views it made: "
                                 "dimensions must be parquet")
            for t in self.tables:
                self._http("/sql", "POST", {"query": self._view_ddl(t)})
            return
        for t, cols in self.tables.items():
            if not datagen.is_fact(t) and in_memory:
                self.spark.createDataFrame(cols).createOrReplaceTempView(t)
            elif datagen.is_fact(t) and self.fact_source == "cached":
                df = self.spark.read.parquet(self._path(t)) \
                    .select(*self.traffic["cached_columns"][t]).cache()
                df.createOrReplaceTempView(t)
                self._cached.append(df)
            else:
                self.spark.sql(self._view_ddl(t))

    def stop(self):
        """Free what the cell held on the device, stop the server."""
        for df in self._cached:
            df.unpersist()
        self._cached = []
        if self._server is not None:
            self._server.stop()
            self._server = None
        for k, v in self._conf_before.items():   # the session is process-wide
            if v is None:
                self.spark.conf.unset(k)
            else:
                self.spark.conf.set(k, str(v))

    # -- one statement -----------------------------------------------------
    def _http(self, path, method="GET", body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self._server.port}{path}", data=data,
            method=method)
        req.add_header("Content-Type", "application/json")
        if self._sid:
            req.add_header("X-Session-Id", self._sid)
        with urllib.request.urlopen(req, timeout=900) as resp:
            return json.loads(resp.read().decode())

    def run(self, sql: str):
        """(rows, server_ms): one statement, ending in rows on the client.
        ``server_ms`` is the reply's ``durationMs`` (None off HTTP)."""
        if self.entry == "http":
            out = self._http("/sql", "POST", {"query": sql})
            return [tuple(r) for r in out["rows"]], float(out["durationMs"])
        return [tuple(r) for r in self.spark.sql(sql).collect()], None

    # -- what the program records ------------------------------------------
    def agg_lowering(self) -> str:
        """Which keyed-aggregate lowering the last session statement RAN,
        from the program text of the stage-cache entry it dispatched: the
        Mosaic kernel is a ``tpu_custom_call``, the portable MXU form a
        ``dot_general``, the sort-based aggregate neither.  The program has
        no public way to ask (PERF.md, Open questions), so this reads its
        internals as ``chip_smoke._agg_lowering`` does; where a later PR
        has moved them the answer is ``unreadable``, which fails no run."""
        try:
            from spark_tpu.sql import stagecompile as SC
            from spark_tpu.sql.planner import local_stage_key
            key, slots, leaves = local_stage_key(
                self.spark, self.spark._last_qe.planned)
            entry = SC.stage_cache(self.spark).peek(key)
            text = entry.fn.lower(tuple(b.to_device() for b in leaves),
                                  SC.param_values(slots)).as_text()
        except (ImportError, AttributeError, TypeError):
            return "unreadable"
        if "tpu_custom_call" in text:
            return "pallas"
        return "einsum" if "dot_general" in text else "sort"
