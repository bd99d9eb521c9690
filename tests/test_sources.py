"""Socket streaming source, kafka gating, DStream compat shim."""
import socket
import threading
import time

import pytest

from spark_tpu.expressions import AnalysisException
from spark_tpu.sql.session import SparkSession


def _serve_lines(lines, port_holder, stop_evt):
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port_holder.append(srv.getsockname()[1])
    conn, _ = srv.accept()
    for line in lines:
        conn.sendall(line.encode() + b"\n")
    stop_evt.wait(5)
    conn.close()
    srv.close()


def test_socket_source_reads_lines():
    spark = SparkSession()
    port_holder, stop_evt = [], threading.Event()
    th = threading.Thread(target=_serve_lines,
                          args=(["hello", "world"], port_holder, stop_evt),
                          daemon=True)
    th.start()
    for _ in range(100):
        if port_holder:
            break
        time.sleep(0.01)
    df = (spark.readStream.format("socket")
          .option("host", "127.0.0.1").option("port", port_holder[0]).load())
    q = (df.writeStream.format("memory").queryName("sock")
         .outputMode("append").start())
    try:
        deadline = time.time() + 5
        rows = []
        while time.time() < deadline:
            q.processAllAvailable()
            try:
                rows = spark.sql("SELECT * FROM sock").collect()
            except AnalysisException:
                rows = []       # no line has arrived yet: the memory sink
                                # makes its view with its first batch
            if len(rows) >= 2:
                break
            time.sleep(0.05)
        assert sorted(r["value"] for r in rows) == ["hello", "world"]
    finally:
        stop_evt.set()
        q.stop()


def test_kafka_source_gated_with_clear_error():
    spark = SparkSession()
    with pytest.raises(AnalysisException, match="kafka"):
        spark.readStream.format("kafka").load()


def test_dstream_shim_socket_foreach():
    from spark_tpu.streaming.dstream import StreamingContext
    spark = SparkSession()
    port_holder, stop_evt = [], threading.Event()
    th = threading.Thread(target=_serve_lines,
                          args=(["a", "b", "c"], port_holder, stop_evt),
                          daemon=True)
    th.start()
    for _ in range(100):
        if port_holder:
            break
        time.sleep(0.01)
    ssc = StreamingContext(batchDuration=0.05)
    seen = []
    stream = ssc.socketTextStream("127.0.0.1", port_holder[0])
    stream.foreachRDD(lambda bdf: seen.extend(
        r["value"] for r in bdf.collect()))
    ssc.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and len(seen) < 3:
            for q in ssc._queries:
                q.processAllAvailable()
            time.sleep(0.05)
        assert sorted(seen) == ["a", "b", "c"]
    finally:
        stop_evt.set()
        ssc.stop()
