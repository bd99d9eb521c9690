"""CLI entry points + runnable examples (bin/ + examples/ analogs)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}


def run(args, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=240, env=ENV, cwd=ROOT, **kw)


def test_sql_e():
    r = run(["-m", "spark_tpu.cli", "sql", "-e",
             "SELECT 1 AS one, 'x' AS s"])
    assert r.returncode == 0, r.stderr[-800:]
    assert "one" in r.stdout and "x" in r.stdout


def test_sql_file(tmp_path):
    f = tmp_path / "q.sql"
    f.write_text("CREATE TEMP VIEW v AS SELECT id FROM range(3);\n"
                 "SELECT count(*) AS c FROM v;")
    r = run(["-m", "spark_tpu.cli", "sql", "-f", str(f)])
    assert r.returncode == 0, r.stderr[-800:]
    assert "3" in r.stdout


def test_submit_runs_script(tmp_path):
    app = tmp_path / "app.py"
    app.write_text(
        "import sys\n"
        "from spark_tpu.sql.session import SparkSession\n"
        "spark = SparkSession.builder.getOrCreate()\n"
        "print('ROWS', spark.range(int(sys.argv[1])).count())\n")
    r = run(["-m", "spark_tpu.cli", "submit", str(app), "7"])
    assert r.returncode == 0, r.stderr[-800:]
    assert "ROWS 7" in r.stdout


@pytest.mark.parametrize("example", [
    "pi.py", "sql_basic.py", "streaming_window_agg.py",
    "graphx_pagerank.py", "ml_pipeline.py", "jdbc_etl.py",
])
def test_example(example):
    r = run([os.path.join("examples", example)])
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1200:])
