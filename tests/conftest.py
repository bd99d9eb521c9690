"""Test bootstrap: force a virtual 8-device CPU platform BEFORE jax import.

Mirrors the reference's `local-cluster[N,...]` testing trick
(`core/src/main/scala/org/apache/spark/deploy/LocalSparkCluster.scala:36`):
distributed code paths are exercised in-process on N virtual devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# the compile tests describe a v5e in two xdist workers at once: without this
# the second to load the TPU library fails on its multi-process lockfile
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

# TPU float64 is emulated (~1e-15 error), which breaks exact dual-path
# tests: the suite runs on the CPU backend, pinned by JAX_PLATFORMS above.

# Persistent compile cache: this jax build pays ~0.8s per jit and ~20ms per
# uncached eager op; caching across pytest runs keeps the suite usable.  The
# DIRECTORY is the program's own rule (`spark_tpu/__init__.py`, imported
# below): JAX_COMPILATION_CACHE_DIR where set, else `.jax_cache` inside the
# checkout.  Only the policy is the suite's: cache everything.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# the MXU aggregation path auto-disables off-TPU; tests run on the virtual
# CPU mesh as the TPU stand-in, so force it on to keep exercising the
# one-hot-matmul kernel (the suite's dual-path oracle checks depend on it)
from spark_tpu import kernels as _kernels  # noqa: E402

_kernels.MXU_AGG_ENABLED = True


def pytest_configure(config):
    # the tier-1 sweep runs `-m 'not slow'`; heavy subprocess/thread-pool
    # suites (chaos, stress-scale wire round-trips) opt out via this mark
    config.addinivalue_line(
        "markers", "slow: >~5s test, excluded from the tier-1 sweep")
    config.addinivalue_line(
        "markers", "chaos_smoke: multi-process fault-injection scenario "
        "from tests/chaos_matrix.py (also runnable via bin/chaos)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def spark():
    """Shared session (SharedSparkContext/SharedSQLContext analog).

    Pinned to single-shard local execution; distributed suites opt into the
    8-device mesh via their own fixture (see test_distributed.py).
    """
    from spark_tpu.sql.session import SparkSession
    s = SparkSession.builder.appName("tests").getOrCreate()
    s.conf.set("spark.tpu.mesh.shards", "1")
    return s


@pytest.fixture(scope="module")
def topo():
    """A TPU v5e 2x2 described, not attached, for the compile tests.  Only
    a module that asks for it loads the TPU library."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """Steer the engine's device gate to its TPU branch for one test."""
    monkeypatch.setattr(_kernels, "_on_tpu_device", lambda: True)
    monkeypatch.setattr(_kernels, "MXU_AGG_ENABLED", None)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """The full 600+-test suite accumulates thousands of live XLA:CPU
    executables in one process and eventually segfaults inside a CPU
    kernel; dropping compiled programs between modules keeps the working
    set bounded (the persistent on-disk cache makes recompiles cheap).

    ROOT CAUSE (confirmed via the engine-free reproducer
    tests/repro_xla_cpu_segfault.py, 2026-07-31): XLA:CPU's LLVM JIT
    code arena exhausts after ~2,250 live executables —
    `execution_engine.cc:54 LLVM compilation error: Cannot allocate
    memory` repeats, the failure is not surfaced to Python, and the
    next executable use SIGSEGVs (rc=139).  Pure jax + numpy; no
    spark_tpu code involved, so this fixture is a workaround for an
    upstream XLA:CPU condition, not a mask over an engine bug.  If you
    run a custom large subset WITHOUT this conftest, call
    jax.clear_caches() periodically or expect the late segfault."""
    yield
    jax.clear_caches()
