"""spark_tpu — a TPU-native distributed data-processing engine.

A ground-up reimplementation of the capabilities of Apache Spark
(reference surveyed in SURVEY.md) designed for JAX/XLA on TPU:

* columnar device batches instead of UnsafeRow (``spark_tpu.columnar``)
* XLA jit fusion instead of Janino whole-stage codegen (``spark_tpu.exec``)
* mesh collectives (all_to_all/psum/all_gather) instead of Netty shuffle
  (``spark_tpu.parallel``)
* a SQL frontend (parser → analyzer → optimizer → planner) compiling to the
  above (``spark_tpu.sql``)
"""

__version__ = "0.1.0"

import jax as _jax

# The engine owns its process (like the Spark driver JVM): int64/float64 are
# core SQL types (LongType keys, DoubleType aggregates), so JAX's default
# silent downcast to 32-bit would corrupt data. Hot paths opt into
# f32/bf16 explicitly where it is safe.
_jax.config.update("jax_enable_x64", True)

# ONE persistent compile cache, placeable from outside: where
# JAX_COMPILATION_CACHE_DIR is set jax already uses that directory and
# nothing is set here; otherwise a fixed directory inside the checkout (the
# path is part of the cache key, so a directory that moves never hits).
import os as _os  # noqa: E402
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

from . import types  # noqa: F401
from .config import Conf  # noqa: F401
from .columnar import ColumnBatch, ColumnVector  # noqa: F401


def __getattr__(name):
    # Lazy imports keep `import spark_tpu` light.
    if name == "SparkSession":
        from .sql.session import SparkSession
        return SparkSession
    if name == "SparkContext":
        from .rdd.context import SparkContext
        return SparkContext
    if name == "functions":
        from .sql import functions
        return functions
    raise AttributeError(name)
