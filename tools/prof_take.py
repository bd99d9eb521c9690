"""When one plane gather pays: columns read through one index as ONE
``kernels.gather_columns`` plane against one gather a column, timed alone on
whatever backend jax gives.  One JSON line a case: the words the uint32
plane holds, the float64 columns beside it, compile seconds and median run
ms of each form, and whether the plane's columns equal the gathered ones bit
for bit.

    python tools/prof_take.py [log2 rows ...]        # default 20 22

Cases: k int32 columns (k words), k int64 columns (2k words), k bool
columns (packed four to a word), one int32 column and its row mask (a
one-column ``compact``), and int32 planes with 1-2 float64 columns beside
them; a few planes also in the row-major ``[n, k]`` layout.  On a v5e one
int32 word already reads faster as a plane than as its column, so
``kernels.take_columns`` takes the plane there whatever it holds (PERF.md
section 6)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import spark_tpu  # noqa: F401  (x64)
import jax
import jax.numpy as jnp
from spark_tpu import kernels as K

jax.config.update("jax_enable_compilation_cache", False)
rng = np.random.default_rng(0)


def run_ms(fn, args):
    t0 = time.time()
    c = jax.jit(fn).lower(*args).compile()
    compile_s = time.time() - t0
    out = c(*args)
    jax.block_until_ready(out)
    runs = []
    for _ in range(5):
        t0 = time.time()
        jax.block_until_ready(c(*args))
        runs.append((time.time() - t0) * 1e3)
    return out, round(compile_s, 2), round(float(np.median(runs)), 3)


def words_of(cols):
    """(uint32 words, float64 columns) of ``gather_columns``'s two planes:
    8-byte integers two words, 2- and 4-byte ones one, 1-byte ones and
    bools a quarter word, rounded up."""
    floats = sum(c.dtype == np.float64 for c in cols)
    small = sum(c.dtype.itemsize == 1 for c in cols)
    words = sum(c.dtype.itemsize // 4 + (c.dtype.itemsize == 2)
                for c in cols if c.dtype != np.float64)
    return words + -(-small // 4), floats


def case(name, rows, perm, cols, rows_layout=False):
    """The plane (``gather_columns``), the columns, and where asked the
    uint32 words as ONE ``[n, k]`` plane gathered by rows."""
    line = {"case": name, "rows": rows, "columns": len(cols),
            "words": words_of(cols)[0], "float64": words_of(cols)[1],
            "kind": jax.devices()[0].device_kind}
    try:
        plane, line["plane_compile_s"], line["plane_ms"] = run_ms(
            lambda p, *c: K.gather_columns(jnp, c, p),
            [perm] + cols)
        got, line["columns_compile_s"], line["columns_ms"] = run_ms(
            lambda p, *c: [x[p] for x in c], [perm] + cols)
        line["same"] = all(
            np.array_equal(np.asarray(a).view(np.uint8),
                           np.asarray(b).view(np.uint8))
            for a, b in zip(plane, got))
        if rows_layout:
            words = [jax.lax.bitcast_convert_type(c, jnp.uint32)
                     for c in cols if c.dtype == jnp.int32]
            _, _, line["rows_layout_ms"] = run_ms(
                lambda p, *w: jnp.stack(w, axis=1)[p], [perm] + words)
        line["plane_over_columns"] = round(
            line["plane_ms"] / line["columns_ms"], 3)
    except Exception as e:                      # what the compiler refuses
        line["error"] = str(e)[:200]
    print(json.dumps(line), flush=True)


for lg in [int(a) for a in sys.argv[1:]] or [20, 22]:
    n = 1 << lg
    perm = jax.device_put(rng.permutation(n).astype(np.int32))
    i32 = [jax.device_put(rng.integers(-1 << 30, 1 << 30, n, dtype=np.int32))
           for _ in range(13)]
    i64 = [jax.device_put(rng.integers(-1 << 40, 1 << 40, n))
           for _ in range(6)]
    b = [jax.device_put(rng.random(n) < 0.7) for _ in range(4)]
    f64 = [jax.device_put(rng.random(n) * 1e4) for _ in range(2)]
    for k in range(1, 14):
        case(f"int32x{k}", n, perm, i32[:k], rows_layout=k in (2, 5, 9, 13))
    for k in range(1, 7):
        case(f"int64x{k}", n, perm, i64[:k])
    for k in range(1, 5):
        case(f"boolx{k}", n, perm, b[:k])
    case("int32+rowmask", n, perm, [i32[0], b[0]])
    case("int64+rowmask", n, perm, [i64[0], b[0]])
    for f in (1, 2):
        for k in (0, 1, 2, 5, 13):
            case(f"int32x{k}+float64x{f}", n, perm, i32[:k] + f64[:f])
