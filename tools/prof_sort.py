"""``kernels.multi_key_argsort``'s two jax forms on whatever backend jax
gives: the chain of single-key stable sorts (the TPU form) against the one
variadic ``lax.sort`` (the form elsewhere) — same permutation, compile
seconds and run seconds of each.  One JSON line per form.

    python tools/prof_sort.py [rows] [sig/sig/...]     # sigs: see SIGS

Run by hand (through the builder's tool for the chip); PERF.md carries
the readings."""
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

SIGS = {
    "2xi32": [np.int32, np.int32],
    "i8,i8,i32": [np.int8, np.int8, np.int32],
    "3xi64": [np.int64, np.int64, np.int64],
    # the sorted aggregate's keys in q3: (dead, null, key, null, float key)
    "i8,i8,i64,i8,f64": [np.int8, np.int8, np.int64, np.int8, np.float64],
}
n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 22
names = sys.argv[2].split("/") if len(sys.argv) > 2 else ["2xi32", "i8,i8,i32"]
rng = np.random.default_rng(0)
gate = K._on_tpu_device

for name in names:
    keys = []
    for dt in SIGS[name]:
        if dt == np.int8:
            keys.append(rng.integers(0, 2, n).astype(dt))
        elif dt == np.float64:
            keys.append(rng.random(n) * 1e4)
        else:
            keys.append(rng.integers(0, 2000, n).astype(dt))
    dev = [jax.device_put(k) for k in keys]
    perms = {}
    for form, on_tpu in (("chain", True), ("variadic", False)):
        K._on_tpu_device = lambda on_tpu=on_tpu: on_tpu
        try:
            t0 = time.time()
            fn = jax.jit(lambda *ks: K.multi_key_argsort(jnp, list(ks), n)) \
                .lower(*dev).compile()
            compile_s = time.time() - t0
        finally:
            K._on_tpu_device = gate
        perms[form] = np.asarray(fn(*dev))
        runs = []
        for _ in range(5):
            t0 = time.time()
            fn(*dev).block_until_ready()
            runs.append(round(time.time() - t0, 4))
        print(json.dumps({"backend": jax.default_backend(),
                          "kind": jax.devices()[0].device_kind, "rows": n,
                          "keys": name, "form": form,
                          "compile_s": round(compile_s, 2),
                          "run_s_median": float(np.median(runs)),
                          "run_s": runs}), flush=True)
    assert (perms["chain"] == perms["variadic"]).all(), name
    assert (perms["chain"] == np.lexsort(tuple(reversed(keys)))).all(), name
