"""Compile-on-first-use loader for the native kernel library."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading
from typing import Optional

_SRC = os.path.join(os.path.dirname(__file__), "src", "sketch.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _cache_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    d = os.environ.get("SPARK_TPU_NATIVE_CACHE") or os.path.join(
        tempfile.gettempdir(), "spark_tpu_native")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"spark_tpu_native_{digest}.so")


def load_library() -> Optional[ctypes.CDLL]:
    """The compiled library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _cache_path()
            if not os.path.exists(so):
                tmp = so + f".build-{os.getpid()}"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                     _SRC, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, so)     # atomic vs concurrent builders
            lib = ctypes.CDLL(so)
            _sign(lib)
            _lib = lib
        except Exception as e:
            # once (``_tried``): callers fall back to their numpy forms
            detail = getattr(e, "stderr", b"") or b""
            print(f"spark_tpu.native: library unavailable ({e})"
                  + (f": {detail.decode(errors='replace')[-500:]}"
                     if detail else ""), file=sys.stderr)
            _lib = None
        return _lib


def native_available() -> bool:
    return load_library() is not None


def _sign(lib: ctypes.CDLL) -> None:
    i32, i64 = ctypes.c_int32, ctypes.c_int64
    p_i64 = ctypes.POINTER(i64)
    p_u64 = ctypes.POINTER(ctypes.c_uint64)
    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    lib.murmur3_hash_long.restype = i32
    lib.murmur3_hash_long.argtypes = [i64, i32]
    lib.bloom_put_longs.restype = None
    lib.bloom_put_longs.argtypes = [p_u64, i64, i32, p_i64, i64]
    lib.bloom_might_contain_longs.restype = None
    lib.bloom_might_contain_longs.argtypes = [p_u64, i64, i32, p_i64, i64,
                                              p_u8]
    lib.cms_add_longs.restype = None
    lib.cms_add_longs.argtypes = [p_i64, i32, i32, p_i64, i64, i64]
    lib.cms_estimate_longs.restype = None
    lib.cms_estimate_longs.argtypes = [p_i64, i32, i32, p_i64, i64, p_i64]
    lib.merge_sorted_runs.restype = None
    lib.merge_sorted_runs.argtypes = [p_i64, p_i64, i32, p_i64]
    lib.partition_permutation.restype = None
    lib.partition_permutation.argtypes = [p_i64, i64, i64, p_i64, p_i64]
