"""ctypes loader for the native mxh256 kernel (native/mxh256.cc).

Compiled on first use with -O3 -march=native by the shared build rule
(native/_build.py); a host without a toolchain raises BuildError and
callers use ops/mxhash.mxh256_batch.
"""

from __future__ import annotations

import ctypes
import functools
import os

import numpy as np

from ._build import build

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "mxh256.cc")

_lib = None


def load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build("mxh256", _SRC))
        lib.mxh_isa.restype = ctypes.c_char_p
        lib.mxh256_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p]
        _lib = lib
    return _lib


def isa() -> str:
    return load().mxh_isa().decode()


@functools.lru_cache(maxsize=1)
def _matrix_material():
    from minio_tpu.ops import mxhash
    a = mxhash.matrix_a()                       # (256, 8) int8
    at = np.ascontiguousarray(a.T)              # (8, 256) int8
    corr = (128 * a.astype(np.int32).sum(axis=0)).astype(np.int32)
    return at, np.ascontiguousarray(corr)


def mxh256_rows_native(rows: np.ndarray) -> np.ndarray:
    """(n, L) uint8 -> (n, 32) digests, bit-identical to the spec path.

    ctypes releases the GIL for the whole batch, so thread pools overlap
    hashing with I/O.
    """
    lib = load()
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, ln = rows.shape
    from minio_tpu.ops import mxhash
    at, corr = _matrix_material()
    tag = np.ascontiguousarray(mxhash.length_tag(ln))
    out = np.empty((n, 32), dtype=np.uint8)
    max_lvl = (max(ln, 1) + 255) // 256 * 32
    scratch = np.empty(2 * max_lvl + 64, dtype=np.uint8)
    lib.mxh256_rows(rows.ctypes.data, n, ln, at.ctypes.data,
                    corr.ctypes.data, tag.ctypes.data, out.ctypes.data,
                    scratch.ctypes.data)
    return out
