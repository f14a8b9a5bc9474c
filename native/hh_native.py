"""ctypes loader for the native HighwayHash-256 kernel
(native/highwayhash.cc).

Compiled on first use with -O3 -march=native by the shared build rule
(native/_build.py); a host without a toolchain raises BuildError and
callers use the numpy/JAX spec paths. ctypes releases the GIL for the
whole batch.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ._build import build

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "highwayhash.cc")

_lib = None


def load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build("highwayhash", _SRC))
        lib.hh_isa.restype = ctypes.c_char_p
        lib.hh256_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p]
        lib.hh256.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_void_p]
        lib.hh256_frames.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_void_p]
        _lib = lib
    return _lib


def isa() -> str:
    return load().hh_isa().decode()


def _key_bytes(key: bytes | None) -> bytes:
    if key is None:
        from minio_tpu.ops.highwayhash import MAGIC_KEY
        key = MAGIC_KEY
    if len(key) != 32:
        raise ValueError("key must be 32 bytes")
    return key


def hh256_rows_native(rows: np.ndarray,
                      key: bytes | None = None) -> np.ndarray:
    """(n, L) uint8 -> (n, 32) HighwayHash-256 digests (magic key)."""
    lib = load()
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, ln = rows.shape
    out = np.empty((n, 32), dtype=np.uint8)
    lib.hh256_rows(rows.ctypes.data, n, ln, _key_bytes(key),
                   out.ctypes.data)
    return out


def hh256_frames_native(buf, n: int, stride: int, off: int, length: int,
                        key: bytes | None = None) -> np.ndarray:
    """Hash n strided segments buf[i*stride+off : +length] -> (n, 32).

    The verify-only entry for bitrot-framed shard files: digests the
    data region of every [32B digest | shard] frame in place, with no
    gather copy.  ctypes releases the GIL for the whole batch, so the
    healthy-GET fast path can fan shard files out across the pool.
    """
    lib = load()
    arr = np.frombuffer(buf, dtype=np.uint8)   # zero-copy view
    if n and (n - 1) * stride + off + length > arr.size:
        raise ValueError("strided frames overrun buffer")
    out = np.empty((n, 32), dtype=np.uint8)
    lib.hh256_frames(arr.ctypes.data, n, stride, off, length,
                     _key_bytes(key), out.ctypes.data)
    return out


def hh256_native(data: bytes | bytearray | memoryview,
                 key: bytes | None = None) -> bytes:
    """One-shot digest of an arbitrary buffer (whole-file verify)."""
    lib = load()
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(32, dtype=np.uint8)
    lib.hh256(buf.ctypes.data, buf.size, _key_bytes(key), out.ctypes.data)
    return out.tobytes()
