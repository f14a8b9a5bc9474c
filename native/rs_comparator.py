"""ctypes loader + measured-baseline driver for the native RS comparator.

Builds native/rs_cpu.cc on first use (native/_build.py), loads it,
and offers:
  - encode(): native encode for differential testing vs the gf256 oracle,
  - measure_encode_gbps(): the measured CPU baseline bench.py uses in
    place of the round-1 hardcoded constant.

Nibble tables come from minio_tpu.ops.gf256, so the native path computes
the exact same code as the TPU path (cf. klauspost/reedsolomon's
galMulSlicesAvx2 technique the reference depends on, go.mod:41).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ._build import build

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "rs_cpu.cc")

_lib = None


def load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build("rs_cpu", _SRC))
        lib.rs_isa.restype = ctypes.c_char_p
        lib.rs_bench_encode.restype = ctypes.c_double
        lib.rs_bench_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_size_t,
            ctypes.c_int, ctypes.c_int]
        lib.rs_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_size_t]
        _lib = lib
    return _lib


def isa() -> str:
    return load().rs_isa().decode()


def nibble_tables(k: int, m: int) -> np.ndarray:
    """(m, k, 32) uint8: [lo16 | hi16] per parity-matrix coefficient."""
    from minio_tpu.ops import gf256
    mat = gf256.parity_matrix(k, m)  # (m, k) GF bytes
    v = np.arange(16, dtype=np.uint8)
    tabs = np.empty((m, k, 32), dtype=np.uint8)
    for r in range(m):
        for c in range(k):
            coef = int(mat[r, c])
            tabs[r, c, :16] = [gf256.gf_mul(coef, int(x)) for x in v]
            tabs[r, c, 16:] = [gf256.gf_mul(coef, int(x) << 4) for x in v]
    return tabs


def encode(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """(k, len) uint8 data shards -> (m, len) parity, via the native path."""
    lib = load()
    data = np.ascontiguousarray(data, dtype=np.uint8)
    _, length = data.shape
    parity = np.empty((m, length), dtype=np.uint8)
    tabs = np.ascontiguousarray(nibble_tables(k, m))
    lib.rs_encode(tabs.ctypes.data, data.ctypes.data, parity.ctypes.data,
                  k, m, length)
    return parity


def measure_encode_gbps(k: int = 8, m: int = 4, shard_size: int = 131072,
                        blocks: int = 64, min_seconds: float = 0.5) -> float:
    """Measured native encode throughput (data GB/s) on this host."""
    lib = load()
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(blocks, k, shard_size), dtype=np.uint8)
    parity = np.empty((m, shard_size), dtype=np.uint8)
    tabs = np.ascontiguousarray(nibble_tables(k, m))
    iters = 1
    while True:
        secs = lib.rs_bench_encode(tabs.ctypes.data, data.ctypes.data,
                                   parity.ctypes.data, k, m, shard_size,
                                   blocks, iters)
        if secs >= min_seconds:
            break
        iters = max(iters * 2, int(iters * min_seconds / max(secs, 1e-9)) + 1)
    total = float(blocks) * k * shard_size * iters
    return total / secs / 1e9
