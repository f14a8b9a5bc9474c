"""Native (AVX-512/AVX2) GF(2^8) codec — the engine's host-path backend.

Role: SURVEY.md §7 hard-part #5 ("a TPU failure must degrade, not
corrupt") and the honest host-path e2e numbers: when the process has no
TPU the erasure engine runs shard math through native/rs_cpu.cc, the
same vpshufb
nibble-table technique as the reference's klauspost/reedsolomon assembly
(go.mod:41).  Tables come from the repo's own gf256, so bytes on disk
are identical to the device path's (differentially tested).

rs_encode applies an arbitrary (R, C) coefficient matrix, so the one
entry point covers encode (parity matrix), decode (inverted-submatrix
rows), and heal — exactly like the device kernel's transform seam.
"""

from __future__ import annotations

import functools

import numpy as np

from . import gf256


@functools.lru_cache(maxsize=4096)
def _tables_cached(mat_bytes: bytes, r: int, c: int) -> np.ndarray:
    """(R, C, 32) uint8 nibble tables [lo16 | hi16] for a GF matrix."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, c)
    mul = gf256.mul_table()                     # (256, 256) products
    v = np.arange(16, dtype=np.uint8)
    tabs = np.empty((r, c, 32), dtype=np.uint8)
    tabs[:, :, :16] = mul[mat][:, :, v]
    tabs[:, :, 16:] = mul[mat][:, :, v << 4]
    return np.ascontiguousarray(tabs)


def tables_for_matrix(gf_mat: np.ndarray) -> np.ndarray:
    gf_mat = np.ascontiguousarray(gf_mat, dtype=np.uint8)
    r, c = gf_mat.shape
    return _tables_cached(gf_mat.tobytes(), r, c)


@functools.lru_cache(maxsize=4096)
def transform_matrix(k: int, m: int, sources: tuple[int, ...],
                     targets: tuple[int, ...]) -> np.ndarray:
    """(T, K) GF byte matrix mapping `sources` rows -> `targets` rows
    (byte-level sibling of erasure_jax._transform_matrix_bits)."""
    full = gf256.build_matrix(k, k + m)
    inv = gf256.gf_mat_invert(full[list(sources)[:k], :])
    return gf256.gf_matmul(full[list(targets), :], inv)


def _apply(tabs: np.ndarray, x: np.ndarray, rows: int) -> np.ndarray:
    """(B, C, S) uint8 -> (B, rows, S) via native rs_encode per block.

    ctypes releases the GIL during each C call, so engine thread pools
    overlap these with drive I/O for free.
    """
    from native import rs_comparator
    lib = rs_comparator.load()
    x = np.ascontiguousarray(x, dtype=np.uint8)
    b, c, s = x.shape
    out = np.empty((b, rows, s), dtype=np.uint8)
    for i in range(b):
        lib.rs_encode(tabs.ctypes.data, x[i].ctypes.data,
                      out[i].ctypes.data, c, rows, s)
    return out


class ReedSolomonNative:
    """Drop-in for ReedSolomonTPU's encode/transform seam, on the host.

    Returns numpy arrays (already host-resident — callers that
    np.asarray() the device result get a no-op).
    """

    def __init__(self, data_shards: int, parity_shards: int):
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards

    def encode_blocks(self, data: np.ndarray,
                      salt=None) -> np.ndarray:
        data = np.asarray(data, dtype=np.uint8)
        if salt is not None:
            data = data ^ np.uint8(int(salt[0]) & 0xFF)
        tabs = tables_for_matrix(
            gf256.parity_matrix(self.data_shards, self.parity_shards))
        return _apply(tabs, data, self.parity_shards)

    def transform_blocks(self, shards: np.ndarray,
                         sources: tuple[int, ...],
                         targets: tuple[int, ...],
                         salt=None) -> np.ndarray:
        shards = np.asarray(shards, dtype=np.uint8)
        if salt is not None:
            shards = shards ^ np.uint8(int(salt[0]) & 0xFF)
        mat = transform_matrix(self.data_shards, self.parity_shards,
                               tuple(sources), tuple(targets))
        return _apply(tables_for_matrix(mat), shards, len(targets))
