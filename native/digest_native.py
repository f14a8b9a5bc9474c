"""ctypes loader for the native batched digest plane (native/digest.cc).

Unlike hh_native, this library is deliberately built WITHOUT
-march=native: digest.cc compiles every ISA path (scalar, SSE2 x4,
AVX2 x8, SHA-NI) unconditionally behind `#pragma GCC target` and picks
at runtime via CPUID, so one binary serves any x86-64 host and the
selftest can force each compiled path.  Callers catch
ImportError/OSError and fall back to hashlib.  ctypes releases the GIL
for every batch call.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ._build import build

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "digest.cc")

_lib = None

# isa selectors (mirror digest.cc); pass to any entry to force a path.
ISA_AUTO = 0
MD5_SCALAR, MD5_SSE2, MD5_AVX2 = 1, 2, 3
SHA_SCALAR, SHA_NI = 1, 2

MD5_ISA_NAMES = {MD5_SCALAR: "scalar", MD5_SSE2: "sse2", MD5_AVX2: "avx2"}
SHA_ISA_NAMES = {SHA_SCALAR: "scalar", SHA_NI: "shani"}


def load():
    global _lib
    if _lib is None:
        # No -march=native on purpose: runtime dispatch is the contract.
        lib = ctypes.CDLL(build("mtpudigest", _SRC, march_native=False))
        lib.mtpu_digest_isa.restype = ctypes.c_char_p
        lib.mtpu_digest_supported.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.mtpu_digest_supported.restype = ctypes.c_int
        lib.mtpu_md5_lanes.argtypes = [ctypes.c_int]
        lib.mtpu_md5_lanes.restype = ctypes.c_int
        lib.mtpu_md5_init.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.mtpu_md5_update_mb.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_int]
        lib.mtpu_md5_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_int]
        lib.mtpu_sha256_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_int]
        _lib = lib
    return _lib


def isa() -> str:
    return load().mtpu_digest_isa().decode()


def md5_lanes(isa_sel: int = ISA_AUTO) -> int:
    return load().mtpu_md5_lanes(isa_sel)


def supported_md5_isas() -> list[int]:
    lib = load()
    return [i for i in (MD5_SCALAR, MD5_SSE2, MD5_AVX2)
            if lib.mtpu_digest_supported(0, i)]


def supported_sha_isas() -> list[int]:
    lib = load()
    return [i for i in (SHA_SCALAR, SHA_NI)
            if lib.mtpu_digest_supported(1, i)]


def _as_u8(buf) -> np.ndarray:
    """Zero-copy uint8 view of any contiguous buffer (incl. empty)."""
    if isinstance(buf, memoryview) and buf.format != "B":
        buf = buf.cast("B")
    return np.frombuffer(buf, dtype=np.uint8)


def _ptr_len_arrays(bufs):
    n = len(bufs)
    ptrs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint64 * n)()
    views = []                       # keep the arrays alive over the call
    for i, b in enumerate(bufs):
        arr = _as_u8(b)
        views.append(arr)
        ptrs[i] = arr.ctypes.data if arr.size else None
        lens[i] = arr.size
    return ptrs, lens, views


def md5_init_states(n: int) -> np.ndarray:
    """(n, 4) uint32 fresh MD5 states."""
    states = np.empty((n, 4), dtype=np.uint32)
    load().mtpu_md5_init(states.ctypes.data, n)
    return states


def md5_update_mb(states: np.ndarray, bufs, isa_sel: int = ISA_AUTO) -> None:
    """Advance n incremental MD5 streams in SIMD lockstep.

    states is (n, 4) uint32 (one row per stream); bufs[i] is the next
    run of whole 64-byte blocks for stream i (len % 64 == 0; empty is
    fine — that lane just idles this call).
    """
    assert states.dtype == np.uint32 and states.flags.c_contiguous
    ptrs, lens, _views = _ptr_len_arrays(bufs)
    load().mtpu_md5_update_mb(states.ctypes.data, ptrs, lens,
                              len(bufs), isa_sel)


def md5_finalize(state_row: np.ndarray, total_len: int) -> bytes:
    """Digest bytes for a stream whose tail padding was already fed
    through md5_update_mb (see md5_pad)."""
    return state_row.astype("<u4", copy=False).tobytes()


def md5_pad(tail: bytes, total_len: int) -> bytes:
    """MD5 padding block(s) for a message of total_len bytes ending in
    `tail` (the < 64-byte remainder); result length is 64 or 128."""
    rem = len(tail)
    assert rem == total_len % 64
    tail_len = 64 if rem < 56 else 128
    out = bytearray(tail_len)
    out[:rem] = tail
    out[rem] = 0x80
    out[-8:] = (total_len * 8).to_bytes(8, "little")
    return bytes(out)


def md5_batch(bufs, isa_sel: int = ISA_AUTO) -> list[bytes]:
    """One-shot batched MD5 of n buffers -> n 16-byte digests."""
    n = len(bufs)
    if not n:
        return []
    ptrs, lens, _views = _ptr_len_arrays(bufs)
    out = np.empty((n, 16), dtype=np.uint8)
    load().mtpu_md5_batch(ptrs, lens, n, out.ctypes.data, isa_sel)
    return [out[i].tobytes() for i in range(n)]


def sha256_batch(bufs, isa_sel: int = ISA_AUTO) -> list[bytes]:
    """Batched SHA256 of n buffers in ONE GIL-released call -> n x 32B."""
    n = len(bufs)
    if not n:
        return []
    ptrs, lens, _views = _ptr_len_arrays(bufs)
    out = np.empty((n, 32), dtype=np.uint8)
    load().mtpu_sha256_batch(ptrs, lens, n, out.ctypes.data, isa_sel)
    return [out[i].tobytes() for i in range(n)]
