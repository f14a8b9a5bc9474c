"""ctypes loader for the fused erasure-IO kernels (native/ecio.cc).

The host data path's hot core: one C pass per batch doing
encode+hash+frame (PUT) or verify+gather+reconstruct (GET), reading and
writing mmap'd shard files so Python never copies object bytes.
Compiled on first use with -O3 -march=native by the shared build rule
(native/_build.py); a host without a toolchain raises BuildError and
callers keep the separate-pass numpy path.
"""

from __future__ import annotations

import ctypes
import functools
import os

import numpy as np

from ._build import build

try:
    from minio_tpu.observe.span import span as _span
except Exception:  # standalone shim use: tracing becomes a no-op
    import contextlib

    def _span(name):
        return contextlib.nullcontext()

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "ecio.cc")
_DEPS = (os.path.join(_DIR, "mxh256.cc"), os.path.join(_DIR, "rs_cpu.cc"))

_lib = None
_load_error: Exception | None = None

ALGO = "mxh256"          # the one algorithm these kernels speak
HASH_SIZE = 32
MAX_ROWS = 64            # C kernels use fixed srcs[64] stack arrays


def load():
    """Build+load once; a failed build is cached so hot paths don't
    spawn a failing g++ subprocess per call on toolchain-less hosts."""
    global _lib, _load_error
    if _load_error is not None:
        raise _load_error
    if _lib is None:
        try:
            lib = _load_inner()
        except Exception as e:  # noqa: BLE001 — cache and re-raise
            _load_error = e
            raise
        _lib = lib
    return _lib


def _load_inner():
    lib = ctypes.CDLL(build("ecio", _SRC, _DEPS))
    lib.ec_isa.restype = ctypes.c_char_p
    lib.ec_put_frame.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p]
    lib.ec_get_verify.restype = ctypes.c_int
    lib.ec_get_verify.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_size_t, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p]
    lib.ec_verify_frames.restype = ctypes.c_int
    lib.ec_verify_frames.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.ec_gf_rows.restype = None
    lib.ec_gf_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.c_size_t]
    lib.ec_recv_exact.restype = ctypes.c_ssize_t
    lib.ec_recv_exact.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int)]
    lib.ec_read_rows.restype = ctypes.c_int
    lib.ec_read_rows.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int64, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p]
    lib.ec_selftest_mul.restype = ctypes.c_int
    lib.ec_selftest_mul.argtypes = [ctypes.c_void_p, ctypes.c_int]
    if b"gfni" in lib.ec_isa():
        _gfni_selftest(lib)
    return lib


def isa() -> str:
    return load().ec_isa().decode()


@functools.lru_cache(maxsize=4096)
def _affine_qwords_cached(mat_bytes: bytes, r: int, c: int) -> np.ndarray:
    """(R, C) uint64 GFNI affine matrices: qword byte (7-row) holds the
    bit-row of the GF(2)-linear map x -> coeff*x over GF(2^8)/0x11D
    (layout calibrated against vgf2p8affineqb, self-checked at load)."""
    from minio_tpu.ops import gf256
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, c)
    mul = gf256.mul_table()
    basis = mul[mat][:, :, [1, 2, 4, 8, 16, 32, 64, 128]]   # (R,C,8): c*2^b
    # bits[..., row, b] = bit `row` of basis[..., b]
    bits = (basis[:, :, None, :] >> np.arange(8)[None, None, :, None]) & 1
    rowbits = (bits.astype(np.uint64)
               << np.arange(8, dtype=np.uint64)[None, None, None, :]
               ).sum(axis=-1)                               # (R,C,8rows)
    shifts = (8 * (7 - np.arange(8, dtype=np.uint64)))
    return np.ascontiguousarray(
        (rowbits << shifts[None, None, :]).sum(axis=-1, dtype=np.uint64))


def affine_qwords(gf_mat: np.ndarray) -> np.ndarray:
    gf_mat = np.ascontiguousarray(gf_mat, dtype=np.uint8)
    r, c = gf_mat.shape
    return _affine_qwords_cached(gf_mat.tobytes(), r, c)


def _gfni_selftest(lib) -> None:
    """Validate the affine layout against the repo's own field tables —
    a silent convention mismatch would corrupt every parity byte."""
    from minio_tpu.ops import gf256
    mul = gf256.mul_table()
    for coeff in (1, 2, 0x1D, 0x8E, 0xFF):
        q = affine_qwords(np.array([[coeff]], dtype=np.uint8))
        for x in (0, 1, 0x53, 0xFF):
            got = lib.ec_selftest_mul(q.ctypes.data, x)
            if got != int(mul[coeff, x]):
                raise RuntimeError(
                    f"GFNI affine layout mismatch: {coeff}*{x} -> {got}, "
                    f"want {int(mul[coeff, x])}")


@functools.lru_cache(maxsize=64)
def _mxh_material(shard_size: int):
    from minio_tpu.ops import mxhash
    a = mxhash.matrix_a()
    at = np.ascontiguousarray(a.T)
    corr = np.ascontiguousarray(
        (128 * a.astype(np.int32).sum(axis=0)).astype(np.int32))
    tag = np.ascontiguousarray(mxhash.length_tag(shard_size))
    return at, corr, tag


def _scratch(shard_size: int) -> np.ndarray:
    return np.empty(2 * ((max(shard_size, 1) + 255) // 256 * 32) + 64,
                    dtype=np.uint8)


def _addr(buf) -> int:
    """Base address of a writable buffer (ndarray or mmap)."""
    if isinstance(buf, np.ndarray):
        return buf.ctypes.data
    return ctypes.addressof(ctypes.c_char.from_buffer(buf))


def _raddr(buf, keep: list) -> int:
    """Base address of a read-only view (bytes/memoryview/ndarray/mmap).

    Anything materialized to get a stable pointer is appended to `keep`
    so it outlives the C call."""
    if isinstance(buf, np.ndarray):
        keep.append(buf)
        return buf.ctypes.data
    mv = memoryview(buf)
    if mv.readonly:
        arr = np.frombuffer(mv, dtype=np.uint8)   # zero-copy view
        keep.append(arr)
        return arr.ctypes.data
    obj = ctypes.c_char.from_buffer(mv)
    keep.append((mv, obj))
    return ctypes.addressof(obj)


_arena = __import__("threading").local()


def _arena_buf(nbytes: int) -> np.ndarray:
    """Reused per-thread backing for put_frame output.

    A fresh allocation beyond glibc's mmap threshold pays ~0.5 ms/MiB
    in page faults on every call (measured on the 1-core bench host);
    the framed batch is consumed (written to staging files) before the
    caller encodes its next batch, so one arena per thread is safe."""
    buf = getattr(_arena, "buf", None)
    if buf is None or buf.size < nbytes:
        buf = np.empty(nbytes, dtype=np.uint8)
        _arena.buf = buf
    return buf


def put_frame(blocks: np.ndarray, k: int, m: int,
              outs: list | None = None) -> list:
    """(nb, k, S) uint8 -> k+m framed shard streams (mxh256 frames).

    `outs`: optional k+m writable buffers (each >= nb*(32+S) bytes, e.g.
    mmap'd staging files) the kernel writes into directly; when omitted,
    per-shard views over a REUSED per-thread arena are returned — they
    are valid only until this thread's next put_frame call, which is the
    PUT staging pattern (frame batch, fan out to drives, repeat).
    ctypes releases the GIL for the whole batch.
    """
    from minio_tpu.ops.erasure_native import tables_for_matrix
    from minio_tpu.ops import gf256
    if k + m > MAX_ROWS:
        raise ValueError(f"set width {k + m} > {MAX_ROWS} "
                         "(C kernel srcs[] bound)")
    lib = load()
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    nb, kk, S = blocks.shape
    assert kk == k
    frame = HASH_SIZE + S
    views = None
    if outs is None:
        per = nb * frame
        backing = _arena_buf((k + m) * per)
        views = [backing[i * per:(i + 1) * per] for i in range(k + m)]
        ptrs = (ctypes.c_void_p * (k + m))(
            *[v.ctypes.data for v in views])
    else:
        # Caller-owned buffers (mmap'd staging files, the coalescer's
        # pooled dispatch slices): validate before handing raw pointers
        # to C — an undersized slice here is a heap overwrite, not an
        # IndexError.
        if len(outs) != k + m:
            raise ValueError(f"put_frame outs: {len(outs)} buffers "
                             f"for {k + m} shards")
        for i, o in enumerate(outs):
            if memoryview(o).nbytes < nb * frame:
                raise ValueError(
                    f"put_frame outs[{i}]: {memoryview(o).nbytes} bytes "
                    f"< {nb * frame} required")
        ptrs = (ctypes.c_void_p * (k + m))(*[_addr(o) for o in outs])
    pmat = gf256.parity_matrix(k, m)
    tabs = tables_for_matrix(pmat)
    mats = affine_qwords(pmat)
    at, corr, tag = _mxh_material(S)
    scratch = _scratch(S)
    with _span("native.put_frame"):
        lib.ec_put_frame(blocks.ctypes.data, nb, k, m, S,
                         tabs.ctypes.data, mats.ctypes.data,
                         at.ctypes.data, corr.ctypes.data,
                         tag.ctypes.data, ptrs, scratch.ctypes.data)
    return views if outs is None else outs


def get_verify(frames: list, sel: list[int], nb: int, S: int, k: int,
               m: int, targets: list[int], out=None
               ) -> tuple[np.ndarray, np.ndarray, int]:
    """Verify + gather + reconstruct one batch of framed shard segments.

    frames[j]: buffer (bytes/mmap/ndarray) holding nb frames of (32|S)
    for shard index sel[j]; len(frames) == len(sel) == the chosen K rows.
    `out`: optional writable buffer of nb*k*S bytes the data rows are
    gathered into directly (the healthy-GET fast path hands a slice of
    the final object buffer, saving the assemble copy); when omitted a
    fresh array is allocated.
    Returns (y (nb, k, S) data rows, ok flags per selected row, nbad).
    On nbad > 0, y is unusable — drop the bad rows and retry with spares.
    """
    from minio_tpu.ops.erasure_native import (tables_for_matrix,
                                              transform_matrix)
    if len(sel) > MAX_ROWS:
        raise ValueError(f"ksel {len(sel)} > {MAX_ROWS} "
                         "(C kernel srcs[] bound)")
    lib = load()
    ksel = len(sel)
    if out is None:
        y = np.empty((nb, k, S), dtype=np.uint8)
    else:
        y = np.frombuffer(out, dtype=np.uint8, count=nb * k * S)
        y = y.reshape(nb, k, S)
    ok = np.ones(ksel, dtype=np.uint8)
    sel_a = np.ascontiguousarray(sel, dtype=np.int32)
    tgt_a = np.ascontiguousarray(targets, dtype=np.int32)
    if targets:
        # Decode matrix: rows `targets` from rows `sel` (columns in sel
        # order).
        mat = transform_matrix(k, m, tuple(sel), tuple(targets))
        tabs = tables_for_matrix(mat)
        mats = affine_qwords(mat)
        tabs_ptr, mats_ptr = tabs.ctypes.data, mats.ctypes.data
    else:
        tabs_ptr = mats_ptr = None
    at, corr, tag = _mxh_material(S)
    scratch = _scratch(S)
    keep: list = []
    ptrs = (ctypes.c_void_p * ksel)(*[_raddr(f, keep) for f in frames])
    with _span("native.get_verify"):
        nbad = lib.ec_get_verify(
            ptrs, sel_a.ctypes.data, ksel, nb, S, k, tabs_ptr, mats_ptr,
            tgt_a.ctypes.data, len(targets), at.ctypes.data,
            corr.ctypes.data, tag.ctypes.data, y.ctypes.data,
            ok.ctypes.data, scratch.ctypes.data)
    return y, ok, nbad


def verify_frames(frames: list, nb: int, S: int
                  ) -> tuple[np.ndarray, int]:
    """Verdict-only bitrot check of framed shard segments (mxh256).

    frames[j]: buffer holding nb frames of (32|S).  Hashes every frame,
    compares digests, touches nothing else — no gather, no GF(2^8).
    Returns (ok flags per row, nbad).  The healthy-GET fast path and
    bench stage attribution use this to price verification separately
    from assembly.  ctypes releases the GIL for the whole batch.
    """
    if len(frames) > MAX_ROWS:
        raise ValueError(f"ksel {len(frames)} > {MAX_ROWS} "
                         "(C kernel srcs[] bound)")
    lib = load()
    ksel = len(frames)
    ok = np.ones(ksel, dtype=np.uint8)
    at, corr, tag = _mxh_material(S)
    scratch = _scratch(S)
    keep: list = []
    ptrs = (ctypes.c_void_p * ksel)(*[_raddr(f, keep) for f in frames])
    nbad = lib.ec_verify_frames(
        ptrs, ksel, nb, S, at.ctypes.data, corr.ctypes.data,
        tag.ctypes.data, ok.ctypes.data, scratch.ctypes.data)
    return ok, nbad


def gf_transform_rows(srcs: list, sel: list[int], k: int, m: int,
                      targets: list[int]) -> list[np.ndarray]:
    """Reconstruct whole logical shard rows: targets from the selected
    rows, one GF pass per target with per-row POINTERS — no batch
    stacking, no per-block loop (the heal hot path; RS is positional,
    so one call covers full blocks AND the tail fragment)."""
    from minio_tpu.ops.erasure_native import (tables_for_matrix,
                                              transform_matrix)
    if len(sel) > MAX_ROWS:
        raise ValueError(f"ksel {len(sel)} > {MAX_ROWS}")
    lib = load()
    mat = transform_matrix(k, m, tuple(sel), tuple(targets))
    tabs = tables_for_matrix(mat)
    mats = affine_qwords(mat)
    L = int(srcs[0].size)
    keep: list = []
    sptr = (ctypes.c_void_p * len(sel))(
        *[_raddr(np.ascontiguousarray(r, dtype=np.uint8), keep)
          for r in srcs])
    outs = [np.empty(L, dtype=np.uint8) for _ in targets]
    dptr = (ctypes.c_void_p * len(targets))(
        *[o.ctypes.data for o in outs])
    lib.ec_gf_rows(tabs.ctypes.data, mats.ctypes.data, sptr, len(sel),
                   dptr, len(targets), L)
    return outs


def recv_exact(fd: int, view, timeout_ms: int) -> tuple[int, int]:
    """Fill the writable contiguous buffer `view` from the connected
    stream socket `fd` in one native call: poll + recv until it is
    full or the peer closes, the GIL released once for all of it.

    Returns (bytes filled, recvs made); filled < len(view) only at the
    peer's close.  `timeout_ms` is an idle limit (that long without a
    byte, not that long a call; negative waits for ever).  Raises the
    OSError of the socket's errno: TimeoutError when the idle limit
    passed, ConnectionResetError on a reset."""
    lib = load()
    mv = memoryview(view)
    if mv.readonly or not mv.c_contiguous:
        raise ValueError("recv_exact needs a writable contiguous buffer")
    n = mv.nbytes
    if not n:
        return 0, 0
    recvs = ctypes.c_int(0)
    got = lib.ec_recv_exact(fd, _addr(mv), n, int(timeout_ms),
                            ctypes.byref(recvs))
    if got < 0:
        raise OSError(-got, os.strerror(-got))
    return got, recvs.value


# read_rows' outcomes of a candidate besides 0 (read) and an errno.
ROW_SIZE = -1           # the file ends before the range does (or after it)
ROW_UNTRIED = -2        # k slots were filled before its turn


def read_rows(paths: list[str], offset: int, length: int, k: int, out,
              exact_end: bool, drop: bool
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Read [offset, offset + length) of the files `paths`, one after
    another in that order, each into the next free `length`-byte slot
    of the writable buffer `out` (k slots), until k are filled: open,
    pread, close, in one native call with the GIL released once.
    `exact_end`: a file must end where the range does; `drop`:
    POSIX_FADV_DONTNEED once a file is read.

    Returns (err, slot, ns) per candidate: 0, the errno of a failed
    open or read, ROW_SIZE or ROW_UNTRIED; the slot it filled or -1;
    the nanoseconds it took."""
    lib = load()
    mv = memoryview(out)
    n = len(paths)
    if mv.readonly or not mv.c_contiguous or mv.nbytes < k * length:
        raise ValueError(f"read_rows needs a writable contiguous buffer "
                         f"of {k} x {length} bytes, not {mv.nbytes}")
    if length <= 0 or not 0 < k <= n:
        raise ValueError(f"read_rows: {k} slots of {length} bytes "
                         f"from {n} files")
    err = np.zeros(n, dtype=np.int32)
    slot = np.zeros(n, dtype=np.int32)
    ns = np.zeros(n, dtype=np.int64)
    cpaths = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
    lib.ec_read_rows(cpaths, n, k, offset, length, int(exact_end),
                     int(drop), _addr(mv), err.ctypes.data,
                     slot.ctypes.data, ns.ctypes.data)
    return err, slot, ns
