"""The plain reference: what an acknowledged object must look like on disk.

A copy, independent of the program and of any device code, of
  - the systematic Reed-Solomon code over GF(2^8) (polynomial 0x11D,
    generator 2, Vandermonde matrix times the inverse of its top square:
    klauspost/reedsolomon's default, which MinIO writes), in numpy;
  - the mxh256 bitrot digest, from its spec (exact int8 x int8 -> int32
    matrix products in a tree, XOR a length tag), in numpy;
  - the shard file layout: one file per drive and part, a sequence of frames
    [32-byte digest | shard block], one frame per 1 MiB erasure block.
Imports numpy and hashlib only.
"""

from __future__ import annotations

import functools
import hashlib
import struct

import numpy as np

BLOCK = 1 << 20          # the erasure block (MinIO's blockSizeV2)
DIGEST = 32              # bytes of digest in front of every shard block
_POLY = 0x11D


# -- GF(2^8) -------------------------------------------------------------------

@functools.cache
def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(exp[510], log[256], mul[256, 256])."""
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:] = exp[:255]
    mul = exp[log[:, None] + log[None, :]]
    mul[0, :] = 0
    mul[:, 0] = 0
    return exp, log, mul.astype(np.uint8)


def _gf_pow(a: int, n: int) -> int:
    exp, log, _ = _tables()
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(exp[(log[a] * n) % 255])


def _gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(m, k) @ (k, n) over GF(2^8): XOR of table-lookup rows."""
    mul = _tables()[2]
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(a.shape[1]):
            if a[i, j]:
                out[i] ^= mul[a[i, j]][b[j]]
    return out


def _gf_invert(m: np.ndarray) -> np.ndarray:
    exp, log, mul = _tables()
    n = m.shape[0]
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for r in range(n):
        if work[r, r] == 0:
            below = np.nonzero(work[r + 1:, r])[0]
            if below.size == 0:
                raise ValueError("singular matrix")
            swap = r + 1 + below[0]
            work[[r, swap]] = work[[swap, r]]
        inv = int(exp[255 - log[work[r, r]]])
        work[r] = mul[inv][work[r]]
        for rr in range(n):
            if rr != r and work[rr, r]:
                work[rr] ^= mul[work[rr, r]][work[r]]
    return work[:, n:].copy()


@functools.cache
def parity_rows(k: int, m: int) -> np.ndarray:
    """The (m, k) parity rows of the systematic coding matrix."""
    vm = np.array([[_gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    full = _gf_matmul(vm, _gf_invert(vm[:k, :k]))
    if not np.array_equal(full[:k], np.eye(k, dtype=np.uint8)):
        raise AssertionError("coding matrix is not systematic")
    return full[k:].copy()


def data_rows(block: bytes | memoryview, k: int) -> np.ndarray:
    """One erasure block (<= 1 MiB) -> its (k, ceil(len/k)) data rows: the
    block split in k, zero-padded.  The code is systematic, so these are
    the shard blocks of data shards 1..k as written."""
    buf = np.frombuffer(block, dtype=np.uint8)
    s = -(-buf.size // k)
    data = np.zeros(k * s, dtype=np.uint8)
    data[:buf.size] = buf
    return data.reshape(k, s)


def encode_block(block: bytes | memoryview, k: int, m: int) -> np.ndarray:
    """One erasure block (<= 1 MiB) -> (k+m, ceil(len/k)) shard rows:
    the data rows, then the m parity rows."""
    data = data_rows(block, k)
    return np.concatenate([data, _gf_matmul(parity_rows(k, m), data)])


# -- mxh256 (spec: minio_tpu/ops/mxhash.py docstring) ---------------------------

_CHUNK, _WORDS = 256, 8


def _sha_stream(seed: bytes, nbytes: int) -> bytes:
    out = bytearray()
    i = 0
    while len(out) < nbytes:
        out += hashlib.sha256(seed + struct.pack("<Q", i)).digest()
        i += 1
    return bytes(out[:nbytes])


@functools.cache
def _matrix_a() -> np.ndarray:
    raw = np.frombuffer(_sha_stream(b"minio-tpu/mxh256/A/v1",
                                    _CHUNK * _WORDS), dtype=np.uint8)
    # float32 holds every integer below 2^24 exactly, and no partial sum of
    # 256 int8 x int8 products reaches it, so the BLAS product is exact.
    return (raw | 1).astype(np.int8).reshape(_CHUNK, _WORDS).astype(np.float32)


def mxh256_rows(rows: np.ndarray) -> np.ndarray:
    """(n, L) uint8 -> (n, 32) uint8: the digest of each row."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, length = rows.shape
    cur = rows
    while True:
        ln = cur.shape[1]
        pad = (-ln) % _CHUNK
        if pad or ln == 0:
            cur = np.pad(cur, ((0, 0), (0, max(pad, _CHUNK - ln))))
        chunks = cur.reshape(-1, _CHUNK).view(np.int8).astype(np.float32)
        words = chunks @ _matrix_a()
        cur = np.ascontiguousarray(words.astype("<i4")).view(np.uint8) \
            .reshape(n, -1)
        if cur.shape[1] == DIGEST:
            break
    tag = hashlib.sha256(b"minio-tpu/mxh256/len/v1"
                         + struct.pack("<Q", length)).digest()
    return cur ^ np.frombuffer(tag, dtype=np.uint8)[None, :]


# -- the shard files of one part -------------------------------------------------

def shard_files(body: bytes | memoryview, k: int, m: int) -> list[bytes]:
    """The k+m shard files (frames and all) of an object or part whose
    bytes are `body`, in shard order 1..k+m."""
    body = memoryview(body)
    files: list[list[bytes]] = [[] for _ in range(k + m)]
    for off in range(0, len(body), BLOCK):
        rows = encode_block(body[off:off + BLOCK], k, m)
        digests = mxh256_rows(rows)
        for i in range(k + m):
            files[i].append(digests[i].tobytes())
            files[i].append(rows[i].tobytes())
    return [b"".join(f) for f in files]


def compare_part(body: bytes | memoryview, k: int, m: int,
                 on_disk: list[bytes]) -> dict:
    """Hold the files found on the drives for one part against the
    reference.  `on_disk` has one entry per drive that holds the part, in
    any order (which drive holds which shard is the program's choice; that
    each shard is there exactly once is not).  Returns counts: frames
    compared, frames whose data or parity bytes differ, frames whose digest
    differs, shards missing or doubled."""
    want = shard_files(body, k, m)
    index = {hashlib.sha256(w).digest(): i for i, w in enumerate(want)}
    # (offset in the shard file, shard block length) of every frame.
    layout, pos = [], 0
    for off in range(0, len(body), BLOCK):
        s = -(-min(BLOCK, len(body) - off) // k)
        layout.append((pos, s))
        pos += DIGEST + s
    s0 = layout[0][1]
    seen: dict[int, int] = {}
    bad_bytes = bad_digest = frames = 0
    for got in on_disk:
        i = index.get(hashlib.sha256(got).digest())
        if i is not None:
            seen[i] = seen.get(i, 0) + 1
            frames += len(layout)
            continue
        # Not one of the k+m files.  Say how it differs from the shard
        # whose first block it carries; a file that is no shard's at all
        # counts as one frame of wrong bytes.
        near = next((j for j, w in enumerate(want)
                     if got[DIGEST:DIGEST + s0] == w[DIGEST:DIGEST + s0]
                     and len(got) == len(w)), None)
        if near is None:
            bad_bytes += 1
            continue
        seen[near] = seen.get(near, 0) + 1
        w = want[near]
        for pos, s in layout:
            frames += 1
            bad_digest += got[pos:pos + DIGEST] != w[pos:pos + DIGEST]
            bad_bytes += (got[pos + DIGEST:pos + DIGEST + s]
                          != w[pos + DIGEST:pos + DIGEST + s])
    missing = sum(1 for i in range(k + m) if seen.get(i, 0) != 1)
    return {"frames": frames, "bad_bytes": int(bad_bytes),
            "bad_digest": int(bad_digest), "shards_missing": missing}
