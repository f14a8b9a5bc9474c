"""The plain reference: what an acknowledged object must look like on disk.

A copy, independent of the program and of any device code, of
  - the systematic Reed-Solomon code over GF(2^8) (polynomial 0x11D,
    generator 2, Vandermonde matrix times the inverse of its top square:
    klauspost/reedsolomon's default, which MinIO writes), in numpy;
  - the mxh256 bitrot digest, from its spec (exact int8 x int8 -> int32
    matrix products in a tree, XOR a length tag), in numpy;
  - HighwayHash-256 under MinIO's fixed bitrot key (`highwayhash256S`,
    MinIO's DefaultBitrotAlgorithm), from the published algorithm, in numpy:
    many messages of one length advance in lock-step as rows of one array;
  - the shard file layout: one file per drive and part, a sequence of frames
    [32-byte digest | shard block], one frame per 1 MiB erasure block, the
    digest by the algorithm the configuration states (`ALGOS`).
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


# -- HighwayHash-256 (google/highwayhash, portable C; key: minio cmd/bitrot.go) ----

# magicHighwayHash256Key: public, the same in every MinIO deployment.
HH_KEY = bytes.fromhex("4be734fa8e238acd263e83e6bb968552"
                       "040f935da39f441497e09d1322de36a0")
_U64 = np.dtype("<u8")
_HH_MUL0 = np.array([0xdbe6d5d5fe4cce2f, 0xa4093822299f31d0,
                     0x13198a2e03707344, 0x243f6a8885a308d3], dtype=_U64)
_HH_MUL1 = np.array([0x3bd39e10cb0ef593, 0xc0acf169b5f18a8c,
                     0xbe5466cf34e90c6c, 0x452821e638d01377], dtype=_U64)
_LOW32, _S32 = np.uint64(0xFFFFFFFF), np.uint64(32)
# The zipper merge is a permutation of the 16 bytes of a pair of lanes
# (v0 = bytes 0-7, v1 = bytes 8-15; out: the byte added to lane 0, lane 1),
# the same on both pairs of a state's four lanes.
_ZIP16 = [3, 12, 2, 5, 14, 1, 15, 0, 11, 4, 10, 13, 9, 6, 8, 7]
_ZIP32 = np.array(_ZIP16 + [16 + i for i in _ZIP16])


def _swap32(v: np.ndarray) -> np.ndarray:
    """The two 32-bit halves of every 64-bit lane exchanged."""
    return (v >> _S32) | (v << _S32)


class _HHState:
    """n HighwayHash states, each four 64-bit lanes of v0, v1, mul0, mul1."""

    def __init__(self, n: int):
        key = np.frombuffer(HH_KEY, dtype=_U64)
        self.mul0 = np.tile(_HH_MUL0, (n, 1))
        self.mul1 = np.tile(_HH_MUL1, (n, 1))
        self.v0 = self.mul0 ^ key
        self.v1 = self.mul1 ^ _swap32(key)

    def update(self, packet: np.ndarray) -> None:
        """One 32-byte packet a state: (n, 4) little-endian lanes.  Sums
        and products wrap at 64 bits, as numpy's uint64 does."""
        self.v1 += self.mul0 + packet
        self.mul0 ^= (self.v1 & _LOW32) * (self.v0 >> _S32)
        self.v0 += self.mul1
        self.mul1 ^= (self.v0 & _LOW32) * (self.v1 >> _S32)
        self.v0 += self._zipped(self.v1)
        self.v1 += self._zipped(self.v0)

    @staticmethod
    def _zipped(v: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(
            v.view(np.uint8)[:, _ZIP32]).view(_U64)

    def remainder(self, tail: np.ndarray) -> None:
        """The last 1..31 bytes of each message, (n, r) uint8."""
        n, r = tail.shape
        self.v0 += np.uint64((r << 32) + r)
        half = self.v1.view("<u4")          # each 32-bit half rotated by r
        half[:] = (half << np.uint32(r)) | (half >> np.uint32(32 - r))
        whole, mod4 = r & ~3, r & 3
        packet = np.zeros((n, 32), dtype=np.uint8)
        packet[:, :whole] = tail[:, :whole]
        if r & 16:
            packet[:, 28:] = tail[:, r - 4:]
        elif mod4:
            packet[:, 16] = tail[:, whole]
            packet[:, 17] = tail[:, whole + (mod4 >> 1)]
            packet[:, 18] = tail[:, r - 1]
        self.update(packet.view(_U64))

    def digest(self) -> np.ndarray:
        for _ in range(10):
            self.update(_swap32(self.v0[:, [2, 3, 0, 1]]))
        out = np.empty_like(self.v0)
        for lo in (0, 2):                   # 256 bits -> 128, a pair a time
            a0 = self.v0[:, lo] + self.mul0[:, lo]
            a1 = self.v0[:, lo + 1] + self.mul0[:, lo + 1]
            a2 = self.v1[:, lo] + self.mul1[:, lo]
            a3 = (self.v1[:, lo + 1] + self.mul1[:, lo + 1]) \
                & np.uint64(0x3FFFFFFFFFFFFFFF)
            one, two = np.uint64(1), np.uint64(2)
            out[:, lo] = a0 ^ (a2 << one) ^ (a2 << two)
            out[:, lo + 1] = a1 ^ ((a3 << one) | (a2 >> np.uint64(63))) \
                ^ ((a3 << two) | (a2 >> np.uint64(62)))
        return out.view(np.uint8)


def highwayhash256_rows(rows: np.ndarray) -> np.ndarray:
    """(n, L) uint8 -> (n, 32) uint8: HighwayHash-256 of each row under
    `HH_KEY`.  The n messages advance together, a packet a step."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, length = rows.shape
    whole = length & ~31
    state = _HHState(n)
    # (steps, n, 4): a step's packets lie together.
    packets = np.ascontiguousarray(
        rows[:, :whole].reshape(n, -1, 32).transpose(1, 0, 2)).view(_U64)
    for packet in packets:
        state.update(packet)
    if length > whole:
        state.remainder(rows[:, whole:])
    return state.digest()


# -- the shard files of one part -------------------------------------------------

# What a configuration's `bitrot_algo` may say -> the digest in front of each
# shard block.  `highwayhash256S` is MinIO's streaming layout, which is the
# layout here: one digest a shard block.
ALGOS = {"mxh256": mxh256_rows, "highwayhash256S": highwayhash256_rows}
DEFAULT_ALGO = "mxh256"         # where a configuration states none


def frame_digests(blocks: list[np.ndarray], algo: str) -> list[np.ndarray]:
    """The (k+m, 32) digests of each block's (k+m, L) shard rows.  mxh256
    goes a block at a time (its products are float32: four bytes a byte);
    HighwayHash takes a part's full blocks in one lock-step pass, and the
    shorter block a part may end on in another."""
    hash_rows = ALGOS[algo]
    if algo == "mxh256" or not blocks:
        return [hash_rows(b) for b in blocks]
    full = sum(b.shape[1] == blocks[0].shape[1] for b in blocks)
    digests = hash_rows(np.concatenate(blocks[:full]))
    return [*digests.reshape(full, -1, DIGEST),
            *(hash_rows(b) for b in blocks[full:])]


def shard_files(body: bytes | memoryview, k: int, m: int,
                algo: str = DEFAULT_ALGO) -> list[bytes]:
    """The k+m shard files (frames and all) of an object or part whose
    bytes are `body`, in shard order 1..k+m, framed with `algo`'s digests."""
    body = memoryview(body)
    blocks = [encode_block(body[off:off + BLOCK], k, m)
              for off in range(0, len(body), BLOCK)]
    files: list[list[bytes]] = [[] for _ in range(k + m)]
    for rows, digests in zip(blocks, frame_digests(blocks, algo)):
        for i in range(k + m):
            files[i].append(digests[i].tobytes())
            files[i].append(rows[i].tobytes())
    return [b"".join(f) for f in files]


def compare_part(body: bytes | memoryview, k: int, m: int,
                 on_disk: list[bytes], algo: str = DEFAULT_ALGO) -> dict:
    """Hold the files found on the drives for one part against the
    reference.  `on_disk` has one entry per drive that holds the part, in
    any order (which drive holds which shard is the program's choice; that
    each shard is there exactly once is not).  Returns counts: frames
    compared, frames whose data or parity bytes differ, frames whose digest
    differs, shards missing or doubled."""
    want = shard_files(body, k, m, algo)
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
