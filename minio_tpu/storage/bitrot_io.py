"""Streaming bitrot framing: [32-byte HighwayHash256 | shard block] per block.

Same on-disk frame layout as the reference's streaming bitrot writer/reader
(/root/reference/cmd/bitrot-streaming.go:35-189): a shard file of logical
size L with shard block size `shard_size` is stored as
ceil(L/shard_size) frames, each `32 + min(shard_size, remaining)` bytes.
`bitrot_shard_file_size` mirrors cmd/bitrot.go:146.

Hashing is vectorized across all blocks of a batch (HighwayHashVec) — the
multi-stream layout that maps onto the device hash kernel later.
"""

from __future__ import annotations

import numpy as np

import os

from ..observe import span as ospan
from ..ops.highwayhash import HighwayHash256, highwayhash256_batch
from .errors import ErrFileCorrupt

HASH_SIZE = 32

# -- bitrot algorithm registry (cf. cmd/bitrot.go:39) ------------------------
# The reference supports four algorithms with HighwayHash256S the default;
# here the default WRITE algorithm is mxh256 (ops/mxhash.py) — designed so
# verify runs as MXU matmuls at codec speed — while HighwayHash256S is kept
# for interop reads of objects written before the switch. Each entry:
# digest size and a batch hasher (n, L) uint8 -> (n, size).

_DEVICE_HASH_THRESHOLD = 1 << 16


_HH_NATIVE = None        # None = untried; False = unavailable


def _hh_native():
    """The AVX2/AVX-512 HighwayHash kernel (native/highwayhash.cc), or
    False on a host with no toolchain to build it."""
    global _HH_NATIVE
    if _HH_NATIVE is None:
        from native import hh_native
        from native._build import BuildError
        try:
            hh_native.load()
            _HH_NATIVE = hh_native.hh256_rows_native
        except BuildError:  # no g++: spec paths
            _HH_NATIVE = False
    return _HH_NATIVE


def _hh_batch(blocks: np.ndarray) -> np.ndarray:
    # HighwayHash is a serial per-stream chain: the native host kernel
    # (~8 GB/s, two streams per AVX-512 register set) beats both the
    # device formulation (~2 GB/s through 32-bit lanes) and the numpy
    # spec path — route host-first, device only as the fallback
    # (VERDICT r3 weak #2).
    native = _hh_native()
    if native:
        return native(blocks)
    if blocks.size >= _DEVICE_HASH_THRESHOLD:
        from ..ops.highwayhash_jax import hh256_batch_jax
        return np.asarray(hh256_batch_jax(blocks))
    return highwayhash256_batch(blocks)


def device_preferred(algo: str) -> bool:
    """Should this algorithm's hashing fuse into the device codec
    dispatch — on BOTH paths (GET: verify+decode, PUT:
    encode_and_hash)? mxh256 was designed for the MXU (hash at codec
    speed); HighwayHash runs faster on the host's native kernel, so HH
    shards hash host-side and the device only encodes/reconstructs —
    the engine picks the winner per recorded algo."""
    if algo == "mxh256":
        return True
    if algo.startswith("highwayhash"):
        return not _hh_native()
    return False


_MXH_NATIVE = None       # None = untried; False = unavailable


def _mxh_host(blocks: np.ndarray) -> np.ndarray:
    """Host mxh256: native AVX-VNNI kernel (native/mxh256.cc), or the
    numpy spec path on a host with no toolchain to build it."""
    global _MXH_NATIVE
    if _MXH_NATIVE is None:
        from native import mxh_native
        from native._build import BuildError
        try:
            mxh_native.load()
            _MXH_NATIVE = mxh_native.mxh256_rows_native
        except BuildError:  # no g++: spec path
            _MXH_NATIVE = False
    if _MXH_NATIVE:
        return _MXH_NATIVE(blocks)
    from ..ops.mxhash import mxh256_batch
    return mxh256_batch(blocks)


def _hashlib_batch(name: str, digest_size: int):
    import hashlib

    def hasher(blocks: np.ndarray) -> np.ndarray:
        out = np.empty((blocks.shape[0], digest_size), dtype=np.uint8)
        for i in range(blocks.shape[0]):
            h = hashlib.new(name, blocks[i].tobytes())
            out[i] = np.frombuffer(h.digest(), dtype=np.uint8)
        return out
    return hasher


ALGORITHMS: dict[str, tuple[int, object]] = {
    # mxh256 (ops/mxhash.py) is TPU-native where its shapes are bounded
    # and its uploads counted: in the fused programs and the coalescer
    # lanes' digest kernel.  This generic hasher sees any row length
    # (tails, inline objects, heal reads) — one device compile per new
    # length — so it is the host kernel, like HighwayHash's.
    "mxh256": (32, _mxh_host),
    "highwayhash256S": (32, _hh_batch),
    "highwayhash256": (32, _hh_batch),      # whole-file legacy variant
    "sha256": (32, _hashlib_batch("sha256", 32)),
    "blake2b512": (64, _hashlib_batch("blake2b", 64)),
}

# Default for READING frames whose metadata predates per-object algo
# recording (rounds 1-2 wrote HighwayHash256S unconditionally).
DEFAULT_ALGO = "highwayhash256S"

# Algorithms selectable for new writes (32-byte digests only, so the
# frame geometry — and therefore shard file sizes — is algo-independent).
WRITE_ALGORITHMS = ("mxh256", "highwayhash256S", "sha256")


def write_algo() -> str:
    """Bitrot algorithm for NEW objects: env MTPU_BITROT_ALGO; defaults
    to the TPU-native mxh256. Misconfiguration is a ValueError (validated
    again at server boot, server/__main__.py self-tests) — not a storage
    corruption error."""
    algo = os.environ.get("MTPU_BITROT_ALGO", "mxh256")
    if algo not in WRITE_ALGORITHMS:
        raise ValueError(
            f"MTPU_BITROT_ALGO={algo!r} not one of {WRITE_ALGORITHMS}")
    return algo


def digest_size(algo: str = DEFAULT_ALGO) -> int:
    try:
        return ALGORITHMS[algo][0]
    except KeyError:
        raise ErrFileCorrupt(f"unknown bitrot algorithm {algo!r}") from None


def _hash_batch(blocks: np.ndarray,
                algo: str = DEFAULT_ALGO) -> np.ndarray:
    """(n, L) uint8 -> (n, digest_size) digests for the given algorithm."""
    with ospan.span("host.hash_batch"):
        return hash_rows(blocks, algo)


def hash_rows(blocks: np.ndarray, algo: str = DEFAULT_ALGO) -> np.ndarray:
    """`_hash_batch` under no span of its own: for a caller whose span
    is the hash (engine/shardmath.py: `engine.hash`)."""
    try:
        fn = ALGORITHMS[algo][1]
    except KeyError:
        raise ErrFileCorrupt(f"unknown bitrot algorithm {algo!r}") from None
    return fn(blocks)


def whole_file_digest(data: bytes, algo: str = DEFAULT_ALGO) -> bytes:
    """Legacy whole-file bitrot (cf. cmd/bitrot-whole.go): one digest over
    the entire shard file instead of per-block frames."""
    buf = np.frombuffer(data, dtype=np.uint8)[None, :]
    if algo.startswith("highwayhash"):
        if _hh_native():
            from native.hh_native import hh256_native
            return hh256_native(data)
        h = HighwayHash256()
        h.update(data)
        return h.digest()
    return _hash_batch(np.ascontiguousarray(buf), algo)[0].tobytes()


def verify_whole_file(data: bytes, want: bytes,
                      algo: str = DEFAULT_ALGO) -> None:
    if whole_file_digest(data, algo) != want:
        raise ErrFileCorrupt(f"whole-file bitrot mismatch ({algo})")


def ceil_frac(num: int, den: int) -> int:
    return -(-num // den)


def bitrot_shard_file_size(size: int, shard_size: int,
                           algo: str = DEFAULT_ALGO) -> int:
    """On-disk size of a shard file of logical size `size`."""
    if size == 0:
        return 0
    return ceil_frac(size, shard_size) * digest_size(algo) + size


def bitrot_logical_size(disk_size: int, shard_size: int,
                        algo: str = DEFAULT_ALGO) -> int:
    """Inverse of bitrot_shard_file_size."""
    if disk_size == 0:
        return 0
    hs = digest_size(algo)
    frame = hs + shard_size
    full = disk_size // frame
    rest = disk_size % frame
    if rest:
        if rest <= hs:
            # A trailing fragment that can't hold a hash + >=1 data byte
            # only occurs on a corrupt/truncated file.
            raise ErrFileCorrupt("truncated bitrot frame")
        rest -= hs
    return full * shard_size + rest


def frame_shard(shard: np.ndarray, shard_size: int,
                algo: str = DEFAULT_ALGO) -> bytes:
    """Frame one shard file's bytes into [hash|block] frames."""
    shard = np.asarray(shard, dtype=np.uint8).ravel()
    out = bytearray()
    n_full = shard.size // shard_size
    # Vectorized hash over all the full-size blocks at once.
    if n_full:
        blocks = shard[:n_full * shard_size].reshape(n_full, shard_size)
        digests = _hash_batch(blocks, algo)
        for i in range(n_full):
            out += digests[i].tobytes()
            out += blocks[i].tobytes()
    tail = shard[n_full * shard_size:]
    if tail.size:
        out += _hash_batch(tail[None, :].copy(), algo)[0].tobytes()
        out += tail.tobytes()
    return bytes(out)


def frame_shards_batch(shards: np.ndarray,
                       digests: np.ndarray | None = None,
                       algo: str = DEFAULT_ALGO) -> list[bytes]:
    """Frame a batch at once: (n_shards, n_blocks, shard_size) -> one framed
    byte string per shard file, hashing all n_shards*n_blocks streams in a
    single vectorized pass (the hot PUT path). Pass `digests`
    ((n_shards, n_blocks, 32), e.g. from ops.fused.encode_and_hash) to skip
    hashing entirely — framing is then pure byte interleaving."""
    views = frame_shard_views(None, None, digests, algo, shards=shards)
    return [bytes(v) for v in views]


def frame_shard_views(blocks: np.ndarray | None,
                      parity: np.ndarray | None,
                      digests: np.ndarray | None,
                      algo: str = DEFAULT_ALGO,
                      shards: np.ndarray | None = None,
                      out: np.ndarray | None = None) -> list[np.ndarray]:
    """The ONE implementation of the on-disk frame layout
    ([32B digest | shard bytes] per block), producing zero-copy
    per-shard views over a single (n_shards, n_blocks, hs+S) buffer.

    Two input shapes: `shards` already shard-major
    ((n_shards, n_blocks, S)), or `blocks`/`parity` in the codec's
    block-major layout ((n_blocks, K, S) and (n_blocks, M, S)) —
    the latter avoids the caller materializing a transposed copy.
    Digests, when absent, are hashed from the contiguous inputs.

    `out`: the buffer to fill, a contiguous 1-D uint8 array of at least
    n_shards * n_blocks * (hs + S) bytes that the caller owns (a PUT
    stream's reused one: engine/shardmath.py); the views are over its
    head and live as long as the caller leaves it alone.  Without one
    a fresh buffer is allocated per call."""
    hs = digest_size(algo)
    if shards is not None:
        n_shards, n_blocks, shard_size = shards.shape
    else:
        n_blocks, k, shard_size = blocks.shape
        n_shards = k + parity.shape[1]
    shape = (n_shards, n_blocks, hs + shard_size)
    if out is None:
        framed = np.empty(shape, dtype=np.uint8)
    else:
        need = shape[0] * shape[1] * shape[2]
        if (out.dtype != np.uint8 or out.ndim != 1
                or not out.flags.c_contiguous or out.size < need):
            raise ValueError(
                f"frame_shard_views out: need {need} contiguous uint8 "
                f"bytes, got {out.dtype}{out.shape}")
        framed = out[:need].reshape(shape)
    if shards is not None:
        framed[:, :, hs:] = shards
        if digests is None:
            flat = np.ascontiguousarray(shards).reshape(
                n_shards * n_blocks, shard_size)
            digests = _hash_batch(flat, algo).reshape(
                n_shards, n_blocks, hs)
        framed[:, :, :hs] = digests
        return [framed[i].reshape(-1) for i in range(n_shards)]

    nb, m = n_blocks, n_shards - k
    framed[:k, :, hs:] = blocks.transpose(1, 0, 2)
    framed[k:, :, hs:] = parity.transpose(1, 0, 2)
    if digests is not None:
        framed[:, :, :hs] = digests
    else:
        # Hash blocks/parity in their native contiguous layouts (no
        # big strided reads); only the 32-byte digests transpose.
        bd = _hash_batch(np.ascontiguousarray(blocks).reshape(
            nb * k, shard_size), algo).reshape(nb, k, hs)
        pd = _hash_batch(np.ascontiguousarray(parity).reshape(
            nb * m, shard_size), algo).reshape(nb, m, hs)
        framed[:k, :, :hs] = bd.transpose(1, 0, 2)
        framed[k:, :, :hs] = pd.transpose(1, 0, 2)
    return [framed[i].reshape(-1) for i in range(k + m)]


def unframe_shard(data: bytes, shard_size: int, verify: bool = True,
                  logical_size: int | None = None,
                  algo: str = DEFAULT_ALGO) -> np.ndarray:
    """Parse and (optionally) verify a framed shard file back to raw bytes.

    Raises ErrFileCorrupt on hash mismatch or size inconsistency — the same
    condition the reference's verifying ReadAt surfaces
    (cmd/bitrot-streaming.go:142).
    """
    if logical_size is not None and len(data) != bitrot_shard_file_size(
            logical_size, shard_size, algo):
        raise ErrFileCorrupt("framed size mismatch")
    hs = digest_size(algo)
    buf = np.frombuffer(data, dtype=np.uint8)
    frame = hs + shard_size
    n_full = buf.size // frame
    rest = buf.size % frame
    pieces = []
    if n_full:
        frames = buf[:n_full * frame].reshape(n_full, frame)
        if verify and algo == "mxh256" and n_full * shard_size >= (1 << 18):
            # Fused native pass (heal/scanner hot path): hash-verify and
            # gather the frames in one sweep instead of
            # contiguous-copy -> hash -> concatenate-copy.
            from native import ecio_native
            from native._build import BuildError
            try:
                y, _, nbad = ecio_native.get_verify(
                    [frames], [0], n_full, shard_size, 1, 1, [])
            except BuildError:  # no toolchain: numpy path
                pass
            else:
                if nbad:
                    raise ErrFileCorrupt("bitrot hash mismatch")
                pieces.append(y.reshape(-1))
                frames = None
        if frames is not None:
            hashes = frames[:, :hs]
            blocks = frames[:, hs:]
            if verify:
                got = _hash_batch(np.ascontiguousarray(blocks), algo)
                if not np.array_equal(got, hashes):
                    raise ErrFileCorrupt("bitrot hash mismatch")
            pieces.append(blocks.reshape(-1))
    if rest:
        tail = buf[n_full * frame:]
        if tail.size <= hs:
            raise ErrFileCorrupt("truncated bitrot frame")
        h, block = tail[:hs], tail[hs:]
        if verify:
            got = _hash_batch(np.ascontiguousarray(block)[None, :], algo)
            if got[0].tobytes() != h.tobytes():
                raise ErrFileCorrupt("bitrot hash mismatch (tail)")
        pieces.append(block)
    if not pieces:
        return np.zeros(0, dtype=np.uint8)
    return np.concatenate(pieces)


def read_frames_range(data: bytes, shard_size: int, block_start: int,
                      block_end: int, verify: bool = True,
                      algo: str = DEFAULT_ALGO) -> np.ndarray:
    """Read shard blocks [block_start, block_end) from a framed file —
    the ranged-read fast path (no need to touch earlier frames)."""
    frame = digest_size(algo) + shard_size
    sub = data[block_start * frame:block_end * frame]
    return unframe_shard(sub, shard_size, verify=verify, algo=algo)
