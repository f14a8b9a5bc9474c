"""TPU-native Reed-Solomon codec: GF(2^8) shard math as MXU matmuls.

The reference's hot loop is a (parity x data) GF(2^8) matrix multiply per
1 MiB block, executed as AVX512 Galois-multiply assembly
(/root/reference/cmd/erasure-coding.go:77, klauspost/reedsolomon). TPUs have
no byte-level Galois ops — instead we exploit that multiplication by a
constant in GF(2^8) is linear over GF(2): unpack shard bytes into 8 bit-planes
and the whole codec becomes a
    (8*rows x 8*cols) binary-matrix @ (8*cols x shard_size) bit-plane
matmul with XOR accumulation (= integer matmul mod 2) — exactly the batched
matmul shape the MXU is built for. Bits are carried as bf16 0/1 values
(products and sums here are exact: max inner dim 8*16=128 << 2^8 mantissa).

Layout: *plane-major* bit rows (row j*C + c = bit j of byte-column c), which
lets unpack/pack be one broadcasted shift/weighted-sum over the whole tile.

This module is the portable XLA path (runs on CPU/TPU, used by tests and as
the sharding building block); ops/erasure_pallas.py fuses unpack->matmul->pack
into one VMEM pass to cut HBM traffic 16x.

All codec entry points take batches of blocks: (B, C, S) uint8 — B blocks
staged into HBM at once, the TPU analogue of the reference's per-block
streaming SIMD calls (SURVEY.md §5 "blocks are the natural batch dimension").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256


# ---------------------------------------------------------------------------
# Host-side matrix preparation.
# ---------------------------------------------------------------------------

def _plane_major_bits(gf_matrix: np.ndarray) -> np.ndarray:
    """Expand an (R, C) GF(2^8) matrix to plane-major (8R, 8C) GF(2) bits.

    out[i*R + r, j*C + c] = bit i of (gf_matrix[r, c] * 2^j).
    """
    gf_matrix = np.asarray(gf_matrix, dtype=np.uint8)
    r, c = gf_matrix.shape
    bits = gf256.expand_matrix_to_bits(gf_matrix)  # byte-major (8r, 8c)
    row_perm = np.arange(8 * r).reshape(8, r)  # [i, rr] -> position i*r+rr
    row_src = (np.arange(r)[None, :] * 8 + np.arange(8)[:, None]).ravel()
    col_src = (np.arange(c)[None, :] * 8 + np.arange(8)[:, None]).ravel()
    del row_perm
    return bits[row_src][:, col_src]


@functools.lru_cache(maxsize=256)
def _encode_matrix_bits(data_shards: int, parity_shards: int) -> np.ndarray:
    return _plane_major_bits(gf256.parity_matrix(data_shards, parity_shards))


@functools.lru_cache(maxsize=4096)
def _transform_matrix_bits(data_shards: int, parity_shards: int,
                           sources: tuple[int, ...],
                           targets: tuple[int, ...]) -> np.ndarray:
    """Bit matrix mapping `sources` shard rows -> `targets` shard rows.

    sources: indices of >= data_shards available shards (first K used).
    targets: arbitrary shard indices to (re)compute — missing data rows for a
    GET-path decode, any missing rows for a heal, parity rows for encode.
    This single primitive covers the reference's Encode / ReconstructData /
    Heal seams (cmd/erasure-coding.go:77,96; cmd/erasure-lowlevel-heal.go:31).
    """
    k = data_shards
    full = gf256.build_matrix(k, k + parity_shards)
    use = list(sources)[:k]
    inv = gf256.gf_mat_invert(full[use, :])
    target_rows = full[list(targets), :]
    gf_mat = gf256.gf_matmul(target_rows, inv)
    return _plane_major_bits(gf_mat)


# ---------------------------------------------------------------------------
# Device kernels (portable XLA).
# ---------------------------------------------------------------------------

def _unpack_planes(x: jax.Array) -> jax.Array:
    """(B, C, S) uint8 -> (B, 8C, S) bf16 bit-planes, plane-major."""
    b, c, s = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None, None]
    planes = (x[:, None, :, :] >> shifts) & jnp.uint8(1)
    return planes.reshape(b, 8 * c, s).astype(jnp.bfloat16)


def _pack_planes(y: jax.Array, rows: int) -> jax.Array:
    """(B, 8R, S) f32 integer counts -> (B, R, S) uint8 (mod-2 then pack)."""
    b, r8, s = y.shape
    bits = jnp.bitwise_and(y.astype(jnp.int32), 1)
    planes = bits.reshape(b, 8, rows, s)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))[None, :, None, None]
    return jnp.sum(planes * weights, axis=1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("rows",))
def _gf_matmul_blocks(mat_bits: jax.Array, x: jax.Array, rows: int) -> jax.Array:
    """Batched GF(2^8) matmul via bit-planes.

    mat_bits: (8R, 8C) bf16 0/1 (plane-major); x: (B, C, S) uint8.
    Returns (B, R, S) uint8 = GF-matmul of the underlying (R, C) GF matrix.
    """
    planes = _unpack_planes(x)  # (B, 8C, S)
    y = jnp.einsum("rc,bcs->brs", mat_bits, planes,
                   preferred_element_type=jnp.float32)
    return _pack_planes(y, rows)


class ReedSolomonTPU:
    """Device codec with the same narrow seam as the reference's `Erasure`.

    Encode/reconstruct/heal all lower onto one batched bit-plane matmul; the
    (tiny) GF matrix algebra runs on host, mirroring how the reference keeps
    matrix inversion in Go while the shard math is SIMD
    (cmd/erasure-coding.go:35 holds the codec behind a narrow closure).
    """

    def __init__(self, data_shards: int, parity_shards: int,
                 use_pallas: bool | None = None):
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        if use_pallas is None:
            from . import devices
            use_pallas = devices.on_tpu()
        self.use_pallas = use_pallas

    # -- core primitive -------------------------------------------------------

    def _apply(self, mat_bits: np.ndarray, x: jax.Array, rows: int,
               salt: jax.Array | None = None) -> jax.Array:
        mat = jnp.asarray(mat_bits, dtype=jnp.bfloat16)
        if self.use_pallas:
            from . import erasure_pallas
            return erasure_pallas.gf_matmul_blocks(mat, x, rows, salt=salt)
        if salt is not None:
            x = x ^ salt[0].astype(jnp.uint8)
        return _gf_matmul_blocks(mat, x, rows)

    # -- public API -----------------------------------------------------------

    def encode_blocks(self, data: jax.Array | np.ndarray,
                      salt: jax.Array | None = None) -> jax.Array:
        """(B, K, S) data shards -> (B, M, S) parity shards.

        salt: benchmark-protocol scalar xor of the input inside the
        kernel (see erasure_pallas.gf_matmul_blocks); production None.
        """
        data = jnp.asarray(data, dtype=jnp.uint8)
        mat = _encode_matrix_bits(self.data_shards, self.parity_shards)
        return self._apply(mat, data, self.parity_shards, salt=salt)

    def transform_blocks(self, shards: jax.Array | np.ndarray,
                         sources: tuple[int, ...],
                         targets: tuple[int, ...],
                         salt: jax.Array | None = None) -> jax.Array:
        """(B, K, S) shards at rows `sources[:K]` -> (B, T, S) rows `targets`.

        The universal decode/heal primitive: reconstruct any target rows from
        any K available rows.
        """
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        mat = _transform_matrix_bits(self.data_shards, self.parity_shards,
                                     tuple(sources), tuple(targets))
        return self._apply(mat, shards, len(targets), salt=salt)

    def reconstruct_blocks(self, shards: list[jax.Array | np.ndarray | None],
                           data_only: bool = False) -> list[jax.Array]:
        """Fill missing entries of a (total_shards)-list of (B, S) arrays."""
        available = [i for i, s in enumerate(shards) if s is not None]
        if len(available) < self.data_shards:
            raise ValueError("too few shards to reconstruct")
        limit = self.data_shards if data_only else self.total_shards
        missing = [i for i in range(limit)
                   if i < len(shards) and shards[i] is None]
        if not missing:
            return list(shards)
        use = available[:self.data_shards]
        x = jnp.stack([jnp.asarray(shards[i], dtype=jnp.uint8) for i in use],
                      axis=1)  # (B, K, S)
        out = self.transform_blocks(x, tuple(use), tuple(missing))
        result = list(shards)
        for j, idx in enumerate(missing):
            result[idx] = out[:, j, :]
        return result
