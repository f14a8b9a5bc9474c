"""Fused Pallas TPU kernel for the batched GF(2^8) bit-plane matmul.

The portable XLA path (ops/erasure_jax.py) materializes bf16 bit-planes in
HBM — 16x the input bytes of traffic; measured ~4x slower than this kernel on
chip. Here unpack -> MXU matmul -> mod-2 -> byte pack are fused into one
VMEM-resident pass per (block, lane-tile) grid step, so HBM traffic is just
shard bytes in + computed shards out — the device analogue of the reference
streaming 1 MiB blocks through AVX512 registers (cmd/erasure-encode.go:73).

Design notes (measured on the target chip):
- Plane construction by 2D `concat` of `(x >> j) & 1` slices avoids the
  cross-sublane relayouts that made a 4D-reshape variant ~50x slower.
- The matmul is skinny ((8R x 8C) @ (8C x TILE_S), e.g. 32x64 for EC:8+4 —
  ~12% MXU occupancy) but the kernel is HBM-bound on the target, so
  occupancy tricks (block-diagonal batching, int8 MXU) measured neutral;
  the simple 2D form is kept.
- Encode, decode/reconstruct, and heal all call this one kernel with
  different (tiny, host-built) matrices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane tile along the shard dimension; multiple of 128.
DEFAULT_TILE_S = 8192

# Set True in tests to exercise the kernel in interpreter mode off-TPU.
FORCE_INTERPRET = False


def tile_plan(s: int) -> tuple[int, int]:
    """(tile, steps) along a shard axis of s > 0 bytes.

    A shard of DEFAULT_TILE_S or fewer bytes is one full-width block.  A
    longer one runs in DEFAULT_TILE_S tiles, cdiv(s, tile) of them: where
    the tile divides s (EC:8+4, 2+2 at 1 MiB) they cover it exactly; where
    it does not (S = ceil(1 MiB / K) for K = 3, 5, 6, 7, 12) the last tile
    is ragged.  Its out-of-range input columns hold whatever the block
    buffer held, and the matmul is bytewise along S, so they only feed
    output columns that lie past s, which the kernel's write-back masks.
    """
    if s <= DEFAULT_TILE_S:
        return s, 1
    return DEFAULT_TILE_S, pl.cdiv(s, DEFAULT_TILE_S)


def _unpack_mm_pack(x, mat_ref, rows: int):
    planes = jnp.concatenate(
        [(x >> j) & 1 for j in range(8)], axis=0).astype(jnp.bfloat16)
    y = jnp.dot(mat_ref[...], planes,
                preferred_element_type=jnp.float32)      # (8R, TS)
    bits = y.astype(jnp.int32) & 1                       # plane-major: row j*R+r
    out = bits[0:rows]
    for j in range(1, 8):
        out = out | (bits[j * rows:(j + 1) * rows] << j)
    return out.astype(jnp.uint8)


def _kernel(mat_ref, x_ref, out_ref, *, rows: int):
    """One grid step: (C, TILE_S) uint8 shards -> (R, TILE_S) output shards."""
    x = x_ref[0].astype(jnp.int32)                      # (C, TS)
    out_ref[0] = _unpack_mm_pack(x, mat_ref, rows)


def _kernel_salted(salt_ref, mat_ref, x_ref, out_ref, *, rows: int):
    """Benchmark-protocol variant: input bytes are xor-perturbed by a
    per-dispatch scalar INSIDE the kernel (VMEM, zero extra HBM traffic)
    so a timing loop can defeat CSE/hoisting without the host-side
    128 MiB xor pass that used to dominate the measurement."""
    x = (x_ref[0].astype(jnp.int32) ^ salt_ref[0]) & 0xFF
    out_ref[0] = _unpack_mm_pack(x, mat_ref, rows)


@functools.partial(jax.jit,
                   static_argnames=("rows", "interpret"))
def _pallas_gf_matmul(mat: jax.Array, x: jax.Array, rows: int,
                      interpret: bool = False,
                      salt: jax.Array | None = None) -> jax.Array:
    b, c, s = x.shape
    tile_s, steps = tile_plan(s)
    common = dict(
        grid=(b, steps),
        out_specs=pl.BlockSpec((1, rows, tile_s), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, rows, s), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=2 * (8 * rows) * (8 * c) * s * b,
            bytes_accessed=b * c * s + b * rows * s,
            transcendentals=0),
        interpret=interpret,
    )
    mat_spec = pl.BlockSpec((8 * rows, 8 * c), lambda i, j: (0, 0),
                            memory_space=pltpu.VMEM)
    x_spec = pl.BlockSpec((1, c, tile_s), lambda i, j: (i, 0, j),
                          memory_space=pltpu.VMEM)
    if salt is None:
        return pl.pallas_call(
            functools.partial(_kernel, rows=rows),
            in_specs=[mat_spec, x_spec], **common)(mat, x)
    return pl.pallas_call(
        functools.partial(_kernel_salted, rows=rows),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), mat_spec,
                  x_spec], **common)(salt, mat, x)


def gf_matmul_blocks(mat_bits: jax.Array | np.ndarray, x: jax.Array,
                     rows: int, salt: jax.Array | None = None) -> jax.Array:
    """Fused-kernel GF(2^8) batched matmul; drop-in for the XLA path.

    mat_bits: (8R, 8C) plane-major bit matrix; x: (B, C, S) uint8 shards.
    On a TPU every geometry runs the kernel on x as it is, at
    `tile_plan(S)`: a shard size that no large tile divides (K = 3, 5, 6,
    7, 12 over a 1 MiB block) is neither padded nor sliced, its last
    tile ragged.  Off-TPU the portable XLA path (ops/erasure_jax.py)
    serves, unless a test sets FORCE_INTERPRET to run the kernel in the
    interpreter.

    salt: optional (1,) int32 — xors every input byte inside the kernel
    (benchmark protocol; production passes None and pays nothing).
    """
    from . import devices, erasure_jax

    with jax.named_scope("gf_matmul"):
        x = jnp.asarray(x, dtype=jnp.uint8)
        b, c, s = x.shape
        mat = jnp.asarray(mat_bits, dtype=jnp.bfloat16)
        on_tpu = devices.on_tpu()
        if (not on_tpu and not FORCE_INTERPRET) or b == 0 or s == 0:
            if salt is not None:
                x = x ^ salt[0].astype(jnp.uint8)
            return erasure_jax._gf_matmul_blocks(mat, x, rows)
        return _pallas_gf_matmul(mat, x, rows, interpret=not on_tpu,
                                 salt=salt)
