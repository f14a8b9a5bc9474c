"""Device path for mxh256 (ops/mxhash.py): the digest as MXU int8 matmuls.

Every level is one (rows, 256) int8 @ (256, 8) int8 -> int32 matmul with
exact integer accumulation — bytes feed the MXU directly (no bit-plane
unpack, so HBM traffic stays ~1x the hashed bytes).  The level loop is a
Python loop over STATIC shapes: a fixed input length compiles to a fixed
chain of shrinking matmuls (depth ceil(log8(L/32))), all inside one jit.

`mxh256_rows` is the traceable core shared with ops/fused.py, where the
digest rides in the same dispatch as the erasure matmul (north-star
config #5): the shard bytes cross HBM once for verify + reconstruct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mxhash


def _matrix_a_dev():
    # jnp.asarray of the cached numpy constant; inside a jit this traces to
    # a compile-time constant (caching the jnp array would leak tracers).
    return jnp.asarray(mxhash.matrix_a())


def _level(rows: jax.Array) -> jax.Array:
    """(n, L) uint8 -> (n, 32*ceil(L/256)) uint8. Static-shape tree level."""
    n, ln = rows.shape
    pad = (-ln) % mxhash.CHUNK
    if pad or ln == 0:
        rows = jnp.pad(rows, ((0, 0), (0, max(pad, mxhash.CHUNK - ln))))
    chunks = jax.lax.bitcast_convert_type(
        rows.reshape(n, -1, mxhash.CHUNK), jnp.int8)
    h = jnp.matmul(chunks, _matrix_a_dev(),
                   preferred_element_type=jnp.int32)        # (n, nc, 8)
    # Serialize words little-endian: byte k of word w -> offset 4w + k.
    # bitcast_convert_type appends a (4,) LE byte dim — one op instead
    # of the 4x shift/mask/stack chain (verified bit-identical on chip).
    b = jax.lax.bitcast_convert_type(h, jnp.uint8)          # (n, nc, 8, 4)
    return b.reshape(n, -1)


def mxh256_rows(x: jax.Array) -> jax.Array:
    """Traceable core: (n, L) uint8 -> (n, 32) uint8 digests."""
    n, ln = x.shape
    cur = x
    with jax.named_scope("mxh256"):
        while True:
            cur = _level(cur)
            if cur.shape[1] == mxhash.DIGEST_SIZE:
                break
        tag = jnp.asarray(mxhash.length_tag(ln))  # trace-time constant
        return cur ^ tag[None, :]


@jax.jit
def mxh256_batch(x):
    return mxh256_rows(x)


def mxh256_batch_jax(blocks) -> jax.Array:
    """Jitted batch digest: (n, L) uint8 -> (n, 32) uint8."""
    return mxh256_batch(jnp.asarray(blocks, dtype=jnp.uint8))
