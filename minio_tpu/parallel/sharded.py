"""Multi-device sharded erasure codec: the framework's parallelism plane.

The reference scales with per-disk goroutine fan-out (parallelWriter/
parallelReader, SURVEY.md §2.7); the TPU-native analogue runs the shard math
SPMD over a `jax.sharding.Mesh` and lets XLA insert collectives over ICI:

- axis "blocks": block-batch data parallelism (the natural batch dim — many
  1 MiB blocks in flight, SURVEY.md §5 long-context mapping). Encode is
  embarrassingly parallel here.
- axis "lanes": shard-byte parallelism (the "sequence/context parallel" axis):
  every shard's bytes are split across devices; the GF matmul is elementwise
  along bytes so no halo exchange is needed.
- distributed heal/decode: shard *rows* live on the devices that own the
  corresponding drives (drive-sharded layout); reconstruction all-gathers the
  K needed rows over ICI — the device analogue of parallelReader fan-in
  (cmd/erasure-decode.go:101) — then each device computes its target rows.
- bitrot verify: per-device hash-compare, psum of mismatch counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.erasure_jax import _encode_matrix_bits, _transform_matrix_bits
from ..ops.erasure_pallas import gf_matmul_blocks


def make_mesh(n_devices: int | None = None,
              axes: tuple[str, str] = ("blocks", "lanes")) -> Mesh:
    """Build a 2D device mesh: block-batch x shard-byte parallelism.

    Factors n into (n // 2, 2) when even (so both axes are exercised),
    else (n, 1).
    """
    devices = jax.devices()
    n = n_devices or len(devices)
    devices = devices[:n]
    if n % 2 == 0 and n > 1:
        shape = (n // 2, 2)
    else:
        shape = (n, 1)
    return Mesh(np.asarray(devices).reshape(shape), axes)


class ShardedCodec:
    """SPMD encode/reconstruct/verify over a mesh.

    Single-chip geometry stays identical; the mesh only changes placement —
    by design, so that bytes produced under any mesh match the CPU oracle.
    Each device's share of the GF matmul goes through the same entry
    point as the single-device path (the Pallas kernel on a TPU), so the
    shard_maps run with check_vma=False: the static VMA check cannot see
    through a pallas_call (nor infer that an all_gather's output is
    replicated over "lanes").
    """

    def __init__(self, data_shards: int, parity_shards: int, mesh: Mesh):
        self.k = data_shards
        self.m = parity_shards
        self.mesh = mesh
        self.n_total = data_shards + parity_shards
        self._reconstruct_cache: dict[tuple, object] = {}

    # -- encode: dp over blocks, sp over shard bytes -------------------------

    @functools.cached_property
    def _encode_jit(self):
        mesh = self.mesh
        mat = jnp.asarray(_encode_matrix_bits(self.k, self.m),
                          dtype=jnp.bfloat16)
        in_spec = P("blocks", None, "lanes")
        out_spec = P("blocks", None, "lanes")

        def step(x):
            # Elementwise along lanes + batched over blocks: no collectives;
            # XLA keeps everything local to each device.
            return gf_matmul_blocks(mat, x, self.m)

        return jax.jit(
            jax.shard_map(step, mesh=mesh, in_specs=(in_spec,),
                          out_specs=out_spec, check_vma=False))

    def encode_blocks(self, data: jax.Array | np.ndarray) -> jax.Array:
        """(B, K, S) -> (B, M, S), B sharded over "blocks", S over "lanes"."""
        x = self._place(jnp.asarray(data, dtype=jnp.uint8),
                        P("blocks", None, "lanes"))
        return self._encode_jit(x)

    # -- drive-sharded reconstruct: all-gather rows over ICI -----------------

    def make_reconstruct_jit(self, sources: tuple[int, ...],
                             targets: tuple[int, ...]):
        key = (sources, targets)
        cached = self._reconstruct_cache.get(key)
        if cached is not None:
            return cached
        fn = self._build_reconstruct_jit(sources, targets)
        self._reconstruct_cache[key] = fn
        return fn

    def _build_reconstruct_jit(self, sources: tuple[int, ...],
                               targets: tuple[int, ...]):
        """Build an SPMD step where shard rows are device-local and the K
        source rows are all-gathered over the "lanes" axis.

        Input layout: (B, K, S) with the row dim sharded over "lanes" —
        modelling drives attached to different devices — and B over "blocks".
        """
        mesh = self.mesh
        mat = jnp.asarray(
            _transform_matrix_bits(self.k, self.m, sources, targets),
            dtype=jnp.bfloat16)
        n_t = len(targets)

        def step(x_local):
            # x_local: (B_local, K/axis, S) — gather full K rows on-device.
            x_full = jax.lax.all_gather(x_local, "lanes", axis=1, tiled=True)
            return gf_matmul_blocks(mat, x_full, n_t)

        return jax.jit(
            jax.shard_map(step, mesh=mesh,
                          in_specs=(P("blocks", "lanes", None),),
                          out_specs=P("blocks", None, None),
                          check_vma=False))

    def reconstruct_blocks(self, shards, sources: tuple[int, ...],
                           targets: tuple[int, ...]) -> jax.Array:
        """shards: (B, K, S) rows ordered as sources[:K]; returns (B, T, S)."""
        x = jnp.asarray(shards, dtype=jnp.uint8)
        fn = self.make_reconstruct_jit(tuple(sources), tuple(targets))
        x = self._place(x, P("blocks", "lanes", None))
        return fn(x)

    # -- distributed verify: psum of parity mismatches -----------------------

    @functools.cached_property
    def _verify_jit(self):
        mesh = self.mesh
        mat = jnp.asarray(_encode_matrix_bits(self.k, self.m),
                          dtype=jnp.bfloat16)

        def step(x, parity):
            want = gf_matmul_blocks(mat, x, self.m)
            local = jnp.sum((want != parity).astype(jnp.int32))
            return jax.lax.psum(jax.lax.psum(local, "blocks"), "lanes")

        return jax.jit(
            jax.shard_map(step, mesh=mesh,
                          in_specs=(P("blocks", None, "lanes"),
                                    P("blocks", None, "lanes")),
                          out_specs=P(), check_vma=False))

    def verify_blocks(self, data, parity) -> int:
        """Returns the number of mismatching parity bytes (0 == healthy)."""
        x = self._place(jnp.asarray(data, dtype=jnp.uint8),
                        P("blocks", None, "lanes"))
        p = self._place(jnp.asarray(parity, dtype=jnp.uint8),
                        P("blocks", None, "lanes"))
        return int(self._verify_jit(x, p))

    def _place(self, x: jax.Array, spec: P) -> jax.Array:
        return jax.device_put(x, NamedSharding(self.mesh, spec))
