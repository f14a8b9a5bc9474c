"""Differential tests: device codec (XLA + Pallas-interpret) vs CPU oracle.

Runs on the 8-device virtual CPU mesh configured in conftest.py; the same
code paths execute on real TPU (bench.py / __graft_entry__.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minio_tpu.ops import erasure_pallas, fused
from minio_tpu.ops.erasure_cpu import ReedSolomonCPU
from minio_tpu.ops.erasure_jax import ReedSolomonTPU


def _random_blocks(b, k, s, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(b, k, s), dtype=np.uint8)


@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (5, 3), (14, 2)])
def test_encode_matches_oracle(k, m):
    blocks = _random_blocks(4, k, 256, seed=k * 100 + m)
    dev = ReedSolomonTPU(k, m, use_pallas=False)
    parity = np.asarray(dev.encode_blocks(blocks))
    cpu = ReedSolomonCPU(k, m)
    for b in range(blocks.shape[0]):
        want = cpu.encode(list(blocks[b]))[k:]
        assert np.array_equal(parity[b], np.stack(want)), f"block {b}"


@pytest.mark.parametrize("k,m,lost", [
    (8, 4, (0, 3, 9, 11)),   # 2 data + 2 parity lost
    (8, 4, (0, 1, 2, 3)),    # worst case: 4 data lost
    (2, 2, (1, 2)),
    (4, 2, (5,)),            # parity-only loss
])
def test_reconstruct_matches_oracle(k, m, lost):
    blocks = _random_blocks(3, k, 128, seed=42)
    dev = ReedSolomonTPU(k, m, use_pallas=False)
    parity = np.asarray(dev.encode_blocks(blocks))
    full = np.concatenate([blocks, parity], axis=1)  # (B, k+m, S)

    shard_list = [None if i in lost else full[:, i, :] for i in range(k + m)]
    out = dev.reconstruct_blocks(shard_list)
    for i in range(k + m):
        assert np.array_equal(np.asarray(out[i]), full[:, i, :]), f"shard {i}"


def test_transform_targets_subset():
    # Heal-style: reconstruct only specific rows from a mix of data+parity.
    k, m = 6, 3
    blocks = _random_blocks(2, k, 192, seed=9)
    dev = ReedSolomonTPU(k, m, use_pallas=False)
    parity = np.asarray(dev.encode_blocks(blocks))
    full = np.concatenate([blocks, parity], axis=1)
    sources = (1, 2, 3, 5, 6, 8)   # 4 data rows + 2 parity rows
    targets = (0, 7)               # one data, one parity
    x = full[:, list(sources), :]
    got = np.asarray(dev.transform_blocks(x, sources, targets))
    assert np.array_equal(got[:, 0, :], full[:, 0, :])
    assert np.array_equal(got[:, 1, :], full[:, 7, :])


def test_pallas_interpret_matches_oracle():
    # Force the fused kernel (interpreter mode on CPU) on a tileable shape.
    k, m = 8, 4
    blocks = _random_blocks(8, k, 512, seed=3)
    cpu = ReedSolomonCPU(k, m)
    erasure_pallas.FORCE_INTERPRET = True
    try:
        dev = ReedSolomonTPU(k, m, use_pallas=True)
        parity = np.asarray(dev.encode_blocks(blocks))
    finally:
        erasure_pallas.FORCE_INTERPRET = False
    for b in range(blocks.shape[0]):
        want = np.stack(cpu.encode(list(blocks[b]))[k:])
        assert np.array_equal(parity[b], want), f"block {b}"


# S = ceil(1 MiB / K) for every K whose shard size no 8 KiB lane tile
# divides: 6 (the server's default on 12 drives), 12 (16-drive EC:4),
# 3 (6 drives), 5 (9 drives), 7 (11 drives).
@pytest.mark.parametrize("k,m,s", [
    (6, 6, 174763), (12, 4, 87382), (3, 3, 349526), (5, 4, 209716),
    (7, 4, 149797)])
def test_pallas_interpret_pads_awkward_shard_sizes(k, m, s):
    # The kernel runs on S as it is, its last lane tile ragged: the
    # columns past S it computes are never written.
    blocks = _random_blocks(2, k, s, seed=7)
    cpu = ReedSolomonCPU(k, m)
    erasure_pallas.FORCE_INTERPRET = True
    try:
        parity = np.asarray(
            ReedSolomonTPU(k, m, use_pallas=True).encode_blocks(blocks))
    finally:
        erasure_pallas.FORCE_INTERPRET = False
    assert parity.shape == (2, m, s)
    for b in range(2):
        want = np.stack(cpu.encode(list(blocks[b]))[k:])
        assert np.array_equal(parity[b], want), f"block {b}"


def test_pallas_interpret_rebuilds_six_rows_at_ec6p6():
    # The degraded GET's rebuild at EC:6+6 (T = 6: three data rows and
    # three parity rows lost), through the digest-free decode program.
    k, m, s = 6, 6, 174763
    blocks = _random_blocks(2, k, s, seed=17)
    cpu = ReedSolomonCPU(k, m)
    full = np.stack([np.stack(cpu.encode(list(b))) for b in blocks])
    sources, targets = (3, 4, 5, 9, 10, 11), (0, 1, 2, 6, 7, 8)
    want = [cpu.reconstruct([None if i in targets else full[b, i]
                             for i in range(k + m)]) for b in range(2)]
    erasure_pallas.FORCE_INTERPRET = True
    fused._verify_transform_jit.cache_clear()
    try:
        digests, rows = fused.verify_and_transform(
            full[:, list(sources)], k, m, sources, targets, algo=None)
        rows = [np.asarray(r) for r in rows]
    finally:
        erasure_pallas.FORCE_INTERPRET = False
        fused._verify_transform_jit.cache_clear()
    assert digests is None and len(rows) == len(targets)
    for j, t in enumerate(targets):
        assert np.array_equal(rows[j], np.stack([w[t] for w in want])), t


@pytest.mark.parametrize("s", [131072, 524288])
def test_tile_plan_covers_a_tileable_shard_exactly(s):
    # EC:8+4 and 2+2 at 1 MiB: today's tile, no ragged tail.
    assert erasure_pallas.tile_plan(s) == (8192, s // 8192)


@pytest.mark.parametrize("k", range(2, 17))
def test_tile_plan_is_large_at_every_k(k):
    s = -(-(1 << 20) // k)
    tile, steps = erasure_pallas.tile_plan(s)
    assert tile >= 4096 and tile % 128 == 0
    assert steps == -(-s // tile) <= -(-s // 4096)


def _pallas_grids(jaxpr) -> tuple[list[str], list[tuple]]:
    """Every primitive of a jaxpr, nested ones too, and the grid and
    shard-input block of each pallas_call in it."""
    prims, grids = [], []
    for eqn in jaxpr.eqns:
        prims.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            block = gm.block_mappings[1].block_shape
            grids.append((tuple(gm.grid), tuple(
                getattr(d, "block_size", d) for d in block)))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            p, g = _pallas_grids(sub)
            prims += p
            grids += g
    return prims, grids


@pytest.mark.parametrize("k,s,grid", [
    (8, 131072, (4, 16)), (2, 524288, (4, 64)), (6, 174763, (4, 22)),
    (12, 87382, (4, 11))])
def test_kernel_takes_the_shard_as_it_is(k, s, grid):
    # No pad before the kernel and no slice after it, at any K; the
    # 8+4 and 2+2 grids are those the kernel has always run.
    erasure_pallas.FORCE_INTERPRET = True
    try:
        jaxpr = jax.make_jaxpr(
            lambda mat, x: erasure_pallas.gf_matmul_blocks(mat, x, 4))(
                jax.ShapeDtypeStruct((32, 8 * k), jnp.bfloat16),
                jax.ShapeDtypeStruct((4, k, s), jnp.uint8))
    finally:
        erasure_pallas.FORCE_INTERPRET = False
    prims, grids = _pallas_grids(jaxpr.jaxpr)
    assert grids == [(grid, (1, k, 8192))]
    assert not {"pad", "slice", "dynamic_slice"} & set(prims), prims
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(4, 4, s)]


def test_xla_path_serves_any_shard_size_off_tpu():
    # Off-TPU (and outside FORCE_INTERPRET) the portable XLA path is the
    # codec, whatever the shard size.
    k, m = 4, 2
    blocks = _random_blocks(2, k, 100, seed=5)
    dev = ReedSolomonTPU(k, m, use_pallas=True)
    parity = np.asarray(dev.encode_blocks(blocks))
    cpu = ReedSolomonCPU(k, m)
    want = np.stack(cpu.encode(list(blocks[0]))[k:])
    assert np.array_equal(parity[0], want)


def test_large_block_batch_roundtrip():
    # MinIO-shaped: 1 MiB block, EC:8+4 -> shard size 128 KiB... scaled to
    # 8 KiB shards here to keep CPU-mesh test time sane.
    k, m = 8, 4
    s = 8192
    blocks = _random_blocks(4, k, s, seed=11)
    dev = ReedSolomonTPU(k, m, use_pallas=False)
    parity = np.asarray(dev.encode_blocks(blocks))
    full = np.concatenate([blocks, parity], axis=1)
    lost = (2, 6, 8, 10)
    shard_list = [None if i in lost else full[:, i, :] for i in range(k + m)]
    out = dev.reconstruct_blocks(shard_list)
    for i in lost:
        assert np.array_equal(np.asarray(out[i]), full[:, i, :])
