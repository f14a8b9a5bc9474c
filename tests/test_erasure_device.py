"""Differential tests: device codec (XLA + Pallas-interpret) vs CPU oracle.

Runs on the 8-device virtual CPU mesh configured in conftest.py; the same
code paths execute on real TPU (bench.py / __graft_entry__.py).
"""

import numpy as np
import pytest

from minio_tpu.ops import erasure_pallas
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


@pytest.mark.parametrize("k,m,s", [(6, 6, 174763), (12, 4, 87382)])
def test_pallas_interpret_pads_awkward_shard_sizes(k, m, s):
    # S = ceil(1 MiB / K) for K = 6 (the server's default on 12 drives)
    # and K = 12 (16-drive EC:4) is no multiple of 128: the kernel runs
    # on S padded up to one and the pad's output is sliced off.
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
