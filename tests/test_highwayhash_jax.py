"""Device (JAX) HighwayHash + fused verify/encode kernels vs the oracles.

The scalar python-int HighwayHash256 (itself validated against the
reference's golden chain, /root/reference/cmd/bitrot.go:215) is the
ground truth; the numpy HighwayHashVec and the device kernel must agree
bit-for-bit for every length class (bulk packets + all 31 remainder sizes).
"""

import numpy as np
import pytest

from minio_tpu.ops import fused
from minio_tpu.ops.erasure_cpu import ReedSolomonCPU
from minio_tpu.ops.highwayhash import (HighwayHash256, highwayhash256,
                                       highwayhash256_batch)
from minio_tpu.ops.highwayhash_jax import hh256_batch_jax

rng = np.random.default_rng(42)


@pytest.mark.parametrize("n,length", [
    (1, 32), (4, 64), (3, 0), (2, 31), (5, 17), (2, 100),
    (2, 1024), (8, 87382 % 512 + 22),   # odd remainder like k=12 shards
])
def test_device_hash_matches_oracle(n, length):
    x = rng.integers(0, 256, size=(n, length), dtype=np.uint8)
    got = np.asarray(hh256_batch_jax(x))
    for i in range(n):
        want = highwayhash256(x[i].tobytes())
        assert got[i].tobytes() == want


def test_device_hash_remainder_classes():
    # One representative per remainder branch class (r&16, mod4 cases);
    # the full 1..31 sweep was validated once out-of-band — each extra
    # size is a separate XLA compile, too slow for every CI run.
    for r in (1, 3, 4, 8, 15, 16, 17, 20, 23, 31):
        x = rng.integers(0, 256, size=(2, 64 + r), dtype=np.uint8)
        got = np.asarray(hh256_batch_jax(x))
        want = highwayhash256_batch(x)
        assert np.array_equal(got, want), f"remainder {r}"


def test_device_hash_empty_input():
    got = np.asarray(hh256_batch_jax(np.zeros((2, 0), dtype=np.uint8)))
    want = HighwayHash256().digest()
    assert got[0].tobytes() == want and got[1].tobytes() == want


def test_encode_and_hash_matches_separate_paths():
    k, m, B, S = 4, 2, 3, 96
    x = rng.integers(0, 256, size=(B, k, S), dtype=np.uint8)
    parity, digests = fused.encode_and_hash(x, k, m)
    parity, digests = np.asarray(parity), np.asarray(digests)
    cpu = ReedSolomonCPU(k, m)
    for b in range(B):
        shards = cpu.encode_data(x[b].reshape(-1).tobytes())
        assert np.array_equal(parity[b], np.stack(shards[k:]))
    full = np.concatenate([x, parity], axis=1).transpose(1, 0, 2)
    want = highwayhash256_batch(full.reshape((k + m) * B, S))
    assert np.array_equal(digests.reshape(-1, 32), want)


def test_verify_and_transform_reconstructs_and_hashes():
    k, m, B, S = 4, 2, 2, 64
    x = rng.integers(0, 256, size=(B, k, S), dtype=np.uint8)
    parity = np.asarray(fused.encode_and_hash(x, k, m)[0])
    full = np.concatenate([x, parity], axis=1)
    sources, targets = (1, 2, 3, 4), (0, 5)
    xin = np.ascontiguousarray(full[:, list(sources), :])
    digests, rows = fused.verify_and_transform(xin, k, m, sources, targets)
    digests, out = np.asarray(digests), fused.rows_on_host(rows)
    assert np.array_equal(out[:, 0], full[:, 0])
    assert np.array_equal(out[:, 1], full[:, 5])
    want = highwayhash256_batch(xin.reshape(B * k, S)).reshape(B, k, 32)
    assert np.array_equal(digests, want)


def test_verify_and_transform_no_targets_hash_only():
    k, m, B, S = 2, 2, 2, 32
    x = rng.integers(0, 256, size=(B, k, S), dtype=np.uint8)
    digests, out = fused.verify_and_transform(x, k, m, (0, 1), ())
    assert out is None
    want = highwayhash256_batch(x.reshape(B * k, S)).reshape(B, k, 32)
    assert np.array_equal(np.asarray(digests), want)


def test_verify_detects_flipped_bit():
    k, m, B, S = 2, 1, 2, 64
    x = rng.integers(0, 256, size=(B, k, S), dtype=np.uint8)
    good = np.asarray(fused.verify_and_transform(x, k, m, (0, 1), ())[0])
    x2 = x.copy()
    x2[1, 0, 5] ^= 0x40
    bad = np.asarray(fused.verify_and_transform(x2, k, m, (0, 1), ())[0])
    assert np.array_equal(good[0], bad[0])
    assert not np.array_equal(good[1, 0], bad[1, 0])
    assert np.array_equal(good[1, 1], bad[1, 1])


@pytest.mark.skipif(
    __import__("jax").default_backend() != "tpu",
    reason="pallas hash kernel needs a real TPU")
def test_pallas_bulk_kernel_matches_oracle():
    """Gated experiment (MTPU_HH_PALLAS): in-kernel packet chain must stay
    bit-identical to the XLA/scalar paths when enabled."""
    import os
    from minio_tpu.ops import highwayhash_pallas as hp
    x = rng.integers(0, 256, size=(hp.SBLK, 32 * hp.PB * 2), dtype=np.uint8)
    saved = os.environ.get("MTPU_HH_PALLAS")
    os.environ["MTPU_HH_PALLAS"] = "1"
    try:
        got = np.asarray(hh256_batch_jax(x))
    finally:
        if saved is None:
            os.environ.pop("MTPU_HH_PALLAS", None)
        else:
            os.environ["MTPU_HH_PALLAS"] = saved
    want = highwayhash256_batch(x[:2])
    assert np.array_equal(got[:2], want)
