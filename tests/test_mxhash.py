"""mxh256 — the TPU-native bitrot algorithm (ops/mxhash.py).

Covers the registry role the reference gives its bitrot algorithms
(/root/reference/cmd/bitrot_test.go, cmd/bitrot.go:39): golden vectors
pin the spec, the device path must be bit-identical to the numpy spec
implementation, corruption must be detected through the framing layer,
and the engine must read objects written under EITHER algorithm.
"""

import os

import numpy as np
import pytest

from minio_tpu.ops import mxhash
from minio_tpu.ops.mxhash_jax import mxh256_batch_jax
from minio_tpu.storage import bitrot_io

# Golden vectors pinned from the spec implementation (exact integer math:
# identical on every platform/backend).
GOLDEN = {
    b"": "efd993d20980ffb67ae758d2fe82faa07b1dc328ff36e32f9b6bf6f757bd1761",
    b"The quick brown fox jumps over the lazy dog":
        "11fc6143dd0896a9eb04bab154b81e8be51175673881c8763f2dc0e3a3d1e524",
}


def test_golden_vectors():
    for msg, want in GOLDEN.items():
        assert mxhash.mxh256(msg).hex() == want


def test_matrix_is_odd_int8():
    a = mxhash.matrix_a()
    assert a.shape == (mxhash.CHUNK, mxhash.WORDS)
    assert a.dtype == np.int8
    assert np.all(a.astype(np.int32) % 2 != 0)  # odd => single-byte detection


@pytest.mark.parametrize("length", [0, 1, 31, 32, 255, 256, 257,
                                    8192, 131072, 100000])
def test_device_matches_spec(length):
    rng = np.random.default_rng(length + 1)
    x = rng.integers(0, 256, size=(4, length), dtype=np.uint8)
    assert np.array_equal(mxhash.mxh256_batch(x),
                          np.asarray(mxh256_batch_jax(x)))


def test_single_byte_corruption_always_detected():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, size=(1, 4096), dtype=np.uint8)
    d0 = mxhash.mxh256_batch(x)[0]
    for pos in [0, 1, 255, 256, 1024, 4095]:
        for delta in [1, 0x80, 0xFF]:
            y = x.copy()
            y[0, pos] ^= delta
            assert not np.array_equal(mxhash.mxh256_batch(y)[0], d0), \
                (pos, delta)


def test_length_extension_detected():
    x = np.zeros((1, 100), dtype=np.uint8)
    y = np.zeros((1, 101), dtype=np.uint8)
    assert not np.array_equal(mxhash.mxh256_batch(x)[0],
                              mxhash.mxh256_batch(y)[0])


def test_registry_roundtrip_and_corruption():
    rng = np.random.default_rng(11)
    shard = rng.integers(0, 256, size=5000, dtype=np.uint8)
    framed = bitrot_io.frame_shard(shard, 1024, "mxh256")
    assert len(framed) == bitrot_io.bitrot_shard_file_size(5000, 1024,
                                                           "mxh256")
    back = bitrot_io.unframe_shard(framed, 1024, verify=True, algo="mxh256")
    assert np.array_equal(back, shard)
    # flip one data byte inside a frame -> ErrFileCorrupt
    bad = bytearray(framed)
    bad[32 + 100] ^= 0x01
    with pytest.raises(bitrot_io.ErrFileCorrupt):
        bitrot_io.unframe_shard(bytes(bad), 1024, verify=True, algo="mxh256")
    # wrong algorithm also fails verification
    with pytest.raises(bitrot_io.ErrFileCorrupt):
        bitrot_io.unframe_shard(framed, 1024, verify=True,
                                algo="highwayhash256S")


def test_write_algo_env(monkeypatch):
    monkeypatch.delenv("MTPU_BITROT_ALGO", raising=False)
    assert bitrot_io.write_algo() == "mxh256"
    monkeypatch.setenv("MTPU_BITROT_ALGO", "highwayhash256S")
    assert bitrot_io.write_algo() == "highwayhash256S"
    monkeypatch.setenv("MTPU_BITROT_ALGO", "nope")
    with pytest.raises(ValueError):
        bitrot_io.write_algo()


def test_selftest_guard():
    from minio_tpu.ops import selftest
    selftest.mxhash_self_test()


def test_fused_encode_hash_matches_host():
    from minio_tpu.ops import fused
    rng = np.random.default_rng(21)
    k, m, s = 4, 2, 2048
    x = rng.integers(0, 256, size=(3, k, s), dtype=np.uint8)
    parity, digests = fused.encode_and_hash(x, k, m, algo="mxh256")
    parity, digests = np.asarray(parity), np.asarray(digests)
    full = np.concatenate([x, parity], axis=1)          # (3, k+m, s)
    for shard in range(k + m):
        want = mxhash.mxh256_batch(full[:, shard, :])
        assert np.array_equal(digests[shard], want)


def test_fused_verify_transform_mxh():
    from minio_tpu.ops import fused
    from minio_tpu.ops.erasure_cpu import ReedSolomonCPU
    rng = np.random.default_rng(22)
    k, m, s = 4, 2, 1024
    data = rng.integers(0, 256, size=(2, k, s), dtype=np.uint8)
    cpu = ReedSolomonCPU(k, m)
    # build parity per block on host
    blocks = []
    for b in range(2):
        blocks.append(np.stack(cpu.encode([data[b, i] for i in range(k)])))
    full = np.stack(blocks)                              # (2, k+m, s)
    sources = (1, 2, 3, 4)
    x = full[:, list(sources), :]
    digests, rows = fused.verify_and_transform(x, k, m, sources, (0,),
                                               algo="mxh256")
    digests, out = np.asarray(digests), fused.rows_on_host(rows)
    assert np.array_equal(out[:, 0, :], full[:, 0, :])
    for i, srow in enumerate(sources):
        want = mxhash.mxh256_batch(full[:, srow, :])
        assert np.array_equal(digests[:, i], want)


# ---------------------------------------------------------------------------
# engine integration: per-object algorithm recording + cross-algo reads
# ---------------------------------------------------------------------------

def _make_set(tmp_path, n=4):
    from minio_tpu.engine.erasure_set import ErasureSet
    from minio_tpu.storage.drive import LocalDrive
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(n)]
    return ErasureSet(drives, default_parity=2)


def _payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def test_engine_records_default_algo(tmp_path, monkeypatch):
    monkeypatch.delenv("MTPU_BITROT_ALGO", raising=False)
    es = _make_set(tmp_path)
    es.make_bucket("algob")
    data = _payload(300_000, 1)
    fi = es.put_object("algob", "obj", data)
    assert fi.erasure.bitrot_algo() == "mxh256"
    got_fi, got = es.get_object("algob", "obj")
    assert got == data
    # ranged read through the fused verify path
    _, part = es.get_object("algob", "obj", offset=1000, length=50_000)
    assert part == data[1000:51_000]


def test_engine_reads_old_hh_objects(tmp_path, monkeypatch):
    """Objects written under HighwayHash256S (rounds 1-2 / explicit config)
    still verify after the default flips to mxh256."""
    es = _make_set(tmp_path)
    data = _payload(200_000, 2)
    monkeypatch.setenv("MTPU_BITROT_ALGO", "highwayhash256S")
    es.make_bucket("oldb")
    fi = es.put_object("oldb", "legacy", data)
    assert fi.erasure.bitrot_algo() == "highwayhash256S"
    monkeypatch.delenv("MTPU_BITROT_ALGO", raising=False)
    _, got = es.get_object("oldb", "legacy")
    assert got == data
    # and new writes use mxh256 while the old object still reads
    es.put_object("oldb", "new", data)
    assert es.head_object("oldb", "new").erasure.bitrot_algo() == "mxh256"
    _, got2 = es.get_object("oldb", "legacy")
    assert got2 == data


def test_engine_mxh_detects_shard_corruption(tmp_path, monkeypatch):
    """Flip bytes in one drive's shard file: the fused mxh256 verify must
    catch it and the read must recover via spare shards."""
    monkeypatch.delenv("MTPU_BITROT_ALGO", raising=False)
    es = _make_set(tmp_path)
    es.make_bucket("corb")
    data = _payload(1_500_000, 3)   # > 1 block => streaming path
    fi = es.put_object("corb", "victim", data)
    # corrupt the first drive's shard data region
    root = es.drives[0].root
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.startswith("part."):
                p = os.path.join(dirpath, f)
                with open(p, "r+b") as fh:
                    fh.seek(100)      # inside frame 0 data
                    fh.write(b"\xAA\xBB\xCC")
    _, got = es.get_object("corb", "victim")
    assert got == data


def test_engine_sha256_write_algo(tmp_path, monkeypatch):
    """sha256 (host-hashed) is a valid write algorithm end-to-end."""
    monkeypatch.setenv("MTPU_BITROT_ALGO", "sha256")
    es = _make_set(tmp_path)
    es.make_bucket("shab")
    data = _payload(1_200_000, 4)
    fi = es.put_object("shab", "o", data)
    assert fi.erasure.bitrot_algo() == "sha256"
    _, got = es.get_object("shab", "o")
    assert got == data
