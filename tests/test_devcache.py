"""Device-resident shard cache + pinned-staging H2D pipeline tests.

The device-residency verticals must be invisible except at the boundary
ledger: MTPU_DEVCACHE=0 and MTPU_H2D_PIPELINE=0 are byte-identical
oracles (randomized GET/ranged/HEAD/heal differentials below), and the
`mtpu_h2d_*` counters prove the perf claims — bytes-crossing-per-
byte-served ~= 1.0 on first touch, ZERO device_put on a devcache hit.

Fill discipline chaos legs: corrupted and degraded reads must never
populate the cache; overwrites/deletes invalidate through the
`_mark_dirty` generation; a recovery boot (fresh ErasureSet over the
same drives) starts cold because owner tokens are per-incarnation.
"""

import os
import shutil

import numpy as np
import pytest

from minio_tpu.engine import shardmath
from minio_tpu.engine import heal
from minio_tpu.engine.erasure_set import BATCH_BLOCKS, BLOCK_SIZE, ErasureSet
from minio_tpu.ops import coalesce, devcache
from minio_tpu.storage.drive import LocalDrive
from minio_tpu.storage.errors import ErrObjectNotFound


def make_set(tmp_path, n=4, parity=None, name="dc"):
    drives = [LocalDrive(str(tmp_path / name / f"d{i}")) for i in range(n)]
    return ErasureSet(drives, default_parity=parity)


def payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def corrupt_part(es, drive_idx, bucket, obj, fi, byte=100):
    p = os.path.join(es.drives[drive_idx].root, bucket, obj,
                     fi.data_dir, "part.1")
    raw = bytearray(open(p, "rb").read())
    raw[byte] ^= 0xFF
    open(p, "wb").write(bytes(raw))


def drive_files(drive, bucket):
    base = os.path.join(drive.root, bucket)
    out = {}
    for dirpath, _, files in os.walk(base):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, base)] = fh.read()
    return out


@pytest.fixture
def forced_device(monkeypatch):
    """Pin the device kernel paths on the CPU test mesh (jax host
    devices stand in for TPU cores) so GET verify and PUT encode
    actually cross the H2D boundary — the paths the staging pipeline
    and the ledger instrument.  Coalescer retired on both edges so
    lanes with pipelined kernels never straddle the flip.  Hedged
    reads are off: on a loaded test host a drive read can be slow
    enough that a parity shard wins, and a degraded read neither
    fills the cache nor crosses the boundary once."""
    monkeypatch.setenv("MTPU_HEDGE", "0")
    coalesce.reset()
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    yield
    coalesce.reset()


class TestOracleEquivalence:
    """Randomized byte-identity differential: every assertion here runs
    under both MTPU_DEVCACHE values (and repeats each range so the
    second read exercises the hit path when the cache is armed)."""

    def test_randomized_ranges(self, tmp_path, devcache_mode):
        es = make_set(tmp_path)
        es.make_bucket("b")
        data = payload(3 * BLOCK_SIZE + 12345, seed=9)
        es.put_object("b", "o", data)
        _, whole = es.get_object("b", "o")
        assert whole == data
        rng = np.random.default_rng(17)
        for _ in range(12):
            off = int(rng.integers(0, len(data)))
            ln = int(rng.integers(1, len(data) - off + 1))
            for _rep in range(2):     # second read may hit the cache
                _, got = es.get_object("b", "o", off, ln)
                assert got == data[off:off + ln], (off, ln)
        # HEAD is metadata-only either way.
        fi = es.head_object("b", "o")
        assert fi.size == len(data)
        # Whole-object re-read after the ranged storm stays exact.
        _, whole2 = es.get_object("b", "o")
        assert whole2 == data

    def test_h2d_pipeline_oracle(self, tmp_path, h2d_mode, forced_device):
        """Pipelined vs serial-upload staging must be byte-identical on
        PUT (parity+digests land on disk) and GET (verify verdicts)."""
        es = make_set(tmp_path, name=f"h2d{h2d_mode}")
        es.make_bucket("b")
        data = payload(2 * BLOCK_SIZE + 777, seed=21)
        es.put_object("b", "o", data)
        _, got = es.get_object("b", "o")
        assert got == data
        _, got2 = es.get_object("b", "o", BLOCK_SIZE // 2, BLOCK_SIZE)
        assert got2 == data[BLOCK_SIZE // 2:BLOCK_SIZE // 2 + BLOCK_SIZE]

    def test_heal_end_state(self, tmp_path, devcache_mode):
        """Heal after corruption restores byte-identical shard files
        whether the rebuild sources from the resident verified matrix
        (devcache hit) or re-reads the disks (oracle)."""
        es = make_set(tmp_path, n=4)
        es.make_bucket("b")
        data = payload(2 * BLOCK_SIZE + 100, seed=5)
        fi = es.put_object("b", "o", data)
        golden = [drive_files(d, "b") for d in es.drives]
        _, got = es.get_object("b", "o")     # fills when armed
        assert got == data
        corrupt_part(es, 2, "b", "o", fi)
        r = heal.heal_object(es, "b", "o", deep=True)[0]
        assert r.healed_drives == [2]
        for i, d in enumerate(es.drives):
            restored = drive_files(d, "b")
            for rel, blob in golden[i].items():
                if rel.endswith("xl.meta"):
                    continue     # msgpack map order may differ
                assert restored[rel] == blob, (i, rel)
        _, got2 = es.get_object("b", "o")
        assert got2 == data


class TestBoundaryAccounting:
    SIZE = BATCH_BLOCKS * BLOCK_SIZE      # one full device batch

    def test_hit_performs_zero_device_put(self, tmp_path, forced_device,
                                          monkeypatch):
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        devcache.reset()
        es = make_set(tmp_path, name="zerohit")
        es.make_bucket("b")
        data = payload(self.SIZE, seed=3)
        es.put_object("b", "o", data)
        coalesce.get()._ema = 2.0            # queued mode: lane pipeline
        _, first = es.get_object("b", "o")   # first touch: upload + fill
        assert first == data
        st0 = devcache.h2d_stats()
        assert st0["h2d_dispatches"] > 0     # the verify crossed once
        c0 = devcache.get().stats()
        assert c0["fills"] >= 1
        _, second = es.get_object("b", "o")  # resident: zero crossings
        assert second == data
        st1 = devcache.h2d_stats()
        assert st1["h2d_dispatches"] == st0["h2d_dispatches"]
        assert st1["h2d_bytes"] == st0["h2d_bytes"]
        c1 = devcache.get().stats()
        assert c1["hits"] > c0["hits"]

    def test_first_touch_bytes_per_byte(self, tmp_path, forced_device,
                                        monkeypatch):
        """First-touch GET ships each served byte across the boundary
        exactly once: h2d_bytes / object_size ~= 1.0 (the batch is an
        exact pad_rows multiple, so staging adds no padding)."""
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        devcache.reset()
        es = make_set(tmp_path, name="ratio")
        es.make_bucket("b")
        data = payload(self.SIZE, seed=4)
        es.put_object("b", "o", data)
        coalesce.get()._ema = 2.0            # queued mode: lane pipeline
        devcache.reset_h2d()                 # drop the PUT-side uploads
        _, got = es.get_object("b", "o")
        assert got == data
        st = devcache.h2d_stats()
        ratio = st["h2d_bytes"] / self.SIZE
        assert 0.9 <= ratio <= 1.5, st

    def test_pipeline_engages_and_overlaps(self, tmp_path, forced_device,
                                           h2d_mode):
        es = make_set(tmp_path, name=f"pl{h2d_mode}")
        es.make_bucket("b")
        data = payload(self.SIZE, seed=6)
        es.put_object("b", "o", data)
        coalesce.get()._ema = 2.0            # queued mode: lane pipeline
        _, got = es.get_object("b", "o")
        assert got == data
        st = coalesce.get().stats()
        if h2d_mode == "1":
            assert st["pipeline_dispatches"] > 0
        else:
            assert st["pipeline_dispatches"] == 0


class TestFillDiscipline:
    def test_corrupt_read_never_populates(self, tmp_path, devcache_mode):
        if devcache_mode != "1":
            pytest.skip("fill discipline only exists with the cache on")
        es = make_set(tmp_path)
        es.make_bucket("b")
        data = payload(2 * BLOCK_SIZE + 50, seed=7)
        fi = es.put_object("b", "o", data)
        corrupt_part(es, 1, "b", "o", fi)
        _, got = es.get_object("b", "o")     # reconstructs via parity
        assert got == data
        st = devcache.get().stats()
        assert st["fills"] == 0 and st["entries"] == 0

    def test_degraded_read_never_populates(self, tmp_path, devcache_mode):
        if devcache_mode != "1":
            pytest.skip("fill discipline only exists with the cache on")
        es = make_set(tmp_path)
        es.make_bucket("b")
        data = payload(2 * BLOCK_SIZE, seed=8)
        es.put_object("b", "o", data)
        es.drives[0] = None                  # degraded: parity rebuild
        _, got = es.get_object("b", "o")
        assert got == data
        st = devcache.get().stats()
        assert st["fills"] == 0 and st["entries"] == 0

    def test_overwrite_invalidates(self, tmp_path, devcache_mode):
        es = make_set(tmp_path)
        es.make_bucket("b")
        old = payload(2 * BLOCK_SIZE + 9, seed=10)
        new = payload(2 * BLOCK_SIZE + 9, seed=11)
        es.put_object("b", "o", old)
        _, got = es.get_object("b", "o")     # fills when armed
        assert got == old
        es.put_object("b", "o", new)         # generation bump + new dir
        _, got2 = es.get_object("b", "o")
        assert got2 == new
        if devcache_mode == "1":
            assert devcache.get().stats()["invalidations"] > 0

    def test_delete_invalidates(self, tmp_path, devcache_mode):
        es = make_set(tmp_path)
        es.make_bucket("b")
        es.put_object("b", "o", payload(BLOCK_SIZE + 3, seed=12))
        _, _ = es.get_object("b", "o")
        es.delete_object("b", "o")
        with pytest.raises(ErrObjectNotFound):
            es.get_object("b", "o")

    def test_mutation_during_disable_invalidates_on_reenable(
            self, tmp_path, monkeypatch):
        """A write that lands while MTPU_DEVCACHE=0 must still bump the
        generation — otherwise re-enabling would resurrect pre-write
        entries."""
        devcache.reset()
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        es = make_set(tmp_path, name="flip")
        es.make_bucket("b")
        old = payload(BLOCK_SIZE + 40, seed=13)
        es.put_object("b", "o", old)
        _, got = es.get_object("b", "o")     # fill under gen g
        assert got == old
        monkeypatch.setenv("MTPU_DEVCACHE", "0")
        new = payload(BLOCK_SIZE + 40, seed=14)
        es.put_object("b", "o", new)         # mutation while disabled
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        _, got2 = es.get_object("b", "o")
        assert got2 == new
        devcache.reset()

    def test_recovery_boot_starts_cold(self, tmp_path, devcache_mode):
        """Crash-matrix leg: a reopened set (recovery boot) gets a fresh
        owner token, so the previous incarnation's entries are
        unreachable even though the singleton survives in-process."""
        es = make_set(tmp_path, name="boot")
        es.make_bucket("b")
        data = payload(2 * BLOCK_SIZE + 64, seed=15)
        es.put_object("b", "o", data)
        _, got = es.get_object("b", "o")     # fills under owner A
        assert got == data
        es2 = ErasureSet(list(es.drives))    # the recovery-boot reopen
        assert es2._devcache_owner != es._devcache_owner
        if devcache_mode == "1":
            before = devcache.get().stats()["hits"]
        _, got2 = es2.get_object("b", "o")
        assert got2 == data
        if devcache_mode == "1":
            st = devcache.get().stats()
            assert st["hits"] == before      # cold: no cross-boot hit
            assert st["misses"] > 0


class TestCapacityAndEviction:
    def test_lru_eviction_under_small_cap(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        monkeypatch.setenv("MTPU_DEVCACHE_MB", "4")
        devcache.reset()
        es = make_set(tmp_path, name="cap")
        es.make_bucket("b")
        blobs = {}
        for i in range(4):                   # 4 x 2 MiB > 4 MiB cap
            blobs[i] = payload(2 * BLOCK_SIZE, seed=20 + i)
            es.put_object("b", f"o{i}", blobs[i])
        for i in range(4):
            _, got = es.get_object("b", f"o{i}")
            assert got == blobs[i]
        st = devcache.get().stats()
        assert st["evictions"] > 0
        assert st["resident_bytes"] <= 4 << 20
        for i in range(4):                   # evicted entries re-read fine
            _, got = es.get_object("b", f"o{i}")
            assert got == blobs[i]
        devcache.reset()

    def test_oversize_fill_rejected(self, monkeypatch):
        monkeypatch.setenv("MTPU_DEVCACHE", "1")
        monkeypatch.setenv("MTPU_DEVCACHE_MB", "1")
        devcache.reset()
        c = devcache.get()
        big = np.zeros((2, 2, 1 << 20), dtype=np.uint8)   # 4 MiB > 1 MiB
        assert not c.fill(("own", "b", "o", 1, "dd", 0, 2, "mxh256"),
                          0, big)
        assert c.stats()["rejects"] == 1
        devcache.reset()
