"""Storage layer tests: msgpack codec, xl.meta format, LocalDrive ops,
format bootstrap. Mirrors the reference's xl-storage unit-test approach
(temp-dir drives, corrupt-then-assert, cf. cmd/xl-storage_test.go)."""

import os

import numpy as np
import pytest

from minio_tpu.storage import bitrot_io
from minio_tpu.storage.drive import SMALL_FILE_THRESHOLD, SYS_VOL, LocalDrive
from minio_tpu.storage.errors import (ErrFileCorrupt, ErrFileNotFound,
                                      ErrFileVersionNotFound,
                                      ErrVolumeExists, ErrVolumeNotEmpty,
                                      ErrVolumeNotFound)
from minio_tpu.storage.format import (init_format_sets, load_format,
                                      quorum_formatted)
from minio_tpu.storage.xlmeta import (ErasureInfo, FileInfo, ObjectPartInfo,
                                      XLMeta, new_uuid)
from minio_tpu.utils import msgpackx


# ---------------------------------------------------------------------------
# msgpack
# ---------------------------------------------------------------------------

class TestMsgpack:
    CASES = [
        None, True, False, 0, 1, 127, 128, 255, 256, 65535, 65536,
        2**32 - 1, 2**32, 2**63 - 1, -1, -31, -32, -33, -128, -129,
        -32768, -32769, -2**63, 1.5, -0.25,
        "", "a", "x" * 31, "x" * 32, "x" * 255, "x" * 70000, "héllo",
        b"", b"\x00\xff", b"y" * 255, b"y" * 256, b"z" * 70000,
        [], [1, "two", b"three", None], list(range(20)),
        {}, {"k": "v", "n": 5}, {"nested": {"a": [1, {"b": b"c"}]}},
    ]

    @pytest.mark.parametrize("obj", CASES, ids=lambda o: repr(o)[:40])
    def test_roundtrip(self, obj):
        assert msgpackx.unpackb(msgpackx.packb(obj)) == obj

    def test_big_array_map(self):
        arr = list(range(70000))
        assert msgpackx.unpackb(msgpackx.packb(arr)) == arr
        m = {f"k{i}": i for i in range(70000)}
        assert msgpackx.unpackb(msgpackx.packb(m)) == m

    def test_trailing_bytes_rejected(self):
        with pytest.raises(msgpackx.MsgpackError):
            msgpackx.unpackb(msgpackx.packb(1) + b"\x00")

    def test_truncated_rejected(self):
        buf = msgpackx.packb({"key": b"value" * 100})
        with pytest.raises(msgpackx.MsgpackError):
            msgpackx.unpackb(buf[:-3])

    def test_prefix_decode(self):
        buf = msgpackx.packb([1, 2]) + b"tail"
        obj, n = msgpackx.unpackb_prefix(buf)
        assert obj == [1, 2] and buf[n:] == b"tail"


# ---------------------------------------------------------------------------
# xl.meta
# ---------------------------------------------------------------------------

def make_fi(version_id="", mod_time=1000, size=4096, inline=None,
            deleted=False, data_dir=None):
    ec = ErasureInfo(data_blocks=2, parity_blocks=2, block_size=1 << 20,
                     index=1, distribution=[1, 2, 3, 4])
    return FileInfo(
        volume="b", name="o", version_id=version_id,
        data_dir=(new_uuid() if data_dir is None else data_dir),
        mod_time_ns=mod_time, size=size, deleted=deleted,
        metadata={"etag": "abc", "content-type": "text/plain"},
        parts=[ObjectPartInfo(1, size, size)],
        erasure=None if deleted else ec, inline_data=inline)


class TestXLMeta:
    def test_roundtrip(self):
        meta = XLMeta()
        fi = make_fi(inline=b"\x01\x02" * 100)
        meta.add_version(fi)
        meta2 = XLMeta.from_bytes(meta.to_bytes())
        got = meta2.latest("b", "o")
        assert got.version_id == fi.version_id
        assert got.inline_data == fi.inline_data
        assert got.erasure.distribution == [1, 2, 3, 4]
        assert got.parts[0].size == 4096
        assert got.metadata["etag"] == "abc"

    def test_corrupt_detected(self):
        meta = XLMeta()
        meta.add_version(make_fi())
        buf = bytearray(meta.to_bytes())
        buf[10] ^= 0xFF
        with pytest.raises(ErrFileCorrupt):
            XLMeta.from_bytes(bytes(buf))
        with pytest.raises(ErrFileCorrupt):
            XLMeta.from_bytes(b"JUNK" + bytes(buf)[4:])

    def test_version_ordering_latest_first(self):
        meta = XLMeta()
        v1, v2, v3 = new_uuid(), new_uuid(), new_uuid()
        meta.add_version(make_fi(v1, mod_time=100))
        meta.add_version(make_fi(v2, mod_time=300))
        meta.add_version(make_fi(v3, mod_time=200))
        assert meta.latest().version_id == v2
        ids = [fi.version_id for fi in meta.list_versions()]
        assert ids == [v2, v3, v1]
        assert meta.list_versions()[0].is_latest
        assert not meta.list_versions()[1].is_latest

    def test_delete_version_frees_unshared_datadir(self):
        meta = XLMeta()
        fi = make_fi(new_uuid())
        meta.add_version(fi)
        assert meta.delete_version(fi.version_id) == fi.data_dir
        with pytest.raises(ErrFileVersionNotFound):
            meta.find_version(fi.version_id)

    def test_delete_version_keeps_shared_datadir(self):
        meta = XLMeta()
        dd = new_uuid()
        a, b = make_fi(new_uuid(), data_dir=dd), make_fi(new_uuid(), data_dir=dd)
        meta.add_version(a)
        meta.add_version(b)
        assert meta.delete_version(a.version_id) == ""
        assert meta.delete_version(b.version_id) == dd

    def test_null_version_replace(self):
        meta = XLMeta()
        meta.add_version(make_fi("", mod_time=1))
        meta.add_version(make_fi("", mod_time=2))
        assert len(meta.versions) == 1
        assert meta.latest().mod_time_ns == 2


# ---------------------------------------------------------------------------
# LocalDrive
# ---------------------------------------------------------------------------

@pytest.fixture
def drive(tmp_path):
    return LocalDrive(str(tmp_path / "d0"))


class TestLocalDrive:
    def test_volumes(self, drive):
        drive.make_volume("bucket1")
        with pytest.raises(ErrVolumeExists):
            drive.make_volume("bucket1")
        assert drive.list_volumes() == ["bucket1"]
        with pytest.raises(ErrVolumeNotFound):
            drive.stat_volume("nope")
        drive.make_volume("bucket2")
        drive.write_all("bucket2", "o/xl.meta", b"x")
        with pytest.raises(ErrVolumeNotEmpty):
            drive.delete_volume("bucket2")
        drive.delete_volume("bucket2", force=True)
        drive.delete_volume("bucket1")
        assert drive.list_volumes() == []

    def test_path_escape_rejected(self, drive):
        drive.make_volume("b")
        drive.make_volume("other")
        drive.write_all("other", "obj/xl.meta", b"secret")
        from minio_tpu.storage.errors import StorageError
        with pytest.raises(StorageError):
            drive.read_all("b", "../../../etc/passwd")
        with pytest.raises(StorageError):
            drive.read_all("..", "x")
        # '..' must not reach sibling volumes or the system namespace.
        with pytest.raises(StorageError):
            drive.read_all("b", "../other/obj/xl.meta")
        with pytest.raises(StorageError):
            drive.write_all("b", f"../{SYS_VOL}/format.json", b"junk")
        with pytest.raises(StorageError):
            drive.read_all("a/../other", "obj/xl.meta")
        with pytest.raises(StorageError):
            drive.list_dir("b", "../..")
        with pytest.raises(StorageError):
            list(drive.walk_dir("b", "../other/"))

    def test_write_to_missing_volume_rejected(self, drive):
        with pytest.raises(ErrVolumeNotFound):
            drive.write_all("ghost", "x", b"d")
        with pytest.raises(ErrVolumeNotFound):
            drive.create_file("ghost", "o/part.1", b"d")
        assert drive.list_volumes() == []

    def test_write_read_all(self, drive):
        drive.make_volume("b")
        drive.write_all("b", "cfg/x.json", b"hello")
        assert drive.read_all("b", "cfg/x.json") == b"hello"
        with pytest.raises(ErrFileNotFound):
            drive.read_all("b", "cfg/missing")

    @pytest.mark.parametrize("extra", [-1, 0, 1])
    @pytest.mark.parametrize("chunks", [0, 1, 3])
    def test_read_all_returns_every_byte(self, drive, monkeypatch,
                                         chunks, extra):
        """read_all reads with os.read in chunks until the end: files
        empty, under, exactly at and over a chunk boundary come back
        whole, equal to what open().read() gives."""
        from minio_tpu.storage import drive as drive_mod
        monkeypatch.setattr(drive_mod, "_READ_ALL_CHUNK", 4096)
        size = max(0, chunks * 4096 + extra)
        body = np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        drive.make_volume("b")
        drive.write_all("b", "cfg/blob", body)
        with open(os.path.join(drive.root, "b", "cfg", "blob"), "rb") as f:
            assert drive.read_all("b", "cfg/blob") == f.read() == body

    def test_read_all_errors_and_no_fd_leak(self, drive):
        """Same errors as before: a directory and a missing file read
        as ErrFileNotFound; no descriptor stays open either way."""
        drive.make_volume("b")
        drive.write_all("b", "dir/x", b"d")
        fds = len(os.listdir("/proc/self/fd"))
        for _ in range(8):
            with pytest.raises(ErrFileNotFound):
                drive.read_all("b", "dir")
            with pytest.raises(ErrFileNotFound):
                drive.read_all("b", "dir/missing")
            assert drive.read_all("b", "dir/x") == b"d"
        assert len(os.listdir("/proc/self/fd")) == fds

    def test_rename_data_publish_and_read_version(self, drive):
        drive.make_volume("b")
        # Stage shard file in tmp, then publish.
        shard = np.arange(1000, dtype=np.uint8)
        framed = bitrot_io.frame_shard(shard, 256)
        tmp_id = "stage-1"
        drive.create_file(SYS_VOL, f"tmp/{tmp_id}/part.1", framed)
        fi = make_fi(size=1000)
        drive.rename_data(SYS_VOL, f"tmp/{tmp_id}", fi, "b", "obj/key")
        got = drive.read_version("b", "obj/key")
        assert got.size == 1000
        data = drive.read_file("b", f"obj/key/{fi.data_dir}/part.1")
        assert data == framed
        # Overwrite null version: old datadir must be freed.
        framed2 = bitrot_io.frame_shard(shard[::-1].copy(), 256)
        drive.create_file(SYS_VOL, "tmp/stage-2/part.1", framed2)
        fi2 = make_fi(size=1000, mod_time=2000)
        drive.rename_data(SYS_VOL, "tmp/stage-2", fi2, "b", "obj/key")
        assert drive.read_version("b", "obj/key").data_dir == fi2.data_dir
        assert not os.path.isdir(
            os.path.join(drive.root, "b", "obj/key", fi.data_dir))

    def test_inline_object_no_datadir(self, drive):
        drive.make_volume("b")
        payload = b"tiny" * 10
        fi = make_fi(size=len(payload), inline=payload, data_dir="")
        drive.write_metadata("b", "small", fi)
        got = drive.read_version("b", "small")
        assert got.inline_data == payload
        assert sorted(os.listdir(os.path.join(drive.root, "b", "small"))) == [
            "xl.meta"]

    def test_delete_version_cleans_up(self, drive):
        drive.make_volume("b")
        drive.create_file(SYS_VOL, "tmp/s/part.1", b"framedbytes" * 10)
        fi = make_fi(version_id=new_uuid())
        drive.rename_data(SYS_VOL, "tmp/s", fi, "b", "deep/path/obj")
        drive.delete_version("b", "deep/path/obj", fi.version_id)
        with pytest.raises(ErrFileNotFound):
            drive.read_version("b", "deep/path/obj")
        # Empty parents removed up to the volume root.
        assert not os.path.exists(os.path.join(drive.root, "b", "deep"))

    def test_delete_marker(self, drive):
        drive.make_volume("b")
        fi = make_fi(inline=b"x", data_dir="")
        drive.write_metadata("b", "o", fi)
        dm = make_fi(version_id=new_uuid(), mod_time=5000, deleted=True,
                     data_dir="")
        dm.inline_data = None
        drive.delete_version("b", "o", mark_delete=True, fi=dm)
        got = drive.read_version("b", "o")
        assert got.deleted and got.version_id == dm.version_id
        # Null version still reachable via its explicit "null" alias.
        old = drive.read_version("b", "o", "null")
        assert not old.deleted and old.inline_data == b"x"

    def test_verify_file_detects_corruption(self, drive):
        drive.make_volume("b")
        shard = np.arange(5000, dtype=np.uint8) % 251
        framed = bytearray(bitrot_io.frame_shard(shard, 1024))
        drive.create_file("b", "o/dd/part.1", bytes(framed))
        drive.verify_file("b", "o/dd/part.1", 1024, expected_logical=5000)
        framed[200] ^= 1  # flip a data byte inside frame 0
        drive.create_file("b", "o/dd/part.1", bytes(framed))
        with pytest.raises(ErrFileCorrupt):
            drive.verify_file("b", "o/dd/part.1", 1024)
        # Truncation detected via size check.
        drive.create_file("b", "o/dd/part.2", bytes(framed[:-10]))
        with pytest.raises(ErrFileCorrupt):
            drive.verify_file("b", "o/dd/part.2", 1024, expected_logical=5000)

    def test_list_dir_and_walk(self, drive):
        drive.make_volume("b")
        for name in ("a/1", "a/2", "z"):
            fi = make_fi(inline=b"d", data_dir="")
            drive.write_metadata("b", name, fi)
        assert drive.list_dir("b") == ["a/", "z"]
        assert drive.list_dir("b", "a") == ["1", "2"]
        walked = [name for name, _ in drive.walk_dir("b")]
        assert walked == ["a/1", "a/2", "z"]
        walked = [name for name, _ in drive.walk_dir("b", "a/")]
        assert walked == ["a/1", "a/2"]

    def test_disk_info(self, drive):
        info = drive.disk_info()
        assert info["total"] > 0 and info["free"] > 0


# ---------------------------------------------------------------------------
# format bootstrap
# ---------------------------------------------------------------------------

class TestFormat:
    def test_fresh_init_and_reload(self, tmp_path):
        drives = [[LocalDrive(str(tmp_path / f"s{s}d{d}")) for d in range(4)]
                  for s in range(2)]
        fmt = init_format_sets(drives)
        dep = fmt["id"]
        ids = {d.disk_id for row in drives for d in row}
        assert len(ids) == 8  # unique drive ids
        # Reload: same layout adopted, ids verified.
        drives2 = [[LocalDrive(str(tmp_path / f"s{s}d{d}")) for d in range(4)]
                   for s in range(2)]
        fmt2 = init_format_sets(drives2)
        assert fmt2["id"] == dep
        assert fmt2["xl"]["sets"] == fmt["xl"]["sets"]

    def test_heal_unformatted_drive(self, tmp_path):
        drives = [[LocalDrive(str(tmp_path / f"d{d}")) for d in range(4)]]
        fmt = init_format_sets(drives)
        # Wipe one drive's format; re-init restores it at the same slot.
        import shutil
        shutil.rmtree(drives[0][2].root)
        drives2 = [[LocalDrive(str(tmp_path / f"d{d}")) for d in range(4)]]
        fmt2 = init_format_sets(drives2)
        assert fmt2["xl"]["sets"] == fmt["xl"]["sets"]
        assert drives2[0][2].disk_id == fmt["xl"]["sets"][0][2]

    def test_wrong_position_rejected(self, tmp_path):
        drives = [[LocalDrive(str(tmp_path / f"d{d}")) for d in range(4)]]
        init_format_sets(drives)
        # Swap two drives on disk.
        os.rename(str(tmp_path / "d0"), str(tmp_path / "tmp"))
        os.rename(str(tmp_path / "d1"), str(tmp_path / "d0"))
        os.rename(str(tmp_path / "tmp"), str(tmp_path / "d1"))
        drives2 = [[LocalDrive(str(tmp_path / f"d{d}")) for d in range(4)]]
        with pytest.raises(ErrFileCorrupt):
            init_format_sets(drives2)

    def test_quorum(self):
        assert quorum_formatted([{}, {"a": 1}, {"a": 1}, None]) is False
        assert quorum_formatted([{"a": 1}] * 3 + [None]) is True

    def test_adopt_tolerates_unreachable_minority(self, tmp_path):
        """A formatted deployment must (re)load with a dead drive — one
        dead peer cannot block a node restart (waitForFormatErasure's
        quorum, cmd/prepare-storage.go:298)."""
        drives = [[LocalDrive(str(tmp_path / f"q{d}")) for d in range(4)]]
        fmt = init_format_sets(drives)

        class DeadDrive:
            root = "dead"

            def read_all(self, vol, path):
                from minio_tpu.storage.errors import ErrDiskNotFound
                raise ErrDiskNotFound("dead peer")

            def write_all(self, vol, path, data):
                from minio_tpu.storage.errors import ErrDiskNotFound
                raise ErrDiskNotFound("dead peer")

        row = [LocalDrive(str(tmp_path / f"q{d}")) for d in range(3)]
        row.append(DeadDrive())
        fmt2 = init_format_sets([row])
        assert fmt2["id"] == fmt["id"]

    def test_fresh_format_requires_all_drives(self, tmp_path):
        """Formatting a FRESH deployment around an unreachable drive
        could mint two deployments — it must wait instead."""
        from minio_tpu.storage.errors import ErrDiskNotFound

        class DeadDrive:
            root = "dead"

            def read_all(self, vol, path):
                raise ErrDiskNotFound("dead peer")

        row = [LocalDrive(str(tmp_path / f"f{d}")) for d in range(3)]
        row.append(DeadDrive())
        with pytest.raises(ErrDiskNotFound):
            init_format_sets([row])


class TestXLMetaIntegrity:
    def test_xxhash64_roundtrip_and_corruption(self):
        from minio_tpu.storage.xlmeta import XLMeta, XL_MAGIC2
        from minio_tpu.storage.errors import ErrFileCorrupt
        m = XLMeta([{"id": "", "mt": 1, "size": 3}])
        raw = m.to_bytes()
        assert raw[:4] == XL_MAGIC2              # new writes: xxhash64
        assert XLMeta.from_bytes(raw).versions == m.versions
        bad = bytearray(raw)
        bad[-1] ^= 1
        import pytest as _pytest
        with _pytest.raises(ErrFileCorrupt):
            XLMeta.from_bytes(bytes(bad))

    def test_legacy_crc32_meta_still_readable(self):
        import binascii
        import struct
        from minio_tpu.storage.xlmeta import XLMeta, XL_MAGIC
        from minio_tpu.utils import msgpackx
        payload = msgpackx.packb({"v": 1, "versions": [{"id": "x"}]})
        crc = binascii.crc32(payload) & 0xFFFFFFFF
        legacy = XL_MAGIC + struct.pack(">I", crc) + payload
        assert XLMeta.from_bytes(legacy).versions == [{"id": "x"}]


class TestDirtyPersistence:
    def test_dirty_set_survives_restart(self, tmp_path):
        """Buckets marked dirty before a restart still get a full
        rescan after it (VERDICT r2 item 9)."""
        from minio_tpu.background.scanner import DataScanner
        from minio_tpu.background.usage import DirtyTracker
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.engine.sets import ErasureSets
        from minio_tpu.storage.drive import LocalDrive

        drives = [LocalDrive(str(tmp_path / f"dp{i}")) for i in range(4)]
        pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
        pools.make_bucket("dirtyb")
        t1 = DirtyTracker()
        s1 = DataScanner(pools, dirty=t1)
        s1.scan_cycle()                  # persists the (empty) baseline
        t1.mark("dirtyb")
        t1.save(pools.pools[0].sets[0])  # the periodic checkpoint
        # "restart": a fresh tracker + scanner over the same drives
        t2 = DirtyTracker()
        DataScanner(pools, dirty=t2)
        assert t2.is_dirty("dirtyb")


class TestDiskIO:
    @pytest.mark.parametrize("mode", ["off", "fadvise", "direct"])
    def test_read_modes_equivalent(self, tmp_path, monkeypatch, mode):
        """All cache modes return identical bytes, aligned or not
        (the O_DIRECT-role knob, cmd/xl-storage.go:1424,1533)."""
        from minio_tpu.storage import diskio
        monkeypatch.setenv("MTPU_ODIRECT", mode)
        p = str(tmp_path / "blob")
        data = bytes(range(256)) * 2048          # 512 KiB, > BULK
        with open(p, "wb") as f:
            f.write(data)
        assert diskio.read_range(p, 0, -1) == data
        assert diskio.read_range(p, 0, len(data)) == data
        # unaligned offset/length crossing alignment boundaries
        assert diskio.read_range(p, 4097, 140000) == data[4097:4097 + 140000]
        # read past EOF trims
        assert diskio.read_range(p, len(data) - 10, 10 ** 6) == data[-10:]

    def test_drive_read_file_uses_modes(self, tmp_path, monkeypatch):
        from minio_tpu.storage.drive import LocalDrive
        monkeypatch.setenv("MTPU_ODIRECT", "direct")
        d = LocalDrive(str(tmp_path / "dd"))
        d.make_volume("v")
        blob = b"\xab" * 300000
        d.create_file("v", "big", blob)
        assert d.read_file("v", "big") == blob
        assert d.read_file("v", "big", 4096, 131072) == \
            blob[4096:4096 + 131072]


    def test_mark_persists_without_manual_save(self, tmp_path,
                                               monkeypatch):
        """A mark between scan cycles checkpoints itself (debounced) —
        no manual save() needed (review r3 finding)."""
        from minio_tpu.background.scanner import DataScanner
        from minio_tpu.background.usage import DirtyTracker
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.engine.sets import ErasureSets
        from minio_tpu.storage.drive import LocalDrive

        monkeypatch.setattr(DirtyTracker, "SAVE_INTERVAL", 0.0)
        drives = [LocalDrive(str(tmp_path / f"mp{i}")) for i in range(4)]
        pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
        pools.make_bucket("autod")
        t1 = DirtyTracker()
        DataScanner(pools, dirty=t1)      # binds the tracker
        t1.mark("autod")                  # product path: engine mark
        # checkpoint runs off the request path (background thread)
        import time as _time
        deadline = _time.time() + 5
        found = False
        while _time.time() < deadline and not found:
            t2 = DirtyTracker()
            DataScanner(pools, dirty=t2)
            found = t2.is_dirty("autod")
            if not found:
                _time.sleep(0.05)
        assert found


class TestOSCounters:
    def test_drive_ops_are_counted(self, tmp_path):
        from minio_tpu.storage.drive import LocalDrive
        d = LocalDrive(str(tmp_path / "oc"))
        d.make_volume("v")
        d.create_file("v", "f", b"x" * 1000)
        d.read_file("v", "f")
        d.write_all("v", "meta", b"{}")
        d.read_all("v", "meta")
        d.delete("v", "f")
        snap = d._osc.snapshot()
        assert snap["read"]["count"] >= 2
        assert snap["write"]["count"] >= 2
        assert snap["delete"]["count"] >= 1
        assert d.disk_info()["os"]["read"]["count"] >= 2
