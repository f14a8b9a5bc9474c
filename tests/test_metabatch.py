"""Small-object metadata plane (PR 19): group-commit publishes, journal
replay and the FileInfo cache LRU, each proven against the
MTPU_METABATCH=0 single-op oracle; and the xl.meta read fan-out
(PR 30): on the request's own thread where every drive is in-process.
"""

import os
import threading
import zlib

import numpy as np
import pytest

from minio_tpu.engine.erasure_set import ErasureSet
from minio_tpu.observe import span as ospan
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import metalanes
from minio_tpu.storage.drive import (META_JOURNAL_DIR, SYS_VOL,
                                     LocalDrive)
from minio_tpu.storage.errors import (ErrDiskNotFound,
                                      ErrErasureReadQuorum,
                                      ErrObjectNotFound,
                                      ErrVersionNotFound,
                                      ErrVolumeNotFound)
from minio_tpu.storage.health_wrap import HealthWrappedDrive
from minio_tpu.storage.xlmeta import FileInfo
from minio_tpu.utils import msgpackx


def make_set(tmp_path, n=4, parity=None, name="set0"):
    drives = [LocalDrive(str(tmp_path / name / f"d{i}"))
              for i in range(n)]
    return ErasureSet(drives, default_parity=parity)


def payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def fi_for(vol, obj, data, vid="", mod=1):
    return FileInfo(volume=vol, name=obj, version_id=vid,
                    mod_time_ns=mod, size=len(data), inline_data=data)


# ---------------------------------------------------------------------------
# drive layer: write_metadata_many / journal replay
# ---------------------------------------------------------------------------

class TestDriveGroupCommit:
    def test_batch_equals_solo_sequence(self, tmp_path):
        """A group-committed batch must leave the same xl.meta state a
        sequence of solo write_metadata calls would."""
        da = LocalDrive(str(tmp_path / "a"))
        db = LocalDrive(str(tmp_path / "b"))
        for d in (da, db):
            d.make_volume("v")
        items = [("v", f"o{i}", fi_for("v", f"o{i}", bytes([i]) * 64,
                                       mod=i + 1))
                 for i in range(8)]
        errs = da.write_metadata_many(items)
        assert errs == [None] * 8
        for vol, obj, fi in items:
            db.write_metadata(vol, obj, fi)
        for i in range(8):
            ra = da.read_version("v", f"o{i}")
            rb = db.read_version("v", f"o{i}")
            assert ra.inline_data == rb.inline_data == bytes([i]) * 64
            assert ra.mod_time_ns == rb.mod_time_ns

    def test_same_key_batch_chains_versions(self, tmp_path):
        """Two versions of one key inside ONE batch must both land —
        the second item's blob chains on the first's staged meta
        instead of re-reading the (stale) on-disk xl.meta."""
        d = LocalDrive(str(tmp_path / "d"))
        d.make_volume("v")
        items = [("v", "k", fi_for("v", "k", b"one", vid="v1" + "0" * 30,
                                   mod=1)),
                 ("v", "k", fi_for("v", "k", b"two", vid="v2" + "0" * 30,
                                   mod=2))]
        assert d.write_metadata_many(items) == [None, None]
        meta = d._read_xlmeta("v", "k")
        assert len(meta.versions) == 2
        assert d.read_version("v", "k").inline_data == b"two"

    def test_per_item_fault_isolation(self, tmp_path):
        """A poisoned item (missing volume) fails alone; its
        batch-mates publish normally."""
        d = LocalDrive(str(tmp_path / "d"))
        d.make_volume("v")
        items = [("v", "good1", fi_for("v", "good1", b"a")),
                 ("novol", "bad", fi_for("novol", "bad", b"b")),
                 ("v", "good2", fi_for("v", "good2", b"c"))]
        errs = d.write_metadata_many(items)
        assert errs[0] is None and errs[2] is None
        assert isinstance(errs[1], ErrVolumeNotFound)
        assert d.read_version("v", "good1").inline_data == b"a"
        assert d.read_version("v", "good2").inline_data == b"c"

    def test_no_journal_residue_after_commit(self, tmp_path):
        d = LocalDrive(str(tmp_path / "d"))
        d.make_volume("v")
        d.write_metadata_many([("v", "o", fi_for("v", "o", b"x"))])
        jdir = os.path.join(d.root, SYS_VOL, META_JOURNAL_DIR)
        assert os.listdir(jdir) == []

    def test_replay_publishes_fsynced_segment(self, tmp_path):
        """A segment a crash left behind republishes its blobs at the
        boot sweep — the zero-acked-write-loss half of the contract."""
        d = LocalDrive(str(tmp_path / "d"))
        d.make_volume("v")
        # Craft the segment the group commit would have fsynced just
        # before dying pre-publish.
        from minio_tpu.storage.xlmeta import XLMeta
        meta = XLMeta()
        meta.add_version(fi_for("v", "lost", b"recovered", mod=9))
        pay = msgpackx.packb({"v": 1, "entries": [
            {"vol": "v", "obj": "lost", "blob": meta.to_bytes()}]})
        seg = os.path.join(d.root, SYS_VOL, META_JOURNAL_DIR,
                           "seg-000000000001-1-deadbeef")
        with open(seg, "wb") as f:
            f.write(b"MJ01" + zlib.crc32(pay).to_bytes(4, "big") + pay)
        counts = d.sweep_stale()
        assert counts["meta_journal"] == 1
        assert d.read_version("v", "lost").inline_data == b"recovered"
        assert not os.path.exists(seg)

    def test_replay_discards_torn_segment(self, tmp_path):
        """A torn (CRC-failing) segment was never fsync-complete, so
        nothing in it was acked — replay must drop it, not crash."""
        d = LocalDrive(str(tmp_path / "d"))
        d.make_volume("v")
        seg = os.path.join(d.root, SYS_VOL, META_JOURNAL_DIR,
                           "seg-000000000001-1-torn")
        with open(seg, "wb") as f:
            f.write(b"MJ01" + b"\x00\x00\x00\x00" + b"garbage")
        assert d.sweep_stale()["meta_journal"] == 0
        assert not os.path.exists(seg)
        with pytest.raises(Exception):
            d.read_version("v", "lost")


# ---------------------------------------------------------------------------
# lane scheduler: fault containment, degradation, solo forcing
# ---------------------------------------------------------------------------

class TestMetaLane:
    def test_batch_mate_failure_is_contained(self):
        """The in-process half of the durability satellite: one
        poisoned batch member must not fail or block an unrelated
        caller whose op is committed by the same dispatch."""
        done = []

        def solo(item):
            if item == "poison":
                raise RuntimeError("bad item")
            done.append(item)
            return f"ok-{item}"

        def batch(items):
            # Whole-batch fault: the lane must retry each item solo
            # and only the guilty one may surface an error.
            raise RuntimeError("batch exploded")

        lane = metalanes.MetaLane("t", solo, batch)
        try:
            # Drive one dispatch over a known 3-item batch directly —
            # deterministic, no scheduler timing in the assertion.
            items = [(x, metalanes.MetaHandle())
                     for x in ("a", "poison", "b")]
            lane._dispatch(items)
            assert items[0][1].result() == "ok-a"
            with pytest.raises(RuntimeError, match="bad item"):
                items[1][1].result()
            assert items[2][1].result() == "ok-b"
            assert sorted(done) == ["a", "b"]
            assert lane.stats()["batch_faults"] == 1
        finally:
            lane.close()

    def test_idle_submit_runs_inline(self):
        lane = metalanes.MetaLane("t", lambda x: x * 2)
        try:
            assert lane.submit(21).result() == 42
            assert lane.stats()["inline_ops"] == 1
            assert lane.stats()["dispatches"] == 0
        finally:
            lane.close()

    def test_dead_dispatcher_degrades_to_inline(self, monkeypatch):
        monkeypatch.setenv("MTPU_METABATCH_SOLO", "1")
        lane = metalanes.MetaLane("t", lambda x: x + 1)
        try:
            assert lane.submit(1).result() == 2  # starts dispatcher
            lane._abort(RuntimeError("simulated death"))
            # Submits after death run inline on the caller's thread.
            assert lane.submit(5).result() == 6
            assert lane.stats()["broken"]
        finally:
            lane.close()

    def test_batch_results_shape_enforced(self, monkeypatch):
        monkeypatch.setenv("MTPU_METABATCH_SOLO", "1")
        lane = metalanes.MetaLane("t", lambda x: x, lambda items: [])
        try:
            h = lane.submit("only")
            # Wrong-shape batch result on a single-item batch surfaces
            # as that item's error (no solo fallback to hide the bug).
            with pytest.raises(RuntimeError):
                h.result()
        finally:
            lane.close()


# ---------------------------------------------------------------------------
# engine: oracle byte-identity, read fan-out, LRU cache
# ---------------------------------------------------------------------------

class TestEngineOracleIdentity:
    def test_put_get_identity_both_modes(self, tmp_path, metabatch_mode):
        """The full observable S3 surface — body, ETag metadata, size,
        version behavior — must be identical with the lanes on or off
        (versioned and unversioned paths; multipart is excluded from
        the inline plane by size)."""
        es = make_set(tmp_path)
        es.make_bucket("b")
        body = payload(4096, seed=3)
        fi = es.put_object("b", "small", body)
        got_fi, got = es.get_object("b", "small")
        assert got == body
        assert got_fi.size == 4096
        assert es.head_object("b", "small").metadata == fi.metadata

        # Versioned: two versions, both addressable, latest wins.
        v1 = es.put_object("b", "ver", payload(1024, 1), versioned=True)
        v2 = es.put_object("b", "ver", payload(1024, 2), versioned=True)
        assert v1.version_id and v2.version_id
        assert es.get_object("b", "ver")[1] == payload(1024, 2)
        assert es.get_object(
            "b", "ver", version_id=v1.version_id)[1] == payload(1024, 1)
        assert es.get_object(
            "b", "ver", version_id=v2.version_id)[1] == payload(1024, 2)

        with pytest.raises(ErrObjectNotFound):
            es.head_object("b", "nope")

    def test_concurrent_puts_group_commit_and_verify(self, tmp_path):
        """Concurrency ignites the lanes; every acked PUT must read
        back byte-exact and the drive layer must show real group
        commits with fewer fsyncs than publishes."""
        es = make_set(tmp_path)
        es.make_bucket("b")
        snap0 = DATA_PATH.snapshot()
        bodies = {}
        errors = []

        def worker(i):
            try:
                for j in range(10):
                    k = f"o-{i}-{j}"
                    b = payload(2048, seed=i * 100 + j)
                    es.put_object("b", k, b)
                    bodies[k] = b
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors
        for k, b in bodies.items():
            assert es.get_object("b", k)[1] == b
        snap1 = DATA_PATH.snapshot()
        if metalanes.enabled():
            assert (snap1["meta_group_commits"]
                    > snap0["meta_group_commits"])
            d_fs = snap1["meta_fsyncs"] - snap0["meta_fsyncs"]
            d_pub = snap1["meta_publishes"] - snap0["meta_publishes"]
            assert d_fs < d_pub  # group commit amortized something

    def test_solo_forced_uses_journal_path(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MTPU_METABATCH_SOLO", "1")
        metalanes.reset()
        try:
            es = make_set(tmp_path)
            es.make_bucket("b")
            snap0 = DATA_PATH.snapshot()
            es.put_object("b", "k", payload(512))
            snap1 = DATA_PATH.snapshot()
            assert (snap1["meta_group_commits"]
                    - snap0["meta_group_commits"]) == es.n
            assert es.get_object("b", "k")[1] == payload(512)
        finally:
            metalanes.reset()


class RemoteStub:
    """A drive that is not a LocalDrive of this process (what an RPC
    client is to the engine): delegates every call to one, and notes
    the thread each read_version ran on."""

    def __init__(self, root):
        self._local = LocalDrive(root)
        self.read_threads = []

    def __getattr__(self, name):
        return getattr(self._local, name)

    def read_version(self, vol, obj, version_id="", read_data=False):
        self.read_threads.append(threading.current_thread().name)
        return self._local.read_version(vol, obj, version_id, read_data)


class DeadDrive(LocalDrive):
    """An in-process drive whose xl.meta reads fail (a pulled disk)."""

    def read_version(self, vol, obj, version_id="", read_data=False):
        raise ErrDiskNotFound(self.root)


def _tripped(drive):
    """`drive` behind a breaker whose circuit is open."""
    wd = HealthWrappedDrive(drive)
    object.__setattr__(wd, "_state", "offline")
    object.__setattr__(wd, "_last_fault", "test")
    return wd


def _read_lane_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("mtpu-metalane-")
            and t.name.endswith("-read")]


def _fanouts(snap0, snap1):
    return {p: snap1["meta_read_fanouts"][p] - snap0["meta_read_fanouts"][p]
            for p in ("inline", "pool")}


SMALL = payload(4096, 5)                  # inline in xl.meta
BIG = payload(3 * (1 << 20), 6)           # streaming: shard files


class TestReadFanout:
    """PR 30: a request reads its own xl.meta.  All drives in-process
    -> all N on the calling thread ("inline"); any remote drive -> the
    pool fan-out ("pool").  No lane, no second round, no K+1 trim."""

    def _prime(self, tmp_path, **kw):
        es = make_set(tmp_path, **kw)
        es.make_bucket("b")
        es.put_object("b", "small", SMALL)
        es.put_object("b", "big", BIG)
        return es

    @pytest.mark.parametrize("readers", [1, 8])
    def test_local_set_reads_inline(self, tmp_path, monkeypatch,
                                    readers):
        """All-local set: every drive read on the caller's own thread,
        all N metas back, and no read lane thread ever started — under
        8 concurrent readers too (the case that lit the lanes)."""
        monkeypatch.setattr(ErasureSet, "_SERIAL_FANOUT", False)
        es = self._prime(tmp_path)
        seen: dict[str, set] = {}
        real = LocalDrive.read_version

        def spy(self, vol, obj, version_id="", read_data=False):
            seen.setdefault(threading.current_thread().name,
                            set()).add(self.root)
            return real(self, vol, obj, version_id, read_data)

        monkeypatch.setattr(LocalDrive, "read_version", spy)
        snap0 = DATA_PATH.snapshot()
        out, errors = [], []

        def reader(i):
            try:
                for j in range(20):
                    out.append(es._read_metadata(
                        "b", ("small", "big")[(i + j) % 2]))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=reader, args=(i,),
                               name=f"reader-{i}")
              for i in range(readers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors
        assert len(out) == 20 * readers
        for fi, metas, errs in out:
            assert all(m is not None for m in metas)
            assert errs == [None] * es.n
        # Each reader thread itself touched all N drives; nobody else.
        assert set(seen) == {f"reader-{i}" for i in range(readers)}
        assert all(len(roots) == es.n for roots in seen.values())
        assert _read_lane_threads() == []
        assert _fanouts(snap0, DATA_PATH.snapshot()) == {
            "inline": 20 * readers, "pool": 0}

    def test_remote_drive_takes_pool(self, tmp_path, monkeypatch):
        """One drive that is no LocalDrive: the whole fan-out goes to
        the drive pool, where round trips overlap; same election."""
        monkeypatch.setattr(ErasureSet, "_SERIAL_FANOUT", False)
        es = self._prime(tmp_path)
        want = es._read_metadata("b", "big")
        stub = RemoteStub(es.drives[2].root)
        es.drives[2] = stub
        snap0 = DATA_PATH.snapshot()
        fi, metas, errs = es._read_metadata("b", "big")
        assert _fanouts(snap0, DATA_PATH.snapshot()) == {
            "inline": 0, "pool": 1}
        assert stub.read_threads
        assert threading.current_thread().name not in stub.read_threads
        assert fi == want[0] and metas == want[1]
        assert errs == [None] * es.n
        assert es.get_object("b", "big")[1] == BIG
        assert _read_lane_threads() == []

    @pytest.mark.parametrize("key", ["small", "big"])
    def test_inline_and_streaming_get_all_n_metas(self, tmp_path, key):
        """No trim: an inline object's read returns all N metas just as
        a streaming one's does (the healthy-read fast path keys off
        `any(m is None)`), in one pass over the drives."""
        es = self._prime(tmp_path)
        calls = []
        for pos, d in enumerate(es.drives):
            real = d.read_version
            d.read_version = (lambda *a, _r=real, _p=pos, **k:
                              (calls.append(_p), _r(*a, **k))[1])
        fi, metas, errs = es._read_metadata("b", key)
        assert calls == list(range(es.n))      # once each, in order
        assert all(m is not None for m in metas)
        assert (fi.inline_data is not None) == (key == "small")
        assert es.get_object("b", key)[1] == (SMALL if key == "small"
                                              else BIG)

    @pytest.mark.parametrize("fault", ["none", "failing", "tripped"])
    @pytest.mark.parametrize("lost", [1, 3])
    @pytest.mark.parametrize("key", ["small", "big", "missing",
                                     "small@badversion"])
    def test_classified_as_map_drives_oracle(self, tmp_path,
                                             monkeypatch, fault, lost,
                                             key):
        """A hole, a failing drive and a breaker-tripped drive give the
        inline pass exactly what the pool fan-out (`_map_drives`, the
        MTPU_METABATCH=0 oracle before PR 30) gives: same fi, same
        metas, same error types position by position, and the same
        exception for a missing object, a missing version and a lost
        read quorum."""
        monkeypatch.setattr(ErasureSet, "_SERIAL_FANOUT", False)
        es = self._prime(tmp_path)
        for pos in range(lost):
            d = es.drives[pos]
            es.drives[pos] = {"none": lambda d: None,
                              "failing": lambda d: DeadDrive(d.root),
                              "tripped": _tripped}[fault](d)
        obj, _, vid = key.partition("@")
        vid = vid and "0" * 8 + "-0000-4000-8000-" + "0" * 12

        def run():
            try:
                return es._read_metadata("b", obj, vid)
            except Exception as e:  # noqa: BLE001 — the verdict compared
                return e

        snap0 = DATA_PATH.snapshot()
        got = run()
        assert _fanouts(snap0, DATA_PATH.snapshot())["inline"] == 1
        monkeypatch.setattr(ErasureSet, "_in_process",
                            lambda self, drives=None: False)
        snap0 = DATA_PATH.snapshot()
        want = run()
        assert _fanouts(snap0, DATA_PATH.snapshot())["pool"] == 1
        named = {("missing", 1): ErrObjectNotFound,
                 ("small@badversion", 1): ErrVersionNotFound,
                 ("small", 3): ErrErasureReadQuorum,
                 ("big", 3): ErrErasureReadQuorum}.get((key, lost))
        if isinstance(want, Exception):
            assert type(got) is type(want)
            assert str(got) == str(want)
            assert named is None or type(got) is named
        else:
            assert named is None and lost == 1
            assert got[0] == want[0]
            assert got[1] == want[1]
            assert [type(e) for e in got[2]] == \
                [type(e) for e in want[2]]
            assert got[1][0] is None and got[2][0] is not None

    def test_fanout_counter_adds_up_to_requests(self, tmp_path,
                                                monkeypatch):
        """mtpu_meta_read_fanouts_total{path} sums to
        mtpu_meta_read_requests_total over local and remote sets, GET,
        HEAD, DELETE and misses alike, and the engine.quorum span
        carries the same `path` as a tag."""
        monkeypatch.setattr(ErasureSet, "_SERIAL_FANOUT", False)
        local = self._prime(tmp_path, name="local")
        remote = self._prime(tmp_path, name="remote")
        remote.drives[0] = RemoteStub(remote.drives[0].root)
        snap0 = DATA_PATH.snapshot()
        ospan.TRACER.configure(ring=8, sample=1.0)
        try:
            for es, api in ((local, "api.Local"), (remote, "api.Remote")):
                with ospan.TRACER.root(api):
                    es.head_object("b", "small")
                    es.get_object("b", "big")
                    with pytest.raises(ErrObjectNotFound):
                        es.head_object("b", "missing")
                    es.delete_object("b", "small")
            recs = {r["name"]: r for r in ospan.TRACER.traces()}
        finally:
            ospan.TRACER.configure(ring=0, sample=1.0)
            ospan.TRACER.reset()
        snap1 = DATA_PATH.snapshot()
        d = _fanouts(snap0, snap1)
        assert d["inline"] > 0 and d["inline"] == d["pool"]
        assert d["inline"] + d["pool"] == (snap1["meta_read_requests"]
                                           - snap0["meta_read_requests"])

        def quorum_paths(rec):
            out = [rec.get("tags", {}).get("path")] \
                if rec["name"] == "engine.quorum" else []
            for c in rec.get("spans", []):
                out += quorum_paths(c)
            return out

        assert set(quorum_paths(recs["api.Local"])) == {"inline"}
        assert set(quorum_paths(recs["api.Remote"])) == {"pool"}
        assert len(quorum_paths(recs["api.Local"])) == d["inline"]
        from minio_tpu.observe.metrics import MetricsRegistry
        text = MetricsRegistry().render()
        assert 'mtpu_meta_read_fanouts_total{path="inline"}' in text
        assert 'mtpu_meta_read_fanouts_total{path="pool"}' in text
        assert "mtpu_meta_trim" not in text


class TestSmallobjBenchSmoke:
    def test_engine_leg_runs_cpu(self, tmp_path):
        """The smallobj_bench engine leg must run end-to-end on the
        CPU backend (CI has no TPU): one tiny batch leg — PUT storm,
        HEAD storm, idle probe — producing every key the suite's
        ratios are built from."""
        import bench
        leg = bench._smallobj_leg(str(tmp_path), "1", clients=2,
                                  duration_s=0.4, idle_ops=5,
                                  warmup_s=0.2)
        for k in ("put_ops_per_s", "put_p50_ms", "fsyncs_per_object",
                  "batch_occupancy", "head_ops_per_s",
                  "head_p50_ms", "idle_put_p50_ms",
                  "idle_get_p50_ms"):
            assert k in leg
        assert leg["put_ops_per_s"] > 0
        assert leg["head_ops_per_s"] > 0


class TestFiCacheLru:
    def test_hot_entries_survive_overflow(self, tmp_path, monkeypatch):
        """Satellite regression: a key scan overflowing the cache used
        to clear() everything; bounded LRU must keep recently-touched
        entries."""
        es = make_set(tmp_path)
        es.make_bucket("b")
        monkeypatch.setattr(ErasureSet, "_FI_CACHE_MAX", 8)
        es.put_object("b", "hot", payload(256))
        for i in range(24):
            es.put_object("b", f"scan{i}", payload(64, i))
        es.head_object("b", "hot")          # stores the hot entry
        assert any(k[1] == "hot" for k in es._fi_cache)
        for i in range(24):
            es.head_object("b", f"scan{i}")
            es.head_object("b", "hot")      # touch: stays MRU
        assert any(k[1] == "hot" for k in es._fi_cache)
        assert len(es._fi_cache) <= 8

    def test_eviction_is_bounded_not_total(self, tmp_path, monkeypatch):
        es = make_set(tmp_path)
        es.make_bucket("b")
        monkeypatch.setattr(ErasureSet, "_FI_CACHE_MAX", 4)
        for i in range(12):
            es.put_object("b", f"k{i}", payload(64, i))
            es.head_object("b", f"k{i}")
        # Never wiped: the most recent keys are still cached.
        assert 1 <= len(es._fi_cache) <= 4
        assert any(k[1] == "k11" for k in es._fi_cache)


class TestRegistryDocs:
    def test_meta_metrics_documented(self):
        """The registry self-test enforces that every mtpu_meta_*
        family is named in README.md."""
        from minio_tpu.ops.selftest import metrics_registry_self_test
        metrics_registry_self_test()  # raises SelfTestError on drift
