"""Pre-fork worker pool: arenas, rings, the remote dispatch protocol,
and the multi-process serving vertical.

Layers, cheapest first:

  * ShmArena / ShmRing units — allocation algebra, backpressure,
    MPMC ordering.  Pure in-process, always tier-1.
  * SharedState / WorkerPlane units — the cross-process control block
    and its /metrics rendering, exercised without any fork.
  * Remote-protocol differential — a RemoteCoalescer front end talking
    to serve_owner() running IN-THREAD over a real plane: the shard
    bytes cross the same arena+ring path they cross between processes,
    minus the fork.  Byte-identity against the in-process
    DispatchCoalescer oracle.
  * MRF journal topology — per-worker journal naming and orphan
    adoption (worker 5's pending heals survive a pool shrink).
  * One real pool boot (MTPU_WORKERS=2, MTPU_IPC_DISPATCH=all) stays
    in tier-1 as the end-to-end smoke: PUT/GET byte identity through
    SO_REUSEPORT workers and the owner dispatch plane, /metrics and
    admin-info aggregation.  The expensive matrix — oracle
    differential, owner-death degrade, worker respawn, graceful
    drain — is marked slow:

        pytest -m slow tests/test_workers.py
"""

import hashlib
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.background import mrf
from minio_tpu.ops import coalesce
from minio_tpu.ops import ipc_dispatch as ipc
from minio_tpu.ops.ipc_ring import REC, ShmRing
from minio_tpu.ops.shm_arena import ArenaFull, ShmArena
from minio_tpu.server.client import S3Client
from minio_tpu.server.workers import SharedState, WorkerPlane, nworkers_env
from minio_tpu.storage import bitrot_io

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MB = 1 << 20


# -- shared-memory arena ------------------------------------------------------

class TestShmArena:
    def test_alloc_view_free_roundtrip(self):
        a = ShmArena(total_bytes=4 * _MB, slot_bytes=_MB)
        off = a.alloc(3 * _MB)
        a.view(off, 4)[:] = (1, 2, 3, 4)
        assert bytes(a.view(off, 4)) == b"\x01\x02\x03\x04"
        s = a.stats()
        assert s["in_use_bytes"] == 3 * _MB
        assert s["high_water_bytes"] == 3 * _MB
        a.free(off, 3 * _MB)
        s = a.stats()
        assert s["in_use_bytes"] == 0 and s["frees"] == 1
        assert s["high_water_bytes"] == 3 * _MB    # monotone

    def test_request_larger_than_arena_rejected_immediately(self):
        a = ShmArena(total_bytes=2 * _MB, slot_bytes=_MB)
        t0 = time.monotonic()
        with pytest.raises(ArenaFull):
            a.alloc(3 * _MB, timeout=5.0)
        assert time.monotonic() - t0 < 1.0         # no pointless wait

    def test_full_arena_blocks_then_raises(self):
        a = ShmArena(total_bytes=2 * _MB, slot_bytes=_MB)
        a.alloc(2 * _MB)
        t0 = time.monotonic()
        with pytest.raises(ArenaFull):
            a.alloc(_MB, timeout=0.4)
        assert time.monotonic() - t0 >= 0.3        # backpressure, not fail-fast
        assert a.stats()["alloc_timeouts"] == 1

    def test_blocked_alloc_wakes_on_free(self):
        a = ShmArena(total_bytes=2 * _MB, slot_bytes=_MB)
        off = a.alloc(2 * _MB)
        got = {}

        def taker():
            got["off"] = a.alloc(_MB, timeout=10.0)

        t = threading.Thread(target=taker)
        t.start()
        time.sleep(0.3)
        a.free(off, 2 * _MB)
        t.join(timeout=10)
        assert not t.is_alive() and "off" in got
        assert a.stats()["alloc_waits"] >= 1

    def test_concurrent_alloc_free_no_corruption(self):
        a = ShmArena(total_bytes=8 * _MB, slot_bytes=_MB)
        errs = []

        def churn(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(50):
                    n = int(rng.integers(1, 3)) * _MB
                    off = a.alloc(n, timeout=15.0)
                    a.view(off, 1)[0] = seed
                    a.free(off, n)
            except Exception as e:  # noqa: BLE001 — collected for assert
                errs.append(e)

        ts = [threading.Thread(target=churn, args=(i + 1,))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errs
        s = a.stats()
        assert s["in_use_bytes"] == 0
        assert s["allocs"] == s["frees"] == 200


# -- descriptor ring ----------------------------------------------------------

class TestShmRing:
    def test_fifo_with_padding(self):
        r = ShmRing(capacity=4)
        assert r.put(b"a") and r.put(b"bb")
        assert r.depth() == 2
        assert r.get() == b"a".ljust(REC, b"\x00")
        assert r.get() == b"bb".ljust(REC, b"\x00")
        assert r.depth() == 0

    def test_oversize_record_rejected(self):
        r = ShmRing(capacity=2)
        with pytest.raises(ValueError):
            r.put(b"x" * (REC + 1))

    def test_full_put_and_empty_get_time_out(self):
        r = ShmRing(capacity=2)
        assert r.put(b"1") and r.put(b"2")
        assert r.put(b"3", timeout=0.1) is False
        assert len(r.drain()) == 2
        assert r.get(timeout=0.1) is None

    def test_threaded_mpmc_preserves_every_record(self):
        r = ShmRing(capacity=16)
        nprod, per = 3, 80
        seen, mu = [], threading.Lock()

        def consumer():
            while True:
                rec = r.get(timeout=2.0)
                if rec is None:
                    return
                with mu:
                    seen.append(struct.unpack_from("<I", rec)[0])

        def producer(base):
            for i in range(per):
                assert r.put(struct.pack("<I", base + i), timeout=10.0)

        cons = [threading.Thread(target=consumer) for _ in range(2)]
        prods = [threading.Thread(target=producer, args=(k * 1000,))
                 for k in range(nprod)]
        for t in cons + prods:
            t.start()
        for t in prods:
            t.join(timeout=60)
        for t in cons:
            t.join(timeout=60)
        want = sorted(k * 1000 + i for k in range(nprod)
                      for i in range(per))
        assert sorted(seen) == want


# -- shared control block -----------------------------------------------------

class TestSharedState:
    def test_worker_slab_roundtrip(self):
        st = SharedState(3)
        st.worker_register(1, 4242)
        st.worker_beat(1, inflight=5)
        st.note_request(1)
        st.note_request(1)
        st.set_ready(1)
        st.set_draining(1)
        assert st.bump_respawn(1) == 1
        rows = st.worker_rows()
        assert len(rows) == 3
        r = rows[1]
        assert r["pid"] == 4242 and r["up"] and r["ready"]
        assert r["draining"] and r["respawns"] == 1
        assert r["requests"] == 2 and r["inflight"] == 5
        assert rows[0]["up"] is False    # never registered

    def test_owner_generation_and_staleness(self):
        st = SharedState(1)
        assert st.owner_ok(5.0) is False           # never registered
        gen = st.bump_owner_gen()
        st.owner_register(123)
        assert st.owner_ok(5.0) is True
        st._a[2] = 0                               # rewind the heartbeat
        assert st.owner_ok(5.0) is False
        st.owner_beat({"dispatches": 4, "items": 8,
                       "pending_items": 1, "weight": 2})
        info = st.owner_info()
        assert info["pid"] == 123 and info["generation"] == gen
        assert info["co_occupancy"] == 2.0         # 8 items / 4 dispatches


class TestWorkerPlane:
    def test_info_and_prometheus_rendering(self):
        plane = WorkerPlane(2, arena_bytes=8 * _MB, ring_capacity=32)
        plane.state.worker_register(0, os.getpid())
        plane.state.set_ready(0)
        plane.state.note_request(0)
        info = plane.workers_info()
        assert {"workers", "owner", "arena", "rings"} <= set(info)
        assert len(info["workers"]) == 2
        assert info["workers"][0]["up"] is True
        prom = plane.render_prom()
        for name in ("mtpu_worker_up", "mtpu_worker_respawns_total",
                     "mtpu_worker_requests_total",
                     "mtpu_shm_arena_bytes", "mtpu_shm_arena_in_use",
                     "mtpu_ipc_ring_depth", "mtpu_owner_up",
                     "mtpu_owner_generation"):
            assert name in prom
        assert 'mtpu_worker_up{worker="0"} 1' in prom
        assert 'mtpu_worker_up{worker="1"} 0' in prom

    def test_nworkers_env_parsing(self, monkeypatch):
        monkeypatch.delenv("MTPU_WORKERS", raising=False)
        assert nworkers_env() == 0
        monkeypatch.setenv("MTPU_WORKERS", "3")
        assert nworkers_env() == 3
        monkeypatch.setenv("MTPU_WORKERS", "junk")
        assert nworkers_env() == 0


# -- remote dispatch protocol (in-thread, no fork) ----------------------------

@pytest.fixture()
def ipc_plane(monkeypatch):
    """A WorkerPlane with serve_owner() running in-thread: the same
    arena+ring protocol the forked pool uses, minus the processes."""
    monkeypatch.setenv("MTPU_IPC_DISPATCH", "all")
    plane = WorkerPlane(1, arena_bytes=16 * _MB, ring_capacity=64)
    plane.state.bump_owner_gen()
    plane.state.owner_register(os.getpid())
    plane.state.owner_beat()
    stop = threading.Event()
    co = coalesce.DispatchCoalescer()
    ipc.serve_owner(plane, stop, co, nthreads=2)
    yield plane
    stop.set()
    co.close()


def host_digest_kernel(stacked, spans, ctx):
    """A dispatch kernel of the coalescer's shape: the host's mxh256
    digests of the stacked rows, a span's rows each."""
    out = bitrot_io._hash_batch(stacked, "mxh256")
    return [out[lo:hi] for lo, hi in spans]


# The device digest program's key, as the engine submits it: the owner
# rebuilds its kernel from the key alone (ipc_dispatch.kernel_from_key).
PAD = 32


class TestRemoteProtocol:
    def test_digest_roundtrip_matches_local_oracle(self, ipc_plane):
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, size=(8, 4096), dtype=np.uint8)
        key = ("digest", "mxh256", 4096, PAD)
        fn = host_digest_kernel

        local = coalesce.DispatchCoalescer()
        try:
            h = local.submit(key, payload, fn)
            want = np.asarray(h.result(timeout=60.0))
            h.release()
        finally:
            local.close()

        rc = ipc.RemoteCoalescer(ipc_plane, 0)
        try:
            ipc_plane.state.owner_beat()
            h = rc.submit(key, payload, fn)
            got = np.asarray(h.result(timeout=60.0))
            assert np.array_equal(got, want)
            st = rc.stats()
            assert st["remote_submits"] == 1
            assert st["remote_results"] == 1
            assert st["remote_fallbacks"] == 0
        finally:
            rc.close()

    def test_arena_slots_returned_after_roundtrips(self, ipc_plane):
        payload = np.zeros((4, 1024), dtype=np.uint8)
        rc = ipc.RemoteCoalescer(ipc_plane, 0)
        try:
            for _ in range(5):
                ipc_plane.state.owner_beat()
                h = rc.submit(("digest", "mxh256", 1024, PAD), payload,
                              host_digest_kernel)
                h.result(timeout=60.0)
            deadline = time.monotonic() + 10
            while (ipc_plane.arena.stats()["in_use_bytes"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)          # listener frees after decode
            assert ipc_plane.arena.stats()["in_use_bytes"] == 0
        finally:
            rc.close()

    def test_unknown_kind_surfaces_as_error(self, ipc_plane):
        rc = ipc.RemoteCoalescer(ipc_plane, 0)
        try:
            ipc_plane.state.owner_beat()
            h = rc.submit(("bogus", 1), np.zeros((2, 8), np.uint8),
                          host_digest_kernel)
            with pytest.raises(RuntimeError):
                h.result(timeout=60.0)
            assert rc.stats()["remote_errors"] == 1
        finally:
            rc.close()

    def test_mode_zero_never_routes_remote(self, monkeypatch):
        monkeypatch.setenv("MTPU_IPC_DISPATCH", "0")
        plane = WorkerPlane(1, arena_bytes=8 * _MB, ring_capacity=16)
        plane.state.bump_owner_gen()
        plane.state.owner_register(os.getpid())
        plane.state.owner_beat()
        rc = ipc.RemoteCoalescer(plane, 0)
        try:
            h = rc.submit(("digest", "mxh256", 64, PAD),
                          np.zeros((1, 64), np.uint8), host_digest_kernel)
            np.asarray(h.result(timeout=60.0))
            h.release()
            st = rc.stats()
            assert st["remote_submits"] == 0
            assert st["remote_active"] is False
        finally:
            rc.close()

    def test_owner_death_fails_pending_and_pins_local(self, monkeypatch):
        monkeypatch.setenv("MTPU_IPC_DISPATCH", "all")
        # No serve_owner: the submit sits pending until the watchdog
        # declares the (silent) owner dead.
        plane = WorkerPlane(1, arena_bytes=8 * _MB, ring_capacity=16)
        plane.state.bump_owner_gen()
        plane.state.owner_register(os.getpid())
        plane.state.owner_beat()
        rc = ipc.RemoteCoalescer(plane, 0)
        try:
            h = rc.submit(("digest", "mxh256", 64, PAD),
                          np.zeros((1, 64), np.uint8), host_digest_kernel)
            assert rc.stats()["remote_submits"] == 1
            plane.state._a[2] = 0          # heartbeat goes stale NOW
            with pytest.raises(RuntimeError):
                h.result(timeout=30.0)
            assert rc._remote_active() is False   # pinned local
            # A NEW generation with a fresh heartbeat re-enables routing.
            plane.state.bump_owner_gen()
            plane.state.owner_beat()
            assert rc._remote_active() is True
        finally:
            rc.close()


# -- MRF journal topology -----------------------------------------------------

class TestMRFJournalTopology:
    def test_journal_name_per_worker(self, monkeypatch):
        monkeypatch.delenv("MTPU_WORKER_ID", raising=False)
        assert mrf._journal_name() == "mrf-journal.jsonl"
        monkeypatch.setenv("MTPU_WORKER_ID", "3")
        assert mrf._journal_name() == "mrf-journal.w3.jsonl"

    def test_adopts_orphans_but_not_live_siblings(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv("MTPU_WORKERS_TOTAL", "2")
        home = tmp_path
        adopter = str(home / "mrf-journal.w0.jsonl")

        def rec(**kw):
            return json.dumps(kw, separators=(",", ":")) + "\n"

        # Live sibling (w1 < width): must NOT be adopted.
        (home / "mrf-journal.w1.jsonl").write_text(
            rec(op="enq", b="bkt", o="live", vid="v1"))
        # Orphan (w5 >= width): net pending after its own algebra is
        # only "keep" — "gone" was completed before the writer died.
        (home / "mrf-journal.w5.jsonl").write_text(
            rec(op="enq", b="bkt", o="gone", vid="v1")
            + rec(op="enq", b="bkt", o="keep", vid="v2")
            + rec(op="done", k="bkt/gone@v1"))
        # Legacy single-writer journal: adopted too in pool mode.
        (home / "mrf-journal.jsonl").write_text(
            rec(op="enq", b="bkt", o="legacy", vid="v3"))

        adopted = mrf.adopt_orphan_journals(adopter)
        assert adopted == 2
        assert not (home / "mrf-journal.w5.jsonl").exists()
        assert not (home / "mrf-journal.jsonl").exists()
        assert (home / "mrf-journal.w1.jsonl").exists()

        objs = [json.loads(ln)["o"]
                for ln in open(adopter, encoding="utf-8")]
        assert sorted(objs) == ["keep", "legacy"]
        assert "gone" not in objs


# -- real pool subprocesses ---------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot_pool(root, nworkers, extra_env=None):
    """Boot `python -m minio_tpu.server` over 4 drives; returns
    (proc, port).  Caller terminates."""
    os.makedirs(root, exist_ok=True)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MTPU_SCANNER": "0",
                "MTPU_WORKERS": str(nworkers)})
    env.update(extra_env or {})
    port = _free_port()
    log = open(os.path.join(root, "server.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, "-m", "minio_tpu.server",
         "--drives", f"{root}/d{{1...4}}", "--port", str(port)],
        env=env, cwd=_REPO, stdout=log, stderr=subprocess.STDOUT)
    log.close()
    deadline = time.monotonic() + 240
    import urllib.request
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"server died rc={proc.returncode}; see {root}/server.log")
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/minio/health/ready",
                    timeout=2) as r:
                if r.status == 200:
                    return proc, port
        except Exception:  # noqa: BLE001 — still booting
            pass
        time.sleep(0.2)
    proc.kill()
    raise RuntimeError("server never became ready")


def _stop(proc):
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    return proc.returncode


def _cli(port) -> S3Client:
    return S3Client(f"http://127.0.0.1:{port}",
                    "minioadmin", "minioadmin")


@pytest.fixture(scope="class")
def pool_server(tmp_path_factory):
    """ONE tier-1 pool boot shared by the smoke class: 2 workers +
    device owner, everything force-routed through the shared-memory
    dispatch plane."""
    root = str(tmp_path_factory.mktemp("pool"))
    proc, port = _boot_pool(root, 2, {"MTPU_IPC_DISPATCH": "all"})
    # health/ready turns 200 as soon as ONE worker serves; the smoke
    # asserts on BOTH slabs, so wait out the second worker's boot too.
    cli = _cli(port)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        _, _, data = cli.request("GET", "/minio/admin/v1/info")
        rows = json.loads(data)["pool"]["workers"]
        if len(rows) == 2 and all(r["up"] and r["ready"] for r in rows):
            break
        time.sleep(0.1)
    else:
        proc.kill()
        raise RuntimeError("second worker never became ready")
    yield port
    assert _stop(proc) == 0      # graceful drain is part of the smoke


class TestPoolSmoke:
    """The cheapest end-to-end proof that the forked pool serves the
    same S3 the single process serves: byte identity, ETags, and the
    cross-process observability planes, all against one boot."""

    def test_put_get_identity_through_the_pool(self, pool_server):
        cli = _cli(pool_server)
        cli.make_bucket("poolsmoke")
        rng = np.random.default_rng(11)
        for n in (0, 1, 4096, _MB + 17):
            body = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            h = cli.put_object("poolsmoke", f"o{n}", body)
            assert h["ETag"].strip('"') == hashlib.md5(body).hexdigest()
            assert cli.get_object("poolsmoke", f"o{n}") == body
        big = rng.integers(0, 256, size=_MB + 17, dtype=np.uint8).tobytes()
        cli.put_object("poolsmoke", "ranged", big)
        assert cli.get_object("poolsmoke", "ranged",
                              range_=(1000, 999999)) == big[1000:1000000]

    def test_no_worker_initialises_a_jax_backend(self, pool_server):
        """A chip belongs to one process: the device owner asks JAX,
        the workers adopt its answer.  After a PUT through the codec,
        every worker that answers healthinfo (fresh connections land on
        both) reports the owner's platform and no backend of its own."""
        cli = _cli(pool_server)
        cli.make_bucket("nojax")
        body = np.random.default_rng(5).integers(
            0, 256, size=2 * _MB + 3, dtype=np.uint8).tobytes()
        cli.put_object("nojax", "o", body)
        assert cli.get_object("nojax", "o") == body
        for _ in range(24):
            _, _, data = cli.request("GET", "/minio/admin/v3/healthinfo")
            (doc,) = json.loads(data)["nodes"].values()
            assert doc["device"] == {
                "platform": "cpu", "kind": "cpu", "count": 8, "lanes": 1,
                "in_process": False}

    def test_requests_spread_across_workers(self, pool_server):
        cli = _cli(pool_server)
        # SO_REUSEPORT balancing is kernel-side and not strictly fair,
        # but 40 fresh connections essentially never all land on one
        # worker; what we pin is that BOTH slabs count and aggregate.
        for i in range(40):
            cli.request("GET", "/minio/health/ready")
        _, _, data = cli.request("GET", "/minio/admin/v1/info")
        info = json.loads(data)
        pool = info["pool"]
        rows = pool["workers"]
        assert len(rows) == 2
        assert all(r["up"] and r["ready"] for r in rows)
        assert sum(r["requests"] for r in rows) >= 40
        assert pool["owner"]["up"] is True
        assert pool["owner"]["generation"] >= 1

    def test_metrics_aggregate_across_processes(self, pool_server):
        cli = _cli(pool_server)
        _, _, data = cli.request("GET", "/minio/v2/metrics/cluster")
        text = data.decode()
        assert 'mtpu_worker_up{worker="0"} 1' in text
        assert 'mtpu_worker_up{worker="1"} 1' in text
        assert "mtpu_owner_up 1" in text
        assert "mtpu_shm_arena_bytes" in text
        assert "mtpu_ipc_ring_depth" in text

    def test_hot_tier_shared_across_pool(self, pool_server):
        """One shared segment behind both SO_REUSEPORT workers: no
        matter which worker each GET lands on, the first two lookups
        miss (ghost, then fill) and every later one hits — visible in
        the pool-wide hotcache stats block and the per-worker slab
        counters."""
        cli = _cli(pool_server)
        cli.make_bucket("hotpool")
        body = np.random.default_rng(23).integers(
            0, 256, size=_MB + 7, dtype=np.uint8).tobytes()
        cli.put_object("hotpool", "hot", body)
        for _ in range(6):
            assert cli.get_object("hotpool", "hot") == body
        _, _, data = cli.request("GET", "/minio/admin/v1/info")
        pool = json.loads(data)["pool"]
        st = pool["hotcache"]
        assert st["fills"] >= 1 and st["hits"] >= 1
        rows = pool["workers"]
        assert all("hotcache_hits" in r and "hotcache_misses" in r
                   for r in rows)
        assert sum(r["hotcache_hits"] + r["hotcache_misses"]
                   for r in rows) >= 6


class TestHotTierForkShare:
    """The satellite acceptance shape, minus HTTP: two forked
    processes over ONE pre-fork HotObjectCache segment and the same
    drive roots.  A's fill serves B's hit; a PUT through A invalidates
    B's cached copy via the shared generation table."""

    def _run(self, fn):
        import multiprocessing
        ctx = multiprocessing.get_context("fork")
        p = ctx.Process(target=fn)
        p.start()
        p.join(60)
        assert p.exitcode == 0

    def test_fill_hit_and_invalidation_across_fork(self, tmp_path):
        from minio_tpu.engine.erasure_set import ErasureSet
        from minio_tpu.engine.hotcache import (HotObjectCache,
                                               attach_sets)
        from minio_tpu.storage.drive import LocalDrive

        es = ErasureSet([LocalDrive(str(tmp_path / f"d{i}"))
                         for i in range(4)])
        tier = HotObjectCache(total_bytes=16 * _MB)   # pre-fork
        attach_sets(es, tier)
        es.make_bucket("b")
        rng = np.random.default_rng(29)
        v1 = rng.integers(0, 256, size=300_000, dtype=np.uint8).tobytes()
        v2 = rng.integers(0, 256, size=310_000, dtype=np.uint8).tobytes()

        def a_put_and_warm():
            es.put_object("b", "o", v1)
            for _ in range(3):                        # ghost, fill, hit
                _, got = es.get_object("b", "o")
            assert bytes(got) == v1

        self._run(a_put_and_warm)
        st = tier.stats()                  # shared mapping: parent sees
        assert st["fills"] == 1 and st["hits"] >= 1
        hits0 = st["hits"]

        def b_hits_a_fill():
            _, got = es.get_object("b", "o")
            assert bytes(got) == v1

        self._run(b_hits_a_fill)
        st = tier.stats()
        assert st["hits"] == hits0 + 1     # B hit, and filled nothing
        assert st["fills"] == 1

        def a_overwrites():
            es.put_object("b", "o", v2)    # _mark_dirty -> shared gen

        self._run(a_overwrites)

        def b_sees_v2():
            _, got = es.get_object("b", "o")
            assert bytes(got) == v2

        self._run(b_sees_v2)
        assert tier.stats()["stale_gen"] >= 1


@pytest.mark.slow
class TestPoolMatrix:
    """The expensive proofs: oracle differential, owner-death degrade,
    worker respawn.  Each boots its own subprocess tree."""

    def test_pool_is_byte_identical_to_single_process_oracle(
            self, tmp_path):
        rng = np.random.default_rng(23)
        sizes = (0, 1, 4096, _MB + 17, 3 * _MB + 5)
        bodies = {n: rng.integers(0, 256, size=n,
                                  dtype=np.uint8).tobytes()
                  for n in sizes}
        parts = [rng.integers(0, 256, size=5 * _MB,
                              dtype=np.uint8).tobytes(),
                 rng.integers(0, 256, size=5 * _MB,
                              dtype=np.uint8).tobytes(),
                 rng.integers(0, 256, size=123457,
                              dtype=np.uint8).tobytes()]
        results = {}
        for label, nw, extra in (
                ("oracle", 0, {}),
                ("pool", 2, {"MTPU_IPC_DISPATCH": "all"})):
            proc, port = _boot_pool(str(tmp_path / label), nw, extra)
            try:
                cli = _cli(port)
                cli.make_bucket("diffb")
                out = {}
                for n, body in bodies.items():
                    h = cli.put_object("diffb", f"o{n}", body)
                    out[f"etag{n}"] = h["ETag"]
                    out[f"get{n}"] = hashlib.sha256(
                        cli.get_object("diffb", f"o{n}")).hexdigest()
                out["range"] = hashlib.sha256(cli.get_object(
                    "diffb", f"o{3 * _MB + 5}",
                    range_=(4097, 2 * _MB))).hexdigest()
                uid = cli.create_multipart("diffb", "mpu")
                etags = [cli.upload_part("diffb", "mpu", uid, i + 1, p)
                         for i, p in enumerate(parts)]
                cli.complete_multipart(
                    "diffb", "mpu", uid,
                    [(i + 1, e) for i, e in enumerate(etags)])
                out["mpu_etag"] = cli.head_object("diffb", "mpu")["ETag"]
                out["mpu"] = hashlib.sha256(
                    cli.get_object("diffb", "mpu")).hexdigest()
                results[label] = out
            finally:
                _stop(proc)
        assert results["pool"] == results["oracle"]

    def test_owner_death_degrades_then_recovers(self, tmp_path):
        proc, port = _boot_pool(
            str(tmp_path / "od"), 2,
            {"MTPU_IPC_DISPATCH": "all", "MTPU_RESPAWN_DELAY_S": "2"})
        try:
            cli = _cli(port)
            cli.make_bucket("odb")
            _, _, data = cli.request("GET", "/minio/admin/v1/info")
            owner = json.loads(data)["pool"]["owner"]
            gen0, pid = owner["generation"], owner["pid"]
            os.kill(pid, signal.SIGKILL)
            # Degrade window: workers fall back to local compute — a
            # PUT right now must still succeed.
            body = os.urandom(256 * 1024)
            cli.put_object("odb", "during", body)
            assert cli.get_object("odb", "during") == body
            # Supervisor respawns the owner under a NEW generation.
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                _, _, data = cli.request("GET", "/minio/admin/v1/info")
                owner = json.loads(data)["pool"]["owner"]
                if owner["generation"] > gen0 and owner["up"]:
                    break
                time.sleep(0.5)
            assert owner["generation"] > gen0 and owner["up"]
            cli.put_object("odb", "after", body)
            assert cli.get_object("odb", "after") == body
        finally:
            assert _stop(proc) == 0

    def test_dead_worker_respawns_and_counts(self, tmp_path):
        proc, port = _boot_pool(
            str(tmp_path / "rs"), 2, {"MTPU_RESPAWN_DELAY_S": "1"})
        try:
            cli = _cli(port)
            _, _, data = cli.request("GET", "/minio/admin/v1/info")
            rows = json.loads(data)["pool"]["workers"]
            victim = rows[1]
            os.kill(victim["pid"], signal.SIGKILL)
            deadline = time.monotonic() + 120
            row = None
            while time.monotonic() < deadline:
                _, _, data = cli.request("GET", "/minio/admin/v1/info")
                row = json.loads(data)["pool"]["workers"][1]
                if (row["respawns"] >= 1 and row["up"] and row["ready"]
                        and row["pid"] != victim["pid"]):
                    break
                time.sleep(0.5)
            assert row["respawns"] >= 1 and row["up"] and row["ready"]
            assert row["pid"] != victim["pid"]
            cli.make_bucket("rsb")
            cli.put_object("rsb", "x", b"still serving")
            assert cli.get_object("rsb", "x") == b"still serving"
        finally:
            assert _stop(proc) == 0
