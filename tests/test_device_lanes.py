"""Per-device coalescer lanes + erasure-set device affinity (PR 10).

The sharded kernel plane's contract, tested on the 8-virtual-CPU-device
mesh conftest forces:

  - affinity is the deterministic modulo of the set index (the same
    placement scheme as sipHashMod object routing), clamped to what is
    visible, with MTPU_DEVICES=1 the byte-identical oracle;
  - the facade routes each submit to its device's lane, and lanes keep
    fully independent adaptive-window stats (one lane's EMA or fault
    never leaks into another's decisions);
  - MTPU_DEVICES=1 vs =8 is a byte-identity differential over a
    randomized PUT/GET/corrupt/heal sequence: same objects, same ETags,
    same on-disk shard bytes, same bitrot verdicts;
  - the PR 9 IPC descriptor carries the device index end to end;
  - the device-parallel heal sweep overlaps device groups and converges
    to the serial sweep's end state;
  - the boot self-test covers EVERY configured lane and names the
    failing device.
"""

import hashlib
import os
import shutil
import threading

import numpy as np
import pytest

from minio_tpu.engine import heal as heal_mod
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe.metrics import DATA_PATH, MetricsRegistry
from minio_tpu.ops import coalesce, devices
from minio_tpu.ops import ipc_dispatch as ipc
from minio_tpu.ops.ipc_ring import REC
from minio_tpu.storage.drive import LocalDrive
from tools.loadgen import keyspace_names

DEP_ID = "d6bb7f1e-9f77-4a65-8b6a-3d0a5e2b9c41"


def make_ring(root, nsets=4, set_drives=4, parity=1,
              deployment_id=DEP_ID):
    drives = [LocalDrive(os.path.join(str(root), f"d{i}"))
              for i in range(nsets * set_drives)]
    return ErasureSets(drives, set_drive_count=set_drives,
                       default_parity=parity,
                       deployment_id=deployment_id)


@pytest.fixture
def ndev(monkeypatch):
    """Set MTPU_DEVICES for the test and give it a cold coalescer."""
    def set_ndev(n):
        monkeypatch.setenv("MTPU_DEVICES", str(n))
        coalesce.reset()
    yield set_ndev
    coalesce.reset()


def sum_kernel():
    def kernel(stacked, spans, ctx):
        return [int(stacked[lo:hi].sum()) for lo, hi in spans]
    return kernel


# -- affinity ----------------------------------------------------------------

class TestAffinity:
    def test_affinity_is_set_index_modulo_devices(self, ndev):
        ndev(8)
        assert devices.n_devices() == 8
        for i in range(32):
            assert devices.device_for_set(i) == i % 8

    def test_single_device_oracle_pins_everything_to_zero(self, ndev):
        ndev(1)
        assert devices.n_devices() == 1
        assert all(devices.device_for_set(i) == 0 for i in range(32))

    def test_requested_devices_clamp_to_visible(self, ndev):
        ndev(64)
        assert devices.n_devices() == devices.visible_count() == 8

    def test_set_affinity_survives_reboot_and_root_move(self, tmp_path,
                                                        ndev):
        """Same deployment id => same object->set routing => same
        device placement, regardless of where the drives live."""
        ndev(8)
        a = make_ring(tmp_path / "a")
        b = make_ring(tmp_path / "b")
        for i in range(64):
            name = f"obj-{i}"
            sa, sb = a.set_for(name), b.set_for(name)
            assert sa.set_index == sb.set_index
            assert sa.device_idx == sb.device_idx == sa.set_index % 8
        assert a.device_map() == b.device_map()
        assert sorted(x for v in a.device_map().values()
                      for x in v) == list(range(4))


# -- lane facade -------------------------------------------------------------

class TestLaneFacade:
    def test_submit_routes_to_affine_lane(self, ndev):
        ndev(8)
        co = coalesce.get()
        assert co.nlanes() == 8
        h = co.submit(("lane",), np.ones(3, dtype=np.uint8),
                      sum_kernel(), device=5)
        assert h.result(5.0) == 3
        st = co.lane_stats()
        assert st[5]["dispatches"] == 1 and st[5]["device"] == 5
        assert all(d == 5 or s["dispatches"] == 0
                   for d, s in st.items())
        agg = co.stats()
        assert agg["n_lanes"] == 8 and agg["dispatches"] == 1

    def test_out_of_range_device_wraps_modulo_lanes(self, ndev):
        ndev(2)
        co = coalesce.get()
        h = co.submit(("wrap",), np.ones(2, dtype=np.uint8),
                      sum_kernel(), device=7)      # 7 % 2 == lane 1
        assert h.result(5.0) == 2
        assert co.lane_stats()[1]["dispatches"] == 1

    def test_lane_stats_blocks_are_isolated(self, ndev):
        """The satellite fix: one lane's occupancy EMA must not pollute
        another lane's adaptive-window decisions."""
        ndev(8)
        co = coalesce.get()
        co.lane(3)._ema = 5.0
        assert co.lane(0)._ema <= 1.05

        def where(stacked, spans, ctx):
            return [threading.current_thread().name for _ in spans]

        # Lane 0 runs an idle submit inline on the caller; lane 3, whose
        # EMA says concurrent traffic is packing, queues it for its
        # own thread.
        h0 = co.submit(("where",), np.ones(1, np.uint8), where, device=0)
        h3 = co.submit(("where",), np.ones(1, np.uint8), where, device=3)
        assert h0.result(5.0) == threading.current_thread().name
        assert h3.result(5.0) == "mtpu-coalesce-d3"
        assert co.lane(0)._ema <= 1.05 and co.lane(3)._ema > 1.05

    def test_lane_fault_never_fails_another_lane(self, ndev,
                                                 monkeypatch):
        """Poison lane 2's scheduler: its queued handle dies promptly
        and later device-2 submits degrade inline, while lane 1 keeps
        batching untouched."""
        ndev(8)
        co = coalesce.get()
        co.lane(1)._ema = 5.0              # force both queued paths
        co.lane(2)._ema = 5.0
        monkeypatch.setattr(
            co.lane(2), "_pick_key",
            lambda: (_ for _ in ()).throw(RuntimeError("lane bug")))
        h2 = co.submit(("f", 2), np.ones(3, dtype=np.uint8),
                       sum_kernel(), device=2)
        with pytest.raises(RuntimeError, match="dispatcher died"):
            h2.result(5.0)
        assert co.lane_stats()[2]["broken"]
        # the healthy lane still dispatches through its queue
        h1 = co.submit(("f", 1), np.ones(4, dtype=np.uint8),
                       sum_kernel(), device=1)
        assert h1.result(5.0) == 4
        assert not co.lane_stats()[1]["broken"]
        # facade aggregate reflects the one broken lane
        assert co.stats()["broken"] is True
        # device-2 traffic survives via inline degradation
        h2b = co.submit(("f", 2), np.ones(5, dtype=np.uint8),
                        sum_kernel(), device=2)
        assert h2b.result(5.0) == 5


# -- 1-vs-8 device byte-identity differential --------------------------------

def _run_sequence(root, nd, monkeypatch):
    """One deterministic PUT/GET/corrupt/heal sequence on a fresh ring
    under MTPU_DEVICES=nd; returns everything the oracle compares."""
    monkeypatch.setenv("MTPU_DEVICES", str(nd))
    monkeypatch.setenv("MTPU_COALESCE", "1")
    coalesce.reset()
    try:
        ring = make_ring(root)
        ring.make_bucket("b")
        names = keyspace_names(ring, "spread", total=8)
        rng = np.random.default_rng(1234)
        sizes = [100, 70_000, (1 << 20) + 4097, 3 << 20] * 2
        bodies, etags = {}, {}
        for name, size in zip(names, sizes):
            body = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            bodies[name] = body
            etags[name] = ring.put_object("b", name, body).etag
        # overwrite one, delete one
        bodies[names[0]] = b"v2" * 4096
        etags[names[0]] = ring.put_object("b", names[0],
                                          bodies[names[0]]).etag
        ring.delete_object("b", names[1])
        del bodies[names[1]], etags[names[1]]
        gets = {n: hashlib.sha256(
            bytes(ring.get_object("b", n)[1])).hexdigest()
            for n in bodies}
        # on-disk shard bytes, keyed by drive position (uuid data-dir
        # names differ between runs; the shard BYTES must not)
        shards = {}
        for i in range(16):
            digs = []
            droot = os.path.join(str(root), f"d{i}")
            for dp, _, fn in os.walk(droot):
                digs.extend(
                    hashlib.sha256(
                        open(os.path.join(dp, f), "rb").read())
                    .hexdigest() for f in fn if f.startswith("part."))
            shards[i] = sorted(digs)
        # bitrot: corrupt the biggest object's first part file on its
        # first drive — the read must detect + reconstruct
        victim = names[3]
        vset = ring.set_for(victim)
        vdrive = 16  # resolved below: first drive of the victim's set
        vdrive = vset.set_index * 4
        part = sorted(
            os.path.join(dp, f)
            for dp, _, fn in os.walk(
                os.path.join(str(root), f"d{vdrive}", "b", victim))
            for f in fn if f.startswith("part."))[0]
        raw = bytearray(open(part, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(part, "wb").write(bytes(raw))
        bitrot_get = hashlib.sha256(
            bytes(ring.get_object("b", victim)[1])).hexdigest()
        # heal: lose one whole drive's bucket tree, device-parallel
        # sweep must restore every set it owns
        shutil.rmtree(os.path.join(str(root), "d0", "b"),
                      ignore_errors=True)
        ring.heal_bucket("b")
        healed = heal_mod.sweep_sets_device_parallel(
            ring.sets,
            lambda es: heal_mod.heal_bucket_objects(es, "b"))
        final = {n: hashlib.sha256(
            bytes(ring.get_object("b", n)[1])).hexdigest()
            for n in bodies}
        return {"etags": etags, "gets": gets, "shards": shards,
                "bitrot": bitrot_get, "final": final,
                "healed_sets": sorted(healed),
                "set_route": {n: ring.set_for(n).set_index
                              for n in names}}
    finally:
        coalesce.reset()


class TestDeviceOracle:
    @pytest.mark.slow
    def test_1_vs_8_devices_byte_identical(self, tmp_path, monkeypatch):
        a = _run_sequence(tmp_path / "nd1", 1, monkeypatch)
        b = _run_sequence(tmp_path / "nd8", 8, monkeypatch)
        assert a == b

    def test_1_vs_8_devices_smoke(self, tmp_path, monkeypatch):
        """Tier-1 cut of the differential: PUT/GET byte identity and
        ETags across the topologies (the slow test adds corrupt+heal
        and the on-disk shard comparison)."""
        results = {}
        for nd in (1, 8):
            monkeypatch.setenv("MTPU_DEVICES", str(nd))
            monkeypatch.setenv("MTPU_COALESCE", "1")
            coalesce.reset()
            try:
                ring = make_ring(tmp_path / f"s{nd}")
                ring.make_bucket("b")
                names = keyspace_names(ring, "spread", total=4)
                rng = np.random.default_rng(9)
                et, gt = {}, {}
                for n in names:
                    body = rng.integers(
                        0, 256, (1 << 20) + 33,
                        dtype=np.uint8).tobytes()
                    et[n] = ring.put_object("b", n, body).etag
                    got = bytes(ring.get_object("b", n)[1])
                    assert got == body
                    gt[n] = hashlib.sha256(got).hexdigest()
                results[nd] = (et, gt)
            finally:
                coalesce.reset()
        assert results[1] == results[8]


# -- IPC descriptor ----------------------------------------------------------

class TestIpcDeviceIndex:
    def test_descriptor_roundtrips_device_and_fits_record(self):
        assert ipc._DESC.size <= REC
        rec = ipc._DESC.pack(ipc._MAGIC, 3, 77, 4096, 12345, 64,
                             ipc.ST_OK, 9, 5)
        (magic, wid, req, off, total, hdr, status, gen,
         dev) = ipc._DESC.unpack(rec)
        assert (magic, wid, req, dev) == (ipc._MAGIC, 3, 77, 5)
        assert (off, total, hdr, status, gen) == (4096, 12345, 64,
                                                  ipc.ST_OK, 9)

    def test_kernel_from_key_places_on_device(self, ndev):
        """The owner rebuilds an encode kernel FOR the descriptor's
        device; its output must match the default-device kernel bit for
        bit (the oracle contract, now per lane)."""
        ndev(8)
        key = ("enc", "fd", 2, 2, "mxh256", 128)
        x = np.random.default_rng(5).integers(
            0, 256, size=(2, 2, 128), dtype=np.uint8)
        co = coalesce.get()
        h5 = co.submit(key, x, ipc.kernel_from_key(key, device=5),
                       device=5)
        p5, d5 = h5.result(30.0)
        h0 = co.submit(key, x, ipc.kernel_from_key(key, device=None),
                       device=0)
        p0, d0 = h0.result(30.0)
        assert np.array_equal(np.asarray(p5), np.asarray(p0))
        assert np.array_equal(np.asarray(d5), np.asarray(d0))
        st = co.lane_stats()
        assert st[5]["dispatches"] >= 1 and st[0]["dispatches"] >= 1


# -- device-parallel heal sweep ----------------------------------------------

class _FakeSet:
    def __init__(self, i, dev):
        self.set_index = i
        self.device_idx = dev


class TestDeviceParallelHeal:
    def test_groups_overlap_across_devices(self, monkeypatch):
        """With 4 device groups, at least two heal jobs must be in
        flight at once (the sweep's whole point)."""
        monkeypatch.setenv("MTPU_HEAL_DEVICE_PARALLEL", "1")
        sets = [_FakeSet(i, i % 4) for i in range(8)]
        mu = threading.Lock()
        state = {"active": 0, "peak": 0}
        both = threading.Event()

        def job(es):
            with mu:
                state["active"] += 1
                state["peak"] = max(state["peak"], state["active"])
                if state["active"] >= 2:
                    both.set()
            both.wait(10.0)
            with mu:
                state["active"] -= 1
            return es.set_index

        res = heal_mod.sweep_sets_device_parallel(sets, job)
        assert res == {i: i for i in range(8)}
        assert state["peak"] >= 2

    def test_same_device_sets_stay_serial_within_group(self,
                                                       monkeypatch):
        monkeypatch.setenv("MTPU_HEAL_DEVICE_PARALLEL", "1")
        sets = [_FakeSet(i, 0) for i in range(4)]   # one group
        order = []

        def job(es):
            order.append(es.set_index)
            return es.set_index

        heal_mod.sweep_sets_device_parallel(sets, job)
        assert order == [0, 1, 2, 3]

    def test_serial_oracle_runs_on_caller_thread_in_order(self,
                                                          monkeypatch):
        monkeypatch.setenv("MTPU_HEAL_DEVICE_PARALLEL", "0")
        sets = [_FakeSet(i, i % 4) for i in range(8)]
        seen = []

        def job(es):
            seen.append((es.set_index,
                         threading.current_thread().name))
            return es.set_index

        res = heal_mod.sweep_sets_device_parallel(sets, job)
        assert res == {i: i for i in range(8)}
        assert [s for s, _ in seen] == list(range(8))
        assert len({t for _, t in seen}) == 1

    def test_group_exception_propagates_after_join(self, monkeypatch):
        monkeypatch.setenv("MTPU_HEAL_DEVICE_PARALLEL", "1")
        sets = [_FakeSet(i, i % 2) for i in range(4)]
        done = []

        def job(es):
            if es.device_idx == 1:
                raise RuntimeError("group 1 died")
            done.append(es.set_index)
            return es.set_index

        with pytest.raises(RuntimeError, match="group 1 died"):
            heal_mod.sweep_sets_device_parallel(sets, job)
        assert done == [0, 2]        # the healthy group still finished

    def test_parallel_converges_to_serial_end_state(self, tmp_path,
                                                    monkeypatch,
                                                    ndev):
        """Two identically damaged rings; the device-parallel sweep
        must leave exactly the serial sweep's end state."""
        ndev(8)
        rng = np.random.default_rng(21)
        objs = {}
        ring = make_ring(tmp_path / "a")
        ring.make_bucket("h")
        names = keyspace_names(ring, "spread", total=4, prefix="h")
        for n in names:
            objs[n] = rng.integers(0, 256, 300_000,
                                   dtype=np.uint8).tobytes()
            ring.put_object("h", n, objs[n])
        shutil.copytree(tmp_path / "a", tmp_path / "b")
        finals = {}
        for label, mode in (("serial", "0"), ("parallel", "1")):
            root = tmp_path / ("a" if label == "serial" else "b")
            for si in range(4):          # drive 0 of every set
                shutil.rmtree(root / f"d{si * 4}" / "h",
                              ignore_errors=True)
            monkeypatch.setenv("MTPU_HEAL_DEVICE_PARALLEL", mode)
            r = make_ring(root)
            r.heal_bucket("h")
            heal_mod.sweep_sets_device_parallel(
                r.sets,
                lambda es: heal_mod.heal_bucket_objects(es, "h"))
            finals[label] = {n: bytes(r.get_object("h", n)[1])
                             for n in objs}
        assert finals["serial"] == finals["parallel"]
        assert all(finals["serial"][n] == objs[n] for n in objs)


# -- boot self-test ----------------------------------------------------------

class TestDeviceSelfTest:
    def test_passes_on_every_configured_lane(self, ndev):
        from minio_tpu.ops import selftest
        ndev(8)
        selftest.device_lane_self_test()
        ndev(1)
        selftest.device_lane_self_test()

    def test_failure_names_the_device(self, ndev, monkeypatch):
        from minio_tpu.ops import fused, selftest
        ndev(8)
        real = fused.encode_and_hash

        def poisoned(x, k, m, algo="highwayhash256S", key=None,
                     device=None):
            if device == 3:
                raise RuntimeError("HBM parity error")
            return real(x, k, m, algo=algo, device=device)

        monkeypatch.setattr(fused, "encode_and_hash", poisoned)
        with pytest.raises(selftest.SelfTestError,
                           match="device 3"):
            selftest.device_lane_self_test()


# -- observability -----------------------------------------------------------

class TestLaneObservability:
    def test_lane_dispatches_reach_snapshot_and_gauges(self, ndev):
        ndev(8)
        before = DATA_PATH.snapshot()["lanes"].get(6,
                                                   {}).get("dispatches",
                                                           0)
        co = coalesce.get()
        co.submit(("obs",), np.ones(4, dtype=np.uint8),
                  sum_kernel(), device=6).result(5.0)
        snap = DATA_PATH.snapshot()["lanes"]
        assert snap[6]["dispatches"] == before + 1
        assert snap[6]["items"] >= 1
        text = MetricsRegistry().render()
        assert 'mtpu_device_lane_dispatches_total{device="6"}' in text
        assert 'mtpu_device_lane_occupancy{device="6"}' in text
        assert 'mtpu_device_lane_queue_wait_seconds_total{device="6"}' \
            in text

    def test_rows_padded_rows_and_d2h_grow_as_said(self, ndev):
        """One 1-row and one 32-row encode on a built ladder: 33 rows
        carried, 33 rows run, and the parity and digests of 33 rows
        fetched back; all three in the registry the boot self test
        walks."""
        from minio_tpu.ops import devcache, fused
        from minio_tpu.ops.selftest import metrics_registry_self_test
        ndev(1)
        k, m, s = 2, 2, 256
        fn = coalesce.make_encode_kernel(k, m, "mxh256", 32, 0)
        coalesce.build_ladder(fn, (k, s))
        coalesce.ladder_wait()
        try:
            before = dict(DATA_PATH.snapshot()["lanes"].get(0, {}))
            devcache.reset_h2d()
            co = coalesce.get()
            for n in (1, 32):
                co.submit(("enc", "fd", k, m, "mxh256", s),
                          np.ones((n, k, s), dtype=np.uint8),
                          fn).result(30.0)
            row = DATA_PATH.snapshot()["lanes"][0]
            assert row["rows"] - before.get("rows", 0) == 33
            assert row["padded_rows"] - before.get("padded_rows", 0) == 33
            assert devcache.h2d_stats()["d2h_bytes"] == \
                33 * m * s + (k + m) * 33 * 32
            assert devcache.h2d_stats()["h2d_bytes"] == 33 * k * s
        finally:
            fused.encode_hash_program(k, m, "mxh256")._built.clear()
        text = MetricsRegistry().render()
        assert f'mtpu_device_lane_rows_total{{lane="0"}} {row["rows"]}' \
            in text
        assert ('mtpu_device_lane_padded_rows_total{lane="0"} '
                f'{row["padded_rows"]}') in text
        assert f"mtpu_d2h_bytes_total {33 * m * s + (k + m) * 33 * 32}" \
            in text
        names = {f.name for f in MetricsRegistry().families()}
        assert {"mtpu_device_lane_rows_total",
                "mtpu_device_lane_padded_rows_total",
                "mtpu_d2h_bytes_total"} <= names
        metrics_registry_self_test()

    def test_dispatch_span_tagged_with_device(self, ndev):
        """The device index rides the lane's spans: `lane.dispatch`
        (here run inline, so nested under the request) and the
        request's `coalesce.wait`."""
        from minio_tpu.observe import span as ospan
        ndev(8)
        ospan.TRACER.configure(ring=8)
        try:
            x = np.ones((3, 4), dtype=np.uint8)
            with ospan.root_span("get") as root:
                h = coalesce.get().submit(("sum",), x, sum_kernel(),
                                          device=5)
                assert h.result() == 12
            by = {s.name: s for s in root.children}
            assert by["lane.dispatch"].tags["device"] == 5
            assert by["lane.dispatch"].tags["program"] == "sum"
            assert by["coalesce.wait"].tags["device"] == 5
        finally:
            ospan.TRACER.configure(ring=0)


# -- the ladder a deployment asks for at boot ---------------------------------

class TestBootLadder:
    @pytest.fixture
    def asked(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            coalesce, "build_geometry_ladder",
            lambda *a, padded_blocks: calls.append(a + (padded_blocks,)))
        return calls

    # want: (k, m, shard size, algo, pad blocks), then whether K leaves
    # the block padded (1 MiB / 3: yes, and its GETs take the generic
    # read, whose verify-only hash is then built ahead too).
    @pytest.mark.parametrize("use_device,parity,want", [
        (True, None, [((3, 1, 349526, "mxh256", 32), True)]),
        (True, 2, [((2, 2, 524288, "mxh256", 32), False)]),
        (False, None, []),
    ])
    def test_each_set_asks_for_its_geometry_on_its_lane(
            self, tmp_path, ndev, monkeypatch, asked, use_device, parity,
            want):
        from minio_tpu.engine import shardmath
        from minio_tpu.engine.pools import ServerPools
        ndev(8)
        monkeypatch.setattr(shardmath, "platform",
                            lambda: (use_device, False))
        pools = ServerPools([make_ring(tmp_path, nsets=3)])
        pools.build_ladders(parity)
        assert asked == [w + (lane, padded) for lane in range(3)
                         for w, padded in want]

    def test_server_asks_at_every_configured_parity(self, tmp_path,
                                                    asked, monkeypatch):
        """Boot and an admin `config set storage_class` both end in
        build_ladders(): the sets' default parity and the classes'."""
        from minio_tpu.engine import shardmath
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.server.server import S3Server
        from minio_tpu.server.sigv4 import Credentials
        monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
        pools = ServerPools([make_ring(tmp_path, nsets=1, parity=2)])
        srv = S3Server(pools, Credentials("minioadmin", "minioadmin"),
                       port=0)
        try:
            srv.build_ladders()
            assert [a[:2] for a in asked] == [(2, 2)]
            del asked[:]
            srv.handlers.config_sys.set("storage_class", "rrs", "EC:1")
            srv.build_ladders()
            assert sorted(a[:2] for a in asked) == [(2, 2), (3, 1)]
        finally:
            srv._httpd.server_close()

    def test_readiness_waits_for_the_boot_ladder_only(self, tmp_path,
                                                      monkeypatch):
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.server.server import S3Server
        from minio_tpu.server.sigv4 import Credentials
        srv = S3Server(ServerPools([make_ring(tmp_path, nsets=1)]),
                       Credentials("minioadmin", "minioadmin"), port=0)
        idle = {"v": False}
        monkeypatch.setattr(coalesce, "ladder_idle", lambda: idle["v"])

        def ready():
            return srv._dispatch_internal(None, "/minio/health/ready",
                                          {}).status
        try:
            assert ready() == 200          # nothing was asked for
            srv.build_ladders()            # a storage class set live
            assert ready() == 200
            srv.build_ladders(hold_ready=True)             # boot
            assert ready() == 503
            srv.build_ladders()            # ... does not end the wait
            assert ready() == 503
            idle["v"] = True
            assert ready() == 200
            idle["v"] = False              # a later build: not waited for
            assert ready() == 200
        finally:
            srv._httpd.server_close()

    def test_readiness_waits_while_boot_decides_what_to_ask_for(
            self, tmp_path, monkeypatch):
        """Boot's asks can take seconds before the first reaches the
        ladder's build thread (a host-hashed algorithm loads its native
        kernel to learn that its digests stay on the host): an idle
        build thread in that time is no ready ladder."""
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.server.server import S3Server
        from minio_tpu.server.sigv4 import Credentials
        srv = S3Server(ServerPools([make_ring(tmp_path, nsets=1)]),
                       Credentials("minioadmin", "minioadmin"), port=0)
        idle = {"v": True}
        monkeypatch.setattr(coalesce, "ladder_idle", lambda: idle["v"])
        seen = []

        def ready():
            return srv._dispatch_internal(None, "/minio/health/ready",
                                          {}).status

        def asking(parity=None, pools=None):
            seen.append(ready())        # nothing queued yet: still 503
            idle["v"] = False           # the ask reaches the thread

        monkeypatch.setattr(srv.pools, "build_ladders", asking)
        try:
            srv.build_ladders(hold_ready=True)
            assert seen == [503]
            assert ready() == 503
            idle["v"] = True
            assert ready() == 200
        finally:
            srv._httpd.server_close()

    def test_a_pool_worker_builds_nothing(self, tmp_path, monkeypatch):
        """A worker has adopted the owner's platform and holds no
        device: asking for a ladder there (an admin `config set` lands
        in a worker) queues nothing and looks no program up."""
        from minio_tpu.engine.pools import ServerPools
        from minio_tpu.ops import fused
        monkeypatch.setattr(devices, "_VISIBLE",
                            ([], "tpu", "TPU v5 lite", 1))
        monkeypatch.setattr(
            fused, "encode_hash_program",
            lambda *a: pytest.fail("a worker reached for a program"))
        pools = ServerPools([make_ring(tmp_path, nsets=1, parity=2)])
        assert pools.pools[0].sets[0].math.use_device
        pools.build_ladders()
        assert coalesce.ladder_idle() and not coalesce._BUILD_Q

    def test_pool_owner_asks_for_the_boot_geometry(self, asked,
                                                   monkeypatch):
        from minio_tpu.server import workers
        cfg = {"pool_paths": [[f"/d{i}" for i in range(8)]],
               "set_drive_count": 4}
        workers._owner_ladders(cfg)
        assert asked == []                  # shard math on the host
        monkeypatch.setattr(devices, "on_tpu", lambda: True)
        monkeypatch.setenv("MTPU_DEVICES", "2")
        workers._owner_ladders(cfg)
        assert asked == [(2, 2, 524288, "mxh256", 32, lane, False)
                         for lane in (0, 1)]


# -- keyspace placement (tools/loadgen) --------------------------------------

class TestKeyspace:
    def test_spread_fans_out_over_every_set(self, tmp_path):
        ring = make_ring(tmp_path)
        names = keyspace_names(ring, "spread", total=16)
        route = [ring.set_for(n).set_index for n in names]
        assert sorted(set(route)) == [0, 1, 2, 3]
        # interleaved round-robin: consecutive names walk the sets
        assert route[:4] == [0, 1, 2, 3]
        assert all(route.count(s) == 4 for s in range(4))

    def test_pinned_lands_on_set_zero_only(self, tmp_path):
        ring = make_ring(tmp_path)
        names = keyspace_names(ring, "pinned", total=8)
        assert len(names) == 8
        assert all(ring.set_for(n).set_index == 0 for n in names)

    def test_single_set_degrades_to_plain_names(self, tmp_path):
        from minio_tpu.engine.erasure_set import ErasureSet
        es = ErasureSet([LocalDrive(str(tmp_path / f"d{i}"))
                         for i in range(4)])
        assert keyspace_names(es, "spread", total=3) == \
            ["ks-0", "ks-1", "ks-2"]
