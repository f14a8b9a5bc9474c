"""The engine's one seam for a set's shard math (engine/shardmath.py).

The table: what the seam chooses, over platform x chips x sets x algorithm
x native HighwayHash x pool worker x MTPU_COALESCE / MTPU_MESH, read off
what it calls (every backend is stood in for, nothing is computed); what
the lane is doing is not an input.  The fallbacks: a failed coalescer
handle gives the direct function's bytes and counts one fallback, for
each operation, computed for real.  Concurrent GETs on the CPU plane:
every digest checked on the thread that holds its rows.
"""

from __future__ import annotations

import os
import threading
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from minio_tpu.engine import shardmath
from minio_tpu.engine.erasure_set import ErasureSet
from minio_tpu.engine.shardmath import BLOCK_SIZE, ShardMath
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import coalesce, fused
from minio_tpu.ops import devices as devices_mod
from minio_tpu.storage import bitrot_io
from minio_tpu.storage.drive import LocalDrive

K, M = 2, 2
S = BLOCK_SIZE // K
HOST, TPU, WORKER = (False, False), (True, True), (True, False)


class Handle:
    def __init__(self, res=None, exc=None):
        self.res, self.exc, self.released = res, exc, 0

    def result(self, timeout=None):
        if self.exc is not None:
            raise self.exc
        return self.res

    def release(self):
        self.released += 1


class Coalescer:
    """Stands in for ops/coalesce's scheduler (or a worker's remote front
    end): records what is submitted, answers with `make_handle`."""

    def __init__(self, make_handle=Handle):
        self.make_handle, self.seen = make_handle, []

    def submit(self, key, payload, fn, weight=None, device=0):
        self.seen.append({"key": key, "weight": weight, "device": device,
                          "rows": payload.shape[0], "fn": fn})
        return self.make_handle()


@pytest.fixture()
def host(monkeypatch):
    """A stood-in host: `host(platform, chips, sets, ...)` returns the
    ShardMath of the LAST of `sets` sets and the coalescer it will meet."""
    monkeypatch.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    keep = []

    def build(platform, chips=1, sets=1, hh_native=True, coalesced=True,
              mesh=""):
        monkeypatch.setattr(shardmath, "platform", lambda: platform)
        monkeypatch.setattr(
            devices_mod, "_VISIBLE",
            ([object()] * chips if platform[1] else [],
             "tpu" if platform[0] else "cpu", "stood-in", chips))
        monkeypatch.setenv("MTPU_DEVICES", str(chips))
        monkeypatch.setenv("MTPU_COALESCE", "1" if coalesced else "0")
        if mesh:
            monkeypatch.setenv("MTPU_MESH", mesh)
        else:
            monkeypatch.delenv("MTPU_MESH", raising=False)
        monkeypatch.setattr(bitrot_io, "_hh_native", lambda: hh_native)
        co = Coalescer()
        if platform == WORKER:
            # A pool worker: coalesce.get() answers its remote front end.
            monkeypatch.setattr(coalesce, "_REMOTE", co)
        else:
            monkeypatch.setattr(coalesce, "get", lambda: co)
        keep[:] = [ShardMath(i) for i in range(sets)]
        return keep[-1], co

    return build


@pytest.fixture()
def backends(monkeypatch):
    """Every backend the seam can reach, stood in for: the name of each
    one called is appended to the list this returns."""
    called = []

    def note(name, out):
        def fn(*a, **kw):
            called.append(name)
            return out
        return fn

    class Codec:
        def __init__(self, name):
            self.encode_blocks = note(name, "parity")
            self.transform_blocks = note(name, np.zeros((1, 1, S), np.uint8))

    class Ecio:
        put_frame = staticmethod(note("host_fused", ["frames"]))

    monkeypatch.setattr(shardmath, "ecio_mod", lambda: Ecio)
    monkeypatch.setattr(ShardMath, "_on_mesh",
                        note("mesh", np.zeros((1, 1, S), np.uint8)))
    monkeypatch.setattr(ShardMath, "_codec",
                        lambda self, k, m: Codec("device_codec"))
    monkeypatch.setattr(ShardMath, "native",
                        lambda self, k, m: Codec("native"))
    monkeypatch.setattr(devices_mod, "put", lambda x, idx: x)
    monkeypatch.setattr(fused, "encode_and_hash",
                        note("lane", ("parity", "digests")))
    monkeypatch.setattr(
        fused, "verify_and_transform",
        note("lane", (np.zeros((1, K, 32), np.uint8),
                      (np.zeros((1, S), np.uint8),))))
    for name in ("_hash_batch", "hash_rows"):
        monkeypatch.setattr(bitrot_io, name,
                            note("host_hash", np.zeros((K, 32), np.uint8)))
    return called


# platform, chips, sets, algo, native HighwayHash, MTPU_COALESCE, MTPU_MESH
#   -> encode: (plane, where the digests are computed, the coalescer key's
#      head or "direct"), verify_transform: its key's head or what it
#      calls; ("vt", "host_hash"): the digest-free rebuild rides the lane
#      while the calling thread hashes
CHOICES = [
    (HOST, 1, 1, "mxh256", True, True, "",
     ("host_fused", "kernel", ("pf",)), ["host_hash", "native"]),
    (HOST, 1, 1, "mxh256", True, False, "",
     ("host_fused", "kernel", "direct"), ["host_hash", "native"]),
    (HOST, 8, 1, "mxh256", True, True, "",      # the CPU's virtual eight
     ("host_fused", "kernel", ("pf",)), ["host_hash", "native"]),
    (HOST, 1, 1, "sha256", True, True, "",
     ("native", "framing", ("enc", "nat")), ["host_hash", "native"]),
    (HOST, 1, 1, "highwayhash256S", True, False, "",
     ("native", "framing", "direct"), ["host_hash", "native"]),
    (HOST, 4, 1, "mxh256", True, True, "1",     # forced: tests' SPMD path
     ("mesh", "framing", "direct"), ["host_hash", "mesh"]),
    (TPU, 1, 1, "mxh256", True, True, "",
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (TPU, 1, 1, "mxh256", True, False, "",
     ("lane", "device", "direct"), ["lane"]),
    (TPU, 4, 4, "mxh256", True, True, "",       # a set a chip: its lane
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (TPU, 4, 8, "mxh256", True, True, "",
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (TPU, 4, 1, "mxh256", True, True, "",       # chips sit by: the mesh
     ("mesh", "framing", "direct"), ["host_hash", "mesh"]),
    (TPU, 4, 2, "mxh256", True, True, "",
     ("mesh", "framing", "direct"), ["host_hash", "mesh"]),
    (TPU, 4, 1, "mxh256", True, True, "0",
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (TPU, 4, 4, "mxh256", True, True, "1",
     ("mesh", "framing", "direct"), ["host_hash", "mesh"]),
    (TPU, 4, 4, "highwayhash256S", True, True, "",   # host kernel wins
     ("device_codec", "framing", ("enc", "dev")), ("vt", "host_hash")),
    (TPU, 4, 4, "highwayhash256S", False, True, "",  # none: the device's
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (TPU, 1, 1, "sha256", True, True, "",
     ("device_codec", "framing", ("enc", "dev")), ("vt", "host_hash")),
    (TPU, 1, 1, "sha256", True, False, "",
     ("device_codec", "framing", "direct"), ["host_hash", "device_codec"]),
    (WORKER, 4, 1, "mxh256", True, True, "",    # holds no chip: no mesh
     ("lane", "device", ("enc", "fd")), ("vt",)),
    (WORKER, 4, 4, "highwayhash256S", True, True, "",
     ("device_codec", "framing", ("enc", "dev")), ("vt", "host_hash")),
    (WORKER, 1, 1, "mxh256", True, False, "",
     ("lane", "device", "direct"), ["lane"]),
]


@pytest.mark.parametrize(
    "platform,chips,sets,algo,hh_native,coalesced,mesh,encode,vt", CHOICES)
def test_the_seams_choice(host, backends, platform, chips, sets, algo,
                          hh_native, coalesced, mesh, encode, vt):
    sm, co = host(platform, chips, sets, hh_native, coalesced, mesh)
    lane = (sets - 1) % chips
    assert sm.device_idx == lane
    plane, hashes, how = encode
    blocks = np.zeros((3, K, S), np.uint8)
    enc0 = DATA_PATH.snapshot()["encode_blocks"]

    enc = sm.encoder(K, M, algo)
    _, handle, started = enc.encode(blocks)
    assert (enc.fused_host is not None) == (plane == "host_fused")
    assert enc.overlaps == (how != "direct" or plane != "host_fused")
    if how == "direct":
        assert handle is None and co.seen == []
        if plane == "host_fused":       # frames() runs the one native pass
            assert started is None and backends == []
            assert enc.frames((blocks, handle, started)) == ["frames"]
        else:                           # started, not waited for
            assert started[1] == ("digests" if hashes == "device" else None)
        assert backends == [plane]
    else:
        (sub,) = co.seen                # on this set's lane, by its blocks
        assert (sub["device"], sub["weight"], sub["rows"]) == (lane, 3, 3)
        assert sub["key"] == {
            ("pf",): ("pf", K, M, S),
            ("enc", "fd"): ("enc", "fd", K, M, algo, S),
            ("enc", "dev"): ("enc", "dev", K, M, algo, S),
            ("enc", "nat"): ("enc", "nat", K, M, algo, S)}[how]
        assert handle is not None and started is None and backends == []
        if how[0] == "enc":         # the kernel is placed where it is queued
            assert getattr(sub["fn"], "device", None) == \
                (None if how[1] == "nat" else lane)
    counted = {"host_fused": "host", "native": "host", "mesh": "mesh",
               "lane": "lane", "device_codec": "lane"}[plane]
    enc1 = DATA_PATH.snapshot()["encode_blocks"]
    assert {p: enc1[p] - enc0.get(p, 0) for p in enc1
            if enc1[p] != enc0.get(p, 0)} == {counted: 3}

    # verify + transform of a degraded read / a heal batch
    del backends[:], co.seen[:]
    host_hashed = vt == ("vt", "host_hash")
    co.make_handle = lambda: Handle((
        None if host_hashed else np.zeros((1, K, 32), np.uint8),
        (np.zeros((1, S), np.uint8),)))
    x = np.zeros((1, K, S), np.uint8)
    digests, rebuilt = sm.verify_transform(x, K, M, (1, 2), (0,), algo)
    assert digests.shape == (1, K, 32)
    assert [r.shape for r in rebuilt] == [(1, S)]
    if vt[0] == "vt":
        (sub,) = co.seen
        assert sub["key"] == ("vt", K, M, (1, 2), (0,),
                              None if host_hashed else algo, S)
        assert (sub["device"], sub["weight"], sub["fn"].device) == \
            (lane, 1, lane)
        assert backends == list(vt[1:])
    else:
        assert co.seen == [] and backends == vt

    # the capability the read path asks for, and the GET segment
    assert (sm.host_fused(K, M, algo) is not None) == (plane == "host_fused")
    assert (sm.host_fused(K, M) is not None) == (not platform[0])
    assert sm.host_fused(40, 30, "mxh256") is None      # 64 row pointers
    assert sm.segment_blocks() == (32 if platform[0] else 16)
    # a healthy GET's digest rides the lane only as a device program
    device_hashed = algo == "mxh256" or (
        algo.startswith("highwayhash") and not hh_native)
    assert sm.digest_rides(2, algo) is (
        coalesced and platform[0] and device_hashed)


@pytest.mark.parametrize("platform,algo,rides,key", [
    (TPU, "mxh256", True, ("digest", "mxh256", S, 32 * K)),
    (WORKER, "mxh256", True, ("digest", "mxh256", S, 32 * K)),
    (TPU, "sha256", False, None),               # the host hashes it
    (TPU, "highwayhash256S", False, None),      # its native kernel wins
    (WORKER, "highwayhash256S", False, None),
    (HOST, "mxh256", False, None),              # get_verify, not a digest
    (HOST, "sha256", False, None),
])
def test_healthy_get_digest_rides_or_not(host, backends, platform, algo,
                                         rides, key):
    sm, co = host(platform)
    co.make_handle = lambda: Handle(np.zeros((2 * K, 32), np.uint8))
    assert sm.digest_rides(2, algo) is rides
    assert sm.digest_rides(0, algo) is False
    y = np.zeros((2, K, S), np.uint8)
    digests = sm.digest(y, K, M, algo, rides)
    if rides:
        assert digests.shape == (2, K, 32)
        (sub,) = co.seen
        assert sub["key"] == key and sub["rows"] == 2 * K
        assert (sub["device"], sub["weight"]) == (0, 2)
    else:               # the host kernels hash the frames where they lie
        assert digests is None and co.seen == [] and backends == []


def test_digest_direct_on_the_lane_without_the_coalescer(host, backends):
    sm, co = host(TPU, coalesced=False)
    assert sm.digest_rides(1, "mxh256") is False
    assert sm.digest(np.zeros((1, K, S), np.uint8), K, M, "mxh256",
                     False).shape == (1, K, 32)
    assert backends == ["lane"] and co.seen == []


# What a busy lane looks like: the states in which the lane once took
# host-computed digests too.
LANE_STATES = {
    "items_pending": {"_pending_items": 3},
    "dispatching": {"_dispatching": True},
    "inline": {"_inline": 1},
    "packing": {"_ema": 5.0},
}


@pytest.mark.parametrize("state", list(LANE_STATES))
def test_a_host_digest_stays_on_its_thread_however_busy_the_lane(
        host, backends, monkeypatch, state):
    """Where a digest is computed is a function of platform, algorithm
    and MTPU_COALESCE: on a lane in any state a host-hashed digest is
    submitted nowhere (on a chip only its digest-free rebuild is), and
    an on-chip mxh256 digest still rides."""
    co = coalesce.DispatchCoalescer()
    seen = []

    def submit(key, payload, fn, weight=None, device=0):
        seen.append(key)
        if key[0] == "vt":
            return Handle((None, (np.zeros((payload.shape[0], S),
                                           np.uint8),)))
        return Handle(np.zeros((payload.shape[0], 32), np.uint8))

    monkeypatch.setattr(co, "submit", submit)
    try:
        for platform, algo in [(HOST, "mxh256"), (HOST, "sha256"),
                               (HOST, "highwayhash256S"), (TPU, "sha256"),
                               (TPU, "highwayhash256S"), (TPU, "mxh256")]:
            sm, _ = host(platform)
            monkeypatch.setattr(coalesce, "get", lambda: co)
            lane = co.lane(sm.device_idx)
            for name, value in LANE_STATES[state].items():
                setattr(lane, name, value)
            del seen[:]
            rides = sm.digest_rides(2, algo)
            digests = sm.digest(np.zeros((2, K, S), np.uint8), K, M, algo,
                                rides)
            if (platform, algo) == (TPU, "mxh256"):
                assert rides and digests.shape == (2, K, 32)
                assert seen == [("digest", "mxh256", S, 32 * K)]
                continue
            assert not rides and digests is None
            got, rebuilt = sm.verify_transform(
                np.zeros((1, K, S), np.uint8), K, M, (1, 2), (0,), algo)
            assert got.shape == (1, K, 32) and len(rebuilt) == 1
            assert seen == ([("vt", K, M, (1, 2), (0,), None, S)]
                            if platform == TPU else []), (platform, algo)
    finally:
        co.close()


def test_census_counts_lanes_that_own_a_live_set(host):
    """The rule's `sets` is read when asked, so it grows while
    engine/sets.py builds the sets (and a dropped set stops counting):
    a plane resolved at construction would say `mesh` for sets 0-2."""
    answers = []
    real = ShardMath.__init__

    def spy(self, set_index=0):
        real(self, set_index)
        answers.append(shardmath.mesh_mode())

    sm, _ = host(TPU, chips=4, sets=1)
    assert shardmath.mesh_mode() is True
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ShardMath, "__init__", spy)
        more = [ShardMath(i) for i in range(1, 4)]
    assert answers == [True, True, False]
    assert shardmath._chips_with_a_set() == 4
    del more
    assert shardmath.mesh_mode() is True and sm.device_idx == 0


# -- a failed handle: the direct function's bytes, one fallback counted -------

def _blocks(nb, seed):
    return np.random.default_rng(seed).integers(
        0, 256, (nb, K, S), dtype=np.uint8)


def _encode(sm, algo):
    enc = sm.encoder(K, M, algo)
    return [bytes(memoryview(np.ascontiguousarray(f)))
            for f in enc.frames(enc.encode(_blocks(2, 1)))]


def _digest(sm, algo):
    y = _blocks(2, 2)
    return sm.digest(y, K, M, algo, sm.digest_rides(2, algo)).tobytes()


def _verify_transform(sm, algo):
    digests, rebuilt = sm.verify_transform(_blocks(2, 3), K, M, (1, 2),
                                           (0,), algo)
    return digests.tobytes(), [r.tobytes() for r in rebuilt]


@pytest.mark.parametrize("op,platform,algo,kind", [
    (_encode, HOST, "mxh256", "pf"),
    (_encode, HOST, "sha256", "enc"),
    (_encode, WORKER, "mxh256", "enc"),     # the device codec on the CPU
    (_digest, WORKER, "mxh256", "digest"),
    (_digest, TPU, "mxh256", "digest"),     # the chip's owner
    (_verify_transform, WORKER, "mxh256", "vt"),
])
def test_failed_handle_gives_the_direct_bytes_and_counts_one_fallback(
        monkeypatch, op, platform, algo, kind):
    failed = []

    def broken():
        failed.append(Handle(exc=RuntimeError("poisoned batch")))
        return failed[-1]

    monkeypatch.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    monkeypatch.setattr(shardmath, "platform", lambda: platform)
    monkeypatch.setenv("MTPU_MESH", "0")
    sm = ShardMath(0)
    monkeypatch.setenv("MTPU_COALESCE", "0")
    want = op(sm, algo)                         # the direct function
    monkeypatch.setenv("MTPU_COALESCE", "1")
    co = Coalescer(broken)
    monkeypatch.setattr(coalesce, "get", lambda: co)
    before = DATA_PATH.snapshot()["co_fallbacks"]
    assert op(sm, algo) == want
    assert [s["key"][0] for s in co.seen] == [kind]
    assert DATA_PATH.snapshot()["co_fallbacks"] == before + 1
    assert failed[0].released == 0      # nothing of a failed handle's


def test_coalesced_put_frame_buffers_are_released_two_batches_later(
        monkeypatch):
    """A "pf" result aliases a pooled dispatch buffer that a pipelined
    consumer may still be writing: its handle is released only when two
    later batches have been handed out."""
    handles = []

    def ok():
        handles.append(Handle(["frames"]))
        return handles[-1]

    monkeypatch.setattr(shardmath, "platform", lambda: HOST)
    monkeypatch.setenv("MTPU_COALESCE", "1")
    co = Coalescer(ok)
    monkeypatch.setattr(coalesce, "get", lambda: co)
    enc = ShardMath(0).encoder(K, M, "mxh256")
    for i in range(4):
        assert enc.frames(enc.encode(_blocks(1, i))) == ["frames"]
        assert [h.released for h in handles] == \
            [1] * max(0, i - 1) + [0] * min(i + 1, 2)


# -- many GETs at once: every digest where its rows are ----------------------

NB = 2                          # full blocks an object; a tail besides


def _spy(monkeypatch, owner, name, count, calls):
    """Wrap `owner.name`: each call appends (digests it checked, the
    calling thread's name) to `calls`."""
    real = getattr(owner, name)

    def spy(*a, **kw):
        out = real(*a, **kw)
        calls.append((count(a, out), threading.current_thread().name))
        return out

    monkeypatch.setattr(owner, name, spy)


@pytest.mark.parametrize("lose", [0, 1], ids=["healthy", "hidden"])
@pytest.mark.parametrize("k,m", [(2, 2), (3, 3)], ids=["2+2", "3+3"])
@pytest.mark.parametrize("algo", ["highwayhash256S", "mxh256"])
def test_eight_threads_of_gets_check_every_digest_off_the_lane(
        tmp_path, monkeypatch, algo, k, m, lose):
    """Eight threads x 10 GETs of a set written under `algo` on the CPU
    plane, with `lose` data shards of every object unlinked: every body
    exact, every full block's K digests checked on a request's thread
    (HighwayHash by the host hash, mxh256 by `get_verify`), and not one
    digest or verify submitted to the coalescer."""
    monkeypatch.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    monkeypatch.setattr(shardmath, "platform", lambda: HOST)
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.setenv("MTPU_COALESCE", "1")
    monkeypatch.setenv("MTPU_BITROT_ALGO", algo)
    monkeypatch.setenv("MTPU_HOTCACHE", "0")      # every GET reads shards
    monkeypatch.setenv("MTPU_DEVCACHE", "0")
    coalesce.reset()
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(k + m)]
    es = ErasureSet(drives, default_parity=m)
    es.make_bucket("b")
    bodies = []
    for i in range(4):
        body = np.random.default_rng([43, k, m, i]).bytes(
            NB * BLOCK_SIZE + 999)
        fi = es.put_object("b", f"o{i}", body)
        bodies.append(body)
        dist = fi.erasure.distribution
        for p in sorted(range(es.n), key=lambda p: dist[p])[:lose]:
            for dirpath, _, names in os.walk(
                    os.path.join(drives[p].root, "b", f"o{i}")):
                for n in names:
                    if n.startswith("part."):
                        os.unlink(os.path.join(dirpath, n))

    submitted, calls = [], []
    real_submit = coalesce.DispatchCoalescer.submit

    def submit(self, key, *a, **kw):
        submitted.append(key)
        return real_submit(self, key, *a, **kw)

    monkeypatch.setattr(coalesce.DispatchCoalescer, "submit", submit)
    if algo == "mxh256":
        ecio = shardmath.ecio_mod()
        assert ecio is not None
        _spy(monkeypatch, ecio, "get_verify",
             lambda a, out: a[2] * len(a[1]), calls)
    else:
        _spy(monkeypatch, ShardMath, "verify_transform",
             lambda a, out: out[0].shape[0] * out[0].shape[1], calls)
        _spy(monkeypatch, ShardMath, "digest",
             lambda a, out: 0 if out is None else out.shape[0] * k, calls)
        _spy(monkeypatch, ErasureSet, "_hash_shard_frames",
             lambda a, out: sum(len(d) for d in out), calls)

    def client(c: int) -> int:
        for i in range(10):
            j = (c + i) % len(bodies)
            _, it = es.get_object_iter("b", f"o{j}")
            assert b"".join(it) == bodies[j]
        return c

    try:
        with ThreadPoolExecutor(8) as ex:
            assert [f.result(timeout=300) for f in
                    [ex.submit(client, c) for c in range(8)]] == \
                list(range(8))
    finally:
        coalesce.reset()
    assert submitted == []
    assert sum(n for n, _ in calls) >= 8 * 10 * NB * k
    assert not [t for _, t in calls if t.startswith("mtpu-coalesce")]
