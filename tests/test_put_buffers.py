"""A PUT stream's large per-batch buffers are held, not allocated anew.

Two sites, one rule (PR 36): a batch's framed shards are written into
one of two buffers the request's thread keeps (engine/shardmath.py:
`_db_arena`, `Encoder.frames` -> `bitrot_io.frame_shard_views(out=)`),
and the ETag digest hashes the ingest ring's view where it lies, the
ring waiting for the digest lanes before it refills a slot
(utils/streams.py: `_pooled_chunks`, utils/digestlanes.py:
`wait_consumed`).  `mtpu_put_fresh_buffer_bytes_total` counts what was
allocated all the same.  Frames on disk and ETags are what they were.
"""

import hashlib
import threading
import time
import weakref

import numpy as np
import pytest

from minio_tpu.engine import shardmath
from minio_tpu.engine.erasure_set import BATCH_BLOCKS, BLOCK_SIZE, ErasureSet
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.engine.shardmath import ShardMath
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import bpool, fused
from minio_tpu.ops import devices as devices_mod
from minio_tpu.server.client import S3Client
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage import bitrot_io
from minio_tpu.storage.drive import LocalDrive
from minio_tpu.utils import digestlanes, streams

MIB = 1 << 20
GEOMETRIES = [(8, 4), (6, 6), (2, 2)]
GEOMETRY_IDS = ["8+4", "6+6", "2+2"]
# mxh256: the device program brings the digests; HighwayHash: the
# framing pass hashes on the host (bitrot_io.device_preferred).
ALGOS = ["mxh256", "highwayhash256S"]
ALGO_IDS = ["device-digests", "host-digests"]


def fresh() -> int:
    return DATA_PATH.snapshot()["put_fresh_buffer_bytes"]


def fresh_during(fn) -> int:
    before = fresh()
    fn()
    return fresh() - before


def eventually(cond, seconds: float = 30.0) -> bool:
    """Poll `cond` (a loaded host stalls a thread for seconds)."""
    deadline = time.monotonic() + seconds
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


def body_of(size: int, seed: int = 0) -> bytes:
    return np.random.default_rng([size, seed]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def blocks_of(nb: int, k: int, seed: int) -> np.ndarray:
    """(nb, K, S) as the engine cuts a body: zero-padded where K does
    not divide the block."""
    shard = -(-BLOCK_SIZE // k)
    out = np.zeros((nb, k * shard), dtype=np.uint8)
    out[:, :BLOCK_SIZE] = np.random.default_rng([nb, k, seed]).integers(
        0, 256, (nb, BLOCK_SIZE), dtype=np.uint8)
    return out.reshape(nb, k, shard)


def parity_and_digests(blocks, k, m, algo, with_digests):
    parity = np.asarray(ShardMath().native(k, m).encode_blocks(blocks))
    if not with_digests:
        return parity, None
    nb, _, shard = blocks.shape
    hs = bitrot_io.digest_size(algo)
    rows = np.concatenate([blocks, parity], axis=1)       # (nb, n, S)
    digests = bitrot_io._hash_batch(
        np.ascontiguousarray(rows).reshape(nb * (k + m), shard), algo)
    return parity, np.ascontiguousarray(
        digests.reshape(nb, k + m, hs).transpose(1, 0, 2))


def in_a_new_thread(fn):
    """Run `fn` on a thread of its own (it holds no framing buffers yet)
    and hand back what it returned or raised."""
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["err"] = e

    t = threading.Thread(target=run)
    t.start()
    t.join(300)
    assert not t.is_alive()
    if "err" in box:
        raise box["err"]
    return box["out"]


# -- frame_shard_views(out=) ---------------------------------------------------

@pytest.mark.parametrize("with_digests", [True, False],
                         ids=["digests-given", "digests-hashed"])
@pytest.mark.parametrize("k,m", GEOMETRIES, ids=GEOMETRY_IDS)
def test_frames_into_a_passed_buffer_equal_the_fresh_ones(k, m, with_digests):
    blocks = blocks_of(5, k, seed=1)
    parity, digests = parity_and_digests(blocks, k, m, "mxh256",
                                         with_digests)
    want = bitrot_io.frame_shard_views(blocks, parity, digests, "mxh256")
    frame = 32 + blocks.shape[2]
    out = np.full((k + m) * 5 * frame + 4096, 0xA5, dtype=np.uint8)
    got = bitrot_io.frame_shard_views(blocks, parity, digests, "mxh256",
                                      out=out)
    assert len(got) == k + m
    for g, w in zip(got, want):
        assert np.shares_memory(g, out) and not np.shares_memory(w, out)
        assert g.tobytes() == w.tobytes()
    assert (out[(k + m) * 5 * frame:] == 0xA5).all()     # head only
    # shard-major input (heal's shape) fills a passed buffer as well
    shards = np.concatenate([blocks, parity], axis=1).transpose(1, 0, 2)
    again = bitrot_io.frame_shard_views(None, None, digests, "mxh256",
                                        shards=shards, out=out)
    assert [a.tobytes() for a in again] == [w.tobytes() for w in want]


@pytest.mark.parametrize("bad", ["short", "2-d", "uint16", "strided"])
def test_a_buffer_that_cannot_hold_the_frames_is_refused(bad):
    blocks = blocks_of(2, 2, seed=2)
    parity, digests = parity_and_digests(blocks, 2, 2, "mxh256", True)
    need = 4 * 2 * (32 + blocks.shape[2])
    out = {"short": np.empty(need - 1, np.uint8),
           "2-d": np.empty((2, need), np.uint8),
           "uint16": np.empty(need, np.uint16),
           "strided": np.empty(2 * need, np.uint8)[::2]}[bad]
    with pytest.raises(ValueError, match="frame_shard_views out"):
        bitrot_io.frame_shard_views(blocks, parity, digests, "mxh256",
                                    out=out)


# -- Encoder: two buffers a thread, on the lane as on the host ------------------

@pytest.fixture
def lane(monkeypatch):
    """The device lane's path through `Encoder` on this host: the
    platform says TPU, the two device programs are stood in by the host
    codec and the host hash (the bytes are the same by construction of
    both), no coalescer between."""
    monkeypatch.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    monkeypatch.setattr(shardmath, "platform", lambda: (True, True))
    monkeypatch.setenv("MTPU_COALESCE", "0")
    monkeypatch.setenv("MTPU_MESH", "0")
    monkeypatch.setattr(devices_mod, "put", lambda x, idx: x)
    monkeypatch.setattr(
        fused, "encode_and_hash",
        lambda x, k, m, algo, device=None:
        parity_and_digests(x, k, m, algo, True))
    monkeypatch.setattr(ShardMath, "_codec",
                        lambda self, k, m: self.native(k, m))
    return ShardMath()


@pytest.mark.parametrize("algo", ALGOS, ids=ALGO_IDS)
@pytest.mark.parametrize("k,m", GEOMETRIES, ids=GEOMETRY_IDS)
def test_one_encoder_frames_32_then_7_then_32_blocks(lane, k, m, algo):
    """Shrinking and growing through one stream: every batch equals the
    freshly allocated frames, batch i is untouched by batch i+1 (two
    buffers), batch i+2 lands where batch i was, and only what grew was
    allocated."""
    def stream():
        enc = lane.encoder(k, m, algo)
        assert enc.fused_host is None and enc.overlaps
        frame = bitrot_io.digest_size(algo) + enc.shard_size
        seen, grew = [], []
        for i, nb in enumerate([32, 7, 32, 32]):
            blocks = blocks_of(nb, k, seed=i)
            before = fresh()
            views = enc.frames(enc.encode(blocks))
            grew.append(fresh() - before)
            parity, digests = parity_and_digests(
                blocks, k, m, algo, algo == "mxh256")
            want = [w.tobytes() for w in bitrot_io.frame_shard_views(
                blocks, parity, digests, algo)]
            assert [v.tobytes() for v in views] == want
            if seen:        # the batch before is still what it was
                assert [v.tobytes() for v in seen[-1][0]] == seen[-1][1]
            seen.append((views, want))
        n = k + m
        assert grew == [n * 32 * frame, n * 7 * frame, 0, n * 32 * frame]
        first, second, third, fourth = (s[0][0] for s in seen)
        assert np.shares_memory(first, third)
        assert not np.shares_memory(first, second)
        assert not np.shares_memory(third, fourth)
    in_a_new_thread(stream)


@pytest.mark.parametrize("plane", ["lane", "host"])
@pytest.mark.parametrize("k,m", GEOMETRIES, ids=GEOMETRY_IDS)
def test_a_one_block_put_holds_one_blocks_frames(request, monkeypatch, k, m,
                                                 plane):
    """Sized from what is framed, not from BATCH_BLOCKS: a thread's
    first 1-block stream allocates (and so can touch) one block's
    frames, in one buffer."""
    monkeypatch.setenv("MTPU_COALESCE", "0")    # the planes' direct pass
    sm = request.getfixturevalue("lane") if plane == "lane" else ShardMath()

    def stream():
        enc = sm.encoder(k, m, "mxh256")
        assert (enc.fused_host is None) == (plane == "lane")
        before = fresh()
        views = enc.frames(enc.encode(blocks_of(1, k, seed=9)))
        held = shardmath._DB_ARENAS.pair
        return fresh() - before, views, held, enc.frame_len

    grew, views, held, frame = in_a_new_thread(stream)
    assert grew == (k + m) * frame
    assert held[0].size == (k + m) * frame and held[1] is None
    assert sum(v.size for v in views) == held[0].size
    assert BATCH_BLOCKS * held[0].size > 16 * MIB       # what it is not


@pytest.mark.parametrize("plane", ["lane", "host"])
def test_counter_grows_on_a_streams_first_batches_only(
        request, monkeypatch, tmp_path, plane):
    """A streamed 3-batch PutObject, then another on the same thread:
    the first allocates its framing buffers, its third batch and the
    whole second stream allocate nothing."""
    monkeypatch.setenv("MTPU_COALESCE", "0")
    if plane == "lane":
        request.getfixturevalue("lane")
    es = ErasureSet([LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)],
                    2)
    es.make_bucket("b")
    size = 2 * BATCH_BLOCKS * BLOCK_SIZE + 3 * BLOCK_SIZE + 17
    src = body_of(size, seed=3)

    def two_streams():
        grew = []
        for key in ("one", "two"):
            before = fresh()
            fi = es.put_object("b", key, streams.BytesReader(src))
            grew.append(fresh() - before)
            assert fi.metadata["etag"] == hashlib.md5(src).hexdigest()
        return grew

    grew = in_a_new_thread(two_streams)
    frame = 32 + BLOCK_SIZE // 2
    # Two buffers on the lane, always; the fused host kernel's PutObject
    # writes batch i before it frames batch i + 1, into one.
    held = 2 if plane == "lane" else 1
    assert grew == [held * 4 * BATCH_BLOCKS * frame, 0]
    assert bytes(es.get_object("b", "two")[1]) == src


# -- through the server ----------------------------------------------------------

ACCESS, SECRET = "bufadmin", "bufadmin-secret-key"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("served")
    drives = [LocalDrive(str(root / f"d{i}")) for i in range(4)]
    srv = S3Server(ServerPools([ErasureSets(drives, set_drive_count=4)]),
                   Credentials(ACCESS, SECRET)).start()
    cli = S3Client(srv.endpoint, ACCESS, SECRET)
    cli.make_bucket("buf")
    yield cli
    srv.shutdown()


@pytest.mark.parametrize("native_digest", ["1", "0"],
                         ids=["digest-lanes", "hashlib-oracle"])
@pytest.mark.parametrize("what", ["3-batch-part", "10m-object"])
def test_bodies_read_back_exact_with_the_md5_etag(served, monkeypatch, what,
                                                  native_digest):
    monkeypatch.setenv("MTPU_NATIVE_DIGEST", native_digest)
    before = fresh()
    if what == "10m-object":
        src = body_of(10 * MIB, seed=5)
        etag = served.put_object("buf", "ten", src)["ETag"].strip('"')
        assert etag == hashlib.md5(src).hexdigest()
        assert served.get_object("buf", "ten") == src
    else:
        src = body_of(2 * BATCH_BLOCKS * BLOCK_SIZE + 5 * BLOCK_SIZE + 333,
                      seed=6)
        uid = served.create_multipart("buf", "mp")
        etag = served.upload_part("buf", "mp", uid, 1, src)
        assert etag == hashlib.md5(src).hexdigest()
        served.complete_multipart("buf", "mp", uid, [(1, etag)])
        assert served.get_object("buf", "mp") == src
    copied = fresh() - before
    if native_digest == "1":
        # framing buffers at most (a connection's first): no copy of
        # the body for the digest
        assert copied <= 2 * 4 * BATCH_BLOCKS * (32 + BLOCK_SIZE // 2)
    else:
        assert copied >= len(src)       # the oracle keeps its copy


# -- the digest's wait ------------------------------------------------------------

class HeldLanes:
    """native/digest_native with `md5_update_mb` held at a gate: the
    lane scheduler's tick blocks there, holding what it collected."""

    def __init__(self, dn):
        self._dn = dn
        self.gate = threading.Event()
        self.entered = threading.Event()

    def __getattr__(self, name):
        return getattr(self._dn, name)

    def md5_update_mb(self, states, chunks):
        self.entered.set()
        assert self.gate.wait(60)
        return self._dn.md5_update_mb(states, chunks)


@pytest.fixture
def held(monkeypatch):
    """A lane scheduler of the test's own whose ticks wait for
    `held.gate`, behind every PipelinedMD5 opened meanwhile; and a
    buffer pool of its own, to count leases in."""
    if not digestlanes.use_native():
        pytest.skip("native/digest.cc did not build")
    monkeypatch.setenv("MTPU_ZEROCOPY", "1")
    sched = digestlanes.LaneScheduler()
    lanes = sched._dn = HeldLanes(sched._dn)
    monkeypatch.setattr(digestlanes, "_SCHED", sched)
    pool = bpool.BufferPool(total_bytes=64 * MIB)
    monkeypatch.setattr(bpool, "_POOL", pool)
    lanes.sched, lanes.pool = sched, pool
    yield lanes
    lanes.gate.set()


CHUNK = 128 << 10


@pytest.mark.parametrize("chunks,tail", [
    (streams._RING_DEPTH + 1, 0), (3 * streams._RING_DEPTH, 0),
    (2 * streams._RING_DEPTH, 10), (2 * streams._RING_DEPTH, CHUNK - 1),
], ids=["depth+1", "3xdepth", "10-byte-tail", "chunk-1-tail"])
def test_ring_refills_no_slot_the_lanes_have_not_consumed(held, chunks, tail):
    """With the lanes held back the ring hands out every slot once and
    then waits, however deep it is; let go, every slot is refilled and
    the ETag is hashlib's."""
    src = body_of(chunks * CHUNK + tail, seed=chunks)
    pulled, box = [], {}

    def put():
        md5 = streams.PipelinedMD5()
        for chunk, _last in streams.batched_chunks(
                b"", streams.BytesReader(src), CHUNK, digest=md5):
            md5.update(chunk)
            pulled.append(len(chunk))
        box["etag"] = md5.hexdigest()

    t = threading.Thread(target=put)
    t.start()
    assert held.entered.wait(30)
    assert eventually(lambda: len(pulled) >= streams._RING_DEPTH)
    t.join(0.3)             # and no further: the next slot is not free
    assert t.is_alive()
    assert pulled == [CHUNK] * streams._RING_DEPTH, pulled
    held.gate.set()
    t.join(60)
    assert not t.is_alive()
    assert len(pulled) == chunks + 1 and sum(pulled) == len(src)
    assert box["etag"] == hashlib.md5(src).hexdigest()
    assert held.pool.stats()["in_use_bytes"] == 0
    assert not held.sched._streams


@pytest.mark.parametrize("lanes_held", [True, False],
                         ids=["lanes-held", "lanes-running"])
def test_an_abandoned_stream_frees_its_slots_and_its_row(held, lanes_held):
    """close() without hexdigest(), mid-body: the ring's leases go back
    once the lanes are done with what they were fed (not before), and
    the scheduler's row is free again."""
    if not lanes_held:
        held.gate.set()
    src = body_of(6 * CHUNK, seed=7)
    rows = len(held.sched._free)
    done = threading.Event()

    def put():
        md5 = streams.PipelinedMD5()
        ring = streams.batched_chunks(b"", streams.BytesReader(src), CHUNK,
                                      digest=md5)
        try:
            for n, (chunk, _last) in enumerate(ring):
                md5.update(chunk)
                if n == 2:
                    raise ConnectionResetError("the client went away")
        except ConnectionResetError:
            pass
        finally:
            md5.close()
            ring.close()
        done.set()

    t = threading.Thread(target=put)
    t.start()
    if lanes_held:
        assert held.entered.wait(30)
        # three slots are out and stay out: the lanes still read them
        assert eventually(
            lambda: held.pool.stats()["in_use_bytes"] == 3 * CHUNK)
        assert not done.wait(0.3)
        assert held.pool.stats()["in_use_bytes"] == 3 * CHUNK
        held.gate.set()
    assert done.wait(60)
    t.join(10)
    assert held.pool.stats()["in_use_bytes"] == 0
    assert held.sched.drain(10)
    assert len(held.sched._free) == rows and not held.sched._streams


def test_a_digest_nobody_lent_to_copies_a_writable_view(held):
    """Only the ring's promise to wait makes a writable view safe to
    hold: fed by anyone else, PipelinedMD5 stabilizes it (and counts
    the fresh buffer), so overwriting the view afterwards is harmless."""
    held.gate.set()
    src = bytearray(body_of(CHUNK, seed=8))
    want = hashlib.md5(src).hexdigest()
    md5 = streams.PipelinedMD5()
    copied = fresh_during(lambda: md5.update(memoryview(src)))
    src[:] = bytes(len(src))
    assert md5.hexdigest() == want
    assert copied == CHUNK
