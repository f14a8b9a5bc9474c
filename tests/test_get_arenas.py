"""A read's segment buffers are leased, not mapped (PR 41).

`engine/segarena.py`: `x`, `y` and the join of `_read_part` come from a
pool of arenas that a buffer goes back to when its last view dies.
Held here on the CPU backend: who keeps an arena leased, that nothing
is ever written under a live view, the cap, the counters.  No time.
"""

from __future__ import annotations

import gc
import mmap
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from minio_tpu.engine import segarena, shardmath
from minio_tpu.engine.erasure_set import (BLOCK_SIZE, ErasureSet,
                                          _join_range)
from minio_tpu.engine.segarena import SegmentArenas
from minio_tpu.observe.metrics import DATA_PATH, MetricsRegistry
from minio_tpu.ops import coalesce
from minio_tpu.storage.drive import LocalDrive

LEASE_SITES = ("gather", "assemble", "join")


def counters() -> tuple[dict, dict]:
    snap = DATA_PATH.snapshot()
    return snap["get_fresh_buffer_bytes"], snap["get_leased_buffer_bytes"]


def growth(fn) -> tuple[dict, dict]:
    """(fresh, leased) bytes `fn()` grew each leasing site by."""
    f0, l0 = counters()
    fn()
    f1, l1 = counters()
    return ({s: f1[s] - f0[s] for s in LEASE_SITES},
            {s: l1[s] - l0[s] for s in LEASE_SITES})


# -- the pool ------------------------------------------------------------------------

def test_a_lease_is_exactly_as_long_as_asked_and_its_arena_no_ndarray():
    pool = SegmentArenas()
    a = pool.lease(500_001, "join")
    assert a.shape == (500_001,) and a.dtype == np.uint8
    assert a.flags.writeable
    # numpy collapses a view's base to the first ndarray of the chain:
    # that has to be the lease, so the arena below it is none.
    assert not isinstance(a.base, np.ndarray)
    assert isinstance(a.base.obj, mmap.mmap)
    assert a[10:4000][5:100].reshape(5, 19).base is a
    assert memoryview(a[3:9]).obj.base is a


def test_under_the_mmap_threshold_an_array_is_allocated_not_leased():
    """A ranged read's few bytes never were a mapping, and an arena is
    whole pages: counted as allocated, kept by nobody."""
    pool = SegmentArenas()
    f, l = growth(lambda: pool.lease(segarena.MIN_LEASE_BYTES - 1, "join"))
    assert (f["join"], l["join"]) == (segarena.MIN_LEASE_BYTES - 1, 0)
    assert pool.lease(0, "join").size == 0 and pool.free_bytes() == 0
    a = pool.lease(segarena.MIN_LEASE_BYTES, "join")
    del a
    assert pool.free_bytes() == segarena.MIN_LEASE_BYTES


def test_a_lease_comes_back_when_its_last_view_is_dead():
    """The base, a slice of a slice and a memoryview of it: the arena
    is free only when all three are gone, in whatever order."""
    pool = SegmentArenas()
    n = 1 << 18
    base = pool.lease(n, "gather")
    base[:] = 7
    inner = base[100:5000][10:20]
    view = memoryview(base.reshape(512, 512)[3])
    del base
    assert pool.free_bytes() == 0
    other = pool.lease(n, "gather")             # has to be another arena
    other[:] = 9
    assert bytes(inner) == b"\x07" * 10 and bytes(view) == b"\x07" * 512
    del inner
    assert pool.free_bytes() == 0
    del view
    assert pool.free_bytes() == n
    del other
    assert pool.free_bytes() == 2 * n


def test_smallest_fit_and_never_an_arena_twice_the_size():
    pool = SegmentArenas()
    sizes = (200_000, 660_000, 2_000_000)
    held = [pool.lease(n, "join") for n in sizes]
    del held
    assert pool.free_bytes() == sum(sizes)
    f, l = growth(lambda: pool.lease(180_000, "join"))
    assert (f["join"], l["join"]) == (0, 180_000)       # the 200,000
    assert pool.free_bytes() == sum(sizes)
    a = pool.lease(400_000, "join")                     # the 660,000
    b = pool.lease(400_000, "join")     # 2,000,000 is over twice: a new one
    assert pool.free_bytes() == 2_200_000
    f, l = growth(lambda: pool.lease(150_000, "join"))  # so is 2,200,000
    assert (f["join"], l["join"]) == (0, 150_000)       # the 200,000
    c = pool.lease(150_000, "join")
    f, l = growth(lambda: pool.lease(150_000, "join"))
    assert (f["join"], l["join"]) == (150_000, 0)
    del a, b, c
    assert pool.free_bytes() == sum(sizes) + 400_000 + 150_000


def test_the_free_list_keeps_its_cap_and_lets_the_oldest_go():
    pool = SegmentArenas(cap_bytes=500_000)
    a, b, c = (pool.lease(200_000, "join") for _ in range(3))
    a[:], b[:], c[:] = 1, 2, 3
    del a
    del b
    assert pool.free_bytes() == 400_000
    del c                           # 600,000: a's arena, the oldest, goes
    assert pool.free_bytes() == 400_000
    got = [pool.lease(200_000, "join") for _ in range(2)]
    assert sorted(int(g[0]) for g in got) == [2, 3]     # nothing is zeroed
    big = pool.lease(800_000, "join")   # over the cap on its own
    del got
    del big
    assert pool.free_bytes() == 400_000     # the 800,000 was let go


def test_a_finalizer_that_finds_the_lock_held_leaves_the_arena_for_later():
    """A base can die on any thread at any point, the leasing thread
    inside `lease` included: the return takes no lock it could wait
    for."""
    pool = SegmentArenas()
    a = pool.lease(1 << 18, "join")
    with pool._mu:
        done = threading.Event()

        def drop(arr):
            del arr
            done.set()
        t = threading.Thread(target=drop, args=(a,))
        del a
        t.start()
        assert done.wait(10), "the finalizer waited for the pool's lock"
        t.join(10)
        gc.collect()
        assert pool._free_bytes == 0 and len(pool._returned) == 1
    assert pool.free_bytes() == 1 << 18


def test_eight_threads_never_share_a_live_arena():
    """More workers than cores, a short switch interval: every lease is
    filled with its owner's mark and still holds it when its owner lets
    go; the free list stays under its cap throughout."""
    pool = SegmentArenas(cap_bytes=4 << 20)
    sizes = (140_000, 270_000, 530_000)
    stop = time.monotonic() + 1.5
    over = []

    def worker(mark: int) -> int:
        rng = np.random.default_rng(mark)
        held, rounds = [], 0
        while time.monotonic() < stop:
            arr = pool.lease(int(rng.choice(sizes)), "join")
            arr[:] = mark
            held.append(arr[rng.integers(0, 100):])         # a view only
            del arr
            if len(held) > 3:
                old = held.pop(int(rng.integers(0, len(held))))
                assert (old == mark).all()
            if pool._free_bytes > pool.cap_bytes:
                over.append(pool._free_bytes)
            rounds += 1
        assert all((h == mark).all() for h in held)
        return rounds

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(16) as ex:
            rounds = [f.result(timeout=60)
                      for f in [ex.submit(worker, i + 1) for i in range(16)]]
    finally:
        sys.setswitchinterval(interval)
    assert min(rounds) > 0
    assert pool.free_bytes() <= pool.cap_bytes and not over


def test_a_forked_child_starts_with_an_empty_pool():
    n = 1 << 18
    a = segarena.lease(n, "join")
    del a
    assert segarena.POOL.free_bytes() == n
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:                                    # the child
        try:
            ok = segarena.POOL.free_bytes() == 0
            b = segarena.lease(n, "join")
            b[:] = 5
            del b
            ok = ok and segarena.POOL.free_bytes() == n
            os.write(w, b"1" if ok else b"0")
        finally:
            os._exit(0)
    os.close(w)
    try:
        assert os.read(r, 1) == b"1"
    finally:
        os.close(r)
        os.waitpid(pid, 0)


@pytest.mark.parametrize("seed", range(4))
def test_join_range_against_a_concatenation(seed):
    """Any range of (rows of a strided block view, then a tail), into a
    lease or into the caller's buffer."""
    rng = np.random.default_rng(seed)
    for _ in range(300):
        nb, w, pad = (int(rng.integers(0, 4)), int(rng.integers(1, 9)),
                      int(rng.integers(0, 3)))
        tl = int(rng.integers(0, 6))
        blocks = (rng.integers(0, 255, (nb, w + pad), dtype=np.uint8)[:, :w]
                  if nb else None)
        tail = rng.integers(0, 255, tl, dtype=np.uint8) if tl else None
        ref = np.concatenate([p.reshape(-1) for p in (blocks, tail)
                              if p is not None] or [np.zeros(0, np.uint8)])
        if not ref.size:
            assert _join_range(blocks, tail, 0, 0) == (b"", 0)
            continue
        lo = int(rng.integers(0, ref.size))
        length = int(rng.integers(1, ref.size - lo + 1))
        want = ref[lo:lo + length].tobytes()
        res, copied = _join_range(blocks, tail, lo, length)
        assert isinstance(res, memoryview) and bytes(res) == want
        assert copied in (0, length)
        dst = bytearray(length + 3)
        res, copied = _join_range(blocks, tail, lo, length, memoryview(dst))
        assert res is None and copied == length
        assert bytes(dst) == want + b"\0\0\0"


# -- the read -------------------------------------------------------------------------

BLOCKS = 2                  # a body of two one-block segments and a tail


@pytest.fixture(autouse=True)
def pool(monkeypatch):
    """A segment pool of the test's own, empty and at the cap the
    process's has."""
    monkeypatch.setattr(segarena, "POOL", SegmentArenas())
    return segarena.POOL


@pytest.fixture
def device_codec(monkeypatch):
    """The device codec on the CPU backend, through cold lanes."""
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.setenv("MTPU_DEVICES", "1")
    coalesce.reset()
    yield
    coalesce.reset()


def make_set(tmp_path, k: int, m: int, size: int, lose: int, objects=1,
             segment_blocks=1):
    """An EC:k+m set whose reads come in segments of `segment_blocks`,
    with `lose` data shards of every object unlinked."""
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(k + m)]
    es = ErasureSet(drives, default_parity=m)
    es.math.segment_blocks = lambda: segment_blocks
    es.make_bucket("b")
    bodies = []
    for i in range(objects):
        body = np.random.default_rng([41, k, m, i]).bytes(size)
        fi = es.put_object("b", f"o{i}", body)
        bodies.append(body)
    dist = fi.erasure.distribution
    for p in sorted(range(es.n), key=lambda p: dist[p])[:lose]:
        for dirpath, _, names in os.walk(os.path.join(drives[p].root, "b")):
            for n in names:
                if n.startswith("part."):
                    os.unlink(os.path.join(dirpath, n))
    return es, bodies


def get(es, name: str) -> bytes:
    _, it = es.get_object_iter("b", name)
    return b"".join(it)


def test_a_kept_chunk_is_never_written_again(device_codec, tmp_path):
    """At 3+3 a chunk is a view of the arena its join was copied into:
    twenty more GETs lease and return arenas all around it."""
    es, (body,) = make_set(tmp_path, 3, 3, 4 * BLOCK_SIZE + 999, lose=1,
                           segment_blocks=2)
    _, it = es.get_object_iter("b", "o0")
    kept = next(it)
    it.close()
    assert isinstance(kept, memoryview)
    assert isinstance(kept.obj.base.obj, mmap.mmap)
    with ThreadPoolExecutor(4) as ex:
        for f in [ex.submit(get, es, "o0") for _ in range(20)]:
            assert f.result(timeout=300) == body
    assert kept == body[:2 * BLOCK_SIZE]


@pytest.mark.parametrize("k,m", [(2, 2), (3, 3)], ids=["2+2", "3+3"])
def test_eight_threads_of_degraded_gets_under_a_small_cap(
        device_codec, tmp_path, monkeypatch, k, m):
    """The free list holds far less than the threads lease at once: what
    it cannot keep is unmapped and mapped again, and every byte is
    right."""
    es, bodies = make_set(tmp_path, k, m, BLOCKS * BLOCK_SIZE + 999,
                          lose=1, objects=4)
    monkeypatch.setattr(segarena.POOL, "cap_bytes", 3 * BLOCK_SIZE)
    assert get(es, "o0") == bodies[0]                       # compile
    over = []

    def client(c: int) -> int:
        for i in range(10):
            j = (c + i) % len(bodies)
            assert get(es, f"o{j}") == bodies[j]
            if segarena.POOL._free_bytes > segarena.POOL.cap_bytes:
                over.append(segarena.POOL._free_bytes)
        return c
    with ThreadPoolExecutor(8) as ex:
        assert [f.result(timeout=300) for f in
                [ex.submit(client, c) for c in range(8)]] == list(range(8))
    gc.collect()
    assert 0 < segarena.POOL.free_bytes() <= 3 * BLOCK_SIZE
    assert not over, max(over)


def test_an_iterator_closed_early_returns_every_arena(device_codec,
                                                      tmp_path):
    es, (body,) = make_set(tmp_path, 3, 3, 4 * BLOCK_SIZE, lose=1)
    assert get(es, "o0") == body
    gc.collect()
    level = segarena.POOL.free_bytes()
    assert level > 0
    gauge = "mtpu_get_arena_free_bytes "

    def rendered() -> float:
        (line,) = [ln for ln in MetricsRegistry().render().splitlines()
                   if ln.startswith(gauge)]
        return float(line[len(gauge):])
    assert rendered() == pytest.approx(level, rel=1e-5)

    def first_chunk_only():
        _, it = es.get_object_iter("b", "o0")
        chunk = next(it)
        assert chunk == body[:BLOCK_SIZE]
        assert segarena.POOL.free_bytes() < level       # leased out
        it.close()
    f, _ = growth(first_chunk_only)
    gc.collect()
    # Everything is back: what was free before, and whatever the
    # segments in flight had to map beside it.
    assert segarena.POOL.free_bytes() == level + sum(f.values())
    assert rendered() == pytest.approx(level + sum(f.values()), rel=1e-5)


def test_the_join_at_3_3_is_a_view_of_a_leased_arena(device_codec, tmp_path):
    """K does not divide the block: the rows' zero pad is cut out by one
    strided copy into an arena, and what comes back is a memoryview."""
    es, (body,) = make_set(tmp_path, 3, 3, 2 * BLOCK_SIZE, lose=1)
    fi = es.head_object("b", "o0")
    got = []
    f, l = growth(lambda: got.append(es._read_part(
        "b", "o0", fi, part_number=1, offset=0, length=2 * BLOCK_SIZE,
        healthy=False)))
    (res,) = got
    assert isinstance(res, memoryview) and not res.readonly
    assert isinstance(res.obj, np.ndarray)
    assert isinstance(res.obj.base.obj, mmap.mmap)
    assert res == body and res.nbytes == 2 * BLOCK_SIZE
    assert f["join"] + l["join"] == 2 * BLOCK_SIZE
    # A ranged read copies its range alone.
    part = es._read_part("b", "o0", fi, part_number=1, offset=BLOCK_SIZE - 5,
                         length=11, healthy=False)
    assert isinstance(part, memoryview) and part.nbytes == 11
    assert part == body[BLOCK_SIZE - 5:BLOCK_SIZE + 6]


@pytest.mark.parametrize("k,m", [(2, 2), (3, 3)], ids=["2+2", "3+3"])
def test_the_second_degraded_get_maps_nothing(device_codec, tmp_path, k, m):
    """Whatever the first GET of a geometry had to map, or could lease
    already (the join in the arena its own `x` had just left), the
    second leases: the fresh counters stand still."""
    es, (body,) = make_set(tmp_path, k, m, 2 * BLOCK_SIZE, lose=1,
                           segment_blocks=2)
    shard = es.head_object("b", "o0").erasure.shard_size

    def read():
        assert get(es, "o0") == body
        gc.collect()
    f1, l1 = growth(read)
    assert (f1["gather"], f1["assemble"]) == (2 * k * shard, 2 * k * shard)
    if k == 2:          # K divides the block: the chunk is a view of `y`
        assert f1["join"] == 0 and sum(l1.values()) == 0
    else:
        assert f1["join"] + l1["join"] == 2 * BLOCK_SIZE
    f2, l2 = growth(read)
    assert f2 == dict.fromkeys(LEASE_SITES, 0)
    assert l2 == {s: f1[s] + l1[s] for s in LEASE_SITES}
    text = MetricsRegistry().render()
    for site in LEASE_SITES:
        (line,) = [ln for ln in text.splitlines() if ln.startswith(
            f'mtpu_get_leased_buffer_bytes_total{{site="{site}"}} ')]
        assert float(line.split()[-1]) == pytest.approx(
            counters()[1][site], rel=1e-5)
