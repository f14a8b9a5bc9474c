"""A GET segment's shard rows come from one native read.

`storage/drive.read_rows` (native/ecio.cc `ec_read_rows`): where every
candidate drive is a LocalDrive of this process, no fused host pass
reads mmap views of the shards, the cache mode is not O_DIRECT and the
native library built, `_read_part` reads the K rows a round needs in ONE
call, into one buffer leased at site `read`.  Held here on the CPU
backend against the pool path (a drive call a row), which stays the
oracle.  On this CPU the mxh256 host plane reads mmap views, so the path
is forced with `highwayhash256S` or with the device plane faked.  No
time.
"""

from __future__ import annotations

import errno
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from minio_tpu.engine import erasure_set, segarena, shardmath
from minio_tpu.engine.erasure_set import BLOCK_SIZE, ErasureSet
from minio_tpu.engine.segarena import SegmentArenas
from minio_tpu.observe.metrics import DATA_PATH, MetricsRegistry
from minio_tpu.ops import coalesce
from minio_tpu.storage import diskio
from minio_tpu.storage.drive import LocalDrive
from minio_tpu.storage.health_wrap import HealthWrappedDrive
from minio_tpu.storage.naughty import NaughtyDrive

SIZE = 2 * BLOCK_SIZE + 4321            # two full blocks and a tail


@pytest.fixture(autouse=True)
def every_get_reads_its_shards(monkeypatch):
    """No device-resident cache between a GET and its shards, and a
    segment pool of the test's own."""
    monkeypatch.setenv("MTPU_DEVCACHE", "0")
    monkeypatch.setattr(segarena, "POOL", SegmentArenas())


@pytest.fixture
def hh(monkeypatch):
    """Objects framed with HighwayHash: no fused host pass reads them."""
    monkeypatch.setenv("MTPU_BITROT_ALGO", "highwayhash256S")


@pytest.fixture
def device_codec(monkeypatch):
    """The device codec on the CPU backend, through cold lanes."""
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.setenv("MTPU_DEVICES", "1")
    coalesce.reset()
    yield
    coalesce.reset()


@pytest.fixture
def native_calls(monkeypatch):
    """Each native call's outcome per drive: [(slot, error name), ...]."""
    calls = []
    real = erasure_set.read_rows

    def spy(*args, **kwargs):
        got = real(*args, **kwargs)
        calls.append([(j, type(e).__name__ if e else None)
                      for j, e, _ in got])
        return got
    monkeypatch.setattr(erasure_set, "read_rows", spy)
    return calls


def pool_path(monkeypatch):
    """Every read a drive call a row: the oracle."""
    monkeypatch.setattr(erasure_set, "rows_readable", lambda drives: False)


class ElsewhereDrive:
    """A drive another process serves, as the engine sees one: not a
    LocalDrive (an RPC client's shape), its calls answered all the
    same."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)


def make_set(tmp_path, k: int, m: int, size: int = SIZE, wrap=None,
             cls=LocalDrive):
    raw = [cls(str(tmp_path / f"d{i}")) for i in range(k + m)]
    drives = [wrap(d) for d in raw] if wrap else raw
    es = ErasureSet(drives, default_parity=m)
    es.make_bucket("b")
    body = np.random.default_rng([44, k, m, size]).bytes(size)
    fi = es.put_object("b", "o", body)
    return es, raw, fi, body


def shard_file(raw, fi, s: int) -> str:
    """The part file of shard `s` (0-based) of object `o`."""
    pos = fi.erasure.distribution.index(s + 1)
    return os.path.join(raw[pos].root, "b", "o", fi.data_dir, "part.1")


def rows_read() -> dict:
    return dict(DATA_PATH.snapshot()["shard_rows_read"])


def grew(before: dict) -> dict:
    now = rows_read()
    return {p: now[p] - before[p] for p in now}


def get(es, offset=0, length=-1) -> bytes:
    return bytes(es.get_object("b", "o", offset, length)[1])


def read_part(es, fi) -> bytes:
    """The part's shard reads alone: no xl.meta read beside them."""
    return bytes(es._read_part("b", "o", fi, part_number=1, offset=0,
                               length=fi.size))


# -- against the pool path -------------------------------------------------------

@pytest.mark.parametrize("k,m,lost", [
    (2, 2, 0), (2, 2, 1), (8, 4, 0), (8, 4, 1), (6, 6, 0), (6, 6, 1),
    (6, 6, 6)], ids=["2+2", "2+2-lost1", "8+4", "8+4-lost1", "6+6",
                     "6+6-lost1", "6+6-lost6"])
def test_bodies_equal_the_pool_paths(hh, native_calls, monkeypatch, tmp_path,
                                     k, m, lost):
    """Whole, ranged in the middle and ranged in the tail: one native call
    a GET, K rows, and the bytes the pool path reads."""
    es, raw, fi, body = make_set(tmp_path, k, m)
    assert fi.erasure.bitrot_algo(1) == "highwayhash256S"
    for s in range(lost):
        os.unlink(shard_file(raw, fi, s))
    ranges = [(0, -1), (BLOCK_SIZE - 7, 1000), (2 * BLOCK_SIZE + 100, 50)]
    before = rows_read()
    got = [get(es, *r) for r in ranges]
    assert got[0] == body
    assert got[1] == body[BLOCK_SIZE - 7:BLOCK_SIZE + 993]
    assert got[2] == body[2 * BLOCK_SIZE + 100:2 * BLOCK_SIZE + 150]
    assert grew(before) == {"batched": 3 * k, "pool": 0}
    assert len(native_calls) == 3
    for call in native_calls:
        assert [e for _, e in call[:lost]] == ["ErrFileNotFound"] * lost
        assert sorted(j for j, _ in call if j is not None) == list(range(k))
    pool_path(monkeypatch)
    before = rows_read()
    assert [get(es, *r) for r in ranges] == got
    assert grew(before)["batched"] == 0 and grew(before)["pool"] >= 3 * k


def test_the_device_plane_reads_batched(device_codec, native_calls, tmp_path):
    """mxh256 objects on the (faked) device plane: no mmap views, so the
    rows come from one native call and the decode program rebuilds."""
    es, raw, fi, body = make_set(tmp_path, 3, 3)
    assert es.math.host_fused(3, 3, "mxh256") is None
    os.unlink(shard_file(raw, fi, 1))
    before = rows_read()
    assert get(es) == body
    assert grew(before) == {"batched": 3, "pool": 0}
    assert len(native_calls) == 1


@pytest.mark.parametrize("k,m", [(2, 2), (6, 6)], ids=["2+2", "6+6"])
@pytest.mark.parametrize("fault,calls", [("short", 1), ("frame", 2),
                                         ("tail", 2)])
def test_a_bad_data_shard_is_covered_by_a_spare(hh, native_calls, tmp_path,
                                                k, m, fault, calls):
    """A file that ends early fails inside the call and the next spare
    takes its slot; a flipped byte in a full frame (its digest) or in
    the tail's (verified as it is parsed) drops the row, and the next
    round's call reads the spare."""
    es, raw, fi, body = make_set(tmp_path, k, m)
    victim = shard_file(raw, fi, 0)
    size = os.path.getsize(victim)
    if fault == "short":
        os.truncate(victim, size - 1000)
    else:
        with open(victim, "r+b") as f:
            f.seek(32 + 10 if fault == "frame" else size - 1)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
    before = rows_read()
    assert get(es) == body
    assert len(native_calls) == calls
    first = native_calls[0]
    if fault == "short":
        assert first[0] == (None, "ErrFileCorrupt")
        assert grew(before) == {"batched": k, "pool": 0}
    else:
        assert first[0] == (0, None)        # read in full; dropped after
        assert native_calls[1][0][1] is None     # the next spare
        assert grew(before)["batched"] == k + (fault == "frame")


# -- where the pool path stays ---------------------------------------------------

@pytest.mark.parametrize("plane", ["remote", "direct", "fused_host",
                                   "no_toolchain", "fault_injection"])
def test_the_pool_path_stays(hh, native_calls, monkeypatch, tmp_path, plane):
    """A drive of another process, O_DIRECT, the host's fused pass over
    mmap views, a host that cannot build the library, a drive whose
    reads are programmed: a drive call a row, as before (no hedge: a
    spare the timer launched would be a third row)."""
    monkeypatch.setenv("MTPU_HEDGE", "0")
    cls = NaughtyDrive if plane == "fault_injection" else LocalDrive
    if plane == "fused_host":
        monkeypatch.setenv("MTPU_BITROT_ALGO", "mxh256")
    es, raw, fi, body = make_set(
        tmp_path, 2, 2, cls=cls,
        wrap=ElsewhereDrive if plane == "remote" else None)
    if plane == "fused_host" and es.math.host_fused(2, 2, "mxh256") is None:
        pytest.skip("no native library here")
    if plane == "direct":
        monkeypatch.setenv("MTPU_ODIRECT", "direct")
    if plane == "no_toolchain":
        monkeypatch.setattr(diskio, "native_read_rows", lambda: None)
    before = rows_read()
    assert get(es) == body
    assert grew(before) == {"batched": 0, "pool": 2}
    assert native_calls == []


def test_an_open_circuit_is_left_out_and_the_rest_come_batched(
        hh, native_calls, monkeypatch, tmp_path):
    """An open circuit takes its drive out of the candidates before any
    read, as on the pool path; the others are read in one call."""
    monkeypatch.setenv("MTPU_BREAKER_PROBE_S", "30")
    es, raw, fi, body = make_set(tmp_path, 4, 2, wrap=HealthWrappedDrive)
    pos = fi.erasure.distribution.index(1)
    es.drives[pos]._state = "offline"
    reads_before = raw[pos]._osc.snapshot().get("read", {}).get("count", 0)
    before = rows_read()
    assert get(es) == body
    assert grew(before) == {"batched": 4, "pool": 0}
    assert len(native_calls) == 1 and len(native_calls[0]) == 5
    assert raw[pos]._osc.snapshot().get("read", {}).get("count", 0) == \
        reads_before
    assert "read_file" not in es.drives[pos].api_stats()


# -- what each row reaches -------------------------------------------------------

def test_each_row_reaches_the_counters_and_the_breaker(hh, monkeypatch,
                                                       tmp_path):
    """As a `read_file` through the health wrapper would: a missing file
    is a clean call, a directory where a file should be is a fault that
    walks the breaker, every drive tried counts a read, and a row that
    came feeds its position's read EWMA."""
    monkeypatch.setenv("MTPU_BREAKER_ERRS", "1")
    monkeypatch.setenv("MTPU_BREAKER_PROBE_S", "30")
    es, raw, fi, body = make_set(tmp_path, 2, 2, wrap=HealthWrappedDrive)
    gone, dir_ = (fi.erasure.distribution.index(s + 1) for s in (0, 1))
    os.unlink(shard_file(raw, fi, 0))
    victim = shard_file(raw, fi, 1)
    os.unlink(victim)
    os.mkdir(victim)
    reads = [d._osc.snapshot().get("read", {}).get("count", 0) for d in raw]
    assert read_part(es, fi) == body
    for p, d in enumerate(raw):
        assert d._osc.snapshot()["read"]["count"] == reads[p] + 1
    stats = es.drives[gone].api_stats()["read_file"]
    assert (stats["calls"], stats["errors"]) == (1, 0)
    assert es.drives[gone].health_state() == "ok"
    stats = es.drives[dir_].api_stats()["read_file"]
    assert (stats["calls"], stats["errors"]) == (1, 1)
    assert es.drives[dir_].health_state() == "suspect"
    assert "IsNotRegular" in es.drives[dir_].health_info()["last_fault"]
    parity = [fi.erasure.distribution.index(s + 1) for s in (2, 3)]
    assert all(es._read_ewma_ms[p] > 0 for p in parity)
    assert es._read_ewma_ms[gone] == es._read_ewma_ms[dir_] == 0.0


def test_a_local_drive_that_turns_slow_still_opens_its_circuit(
        hh, monkeypatch, tmp_path):
    """Every call over the latency bound: the drives read walk to
    SUSPECT after the configured number of slow calls."""
    es, raw, fi, body = make_set(tmp_path, 2, 2, wrap=HealthWrappedDrive)
    monkeypatch.setenv("MTPU_BREAKER_SLOW_MS", "0")
    monkeypatch.setenv("MTPU_BREAKER_SLOW_CALLS", "2")
    states = [fi.erasure.distribution.index(s + 1) for s in range(4)]
    assert read_part(es, fi) == body
    assert [es.drives[p].health_state() for p in states] == ["ok"] * 4
    assert read_part(es, fi) == body
    # The parity drives were not read.
    assert [es.drives[p].health_state() for p in states] == \
        ["suspect", "suspect", "ok", "ok"]
    data = states[:2]
    assert "read_file" in es.drives[data[0]].health_info()["last_fault"]


# -- the counters and the read site ----------------------------------------------

def test_rows_by_path_and_a_second_read_leases(hh, tmp_path):
    """The rows of a first GET are read into an arena the pool had to
    map; a second GET of the same size leases it back.  Both routes are
    rendered."""
    es, raw, fi, body = make_set(tmp_path, 8, 4)
    rows = 8 * (2 * (32 + fi.erasure.shard_size) + 32
                + -(-4321 // 8))

    def read_site() -> tuple[int, int]:
        snap = DATA_PATH.snapshot()
        return (snap["get_fresh_buffer_bytes"]["read"],
                snap["get_leased_buffer_bytes"]["read"])
    f0, l0 = read_site()
    assert get(es) == body
    f1, l1 = read_site()
    assert (f1 - f0, l1 - l0) == (rows, 0)
    assert get(es) == body
    f2, l2 = read_site()
    assert (f2 - f1, l2 - l1) == (0, rows)
    text = MetricsRegistry().render()
    for path, n in rows_read().items():
        (line,) = [ln for ln in text.splitlines() if ln.startswith(
            f'mtpu_shard_rows_read_total{{path="{path}"}} ')]
        assert float(line.split()[-1]) == pytest.approx(n, rel=1e-5)


def test_sixteen_threads_of_batched_gets(hh, tmp_path):
    """More readers than cores and a short switch interval, a data shard
    of every object gone: every body exact, every row batched."""
    es, raw, fi, body = make_set(tmp_path, 3, 3)
    os.unlink(shard_file(raw, fi, 2))
    before = rows_read()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(16) as ex:
            got = [f.result(timeout=120)
                   for f in [ex.submit(get, es) for _ in range(48)]]
    finally:
        sys.setswitchinterval(interval)
    assert all(g == body for g in got)
    assert grew(before) == {"batched": 48 * 3, "pool": 0}


# -- ec_read_rows alone ----------------------------------------------------------

@pytest.fixture
def ecio():
    from native import ecio_native
    from native._build import BuildError
    try:
        ecio_native.load()
    except BuildError:
        pytest.skip("no native library here")
    return ecio_native


def files(tmp_path, sizes) -> list[str]:
    out = []
    for i, n in enumerate(sizes):
        p = tmp_path / f"f{i}"
        if n is None:               # no such file
            pass
        elif n == "dir":
            p.mkdir()
        else:
            p.write_bytes(bytes([i + 1]) * n)
        out.append(str(p))
    return out


@pytest.mark.parametrize("sizes,k,exact_end,want_err,want_slot", [
    ([900, 900, 900, 900], 2, False, [0, 0, -2, -2], [0, 1, -1, -1]),
    ([None, 900, 900], 2, False, [errno.ENOENT, 0, 0], [-1, 0, 1]),
    ([500, 900, 900], 2, False, [-1, 0, 0], [-1, 0, 1]),
    (["dir", 900, 900], 1, False, [errno.EISDIR, 0, -2], [-1, 0, -1]),
    ([900, 800], 1, True, [-1, 0], [-1, 0]),
    ([900, 800], 2, False, [0, 0], [0, 1]),
    ([None, None, 900], 2, False, [errno.ENOENT, errno.ENOENT, 0],
     [-1, -1, 0]),
], ids=["stop-at-k", "enoent", "short", "eisdir", "exact-end-long",
        "long-without-exact-end", "fewer-than-k"])
def test_ec_read_rows(ecio, tmp_path, sizes, k, exact_end, want_err,
                      want_slot):
    """Candidates in order into the next free slot of a range at offset
    100 of 700 bytes, until k are filled."""
    paths = files(tmp_path, sizes)
    out = np.full(k * 700, 0xEE, dtype=np.uint8)
    err, slot, ns = ecio.read_rows(paths, 100, 700, k, out, exact_end,
                                   drop=True)
    assert err.tolist() == [ecio.ROW_UNTRIED if e == -2 else e
                            for e in want_err]
    assert slot.tolist() == want_slot
    for i, j in enumerate(want_slot):
        if j >= 0:
            assert (out[j * 700:(j + 1) * 700] == i + 1).all()
    filled = sum(j >= 0 for j in want_slot)
    assert (out[filled * 700:] == 0xEE).all()
    assert all((t > 0) == (e != ecio.ROW_UNTRIED)
               for t, e in zip(ns.tolist(), err.tolist()))


def test_ec_read_rows_refuses_a_buffer_too_small(ecio, tmp_path):
    paths = files(tmp_path, [900, 900])
    with pytest.raises(ValueError):
        ecio.read_rows(paths, 0, 700, 2, np.empty(1399, np.uint8), False,
                       False)
    with pytest.raises(ValueError):
        ecio.read_rows(paths, 0, 700, 3, np.empty(2100, np.uint8), False,
                       False)
