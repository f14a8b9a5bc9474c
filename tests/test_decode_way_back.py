"""A decode's way back from the device (PR 39).

The rows a degraded read or a heal batch had rebuilt leave the dispatch
kernel as the T arrays the runtime filled, one a target, each member of
a packed batch given views of its own span: no (n, T, S) restack, and
the arrays' way back is begun at the launch.  Held here on the CPU
backend against the plain reference (`benchmark/reference.py`) at 2+2,
8+4 and 6+6: the kernel's results and what the boundary's counters say
of them, the seam (`ShardMath.verify_transform`) on every plane, a
degraded GET and a heal through it, and the pool's wire codec.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from minio_tpu.engine import heal, shardmath
from minio_tpu.engine.erasure_set import BLOCK_SIZE, ErasureSet
from minio_tpu.observe.metrics import MetricsRegistry
from minio_tpu.ops import coalesce, devcache, ipc_dispatch
from minio_tpu.storage.drive import LocalDrive

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

ALGO = "mxh256"
S = 173                                 # shard bytes: no multiple of 128
PAD = 32                                # rows a batch is padded to
GEOMETRIES = [(2, 2, 1), (8, 4, 1), (8, 4, 2), (8, 4, 4), (6, 6, 1),
              (6, 6, 6)]
IDS = [f"{k}+{m}-T{t}" for k, m, t in GEOMETRIES]
PLANES = ["lane", "direct", "host_hashed"]


def pattern(k: int, t: int) -> tuple[tuple, tuple]:
    """The first T data rows lost, read from the K rows after them."""
    return tuple(range(t, t + k)), tuple(range(t))


def stripes(k: int, m: int, blocks: int) -> np.ndarray:
    """(blocks, K+M, S) rows as the reference encodes seeded blocks."""
    rng = np.random.default_rng([39, k, m])
    return np.stack([reference.encode_block(rng.bytes(k * S - 1), k, m)
                     for _ in range(blocks)])


def way_back() -> dict:
    st = devcache.h2d_stats()
    return {n: st[n] for n in ("d2h_bytes", "d2h_fetches",
                               "d2h_early_starts", "result_copy_bytes")}


def grown(before: dict) -> dict:
    return {n: v - before[n] for n, v in way_back().items()}


@pytest.fixture
def fresh_lanes():
    coalesce.reset()
    devcache.reset_h2d()
    yield
    coalesce.reset()
    devcache.reset_h2d()


# -- the kernel ---------------------------------------------------------------------

@pytest.mark.parametrize("k,m,t", GEOMETRIES, ids=IDS)
def test_each_member_of_a_packed_batch_gets_its_own_span_of_t_arrays(
        fresh_lanes, k, m, t):
    """Two members packed (2 blocks and 1): each gets T arrays of
    (n, S) equal to the reference rows of its own blocks, views of the
    T arrays that came back; the boundary counts T rows + digests at
    the padded shape, as many early starts as fetches, and no copy."""
    sources, targets = pattern(k, t)
    full = stripes(k, m, 3)
    x = np.ascontiguousarray(full[:, list(sources)])
    fn = coalesce.make_verify_kernel(k, m, sources, targets, ALGO, PAD, 0)
    before = way_back()
    results = fn(x, [(0, 2), (2, 3)], None)
    assert grown(before) == {
        "d2h_bytes": PAD * (k * 32 + t * S), "d2h_fetches": t + 1,
        "d2h_early_starts": t + 1, "result_copy_bytes": 0}
    want_digests = reference.mxh256_rows(
        x.reshape(3 * k, S)).reshape(3, k, 32)
    for (lo, hi), (digests, rows) in zip([(0, 2), (2, 3)], results):
        assert np.array_equal(digests, want_digests[lo:hi])
        assert isinstance(rows, tuple) and len(rows) == t
        for row, target in zip(rows, targets):
            assert row.shape == (hi - lo, S) and row.base is not None
            assert np.array_equal(row, full[lo:hi, target])
    # One array a target, shared by the members: spans of one fetch.
    for a, b in zip(results[0][1], results[1][1]):
        assert np.may_share_memory(a.base, b.base)


@pytest.mark.parametrize("k,m,t", [(2, 2, 1), (6, 6, 6)],
                         ids=["2+2-T1", "6+6-T6"])
def test_a_lane_dispatch_begins_every_fetch_at_its_launch(fresh_lanes, k,
                                                          m, t):
    """Through the lane thread's pipelined path (launch, then resolve):
    every output fetched was asked for at the launch."""
    sources, targets = pattern(k, t)
    full = stripes(k, m, 2)
    x = np.ascontiguousarray(full[:, list(sources)])
    fn = coalesce.make_verify_kernel(k, m, sources, targets, ALGO, PAD, 0)
    co = coalesce.get()
    co._ema = 2.0                        # through the lane thread
    before = way_back()
    _, rows = co.submit(("vt", k, m, sources, targets, ALGO, S), x,
                        fn).result(60)
    assert co._thread is not None
    assert co.stats()["pipeline_dispatches"] == 1
    assert [np.array_equal(r, full[:, j]) for r, j in zip(rows, targets)] \
        == [True] * t
    assert grown(before) == {
        "d2h_bytes": PAD * (k * 32 + t * S), "d2h_fetches": t + 1,
        "d2h_early_starts": t + 1, "result_copy_bytes": 0}


def test_every_device_kernel_begins_its_fetch_and_copies_nothing(
        fresh_lanes):
    """The encode's parity and digests and the digest kernel's output
    take the same way back as the decode's rows."""
    k, m = 2, 2
    x = np.ascontiguousarray(stripes(k, m, 3)[:, :k])
    before = way_back()
    (parity, digests), = coalesce.make_encode_kernel(
        k, m, ALGO, PAD, 0)(x, [(0, 3)], None)
    assert np.array_equal(parity, stripes(k, m, 3)[:, k:])
    assert digests.shape == (k + m, 3, 32)
    (out,) = coalesce.make_digest_kernel(ALGO, PAD, 0)(
        x.reshape(3 * k, S), [(0, 3 * k)], None)
    assert np.array_equal(out, reference.mxh256_rows(x.reshape(3 * k, S)))
    assert grown(before) == {
        "d2h_bytes": PAD * (m * S + (k + m) * 32) + PAD * 32,
        "d2h_fetches": 3, "d2h_early_starts": 3, "result_copy_bytes": 0}


def test_a_resolve_that_copies_is_counted(fresh_lanes):
    """What the counter is for: a scatter that restacks (the shape this
    kernel gave before) reads as T x n x S bytes copied; views read 0."""
    import jax.numpy as jnp

    rows_d = tuple(jnp.full((PAD, S), j, jnp.uint8) for j in range(3))

    def restacking(x, spans):
        return rows_d, lambda *rows: [
            np.stack([r[lo:hi] for r in rows], axis=1) for lo, hi in spans]

    def viewing(x, spans):
        return rows_d, lambda *rows: [
            tuple(r[lo:hi] for r in rows) for lo, hi in spans]

    x = np.zeros((4, S), np.uint8)
    before = way_back()
    (out,) = coalesce._device_kernel(viewing, PAD, 0)(x, [(0, 4)], None)
    assert grown(before)["result_copy_bytes"] == 0
    (stacked,) = coalesce._device_kernel(restacking, PAD, 0)(
        x, [(0, 4)], None)
    assert np.array_equal(stacked, np.stack(out, axis=1))
    assert grown(before)["result_copy_bytes"] == 3 * 4 * S


def test_the_scrape_carries_the_three_families(fresh_lanes):
    k, m, t = 2, 2, 1
    sources, targets = pattern(k, t)
    x = np.ascontiguousarray(stripes(k, m, 1)[:, list(sources)])
    coalesce.make_verify_kernel(k, m, sources, targets, ALGO, PAD, 0)(
        x, [(0, 1)], None)
    page = MetricsRegistry().render()
    assert "\nmtpu_lane_result_copy_bytes_total 0\n" in page
    assert "\nmtpu_d2h_fetches_total 2\n" in page
    assert "\nmtpu_d2h_early_starts_total 2\n" in page


# -- the seam, on every plane -------------------------------------------------------

@pytest.fixture
def plane(request, monkeypatch, fresh_lanes):
    """`lane`: the set's coalesced device dispatch (the device codec on
    the CPU backend); `direct`: the same program with MTPU_COALESCE=0;
    `host_hashed`: an algorithm hashed on the host by the calling
    thread, the rows rebuilt on the lane by the digest-free decode
    program."""
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.setenv("MTPU_DEVICES", "1")
    algo = ALGO
    if request.param == "direct":
        monkeypatch.setenv("MTPU_COALESCE", "0")
    elif request.param == "host_hashed":
        algo = "highwayhash256S"
        monkeypatch.setenv("MTPU_BITROT_ALGO", algo)
    return request.param, algo


@pytest.mark.parametrize("plane", PLANES, indirect=True)
@pytest.mark.parametrize("k,m,t", GEOMETRIES, ids=IDS)
def test_verify_transform_gives_t_rows_on_every_plane(plane, k, m, t):
    name, algo = plane
    sources, targets = pattern(k, t)
    full = stripes(k, m, 3)
    x = np.ascontiguousarray(full[:, list(sources)])
    before = way_back()
    digests, rows = shardmath.ShardMath().verify_transform(
        x, k, m, sources, targets, algo)
    hashed = reference.ALGOS[algo](x.reshape(3 * k, S))
    assert np.array_equal(digests, hashed.reshape(3, k, 32))
    assert isinstance(rows, tuple) and len(rows) == t
    for row, target in zip(rows, targets):
        assert row.shape == (3, S)
        assert np.array_equal(row, full[:, target])
    grew = grown(before)
    assert grew["result_copy_bytes"] == 0
    if name == "lane":
        assert grew["d2h_early_starts"] == grew["d2h_fetches"] == t + 1
        assert grew["d2h_bytes"] == PAD * (k * 32 + t * S)
    elif name == "direct":      # the rows counted, at the batch's own shape
        assert grew["d2h_bytes"] == 3 * t * S
    # No targets: no rows, on any plane.
    assert shardmath.ShardMath().verify_transform(
        x, k, m, sources, (), algo)[1] is None


# -- a degraded GET and a heal through the seam -------------------------------------

SIZE = 2 * BLOCK_SIZE + 4321            # two full blocks and a tail


def data_drives(es, fi, k: int) -> list[int]:
    """Drive positions that hold data shards 0..K-1, in shard order."""
    dist = fi.erasure.distribution
    return sorted(range(es.n), key=lambda p: dist[p])[:k]


def part_files(es, bucket: str) -> list[dict]:
    out = []
    for d in es.drives:
        files = {}
        for dirpath, _, names in os.walk(os.path.join(d.root, bucket)):
            for n in names:
                if n.startswith("part."):
                    with open(os.path.join(dirpath, n), "rb") as f:
                        files[n] = f.read()
        out.append(files)
    return out


@pytest.mark.parametrize("plane", PLANES, indirect=True)
@pytest.mark.parametrize("k,m,t", GEOMETRIES, ids=IDS)
def test_degraded_get_and_heal_are_byte_exact(plane, tmp_path, k, m, t):
    """T data shards' files removed: the GET rebuilds them and serves
    the body; the heal rebuilds them and writes the files back as they
    were; nothing on the way copies a result on the lane."""
    name, algo = plane
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(k + m)]
    es = ErasureSet(drives, default_parity=m)
    es.make_bucket("b")
    body = np.random.default_rng([k, m, t]).bytes(SIZE)
    fi = es.put_object("b", "o", body)
    golden = part_files(es, "b")
    assert all(len(f) == 1 for f in golden)
    lost = data_drives(es, fi, k)[:t]
    for p in lost:
        for dirpath, _, names in os.walk(os.path.join(drives[p].root, "b")):
            for n in names:
                if n.startswith("part."):
                    os.unlink(os.path.join(dirpath, n))
    before = way_back()
    _, got = es.get_object("b", "o")
    assert bytes(got) == body
    (res,) = heal.heal_object(es, "b", "o")
    assert sorted(res.healed_drives) == sorted(lost)
    assert part_files(es, "b") == golden
    grew = grown(before)
    assert grew["result_copy_bytes"] == 0
    if name == "lane":
        assert grew["d2h_fetches"] == grew["d2h_early_starts"] > 0
    _, again = es.get_object("b", "o")
    assert bytes(again) == body


# -- the pool's wire codec ----------------------------------------------------------

@pytest.mark.parametrize("t", [0, 1, 6])
def test_wire_codec_round_trips_a_vt_result(t):
    rng = np.random.default_rng(t)
    digests = rng.integers(0, 256, (3, 6, 32), dtype=np.uint8)
    whole = [rng.integers(0, 256, (5, S), dtype=np.uint8) for _ in range(t)]
    res = (digests, tuple(r[1:4] for r in whole) if t else None)
    hdr, copies = ipc_dispatch._encode_arrays(
        ipc_dispatch._flatten_result("vt", res))
    view = np.frombuffer(hdr + b"".join(a.tobytes() for a in copies),
                         dtype=np.uint8)
    got_digests, got_rows = ipc_dispatch._rebuild_result(
        "vt", ipc_dispatch._decode_arrays(view, len(hdr)))
    assert np.array_equal(got_digests, digests)
    if not t:
        assert got_rows is None
        return
    assert isinstance(got_rows, tuple) and len(got_rows) == t
    for got, r in zip(got_rows, whole):
        assert np.array_equal(got, r[1:4])
    # A span of a fetched row is contiguous: framed without a copy.
    assert all(np.may_share_memory(c, w) for c, w in zip(copies[1:], whole))
