"""MinIO's default bitrot deployment on the normal path: HighwayHash256S
at EC:8+4 (`benchmark/configs/ec8p4hh-12drive.json`).

The digests of a HighwayHash object are the host's work (its native kernel
beats the device form), so on a chip a degraded read or a heal hashes its K
rows on the calling thread while its rebuild rides the set's lane on the
geometry's one digest-free decode program, built in the boot ladder with the
digest-free encode.  Held here on the CPU backend, the device codec on
(`platform` adopted as a pool worker adopts its owner's), against the plain
reference (`benchmark/reference.py`: numpy GF(2^8) and HighwayHash-256 under
MinIO's key): every pair of hidden data shards, a corrupt row in a round,
a heal, the ladder that leaves nothing to compile, and what
`mtpu_host_hash_bytes_total` counts.
"""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np
import pytest

from minio_tpu.engine import heal, shardmath
from minio_tpu.engine.erasure_set import BLOCK_SIZE, ErasureSet
from minio_tpu.engine.shardmath import ShardMath
from minio_tpu.observe.metrics import DATA_PATH, MetricsRegistry
from minio_tpu.ops import coalesce
from minio_tpu.storage.drive import LocalDrive

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

ALGO = "highwayhash256S"
K, M = 8, 4
S = BLOCK_SIZE // K
NB = 3                                  # full blocks; a tail besides
SIZE = NB * BLOCK_SIZE + 4321
PAIRS = list(itertools.combinations(range(K), 2))


def lane_dispatches() -> int:
    return sum(row["dispatches"]
               for row in DATA_PATH.snapshot()["lanes"].values())


def host_hashed(site: str) -> int:
    return DATA_PATH.snapshot()["host_hash_bytes"][site]


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """One EC:8+4 set of 12 drives writing HighwayHash frames, its
    boot ladder built, one object of NB blocks and a tail PUT through
    it; every shard file held against the reference."""
    root = str(tmp_path_factory.mktemp("ec8p4hh"))
    mp = pytest.MonkeyPatch()
    mp.setattr(shardmath, "platform", lambda: (True, False))
    mp.setenv("MTPU_BITROT_ALGO", ALGO)
    mp.setenv("MTPU_DEVICES", "1")
    mp.setenv("MTPU_HOTCACHE", "0")         # every GET reads its shards
    mp.setenv("MTPU_DEVCACHE", "0")
    mp.delenv("MTPU_MESH", raising=False)
    mp.delenv("MTPU_COALESCE", raising=False)
    coalesce.reset()
    try:
        drives = [LocalDrive(os.path.join(root, f"d{i}"))
                  for i in range(K + M)]
        es = ErasureSet(drives, default_parity=M)
        es.make_bucket("b")
        compiles0 = DATA_PATH.snapshot()["jit_compiles"]
        es.math.build_ladder(K, M)
        coalesce.ladder_wait()
        built = DATA_PATH.snapshot()["jit_compiles"] - compiles0
        body = np.random.default_rng([46, K, M]).bytes(SIZE)
        put_hashed = host_hashed("put")
        fi = es.put_object("b", "o", body)
        dist = fi.erasure.distribution
        # drive position of shard s: dist[p] is p's shard, 1-based
        position = sorted(range(K + M), key=lambda p: dist[p])
        paths = {}
        for s, p in enumerate(position):
            for dirpath, _, names in os.walk(
                    os.path.join(drives[p].root, "b", "o")):
                if "part.1" in names:
                    paths[s] = os.path.join(dirpath, "part.1")
        golden = {s: open(p, "rb").read() for s, p in paths.items()}
        yield {"es": es, "body": body, "paths": paths, "golden": golden,
               "built": built,
               "put_compiles": DATA_PATH.snapshot()["jit_compiles"]
               - compiles0 - built,
               "put_hashed": host_hashed("put") - put_hashed}
    finally:
        coalesce.ladder_wait()
        coalesce.reset()
        mp.undo()


@pytest.fixture
def hidden(deployment):
    """`hide(*shards)` unlinks those shards' part.1 (xl.meta stays) until
    the test ends; then each is written back as it was, and every file
    is the golden one again."""
    gone = []

    def hide(*shards):
        for s in shards:
            os.unlink(deployment["paths"][s])
            gone.append(s)

    yield hide
    for s in gone:
        with open(deployment["paths"][s], "wb") as f:
            f.write(deployment["golden"][s])
    for s, p in deployment["paths"].items():
        with open(p, "rb") as f:
            assert f.read() == deployment["golden"][s]


@pytest.fixture
def seen(monkeypatch):
    """What the read submits to the lanes, what the seam hands back,
    and every direct `transform` call."""
    out = {"keys": [], "vt": [], "direct": 0}
    submit = coalesce.DispatchCoalescer.submit
    vt = ShardMath.verify_transform
    transform = ShardMath.transform

    def spy_submit(self, key, *a, **kw):
        out["keys"].append(key)
        return submit(self, key, *a, **kw)

    def spy_vt(self, x, k, m, sources, targets, algo, site="get"):
        got = vt(self, x, k, m, sources, targets, algo, site)
        out["vt"].append((np.array(x), sources, targets, got))
        return got

    def spy_transform(self, *a, **kw):
        out["direct"] += 1
        return transform(self, *a, **kw)

    monkeypatch.setattr(coalesce.DispatchCoalescer, "submit", spy_submit)
    monkeypatch.setattr(ShardMath, "verify_transform", spy_vt)
    monkeypatch.setattr(ShardMath, "transform", spy_transform)
    return out


def reference_digests(x: np.ndarray) -> np.ndarray:
    nb, k, s = x.shape
    return reference.highwayhash256_rows(
        np.ascontiguousarray(x).reshape(nb * k, s)).reshape(nb, k, 32)


def test_the_put_lays_the_reference_frames_and_compiles_nothing(
        deployment):
    """Shard files equal the reference's HighwayHash frames on all 12
    drives; the ladder built the digest-free encode and decode (six
    steps each), so the PUT's parity compiled nothing, and the host
    hashed its K+M rows of every full block."""
    golden = deployment["golden"]
    want = reference.shard_files(deployment["body"], K, M, ALGO)
    assert [golden[s] for s in range(K + M)] == want
    assert reference.compare_part(deployment["body"], K, M, want,
                                  ALGO)["bad_digest"] == 0
    assert deployment["built"] >= 2 * len(coalesce.LADDER)
    assert deployment["put_compiles"] == 0
    assert deployment["put_hashed"] == (K + M) * NB * S


@pytest.mark.parametrize("pair", PAIRS,
                         ids=[f"hide{a}{b}" for a, b in PAIRS])
def test_every_pair_of_data_shards_hidden_reads_back_through_the_lane(
        deployment, hidden, seen, pair):
    """Two data shards' files gone: the body exact, the K rows' digests
    the reference's, the rebuild one dispatch of the digest-free decode
    program on the lane (no direct `transform`), nothing compiled, and
    the host hashed K shard blocks of every full block read."""
    hidden(*pair)
    es = deployment["es"]
    d0, c0 = lane_dispatches(), DATA_PATH.snapshot()["jit_compiles"]
    h0 = host_hashed("get")
    _, got = es.get_object("b", "o")
    assert bytes(got) == deployment["body"]
    ((x, sources, targets, (digests, rows)),) = seen["vt"]
    assert targets == pair and len(rows) == 2
    assert np.array_equal(digests, reference_digests(x))
    assert [k[0] for k in seen["keys"]] == ["vt"]
    assert seen["keys"][0] == ("vt", K, M, sources, targets, None, S)
    assert seen["direct"] == 0
    assert lane_dispatches() - d0 == 1
    assert DATA_PATH.snapshot()["jit_compiles"] == c0
    assert host_hashed("get") - h0 == K * NB * S


LOSSES = [(s,) for s in range(K)] + [(0, 1, 2), (4, 5, 6, 7)]


@pytest.mark.parametrize("lost", LOSSES,
                         ids=["hide" + "".join(map(str, t)) for t in LOSSES])
def test_one_to_four_hidden_data_shards_compile_nothing(
        deployment, hidden, seen, lost):
    """T = 1, 3 and 4 run the same laddered program as T = 2: the
    matrix is an operand, so no read compiles anything."""
    hidden(*lost)
    c0 = DATA_PATH.snapshot()["jit_compiles"]
    _, got = deployment["es"].get_object("b", "o")
    assert bytes(got) == deployment["body"]
    ((_, _, targets, (_, rows)),) = seen["vt"]
    assert targets == lost and len(rows) == len(lost)
    assert seen["keys"][0][5] is None and seen["direct"] == 0
    assert DATA_PATH.snapshot()["jit_compiles"] == c0


def test_a_healthy_read_hashes_on_its_thread_and_sends_nothing(
        deployment, seen):
    es = deployment["es"]
    d0, h0 = lane_dispatches(), host_hashed("get")
    _, got = es.get_object("b", "o")
    assert bytes(got) == deployment["body"]
    assert seen["keys"] == [] and seen["vt"] == []
    assert lane_dispatches() == d0
    assert host_hashed("get") - h0 == K * NB * S


def test_a_flipped_byte_drops_its_row_and_a_spare_serves(deployment,
                                                         hidden, seen):
    """Shards 0 and 1 hidden and one byte of shard 2's second block
    flipped: the first round's digest of row 2 fails and its rebuild is
    thrown away, a spare parity row is read, and the second round
    rebuilds shards 0-2 from rows 3-10: the body exact."""
    hidden(0, 1)
    p = deployment["paths"][2]
    frame = 32 + S
    with open(p, "r+b") as f:
        f.seek(frame + 32 + 777)
        b = f.read(1)
        f.seek(frame + 32 + 777)
        f.write(bytes([b[0] ^ 0x5A]))
    try:
        d0 = lane_dispatches()
        _, got = deployment["es"].get_object("b", "o")
        assert bytes(got) == deployment["body"]
    finally:
        with open(p, "wb") as f:
            f.write(deployment["golden"][2])
    first, second = seen["vt"]
    assert (first[1], first[2]) == (tuple(range(2, 10)), (0, 1))
    assert (second[1], second[2]) == (tuple(range(3, 11)), (0, 1, 2))
    stored = np.frombuffer(deployment["golden"][2][frame:frame + 32],
                           np.uint8)
    assert not np.array_equal(first[3][0][1, 0], stored)
    for x, _, _, (digests, _) in (first, second):
        assert np.array_equal(digests, reference_digests(x))
    assert [k[5] for k in seen["keys"]] == [None, None]
    assert seen["direct"] == 0 and lane_dispatches() - d0 == 2


def test_a_heal_rebuilds_on_the_lane_and_writes_the_reference_frames(
        deployment, hidden, seen):
    """Data shards 3 and 6 and parity shard 9 gone: the heal rebuilds
    the three on the lane (targets parity too) and writes back the
    reference's frames; its K rows and the rows it framed hashed on the
    host at site heal."""
    hidden(3, 6, 9)
    h0 = host_hashed("heal")
    (res,) = heal.heal_object(deployment["es"], "b", "o")
    assert len(res.healed_drives) == 3
    for s in (3, 6, 9):
        with open(deployment["paths"][s], "rb") as f:
            assert f.read() == deployment["golden"][s]
    ((x, sources, targets, (digests, rows)),) = seen["vt"]
    assert targets == (3, 6, 9) and len(rows) == 3
    assert np.array_equal(digests, reference_digests(x))
    assert [k[0] for k in seen["keys"]] == ["vt"]
    assert seen["keys"][0][5] is None and seen["direct"] == 0
    assert host_hashed("heal") - h0 >= (K + 3) * NB * S


def test_the_scrape_carries_the_counter_by_site(deployment):
    page = MetricsRegistry().render()
    for site in ("get", "heal", "put"):
        assert f'\nmtpu_host_hash_bytes_total{{site="{site}"}} ' in page
