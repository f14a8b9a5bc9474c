"""One decode program a geometry (PR 35), and EC:6+6 on 12 drives served.

The rows a read has to rebuild reach `fused.verify_transform_program` as a
matrix operand of fixed shape, so every (sources, targets) of a geometry
runs one executable.  Held here against the plain reference
(`benchmark/reference.py`: numpy GF(2^8) and mxh256, independent of the
program) at a shard size that is no multiple of 128: every pattern of 2+2,
a seeded sample of 8+4 and of 6+6.  Then the deployment `ec6p6-12drive` at
a small size through the S3 front door, the device codec forced onto the
CPU backend: shard files against the reference, GETs with shard files
removed, the write quorum of 7, and what the new counters count.
"""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np
import pytest

from minio_tpu.engine import shardmath
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import coalesce, fused
from minio_tpu.server.client import S3Client, S3ClientError
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

ALGO = "mxh256"
S = 173                                 # shard bytes: no multiple of 128
B = 3                                   # blocks a batch


# -- the program against the reference -------------------------------------------

def every_pattern(k: int, m: int) -> list[tuple]:
    return [(src, tgt) for src in itertools.combinations(range(k + m), k)
            for r in range(1, m + 1)
            for tgt in itertools.combinations(
                [i for i in range(k + m) if i not in src], r)]


def sampled_patterns(k: int, m: int, n: int) -> list[tuple]:
    """T = 1, T = M, the sources with the most parity among them, then a
    seeded draw: any K rows in, any 1..M of the others out."""
    n_all = k + m
    out = [(tuple(range(1, k + 1)), (0,)),
           (tuple(range(m, n_all)), tuple(range(m))),
           (tuple(range(n_all - k, n_all)), (0,))]
    rng = np.random.default_rng([35, k, m])
    while len(out) < n:
        src = tuple(sorted(rng.choice(n_all, k, replace=False).tolist()))
        rest = [i for i in range(n_all) if i not in src]
        tgt = tuple(sorted(rng.choice(
            rest, int(rng.integers(1, m + 1)), replace=False).tolist()))
        if (src, tgt) not in out:
            out.append((src, tgt))
    return out


def stripes(k: int, m: int) -> np.ndarray:
    """(B, K+M, S) rows of B seeded blocks as the reference encodes them;
    a block is a byte short of K*S, so its last data row ends in pad."""
    rng = np.random.default_rng([k, m, S])
    return np.stack([reference.encode_block(rng.bytes(k * S - 1), k, m)
                     for _ in range(B)])


CASES = ([(2, 2, *p) for p in every_pattern(2, 2)]
         + [(8, 4, *p) for p in sampled_patterns(8, 4, 32)]
         + [(6, 6, *p) for p in sampled_patterns(6, 6, 32)])


def test_the_sample_holds_what_it_should():
    assert len(every_pattern(2, 2)) == 18
    for k, m in ((8, 4), (6, 6)):
        got = sampled_patterns(k, m, 32)
        assert len(set(got)) == 32
        assert {len(t) for _, t in got} >= {1, m}
    # 6+6: a read served by the six parity rows alone
    assert (tuple(range(6, 12)), (0,)) in sampled_patterns(6, 6, 32)


@pytest.mark.parametrize(
    "k,m,sources,targets", CASES,
    ids=[f"{k}+{m}-s{'.'.join(map(str, s))}-t{'.'.join(map(str, t))}"
         for k, m, s, t in CASES])
def test_decode_program_gives_the_reference_rows(k, m, sources, targets):
    full = stripes(k, m)
    x = np.ascontiguousarray(full[:, list(sources)])
    digests, rows = fused.verify_and_transform(x, k, m, sources, targets,
                                               algo=ALGO)
    assert len(rows) == len(targets)
    assert np.array_equal(fused.rows_on_host(rows), full[:, list(targets)])
    want = reference.mxh256_rows(x.reshape(B * k, S)).reshape(B, k, 32)
    assert np.array_equal(np.asarray(digests), want)


@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (6, 6)])
def test_matrix_operand_has_the_geometrys_shape(k, m):
    """Zero-padded from T to M target rows, plane-major: plane j of
    target t is row j*M + t, and rows past T are zero."""
    for sources, targets in sampled_patterns(k, m, 8) if k > 2 \
            else every_pattern(k, m):
        mat = fused.decode_matrix(k, m, sources, targets)
        assert mat.shape == (8 * m, 8 * k) and mat.dtype == "bfloat16"
        planes = np.asarray(mat, dtype=np.float32).reshape(8, m, 8 * k)
        assert not planes[:, len(targets):].any()
        assert planes[:, :len(targets)].any(axis=(0, 2)).all()
    with pytest.raises(ValueError):
        fused.decode_matrix(k, m, tuple(range(k)), tuple(range(m + 1)))


@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (6, 6)])
def test_patterns_of_a_geometry_run_one_executable(k, m):
    """The jit holds one entry however many patterns ran, and a name
    without a pattern in it."""
    full = stripes(k, m)
    prog = fused.verify_transform_program(k, m, (), (0,), ALGO)
    assert prog.name == f"verify_transform_k{k}m{m}_{ALGO}"
    pats = sampled_patterns(k, m, 6) if k > 2 else every_pattern(k, m)[:6]
    for sources, targets in pats:
        assert fused.verify_transform_program(
            k, m, sources, targets, ALGO) is prog
        fused.verify_and_transform(
            np.ascontiguousarray(full[:, list(sources)]), k, m, sources,
            targets, algo=ALGO)
    assert prog.jit._cache_size() == 1


def test_lane_builds_one_executable_for_every_pattern(monkeypatch):
    """Through the lanes: two patterns, two coalescer keys, one program
    object, one build a shape; the dispatch span of a decode says the
    one name and how many rows it rebuilt."""
    k, m = 6, 6
    full = stripes(k, m)
    coalesce.reset()
    prog = fused.verify_transform_program(k, m, (), (0,), ALGO)
    prog._built.clear()
    builds, build = [], fused.Program.build

    def spy(self, shape, device):
        if not self.built(shape, device):
            builds.append((self.name, tuple(shape)))
        return build(self, shape, device)

    monkeypatch.setattr(fused.Program, "build", spy)
    try:
        for sources, targets in sampled_patterns(k, m, 4):
            fn = coalesce.make_verify_kernel(k, m, sources, targets, ALGO,
                                             32, 0)
            assert fn.ladder and fn.program() is prog
            assert fn.span_tags == {"program": prog.name,
                                    "targets": len(targets)}
            x = np.ascontiguousarray(full[:, list(sources)])
            _, out = coalesce.get().submit(
                ("vt", k, m, sources, targets, ALGO, S), x, fn).result(60)
            assert len(out) == len(targets)
            for row, t in zip(out, targets):
                assert np.array_equal(row, full[:, t])
        assert builds == [(prog.name, (32, k, S))]
        assert prog.jit._cache_size() <= 1
    finally:
        coalesce.reset()
        prog._built.clear()


# -- ec6p6-12drive through the front door ------------------------------------------

ACCESS, SECRET = "sixsix", "sixsix-secret-key"
BUCKET = "bench"
N, K, M = 12, 6, 6
MIB = 1 << 20
BLOCKS = 3
SIZE = BLOCKS * MIB + 4321              # three full blocks and a tail
GONE = {"g1": (0,), "g3": (1, 4, 7), "g6": (0, 2, 4, 6, 8, 10)}


def body_of(key: str) -> bytes:
    seed = int.from_bytes(key.encode(), "little") % (2**31)
    return np.random.default_rng(seed).bytes(SIZE)


def part_paths(root: str, key: str) -> dict[int, str]:
    """{drive index: path of the object's part.1 there}."""
    out = {}
    for d in range(N):
        for dirpath, _, names in os.walk(
                os.path.join(root, f"d{d}", BUCKET, key)):
            if "part.1" in names:
                out[d] = os.path.join(dirpath, "part.1")
    return out


def counters() -> dict:
    snap = DATA_PATH.snapshot()
    out = {n: snap[n] for n in ("verify_blocks", "decode_blocks",
                                "decode_patterns", "stage_pad_bytes")}
    out["lane_dispatches"] = sum(row["dispatches"]
                                 for row in snap["lanes"].values())
    return out


def grown(before: dict) -> dict:
    return {n: v - before[n] for n, v in counters().items()}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One run of the deployment: 12 drives, one set, default parity
    (N/2 = 6), the device codec on the CPU backend.  A spare is read
    only when a shard file is not there (the hedge timer pinned far
    off), so which GET rebuilds rows is the test's choice."""
    root = str(tmp_path_factory.mktemp("ec6p6"))
    mp = pytest.MonkeyPatch()
    mp.setattr(shardmath, "platform", lambda: (True, False))
    mp.setenv("MTPU_HEDGE_MS", "60000")
    mp.delenv("MTPU_MESH", raising=False)
    coalesce.reset()
    drives = [LocalDrive(os.path.join(root, f"d{i}")) for i in range(N)]
    sets = ErasureSets(list(drives), set_drive_count=N)
    (es,) = sets.sets
    srv = S3Server(ServerPools([sets]), Credentials(ACCESS, SECRET)).start()
    out = {"root": root, "files": {}, "got": {}, "grew": {}}
    try:
        cli = S3Client(srv.endpoint, ACCESS, SECRET)
        cli.make_bucket(BUCKET)
        for key in ("whole", *GONE):
            c0 = counters()
            cli.put_object(BUCKET, key, body_of(key))
            out["grew"]["put " + key] = grown(c0)
            paths = part_paths(root, key)
            out["files"][key] = {d: open(p, "rb").read()
                                 for d, p in paths.items()}
            want = reference.shard_files(body_of(key), K, M)
            for shard in GONE.get(key, ()):
                (d,) = [d for d, f in out["files"][key].items()
                        if f == want[shard]]
                os.unlink(paths[d])
            c0 = counters()
            out["got"][key] = cli.get_object(BUCKET, key)
            out["grew"]["get " + key] = grown(c0)
        # Write quorum K + 1 = 7 where K = M: five drives gone leaves
        # seven, six gone leaves six.
        for gone, key in ((5, "q5"), (6, "q6")):
            for i in range(gone):
                es.drives[i] = None
            try:
                cli.put_object(BUCKET, key, body_of(key))
                out[key] = 200
                out["got"][key] = cli.get_object(BUCKET, key)
            except S3ClientError as e:
                out[key] = e.status
            finally:
                es.drives[:] = drives
        st, _, page = cli.request("GET", "/minio/v2/metrics/node")
        out["metrics_page"] = page.decode() if st == 200 else ""
    finally:
        srv.shutdown()
        coalesce.reset()
        mp.undo()
    return out


@pytest.mark.parametrize("key", ["whole", *GONE])
def test_put_lays_the_reference_shard_files_on_all_twelve_drives(served,
                                                                 key):
    files = served["files"][key]
    assert sorted(files) == list(range(N))
    res = reference.compare_part(body_of(key), K, M, list(files.values()))
    assert res == {"frames": N * (BLOCKS + 1), "bad_bytes": 0,
                   "bad_digest": 0, "shards_missing": 0}


@pytest.mark.parametrize("key", ["whole", *GONE])
def test_get_returns_the_bytes_with_shard_files_gone(served, key):
    """None, one, three and six of the twelve shard files removed (data
    shards among them; six: every data shard but one is rebuilt)."""
    assert served["got"][key] == body_of(key)


def test_write_quorum_is_seven(served):
    assert served["q5"] == 200 and served["got"]["q5"] == body_of("q5")
    assert served["q6"] == 503


@pytest.mark.parametrize("key", ["whole", *GONE])
def test_counters_say_which_reads_rebuilt_rows(served, key):
    """Every GET's full blocks are verified (K = 6 does not divide the
    block: the generic read); those of a GET that lost a data shard are
    rebuilt as well, each loss pattern counted once; every PUT's full
    blocks were copied into the padded layout.  Each was one dispatch
    on the set's lane: the device programs served, not the host's."""
    lost_data = any(s < K for s in GONE.get(key, ()))
    assert served["grew"]["get " + key] == {
        "verify_blocks": BLOCKS, "decode_blocks": BLOCKS * lost_data,
        "decode_patterns": int(lost_data), "stage_pad_bytes": 0,
        "lane_dispatches": 1}
    assert served["grew"]["put " + key] == {
        "verify_blocks": 0, "decode_blocks": 0, "decode_patterns": 0,
        "stage_pad_bytes": BLOCKS * MIB, "lane_dispatches": 1}


def test_metrics_page_carries_the_new_families(served):
    for family in ("mtpu_verify_blocks_total", "mtpu_decode_blocks_total",
                   "mtpu_decode_patterns_total",
                   "mtpu_stage_pad_bytes_total"):
        assert f"\n{family} " in served["metrics_page"], family
