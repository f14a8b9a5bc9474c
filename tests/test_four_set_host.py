"""The four-chip host as deployed: 16 drives cut into four EC:2+2 sets
(`--set-drive-count 4`), set i on device lane i % 4, served through the
S3 front door (benchmark cell `ec2p2x4-10m-mixed-4chip`, at a small size
on the CPU backend's virtual devices, the device codec forced on).

Two references, both independent of the program: the placement is a plain
SipHash-2-4(key) mod sets written here; the bytes on the drives are
`benchmark/reference.py`'s (numpy Reed-Solomon, mxh256, frame layout).

The rule that picks the plane for the shard math (`shardmath.mesh_rule`)
is a pure function and is tested as one.  The served runs force each plane
in turn (MTPU_MESH=1, MTPU_MESH=0, one device) and then leave the choice to
the rule (`auto`: no MTPU_MESH, the seam's platform predicate answering "a
TPU this process holds", four chips visible): the shard files must be
byte-identical between all four.
"""

from __future__ import annotations

import os
import sys
import uuid
import weakref

import numpy as np
import pytest

from minio_tpu.engine import shardmath
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import coalesce
from minio_tpu.ops import devices as devices_mod
from minio_tpu.server.client import S3Client, S3ClientError
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

ACCESS, SECRET = "fourset", "fourset-secret-key"
DEP_ID = "6a1f0b52-3c5e-4d8a-9b7e-2f4c6d8e0a13"
BUCKET = "bench"
SETS, SET_DRIVES, K, M = 4, 4, 2, 2
MIB = 1 << 20
BLOCKS = 3                              # full 1 MiB blocks an object
SIZE = BLOCKS * MIB + 4321              # ... and a tail block
KEYS = [f"c{i % 4}/o-{i}" for i in range(10)]
PLANES = {                              # plane -> (environment, lanes)
    "mesh": ({"MTPU_MESH": "1", "MTPU_DEVICES": "4"}, 4),
    "lane": ({"MTPU_MESH": "0", "MTPU_DEVICES": "4"}, 4),
    "one_lane": ({"MTPU_MESH": "0", "MTPU_DEVICES": "1"}, 1),
    "auto": ({"MTPU_DEVICES": "4"}, 4),   # the rule decides: a set a lane
}


# -- the placement's plain reference -------------------------------------------

def _siphash24(key16: bytes, data: bytes) -> int:
    """SipHash-2-4 from the paper (Aumasson, Bernstein 2012), 64 bits."""
    mask = (1 << 64) - 1
    k0, k1 = (int.from_bytes(key16[i:i + 8], "little") for i in (0, 8))
    v = [k0 ^ 0x736F6D6570736575, k1 ^ 0x646F72616E646F6D,
         k0 ^ 0x6C7967656E657261, k1 ^ 0x7465646279746573]

    def rotl(x, b):
        return ((x << b) | (x >> (64 - b))) & mask

    def rounds(n):
        for _ in range(n):
            v[0] = (v[0] + v[1]) & mask
            v[2] = (v[2] + v[3]) & mask
            v[1] = rotl(v[1], 13) ^ v[0]
            v[3] = rotl(v[3], 16) ^ v[2]
            v[0] = rotl(v[0], 32)
            v[2] = (v[2] + v[1]) & mask
            v[0] = (v[0] + v[3]) & mask
            v[1] = rotl(v[1], 17) ^ v[2]
            v[3] = rotl(v[3], 21) ^ v[0]
            v[2] = rotl(v[2], 32)

    padded = data + bytes(-(len(data) + 1) % 8) + bytes([len(data) & 0xFF])
    for off in range(0, len(padded), 8):
        word = int.from_bytes(padded[off:off + 8], "little")
        v[3] ^= word
        rounds(2)
        v[0] ^= word
    v[2] ^= 0xFF
    rounds(4)
    return v[0] ^ v[1] ^ v[2] ^ v[3]


def ref_set(key: str) -> int:
    return _siphash24(uuid.UUID(DEP_ID).bytes, key.encode()) % SETS


def test_siphash_reference_vector():
    """The paper's test vector: key 00..0f, message 00..0e."""
    assert _siphash24(bytes(range(16)), bytes(range(15))) == \
        0xA129CA6149BE45E5


def body_of(key: str) -> bytes:
    seed = int.from_bytes(key.encode(), "little") % (2**31)
    return np.random.default_rng(seed).integers(
        0, 256, SIZE, dtype=np.uint8).tobytes()


# -- the rule --------------------------------------------------------------------

@pytest.mark.parametrize("local_tpu,chips,sets,forced,want", [
    (True, 4, 4, "", False),      # four sets on four chips: a set a lane
    (True, 4, 8, "", False),      # more sets than chips: still lanes
    (True, 4, 1, "", True),       # one set on four chips: the mesh
    (True, 4, 2, "", True),       # chips outnumber sets: the mesh
    (True, 1, 1, "", False),      # one chip: its lane
    (True, 4, 4, "1", True),      # MTPU_MESH forces, both ways
    (True, 4, 1, "0", False),
    (False, 4, 1, "", False),     # a pool worker holds no chip
    (False, 8, 1, "", False),     # a host backend (the CPU's virtual eight)
    (False, 1, 0, "1", True),
])
def test_mesh_rule(local_tpu, chips, sets, forced, want):
    assert shardmath.mesh_rule(local_tpu, chips, sets, forced) is want


@pytest.mark.parametrize("nsets,env,want", [
    (4, {}, False), (1, {}, True), (2, {}, True),
    (4, {"MTPU_DEVICES": "1"}, True),     # three chips would sit by
    (1, {"MTPU_MESH": "0"}, False), (4, {"MTPU_MESH": "1"}, True),
])
def test_mesh_mode_counts_chips_held_and_sets_served(
        tmp_path, monkeypatch, nsets, env, want):
    """`mesh_mode()` feeds the rule what this process observes: a TPU
    host with four chips (stood in for here), and its live sets."""
    monkeypatch.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    monkeypatch.setattr(devices_mod, "_VISIBLE",
                        ([object()] * 4, "tpu", "TPU v5 lite", 4))
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.delenv("MTPU_DEVICES", raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    ring = ErasureSets(
        [LocalDrive(str(tmp_path / f"d{i}")) for i in range(nsets * 4)],
        set_drive_count=4, default_parity=2, deployment_id=DEP_ID)
    assert len(ring.sets) == nsets
    assert shardmath.mesh_mode() is want


# -- the served runs ---------------------------------------------------------------

def lane_rows() -> dict[int, int]:
    return {int(d): row["dispatches"]
            for d, row in DATA_PATH.snapshot()["lanes"].items()}


def grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def part_files(root, key: str) -> dict[int, bytes]:
    """{drive index: the object's part.1 there}."""
    out = {}
    for d in range(SETS * SET_DRIVES):
        odir = os.path.join(root, f"d{d}", BUCKET, key)
        for dirpath, _, names in os.walk(odir):
            if "part.1" in names:
                with open(os.path.join(dirpath, "part.1"), "rb") as f:
                    out[d] = f.read()
    return out


def serve(root: str, plane: str) -> dict:
    """One served run on `plane`: every key PUT, STATed, read back (every
    second one with its first data shard file removed first), one in
    three deleted.  Returns what the tests below hold against the
    references."""
    env, lanes = PLANES[plane]
    mp = pytest.MonkeyPatch()
    # The device codec on the CPU backend; `auto` also says the chips are
    # this process's, four of them, and counts this run's sets only.
    mp.setattr(shardmath, "platform", lambda: (True, plane == "auto"))
    if plane == "auto":
        import jax
        mp.setattr(devices_mod, "_VISIBLE",
                   (list(jax.devices())[:4], "cpu", "cpu", 4))
        mp.setattr(shardmath, "_LOCAL_SETS", weakref.WeakSet())
    mp.delenv("MTPU_MESH", raising=False)
    for name, value in env.items():
        mp.setenv(name, value)
    coalesce.reset()
    drives = [LocalDrive(os.path.join(root, f"d{i}"))
              for i in range(SETS * SET_DRIVES)]
    pools = ServerPools([ErasureSets(
        drives, set_drive_count=SET_DRIVES, default_parity=M,
        deployment_id=DEP_ID)])
    srv = S3Server(pools, Credentials(ACCESS, SECRET)).start()
    out = {"plane": plane, "lanes": lanes, "files": {}, "got": {},
           "stat": {}, "put_lanes": {}, "get_lanes": {}, "gone": {},
           # what the seam answers for each set once all four are built
           "chose": [("mesh" if shardmath.mesh_mode() else "lane",
                      es.math.device_idx) for es in pools.pools[0].sets]}
    try:
        cli = S3Client(srv.endpoint, ACCESS, SECRET)
        cli.make_bucket(BUCKET)
        enc0 = DATA_PATH.snapshot()["encode_blocks"]
        for key in KEYS:
            before = lane_rows()
            cli.put_object(BUCKET, key, body_of(key))
            out["put_lanes"][key] = grown(before, lane_rows())
        out["encode_blocks"] = grown(enc0,
                                     DATA_PATH.snapshot()["encode_blocks"])
        for i, key in enumerate(KEYS):
            out["files"][key] = files = part_files(root, key)
            out["stat"][key] = int(cli.head_object(BUCKET, key)
                                   ["Content-Length"])
            if i % 2:
                first = reference.shard_files(body_of(key), K, M)[0]
                (victim,) = [d for d, f in files.items() if f == first]
                odir = os.path.join(root, f"d{victim}", BUCKET, key)
                for dirpath, _, names in os.walk(odir):
                    if "part.1" in names:
                        os.unlink(os.path.join(dirpath, "part.1"))
            before = lane_rows()
            out["got"][key] = cli.get_object(BUCKET, key)
            out["get_lanes"][key] = grown(before, lane_rows())
        for key in KEYS[::3]:
            cli.delete_object(BUCKET, key)
            try:
                cli.get_object(BUCKET, key)
                out["gone"][key] = 200
            except S3ClientError as e:
                out["gone"][key] = e.status
        st, _, page = cli.request("GET", "/minio/v2/metrics/node")
        out["metrics_page"] = page.decode() if st == 200 else ""
    finally:
        srv.shutdown()
        coalesce.reset()
        mp.undo()
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    return {plane: serve(str(tmp_path_factory.mktemp(plane)), plane)
            for plane in PLANES}


@pytest.fixture(params=list(PLANES))
def run(request, runs):
    return runs[request.param]


def test_keys_cover_every_set():
    assert {ref_set(k) for k in KEYS} == set(range(SETS))


def test_object_lies_on_the_set_siphash_names(run):
    for key, files in run["files"].items():
        s = ref_set(key)
        assert sorted(files) == list(range(s * SET_DRIVES,
                                           (s + 1) * SET_DRIVES)), key


def test_shard_files_equal_the_reference(run):
    for key, files in run["files"].items():
        res = reference.compare_part(body_of(key), K, M,
                                     list(files.values()))
        assert res == {"frames": (K + M) * (BLOCKS + 1), "bad_bytes": 0,
                       "bad_digest": 0, "shards_missing": 0}, key


def test_get_returns_the_bytes_also_with_a_data_shard_gone(run):
    for key in KEYS:
        assert run["stat"][key] == SIZE
        assert run["got"][key] == body_of(key), key


def test_deleted_key_answers_404(run):
    assert run["gone"] == {key: 404 for key in KEYS[::3]}


def test_set_dispatches_on_its_own_lane_and_no_other(run):
    """Set i rides lane i % lanes.  On the mesh plane a PUT reaches no
    lane at all; a healthy GET's digests always ride the set's."""
    for i, key in enumerate(KEYS):
        lane = ref_set(key) % run["lanes"]
        put, get = run["put_lanes"][key], run["get_lanes"][key]
        if run["plane"] == "mesh":
            assert put == {}, key
            assert set(get) <= {lane}, key      # degraded: the mesh decodes
        else:
            assert set(put) == {lane} and set(get) == {lane}, key
        if not i % 2:
            assert set(get) == {lane}, key


def test_encode_blocks_counted_under_the_plane_that_served(run):
    plane = "mesh" if run["plane"] == "mesh" else "lane"
    assert run["chose"] == [(plane, i % run["lanes"]) for i in range(SETS)]
    assert run["encode_blocks"] == {plane: BLOCKS * len(KEYS)}
    assert f'mtpu_encode_blocks_total{{plane="{plane}"}}' \
        in run["metrics_page"]


def test_shard_files_identical_across_planes(runs):
    want = runs["one_lane"]["files"]
    for plane in ("mesh", "lane", "auto"):
        assert runs[plane]["files"] == want, plane
