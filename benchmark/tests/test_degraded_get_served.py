"""A GET with data shards' `part.1` unlinked, through the S3 front door with
the device codec forced on (the CPU backend stands in for the chip, as in
`tests/test_four_set_host.py`): the bytes are exact, no read was a healthy
one, every full block had rows rebuilt, and the rows the device program
rebuilt are `reference.encode_block`'s.  EC:8+4 with 2, EC:6+6 with 6 and
EC:2+2 with 2 data shards gone, several seeded choices each.

ISSUE 37 asked for this file as tier-1's `tests/test_degraded_get_cell.py`;
a `benchmark` PR may add files under `benchmark/` only, so it lies here and
is run by hand like its neighbours (a later PR moves it: `PERF.md` §7):

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import glob
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

from minio_tpu.engine import shardmath  # noqa: E402
from minio_tpu.engine.pools import ServerPools  # noqa: E402
from minio_tpu.engine.sets import ErasureSets  # noqa: E402
from minio_tpu.observe.metrics import DATA_PATH  # noqa: E402
from minio_tpu.ops import coalesce  # noqa: E402
from minio_tpu.server.client import S3Client  # noqa: E402
from minio_tpu.server.server import S3Server  # noqa: E402
from minio_tpu.server.sigv4 import Credentials  # noqa: E402
from minio_tpu.storage.drive import LocalDrive  # noqa: E402

ACCESS, SECRET, BUCKET = "degraded", "degraded-secret-key", "bench"
MIB = 1 << 20
BLOCKS = 3
SIZE = BLOCKS * MIB + 4321                  # three full blocks and a tail
GEOMETRIES = {"ec8p4": (8, 4, 2), "ec6p6": (6, 6, 6), "ec2p2": (2, 2, 2)}
SEEDS = (1, 2, 3, 2**31 + 4)


def body_of(seed: int) -> bytes:
    return np.random.default_rng([seed, 0xB0D1]).bytes(SIZE)


@pytest.fixture(scope="module", params=list(GEOMETRIES))
def served(request, tmp_path_factory):
    """One server a geometry, the device codec on, its decode's rebuilt
    rows recorded at the seam the engine asks through."""
    k, m, h = GEOMETRIES[request.param]
    root = str(tmp_path_factory.mktemp(request.param))
    mp = pytest.MonkeyPatch()
    mp.setattr(shardmath, "platform", lambda: (True, False))
    mp.delenv("MTPU_MESH", raising=False)
    mp.setenv("MTPU_DEVICES", "1")
    rebuilt = []
    orig = shardmath.ShardMath.verify_transform

    def recording(self, x, kk, mm, sources, targets, algo):
        digests, out = orig(self, x, kk, mm, sources, targets, algo)
        if targets:
            rebuilt.append((tuple(targets), np.array(out)))
        return digests, out
    mp.setattr(shardmath.ShardMath, "verify_transform", recording)
    coalesce.reset()
    drives = [LocalDrive(os.path.join(root, f"d{i}")) for i in range(k + m)]
    pools = ServerPools([ErasureSets(drives, set_drive_count=k + m,
                                     default_parity=m)])
    srv = S3Server(pools, Credentials(ACCESS, SECRET)).start()
    cli = S3Client(srv.endpoint, ACCESS, SECRET)
    cli.make_bucket(BUCKET)
    yield {"k": k, "m": m, "h": h, "root": root, "cli": cli,
           "rebuilt": rebuilt}
    srv.shutdown()
    coalesce.reset()
    mp.undo()


@pytest.mark.parametrize("seed", SEEDS)
def test_get_with_data_shards_unlinked(served, seed):
    k, m, h = served["k"], served["m"], served["h"]
    cli, key, body = served["cli"], f"c0/o-{seed}", body_of(seed)
    cli.put_object(BUCKET, key, body)
    # Which drive holds data shard i: by content, with the harness's finder.
    where = run.data_shard_files(
        sorted(glob.glob(os.path.join(served["root"], "d*"))), key,
        body[:MIB], k)
    assert sorted(where) == list(range(k))
    gone = traffic.hidden_shards(seed, 0, seed % 97, k, h)
    assert len(gone) == h
    for i in gone:
        os.unlink(where[i])

    before = DATA_PATH.snapshot()
    del served["rebuilt"][:]
    assert cli.get_object(BUCKET, key) == body
    after = DATA_PATH.snapshot()
    assert after["healthy_reads"] == before["healthy_reads"]
    assert after["decode_blocks"] - before["decode_blocks"] == BLOCKS
    assert after["verify_blocks"] - before["verify_blocks"] == BLOCKS
    # The rows the program rebuilt, block for block, against the reference.
    got = {}
    for targets, out in served["rebuilt"]:
        assert targets == tuple(gone)
        for b in range(out.shape[0]):
            got[len(got)] = out[b]
    assert len(got) == BLOCKS
    for b in range(BLOCKS):
        want = reference.encode_block(body[b * MIB:(b + 1) * MIB], k, m)
        assert np.array_equal(got[b], want[gone]), (b, gone)
    # xl.meta stayed on every drive and nothing came back.
    assert not any(os.path.exists(where[i]) for i in gone)
    assert int(cli.head_object(BUCKET, key)["Content-Length"]) == SIZE
