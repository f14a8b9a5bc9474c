"""A configuration states the bitrot digest its server writes (PR 38):
HighwayHash-256 in the plain reference, `bitrot_algo` in the loader, the
server's environment, `correct` and the rooflines, and the cell
`ec6p6-64m-degraded-get`.  Run by hand, on the CPU backend, at the
rehearsal's sizes, like `test_correct.py`:

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

sys.path.insert(0, HERE)

import controls  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import test_correct  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

HH = "highwayhash256S"
H6 = "ec6p6-64m-degraded-get"
TRAFFIC = os.path.join("tests", "traffic")
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def bench() -> dict:
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def hh(data: bytes) -> str:
    row = np.frombuffer(data, dtype=np.uint8)[None, :]
    return reference.highwayhash256_rows(row)[0].tobytes().hex()


# -- HighwayHash-256 in the reference ---------------------------------------------

def test_golden_chain_of_minios_bitrot_self_test():
    """`bitrotSelfTest` (minio cmd/bitrot.go): 32 rounds of hash(msg), msg
    growing by each digest; MinIO refuses to start on another end."""
    msg = digest = b""
    for _ in range(32):
        digest = bytes.fromhex(hh(msg))
        msg += digest
    assert digest.hex() == ("39c0407ed3f01b18d22c85db4aeff11e"
                            "060ca5f43131b0126731ca197cd42313")


# HighwayHash-256 of bytes(range(n)) under MinIO's key, from the portable C++
# reference (the vectors the program's own tests hold, tests/highwayhash_vectors.py).
@pytest.mark.parametrize("n,want", [
    (0, "5e76d207cf4ab20866fdc03c83e8a0f4e8f458e880777956ec0bae4e9f23f6c5"),
    (1, "824f232288e3a62a106404a8adb9e641d7a606fef3b0c81e8b4e10ab6d4944f6"),
    (3, "d450ca9626635b83e237be13ac795509fb79a2ea5d62120604fdf32c60e31d2e"),
    (16, "f94f4ab5813912a13552147a599019341401024340c7dd07d5d8d682e48d7bfd"),
    (19, "cdcde60d71d62434e67bf93056cf0bbb060fd0669e21ad401d071e68655523d7"),
    (31, "46d1434308b9e6b43fb301456fcff96e05d216b5fce478d8f1edeb65ea8d950d"),
    (32, "3c224e72ba74571f41044698f79123ba6481c70051b379b4413d42214f78c513"),
    (33, "e0300cc02538626ed1c398901bea1b4b686a7d79f2fada3730985303ab3faf22"),
])
def test_lengths_that_are_no_multiple_of_32(n, want):
    assert hh(bytes(range(n))) == want


def test_a_shard_block_of_ec6p6_against_the_programs_scalar_hash():
    """174,763 bytes: 5,461 packets and a remainder of 11.  The literal was
    read from this reference; the program's scalar implementation, where it
    can be imported, is the second witness.  Rows hashed in lock-step equal
    rows hashed alone."""
    rows = np.random.default_rng([38, 0x0DD]).integers(
        0, 256, (3, 174763), dtype=np.uint8)
    digests = reference.highwayhash256_rows(rows)
    assert digests[0].tobytes().hex() == (
        "ee78663c6c929c0f4d39c85da347c0ad01e5825df37228efd47fb30814a13c68")
    assert [d.tobytes().hex() for d in digests] == \
        [hh(r.tobytes()) for r in rows]
    sys.path.insert(0, run.CHECKOUT)
    theirs = pytest.importorskip("minio_tpu.ops.highwayhash")
    assert theirs.MAGIC_KEY == reference.HH_KEY
    assert theirs.highwayhash256(rows[1].tobytes()) == digests[1].tobytes()


# -- the shard files ---------------------------------------------------------------

BODY = np.random.default_rng([38, 0xF11E5]).bytes(2 * (1 << 20) + 4321)


@pytest.mark.parametrize("k,m,algo,want", [
    # No algorithm given: the bytes PR 37's reference returned (read there).
    (2, 2, None, "b5c700d833816ec50e374d22e81b764db44d54da9b4e3e919999503277c6b7c6"),
    (8, 4, None, "d9ad04e1db47d4c34af963f04e116c37d9480436e6d24eac92b38714ed9dd23f"),
    (6, 6, None, "7149ac796d8875dcfbb69d0207f5e71aba645d476f032bd6ffa52ec08fc94f57"),
    (2, 2, "mxh256", "b5c700d833816ec50e374d22e81b764db44d54da9b4e3e919999503277c6b7c6"),
    (2, 2, HH, "d07125927420bc72100c1e77792880bfa14de0b3f0fe0c369ef8d41074b5d09d"),
    (8, 4, HH, "53846e826ffe93ae5e75cbbc53d5f0eba14d421c049bcbb9b443c032841f0cfa"),
    (6, 6, HH, "58105182c23c00fac21168a1e8c4f2d22c7057c1a5b6905d99bb6ce95b471d81"),
])
def test_shard_files_are_pinned(k, m, algo, want):
    files = reference.shard_files(BODY, k, m, *([algo] if algo else []))
    assert hashlib.sha256(b"".join(files)).hexdigest() == want
    if algo == HH:
        # The frames differ from mxh256's by their digests alone, and a
        # frame's digest is the hash of the shard block behind it.
        plain = reference.shard_files(BODY, k, m)
        s = -(-reference.BLOCK // k)
        assert all(f[32:32 + s] == p[32:32 + s] and f[:32] != p[:32]
                   for f, p in zip(files, plain))
        assert files[k][:32].hex() == hh(files[k][32:32 + s])
        tail = -(-4321 // k)
        assert files[0][-tail - 32:-tail].hex() == hh(files[0][-tail:])


def test_a_part_written_under_one_digest_fails_the_other():
    """The statement and the server made to disagree: every frame's digest
    is wrong, no byte of data is."""
    k, m = 8, 4
    for wrote, stated in (("mxh256", HH), (HH, "mxh256")):
        files = reference.shard_files(BODY, k, m, wrote)
        res = reference.compare_part(BODY, k, m, files, stated)
        assert res == {"frames": 3 * (k + m), "bad_bytes": 0,
                       "bad_digest": 3 * (k + m), "shards_missing": 0}


# -- the configuration's statement ---------------------------------------------------

def scratch_config(monkeypatch, cell_config: str, **change):
    """`configs/<cell_config>.json` as the loader reads it, with `change`."""
    load_json = traffic.load_json

    def changed(*parts):
        doc = load_json(*parts)
        if parts == ("configs", f"{cell_config}.json"):
            doc = dict(doc, **change)
        return doc
    monkeypatch.setattr(traffic, "load_json", changed)


def test_every_configuration_says_mxh256_by_saying_nothing(bench):
    for cell in bench["workloads"]:
        assert "bitrot_algo" not in traffic.load_json(
            "configs", f"{cell['config']}.json")
        _, cfg = traffic.load_cell(bench, cell["name"])
        assert cfg["bitrot_algo"] == "mxh256" and cfg["env"] == {}


def test_loader_takes_the_statement(bench, monkeypatch):
    scratch_config(monkeypatch, "ec8p4-12drive", bitrot_algo=HH)
    assert traffic.load_cell(bench, "ec8p4-mp64-put")[1]["bitrot_algo"] == HH
    # Another configuration is not touched.
    assert traffic.load_cell(bench, H6)[1]["bitrot_algo"] == "mxh256"


@pytest.mark.parametrize("change,why", [
    ({"bitrot_algo": "sha256"}, "is not one of"),
    ({"bitrot_algo": "highwayhash256"}, "is not one of"),
    ({"env": {"MTPU_BITROT_ALGO": "mxh256"}}, "say it as bitrot_algo"),
    ({"bitrot_algo": HH, "env": {"MTPU_BITROT_ALGO": HH}},
     "say it as bitrot_algo"),
])
def test_loader_refuses(bench, monkeypatch, change, why):
    scratch_config(monkeypatch, "ec8p4-12drive", **change)
    with pytest.raises(ValueError, match=why) as e:
        traffic.load_cell(bench, "ec8p4-mp64-put")
    assert "configs/ec8p4-12drive.json" in str(e.value)


@pytest.mark.parametrize("k,m,t", [(8, 4, 2), (6, 6, 6), (2, 2, 2)])
def test_host_hashed_work_is_the_work_less_the_digest_term(k, m, t):
    d = 3e9
    blocks = d / work.BLOCK
    enc, enc_hh = work.encode_work(d, k, m), work.encode_work(d, k, m, HH)
    assert enc == work.encode_work(d, k, m, "mxh256")
    assert enc_hh["ops"] == 128.0 * m * d
    assert enc["ops"] - enc_hh["ops"] == pytest.approx(
        work.MXH_OPS_PER_BYTE * d * (1 + m / k))
    assert enc["hbm_bytes"] - enc_hh["hbm_bytes"] == pytest.approx(
        32 * (k + m) * blocks)
    dec, dec_hh = work.decode_work(d, k, t), work.decode_work(d, k, t, HH)
    assert dec == work.decode_work(d, k, t, "mxh256")
    assert dec_hh == {"ops": 128.0 * t * d, "hbm_bytes": d * (1 + t / k)}
    assert dec["ops"] - dec_hh["ops"] == pytest.approx(
        work.MXH_OPS_PER_BYTE * d)
    assert dec["hbm_bytes"] - dec_hh["hbm_bytes"] == pytest.approx(
        32 * k * blocks)


def test_rooflines_follow_the_configuration():
    q = {"client": {"get_bytes": 4e9, "put_bytes": 4e9},
         "trace": {"busy_s": 0.5}, "device_kind": V5E, "hidden": 6}
    enc, dec = (traffic.load_metric(n)
                for n in ("encode_roofline", "decode_roofline"))
    for algo in reference.ALGOS:
        cfg = {"data_shards": 6, "parity_shards": 6, "bitrot_algo": algo}
        for how, w in ((enc, work.encode_work(4e9, 6, 6, algo)),
                       (dec, work.decode_work(4e9, 6, 6, algo))):
            assert run.read_metric(how, q, cfg) == \
                100.0 * work.least_seconds(w, V5E)["seconds"] / 0.5


# -- whole runs on the CPU backend --------------------------------------------------

def served_algo(result: dict) -> None:
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is True, compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert compared["frames_compared"] >= 1
    assert compared["frames_bad_digest"] == 0
    assert compared["frames_bad_bytes"] == 0
    assert compared["shards_missing"] == 0


@pytest.mark.parametrize("cell,config", [
    ("ec8p4-mp64-put", "ec8p4-12drive"),
    ("ec8p4-64m-degraded-get", "ec8p4-12drive"),
    ("ec6p6-10m-mixed", "ec6p6-12drive"),
])
def test_a_highwayhash_configuration_ends_correct(monkeypatch, cell, config):
    """A scratch configuration, `bitrot_algo` its one change: the server is
    told, writes HighwayHash frames, and the reference follows."""
    scratch_config(monkeypatch, config, bitrot_algo=HH)
    served_algo(test_correct.drive(monkeypatch, cell, None))


def test_statement_and_server_disagree_is_not_correct(monkeypatch):
    """The server writes HighwayHash, the check is told mxh256."""
    scratch_config(monkeypatch, "ec8p4-12drive", bitrot_algo=HH)
    check = run.check_correct

    def told_mxh256(srv, seed, wl, cfg, *rest):
        return check(srv, seed, wl, dict(cfg, bitrot_algo="mxh256"), *rest)
    monkeypatch.setattr(run, "check_correct", told_mxh256)
    result = test_correct.drive(monkeypatch, "ec8p4-mp64-put", None)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False
    assert compared["frames_bad_digest"] == compared["frames_compared"] > 0
    assert compared["frames_bad_bytes"] == 0
    assert compared["shards_missing"] == 0


def test_bad_digest_under_highwayhash_is_not_correct(monkeypatch):
    scratch_config(monkeypatch, "ec8p4-12drive", bitrot_algo=HH)
    result = test_correct.drive(monkeypatch, "ec8p4-mp64-put", "bad_digest")
    assert result["correct"] is False
    assert result["compared"]["frames_bad_digest"]["value"] > 0
    assert result["compared"]["frames_bad_bytes"]["value"] == 0


# -- the cell ec6p6-64m-degraded-get --------------------------------------------------

def test_the_cell_is_the_issues(bench):
    wl, cfg = traffic.load_cell(bench, H6)
    assert (cfg["name"], cfg["env"], cfg["server_args"]) == \
        ("ec6p6-12drive", {}, [])
    other, _ = traffic.load_cell(bench, "ec8p4-64m-degraded-get")
    differ = {k for k in wl if wl[k] != other.get(k)}
    assert differ == {"name", "who", "source", "hide_shards", "assumed",
                      "reduced"}
    assert wl["hide_shards"] == 6 == cfg["data_shards"] == cfg["parity_shards"]
    assert wl["clients"] == 8
    # Six of six: one pattern, whatever the seed and the object.
    assert {tuple(traffic.hidden_shards(s, c, n, 6, 6))
            for s in (1, 2**31 + 7) for c in range(8) for n in range(4)} == \
        {(0, 1, 2, 3, 4, 5)}
    small, _ = traffic.load_cell(bench, H6, TRAFFIC)
    assert set(small) == set(wl) - {"source", "assumed", "reduced"}
    assert small["mix"] == wl["mix"] and small["hide_shards"] == 6
    # It reads what the 8+4 degraded cell reads, metric for metric.
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in run.cell_metrics(bench, section, H6)] == \
            [m["name"] for m in run.cell_metrics(
                bench, section, "ec8p4-64m-degraded-get")]
    assert len(run.cell_metrics(bench, "per_layer", H6)) == 16


def test_the_cells_sound_run_is_correct(monkeypatch):
    result = test_correct.drive(monkeypatch, H6, None)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is True, compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert compared["gets_served_healthy"] == 0
    assert compared["decode_blocks_short"] == 0
    assert compared["hidden_files_back"] == 0
    assert compared["get_mismatch"] == 0
    assert compared["disk_parts_compared"] == 2
    assert compared["frames_compared"] == 2 * 12 * 6
    assert compared["gets_compared"] == result["attempted"]


def test_the_cells_hiding_skipped_is_not_correct(monkeypatch):
    """The control.  At K = 6 it is `decode_blocks_short` alone that reads
    it: the program counts `mtpu_healthy_reads_total` on the read that K
    dividing 1 MiB admits (`erasure_set.py`: `BLOCK_SIZE % k == 0`), so a
    healthy read at 6+6 goes uncounted and `gets_served_healthy` stays 0."""
    monkeypatch.setattr(run, "hide_shards", controls.look_only)
    result = test_correct.drive(monkeypatch, H6, None)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False
    assert compared["decode_blocks_short"] == \
        5 * compared["gets_compared"] > 0, compared
    assert compared["gets_served_healthy"] == 0, compared
    assert compared["get_mismatch"] == 0 and compared["shards_missing"] == 0


def test_the_cells_rebuilt_row_altered_is_not_correct(monkeypatch):
    result = test_correct.drive(monkeypatch, H6, "flip_rebuilt")
    assert result["correct"] is False
    assert result["compared"]["get_mismatch"]["value"] > 0
