#!/usr/bin/env python3
"""Checks of the yardstick's own arithmetic.  Run by hand (needs no chip, no
server, no JAX):

    python3 benchmark/selfcheck.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

V5E = "TPU v5 lite"


def close(a: float, b: float, rel: float = 5e-3) -> bool:
    return abs(a - b) <= rel * abs(b)


def main() -> int:
    # The trace reduction: union of overlapping, nested and disjoint
    # intervals, and the idle share it gives.
    spans = [(0, 10), (5, 12), (20, 30), (22, 25), (30, 31), (40, 40)]
    assert trace_reduce.union_ns(spans) == 12 + 11, "union"
    assert trace_reduce.union_ns([]) == 0.0
    assert trace_reduce.idle_pct(23e-9, 100e-9) == 77.0, "idle share"

    # work.py against ROADMAP Speed 7's two figures for EC:8+4.
    assert close(work.bf16_parity_gbps(4, V5E), 385.0), "bf16-bound GB/s"
    assert close(work.hbm_gbps(8, 4, V5E), 546.0), "HBM-bound GB/s"
    least = work.least_seconds(work.encode_work(1e9, 8, 4), V5E)
    assert least["bound"] == "hbm" and close(least["seconds"], 1.5 / 819,
                                             1e-3), least
    # A degraded GET at 8+4 with two data shards rebuilt: D in, D/4 out, so
    # HBM binds at 819 / 1.25 GB/s of GET data; with all M rows rebuilt the
    # work is an encode's parity (less the digests of the M rows written).
    assert close(work.hbm_gbps(8, 2, V5E), 655.2, 1e-6), "decode HBM GB/s"
    least = work.least_seconds(work.decode_work(1e9, 8, 2), V5E)
    assert least["bound"] == "hbm" and close(least["seconds"], 1.25 / 819,
                                             1e-3), least
    dec, enc = work.decode_work(1e9, 8, 4), work.encode_work(1e9, 8, 4)
    mxh = work.MXH_OPS_PER_BYTE * 1e9
    assert close(dec["ops"] - mxh, 128.0 * 4 * 1e9, 1e-9)
    assert close(enc["ops"] - 1.5 * mxh, 128.0 * 4 * 1e9, 1e-9)
    assert close(dec["hbm_bytes"], enc["hbm_bytes"], 1e-4)
    assert dec["hbm_bytes"] == 1.5e9 + 32 * 8 * 1e9 / work.BLOCK
    # Under a digest the host computes (`highwayhash256S`) the chip's work is
    # the same less the digest term: mxh256's operations over the bytes
    # hashed, 32 bytes a shard block.
    hh = "highwayhash256S"
    for k, m, t in ((8, 4, 2), (6, 6, 6), (2, 2, 2)):
        enc, dec = work.encode_work(1e9, k, m), work.decode_work(1e9, k, t)
        assert enc == work.encode_work(1e9, k, m, "mxh256")
        assert dec == work.decode_work(1e9, k, t, "mxh256")
        enc_hh = work.encode_work(1e9, k, m, hh)
        dec_hh = work.decode_work(1e9, k, t, hh)
        blocks = 1e9 / work.BLOCK
        assert enc_hh["ops"] == 128.0 * m * 1e9
        assert close(enc["ops"] - enc_hh["ops"], mxh * (1 + m / k), 1e-9)
        assert close(enc["hbm_bytes"] - enc_hh["hbm_bytes"],
                     32 * (k + m) * blocks, 1e-6)
        assert dec_hh == {"ops": 128.0 * t * 1e9,
                          "hbm_bytes": 1e9 * (1 + t / k)}
        assert close(dec["ops"] - dec_hh["ops"], mxh, 1e-9)
        assert close(dec["hbm_bytes"] - dec_hh["hbm_bytes"],
                     32 * k * blocks, 1e-6)
    # EC:2+2 moves 2 bytes per data byte and needs half the parity rows.
    assert close(work.hbm_gbps(2, 2, V5E), 409.5)
    try:
        work.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")

    # The percentile on a known list.
    assert run.percentile([1, 2, 3, 4, 5], 50) == 3
    assert run.percentile(list(range(1, 101)), 95) == 95.05
    assert run.percentile([7.0], 95) == 7.0
    assert run.percentile([1, 2, float("inf")], 95) == float("inf")

    # The loader on every cell of BENCHMARK.json, and the traffic rules.
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        for tdir in (None, os.path.join("tests", "traffic")):
            wl, cfg = traffic.load_cell(bench, cell["name"], tdir)
            assert cfg["drives"] == cfg.get("sets", 1) * (
                cfg["data_shards"] + cfg["parity_shards"]), cfg["name"]
            assert cfg["bitrot_algo"] in reference.ALGOS, cfg["name"]
            assert "MTPU_BITROT_ALGO" not in cfg["env"], cfg["name"]
            block = sum(wl["mix"].values())
            ops = traffic.op_blocks(3, 0, wl["mix"])
            first = [next(ops) for _ in range(block)]
            assert {o: first.count(o) for o in wl["mix"]} == wl["mix"]
        for m in run.cell_metrics(bench, "per_layer", cell["name"]):
            how = traffic.load_metric(m["name"])
            assert m["name"].startswith(how["name"]) and how["kind"] in (
                "ratio", "trace_idle", "trace_roofline"), how
            assert how["kind"] != "trace_roofline" or how.get(
                "work", "encode") in ("encode", "decode"), how
    # The lists of `ec6p6-64m-degraded-get` are whole: it reads what the 8+4
    # degraded cell reads, end to end and layer by layer, and end to end
    # that is every metric a GET-only cell can report.
    lists = {section: [[m["name"] for m in run.cell_metrics(bench, section, c)]
                       for c in ("ec6p6-64m-degraded-get",
                                 "ec8p4-64m-degraded-get")]
             for section in ("end_to_end", "per_layer")}
    assert all(mine == its for mine, its in lists.values()), lists
    assert set(lists["end_to_end"][0]) == {"get_gbps", "get_p95_ms",
                                           "setup_s"}
    assert len(lists["per_layer"][0]) == 16
    # A mix that hides shards: from 1 to M of them, of prefilled single-part
    # objects under new keys, and nothing written or deleted in the window.
    hid = next(c["name"] for c in bench["workloads"] if traffic.load_cell(
        bench, c["name"])[0].get("hide_shards"))
    wl, cfg = traffic.load_cell(bench, hid)
    for change in ({"hide_shards": cfg["parity_shards"] + 1},
                   {"prefill_per_client": 0}, {"mix": {"GET": 1, "PUT": 1}},
                   {"mix": {"GET": 1, "DELETE": 1}}, {"put_key_ring": 2},
                   {"part_bytes": wl["object_bytes"]}):
        with tempfile.TemporaryDirectory() as tmp:
            with open(os.path.join(tmp, f"{wl['name']}.json"), "w") as f:
                json.dump(dict(wl, **change), f)
            try:
                traffic.load_cell(bench, hid, tmp)
            except ValueError as e:
                assert f"{wl['name']}.json: hide_shards" in str(e), e
            else:
                raise AssertionError(f"the loader admitted {change}")
    picks = [traffic.hidden_shards(5, c, n, 8, 2)
             for c in range(8) for n in range(4)]
    assert all(len(p) == 2 and 0 <= p[0] < p[1] < 8 for p in picks)
    assert len({tuple(p) for p in picks}) > 8, "pairs differ by object"
    assert picks != [traffic.hidden_shards(6, c, n, 8, 2)
                     for c in range(8) for n in range(4)], "and by seed"
    # Two seeds send the same operations in another order.
    a = traffic.op_blocks(1, 0, {"GET": 3, "PUT": 1})
    b = traffic.op_blocks(2 ** 31 + 5, 0, {"GET": 3, "PUT": 1})
    sa, sb = [next(a) for _ in range(40)], [next(b) for _ in range(40)]
    assert sorted(sa) == sorted(sb) and sa != sb

    # A body is a function of (seed, client, key, part, generation).
    wl = {"object_bytes": 4096, "part_bytes": 0, "pool_buffers": 2}
    b0, b1 = traffic.Bodies(9, 0, wl), traffic.Bodies(9, 0, wl)
    body = b"".join(b0.chunks("k", 0, 0))
    assert len(body) == 4096 and body == b"".join(b1.chunks("k", 0, 0))
    assert body != b"".join(b0.chunks("k", 0, 1))
    assert b0.matches(body, "k", 0, wl) and not b0.matches(body, "k", 1, wl)

    # The reference: the code is systematic, and a bit flipped on disk in
    # the bytes or in the digest, or a shard not there, is seen.
    block = np.random.default_rng(4).bytes(5000)
    rows = reference.encode_block(block, 2, 2)
    assert rows.shape == (4, 2500)
    assert np.array_equal(rows[:2], reference.data_rows(block, 2))
    assert bytes(rows[:2].reshape(-1)[:5000]) == block
    files = reference.shard_files(block, 2, 2)
    assert reference.compare_part(block, 2, 2, files)["frames"] == 4
    for at, what in ((40, "bad_bytes"), (5, "bad_digest")):
        bad = bytearray(files[3])
        bad[at] ^= 1
        res = reference.compare_part(block, 2, 2, files[:3] + [bytes(bad)])
        assert res[what] == 1, res
    assert reference.compare_part(block, 2, 2, files[:3])["shards_missing"] \
        == 1
    # HighwayHash-256 under MinIO's key: the golden chain of MinIO's own
    # `bitrotSelfTest` (32 rounds of hash(msg), msg growing by each digest),
    # and the same four cases under `highwayhash256S`.
    msg = digest = b""
    for _ in range(32):
        digest = reference.highwayhash256_rows(
            np.frombuffer(msg, dtype=np.uint8)[None, :])[0].tobytes()
        msg += digest
    assert digest.hex() == ("39c0407ed3f01b18d22c85db4aeff11e"
                            "060ca5f43131b0126731ca197cd42313"), "golden chain"
    files_hh = reference.shard_files(block, 2, 2, hh)
    assert [f[32:] for f in files_hh] == [f[32:] for f in files]
    assert all(a[:32] != b[:32] for a, b in zip(files_hh, files))
    assert reference.compare_part(block, 2, 2, files_hh, hh) == {
        "frames": 4, "bad_bytes": 0, "bad_digest": 0, "shards_missing": 0}
    assert reference.compare_part(block, 2, 2, files_hh)["bad_digest"] == 4
    for at, what in ((40, "bad_bytes"), (5, "bad_digest")):
        bad = bytearray(files_hh[3])
        bad[at] ^= 1
        res = reference.compare_part(block, 2, 2,
                                     files_hh[:3] + [bytes(bad)], hh)
        assert res[what] == 1, res
    assert reference.compare_part(block, 2, 2, files_hh[:3],
                                  hh)["shards_missing"] == 1
    print("selfcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
