"""The one-chip 10 MiB mixed cells of PR 35, `ec8p4-10m-mixed` and
`ec6p6-10m-mixed` (configuration `ec6p6-12drive`): their data files load,
the reference holds at K = 6, and `correct` has been shown to fail on the
new configuration's cell.  Run by hand, on the CPU backend, at the
rehearsal's sizes, like `test_correct.py`:

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

sys.path.insert(0, HERE)

import reference  # noqa: E402
import run  # noqa: E402
import test_correct  # noqa: E402
import traffic  # noqa: E402

CELLS = {"ec8p4-10m-mixed": (8, 4, "EC:4"), "ec6p6-10m-mixed": (6, 6, "EC:6")}
EC6P6 = "ec6p6-10m-mixed"
TRAFFIC = os.path.join("tests", "traffic")


@pytest.fixture(scope="module")
def bench() -> dict:
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("traffic_dir", [None, TRAFFIC])
def test_cell_loads(bench, cell, traffic_dir):
    k, m, storage_class = CELLS[cell]
    wl, cfg = traffic.load_cell(bench, cell, traffic_dir)
    assert (cfg["drives"], cfg["sets"], cfg["chips"]) == (12, 1, 1)
    assert (cfg["data_shards"], cfg["parity_shards"]) == (k, m)
    assert cfg["storage_class_standard"] == storage_class
    assert cfg["put_headers"] == {"x-amz-storage-class": "STANDARD"}
    assert cfg["server_args"] == [] and cfg["env"] == {}
    assert cfg["reduced"] == []
    assert wl["object_bytes"] == 10 << 20 and wl["part_bytes"] == 0
    assert wl["mix"] == {"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10}
    assert wl["put_key_ring"] == 0 and not wl["hide_shards"]
    if traffic_dir is None:
        assert wl["clients"] == 8 and wl["prefill_per_client"] == 16
        assert wl["stagger_s"] == 0.32 and wl["pool_buffers"] == 16
        assert wl["check"] == {"readback_objects": 32, "disk_parts": 32,
                               "deleted_gets": 16}


def test_the_two_cells_differ_by_geometry_alone(bench):
    a, b = (next(w for w in bench["workloads"] if w["name"] == c)
            for c in CELLS)
    assert a["traffic"] == b["traffic"] == "10m-mixed-8c"
    assert a["chips"] == b["chips"] == 1
    big = traffic.load_json("traffic", "10m-mixed.json")
    mine = traffic.load_json("traffic", "10m-mixed-8c.json")
    differ = {k for k in big if big[k] != mine[k]}
    assert differ == {"name", "who", "clients", "reduced"}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_per_layer_metric_of_the_cell_has_a_reader(bench, cell):
    mine = run.cell_metrics(bench, "per_layer", cell)
    names = {m["name"] for m in mine}
    assert {"decode_blocks_pct.get", "hedge_wins_per_100_reads.get",
            "rows_per_padded_row.get", "lane_no_work_pct.get",
            "device_idle_pct.get", "compile_ms_in_window.get"} <= names
    assert ("stage_pad_bytes_per_byte.put" in names) == (cell == EC6P6)
    # The lane means and the PUT cell's roofline are not these cells'.
    assert not names & {"lane_mean_no_work_pct.get", "encode_roofline",
                        "encode_blocks_on_lane_pct.put"}
    for m in mine:
        assert traffic.load_metric(m["name"])["kind"] in (
            "ratio", "trace_idle")
    ends = {m["name"] for m in run.cell_metrics(bench, "end_to_end", cell)}
    assert ends == {"put_gbps", "put_p95_ms", "get_gbps", "get_p95_ms",
                    "setup_s"}


def test_new_metrics_read_nothing_where_the_program_lacks_the_counters():
    """On the parent commit the families are not on the metrics page: the
    reader gives None and the line leaves the metric out."""
    q = {"metrics0": {"mtpu_hedged_reads_total": 1.0},
         "metrics1": {"mtpu_hedged_reads_total": 9.0,
                      "mtpu_hedge_wins_total": 2.0},
         "client": {"put_bytes": 100.0}}
    read = {n: run.read_metric(traffic.load_metric(n), q, {})
            for n in ("decode_blocks_pct.get", "stage_pad_bytes_per_byte.put",
                      "hedge_wins_per_100_reads.get")}
    assert read == {"decode_blocks_pct.get": None,
                    "stage_pad_bytes_per_byte.put": None,
                    "hedge_wins_per_100_reads.get": 25.0}


@pytest.mark.parametrize("algo", ["mxh256", "highwayhash256S"])
@pytest.mark.parametrize("nbytes", [3 * (1 << 20) + 4321, 10 << 20, 5])
def test_reference_at_k6_on_a_body_that_is_no_multiple_of_6(nbytes, algo):
    """1 MiB is no multiple of 6: every full block is zero-padded by 2
    bytes into 6 rows of 174,763; the tail block by its own rule.  Under
    either digest a configuration may state (PR 38)."""
    k, m = 6, 6
    assert nbytes % k
    body = np.random.default_rng(nbytes).bytes(nbytes)
    files = reference.shard_files(body, k, m, algo)
    blocks = [min(reference.BLOCK, nbytes - off)
              for off in range(0, nbytes, reference.BLOCK)]
    sizes = [-(-b // k) for b in blocks]
    assert len(files) == k + m
    assert all(len(f) == sum(reference.DIGEST + s for s in sizes)
               for f in files)
    if nbytes >= reference.BLOCK:
        assert sizes[0] == 174763
    # The data rows, frames stripped, are the body and its zero pad.
    pos, got = 0, []
    for b, s in zip(blocks, sizes):
        rows = b"".join(f[pos + reference.DIGEST:pos + reference.DIGEST + s]
                        for f in files[:k])
        assert rows[b:] == bytes(k * s - b)
        got.append(rows[:b])
        pos += reference.DIGEST + s
    assert b"".join(got) == body
    res = reference.compare_part(body, k, m, files[::-1], algo)
    assert res == {"frames": (k + m) * len(blocks), "bad_bytes": 0,
                   "bad_digest": 0, "shards_missing": 0}
    broken = list(files)
    broken[7] = broken[7][:3] + bytes([broken[7][3] ^ 1]) + broken[7][4:]
    res = reference.compare_part(body, k, m, broken[:-1], algo)
    assert res["bad_digest"] == 1 and res["shards_missing"] == 1
    assert res["bad_bytes"] == 0
    # A data byte flipped in the last frame of a parity shard's file.
    broken = list(files)
    broken[9] = broken[9][:-1] + bytes([broken[9][-1] ^ 1])
    res = reference.compare_part(body, k, m, broken, algo)
    # (A file whose first block is wrong is no shard's: one frame of
    # wrong bytes, and its shard is missing.)
    assert (res["bad_bytes"], res["bad_digest"], res["shards_missing"]) == \
        (1, 0, len(blocks) == 1)


def test_sound_run_is_correct(monkeypatch):
    result = test_correct.drive(monkeypatch, EC6P6, None)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault,number", [
    ("lose_shard", "shards_missing"),           # control
    ("flip_parity", "frames_bad_bytes"),
])
def test_fault_is_not_correct(monkeypatch, fault, number):
    result = test_correct.drive(monkeypatch, EC6P6, fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0, result["compared"]
