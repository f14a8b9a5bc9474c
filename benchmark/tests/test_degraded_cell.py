"""The cell `ec8p4-64m-degraded-get` (PR 37): `hide_shards` in the loader and
in a run, and `correct` shown to fail on it.  Run by hand, on the CPU
backend, at the rehearsal's sizes, like `test_correct.py`:

    python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

sys.path.insert(0, HERE)

import controls  # noqa: E402
import run  # noqa: E402
import test_correct  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

CELL = "ec8p4-64m-degraded-get"
TRAFFIC = os.path.join("tests", "traffic")


@pytest.fixture(scope="module")
def bench() -> dict:
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_the_issues(bench):
    wl, cfg = traffic.load_cell(bench, CELL)
    assert (cfg["name"], cfg["env"], cfg["server_args"]) == \
        ("ec8p4-12drive", {}, [])
    want = {"loop": "closed", "clients": 8, "stagger_s": 0.8,
            "object_bytes": 67108864, "part_bytes": 0, "mix": {"GET": 1},
            "prefill_per_client": 4, "put_key_ring": 0, "pool_buffers": 2,
            "hide_shards": 2, "reuse_get_buffer": True,
            "check": {"readback_objects": 0, "disk_parts": 4,
                      "deleted_gets": 0}}
    assert {k: wl[k] for k in want} == want
    small, _ = traffic.load_cell(bench, CELL, TRAFFIC)
    assert set(small) == set(wl) - {"source", "assumed", "reduced"}
    assert small["mix"] == wl["mix"] and small["hide_shards"] == 2
    ends = {m["name"] for m in run.cell_metrics(bench, "end_to_end", CELL)}
    assert ends == {"get_gbps", "get_p95_ms", "setup_s"}
    layers = {m["name"] for m in run.cell_metrics(bench, "per_layer", CELL)}
    assert {"decode_roofline", "d2h_bytes_per_byte.get",
            "decode_blocks_pct.get", "device_idle_pct.get"} <= layers
    assert not {n for n in layers if n.endswith(".put")} | \
        layers & {"encode_roofline"}


@pytest.mark.parametrize("change,why", [
    ({"hide_shards": 5}, "parity shards"),
    ({"hide_shards": -1}, "parity shards"),
    ({"prefill_per_client": 0}, "nothing is prefilled"),
    ({"mix": {"GET": 9, "PUT": 1}}, "writes or deletes"),
    ({"mix": {"GET": 9, "DELETE": 1}}, "writes or deletes"),
    ({"part_bytes": 5247201}, "multipart"),
])
def test_loader_refuses(bench, tmp_path, change, why):
    wl, _ = traffic.load_cell(bench, CELL, TRAFFIC)
    with open(tmp_path / "64m-degraded-get.json", "w") as f:
        json.dump(dict(wl, **change), f)
    with pytest.raises(ValueError, match=why) as e:
        traffic.load_cell(bench, CELL, str(tmp_path))
    assert "64m-degraded-get.json" in str(e.value)


def test_hidden_shards_differ_by_object_and_by_seed():
    picks = {(s, c, n): tuple(traffic.hidden_shards(s, c, n, 8, 2))
             for s in (1, 2**31 + 7) for c in range(8) for n in range(4)}
    assert all(len(set(p)) == 2 and set(p) < set(range(8))
               for p in picks.values())
    assert len(set(picks.values())) > 12            # of the 28 pairs
    assert [picks[1, c, n] for c in range(8) for n in range(4)] != \
        [picks[2**31 + 7, c, n] for c in range(8) for n in range(4)]
    assert picks[1, 0, 0] == tuple(traffic.hidden_shards(1, 0, 0, 8, 2))


def test_decode_roofline_reads_decode_work_where_shards_are_hidden():
    how = traffic.load_metric("decode_roofline")
    q = {"client": {"get_bytes": 4e9, "put_bytes": 0.0},
         "trace": {"busy_s": 0.5}, "device_kind": "TPU v5 lite", "hidden": 2}
    cfg = {"data_shards": 8, "parity_shards": 4, "bitrot_algo": "mxh256"}
    least = work.least_seconds(work.decode_work(4e9, 8, 2), "TPU v5 lite")
    assert run.read_metric(how, q, cfg) == 100.0 * least["seconds"] / 0.5
    assert run.read_metric(how, dict(q, hidden=0), cfg) is None
    assert run.read_metric(how, dict(q, trace=None), cfg) is None
    # The PUT cell's reader is as it was: encode work for the PUT bytes.
    enc = traffic.load_metric("encode_roofline")
    q["client"]["put_bytes"] = 4e9
    least = work.least_seconds(work.encode_work(4e9, 8, 4), "TPU v5 lite")
    assert run.read_metric(enc, q, cfg) == 100.0 * least["seconds"] / 0.5


def test_sound_run_is_correct(monkeypatch):
    result = test_correct.drive(monkeypatch, CELL, None)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is True, compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert compared["gets_served_healthy"] == 0
    assert compared["decode_blocks_short"] == 0
    assert compared["hidden_files_back"] == 0
    assert compared["disk_parts_compared"] == 2
    assert compared["frames_compared"] == 2 * 12 * 6
    assert compared["gets_compared"] == result["attempted"]
    assert result["compared"]["readbacks_compared"]["at_least"] == 0


def test_hiding_skipped_is_not_correct(monkeypatch):
    """The control: the cell states that every read finds two data shards
    gone; with nothing hidden every read is a healthy one."""
    monkeypatch.setattr(run, "hide_shards", controls.look_only)
    result = test_correct.drive(monkeypatch, CELL, None)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"] is False
    assert compared["gets_served_healthy"] > 0, compared
    assert compared["decode_blocks_short"] > 0, compared
    assert compared["get_mismatch"] == 0 and compared["shards_missing"] == 0


@pytest.mark.parametrize("fault,number", [
    ("flip_rebuilt", "get_mismatch"),
    ("flip_get", "get_mismatch"),
    ("lose_shard", "shards_missing"),       # seen by the look before hiding
])
def test_fault_is_not_correct(monkeypatch, fault, number):
    result = test_correct.drive(monkeypatch, CELL, fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0, result["compared"]
