"""The four-set, four-chip cell `ec2p2x4-10m-mixed-4chip` (PR 29): its data
files load, and `correct` has been shown to fail on it.  Run by hand, on the
CPU backend with four virtual devices, at the rehearsal's sizes, like
`test_correct.py`:

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

import run  # noqa: E402
import test_correct  # noqa: E402
import traffic  # noqa: E402

CELL = "ec2p2x4-10m-mixed-4chip"
TRAFFIC = os.path.join("tests", "traffic")


@pytest.fixture(scope="module")
def bench() -> dict:
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traffic_dir", [None, TRAFFIC])
def test_cell_loads(bench, traffic_dir):
    wl, cfg = traffic.load_cell(bench, CELL, traffic_dir)
    assert (cfg["drives"], cfg["sets"], cfg["chips"]) == (16, 4, 4)
    assert (cfg["data_shards"], cfg["parity_shards"]) == (2, 2)
    assert cfg["server_args"] == ["--set-drive-count", "4"]
    assert cfg["env"] == {} and cfg["reduced"] == []
    assert wl["object_bytes"] == 10 << 20 and wl["part_bytes"] == 0
    assert wl["mix"] == {"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10}
    if traffic_dir is None:
        assert wl["clients"] == 16 and wl["prefill_per_client"] == 16


def test_every_per_layer_metric_of_the_cell_has_a_reader(bench):
    mine = run.cell_metrics(bench, "per_layer", CELL)
    names = {m["name"] for m in mine}
    assert {"lane_mean_no_work_pct.get", "lane_mean_device_wait_pct.get",
            "rows_per_padded_row.get", "encode_blocks_on_lane_pct.put",
            "device_idle_pct.get", "compile_ms_in_window.get"} <= names
    # The lane sums and the PUT cell's roofline are one-chip metrics.
    assert not names & {"lane_no_work_pct.get", "lane_device_wait_pct.get",
                        "encode_roofline"}
    for m in mine:
        assert traffic.load_metric(m["name"])["kind"] in (
            "ratio", "trace_idle")


def drive(monkeypatch, fault: str | None) -> dict:
    """`test_correct.drive` with four virtual devices for the child, so
    that the harness finds the chips the configuration maps onto."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    return test_correct.drive(monkeypatch, CELL, fault)


def test_sound_four_set_run_is_correct(monkeypatch):
    result = drive(monkeypatch, None)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4


def test_lost_shard_on_one_set_is_not_correct(monkeypatch):
    """The fault loses the part files of drive 1 only, so of set 0's
    objects only: the sample of the drives still finds them."""
    result = drive(monkeypatch, "lose_shard")
    assert result["correct"] is False
    assert result["compared"]["shards_missing"]["value"] > 0, \
        result["compared"]
