"""`correct` has been shown to fail: run by hand, on the CPU backend, at the
rehearsal's sizes (tier-1 collects `tests/` only).

    python3 -m pytest benchmark/tests -q

Each case skips the harness's look for a chip and drives the rest of a run
(`run.run_cell`: boot, prefill, warm-up, window, drain, checks, SIGTERM) with
the serving process broken underneath by `faulty_serve.py`, and sees `correct`
come out false through the number that fault is for.  The same faults at the
cells' own sizes, on the chip: `controls.py`.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

FAULTY = os.path.join(HERE, "faulty_serve.py")
TRAFFIC = os.path.join("tests", "traffic")
MIXED, MP = "ec2p2-1m-mixed", "ec8p4-mp64-put"


def drive(monkeypatch, cell: str, fault: str | None) -> dict:
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    if fault:
        monkeypatch.setenv("BENCH_FAULT", fault)
    result = run.run_cell(
        cell, seed=2**31 + 11, seconds=4.0, trace=False, traffic_dir=TRAFFIC,
        require_chip=False,
        serve_script=FAULTY if fault else os.path.join(run.HERE, "serve.py"))
    assert result is not None
    return result


@pytest.mark.parametrize("cell", [MIXED, MP])
def test_sound_run_is_correct(monkeypatch, cell):
    result = drive(monkeypatch, cell, None)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("cell,fault,number", [
    (MIXED, "lose_shard", "shards_missing"),        # control
    (MIXED, "bad_digest", "frames_bad_digest"),     # control
    (MIXED, "flip_parity", "frames_bad_bytes"),
    (MIXED, "flip_get", "get_mismatch"),
    (MIXED, "keep_deleted", "deleted_still_served"),
    (MP, "lose_shard", "shards_missing"),           # control
    (MP, "bad_digest", "frames_bad_digest"),        # control
    (MP, "flip_parity", "frames_bad_bytes"),
    (MP, "flip_get", "readback_mismatch"),
])
def test_fault_is_not_correct(monkeypatch, cell, fault, number):
    result = drive(monkeypatch, cell, fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0, result["compared"]
