"""End-to-end runs of the harness on the CPU at 512 neurons, with the
look for a chip skipped: a sound run is correct, each planted fault is
not, a run without the chips exits non-zero with no result, and a new mix
file is a runnable cell with no existing file edited."""
from __future__ import annotations

import json

import pytest

from bench.tests.conftest import run_cell, write_small_root


@pytest.mark.parametrize("workload", ["tiny_1chip.async", "tiny_2x2.async", "tiny_2x2.tonic"])
def test_sound_run_is_correct(small_root, workload):
    rc, res, out, err = run_cell(small_root, workload, seed=3_000_000_019)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"steps_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-2].startswith("gap_mV ")
    assert "rate: " in out


@pytest.mark.parametrize(
    "workload,fault",
    [
        ("tiny_1chip.tonic", "state_unchanged"),
        ("tiny_1chip.tonic", "half_left_out"),
        ("tiny_1chip.async", "altered"),
        ("tiny_2x2.tonic", "no_exchange"),
    ],
)
def test_fault_is_not_correct(small_root, workload, fault):
    rc, res, _, err = run_cell(small_root, workload, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["gap_mV"]["value"] > res["checks"]["gap_mV"]["limit"]


@pytest.mark.parametrize("devices", [1, 2])
def test_without_the_chips_no_result(small_root, devices):
    """No TPU at all (the CPU), or too few devices: exit non-zero, no JSON."""
    rc, res, out, _ = run_cell(small_root, "tiny_2x2.async", skip_chip=False, devices=devices)
    assert rc != 0
    assert res is None and "{" not in out


def test_new_mix_file_is_a_cell(tmp_path):
    burst = {"regime": "test", "chunk_steps": 300, "i_ext": 2.0, "noise_sigma": 2.0}
    small = write_small_root(tmp_path, mixes={"strong": burst})
    spec = json.loads((small / "BENCHMARK.json").read_text())
    assert "tiny_1chip.strong" in {w["name"] for w in spec["workloads"]}
    rc, res, out, err = run_cell(small, "tiny_1chip.strong")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert "300 steps" in out
