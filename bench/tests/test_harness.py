"""End-to-end runs of the harness on the CPU at 512 neurons, with the
look for a chip skipped: a sound run is correct, each planted fault is
not, a run without the chips exits non-zero with no result, and a new mix
file, or a new network family with its configuration, is a runnable cell
with no existing file edited."""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

import pytest

from bench.tests.conftest import ROOT, run_cell, write_small_root


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


def file_hashes(root: Path) -> dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in root.rglob("*")
            if p.is_file() and not {"__pycache__", ".jax_cache", ".trace"} & set(p.parts)}


EI_CONFIG = {
    "network": "ei_indegree", "neurons": 512, "excitatory_frac": 0.8, "c_e": 40, "c_i": 10,
    "j": 0.4, "g": 5.0, "connectome_seed": 2000, "mesh": [2, 2], "exchange": "ragged",
    "lif": {"tau_m": 10.0, "v_rest": -65.0, "v_reset": -65.0, "v_thresh": -50.0, "r_m": 10.0,
            "t_refrac": 2.0, "dt": 0.1},
}


def test_new_model_file_is_a_config(tmp_path):
    """A network family of its own (``data/ei_indegree.py``: fixed
    in-degree, its own float64 reference) becomes a cell by new files and
    new entries of ``BENCHMARK.json`` alone: the cell runs correct, a
    planted fault fails it, and no file that was there changes."""
    small = write_small_root(tmp_path)
    before, repo_before = file_hashes(small), file_hashes(ROOT / "bench")
    spec = json.loads((small / "BENCHMARK.json").read_text())

    bench = small / "bench"
    (bench / "models" / "ei_indegree.py").write_text(
        (ROOT / "bench" / "tests" / "data" / "ei_indegree.py").read_text())
    (bench / "configs" / "tiny_ei.json").write_text(json.dumps(EI_CONFIG))
    (bench / "limits" / "tiny_ei.async.json").write_text(
        json.dumps({"gap_mV": 0.03, "controls": ["bfloat16"]}))
    grown = json.loads(json.dumps(spec))
    grown["configs"].append({"name": "tiny_ei", "source": "Brunel 2000, J. Comput. Neurosci. 8:183",
                             "file": "bench/configs/tiny_ei.json", "reduced": [], "why": "test"})
    grown["workloads"].append({"name": "tiny_ei.async", "config": "tiny_ei", "traffic": "async",
                               "chips": 4, "why": "test"})
    (small / "BENCHMARK.json").write_text(json.dumps(grown))

    rc, res, out, err = run_cell(small, "tiny_ei.async")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert set(res["metrics"]) == {"steps_per_s", "setup_s"}
    assert "1000 steps" in out
    for fault in ("state_unchanged", "half_left_out", "no_exchange", "altered"):
        rc, res, _, err = run_cell(small, "tiny_ei.async", fault=fault)
        assert rc == 0, err[-3000:]
        assert res["correct"] is False, fault

    after = file_hashes(small)
    assert {p: after[p] for p in before if p != "BENCHMARK.json"} == {
        p: h for p, h in before.items() if p != "BENCHMARK.json"}
    now = json.loads((small / "BENCHMARK.json").read_text())
    assert now["configs"][:-1] == spec["configs"] and now["workloads"][:-1] == spec["workloads"]
    assert {k: v for k, v in now.items() if k not in ("configs", "workloads")} == {
        k: v for k, v in spec.items() if k not in ("configs", "workloads")}
    assert file_hashes(ROOT / "bench") == repo_before
