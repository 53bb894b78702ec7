"""Helpers for the benchmark's own tests (run with ``pytest bench/tests``).

Harness runs go through a subprocess with fake CPU devices, the tiny
cells of ``small_root`` and the look for a chip skipped; the program
under them may be broken on purpose (``faults.py``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {"populations": 32, "neurons_per_pop": 16}


def write_small_root(root: Path, mixes: dict[str, dict] | None = None) -> Path:
    """A checkout-like directory: the network families of ``bench/models/``,
    ``BENCHMARK.json`` with the real metrics and cells ``tiny_1chip.<mix>``
    and ``tiny_2x2.<mix>`` of 512 neurons, for the real mixes and any in
    ``mixes``, each with the limits of ``brain16k_1chip.<mix>`` (of
    ``brain16k_1chip.async`` for a new mix)."""
    bench = root / "bench"
    for d in ("configs", "mixes", "limits", "models"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    for p in (ROOT / "bench" / "models").glob("*.py"):
        (bench / "models" / p.name).write_text(p.read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = json.loads((ROOT / "bench" / "configs" / "brain16k_1chip.json").read_text())
    for name, mesh, exchange in (("tiny_1chip", [1, 1], "sparse"), ("tiny_2x2", [2, 2], "ragged")):
        cfg = dict(base, **TINY, mesh=mesh, exchange=exchange)
        cfg["neurons_per_device"] = 512 // (mesh[0] * mesh[1])
        cfg["model"] = dict(base["model"], n_regions=8)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    all_mixes = {p.stem: json.loads(p.read_text()) for p in (ROOT / "bench" / "mixes").glob("*.json")}
    all_mixes.update(mixes or {})
    cells = []
    for mix, body in all_mixes.items():
        (bench / "mixes" / f"{mix}.json").write_text(json.dumps(body))
        for cfg, chips in (("tiny_1chip", 1), ("tiny_2x2", 4)):
            cells.append({"name": f"{cfg}.{mix}", "config": cfg, "traffic": mix,
                          "chips": chips, "why": "test"})
            limits = ROOT / "bench" / "limits" / f"brain16k_1chip.{mix}.json"
            if not limits.exists():
                limits = ROOT / "bench" / "limits" / "brain16k_1chip.async.json"
            (bench / "limits" / f"{cfg}.{mix}.json").write_text(limits.read_text())
    spec["workloads"] = cells
    for m in spec["per_layer"]:
        m["workloads"] = [w.replace("brain16k_", "tiny_") for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


DRIVER = """
import sys
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench import run
from bench.tests import faults
run.ROOT = Path({small!r})
if {skip_chip!r}:
    run.require_chips = lambda n: {{"platform": "cpu", "kind": "cpu", "count": n}}
faults.install({fault!r}, run.family(run.load_cell({workload!r}).config).neuron_step)
sys.exit(run.main({argv!r}))
"""


def run_cell(small: Path, workload: str, *, seed: int = 11, seconds: float = 1.0,
             fault: str | None = None, skip_chip: bool = True, devices: int = 4):
    """Run ``bench/run.py``'s ``main`` in a subprocess; returns
    ``(returncode, result or None, stdout, stderr)``."""
    code = DRIVER.format(root=str(ROOT), src=str(ROOT / "src"), small=str(small),
                         skip_chip=skip_chip, fault=fault, workload=workload,
                         argv=["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", "0"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    result = None
    lines = out.stdout.strip().splitlines()
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return out.returncode, result, out.stdout, out.stderr


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    return write_small_root(tmp_path_factory.mktemp("checkout"))
