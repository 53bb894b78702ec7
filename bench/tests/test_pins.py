"""What the harness reads of the tiny cells, pinned: for each cell and
seed of ``data/pins_tiny.json``, the SHA-1 of each distinct raster of a
two-chunk closed loop, ``gap_mV``, the controls' gaps and the ``Work``
arrays, as the harness read them before network families were modules
(recorded on the CPU).  The harness has to reproduce them bit for bit."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

PINS = json.loads((ROOT / "bench" / "tests" / "data" / "pins_tiny.json").read_text())

READ = """
import hashlib, json, sys
from pathlib import Path
import numpy as np
sys.path[:0] = [{root!r}, {src!r}]
from bench import run
out = []
for workload, seed in {cases!r}:
    cell = run.load_cell(workload, Path({small!r}))
    built = run.build(cell, seed)
    key = run.seed_key(seed)
    steps = int(cell.mix["chunk_steps"])
    compiled, args, _ = built.engine.compile(steps, key=key)
    win = run.closed_loop(compiled, args, chunks=2)
    m = built.net.n_neurons
    valid = [r for r in win.rasters if r.shape == (steps, m) and ((r == 0) | (r == 1)).all()]
    distinct = {{hashlib.sha1(r.tobytes()).hexdigest(): r for r in valid}}
    fam, judged = built.family, (built.net, key, cell.config, cell.mix)
    w = fam.work(valid, built.net, int(np.prod(cell.config["mesh"])))
    out.append({{
        "workload": workload, "seed": seed, "chunks": len(valid), "rasters": list(distinct),
        "gap_mV": max(fam.check(r, *judged).gap_mV for r in distinct.values()),
        "controls": {{name: max(fam.controls[name](r, *judged).gap_mV for r in distinct.values())
                     for name in ("bfloat16", "bfloat16_weights")}},
        "work": {{"steps": w.steps, **{{f: getattr(w, f).tolist() for f in
                 ("accum_ops", "accum_bytes", "state_bytes", "exchange_bytes")}}}},
    }})
print(json.dumps(out))
"""


@pytest.mark.parametrize("workload", sorted({p["workload"] for p in PINS}))
def test_tiny_outputs_are_pinned(small_root, workload):
    pins = [p for p in PINS if p["workload"] == workload]
    code = READ.format(root=str(ROOT), src=str(ROOT / "src"), small=str(small_root),
                       cases=[(p["workload"], p["seed"]) for p in pins])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == pins
