"""Readings that set a cell's comparison limit, on the chip.

    python3 bench/calibrate.py --workload brain16k_1chip.async --seeds 1,2,3

For each seed, in one process: the cell's set-up as ``run.py`` makes it,
one call of the timed chunk at the cell's size, then, with the program's
state freed, the float64 replay of its raster by the network family
(``bench/models/<family>.py``).  Per seed it prints the program's widest
gap and the family's controls' (``controls``, each the reference in a
lower precision put in the program's place); for ``brain_model`` the
reference computed in bfloat16, float32 with bfloat16 weights, and, for
information, plain float32.  The last line is a JSON summary: the lower
reading (largest program gap), each control's smallest gap, the upper
reading (the smallest of the controls that the cell's limits file
requires to fail, ``controls``) and whether every one of those fails.

With ``--fault <name>`` a fault of ``bench/tests/faults.py`` is planted
in the program first, and the program's gaps are the fault's readings at
the cell's own size (``program_min`` is the smallest).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1]), str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


def reading(cell: run.Cell, seed: int) -> dict:
    built = run.build(cell, seed)
    key = run.seed_key(seed)
    steps = int(cell.mix["chunk_steps"])
    compiled, args, _ = built.engine.compile(steps, key=key)
    raster = np.asarray(compiled(*args))
    del compiled, args
    built.engine = None
    gc.collect()
    fam, net, cfg, mix = built.family, built.net, cell.config, cell.mix
    t = time.perf_counter()
    v = fam.check(raster, net, key, cfg, mix)
    out = {"seed": seed, "gap_mV": v.gap_mV, "flips": v.flips, "spikes": v.spikes,
           "check_s": time.perf_counter() - t}
    for name, control in fam.controls.items():
        c = control(raster, net, key, cfg, mix)
        out[f"control_{name}_gap_mV"] = c.gap_mV
        out[f"control_{name}_flips"] = c.flips
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault", help="plant this fault of bench/tests/faults.py")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    device = run.require_chips(cell.chips)
    run.use_cache()
    if args.fault:
        from bench.tests import faults

        faults.install(args.fault, run.family(cell.config).neuron_step)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(reading(cell, seed))
        print(json.dumps(rows[-1]), flush=True)
    control_min = {c: min(r[f"control_{c}_gap_mV"] for r in rows)
                   for c in run.family(cell.config).controls}
    required = cell.limits["controls"]
    print(json.dumps({
        "workload": cell.name,
        "device": device,
        "seeds": len(rows),
        "fault": args.fault,
        "lower": max(r["gap_mV"] for r in rows),
        "program_min": min(r["gap_mV"] for r in rows),
        "upper": min(control_min[c] for c in required),
        "control_min": control_min,
        "limit": cell.limits["gap_mV"],
        "required_controls_fail": all(control_min[c] > cell.limits["gap_mV"] for c in required),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
