"""Record the small chip trace that ``test_trace.py`` reduces by hand.

    python3 bench/tests/record_trace.py            # on one chip
    python3 bench/tests/record_trace.py            # on a 2x2 host: the mesh

Runs the harness's set-up and closed loop (``run.build``,
``run.closed_loop``) for two chunks of 20 steps of a 512-neuron network
(the one-chip configuration on one chip, the 2x2 one on four) under
``jax.profiler``, and writes the events ``trace.load_xplane`` keeps to
``chiprun_out/trace_<n>chips.json.gz``; copied into ``bench/tests/data/``
it is the fixture.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench import trace as tr  # noqa: E402

STEPS = 20
SEED = 20260


def small_cell(chips: int) -> run.Cell:
    name = "brain16k_1chip" if chips == 1 else "brain16k_2x2"
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(populations=32, neurons_per_pop=16, neurons_per_device=512 // chips)
    cfg["model"] = dict(cfg["model"], n_regions=8)
    mix = json.loads((ROOT / "bench" / "mixes" / "tonic.json").read_text())
    mix["chunk_steps"] = STEPS
    return run.Cell(name="fixture", config=cfg, mix=mix, chips=chips,
                    end_to_end=[], per_layer=[], limits={})


def main() -> int:
    import jax

    run.use_cache()
    chips = jax.device_count()
    run.require_chips(chips)
    built = run.build(small_cell(chips), SEED)
    compiled, args, _ = built.engine.compile(STEPS, key=run.seed_key(SEED))
    jax.block_until_ready(compiled(*args))
    log = ROOT / "chiprun_out" / f"trace_{chips}chips"
    shutil.rmtree(log, ignore_errors=True)
    with jax.profiler.trace(str(log)):
        win = run.closed_loop(compiled, args, chunks=2)
    events = tr.load_xplane(tr.find_xplane(log))
    out = ROOT / "chiprun_out" / f"trace_{chips}chips.json.gz"
    tr.save(events, out)
    shutil.rmtree(log)
    red = tr.reduce(events)
    print(f"{len(events)} events, {win.attempted} chunks -> {out}")
    print(json.dumps({str(k): vars(c) | {"gaps": len(c.gaps), "ops": len(c.ops)}
                      for k, c in red.chips.items()}))
    print(json.dumps(red.breakdown()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
