"""A traced run of one cell, read by the step's named scopes.

    python3 bench/scoped.py --workload brain16k_2x2.async --seed 7

Runs the cell as ``run.py --trace 1`` does (set-up, a warm-up chunk,
``run.TRACE_CHUNKS`` chunks under ``jax.profiler``), with the program's
tracer (``repro.obs``) on from the start of set-up, so that its set-up
spans and counters are recorded.  After the traced window and the
peak-memory reading it compiles the step again with the compilation
cache off, takes the op -> scope map from that HLO text and checks that
the traced ops are in it (``scopes.py``).  The last line of standard
output is JSON: per step, in ms, each scope's device time on the slowest
chip, the in-step idle on the idlest chip and ``trace.py``'s layers; the
program's spans (seconds) and counters; the idle inside programs by the
scope of the op that ends it.  ``--seconds`` adds a timed closed loop
before the traced one, for the tracer's cost.  ``--save PATH`` keeps the
trace with its scopes (``scopes.save``); with ``--chunks 2 --chunk-steps
20`` that is the fixture of ``bench/tests/test_scopes.py``.

Nothing here is a metric of ``BENCHMARK.json``: ``run.py`` hands its
readers neither the HLO text nor the program's spans.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402
from bench import scopes as sc  # noqa: E402
from bench import trace as tr  # noqa: E402

TRACE_DIR = ROOT / "bench" / ".trace" / "scoped"


def program_record(events: list[dict]) -> tuple[dict[str, float], dict[str, dict]]:
    """Span name -> seconds (summed) and counter name -> last values, from
    the tracer's events."""
    spans: dict[str, float] = {}
    counters: dict[str, dict] = {}
    for e in events:
        if e["ph"] == "X":
            spans[e["name"]] = spans.get(e["name"], 0.0) + e["dur"] * 1e-6
        elif e["ph"] == "C":
            counters[e["name"]] = e["args"]
    return spans, counters


def traced(cell: run.Cell, seed: int, *, chunks: int, seconds: float = 0.0) -> dict:
    """Set up ``cell``, trace ``chunks`` chunks and read them by scope."""
    import jax

    from repro import obs

    obs.enable()
    t_build = time.perf_counter()
    built = run.build(cell, seed)
    key = run.seed_key(seed)
    steps = int(cell.mix["chunk_steps"])
    compiled, args, compile_s = built.engine.compile(steps, key=key)
    np.asarray(compiled(*args))  # warm-up chunk
    setup_s = time.perf_counter() - T0
    spans, counters = program_record(obs.events())
    out = {"setup_s": setup_s, "start_s": t_build - T0, "compile_s": compile_s,
           "build_s": built.build_s, "spans": spans, "counters": counters}
    if seconds:
        win = run.closed_loop(compiled, args, seconds=seconds)
        out["timed_steps_per_s"] = steps * len(win.rasters) / win.seconds
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(str(TRACE_DIR)):
        win = run.closed_loop(compiled, args, chunks=chunks)
    events = tr.load_xplane(tr.find_xplane(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out["trace_steps_per_s"] = steps * len(win.rasters) / win.seconds
    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())
    del compiled, args
    obs.disable()

    scopes = sc.step_scopes()
    smap = sc.scope_map(sc.fresh_hlo_text(built.engine, steps, key), scopes)
    sc.check_fresh(events, smap)
    n = steps * len(win.rasters)
    chips = sc.reduce(events, smap)
    red = tr.reduce(events)
    per_step = {s: sc.per_step_ms(chips, s, n) for s in scopes + (sc.UNSCOPED,)}
    per_step["step_idle"] = sc.step_idle_ms(chips, n)
    for layer in ("accumulation", "update", "exchange"):
        per_step["trace." + layer] = float(red.per_chip(layer).max()) / n * 1e3
    leaf = max(c.leaf for c in chips.values())
    out.update(
        steps=n,
        scopes_in_hlo=sorted(set(smap.values()) - {sc.UNSCOPED}),
        ms_per_step=per_step,
        unscoped_share=max(c.scopes.get(sc.UNSCOPED, 0.0) / c.leaf for c in chips.values()),
        leaf_s=leaf,
        idle_by_scope=sc.idle_by_scope(chips),
        device_idle_share=100.0 * (1.0 - red.per_chip("busy").min() / red.window_s),
        events=events,
        smap=smap,
    )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chunks", type=int, default=run.TRACE_CHUNKS)
    ap.add_argument("--chunk-steps", type=int, help="steps a chunk (default: the mix's)")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--save", type=Path)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    if args.chunk_steps:
        cell = dataclasses.replace(cell, mix=dict(cell.mix, chunk_steps=args.chunk_steps))
    device = run.require_chips(cell.chips)
    run.use_cache()
    out = traced(cell, args.seed, chunks=args.chunks, seconds=args.seconds)
    events, smap = out.pop("events"), out.pop("smap")
    if args.save:
        sc.save(events, smap, args.save)
    print(f"unscoped device time: {out['ms_per_step'][sc.UNSCOPED]} ms a step, "
          f"{100 * out['unscoped_share']}% of leaf time", file=sys.stderr)
    print(json.dumps({"workload": cell.name, "seed": args.seed, "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
