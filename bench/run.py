"""One run of one benchmark cell of the distributed spiking engine.

    python3 bench/run.py --workload brain16k_1chip.async --seed 7 \\
        --seconds 20 --trace 0

A cell ``<config>.<mix>`` of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``: network, neuron model, chips, exchange)
and a traffic mix (``bench/mixes/<mix>.json``: drive, noise and chunk
length); its comparison limit is ``bench/limits/<cell>.json`` and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  A run:

1. fails, printing no result, unless JAX sees TPU chips, exactly as many
   as the cell asks for;
2. keeps JAX's compilation cache at a fixed path inside the checkout
   (``.jax_cache/``);
3. makes the network (``network.py``: the configuration's connectome,
   weights from the seed), hands it to the program (Algorithm-1 partition, block-CSR tiles, ``DistributedSNN``),
   compiles one chunk of ``chunk_steps`` steps and runs it once: all of
   this is set-up;
4. measures a closed loop of chunks for ``--seconds``: each chunk is one
   call of the compiled step, and its raster is on the host before the
   next is dispatched (a user who records spikes);
5. frees the program's state and replays every distinct raster against
   the float64 reference (``reference.py``).

With ``--trace 1`` the loop is a few chunks under ``jax.profiler``, and
the per-layer metrics come from that trace, the work counts and the
set-up.  The last line of standard output is the result as JSON; the
numbers compared are the last lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import network as nw  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench import work as wk  # noqa: E402

TRACE_CHUNKS = 3  # chunks under the profiler in a --trace 1 run
TRACE_DIR = BENCH / ".trace"  # profiler output, read and deleted by the run
PLATFORM = "tpu"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict


def load_cell(name: str, root: Path | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files, found by name
    (under ``root``, the checkout by default)."""
    root = ROOT if root is None else root
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    bench = root / "bench"

    def applies(m: dict, reported: set[str] | None = None) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name,
        config=json.loads((bench / "configs" / f"{w['config']}.json").read_text()),
        mix=json.loads((bench / "mixes" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
        end_to_end=e2e,
        per_layer=[m for m in spec["per_layer"] if applies(m, names)],
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
    )


def require_chips(n: int) -> dict:
    """The device as JAX reports it; exit non-zero unless it is ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != PLATFORM or info["count"] != n:
        print(f"FAIL: the cell needs {n} {PLATFORM} chip(s); JAX sees {info}", file=sys.stderr)
        raise SystemExit(2)
    return info


def use_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program the run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def seed_key(seed: int):
    """The JAX key of a seed of any size."""
    import jax

    word = int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.random.PRNGKey(word)


@dataclasses.dataclass
class Built:
    engine: object
    net: nw.Network
    build_s: float  # the program's build: partition, tiling, engine
    network_s: float  # making the network and its tiles (benchmark)
    lif: ref.Lif


def network(cfg: dict, seed: int) -> tuple[nw.Network, float]:
    """The configuration's network in the layout of the program's
    partition, and the seconds that partition took.

    The connectome (populations, which neuron pairs are synapses, which
    neurons are inhibitory) and the partition come from the
    configuration's ``connectome_seed``; the run's ``seed`` draws the
    synaptic weights.  So every seed gives the program the same tile
    occupancy, exchange plan and compiled step, with other weights and
    other spikes.
    """
    from repro.core import build_graph, greedy_partition

    rng = np.random.default_rng(cfg["connectome_seed"])
    pops = nw.brain_model(rng, n_populations=cfg["populations"], **cfg["model"])
    syn = nw.synapses(rng, np.random.default_rng(seed), pops.pair_probs(),
                      cfg["neurons_per_pop"], **cfg["synapses"])
    n_dev = int(np.prod(cfg["mesh"]))
    if syn.n_neurons != n_dev * cfg["neurons_per_device"]:
        raise ValueError(f"{syn.n_neurons} neurons over {n_dev} chips is not "
                         f"{cfg['neurons_per_device']} a chip")

    t = time.perf_counter()
    graph = build_graph(pops.src, pops.dst, pops.prob, pops.weights)
    part = greedy_partition(graph, n_dev, seed=cfg["connectome_seed"])
    partition_s = time.perf_counter() - t

    order = nw.layout(nw.equal_blocks(part.assign, n_dev), cfg["neurons_per_pop"])
    position_of = np.empty_like(order)
    position_of[order] = np.arange(order.shape[0])
    return nw.Network.from_synapses(syn, position_of), partition_s


def build(cell: Cell, seed: int) -> Built:
    """Make the network from the seed and hand it to the program."""
    import jax
    from jax.sharding import AxisType

    from repro.kernels import KernelPolicy
    from repro.snn import BlockSynapses, DistributedSNN, LIFParams

    cfg, mix = cell.config, cell.mix
    n_dev = int(np.prod(cfg["mesh"]))
    t0 = time.perf_counter()
    net, build_s = network(cfg, seed)
    src, dst, blocks = net.tiles(n_dev)

    t = time.perf_counter()
    network_s = t - t0 - build_s
    tiles = BlockSynapses.from_tiles(src, dst, blocks, n_dev)
    del blocks
    mesh = jax.make_mesh(tuple(cfg["mesh"]), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
    engine = DistributedSNN(
        mesh=mesh,
        params=LIFParams(**cfg["lif"], noise_sigma=mix["noise_sigma"]),
        exchange=cfg["exchange"],
        i_ext=mix["i_ext"],
        syn=tiles,
        policy=KernelPolicy(use_pallas=jax.devices()[0].platform == PLATFORM),
    )
    build_s += time.perf_counter() - t
    return Built(engine=engine, net=net, build_s=build_s, network_s=network_s,
                 lif=ref.Lif(**cfg["lif"]))


@dataclasses.dataclass
class Window:
    rasters: list[np.ndarray]
    attempted: int
    errors: int
    seconds: float
    compiles: int


def closed_loop(compiled, args, *, seconds: float = 0.0, chunks: int = 0) -> Window:
    """Dispatch a chunk, bring its raster to the host, repeat: for
    ``seconds`` of wall time, or for ``chunks`` chunks."""
    import jax

    compiles = []

    def on_event(event: str, *_, **__):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    rasters, attempted, errors = [], 0, 0
    t_start = time.perf_counter()
    t_end = t_start
    try:
        while (t_end - t_start < seconds) if chunks == 0 else (attempted < chunks):
            attempted += 1
            try:
                with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "dispatch"):
                    out = compiled(*args)
                with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "fetch"):
                    rasters.append(np.asarray(out))
            except Exception as e:  # a failed chunk is counted, the loop goes on
                errors += 1
                print(f"chunk {attempted} failed: {e!r}", file=sys.stderr)
            t_end = time.perf_counter()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return Window(rasters, attempted, errors, t_end - t_start, len(compiles))


def read_metric(name: str, run) -> float | None:
    """Call ``bench/metrics/<name>.py``'s ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@dataclasses.dataclass
class Traced:
    """What a per-layer metric reader gets."""

    build_s: float
    compile_s: float
    steps: int  # simulated steps in the traced window
    reduction: tr.Reduction
    work: wk.Work
    peaks: dict


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    device = require_chips(cell.chips)

    import jax

    use_cache()

    t_build = time.perf_counter()
    built = build(cell, args.seed)
    key = seed_key(args.seed)
    steps = int(cell.mix["chunk_steps"])
    t_compile = time.perf_counter()
    compiled, call_args, compile_s = built.engine.compile(steps, key=key)
    t_warm = time.perf_counter()
    np.asarray(compiled(*call_args))  # warm-up chunk
    setup_s = time.perf_counter() - T0
    print(f"set-up {setup_s} s: start and imports {t_build - T0}, network {built.network_s}, "
          f"program build {built.build_s}, compile and staging {t_warm - t_compile} "
          f"(compile {compile_s}), warm-up chunk {T0 + setup_s - t_warm}")

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with jax.profiler.trace(str(TRACE_DIR)):
            win = closed_loop(compiled, call_args, chunks=TRACE_CHUNKS)
        events = tr.load_xplane(tr.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        win = closed_loop(compiled, call_args, seconds=args.seconds)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
    )
    del compiled, call_args
    built.engine = None
    gc.collect()

    # -- correctness: every distinct raster of the window against float64
    m = built.net.n_neurons
    valid = [r for r in win.rasters if r.shape == (steps, m) and ((r == 0) | (r == 1)).all()]
    failed = win.errors + len(win.rasters) - len(valid)
    distinct = {hashlib.sha1(r.tobytes()).hexdigest(): r for r in valid}
    n_dev = int(np.prod(cell.config["mesh"]))
    noise64 = ref.noise(key, n_dev, m // n_dev, steps, cell.mix["noise_sigma"], built.lif.dt)
    verdicts = [ref.check(r, built.net, noise64, built.lif, cell.mix["i_ext"]) for r in distinct.values()]
    gap = max((v.gap_mV for v in verdicts), default=float("inf"))
    checks = {
        "gap_mV": {"value": gap, "limit": cell.limits["gap_mV"]},
        "failed_chunks": {"value": failed, "limit": 0},
    }
    correct = bool(valid) and all(c["value"] <= c["limit"] for c in checks.values())

    spikes = sum(int(r.sum()) for r in valid)
    rate = spikes / max(1, len(valid) * steps * m)
    print(f"cell {cell.name} seed {args.seed}: {len(valid)} chunks of {steps} steps, "
          f"{len(distinct)} distinct raster(s), {spikes} spikes")
    print(f"rate: {rate} spikes per neuron per step ({rate / built.lif.dt * 1e3} Hz)")
    print(f"window: {win.seconds} s, compilations inside it: {win.compiles}")

    metrics = {}
    if args.trace:
        red = tr.reduce(events)
        traced = Traced(
            build_s=built.build_s,
            compile_s=compile_s,
            steps=steps * len(valid),
            reduction=red,
            work=wk.count(valid, built.net.per_block(n_dev)),
            peaks=wk.peaks(device["kind"]),
        )
        for spec in cell.per_layer:
            value = read_metric(spec["name"], traced)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
    else:
        values = {
            "steps_per_s": steps * len(valid) / win.seconds,
            "setup_s": setup_s,
        }
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": values[spec["name"]], "unit": spec["unit"]}

    result = {
        "correct": correct,
        "attempted": win.attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = red.breakdown()
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
