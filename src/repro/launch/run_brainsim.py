"""Brain-simulation launcher: partition (Alg. 1) → route (Alg. 2) →
block-CSR synapses → distributed spiking run on every visible device.

One device runs ``exchange='sparse'``, several run ``'ragged'``; on a TPU
the synaptic accumulation runs the Pallas ``spike_accum_blocks`` kernel.

    PYTHONPATH=src python -m repro.launch.run_brainsim \\
        --populations 512 --neurons-per-pop 32 --steps 1000

``--trace DIR`` runs the whole launch under ``jax.profiler.trace(DIR)``
with the tracer (:mod:`repro.obs`) on: its planner and executor spans
and the device's ops land in one profile, on one clock.  After the timed
run the launcher prints how many 8-row weight strips the accumulation
streamed a step (``DistributedSNN.accum_stats``, counter
``snn.accum_strips``).

On the CPU, with fake host devices for the mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m repro.launch.run_brainsim \\
        --populations 64 --neurons-per-pop 2 --steps 300
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import AxisType

from repro import obs
from repro.core import (
    device_traffic_csr,
    greedy_partition,
    p2p_routing,
    step_latency,
    two_level_routing,
)
from repro.kernels import KernelPolicy
from repro.snn import DistributedSNN, LIFParams, expand_synapses_sparse, generate_brain_model

#: compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path at the root of the checkout (the path is part of the key)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: LIF drive: steady state −25 mV, above the −50 mV threshold, so every
#: neuron fires tonically (~1.5% of steps) before synaptic input
I_EXT = 4.0


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is changed; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """The device the run uses, as JAX reports it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": jax.device_count()}


@dataclasses.dataclass(frozen=True)
class Launch:
    """What one launcher run built and measured."""

    engine: DistributedSNN  # its block-CSR tiles are ``engine.syn``
    raster: np.ndarray  # [T, M] spikes of the timed run
    compiled: jax.stages.Compiled  # the step the timed run executed
    compile_s: float  # lower + compile of the step
    steps_per_s: float  # timed run, after a warm-up run


def build_engine(
    populations: int,
    neurons_per_pop: int,
    *,
    seed: int = 0,
    exchange: str | None = None,
    noise: float = 0.0,
) -> DistributedSNN:
    """Brain model → Algorithm-1 partition over ``jax.device_count()``
    devices → block-CSR tiles (one block per device) → engine.

    Populations are assigned to devices in partition order, in equal
    counts (the executor needs equal blocks).
    """
    n_dev = jax.device_count()
    if populations % n_dev:
        raise ValueError(f"{populations} populations do not split over {n_dev} devices")
    bm = generate_brain_model(
        n_populations=populations,
        n_regions=max(8, populations // 16),
        total_neurons=1_000_000,
        seed=seed,
    )
    with obs.span("launch.partition", cat="plan", tid="launch"):
        part = greedy_partition(bm.graph, n_dev, seed=seed)
    if n_dev > 1:
        t, wg = device_traffic_csr(bm.graph, part.assign, n_dev)  # sparse CSR
        with obs.span("launch.route", cat="plan", tid="launch"):
            tb = two_level_routing(t, wg, max(2, n_dev // 4))
        print(
            f"cut={part.cut:.1f} groups={tb.n_groups} "
            f"latency p2p={step_latency(p2p_routing(t, wg)).t_total * 1e3:.2f}ms "
            f"two-level={step_latency(tb).t_total * 1e3:.2f}ms"
        )
    order = np.argsort(part.assign, kind="stable")
    assign = np.empty(populations, np.int64)
    assign[order] = np.arange(populations) // (populations // n_dev)
    # w_scale 0.4: gamma(2, 0.2) weights, mean 0.4 per synapse
    syn, _ = expand_synapses_sparse(
        bm.graph, neurons_per_pop, n_dev, assign=assign, w_scale=0.4, seed=seed
    )
    shape = (2, n_dev // 2) if n_dev % 2 == 0 and n_dev > 2 else (1, n_dev)
    mesh = jax.make_mesh(shape, ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
    return DistributedSNN(
        mesh=mesh,
        params=LIFParams(noise_sigma=noise),
        exchange=exchange or ("sparse" if n_dev == 1 else "ragged"),
        i_ext=I_EXT,
        syn=syn,
        policy=KernelPolicy(use_pallas=jax.devices()[0].platform == "tpu"),
    )


def run_timed(
    eng: DistributedSNN, n_steps: int, *, key: jax.Array
) -> tuple[np.ndarray, jax.stages.Compiled, float, float]:
    """Compile the ``n_steps`` step, run it once to warm up, then time a
    second run to completion.  Returns ``(raster, compiled, compile_s,
    steps_per_s)``."""
    compiled, args, compile_s = eng.compile(n_steps, key=key)
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    raster = jax.block_until_ready(compiled(*args))
    steps_per_s = n_steps / (time.perf_counter() - t0)
    return np.asarray(raster), compiled, compile_s, steps_per_s


def main(argv: list[str] | None = None) -> Launch:
    ap = argparse.ArgumentParser()
    ap.add_argument("--populations", type=int, default=128)
    ap.add_argument("--neurons-per-pop", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument(
        "--exchange",
        choices=["sparse", "ragged"],
        help="default: sparse on one device, ragged on several",
    )
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="DIR",
                    help="profile the whole run into DIR (jax.profiler: "
                         "the planner and executor spans beside the device's ops)")
    args = ap.parse_args(argv)
    if not args.trace:
        return _launch(args)
    obs.enable()
    try:
        with jax.profiler.trace(args.trace, create_perfetto_trace=True):
            launch = _launch(args)
    finally:
        obs.disable()
    print(f"trace written under {args.trace}")
    return launch


def _launch(args: argparse.Namespace) -> Launch:
    cache = use_compile_cache()
    dev = device_info()
    print(
        f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']} compile_cache={cache}"
    )
    with obs.span("launch.build", cat="plan", tid="launch"):
        eng = build_engine(
            args.populations,
            args.neurons_per_pop,
            seed=args.seed,
            exchange=args.exchange,
            noise=args.noise,
        )
    key = jax.random.PRNGKey(args.seed)
    with obs.span("launch.run", cat="exec", tid="launch",
                  args={"exchange": eng.exchange, "steps": args.steps}):
        raster, compiled, compile_s, steps_per_s = run_timed(eng, args.steps, key=key)
    print(
        f"simulated {eng.syn.n_neurons} neurons × {args.steps} steps "
        f"({eng.exchange} exchange, pallas={eng.policy.use_pallas}): "
        f"{int(raster.sum())} spikes, mean rate {raster.mean():.4f} per step"
    )
    print(
        f"compile {compile_s:.3f} s, {steps_per_s:.1f} steps/s "
        f"(measured on {dev['count']}× {dev['kind']})"
    )
    strips = eng.accum_stats(raster)
    obs.counter("snn.accum_strips", strips, tid="snn")
    print(
        f"accumulation streamed {strips['mean']:.2f} of {strips['of']} weight strips "
        f"a step ({strips['mean'] / strips['of']:.4%}), at most {strips['max']}"
    )
    vol = eng.exchange_stats()
    print("slow-axis bytes/step: " + "  ".join(f"{k}={v}" for k, v in sorted(vol.items())))
    return Launch(
        engine=eng,
        raster=raster,
        compiled=compiled,
        compile_s=compile_s,
        steps_per_s=steps_per_s,
    )


if __name__ == "__main__":
    main()
