"""SNN engine throughput + exchanged-byte accounting: flat vs sparse vs
ragged.

The tentpole claim of the routing-aware spike exchange, in two rungs: on
a clustered brain model the *sparse* schedule moves strictly fewer bytes
across the slow mesh axis than the flat all-gather, and the *ragged*
schedule (bridge-compacted, column-pruned payloads — the Algorithm-2
bridge applied to the simulation loop) strictly fewer than sparse, all
at the same raster.  Two measurements:

  1. Deterministic: block-mask density and per-step slow-axis receive
     volume (``exchange_volume`` with a ``RaggedPlan``) for the flat vs
     sparse vs ragged schedules on a 1-D and a 2-D mesh — these feed the
     CI regression gate.
  2. Executable: an 8-host-device subprocess runs the distributed engine
     with ``exchange='flat'``, ``'sparse'`` and ``'ragged'`` on the same
     model, asserts raster equality, and times steps/s (reported, not
     gated — CI wall clocks are noisy).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys


from benchmarks.common import emit

_CHILD = r"""
import sys, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.snn import DistributedSNN, LIFParams, expand_synapses_sparse, generate_brain_model

n_pop, n_reg, npp, steps = (int(a) for a in sys.argv[1:5])
bm = generate_brain_model(n_populations=n_pop, n_regions=n_reg,
                          total_neurons=10**7, seed=0)
syn, _ = expand_synapses_sparse(bm.graph, npp, 8, seed=0)
params = LIFParams(noise_sigma=0.0)
mesh = jax.make_mesh((4, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
engines = {
    "flat": DistributedSNN(mesh=mesh, w_syn=jnp.asarray(syn.to_dense()),
                           params=params, exchange="flat", i_ext=4.0),
    "sparse": DistributedSNN(mesh=mesh, params=params, exchange="sparse",
                             i_ext=4.0, syn=syn),
    "ragged": DistributedSNN(mesh=mesh, params=params, exchange="ragged",
                             i_ext=4.0, syn=syn),
}
rasters = {}
for name, eng in engines.items():
    eng.run(2, key=jax.random.PRNGKey(1)).block_until_ready()  # compile
    t0 = time.perf_counter()
    rasters[name] = eng.run(steps, key=jax.random.PRNGKey(1))
    rasters[name].block_until_ready()
    dt = time.perf_counter() - t0
    print(f"steps_per_s_{name},{steps / dt:.1f}")
np.testing.assert_allclose(np.asarray(rasters["flat"]), np.asarray(rasters["sparse"]))
np.testing.assert_allclose(np.asarray(rasters["flat"]), np.asarray(rasters["ragged"]))
print("rasters_equal,1")
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--populations", type=int, default=128)
    ap.add_argument("--neurons-per-pop", type=int, default=4)
    ap.add_argument("--regions", type=int, default=16)
    ap.add_argument("--devices", type=int, default=32)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--skip-exec", action="store_true")
    # accepted for benchmarks.run compatibility (unused here)
    ap.add_argument("--method", default="greedy")
    args, _ = ap.parse_known_args(argv)

    from repro.snn import (
        build_ragged_plan,
        exchange_volume,
        expand_synapses_sparse,
        generate_brain_model,
    )

    bm = generate_brain_model(
        n_populations=args.populations,
        n_regions=args.regions,
        total_neurons=10**7,
        seed=0,
    )
    syn, _ = expand_synapses_sparse(
        bm.graph, args.neurons_per_pop, args.devices, seed=0
    )
    emit("snn/block_density", round(syn.density, 4), f"{args.devices} blocks")
    blk_bytes = syn.block_size * 4
    plan1 = build_ragged_plan(syn, (args.devices, 1))
    v1 = exchange_volume(syn.mask(), block_bytes=blk_bytes, plan=plan1)
    emit("snn/bytes_flat_1d", v1["flat"], "per step, slow axis")
    emit("snn/bytes_sparse_1d", v1["sparse"], "per step, slow axis")
    emit("snn/bytes_ragged_1d", v1["ragged"], "per step, slow axis")
    g = args.devices // 4
    plan2 = build_ragged_plan(syn, (g, 4))
    v2 = exchange_volume(
        syn.mask(), mesh_shape=(g, 4), block_bytes=blk_bytes, plan=plan2
    )
    emit("snn/bytes_flat_2d", v2["flat"], f"({g},4) mesh level-2")
    emit("snn/bytes_sparse_2d", v2["sparse"], f"({g},4) mesh level-2")
    emit("snn/bytes_ragged_2d", v2["ragged"], f"({g},4) mesh level-2")
    emit(
        "snn/bytes_reduction_1d",
        round(v1["flat"] / max(v1["sparse"], 1), 2),
        "flat / sparse",
    )
    emit(
        "snn/ragged_vs_sparse_1d",
        round(v1["sparse"] / max(v1["ragged"], 1), 2),
        "sparse / ragged",
    )
    emit(
        "snn/ragged_vs_sparse_2d",
        round(v2["sparse"] / max(v2["ragged"], 1), 2),
        "sparse / ragged",
    )

    if not args.skip_exec:
        env = dict(os.environ)
        # fake host devices only: the child never touches an accelerator
        # the parent process may hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
        out = subprocess.run(
            [
                sys.executable,
                "-c",
                _CHILD,
                "64",
                "8",
                str(args.neurons_per_pop),
                str(args.steps),
            ],
            env=env,
            capture_output=True,
            text=True,
        )
        if out.returncode != 0:
            raise SystemExit(f"8-device SNN run failed:\n{out.stderr[-3000:]}")
        for line in out.stdout.strip().splitlines():
            k, v = line.split(",")
            emit(f"snn/exec_{k}", v, "8 host devices")


if __name__ == "__main__":
    main()
