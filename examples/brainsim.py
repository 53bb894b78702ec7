"""Distributed brain simulation — the paper's system end to end.

    PYTHONPATH=src python examples/brainsim.py [--devices 8] [--steps 100]

Builds a brain model, partitions it with Algorithm 1, derives the
Algorithm 2 routing table, then runs the distributed spiking engine on
a simulated multi-device mesh (8 fake host devices, 2 pods × 4) with
BOTH exchange schedules — flat all-gather (the paper's P2P baseline)
and the two-level bridge schedule — verifying they produce identical
spike rasters while the traffic model shows the latency gap.

NOTE: re-execs itself with XLA_FLAGS to create the fake devices, so run
it as a script (not -m).
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

sys.path.insert(0, "src")

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    device_traffic_csr,
    greedy_partition,
    multilevel_partition,
    step_latency,
    p2p_routing,
    two_level_routing,
)
from repro.snn import DistributedSNN, LIFParams, expand_synapses, generate_brain_model
from repro.snn.distributed import partition_permutation


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--neurons-per-pop", type=int, default=4)
    ap.add_argument(
        "--method",
        choices=["greedy", "multilevel"],
        default="greedy",
        help="partitioner: Algorithm 1 greedy or the multilevel scheme",
    )
    args = ap.parse_args()
    n_dev = 8

    print(f"=== model + partition (Algorithm 1, method={args.method}) ===")
    bm = generate_brain_model(
        n_populations=128, n_regions=8, total_neurons=1_000_000, seed=0
    )
    partition_fn = greedy_partition if args.method == "greedy" else multilevel_partition
    part = partition_fn(bm.graph, n_dev)
    print(f"populations={bm.n_populations} devices={n_dev} cut={part.cut:.1f} "
          f"loads={np.round(part.loads, 1)}")

    print("\n=== routing (Algorithm 2) + latency model ===")
    t, wg = device_traffic_csr(bm.graph, part.assign, n_dev)  # sparse CSR
    tb = two_level_routing(t, wg, 2)
    lat_p2p = step_latency(p2p_routing(t, wg)).t_total
    lat_two = step_latency(tb).t_total
    print(f"groups={tb.n_groups} bridges=\n{tb.bridge}")
    print(f"modeled step latency: p2p {lat_p2p*1e3:.2f} ms → two-level {lat_two*1e3:.2f} ms")

    print("\n=== distributed spiking engine (8 devices, 2 pods × 4) ===")
    # neuron-level expansion + physical permutation realizing the partition
    w, pop_of = expand_synapses(bm.graph, args.neurons_per_pop, seed=0)
    m = w.shape[0]
    # device of each neuron = device of its population; equalize counts
    n_assign = part.assign[pop_of]
    order = np.argsort(n_assign, kind="stable")
    per = m // n_dev
    n_assign_eq = np.empty(m, np.int64)
    n_assign_eq[order] = np.arange(m) // per
    perm = partition_permutation(n_assign_eq, n_dev)
    wp = w[np.ix_(perm, perm)].astype(np.float32) * 0.05

    from jax.sharding import AxisType

    mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
    rasters = {}
    for exchange in ("flat", "two_level"):
        eng = DistributedSNN(
            mesh=mesh,
            w_syn=jnp.asarray(wp),
            params=LIFParams(noise_sigma=0.0),
            exchange=exchange,
            i_ext=3.5,
        )
        rasters[exchange] = np.asarray(eng.run(args.steps, key=jax.random.PRNGKey(0)))
        print(f"{exchange:10s}: {int(rasters[exchange].sum())} spikes "
              f"over {args.steps} steps × {m} neurons")
    assert np.array_equal(rasters["flat"], rasters["two_level"]), "schedules must agree"
    print("flat and two-level exchanges produce identical rasters ✓")


if __name__ == "__main__":
    main()
