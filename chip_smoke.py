#!/usr/bin/env python3
"""Smoke run of the distributed spiking engine on TPU chips.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the 2×2 mesh phase, and nothing else

One chip: the launcher's path (``repro.launch.run_brainsim``) at
M = 16,384 neurons (512 populations × 32), one 1 GiB f32 tile, the
``'sparse'`` exchange and the Pallas ``spike_accum_blocks`` kernel, timed
over 1,000 steps (0.1 s of biological time at dt = 0.1 ms).  Channel
noise (σ = 1 mV/√ms) desynchronizes the tonic neurons: without it every
neuron fires in the same step and its synaptic input lands while all are
refractory, so the raster would not depend on the currents.  Checks:

* the compiled step contains the kernel (``tpu_custom_call``);
* the kernel's currents agree with a float64 NumPy ``Σ_k s[src_k] @ W_k``
  at the raster's busiest step, at a median one, and on one chip's share
  of the 2×2 network (n_blocks = 4, B = 8,192, K = 3): per neuron,
  |error| ≤ 16·√n·2⁻²⁴ · Σ_i |s_i w_ij| for n spikes (f32 summation error
  grows as √n·2⁻²⁴; rounding the weights to bf16 misses the limit at a
  median step by an order of magnitude);
* the raster equals ``SNNEngine`` on the same tile; where it does not,
  the first diverging step is printed and every neuron's rate must stay
  within 0.003 spikes per step of the reference.

``--chips 4``: one network of M = 32,768 (B = 8,192 per chip) on the 2×2
mesh, ``'ragged'`` exchange with the kernel against ``'flat'`` on the
same raster (same check as above), with bytes per step, collectives per
step and each chip's memory in use.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STEPS = 1000  # 0.1 s of biological time at dt = 0.1 ms
SEED = 0  # brain model, partition, synapses, noise and the 2×2 share
NOISE = 1.0  # channel noise, mV/√ms
RATE_BAND = 0.003  # max per-neuron |Δ rate| (spikes per step) when rasters differ
CURRENT_TOL = 16 * 2.0**-24  # × √(spikes): max |error| / Σ_i |s_i w_ij| vs float64

_COLLECTIVE = re.compile(  # an op, or the start of an async one
    r"\s(all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter)"
    r"(?:-start)?\("
)


def collectives(hlo_text: str) -> dict[str, int]:
    """Collective ops in a compiled step, by kind.  The time loop's body
    holds them once, so these are counts per simulation step."""
    counts: dict[str, int] = {}
    for kind in _COLLECTIVE.findall(hlo_text):
        counts[kind] = counts.get(kind, 0) + 1
    return counts


def check_currents(syn, spikes: dict[str, np.ndarray], policy) -> None:
    """Kernel currents of device block 0 against float64 NumPy for each
    labelled global spike vector ``f32[M]``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import spike_currents_blocks

    lo, hi = int(syn.indptr[0]), int(syn.indptr[1])
    src = syn.src_ids[lo:hi]
    tiles = jax.device_put(syn.blocks[lo:hi])
    for label, vec in spikes.items():
        s = vec.reshape(syn.n_blocks, syn.block_size)
        got = np.asarray(
            spike_currents_blocks(
                jnp.asarray(s), jnp.asarray(src.astype(np.int32)), tiles, policy=policy
            )
        )
        ref = np.zeros(syn.block_size)
        mag = np.zeros(syn.block_size)
        for k, sk in enumerate(src):
            w = syn.blocks[lo + k][np.nonzero(s[sk])[0]].astype(np.float64)
            ref += w.sum(axis=0)
            mag += np.abs(w).sum(axis=0)
        ratio = float((np.abs(got - ref) / np.maximum(mag, 1e-30)).max())
        limit = CURRENT_TOL * np.sqrt(s.sum())
        print(f"currents, {label} ({int(s.sum())} spikes): "
              f"max |err| / sum|s*w| = {ratio:.3e} (limit {limit:.3e})")
        if ratio > limit:
            raise SystemExit(f"FAIL: kernel currents off float64 by {ratio:.3e}")


def raster_steps(raster: np.ndarray) -> dict[str, np.ndarray]:
    """The raster's busiest step and its median step with spikes."""
    counts = raster.sum(axis=1)
    firing = np.nonzero(counts)[0]
    if firing.size == 0:
        raise SystemExit("FAIL: the network never fired; no currents to check")
    median = int(firing[np.argsort(counts[firing], kind="stable")[firing.size // 2]])
    busiest = int(counts.argmax())
    return {f"step {busiest}": raster[busiest], f"step {median}": raster[median]}


def share_of_mesh():
    """One chip's share of the 2×2 network: n_blocks = 4, B = 8,192,
    K = 3 random tiles (source block 1 stored, then silenced), and 1.5%
    random spikes."""
    from repro.snn import BlockSynapses

    rng = np.random.default_rng(SEED)
    b = 8192
    tiles = rng.standard_normal((3, b, b), dtype=np.float32)
    syn = BlockSynapses.from_tiles(np.array([0, 1, 3]), np.zeros(3, np.int64), tiles, 4)
    s = (rng.random(4 * b) < 0.015).astype(np.float32)
    s[b : 2 * b] = 0.0
    return syn, {"2x2 share, n_blocks 4, B 8192, K 3": s}


def compare_rasters(name: str, got: np.ndarray, ref: np.ndarray) -> None:
    """Equal rasters, or the first diverging step and rates in the band."""
    if got.shape != ref.shape:
        raise SystemExit(f"FAIL: {name} raster {got.shape} != {ref.shape}")
    diff = np.nonzero((got != ref).any(axis=1))[0]
    if diff.size == 0:
        print(f"raster vs {name}: identical over {got.shape[0]} steps")
        return
    drate = float(np.abs(got.mean(axis=0) - ref.mean(axis=0)).max())
    print(f"raster vs {name}: first differs at step {int(diff[0])} "
          f"({int((got != ref).sum())} of {got.size} entries); max per-neuron "
          f"|rate diff| = {drate:.4f} (band {RATE_BAND})")
    if drate > RATE_BAND:
        raise SystemExit(f"FAIL: rates off {name} by {drate:.4f} per step")


def require_kernel(compiled) -> None:
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"Pallas kernel in compiled step (tpu_custom_call): {has_kernel}")
    if not has_kernel:
        raise SystemExit("FAIL: the compiled step does not contain the kernel")


def one_chip() -> None:
    import jax

    from repro.launch import run_brainsim
    from repro.snn import SNNEngine

    launch = run_brainsim.main([
        "--populations", "512", "--neurons-per-pop", "32",
        "--steps", str(STEPS), "--seed", str(SEED), "--noise", str(NOISE),
    ])
    eng = launch.engine
    syn = eng.syn
    hbm = jax.devices()[0].memory_stats()["bytes_limit"]
    print(f"M = {syn.n_neurons}; tiles {syn.blocks.nbytes} bytes "
          f"= {syn.blocks.nbytes / hbm:.1%} of {hbm} bytes of device memory")
    require_kernel(launch.compiled)
    check_currents(syn, raster_steps(launch.raster), eng.policy)
    check_currents(*share_of_mesh(), eng.policy)
    # the engine's one device draws its noise from split(key, 1)[0]
    ref = SNNEngine(w_syn=syn.to_dense(), params=eng.params, i_ext=eng.i_ext).run(
        STEPS, key=jax.random.split(jax.random.PRNGKey(SEED), 1)[0]
    )
    compare_rasters("SNNEngine", launch.raster, np.asarray(ref.spikes))


def four_chips() -> None:
    import jax

    from repro.launch import run_brainsim
    from repro.snn import DistributedSNN

    launch = run_brainsim.main([
        "--populations", "1024", "--neurons-per-pop", "32", "--exchange", "ragged",
        "--steps", str(STEPS), "--seed", str(SEED), "--noise", str(NOISE),
    ])
    eng = launch.engine
    syn = eng.syn
    print(f"M = {syn.n_neurons} over mesh {dict(eng.mesh.shape)}, B = {syn.block_size}, "
          f"{syn.nnzb} stored tiles ({syn.blocks.nbytes} bytes)")
    require_kernel(launch.compiled)
    print(f"ragged collectives per step: {collectives(launch.compiled.as_text())}")
    print(f"slow-axis bytes per step: {eng.exchange_stats()}")
    for d in jax.devices():
        ms = d.memory_stats()
        print(f"chip {d.id}: bytes_in_use = {ms['bytes_in_use']}, "
              f"peak_bytes_in_use = {ms['peak_bytes_in_use']}")
    flat = DistributedSNN(
        mesh=eng.mesh, w_syn=syn.to_dense(), params=eng.params,
        exchange="flat", i_ext=eng.i_ext,
    )
    raster_flat = np.asarray(flat.run(STEPS, key=jax.random.PRNGKey(SEED)))
    compare_rasters("flat", launch.raster, raster_flat)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)

    from repro.launch.run_brainsim import device_info

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"FAIL: no TPU (JAX found {dev['platform']}: {dev['kind']})", file=sys.stderr)
        return 1
    if dev["count"] != args.chips:
        print(f"FAIL: {dev['count']} chips visible, --chips {args.chips}", file=sys.stderr)
        return 1
    (one_chip if args.chips == 1 else four_chips)()
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
