"""Network family ``ei_indegree``, for ``test_new_model_file_is_a_config``:
a random network of excitatory and inhibitory LIF neurons in which every
neuron receives exactly ``c_e`` synapses from excitatory and ``c_i`` from
inhibitory neurons (Brunel 2000, J. Comput. Neurosci. 8:183), run on the
program's ``DistributedSNN`` in natural order, ``neurons / chips`` a
chip.  It brings its own float64 reference and reads nothing of the
brain model's files: only ``work.py``'s shared arithmetic.

Configuration keys: ``neurons``, ``excitatory_frac``, ``c_e``, ``c_i``,
``j`` (excitatory weight, nA), ``g`` (inhibitory over excitatory),
``connectome_seed``, ``mesh``, ``exchange``, ``lif``; the mix gives
``i_ext`` and ``noise_sigma``.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from repro.snn import BlockSynapses, DistributedSNN, LIFParams

from bench import work as wk

neuron_step = "lif_step"


@dataclasses.dataclass(frozen=True)
class Verdict:
    gap_mV: float
    flips: int
    spikes: int


@dataclasses.dataclass
class Net:
    pre: np.ndarray  # int64[nnz], sorted
    post: np.ndarray  # int64[nnz]
    w: np.ndarray  # float32[nnz]
    n_neurons: int
    lif: dict
    dt: float


def make(cfg: dict, mix: dict, seed: int, n_chips: int) -> tuple[Net, float]:
    """Fixed in-degree: the presynaptic neurons of every neuron from
    ``connectome_seed`` (no self-synapse), the weights' magnitudes,
    uniform in [0.5, 1.5] times ``j``, from ``seed``."""
    n = cfg["neurons"]
    if n % n_chips:
        raise ValueError(f"{n} neurons do not split over {n_chips} chips")
    n_e = int(round(cfg["excitatory_frac"] * n))
    rng = np.random.default_rng(cfg["connectome_seed"])
    pre, post = [], []
    for j in range(n):
        e = rng.choice(np.setdiff1d(np.arange(n_e), [j]), cfg["c_e"], replace=False)
        i = rng.choice(np.setdiff1d(np.arange(n_e, n), [j]), cfg["c_i"], replace=False)
        pre.append(np.concatenate([e, i]))
        post.append(np.full(cfg["c_e"] + cfg["c_i"], j))
    pre, post = np.concatenate(pre), np.concatenate(post)
    order = np.argsort(pre, kind="stable")
    pre, post = pre[order], post[order]
    w = np.random.default_rng(seed).uniform(0.5, 1.5, pre.shape[0]) * cfg["j"]
    w = np.where(pre < n_e, w, -cfg["g"] * w).astype(np.float32)
    lif = cfg["lif"]
    return Net(pre=pre, post=post, w=w, n_neurons=n, lif=lif, dt=lif["dt"]), 0.0


def hand_off(cfg: dict, mix: dict, net: Net, mesh, policy) -> DistributedSNN:
    n = net.n_neurons
    dense = np.zeros((n, n), np.float32)
    dense[net.pre, net.post] = net.w
    return DistributedSNN(
        mesh=mesh,
        params=LIFParams(**net.lif, noise_sigma=mix["noise_sigma"]),
        exchange=cfg["exchange"],
        i_ext=mix["i_ext"],
        syn=BlockSynapses.from_dense(dense, mesh.size),
        policy=policy,
    )


def noise(key, n_chips: int, steps: int, n: int, sigma: float, dt: float) -> np.ndarray:
    """The program's channel noise: one key a chip (``split``), one
    ``split`` a step, ``normal((n / chips,), float32) · sigma · sqrt(dt)``."""
    import jax
    import jax.numpy as jnp

    def one_chip(k):
        def body(k, _):
            k, sub = jax.random.split(k)
            return k, jax.random.normal(sub, (n // n_chips,), jnp.float32)

        return jax.lax.scan(body, k, None, length=steps)[1]

    z = jax.jit(jax.vmap(one_chip))(jax.random.split(key, n_chips))
    z = np.asarray(z, np.float64).transpose(1, 0, 2).reshape(steps, n)
    return z * (sigma * np.sqrt(dt))


def as_bfloat16(x):
    """Round to bfloat16, nearest with ties to even (kept in float64)."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def potentials(raster, net: Net, key, cfg: dict, mix: dict, q=lambda x: x):
    """Yield ``(t, v, refractory)``: the LIF potential before threshold
    and reset, every result rounded by ``q`` (exact float64 by default),
    with the raster as the spikes and resets of every earlier step."""
    p = net.lif
    z = q(noise(key, int(np.prod(cfg["mesh"])), raster.shape[0], net.n_neurons,
                mix["noise_sigma"], p["dt"]))
    w = q(net.w.astype(np.float64))
    c, i0, v_rest, r_m = q(p["dt"] / p["tau_m"]), q(mix["i_ext"]), q(p["v_rest"]), q(p["r_m"])
    v = np.full(net.n_neurons, v_rest)
    u = np.zeros(net.n_neurons)
    prev = np.zeros(net.n_neurons, bool)
    for t in range(raster.shape[0]):
        hit = prev[net.pre]
        i_syn = q(q(np.bincount(net.post[hit], weights=w[hit], minlength=net.n_neurons)) + i0)
        refractory = u > 0.0
        v = np.where(refractory, v, q(q(v + q(c * q(q(v_rest - v) + q(r_m * i_syn)))) + z[t]))
        yield t, v, refractory
        fired = raster[t] > 0
        v = np.where(fired, q(p["v_reset"]), v)
        u = np.where(fired, p["t_refrac"], np.maximum(u - p["dt"], 0.0))
        prev = fired


def judge(decisions, v64, refractory, thresh: float) -> tuple[float, int]:
    """Widest distance from threshold (mV) of the float64 potential where
    ``decisions`` contradicts it (inf for a spike while refractory), and
    how many decisions do."""
    if (decisions & refractory).any():
        return float("inf"), 0
    wrong = decisions != ((v64 >= thresh) & ~refractory)
    return (float(np.abs(v64[wrong] - thresh).max()) if wrong.any() else 0.0), int(wrong.sum())


def check(raster, net: Net, key, cfg: dict, mix: dict) -> Verdict:
    worst, flips = 0.0, 0
    for t, v, refr in potentials(raster, net, key, cfg, mix):
        gap, n = judge(raster[t] > 0, v, refr, net.lif["v_thresh"])
        worst, flips = max(worst, gap), flips + n
    return Verdict(worst, flips, int((raster > 0).sum()))


def control_bfloat16(raster, net: Net, key, cfg: dict, mix: dict) -> Verdict:
    """The reference computed in bfloat16, in the program's place."""
    worst, flips, spikes = 0.0, 0, 0
    exact = potentials(raster, net, key, cfg, mix)
    low = potentials(raster, net, key, cfg, mix, q=as_bfloat16)
    thresh = net.lif["v_thresh"]
    for (t, v, refr), (_, vq, _) in zip(exact, low):
        mine = (vq >= thresh) & ~refr
        gap, n = judge(mine, v, refr, thresh)
        worst, flips, spikes = max(worst, gap), flips + n, spikes + int(mine.sum())
    return Verdict(worst, flips, spikes)


controls = {"bfloat16": control_bfloat16}


def work(rasters, net: Net, n_chips: int) -> wk.Work:
    b = net.n_neurons // n_chips
    per_block = np.zeros((net.n_neurons, n_chips), np.int64)
    np.add.at(per_block, (net.pre, net.post // b), 1)
    return wk.count(rasters, per_block, state_bytes=20, id_bytes=4)
