"""Plain float64 reference of the simulated network, and the comparison
that decides ``correct``.

The program returns a spike raster ``[T, M]``.  The reference replays it:
at every step it computes each neuron's membrane potential in float64
from its own synapse lists (``network.Network``), the LIF equations and
the same channel-noise samples, taking the spikes of the previous step
and the resets from the program's raster (teacher forcing, so one flip
at rounding level does not spread through the network).  A spike where
the reference's potential is below threshold, or none where it is above,
is a contradiction; its size is the distance of the reference's
potential from threshold in mV.  The number compared is the widest such
gap over the raster, and a spike inside a refractory period counts as an
infinite gap.  Sound float32 runs read gaps at the level of float32
rounding of the potential (below 1e-4 mV); the reference computed in
bfloat16 (:func:`control`) reads gaps of about 1 mV and more.

The noise is the program's documented stream, drawn again here with
``jax.random``: one key per device (``split(key, n_devices)``), one
``split`` per step, ``normal(sub, (B,), float32)`` scaled by
``sigma · sqrt(dt)``.  Nothing of the program is imported.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.network import Network


@dataclasses.dataclass(frozen=True)
class Lif:
    """Leaky integrate-and-fire constants (mV, ms, MΩ)."""

    tau_m: float
    v_rest: float
    v_reset: float
    v_thresh: float
    r_m: float
    t_refrac: float
    dt: float


def noise(key, n_blocks: int, block: int, steps: int, sigma: float, dt: float) -> np.ndarray:
    """``f64[steps, n_blocks · block]`` channel noise of every neuron."""
    import jax
    import jax.numpy as jnp

    def one_device(k):
        def body(k, _):
            k, sub = jax.random.split(k)
            return k, jax.random.normal(sub, (block,), jnp.float32)

        return jax.lax.scan(body, k, None, length=steps)[1]

    z = jax.jit(jax.vmap(one_device))(jax.random.split(key, n_blocks))
    z = np.asarray(z, np.float64).transpose(1, 0, 2).reshape(steps, n_blocks * block)
    return z * (sigma * np.sqrt(dt))


def _currents(net: Network, spikes: np.ndarray, w: np.ndarray) -> np.ndarray:
    idx = np.flatnonzero(spikes)
    if idx.size == 0:
        return np.zeros(net.n_neurons)
    lo, hi = net.indptr[idx], net.indptr[idx + 1]
    sel = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
    return np.bincount(net.post[sel], weights=w[sel], minlength=net.n_neurons)


def _exact(x):
    return x


def as_float32(x):
    """Round to float32 (kept in a float64 array)."""
    return np.asarray(x, np.float32).astype(np.float64)


def as_bfloat16(x):
    """Round to bfloat16, nearest with ties to even (kept in float64)."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


CONTROLS = {
    # the reference computed in bfloat16, the precision below float32's
    "bfloat16": {"q": as_bfloat16},
    # float32 with the weights rounded to bfloat16: a one-pass bfloat16
    # matrix product, the kernel's default precision
    "bfloat16_weights": {"q": as_float32, "w_q": as_bfloat16},
}


def replay(
    raster: np.ndarray,
    net: Network,
    noise64: np.ndarray,
    lif: Lif,
    i_ext: float,
    *,
    q=_exact,
    w_q=None,
):
    """Yield ``(t, v, refractory)`` per step: the potential before reset
    and threshold, with the program's raster as the spikes of every
    earlier step.  ``q`` rounds the result of every operation (exact
    float64 by default); ``w_q`` rounds the weights (``q`` by default)."""
    w = (w_q or q)(net.w.astype(np.float64))
    c, v_rest, r_m = q(lif.dt / lif.tau_m), q(lif.v_rest), q(lif.r_m)
    v_reset, i0 = q(lif.v_reset), q(i_ext)
    zeta = q(noise64)
    v = np.full(net.n_neurons, v_rest)
    u = np.zeros(net.n_neurons)  # refractory countdown, ms
    prev = np.zeros(net.n_neurons, bool)
    for t in range(raster.shape[0]):
        i_syn = q(q(_currents(net, prev, w)) + i0)
        refractory = u > 0.0
        dv = q(c * q(q(v_rest - v) + q(r_m * i_syn)))
        v = np.where(refractory, v, q(q(v + dv) + zeta[t]))
        yield t, v, refractory
        spk = raster[t] > 0
        v = np.where(spk, v_reset, v)
        u = np.where(spk, lif.t_refrac, np.maximum(u - lif.dt, 0.0))
        prev = spk


def gap(decisions: np.ndarray, v64: np.ndarray, refractory: np.ndarray, thresh: float) -> float:
    """Widest distance from threshold (mV) of the float64 potential where
    ``decisions`` contradicts it; inf for a spike while refractory."""
    if (decisions & refractory).any():
        return float("inf")
    above = (v64 >= thresh) & ~refractory
    wrong = decisions != above
    return float(np.abs(v64[wrong] - thresh).max()) if wrong.any() else 0.0


@dataclasses.dataclass(frozen=True)
class Verdict:
    gap_mV: float  # widest contradiction, mV (the number compared)
    flips: int  # entries where the raster contradicts float64
    spikes: int


def check(raster: np.ndarray, net: Network, noise64: np.ndarray, lif: Lif, i_ext: float) -> Verdict:
    """The program's raster against the float64 reference."""
    worst, flips = 0.0, 0
    for t, v, refr in replay(raster, net, noise64, lif, i_ext):
        spk = raster[t] > 0
        worst = max(worst, gap(spk, v, refr, lif.v_thresh))
        flips += int((spk != ((v >= lif.v_thresh) & ~refr)).sum())
    return Verdict(gap_mV=worst, flips=flips, spikes=int((raster > 0).sum()))


def control(
    raster: np.ndarray,
    net: Network,
    noise64: np.ndarray,
    lif: Lif,
    i_ext: float,
    *,
    q=as_bfloat16,
    w_q=None,
) -> Verdict:
    """The reference computed in a lower precision (``q``, and ``w_q`` for
    the weights), put in the program's place: at each step of the same
    raster its own threshold decisions are judged against float64 as the
    program's are."""
    worst, flips, spikes = 0.0, 0, 0
    low = replay(raster, net, noise64, lif, i_ext, q=q, w_q=w_q)
    for (t, v, refr), (_, vq, _) in zip(replay(raster, net, noise64, lif, i_ext), low):
        mine = (vq >= lif.v_thresh) & ~refr
        worst = max(worst, gap(mine, v, refr, lif.v_thresh))
        flips += int((mine != ((v >= lif.v_thresh) & ~refr)).sum())
        spikes += int(mine.sum())
    return Verdict(gap_mV=worst, flips=flips, spikes=spikes)
