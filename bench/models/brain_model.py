"""Network family ``brain_model``: the repo's brain model (``network.py``),
LIF neurons with an instantaneous synaptic current, Gaussian channel
noise and a constant drive, every spike landing on the next step.

A configuration names its family by the key ``"network"`` (this one when
the key is absent), and the harness (``run.py``, ``calibrate.py``) reaches
the network, the program hand-off, the reference and the work counts only
through the family's module, ``bench/models/<family>.py``.  A module
exposes:

* ``make(cfg, mix, seed, n_chips) -> (net, partition_s)``: the network in
  the program's layout, the connectome from the configuration, the
  weights from ``seed``; ``net.n_neurons`` is the raster's column count
  and ``net.dt`` the step in ms; ``partition_s`` is the program's
  partition time, part of ``build_s``;
* ``hand_off(cfg, mix, net, mesh, policy) -> engine``: the program's
  constructors alone (timed as the rest of ``build_s``); ``engine.compile``
  returns the chunk step;
* ``check(raster, net, key, cfg, mix) -> Verdict``: the raster against
  the family's own float64 reference; ``gap_mV`` is the number compared;
* ``controls``: name -> a function of the same arguments, the reference
  in a lower precision put in the program's place (``calibrate.py``);
* ``work(rasters, net, n_chips) -> work.Work``: the work the steps need,
  with the family's own state and exchange bytes;
* ``neuron_step``: the name of the program's neuron update in
  ``repro.snn.distributed``, where ``tests/faults.py`` plants its faults.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from repro.snn import BlockSynapses, DistributedSNN, LIFParams

from bench import network as nw
from bench import reference as ref
from bench import work as wk

STATE_BYTES = 20  # v, u read; v, u, spike written (float32)
ID_BYTES = 4  # a spike reaches another chip as a neuron id

neuron_step = "lif_step"


@dataclasses.dataclass
class Net:
    network: nw.Network
    lif: ref.Lif
    tiles: tuple | None  # the host tiles, handed to the program once
    noise: dict = dataclasses.field(default_factory=dict)  # the reference's noise, by key

    @property
    def n_neurons(self) -> int:
        return self.network.n_neurons

    @property
    def dt(self) -> float:
        return self.lif.dt


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


def make(cfg: dict, mix: dict, seed: int, n_chips: int) -> tuple[Net, float]:
    """The network and its ``B × B`` host tiles (benchmark work), and the
    partition's seconds."""
    net, partition_s = network(cfg, seed)
    return Net(network=net, lif=ref.Lif(**cfg["lif"]), tiles=net.tiles(n_chips)), partition_s


def hand_off(cfg: dict, mix: dict, net: Net, mesh, policy) -> DistributedSNN:
    """Block-CSR tiles from the host tiles, and the engine over ``mesh``."""
    src, dst, blocks = net.tiles
    net.tiles = None
    tiles = BlockSynapses.from_tiles(src, dst, blocks, mesh.size)
    del blocks
    return DistributedSNN(
        mesh=mesh,
        params=LIFParams(**cfg["lif"], noise_sigma=mix["noise_sigma"]),
        exchange=cfg["exchange"],
        i_ext=mix["i_ext"],
        syn=tiles,
        policy=policy,
    )


def _noise(net: Net, key, steps: int, cfg: dict, mix: dict) -> np.ndarray:
    tag = (np.asarray(key).tobytes(), steps)
    if tag not in net.noise:
        n_dev = int(np.prod(cfg["mesh"]))
        m = net.n_neurons
        net.noise.clear()
        net.noise[tag] = ref.noise(key, n_dev, m // n_dev, steps, mix["noise_sigma"], net.lif.dt)
    return net.noise[tag]


def check(raster: np.ndarray, net: Net, key, cfg: dict, mix: dict) -> ref.Verdict:
    noise64 = _noise(net, key, raster.shape[0], cfg, mix)
    return ref.check(raster, net.network, noise64, net.lif, mix["i_ext"])


def _control(kw: dict):
    def control(raster: np.ndarray, net: Net, key, cfg: dict, mix: dict) -> ref.Verdict:
        noise64 = _noise(net, key, raster.shape[0], cfg, mix)
        return ref.control(raster, net.network, noise64, net.lif, mix["i_ext"], **kw)

    return control


controls = {name: _control(kw) for name, kw in
            dict(ref.CONTROLS, float32={"q": ref.as_float32}).items()}


def work(rasters: list[np.ndarray], net: Net, n_chips: int) -> wk.Work:
    return wk.count(rasters, net.network.per_block(n_chips),
                    state_bytes=STATE_BYTES, id_bytes=ID_BYTES)
