"""The benchmark's network: a brain model, its synapses and their weights.

The data takes the place of a model's weights, so it is made here and not
by the program under test: the population model is a copy of the
program's ``generate_brain_model`` (AAL-like regions on a shell,
log-normal population sizes and rates, intra-region communities,
distance-dependent and long-range projections), and the neuron-level
synapses are drawn from it with the statistics of the program's
``expand_synapses_sparse`` (Bernoulli ``P[a, b] · synapse_p`` per neuron
pair, gamma(2, w_scale/2) weights, Dale's law with inhibitory
presynaptic neurons, no self-synapse).  The draw is sparse, so it costs
milliseconds where sampling dense tiles costs seconds.  The connectome
comes from the configuration's seed and the weights from the run's
(``models/brain_model.py:network``).

The program receives the population graph (for its Algorithm-1
partition) and the ``B × B`` tiles that hold synapses, in the layout that
partition chooses, as its launcher builds them (``BlockSynapses.from_tiles``);
the reference and the work counts read the same synapses as per-neuron
lists.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Populations:
    """The population-level model: directed edges with probabilities and
    each population's traffic weight (neurons × rate × bytes/spike)."""

    src: np.ndarray
    dst: np.ndarray
    prob: np.ndarray
    weights: np.ndarray

    @property
    def n(self) -> int:
        return int(self.weights.shape[0])

    def pair_probs(self) -> np.ndarray:
        """``f64[n, n]`` connection probability per population pair:
        symmetric, duplicates merged by their maximum, 1 on the diagonal."""
        pp = np.zeros((self.n, self.n))
        np.maximum.at(pp, (self.src, self.dst), self.prob)
        np.maximum.at(pp, (self.dst, self.src), self.prob)
        np.fill_diagonal(pp, 1.0)
        return pp


def brain_model(
    rng: np.random.Generator,
    *,
    n_populations: int,
    n_regions: int,
    total_neurons: int,
    intra_region_p: float,
    lambda_mm: float,
    inter_degree: float,
    long_range_frac: float,
    mean_rate_hz: float,
    bytes_per_spike: float,
) -> Populations:
    """Population model of the paper's class (arXiv:2205.07125, §I/§V)."""
    if n_regions > n_populations:
        raise ValueError("need at least one population per region")
    u = rng.normal(size=(n_regions, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    region_pos = u * rng.uniform(60.0, 80.0, size=(n_regions, 1))

    region_of = np.sort(rng.integers(0, n_regions, size=n_populations))
    region_of[:n_regions] = np.arange(n_regions)  # every region non-empty
    region_of = np.sort(region_of)
    positions = region_pos[region_of] + rng.normal(scale=4.0, size=(n_populations, 3))

    raw = rng.lognormal(mean=0.0, sigma=0.8, size=n_populations)
    neuron_counts = np.maximum(1, np.round(raw / raw.sum() * total_neurons))
    firing_rate = rng.lognormal(mean=np.log(mean_rate_hz), sigma=0.5, size=n_populations)

    srcs, dsts, ps = [], [], []
    for r in range(n_regions):  # intra-region communities
        members = np.nonzero(region_of == r)[0]
        if members.shape[0] < 2:
            continue
        ii, jj = np.triu_indices(members.shape[0], 1)
        keep = rng.random(ii.shape[0]) < intra_region_p
        srcs.append(members[ii[keep]])
        dsts.append(members[jj[keep]])
        ps.append(rng.uniform(0.3, 1.0, int(keep.sum())))

    # inter-region: pairs accepted with probability exp(-dist / λ); the
    # candidate count aims at `inter_degree` partners per population
    pilot_i = rng.integers(0, n_populations, size=4096)
    pilot_j = rng.integers(0, n_populations, size=4096)
    pd = np.linalg.norm(positions[pilot_i] - positions[pilot_j], axis=1)
    acc_rate = max(float(np.exp(-pd / lambda_mm).mean()), 1e-4)
    n_cand = int(inter_degree * n_populations / 2 / acc_rate)
    ci = rng.integers(0, n_populations, size=n_cand)
    cj = rng.integers(0, n_populations, size=n_cand)
    valid = (ci != cj) & (region_of[ci] != region_of[cj])
    ci, cj = ci[valid], cj[valid]
    dist = np.linalg.norm(positions[ci] - positions[cj], axis=1)
    accept = rng.random(ci.shape[0]) < np.exp(-dist / lambda_mm)
    srcs.append(ci[accept])
    dsts.append(cj[accept])
    ps.append(rng.uniform(0.05, 0.4, int(accept.sum())))

    n_long = max(1, int(long_range_frac * n_populations))  # long-range fascicles
    li = rng.integers(0, n_populations, size=n_long)
    lj = rng.integers(0, n_populations, size=n_long)
    keep = li != lj
    srcs.append(li[keep])
    dsts.append(lj[keep])
    ps.append(rng.uniform(0.4, 0.9, int(keep.sum())))

    weights = neuron_counts * firing_rate * bytes_per_spike
    return Populations(
        src=np.concatenate(srcs),
        dst=np.concatenate(dsts),
        prob=np.concatenate(ps),
        weights=weights / weights.mean(),
    )


@dataclasses.dataclass(frozen=True)
class Synapses:
    """Neuron-level synapses in population-major ids
    (``neuron = population · neurons_per_pop + k``)."""

    pre: np.ndarray  # int64[nnz]
    post: np.ndarray  # int64[nnz]
    w: np.ndarray  # float32[nnz]
    n_neurons: int


def synapses(
    rng: np.random.Generator,
    w_rng: np.random.Generator,
    pp: np.ndarray,
    neurons_per_pop: int,
    *,
    synapse_p: float,
    w_scale: float,
    inhibitory_frac: float,
) -> Synapses:
    """Each ordered neuron pair (i in population a, j in population b,
    i ≠ j) is a synapse with probability ``pp[a, b] · synapse_p``; its
    weight is gamma(2, w_scale/2), negative where ``i`` is inhibitory.
    The pairs and the inhibitory neurons are drawn from ``rng``, the
    weights' magnitudes from ``w_rng``."""
    n_pop = pp.shape[0]
    k = neurons_per_pop
    m = n_pop * k
    inhib = rng.random(m) < inhibitory_frac
    pa, pb = np.nonzero(pp)
    hit = rng.random((pa.shape[0], k, k)) < (pp[pa, pb] * synapse_p)[:, None, None]
    e, ki, kj = np.nonzero(hit)
    pre = pa[e] * k + ki
    post = pb[e] * k + kj
    keep = pre != post
    pre, post = pre[keep], post[keep]
    w = w_rng.gamma(2.0, w_scale / 2.0, size=pre.shape[0]).astype(np.float32)
    w[inhib[pre]] *= -1.0
    return Synapses(pre=pre, post=post, w=w, n_neurons=m)


def equal_blocks(assign: np.ndarray, n_blocks: int) -> np.ndarray:
    """Equal population counts per block, in the partition's order: the
    executor needs blocks of one size (as the program's launcher does)."""
    n_pop = assign.shape[0]
    order = np.argsort(assign, kind="stable")
    out = np.empty(n_pop, np.int64)
    out[order] = np.arange(n_pop) // (n_pop // n_blocks)
    return out


def layout(block_of_pop: np.ndarray, neurons_per_pop: int) -> np.ndarray:
    """``int64[M]``: the population-major id of the neuron at each
    position of the block-contiguous layout (block 0's neurons first)."""
    pop_perm = np.argsort(block_of_pop, kind="stable")
    k = neurons_per_pop
    return (pop_perm[:, None] * k + np.arange(k)[None, :]).reshape(-1)


@dataclasses.dataclass(frozen=True)
class Network:
    """The synapses in layout order: ``pre``/``post`` are layout positions,
    sorted by ``pre``, with CSR pointers ``indptr`` over presynaptic
    neurons."""

    indptr: np.ndarray
    post: np.ndarray
    w: np.ndarray
    n_neurons: int

    @classmethod
    def from_synapses(cls, syn: Synapses, position_of: np.ndarray) -> "Network":
        pre = position_of[syn.pre]
        post = position_of[syn.post]
        order = np.argsort(pre, kind="stable")
        indptr = np.zeros(syn.n_neurons + 1, np.int64)
        np.cumsum(np.bincount(pre, minlength=syn.n_neurons), out=indptr[1:])
        return cls(indptr=indptr, post=post[order], w=syn.w[order], n_neurons=syn.n_neurons)

    def pre(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_neurons), np.diff(self.indptr))

    def tiles(self, n_blocks: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """What the program is given: ``(src, dst, f32[k, B, B])``, the
        ``B × B`` tile of ``W[pre, post]`` for every (source block,
        destination block) pair that holds a synapse, as
        ``BlockSynapses.from_tiles`` takes them."""
        b = self.n_neurons // n_blocks
        pre = self.pre()
        keys, tile = np.unique((pre // b) * n_blocks + self.post // b, return_inverse=True)
        out = np.zeros((keys.shape[0], b, b), np.float32)
        out[tile, pre % b, self.post % b] = self.w
        return keys // n_blocks, keys % n_blocks, out

    def dense(self) -> np.ndarray:
        """``f32[M, M]`` with ``W[pre, post]`` (brute-force checks)."""
        out = np.zeros((self.n_neurons, self.n_neurons), np.float32)
        out[self.pre(), self.post] = self.w
        return out

    def per_block(self, n_blocks: int) -> np.ndarray:
        """``int64[M, n_blocks]``: synapses of each presynaptic neuron onto
        the neurons of each block (a block is one chip's neurons)."""
        b = self.n_neurons // n_blocks
        key = self.pre() * n_blocks + self.post // b
        return np.bincount(key, minlength=self.n_neurons * n_blocks).reshape(
            self.n_neurons, n_blocks
        )
