"""Work counts against a brute-force sum over the raster and the synapse
lists, and the table of peaks."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import network as nw
from bench import work as wk
from bench.tests.conftest import ROOT


def small_network(seed: int, n_blocks: int) -> nw.Network:
    rng = np.random.default_rng(seed)
    pops = nw.brain_model(
        rng, n_populations=16, n_regions=8, total_neurons=10_000, intra_region_p=0.35,
        lambda_mm=28.0, inter_degree=12.0, long_range_frac=0.015, mean_rate_hz=4.0,
        bytes_per_spike=4.0,
    )
    syn = nw.synapses(rng, rng, pops.pair_probs(), 8, synapse_p=0.3, w_scale=0.4, inhibitory_frac=0.2)
    order = nw.layout(nw.equal_blocks(rng.integers(0, n_blocks, 16), n_blocks), 8)
    position_of = np.empty_like(order)
    position_of[order] = np.arange(order.shape[0])
    return nw.Network.from_synapses(syn, position_of)


@pytest.mark.parametrize("n_blocks", [1, 4])
def test_counts_match_brute_force(n_blocks):
    net = small_network(5, n_blocks)
    m, b = net.n_neurons, net.n_neurons // n_blocks
    rng = np.random.default_rng(6)
    rasters = [(rng.random((30, m)) < 0.05).astype(np.float32) for _ in range(2)]
    dense = net.dense()
    ops = np.zeros(n_blocks)
    xbytes = np.zeros(n_blocks)
    for r in rasters:
        for t in range(1, r.shape[0]):
            for i in np.flatnonzero(r[t - 1]):
                for d in range(n_blocks):
                    n_syn = np.count_nonzero(dense[i, d * b:(d + 1) * b])
                    ops[d] += 2 * n_syn
                    if n_syn and i // b != d:
                        xbytes[d] += 4
    w = wk.count(rasters, net.per_block(n_blocks))
    assert w.steps == 60
    np.testing.assert_array_equal(w.accum_ops, ops)
    np.testing.assert_array_equal(w.accum_bytes, 2 * ops)
    np.testing.assert_array_equal(w.exchange_bytes, xbytes)
    np.testing.assert_array_equal(w.state_bytes, np.full(n_blocks, 20.0 * b * 60))
    pk = wk.peaks("TPU v5 lite")
    least = w.step_least_s(pk)
    assert np.all(least >= w.accum_least_s(pk))
    np.testing.assert_allclose(w.accum_least_s(pk), 2 * ops / pk["hbm_bytes_per_s"])


def test_network_layout_is_a_permutation():
    net = small_network(7, 4)
    dense = net.dense()
    assert np.count_nonzero(dense) == net.post.shape[0]
    assert np.all(np.diag(dense) == 0)
    pre = net.pre()
    assert np.all(np.diff(pre) >= 0)
    # Dale's law: every presynaptic neuron's weights share one sign
    signs = {(int(i), bool(s)) for i, s in zip(pre, net.w < 0)}
    assert len({i for i, _ in signs}) == len(signs)


@pytest.mark.parametrize("n_blocks", [1, 4])
def test_tiles_are_the_dense_matrix_tiled(n_blocks):
    """The tiles the program is given hold the same synapses, in the same
    tiles, as its own tiling of the dense matrix would."""
    from repro.snn import BlockSynapses

    net = small_network(8, n_blocks)
    mine = BlockSynapses.from_tiles(*net.tiles(n_blocks), n_blocks)
    theirs = BlockSynapses.from_dense(net.dense(), n_blocks)
    np.testing.assert_array_equal(mine.indptr, theirs.indptr)
    np.testing.assert_array_equal(mine.src_ids, theirs.src_ids)
    np.testing.assert_array_equal(mine.blocks, theirs.blocks)


def test_seed_draws_weights_not_connectome():
    """Every seed gives the program the same synapse pattern (so the same
    tiles, exchange plan and compiled step) with other weights."""
    from bench import run

    cfg = json.loads((ROOT / "bench" / "configs" / "brain16k_2x2.json").read_text())
    cfg.update(populations=32, neurons_per_pop=16, neurons_per_device=128)
    cfg["model"] = dict(cfg["model"], n_regions=8)
    a, _ = run.network(cfg, 1)
    b, _ = run.network(cfg, 2**40 + 7)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.post, b.post)
    np.testing.assert_array_equal(np.sign(a.w), np.sign(b.w))
    assert not np.array_equal(a.w, b.w)


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(ValueError, match="no peaks"):
        wk.peaks("TPU v99")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"x": {"flops_per_s": 1}}))
    assert wk.peaks("x", p) == {"flops_per_s": 1}
