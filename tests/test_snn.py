"""SNN substrate tests: generator, dynamics, engine."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from tests._hypothesis_compat import given, settings, st

from repro.snn import (
    IzhikevichParams,
    LIFParams,
    SNNEngine,
    expand_synapses,
    generate_brain_model,
    init_state,
    izhikevich_step,
    lif_step,
)


class TestBrainModel:
    def test_generation_deterministic(self):
        a = generate_brain_model(n_populations=128, n_regions=8, total_neurons=10**6, seed=3)
        b = generate_brain_model(n_populations=128, n_regions=8, total_neurons=10**6, seed=3)
        assert np.array_equal(a.graph.indices, b.graph.indices)
        assert np.array_equal(a.neuron_counts, b.neuron_counts)

    def test_scales_to_10b_neurons(self):
        bm = generate_brain_model(n_populations=512, n_regions=32, total_neurons=10_000_000_000)
        assert abs(bm.total_neurons - 10_000_000_000) / 1e10 < 0.01
        bm.graph.validate()

    def test_region_structure(self, small_brain):
        g = small_brain.graph
        rows = g.rows()
        same_region = small_brain.region_of[rows] == small_brain.region_of[g.indices]
        # intra-region connectivity dominates (community structure)
        assert same_region.mean() > 0.3

    def test_uneven_weights(self, small_brain):
        w = small_brain.graph.weights
        assert w.max() / w.mean() > 3  # heavy-tailed (paper guideline #3)


class TestDynamics:
    def test_lif_fires_and_resets(self):
        p = LIFParams()
        st_ = init_state(4, p, jax.random.PRNGKey(0))
        spikes_seen = jnp.zeros(4)
        s = st_
        for _ in range(600):
            s, spk = lif_step(s, jnp.full((4,), 3.0), p)
            spikes_seen = spikes_seen + spk
        assert float(spikes_seen.min()) > 0  # all neurons fired
        assert float(s.v.max()) < p.v_thresh + 1e-3

    def test_lif_refractory(self):
        p = LIFParams(t_refrac=5.0)
        s = init_state(1, p, jax.random.PRNGKey(0))
        s = s._replace(v=jnp.array([p.v_thresh + 1.0]))
        s, spk = lif_step(s, jnp.zeros(1), p)
        assert float(spk[0]) == 1.0
        s, spk2 = lif_step(s, jnp.full((1,), 100.0), p)
        assert float(spk2[0]) == 0.0  # refractory blocks immediate refire

    def test_izhikevich_spikes(self):
        p = IzhikevichParams()
        s = init_state(2, p, jax.random.PRNGKey(0))
        total = 0.0
        for _ in range(400):
            s, spk = izhikevich_step(s, jnp.full((2,), 10.0), p)
            total += float(spk.sum())
        assert total > 0

    @given(drive=st.floats(0.5, 5.0))
    @settings(max_examples=8, deadline=None)
    def test_rate_monotone_in_drive(self, drive):
        p = LIFParams()
        eng = SNNEngine(w_syn=jnp.zeros((8, 8)), params=p, i_ext=drive)
        low = eng.run(400, key=jax.random.PRNGKey(1)).rates.mean()
        eng2 = SNNEngine(w_syn=jnp.zeros((8, 8)), params=p, i_ext=drive + 1.0)
        high = eng2.run(400, key=jax.random.PRNGKey(1)).rates.mean()
        assert float(high) >= float(low)


class TestEngine:
    def test_expand_synapses_dale(self, small_brain):
        w, pop_of = expand_synapses(small_brain.graph, 2, seed=0)
        m = w.shape[0]
        assert w.shape == (m, m)
        assert np.allclose(np.diag(w), 0.0)
        # Dale's law: each neuron's outgoing weights share a sign
        for i in range(m):
            row = w[i][w[i] != 0]
            if row.size:
                assert (row > 0).all() or (row < 0).all()

    def test_engine_with_kernel_current(self):
        """The Pallas spike_accum kernel slots in as the current hook."""
        from repro.kernels import spike_currents, KernelPolicy

        rng = np.random.default_rng(0)
        w = (rng.random((128, 128)) < 0.1).astype(np.float32)
        np.fill_diagonal(w, 0)
        pol = KernelPolicy(use_pallas=True, interpret=True)
        eng = SNNEngine(w_syn=jnp.asarray(w), params=LIFParams(), i_ext=3.0)
        ref = eng.run(30, key=jax.random.PRNGKey(5))
        eng2 = SNNEngine(w_syn=jnp.asarray(w), params=LIFParams(), i_ext=3.0)
        out = eng2.run(
            30,
            key=jax.random.PRNGKey(5),
            current_fn=lambda s, wm: spike_currents(s, wm, policy=pol),
        )
        np.testing.assert_allclose(np.asarray(ref.spikes), np.asarray(out.spikes))


class TestDistributed:
    def test_distributed_matches_reference(self, run_code=None):
        from tests.conftest import run_devices

        code = """
import numpy as np, jax, jax.numpy as jnp
from repro.snn import SNNEngine, DistributedSNN, LIFParams
from repro.snn.distributed import partition_permutation
rng = np.random.default_rng(2)
m = 64
w = (rng.random((m, m)) < 0.2).astype(np.float32) * rng.gamma(2., 2., (m, m)).astype(np.float32)
np.fill_diagonal(w, 0)
params = LIFParams(noise_sigma=0.0)
ref = SNNEngine(w_syn=jnp.asarray(w), params=params, i_ext=4.0).run(60, key=jax.random.PRNGKey(7))
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
assign = np.repeat(np.arange(8), m // 8)
perm = partition_permutation(assign, 8)
wp = w[np.ix_(perm, perm)]
ref_p = np.asarray(ref.spikes)[:, perm]
for exch in ("flat", "two_level"):
    d = DistributedSNN(mesh=mesh, w_syn=jnp.asarray(wp), params=params, exchange=exch, i_ext=4.0)
    raster = np.asarray(d.run(60, key=jax.random.PRNGKey(7)))
    np.testing.assert_allclose(raster, ref_p)
print("OK")
"""
        out = run_devices(code)
        assert "OK" in out

    def test_routing_table_drives_mesh_end_to_end(self):
        """Algorithm 2 table (computed, not hand-built: the pair-swap
        refinement recovers the planted size-2 communities) →
        ``group_mesh_permutation`` → mesh: the permuted two-level, sparse
        and ragged exchanges reproduce the reference raster, the measured
        ``dispatch_messages_from_table`` level-2 connections cover
        exactly the cross-group pairs the sparse mesh schedule actually
        transfers (splits across a group's bridges only add parallel
        connections for the same pair), and the ragged accounting
        equals the executed packed-payload bytes derived independently
        from the synapse structure."""
        from tests.conftest import run_devices

        code = """
import numpy as np, jax, jax.numpy as jnp
from repro.snn import (SNNEngine, DistributedSNN, LIFParams, exchange_schedule,
                       bridge_inner_from_table)
from repro.snn.distributed import group_mesh_permutation
from repro.core import TrafficMatrix, needed_sources, pool_block_mask, two_level_routing
from repro.core.hierarchical import dispatch_messages_from_table
from jax.sharding import AxisType

# 8 devices in 4 communities of 2 (shuffled ids), ring between communities
grp = np.array([0, 2, 1, 3, 0, 1, 3, 2])
n_dev, B = 8, 8
m = n_dev * B
rng = np.random.default_rng(5)
w = np.zeros((m, m), dtype=np.float32)
for a in range(n_dev):
    for b in range(n_dev):
        same = grp[a] == grp[b]
        ring = (grp[a] + 1) % 4 == grp[b] or (grp[b] + 1) % 4 == grp[a]
        if not (same or ring):
            continue
        scale = 1.0 if same else 0.02  # strong communities, weak ring
        p = 0.6 if same else 0.3
        tile = (rng.random((B, B)) < p) * rng.gamma(2.0, 2.0, (B, B)) * scale
        w[a*B:(a+1)*B, b*B:(b+1)*B] = tile
np.fill_diagonal(w, 0.0)

# device traffic consistent with the realized synapses
t = np.abs(w).reshape(n_dev, B, n_dev, B).sum(axis=(1, 3))
t = t + t.T
np.fill_diagonal(t, 0.0)
# Algorithm 2 recovers the planted grouping (balanced pair-swaps: single
# moves cannot fix transposed members of full size-2 groups)
tb = two_level_routing(
    TrafficMatrix.from_dense(t), np.full(n_dev, float(B)), 4, seed=0)
planted = {frozenset(np.nonzero(grp == g)[0].tolist()) for g in range(4)}
got = {frozenset(np.nonzero(tb.group_of == g)[0].tolist()) for g in range(4)}
assert got == planted, (tb.group_of, grp)

perm, (G, R) = group_mesh_permutation(tb)
assert (G, R) == (4, 2)
neuron_perm = (perm[:, None] * B + np.arange(B)).ravel()
wp = w[np.ix_(neuron_perm, neuron_perm)]

params = LIFParams(noise_sigma=0.0)
ref = SNNEngine(w_syn=jnp.asarray(w), params=params, i_ext=4.0).run(
    60, key=jax.random.PRNGKey(7))
ref_p = np.asarray(ref.spikes)[:, neuron_perm]
mesh = jax.make_mesh((G, R), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
rasters = {}
bridge_inner = bridge_inner_from_table(tb)
for exch in ("flat", "two_level", "sparse", "ragged"):
    d = DistributedSNN(mesh=mesh, w_syn=jnp.asarray(wp), params=params,
                       exchange=exch, i_ext=4.0,
                       bridge_inner=bridge_inner if exch == "ragged" else None)
    rasters[exch] = np.asarray(d.run(60, key=jax.random.PRNGKey(7)))
    np.testing.assert_allclose(rasters[exch], ref_p)
    if exch == "sparse":
        vol = d.exchange_stats()
        assert vol["sparse"] < vol["flat"], vol

# measured level-2 accounting covers the mesh schedule's cross-group
# transfers: the distinct bridged group pairs ARE the scheduled pairs
# (in mesh group labels via the permutation), and split flows only add
# parallel bridge connections for the same pair
mask = needed_sources(tb)[np.ix_(perm, perm)]  # mesh device order
gmask = pool_block_mask(mask, np.arange(n_dev) // R, G)
sched_pairs = {p for pairs in exchange_schedule(gmask) for p in pairs}
scheduled = len(sched_pairs)
assert scheduled == 8  # ring: each group exchanges with its 2 neighbors
sdev, sgrp, _ = tb.share_coo
mesh_group = np.empty(G, dtype=np.int64)  # table group id -> mesh slot
mesh_group[tb.group_of[perm[::R]]] = np.arange(G)
bridged = {(int(mesh_group[tb.group_of[d]]), int(mesh_group[g]))
           for d, g in zip(sdev, sgrp)}
assert bridged == sched_pairs, (bridged, sched_pairs)
msgs = dispatch_messages_from_table(tb)
assert msgs["level2"] >= scheduled, (msgs, scheduled)

# ragged accounting == executed packed-payload bytes, derived here
# independently of the planner: per scheduled pair, the consumed source
# columns are the nonzero rows of the permuted weight slab; each shift
# round pads its pairs to the round max and moves one payload per pair.
group_of = np.arange(n_dev) // R
widths = {}
for gs in range(G):
    for gd in range(G):
        if gs == gd or not gmask[gs, gd]:
            continue
        rows = np.nonzero(group_of == gs)[0]
        cols = np.nonzero(group_of == gd)[0]
        slab = wp[rows[0]*B:(rows[-1]+1)*B, cols[0]*B:(cols[-1]+1)*B]
        widths[(gs, gd)] = int(np.count_nonzero(np.abs(slab).sum(axis=1) > 0))
expected = 0
for shift in range(1, G):
    pairs = [(gs, (gs + shift) % G) for gs in range(G)
             if (gs, (gs + shift) % G) in widths]
    if pairs:
        expected += len(pairs) * max(widths[p] for p in pairs) * 4
d = DistributedSNN(mesh=mesh, w_syn=jnp.asarray(wp), params=params,
                   exchange="ragged", i_ext=4.0, bridge_inner=bridge_inner)
vol = d.exchange_stats()
assert vol["ragged"] == expected, (vol, expected, widths)
assert vol["ragged"] < vol["sparse"] < vol["flat"], vol
print("OK")
"""
        out = run_devices(code)
        assert "OK" in out
