"""Routing-table-driven sparse/ragged spike exchange: block-CSR storage,
the masked exchange schedule, the ragged (bridge-compacted,
column-pruned) planner, the Pallas block kernel, and end-to-end parity
of ``exchange='sparse'``/``'ragged'`` with the single-device reference
engine."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TrafficMatrix,
    needed_sources,
    p2p_routing,
    payload_widths,
    pool_block_mask,
)
from repro.snn import (
    BlockSynapses,
    LIFParams,
    build_ragged_plan,
    exchange_schedule,
    exchange_volume,
    expand_synapses_sparse,
    generate_brain_model,
)
from tests.conftest import run_devices


def _clustered_w(m: int, n_blocks: int, *, extra=((0, 1),), seed: int = 2):
    """Block-diagonal weights plus a few off-diagonal tiles — the shape a
    good Algorithm-1 partition produces."""
    rng = np.random.default_rng(seed)
    b = m // n_blocks
    w = np.zeros((m, m), dtype=np.float32)
    pairs = [(d, d) for d in range(n_blocks)] + [
        ((d + di) % n_blocks, (d + dj) % n_blocks)
        for d in range(n_blocks)
        for di, dj in extra
    ]
    for src, dst in pairs:
        tile = (rng.random((b, b)) < 0.3) * rng.gamma(2.0, 2.0, (b, b))
        w[src * b : (src + 1) * b, dst * b : (dst + 1) * b] = tile
    np.fill_diagonal(w, 0.0)
    return w


class TestBlockSynapses:
    def test_dense_roundtrip_and_mask(self):
        w = _clustered_w(64, 8)
        syn = BlockSynapses.from_dense(w, 8)
        np.testing.assert_array_equal(syn.to_dense(), w)
        assert syn.nnzb < 64  # actually sparse
        mask = syn.mask()
        tiled = np.abs(w.reshape(8, 8, 8, 8).transpose(0, 2, 1, 3)).sum((2, 3))
        np.testing.assert_array_equal(mask | np.eye(8, dtype=bool), mask)
        np.testing.assert_array_equal(mask & ~np.eye(8, dtype=bool),
                                      (tiled > 0) & ~np.eye(8, dtype=bool))

    def test_padded_is_lossless(self):
        w = _clustered_w(64, 8)
        syn = BlockSynapses.from_dense(w, 8)
        src, blk = syn.padded()
        assert src.shape[0] == 8 and blk.shape[:2] == src.shape
        b = syn.block_size
        for d in range(8):
            dense_col = w[:, d * b : (d + 1) * b]
            rebuilt = np.zeros_like(dense_col)
            for k in range(src.shape[1]):
                s = src[d, k]  # padding tiles are all-zero: add nothing
                rebuilt[s * b : (s + 1) * b] += blk[d, k]
            np.testing.assert_array_equal(rebuilt, dense_col)

    def test_from_tiles_rejects_duplicates(self):
        t = np.ones((2, 4, 4), dtype=np.float32)
        with pytest.raises(ValueError, match="duplicate"):
            BlockSynapses.from_tiles([0, 0], [1, 1], t, 2)


class TestSchedule:
    def test_schedule_covers_exactly_the_mask(self):
        rng = np.random.default_rng(0)
        g = 6
        gmask = rng.random((g, g)) < 0.4
        np.fill_diagonal(gmask, True)
        rounds = exchange_schedule(gmask)
        assert len(rounds) == g - 1
        seen = set()
        for r, pairs in enumerate(rounds, start=1):
            for gs, gd in pairs:
                assert gd == (gs + r) % g  # shift structure
                assert gmask[gs, gd]
                seen.add((gs, gd))
        want = {
            (s, d) for s in range(g) for d in range(g) if s != d and gmask[s, d]
        }
        assert seen == want

    def test_exchange_volume_1d_and_2d(self):
        mask = np.eye(8, dtype=bool)
        mask[0, 4] = mask[4, 0] = True
        v1 = exchange_volume(mask, block_bytes=4)
        assert v1["flat"] == 8 * 7 * 4 and v1["sparse"] == 2 * 4
        v2 = exchange_volume(mask, mesh_shape=(4, 2), block_bytes=4)
        # groups {0,1},{2,3},{4,5},{6,7}: only groups 0↔2 exchange
        assert v2["flat"] == 4 * 3 * (2 * 2 * 4) and v2["sparse"] == 2 * (2 * 2 * 4)
        with pytest.raises(ValueError):
            exchange_volume(mask, mesh_shape=(3, 2), block_bytes=4)

    def test_exchange_volume_dense_mask_1d_equals_flat(self):
        """A fully dense mask schedules every pair: sparse == flat."""
        n, bb = 6, 16
        mask = np.ones((n, n), dtype=bool)
        v = exchange_volume(mask, block_bytes=bb)
        assert v["sparse"] == v["flat"] == n * (n - 1) * bb

    def test_exchange_volume_single_group_2d_is_zero(self):
        """A single-group 2-D mesh has no level-2 rounds: every exchange
        (flat, sparse, ragged) moves zero slow-axis bytes."""
        mask = np.ones((4, 4), dtype=bool)
        w = _clustered_w(16, 4)
        syn = BlockSynapses.from_dense(w, 4)
        plan = build_ragged_plan(syn, (1, 4))
        v = exchange_volume(mask, mesh_shape=(1, 4), block_bytes=16, plan=plan)
        assert v["flat"] == v["sparse"] == v["ragged"] == 0
        assert plan.bytes_per_step == 0 and not any(
            rnd.pairs for rnd in plan.rounds
        )

    def test_exchange_volume_ragged_matches_executed_bytes(self):
        """The 'ragged' entry equals the bytes of the packed schedule:
        per shift round, one padded payload per scheduled pair, widths
        derived independently from the dense weights.  The executor
        moves one masked ``R·B`` group block per scheduled pair."""
        w = _clustered_w(64, 8, extra=((0, 2), (1, 3)))
        syn = BlockSynapses.from_dense(w, 8)
        g, r = 4, 2
        plan = build_ragged_plan(syn, (g, r))
        rb = r * syn.block_size
        widths = {}
        for gs in range(g):
            for gd in range(g):
                if gs == gd:
                    continue
                slab = w[gs * rb : (gs + 1) * rb, gd * rb : (gd + 1) * rb]
                cols = np.count_nonzero(np.abs(slab).sum(axis=1) > 0)
                if cols:
                    widths[(gs, gd)] = int(cols)
        expected = 0
        for shift in range(1, g):
            pairs = [
                (gs, (gs + shift) % g)
                for gs in range(g)
                if (gs, (gs + shift) % g) in widths
            ]
            if pairs:
                expected += len(pairs) * max(widths[p] for p in pairs) * 4
        v = exchange_volume(
            syn.mask(), mesh_shape=(g, r), block_bytes=syn.block_size * 4,
            plan=plan,
        )
        assert v["ragged"] == expected == plan.bytes_per_step
        assert plan.packed_bytes_per_step <= plan.bytes_per_step
        assert plan.block_bytes_per_step == len(widths) * rb * 4
        assert plan.bytes_per_step <= plan.block_bytes_per_step < v["sparse"]
        with pytest.raises(ValueError, match="plan mesh"):
            exchange_volume(
                syn.mask(), mesh_shape=(2, 4), block_bytes=syn.block_size * 4,
                plan=plan,
            )


class TestRaggedPlan:
    def test_pair_columns_match_dense_bruteforce(self):
        w = _clustered_w(64, 8, extra=((0, 1), (0, 3)))
        syn = BlockSynapses.from_dense(w, 8)
        g, r = 4, 2
        plan = build_ragged_plan(syn, (g, r))
        b = syn.block_size
        rb = r * b
        for (gs, gd), cols in plan.pair_cols.items():
            slab = w[gs * rb : (gs + 1) * rb, gd * rb : (gd + 1) * rb]
            want = np.flatnonzero(np.abs(slab).sum(axis=1) > 0)
            np.testing.assert_array_equal(cols, want)

    def test_rounds_cover_each_scheduled_pair_once(self):
        w = _clustered_w(64, 8, extra=((0, 1), (1, 2)))
        syn = BlockSynapses.from_dense(w, 8)
        plan = build_ragged_plan(syn, (4, 2))
        seen = []
        for rnd in plan.rounds:
            for gs, gd in rnd.pairs:
                assert gd == (gs + rnd.shift) % 4
                seen.append((gs, gd))
        assert sorted(seen) == sorted(plan.pair_cols)
        for rnd in plan.rounds:
            if rnd.pairs:
                assert rnd.width == max(
                    plan.pair_cols[p].size for p in rnd.pairs
                )

    @pytest.mark.parametrize("g,r", [(8, 1), (4, 2), (8, 4)])
    def test_col_masks_keep_each_pairs_columns(self, g, r):
        """Each live round's mask is 1 exactly on the consumed columns of
        the pair sent from a device's group (every member of a sending
        group) and 0 elsewhere — the columns the packed payload holds."""
        n_dev, b = g * r, 32
        m, rb = b * n_dev, r * b
        w = _clustered_w(m, n_dev, extra=((0, 1), (1, 3), (0, 5)))
        cross = np.arange(m)[:, None] // rb != np.arange(m)[None, :] // rb
        w[1::2] *= ~cross[1::2]  # odd rows feed no other group
        syn = BlockSynapses.from_dense(w, n_dev)
        plan = build_ragged_plan(syn, (g, r))
        live = [rnd for rnd in plan.rounds if rnd.pairs]
        masks = plan.col_masks()
        assert len(masks) == len(live) > 0
        assert all(0 < cols.size <= rb // 2 for cols in plan.pair_cols.values())
        for rnd, mask in zip(live, masks):
            assert mask.shape == (n_dev, rb) and mask.dtype == np.float32
            want = np.zeros((n_dev, rb), np.float32)
            for gs, gd in rnd.pairs:
                want[gs * r : (gs + 1) * r, plan.pair_cols[(gs, gd)]] = 1.0
                src = rnd.perm[rnd.pairs.index((gs, gd))][0]
                sent = rnd.send_idx[src, : plan.pair_cols[(gs, gd)].size]
                np.testing.assert_array_equal(np.flatnonzero(mask[src]), np.sort(sent))
            np.testing.assert_array_equal(mask, want)
        assert plan.block_bytes_per_step == sum(len(rnd.pairs) for rnd in live) * rb * 4

    def test_bridge_compaction_one_sender_per_pair(self):
        """Exactly one flat device per scheduled pair appears in the
        ppermute perm, and it belongs to the sending group (bridge);
        the destination belongs to the receiving group."""
        w = _clustered_w(64, 8, extra=((0, 1),))
        syn = BlockSynapses.from_dense(w, 8)
        g, r = 4, 2
        plan = build_ragged_plan(syn, (g, r))
        for rnd in plan.rounds:
            assert len(rnd.perm) == len(rnd.pairs)
            for (gs, gd), (src, dst) in zip(rnd.pairs, rnd.perm):
                assert src // r == gs and dst // r == gd

    def test_bridge_inner_override_and_validation(self):
        w = _clustered_w(64, 8, extra=((0, 1),))
        syn = BlockSynapses.from_dense(w, 8)
        g, r = 4, 2
        bi = np.ones((g, g), dtype=np.int64)
        np.fill_diagonal(bi, -1)
        plan = build_ragged_plan(syn, (g, r), bridge_inner=bi)
        for rnd in plan.rounds:
            for src, dst in rnd.perm:
                assert src % r == 1 and dst % r == 1
        bad = bi.copy()
        bad[0, 1] = r  # out of range
        with pytest.raises(ValueError, match="bridge_inner"):
            build_ragged_plan(syn, (g, r), bridge_inner=bad)
        with pytest.raises(ValueError, match="blocks"):
            build_ragged_plan(syn, (2, 2))

    def test_mask_superset_pairs_get_full_blocks(self):
        """A routing-table mask can schedule pairs no tile realizes; the
        planner ships the full source blocks for those (safe superset)."""
        w = _clustered_w(64, 8, extra=())  # block-diagonal: no cross tiles
        syn = BlockSynapses.from_dense(w, 8)
        g, r, b = 4, 2, 8
        mask = np.eye(8, dtype=bool)
        mask[0, 2] = True  # device 0 (group 0) → device 2 (group 1)
        plan = build_ragged_plan(syn, (g, r), mask=mask)
        assert set(plan.pair_cols) == {(0, 1)}
        np.testing.assert_array_equal(plan.pair_cols[(0, 1)], np.arange(b))

    def test_tile_occupancy(self):
        tiles = np.zeros((2, 4, 4), dtype=np.float32)
        tiles[0, 1, 2] = 1.0
        tiles[1, 3, :] = -2.0
        syn = BlockSynapses.from_tiles([0, 1], [1, 0], tiles, 2)
        occ = syn.tile_occupancy()
        # from_tiles sorts by destination: tile for dst 0 first
        want = np.zeros((2, 4), dtype=bool)
        want[0, 3] = True  # src 1 → dst 0 tile, row 3 occupied
        want[1, 1] = True  # src 0 → dst 1 tile, row 1 occupied
        np.testing.assert_array_equal(occ, want)

    def test_payload_widths_superset(self):
        tm = TrafficMatrix.from_coo([0, 2], [1, 0], [1.0, 3.0], 4)
        wid = tm.payload_widths(16)
        assert wid[0, 1] == wid[2, 0] == 16
        assert wid[1, 0] == 0 and np.all(np.diag(wid) == 16)
        tb = p2p_routing(tm, np.ones(4))
        np.testing.assert_array_equal(payload_widths(tb, 16), wid)


class TestMaskExports:
    def test_consumer_mask_matches_traffic(self):
        tm = TrafficMatrix.from_coo([0, 2], [1, 0], [1.0, 3.0], 4)
        mask = tm.consumer_mask()
        assert mask[0, 1] and mask[2, 0]
        assert not mask[1, 0] and not mask[0, 2]
        assert mask.diagonal().all()

    def test_needed_sources_sparse_dense_agree(self):
        rng = np.random.default_rng(1)
        t = rng.random((12, 12)) * (rng.random((12, 12)) < 0.3)
        t = t + t.T
        np.fill_diagonal(t, 0.0)
        wg = np.ones(12)
        m_dense = needed_sources(p2p_routing(t, wg))
        m_sparse = needed_sources(p2p_routing(TrafficMatrix.from_dense(t), wg))
        np.testing.assert_array_equal(m_dense, m_sparse)

    def test_pool_block_mask(self):
        mask = np.eye(8, dtype=bool)
        mask[5, 0] = True
        gm = pool_block_mask(mask, np.arange(8) // 2, 4)
        assert gm[2, 0] and gm.diagonal().all()
        assert gm.sum() == 5  # 4 diagonal + the one pooled pair


class TestExpandSparse:
    @pytest.fixture(scope="class")
    def model(self):
        return generate_brain_model(
            n_populations=64, n_regions=8, total_neurons=10**6, seed=0
        )

    def test_structure_and_dale(self, model):
        syn, pop_of = expand_synapses_sparse(model.graph, 3, 8, seed=1)
        assert syn.n_neurons == 64 * 3 and pop_of.shape == (192,)
        w = syn.to_dense()
        assert np.allclose(np.diag(w), 0.0)
        for i in range(w.shape[0]):
            row = w[i][w[i] != 0]
            if row.size:
                assert (row > 0).all() or (row < 0).all()

    def test_deterministic(self, model):
        a, _ = expand_synapses_sparse(model.graph, 2, 8, seed=5)
        b, _ = expand_synapses_sparse(model.graph, 2, 8, seed=5)
        np.testing.assert_array_equal(a.src_ids, b.src_ids)
        np.testing.assert_array_equal(a.blocks, b.blocks)

    def test_tiles_respect_population_structure(self, model):
        """A stored tile implies a connected (or identical) population
        pair spanning that block pair — no phantom synapses."""
        syn, pop_of = expand_synapses_sparse(model.graph, 2, 8, seed=0)
        g = model.graph
        pp = np.zeros((64, 64), dtype=bool)
        rows = g.rows()
        pp[rows, g.indices] = pp[g.indices, rows] = True
        np.fill_diagonal(pp, True)
        blk_of_pop = np.empty(64, dtype=np.int64)
        ppb = 64 // 8
        blk_of_neuron = np.arange(syn.n_neurons) // syn.block_size
        for b in range(8):
            blk_of_pop[np.unique(pop_of[blk_of_neuron == b])] = b
        allowed = np.zeros((8, 8), dtype=bool)
        s, d = np.nonzero(pp)
        allowed[blk_of_pop[s], blk_of_pop[d]] = True
        for k, dst in zip(range(syn.nnzb), syn.dst_of()):
            assert allowed[syn.src_ids[k], dst]

    def test_uneven_assign_rejected(self, model):
        bad = np.zeros(64, dtype=np.int64)
        bad[:10] = 1
        with pytest.raises(ValueError, match="uneven"):
            expand_synapses_sparse(model.graph, 2, 8, assign=bad)


class TestBlockKernel:
    def test_matches_dense_and_ref(self):
        from repro.kernels import KernelPolicy, spike_currents_blocks
        from repro.kernels.ref import spike_accum_blocks_ref

        rng = np.random.default_rng(0)
        w = _clustered_w(512, 4, seed=4)
        syn = BlockSynapses.from_dense(w, 4)
        src_pad, blk_pad = syn.padded()
        b = syn.block_size
        s = (rng.random(512) < 0.05).astype(np.float32)
        sb = jnp.asarray(s.reshape(4, b))
        pol = KernelPolicy(use_pallas=True, interpret=True)
        for d in range(4):
            dense = s @ w[:, d * b : (d + 1) * b]
            ref = spike_accum_blocks_ref(
                sb, jnp.asarray(src_pad[d]), jnp.asarray(blk_pad[d])
            )
            np.testing.assert_allclose(np.asarray(ref), dense, rtol=1e-5, atol=1e-5)
            out = spike_currents_blocks(
                sb, jnp.asarray(src_pad[d]), jnp.asarray(blk_pad[d]), policy=pol
            )
            np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-5, atol=1e-5)

    def test_silent_input_is_zero(self):
        from repro.kernels import KernelPolicy, spike_currents_blocks

        blk = np.ones((3, 8, 8), dtype=np.float32)
        out = spike_currents_blocks(
            jnp.zeros((4, 8)),
            jnp.array([0, 2, 3]),
            jnp.asarray(blk),
            policy=KernelPolicy(use_pallas=True, interpret=True),
        )
        np.testing.assert_array_equal(np.asarray(out), np.zeros(8))


class TestSparseExchange:
    def test_sparse_and_ragged_match_reference_1d_and_2d(self):
        """``exchange='sparse'`` and ``'ragged'`` are bit-identical
        (modulo the neuron permutation already applied to W) to the
        single-device engine on a 1-D and a 2-D mesh, while moving
        strictly fewer slow-axis bytes than the flat oracle — and the
        ragged schedule never more than the sparse one (strictly fewer
        on the 2-D mesh, where bridge compaction kills the R×
        inner-position redundancy)."""
        code = """
import numpy as np, jax, jax.numpy as jnp
from repro.snn import SNNEngine, DistributedSNN, LIFParams, BlockSynapses
from jax.sharding import AxisType
from tests.test_snn_sparse import _clustered_w

m = 64
w = _clustered_w(m, 8)
params = LIFParams(noise_sigma=0.0)
ref = SNNEngine(w_syn=jnp.asarray(w), params=params, i_ext=4.0).run(
    60, key=jax.random.PRNGKey(7))
ref_r = np.asarray(ref.spikes)
syn = BlockSynapses.from_dense(w, 8)
for mesh, tag in [
    (jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,)), "1d"),
    (jax.make_mesh((4, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2), "2d"),
]:
    for exch in ("sparse", "ragged"):
        d = DistributedSNN(mesh=mesh, params=params, exchange=exch,
                           i_ext=4.0, syn=syn)
        raster = np.asarray(d.run(60, key=jax.random.PRNGKey(7)))
        np.testing.assert_allclose(raster, ref_r, err_msg=f"{tag}/{exch}")
    vol = d.exchange_stats()
    assert vol["ragged"] <= vol["sparse"] < vol["flat"], (tag, vol)
    if tag == "2d":
        assert vol["ragged"] < vol["sparse"], vol
    flat = DistributedSNN(mesh=mesh, w_syn=jnp.asarray(w), params=params,
                          exchange="flat", i_ext=4.0)
    np.testing.assert_allclose(np.asarray(flat.run(60, key=jax.random.PRNGKey(7))), ref_r)
print("OK")
"""
        assert "OK" in run_devices(code)

    def test_kernel_policy_flips_accumulation(self):
        """One config flag moves the block-CSR accumulation between the
        jnp einsum oracle and the (interpret-mode) Pallas
        ``spike_accum_blocks`` kernel, with the raster pinned identical
        on both the sparse and ragged exchanges."""
        code = """
import numpy as np, jax, jax.numpy as jnp
from repro.snn import DistributedSNN, LIFParams, BlockSynapses
from repro.kernels import KernelPolicy
from jax.sharding import AxisType
from tests.test_snn_sparse import _clustered_w

w = _clustered_w(64, 8)
params = LIFParams(noise_sigma=0.0)
syn = BlockSynapses.from_dense(w, 8)
mesh = jax.make_mesh((4, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
for exch in ("sparse", "ragged"):
    rasters = {}
    for name, pol in [
        ("einsum", KernelPolicy()),
        ("pallas", KernelPolicy(use_pallas=True, interpret=True)),
    ]:
        d = DistributedSNN(mesh=mesh, params=params, exchange=exch,
                           i_ext=4.0, syn=syn, policy=pol)
        rasters[name] = np.asarray(d.run(40, key=jax.random.PRNGKey(3)))
    np.testing.assert_allclose(rasters["einsum"], rasters["pallas"],
                               err_msg=exch)
print("OK")
"""
        assert "OK" in run_devices(code)

    def test_accum_stats_counts_the_strips_each_device_receives(self):
        """``accum_stats`` counts, per device and step, the 8-row strips
        of its stored tiles (padding tiles included) that hold a spike of
        the previous step which the exchange delivers: every column of a
        moved block for ``'sparse'``, and for ``'ragged'`` only the
        columns another group sends, those with a synapse into the
        receiving group."""
        code = """
import numpy as np, jax
from repro.snn import DistributedSNN, LIFParams, BlockSynapses
from jax.sharding import AxisType
from tests.test_snn_sparse import _clustered_w

m, n_dev, b = 128, 8, 16
w = _clustered_w(m, n_dev, extra=((0, 1), (2, 5)))
w[0:b, b:2 * b] = 0.0  # device 1 holds one tile fewer: a padding tile
w[2 * b:2 * b + 8, 4 * b:6 * b] = 0.0  # a strip of block 2 'ragged' skips on 2x2
syn = BlockSynapses.from_dense(w, n_dev)
src_pad, _ = syn.padded()
k = src_pad.shape[1]
raster = (np.random.default_rng(1).random((40, m)) < 0.1).astype(np.float32)
means = {}
for shape, names, exch in [((8,), ("data",), "sparse"),
                           ((4, 2), ("pod", "data"), "sparse"),
                           ((4, 2), ("pod", "data"), "ragged")]:
    mesh = jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))
    eng = DistributedSNN(mesh=mesh, params=LIFParams(), exchange=exch, syn=syn)
    r = 1 if len(shape) == 1 else shape[1]
    counts = []
    for t in range(40):
        prev = raster[t - 1] if t else np.zeros(m)
        for dev in range(n_dev):
            gd = dev // r
            into = w[:, gd * r * b:(gd + 1) * r * b] != 0  # synapses into the group
            n = 0
            for s in src_pad[dev]:
                rows = slice(s * b, (s + 1) * b)
                spikes = prev[rows] != 0
                gs = s // r
                if gs != gd and exch == "ragged":
                    spikes &= into[rows].any(axis=1)
                elif gs != gd:
                    spikes &= into[gs * r * b:(gs + 1) * r * b].any()
                n += spikes.reshape(-1, 8).any(axis=1).sum()
            counts.append(n)
    got = eng.accum_stats(raster)
    assert got["of"] == k * b // 8 and got["max"] == max(counts), (exch, got)
    assert abs(got["mean"] - np.mean(counts)) < 1e-12, (exch, got)
    assert 0 < got["mean"] < got["of"]
    means[exch] = got["mean"]
assert means["ragged"] < means["sparse"]  # the plan's pruned columns
print("OK")
"""
        assert "OK" in run_devices(code)

    @pytest.mark.parametrize("n_blocks,r", [(8, 1), (4, 2), (32, 4)])
    def test_block_rounds_match_dense_oracle(self, n_blocks, r):
        """Level 2 ships each pair's masked group block and lands it by a
        row write: the raster equals the dense ``'flat'`` oracle's bit for
        bit on a 1-D, a 2×2 and an (8, 4) mesh.  The drive is noisy and
        near threshold, so the raster depends on what level 2 delivers:
        empty masks change it, and the masks keep out every column the
        plan leaves unconsumed — strong synapses added later on those
        rows (same tiles) leave the raster as it was, while ``'sparse'``,
        which ships whole blocks, feels them."""
        code = f"""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.snn import DistributedSNN, LIFParams, BlockSynapses
from jax.sharding import AxisType
from tests.test_snn_sparse import _clustered_w

n_blocks, r, m, steps = {n_blocks}, {r}, 256, 300
b, g = m // n_blocks, n_blocks // r
w = _clustered_w(m, n_blocks, extra=((0, 1), (1, 3))) * 0.25
cross = np.arange(m)[:, None] // (r * b) != np.arange(m)[None, :] // (r * b)
w[1::2] *= ~cross[1::2]  # odd rows feed no other group
syn = BlockSynapses.from_dense(w, n_blocks)
params = LIFParams(noise_sigma=1.0)
key = jax.random.PRNGKey(7)
shape, names = ((g,), ("data",)) if r == 1 else ((g, r), ("pod", "data"))
mesh = jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))
ref = np.asarray(DistributedSNN(mesh=mesh, w_syn=jnp.asarray(w), params=params,
                                exchange="flat", i_ext=1.5).run(steps, key=key))
eng = DistributedSNN(mesh=mesh, params=params, exchange="ragged", i_ext=1.5, syn=syn)
plan = eng._ragged_plan()
assert eng.level2_rounds()["block"] > 0
raster = np.asarray(eng.run(steps, key=key))
assert raster.sum() > 0 and np.array_equal(raster, ref)

empty = {{p: c[:0] for p, c in plan.pair_cols.items()}}
cut = eng.with_plan(dataclasses.replace(plan, pair_cols=empty)).run(steps, key=key)
assert not np.array_equal(np.asarray(cut), ref)

tiles = np.repeat(np.repeat(syn.mask(), b, axis=0), b, axis=1)
w2 = w.copy()
w2[1::2] += 5.0 * (cross & tiles)[1::2]  # inside stored cross tiles only
syn2 = BlockSynapses.from_dense(w2, n_blocks)
assert np.array_equal(syn2.mask(), syn.mask())
masked = eng.with_plan(plan, syn=syn2).run(steps, key=key)
assert np.array_equal(np.asarray(masked), ref)
whole = DistributedSNN(mesh=mesh, params=params, exchange="sparse", i_ext=1.5, syn=syn2)
assert not np.array_equal(np.asarray(whole.run(steps, key=key)), ref)
print("OK")
"""
        assert "OK" in run_devices(code, n_devices=n_blocks)

    @pytest.mark.parametrize("g,r", [(8, 1), (4, 2)])
    def test_mask_plan_executes_as_blocks(self, g, r):
        """A plan made from the block mask alone (the out-of-core
        planner's, full device blocks for every masked pair) runs through
        the same executor and gives the dense oracle's raster."""
        code = f"""
import numpy as np, jax, jax.numpy as jnp
from repro.snn import DistributedSNN, LIFParams, BlockSynapses, build_ragged_plan_from_mask
from jax.sharding import AxisType
from tests.test_snn_sparse import _clustered_w

g, r = {g}, {r}
w = _clustered_w(256, 8, extra=((0, 1), (1, 3))) * 0.25
syn = BlockSynapses.from_dense(w, 8)
params = LIFParams(noise_sigma=1.0)
key = jax.random.PRNGKey(11)
shape, names = ((g,), ("data",)) if r == 1 else ((g, r), ("pod", "data"))
mesh = jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))
plan = build_ragged_plan_from_mask(syn.mask(), (g, r), syn.block_size)
eng = DistributedSNN(mesh=mesh, params=params, exchange="ragged", i_ext=1.5,
                     syn=syn, plan=plan)
ref = DistributedSNN(mesh=mesh, w_syn=jnp.asarray(w), params=params,
                     exchange="flat", i_ext=1.5).run(200, key=key)
raster = np.asarray(eng.run(200, key=key))
assert raster.sum() > 0 and np.array_equal(raster, np.asarray(ref))
print("OK")
"""
        assert "OK" in run_devices(code, n_devices=8)

    def test_sparse_from_expanded_model(self):
        """End-to-end: brain model → sparse expansion → sparse exchange
        equals the dense engine on the densified tiles."""
        code = """
import numpy as np, jax, jax.numpy as jnp
from repro.snn import (SNNEngine, DistributedSNN, LIFParams,
                       expand_synapses_sparse, generate_brain_model)
from jax.sharding import AxisType

bm = generate_brain_model(n_populations=32, n_regions=8,
                          total_neurons=10**6, seed=1)
syn, _ = expand_synapses_sparse(bm.graph, 2, 8, seed=2)
assert syn.density < 1.0
params = LIFParams(noise_sigma=0.0)
w = jnp.asarray(syn.to_dense())
ref = SNNEngine(w_syn=w, params=params, i_ext=4.0).run(
    50, key=jax.random.PRNGKey(3))
mesh = jax.make_mesh((4, 2), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
d = DistributedSNN(mesh=mesh, params=params, exchange="sparse", i_ext=4.0,
                   syn=syn)
np.testing.assert_allclose(
    np.asarray(d.run(50, key=jax.random.PRNGKey(3))),
    np.asarray(ref.spikes))
print("OK")
"""
        assert "OK" in run_devices(code)

    def test_validation(self):
        import jax
        from jax.sharding import AxisType

        from repro.snn import DistributedSNN

        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        with pytest.raises(ValueError, match="w_syn or syn"):
            DistributedSNN(mesh=mesh, params=LIFParams())
        with pytest.raises(ValueError, match="bogus"):
            DistributedSNN(
                mesh=mesh,
                params=LIFParams(),
                w_syn=jnp.zeros((4, 4)),
                exchange="bogus",
            )

    def test_dense_w_needed_for_flat(self):
        import jax
        from jax.sharding import AxisType

        from repro.snn import DistributedSNN

        syn = BlockSynapses.from_dense(np.zeros((4, 4), np.float32), 1)
        with pytest.raises(ValueError, match="dense w_syn"):
            DistributedSNN(
                mesh=jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,)),
                params=LIFParams(),
                exchange="flat",
                syn=syn,
            )
