"""planlint Layer-1 tests: golden silence + targeted mutations.

Every mutation takes a known-good artifact from one pipeline stage,
applies one corruption, and asserts the linter flags it with the
documented rule id — and the seeded benchmark scenarios stay silent.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.analysis import PlanContext, run_lints
from repro.analysis.cli import load_table_npz, main as cli_main, save_table_npz
from repro.core.graph import CommGraph, planted_partition_graph
from repro.core.routing import p2p_routing, two_level_routing
from repro.core.traffic import TrafficMatrix
from repro.snn.ragged import build_ragged_plan
from repro.snn.sparse import BlockSynapses


def _ids(findings):
    return {f.rule_id for f in findings}


@pytest.fixture(scope="module")
def good_table():
    n, g = 64, 8
    graph, _ = planted_partition_graph(
        n, n_blocks=g, avg_degree=16, p_in_frac=0.9, seed=0
    )
    tm = TrafficMatrix.from_coo(
        graph.rows(), graph.indices, graph.edge_traffic(), n
    ).symmetrized(halve=True)
    wg = np.ones(n)
    return two_level_routing(tm, wg, g, seed=0), tm, wg


@pytest.fixture(scope="module")
def good_plan():
    from repro.snn import expand_synapses_sparse, generate_brain_model

    bm = generate_brain_model(
        n_populations=64, n_regions=8, total_neurons=10**6, seed=0
    )
    syn, _ = expand_synapses_sparse(bm.graph, 4, 16, seed=0)
    return syn, build_ragged_plan(syn, (4, 4))


# ---------------------------------------------------------------------------
# golden silence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "scenario", ["fig3a", "fig3b", "table2", "snn_throughput", "replan_bench"]
)
def test_seeded_scenarios_are_silent(scenario):
    from repro.analysis.scenarios import build_scenario

    for ctx in build_scenario(scenario):
        assert run_lints(ctx) == [], ctx.name


def test_good_table_is_silent(good_table):
    tb, _tm, wg = good_table
    ctx = PlanContext.from_table(tb, name="good", wg=wg, balance_slack=0.25)
    assert run_lints(ctx) == []


def test_good_plan_is_silent(good_plan):
    syn, plan = good_plan
    ctx = PlanContext.from_synapses(
        syn, (4, 4), name="good", plan=plan, waste_threshold=1.0
    )
    assert run_lints(ctx) == []


# ---------------------------------------------------------------------------
# table / schedule mutations
# ---------------------------------------------------------------------------


def test_bridge_out_of_group_pl005(good_table):
    tb, _, _ = good_table
    bad = np.array(tb.bridge, copy=True)
    bad[0, 1] = tb.members(1)[0]  # a member of group 1 bridging for group 0
    ctx = PlanContext.from_table(dataclasses.replace(tb, bridge=bad))
    assert "PL005" in _ids(run_lints(ctx))


def test_dropped_round_pl101(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(tb)
    live = next(i for i, pairs in enumerate(ctx.schedule) if pairs)
    ctx.schedule = [
        [] if i == live else pairs for i, pairs in enumerate(ctx.schedule)
    ]
    findings = run_lints(ctx)
    assert "PL101" in _ids(findings)
    assert any("no scheduled round" in f.message for f in findings)


def test_unmasked_scheduled_pair_pl101(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(tb)
    gs, gd = ctx.schedule[0][0]
    ctx.gmask = np.array(ctx.gmask, copy=True)
    ctx.gmask[gs, gd] = False  # schedule now ships a dead transfer
    findings = run_lints(ctx)
    assert "PL101" in _ids(findings)
    assert any("no masked traffic" in f.message for f in findings)


def test_duplicate_send_pl110(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(tb)
    ctx.schedule = [list(p) for p in ctx.schedule]
    ctx.schedule[0].append(ctx.schedule[0][0])
    assert "PL110" in _ids(run_lints(ctx))


def test_self_send_pl110(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(tb)
    ctx.schedule = [list(p) for p in ctx.schedule]
    ctx.schedule[1].append((3, 3))
    findings = run_lints(ctx)
    assert any(
        f.rule_id == "PL110" and "self-send" in f.message for f in findings
    )


def test_too_many_rounds_pl110(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(tb)
    ctx.schedule = list(ctx.schedule) + [[(0, 1)]]
    findings = run_lints(ctx)
    assert any(
        f.rule_id == "PL110" and "at most G-1" in f.message for f in findings
    )


def test_dead_device_still_bridging_pl120(good_table):
    tb, _, _ = good_table
    dead = int(tb.bridge[tb.bridge >= 0].ravel()[0])
    ctx = PlanContext.from_table(tb, dead=[dead])
    findings = run_lints(ctx)
    assert "PL120" in _ids(findings)


def test_share_fraction_desync_pl121(good_table):
    tb, _, _ = good_table
    dev, grp, frac = tb.share_coo
    bad = dataclasses.replace(tb, share_coo=(dev, grp, frac * 0.5))
    assert "PL121" in _ids(run_lints(PlanContext.from_table(bad)))


def test_share_primary_missing_pl121(good_table):
    tb, _, _ = good_table
    dev, grp, frac = (np.array(a, copy=True) for a in tb.share_coo)
    # retarget a whole-flow share (frac == 1) to a non-primary member of
    # the same group: sums stay 1, but the primary bridge loses its row
    i = int(np.flatnonzero(frac == 1.0)[0])
    members = tb.members(int(tb.group_of[dev[i]]))
    dev[i] = int(members[members != dev[i]][0])
    bad = dataclasses.replace(tb, share_coo=(dev, grp, frac))
    findings = run_lints(PlanContext.from_table(bad))
    assert any(
        f.rule_id == "PL121" and "primary bridge" in f.message
        for f in findings
    )


def test_p2p_table_with_shares_pl121(good_table):
    _, tm, wg = good_table
    p2p = p2p_routing(tm, wg)
    bad = dataclasses.replace(
        p2p,
        share_coo=(
            np.array([0]),
            np.array([1]),
            np.array([1.0]),
        ),
    )
    # the validate() delegation covers the historical P2P blind spot …
    with pytest.raises(ValueError, match="PL121"):
        bad.validate()
    # … and the batch linter flags the same corruption
    assert "PL121" in _ids(run_lints(PlanContext.from_table(bad)))
    # a clean P2P table still validates
    p2p.validate()


def test_unbalanced_groups_pl130(good_table):
    tb, _, _ = good_table
    wg = np.ones(tb.n_devices)
    wg[tb.members(0)] = 10.0
    ctx = PlanContext.from_table(tb, wg=wg)
    findings = run_lints(ctx)
    assert any(
        f.rule_id == "PL130" and f.severity == "warning" for f in findings
    )


def test_empty_group_pl131(good_table):
    tb, _, _ = good_table
    group_of = np.array(tb.group_of, copy=True)
    group_of[group_of == 7] = 6  # group 7 loses every member
    bad = dataclasses.replace(tb, group_of=group_of)
    assert "PL131" in _ids(run_lints(PlanContext.from_table(bad)))


def test_unroutable_pair_pl150(good_table):
    from repro import netsim

    tb, _, _ = good_table
    # fabric half the size of the device set: high device ids can't route
    ctx = PlanContext.from_table(tb, topology=netsim.single_switch(32))
    assert "PL150" in _ids(run_lints(ctx))


# ---------------------------------------------------------------------------
# ragged-plan mutations
# ---------------------------------------------------------------------------


def _live_round(plan, min_width=2):
    return next(
        i
        for i, rnd in enumerate(plan.rounds)
        if rnd.pairs and rnd.width >= min_width
    )


def test_inflated_width_pl102(good_plan):
    syn, plan = good_plan
    i = _live_round(plan)
    rounds = list(plan.rounds)
    rounds[i] = dataclasses.replace(rounds[i], width=rounds[i].width + 5)
    bad = dataclasses.replace(plan, rounds=tuple(rounds))
    ctx = PlanContext.from_synapses(syn, (4, 4), plan=bad, waste_threshold=1.0)
    assert "PL102" in _ids(run_lints(ctx))


def test_dropped_plan_pair_pl102(good_plan):
    syn, plan = good_plan
    i = next(j for j, rnd in enumerate(plan.rounds) if len(rnd.pairs) >= 2)
    rounds = list(plan.rounds)
    rounds[i] = dataclasses.replace(
        rounds[i],
        pairs=rounds[i].pairs[1:],
        perm=rounds[i].perm[1:],
    )
    bad = dataclasses.replace(plan, rounds=tuple(rounds))
    ctx = PlanContext.from_synapses(syn, (4, 4), plan=bad, waste_threshold=1.0)
    findings = run_lints(ctx)
    assert any(
        f.rule_id == "PL102" and "no scheduled round" in f.message
        for f in findings
    )


def test_trash_slot_collision_pl141(good_plan):
    syn, plan = good_plan
    rb = 4 * syn.block_size
    i = _live_round(plan)
    rnd = plan.rounds[i]
    recv = np.array(rnd.recv_idx, copy=True)
    row = next(
        d for d in range(recv.shape[0]) if np.count_nonzero(recv[d] < rb) >= 2
    )
    live = np.flatnonzero(recv[row] < rb)
    recv[row, live[1]] = recv[row, live[0]]  # two lanes, one buffer slot
    rounds = list(plan.rounds)
    rounds[i] = dataclasses.replace(rnd, recv_idx=recv)
    bad = dataclasses.replace(plan, rounds=tuple(rounds))
    ctx = PlanContext.from_synapses(syn, (4, 4), plan=bad, waste_threshold=1.0)
    assert "PL141" in _ids(run_lints(ctx))


def test_send_column_out_of_bounds_pl142(good_plan):
    syn, plan = good_plan
    rb = 4 * syn.block_size
    i = _live_round(plan)
    send = np.array(plan.rounds[i].send_idx, copy=True)
    send[0, 0] = rb  # reads past the group block
    rounds = list(plan.rounds)
    rounds[i] = dataclasses.replace(rounds[i], send_idx=send)
    bad = dataclasses.replace(plan, rounds=tuple(rounds))
    ctx = PlanContext.from_synapses(syn, (4, 4), plan=bad, waste_threshold=1.0)
    assert "PL142" in _ids(run_lints(ctx))


def test_padding_waste_warns_pl140(good_plan):
    syn, plan = good_plan
    ctx = PlanContext.from_synapses(
        syn, (4, 4), plan=plan, waste_threshold=0.0
    )
    findings = [f for f in run_lints(ctx) if f.rule_id == "PL140"]
    assert findings and all(f.severity == "warning" for f in findings)


# ---------------------------------------------------------------------------
# structural (PL00x) mutations through the context path
# ---------------------------------------------------------------------------


def test_traffic_diagonal_pl002():
    tm = TrafficMatrix(
        indptr=np.array([0, 1, 1]),
        indices=np.array([0]),  # self-traffic
        data=np.array([1.0]),
    )
    assert "PL002" in _ids(run_lints(PlanContext(traffic=tm)))


def test_graph_bad_probs_pl001():
    g = CommGraph(
        indptr=np.array([0, 1, 1]),
        indices=np.array([1]),
        probs=np.array([1.5]),  # > 1
        weights=np.ones(2),
    )
    assert "PL001" in _ids(run_lints(PlanContext(graph=g)))


def test_partition_out_of_range_pl003():
    ctx = PlanContext(partition=np.array([0, 1, 5]), n_parts=2)
    assert "PL003" in _ids(run_lints(ctx))


def test_synapses_unsorted_pl004():
    b = 2
    syn = BlockSynapses(
        indptr=np.array([0, 2, 2]),
        src_ids=np.array([1, 0]),  # unsorted within destination 0
        blocks=np.ones((2, b, b), dtype=np.float32),
        n_blocks=2,
    )
    assert "PL004" in _ids(run_lints(PlanContext(syn=syn)))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_table_roundtrip_and_exit_codes(good_table, tmp_path, capsys):
    tb, _, _ = good_table
    good = tmp_path / "good.npz"
    save_table_npz(tb, str(good))
    back = load_table_npz(str(good))
    assert np.array_equal(back.bridge, tb.bridge)
    assert np.array_equal(back.group_of, tb.group_of)
    assert np.array_equal(
        back.device_traffic.indptr, tb.device_traffic.indptr
    )
    assert np.array_equal(back.device_traffic.data, tb.device_traffic.data)
    assert cli_main(["--table", str(good)]) == 0

    dev, grp, frac = tb.share_coo
    bad_tb = dataclasses.replace(tb, share_coo=(dev, grp, frac * 0.5))
    bad = tmp_path / "bad.npz"
    save_table_npz(bad_tb, str(bad))
    assert cli_main(["--table", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "PL121" in out


def test_cli_scenario_exit_zero(capsys):
    assert cli_main(["--scenario", "table2"]) == 0
    assert "ok [" in capsys.readouterr().out


def test_cli_rule_catalog(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("PL001", "PL101", "PL110", "PL121", "PL150", "PL201"):
        assert rid in out


# ---------------------------------------------------------------------------
# Layer 2: traced-step regression (subprocess, 32 fake devices)
# ---------------------------------------------------------------------------


def test_traced_collective_counts_pinned():
    """Pin the exact collective-eqn counts of the compiled sparse and
    ragged steps for the snn_throughput model on both meshes.

    These are the numbers PL201 checks against; a drift here means the
    lowering changed (e.g. an extra all-gather or a psum smuggled onto
    the hot path) and both this pin and ``expected_collectives`` must be
    revisited together.
    """
    from tests.conftest import run_devices

    code = """
import json
from repro.analysis import count_collectives, expected_collectives, \\
    lint_traced_step
import jax
from jax.sharding import AxisType
from repro.snn import (DistributedSNN, LIFParams, build_ragged_plan,
                       expand_synapses_sparse, generate_brain_model)

bm = generate_brain_model(
    n_populations=128, n_regions=16, total_neurons=10**7, seed=0
)
syn, _ = expand_synapses_sparse(bm.graph, 4, 32, seed=0)
params = LIFParams(noise_sigma=0.0)
out = {}
for mesh_spec, tag in [
    (((32,), ("data",)), "1d"),
    (((8, 4), ("pod", "data")), "8x4"),
]:
    mesh = jax.make_mesh(*mesh_spec, axis_types=(AxisType.Auto,) * len(mesh_spec[1]))
    for exch in ("sparse", "ragged"):
        eng = DistributedSNN(mesh=mesh, params=params, exchange=exch,
                             i_ext=4.0, syn=syn)
        raw = count_collectives(eng.trace_step(2))
        counts = {p: raw.get(p, 0) for p in ("ppermute", "psum", "all_gather")}
        assert counts == expected_collectives(eng), (tag, exch, counts)
        assert lint_traced_step(eng) == [], (tag, exch)
        out[f"{tag}/{exch}"] = counts
print("COUNTS=" + json.dumps(out))
"""
    stdout = run_devices(code, n_devices=32)
    import json

    line = next(l for l in stdout.splitlines() if l.startswith("COUNTS="))
    counts = json.loads(line[len("COUNTS="):])
    assert counts["1d/sparse"] == {"ppermute": 31, "psum": 0, "all_gather": 0}
    assert counts["1d/ragged"] == {"ppermute": 31, "psum": 0, "all_gather": 0}
    assert counts["8x4/sparse"] == {"ppermute": 7, "psum": 0, "all_gather": 1}
    assert counts["8x4/ragged"] == {"ppermute": 7, "psum": 7, "all_gather": 1}


# ---------------------------------------------------------------------------
# PL170 / PL171 — fault-recovery isolation rules
# ---------------------------------------------------------------------------


def test_recovered_plan_silent_pl17x(good_table):
    """A plan produced by the real recovery path (batched evacuate +
    delta replan) must pass both fault rules — this is the clean half of
    the mutation pair below."""
    from repro.core.replan import evacuate_devices, replan

    tb, _tm, wg = good_table
    dead = [5, 17]
    ev = evacuate_devices(tb, wg, dead)
    res = replan(tb, ev.wg_after, ev.delta, dead=dead)
    ctx = PlanContext.from_table(
        res.table, name="recovered", wg=ev.wg_after, dead=dead
    )
    assert not {"PL170", "PL171"} & _ids(run_lints(ctx))


def test_dead_device_in_bridge_row_pl170(good_table):
    """Mutation: electing an evacuated device as a group bridge must
    fire PL170 — at runtime that row would wait on a dead sender."""
    from repro.core.replan import evacuate_devices, replan

    tb, _tm, wg = good_table
    dead = [5]
    ev = evacuate_devices(tb, wg, dead)
    res = replan(tb, ev.wg_after, ev.delta, dead=dead)
    tb2 = res.table
    bridge_bad = tb2.bridge.copy()
    gs, gd = np.argwhere(bridge_bad >= 0)[0]
    bridge_bad[gs, gd] = 5  # re-elect the evacuated device
    ctx = PlanContext.from_table(
        dataclasses.replace(tb2, bridge=bridge_bad), dead=dead
    )
    assert "PL170" in _ids(run_lints(ctx))


def test_dead_device_in_traffic_csr_pl170(good_table):
    """Mutation: traffic still booked on an evacuated device (evacuation
    skipped / delta dropped) must fire PL170 with src+dst counts."""
    tb, _tm, _wg = good_table
    dead = [int(tb.bridge[tb.bridge >= 0].ravel()[0])]
    ctx = PlanContext.from_table(tb, dead=dead)  # un-evacuated table
    pl170 = [f for f in run_lints(ctx) if f.rule_id == "PL170"]
    assert pl170
    assert any("sent" in f.message and "received" in f.message for f in pl170)


def test_downed_link_without_backup_pl171():
    """Mutation: a scheduled pair whose only route crosses a downed link
    (single_switch has no alternate path) must fire PL171."""
    from repro.netsim.topology import single_switch

    topo = single_switch(8)
    up0 = int(topo.route(0, 1)[0])
    ctx = PlanContext(
        name="outage",
        mesh_shape=(8, 1),
        schedule=[[(0, 1)]],
        topology=topo,
        down_links=[up0],
    )
    assert "PL171" in _ids(run_lints(ctx))


def test_downed_link_with_spine_backup_silent_pl171():
    """A fat-tree pair crossing a downed spine uplink stays silent:
    ``route_avoiding`` finds the alternate spine, so netsim replay will
    reroute rather than stall."""
    from repro.netsim.topology import fat_tree

    topo = fat_tree(8, 2)
    primary = topo.route(0, 6)
    leaf_up = int(primary[1])  # leaf -> spine hop
    ctx = PlanContext(
        name="outage-backup",
        mesh_shape=(8, 1),
        schedule=[[(0, 6)]],
        topology=topo,
        down_links=[leaf_up],
    )
    findings = run_lints(ctx)
    assert "PL171" not in _ids(findings)
    assert topo.route_avoiding(0, 6, {leaf_up}) is not None


# ---------------------------------------------------------------------------
# PL180: dominant-bottleneck attribution (opt-in netsim replay)
# ---------------------------------------------------------------------------


def test_bottleneck_attribution_fires_pl180(good_table):
    """A two-tier fabric concentrates an Algorithm-2 forwarding replay
    on the leaf uplinks — with the opt-in threshold set below that
    share, PL180 reports the dominant kind and the decomposition."""
    from repro import netsim

    tb, _, _ = good_table
    topo = netsim.two_tier(64, 8)
    ctx = PlanContext.from_table(
        tb, name="bottleneck", topology=topo, bottleneck_threshold=0.3
    )
    findings = [f for f in run_lints(ctx) if f.rule_id == "PL180"]
    assert len(findings) == 1
    f = findings[0]
    assert f.severity == "info"
    assert "leaf_up" in f.message  # the oversubscribed tier
    assert "critical path" in f.message


def test_bottleneck_attribution_opt_in_pl180(good_table):
    """Without the threshold the rule is skipped (the replay is a full
    simulation — too costly for an unasked lint pass), and a threshold
    above the dominant share stays silent."""
    from repro import netsim

    tb, _, _ = good_table
    topo = netsim.two_tier(64, 8)
    ctx = PlanContext.from_table(tb, name="default", topology=topo)
    assert "PL180" not in _ids(run_lints(ctx))
    ctx_hi = PlanContext.from_table(
        tb, name="high-bar", topology=topo, bottleneck_threshold=0.99
    )
    assert "PL180" not in _ids(run_lints(ctx_hi))


def test_bottleneck_attribution_needs_topology_pl180(good_table):
    tb, _, _ = good_table
    ctx = PlanContext.from_table(
        tb, name="no-topo", bottleneck_threshold=0.0
    )
    assert "PL180" not in _ids(run_lints(ctx))


def test_bottleneck_attribution_in_catalog(capsys):
    assert cli_main(["--list-rules"]) == 0
    assert "PL180" in capsys.readouterr().out
