"""Two-level (hierarchical) collective schedules — the paper's bridge
pattern mapped onto TPU mesh axes (DESIGN.md §3/§4).

On an InfiniBand GPU cluster the paper forwards cross-group traffic
through per-group bridge devices, collapsing ``O(N²)`` logical flows into
``O(G²)`` aggregated flows.  On a TPU multi-pod mesh the analogous slow
boundary is the ``pod`` axis (data-center interconnect between pods,
~an order of magnitude slower than intra-pod ICI).  The bridge pattern
becomes a *decomposed collective*:

* ``two_level_all_to_all``  — intra-pod all-to-all (level-1, fast ICI)
  followed by ONE aggregated counterpart-to-counterpart exchange across
  the pod axis (level-2).  Cross-pod message count drops from
  ``inner²·pods·(pods-1)`` to ``inner·pods·(pods-1)`` — the Fig. 4
  claim restated for TPU — while cross-pod bytes stay equal, so the
  α-term (per-message latency) shrinks by the group size.

* ``hierarchical_psum`` — reduce-scatter inside the pod, a single
  pod-axis all-reduce on the 1/inner-sized shard, all-gather inside the
  pod.  Cross-pod bytes drop by the factor ``inner`` versus a flat
  all-reduce over both axes (ring over the joint axis pushes full-size
  traffic across the pod boundary).

Every schedule here is expressed with ``jax.lax`` collectives inside
``shard_map`` and is numerically identical to its flat counterpart
(property-tested in ``tests/test_hierarchical.py``).
"""
from __future__ import annotations

import functools
from collections.abc import Sequence

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

__all__ = [
    "flat_all_to_all",
    "two_level_all_to_all",
    "flat_psum",
    "hierarchical_psum",
    "two_level_all_gather",
    "dispatch_bytes",
    "dispatch_messages",
    "dispatch_messages_from_table",
    "dispatch_rounds",
]


# ---------------------------------------------------------------------------
# All-to-all (MoE dispatch / spike exchange)
# ---------------------------------------------------------------------------


def flat_all_to_all(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Direct peer-to-peer exchange over the joint mesh axes (baseline).

    ``x`` per device: ``[n_devices, chunk, ...]`` — row ``d`` is the block
    destined to device ``d`` (row-major over ``axes``).  Returns the same
    shape where row ``d`` is the block *received from* device ``d``.
    """
    return lax.all_to_all(x, tuple(axes), split_axis=0, concat_axis=0, tiled=True)


def two_level_all_to_all(
    x: jax.Array, pod_axis: str = "pod", inner_axis: str = "data"
) -> jax.Array:
    """The paper's two-level routing as a decomposed all-to-all.

    ``x`` per device: ``[pods, inner, chunk, ...]`` — block ``[p', i']`` is
    destined to device ``(p', i')``.  Result: ``[pods, inner, chunk, ...]``
    where block ``[p, i]`` was *sent by* device ``(p, i)``.

    Level-1 (intra-pod): all-to-all over ``inner_axis`` on the destination
    inner index, so each device aggregates everything its pod sends to its
    own counterpart slot in every pod.  Each device thereby acts as the
    *bridge* for its slot — bridge responsibility is spread uniformly,
    which is exactly the balanced-bridge selection of Algorithm 2.

    Level-2 (cross-pod): all-to-all over ``pod_axis`` on the destination
    pod index — one aggregated message per (device, remote pod).
    """
    # Phase 1 — level-1 routing: exchange on dst-inner (axis 1).
    x = lax.all_to_all(x, inner_axis, split_axis=1, concat_axis=1, tiled=True)
    # Phase 2 — level-2 routing: aggregated exchange on dst-pod (axis 0).
    x = lax.all_to_all(x, pod_axis, split_axis=0, concat_axis=0, tiled=True)
    return x


def two_level_all_gather(
    x: jax.Array, pod_axis: str = "pod", inner_axis: str = "data"
) -> jax.Array:
    """All-gather decomposed as gather-inner → gather-pod (bridge pattern).

    Equivalent to ``all_gather`` over the joint axis but the cross-pod
    stage moves pod-aggregated blocks once instead of interleaving."""
    x = lax.all_gather(x, inner_axis, axis=0, tiled=True)
    x = lax.all_gather(x, pod_axis, axis=0, tiled=True)
    return x


# ---------------------------------------------------------------------------
# All-reduce (gradient reduction)
# ---------------------------------------------------------------------------


def flat_psum(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Flat all-reduce over the joint mesh axes (baseline)."""
    return lax.psum(x, tuple(axes))


def hierarchical_psum(
    x: jax.Array, pod_axis: str = "pod", inner_axis: str = "data"
) -> jax.Array:
    """Hierarchical all-reduce: RS(inner) → AR(pod) → AG(inner).

    Cross-pod bytes: ``size/inner`` per device instead of ``size`` —
    the bridge aggregation of Algorithm 2 applied to gradient traffic.
    Requires ``x.shape[0] %% inner_size == 0`` (pad upstream if needed).
    """
    scattered = lax.psum_scatter(x, inner_axis, scatter_dimension=0, tiled=True)
    reduced = lax.psum(scattered, pod_axis)
    return lax.all_gather(reduced, inner_axis, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Analytic message/byte accounting (used by benchmarks + EXPERIMENTS.md)
# ---------------------------------------------------------------------------


def dispatch_bytes(
    n_pods: int, n_inner: int, chunk_bytes: int, *, two_level: bool
) -> dict[str, float]:
    """Bytes crossing each boundary for one full exchange.

    Per device, every destination device receives ``chunk_bytes``.
    Intra-pod links carry level-1; the pod boundary carries level-2.
    """
    n_dev = n_pods * n_inner
    per_dev_total = n_dev * chunk_bytes
    cross_pod_frac = (n_pods - 1) / n_pods if n_pods > 1 else 0.0
    cross_pod = per_dev_total * cross_pod_frac * n_dev  # system-wide
    if not two_level:
        intra = per_dev_total * (1 - cross_pod_frac) * n_dev
        return {"intra_pod": intra, "cross_pod": cross_pod}
    # level-1 moves remote-destined data once inside the source pod too
    intra = per_dev_total * n_dev  # all data crosses an intra-pod link once
    return {"intra_pod": intra, "cross_pod": cross_pod}


def dispatch_messages(
    n_pods: int, n_inner: int, *, two_level: bool
) -> dict[str, int]:
    """Logical cross-pod message count (the paper's connection count)."""
    if n_pods <= 1:
        return {"cross_pod": 0, "intra_pod": n_inner * (n_inner - 1)}
    if two_level:
        cross = n_pods * (n_pods - 1) * n_inner  # counterpart pairs only
    else:
        cross = n_pods * (n_pods - 1) * n_inner * n_inner  # every pair
    return {
        "cross_pod": cross,
        "intra_pod": n_pods * n_inner * (n_inner - 1),
    }


def dispatch_rounds(
    n_pods: int, n_inner: int, chunk_bytes: int, *, two_level: bool
) -> list[list[tuple[int, int, int]]]:
    """Wire-level ``(src, dst, nbytes)`` triples per phase of the
    all-to-all — the replay input for :mod:`repro.netsim`.

    Devices are row-major over ``(pod, inner)``.  ``two_level=False``
    is one phase of direct P2P chunks (``n·(n-1)`` messages of
    ``chunk_bytes``).  ``two_level=True`` mirrors
    :func:`two_level_all_to_all`: phase 1 exchanges pod-aggregated
    slabs of ``n_pods · chunk_bytes`` between same-pod peers, phase 2
    moves one ``n_inner · chunk_bytes`` slab per (device, remote-pod
    counterpart) across the pod boundary.  Message counts match
    :func:`dispatch_messages` and cross-pod bytes match
    :func:`dispatch_bytes` by construction.
    """
    n_dev = n_pods * n_inner
    if not two_level:
        return [
            [
                (s, d, chunk_bytes)
                for s in range(n_dev)
                for d in range(n_dev)
                if s != d
            ]
        ]
    phase1 = [
        (p * n_inner + i, p * n_inner + j, n_pods * chunk_bytes)
        for p in range(n_pods)
        for i in range(n_inner)
        for j in range(n_inner)
        if i != j
    ]
    phase2 = [
        (p * n_inner + i, q * n_inner + i, n_inner * chunk_bytes)
        for p in range(n_pods)
        for q in range(n_pods)
        for i in range(n_inner)
        if p != q
    ]
    return [phase1, phase2]


def dispatch_messages_from_table(tb, *, threshold: float = 0.0) -> dict[str, int]:
    """*Measured* counterpart of :func:`dispatch_messages`.

    Where :func:`dispatch_messages` counts messages for a uniform
    ``pods × inner`` mesh analytically, this derives the level-1 / level-2
    logical message counts implied by an actual Algorithm-2
    :class:`~repro.core.routing.RoutingTable` (sparse or dense):

      * ``level1`` — direct same-group connections plus forwarder→bridge
        hops (the fast intra-pod / intra-group links);
      * ``level2`` — the aggregated bridge connections crossing the group
        boundary (the slow cross-pod links).

    For a P2P table every connection is level-2 (each flow leaves the
    device individually), matching the flat all-to-all accounting.
    """
    from repro.core.routing import connection_components

    direct, forward, aggregated = connection_components(tb, threshold=threshold)
    if tb.method == "p2p":
        return {"level1": 0, "level2": int(direct.sum())}
    return {
        "level1": int(direct.sum() + forward.sum()),
        "level2": int(aggregated.sum()),
    }


# ---------------------------------------------------------------------------
# shard_map entry points (jit-able, mesh-closing wrappers)
# ---------------------------------------------------------------------------


def make_exchange_fns(mesh: Mesh, pod_axis: str = "pod", inner_axis: str = "data"):
    """Build (flat, two_level) jit-ed exchange functions over ``mesh``.

    Input/output arrays are globally sharded ``[n_dev, n_dev, chunk, ...]``
    with the leading axis split over (pod, inner): row-block d of the
    global array is device d's per-destination send buffer.
    """
    n_pods = mesh.shape[pod_axis]
    n_inner = mesh.shape[inner_axis]
    spec_flat = P((pod_axis, inner_axis))

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec_flat,),
        out_specs=spec_flat,
        check_vma=False,
    )
    def _flat(x):
        # local block: [1, n_dev, chunk, ...] → drop leading, exchange, restore
        y = flat_all_to_all(x[0], (pod_axis, inner_axis))
        return y[None]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec_flat,),
        out_specs=spec_flat,
        check_vma=False,
    )
    def _two_level(x):
        blk = x[0].reshape((n_pods, n_inner) + x.shape[2:])
        y = two_level_all_to_all(blk, pod_axis, inner_axis)
        return y.reshape((1, n_pods * n_inner) + x.shape[2:])

    return jax.jit(_flat), jax.jit(_two_level)
