"""Distributed SNN engine — the paper's simulation system on a TPU mesh.

Neurons are assigned to devices by **Algorithm 1** (the partition result
is realized as a physical permutation), local dynamics run independently
per device, and the per-step spike exchange follows either

* ``exchange='flat'``      — every device broadcasts its spikes to every
  other device (the paper's direct P2P baseline: ``all_gather`` over the
  joint mesh axes), or
* ``exchange='two_level'`` — the paper's two-level routing: gather inside
  the group (level-1, fast axis), then one aggregated exchange across
  groups (level-2, slow/pod axis) — ``repro.core.hierarchical``, or
* ``exchange='sparse'``    — the **routing-table-driven** exchange: the
  block mask (nonzero incoming-weight tiles, or
  :func:`repro.core.routing.needed_sources` from an Algorithm-2 table)
  schedules masked ``ppermute`` rounds over the slow axis so only the
  blocks somebody actually consumes ever move
  (:mod:`repro.snn.sparse`), or
* ``exchange='ragged'``    — the **bridge-compacted, column-pruned**
  exchange (:mod:`repro.snn.ragged`): each scheduled cross-group pair
  moves its sending group's ``f32[R·B]`` block, with the source columns
  no receiver consumes masked to zero, from the sending group's bridge
  device straight to the receiving group's bridge, which re-broadcasts
  it over the fast axis — eliminating the ``R×`` inner-position
  redundancy ``'sparse'`` still carries, exactly the paper's
  Algorithm-2 bridge.

All four deliver the same effective global spike vector; what changes
is the collective schedule — message counts, bytes, and which links
carry them — exactly the paper's claim.  ``'flat'`` is kept as the dense
oracle the sparse/ragged paths are pinned against.

Synaptic accumulation per device: dense ``I_loc = s_global @ W[:, local]``
(each device holds the incoming-weight column block of the permuted
synapse matrix) for ``'flat'``/``'two_level'``; block-CSR
``I_loc = Σ_k s_blk[src_ids[k]] @ blocks[k]`` for ``'sparse'``/``'ragged'``
via :func:`repro.kernels.spike_currents_blocks`, so ``policy``
(:class:`repro.kernels.KernelPolicy`) flips the hot-spot between the
jnp einsum oracle and the Pallas ``spike_accum_blocks`` kernel — the
``[M, M]`` matrix is never materialized on that path.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.routing import pool_block_mask
from repro.obs import trace as obs
from repro.kernels.ops import KernelPolicy, spike_currents_blocks
from repro.kernels.ref import spike_accum_ref
from repro.snn.ragged import RaggedPlan, build_ragged_plan
from repro.snn.sparse import BlockSynapses, exchange_schedule, exchange_volume
from repro.snn.neuron import (
    IzhikevichParams,
    LIFParams,
    NeuronState,
    init_state,
    izhikevich_step,
    lif_step,
)

__all__ = [
    "DistributedSNN",
    "PlanBuffer",
    "STEP_SCOPES",
    "partition_permutation",
    "group_mesh_permutation",
]

#: the ``jax.named_scope`` names of the compiled sparse/ragged step
#: (:func:`_sparse_step`), one per part of the work: the fast-axis
#: gather (level 1); the slow-axis exchange (level 2) as packing the
#: payload, moving it and landing it in the block buffer; the synaptic
#: accumulation; and the noise draw with the neuron update.  They reach
#: the compiled program's ``op_name`` metadata, so a device trace can
#: name each op's part of the step.
STEP_SCOPES = (
    "exchange/level1",
    "exchange/level2/pack",
    "exchange/level2/send",
    "exchange/level2/unpack",
    "accumulate",
    "neuron",
)
LEVEL1, PACK, SEND, UNPACK, ACCUMULATE, NEURON = STEP_SCOPES


def group_mesh_permutation(tb) -> tuple[np.ndarray, tuple[int, int]]:
    """Map an Algorithm-2 :class:`~repro.core.routing.RoutingTable` onto a
    2-D device mesh.

    Returns ``(perm, (G, N/G))``: ``perm`` orders devices
    group-contiguously (``perm[k]`` is the physical device at mesh slot
    ``k``), so a mesh of shape ``(G, N/G)`` puts axis 0 (the slow / pod
    axis) across routing groups and axis 1 inside each group — the
    ``exchange='two_level'`` schedule then realizes exactly the table's
    level-1 / level-2 split.  Requires equal group sizes (static mesh
    shapes); group with ``grouping='random'``/balanced partitions or pad
    upstream otherwise.
    """
    counts = np.bincount(tb.group_of, minlength=tb.n_groups)
    if counts.max() != counts.min():
        raise ValueError(
            f"uneven grouping ({counts.min()}–{counts.max()} devices per "
            "group); a mesh needs equal group sizes"
        )
    perm = np.argsort(tb.group_of, kind="stable")
    return perm, (tb.n_groups, int(counts[0]))


def partition_permutation(assign: np.ndarray, n_devices: int) -> np.ndarray:
    """Permutation placing neurons device-contiguously per ``assign``.

    Devices must receive equal counts (static shapes) — callers pad the
    assignment upstream if the partition is uneven (Alg. 1 with
    ``balance_slack=0`` on equal-weight neurons is already even).
    """
    counts = np.bincount(assign, minlength=n_devices)
    if counts.max() != counts.min():
        raise ValueError(
            f"uneven partition ({counts.min()}–{counts.max()} per device); "
            "equalize counts before building the permutation"
        )
    return np.argsort(assign, kind="stable")


@dataclasses.dataclass(frozen=True)
class DistributedSNN:
    """shard_map SNN engine over a 1-D or 2-D device mesh.

    Attributes:
      mesh: device mesh; axis names e.g. ``("data",)`` or ``("pod", "data")``.
      w_syn: ``f32[M, M]`` *permuted* synapse matrix (Alg. 1 order).
        Optional when ``syn`` is given and ``exchange`` is
        ``'sparse'``/``'ragged'``.
      params: neuron model constants.
      exchange: 'flat' | 'two_level' | 'sparse' | 'ragged' (two_level
        requires a 2-D mesh; sparse and ragged run on 1-D and 2-D).
      i_ext: external drive.
      syn: block-CSR synapse tiles (``exchange='sparse'``/``'ragged'``);
        derived from ``w_syn`` when omitted.  ``syn.n_blocks`` must equal
        the device count.
      policy: how the block-CSR accumulation hot-spot executes — the jnp
        einsum oracle (default) or the Pallas ``spike_accum_blocks``
        kernel (``KernelPolicy(use_pallas=True)``; add
        ``interpret=True`` on CPU).
      bridge_inner: ``int[G, G]`` inner mesh index of each group's bridge
        device per destination group (``exchange='ragged'``); ``None``
        spreads bridge duty round-robin.  Derive from an Algorithm-2
        table with :func:`repro.snn.ragged.bridge_inner_from_table`.
    """

    mesh: Mesh
    w_syn: jax.Array | None = None
    params: LIFParams | IzhikevichParams | None = None
    exchange: str = "flat"
    i_ext: float = 0.0
    syn: BlockSynapses | None = None
    policy: KernelPolicy = KernelPolicy()
    bridge_inner: np.ndarray | None = None
    plan: RaggedPlan | None = None

    def __post_init__(self):
        with obs.span("snn.engine_init", cat="build", tid="snn"):
            if self.params is None:
                raise ValueError("params is required")
            if self.exchange not in ("flat", "two_level", "sparse", "ragged"):
                raise ValueError(self.exchange)
            if self.exchange == "two_level" and len(self.mesh.axis_names) < 2:
                raise ValueError("two_level exchange needs a 2-D mesh")
            if self.w_syn is None and self.syn is None:
                raise ValueError("need w_syn or syn")
            if self.w_syn is None and self.exchange not in ("sparse", "ragged"):
                raise ValueError(f"exchange={self.exchange!r} needs dense w_syn")
            if self.syn is not None and self.syn.n_blocks != self.n_devices:
                raise ValueError(
                    f"syn has {self.syn.n_blocks} blocks for {self.n_devices} devices"
                )
            if self.plan is not None:
                if self.exchange != "ragged":
                    raise ValueError("plan= only applies to exchange='ragged'")
                if self.plan.mesh_shape != self._mesh_groups():
                    raise ValueError(
                        f"plan mesh {self.plan.mesh_shape} != engine mesh "
                        f"{self._mesh_groups()}"
                    )

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def n_devices(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axis_names]))

    def _mesh_groups(self) -> tuple[int, int]:
        """``(G, R)``: slow-axis size and devices per group.  1-D meshes
        treat every device as its own group (R = 1)."""
        axes = self.axis_names
        if len(axes) == 1:
            return self.mesh.shape[axes[0]], 1
        inner = int(np.prod([self.mesh.shape[a] for a in axes[1:]]))
        return self.mesh.shape[axes[0]], inner

    def _block_synapses(self) -> BlockSynapses:
        if self.syn is not None:
            return self.syn
        return BlockSynapses.from_dense(np.asarray(self.w_syn), self.n_devices)

    def _ragged_plan(self) -> RaggedPlan:
        """The static ragged level-2 schedule this engine executes (or
        would execute) with ``exchange='ragged'`` — the explicit
        ``plan`` field when set (the double-buffered swap path), else
        planned fresh from the synapse tiles."""
        if self.plan is not None:
            return self.plan
        g, r = self._mesh_groups()
        return build_ragged_plan(
            self._block_synapses(), (g, r), bridge_inner=self.bridge_inner
        )

    def with_plan(
        self, plan: RaggedPlan, *, syn: BlockSynapses | None = None
    ) -> "DistributedSNN":
        """New engine executing ``plan`` (and optionally edited synapse
        tiles) — the flip half of the double-buffered plan swap.

        When ``plan`` shares the active plan's :meth:`step_signature`,
        the flipped engine reuses the already-compiled step (the
        module-level :func:`_sparse_step` cache): only the index / tile
        *values* change, and those are jit inputs.
        """
        return dataclasses.replace(
            self, plan=plan, syn=self.syn if syn is None else syn
        )

    def step_signature(self) -> tuple:
        """Static signature of the compiled sparse/ragged step.

        Two engines with equal signatures (and equal mesh / params /
        policy) share one compiled step — array contents (column masks,
        synapse tiles) are jit inputs, so a plan swap that keeps the
        signature flips between steps without a recompile stall.  For
        ``'ragged'`` the signature is the live rounds' (shift, ppermute
        perm): every round moves ``R·B`` lanes a pair, whatever its
        columns; for ``'sparse'`` the masked round pair lists.
        """
        if self.exchange == "ragged":
            plan = self._ragged_plan()
            return (
                "ragged",
                tuple((rnd.shift, rnd.perm) for rnd in plan.rounds if rnd.pairs),
            )
        syn = self._block_synapses()
        g, r = self._mesh_groups()
        gmask = pool_block_mask(
            syn.mask(), np.arange(self.n_devices) // r, g
        )
        return (
            "sparse",
            tuple(tuple(pairs) for pairs in exchange_schedule(gmask)),
        )

    def exchange_stats(self) -> dict[str, int]:
        """Per-step slow-axis receive volume (bytes): the dense schedule
        vs the block-mask-driven one (``exchange='sparse'``) vs the
        bridge-compacted column-pruned one (``'ragged'``: the plan's
        packed payloads, what a fabric priced by the byte carries; the
        executor moves :attr:`~repro.snn.ragged.RaggedPlan.block_bytes_per_step`)."""
        syn = self._block_synapses()
        g, r = self._mesh_groups()
        return exchange_volume(
            syn.mask(),
            mesh_shape=(g, r) if len(self.axis_names) > 1 else (g,),
            block_bytes=syn.block_size * 4,
            plan=self._ragged_plan(),
        )

    def level2_rounds(self) -> dict[str, int]:
        """Live level-2 rounds of the ragged plan, ``{"block": n}``:
        every round ships its pairs' masked group blocks."""
        if self.exchange != "ragged":
            raise ValueError("level2_rounds covers exchange='ragged'")
        return {"block": sum(1 for rnd in self._ragged_plan().rounds if rnd.pairs)}

    def run(self, n_steps: int, *, key: jax.Array | None = None) -> jax.Array:
        """Simulate; returns the global spike raster ``[T, M]``."""
        key = jax.random.PRNGKey(0) if key is None else key
        if self.exchange in ("sparse", "ragged"):
            return self._run_sparse(n_steps, key=key)
        m = self.w_syn.shape[0]
        n_dev = self.n_devices
        if m % n_dev:
            raise ValueError("neuron count must divide the device count")
        axes = self.axis_names
        step = lif_step if isinstance(self.params, LIFParams) else izhikevich_step
        params = self.params
        i_ext = jnp.float32(self.i_ext)
        exchange = self.exchange

        col_spec = P(None, axes)  # W column-sharded: [M, M/n_dev] per device
        vec_spec = P(axes)  # state vectors sharded over neurons

        def gather(spikes_loc):
            if exchange == "flat":
                return jax.lax.all_gather(spikes_loc, axes, axis=0, tiled=True)
            pod, inner = axes[0], axes[1:]
            g = jax.lax.all_gather(spikes_loc, inner, axis=0, tiled=True)
            return jax.lax.all_gather(g, pod, axis=0, tiled=True)

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(vec_spec, vec_spec, P(axes), col_spec),
            out_specs=P(None, axes),
            check_vma=False,
        )
        def _run(v0, u0, keys, w_block):
            state = NeuronState(v=v0, u=u0, key=keys[0])
            n_loc = v0.shape[0]

            def body(carry, _):
                state, prev_loc = carry
                s_global = gather(prev_loc)
                i_syn = spike_accum_ref(s_global, w_block) + i_ext
                state, spikes = step(state, i_syn, params)
                return (state, spikes), spikes

            (_, _), raster = jax.lax.scan(
                body,
                (state, jnp.zeros((n_loc,), jnp.float32)),
                None,
                length=n_steps,
            )
            return raster  # [T, n_loc] per device → [T, M] stitched

        # per-device RNG: one key per device, sharded over the full mesh
        # (splitting over the last axis only would hand slow-axis replicas
        # identical noise streams)
        keys = jax.random.split(key, n_dev)
        st0 = init_state(m, params, key)
        sharding = NamedSharding(self.mesh, vec_spec)
        v0 = jax.device_put(st0.v, sharding)
        u0 = jax.device_put(st0.u, sharding)
        keys = jax.device_put(keys, NamedSharding(self.mesh, P(axes)))
        w = jax.device_put(self.w_syn, NamedSharding(self.mesh, col_spec))
        return jax.jit(_run)(v0, u0, keys, w)

    def compile(
        self, n_steps: int, *, key: jax.Array | None = None
    ) -> tuple[jax.stages.Compiled, tuple, float]:
        """Stage the sparse/ragged run's inputs and compile its step ahead
        of time.

        Returns ``(compiled, args, compile_s)``: ``compiled(*args)`` runs
        the ``n_steps`` simulation and returns the raster ``[T, M]``, as
        :meth:`run` does; ``compile_s`` is the wall time of lowering and
        compiling alone (input staging excluded).  While the tracer
        (:mod:`repro.obs`) is on, staging, lowering and compiling are the
        spans ``snn.stage``, ``snn.lower`` and ``snn.compile``, the
        counter ``snn.exchange_bytes`` records the slow-axis bytes a step
        moves (``{"level2": n}``: ``exchange_stats()['sparse']``, or the
        plan's ``block_bytes_per_step`` for ``'ragged'``), and for
        ``'ragged'`` the counter ``snn.level2_rounds`` the live rounds
        (:meth:`level2_rounds`).
        """
        if self.exchange not in ("sparse", "ragged"):
            raise ValueError("compile covers exchange='sparse'/'ragged'")
        key = jax.random.PRNGKey(0) if key is None else key
        fn, args = self._sparse_callable_and_args(n_steps, key=key)
        t = time.perf_counter()
        with obs.span("snn.lower", cat="exec", tid="snn"):
            lowered = fn.lower(*args)
        with obs.span("snn.compile", cat="exec", tid="snn"):
            compiled = lowered.compile()
        compile_s = time.perf_counter() - t
        if obs.is_enabled():
            # slow-axis bytes a step of the plan just compiled moves
            if self.exchange == "ragged":
                moved = self._ragged_plan().block_bytes_per_step
            else:
                moved = self.exchange_stats()["sparse"]
            obs.counter("snn.exchange_bytes", {"level2": moved}, tid="snn")
            if self.exchange == "ragged":
                obs.counter("snn.level2_rounds", self.level2_rounds(), tid="snn")
        return compiled, args, compile_s

    def _step_key(self, n_steps: int) -> "_StepKey":
        return _StepKey(
            mesh=self.mesh,
            params=self.params,
            policy=self.policy,
            i_ext=float(self.i_ext),
            n_steps=int(n_steps),
            signature=self.step_signature(),
        )

    def _sparse_callable_and_args(
        self, n_steps: int, *, key: jax.Array
    ) -> tuple:
        """The compiled sparse/ragged step plus its prepared inputs.

        The step is built (and cached) by :func:`_sparse_step` keyed on
        the engine's static signature; this method only prepares the jit
        *inputs* — neuron state, padded synapse tiles, and the per-round
        column masks.  Swapping to a plan with an equal
        :meth:`step_signature` therefore reuses the compiled step.
        Shared by :meth:`run` (executes), :meth:`compile` (compiles
        ahead of time) and :meth:`trace_step` (abstractly traces —
        planlint Layer 2).
        """
        syn = self._block_synapses()
        n_dev = self.n_devices
        misses_before = _sparse_step.cache_info().misses
        fn = _sparse_step(self._step_key(n_steps))
        if _sparse_step.cache_info().misses > misses_before:
            obs.metric_inc("snn.step_cache_misses")
        else:
            obs.metric_inc("snn.step_cache_hits")
        with obs.span("snn.stage", cat="exec", tid="snn"):
            src_pad, blk_pad = syn.padded()  # [n_dev, K], [n_dev, K, B, B]
            # per-device column masks, one [n_dev, R·B] array per live round
            masks = self._ragged_plan().col_masks() if self.exchange == "ragged" else ()
            # one key per device over the full mesh (see the dense path)
            keys = jax.random.split(key, n_dev)
            st0 = init_state(syn.n_neurons, self.params, key)
            vec_spec = P(self.axis_names)
            sharding = NamedSharding(self.mesh, vec_spec)
            v0 = jax.device_put(st0.v, sharding)
            u0 = jax.device_put(st0.u, sharding)
            keys = jax.device_put(keys, sharding)
            blk_sharding = NamedSharding(self.mesh, vec_spec)
            # straight from the host to each device's shard (no staging of
            # the whole tile array on the first device)
            src_arr = jax.device_put(src_pad.astype(np.int32), blk_sharding)
            blk_arr = jax.device_put(blk_pad, blk_sharding)
            masks_put = tuple(jax.device_put(a, blk_sharding) for a in masks)
            args = (v0, u0, keys, src_arr, blk_arr, masks_put)
            if obs.is_enabled():  # the span ends when the arrays are resident
                jax.block_until_ready(args)
        return fn, args

    def _run_sparse(self, n_steps: int, *, key: jax.Array) -> jax.Array:
        fn, args = self._sparse_callable_and_args(n_steps, key=key)
        return fn(*args)

    def trace_step(self, n_steps: int = 2, *, key: jax.Array | None = None):
        """Abstractly trace the compiled sparse/ragged step and return
        its ``ClosedJaxpr`` — the input of planlint's Layer-2 lints
        (:mod:`repro.analysis.traced`), which count the collective eqns
        against what :meth:`step_signature` says the schedule emits.
        Tracing never executes the step (no data movement)."""
        if self.exchange not in ("sparse", "ragged"):
            raise ValueError("trace_step covers exchange='sparse'/'ragged'")
        key = jax.random.PRNGKey(0) if key is None else key
        fn, args = self._sparse_callable_and_args(n_steps, key=key)
        return jax.make_jaxpr(fn)(*args)

    def accum_stats(self, raster: np.ndarray) -> dict[str, float]:
        """Strips the event-driven accumulation streams on one device per
        step of ``raster`` (``[T, M]``, as :meth:`run` returns it), out of
        the ``K·B/8`` 8-row strips of the device's stored tiles
        (:func:`repro.kernels.spike_accum.spike_strips`).  Host NumPy, off
        the timed path.

        Step ``t`` accumulates the spikes of step ``t − 1`` (none at step
        0) that reach the device: the whole group blocks the schedule
        moves, or for ``exchange='ragged'`` the device's own group and the
        columns the plan sends to it.  Returns ``{"mean", "max", "of"}``:
        the mean and the largest count over steps and devices, and
        ``K·B/8``.
        """
        syn = self._block_synapses()
        b, n_dev = syn.block_size, self.n_devices
        g, r = self._mesh_groups()
        deg = np.diff(syn.indptr)
        k = max(int(deg.max()) if deg.size else 0, 1)
        src = np.zeros((n_dev, k), np.int64)  # padded(): padding reads block 0
        for d in range(n_dev):
            src[d, : deg[d]] = syn.src_ids[syn.indptr[d] : syn.indptr[d + 1]]
        group = np.arange(n_dev) // r
        if self.exchange == "ragged":
            plan = self._ragged_plan()
            seen = np.zeros((g, g, r * b), bool)  # [receiving, sending group, column]
            seen[np.arange(g), np.arange(g)] = True
            for (gs, gd), cols in plan.pair_cols.items():
                seen[gd, gs, cols] = True
        else:
            moved = pool_block_mask(syn.mask(), group, g)  # [sending, receiving]
            seen = np.repeat(moved.T[:, :, None], r * b, axis=2)
        seen = seen.reshape(g, n_dev, b)[group]  # [device, source block, column]
        fired = np.asarray(raster[:-1]).reshape(-1, n_dev, b) != 0
        counts = np.zeros((fired.shape[0] + 1, n_dev), np.int64)
        for d in range(n_dev):
            spikes = fired[:, src[d]] & seen[d, src[d]]  # [T - 1, K, B]
            strips = spikes.reshape(spikes.shape[0], -1, 8).any(axis=2)
            counts[1:, d] = strips.sum(axis=1)
        return {"mean": float(counts.mean()), "max": int(counts.max()), "of": k * b // 8}


@dataclasses.dataclass(frozen=True)
class _StepKey:
    """Hashable static description of a compiled sparse/ragged step.

    Everything a retrace could depend on *except* array shapes (jit
    retraces on those by itself): the mesh, neuron/kernel constants, and
    the exchange signature (:meth:`DistributedSNN.step_signature`).
    """

    mesh: Mesh
    params: LIFParams | IzhikevichParams
    policy: KernelPolicy
    i_ext: float
    n_steps: int
    signature: tuple


@functools.lru_cache(maxsize=32)
def _sparse_step(key: _StepKey):
    """Build the jitted sparse/ragged step for a static signature.

    Level-1 (fast axes) gathers the group spike block as in
    ``'two_level'``.  Level-2 depends on the signature kind:

    * ``'sparse'`` — only the ``ppermute`` rounds the group-pooled
      block mask schedules run, every inner position shipping the
      full ``R·B`` group block;
    * ``'ragged'`` — each scheduled pair moves its sending group's
      ``f32[R·B]`` block times the round's 0/1 column mask
      bridge-to-bridge via a joint-axis ``ppermute``, then a fast-axis
      ``psum`` re-broadcasts it inside the receiving group, which
      writes it into its buffer row whole.

    Unneeded group blocks never cross the slow axis and unneeded columns
    cross as zeros — their receive slots stay zero, and the block-CSR
    storage holds no weight for them, so the raster is identical to the
    dense oracle.  All
    shapes and both schedules are static; the accumulation runs through
    :func:`repro.kernels.spike_currents_blocks` so ``policy`` flips
    einsum ↔ Pallas without touching the exchange.

    Each part of a step runs under its name in :data:`STEP_SCOPES`
    (``jax.named_scope``: metadata only, the compiled program is the
    same), so the ops of a device trace can be told apart by part.

    The ``lru_cache`` is what makes the double-buffered plan swap
    stall-free: engines whose plans share a signature get the *same*
    jitted callable, and the per-round column masks / synapse tiles are
    inputs, so flipping plans never rebuilds or recompiles the step.
    """
    mesh = key.mesh
    axes = tuple(mesh.axis_names)
    slow, inner = axes[0], axes[1:]
    g = mesh.shape[slow]
    r = int(np.prod([mesh.shape[a] for a in inner])) if inner else 1
    n_dev = g * r
    kind, schedule = key.signature
    ragged = kind == "ragged"
    params = key.params
    policy = key.policy
    step = lif_step if isinstance(params, LIFParams) else izhikevich_step
    i_ext = jnp.float32(key.i_ext)
    n_steps = key.n_steps
    vec_spec = P(axes)

    def gather_group(spikes_loc):
        if r > 1:
            with jax.named_scope(LEVEL1):
                return jax.lax.all_gather(spikes_loc, inner, axis=0, tiled=True)
        return spikes_loc  # [R·B] group spike block

    def gather_blocks(spikes_loc):
        """[B] local spikes → [n_dev, B] global blocks (zeros where
        the schedule skipped the transfer)."""
        s_grp = gather_group(spikes_loc)
        rb = s_grp.shape[0]
        with jax.named_scope(PACK):
            gid = jax.lax.axis_index(slow)
            buf = jnp.zeros((g, rb), jnp.float32)
            buf = buf.at[gid].set(s_grp)
        for shift, pairs in enumerate(schedule, start=1):
            if not pairs:
                continue
            with jax.named_scope(SEND):
                recv = jax.lax.ppermute(s_grp, slow, perm=pairs)
            # whatever arrived in the shift-`shift` round came from
            # group (gid - shift); untargeted receivers got zeros and
            # write zeros into an otherwise-untouched slot
            with jax.named_scope(UNPACK):
                buf = buf.at[(gid - shift) % g].set(recv)
        with jax.named_scope(UNPACK):
            return buf.reshape(n_dev, rb // r)

    def gather_blocks_ragged(spikes_loc, masks_loc):
        """Ragged level-2: per live round, the group block times the
        round's column mask moves by the bridge-only joint-axis ppermute
        and the fast-axis broadcast, and is written into the block
        buffer's row ``(gid - shift) % G``; the local group block is
        written into row ``gid``.  No gather, no scatter."""
        s_grp = gather_group(spikes_loc)
        rb = s_grp.shape[0]
        gid = jax.lax.axis_index(slow)
        with jax.named_scope(UNPACK):
            buf = jnp.zeros((g, rb), jnp.float32)
            buf = jax.lax.dynamic_update_slice(buf, s_grp[None], (gid, 0))
        for (shift, perm), mask in zip(schedule, masks_loc):
            with jax.named_scope(PACK):
                payload = s_grp * mask[0]  # [rb], unconsumed columns zeroed
            with jax.named_scope(SEND):
                recv = jax.lax.ppermute(payload, axes, perm=perm)
                if r > 1:
                    # only the receiving bridge got data; everyone else
                    # holds zeros, so a psum is the intra-group broadcast
                    recv = jax.lax.psum(recv, inner)
            with jax.named_scope(UNPACK):
                row = (gid - shift) % g
                buf = jax.lax.dynamic_update_slice(buf, recv[None], (row, 0))
        with jax.named_scope(UNPACK):
            return buf.reshape(n_dev, rb // r)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(vec_spec, vec_spec, P(axes), vec_spec, vec_spec, P(axes)),
        out_specs=P(None, axes),
        check_vma=False,
    )
    def _run(v0, u0, keys, src_ids, blocks, masks_loc):
        state = NeuronState(v=v0, u=u0, key=keys[0])
        src_ids_loc = src_ids[0]  # [K]
        blocks_loc = blocks[0]  # [K, B, B]
        n_loc = v0.shape[0]

        def body(carry, _):
            state, prev_loc = carry
            if ragged:
                s_blocks = gather_blocks_ragged(prev_loc, masks_loc)
            else:
                s_blocks = gather_blocks(prev_loc)
            with jax.named_scope(ACCUMULATE):
                i_syn = (
                    spike_currents_blocks(
                        s_blocks, src_ids_loc, blocks_loc, policy=policy
                    )
                    + i_ext
                )
            with jax.named_scope(NEURON):
                state, spikes = step(state, i_syn, params)
            return (state, spikes), spikes

        (_, _), raster = jax.lax.scan(
            body,
            (state, jnp.zeros((n_loc,), jnp.float32)),
            None,
            length=n_steps,
        )
        return raster

    return jax.jit(_run)


class PlanBuffer:
    """Double-buffered :class:`RaggedPlan` holder for a running engine.

    The replan pipeline (:mod:`repro.core.replan`) produces a fresh plan
    off the hot path; :meth:`stage` parks it (with optionally edited
    synapse tiles) next to the active engine, and :meth:`flip` swaps it
    in between steps.  When the staged plan's static signature equals
    the active one, the flipped engine reuses the compiled step via the
    :func:`_sparse_step` cache — the swap is a pointer flip, not a
    recompile stall; :meth:`stage` returns that reuse predicate so
    callers can schedule an off-path warm-up compile when it is False.
    """

    def __init__(self, engine: DistributedSNN):
        if engine.exchange != "ragged":
            raise ValueError("PlanBuffer double-buffers ragged plans")
        if engine.plan is None:
            engine = engine.with_plan(engine._ragged_plan())
        self._active = engine
        self._staged: DistributedSNN | None = None

    @property
    def engine(self) -> DistributedSNN:
        """The active engine — run steps on this."""
        return self._active

    @property
    def staged(self) -> DistributedSNN | None:
        return self._staged

    def stage(
        self, plan: RaggedPlan, *, syn: BlockSynapses | None = None
    ) -> bool:
        """Park ``plan`` (+ optional new tiles) in the back buffer.

        Returns True when flipping will reuse the active compiled step
        (equal static signatures — no recompile stall).
        """
        self._staged = self._active.with_plan(plan, syn=syn)
        return self._staged.step_signature() == self._active.step_signature()

    def flip(self) -> DistributedSNN:
        """Swap the staged engine in and return it (the new active)."""
        if self._staged is None:
            raise RuntimeError("nothing staged — call stage() first")
        self._active, self._staged = self._staged, None
        return self._active
