"""Pallas kernel: spike→current accumulation — the paper's compute
hot-spot (synaptic integration, §II/§V).

Computes ``I[j] = Σ_i s[i] · W[i, j]`` where ``s`` is the global spike
vector (sparse: biological firing rates mean ~1% of entries are 1) and
``W`` the incoming-synapse block held by this device.

GPU simulators implement this with scatter-atomics over the spike list.
That mechanism has no TPU analogue (no atomics; registers are vector
lanes) — the TPU-native adaptation is a **block-masked dense matmul**:
tile ``W`` into MXU-aligned VMEM blocks, check each spike block with a
cheap VPU reduction, and skip the MXU work for blocks with no spikes.
The pipeline still copies every ``W`` tile from HBM, whatever the
firing, so the weight stream bounds both kernels.

Grid of :func:`spike_accum`: ``(n_j_blocks, n_i_blocks)`` — the ``i``
(reduction) dimension is innermost/sequential so a VMEM scratch
accumulator carries partial sums; the output block is written once on
the last ``i`` step.  Both kernels multiply at ``Precision.HIGHEST``, as
the jnp oracles in :mod:`repro.kernels.ref` do: an f32 ``dot_general``
in a TPU kernel otherwise runs one bf16 pass (about 1e-3 relative error
against float64, where HIGHEST stays near 1e-7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["spike_accum", "spike_accum_blocks"]


def _kernel(s_ref, w_ref, out_ref, acc_ref, *, n_i_blocks: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[...]  # [1, Bi]
    # VPU block-sparsity check: skip the matmul when no presynaptic
    # neuron in this block fired.
    @pl.when(jnp.any(s > 0.0))
    def _accumulate():
        w = w_ref[...]  # [Bi, Bj]
        acc_ref[...] += jax.lax.dot_general(
            s,
            w,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_i_blocks - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def spike_accum(
    spikes: jax.Array,
    w: jax.Array,
    *,
    block_i: int = 256,
    block_j: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """``I = spikes @ W`` with block-level spike-sparsity skipping.

    Args:
      spikes: ``f32[M]`` spike vector (0/1, but any f32 works).
      w: ``f32[M, N]`` synapse block (pre → post).
      block_i/block_j: VMEM tile sizes (MXU-aligned multiples of 128 on
        real hardware; any divisor in interpret mode).

    Returns:
      ``f32[N]`` synaptic currents.
    """
    m, n = w.shape
    if spikes.shape != (m,):
        raise ValueError(f"spikes {spikes.shape} incompatible with W {w.shape}")
    block_i = min(block_i, m)
    block_j = min(block_j, n)
    if m % block_i or n % block_j:
        raise ValueError("block sizes must divide matrix dims")
    n_i, n_j = m // block_i, n // block_j
    s2 = spikes.reshape(1, m)
    grid = (n_j, n_i)  # i innermost → sequential accumulation
    out = pl.pallas_call(
        functools.partial(_kernel, n_i_blocks=n_i),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_i), lambda j, i: (0, i)),
            pl.BlockSpec((block_i, block_j), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_j), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_j), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spike_accum",
    )(s2, w)
    return out[0]


#: upper bound on a sub-tile side of :func:`spike_accum_blocks` (a 512²
#: f32 sub-tile is 1 MiB of VMEM, 2 MiB double-buffered)
_TILE = 512


def _tile(b: int) -> int:
    """Largest multiple of 128 that divides ``b`` and is at most
    :data:`_TILE`; ``b`` itself when ``b <= _TILE`` (a block spanning the
    whole dimension is legal at any size)."""
    if b <= _TILE:
        return b
    for t in range(_TILE, 127, -128):
        if b % t == 0:
            return t
    raise ValueError(
        f"block size B={b} has no divisor that is a multiple of 128 and at "
        f"most {_TILE}; choose a B that has one (no padding is applied)"
    )


def _blocks_kernel(src_ref, s_ref, w_ref, out_ref, acc_ref, *, n_k: int, n_i: int):
    k, i = pl.program_id(1), pl.program_id(2)

    @pl.when((k == 0) & (i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[0]  # [1, ti] — rows i of spike block src_ids[k] (scalar prefetch)
    # skip the MXU work for silent source rows and zero padding tiles
    @pl.when(jnp.any(s > 0.0))
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            s,
            w_ref[0],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when((k == n_k - 1) & (i == n_i - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spike_accum_blocks(
    s_blocks: jax.Array,
    src_ids: jax.Array,
    blocks: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Block-CSR synaptic accumulation — the ``'sparse'``/``'ragged'``
    engine's hot-spot, wired into ``DistributedSNN`` behind
    ``KernelPolicy`` (``policy=KernelPolicy(use_pallas=True)`` flips the
    engine's einsum to this kernel).

    Computes ``I = Σ_k s_blocks[src_ids[k]] @ blocks[k]`` for one device's
    stored incoming tiles (:meth:`repro.snn.sparse.BlockSynapses.padded`
    layout, zero padding tiles allowed).  Each ``B × Bj`` tile is streamed
    through VMEM as ``ti × tj`` sub-tiles (:func:`_tile`: the largest
    multiple of 128 up to 512 that divides the side, or the whole side
    when it is at most 512), so neither ``B`` nor the tile count is
    bounded by VMEM.  Grid: ``(Bj/tj, K, B/ti)`` — output column
    tile ``j`` outermost (parallel), then stored tile ``k`` and row tile
    ``i`` (sequential, accumulating into a VMEM scratch that is zeroed at
    the first ``(k, i)`` of each ``j`` and flushed at the last).
    ``src_ids`` is scalar-prefetched, so each grid step DMAs exactly the
    ``ti`` spike lanes its sub-tile consumes; a VPU check skips the MXU
    work for silent rows (the weight sub-tile is still fetched).  The
    matmul runs at ``Precision.HIGHEST`` (f32), the same as the einsum
    oracle.

    Args:
      s_blocks: ``f32[n_blocks, B]`` global spike vector, one row per
        source block (zeros where the exchange skipped a block).
      src_ids: ``i32[K]`` source block per stored tile.
      blocks: ``f32[K, B, Bj]`` the tiles (``Bj`` local output columns).

    Returns:
      ``f32[Bj]`` synaptic currents.
    """
    n_blocks, b = s_blocks.shape
    k, bi, bj = blocks.shape
    if bi != b or src_ids.shape != (k,):
        raise ValueError(
            f"blocks {blocks.shape} / src_ids {src_ids.shape} incompatible "
            f"with s_blocks {s_blocks.shape}"
        )
    if k == 0:  # no tiles → no currents (a zero-size grid cannot run)
        return jnp.zeros((bj,), jnp.float32)
    ti, tj = _tile(b), _tile(bj)
    n_i, n_j = b // ti, bj // tj
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_j, k, n_i),
        in_specs=[
            pl.BlockSpec((1, 1, ti), lambda j, kk, i, src: (src[kk], 0, i)),
            pl.BlockSpec((1, ti, tj), lambda j, kk, i, src: (kk, i, j)),
        ],
        out_specs=pl.BlockSpec((1, tj), lambda j, kk, i, src: (0, j)),
        scratch_shapes=[pltpu.VMEM((1, tj), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_blocks_kernel, n_k=k, n_i=n_i),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, bj), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="spike_accum_blocks",
    )(src_ids.astype(jnp.int32), s_blocks.reshape(n_blocks, 1, b), blocks)
    return out[0]
