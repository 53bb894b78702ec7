"""Pallas kernels: spike→current accumulation — the paper's compute
hot-spot (synaptic integration, §II/§V).

Computes ``I[j] = Σ_i s[i] · W[i, j]`` where ``s`` is the global spike
vector (sparse: biological firing rates mean ~1% of entries are 1) and
``W`` the incoming-synapse block held by this device.

GPU simulators implement this with scatter-atomics over the spike list.
The TPU has no atomics; its equivalent is to fetch by the spike list.
:func:`spike_accum_blocks`, the block-CSR kernel the distributed engine
runs, is event-driven: a wrapper lists the 8-row strips of the stored
tiles that hold a spike (:func:`spike_strips`), and the kernel DMAs only
those strips from HBM and adds their spiking rows in f32.  Its weight
stream scales with the firing, not with the tiles.

:func:`spike_accum`, the dense single-tile kernel, is a block-masked
matmul: a grid of ``(n_j_blocks, n_i_blocks)`` VMEM blocks, ``i``
(reduction) innermost and sequential into a VMEM scratch accumulator,
and a VPU check that skips the MXU work of a silent spike block.  Its
pipeline still copies every ``W`` block from HBM, whatever the firing.
It multiplies at ``Precision.HIGHEST``, as the jnp oracles in
:mod:`repro.kernels.ref` do: an f32 ``dot_general`` in a TPU kernel
otherwise runs one bf16 pass (about 1e-3 relative error against
float64, where HIGHEST stays near 1e-7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["spike_accum", "spike_accum_blocks", "spike_strips"]


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


#: rows of a strip, the unit :func:`spike_accum_blocks` fetches: the f32
#: sublane tile, so one strip is one row of (8, 128) tiles in HBM
_STRIP = 8

#: upper bound on the output columns one grid step of
#: :func:`spike_accum_blocks` holds: a strip of 16,384 f32 columns is
#: 512 KiB of VMEM, its two DMA buffers and the accumulator 1.5 MiB
_COLS = 16384


def _col_tile(bj: int) -> int:
    """Largest multiple of 128 that divides ``bj`` and is at most
    :data:`_COLS`; ``bj`` itself when ``bj <= _COLS`` (a block spanning
    the whole dimension is legal at any size)."""
    if bj <= _COLS:
        return bj
    for t in range(_COLS, 127, -128):
        if bj % t == 0:
            return t
    raise ValueError(
        f"column width Bj={bj} has no divisor that is a multiple of 128 and "
        f"at most {_COLS}; choose a Bj that has one (no padding is applied)"
    )


def spike_strips(
    s_blocks: jax.Array, src_ids: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The event list of :func:`spike_accum_blocks`: which 8-row strips
    of the stored tiles hold a source neuron that spiked.

    Strip ``e`` is rows ``8·(e % (B/8))`` to ``+8`` of tile ``e // (B/8)``.
    Returns ``(strips, count, masks)``: ``i32[K·B/8]`` the active strip
    ids in ascending order (``K·B/8`` past ``count``), ``i32[1]`` their
    number, and ``i32[K·B/8]`` each strip's spike lanes as a bitmask
    (bit ``r`` set when row ``r`` of the strip fired; zero for a silent
    strip).  Spikes are 0/1 events: any nonzero entry counts as a spike.
    The list is compacted by a sort of the ids, a few µs at 2,048
    strips on a v5e, where ``jnp.nonzero``'s scatter took ~20 µs.
    """
    k = src_ids.shape[0]
    n = k * s_blocks.shape[1] // _STRIP
    fired = (s_blocks[src_ids] != 0).reshape(n, _STRIP)
    lanes = jnp.left_shift(1, jnp.arange(_STRIP, dtype=jnp.int32))
    masks = jnp.sum(jnp.where(fired, lanes, 0), axis=1, dtype=jnp.int32)
    strips = jnp.sort(jnp.where(masks != 0, jnp.arange(n, dtype=jnp.int32), n))
    count = jnp.count_nonzero(masks).astype(jnp.int32).reshape(1)
    return strips, count, masks


def _blocks_kernel(count_ref, strip_ref, mask_ref, w_hbm, out_ref, buf, acc, sem,
                   *, tj: int):
    j = pl.program_id(0)
    n = count_ref[0]

    def fetch(i):  # strip i of the list into buffer i % 2
        row = pl.multiple_of(strip_ref[i] * _STRIP, _STRIP)
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(row, _STRIP), pl.ds(pl.multiple_of(j * tj, tj), tj)],
            buf.at[jax.lax.rem(i, 2)],
            sem.at[jax.lax.rem(i, 2)],
        )

    acc[...] = jnp.zeros_like(acc)

    @pl.when(n > 0)
    def _first():
        fetch(0).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (_STRIP, 1), 0)

    def body(i, carry):
        @pl.when(i + 1 < n)
        def _next():  # into the buffer that step i - 1 consumed
            fetch(i + 1).start()

        fetch(i).wait()
        fired = jnp.right_shift(mask_ref[strip_ref[i]], lane) & 1  # [8, 1]
        acc[...] += buf[jax.lax.rem(i, 2)] * fired.astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    out_ref[...] = jnp.sum(acc[...], axis=0, keepdims=True)


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
    layout, zero padding tiles allowed), driven by spike events: the
    weights of the neurons that fired are fetched, the rest never leave
    HBM.  :func:`spike_strips` lists the 8-row strips of the tiles that
    hold a spike; the list, its count and the strips' spike lanes are
    scalar-prefetched, the tiles stay in HBM, and a loop of ``count``
    iterations double-buffers one ``[8, tj]`` strip DMA while adding the
    previous strip's spiking rows into an ``[8, tj]`` f32 accumulator,
    whose sublanes are summed once at the end.  Grid: ``(Bj/tj,)`` over
    output column tiles (:func:`_col_tile`), so VMEM stays bounded at any
    ``Bj``.  Exact for any firing, with no cap: when every neuron fires,
    every strip is streamed.  Every weight of a spiking row is added in
    f32 (no matrix unit, no bf16 pass).

    Args:
      s_blocks: ``f32[n_blocks, B]`` global spike vector, one row per
        source block (zeros where the exchange skipped a block).  Spikes
        are 0/1 events: a nonzero entry adds its row's weights once.
      src_ids: ``i32[K]`` source block per stored tile.
      blocks: ``f32[K, B, Bj]`` the tiles (``Bj`` local output columns);
        ``B`` a multiple of 8.

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
    if b % _STRIP:
        raise ValueError(
            f"block size B={b} is not a multiple of {_STRIP} rows "
            "(no padding is applied)"
        )
    if k == 0:  # no tiles → no currents
        return jnp.zeros((bj,), jnp.float32)
    tj = _col_tile(bj)
    strips, count, masks = spike_strips(s_blocks, src_ids)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bj // tj,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tj), lambda j, *_: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((2, _STRIP, tj), jnp.float32),
            pltpu.VMEM((_STRIP, tj), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_blocks_kernel, tj=tj),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, bj), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="spike_accum_blocks",
    )(count, strips, masks, blocks.reshape(k * b, bj))
    return out[0]
