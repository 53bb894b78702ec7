"""Pallas kernel: RG-LRU (RecurrentGemma) diagonal linear recurrence.

    h_t = a_t ⊙ h_{t-1} + b_t

with per-channel gates ``a_t ∈ (0,1)`` computed upstream
(``a = exp(-c·softplus(Λ)·σ(r_t))``) and ``b_t = √(1-a_t²) ⊙ i_t ⊙ x_t``.

Unlike SSD there is no matmul dual — the recurrence is *diagonal*, so
the MXU can't help; the kernel's job is bandwidth: stream ``a``/``b``
through VMEM in ``[L, Bd]`` tiles and keep the sequential dependency in
a ``[1, Bd]`` VMEM carry instead of bouncing through HBM each step
(which is what a naive ``lax.scan`` over S does at these widths).

Grid: ``(batch, D/Bd, S/L)`` — time is the innermost sequential axis;
channels are embarrassingly parallel.  In-chunk, a ``fori_loop`` runs
the L steps on the VPU with everything VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["rglru_scan"]


def _kernel(a_ref, b_ref, h_out_ref, carry_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)  # [L, Bd]
    b = b_ref[0].astype(jnp.float32)  # [L, Bd]

    def body(t, h):
        h = a[t] * h + b[t]
        h_out_ref[0, pl.ds(t, 1), :] = h[None].astype(h_out_ref.dtype)
        return h

    h0 = carry_ref[0]
    h_final = jax.lax.fori_loop(0, chunk, body, h0)
    carry_ref[...] = h_final[None]


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    chunk: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Run the gated diagonal recurrence; returns the state trace.

    Args:
      a: ``[B, S, D]`` per-step decay gates in (0, 1).
      b: ``[B, S, D]`` gated inputs.
      chunk: time-tile length L.
      block_d: channel-tile width (lane-aligned multiple of 128 on TPU).

    Returns:
      h: ``[B, S, D]`` hidden-state trace.
    """
    bs, s, d = a.shape
    if b.shape != a.shape:
        raise ValueError(f"a {a.shape} != b {b.shape}")
    chunk = min(chunk, s)
    block_d = min(block_d, d)
    if s % chunk or d % block_d:
        raise ValueError("S, D must divide their tile sizes")
    grid = (bs, d // block_d, s // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, id_, ic: (b_, ic, id_)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, id_, ic: (b_, ic, id_)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b_, id_, ic: (b_, ic, id_)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
