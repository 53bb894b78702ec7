"""Pallas kernel: Mamba-2 SSD (state-space duality) chunked scan.

The SSD recurrence per head (state ``h ∈ R^{N×P}``, scalar decay a_t):

    h_t = a_t · h_{t-1} + b_t ⊗ x_t         y_t = cᵗ_t · h_t

A naive scan is sequential in S and VPU-bound.  The SSD decomposition
(Dao & Gu, 2024) splits the sequence into chunks of length ``L``: within
a chunk everything becomes three dense matmuls (MXU work), and only a
tiny ``[N, P]`` state crosses chunk boundaries:

    cum_t       = Σ_{u ≤ t} log a_u                       (in-chunk cumsum)
    y_intra     = ((C Bᵗ) ⊙ exp(cum_t − cum_s)·[t ≥ s]) X   ([L,L]·[L,P])
    y_inter_t   = exp(cum_t) · (C_t · h_prev)               ([L,N]·[N,P])
    h_next      = exp(cum_L) · h_prev + (B ⊙ decay_to_end)ᵗ X

Grid: ``(batch, heads, S/L)`` with the chunk axis sequential; the
carried state lives in VMEM scratch.  B/C head-groups (Mamba-2's GVA
analogue) are resolved in the index maps.  All matmul operands are
``[L, ·]`` with L = 128 — MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["ssd_scan"]


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # [L, P]
    a = a_ref[0, :, 0].astype(jnp.float32)  # [L]
    b = b_ref[0, :, 0, :].astype(jnp.float32)  # [L, N]
    c = c_ref[0, :, 0, :].astype(jnp.float32)  # [L, N]

    log_a = jnp.log(a)[:, None]  # [L, 1]
    cum = jnp.cumsum(log_a, axis=0)  # [L, 1] inclusive
    # causal decay matrix: seg[t, s] = exp(cum_t - cum_s) for t >= s
    diff = cum - cum[:, 0][None, :]  # [L, L] = cum_t - cum_s
    tpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(tpos >= spos, jnp.exp(diff), 0.0)

    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L] = C_t · B_s
    y_intra = jax.lax.dot_general(
        cb * seg, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, P]

    h_prev = h_ref[...]  # [N, P]
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        c, h_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, P]

    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    decay_to_end = jnp.exp(cum[-1, 0] - cum)  # [L, 1]
    h_new = jnp.exp(cum[-1, 0]) * h_prev + jax.lax.dot_general(
        b * decay_to_end,
        x,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [N, P]
    h_ref[...] = h_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Chunked SSD scan.

    Args:
      x: ``[B, S, H, P]`` inputs (Δ-scaled upstream).
      a: ``[B, S, H]`` per-step decay in (0, 1].
      b, c: ``[B, S, G, N]`` input/output projections, ``H % G == 0``.
      chunk: in-chunk length ``L`` (MXU-aligned; must divide S).

    Returns:
      y: ``[B, S, H, P]``.
    """
    bs, s, h, p = x.shape
    _, _, g, n = b.shape
    if a.shape != (bs, s, h) or c.shape != b.shape or h % g:
        raise ValueError(f"bad shapes x={x.shape} a={a.shape} b={b.shape}")
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError("S must divide chunk")
    rep = h // g
    n_chunks = s // chunk
    grid = (bs, h, n_chunks)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, ic: (b_, ic, h_, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b_, h_, ic: (b_, ic, h_)),
            pl.BlockSpec(
                (1, chunk, 1, n), lambda b_, h_, ic, r=rep: (b_, ic, h_ // r, 0)
            ),
            pl.BlockSpec(
                (1, chunk, 1, n), lambda b_, h_, ic, r=rep: (b_, ic, h_ // r, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, ic: (b_, ic, h_, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, a, b, c)
