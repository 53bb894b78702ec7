"""Pallas kernel: single-token decode attention against a KV cache
(the decode_32k / long_500k hot-spot).

Flash-decode structure: the KV cache is streamed through VMEM in blocks
along a sequential grid axis with online-softmax carry; the parallel
work comes from ``batch × q_heads`` grid cells (128 batch × 32 heads =
4096 cells on the decode_32k shape — ample without GPU-style split-K
reductions across cores, see DESIGN.md §7).  Supports GQA and per-batch
valid lengths (ragged cache) via in-kernel iota masking.

The q vector is laid out ``[B, Hq, 1, D]`` — the singleton sublane is
padded on real hardware; the MXU work is the ``[Bk, D] × [D, 1]``
mat-vec per block, which at decode is memory-bound anyway (roofline:
bytes ≫ flops), so the kernel's job is purely to keep the cache
streaming at HBM bandwidth and skip invalid tail blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["decode_attention"]

_NEG_INF = -1.0e30


def _kernel(
    len_ref,  # SMEM i32[1] valid length for this batch row (scalar prefetch)
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    block_k: int,
    n_k_blocks: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid_len = len_ref[0]
    k_start = ik * block_k

    @pl.when(k_start < valid_len)  # skip fully-invalid tail blocks
    def _accumulate():
        q = q_ref[0, 0]  # [1, D]
        k = k_ref[0, 0]  # [Bk, D]
        v = v_ref[0, 0]  # [Bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [1, Bk]
        s *= sm_scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(kpos < valid_len, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k_blocks - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_lens: jax.Array | None = None,
    sm_scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One-token attention vs a KV cache.

    Args:
      q: ``[B, Hq, D]`` current-step queries.
      k, v: ``[B, Hkv, S, D]`` cache (``Hq % Hkv == 0``).
      seq_lens: optional ``i32[B]`` valid cache lengths (default: all S).

    Returns:
      ``[B, Hq, D]``.
    """
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    if hq % hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    block_k = min(block_k, s)
    if s % block_k:
        raise ValueError("cache length must divide block_k")
    group = hq // hkv
    n_k = s // block_k
    if seq_lens is None:
        seq_lens = jnp.full((b,), s, dtype=jnp.int32)
    q4 = q[:, :, None, :]  # [B, Hq, 1, D]
    grid = (b, hq, n_k)
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, block_k=block_k, n_k_blocks=n_k
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1,), lambda b, h, ik: (b,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, 1, d), lambda b, h, ik: (b, h, 0, 0)),
                pl.BlockSpec(
                    (1, 1, block_k, d), lambda b, h, ik, g=group: (b, h // g, ik, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, d), lambda b, h, ik, g=group: (b, h // g, ik, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, d), lambda b, h, ik: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), q4, k, v)
    return out[:, :, 0, :]
