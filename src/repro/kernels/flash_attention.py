"""Pallas kernel: FlashAttention for TPU (train/prefill hot-spot).

Online-softmax block attention over VMEM tiles (Bq × Bk), MXU-aligned.
Supports GQA (query-head groups share one KV head), causal masking, and
sliding-window (SWA) masking — covering every attention variant in the
assigned architecture pool (full GQA, Mixtral SWA, RecurrentGemma local
attention, MusicGen/LLaVA backbones).

Grid: ``(batch, q_heads, Sq/Bq, Sk/Bk)`` — the KV dimension is the
innermost (sequential, "arbitrary") axis; running max ``m``, normalizer
``l`` and the output accumulator live in VMEM scratch and carry across
KV steps.  Fully-masked KV blocks (beyond the causal frontier or outside
the sliding window) are *skipped* — no HBM→VMEM fetch, no MXU work —
which makes causal attention ~2× and SWA ~Sk/W× cheaper, matching the
FLOP accounting the roofline uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["flash_attention"]

_NEG_INF = -1.0e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Static-shape relevance test from grid indices only: causal skip
    # (block entirely above the diagonal) and window skip (block entirely
    # left of every query's window).
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        # largest query position in block attends to j >= q_pos - window + 1
        relevant &= (k_start + block_k - 1) >= (q_start - window + 1)

    @pl.when(relevant)
    def _accumulate():
        q = q_ref[0, 0]  # [Bq, D]
        k = k_ref[0, 0]  # [Bk, D]
        v = v_ref[0, 0]  # [Bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= sm_scale
        if causal or window is not None:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = jnp.bool_(True)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # lane-replicated running max
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
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
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Block FlashAttention with GQA / causal / sliding-window support.

    Args:
      q: ``[B, Hq, Sq, D]``.
      k, v: ``[B, Hkv, Sk, D]`` with ``Hq % Hkv == 0``.
      window: sliding-window size (position ``i`` attends to
        ``(i-window, i]``); ``None`` = unbounded.

    Returns:
      ``[B, Hq, Sq, D]`` attention output in ``q.dtype``.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, dk = k.shape
    if d != dk or v.shape != k.shape or hq % hkv:
        raise ValueError(f"bad shapes q={q.shape} k={k.shape} v={v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("sequence lengths must divide block sizes")
    group = hq // hkv
    n_q, n_k = sq // block_q, sk // block_k
    grid = (b, hq, n_q, n_k)
    kernel = functools.partial(
        _kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_k_blocks=n_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
