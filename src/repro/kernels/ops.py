"""Public jit'd entry points for the kernel layer.

Each op dispatches between the Pallas kernel (TPU target; validated on
CPU via ``interpret=True``) and the pure-jnp oracle in
:mod:`repro.kernels.ref`.  The model zoo — and, since the ragged
exchange landed, the distributed SNN engine's block-CSR accumulation
(:func:`spike_currents_blocks` inside
:meth:`repro.snn.distributed.DistributedSNN`) — calls these through
``KernelPolicy`` so a single config flag flips a hot-spot between
XLA-native ops (used by the dry-run, whose ``cost_analysis`` must see
real HLO FLOPs) and the Pallas path (used by the kernel benchmarks and
on real hardware).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.spike_accum import spike_accum as _spike
from repro.kernels.spike_accum import spike_accum_blocks as _spike_blocks

__all__ = [
    "KernelPolicy",
    "attention",
    "decode_attention",
    "ssd",
    "rglru",
    "spike_currents",
    "spike_currents_blocks",
]


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """How the model zoo executes its hot-spots.

    use_pallas: run Pallas kernels instead of the jnp reference path.
      The dry-run keeps this False so XLA's cost model sees the true
      FLOPs.
    interpret: Pallas interpret mode.  Off by default so the kernels
      compile for the chip; CPU callers (the tests) pass True.
    """

    use_pallas: bool = False
    interpret: bool = False


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    policy: KernelPolicy = KernelPolicy(),
) -> jax.Array:
    if policy.use_pallas:
        return _flash(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale,
            interpret=policy.interpret,
        )
    return _ref.attention_ref(q, k, v, causal=causal, window=window, sm_scale=sm_scale)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_lens: jax.Array | None = None,
    sm_scale: float | None = None,
    policy: KernelPolicy = KernelPolicy(),
) -> jax.Array:
    if policy.use_pallas:
        return _decode(
            q, k, v, seq_lens=seq_lens, sm_scale=sm_scale, interpret=policy.interpret
        )
    return _ref.decode_attention_ref(q, k, v, seq_lens=seq_lens, sm_scale=sm_scale)


def ssd(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    policy: KernelPolicy = KernelPolicy(),
) -> jax.Array:
    if policy.use_pallas:
        return _ssd(x, a, b, c, chunk=chunk, interpret=policy.interpret)
    return _ssd_chunked_jnp(x, a, b, c, chunk=chunk)


def rglru(
    a: jax.Array,
    b: jax.Array,
    *,
    chunk: int = 256,
    policy: KernelPolicy = KernelPolicy(),
) -> jax.Array:
    if policy.use_pallas:
        return _rglru(a, b, chunk=chunk, interpret=policy.interpret)
    return _ref.rglru_ref(a, b)


def spike_currents(
    spikes: jax.Array, w: jax.Array, *, policy: KernelPolicy = KernelPolicy()
) -> jax.Array:
    if policy.use_pallas:
        return _spike(spikes, w, interpret=policy.interpret)
    return _ref.spike_accum_ref(spikes, w)


def spike_currents_blocks(
    s_blocks: jax.Array,
    src_ids: jax.Array,
    blocks: jax.Array,
    *,
    policy: KernelPolicy = KernelPolicy(),
) -> jax.Array:
    """Block-CSR synaptic accumulation (the ``exchange='sparse'`` /
    ``'ragged'`` layout; the distributed engine's per-step hot-spot).

    Spikes are 0/1 events.  The Pallas kernel is event-driven: it reads a
    nonzero entry as one spike and fetches only the weight strips of the
    neurons that fired, so a weighted spike vector would be read as 1s.
    """
    if policy.use_pallas:
        return _spike_blocks(s_blocks, src_ids, blocks, interpret=policy.interpret)
    return _ref.spike_accum_blocks_ref(s_blocks, src_ids, blocks)


def _ssd_chunked_jnp(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *, chunk: int
) -> jax.Array:
    """XLA-native chunked SSD — same math as the Pallas kernel, written
    as batched einsums + ``lax`` loops so the dry-run HLO carries the
    true matmul FLOPs.  The per-head decay matrix ``seg`` ([B,nc,L,L])
    is materialized ONE HEAD AT A TIME via ``lax.map`` — materializing
    it across all heads ([B,nc,L,L,H]) costs gigabytes at production
    shapes (the Pallas kernel grids over heads for the same reason)."""
    bs, s, h, p = x.shape
    _, _, g, n = b.shape
    rep = h // g
    chunk = min(chunk, s)
    nc = s // chunk
    xc = x.reshape(bs, nc, chunk, h, p).astype(jnp.float32)
    ac = a.reshape(bs, nc, chunk, h).astype(jnp.float32)
    bc = b.reshape(bs, nc, chunk, g, n).astype(jnp.float32)
    cc = c.reshape(bs, nc, chunk, g, n).astype(jnp.float32)
    tpos = jnp.arange(chunk)[:, None]
    causal = tpos >= jnp.arange(chunk)[None, :]  # [L, L]

    ys = []
    for gi in range(g):  # B/C groups (1–8): python loop keeps HLO simple
        b_g = bc[:, :, :, gi]  # [B,nc,L,N]
        c_g = cc[:, :, :, gi]
        cb_g = jnp.einsum("bktn,bksn->bkts", c_g, b_g)  # [B,nc,L,L]

        def per_head(inp, b_g=b_g, c_g=c_g, cb_g=cb_g):
            x_h, a_h = inp  # [B,nc,L,P], [B,nc,L]
            cum = jnp.cumsum(jnp.log(a_h), axis=2)  # [B,nc,L]
            seg = jnp.where(
                causal[None, None], jnp.exp(cum[..., :, None] - cum[..., None, :]), 0.0
            )
            y_intra = jnp.einsum("bkts,bksp->bktp", cb_g * seg, x_h)
            decay_end = jnp.exp(cum[:, :, -1:] - cum)  # [B,nc,L]
            states = jnp.einsum("bktn,bkt,bktp->bknp", b_g, decay_end, x_h)
            chunk_decay = jnp.exp(cum[:, :, -1])  # [B,nc]

            def carry_step(hprev, inp2):
                st, dec = inp2  # [B,N,P], [B]
                return dec[:, None, None] * hprev + st, hprev

            h0 = jnp.zeros((bs, n, p), jnp.float32)
            _, h_prevs = jax.lax.scan(
                carry_step,
                h0,
                (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
            )
            h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # [B,nc,N,P]
            y_inter = jnp.einsum(
                "bktn,bknp,bkt->bktp", c_g, h_prevs, jnp.exp(cum)
            )
            return y_intra + y_inter

        heads = slice(gi * rep, (gi + 1) * rep)
        x_g = jnp.moveaxis(xc[:, :, :, heads], 3, 0)  # [rep,B,nc,L,P]
        a_g = jnp.moveaxis(ac[:, :, :, heads], 3, 0)  # [rep,B,nc,L]
        y_g = jax.lax.map(per_head, (x_g, a_g))  # [rep,B,nc,L,P]
        ys.append(jnp.moveaxis(y_g, 0, 3))  # [B,nc,L,rep,P]
    y = jnp.concatenate(ys, axis=3) if len(ys) > 1 else ys[0]
    return y.reshape(bs, s, h, p).astype(x.dtype)
