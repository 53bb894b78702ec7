"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These are deliberately naive — O(S²) attention with explicit masks,
step-by-step recurrences — so correctness is obvious; the kernel tests
sweep shapes/dtypes and ``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "attention_ref",
    "decode_attention_ref",
    "ssd_ref",
    "rglru_ref",
    "spike_accum_ref",
    "spike_accum_blocks_ref",
]


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Dense masked attention. q: [B,Hq,Sq,D]; k/v: [B,Hkv,Sk,D]."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32))
    s *= sm_scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax of all -1e30 is uniform; zero them like the kernel
    any_valid = mask.any(axis=-1)
    p = jnp.where(any_valid[None, None, :, None], p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_lens: jax.Array | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Single-token attention vs a KV cache.

    q: [B,Hq,D]; k/v: [B,Hkv,S,D]; seq_lens: optional i32[B] valid lengths.
    """
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = (
        jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kk.astype(jnp.float32))
        * sm_scale
    )
    if seq_lens is not None:
        valid = jnp.arange(s)[None, None, :] < seq_lens[:, None, None]
        logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, vv.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array
) -> jax.Array:
    """Mamba-2 SSD by direct recurrence.

    x: [B,S,H,P]; a: [B,S,H] decay in (0,1]; b,c: [B,S,G,N] with H % G == 0.
    h_t = a_t·h_{t-1} + b_t ⊗ x_t;  y_t = cᵗ_t·h_t.
    """
    bs, s, h, p = x.shape
    _, _, g, n = b.shape
    rep = h // g
    bb = jnp.repeat(b, rep, axis=2)  # [B,S,H,N]
    cc = jnp.repeat(c, rep, axis=2)

    def step(hstate, inp):
        xt, at, bt, ct = inp  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        hstate = at[..., None, None] * hstate + bt[..., :, None] * xt[..., None, :]
        yt = jnp.einsum("bhn,bhnp->bhp", ct, hstate)
        return hstate, yt

    h0 = jnp.zeros((bs, h, n, p), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(a, 1, 0).astype(jnp.float32),
        jnp.moveaxis(bb, 1, 0).astype(jnp.float32),
        jnp.moveaxis(cc, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def rglru_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Diagonal linear recurrence h_t = a_t ⊙ h_{t-1} + b_t.

    a, b: [B, S, D]; returns h trace [B, S, D].
    """

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, hs = jax.lax.scan(
        step,
        h0,
        (
            jnp.moveaxis(a, 1, 0).astype(jnp.float32),
            jnp.moveaxis(b, 1, 0).astype(jnp.float32),
        ),
    )
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)


def spike_accum_ref(spikes: jax.Array, w: jax.Array) -> jax.Array:
    """I = s @ W (f32, ``Precision.HIGHEST``)."""
    return jnp.matmul(
        spikes.astype(jnp.float32),
        w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def spike_accum_blocks_ref(
    s_blocks: jax.Array, src_ids: jax.Array, blocks: jax.Array
) -> jax.Array:
    """Block-CSR accumulation: ``I = Σ_k s_blocks[src_ids[k]] @ blocks[k]``
    (f32, ``Precision.HIGHEST``, as in the Pallas kernel, whose default on
    a TPU is a single bf16 pass: both compute the same on every backend)."""
    sel = s_blocks.astype(jnp.float32)[src_ids]  # [K, B]
    return jnp.einsum(
        "kb,kbj->j", sel, blocks.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
