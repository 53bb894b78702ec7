"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.spike_accum import spike_accum, spike_accum_blocks, spike_strips
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,d,causal,window",
    [
        (2, 4, 2, 256, 256, 64, True, None),
        (1, 8, 1, 128, 128, 32, True, None),  # MQA
        (2, 4, 4, 256, 256, 64, False, None),  # bidirectional MHA
        (1, 4, 2, 256, 256, 64, True, 96),  # sliding window
        (1, 2, 2, 384, 384, 16, True, 128),  # non-pow2 seq
    ],
)
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, causal, window, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, block_q=128, block_k=128, interpret=True
    )
    ref = R.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,ragged",
    [(2, 4, 2, 1024, 64, False), (3, 8, 2, 512, 32, True), (1, 2, 1, 2048, 128, True)],
)
def test_decode_attention_sweep(b, hq, hkv, s, d, ragged, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    sl = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32) if ragged else None
    out = decode_attention(q, k, v, seq_lens=sl, block_k=256, interpret=True)
    ref = R.decode_attention_ref(q, k, v, seq_lens=sl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "bs,s,h,g,p,n,chunk",
    [(2, 256, 4, 2, 32, 16, 64), (1, 128, 2, 1, 16, 8, 128), (1, 512, 8, 2, 64, 32, 128)],
)
def test_ssd_scan_sweep(bs, s, h, g, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(bs, s, h, p)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.85, 0.999, size=(bs, s, h)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(bs, s, g, n)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(bs, s, g, n)), jnp.float32)
    out = ssd_scan(x, a, b, c, chunk=chunk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.ssd_ref(x, a, b, c)), rtol=3e-3, atol=3e-3
    )


def test_ssd_jnp_chunked_matches_ref():
    from repro.kernels.ops import _ssd_chunked_jnp

    x = jnp.asarray(RNG.normal(size=(2, 256, 4, 32)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.85, 0.999, size=(2, 256, 4)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(2, 256, 2, 16)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(2, 256, 2, 16)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(_ssd_chunked_jnp(x, a, b, c, chunk=64)),
        np.asarray(R.ssd_ref(x, a, b, c)),
        rtol=3e-3,
        atol=3e-3,
    )


@pytest.mark.parametrize(
    "bs,s,d,chunk,bd", [(2, 256, 128, 64, 64), (1, 128, 256, 128, 128), (3, 512, 64, 256, 64)]
)
def test_rglru_scan_sweep(bs, s, d, chunk, bd):
    a = jnp.asarray(RNG.uniform(0.8, 0.999, size=(bs, s, d)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(bs, s, d)), jnp.float32)
    out = rglru_scan(a, b, chunk=chunk, block_d=bd, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.rglru_ref(a, b)), rtol=3e-3, atol=3e-3
    )


@given(
    m_blocks=st.integers(1, 6),
    n_blocks=st.integers(1, 4),
    rate=st.floats(0.0, 0.3),
    seed=st.integers(0, 100),
)
@settings(max_examples=15, deadline=None)
def test_spike_accum_property(m_blocks, n_blocks, rate, seed):
    """Sparsity-skipping never changes the result — any firing pattern,
    including all-zero (every block skipped) and dense."""
    rng = np.random.default_rng(seed)
    m, n = 128 * m_blocks, 128 * n_blocks
    s = (rng.random(m) < rate).astype(np.float32)
    w = rng.normal(size=(m, n)).astype(np.float32)
    out = spike_accum(jnp.asarray(s), jnp.asarray(w), block_i=128, block_j=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), s @ w, rtol=1e-4, atol=1e-4)


def test_spike_accum_weighted_spikes():
    rng = np.random.default_rng(3)
    s = rng.random(256).astype(np.float32) * (rng.random(256) < 0.1)
    w = rng.normal(size=(256, 128)).astype(np.float32)
    out = spike_accum(jnp.asarray(s), jnp.asarray(w), block_i=128, block_j=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), s @ w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "n_blocks,b,bj,k,firing",
    [
        (2, 1024, 1024, 4, "sparse"),  # K > n_blocks
        (3, 1536, 640, 3, "sparse"),
        (4, 1280, 384, 5, "sparse"),
        (5, 96, 64, 3, "sparse"),  # small B and Bj: whole-dimension tiles
        (3, 512, 256, 4, "silent"),  # no spike at all: count 0 gives zeros
        (2, 256, 384, 3, "all"),  # every neuron fires: every strip streamed
        (3, 64, 24576, 3, "last_row"),  # two column tiles of 12,288
        (4, 128, 256, 4, "padding_fires"),
    ],
)
def test_spike_accum_blocks_vs_float64(n_blocks, b, bj, k, firing):
    """The event-driven block-CSR kernel against float64
    ``Σ_k s[src_k] @ W_k``.  ``sparse``: silent source blocks, silent rows
    inside a firing block, repeated sources and an all-zero padding tile;
    ``silent``: no spike; ``all``: every neuron fires; ``last_row``: one
    spike, in the last row of the last tile; ``padding_fires``: only the
    source of a zero padding tile fires."""
    rng = np.random.default_rng(n_blocks * b + k)
    src = rng.integers(0, n_blocks, size=k)
    w = rng.normal(size=(k, b, bj)).astype(np.float32)
    s = np.zeros((n_blocks, b), np.float32)
    if firing == "sparse":
        s[:] = rng.random((n_blocks, b)) < 0.05
        s[1] = 0.0  # a silent source block
        s[0, : min(b, 256)] = 0.0  # silent rows inside a firing block
        src[0] = 1  # at least one tile reads the silent block
        w[-1] = 0.0  # zero padding tile (padded() layout)
    elif firing == "all":
        s[:] = 1.0
    elif firing == "last_row":
        src[-1] = n_blocks - 1
        src[:-1] = 0
        s[n_blocks - 1, b - 1] = 1.0
    elif firing == "padding_fires":
        src[:-1] = 1
        src[-1] = 0
        w[-1] = 0.0
        s[0] = rng.random(b) < 0.5
    out = spike_accum_blocks(
        jnp.asarray(s), jnp.asarray(src), jnp.asarray(w), interpret=True
    )
    ref = np.einsum("kb,kbj->j", s.astype(np.float64)[src], w.astype(np.float64))
    if firing in ("silent", "padding_fires"):
        np.testing.assert_array_equal(np.asarray(out), np.zeros(bj, np.float32))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-4)


def test_spike_accum_blocks_rejects_untileable_b():
    """A B that is not a whole number of 8-row strips, and a column width
    above the tile bound with no multiple-of-128 divisor, are refused,
    never silently padded."""
    with pytest.raises(ValueError, match="multiple of 8"):
        spike_accum_blocks(
            jnp.zeros((2, 100)), jnp.array([0, 1]), jnp.zeros((2, 100, 128)),
            interpret=True,
        )
    with pytest.raises(ValueError, match="multiple of 128"):
        spike_accum_blocks(
            jnp.zeros((1, 8)), jnp.array([0]), jnp.zeros((1, 8, 16400)),
            interpret=True,
        )


@pytest.mark.parametrize("rate", [0.0, 0.002, 0.05, 1.0])
def test_spike_strips_match_numpy(rate):
    """The kernel's event list: the ids of the 8-row strips of the stored
    tiles that hold a spike, ascending, their count and each strip's
    spike lanes, against a NumPy count on random rasters."""
    rng = np.random.default_rng(int(rate * 1000))
    n_blocks, b, k = 4, 256, 6
    for _ in range(3):
        s = (rng.random((n_blocks, b)) < rate).astype(np.float32)
        src = rng.integers(0, n_blocks, size=k)
        strips, count, masks = spike_strips(jnp.asarray(s), jnp.asarray(src))
        fired = s[src].reshape(k * b // 8, 8) != 0
        want = np.flatnonzero(fired.any(axis=1))
        assert int(count[0]) == want.size
        np.testing.assert_array_equal(np.asarray(strips)[: want.size], want)
        np.testing.assert_array_equal(np.asarray(masks), fired @ (1 << np.arange(8)))
