"""Compile rehearsals for a described TPU v5e: the simulator's hot path at
real widths goes through the chip's compiler without a chip attached.

Interpret-mode tests cannot see the TPU's tiling rules or its VMEM limit;
these compiles can.  Each asserts that the Pallas kernel is in the
compiled HLO (``tpu_custom_call``).  The topology is described inside a
module-scoped fixture, so only the worker that runs this file loads the
TPU compiler, and the tests skip where it cannot be described.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import KernelPolicy
from repro.kernels.spike_accum import spike_accum_blocks
from repro.snn import LIFParams
from repro.snn.distributed import _sparse_step, _StepKey


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe the chip means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "n_blocks,b,k",
    [
        (1, 16384, 1),  # one chip holding M = 16,384 as one tile
        (4, 8192, 3),  # one chip's share of a 2×2 mesh at M = 32,768
        (4, 4096, 4),  # one chip's share of the 2×2 cell at M = 16,384
    ],
)
def test_spike_accum_blocks_compiles(topo, n_blocks, b, k):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = spike_accum_blocks.lower(
        _sds((n_blocks, b), jnp.float32, one),
        _sds((k,), jnp.int32, one),
        _sds((k, b, b), jnp.float32, one),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sparse_step_compiles_with_kernel(topo):
    """The whole sparse step (exchange + Pallas accumulation + LIF scan)
    on a one-device described mesh at M = 16,384."""
    m, n_steps = 16384, 2
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    fn = _sparse_step(
        _StepKey(
            mesh=mesh,
            params=LIFParams(),
            policy=KernelPolicy(use_pallas=True),
            i_ext=4.0,
            ragged_scatter="fused",
            n_steps=n_steps,
            signature=("sparse", ()),
        )
    )
    sh = NamedSharding(mesh, P(("data",)))
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), 1))
    compiled = fn.lower(
        _sds((m,), jnp.float32, sh),
        _sds((m,), jnp.float32, sh),
        _sds(key.shape, key.dtype, sh),
        _sds((1, 1), jnp.int32, sh),
        _sds((1, 1, m, m), jnp.float32, sh),
        (),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_step_for_v5e_carries_the_scopes(topo):
    """The 2×2 ragged step as the chip compiles it: every named scope
    reaches the ``op_name`` metadata, the Pallas kernel keeps its name
    under ``accumulate``, and the collective-permute and all-reduce sit
    under ``exchange/level2/send``."""
    import re

    from repro.snn.distributed import STEP_SCOPES

    b, k, width = 128, 4, 181
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("pod", "data"))
    fn = _sparse_step(
        _StepKey(
            mesh=mesh,
            params=LIFParams(noise_sigma=1.0),
            policy=KernelPolicy(use_pallas=True),
            i_ext=1.0,
            ragged_scatter="fused",
            n_steps=4,
            signature=("ragged", ((1, width, ((1, 2), (2, 1))),)),
        )
    )
    sh = NamedSharding(mesh, P(("pod", "data")))
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), 4))
    text = fn.lower(
        _sds((4 * b,), jnp.float32, sh),
        _sds((4 * b,), jnp.float32, sh),
        _sds(key.shape, key.dtype, sh),
        _sds((4, k), jnp.int32, sh),
        _sds((4, k, b, b), jnp.float32, sh),
        (_sds((4, 2, width), jnp.int32, sh),),
    ).compile().as_text()
    ops = re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(.*?op_name="([^"]*)"',
                     text, re.M)
    paths = [p + "/" for _, _, p in ops]
    for scope in STEP_SCOPES:
        assert any(f"/{scope}/" in p for p in paths), scope
    kernel = [p for name, kind, p in ops
              if kind == "custom-call" and name.startswith("spike_accum_blocks")]
    assert kernel and all("/accumulate/" in p for p in kernel)
    sends = [p for _, kind, p in ops if kind.startswith(("collective-permute", "all-reduce"))]
    assert sends and all("/exchange/level2/send/" in p for p in sends)
