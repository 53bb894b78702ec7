"""Compile rehearsal of each cell's chunk step for a described TPU v5e
(``v5e:2x2``): one device for the one-chip configuration, the 2×2 mesh
for the other, at the cells' real sizes (M = 16,384, 1,000-step chunks)
and with the exchange schedule of the configuration's connectome, which
every seed shares (``run.network``).  No
chip is needed; the topology is described inside a module-scoped
fixture, so only the worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import run

SEED = 2026


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe the chip means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


class TileOccupancy:
    """What the ragged planner reads of ``BlockSynapses``, from the
    benchmark's synapse lists, without materializing the tiles."""

    def __init__(self, net, n_blocks: int):
        b = net.n_neurons // n_blocks
        pre, post = net.pre(), net.post
        occ = np.zeros((n_blocks, n_blocks, b), bool)  # [src, dst, row]
        occ[pre // b, post // b, pre % b] = True
        src, dst = np.nonzero(occ.any(axis=2))
        order = np.lexsort((src, dst))
        self.src_ids, self._dst, self._occ = src[order], dst[order], occ[src[order], dst[order]]
        self.n_blocks, self.block_size = n_blocks, b
        self.indptr = np.concatenate([[0], np.cumsum(np.bincount(self._dst, minlength=n_blocks))])

    def dst_of(self):
        return self._dst

    def tile_occupancy(self):
        return self._occ


@pytest.mark.parametrize("cell", ["brain16k_1chip.async", "brain16k_2x2.async", "brain16k_1chip.tonic"])
def test_chunk_step_compiles(topo, cell):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import KernelPolicy
    from repro.snn import LIFParams
    from repro.snn.distributed import _sparse_step, _StepKey
    from repro.snn.ragged import build_ragged_plan

    spec = run.load_cell(cell)
    cfg = spec.config
    net, _ = run.network(cfg, SEED)
    n_dev = int(np.prod(cfg["mesh"]))
    m, b = net.n_neurons, net.n_neurons // n_dev
    tiles = TileOccupancy(net, n_dev)
    k = int(np.diff(tiles.indptr).max())
    if cfg["exchange"] == "ragged":
        plan = build_ragged_plan(tiles, tuple(cfg["mesh"]))
        live = [r for r in plan.rounds if r.pairs]
        signature = ("ragged", tuple((r.shift, r.width, r.perm) for r in live))
        widths = [r.width for r in live]
    else:
        assert n_dev == 1
        signature, widths = ("sparse", ()), []
    mesh = Mesh(np.asarray(topo.devices[:n_dev]).reshape(cfg["mesh"]), ("pod", "data"))
    steps = int(spec.mix["chunk_steps"])
    fn = _sparse_step(_StepKey(
        mesh=mesh,
        params=LIFParams(**cfg["lif"], noise_sigma=spec.mix["noise_sigma"]),
        policy=KernelPolicy(use_pallas=True),
        i_ext=float(spec.mix["i_ext"]),
        ragged_scatter="fused",
        n_steps=steps,
        signature=signature,
    ))
    sh = NamedSharding(mesh, P(("pod", "data")))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n_dev))
    compiled = fn.lower(
        sds((m,), jnp.float32),
        sds((m,), jnp.float32),
        sds(keys.shape, keys.dtype),
        sds((n_dev, k), jnp.int32),
        sds((n_dev, k, b, b), jnp.float32),
        tuple(sds((n_dev, 2, w), jnp.int32) for w in widths),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if n_dev > 1:
        assert "collective-permute" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= k * b * b * 4  # the tiles, per device
