"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Optional policy (DESIGN.md §6): stages hold contiguous layer blocks;
microbatches flow through the pipeline via ``ppermute`` rotation inside
``shard_map``.  The schedule is the classic GPipe fill-drain: with S
stages and M microbatches the loop runs S+M−1 ticks; each tick every
stage applies its block to the microbatch it holds, then activations
rotate one stage forward.  Bubble fraction = (S−1)/(S+M−1).

This is deliberately self-contained (works for any per-stage function
of signature ``f(stage_params, x) -> x``) — the LM integrates by
stacking per-stage layer params.  Numerical equivalence with the
sequential composition is tested in ``tests/test_pipeline.py``.
"""
from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

__all__ = ["gpipe", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + n_microbatches - 1)


def gpipe(
    stage_fn: Callable,
    mesh: Mesh,
    *,
    axis: str = "pipe",
    n_microbatches: int,
):
    """Build a pipelined apply: ``(stage_params, x) -> y``.

    Args:
      stage_fn: per-stage transform ``f(params_for_stage, x_mb) -> x_mb``.
      mesh: mesh containing ``axis`` (its size = number of stages).
      n_microbatches: must be ≥ 1; batch dim must divide it.

    stage_params: pytree whose leaves have leading dim = n_stages
    (sharded over ``axis``).  x: [B, ...] activations, replicated.
    Returns y: [B, ...] after all stages, replicated.
    """
    n_stages = mesh.shape[axis]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(stage_params, x):
        sp = jax.tree.map(lambda a: a[0], stage_params)  # my stage's slice
        stage = lax.axis_index(axis)
        mbs = x.reshape((n_microbatches, x.shape[0] // n_microbatches) + x.shape[1:])
        n_ticks = n_stages + n_microbatches - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry  # buf: my current activation; out: finished mbs
            # stage 0 injects microbatch t (if any remain)
            inject = jnp.where(t < n_microbatches, t, 0)
            buf = jnp.where(stage == 0, mbs[inject], buf)
            # hold only when this stage hasn't been reached yet (t < stage)
            # or its stream has drained (t >= stage + n_microbatches)
            active = (t >= stage) & (t < stage + n_microbatches)
            y = stage_fn(sp, buf)
            buf = jnp.where(active, y, buf)
            # last stage deposits its finished microbatch
            mb_done = t - (n_stages - 1)
            out = jnp.where(
                (stage == n_stages - 1) & active,
                lax.dynamic_update_slice(
                    out, buf[None], (jnp.maximum(mb_done, 0),) + (0,) * buf.ndim
                ),
                out,
            )
            # rotate activations one stage forward
            buf = lax.ppermute(buf, axis, perm)
            return (buf, out), None

        buf0 = jnp.zeros_like(mbs[0])
        out0 = jnp.zeros_like(mbs)
        (buf, out), _ = lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
        # only the last stage holds the real outputs — broadcast them
        out = lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)), axis
        )
        return out.reshape(x.shape)

    return run
