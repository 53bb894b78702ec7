"""Faults planted under the timed path, for ``test_harness.py``: each must
turn ``correct`` false.

* ``state_unchanged``: the neuron step returns its state as it came, and
  no spikes;
* ``half_left_out``: the second half of each chip's neurons never spike
  (half of the work left out);
* ``no_exchange``: the level-2 exchange between chips delivers nothing;
* ``altered``: one answer changed where it is produced: a spike added to
  the raster of every chunk, at the neuron that fires least.
"""
from __future__ import annotations


def install(name: str | None) -> None:
    if name is None:
        return
    import jax
    import jax.numpy as jnp

    from repro.snn import distributed

    if name == "state_unchanged":
        distributed.lif_step = lambda state, i_syn, params: (state, jnp.zeros_like(state.v))
    elif name == "half_left_out":
        step = distributed.lif_step

        def half(state, i_syn, params):
            state, spikes = step(state, i_syn, params)
            return state, spikes.at[spikes.shape[0] // 2:].set(0.0)

        distributed.lif_step = half
    elif name == "no_exchange":
        distributed.jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    elif name == "altered":
        compile_ = distributed.DistributedSNN.compile

        @jax.jit
        def alter(raster):
            i = jnp.argmin(raster.sum(axis=0))
            t = raster.shape[0] // 2
            return raster.at[t, i].set(1.0)

        def compile_altered(self, n_steps, *, key=None):
            compiled, args, compile_s = compile_(self, n_steps, key=key)
            return (lambda *a: alter(compiled(*a))), args, compile_s

        distributed.DistributedSNN.compile = compile_altered
    else:
        raise ValueError(f"unknown fault {name!r}")
