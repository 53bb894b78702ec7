"""GPipe pipeline parallelism: equivalence with sequential execution."""
from __future__ import annotations


from repro.sharding.pipeline import bubble_fraction
from tests.conftest import run_devices


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == 3 / 7
    assert bubble_fraction(1, 8) == 0.0


def test_gpipe_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import gpipe
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
n_stages, d, B, mb = 4, 16, 8, 4
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (n_stages, d, d)) * 0.3
b = jax.random.normal(jax.random.PRNGKey(1), (n_stages, d)) * 0.1
x = jax.random.normal(jax.random.PRNGKey(2), (B, d))

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

run = gpipe(stage_fn, mesh, n_microbatches=mb)
y = run({"w": w, "b": b}, x)

ref = x
for s in range(n_stages):
    ref = jnp.tanh(ref @ w[s] + b[s])
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)
print("OK")
"""
    assert "OK" in run_devices(code, n_devices=4)
