"""Two-level collective schedules: numerical equivalence with flat
collectives (8 fake host devices via subprocess) + analytic accounting."""
from __future__ import annotations

import numpy as np

from repro.core.hierarchical import (
    dispatch_bytes,
    dispatch_messages,
    dispatch_messages_from_table,
)
from tests.conftest import run_devices


def test_two_level_equals_flat_a2a():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.hierarchical import make_exchange_fns
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
n_dev, chunk, d = 8, 3, 5
x = jnp.arange(n_dev*n_dev*chunk*d, dtype=jnp.float32).reshape(n_dev, n_dev, chunk, d)
x = jax.device_put(x, NamedSharding(mesh, P(("pod","data"))))
flat, two = make_exchange_fns(mesh)
yf, yt = flat(x), two(x)
np.testing.assert_allclose(np.asarray(yf), np.asarray(yt))
np.testing.assert_allclose(np.asarray(yf)[3, 5], np.asarray(x)[5, 3])
np.testing.assert_allclose(np.asarray(yf)[0, 7], np.asarray(x)[7, 0])
print("OK")
"""
    assert "OK" in run_devices(code)


def test_hierarchical_psum_equals_flat():
    code = """
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.sharding import AxisType
from repro.core.hierarchical import hierarchical_psum, flat_psum, two_level_all_gather
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
g = jnp.arange(16*4, dtype=jnp.float32).reshape(16, 4)
wrap = lambda f: jax.jit(functools.partial(
    shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(f))
hp = wrap(lambda v: hierarchical_psum(v))
fp = wrap(lambda v: flat_psum(v, ("pod", "data")))
np.testing.assert_allclose(np.asarray(hp(g)), np.asarray(fp(g)))
# two-level all-gather == identity on replicated inputs gathered over shards
xs = jnp.arange(8*3, dtype=jnp.float32).reshape(8, 3)
ag = jax.jit(functools.partial(
    shard_map, mesh=mesh, in_specs=(P(("pod","data")),), out_specs=P(),
    check_vma=False)(lambda v: two_level_all_gather(v)))
np.testing.assert_allclose(np.asarray(ag(xs)), np.asarray(xs))
print("OK")
"""
    assert "OK" in run_devices(code)


def test_message_accounting():
    """Cross-pod messages drop by the inner group size; bytes are equal
    (the paper's Fig. 4 claim restated for collectives)."""
    flat = dispatch_messages(2, 256, two_level=False)
    two = dispatch_messages(2, 256, two_level=True)
    assert flat["cross_pod"] == 2 * 1 * 256 * 256
    assert two["cross_pod"] == 2 * 1 * 256
    assert flat["cross_pod"] / two["cross_pod"] == 256
    bf = dispatch_bytes(2, 256, 1024, two_level=False)
    bt = dispatch_bytes(2, 256, 1024, two_level=True)
    assert bf["cross_pod"] == bt["cross_pod"]
    # level-1 aggregation costs extra intra-pod bytes (the trade)
    assert bt["intra_pod"] >= bf["intra_pod"]


def test_single_pod_no_cross_traffic():
    assert dispatch_messages(1, 64, two_level=True)["cross_pod"] == 0


def test_measured_messages_from_routing_table():
    """The measured accounting derived from an actual Algorithm-2 table
    agrees with the analytic mesh model on uniform all-to-all traffic."""
    from repro.core import p2p_routing, two_level_routing

    pods, inner = 4, 8
    n = pods * inner
    rng = np.random.default_rng(0)
    t = rng.uniform(0.5, 1.0, (n, n))
    t = (t + t.T) / 2
    np.fill_diagonal(t, 0.0)
    wg = np.ones(n)
    # P2P: every flow crosses individually — matches the flat model total
    p2p = dispatch_messages_from_table(p2p_routing(t, wg))
    flat = dispatch_messages(pods, inner, two_level=False)
    assert p2p["level1"] == 0
    assert p2p["level2"] == n * (n - 1) == flat["cross_pod"] + flat["intra_pod"]
    # Two-level: the aggregated cross-group connections collapse below the
    # flat fan-out and never below one per ordered group pair
    tb = two_level_routing(t, wg, pods, grouping="random")
    two = dispatch_messages_from_table(tb)
    model = dispatch_messages(pods, inner, two_level=True)
    assert pods * (pods - 1) <= two["level2"] <= model["cross_pod"]
    assert two["level1"] + two["level2"] < p2p["level2"]
