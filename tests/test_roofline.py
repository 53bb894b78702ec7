"""Roofline layer: HLO parsing, trip-count accounting, collective
classification, and the three-term model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.roofline.analysis import V5E, model_flops, roofline
from repro.roofline.hlo import HloTotals, analyze, parse_module
from tests.conftest import run_devices


def test_scan_trip_count_flops_exact():
    n, k = 64, 5
    w = jnp.ones((k, n, n), jnp.float32)

    def scanned(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None

        return jax.lax.scan(body, x, w)[0]

    txt = jax.jit(scanned).lower(jnp.ones((n, n)), w).compile().as_text()
    t = analyze(txt, n_devices=1)
    assert t.flops == 2 * n**3 * k


def test_nested_scan_multiplies():
    n, k_out, k_in = 32, 3, 4
    w = jnp.ones((k_out, k_in, n, n), jnp.float32)

    def inner(x, ws):
        return jax.lax.scan(lambda h, wi: (h @ wi, None), x, ws)[0]

    def outer(x, w):
        return jax.lax.scan(lambda h, ws: (inner(h, ws), None), x, w)[0]

    txt = jax.jit(outer).lower(jnp.ones((n, n)), w).compile().as_text()
    t = analyze(txt, n_devices=1)
    assert t.flops == 2 * n**3 * k_out * k_in


def test_xla_cost_analysis_undercounts_scans():
    """Documents WHY the custom parser exists: XLA counts while bodies once."""
    n, k = 64, 8
    w = jnp.ones((k, n, n), jnp.float32)

    def scanned(x, w):
        return jax.lax.scan(lambda h, wi: (h @ wi, None), x, w)[0]

    c = jax.jit(scanned).lower(jnp.ones((n, n)), w).compile()
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    # XLA sees roughly one body's flops (elementwise ops may pad it),
    # nowhere near the k-times-unrolled total
    assert ca["flops"] < 2 * n**3 * k / 2
    assert analyze(c.as_text(), n_devices=1).flops == 2 * n**3 * k


def test_collective_parse_and_pod_classification():
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.roofline.hlo import analyze
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
w = jnp.ones((512, 512), jnp.float32)
ws = jax.device_put(w, NamedSharding(mesh, P("data", None)))
x = jax.device_put(jnp.ones((16, 512), jnp.float32), NamedSharding(mesh, P(("pod", "data"), None)))
@jax.jit
def f(x, w):
    y = x @ w
    return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P(("pod", "data"), None)))
t = analyze(f.lower(xs := x, ws).compile().as_text(), n_devices=8, pod_size=4)
assert t.coll_counts.get("all-gather", 0) >= 1, t.coll_counts
assert t.cross_pod_bytes == 0.0  # gather group is intra-pod
assert t.flops == 2 * 2 * 512 * 512  # per-device share
# now force a cross-pod reduction
@jax.jit
def g(x):
    return x.sum()
t2 = analyze(g.lower(x).compile().as_text(), n_devices=8, pod_size=4)
assert t2.cross_pod_bytes > 0 or t2.coll_operand_bytes >= 0
print("OK")
"""
    assert "OK" in run_devices(code)


def test_parse_tuple_types_with_comments():
    hlo = """
HloModule m

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %t = (s32[], f32[8,8]{1,0}, /*index=2*/f32[4,4]{1,0}) tuple(%a)
  ROOT %r = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    comps, entry = parse_module(hlo)
    assert entry == "main"
    ops = {o.name: o for o in comps["main"]}
    assert ops["t"].opcode == "tuple"
    t = analyze(hlo, n_devices=1)
    assert t.flops == 2 * 8 * 8 * 8


def test_roofline_terms():
    t = HloTotals(flops=1.97e13, hbm_bytes=8.19e11, coll_ring_bytes=5e10)
    rep = roofline(t, n_devices=256, model_flops_global=1.97e13 * 256 * 0.8, hw=V5E)
    assert abs(rep.compute_s - 0.1) < 1e-6
    assert abs(rep.memory_s - 1.0) < 1e-6
    assert rep.dominant == "memory"
    assert abs(rep.useful_ratio - 0.8) < 1e-6


def test_model_flops_conventions():
    assert model_flops(1e9, 1000, "train") == 6e12
    assert model_flops(1e9, 1000, "inference") == 2e12
