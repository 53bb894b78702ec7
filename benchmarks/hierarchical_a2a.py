"""Hierarchical (two-level) vs flat all-to-all on the TPU mesh —
the paper's §IV-B bridge pattern applied to MoE dispatch / gradient
reduction (DESIGN.md §4).

Two measurements:
  1. Analytic: cross-pod message count + bytes per full exchange on the
     production 2×16×16 mesh (paper Fig. 4 restated: messages drop by
     the group size; bytes stay equal).
  2. Executable: an 8-host-device subprocess runs both schedules via
     shard_map and asserts numerical equality while timing them.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

from repro.core.hierarchical import dispatch_bytes, dispatch_messages
from benchmarks.common import emit

_CHILD = r"""
import time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.hierarchical import make_exchange_fns
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
n_dev, chunk, d = 8, 64, 256
x = jnp.arange(n_dev * n_dev * chunk * d, dtype=jnp.float32).reshape(
    n_dev, n_dev, chunk, d)
x = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
flat, two = make_exchange_fns(mesh)
yf = flat(x); yt = two(x)
np.testing.assert_allclose(np.asarray(yf), np.asarray(yt))
for name, fn in [("flat", flat), ("two_level", two)]:
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        out = fn(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / 20
    print(f"{name},{dt*1e6:.1f}")
print("equal,1")
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--inner", type=int, default=256)
    ap.add_argument("--chunk-bytes", type=int, default=2 * 320 * 2048)  # qwen3 token block
    ap.add_argument("--skip-exec", action="store_true")
    args = ap.parse_args(argv)

    for two in (False, True):
        tag = "two_level" if two else "flat"
        msgs = dispatch_messages(args.pods, args.inner, two_level=two)
        byts = dispatch_bytes(args.pods, args.inner, args.chunk_bytes, two_level=two)
        emit(f"a2a/{tag}_cross_pod_msgs", msgs["cross_pod"], "per exchange")
        emit(f"a2a/{tag}_cross_pod_bytes", f"{byts['cross_pod']:.3e}", "")
    red = dispatch_messages(args.pods, args.inner, two_level=False)["cross_pod"] / max(
        dispatch_messages(args.pods, args.inner, two_level=True)["cross_pod"], 1
    )
    emit("a2a/msg_reduction_factor", round(red, 1), "= inner group size (paper Fig.4)")

    if not args.skip_exec:
        env = dict(os.environ)
        # fake host devices only: the child never touches an accelerator
        # the parent process may hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, capture_output=True, text=True
        )
        if out.returncode != 0:
            raise SystemExit(f"8-device a2a run failed:\n{out.stderr[-3000:]}")
        for line in out.stdout.strip().splitlines():
            k, v = line.split(",")
            emit(f"a2a/exec_{k}_us" if k != "equal" else "a2a/exec_equal", v, "")


if __name__ == "__main__":
    main()
