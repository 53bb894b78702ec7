"""The brain-simulation launcher and the chip smoke script, on the CPU:
the launcher runs end to end on fake devices, places the compile cache
as documented and sets nothing as it is imported; the smoke script
refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.kernels import KernelPolicy
from tests.conftest import run_devices

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, env: dict[str, str]) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full.update(env)
    full["PYTHONPATH"] = str(ROOT / "src")
    return subprocess.run(
        [sys.executable, "-c", code], env=full, cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )


def test_kernel_policy_defaults_to_compiled_kernels():
    assert KernelPolicy().interpret is False
    assert KernelPolicy().use_pallas is False


def test_import_sets_no_flags_and_cache_is_placed(tmp_path):
    code = """
import json, os, jax
from repro.launch import run_brainsim
seen = {"xla_flags": os.environ.get("XLA_FLAGS"),
        "before": jax.config.jax_compilation_cache_dir}
seen["returned"] = run_brainsim.use_compile_cache()
seen["after"] = jax.config.jax_compilation_cache_dir
print(json.dumps(seen))
"""
    unset = _python(code, {"JAX_PLATFORMS": "cpu"})
    assert unset.returncode == 0, unset.stderr
    got = json.loads(unset.stdout.strip().splitlines()[-1])
    assert got["xla_flags"] is None and got["before"] is None
    assert got["returned"] == got["after"] == str(ROOT / ".jax_cache")

    env_dir = str(tmp_path / "cache")
    placed = _python(code, {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": env_dir})
    assert placed.returncode == 0, placed.stderr
    got = json.loads(placed.stdout.strip().splitlines()[-1])
    # JAX reads the variable itself; the helper changes nothing
    assert got["before"] == got["after"] == got["returned"] == env_dir


@pytest.mark.parametrize("n_devices", [1, 4])
def test_launcher_matches_reference(n_devices, tmp_path, monkeypatch):
    """``main()`` on fake CPU devices ('sparse' on one, 'ragged' on a
    2×2 mesh) gives the raster of ``SNNEngine`` on the same tiles."""
    code = f"""
import numpy as np, jax
from repro.launch import run_brainsim
from repro.snn import SNNEngine
r = run_brainsim.main(["--populations", "32", "--neurons-per-pop", "4",
                       "--steps", "120"])
assert r.engine.exchange == {"'sparse'" if n_devices == 1 else "'ragged'"}
assert r.engine.syn.n_blocks == {n_devices} and r.raster.shape == (120, 128)
assert r.raster.sum() > 0 and r.compile_s > 0 and r.steps_per_s > 0
ref = SNNEngine(w_syn=r.engine.syn.to_dense(), params=r.engine.params,
                i_ext=r.engine.i_ext).run(120, key=jax.random.PRNGKey(0))
np.testing.assert_array_equal(r.raster, np.asarray(ref.spikes))
print("OK")
"""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = run_devices(code, n_devices=n_devices)
    assert "platform=cpu" in out and f"devices={n_devices}" in out
    assert "weight strips a step" in out
    assert "OK" in out


def test_compile_runs_what_run_runs():
    """``DistributedSNN.compile`` stages and compiles the step ``run``
    executes, and with the tracer on records the slow-axis bytes a step
    of the compiled plan moves."""
    import jax
    import numpy as np

    from repro import obs
    from repro.launch import run_brainsim

    eng = run_brainsim.build_engine(32, 4, noise=1.0)
    key = jax.random.PRNGKey(3)
    obs.enable()
    try:
        compiled, args, compile_s = eng.compile(50, key=key)
        counters = [e for e in obs.events() if e["ph"] == "C"]
    finally:
        obs.disable()
        obs.clear()
    assert compile_s > 0
    np.testing.assert_array_equal(
        np.asarray(compiled(*args)), np.asarray(eng.run(50, key=key))
    )
    assert [c["name"] for c in counters] == ["snn.exchange_bytes"]
    assert counters[0]["args"] == {"level2": float(eng.exchange_stats()[eng.exchange])}


def test_chip_smoke_fails_without_tpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_collectives_counted_from_hlo():
    sys.path.insert(0, str(ROOT))
    from chip_smoke import collectives

    hlo = """
  %all-gather.4 = f32[16384]{0} all-gather(%x), channel_id=1
  %collective-permute-start = (f32[8], f32[8], u32[], u32[]) collective-permute-start(%y)
  %collective-permute-done = f32[8]{0} collective-permute-done(%collective-permute-start)
  %psum.10 = f32[16384]{0} all-reduce(%collective-permute-done), channel_id=1
"""
    assert collectives(hlo) == {"all-gather": 1, "collective-permute": 1, "all-reduce": 1}
