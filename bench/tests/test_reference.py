"""The float64 reference on the CPU at 512 neurons: the program's einsum
path passes, and each control that a cell's limits require to fail (the
reference computed in bfloat16; in ``tonic`` also float32 with bfloat16
weights) fails, on the limits that ``bench/limits/`` holds for the
cell's mix."""
from __future__ import annotations

import numpy as np
import pytest

from bench import reference as ref
from bench import run


def program_raster(small_root, cell_name: str, seed: int):
    cell = run.load_cell(cell_name, small_root)
    built = run.build(cell, seed)
    key = run.seed_key(seed)
    steps = int(cell.mix["chunk_steps"])
    compiled, args, _ = built.engine.compile(steps, key=key)
    raster = np.asarray(compiled(*args))
    return raster, built, key, cell


@pytest.mark.parametrize("cell_name", ["tiny_1chip.async", "tiny_1chip.tonic"])
@pytest.mark.parametrize("seed", [1, 2**40 + 3])
def test_program_passes_and_control_fails(small_root, cell_name, seed):
    raster, built, key, cell = program_raster(small_root, cell_name, seed)
    args = (built.net, key, cell.config, cell.mix)
    limit = cell.limits["gap_mV"]
    v = built.family.check(raster, *args)
    assert v.spikes > 0
    assert v.gap_mV <= limit
    assert "bfloat16" in cell.limits["controls"]
    for name in cell.limits["controls"]:
        c = built.family.controls[name](raster, *args)
        assert c.gap_mV > limit, name


def test_tonic_requires_bfloat16_weights_to_fail(small_root):
    cell = run.load_cell("tiny_1chip.tonic", small_root)
    assert cell.limits["controls"] == ["bfloat16", "bfloat16_weights"]


def test_refractory_spike_is_infinite_gap(small_root):
    raster, built, key, cell = program_raster(small_root, "tiny_1chip.tonic", 4)
    t, i = map(int, np.argwhere(raster > 0)[0])
    bad = raster.copy()
    bad[t + 1, i] = 1.0  # the step after a spike is refractory
    v = built.family.check(bad, built.net, key, cell.config, cell.mix)
    assert v.gap_mV == float("inf")


def test_bfloat16_rounding():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, -3.14159], np.float32)
    np.testing.assert_array_equal(ref.as_bfloat16(x), [1.0, 1.0, 1.0 + 2**-6, -3.140625])
