"""The work a simulation step needs, counted from the raster and the
synapse lists, and the least time a chip could take for it.

These counts are of what the algorithm needs, not of what an
implementation moves, so they read the same whatever runs the step:

* accumulation: every synapse of a neuron that spiked in the previous
  step, on the chip that holds its postsynaptic neuron, is read once
  (4 bytes) and multiplied and added (2 operations);
* neuron update: each chip reads and writes the state of its neurons
  (``state_bytes`` a neuron, which the network family states);
* exchange: a spike reaches every other chip that holds one of its
  synapses, as an ``id_bytes`` neuron id (the family's), over that
  chip's interconnect.

The least time is the larger of operations over peak FLOP/s, bytes over
HBM bandwidth and, on several chips, received bytes over ICI bandwidth,
with the peaks of ``peaks.json`` for the device kind.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Work:
    """Per-chip totals over the counted steps (arrays of length n_chips)."""

    steps: int
    accum_ops: np.ndarray
    accum_bytes: np.ndarray
    state_bytes: np.ndarray
    exchange_bytes: np.ndarray

    def accum_least_s(self, pk: dict) -> np.ndarray:
        return np.maximum(
            self.accum_ops / pk["flops_per_s"], self.accum_bytes / pk["hbm_bytes_per_s"]
        )

    def step_least_s(self, pk: dict) -> np.ndarray:
        return np.maximum.reduce([
            self.accum_ops / pk["flops_per_s"],
            (self.accum_bytes + self.state_bytes) / pk["hbm_bytes_per_s"],
            self.exchange_bytes / pk["ici_bytes_per_s"],
        ])


def count(rasters: list[np.ndarray], per_block: np.ndarray, *, state_bytes: float,
          id_bytes: float) -> Work:
    """Work of the steps in ``rasters`` (chunks ``[T, M]``, each starting
    from rest): ``per_block[i, d]`` is the number of synapses of neuron
    ``i`` onto chip ``d``'s neurons; a neuron's state moves
    ``state_bytes`` a step, a spike ``id_bytes`` to each other chip."""
    m, n = per_block.shape
    b = m // n
    fired = np.zeros(m)  # spikes that drive a step: every row but the last
    steps = 0
    for r in rasters:
        fired += r[:-1].sum(axis=0)
        steps += r.shape[0]
    uses = fired @ per_block  # synapse reads per chip
    home = np.arange(m) // b
    remote = (per_block > 0) & (home[:, None] != np.arange(n)[None, :])
    return Work(
        steps=steps,
        accum_ops=2.0 * uses,
        accum_bytes=4.0 * uses,
        state_bytes=np.full(n, float(state_bytes * b * steps)),
        exchange_bytes=id_bytes * (fired @ remote),
    )
