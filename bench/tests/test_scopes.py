"""The reading by named scopes (``scopes.py``) checked by hand on traces
recorded on the chip with their scopes (``data/trace_scoped_<n>chips``):
each cell's real configuration, two chunks of 20 steps, made by

    python3 bench/scoped.py --workload brain16k_1chip.async --seed 1732050807 \\
        --chunks 2 --chunk-steps 20 --save trace_scoped_1chips.json.gz

(``brain16k_2x2.async``, seed 1732050808, on a 2x2 host for the four-chip
file).  Sums are taken straight from the raw rows on a 1 ns grid."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import scopes as sc
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
FILES = ["trace_scoped_1chips.json.gz", "trace_scoped_4chips.json.gz"]
STEPS = 40  # two chunks of 20 steps


def rows_of(path: Path) -> list[list]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def grid(rows: list[list], lo: float, hi: float) -> np.ndarray:
    """Which nanoseconds of [lo, hi) the rows cover."""
    g = np.zeros(int(hi - lo) + 2, bool)
    for r in rows:
        g[int(round(r[3] - lo)): int(round(r[3] + r[4] - lo))] = True
    return g


def is_leaf(r: list) -> bool:
    return tr.Event(*r[:5]).layer in sc.LEAF


def is_running(r: list) -> bool:
    return tr.Event(*r[:5]).layer not in ("module", "outer")


@pytest.mark.parametrize("name", FILES)
def test_scopes_by_hand(name):
    path = DATA / name
    rows = rows_of(path)
    events, smap = sc.load(path)
    chips = sc.reduce(events, smap)
    sc.check_fresh(events, smap)
    for chip, c in chips.items():
        mine = [r for r in rows if r[0] == chip]
        lo = min(r[3] for r in mine)
        hi = max(r[3] + r[4] for r in mine)
        leaf = [r for r in mine if is_leaf(r)]
        for scope in {r[5] for r in leaf}:
            by_hand = grid([r for r in leaf if r[5] == scope], lo, hi).sum() * 1e-9
            assert c.scopes[scope] == pytest.approx(by_hand, rel=1e-3), scope
        assert set(c.scopes) == {r[5] for r in leaf}
        assert c.leaf == pytest.approx(grid(leaf, lo, hi).sum() * 1e-9, rel=1e-3)
        # idle inside the programs: module time with nothing running
        modules = grid([r for r in mine if r[1] == tr.MODULES], lo, hi)
        running = grid([r for r in mine if is_running(r)], lo, hi)
        assert c.step_idle == pytest.approx((modules & ~running).sum() * 1e-9, rel=1e-3, abs=1e-9)
        assert sum(c.idle_by_scope.values()) == pytest.approx(c.step_idle, rel=1e-6)
        # the step's work is named: under 5% of leaf time outside every scope
        assert c.scopes.get(sc.UNSCOPED, 0.0) < 0.05 * c.leaf
        assert c.scopes["accumulate"] > 0 and c.scopes["neuron"] > 0


def test_four_chips_hold_every_scope_and_split_the_update():
    """On the 2x2 mesh every scope runs, and the ops ``trace.py`` calls
    neuron update are the level-2 pack and unpack and the neuron update,
    to within 5%."""
    events, smap = sc.load(DATA / FILES[1])
    chips = sc.reduce(events, smap)
    red = tr.reduce(events)
    for scope in sc.step_scopes():
        assert sc.per_step_ms(chips, scope, STEPS) is not None, scope
    split = sum(sc.per_step_ms(chips, s, STEPS) for s in
                ("exchange/level2/pack", "exchange/level2/unpack", "neuron"))
    update = red.per_chip("update").max() / STEPS * 1e3
    assert split == pytest.approx(update, rel=0.05)
    # the kernel, trace.py's accumulation, is in the accumulation scope
    for chip, c in chips.items():
        assert c.scopes["accumulate"] >= red.chips[chip].accumulation * (1 - 1e-9)


def test_one_chip_has_no_exchange():
    events, smap = sc.load(DATA / FILES[0])
    chips = sc.reduce(events, smap)
    for scope in ("exchange/level1", "exchange/level2/send"):
        assert sc.per_step_ms(chips, scope, STEPS) is None
    assert sc.step_idle_ms(chips, STEPS) > 0
