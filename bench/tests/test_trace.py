"""The trace reduction checked by hand: on a made-up trace whose numbers
are worked out below, and on small traces recorded on the chip
(``data/``, made by ``record_trace.py``), against sums taken straight
from the raw event rows."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"

KERNEL = ('%spike_accum_blocks.8 = f32[1,512]{1,0} custom-call(s32[1]{0} %a, f32[1,1,512]{2,1,0} %b), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = (f32[512]{0:T(512)}, pred[512]{0}) fusion(f32[512]{0} %x), kind=kLoop"
GATHER_START = "%all-gather-start.1 = (f32[128]{0}, f32[512]{0}) all-gather-start(f32[128]{0} %s)"
GATHER_DONE = "%all-gather-done.1 = f32[512]{0} all-gather-done((f32[128]{0}, f32[512]{0}) %all-gather-start.1)"
COPY = "%copy-start.4 = (f32[4,128,128]{2,1,0}, f32[4,128,128]{2,1,0}, u32[]) copy-start(f32[4,128,128]{2,1,0} %g)"
WHILE = "%while.6 = (s32[]{:T(128)}, f32[512]{0}) while((s32[]{:T(128)}, f32[512]{0}) %tuple.60), body=%b"


def made_up() -> list[tr.Event]:
    """Two chunks on one chip; the device clock reads 40 ns less than the
    host's, so each chunk seems to start before its dispatch."""
    ev = [
        tr.Event(-1, "python3", "bench.dispatch", 0, 100),
        tr.Event(-1, "python3", "bench.fetch", 100, 1300),  # chunk 1 ends at 1400
        tr.Event(-1, "python3", "bench.dispatch", 1400, 100),
        tr.Event(-1, "python3", "bench.fetch", 1500, 1300),  # window ends at 2800
    ]
    for base in (-40, 1360):  # device time of the two chunk starts (host: 0, 1400)
        ev += [
            tr.Event(0, "XLA Modules", "jit__run(1)", base, 1000),
            tr.Event(0, "XLA Ops", WHILE, base + 10, 980),
            tr.Event(0, "XLA Ops", KERNEL, base + 20, 500),
            tr.Event(0, "XLA Ops", FUSION, base + 520, 80),
            tr.Event(0, "XLA Ops", GATHER_START, base + 600, 50),
            tr.Event(0, "XLA Ops", FUSION, base + 640, 60),  # overlaps the gather by 10
            # the chip waits at a barrier for 30 ns: idle inside the step
            tr.Event(0, "XLA TraceMe", "barrier-cores", base + 700, 30),
            tr.Event(0, "XLA Ops", GATHER_DONE, base + 730, 20),
            # in flight under the kernel: busy time, no op of a layer
            tr.Event(0, "Async XLA Ops", COPY, base + 15, 300),
        ]
    return ev


def test_made_up_trace():
    red = tr.reduce(made_up())
    c = red.chips[0]
    assert red.window_s == pytest.approx(2800e-9)
    # leaf ops from the copy's start to the gather's end, less the barrier:
    # [15, 700] and [730, 750] in each chunk
    assert c.busy == pytest.approx(2 * 705e-9)
    assert c.accumulation == pytest.approx(1000e-9)
    assert c.update == pytest.approx(2 * 140e-9)
    assert c.exchange == pytest.approx(2 * 70e-9)
    assert c.exchange_exposed == pytest.approx(2 * 60e-9)
    # device [660, 690], [710, 1375], [2060, 2090], shifted by 40 ns onto the host
    assert c.gaps == [(700.0, 730.0), (750.0, 1415.0), (2100.0, 2130.0)]
    bd = red.breakdown()
    assert bd["idle_gaps"] == [["bench.fetch", pytest.approx(665e-9)],
                               ["bench.fetch", pytest.approx(30e-9)],
                               ["bench.fetch", pytest.approx(30e-9)]]
    assert bd["device_ops"][0] == ["spike_accum_blocks.8", pytest.approx(1000e-9)]
    assert {k for k, _ in bd["device_ops"]} == {"spike_accum_blocks.8", "fusion.3",
                                               "all-gather-start.1", "all-gather-done.1"}


def test_kinds():
    kinds = [tr.Event(0, "XLA Ops", n, 0, 1).kind for n in (KERNEL, FUSION, GATHER_START, GATHER_DONE, WHILE)]
    assert kinds == ["custom-call", "fusion", "all-gather-start", "all-gather-done", "while"]
    layers = [tr.Event(0, "XLA Ops", n, 0, 1).layer for n in (KERNEL, FUSION, GATHER_START, WHILE)]
    assert layers == ["accumulation", "update", "exchange", "outer"]
    in_flight = [tr.Event(0, "Async XLA Ops", n, 0, 1).layer for n in (COPY, GATHER_START)]
    assert in_flight == ["in_flight", "exchange"]
    assert tr.Event(0, "XLA TraceMe", "barrier-cores", 0, 1).layer == "outer"


def test_no_host_spans_is_an_error():
    with pytest.raises(ValueError, match="host spans"):
        tr.reduce([e for e in made_up() if e.chip >= 0])


def raw(path: Path) -> list[list]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def busy_by_grid(rows: list[list]) -> float:
    """Union length on a 1 ns grid, seconds."""
    lo = min(r[3] for r in rows)
    hi = max(r[3] + r[4] for r in rows)
    grid = np.zeros(int(hi - lo) + 2, bool)
    for r in rows:
        grid[int(round(r[3] - lo)): int(round(r[3] + r[4] - lo))] = True
    return grid.sum() * 1e-9


COLL = (" all-gather", " all-reduce", " collective-permute", " all-to-all", " reduce-scatter")


@pytest.mark.parametrize("name", ["trace_1chips.json.gz", "trace_4chips.json.gz"])
def test_recorded_trace(name):
    path = DATA / name
    rows = raw(path)
    red = tr.reduce(tr.load(path))
    host = [r for r in rows if r[0] < 0]
    assert red.window_s == pytest.approx(
        (max(r[3] + r[4] for r in host if r[2] == "bench.fetch")
         - min(r[3] for r in host if r[2] == "bench.dispatch")) * 1e-9)
    chips = sorted({r[0] for r in rows if r[0] >= 0})
    assert sorted(red.chips) == chips
    for chip in chips:
        mine = [r for r in rows if r[0] == chip]
        ops = [r for r in mine if r[1] != "XLA Modules"]
        leaf = [r for r in ops if r[1] in ("XLA Ops", "Async XLA Ops") and " while(" not in r[2]]
        modules = [r for r in mine if r[1] == "XLA Modules"]
        kernel = sum(r[4] for r in mine if 'custom_call_target="tpu_custom_call"' in r[2]) * 1e-9
        c = red.chips[chip]
        assert c.accumulation == pytest.approx(kernel)
        assert c.accumulation > 0
        assert c.busy == pytest.approx(busy_by_grid(leaf), rel=1e-3)
        assert c.busy < busy_by_grid(modules)  # idle inside the programs shows
        assert c.busy <= red.window_s
        coll = [r for r in ops if any(k + s in r[2] for k in COLL for s in ("(", "-start(", "-done("))]
        if len(chips) == 1:
            assert not coll and c.exchange == 0.0
        else:
            assert coll and c.exchange > 0
            assert c.exchange <= sum(r[4] for r in coll) * 1e-9 * (1 + 1e-9)
            assert 0 <= c.exchange_exposed <= c.exchange
        assert ops  # the chip ran leaf ops
    bd = red.breakdown()
    times = [t for _, t in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
    kernel = [t for k, t in bd["device_ops"] if k.startswith("spike_accum_blocks")]
    assert kernel == [pytest.approx(red.per_chip("accumulation").mean())]
    assert len(bd["idle_gaps"]) >= 1
