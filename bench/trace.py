"""From a ``jax.profiler`` trace to per-layer device time.

A trace is read into plain events (:func:`load_xplane`): every op of each
chip's ``/device:TPU:<n>`` plane, and the harness's own host spans
(names starting with ``bench.``).  :func:`reduce` then gives, per chip:

* busy: the union of the intervals of the leaf ops, those of the ``XLA
  Ops`` line and those in flight on the ``Async XLA Ops`` line (copies
  and collectives), so that a gap between two ops of a step is idle;
* accumulation: the Pallas kernels (``custom-call`` to
  ``tpu_custom_call``);
* exchange: collectives (all-gather, collective-permute, all-reduce,
  all-to-all, reduce-scatter, with their ``-start`` and ``-done``, and
  their time in flight), as the union of their intervals, and the part
  of it in which no other op runs (exposed);
* update: every other op of the ``XLA Ops`` line (the LIF update, the
  noise draw, the packing and scattering of exchanged spikes, copies).

Ops are told apart by their HLO kind until the step has named scopes.
Whole program executions (the ``XLA Modules`` line), the loop and call
ops that contain the others (``while``, ``conditional``, ``call``) and
the other lines (``XLA TraceMe`` barriers, where a chip waits) are not
leaf ops and count toward nothing; the program executions only place
the device clock on the host's.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
from pathlib import Path

import numpy as np

HOST_PREFIX = "bench."
COLLECTIVES = {
    f"{k}{s}"
    for k in ("all-gather", "collective-permute", "all-reduce", "all-to-all", "reduce-scatter")
    for s in ("", "-start", "-done")
}
CONTAINERS = {"while", "conditional", "call"}
MODULES = "XLA Modules"  # one event per execution of a compiled program
OPS = "XLA Ops"  # the ops as they run, one at a time
IN_FLIGHT = "Async XLA Ops"  # copies and collectives between -start and -done
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_KIND = re.compile(r" ([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    chip: int  # device id, or -1 for a host span
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The op's short name (``spike_accum_blocks.8``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def kind(self) -> str:
        """HLO kind: ``fusion``, ``custom-call``, ``all-gather-start``..."""
        _, _, rest = self.name.partition(" = ")
        m = _KIND.search(" " + rest)
        return m.group(1) if m else ""

    @property
    def layer(self) -> str:
        if self.line == MODULES:
            return "module"
        kind = self.kind
        if kind in COLLECTIVES:
            return "exchange"
        if self.line == IN_FLIGHT:
            return "in_flight"  # busy time only
        if kind in CONTAINERS or self.line != OPS:
            return "outer"  # not a leaf op
        if kind == "custom-call" and 'custom_call_target="tpu_custom_call"' in self.name:
            return "accumulation"
        return "update"


def load_xplane(path: str | Path) -> list[Event]:
    """Device ops and ``bench.`` host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        dev = _DEVICE.match(plane.name)
        for line in plane.lines:
            if dev:
                out += [
                    Event(int(dev.group(1)), line.name, e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                ]
            elif plane.name.startswith("/host"):
                out += [
                    Event(-1, line.name, e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                ]
    return out


def find_xplane(log_dir: str | Path) -> Path:
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(files[-1])


def save(events: list[Event], path: str | Path) -> None:
    rows = [[e.chip, e.line, e.name, e.start_ns, e.dur_ns] for e in events]
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load(path: str | Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: list[tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two disjoint sorted unions."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass(frozen=True)
class Chip:
    """One chip's device time in the traced window, seconds."""

    busy: float
    accumulation: float
    update: float
    exchange: float
    exchange_exposed: float
    gaps: list[tuple[float, float]]  # idle intervals between busy spans, ns
    ops: dict[str, float]  # leaf op name -> seconds


@dataclasses.dataclass(frozen=True)
class Reduction:
    chips: dict[int, Chip]
    host: list[Event]
    window_s: float  # host clock, first dispatch to last fetch

    def per_chip(self, field: str) -> np.ndarray:
        return np.array([getattr(c, field) for c in self.chips.values()])

    def busy_s(self) -> float:
        return float(self.per_chip("busy").mean())

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (mean over chips) and the
        longest idle gaps, each named by the host span it fell in."""
        ops: dict[str, float] = {}
        for c in self.chips.values():
            for k, v in c.ops.items():
                ops[k] = ops.get(k, 0.0) + v / len(self.chips)
        first = self.chips[min(self.chips)]
        gaps = sorted(first.gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[self._host_at(s, e), (e - s) * 1e-9] for s, e in gaps],
        }

    def _host_at(self, start: float, end: float) -> str:
        """The host span that covers most of ``[start, end]``."""
        best, name = 0.0, "host"
        for h in self.host:
            o = min(end, h.end_ns) - max(start, h.start_ns)
            if o > best:
                best, name = o, h.name
        return name


def reduce(events: list[Event]) -> Reduction:
    """Per-chip device time of a trace (see the module docstring).

    Device clocks are shifted onto the host's so that no chunk's first op
    starts before the host span that dispatched it; idle gaps are then
    named by the host span they fall in.
    """
    host = sorted((e for e in events if e.chip < 0), key=lambda e: e.start_ns)
    dispatch = [h for h in host if h.name == HOST_PREFIX + "dispatch"]
    fetch = [h for h in host if h.name == HOST_PREFIX + "fetch"]
    if not dispatch or not fetch:
        raise ValueError("trace holds no bench.dispatch / bench.fetch host spans")
    window_s = (max(h.end_ns for h in fetch) - dispatch[0].start_ns) * 1e-9
    chips: dict[int, Chip] = {}
    for chip in sorted({e.chip for e in events if e.chip >= 0}):
        evs = [e for e in events if e.chip == chip]
        busy = union([(e.start_ns, e.end_ns) for e in evs if e.layer not in ("module", "outer")])
        shift = _shift([e for e in evs if e.layer == "module"], dispatch)
        leaf = [e for e in evs if e.layer in ("accumulation", "update", "exchange")]
        coll = union([(e.start_ns, e.end_ns) for e in leaf if e.layer == "exchange"])
        other = union([(e.start_ns, e.end_ns) for e in leaf if e.layer != "exchange"])
        ops: dict[str, float] = {}
        sums = {"accumulation": 0.0, "update": 0.0}
        for e in leaf:
            if e.layer in sums:
                sums[e.layer] += e.dur_ns * 1e-9
            ops[e.op] = ops.get(e.op, 0.0) + e.dur_ns * 1e-9
        gaps = [(a[1] + shift, b[0] + shift) for a, b in zip(busy, busy[1:])]
        chips[chip] = Chip(
            busy=length(busy) * 1e-9,
            accumulation=sums["accumulation"],
            update=sums["update"],
            exchange=length(coll) * 1e-9,
            exchange_exposed=(length(coll) - overlap(coll, other)) * 1e-9,
            gaps=gaps,
            ops=ops,
        )
    if not chips:
        raise ValueError("trace holds no device ops")
    return Reduction(chips=chips, host=host, window_s=window_s)


def _shift(modules: list[Event], dispatch: list[Event]) -> float:
    """Smallest shift that puts the start of each program execution at or
    after the start of the host span that dispatched it (in order)."""
    starts = sorted(m.start_ns for m in modules)
    return max([d.start_ns - s for s, d in zip(starts, dispatch)] + [0.0])
