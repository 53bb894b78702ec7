"""Device time by the program's named scopes, from a ``jax.profiler`` trace.

The compiled step names its parts with ``jax.named_scope``
(``repro.snn.distributed.STEP_SCOPES``: level-1 gather, level-2 pack,
send and unpack, accumulation, neuron update).  The names reach the
``op_name`` metadata of the executable's HLO text, not the trace: a
trace names an op only by its HLO name (``fusion.35``).  So:

* :func:`scope_map` reads the HLO text of the step and gives each op
  the deepest scope its ``op_name`` path contains, or ``unscoped``;
* :func:`check_fresh` fails unless every op of the trace is in that
  text.  JAX's persistent cache leaves metadata out of its key, so an
  entry compiled from code without the scopes is reused and its text
  has none: the map has to come from a compile with that cache off
  (:func:`fresh_hlo_text`), and the check makes sure it describes the
  program that ran;
* :func:`reduce` gives, per chip, each scope's device time (the union of
  its leaf ops' intervals, leaf ops as in ``trace.py``), the leaf time
  of all ops, and the idle time inside program executions (``XLA
  Modules``), each stretch of it named by the scope of the op that
  ends it.

``save`` and ``load`` keep a trace with its scopes: ``trace.py``'s five
columns and a sixth, the op's scope (``None`` for host spans); a file of
five columns loads with no scopes.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import gzip
import json
import re
from pathlib import Path

from bench import trace as tr

UNSCOPED = "unscoped"
END = "end of program"  # idle after a program's last op
LEAF = ("accumulation", "update", "exchange")  # trace.Event.layer of leaf ops
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def step_scopes() -> tuple[str, ...]:
    """The scopes the program's step declares; none where it has none."""
    from repro.snn import distributed

    return tuple(getattr(distributed, "STEP_SCOPES", ()))


def scope_of(op_name: str, scopes: tuple[str, ...]) -> str:
    """The scope of ``scopes`` that ends deepest in the ``/``-separated
    path ``op_name``, or ``unscoped``."""
    path = "/" + op_name + "/"
    best, end = UNSCOPED, -1
    for s in scopes:
        i = path.rfind("/" + s + "/")
        if i >= 0 and i + len(s) > end:
            best, end = s, i + len(s)
    return best


def scope_map(hlo_text: str, scopes: tuple[str, ...]) -> dict[str, str]:
    """Instruction name -> scope, for every instruction of an HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if m:
            meta = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(meta.group(1) if meta else "", scopes)
    return out


class StaleExecutable(RuntimeError):
    """The traced program is not the one whose HLO text gave the map."""


def check_fresh(events: list[tr.Event], smap: dict[str, str]) -> None:
    """Raise :class:`StaleExecutable` unless every leaf op of the trace is
    an instruction of the text ``smap`` was read from."""
    missing = sorted({e.op for e in events if e.chip >= 0 and e.layer in LEAF} - smap.keys())
    if missing:
        raise StaleExecutable(
            f"{len(missing)} traced op(s) are not in the HLO text of the step compiled "
            f"for the scope map, e.g. {missing[:5]}: the traced executable came from "
            "other code (JAX's persistent cache leaves metadata out of its key, so an "
            "entry compiled before the scopes existed is reused)"
        )


@contextlib.contextmanager
def cache_off():
    """Compile with JAX's persistent cache off and its in-memory caches
    emptied, so that a compile reads nothing cached."""
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def fresh_hlo_text(engine, n_steps: int, key) -> str:
    """HLO text of ``engine``'s ``n_steps`` step, compiled anew (this
    stages the inputs again: call it after what it must not disturb)."""
    with cache_off():
        compiled, args, _ = engine.compile(n_steps, key=key)
        text = compiled.as_text()
    del compiled, args
    return text


@dataclasses.dataclass(frozen=True)
class Chip:
    """One chip's device time by scope in the traced window, seconds."""

    scopes: dict[str, float]  # scope -> union of its leaf ops' intervals
    leaf: float  # union of all leaf ops' intervals
    step_idle: float  # inside program executions, no leaf op or copy running
    idle_by_scope: dict[str, float]  # step_idle by the scope of the op ending it


def reduce(events: list[tr.Event], smap: dict[str, str]) -> dict[int, Chip]:
    """Per-chip scope times and in-step idle (see the module docstring)."""
    chips = {}
    for chip in sorted({e.chip for e in events if e.chip >= 0}):
        evs = [e for e in events if e.chip == chip]
        leaf = [e for e in evs if e.layer in LEAF]
        by_scope: dict[str, list[tuple[float, float]]] = {}
        for e in leaf:
            by_scope.setdefault(smap.get(e.op, UNSCOPED), []).append((e.start_ns, e.end_ns))
        # busy as trace.reduce has it: leaf ops and whatever is in flight
        running = sorted((e for e in evs if e.layer not in ("module", "outer")),
                         key=lambda e: e.start_ns)
        busy = tr.union([(e.start_ns, e.end_ns) for e in running])
        modules = tr.union([(e.start_ns, e.end_ns) for e in evs if e.layer == "module"])
        starts = [e.start_ns for e in running]
        idle_by: dict[str, float] = {}
        for lo, hi, tail in _idle_within(modules, busy):
            # the op that starts where the stretch ends
            name = END if tail else smap.get(running[bisect.bisect_left(starts, hi)].op, UNSCOPED)
            idle_by[name] = idle_by.get(name, 0.0) + (hi - lo) * 1e-9
        chips[chip] = Chip(
            scopes={s: tr.length(tr.union(iv)) * 1e-9 for s, iv in by_scope.items()},
            leaf=tr.length(tr.union([(e.start_ns, e.end_ns) for e in leaf])) * 1e-9,
            step_idle=(tr.length(modules) - tr.overlap(modules, busy)) * 1e-9,
            idle_by_scope=idle_by,
        )
    return chips


def _idle_within(modules, busy):
    """The stretches ``(start, end, tail)`` of the ``modules`` union that
    the disjoint sorted ``busy`` leaves free; ``tail`` where a stretch
    ends with its program."""
    out = []
    ends = [e for _, e in busy]
    for lo, hi in modules:
        t = lo
        for s, e in busy[bisect.bisect_right(ends, lo):]:
            if s >= hi:
                break
            if s > t:
                out.append((t, s, False))
            t = max(t, e)
        if t < hi:
            out.append((t, hi, True))
    return out


def per_step_ms(chips: dict[int, Chip], scope: str, steps: int) -> float | None:
    """A scope's device time a step on the slowest chip, ms; ``None``
    where no chip ran an op of the scope."""
    times = [c.scopes[scope] for c in chips.values() if scope in c.scopes]
    return max(times) / steps * 1e3 if times else None


def step_idle_ms(chips: dict[int, Chip], steps: int) -> float | None:
    """In-step idle a step on the idlest chip, ms."""
    if not chips:
        return None
    return max(c.step_idle for c in chips.values()) / steps * 1e3


def idle_by_scope(chips: dict[int, Chip]) -> dict[str, float]:
    """In-step idle by the scope of the op that ends each stretch,
    seconds, mean over chips (the breakdown's unit)."""
    out: dict[str, float] = {}
    for c in chips.values():
        for k, v in c.idle_by_scope.items():
            out[k] = out.get(k, 0.0) + v / len(chips)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def save(events: list[tr.Event], smap: dict[str, str], path: str | Path) -> None:
    rows = [[e.chip, e.line, e.name, e.start_ns, e.dur_ns,
             smap.get(e.op, UNSCOPED) if e.chip >= 0 else None] for e in events]
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load(path: str | Path) -> tuple[list[tr.Event], dict[str, str]]:
    """Events and the op -> scope map of a saved trace (an empty map for
    a file of five columns)."""
    with gzip.open(path, "rt") as f:
        rows = json.load(f)
    events = [tr.Event(*row[:5]) for row in rows]
    smap = {e.op: row[5] for e, row in zip(events, rows) if len(row) > 5 and row[5] is not None}
    return events, smap
