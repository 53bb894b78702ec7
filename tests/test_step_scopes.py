"""The compiled step's named scopes and the tracer on the profiler's clock.

The scopes of ``STEP_SCOPES`` have to reach the optimized HLO's
``op_name`` metadata, each over the ops of its part of the step; the
tracer's spans have to land in a ``jax.profiler`` trace while it is on,
and cost one branch while it is off.
"""
from __future__ import annotations

import glob
import re

import jax
import pytest

from repro import obs
from repro.obs import trace as obs_trace
from repro.snn.distributed import STEP_SCOPES
from tests.conftest import run_devices

SETUP_SPANS = ["snn.from_tiles", "snn.engine_init", "snn.stage", "snn.lower", "snn.compile"]


@pytest.fixture(autouse=True)
def _global_tracer_off():
    yield
    obs.disable()
    obs.clear()


def op_names(hlo_text: str) -> list[tuple[str, str]]:
    """(HLO kind, op_name) of each instruction of an HLO text that has
    metadata."""
    return re.findall(r'^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(.*?op_name="([^"]*)"',
                      hlo_text, re.M)


def under(op_name: str, scope: str) -> bool:
    return f"/{scope}/" in f"/{op_name}/"


def test_scope_names_are_distinct_paths():
    assert len(set(STEP_SCOPES)) == len(STEP_SCOPES) == 6
    assert [s for s in STEP_SCOPES if s.startswith("exchange/level2/")] == [
        "exchange/level2/pack", "exchange/level2/send", "exchange/level2/unpack"]


def test_scopes_in_ragged_step_on_four_devices():
    """2×2 mesh, ragged exchange: every scope is in the optimized HLO, the
    collectives sit under their level and the accumulation under
    ``accumulate``."""
    code = """
import json, re
from repro.launch import run_brainsim
eng = run_brainsim.build_engine(32, 4, noise=1.0)
assert eng.exchange == "ragged"
compiled, _, _ = eng.compile(8)
print(json.dumps(compiled.as_text()))
"""
    import json

    names = op_names(json.loads(run_devices(code, n_devices=4).strip().splitlines()[-1]))
    for scope in STEP_SCOPES:
        assert any(under(p, scope) for _, p in names), scope
    kinds = {
        "collective-permute": "exchange/level2/send",
        "all-reduce": "exchange/level2/send",
        "all-gather": "exchange/level1",
        "dot": "accumulate",
    }
    for kind, scope in kinds.items():
        paths = [p for k, p in names if k.startswith(kind)]
        assert paths, kind
        assert all(under(p, scope) for p in paths), paths


def test_scopes_in_sparse_step_on_one_device():
    """One device has no exchange to speak of: the accumulation and the
    neuron update are scoped, no op claims level 1 or a send."""
    from repro.launch import run_brainsim

    eng = run_brainsim.build_engine(16, 4, noise=1.0)
    compiled, _, _ = eng.compile(8)
    names = op_names(compiled.as_text())
    dots = [p for k, p in names if k == "dot"]
    assert dots and all(under(p, "accumulate") for p in dots)
    assert any(under(p, "neuron") for _, p in names)
    assert not any(under(p, s) for _, p in names
                   for s in ("exchange/level1", "exchange/level2/send"))


def test_disabled_span_opens_no_annotation(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    assert obs.span("snn.stage") is obs_trace._NOOP
    with obs.span("snn.stage"):
        pass
    assert opened == []
    obs.enable()
    with obs.span("snn.stage"):
        with obs.span("snn.inner"):
            pass
    assert opened == [("enter", "snn.stage"), ("enter", "snn.inner"),
                      ("exit", "snn.inner"), ("exit", "snn.stage")]
    assert [e["name"] for e in obs.events()] == ["snn.inner", "snn.stage"]


def test_setup_spans_and_exchange_bytes_counter():
    """With the tracer on, building and compiling record the set-up spans
    in order and the slow-axis bytes the compiled plan moves."""
    from repro.launch import run_brainsim

    obs.enable()
    eng = run_brainsim.build_engine(16, 4)
    eng.compile(4)
    names = [e["name"] for e in obs.events() if e["ph"] == "X" and e["name"].startswith("snn.")]
    assert names == SETUP_SPANS
    (counter,) = [e for e in obs.events() if e["ph"] == "C"]
    assert counter["name"] == "snn.exchange_bytes"
    assert counter["args"] == {"level2": float(eng.exchange_stats()[eng.exchange])}


def test_level2_rounds_counter_on_four_devices():
    """On a 2×2 mesh the compile also records the ragged plan's live
    rounds, next to the bytes they move: one masked ``R·B``-lane group
    block a scheduled pair, more than the plan's packed payloads."""
    code = """
import json
from repro import obs
from repro.launch import run_brainsim
eng = run_brainsim.build_engine(32, 4)
obs.enable()
eng.compile(4)
plan = eng._ragged_plan()
counters = {e["name"]: e["args"] for e in obs.events() if e["ph"] == "C"}
print(json.dumps([counters, plan.bytes_per_step, 2 * 2 * eng.syn.block_size * 4]))
"""
    import json

    line = run_devices(code, n_devices=4).strip().splitlines()[-1]
    counters, packed, two_blocks = json.loads(line)
    assert list(counters) == ["snn.exchange_bytes", "snn.level2_rounds"]
    assert counters["snn.level2_rounds"] == {"block": 1.0}
    assert counters["snn.exchange_bytes"] == {"level2": float(two_blocks)}
    assert packed < two_blocks


def test_spans_land_in_the_profile(tmp_path):
    """Under ``jax.profiler.trace`` the tracer's spans are on the profile's
    host plane, on the same clock as the rest of the profile."""
    from jax.profiler import ProfileData

    from repro.launch import run_brainsim

    eng = run_brainsim.build_engine(16, 4)
    obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        compiled, args, _ = eng.compile(4)
        jax.block_until_ready(compiled(*args))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("snn."):
                        spans[e.name] = (e.start_ns, e.duration_ns)
    assert set(spans) == {"snn.stage", "snn.lower", "snn.compile"}
    stage, lower, comp = (spans[k] for k in ("snn.stage", "snn.lower", "snn.compile"))
    assert stage[0] + stage[1] <= lower[0] and lower[0] + lower[1] <= comp[0]
    tracer = {e["name"]: e["dur"] * 1e3 for e in obs.events() if e["ph"] == "X"}
    assert spans["snn.compile"][1] == pytest.approx(tracer["snn.compile"], rel=0.2)


def test_launcher_trace_dir(tmp_path):
    """``run_brainsim --trace DIR`` writes one profile that holds the
    launcher's and the executor's spans; the tracer is off after it."""
    from jax.profiler import ProfileData

    from repro.launch import run_brainsim

    run_brainsim.main(["--populations", "16", "--neurons-per-pop", "4",
                       "--steps", "30", "--trace", str(tmp_path)])
    assert not obs.is_enabled()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes if p.name.startswith("/host")
             for line in p.lines for e in line.events}
    assert {"launch.build", "launch.partition", "launch.run"} <= names
    assert set(SETUP_SPANS) <= names
