"""The benchmark's reading of a trace by the step's named scopes
(``bench/scopes.py``), on made-up HLO text and a made-up trace whose
numbers are worked out by hand, and on a real compile whose persistent
cache holds an entry without the scopes."""
from __future__ import annotations

import pytest

from bench import scopes as sc
from bench import trace as tr
from repro.snn.distributed import STEP_SCOPES
from tests.conftest import run_devices

HLO = """
ENTRY %main {
  %fusion.35 = f32[2,257]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(_run)/shard_map/while/body/closed_call/exchange/level2/unpack/scatter-add" stack_frame_id=11}
  %fusion.34 = f32[181]{0} fusion(%b), kind=kCustom, metadata={op_name="jit(_run)/shard_map/while/body/closed_call/exchange/level2/pack/gather"}
  %psum.10 = f32[181]{0} all-reduce(%c), channel_id=1, metadata={op_name="jit(_run)/shard_map/while/body/closed_call/exchange/level2/send/psum"}
  %spike_accum_blocks.5 = f32[1,128]{1,0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(_run)/shard_map/while/body/closed_call/accumulate/jit(spike_accum_blocks)/pallas_call"}
  ROOT %fusion.41 = f32[128]{0} fusion(%e), kind=kLoop, metadata={op_name="jit(_run)/shard_map/while/body/closed_call/neuron/lif_step/add"}
  %dynamic_update_slice.11 = f32[20,128]{1,0} dynamic-update-slice(%f, %g), metadata={op_name="jit(_run)/shard_map/while/body/closed_call/dynamic_update_slice"}
  %copy-start.4 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%h)
}
"""

KERNEL = '%spike_accum_blocks.5 = f32[1,128]{1,0} custom-call(%d), custom_call_target="tpu_custom_call"'
UNPACK = "%fusion.35 = f32[2,257]{1,0} fusion(%a), kind=kLoop"
PACK = "%fusion.34 = f32[181]{0} fusion(%b), kind=kCustom"
PSUM = "%psum.10 = f32[181]{0} all-reduce(%c), channel_id=1"
NEURON = "%fusion.41 = f32[128]{0} fusion(%e), kind=kLoop"
RASTER = "%dynamic_update_slice.11 = f32[20,128]{1,0} dynamic-update-slice(%f, %g)"
COPY = "%copy-start.4 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%h)"


def made_up() -> list[tr.Event]:
    """One chip, two program executions of 200 ns.  In each: a copy in
    flight over [0, 20], the kernel [10, 60], idle [60, 70], pack [70, 80],
    the psum [80, 100] with unpack overlapping it [90, 110], idle [110,
    120], neuron [120, 150], raster [150, 160], idle to the end [160, 200]."""
    ev = [tr.Event(-1, "python3", "bench.dispatch", 0, 10),
          tr.Event(-1, "python3", "bench.fetch", 10, 500)]
    for base in (0, 300):
        ev += [
            tr.Event(0, "XLA Modules", "jit__run(1)", base, 200),
            tr.Event(0, "Async XLA Ops", COPY, base, 20),
            tr.Event(0, "XLA Ops", KERNEL, base + 10, 50),
            tr.Event(0, "XLA Ops", PACK, base + 70, 10),
            tr.Event(0, "XLA Ops", PSUM, base + 80, 20),
            tr.Event(0, "XLA Ops", UNPACK, base + 90, 20),
            tr.Event(0, "XLA Ops", NEURON, base + 120, 30),
            tr.Event(0, "XLA Ops", RASTER, base + 150, 10),
        ]
    return ev


def test_scope_of_takes_the_deepest_scope():
    scopes = ("exchange", "exchange/level2/pack", "neuron")
    assert sc.scope_of("jit(_run)/while/body/exchange/level2/pack/gather", scopes) == \
        "exchange/level2/pack"
    assert sc.scope_of("jit(_run)/while/body/exchange/all_gather", scopes) == "exchange"
    assert sc.scope_of("jit(_run)/neuron/exchange/x", scopes) == "exchange"
    # a name that merely starts like a scope is not in it
    assert sc.scope_of("jit(_run)/neurons/add", scopes) == sc.UNSCOPED
    assert sc.scope_of("", scopes) == sc.UNSCOPED


def test_scope_map_from_hlo_text():
    smap = sc.scope_map(HLO, STEP_SCOPES)
    assert smap["fusion.35"] == "exchange/level2/unpack"
    assert smap["fusion.34"] == "exchange/level2/pack"
    assert smap["psum.10"] == "exchange/level2/send"
    assert smap["spike_accum_blocks.5"] == "accumulate"
    assert smap["fusion.41"] == "neuron"
    # ops outside every scope, or without metadata, are unscoped
    assert smap["dynamic_update_slice.11"] == sc.UNSCOPED
    assert smap["copy-start.4"] == sc.UNSCOPED


def test_made_up_trace_by_scope():
    smap = sc.scope_map(HLO, STEP_SCOPES)
    (chip,) = sc.reduce(made_up(), smap).values()
    ns = 1e-9
    assert chip.scopes == pytest.approx({
        "accumulate": 100 * ns, "exchange/level2/pack": 20 * ns,
        "exchange/level2/send": 40 * ns, "exchange/level2/unpack": 40 * ns,
        "neuron": 60 * ns, sc.UNSCOPED: 20 * ns,
    })
    # leaf ops [10, 60], [70, 110] and [120, 160]; the copy is busy time,
    # no leaf op
    assert chip.leaf == pytest.approx(2 * 130 * ns)
    # idle inside each program: [60, 70], [110, 120] and [160, 200]
    assert chip.step_idle == pytest.approx(2 * 60 * ns)
    assert chip.idle_by_scope == pytest.approx({
        "exchange/level2/pack": 20 * ns, "neuron": 20 * ns, sc.END: 80 * ns})
    chips = {0: chip}
    assert sc.per_step_ms(chips, "neuron", 2) == pytest.approx(30e-6)
    assert sc.step_idle_ms(chips, 2) == pytest.approx(60e-6)
    # busy [0, 60], [70, 110] and [120, 160]; the 140 ns between the two
    # programs are idle, but not in-step idle
    red = tr.reduce(made_up())
    assert red.chips[0].busy == pytest.approx(2 * 140 * ns)


def test_reader_is_none_without_scopes():
    """A trace of a program without scopes: every op is unscoped, so no
    scope's reading exists (``None``, never 0)."""
    chips = sc.reduce(made_up(), sc.scope_map(HLO, ()))
    assert set(chips[0].scopes) == {sc.UNSCOPED}
    for scope in STEP_SCOPES:
        assert sc.per_step_ms(chips, scope, 2) is None
    assert sc.per_step_ms(sc.reduce(made_up(), {}), "neuron", 2) is None


def test_stale_check_fails_on_an_op_missing_from_the_text():
    smap = sc.scope_map(HLO, STEP_SCOPES)
    sc.check_fresh(made_up(), smap)
    stale = made_up() + [tr.Event(0, "XLA Ops", "%fusion.99 = f32[8]{0} fusion(%z), kind=kLoop",
                                  400, 5)]
    with pytest.raises(sc.StaleExecutable, match=r"fusion\.99.*persistent cache"):
        sc.check_fresh(stale, smap)


def test_save_and_load_six_and_five_columns(tmp_path):
    smap = sc.scope_map(HLO, STEP_SCOPES)
    events = made_up()
    sc.save(events, smap, tmp_path / "six.json.gz")
    back, back_map = sc.load(tmp_path / "six.json.gz")
    assert back == events
    assert back_map == {e.op: smap.get(e.op, sc.UNSCOPED) for e in events if e.chip >= 0}
    tr.save(events, tmp_path / "five.json.gz")
    back, back_map = sc.load(tmp_path / "five.json.gz")
    assert back == events and back_map == {}


def test_fresh_compile_reads_no_stale_cache_entry(tmp_path):
    """A persistent-cache entry compiled without the scopes is reused by a
    normal compile (metadata is not in the key), and its text has no
    scopes; ``fresh_hlo_text`` compiles anew and has them all."""
    code = f"""
import contextlib, jax
from bench import scopes as sc
from repro.launch import run_brainsim
from repro.snn import distributed
jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
eng = run_brainsim.build_engine(32, 4, noise=1.0)
named = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
try:  # the program as it was before it had scopes, into the cache
    eng.compile(8)
finally:
    jax.named_scope = named
distributed._sparse_step.cache_clear()
jax.clear_caches()
stale = sc.scope_map(eng.compile(8)[0].as_text(), distributed.STEP_SCOPES)
fresh = sc.scope_map(sc.fresh_hlo_text(eng, 8, jax.random.PRNGKey(0)), distributed.STEP_SCOPES)
assert set(stale.values()) == {{sc.UNSCOPED}}, set(stale.values())
assert set(fresh.values()) == set(distributed.STEP_SCOPES) | {{sc.UNSCOPED}}
print("OK", len(stale), len(fresh))
"""
    assert "OK" in run_devices(code, n_devices=4)
