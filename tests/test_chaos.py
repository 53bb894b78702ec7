"""Chaos layer: seeded fault schedules, per-layer injectors, and the
supervisor's tiered recovery ladder.

* :class:`FaultSchedule` — validation negatives, canonical trace,
  generator determinism (same seed ⇒ bit-identical event tuples,
  property-checked with or without hypothesis).
* Injector determinism — the supervisor hook's fired-event trace and
  the netsim outage records derived twice from one schedule are equal.
* ``filter_dead_rounds`` / ``apply_stragglers`` — executor and topology
  injectors preserve shape and touch only what the schedule names.
* The recovery ladder — classification, deterministic backoff jitter,
  batched evacuation, degraded mode, and the shared-config regression.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.chaos import (
    FaultEvent,
    FaultSchedule,
    apply_stragglers,
    filter_dead_rounds,
    link_outages,
    supervisor_hook,
)
from tests._hypothesis_compat import given, settings, st


class TestSchedule:
    def test_validate_negatives(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSchedule(events=(FaultEvent("meteor_strike", step=0),))
        with pytest.raises(ValueError, match="negative step"):
            FaultSchedule(events=(FaultEvent("device_crash", step=-1, device=0),))
        with pytest.raises(ValueError, match="needs a device"):
            FaultSchedule(events=(FaultEvent("device_crash", step=0),))
        with pytest.raises(ValueError, match="needs a link"):
            FaultSchedule(events=(FaultEvent("link_down", step=0),))
        with pytest.raises(ValueError, match="is empty"):
            FaultSchedule(
                events=(
                    FaultEvent("link_down", step=0, link=1, t_down=2.0, t_up=1.0),
                )
            )
        with pytest.raises(ValueError, match="slowdown"):
            FaultSchedule(
                events=(FaultEvent("straggler", step=0, device=0, slowdown=0.5),)
            )

    def test_dead_devices_fatal_only_and_upto(self):
        sched = FaultSchedule(
            events=(
                FaultEvent("device_crash", step=2, device=7, fatal=True),
                FaultEvent("device_crash", step=5, device=3, fatal=True),
                FaultEvent("device_crash", step=1, device=9, fatal=False),
            )
        )
        assert sched.dead_devices() == (3, 7)
        assert sched.dead_devices(upto_step=2) == (7,)
        assert sched.dead_devices(upto_step=0) == ()

    @given(seed=st.integers(0, 50))
    @settings(max_examples=25, deadline=None)
    def test_generate_deterministic(self, seed):
        kw = dict(n_devices=32, n_steps=10, n_links=64)
        a = FaultSchedule.generate(seed, **kw)
        b = FaultSchedule.generate(seed, **kw)
        assert a.trace() == b.trace()
        assert len(a.crashes()) == 2
        assert len(a.outages()) == 1
        assert len(a.stragglers()) == 1
        # crash/straggler targets drawn without replacement
        targets = [e.device for e in a.crashes() + a.stragglers()]
        assert len(set(targets)) == len(targets)

    def test_generate_seeds_decorrelate(self):
        kw = dict(n_devices=256, n_steps=50, n_links=64)
        traces = {FaultSchedule.generate(s, **kw).trace() for s in range(8)}
        assert len(traces) > 1


class TestInjectorDeterminism:
    @given(seed=st.integers(0, 20))
    @settings(max_examples=10, deadline=None)
    def test_same_schedule_same_injected_trace(self, seed):
        """One schedule, two independent derivations of every injector:
        the supervisor hook's fired trace and the netsim outage records
        must be identical — the layers cannot drift apart."""
        sched = FaultSchedule.generate(
            seed, n_devices=16, n_steps=8, n_links=32
        )
        traces = []
        for _ in range(2):
            hook = supervisor_hook(sched)
            for step in range(8):
                try:
                    hook(step)
                except Exception:
                    pass
            traces.append(tuple(hook.trace))
        assert traces[0] == traces[1]
        assert link_outages(sched) == link_outages(sched)
        # every injected event is in the schedule's canonical trace
        assert set(traces[0]) <= set(sched.trace())

    def test_hook_batches_same_step_crashes_and_fires_once(self):
        from repro.train.fault_tolerance import DeviceFailure

        sched = FaultSchedule(
            events=(
                FaultEvent("device_crash", step=2, device=4, fatal=True),
                FaultEvent("device_crash", step=2, device=6, fatal=False),
                FaultEvent("device_crash", step=5, device=1, fatal=False),
            )
        )
        hook = supervisor_hook(sched)
        hook(0)
        with pytest.raises(DeviceFailure) as ei:
            hook(2)
        assert ei.value.devices == (4, 6)
        assert ei.value.fatal  # any fatal in the batch ⇒ fatal
        hook(2)  # the retry after recovery proceeds
        with pytest.raises(DeviceFailure) as ei:
            hook(5)
        assert ei.value.devices == (1,) and not ei.value.fatal
        hook(5)


class TestExecutorAndTopologyInjectors:
    def test_filter_dead_rounds_drops_only_dead(self):
        from repro.netsim.events import Message

        rounds = [
            [Message(0, 1, 10), Message(2, 3, 10), Message(1, 2, 10)],
            [],
            [Message(3, 0, 10)],
        ]
        out = filter_dead_rounds(rounds, dead=[2])
        assert [len(r) for r in out] == [1, 0, 1]  # boundaries preserved
        assert all(m.src != 2 and m.dst != 2 for rnd in out for m in rnd)
        # no dead devices: structural copy
        same = filter_dead_rounds(rounds, dead=[])
        assert [len(r) for r in same] == [3, 0, 1]

    def test_apply_stragglers_slows_only_egress(self):
        from repro import netsim

        topo = netsim.fat_tree(16, 4)
        sched = FaultSchedule(
            events=(FaultEvent("straggler", step=0, device=5, slowdown=3.0),)
        )
        slow = apply_stragglers(topo, sched)
        assert slow.n_devices == topo.n_devices
        assert "+stragglers" in slow.name
        egress = set(topo.device_egress_links()[5])
        for i, (a, b) in enumerate(zip(topo.links, slow.links)):
            if i in egress:
                assert b.alpha == a.alpha * 3.0 and b.beta == a.beta * 3.0
            else:
                assert b.alpha == a.alpha and b.beta == a.beta
        # no stragglers: the very same object comes back
        empty = FaultSchedule(events=())
        assert apply_stragglers(topo, empty) is topo

    def test_straggler_outside_topology_rejected(self):
        from repro import netsim

        sched = FaultSchedule(
            events=(FaultEvent("straggler", step=0, device=99, slowdown=2.0),)
        )
        with pytest.raises(ValueError, match="outside topology"):
            apply_stragglers(netsim.single_switch(4), sched)


class TestRecoveryLadder:
    @staticmethod
    def _train_step(params, opt, batch):
        return float(batch), params, opt, None

    def test_fatal_crash_climbs_to_batched_evacuation(self, tmp_path):
        from repro.train.fault_tolerance import Supervisor, SupervisorConfig

        sched = FaultSchedule(
            events=(
                FaultEvent("device_crash", step=3, device=5, fatal=True),
                FaultEvent("device_crash", step=3, device=9, fatal=True),
            )
        )
        evac_calls = []
        slept = []
        sup = Supervisor(
            self._train_step,
            {"w": np.zeros(2)},
            {},
            lambda s: np.float64(s),
            SupervisorConfig(
                ckpt_dir=str(tmp_path), ckpt_every=2, backoff_base_s=0.01
            ),
            failure_hook=supervisor_hook(sched),
            evacuate_hook=lambda ds: evac_calls.append(ds) or True,
            sleep=slept.append,
        )
        hist = sup.run(6)
        assert sup.dead == [5, 9]
        assert evac_calls == [(5, 9)]  # one batched call, not two
        assert len(slept) == 1 and slept[0] > 0
        assert not sup.degraded
        assert any(h.restarted for h in hist) and hist[-1].step == 6

    def test_transient_crash_stops_at_rollback(self, tmp_path):
        from repro.train.fault_tolerance import Supervisor, SupervisorConfig

        sched = FaultSchedule(
            events=(FaultEvent("device_crash", step=2, device=3, fatal=False),)
        )
        evac_calls = []
        sup = Supervisor(
            self._train_step,
            {"w": np.zeros(2)},
            {},
            lambda s: np.float64(s),
            SupervisorConfig(ckpt_dir=str(tmp_path), ckpt_every=2),
            failure_hook=supervisor_hook(sched),
            evacuate_hook=lambda ds: evac_calls.append(ds) or True,
        )
        hist = sup.run(4)
        assert evac_calls == [] and sup.dead == []
        assert any(h.restarted for h in hist)

    def test_degraded_mode_when_group_cannot_absorb(self, tmp_path):
        from repro.train.fault_tolerance import Supervisor, SupervisorConfig

        sched = FaultSchedule(
            events=(FaultEvent("device_crash", step=1, device=2, fatal=True),)
        )
        sup = Supervisor(
            self._train_step,
            {"w": np.zeros(2)},
            {},
            lambda s: np.float64(s),
            SupervisorConfig(ckpt_dir=str(tmp_path), ckpt_every=2),
            failure_hook=supervisor_hook(sched),
            evacuate_hook=lambda devs: False,
        )
        hist = sup.run(3)
        assert sup.degraded
        assert hist[-1].degraded

    def test_degraded_disallowed_reraises(self, tmp_path):
        from repro.train.fault_tolerance import (
            DeviceFailure,
            Supervisor,
            SupervisorConfig,
        )

        sched = FaultSchedule(
            events=(FaultEvent("device_crash", step=1, device=2, fatal=True),)
        )
        sup = Supervisor(
            self._train_step,
            {"w": np.zeros(2)},
            {},
            lambda s: np.float64(s),
            SupervisorConfig(
                ckpt_dir=str(tmp_path), ckpt_every=2, allow_degraded=False
            ),
            failure_hook=supervisor_hook(sched),
            evacuate_hook=lambda devs: False,
        )
        with pytest.raises(DeviceFailure):
            sup.run(3)

    def test_classify_failure(self):
        from repro.train.fault_tolerance import DeviceFailure, classify_failure

        assert classify_failure(DeviceFailure(3)) == "fatal"
        assert classify_failure(DeviceFailure(3, fatal=False)) == "transient"
        assert classify_failure(FloatingPointError("nan loss")) == "transient"
        assert classify_failure(RuntimeError("preempted")) == "transient"

    @given(step=st.integers(0, 100), attempt=st.integers(0, 6))
    @settings(max_examples=20, deadline=None)
    def test_backoff_deterministic_bounded(self, step, attempt):
        from repro.train.fault_tolerance import SupervisorConfig, backoff_delay

        cfg = SupervisorConfig(backoff_base_s=0.5, seed=7)
        a = backoff_delay(cfg, step, attempt)
        assert a == backoff_delay(cfg, step, attempt)  # bit-reproducible
        assert 0.0 < a <= cfg.backoff_max_s
        lo = cfg.backoff_base_s * cfg.backoff_factor**attempt
        assert a <= min(
            lo * (1 + cfg.backoff_jitter), cfg.backoff_max_s
        ) and a >= min(lo * (1 - cfg.backoff_jitter), cfg.backoff_max_s)
        # distinct seeds decorrelate (no thundering herd)
        other = backoff_delay(
            SupervisorConfig(backoff_base_s=0.5, seed=8), step, attempt
        )
        if a < cfg.backoff_max_s and other < cfg.backoff_max_s:
            assert a != other

    def test_backoff_disabled_by_default(self):
        from repro.train.fault_tolerance import SupervisorConfig, backoff_delay

        assert backoff_delay(SupervisorConfig(), 3, 2) == 0.0

    def test_supervisor_cfg_default_not_shared(self):
        """Regression: the default config must be constructed per
        instance — a ``cfg=SupervisorConfig()`` default argument was one
        shared mutable object across every supervisor in the process."""
        from repro.train.fault_tolerance import Supervisor

        a = Supervisor(self._train_step, {}, {}, lambda s: 0.0)
        b = Supervisor(self._train_step, {}, {}, lambda s: 0.0)
        assert a.cfg is not b.cfg
        a.cfg.ckpt_every = 999
        assert b.cfg.ckpt_every != 999
