"""AutoscaleController + CostModel: the decision matrix, hysteresis, rescind
handling, audit events with predicted AND realized deltas, actuation routing
through the remediation engine, and the /autoscale status document."""

import json
import time

import pytest

from tpu_resiliency.launcher.autoscale import (
    ACTION_CHECKPOINT,
    ACTION_EXCLUDE,
    ACTION_EXPAND,
    ACTION_NOOP,
    ACTION_SHRINK,
    ACTION_SWAP,
    AutoscaleController,
    ControllerView,
    CostModel,
    Notice,
)
from tpu_resiliency.telemetry.policy import HealthDecision
from tpu_resiliency.telemetry.remediation import RemediationEngine
from tpu_resiliency.utils import events


@pytest.fixture
def seen():
    captured = []
    events.add_sink(captured.append)
    yield captured
    events.remove_sink(captured.append)


def view(
    now=100.0, world=4, target=4, stragglers=None, spares=0, notices=(),
    step_s=0.02, steps_since_ckpt=50,
):
    return ControllerView(
        now=now, world_size=world, target_world=target,
        stragglers=dict(stragglers or {}), spares=spares,
        notices=list(notices), step_s=step_s,
        steps_since_ckpt=steps_since_ckpt,
    )


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def controller(mode="advise", clock=None, **kw):
    kw.setdefault("cost_model", CostModel(horizon_s=10.0))
    kw.setdefault("rescind_grace_s", 5.0)
    kw.setdefault("dwell_s", 2.0)
    kw.setdefault("decision_cooldown_s", 30.0)
    ctl = AutoscaleController(
        mode=mode, now_fn=clock or FakeClock(), **kw
    )
    return ctl


# -- the cost model ----------------------------------------------------------


class TestCostModel:
    def test_swap_beats_noop_under_a_straggler(self):
        m = CostModel(horizon_s=10.0, warm_restart_s=0.05)
        v = view(stragglers={2: 0.4}, spares=1)
        assert m.estimate(ACTION_SWAP, v) == pytest.approx(
            0.6 * 10.0 - 0.05
        )
        assert m.estimate(ACTION_NOOP, v) == 0.0

    def test_exclude_prices_the_capacity_loss(self):
        m = CostModel(horizon_s=10.0, reshard_s=0.1)
        v = view(stragglers={2: 0.4}, spares=0, world=4)
        # slow_frac 0.6 minus 1/4 capacity loss, times horizon, minus reshard
        assert m.estimate(ACTION_EXCLUDE, v) == pytest.approx(
            (0.6 - 0.25) * 10.0 - 0.1
        )

    def test_checkpoint_prices_unbanked_progress(self):
        m = CostModel(horizon_s=10.0, ckpt_s=0.2, p_preempt=0.5)
        n = Notice(key="r1", rank=1, noticed_at=99.0)
        v = view(notices=[n], step_s=0.1, steps_since_ckpt=20)
        assert m.estimate(ACTION_CHECKPOINT, v) == pytest.approx(
            0.5 * 2.0 - 0.2
        )
        # No notice pending: a proactive save is pure cost.
        assert m.estimate(ACTION_CHECKPOINT, view()) < 0

    def test_shrink_and_expand_signs(self):
        m = CostModel(horizon_s=10.0, cold_restart_s=1.0,
                      preempt_block_s=4.0, reshard_s=0.1)
        n = Notice(key="r1", rank=1, noticed_at=90.0)
        assert m.estimate(ACTION_SHRINK, view(notices=[n])) > 0
        grow = m.estimate(ACTION_EXPAND, view(world=3, target=4, spares=1))
        assert grow == pytest.approx(10.0 / 4 - 0.1)
        assert m.estimate(ACTION_EXPAND, view(world=4, target=4)) < 0

    def test_unknown_action_raises(self):
        with pytest.raises(ValueError):
            CostModel().estimate("teleport", view())

    def test_note_outcome_refines_and_clamps(self):
        m = CostModel(ewma_alpha=1.0)
        m.note_outcome(ACTION_SWAP, predicted=10.0, realized=5.0)
        assert m.corrections[ACTION_SWAP] == pytest.approx(0.5)
        v = view(stragglers={1: 0.0}, spares=1)
        # The correction halves the optimistic straggler term.
        assert m.estimate(ACTION_SWAP, v) == pytest.approx(
            1.0 * m.horizon_s * 0.5 - m.warm_restart_s
        )
        m.note_outcome(ACTION_SWAP, predicted=1.0, realized=-100.0)
        assert m.corrections[ACTION_SWAP] >= 0.25  # clamped, never zero/negative

    def test_reshard_price_moves_the_shrink_delta_one_for_one(self):
        """The shrink delta charges ``reshard_s`` once: a model priced with
        plan + fetch (0.04) instead of the serial wall time (0.25) raises the
        delta by exactly the difference."""
        v = view()
        priced_old = CostModel(reshard_s=0.25).estimate(ACTION_SHRINK, v)
        priced_new = CostModel(reshard_s=0.04).estimate(ACTION_SHRINK, v)
        assert priced_new > priced_old
        assert priced_new - priced_old == pytest.approx(0.25 - 0.04)

    def test_there_is_no_from_bench(self):
        """The constants come from the constructor alone: nothing on
        ``CostModel`` reads a record from a directory."""
        assert not hasattr(CostModel, "from_bench")
        priors = {"horizon_s": 60.0, "warm_restart_s": 0.06, "cold_restart_s": 0.75,
                  "reshard_s": 0.15, "ckpt_s": 0.10}
        constants = CostModel().constants()
        assert {k: constants[k] for k in priors} == priors

    def test_the_agent_prices_one_way_wherever_it_is_started(
        self, tmp_path, monkeypatch, coord_store
    ):
        """A launcher started in a directory that holds ``BENCH_restart.json``
        and ``BENCH_reshard.json`` builds its controller on ``CostModel()``'s
        constants: the working directory is no input."""
        from tpu_resiliency.launcher.agent import AgentConfig, ElasticAgent
        from tpu_resiliency.watchdog.config import FaultToleranceConfig

        with open(tmp_path / "BENCH_restart.json", "w") as f:
            json.dump({
                "in_job": {"respawn_ms": 500.0, "detect_ms": 100.0},
                "in_job_warm_spares": {"respawn_ms": 30.0, "detect_ms": 10.0},
            }, f)
        with open(tmp_path / "BENCH_reshard.json", "w") as f:
            json.dump({"ranged_s": 0.25,
                       "phases": {"plan_s": 0.01, "fetch_s": 0.03}}, f)
        monkeypatch.chdir(tmp_path)
        agent = ElasticAgent(
            AgentConfig(argv=["true"], autoscale="advise",
                        run_dir=str(tmp_path / "run")),
            FaultToleranceConfig(), coord_store,
        )
        agent._start_autoscale()
        try:
            assert agent.autoscale.model.constants() == CostModel().constants()
        finally:
            agent.autoscale.stop()
            agent.rdzv.stop_keepalive()


# -- deciding ----------------------------------------------------------------


class TestDecide:
    def test_healthy_job_is_silent(self, seen):
        ctl = controller()
        assert ctl.tick() is None
        assert not [e for e in seen if e.kind == "autoscale_decision"]

    def test_straggler_with_spares_decides_swap(self, seen):
        ctl = controller(spare_capacity_fn=lambda: 2)
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}),
            scores={2: 0.3, 0: 1.0},
        ))
        d = ctl.tick()
        assert d is not None and d.action == ACTION_SWAP
        assert d.victims == [2] and d.predicted_delta_s > 0
        assert d.outcome == "advised"  # advise mode never actuates
        evs = [e for e in seen if e.kind == "autoscale_decision"]
        assert len(evs) == 1
        assert evs[0].payload["predicted_delta_s"] == d.predicted_delta_s
        # Identical decision inside the cooldown is suppressed.
        assert ctl.tick() is None

    def test_straggler_without_spares_decides_exclude(self):
        ctl = controller(spare_capacity_fn=lambda: 0)
        ctl.note_world_size(4)
        ctl.note_health(HealthDecision(
            degraded=frozenset({1}), newly_degraded=frozenset({1}),
            recovered=frozenset(), flagged=frozenset({1}),
            scores={1: 0.1},
        ))
        d = ctl.tick()
        assert d is not None and d.action == ACTION_EXCLUDE

    def test_fresh_notice_checkpoints_then_shrinks_after_grace(self, seen):
        clock = FakeClock(100.0)
        ctl = controller(clock=clock)
        ctl.note_world_size(4)
        # Some unbanked progress so the proactive checkpoint prices > 0.
        t = 100.0
        for i in range(30):
            ctl.observe({"kind": "iteration_start", "iteration": i,
                         "ts": t + i * 0.02, "pid": 7})
        ctl.note_preemption("r3", rank=3)
        d1 = ctl.tick()
        assert d1 is not None and d1.action == ACTION_CHECKPOINT
        clock.t += ctl.rescind_grace_s + 0.1  # the rescind window closes
        d2 = ctl.tick()
        assert d2 is not None and d2.action == ACTION_SHRINK
        assert d2.victims == [3]

    def test_rescind_cancels_the_shrink(self):
        clock = FakeClock(100.0)
        ctl = controller(clock=clock)
        ctl.note_world_size(4)
        ctl.note_preemption("r3", rank=3)
        ctl.note_rescind("r3")
        clock.t += ctl.rescind_grace_s + 1.0
        assert ctl.tick() is None  # no notice left: nothing to shrink for
        assert ctl.status()["rescinds"] == 1

    def test_rescind_event_clears_the_notice(self):
        ctl = controller()
        ctl.observe({"kind": "preemption_sync_point", "ts": 100.0,
                     "rank": 2, "step": 9})
        assert len(ctl.status()["pending_notices"]) == 1
        ctl.observe({"kind": "preemption_rescinded", "ts": 101.0,
                     "rank": 2, "step": 14})
        assert not ctl.status()["pending_notices"]
        assert ctl.status()["rescinds"] == 1

    def test_expand_needs_dwell_and_capacity(self):
        clock = FakeClock(100.0)
        ctl = controller(clock=clock, spare_capacity_fn=lambda: 1)
        ctl.note_world_size(4)
        ctl.observe({"kind": "world_resized", "ts": 100.0, "to_world": 3,
                     "direction": "shrink"})
        ctl._last_resize_ts = clock.t  # a shrink just happened
        assert ctl.tick() is None  # inside the dwell: no flapping
        clock.t += ctl.dwell_s + 0.1
        d = ctl.tick()
        assert d is not None and d.action == ACTION_EXPAND


# -- acting ------------------------------------------------------------------


class TestAct:
    def test_swap_routes_through_remediation_engine(self, seen):
        restarts = []
        engine = RemediationEngine(
            spare_capacity_fn=lambda: 1,
            publish_degraded_fn=lambda d: None,
            request_restart_fn=restarts.append,
        )
        ctl = controller(mode="act", remediation=engine,
                         spare_capacity_fn=lambda: 1)
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        d = ctl.tick()
        assert d.action == ACTION_SWAP and d.outcome == "ok"
        assert restarts, "swap never reached the restart actuator"
        # The engine audited it with its own remediation_action event.
        audits = [e for e in seen if e.kind == "remediation_action"]
        assert audits and audits[0].payload["action"] == "spare_swap"

    def test_ok_swap_clears_victims_no_exclude_cascade(self):
        """REGRESSION (found driving the real launcher in act mode): after a
        successful swap the stale straggler view fired a spurious exclude for
        the same victims on the next tick. An OK swap clears its victims
        optimistically; the next degraded_set re-establishes the truth."""
        spares = [1]
        engine = RemediationEngine(
            spare_capacity_fn=lambda: spares[0],
            publish_degraded_fn=lambda d: None,
            request_restart_fn=lambda r: spares.__setitem__(0, 0),
        )
        ctl = controller(mode="act", remediation=engine,
                         spare_capacity_fn=lambda: spares[0])
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        d = ctl.tick()
        assert d.action == ACTION_SWAP and d.outcome == "ok"
        assert ctl.status()["stragglers"] == {}
        assert ctl.tick() is None  # no exclude cascade for the same ranks

    def test_engine_dry_run_audits_skip(self):
        engine = RemediationEngine(
            spare_capacity_fn=lambda: 1,
            publish_degraded_fn=lambda d: None,
            request_restart_fn=lambda r: None,
            dry_run=True,
        )
        ctl = controller(mode="act", remediation=engine,
                         spare_capacity_fn=lambda: 1)
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        assert ctl.tick().outcome == "skipped"

    def test_shrink_uses_injected_actuator_and_consumes_notice(self):
        clock = FakeClock(100.0)
        shrunk = []
        ctl = controller(
            mode="act", clock=clock,
            shrink_fn=lambda victims, reason: shrunk.append(victims),
        )
        ctl.note_world_size(4)
        ctl.note_preemption("r1", rank=1, deadline=clock.t + 0.5)
        d = ctl.tick()
        assert d.action == ACTION_SHRINK and d.outcome == "ok"
        assert shrunk == [[1]]
        assert not ctl.status()["pending_notices"]  # consumed by the shrink

    def test_actuator_failure_is_audited_not_raised(self):
        clock = FakeClock(100.0)
        ctl = controller(
            mode="act", clock=clock,
            shrink_fn=lambda v, r: (_ for _ in ()).throw(RuntimeError("no")),
        )
        ctl.note_world_size(2)
        ctl.note_preemption("r1", rank=1, deadline=clock.t)
        assert ctl.tick().outcome == "failed"


# -- realized outcomes -------------------------------------------------------


class TestOutcomes:
    def test_every_decision_settles_with_a_realized_delta(self, seen):
        clock = FakeClock(100.0)
        ctl = controller(clock=clock, spare_capacity_fn=lambda: 1,
                         outcome_window_s=1.0)
        t = 100.0
        for i in range(10):
            ctl.observe({"kind": "iteration_start", "iteration": i,
                         "ts": t + i * 0.1, "pid": 7})
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        d = ctl.tick()
        assert d is not None and not d.settled
        # Training continues; the window elapses in event time.
        for i in range(10, 40):
            ctl.observe({"kind": "iteration_start", "iteration": i,
                         "ts": t + i * 0.1, "pid": 7})
        clock.t += 2.0
        ctl.tick()  # settlement pass
        assert d.settled and d.realized_delta_s is not None
        outs = [e for e in seen if e.kind == "autoscale_outcome"]
        assert len(outs) == 1
        p = outs[0].payload
        assert p["decision_id"] == d.decision_id
        assert p["predicted_delta_s"] == d.predicted_delta_s
        assert p["realized_delta_s"] == d.realized_delta_s
        assert ctl.model.outcomes[ACTION_SWAP][0] == 1  # fed back to the model

    def test_finalize_settles_pending_decisions(self, seen):
        ctl = controller(spare_capacity_fn=lambda: 1, outcome_window_s=999.0)
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        d = ctl.tick()
        assert not d.settled
        ctl.finalize()
        assert d.settled and d.realized_delta_s is not None
        assert [e for e in seen if e.kind == "autoscale_outcome"]


# -- signals + status --------------------------------------------------------


class TestSignals:
    def test_degraded_set_event_feeds_stragglers(self):
        ctl = controller()
        ctl.observe({"kind": "degraded_set", "ts": 1.0,
                     "degraded": [1, 3], "newly": [3],
                     "scores": {"1": 0.5, "3": 0.2, "0": 1.0}})
        st = ctl.status()["stragglers"]
        assert st == {"1": 0.5, "3": 0.2}
        # Recovery clears them.
        ctl.observe({"kind": "degraded_set", "ts": 2.0, "degraded": [],
                     "recovered": [1, 3], "scores": {}})
        assert ctl.status()["stragglers"] == {}

    def test_world_and_spares_from_events(self):
        ctl = controller()
        ctl.observe({"kind": "rendezvous_round", "ts": 1.0, "round": 0,
                     "world_size": 8})
        ctl.observe({"kind": "warm_spare_pool", "ts": 1.5, "warm": 3,
                     "parked": 3, "size": 3})
        v = ctl.view()
        assert v.world_size == 8 and v.target_world == 8 and v.spares == 3

    def test_ckpt_saved_resets_unbanked_steps(self):
        ctl = controller()
        for i in range(5):
            ctl.observe({"kind": "iteration_start", "iteration": i,
                         "ts": 1.0 + i, "pid": 3})
        assert ctl.view().steps_since_ckpt == 4
        ctl.observe({"kind": "ckpt_saved", "ts": 7.0, "bytes": 10})
        assert ctl.view().steps_since_ckpt == 0

    def test_poll_tails_an_events_file(self, tmp_path, seen):
        ev = tmp_path / "ev.jsonl"
        with open(ev, "w") as f:
            f.write(json.dumps({"kind": "degraded_set", "ts": 1.0,
                                "degraded": [1], "scores": {"1": 0.2}}) + "\n")
        ctl = controller(events_file=str(ev), spare_capacity_fn=lambda: 1)
        d = ctl.poll()
        assert d is not None and d.action == ACTION_SWAP
        # Torn trailing line does not advance the offset.
        with open(ev, "a") as f:
            f.write('{"kind": "torn')
        off = ctl._offset
        ctl.poll()
        assert ctl._offset == off

    def test_status_document_shape(self):
        ctl = controller(spare_capacity_fn=lambda: 1)
        ctl.note_health(HealthDecision(
            degraded=frozenset({2}), newly_degraded=frozenset({2}),
            recovered=frozenset(), flagged=frozenset({2}), scores={2: 0.2},
        ))
        ctl.tick()
        ctl.finalize()
        doc = ctl.status()
        assert doc["schema"] == "tpu-autoscale-1"
        assert doc["mode"] == "advise"
        assert doc["decisions_total"] == 1
        d = doc["decisions"][0]
        assert d["action"] == ACTION_SWAP
        assert d["predicted_delta_s"] is not None
        assert d["realized_delta_s"] is not None
        assert doc["forecast"]["settled"] == 1
        assert "warm_restart_s" in doc["cost_model"]
        json.dumps(doc)  # must be strict-JSON serializable

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            AutoscaleController(mode="auto")

    def test_thread_start_stop(self, tmp_path):
        ctl = controller(events_file=str(tmp_path / "ev.jsonl"),
                         clock=time.time)
        ctl.interval = 0.05
        ctl.start()
        time.sleep(0.15)
        ctl.stop()
        assert ctl._thread is None


# -- the SLO watchtower's early warning --------------------------------------


PAGE = {"rule": "step_anomaly", "severity": "page", "fire_ts": 99.0}


class TestAlertBias:
    def test_checkpoint_prices_page_alert_risk(self):
        m = CostModel(horizon_s=10.0, ckpt_s=0.2, p_alert_risk=0.35)
        v = view(step_s=0.1, steps_since_ckpt=20)
        # No notice, no alert: a proactive save is pure cost...
        assert m.estimate(ACTION_CHECKPOINT, v) == pytest.approx(-0.2)
        # ...a page-grade alert puts the unbanked progress at alert risk...
        v_alert = view(step_s=0.1, steps_since_ckpt=20)
        v_alert.active_alerts = [PAGE]
        assert m.estimate(ACTION_CHECKPOINT, v_alert) == pytest.approx(
            0.35 * 2.0 - 0.2
        )
        # ...and a real notice still outranks it (p_preempt, not p_alert_risk).
        n = Notice(key="r1", rank=1, noticed_at=99.0)
        v_both = view(notices=[n], step_s=0.1, steps_since_ckpt=20)
        v_both.active_alerts = [PAGE]
        assert m.estimate(ACTION_CHECKPOINT, v_both) == pytest.approx(
            m.p_preempt * 2.0 - 0.2
        )
        # Warn-grade alerts do not move the model.
        v_warn = view(step_s=0.1, steps_since_ckpt=20)
        v_warn.active_alerts = [{"rule": "r", "severity": "warn"}]
        assert m.estimate(ACTION_CHECKPOINT, v_warn) == pytest.approx(-0.2)

    def test_page_alert_decides_checkpoint_before_any_verdict(self, seen):
        """The acceptance story: a page-severity early warning (no straggler
        verdict, no notice) banks progress via an advised checkpoint."""
        firing = []
        ctl = controller(active_alerts_fn=lambda: firing)
        for i in range(30):  # 29 unbanked 0.1s steps
            ctl.observe({"kind": "iteration_start", "iteration": i,
                         "ts": 60.0 + 0.1 * i, "pid": 1})
        assert ctl.tick() is None  # healthy and silent without the alert
        firing.append(dict(PAGE))
        d = ctl.tick()
        assert d is not None and d.action == ACTION_CHECKPOINT
        assert "step_anomaly" in d.reason and d.predicted_delta_s > 0
        doc = ctl.status()
        assert doc["active_alerts"] == [
            {"rule": "step_anomaly", "severity": "page"}
        ]
        evs = [e for e in seen if e.kind == "autoscale_decision"]
        assert [e.payload["action"] for e in evs] == [ACTION_CHECKPOINT]

    def test_crashing_alerts_fn_never_hurts(self):
        def boom():
            raise RuntimeError("watchtower gone")

        ctl = controller(active_alerts_fn=boom)
        assert ctl.view().active_alerts == []
        assert ctl.tick() is None
        assert ctl.status()["active_alerts"] == []

    def test_view_without_alerts_fn_defaults_empty(self):
        assert controller().view().active_alerts == []
        assert view().page_alerts() == []
