"""Closed-loop remediation (ISSUE 16, session/remediate.py): the cause
tier -> bounded action mapping per actuator, the journal + incident
evidence surface, the budget/cooldown suppression discipline (loud,
never silent), the counter-detector's regress-further verdicts with
per-actuator reverts, the no-false-actuation guard (200 noisy-healthy
sweeps -> ZERO actions), runtime quota mutation, the ``why``/``top``
renderers, and the live chaos e2e (slow): loadgen traffic + a replica
kill + a hot-tenant act storm must produce an incident whose mapped
action executes, lands in the incident evidence, and renders."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from surreal_tpu.gateway.admission import AdmissionController
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.incidents import IncidentEngine, load_incidents
from surreal_tpu.session.remediate import (
    RemediationEngine,
    actions_brief,
    actions_report_lines,
    load_actions,
)
from surreal_tpu.session.watchdog import Watchdog
from surreal_tpu.utils import faults


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.configure(None)  # never leak a plan into the next test


# -- synthetic rig ------------------------------------------------------------

def _snap(i, *, serve_ms=2.0, fleet_dead=False, shard_dead=False,
          steps_per_s=5000.0, slo=None, gw_p99=8.0):
    """One merged ops-plane snapshot (the test_watchdog shape, trimmed
    to the signals the remediation objectives read)."""
    return {
        "type": "ops_snapshot", "t": 1000.0 + i, "seq": i, "iteration": i,
        "env_steps": i * 512, "trace": "tr-test",
        "tiers": {
            "learner": {
                "age_s": 0.0, "dead": False, "cadence_s": 1.0,
                "gauges": {"time/env_steps_per_s": steps_per_s,
                           "perf/mfu": 0.3,
                           "experience/sample_wait_ms": 1.0,
                           "lineage/staleness_p99": 2.0},
            },
            "fleet.replica0": {
                "age_s": 9.0 if fleet_dead else 0.2, "dead": fleet_dead,
                "cadence_s": 1.0,
                "gauges": {"fleet/serve_ms": serve_ms,
                           "fleet/respawns": 0.0},
            },
            "experience.shard0": {
                "age_s": 9.0 if shard_dead else 0.2, "dead": shard_dead,
                "cadence_s": 1.0, "gauges": {},
            },
            "gateway": {"age_s": 0.2, "dead": False, "cadence_s": 1.0,
                        "gauges": {}},
        },
        "hops": {"gateway_act_ms": {"p50": 4.0, "p90": 6.0, "p99": gw_p99}},
        "slo": slo or {}, "bad_frames": 0,
    }


class _StubIncidents:
    """Just the surface the engine reads: one settable open incident +
    the attach_action evidence sink."""

    def __init__(self, incident=None):
        self._open = incident
        self.attached = []

    @property
    def open_incident(self):
        return self._open

    def attach_action(self, summary):
        self.attached.append(dict(summary))


def _incident(tier, *, dead=(), n=1, score=2.0):
    return {"id": n, "causes": [{"tier": tier, "score": score,
                                 "reasons": []}],
            "evidence": {"dead_tiers": list(dead)},
            "detector_counts": {}}


class _FakeFleet:
    def __init__(self, fail=False):
        self.ups = 0
        self.downs = 0
        self._fail = fail

    def scale_up(self):
        if self._fail:
            raise RuntimeError("no capacity")
        self.ups += 1
        return self.ups

    def scale_down(self):
        self.downs += 1
        return self.downs


def _engine(tmp_path, incidents, *, events=None, **cfg):
    # a real cooldown by default: after a verdict the incident is often
    # still open, and a zero cooldown would immediately re-execute
    cfg.setdefault("cooldown_s", 300.0)
    cfg.setdefault("verify_windows", 2)
    on_event = None
    if events is not None:
        # first param named like Tracer.event's: the kwargs carry "kind"
        on_event = lambda type_, **kw: events.append({"type": type_, **kw})
    return RemediationEngine(
        folder=str(tmp_path), cfg=cfg, incidents=incidents,
        on_event=on_event, trace_id="tr-test",
    )


# -- no false actuation -------------------------------------------------------

def test_noisy_healthy_200_sweeps_execute_zero_actions(tmp_path):
    """The guard rail extended to actuation: 200 healthy sweeps with
    mild deterministic noise through the REAL watchdog + incident engine
    + remediation engine (live actuators bound) — zero actions, zero
    suppressions, zero journal files, untouched actuators."""
    os.makedirs(os.path.join(str(tmp_path), "telemetry"))
    wd = Watchdog()
    inc = IncidentEngine(folder=str(tmp_path), trace_id="tr-test")
    fleet = _FakeFleet()
    admission = AdmissionController({"hot": {"rate": 100.0, "burst": 10.0}})
    rem = _engine(tmp_path, inc)
    rem.bind_actuators(fleet=fleet, admission=admission,
                       restart={"experience": lambda: None})
    for i in range(200):
        s = _snap(
            i,
            serve_ms=2.0 + 0.4 * np.sin(0.7 * i),
            steps_per_s=5000.0 * (1.0 + 0.08 * np.cos(0.2 * i)),
            gw_p99=8.0 + 1.5 * np.sin(0.3 * i),
        )
        firings = wd.evaluate(s)
        inc.observe(firings, s)
        rem.step(firings, s)
    g = rem.gauges()
    assert g["remediation/actions"] == 0.0
    assert g["remediation/suppressed"] == 0.0
    assert g["remediation/unmapped"] == 0.0
    assert g["remediation/errors"] == 0.0
    assert fleet.ups == 0 and admission.quota_changes == 0
    assert load_actions(str(tmp_path)) == []
    assert actions_report_lines(str(tmp_path)) == []


# -- per-actuator action + counter-detector revert ----------------------------

def test_fleet_cause_scales_up_and_regression_reverts(tmp_path):
    """A fleet-tier cause maps to scale_up; when fleet serve latency
    regresses FURTHER past the at-action baseline over verify_windows,
    the counter-detector marks it ineffective and reverts (scale_down).
    The journal carries the whole story."""
    events = []
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet", dead=["fleet.replica0"]))
    rem = _engine(tmp_path, stub, events=events)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0, serve_ms=50.0))
    assert fleet.ups == 1 and rem.executed == 1
    assert rem.gauges()["remediation/active"] == 1.0
    # verification window: latency got WORSE -> ineffective + revert
    rem.step([], _snap(1, serve_ms=120.0))
    rem.step([], _snap(2, serve_ms=130.0))
    assert fleet.downs == 1
    g = rem.gauges()
    assert g["remediation/ineffective"] == 1.0
    assert g["remediation/reverted"] == 1.0
    assert g["remediation/active"] == 0.0
    (act,) = load_actions(str(tmp_path))
    assert act["kind"] == "fleet_scale_up"
    assert act["cause_tier"] == "fleet"
    assert act["baseline"] == pytest.approx(50.0)
    assert act["verdict"] == "ineffective" and act["reverted"] is True
    # the evidence surface saw both the execution and the verdict
    assert [a["verdict"] for a in stub.attached] == [None, "ineffective"]
    executed = [e for e in events if e["type"] == "remediation"
                and e["status"] == "executed"]
    verdicts = [e for e in events if e["type"] == "remediation_verdict"]
    assert len(executed) == 1 and len(verdicts) == 1
    assert verdicts[0]["reverted"] is True


def test_effective_action_is_not_reverted(tmp_path):
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0, serve_ms=50.0))
    rem.step([], _snap(1, serve_ms=20.0))  # improved
    rem.step([], _snap(2, serve_ms=10.0))
    assert fleet.downs == 0
    (act,) = load_actions(str(tmp_path))
    assert act["verdict"] == "effective" and act["reverted"] is False
    assert rem.gauges()["remediation/effective"] == 1.0


def test_gateway_cause_throttles_burning_tenant_and_revert_restores(
        tmp_path):
    """A gateway-tier cause throttles the tenant burning the most error
    budget through the LIVE AdmissionController.set_quota; an
    ineffective verdict restores the previous quota verbatim."""
    admission = AdmissionController(
        {"hot": {"rate": 100.0, "burst": 40.0, "queue_depth": 8}}
    )
    admission.tenant("hot")  # live tenant state exists pre-throttle
    slo = {"hot": {"act_rtt_p99_ms": {
        "measured": 90.0, "target": 20.0, "breached": True,
        "budget_used": 0.8, "exhausted": False,
    }}}
    stub = _StubIncidents(_incident("gateway"))
    rem = _engine(tmp_path, stub, throttle_factor=0.5)
    rem.bind_actuators(admission=admission)
    rem.step([], _snap(0, slo=slo))
    assert admission.quota_changes == 1
    assert admission.quota_of("hot")["rate"] == pytest.approx(50.0)
    assert admission.tenant("hot").bucket.rate == pytest.approx(50.0)
    (act,) = load_actions(str(tmp_path))
    assert act["kind"] == "tenant_throttle" and act["tenant"] == "hot"
    assert act["baseline"] == pytest.approx(0.8)
    # the budget kept burning anyway -> ineffective -> quota restored
    worse = {"hot": {"act_rtt_p99_ms": {
        "measured": 95.0, "target": 20.0, "breached": True,
        "budget_used": 1.5, "exhausted": True,
    }}}
    rem.step([], _snap(1, slo=worse))
    rem.step([], _snap(2, slo=worse))
    assert admission.quota_of("hot")["rate"] == pytest.approx(100.0)
    assert admission.quota_changes == 2
    (act,) = load_actions(str(tmp_path))
    assert act["verdict"] == "ineffective" and act["reverted"] is True


def test_gateway_cause_with_no_burning_tenant_is_unmapped(tmp_path):
    stub = _StubIncidents(_incident("gateway"))
    rem = _engine(tmp_path, stub)
    rem.bind_actuators(admission=AdmissionController())
    rem.step([], _snap(0))  # empty SLO table: no throttle target
    assert rem.gauges()["remediation/unmapped"] == 1.0
    assert load_actions(str(tmp_path)) == []


def test_dead_tier_targeted_restart_is_irreversible(tmp_path):
    """A DEAD non-fleet tier maps to its supervise() callable; a restart
    cannot be un-run, so even an ineffective verdict must not revert."""
    calls = []
    stub = _StubIncidents(
        _incident("experience", dead=["experience.shard0"])
    )
    rem = _engine(tmp_path, stub)
    rem.bind_actuators(restart={"experience": lambda: calls.append(1)})
    rem.step([], _snap(0, shard_dead=True))
    assert calls == [1]
    (act,) = load_actions(str(tmp_path))
    assert act["kind"] == "targeted_restart"
    assert act["reversible"] is False
    assert act["baseline"] == pytest.approx(1.0)  # dead fraction
    # tier stays dead: not "regressed further" past 1.0 -> no revert try
    rem.step([], _snap(1, shard_dead=True))
    rem.step([], _snap(2, shard_dead=True))
    (act,) = load_actions(str(tmp_path))
    assert act["reverted"] is False and act["status"] == "done"


# -- bounds: budget, cooldown, errors (all loud) ------------------------------

def test_action_budget_exhaustion_suppresses_loudly(tmp_path):
    events = []
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, events=events, max_actions=1,
                  verify_windows=1)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0, serve_ms=50.0))   # executes + burns the budget
    rem.step([], _snap(1, serve_ms=20.0))   # verdict lands; then budget
    rem.step([], _snap(2, serve_ms=50.0))   # suppresses BOTH sweeps
    assert fleet.ups == 1
    g = rem.gauges()
    assert g["remediation/actions"] == 1.0
    assert g["remediation/suppressed"] == 2.0
    sup = [e for e in events
           if e["type"] == "remediation" and e["status"] == "suppressed"]
    assert sup and "budget" in sup[0]["reason"]


def test_cooldown_suppresses_loudly_and_expires(tmp_path):
    events = []
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, events=events, cooldown_s=30.0,
                  verify_windows=1, max_actions=8)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0))
    rem.step([], _snap(1))  # verdict; this and the next decision both
    rem.step([], _snap(2))  # land inside the cooldown
    assert fleet.ups == 1 and rem.suppressed == 2
    sup = [e for e in events
           if e["type"] == "remediation" and e["status"] == "suppressed"]
    assert sup and "cooldown" in sup[0]["reason"]
    rem._last_t["fleet_scale_up"] -= 60.0  # cooldown elapses
    rem.step([], _snap(3))
    assert fleet.ups == 2


def test_one_action_per_incident_in_flight(tmp_path):
    """While an action for the open incident is still verifying, the
    engine must wait — no stacking, and nothing counted as suppressed
    (the verification window is the plan, not a bound)."""
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, verify_windows=4)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0))
    rem.step([], _snap(1))
    rem.step([], _snap(2))
    assert fleet.ups == 1 and rem.suppressed == 0


def test_actuator_error_is_counted_never_fatal(tmp_path):
    events = []
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, events=events)
    rem.bind_actuators(fleet=_FakeFleet(fail=True))
    rem.step([], _snap(0))  # scale_up raises inside
    assert rem.gauges()["remediation/errors"] == 1.0
    assert load_actions(str(tmp_path)) == []
    err = [e for e in events if e.get("status") == "error"]
    assert err and "no capacity" in err[0]["reason"]


def test_unbound_actuator_is_unmapped_not_an_error(tmp_path):
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub)  # nothing bound
    rem.step([], _snap(0))
    g = rem.gauges()
    assert g["remediation/unmapped"] == 1.0 and g["remediation/errors"] == 0.0


# -- runtime quota mutation (satellite: AdmissionController.set_quota) --------

def test_set_quota_swaps_live_bucket_and_keeps_history():
    """set_quota must take effect on the very NEXT act (live bucket
    rebuild), preserve the tenant's counters/queue (history is
    evidence), return the previous quota for revert, and count itself
    into the gateway/quota_changes gauge."""
    ac = AdmissionController({"t": {"rate": 0.0}})  # unlimited
    assert ac.try_act("t") is True
    ac.tenant("t").throttled = 3  # pre-existing history
    prev = ac.set_quota("t", {"rate": 1.0, "burst": 1.0,
                              "max_sessions": 2, "queue_depth": 4})
    assert prev == {"rate": 0.0}
    assert ac.try_act("t") is True      # the single burst token
    assert ac.try_act("t") is False     # throttled immediately
    t = ac.tenant("t")
    assert t.throttled == 4 and t.max_sessions == 2 and t.queue_depth == 4
    assert ac.gauges()["gateway/quota_changes"] == 1.0
    # revert with the returned dict restores the unlimited bucket
    ac.set_quota("t", prev)
    assert ac.try_act("t") is True and ac.quota_changes == 2


# -- journal + renderers ------------------------------------------------------

def test_actions_reports_render_and_tolerate_hostile_files(tmp_path):
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet", n=3))
    rem = _engine(tmp_path, stub, verify_windows=1)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0, serve_ms=50.0))
    rem.step([], _snap(1, serve_ms=10.0))
    act_dir = os.path.join(str(tmp_path), "telemetry", "actions")
    assert sorted(os.listdir(act_dir)) == ["action-1.json"]
    # hostile residue must be skipped, never a crash
    with open(os.path.join(act_dir, "action-2.json"), "w") as f:
        f.write("{torn")
    with open(os.path.join(act_dir, "notes.txt"), "w") as f:
        f.write("not an action")
    acts = load_actions(str(tmp_path))
    assert [a["action"] for a in acts] == [1]
    lines = actions_report_lines(str(tmp_path))
    assert lines and "1 remediation action(s)" in lines[0]
    assert any("fleet" in ln and "fleet_scale_up" in ln for ln in lines)
    # incident filter: a different incident renders nothing
    assert actions_report_lines(str(tmp_path), incident=99) == []
    brief = actions_brief(str(tmp_path))
    assert brief and "1 action(s) taken" in brief[0]
    # round-trip: the journal is plain JSON
    with open(os.path.join(act_dir, "action-1.json")) as f:
        rec = json.load(f)
    assert rec["verdict"] == "effective" and rec["trace"] == "tr-test"


def test_action_lands_in_real_incident_evidence_and_why(tmp_path):
    """Against the REAL incident engine: a dead-replica incident's
    evidence gains the action entry (updated in place on verdict) and
    ``incidents_report`` renders both the per-incident actions block and
    the run-level Actions section."""
    from surreal_tpu.session.incidents import incidents_report

    os.makedirs(os.path.join(str(tmp_path), "telemetry"))
    wd = Watchdog(cfg={"warmup": 4, "sustain": 1})
    eng = IncidentEngine(folder=str(tmp_path), trace_id="tr-test")
    eng.record_fault({"site": "fleet.replica", "kind": "kill"})
    fleet = _FakeFleet()
    rem = _engine(tmp_path, eng, verify_windows=1, cooldown_s=60.0)
    rem.bind_actuators(fleet=fleet)
    for i in range(6):
        s = _snap(i)
        firings = wd.evaluate(s)
        eng.observe(firings, s)
        rem.step(firings, s)
    for i in range(6, 10):
        s = _snap(i, fleet_dead=True, serve_ms=50.0)
        firings = wd.evaluate(s)
        eng.observe(firings, s)
        rem.step(firings, s)
    assert fleet.ups == 1
    inc = eng.open_incident
    assert inc is not None and inc["causes"][0]["tier"] == "fleet"
    actions_ev = inc["evidence"].get("actions")
    assert actions_ev and actions_ev[0]["kind"] == "fleet_scale_up"
    assert actions_ev[0]["verdict"] is not None  # verdict updated in place
    eng.close()
    report = incidents_report(str(tmp_path))
    assert "actions taken (cause -> action -> verdict)" in report
    assert "Actions — 1 remediation action(s)" in report
    assert "fleet_scale_up" in report


def test_close_flushes_still_verifying_actions(tmp_path):
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, verify_windows=8)
    rem.bind_actuators(fleet=_FakeFleet())
    rem.step([], _snap(0))
    rem.close()
    (act,) = load_actions(str(tmp_path))
    assert act["status"] == "verifying" and act["verdict"] is None


def test_disabled_engine_does_nothing(tmp_path):
    fleet = _FakeFleet()
    stub = _StubIncidents(_incident("fleet"))
    rem = _engine(tmp_path, stub, enabled=False)
    rem.bind_actuators(fleet=fleet)
    rem.step([], _snap(0))
    assert fleet.ups == 0 and load_actions(str(tmp_path)) == []


# -- live chaos e2e (slow) ----------------------------------------------------

@pytest.mark.slow
def test_remediation_chaos_e2e_action_executes_and_renders(tmp_path):
    """The acceptance run: a live SEED session with the gateway, tenant
    load (steady + hot-key storm via gateway/loadgen.py), and a replica
    kill. The incident engine must name an injected/afflicted tier, the
    remediation engine must execute the mapped bounded action, the
    action must appear in the journal AND the incident evidence, and
    ``why`` must render the Actions section."""
    from surreal_tpu.gateway.loadgen import LoadGenerator
    from surreal_tpu.launch.seed_trainer import SEEDTrainer
    from surreal_tpu.main.launch import main

    folder = str(tmp_path)
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=8)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=folder,
            total_env_steps=1200,
            metrics=Config(every_n_iters=1, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=2,
                inference_fleet=Config(replicas=2),
                gateway=Config(
                    enabled=True, lease_s=10.0,
                    tenant_quotas=Config(
                        hotkey=Config(rate=50.0, burst=20.0,
                                      queue_depth=8),
                    ),
                ),
            ),
            watchdog=Config(
                warmup=4, sustain=1, mad_k=3.0, min_rel=0.2,
                close_windows=6, capture_cooldown_s=0.0,
            ),
            remediate=Config(cooldown_s=0.5, verify_windows=2),
            faults=Config(plan=[
                {"site": "fleet.replica", "kind": "kill_replica", "at": 40},
            ]),
        ),
    ).extend(base_config())
    trainer = SEEDTrainer(cfg)
    gen_holder: list = []
    stop = threading.Event()

    def traffic():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not stop.is_set():
            gateway = getattr(trainer, "_gateway", None)
            if gateway is not None:
                break
            time.sleep(0.1)
        else:
            return
        gen = LoadGenerator(
            gateway.address,
            tenants=[
                {"tenant": "steady-0", "profile": "steady",
                 "rate_hz": 10.0},
                {"tenant": "hotkey", "profile": "hot_key"},
            ],
            obs_shape=(1, 4), timeout_s=5.0, retries=3,
        ).start()
        gen_holder.append(gen)
        stop.wait(120)

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    try:
        state, metrics = trainer.run()
    finally:
        stop.set()
        if gen_holder:
            gen_holder[0].stop()
        t.join(timeout=15)

    assert metrics["time/env_steps"] >= 1200
    assert metrics["ops/incidents_total"] >= 1.0
    # the tenant mix actually exercised the gateway
    assert gen_holder, "loadgen never saw the gateway address"
    rep = gen_holder[0].report()
    assert rep["loadgen/acts"] > 0, rep
    # the mapped action executed, bounded and journaled
    assert metrics["remediation/actions"] >= 1.0
    actions = load_actions(folder)
    assert actions, "no journaled action"
    assert actions[0]["kind"] in (
        "fleet_scale_up", "tenant_throttle", "targeted_restart"
    ), actions[0]
    # ... and landed in the incident evidence
    incidents = load_incidents(folder)
    assert incidents and incidents[0]["causes"], incidents
    assert any(
        (i.get("evidence") or {}).get("actions") for i in incidents
    ), [i["evidence"].keys() for i in incidents]
    # lifecycle events rode the telemetry spine
    kinds = set()
    tel = os.path.join(folder, "telemetry", "events.jsonl")
    if os.path.exists(tel):
        with open(tel) as f:
            for line in f:
                try:
                    kinds.add(json.loads(line).get("type"))
                except json.JSONDecodeError:
                    continue
    assert "remediation" in kinds, sorted(kinds)
    # why renders the Actions section cleanly
    assert main(["why", folder]) == 0
    # teardown left no data-plane residue
    assert not glob.glob("/dev/shm/surreal_dp_*")
