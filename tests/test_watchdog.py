"""Watchdog & incident engine (ISSUE 15): detector arithmetic, the
upstream-first cause ranking per injected fault class, the false-positive
guard, the chaos site, the transfer-guard proof, the ``why`` renderers,
and the live chaos e2e (slow) where a SEED run with injected faults must
produce a root-caused incident whose top hypothesis names the injected
tier — and a fault-free control run must produce none."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.incidents import (
    IncidentEngine,
    incidents_brief,
    incidents_report,
    load_incidents,
    rank_causes,
    upstream_closure,
)
from surreal_tpu.session.watchdog import Watchdog
from surreal_tpu.utils import faults


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.configure(None)  # never leak a plan into the next test


# -- synthetic snapshot rig ---------------------------------------------------

def make_snap(i, *, iter_s=0.1, serve_ms=2.0, sample_wait_ms=1.0,
              gw_p99=8.0, steps_per_s=5000.0, fleet_dead=False,
              fleet_respawns=0.0, dropped_frames=0.0, staleness=2.0,
              mfu=0.3, slo=None):
    """One merged ops-plane snapshot at a small production census, every
    detector family's signals present and healthy by default."""
    return {
        "type": "ops_snapshot", "t": 1000.0 + i * iter_s, "seq": i,
        "iteration": i, "env_steps": i * 512, "trace": "tr-test",
        "tiers": {
            "learner": {
                "age_s": 0.0, "dead": False, "cadence_s": 1.0,
                "gauges": {
                    "time/env_steps_per_s": steps_per_s,
                    "perf/mfu": mfu,
                    "experience/sample_wait_ms": sample_wait_ms,
                    "lineage/staleness_p99": staleness,
                },
            },
            "fleet.replica0": {
                "age_s": 9.0 if fleet_dead else 0.2,
                "dead": fleet_dead, "cadence_s": 1.0,
                "gauges": {"fleet/serve_ms": serve_ms,
                           "fleet/respawns": fleet_respawns},
            },
            "param_fanout": {
                "age_s": 0.1, "dead": False, "cadence_s": 1.0,
                "gauges": {"param/dropped_frames": dropped_frames},
            },
            "gateway": {"age_s": 0.2, "dead": False, "cadence_s": 1.0,
                        "gauges": {}},
        },
        "hops": {"gateway_act_ms": {"p50": 4.0, "p90": 6.0, "p99": gw_p99}},
        "slo": slo or {}, "bad_frames": 0,
    }


def drive(wd, eng, snaps):
    """Feed snapshots through one sweep+observe step each; returns every
    sweep's firings."""
    out = []
    for s in snaps:
        f = wd.evaluate(s)
        eng.observe(f, s)
        out.append(f)
    return out


WARM = [make_snap(i) for i in range(12)]  # past default warmup=8


# -- detector arithmetic ------------------------------------------------------

def test_breakout_fires_on_sustained_deviation_only():
    """A single outlier sweep must NOT fire (sustain=2); two consecutive
    must, blaming the signal's tier with value/baseline recorded."""
    wd = Watchdog()
    for s in WARM:
        assert wd.evaluate(s) == []
    one = wd.evaluate(make_snap(12, serve_ms=60.0))
    assert one == []  # first outlier: streak, not a firing
    back = wd.evaluate(make_snap(13))  # healthy again -> streak resets
    assert back == []
    wd.evaluate(make_snap(14, serve_ms=60.0))
    fired = wd.evaluate(make_snap(15, serve_ms=60.0))
    assert any(
        f["detector"] == "breakout" and f["signal"] == "fleet_serve_ms"
        and f["tier"] == "fleet" and f["value"] > f["baseline"]
        for f in fired
    ), fired
    assert wd.gauges()["ops/watchdog_firings"] >= 1.0


def test_liveness_and_growth_detectors():
    """A DEAD tier fires liveness immediately; a counted-never-silent
    ``*dropped*`` counter fires growth only while it keeps growing."""
    wd = Watchdog()
    for s in WARM:
        wd.evaluate(s)
    fired = wd.evaluate(make_snap(12, fleet_dead=True))
    assert any(
        f["detector"] == "liveness" and f["signal"] == "fleet.replica0"
        and f["tier"] == "fleet" for f in fired
    ), fired
    # growth: two consecutive increasing windows (default growth_windows=2)
    wd2 = Watchdog()
    for s in WARM:
        wd2.evaluate(s)
    assert wd2.evaluate(make_snap(12, dropped_frames=1.0)) == []
    fired = wd2.evaluate(make_snap(13, dropped_frames=3.0))
    assert any(
        f["detector"] == "growth" and f["signal"] == "param/dropped_frames"
        and f["tier"] == "param_fanout" for f in fired
    ), fired
    # plateaued counter: old drops are history, not an anomaly
    assert wd2.evaluate(make_snap(14, dropped_frames=3.0)) == []


def test_staleness_growth_needs_the_floor():
    """The startup staleness ramp (0 -> steady-state pipeline depth) must
    never fire; a stalled fanout that climbs past ``staleness_floor``
    must. This is the exact false positive a live SEED run produced:
    staleness legitimately climbs one version per update until the
    sample queue turns over."""
    wd = Watchdog()
    for s in WARM:
        wd.evaluate(s)
    # monotonic ramp below the floor (64): sustained growth, no firing
    for i in range(12, 40):
        fired = wd.evaluate(make_snap(i, staleness=float(i)))
        assert all(f["signal"] != "lineage/staleness_p99" for f in fired), (
            i, fired)
    # same ramp continued past the floor: fires
    fired = []
    for i in range(40, 90):
        fired = wd.evaluate(make_snap(i, staleness=float(i + 30)))
        if any(f["signal"] == "lineage/staleness_p99" for f in fired):
            break
    assert any(
        f["detector"] == "growth" and f["signal"] == "lineage/staleness_p99"
        and f["tier"] == "param_fanout" for f in fired
    ), fired


def test_false_positive_guard_clean_run_zero_incidents(tmp_path):
    """The guard rail: 200 healthy sweeps with mild deterministic noise
    on every signal, default thresholds — zero firings, zero incidents,
    and ``why`` renders the explicit all-clear."""
    folder = str(tmp_path)
    os.makedirs(os.path.join(folder, "telemetry"))
    wd = Watchdog()
    eng = IncidentEngine(folder=folder, trace_id="tr-test")
    snaps = [
        make_snap(
            i,
            iter_s=0.1 * (1.0 + 0.1 * np.sin(i)),
            serve_ms=2.0 + 0.4 * np.sin(0.7 * i),
            sample_wait_ms=1.0 + 0.2 * np.cos(i),
            gw_p99=8.0 + 1.5 * np.sin(0.3 * i),
            steps_per_s=5000.0 * (1.0 + 0.08 * np.cos(0.2 * i)),
            # the live startup shape: staleness climbs one version per
            # update until the sample queue turns over, then plateaus
            staleness=min(float(i), 24.0),
        )
        for i in range(200)
    ]
    firings = drive(wd, eng, snaps)
    assert all(f == [] for f in firings), [f for f in firings if f]
    assert eng.opened == 0
    assert load_incidents(folder) == []
    report = incidents_report(folder)
    assert report is not None and "no incidents recorded" in report


# -- cause ranking per injected fault class -----------------------------------

def test_upstream_closure_walks_the_dataflow_graph():
    assert upstream_closure("gateway") == {"fleet", "workers", "param_fanout",
                                           "learner", "experience"}
    assert upstream_closure("workers") == set()


def test_cause_ranking_per_fault_class():
    """The PR's acceptance table: for each injected fault class, the
    top-ranked hypothesis must name the injected tier — upstream-first,
    not merely symptom-first."""
    cases = [
        # replica kill: fault@fleet + dead replica + gateway RTT symptom
        (
            {"site": "fleet.replica", "kind": "kill"},
            dict(fleet_dead=True, gw_p99=150.0),
            "fleet",
        ),
        # shard kill: fault@experience + learner sample-wait symptom
        (
            {"site": "experience.shard", "kind": "kill_shard"},
            dict(sample_wait_ms=40.0),
            "experience",
        ),
        # fanout frame drop: fault@param.publish + dropped-frame growth
        (
            {"site": "param.publish", "kind": "drop_frame"},
            dict(dropped_frames=None),  # ramped below
            "param_fanout",
        ),
        # act delay: fault@gateway.session + act-RTT breakout
        (
            {"site": "gateway.session", "kind": "delay"},
            dict(gw_p99=200.0),
            "gateway",
        ),
    ]
    for fault, overrides, want_tier in cases:
        wd = Watchdog()
        for s in WARM:
            wd.evaluate(s)
        eng = IncidentEngine(cfg={"close_windows": 3}, trace_id="tr-test")
        eng.record_fault(dict(fault))
        for k in range(4):
            kw = dict(overrides)
            if kw.get("dropped_frames", 0.0) is None:
                kw["dropped_frames"] = float(k + 1)  # monotonic ramp
            s = make_snap(12 + k, **kw)
            eng.observe(wd.evaluate(s), s)
        assert eng.opened == 1, (fault, "no incident opened")
        inc = eng._open
        assert inc is not None and inc["causes"], fault
        top = inc["causes"][0]
        assert top["tier"] == want_tier, (fault, inc["causes"])
        assert any("injected fault" in r for r in top["reasons"]), top
        # recovery: sustained-healthy windows close it
        for k in range(3):
            eng.observe([], make_snap(20 + k))
        assert eng.closed == 1 and eng._open is None, fault


def test_rank_causes_upstream_boost_is_pure():
    """rank_causes alone: hard evidence upstream of a symptomatic tier
    outranks the symptom bearer even with more symptom firings."""
    ranked = rank_causes(
        {"breakout:gateway:act_rtt_p99_ms": 3},
        {"faults": [{"site": "fleet.replica", "kind": "kill"}],
         "dead_tiers": ["fleet.replica0"]},
    )
    assert ranked[0]["tier"] == "fleet"
    assert any("upstream of symptomatic tier gateway" in r
               for r in ranked[0]["reasons"])


def test_slo_breach_evidence_correlates_to_owning_tier():
    """A breached per-tenant SLO row in the snapshot lands in evidence
    and scores the objective's owning tier."""
    slo = {"tenantA": {"act_rtt_p99_ms": {
        "measured": 80.0, "target": 10.0, "breached": True,
        "budget_used": 0.5, "exhausted": False,
    }}}
    wd = Watchdog()
    for s in WARM:
        wd.evaluate(s)
    eng = IncidentEngine(trace_id="tr-test")
    for k in range(3):
        s = make_snap(12 + k, gw_p99=200.0, slo=slo)
        eng.observe(wd.evaluate(s), s)
    inc = eng._open
    assert inc is not None
    assert inc["evidence"]["slo_breaches"], inc["evidence"]
    assert any(
        c["tier"] == "gateway"
        and any("SLO breach act_rtt_p99_ms" in r for r in c["reasons"])
        for c in inc["causes"]
    ), inc["causes"]


# -- chaos site + transfer guard ----------------------------------------------

def test_watchdog_eval_chaos_site_drop_is_counted_never_silent():
    """``drop_eval`` skips the sweep but counts it; ``delay`` sleeps and
    still evaluates. Both are drained as recorded firings."""
    faults.configure([
        {"site": "watchdog.eval", "kind": "drop_eval", "at": 0},
        {"site": "watchdog.eval", "kind": "delay", "ms": 1, "at": 1},
    ])
    wd = Watchdog()
    assert wd.evaluate(make_snap(0)) == []  # dropped sweep
    assert wd.dropped_evals == 1 and wd.evals == 0
    t0 = time.perf_counter()
    wd.evaluate(make_snap(1))  # delayed sweep still runs
    assert time.perf_counter() - t0 >= 0.001
    assert wd.evals == 1
    g = wd.gauges()
    assert g["ops/watchdog_dropped_evals"] == 1.0
    assert g["ops/watchdog_evals"] == 1.0
    assert len(faults.drain_fired()) == 2


def test_sweep_and_observe_add_zero_device_syncs(tmp_path):
    """The overhead commitment's other half: a full sweep + incident
    observe (anomalous snapshot included — open, rank, persist) runs
    under ``transfer_guard_device_to_host('disallow')``. Pure host
    arithmetic over the snapshot dict, no device state in reach."""
    import jax

    wd = Watchdog()
    eng = IncidentEngine(folder=str(tmp_path), trace_id="tr-test")
    with jax.transfer_guard_device_to_host("disallow"):
        for s in WARM:
            eng.observe(wd.evaluate(s), s)
        s = make_snap(12, fleet_dead=True)
        eng.observe(wd.evaluate(s), s)
    assert eng.opened == 1


# -- why renderers + CLI ------------------------------------------------------

def _persisted_incident(folder):
    """One closed incident on disk via the real engine lifecycle."""
    wd = Watchdog()
    eng = IncidentEngine(folder=folder, cfg={"close_windows": 2},
                         trace_id="tr-why")
    eng.record_fault({"site": "fleet.replica", "kind": "kill", "at": 40})
    for s in WARM:
        eng.observe(wd.evaluate(s), s)
    for k in range(3):
        s = make_snap(12 + k, fleet_dead=True, gw_p99=150.0)
        eng.observe(wd.evaluate(s), s)
    for k in range(2):
        eng.observe([], make_snap(15 + k))
    assert eng.closed == 1
    return load_incidents(folder)


def test_why_report_renders_causes_evidence_and_units(tmp_path):
    folder = str(tmp_path)
    incidents = _persisted_incident(folder)
    assert len(incidents) == 1 and incidents[0]["status"] == "closed"
    report = incidents_report(folder)
    assert report is not None
    assert "surreal_tpu why" in report and "tr-why" in report
    assert "ranked causes (upstream-first)" in report
    assert "fleet" in report
    assert "injected fault kill @ fleet.replica" in report
    assert "act_rtt_p99_ms" in report and " ms" in report  # unit rendered
    assert "dead_tier   fleet.replica0" in report
    # narrowing to one id works; a missing id says so
    assert "incident #1" in incidents_report(folder, incident=1)
    assert "no incident #9" in incidents_report(folder, incident=9)
    # the brief reuses the same record for diag/top
    brief = incidents_brief(folder)
    assert brief and any("top cause: fleet" in ln for ln in brief)


def test_why_cli_and_top_incidents_section(tmp_path, capsys):
    """``surreal_tpu why``: rc 2 on a non-session folder, rc 0 rendering
    the incidents; ``top --once`` shows the Incidents section."""
    from surreal_tpu.main.launch import main
    from surreal_tpu.session.opsplane import OpsAggregator, load_snapshot, \
        top_report

    assert main(["why", str(tmp_path / "missing")]) == 2
    folder = str(tmp_path)
    _persisted_incident(folder)
    assert main(["why", folder]) == 0
    out = capsys.readouterr().out
    assert "incident #1" in out and "CLOSED" in out
    assert main(["why", folder, "--incident", "1"]) == 0
    # top renders the same brief under an Incidents header
    agg = OpsAggregator(folder, trace_id="tr-why")
    try:
        agg.push_local("learner", gauges={"perf/mfu": 0.25})
        agg.snapshot(iteration=9, env_steps=900)
    finally:
        agg.close()
    report = top_report(load_snapshot(folder), folder)
    assert "Incidents" in report and "top cause: fleet" in report


def test_load_incidents_tolerates_hostile_files(tmp_path):
    """Torn/foreign files under telemetry/incidents/ are skipped."""
    folder = str(tmp_path)
    inc_dir = os.path.join(folder, "telemetry", "incidents")
    os.makedirs(inc_dir)
    with open(os.path.join(inc_dir, "incident-1.json"), "w") as f:
        f.write('{"id": 1, "status": "open", "opened_t": 1.0}')
    with open(os.path.join(inc_dir, "incident-2.json"), "w") as f:
        f.write('{"id": 2, "status": "op')  # torn mid-write
    with open(os.path.join(inc_dir, "notes.txt"), "w") as f:
        f.write("not an incident")
    recs = load_incidents(folder)
    assert [r["id"] for r in recs] == [1]
    assert incidents_report(folder) is not None


# -- the live chaos e2e (the PR's acceptance surface) -------------------------

def _chaos_cfg(folder, fault_plan):
    return Config(
        learner_config=Config(algo=Config(name="impala", horizon=8)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=folder,
            total_env_steps=600,
            metrics=Config(every_n_iters=1, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=2,
                inference_fleet=Config(replicas=2),
                gateway=Config(enabled=True, lease_s=10.0),
            ),
            # sensitive thresholds so the ~30 ms injected act delay and
            # the replica kill register within the short run; close fast
            # so the recovery half of the lifecycle is exercised too
            watchdog=Config(
                warmup=4, sustain=1, mad_k=3.0, min_rel=0.2,
                close_windows=3, capture_cooldown_s=0.0,
            ),
            faults=Config(plan=fault_plan),
        ),
    ).extend(base_config())


@pytest.mark.slow
def test_watchdog_chaos_e2e_incident_names_injected_tier(tmp_path):
    """The acceptance run: live SEED session with the gateway, an
    external tenant, a replica kill and an act delay. The watchdog must
    open an incident whose top-ranked cause names an injected tier
    (fleet or gateway — both were injected), with >= 2 correlated
    evidence kinds, an auto-captured artifact on disk, and a clean
    ``why`` render."""
    import zmq

    from surreal_tpu.gateway import GatewayError, GatewaySession
    from surreal_tpu.launch.seed_trainer import SEEDTrainer
    from surreal_tpu.main.launch import main

    folder = str(tmp_path)
    cfg = _chaos_cfg(folder, [
        {"site": "fleet.replica", "kind": "kill_replica", "at": 40},
        {"site": "gateway.session", "kind": "delay", "ms": 30,
         "at": 20, "times": 4},
    ])
    trainer = SEEDTrainer(cfg)
    tenant_acts: list[int] = []
    tenant_errors: list[BaseException] = []
    stop = threading.Event()

    def tenant_loop():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            gateway = getattr(trainer, "_gateway", None)
            if gateway is not None:
                break
            time.sleep(0.1)
        else:
            return
        sess = GatewaySession(
            gateway.address, tenant="external", obs_shape=(1, 4),
            timeout_s=10.0, retries=3,
        )
        while not stop.is_set():
            try:
                actions, info = sess.act(
                    np.random.rand(1, 4).astype(np.float32)
                )
            except (TimeoutError, GatewayError) as e:
                gw = getattr(trainer, "_gateway", None)
                if not stop.is_set() and gw is not None and gw.alive:
                    tenant_errors.append(e)
                return
            tenant_acts.append(int(info["param_version"]))
            time.sleep(0.05)
        try:
            sess.close()
        except zmq.ZMQError:
            pass

    t = threading.Thread(target=tenant_loop, daemon=True)
    t.start()
    try:
        state, metrics = trainer.run()
    finally:
        stop.set()
        t.join(timeout=15)

    assert metrics["time/env_steps"] >= 600
    assert tenant_acts and not tenant_errors
    assert metrics["ops/watchdog_evals"] >= 1.0
    assert metrics["ops/incidents_total"] >= 1.0
    incidents = load_incidents(folder)
    assert incidents, "no persisted incident"
    inc = incidents[0]
    assert inc["causes"], inc
    top = inc["causes"][0]
    assert top["tier"] in ("fleet", "gateway"), inc["causes"]
    ev = inc["evidence"]
    kinds = [k for k in ("faults", "recoveries", "slo_breaches",
                         "exemplars", "dead_tiers") if ev.get(k)]
    assert len(kinds) >= 2, ev
    assert any(
        f.get("site") in ("fleet.replica", "gateway.session")
        for f in ev["faults"]
    ), ev["faults"]
    # the auto-captured flight-recorder artifact exists on disk
    art = inc["artifacts"].get("flightrec")
    assert art and os.path.isdir(art), inc["artifacts"]
    # the lifecycle events rode the telemetry spine
    events = _events(folder)
    assert any(e.get("type") == "incident_open" for e in events)
    # why renders the record cleanly
    assert main(["why", folder]) == 0
    # teardown left no data-plane residue
    assert not glob.glob("/dev/shm/surreal_dp_*")


@pytest.mark.slow
def test_watchdog_chaos_e2e_fault_free_control_zero_incidents(tmp_path):
    """The control arm: the same live topology with NO injected faults,
    DEFAULT watchdog/remediation thresholds, and well-behaved tenant
    load (gateway/loadgen.py steady profile) opens zero incidents AND
    executes zero remediation actions — detectors and actuation alike
    must survive a real noisy run without crying wolf (ISSUE 16's
    no-false-actuation bar)."""
    from surreal_tpu.gateway.loadgen import LoadGenerator
    from surreal_tpu.launch.seed_trainer import SEEDTrainer
    from surreal_tpu.main.launch import main
    from surreal_tpu.session.remediate import load_actions

    folder = str(tmp_path)
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=8)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=folder,
            total_env_steps=600,
            metrics=Config(every_n_iters=1, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=2,
                inference_fleet=Config(replicas=2),
                gateway=Config(enabled=True, lease_s=10.0),
            ),
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
                {"tenant": "steady-1", "profile": "steady",
                 "rate_hz": 5.0},
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
    assert metrics["time/env_steps"] >= 600
    assert metrics["ops/watchdog_evals"] >= 1.0
    assert metrics["ops/incidents_total"] == 0.0
    assert load_incidents(folder) == []
    # the no-false-actuation bar: zero actions, zero suppressions
    assert metrics.get("remediation/actions", 0.0) == 0.0
    assert metrics.get("remediation/suppressed", 0.0) == 0.0
    assert load_actions(folder) == []
    # the benign tenants were actually served
    assert gen_holder and gen_holder[0].report()["loadgen/acts"] > 0
    report = incidents_report(folder)
    assert report is not None and "no incidents recorded" in report
    assert main(["why", folder]) == 0


def _events(folder):
    from surreal_tpu.session.telemetry import _iter_jsonl

    return list(_iter_jsonl(
        os.path.join(folder, "telemetry", "events.jsonl")
    ))


@pytest.mark.parametrize("since_capture_s,captures", [(5.0, 0), (None, 1), (3600.0, 1)])
def test_an_incident_in_the_wake_of_a_capture_does_not_capture_again(
    since_capture_s, captures,
):
    """A capture's fences, its write and its digest disturb the iterations
    after it (found on the chip, PR 44: a benchmark's triggered capture was
    followed by an incident whose own empty capture was the session's last
    ``profile`` event). The incident engine's cooldown counts from the
    newest capture of the session's profiler, an operator's too."""

    class Profiler:
        last_capture_dir = None
        last_capture_t = (
            None if since_capture_s is None else time.time() - since_capture_s
        )
        requested = []

        def request(self, reason, num_iters=None):
            self.requested.append(reason)
            return True

    profiler = Profiler()
    eng = IncidentEngine(profile=profiler, trace_id="tr-test")
    firing = {
        "detector": "liveness", "signal": "engine", "tier": "learner",
        "value": 44.0, "baseline": 30.0, "direction": "high", "t": time.time(),
    }
    eng.observe([firing], make_snap(8))
    assert eng.opened == 1                  # the incident itself is recorded
    assert len(profiler.requested) == captures
    want = "pending" if captures else None
    assert eng._open["artifacts"]["profile"] == want


def test_the_profiler_counts_its_digest_into_the_wake_of_a_capture(tmp_path):
    """``ProfileManager.last_capture_t`` is now while a capture is open or
    its digest is being reduced, and the digest's end after that: on the
    chip the parse of three thousand-step iterations ended 44 s after the
    capture was saved, so a wake counted from the save alone ran out at a
    parse a third longer."""
    from surreal_tpu.session.profile import ProfileManager

    class Quiet:
        def info(self, *a, **k):
            pass

        warning = event = info

    pm = ProfileManager(
        Config(profile=Config(trigger_file=False)), str(tmp_path), Quiet(), Quiet()
    )
    assert pm.last_capture_t is None
    done = threading.Event()
    pm._settled_t = 5.0                     # the save, long ago
    pm._digest = threading.Thread(target=done.wait, daemon=True)
    pm._digest.start()
    assert time.time() - pm.last_capture_t < 1.0
    done.set()
    pm._digest.join()
    assert pm.last_capture_t == 5.0
    pm._reduce({"dir": str(tmp_path / "none")}, None)   # a digest that fails
    assert time.time() - pm.last_capture_t < 1.0
