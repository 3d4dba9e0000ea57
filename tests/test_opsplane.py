"""Run-wide live ops plane (ISSUE 13): pusher->aggregator wire merge,
trace stamping, cadence bounds + counted chaos drops, bad-frame
hardening, DEAD-tier rendering, per-tenant SLO breaches with error-
budget exhaustion triggering the flight recorder, fault correlation in
the recorder rings, and the ``surreal_tpu top`` CLI — plus the slow
chaos e2e that runs a live SEED session through a replica kill and a
gateway latency fault and reads the incident back out of the plane."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.opsplane import (
    FlightRecorder,
    OpsAggregator,
    OpsPusher,
    load_snapshot,
    snapshot_path,
    top_report,
)
from surreal_tpu.session.slo import SLOTracker
from surreal_tpu.utils import faults


@pytest.fixture(autouse=True)
def _reset_registry():
    yield
    faults.configure(None)  # never leak a plan into the next test


def _wait_for(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


class _Events:
    """A Tracer.event stand-in that records (type, fields) calls."""

    def __init__(self):
        self.rows = []

    def __call__(self, type_, **fields):
        self.rows.append((type_, fields))

    def of(self, type_):
        return [f for t, f in self.rows if t == type_]


# -- wire merge ---------------------------------------------------------------

def test_pusher_aggregator_roundtrip_merges_tiers_and_stamps_trace(tmp_path):
    """Two wire tiers + two learner-local tiers merge into ONE snapshot:
    per-tier rows keep their own gauges/hops/body, hop percentiles from
    every tier land in the merged ``hops`` map, the run trace id stamps
    the snapshot, and the file round-trips through load_snapshot."""
    ev = _Events()
    agg = OpsAggregator(str(tmp_path), trace_id="tracecafe", on_event=ev)
    try:
        gw = OpsPusher(agg.address, "gateway", trace_id="tracecafe",
                       min_interval_s=0.0)
        rep = OpsPusher(agg.address, "fleet.replica0", trace_id="tracecafe",
                        min_interval_s=0.0)
        assert gw.push(
            gauges={"gateway/acts": 7.0},
            hops={"gateway_act_ms": {"p50": 1.0, "p90": 2.0, "p99": 3.0,
                                     "n": 7}},
            body={"tenants": {"alpha": {"acts": 7, "throttled": 0}}},
            force=True,
        )
        assert rep.push(
            gauges={"server/requests": 4.0},
            hops={"serve_batch_ms": {"p50": 0.5, "p90": 0.9, "p99": 1.1,
                                     "n": 4}},
            force=True,
        )
        assert _wait_for(
            lambda: {"gateway", "fleet.replica0"} <= set(agg._tiers)
        ), "wire rows never reached the aggregator"
        agg.push_local("learner", gauges={"perf/mfu": 0.31})
        agg.push_local("param_fanout", gauges={"version": 5.0})
        snap = agg.snapshot(iteration=3, env_steps=300)
        gw.close()
        rep.close()
    finally:
        agg.close()

    assert snap["trace"] == "tracecafe"
    assert snap["iteration"] == 3 and snap["env_steps"] == 300
    tiers = snap["tiers"]
    assert {"gateway", "fleet.replica0", "learner", "param_fanout"} <= set(tiers)
    # each row keeps its identity and the wire rows carry their trace
    assert tiers["gateway"]["trace"] == "tracecafe"
    assert tiers["gateway"]["body"]["tenants"]["alpha"]["acts"] == 7
    assert tiers["fleet.replica0"]["gauges"]["server/requests"] == 4.0
    assert not tiers["gateway"]["dead"]
    # hop percentiles from BOTH wire tiers merged into one map
    assert snap["hops"]["gateway_act_ms"]["p99"] == 3.0
    assert snap["hops"]["serve_batch_ms"]["n"] == 4
    # atomic file write round-trips
    loaded = load_snapshot(str(tmp_path))
    assert loaded is not None and loaded["seq"] == snap["seq"]
    assert os.path.exists(snapshot_path(str(tmp_path)))
    # the cadence-bounded pointer event fired, never silent
    assert ev.of("ops_snapshot")[0]["tiers"] == 4


def test_pusher_cadence_bound_and_chaos_drop_counted():
    """The cadence bound is NOT a drop (returns False, counted nowhere);
    a chaos ``ops.push`` drop_frame IS counted in ``dropped``."""
    agg = OpsAggregator(None)
    try:
        p = OpsPusher(agg.address, "gateway", min_interval_s=60.0)
        assert p.push(gauges={"gateway/acts": 1.0})
        assert not p.push(gauges={"gateway/acts": 2.0})  # cadence-bounded
        assert p.pushes == 1 and p.dropped == 0
        faults.configure(
            [{"site": "ops.push", "kind": "drop_frame", "at": 0, "times": 1}]
        )
        assert not p.push(gauges={"gateway/acts": 3.0}, force=True)
        assert p.dropped == 1  # chaos drop: counted, never silent
        assert p.push(gauges={"gateway/acts": 4.0}, force=True)
        p.close()
    finally:
        agg.close()


def test_aggregator_counts_hostile_rows_as_bad_frames():
    """Garbage on the ops wire — non-JSON bytes, a JSON row without a
    tier — is counted in ``bad_frames`` and never unwinds the receiver
    thread; well-formed rows after the garbage still land."""
    import zmq

    agg = OpsAggregator(None)
    try:
        sock = zmq.Context.instance().socket(zmq.PUSH)
        sock.setsockopt(zmq.LINGER, 0)
        sock.connect(agg.address)
        sock.send(b"\xff\xfe not json at all")
        sock.send(json.dumps({"no_tier": 1}).encode())
        sock.send(json.dumps({"tier": ["not", "a", "string"]}).encode())
        assert _wait_for(lambda: agg.bad_frames >= 3)
        sock.send(json.dumps({"tier": "gateway", "gauges": {}}).encode())
        assert _wait_for(lambda: "gateway" in agg._tiers)
        snap = agg.snapshot()
        assert snap["bad_frames"] >= 3
        assert agg.gauges()["ops/bad_frames"] >= 3.0
        sock.close(0)
    finally:
        agg.close()


def test_silent_tier_rendered_dead_in_snapshot_and_top(tmp_path):
    """The heartbeat rule on the ops wire: a tier silent for 3x its own
    declared cadence is DEAD in the snapshot and called out by top."""
    agg = OpsAggregator(str(tmp_path))
    try:
        agg.push_local("experience.shard0", gauges={"ingested_rows": 1.0},
                       cadence_s=0.01)
        agg.push_local("learner", gauges={"perf/mfu": 0.3})
        time.sleep(0.1)  # > 3x the shard's 10ms cadence, << learner's
        snap = agg.snapshot(iteration=1, env_steps=10)
    finally:
        agg.close()
    assert snap["tiers"]["experience.shard0"]["dead"] is True
    assert snap["tiers"]["learner"]["dead"] is False
    report = top_report(snap, str(tmp_path))
    assert "DEAD (> 3x cadence)" in report
    assert "experience.shard0" in report and "stopped pushing" in report


# -- SLOs and the flight recorder ---------------------------------------------

def test_slo_breach_exhausts_budget_and_dumps_flight_recorder(tmp_path):
    """A declared act-RTT objective breached repeatedly: every breached
    window is a counted slo_breach event, the rolling error budget
    exhausts (edge-triggered ONCE), and the exhaustion dumps the flight
    recorder to telemetry/flightrec/slo/ with the pre-incident ring."""
    ev = _Events()
    agg = OpsAggregator(
        str(tmp_path), trace_id="deadbeef",
        slo_cfg={"enabled": True, "budget_windows": 4, "budget": 0.5,
                 "act_rtt_p99_ms": 1.0},
        on_event=ev,
    )
    try:
        for i in range(3):
            agg.push_local(
                "gateway",
                hops={"gateway_act_ms": {"p50": 5.0, "p90": 9.0,
                                         "p99": 50.0, "n": 10}},
                body={"tenants": {"alpha": {"acts": 10 * (i + 1),
                                            "throttled": 0}}},
            )
            snap = agg.snapshot(iteration=i, env_steps=i * 10)
    finally:
        agg.close()

    breaches = ev.of("slo_breach")
    assert len(breaches) == 3  # every breached window counted
    assert breaches[0]["tenant"] == "alpha"
    assert breaches[0]["objective"] == "act_rtt_p99_ms"
    assert breaches[0]["measured"] == 50.0
    # budget 0.5 over 4 windows -> 2 breaches allowed; the 2nd exhausts
    row = snap["slo"]["alpha"]["act_rtt_p99_ms"]
    assert row["breached"] and row["exhausted"]
    assert snap["slo_counters"]["slo/exhaustions"] == 1.0  # edge, not level
    # the exhaustion dumped the recorder with the PRE-incident snapshots
    slo_dir = os.path.join(str(tmp_path), "telemetry", "flightrec", "slo")
    assert os.path.isdir(slo_dir)
    with open(os.path.join(slo_dir, "snapshots.jsonl")) as f:
        dumped = [json.loads(line) for line in f if line.strip()]
    assert dumped and dumped[0]["trace"] == "deadbeef"
    assert ev.of("ops_flightrec")[0]["trigger"] == "slo"
    # the top view names the incident
    report = top_report(snap, str(tmp_path))
    assert "EXHAUSTED" in report and "alpha" in report


def test_slo_no_data_is_not_a_breach_and_throttle_rate_uses_deltas():
    """An idle window (no hop samples, no new acts) evaluates to NO
    verdict — absence of data must not spend error budget. The throttle
    objective measures per-window counter DELTAS, not lifetime totals."""
    slo = SLOTracker({"throttle_rate": 0.5, "act_rtt_p99_ms": 10.0})
    # window 1: tenant served 10 acts, 0 throttles -> rate 0, no breach
    table, newly = slo.evaluate(
        {"alpha": {"acts": 10, "throttled": 0}}, hops={}, derived={})
    assert table["alpha"]["throttle_rate"]["breached"] is False
    assert "act_rtt_p99_ms" not in table["alpha"]  # no hop data: no verdict
    # window 2: idle (counters unchanged) -> no throttle verdict either
    table, newly = slo.evaluate(
        {"alpha": {"acts": 10, "throttled": 0}}, hops={}, derived={})
    assert "alpha" not in table
    # window 3: 2 new acts, 8 new throttles -> 0.8 > 0.5, breached —
    # lifetime totals (10 acts vs 8 throttles) would have said 0.44
    table, newly = slo.evaluate(
        {"alpha": {"acts": 12, "throttled": 8}}, hops={}, derived={})
    assert table["alpha"]["throttle_rate"]["measured"] == 0.8
    assert table["alpha"]["throttle_rate"]["breached"] is True
    assert slo.breaches == 1 and not newly


def test_flight_recorder_correlates_faults_and_cools_down(tmp_path):
    """The recorder's rings carry the minutes BEFORE the incident: a
    dump after a fault holds both the pre-fault snapshots and the fault
    event; a second dump inside the cooldown is suppressed (a chaos
    storm must not become an IO fault of its own)."""
    rec = FlightRecorder(str(tmp_path), ring=8, min_dump_interval_s=30.0)
    for i in range(12):  # overflow the ring: only the last 8 survive
        rec.record_snapshot({"type": "ops_snapshot", "seq": i, "trace": "t1"})
    rec.record_event("fault", {"site": "fleet.replica", "kind": "kill"})
    rec.record_event("recovery", {"reason": "respawn"})
    out = rec.dump("fault")
    assert out is not None and out.endswith(os.path.join("flightrec", "fault"))
    assert rec.dump("fault") is None  # cooldown
    assert rec.dumps == 1
    with open(os.path.join(out, "snapshots.jsonl")) as f:
        snaps = [json.loads(line) for line in f]
    assert [s["seq"] for s in snaps] == list(range(4, 12))  # bounded ring
    with open(os.path.join(out, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    assert {e["kind"] for e in events} == {"fault", "recovery"}
    # the fault spec's own kind survives as the detail field (it must
    # not clobber the recorder's event kind)
    assert events[0]["site"] == "fleet.replica"
    assert events[0]["detail"] == "kill"
    with open(os.path.join(out, "meta.json")) as f:
        assert json.load(f)["trigger"] == "fault"


# -- hostile files and the CLI ------------------------------------------------

def test_load_snapshot_tolerates_missing_truncated_and_garbage(tmp_path):
    """The reader's hostile shapes: no file, a truncated JSON text, bytes
    cut inside a UTF-8 sequence, a non-dict payload — all -> None, and
    top renders the no-snapshot message instead of crashing."""
    folder = str(tmp_path)
    assert load_snapshot(folder) is None
    path = snapshot_path(folder)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for hostile in (
        b'{"type": "ops_snapshot", "tiers": {"gatew',  # torn mid-write
        '{"t": "café"}'.encode()[:-1],            # cut inside UTF-8
        b"[1, 2, 3]",                                   # parses, not a dict
        b"",
    ):
        with open(path, "wb") as f:
            f.write(hostile)
        assert load_snapshot(folder) is None, hostile
    report = top_report(load_snapshot(folder), folder)
    assert "no ops snapshot" in report


def test_top_cli_once_renders_snapshot_and_fails_cleanly(tmp_path, capsys):
    """``surreal_tpu top <folder> --once``: rc 2 with a message when no
    snapshot exists, rc 0 rendering the live view once one does."""
    from surreal_tpu.main.launch import main

    assert main(["top", str(tmp_path / "missing"), "--once"]) == 2
    folder = str(tmp_path)
    assert main(["top", folder, "--once"]) == 2
    assert "no ops snapshot" in capsys.readouterr().out
    agg = OpsAggregator(folder, trace_id="feedbead")
    try:
        agg.push_local("learner", gauges={"perf/mfu": 0.25})
        agg.snapshot(iteration=9, env_steps=900)
    finally:
        agg.close()
    assert main(["top", folder, "--once"]) == 0
    out = capsys.readouterr().out
    assert "run snapshot" in out and "feedbead" in out
    assert "learner" in out and "iteration 9" in out


# -- the chaos e2e (the PR's acceptance surface) ------------------------------

@pytest.mark.slow
def test_ops_plane_chaos_e2e(tmp_path):
    """A live SEED run with the gateway, a tight act-RTT SLO, a replica
    kill and a gateway latency fault: the run finishes with zero lost
    tenant sessions, the affected tenant's breach is counted, the flight
    recorder dumped with pre-fault snapshots and the fault event
    correlated by trace id, and ``top --once`` renders the incident."""
    import zmq

    from surreal_tpu.gateway import GatewayError, GatewaySession
    from surreal_tpu.launch.seed_trainer import SEEDTrainer
    from surreal_tpu.main.launch import main

    folder = str(tmp_path)
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=8)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=folder,
            total_env_steps=600,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=2,
                inference_fleet=Config(replicas=2),
                gateway=Config(enabled=True, lease_s=10.0),
            ),
            # an unreachable act-RTT target: every served window breaches,
            # the budget exhausts mid-run -> the "slo" incident dump
            slo=Config(act_rtt_p99_ms=0.0001, budget_windows=4, budget=0.25),
            faults=Config(plan=[
                {"site": "fleet.replica", "kind": "kill_replica", "at": 40},
                {"site": "gateway.session", "kind": "delay", "ms": 30,
                 "at": 20, "times": 2},
            ]),
        ),
    ).extend(base_config())
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
                # a session lost while the gateway LIVES is a failure;
                # an act cut off by the end-of-run teardown is not
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
    assert tenant_acts, "the external tenant never got an act served"
    assert not tenant_errors, f"tenant session lost: {tenant_errors!r}"
    # the plane aggregated every tier and counted the tenant's breaches
    assert metrics["ops/snapshots"] >= 1.0
    assert metrics["ops/tiers"] >= 3.0
    assert metrics["slo/breaches"] >= 1.0
    assert metrics["ops/flightrec_dumps"] >= 1.0
    snap = load_snapshot(folder)
    assert snap is not None and snap["trace"], "no live snapshot on disk"
    breach = [
        e for e in _events(folder)
        if e.get("type") == "slo_breach" and e.get("tenant") == "external"
    ]
    assert breach, "no counted slo_breach for the affected tenant"
    # the chaos firings dumped the recorder; the dump's events carry the
    # fault, its snapshots carry the run's trace id (correlated incident)
    dump_dirs = glob.glob(os.path.join(folder, "telemetry", "flightrec", "*"))
    assert dump_dirs, "no flight-recorder dump"
    fault_dir = os.path.join(folder, "telemetry", "flightrec", "fault")
    assert os.path.isdir(fault_dir)
    with open(os.path.join(fault_dir, "events.jsonl")) as f:
        rec_events = [json.loads(line) for line in f if line.strip()]
    assert any(
        e["kind"] == "fault" and e.get("site") == "fleet.replica"
        for e in rec_events
    )
    with open(os.path.join(fault_dir, "snapshots.jsonl")) as f:
        rec_snaps = [json.loads(line) for line in f if line.strip()]
    assert rec_snaps and all(s["trace"] == snap["trace"] for s in rec_snaps)
    # the live view renders the post-incident world
    assert main(["top", folder, "--once"]) == 0
    # teardown left no data-plane residue
    assert not glob.glob("/dev/shm/surreal_dp_*")


def _events(folder):
    from surreal_tpu.session.telemetry import _iter_jsonl

    return list(_iter_jsonl(
        os.path.join(folder, "telemetry", "events.jsonl")
    ))


def test_a_pause_of_the_learner_threads_own_making_is_not_silence(tmp_path):
    """A profiler capture's stop holds the learner thread for as long as
    its trace takes to write (a minute at a thousand acting steps an
    iteration): the tiers that live on that thread are excused that long,
    rows that came over the wire are not (launch/hooks.py calls
    ``excuse_pause`` around ``profile.tick``)."""
    import time

    from surreal_tpu.session.opsplane import OpsAggregator

    agg = OpsAggregator(None)
    try:
        agg.push_local("engine", gauges={"engine/occupancy": 0.5}, cadence_s=0.01)
        with agg._lock:
            agg._tiers["remote"] = {
                "row": {"tier": "remote", "cadence_s": 0.01, "gauges": {}},
                "t_recv": time.monotonic(),
            }
        time.sleep(0.1)
        assert agg.snapshot()["tiers"]["engine"]["dead"]
        agg.excuse_pause(10.0)
        tiers = agg.snapshot()["tiers"]
        assert not tiers["engine"]["dead"] and tiers["remote"]["dead"]
        agg.excuse_pause(0.0)
    finally:
        agg.close()
