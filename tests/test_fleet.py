"""Autoscaling act-serving tier (ISSUE 10, distributed/fleet.py): the
replicated InferenceFleet — session-affinity routing, per-replica
coalescing budgets, respawn/backoff lifecycle, autoscale decisions, and
the kill-replica chaos path (workers re-hello to survivors, training
completes, nothing leaks)."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from surreal_tpu.distributed import run_env_worker
from surreal_tpu.distributed.fleet import InferenceFleet
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import BASE_ENV_CONFIG, base_config
from surreal_tpu.utils import faults


@pytest.fixture(autouse=True)
def _reset_registry():
    yield
    faults.configure(None)  # never leak a plan into the next test


def _act_fn(obs):
    b = obs.shape[0]
    return (
        np.random.randint(0, 2, size=b),
        {"logp": np.full(b, -np.log(2), np.float32)},
    )


def test_fleet_affinity_routes_and_serves_chunks():
    """4 workers over 2 replicas: every worker routes via rendezvous
    affinity, both replicas get a share (and their min_batch budget is
    that share, not the global fleet size), chunks flow through the
    facade queue, and set_act_fn broadcasts a version bump."""
    fleet = InferenceFleet(_act_fn, num_workers=4, replicas=2, unroll_length=8)
    env_cfg = Config(name="gym:CartPole-v1", num_envs=2).extend(BASE_ENV_CONFIG)
    stop = threading.Event()
    workers = []
    try:
        assign = [fleet.replica_of(w) for w in range(4)]
        assert set(assign) == {0, 1}, assign  # both replicas used
        for i, srv in enumerate(fleet._replicas):
            # per-REPLICA coalescing budget = its affinity share
            assert srv.min_batch == max(1, assign.count(i))
        for i in range(4):
            w = threading.Thread(
                target=run_env_worker,
                args=(env_cfg, fleet.address_for(i), i),
                kwargs={"stop_event": stop, "max_steps": 600},
                daemon=True,
            )
            w.start()
            workers.append(w)
        chunk = fleet.chunks.get(timeout=30)
        assert chunk["obs"].shape == (8, 2, 4)
        fleet.set_act_fn(_act_fn)
        assert fleet.version == 1
        assert all(s.version == 1 for s in fleet.servers())
        stats = fleet.queue_stats()
        assert stats["fleet/replicas_live"] == 2.0
        tier = fleet.tier_event()
        assert set(tier["replicas"]) == {"0", "1"}
    finally:
        stop.set()
        fleet.close()


def test_fleet_rendezvous_remap_only_moves_dead_replicas_workers():
    """Session affinity under death: killing one replica remaps ONLY its
    workers (rendezvous hashing) — survivors' workers keep their
    assignment, so their trajectory streams/slabs keep one owner."""
    fleet = InferenceFleet(_act_fn, num_workers=16, replicas=3, unroll_length=4)
    try:
        before = {w: fleet.replica_of(w) for w in range(16)}
        victim = before[0]
        # simulate death: close the victim so its serve thread exits
        fleet._replicas[victim].close()
        for _ in range(50):
            if victim not in fleet._alive_slots():
                break
            time.sleep(0.05)
        after = {w: fleet.replica_of(w) for w in range(16)}
        for w in range(16):
            if before[w] == victim:
                assert after[w] != victim  # remapped to a survivor
            else:
                assert after[w] == before[w]  # unaffected
    finally:
        fleet.close()


def test_fleet_supervise_respawns_dead_replica_with_backoff():
    """A dead replica respawns IN PLACE (same fixed address) under the
    exponential-backoff schedule, version-synced to the fleet counter so
    its transitions don't read as acted by an ancient policy."""
    fleet = InferenceFleet(
        _act_fn, num_workers=2, replicas=2, unroll_length=4,
        respawn_backoff_s=0.05, respawn_backoff_cap_s=0.2,
    )
    try:
        fleet.set_act_fn(_act_fn)  # version 1
        addr = fleet._addresses[0]
        fleet._replicas[0].close()
        for _ in range(100):
            if not fleet._replicas[0].alive:
                break
            time.sleep(0.02)
        fleet.supervise()
        assert fleet.respawns == 1
        assert fleet.respawn_backoff_s == pytest.approx(0.05)
        srv = fleet._replicas[0]
        assert srv.alive and srv.version == fleet.version
        assert fleet._addresses[0] == addr  # bound in place
    finally:
        fleet.close()


def test_fleet_autoscale_up_down_bounded_by_cooldown_and_limits():
    """Autoscale reads the fleet-mean serve EWMA: above the up-threshold
    adds a replica (to max_replicas), below the down-threshold drains
    one (to min_replicas); decisions are cooldown-spaced."""
    fleet = InferenceFleet(
        _act_fn, num_workers=4, replicas=1, unroll_length=4,
        autoscale=True, min_replicas=1, max_replicas=2,
        scale_up_serve_ms=10.0, scale_down_serve_ms=1.0,
        scale_cooldown_s=0.0,
    )
    try:
        assert fleet.maybe_autoscale() is None  # no serve samples yet
        fleet.servers()[0]._serve_ms_ewma = 50.0
        assert fleet.maybe_autoscale() == "up"
        assert len(fleet._alive_slots()) == 2
        for s in fleet.servers():
            s._serve_ms_ewma = 50.0
        assert fleet.maybe_autoscale() is None  # at max_replicas
        for s in fleet.servers():
            s._serve_ms_ewma = 0.5
        assert fleet.maybe_autoscale() == "down"
        assert len(fleet._alive_slots()) == 1
        fleet.servers()[0]._serve_ms_ewma = 0.5
        assert fleet.maybe_autoscale() is None  # at min_replicas
        assert fleet.scale_ups == 1 and fleet.scale_downs == 1
        # cooldown actually spaces decisions
        fleet.scale_cooldown_s = 60.0
        fleet._last_scale_at = time.monotonic()
        fleet.servers()[0]._serve_ms_ewma = 50.0
        assert fleet.maybe_autoscale() is None
    finally:
        fleet.close()


def test_fleet_kill_replica_chaos_workers_rehello_to_survivor(
    tmp_path, monkeypatch
):
    """The chaos done-bar: `kill_replica` mid-training kills one of two
    replicas; its worker gets no reply, times out, dies, and the
    supervisor respawns it against a SURVIVOR (address_for over alive
    replicas); the fleet respawns the replica in place; training goes on;
    no /dev/shm segment survives the run.

    The order is held, not raced: a first in-place respawn is immediate,
    so left alone the replica is back long before its worker's two
    seconds of silence run out, and whether the worker then dies at all
    is the scheduler's choice (a request still queued in its DEALER is
    served by the respawned replica; one the corpse had taken is lost).
    Here the fleet's supervisor leaves a dead replica dead until the
    worker's respawn has been counted, as a crash-looping replica under
    backoff would. The run ends on the outcome, not on a step budget (a
    survivor's worker alone reaches any small budget inside those two
    seconds) or a short deadline: a minute bounds the wait."""
    from surreal_tpu.launch.seed_trainer import SEEDTrainer

    hold = threading.Event()
    hold.set()
    routes = []  # (worker, slot routed to, alive slots, dead slots) per spawn
    real_supervise = InferenceFleet.supervise
    real_address_for = InferenceFleet.address_for

    def _dead_slots(fleet):
        return tuple(
            i for i, s in enumerate(fleet._replicas)
            if s is not None and not s.alive
        )

    def supervise(fleet):
        if hold.is_set() and _dead_slots(fleet):
            return
        real_supervise(fleet)

    def address_for(fleet, worker_id):
        address = real_address_for(fleet, worker_id)
        routes.append((
            worker_id, fleet._addresses.index(address),
            tuple(fleet._alive_slots()), _dead_slots(fleet),
        ))
        return address

    monkeypatch.setattr(InferenceFleet, "supervise", supervise)
    monkeypatch.setattr(InferenceFleet, "address_for", address_for)

    assert not glob.glob("/dev/shm/surreal_dp_*")
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=8)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=str(tmp_path),
            total_env_steps=10**9,  # never reached: `outcome` stops the run
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=2,
                worker_silence_s=2.0,
                respawn_backoff_s=0.05,
                inference_fleet=Config(
                    replicas=2, respawn_backoff_s=0.05,
                ),
            ),
            faults=Config(plan=[
                {"site": "fleet.replica", "kind": "kill_replica", "at": 40},
            ]),
        ),
    ).extend(base_config())
    trainer = SEEDTrainer(cfg)
    give_up = time.monotonic() + 60.0
    respawned_at = []  # env steps when the in-place respawn was counted

    def outcome(_iteration, m):
        if m["workers/respawns"] >= 1.0:
            hold.clear()  # the worker is on a survivor: the replica may return
        if m["fleet/respawns"] >= 1.0 and not respawned_at:
            respawned_at.append(m["time/env_steps"])
        served = m["server/pickle_workers"] + m["server/shm_workers"]
        return bool(
            respawned_at
            and served >= 2.0
            and m["time/env_steps"] >= max(700, respawned_at[0] + 200)
        ) or time.monotonic() > give_up

    state, metrics = trainer.run(on_metrics=outcome)
    # the killed replica's worker died (reply timeout) and was respawned
    # against a survivor: routed to an alive slot while the corpse lay dead
    assert metrics["workers/respawns"] >= 1.0
    assert len(routes) > 2, routes  # after the two first spawns
    _worker, slot, alive, dead = routes[2]
    assert dead and slot in alive, routes
    # then the replica was respawned in place: every slot the fleet holds
    # is alive again, whatever remediation added or drained meanwhile (an
    # incident may scale the fleet while the replica lies dead)
    assert metrics["fleet/respawns"] >= 1.0
    assert metrics["fleet/replicas_live"] == (
        2.0 + metrics["fleet/scale_ups"] - metrics["fleet/scale_downs"]
    )
    # both workers are served, and training went on past the respawn
    assert (
        metrics["server/pickle_workers"] + metrics["server/shm_workers"]
    ) >= 2.0
    assert metrics["time/env_steps"] >= max(700, respawned_at[0] + 200)
    assert not glob.glob("/dev/shm/surreal_dp_*"), "replica cycle leaked shm"
    # the injection is on the record (telemetry mirror), and the tier
    # event stream shows the fleet alive at the end (a slot remediation
    # scaled down again reads "drained")
    events = []
    with open(os.path.join(str(tmp_path), "telemetry", "events.jsonl")) as f:
        for line in f:
            if line.strip():
                events.append(json.loads(line))
    fired = [e for e in events if e.get("type") == "fault"]
    assert any(e.get("site") == "fleet.replica" for e in fired)
    tiers = [e for e in events if e.get("type") == "serving_tier"]
    assert tiers
    states = [r.get("state") for r in tiers[-1]["replicas"].values()]
    assert states.count("alive") >= 2 and set(states) <= {"alive", "drained"}


def test_fleet_lifecycle_fds_steady_over_kill_respawn_cycles():
    """Descriptor hygiene: full fleet lifecycles — including a replica
    kill + in-place respawn each cycle — keep /proc/self/fd steady (the
    experience-plane leak-test discipline: small slack for allocator
    noise, no growth per cycle)."""
    fd_counts = []
    for _ in range(3):
        fleet = InferenceFleet(
            _act_fn, num_workers=2, replicas=2, unroll_length=4,
            respawn_backoff_s=0.01,
        )
        fleet._replicas[0].close()
        for _ in range(100):
            if not fleet._replicas[0].alive:
                break
            time.sleep(0.02)
        time.sleep(0.02)
        fleet.supervise()
        assert fleet.respawns == 1
        fleet.close()
        fd_counts.append(len(os.listdir("/proc/self/fd")))
    assert fd_counts[2] <= fd_counts[0] + 2, fd_counts


def test_fleet_kill_replica_releases_shm_slabs():
    """Slab hygiene under replica death: shm-negotiated workers leave
    slabs on the replica; when the replica dies and the fleet respawns
    it, close() of the corpse unlinks every server-owned segment — no
    /dev/shm residue after the cycle or after fleet.close()."""
    assert not glob.glob("/dev/shm/surreal_dp_*")
    faults.configure([
        {"site": "fleet.replica", "kind": "kill_replica", "at": 30},
    ])
    fleet = InferenceFleet(
        _act_fn, num_workers=2, replicas=2, unroll_length=4,
        transport="auto", respawn_backoff_s=0.05,
    )
    env_cfg = Config(name="gym:CartPole-v1", num_envs=2).extend(BASE_ENV_CONFIG)
    stop = threading.Event()
    try:
        workers = []
        for i in range(2):
            w = threading.Thread(
                target=run_env_worker,
                args=(env_cfg, fleet.address_for(i), i),
                kwargs={
                    "stop_event": stop, "max_steps": 4000,
                    "transport": "shm", "server_silence_s": 3.0,
                },
                daemon=True,
            )
            w.start()
            workers.append(w)
        deadline = time.time() + 30
        while time.time() < deadline:
            if glob.glob("/dev/shm/surreal_dp_*"):
                break
            time.sleep(0.05)
        assert glob.glob("/dev/shm/surreal_dp_*"), "shm never negotiated"
        # wait for the chaos kill, then supervise until the respawn
        deadline = time.time() + 30
        while time.time() < deadline and len(fleet._alive_slots()) == 2:
            time.sleep(0.05)
        assert len(fleet._alive_slots()) == 1, "kill_replica never fired"
        time.sleep(0.1)
        fleet.supervise()
        assert len(fleet._alive_slots()) == 2
        assert fleet.respawns == 1
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=10)
        fleet.close()
    assert not glob.glob("/dev/shm/surreal_dp_*"), "fleet close leaked shm"
