"""Sharded experience plane (ISSUE 8): wire codec, shard-server record
equivalence vs the in-process replay, hash routing + watermarks, the
never-blocking sampler, chaos coverage (kill_shard / delay_sample /
corrupt_wire_frame), and the off-policy + SEED trainer integrations.

Record-equivalence contracts pinned here:

- uniform sampling: remote plane (one shard) BIT-EQUAL to the in-process
  ``UniformReplay`` for the same insert stream and keys, on all three
  negotiated transports — the shard reconstructs the caller's PRNG key
  and draws with the same ``jax.random.randint`` (vmapped per PR 4's
  ``sample_many`` contract).
- prioritized: same drawn indices in practice, weights within rtol 1e-4,
  priority vectors after wire-shipped batched updates within atol 1e-6 —
  the np-vs-jnp float32 cumsum reduction-order budget (documented in
  ``experience/shard.py``).
- strict-mode training (``overlap_rollouts=false``): two identical
  remote runs produce identical final metrics — the watermark deferral
  at the shard makes the pipeline's record deterministic.
"""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.experience import wire
from surreal_tpu.experience.plane import ExperiencePlane
from surreal_tpu.experience.sender import shard_of_slot
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.utils import faults


def _example():
    return {
        "obs": np.zeros((3,), np.float32),
        "action": np.zeros((2,), np.float32),
        "reward": np.zeros((), np.float32),
    }


def _make_plane(transport="tcp", kind="uniform", shards=1, example=None,
                **over):
    cfg = {
        "num_shards": shards, "shard_mode": "thread",
        "transport": transport, "ack_timeout_s": 1.0,
        "sample_timeout_s": 2.0, "watermark_timeout_s": 1.0,
        "respawn_backoff_s": 0.05, "respawn_backoff_cap_s": 0.5,
    }
    cfg.update(over)
    return ExperiencePlane(
        kind=kind, example=example or _example(), capacity=64 * shards,
        batch_size=8 * shards, start_sample_size=1, updates_per_iter=2,
        num_slots=4, max_insert_rows=16, cfg=cfg,
        base_key=jax.random.key(7), prefetch=False, device_put=False,
    )


def _rows(rng, n=12):
    return {
        "obs": rng.normal(size=(n, 3)).astype(np.float32),
        "action": rng.normal(size=(n, 2)).astype(np.float32),
        "reward": rng.normal(size=(n,)).astype(np.float32),
    }


# -- codec --------------------------------------------------------------------

def test_plane_spec_pack_unpack_roundtrip():
    spec = wire.PlaneSpec.from_example(
        {"obs": np.zeros((3,), np.float32),
         "behavior": {"mean": np.zeros((2,), np.float32)},
         "done": np.zeros((), bool)}
    )
    # canonical (sorted, flattened) field order is the cross-process
    # layout contract
    assert spec.names() == ["behavior/mean", "done", "obs"]
    rng = np.random.default_rng(0)
    batch = {
        "behavior/mean": rng.normal(size=(5, 2)).astype(np.float32),
        "done": rng.random(5) > 0.5,
        "obs": rng.normal(size=(5, 3)).astype(np.float32),
    }
    out = spec.unpack(spec.pack(batch, 5), 5)
    for k in batch:
        assert np.array_equal(out[k], batch[k]), k
    nested = wire.unflatten_fields(batch)
    assert set(nested["behavior"]) == {"mean"}


def test_wire_frames_roundtrip():
    f = wire.encode_insert(3, 7, 1, flags=0, t_send=1.25, body=b"xyz")
    kind, obj = wire.decode_payload(f)
    assert kind == "insert" and obj["seq"] == 3 and obj["n"] == 7
    assert bytes(obj["body"]) == b"xyz"
    kind, obj = wire.decode_payload(wire.encode_insert_ok(3, 99))
    assert kind == "insert_ok" and obj["ingested_rows"] == 99
    kind, obj = wire.decode_payload(
        wire.encode_sample(5, 8, 40, 0.5, 2, b"k" * 16, nkeys=2)
    )
    assert kind == "sample" and obj["watermark"] == 40 and obj["nkeys"] == 2
    idx = np.arange(4, dtype=np.uint32)
    prio = np.ones(4, np.float32)
    kind, obj = wire.decode_payload(wire.encode_prio(1, idx, prio))
    assert kind == "prio" and np.array_equal(np.asarray(obj["idx"]), idx)
    # pickle fallback dicts route through the same decoder
    kind, obj = wire.decode_payload(
        wire.encode_pickle_msg({"kind": "insert", "seq": 1})
    )
    assert kind == "msg" and obj["kind"] == "insert"


def test_hash_route_is_deterministic_and_covers_small_fleets():
    # the first num_shards slots must not all collapse onto one shard
    # (the crc32-of-ASCII-digits pathology this function exists to avoid)
    for S in (2, 4):
        assert len({shard_of_slot(i, S) for i in range(S * 2)}) == S
    assert [shard_of_slot(i, 2) for i in range(8)] == [
        shard_of_slot(i, 2) for i in range(8)
    ]


# -- record equivalence -------------------------------------------------------

@pytest.mark.parametrize("transport", ["shm", "tcp", "pickle"])
def test_remote_uniform_bit_equal_in_process(transport):
    """The acceptance contract: one-shard remote plane == in-process
    UniformReplay, bit for bit, for the same insert stream and keys."""
    from surreal_tpu.replay.uniform import UniformReplay

    plane = _make_plane(transport=transport)
    try:
        rep = UniformReplay(Config(
            kind="uniform", capacity=64, batch_size=8, start_sample_size=1
        ))
        state = rep.init({k: jnp.asarray(v) for k, v in _example().items()})
        rng = np.random.default_rng(0)
        for _ in range(3):
            rows = _rows(rng)
            wm = plane.sender.send_rows(rows, np.arange(12) % 4)
            state = rep.insert(
                state, {k: jnp.asarray(v) for k, v in rows.items()}
            )
        for probe in range(2):
            key = jax.random.fold_in(jax.random.key(42), probe)
            batch, info = plane.sampler.fetch_batch(key, 0.0, wm)
            _, ref_batch, ref_info = rep.sample(state, key)
            assert np.array_equal(
                np.asarray(ref_info["idx"]), info["shard_idx"][0]
            )
            for k in ref_batch:
                assert np.array_equal(np.asarray(ref_batch[k]), batch[k]), k
        assert plane.sender.links[0].transport == transport
    finally:
        plane.close()


def test_remote_prioritized_convergence_equivalence():
    """Prioritized arm: same stratified draws in practice, IS weights
    within rtol 1e-4, and the shard's priority vector after wire-shipped
    BATCHED updates matches the in-process one within atol 1e-6 (the
    np-vs-jnp f32 cumsum budget)."""
    from surreal_tpu.replay.prioritized import PrioritizedReplay

    plane = _make_plane(transport="shm", kind="prioritized")
    try:
        rep = PrioritizedReplay(Config(
            kind="prioritized", capacity=64, batch_size=8,
            start_sample_size=1, priority_alpha=0.6, priority_beta0=0.4,
            priority_eps=1e-6,
        ))
        state = rep.init({k: jnp.asarray(v) for k, v in _example().items()})
        rng = np.random.default_rng(0)
        match = 0
        for it in range(3):
            rows = _rows(rng)
            wm = plane.sender.send_rows(rows, np.arange(12) % 4)
            state = rep.insert(
                state, {k: jnp.asarray(v) for k, v in rows.items()}
            )
            key = jax.random.fold_in(jax.random.key(9), it)
            batch, info = plane.sampler.fetch_batch(key, 0.5, wm)
            _, rb, ri = rep.sample(state, key, beta=0.5)
            match += int(np.array_equal(
                np.asarray(ri["idx"]), info["shard_idx"][0]
            ))
            assert np.allclose(
                np.asarray(ri["is_weights"]), batch["is_weights"], rtol=1e-4
            )
            td = np.abs(rng.normal(size=(8,)).astype(np.float32))
            plane.sampler.update_priorities([info], [td])
            state = rep.update_priorities(state, ri["idx"], jnp.asarray(td))
        assert match >= 2  # ulp-boundary searchsorted ties may flip a draw
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            plane._poll_stats()
            if plane._stats_cache[0].get("prio_updates", 0) >= 24:
                break
            time.sleep(0.05)
        st = plane._stats_cache[0]
        assert st["prio_updates"] == 24  # 3 batched frames x 8 pairs
        assert np.isclose(
            st["max_priority"], float(state.max_priority), rtol=1e-6
        )
    finally:
        plane.close()


def test_sender_hash_routing_and_watermarks():
    plane = _make_plane(transport="tcp", shards=2)
    try:
        rng = np.random.default_rng(1)
        rows = _rows(rng, n=16)
        slots = np.arange(16) % 4
        wm = plane.sender.send_rows(rows, slots)
        expect = [0, 0]
        for s in slots:
            expect[shard_of_slot(int(s), 2)] += 1
        assert wm == expect
        assert all(w > 0 for w in wm), "route must cover both shards"
        plane._poll_stats()
        got = [int(plane._stats_cache[i]["ingested_rows"]) for i in (0, 1)]
        assert got == expect
        # fan-in geometry: 2 updates x (4+4) rows concatenated shard-major
        plane.sampler.request_iteration(wm, 0.0)
        staged = plane.sampler.get_iteration()
        assert len(staged) == 2
        batch, _key, info = staged[0]
        # 2-shard plane: batch_size 16 = 8 rows per shard, shard-major
        assert batch["obs"].shape == (16, 3)
        assert set(info["shard_idx"]) == {0, 1}
        assert all(len(v) == 8 for v in info["shard_idx"].values())
    finally:
        plane.close()


def test_shm_arm_ships_control_frames_only():
    """The shm arm's contract as the plane's own gauge counts it: rows and
    sampled batches travel through slabs, so ``experience/wire_bytes_per_
    step`` (bytes over the sockets, both ways, per ingested row) holds
    control frames only — under 100 bytes however wide the row is — while
    the tcp arm ships the rows themselves: more than a row's 1036 bytes."""
    example = dict(_example(), obs=np.zeros((256,), np.float32))
    row_bytes = sum(v.nbytes for v in example.values())
    per_step = {}
    for transport in ("shm", "tcp"):
        plane = _make_plane(transport=transport, example=example)
        try:
            rng = np.random.default_rng(0)
            for _ in range(4):
                rows = {
                    k: rng.normal(size=(16,) + v.shape).astype(np.float32)
                    for k, v in example.items()
                }
                wm = plane.sender.send_rows(rows, np.arange(16) % 4)
            for probe in range(2):
                plane.sampler.fetch_batch(
                    jax.random.fold_in(jax.random.key(42), probe), 0.0, wm
                )
            assert plane.sender.links[0].transport == transport
            gauges = plane.gauges()
            assert gauges["experience/rows"] == 64.0
            per_step[transport] = gauges["experience/wire_bytes_per_step"]
        finally:
            plane.close()
    assert 0 < per_step["shm"] < 100, per_step
    assert per_step["tcp"] > row_bytes > per_step["shm"], per_step


def test_shm_slabs_unlink_on_close_and_no_fd_leak():
    """Plane lifecycles leak neither /dev/shm segments (client-owned
    unlink) nor socket FDs (every DEALER/ROUTER closed on both sides) —
    repeated open/close cycles hold the process fd count steady."""
    fd_counts = []
    for cycle in range(3):
        plane = _make_plane(transport="shm", shards=2)
        rng = np.random.default_rng(2)
        plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
        if cycle == 0:
            assert glob.glob("/dev/shm/surreal_xp_*"), (
                "shm arm should have negotiated slabs"
            )
        plane.close()
        fd_counts.append(len(os.listdir("/proc/self/fd")))
    assert not glob.glob("/dev/shm/surreal_xp_*"), "client-owned unlink leaked"
    # first cycle may lazily initialize shared zmq machinery; later
    # cycles must not grow the fd table
    assert fd_counts[2] <= fd_counts[0] + 2, fd_counts


# -- chaos coverage -----------------------------------------------------------

def test_corrupt_wire_frame_counted_dropped_and_redelivered():
    """A corrupted INSERT is counted+dropped by the shard; the sender's
    ack retry redelivers it — no rows lost, exactly-once ingestion."""
    faults.configure([{
        "site": "experience.send", "kind": "corrupt_wire_frame", "at": 1,
    }])
    try:
        plane = _make_plane(transport="tcp")
        try:
            rng = np.random.default_rng(3)
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
            assert wm == [24]
            # the stale-frame retry rides the send path: the NEXT send
            # after the ack budget elapses redelivers the corrupted frame
            time.sleep(1.1)
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
            assert wm == [36]
            deadline = time.monotonic() + 4.0
            while time.monotonic() < deadline:
                plane._poll_stats()
                st = plane._stats_cache[0]
                if st.get("ingested_rows") == 36:
                    break
                time.sleep(0.05)
            st = plane._stats_cache[0]
            assert st["ingested_rows"] == 36, st
            assert st["decode_errors"] >= 1
            assert plane.sender.resends >= 1
        finally:
            plane.close()
    finally:
        faults.configure(None)


def test_delay_sample_fault_is_absorbed():
    faults.configure([{
        "site": "experience.sample", "kind": "delay_sample", "at": 0,
        "ms": 200,
    }])
    try:
        plane = _make_plane(transport="tcp")
        try:
            rng = np.random.default_rng(4)
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
            batch, _info = plane.sampler.fetch_batch(
                jax.random.key(0), 0.0, wm
            )
            assert batch["obs"].shape == (8, 3)
            assert any(
                f["site"] == "experience.sample" for f in faults.drain_fired()
            )
        finally:
            plane.close()
    finally:
        faults.configure(None)


def _kill_shard_cfg(folder, *, total_env_steps, updates_per_iter,
                    batch_size, kill_at):
    """The kill-shard chaos topology shared by the fast and slow arms:
    2 thread-mode shm shards, a kill_shard fault mid-run, tight plane
    timeouts so the respawn cycle fits the budget."""
    return Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=8,
                        updates_per_iter=updates_per_iter,
                        exploration=Config(warmup_steps=0)),
            replay=Config(kind="remote", remote_kind="uniform",
                          capacity=512, start_sample_size=16,
                          batch_size=batch_size),
        ),
        env_config=Config(name="gym:Pendulum-v1", num_envs=4),
        session_config=Config(
            folder=str(folder),
            total_env_steps=total_env_steps,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(experience_plane=Config(
                num_shards=2, shard_mode="thread", transport="shm",
                ack_timeout_s=0.5, sample_timeout_s=1.0,
                watermark_timeout_s=0.5, respawn_backoff_s=0.05,
            )),
            faults=Config(plan=[
                {"site": "experience.shard", "kind": "kill_shard",
                 "at": kill_at},
            ]),
        ),
    ).extend(base_config())


def test_kill_shard_respawns_fast(tmp_path):
    """Tier-1 trim of the kill-shard chaos run (ISSUE 16 headroom
    satellite): the SAME respawn/renegotiation path — a killed thread
    shard respawns under the schedule while training continues on the
    survivor, no /dev/shm leak — at the minimum workload that still
    trains past the kill (fewer iterations, one update per iteration).
    The full-size run with the diag/registry acceptance sweep rides the
    slow tier below."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    cfg = _kill_shard_cfg(
        tmp_path / "xp_kill_fast", total_env_steps=8 * 4 * 3,
        updates_per_iter=1, batch_size=16, kill_at=4,
    )
    trainer = OffPolicyTrainer(cfg)
    state, metrics = trainer.run()
    assert np.isfinite(metrics["loss/critic"])
    assert metrics["experience/respawns"] >= 1.0, metrics
    assert metrics["experience/shards_live"] == 2.0
    assert metrics["time/env_steps"] >= 8 * 4 * 3
    assert not glob.glob("/dev/shm/surreal_xp_*"), "respawn cycle leaked shm"


@pytest.mark.slow
def test_kill_shard_respawns_learner_keeps_training(tmp_path):
    """The chaos satellite: a killed shard server respawns under the
    exponential-backoff schedule while training keeps going on the
    surviving shard; no /dev/shm leak survives the cycle. The same run
    doubles as the observability acceptance: every emitted experience/*
    gauge is registry-documented, and diag renders the Experience plane
    section (per-shard table + sample-wait) from the run's
    experience_plane events.

    Slow tier: the full-size run (6 cadences, 2 updates/iter) costs
    ~70 s on the one-core suite; test_kill_shard_respawns_fast keeps
    the respawn path in tier-1."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
    from surreal_tpu.session.costs import GAUGE_REGISTRY
    from surreal_tpu.session.telemetry import diag_report, diag_summary

    folder = tmp_path / "xp_kill"
    cfg = _kill_shard_cfg(
        folder, total_env_steps=8 * 4 * 6, updates_per_iter=2,
        batch_size=32, kill_at=10,
    )
    trainer = OffPolicyTrainer(cfg)
    state, metrics = trainer.run()
    assert np.isfinite(metrics["loss/critic"])
    assert metrics["experience/respawns"] >= 1.0, metrics
    assert metrics["experience/shards_live"] == 2.0
    assert metrics["time/env_steps"] >= 8 * 4 * 6
    assert not glob.glob("/dev/shm/surreal_xp_*"), "respawn cycle leaked shm"
    emitted = [k for k in metrics if k.startswith("experience/")]
    assert emitted
    for k in emitted:
        assert k in GAUGE_REGISTRY, f"undocumented gauge {k}"
    s = diag_summary(str(folder))
    assert s["experience"] is not None
    assert s["experience"]["num_shards"] == 2
    assert s["faults"] is not None  # the kill fired and was recorded
    report = diag_report(str(folder))
    assert "Experience plane" in report and "sample-wait" in report


@pytest.mark.slow
def test_process_shard_sigkill_respawns_no_leaks():
    """Process-mode realism: SIGKILL an OS shard server mid-run; the
    plane supervisor respawns it in place (same address), clients
    re-negotiate, and no /dev/shm segment or stats socket leaks.

    Slow tier: spawning OS shard processes (spawn ctx + their lazy jax
    import) costs tens of seconds when the one-core suite is loaded; the
    thread-mode kill_shard test above keeps the respawn/renegotiation
    path in tier-1 — same code path minus the OS process."""
    import signal

    plane = _make_plane(transport="shm", shards=2, shard_mode="process")
    try:
        rng = np.random.default_rng(5)
        for _ in range(3):
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
        victim = plane.shards[0]
        os.kill(victim.pid, signal.SIGKILL)
        victim.join(timeout=10)
        assert not victim.is_alive()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            plane.supervise()
            if plane.shards[0].is_alive() and plane.respawns >= 1:
                break
            time.sleep(0.1)
        assert plane.respawns >= 1
        # ingest keeps working: the sender re-negotiates against the
        # respawned (empty) shard and the survivor never stopped
        for _ in range(4):
            wm = plane.sender.send_rows(_rows(rng), np.arange(12) % 4)
        assert sum(wm) > 0
        batch, _ = plane.sampler.fetch_batch(jax.random.key(1), 0.0, wm)
        assert batch["obs"].shape == (16, 3)  # 2 shards x 8 rows
    finally:
        plane.close()
    assert not glob.glob("/dev/shm/surreal_xp_*"), "SIGKILL cycle leaked shm"


# -- trainer integration ------------------------------------------------------

def _remote_train_cfg(folder, transport="shm", overlap=False, iters=4):
    return Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=8, updates_per_iter=2,
                        exploration=Config(warmup_steps=0)),
            replay=Config(kind="remote", remote_kind="uniform",
                          capacity=512, start_sample_size=16, batch_size=32),
        ),
        env_config=Config(name="gym:Pendulum-v1", num_envs=4),
        session_config=Config(
            folder=str(folder),
            total_env_steps=8 * 4 * iters,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                overlap_rollouts=overlap,
                experience_plane=Config(
                    num_shards=2, shard_mode="thread", transport=transport,
                ),
            ),
        ),
    ).extend(base_config())


def test_strict_remote_training_is_deterministic(tmp_path):
    """overlap_rollouts=false + watermarked sampling: two identical
    remote runs produce identical final metrics (the wire adds zero
    nondeterminism to the training record)."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    finals = []
    for run in range(2):
        trainer = OffPolicyTrainer(
            _remote_train_cfg(tmp_path / f"run{run}", overlap=False, iters=3)
        )
        _state, metrics = trainer.run()
        finals.append(metrics)
    for k in ("loss/critic", "loss/actor", "health/grad_norm"):
        assert finals[0][k] == finals[1][k], (
            k, finals[0][k], finals[1][k]
        )
    # the experience gauges rode the metrics stream
    assert finals[0]["experience/rows"] == finals[1]["experience/rows"] > 0
    assert finals[0]["experience/dropped_rows"] == 0.0


def test_fifo_chunk_relay_component():
    """The SEED arm's building block: whole trajectory chunks (nested
    behavior dict, int32 version rows) roundtrip sender.send_chunk ->
    fifo shard -> sampler.pop_chunk in order, spec carried in-frame."""
    plane = ExperiencePlane(
        kind="fifo", cfg={"num_shards": 1, "shard_mode": "thread",
                          "transport": "tcp"},
    )
    try:
        rng = np.random.default_rng(6)
        chunks = []
        for _ in range(2):
            chunk = {
                "obs": rng.normal(size=(4, 2, 3)).astype(np.float32),
                "behavior": {"mean": rng.normal(size=(4, 2, 1)).astype(np.float32)},
                "param_version": np.full((4, 2), 7, np.int32),
            }
            chunks.append(chunk)
            assert plane.sender.send_chunk(chunk)
        for sent in chunks:
            got, n = plane.sampler.pop_chunk(timeout_s=5.0)
            assert n == 4
            assert np.array_equal(got["obs"], sent["obs"])
            assert np.array_equal(
                got["behavior"]["mean"], sent["behavior"]["mean"]
            )
            assert got["param_version"].dtype == np.int32
        assert plane.sampler.pop_chunk(timeout_s=0.3) is None  # drained
    finally:
        plane.close()


def test_seed_trainer_chunks_relay_through_plane(tmp_path):
    """SEED arm: trajectory chunks route server -> shard tier -> learner
    over the wire (topology.experience_plane.enabled) and training still
    completes with finite losses."""
    from surreal_tpu.launch.seed_trainer import SEEDTrainer

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=8, epochs=2, num_minibatches=2)
        ),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=str(tmp_path / "xp_seed"),
            total_env_steps=8 * 4 * 2,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            topology=Config(
                num_env_workers=1,
                experience_plane=Config(
                    enabled=True, num_shards=2, shard_mode="thread",
                    transport="tcp",
                ),
            ),
        ),
    ).extend(base_config())
    trainer = SEEDTrainer(cfg)
    _state, metrics = trainer.run()
    assert metrics["time/env_steps"] >= 8 * 4 * 2
    assert np.isfinite(metrics["loss/pg"])
    assert metrics["experience/rows"] > 0


def test_remote_requires_host_env():
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ddpg"),
            replay=Config(kind="remote"),
        ),
        env_config=Config(name="jax:pendulum", num_envs=4),
        session_config=Config(folder="/tmp/test_xp_device"),
    ).extend(base_config())
    with pytest.raises(ValueError, match="remote"):
        OffPolicyTrainer(cfg)


def test_build_replay_rejects_remote_with_guidance():
    from surreal_tpu.replay import build_replay

    with pytest.raises(ValueError, match="experience"):
        build_replay(Config(kind="remote"))
