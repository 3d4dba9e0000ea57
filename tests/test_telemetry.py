"""Telemetry spine (session/telemetry.py + the diag CLI + the sync-free
guarantee): span round-trips through the JSONL log, the diag report on a
fresh training session, and the dispatch-count proof that the
instrumented fused train_iter performs no device->host syncs beyond the
existing metrics cadence."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.telemetry import (
    HeartbeatWriter,
    Tracer,
    diag_report,
    diag_summary,
)


# -- pure round-trip: write spans -> diag parses them ------------------------

def test_tracer_span_roundtrip_through_diag(tmp_path):
    folder = str(tmp_path)
    tracer = Tracer(folder, name="train")
    for _ in range(3):
        with tracer.span("rollout"):
            pass
        with tracer.span("learn"):
            pass
    with tracer.span("checkpoint", emit=True):
        pass
    mirror = tracer.flush_phases(step=100)
    # the time/* mirror carries one scalar per phase for the MetricsWriter
    assert set(mirror) == {"time/rollout_ms", "time/learn_ms", "time/checkpoint_ms"}
    tracer.log_metrics(100, {"health/grad_norm": 1.5, "health/nonfinite": 0.0,
                             "loss/pg": -0.01})
    hb = HeartbeatWriter(folder, rank=0, every_s=0.0)
    hb.beat(7, 700)
    tracer.close()

    # the JSONL log is strict one-object-per-line
    with open(os.path.join(folder, "telemetry", "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    assert {"session", "phases", "span", "metrics"} <= {e["type"] for e in events}

    s = diag_summary(folder)
    assert s["phases"]["rollout"]["count"] == 3
    assert s["health"]["health/grad_norm"]["last"] == 1.5
    assert s["heartbeats"][0]["iteration"] == 7
    report = diag_report(folder)
    for needle in ("Phase-time breakdown", "rollout", "health/grad_norm",
                   "Heartbeats", "nonfinite guard: clean"):
        assert needle in report, report


def test_diag_flags_nonfinite_windows(tmp_path):
    tracer = Tracer(str(tmp_path))
    tracer.log_metrics(1, {"health/nonfinite": 1.0, "health/grad_norm": float("inf")})
    tracer.close()
    report = diag_report(str(tmp_path))
    assert "flagged" in report and "nonfinite" in report


def test_disabled_tracer_and_unwritable_heartbeat_are_noops(tmp_path):
    tracer = Tracer(None, enabled=False)
    with tracer.span("x"):
        pass
    tracer.event("y")
    assert tracer.flush_phases(0) == {}
    # rank > 0 on a host without the session folder mounted: silently off
    hb = HeartbeatWriter("/nonexistent-root-dir/nope", rank=3)
    hb.beat(1, 2)  # no raise


def test_tracer_size_rotation_and_readers_follow_segments(tmp_path):
    """Size-based event-log rotation (ISSUE 13): a tracer past
    ``max_log_mb`` shifts the log to ``events.jsonl.1`` and keeps
    writing; ``_iter_jsonl`` reads rotated-then-live as ONE
    chronological stream (seq strictly increasing across the boundary)
    and diag aggregates over both segments."""
    from surreal_tpu.session.telemetry import _iter_jsonl

    folder = str(tmp_path)
    # ~500-byte cap: a few metrics rows force multiple rotations
    tracer = Tracer(folder, name="train", max_log_mb=0.0005)
    for step in range(40):
        tracer.log_metrics(step, {"health/grad_norm": float(step)})
    assert tracer.rotations >= 1
    tracer.close()
    path = os.path.join(folder, "telemetry", "events.jsonl")
    assert os.path.exists(path) and os.path.exists(path + ".1")
    # at most two generations on disk: the rotation drops older segments
    assert not os.path.exists(path + ".2")
    events = list(_iter_jsonl(path))
    assert events, "no events survived rotation"
    seqs = [e["seq"] for e in events if "seq" in e]
    assert seqs == sorted(seqs), "segments read out of order"
    # diag reads THROUGH the rotation: the newest row is the last step
    s = diag_summary(folder)
    steps = [e for e in events if e["type"] == "metrics"]
    assert steps[-1]["step"] == 39
    assert s["health"]["health/grad_norm"]["last"] == 39.0


def test_iter_jsonl_mid_rotation_and_torn_segments(tmp_path):
    """The hostile shapes a LIVE rotation leaves a concurrent reader:
    a rotated segment with a torn tail line, a live file still empty —
    every parseable line still comes out, in segment order, no raise."""
    from surreal_tpu.session.telemetry import _iter_jsonl

    path = str(tmp_path / "events.jsonl")
    with open(path + ".1", "w") as f:
        f.write('{"type": "metrics", "seq": 1}\n')
        f.write('{"type": "metrics", "seq": 2}\n')
        f.write('{"type": "metrics", "se')  # torn mid-rotation write
    with open(path, "w") as f:
        pass  # the freshly reopened live file: empty is legal
    assert [e["seq"] for e in _iter_jsonl(path)] == [1, 2]
    # and the reverse instant: live file has rows, .1 vanished mid-read
    os.remove(path + ".1")
    with open(path, "w") as f:
        f.write('{"type": "metrics", "seq": 3}\n')
    assert [e["seq"] for e in _iter_jsonl(path)] == [3]


def test_diag_cli_missing_folder_returns_2(tmp_path, capsys):
    from surreal_tpu.main.launch import main

    rc = main(["diag", str(tmp_path / "not_a_session")])
    assert rc == 2
    assert "no telemetry" in capsys.readouterr().err


# -- fresh training session -> diag (the acceptance surface) ------------------

def _session_cfg(folder, every_n_iters=2, total_iters=6):
    horizon, num_envs = 8, 8
    return Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=1, num_minibatches=1)
        ),
        env_config=Config(name="jax:cartpole", num_envs=num_envs),
        session_config=Config(
            folder=str(folder),
            total_env_steps=horizon * num_envs * total_iters,
            metrics=Config(every_n_iters=every_n_iters, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            # the CPU has no published peak (session/costs.py): the
            # perf/mfu + perf/membw_util tests supply one
            perf=Config(peak_flops=1e11, peak_membw=5e10),
        ),
    ).extend(base_config())


def test_diag_on_fresh_training_session(tmp_path, capsys):
    """`python -m surreal_tpu diag <folder>` on a just-trained session
    prints a phase-time breakdown and health summary from the JSONL log
    (the acceptance criterion, end to end through the real CLI)."""
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.main.launch import main

    folder = tmp_path / "exp"
    Trainer(_session_cfg(folder)).run()
    rc = main(["diag", str(folder)])
    assert rc == 0
    out = capsys.readouterr().out
    for needle in ("Phase-time breakdown", "train_iter", "metrics-sync",
                   "Training health", "health/grad_norm", "health/param_norm",
                   "nonfinite guard: clean"):
        assert needle in out, out
    # --json mode round-trips the aggregate
    rc = main(["diag", "--json", str(folder)])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["phases"]["train_iter"]["count"] == 6

    # the time/* mirror reached the metrics stream (hooks.last_metrics
    # carries the final synced row, which includes the span mirror)
    events = [
        json.loads(line)
        for line in open(os.path.join(folder, "telemetry", "events.jsonl"))
        if line.strip()
    ]
    metric_rows = [e for e in events if e["type"] == "metrics"]
    assert any("time/train_iter_ms" in e["values"] for e in metric_rows)


def test_telemetry_disabled_writes_no_event_log(tmp_path):
    from surreal_tpu.launch.trainer import Trainer

    folder = tmp_path / "exp_off"
    cfg = _session_cfg(folder, total_iters=2)
    cfg = Config(
        session_config=Config(telemetry=Config(enabled=False))
    ).extend(cfg)
    Trainer(cfg).run()
    assert not os.path.exists(os.path.join(folder, "telemetry", "events.jsonl"))
    assert diag_report(str(folder)) is None


# -- the sync-free guarantee --------------------------------------------------

def test_fused_train_iter_no_syncs_off_metrics_cadence(tmp_path):
    """Dispatch-count proof for the acceptance criterion: the instrumented
    fused train_iter — health diagnostics, replay-style device gauges,
    span tracing, hooks bookkeeping and all — performs NO device->host
    sync except when metrics.every_n_iters fires. Enforced with jax's
    transfer guard: every off-cadence iteration (dispatch + hooks) runs
    under disallow_device_to_host, so any float()/np.asarray of a device
    value raises."""
    from surreal_tpu.launch.hooks import SessionHooks
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer

    every = 4
    cfg = _session_cfg(tmp_path / "exp_guard", every_n_iters=every)
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, trainer.num_envs)
    # warm the compile caches OUTSIDE the guard (compilation is allowed
    # to transfer; steady-state iterations are what the guarantee covers)
    key, wk = jax.random.split(key)
    state, carry, metrics = trainer._train_iter(state, carry, wk)
    jax.block_until_ready(metrics)

    hooks = SessionHooks(cfg, trainer.learner)
    try:
        hooks.begin_run(0, 0)
        steps_per_iter = trainer.horizon * trainer.num_envs
        env_steps = 0
        synced = []
        for it in range(1, 2 * every + 1):
            key, it_key, hk_key = jax.random.split(key, 3)
            env_steps += steps_per_iter
            if it % every == 0:
                # the ONE allowed sync of the window
                state, carry, metrics = trainer._train_iter(state, carry, it_key)
                m, _ = hooks.end_iteration(
                    it, env_steps, state, hk_key, metrics, None
                )
                assert m is not None
                synced.append(m)
            else:
                with jax.transfer_guard_device_to_host("disallow"):
                    state, carry, metrics = trainer._train_iter(
                        state, carry, it_key
                    )
                    m, _ = hooks.end_iteration(
                        it, env_steps, state, hk_key, metrics, None
                    )
                assert m is None  # cadence did not fire -> nothing synced
        # the cadence rows DID carry the in-graph health diagnostics
        assert {"health/grad_norm", "health/param_norm",
                "health/update_ratio", "health/nonfinite"} <= set(synced[-1])
        assert synced[-1]["health/nonfinite"] == 0.0
    finally:
        hooks.close()


def test_perf_gauges_add_no_syncs_beyond_metrics(tmp_path):
    """Transfer-guard proof for the ISSUE-6 cost/MFU gauges: with hot
    programs REGISTERED with the cost accountant, the cadence-firing
    end_iteration — perf/mfu + perf/membw_util computation included —
    performs zero device->host transfers beyond the metrics the caller
    already synced. Proven by pre-syncing the metrics to host floats and
    running the ENTIRE end_iteration (and the gauge arithmetic inside
    it) under disallow_device_to_host."""
    from surreal_tpu.launch.hooks import SessionHooks
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer

    cfg = _session_cfg(tmp_path / "exp_perf_guard", every_n_iters=1)
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, trainer.num_envs)
    key, wk = jax.random.split(key)
    state, carry, metrics = trainer._train_iter(state, carry, wk)
    jax.block_until_ready(metrics)

    hooks = SessionHooks(cfg, trainer.learner)
    try:
        # program registration itself is host-side (lower + HLO cost
        # pass): legal under the guard too — prove it there
        with jax.transfer_guard_device_to_host("disallow"):
            hooks.record_program_costs(
                "train_iter", trainer._train_iter, state, carry, wk,
                phase="train_iter",
            )
        assert "train_iter" in hooks.costs.programs
        hooks.begin_run(0, 0)
        steps_per_iter = trainer.horizon * trainer.num_envs
        # the gauges divide by the fenced `cadence` span, which runs from
        # one metrics sync to the next: the first window has none yet
        for it in (1, 2):
            key, it_key, hk_key = jax.random.split(key, 3)
            with hooks.tracer.span("train_iter"):
                state, carry, metrics = trainer._train_iter(
                    state, carry, it_key
                )
            # the caller's one sync: host floats BEFORE the guard window
            host_metrics_row = {k: float(v) for k, v in metrics.items()}
            with jax.transfer_guard_device_to_host("disallow"):
                m, _ = hooks.end_iteration(
                    it, it * steps_per_iter, state, hk_key,
                    host_metrics_row, None,
                )
            assert m is not None
            assert ("perf/mfu" in m) == (it == 2), sorted(m)
        assert "perf/mfu" in m and "perf/membw_util" in m, sorted(m)
        assert 0.0 < m["perf/mfu"] < 1.0
        # the ops-plane snapshot (ISSUE 13) rode the SAME guarded
        # window: merging tiers, evaluating SLOs and writing the
        # snapshot file performed zero device->host transfers
        assert m["ops/snapshots"] >= 1.0
        # and the bare gauge arithmetic is guard-clean in isolation
        with jax.transfer_guard_device_to_host("disallow"):
            g = hooks.costs.gauges(hooks.tracer.last_window)
        assert set(g) <= {"perf/mfu", "perf/membw_util", "perf/flops_per_s"}
    finally:
        hooks.close()


def test_prefetch_staging_adds_no_device_to_host_syncs(tmp_path):
    """Transfer-guard proof for the dispatch pipeline's staging seam
    (learners/prefetch.py): pulling double-buffered chunks — numpy
    stacking + jax.device_put on the staging thread, exactly what the
    SEED trainer and the off-policy host loop stage — and consuming them
    through a jitted step is pure host->device traffic. The guard runs on
    BOTH sides of the seam, so a device_get smuggled into either the
    producer or the consumer loop raises."""
    import numpy as np

    rng = np.random.default_rng(0)

    def produce():
        with jax.transfer_guard_device_to_host("disallow"):
            chunk = {
                "obs": rng.normal(size=(4, 8, 3)).astype(np.float32),
                "reward": rng.normal(size=(4, 8)).astype(np.float32),
            }
            return jax.device_put(chunk)

    from surreal_tpu.learners.prefetch import Prefetcher

    consume = jax.jit(
        lambda b: b["obs"].sum() + b["reward"].sum(), donate_argnums=()
    )
    # warm the compile outside the guard (compilation may transfer)
    jax.block_until_ready(consume(produce()))

    p = Prefetcher(produce)
    try:
        outs = []
        with jax.transfer_guard_device_to_host("disallow"):
            for _ in range(4):
                outs.append(consume(p.get()))
        # the ONE sync happens after the guarded window, as in the drivers
        assert all(np.isfinite(jax.device_get(o)) for o in outs)
    finally:
        p.close()


def test_offpolicy_host_loop_staged_overlap_trains(tmp_path):
    """The off-policy HOST loop with overlap_rollouts on (the default):
    the staging thread collects + device_puts chunks while the main
    thread updates; the run must produce finite metrics, fill replay, and
    count its env-step budget exactly — and the strict-alternation mode
    must behave identically. The budget runs PAST the env's 200-step
    episode limit so the OU episode-reset masking executes (it writes
    into the noise array — a read-only asarray view crashed here)."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    for overlap in (True, False):
        cfg = Config(
            learner_config=Config(
                algo=Config(name="ddpg", horizon=8, updates_per_iter=1,
                            exploration=Config(warmup_steps=8)),
                replay=Config(capacity=1024, start_sample_size=32, batch_size=16),
            ),
            env_config=Config(name="gym:Pendulum-v1", num_envs=2),
            session_config=Config(
                folder=str(tmp_path / f"host_ov_{overlap}"),
                total_env_steps=8 * 2 * 27,  # 216 steps/env > the 200 limit
                topology=Config(overlap_rollouts=overlap),
                metrics=Config(every_n_iters=1, tensorboard=False,
                               console=False),
                checkpoint=Config(every_n_iters=0),
                eval=Config(every_n_iters=0),
            ),
        ).extend(base_config())
        trainer = OffPolicyTrainer(cfg)
        assert not trainer.device_mode
        state, metrics = trainer.run()
        assert metrics["time/env_steps"] == 8 * 2 * 27, overlap
        assert metrics["replay/size"] >= 32, overlap
        for k, v in metrics.items():
            if k.startswith(("loss/", "health/")):
                assert v == v, (overlap, k)  # NaN guard


def test_offpolicy_fused_iter_no_syncs_off_metrics_cadence(tmp_path):
    """Same guarantee for the off-policy fused iteration, which
    additionally carries the replay occupancy/staleness gauges in-graph."""
    from surreal_tpu.launch.hooks import SessionHooks
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    horizon, num_envs, every = 4, 8, 3
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=horizon, updates_per_iter=2,
                        exploration=Config(warmup_steps=0)),
            replay=Config(capacity=512, start_sample_size=32, batch_size=16),
        ),
        env_config=Config(name="jax:pendulum", num_envs=num_envs),
        session_config=Config(
            folder=str(tmp_path / "exp_ddpg"),
            total_env_steps=10**9,
            metrics=Config(every_n_iters=every, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = OffPolicyTrainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = trainer._init_carry(env_key)
    replay_state = trainer.replay.init(trainer._replay_example())
    # warm both cond branches' compile (first=True and steady)
    key, wk = jax.random.split(key)
    state, replay_state, carry, metrics = trainer._train_iter(
        state, replay_state, carry, wk, jnp.float32(0), jnp.asarray(False),
        jnp.asarray(True),
    )
    state, replay_state, carry, metrics = trainer._train_iter(
        state, replay_state, carry, wk, jnp.float32(0), jnp.asarray(False),
        jnp.asarray(False),
    )
    jax.block_until_ready(metrics)

    hooks = SessionHooks(cfg, trainer.learner)
    try:
        hooks.begin_run(0, 0)
        env_steps, last = 0, None
        for it in range(1, 2 * every + 1):
            key, it_key, hk_key = jax.random.split(key, 3)
            env_steps += horizon * num_envs
            args = (it_key, jnp.float32(0), jnp.asarray(False), jnp.asarray(False))
            if it % every == 0:
                state, replay_state, carry, metrics = trainer._train_iter(
                    state, replay_state, carry, *args
                )
                last, _ = hooks.end_iteration(
                    it, env_steps, state, hk_key, metrics, None
                )
            else:
                with jax.transfer_guard_device_to_host("disallow"):
                    state, replay_state, carry, metrics = trainer._train_iter(
                        state, replay_state, carry, *args
                    )
                    m, _ = hooks.end_iteration(
                        it, env_steps, state, hk_key, metrics, None
                    )
                assert m is None
        assert last is not None
        assert {"replay/size", "replay/fill", "replay/sample_age_frac",
                "health/grad_norm"} <= set(last)
        assert last["replay/size"] > 0
        assert 0.0 <= last["replay/sample_age_frac"] <= 1.0
    finally:
        hooks.close()
