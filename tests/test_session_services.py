"""Checkpoint/resume, metrics writer, evaluator, and hooks integration
(SURVEY.md §5.4/§5.5, §3.5; VERDICT round-1 items 2-4)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.session.checkpoint import CheckpointManager
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.metrics import MetricsWriter


def _specs():
    return EnvSpecs(
        obs=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(1,), dtype=np.dtype(np.float32)),
    )


def _params_equal(a, b) -> bool:
    eq = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    return all(jax.tree.leaves(eq))


# -- checkpoint layer -------------------------------------------------------

def test_checkpoint_save_restore_roundtrip(tmp_path):
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    s0 = learner.init(jax.random.key(0))
    cm = CheckpointManager(str(tmp_path), keep_last=2)
    cm.save(7, s0, env_steps=123)
    template = learner.init(jax.random.key(99))  # different init values
    state, meta = cm.restore(template)
    assert meta == {"iteration": 7, "env_steps": 123}
    assert _params_equal(state.params, s0.params)
    assert not _params_equal(template.params, s0.params)
    cm.close()


def test_checkpoint_keep_last_prunes_and_keep_best_tracks_max(tmp_path):
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    s = learner.init(jax.random.key(0))
    cm = CheckpointManager(str(tmp_path), keep_last=2, keep_best=True)
    cm.save(1, s, metrics={"episode/return": 10.0})
    cm.save(2, s, metrics={"episode/return": 30.0})
    cm.save(3, s, metrics={"episode/return": 20.0})
    steps = sorted(
        int(os.path.basename(p))
        for p in glob.glob(str(tmp_path / "checkpoints" / "*"))
        if os.path.basename(p).isdigit()
    )
    assert steps == [2, 3]  # keep_last=2 pruned step 1
    assert cm.best_metric() == {"value": 30.0, "step": 2}
    restored = cm.restore_best(learner.init(jax.random.key(5)))
    assert restored is not None and restored[1]["iteration"] == 2
    cm.close()


def test_checkpoint_restore_none_when_empty(tmp_path):
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    cm = CheckpointManager(str(tmp_path))
    assert cm.restore(learner.init(jax.random.key(0))) is None
    assert cm.latest_step() is None
    cm.close()


# -- metrics writer ---------------------------------------------------------

def test_metrics_writer_produces_tb_event_file(tmp_path, capsys):
    w = MetricsWriter(str(tmp_path), tensorboard=True, console=True)
    w.write(10, {"loss/total": 1.5, "episode/return": float("nan")})
    w.write(20, {"loss/total": 1.25})
    w.close()
    files = glob.glob(str(tmp_path / "tb" / "train" / "events.out.tfevents.*"))
    assert len(files) == 1 and os.path.getsize(files[0]) > 0
    out = capsys.readouterr().out
    assert "loss/total=1.5" in out
    assert "episode/return" not in out  # NaN dropped


def test_metrics_writer_disabled_backends_are_noop(tmp_path, capsys):
    w = MetricsWriter(str(tmp_path), tensorboard=False, console=False)
    w.write(1, {"a": 1.0})
    w.close()
    assert glob.glob(str(tmp_path / "tb" / "**"), recursive=False) == []
    assert capsys.readouterr().out == ""


def test_metrics_writer_nan_drop_per_key_and_all_nan_row(tmp_path, capsys):
    """NaN scalars (windows with no finished episodes) drop PER KEY: the
    finite keys of the same row still flow, and an all-NaN row writes
    nothing rather than crashing."""
    w = MetricsWriter(str(tmp_path), tensorboard=False, console=True)
    w.write(5, {"episode/return": float("nan"), "loss/pg": 2.0})
    out = capsys.readouterr().out
    assert "loss/pg=2" in out and "episode/return" not in out
    w.write(6, {"episode/return": float("nan")})  # all-NaN row: no crash
    assert "[6]" in capsys.readouterr().out  # row printed, no values
    w.close()


def test_metrics_writer_degrades_without_tensorboard(tmp_path, monkeypatch, caplog):
    """Headless images (no tensorboard package) must still train: with the
    import marked failed, tensorboard=True degrades to a no-op backend
    with ONE warning instead of raising."""
    import logging

    import surreal_tpu.session.metrics as M

    monkeypatch.setattr(M, "_TB_IMPORT_ERROR", ImportError("no tensorboard"))
    with caplog.at_level(logging.WARNING, logger="surreal_tpu"):
        w = M.MetricsWriter(str(tmp_path), tensorboard=True, console=False)
    assert w._tb is None
    assert any("tensorboard" in r.message for r in caplog.records)
    w.write(1, {"a": 1.0})  # no crash, no event files
    w.flush()
    w.close()
    assert glob.glob(str(tmp_path / "tb" / "**" / "events.*")) == []


def test_get_logger_retargets_file_handler_across_sessions(tmp_path):
    """Sequential sessions in one process must never cross-write logs: a
    get_logger call with a NEW folder closes the old file handler and
    retargets, and re-calls with the same folder add no handlers."""
    from surreal_tpu.session.metrics import get_logger

    f1, f2 = tmp_path / "s1", tmp_path / "s2"
    log = get_logger("retarget_probe", str(f1))
    log.info("first-session line")
    log2 = get_logger("retarget_probe", str(f2))
    assert log2 is log  # same logger object, retargeted
    log.info("second-session line")
    for h in log.handlers:
        h.flush()
    t1 = (f1 / "logs" / "retarget_probe.log").read_text()
    t2 = (f2 / "logs" / "retarget_probe.log").read_text()
    assert "first-session line" in t1 and "second-session line" not in t1
    assert "second-session line" in t2 and "first-session line" not in t2
    n = len(log.handlers)
    get_logger("retarget_probe", str(f2))  # idempotent per (name, folder)
    assert len(log.handlers) == n


# -- evaluator --------------------------------------------------------------

def test_evaluator_device_env_returns_full_episode_stats():
    from surreal_tpu.launch.evaluator import Evaluator

    env_cfg = Config(name="jax:pendulum", num_envs=1).extend(
        base_config().env_config
    )
    learner = build_learner(
        Config(algo=Config(name="ppo")),
        EnvSpecs(
            obs=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
            action=ArraySpec(shape=(1,), dtype=np.dtype(np.float32)),
        ),
    )
    state = learner.init(jax.random.key(0))
    ev = Evaluator(env_cfg, Config(episodes=4, mode="deterministic"), learner)
    out = ev.evaluate(state, jax.random.key(1))
    # pendulum episodes truncate at exactly 200 steps; returns are negative costs
    assert out["eval/length"] == 200.0
    assert -2000.0 < out["eval/return"] < 0.0
    ev.close()


def test_evaluator_deterministic_is_repeatable_stochastic_varies():
    from surreal_tpu.launch.evaluator import Evaluator

    env_cfg = Config(name="jax:pendulum", num_envs=1).extend(
        base_config().env_config
    )
    learner = build_learner(
        Config(algo=Config(name="ppo")),
        EnvSpecs(
            obs=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
            action=ArraySpec(shape=(1,), dtype=np.dtype(np.float32)),
        ),
    )
    state = learner.init(jax.random.key(0))
    det = Evaluator(env_cfg, Config(episodes=2, mode="deterministic"), learner)
    # same key -> same reset states; deterministic policy -> identical returns
    a = det.evaluate(state, jax.random.key(7))
    b = det.evaluate(state, jax.random.key(7))
    assert a["eval/return"] == b["eval/return"]
    sto = Evaluator(env_cfg, Config(episodes=2, mode="stochastic"), learner)
    c = sto.evaluate(state, jax.random.key(7))
    assert c["eval/return"] != a["eval/return"]


# -- end-to-end: kill-and-resume -------------------------------------------

def _trainer_cfg(folder, total_steps, **session_overrides):
    from surreal_tpu.session.default_configs import base_config

    session = dict(
        folder=str(folder),
        total_env_steps=total_steps,
        metrics=Config(every_n_iters=4, tensorboard=True, console=False),
        checkpoint=Config(every_n_iters=5),
        eval=Config(every_n_iters=0),
    )
    session.update(session_overrides)
    return Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=16, epochs=2, num_minibatches=2)
        ),
        env_config=Config(name="jax:pendulum", num_envs=8),
        session_config=Config(**session),
    ).extend(base_config())


@pytest.mark.slow
def test_trainer_kill_and_resume_continues_curve(tmp_path):
    from surreal_tpu.launch.trainer import Trainer

    steps_per_iter = 16 * 8
    # run 1: 12 iterations, checkpoints at 5 and 10 (+ final at 12)
    t1 = Trainer(_trainer_cfg(tmp_path, 12 * steps_per_iter))
    s1, _ = t1.run()
    ckpt_steps = sorted(
        int(os.path.basename(p))
        for p in glob.glob(str(tmp_path / "checkpoints" / "*"))
        if os.path.basename(p).isdigit()
    )
    assert 12 in ckpt_steps  # final checkpoint always written

    # run 2: same folder, larger budget -> auto-resumes at iteration 12 and
    # continues from the SAME params (not a fresh init)
    t2 = Trainer(_trainer_cfg(tmp_path, 20 * steps_per_iter))
    seen = []
    s2, m2 = t2.run(on_metrics=lambda it, m: seen.append(it))
    assert _params_equal(
        t2.learner.init(jax.random.key(0)).params, s1.params
    ) is False  # sanity: resume didn't just re-init
    assert m2["time/env_steps"] == 20 * steps_per_iter
    assert min(seen) > 12  # iteration counter continued, not restarted
    ckpt_steps = sorted(
        int(os.path.basename(p))
        for p in glob.glob(str(tmp_path / "checkpoints" / "*"))
        if os.path.basename(p).isdigit()
    )
    assert 20 in ckpt_steps


@pytest.mark.slow
def test_trainer_restore_from_foreign_folder(tmp_path):
    from surreal_tpu.launch.trainer import Trainer

    steps_per_iter = 16 * 8
    src = tmp_path / "src"
    dst = tmp_path / "dst"
    t1 = Trainer(_trainer_cfg(src, 6 * steps_per_iter))
    s1, _ = t1.run()

    cfg = _trainer_cfg(
        dst, 8 * steps_per_iter, checkpoint=Config(every_n_iters=5, restore_from=str(src))
    )
    t2 = Trainer(cfg)
    s2, m2 = t2.run()
    assert m2["time/env_steps"] == 8 * steps_per_iter  # 6 restored + 2 more


# -- launcher/CLI -----------------------------------------------------------

@pytest.mark.slow
def test_cli_train_then_eval_roundtrip(tmp_path):
    from surreal_tpu.main.launch import main

    folder = str(tmp_path / "exp")
    rc = main([
        "train", "ppo", "jax:pendulum",
        "--folder", folder, "--num-envs", "8", "--total-steps", "1024",
        "--set",
        "learner_config.algo.horizon=16",
        "session_config.metrics.every_n_iters=4",
        "session_config.metrics.tensorboard=false",
        "session_config.metrics.console=false",
        "session_config.eval.every_n_iters=0",
    ])
    assert rc == 0
    assert os.path.exists(os.path.join(folder, "config.json"))
    assert glob.glob(os.path.join(folder, "checkpoints", "*"))

    rc = main(["eval", "--folder", folder, "--episodes", "2"])
    assert rc == 0


def test_cli_selects_trainer_by_algo_and_env():
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.main.launch import build_config, select_trainer

    class A:
        algo, env, num_envs, folder = "ddpg", "jax:pendulum", 16, "/tmp/sel1"
        total_steps = restore_from = None
        set = []

    cfg = build_config(A)
    assert isinstance(select_trainer(cfg), OffPolicyTrainer)

    class B(A):
        algo, env, folder = "ppo", "jax:cartpole", "/tmp/sel2"

    assert isinstance(select_trainer(build_config(B)), Trainer)


def test_evaluator_records_video(tmp_path):
    """Eval is where the reference recorded videos (run_eval +
    VideoWrapper); the host evaluator must actually produce an episode
    recording when env_config.video is enabled."""
    import os

    from surreal_tpu.envs.base import DiscreteSpec
    from surreal_tpu.launch.evaluator import Evaluator
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    vdir = str(tmp_path / "videos")
    env_cfg = Config(
        name="gym:CartPole-v1",
        num_envs=1,
        video=Config(enabled=True, dir=vdir, every_n_episodes=1),
    ).extend(BASE_ENV_CONFIG)
    specs = EnvSpecs(
        obs=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), n=2),
    )
    learner = build_learner(Config(algo=Config(name="ppo")), specs)
    state = learner.init(jax.random.key(0))
    ev = Evaluator(env_cfg, Config(episodes=1, mode="deterministic"), learner)
    try:
        out = ev.evaluate(state, jax.random.key(1))
        assert np.isfinite(out["eval/return"])
        files = os.listdir(vdir)
        assert any(f.startswith("episode_") for f in files), files
    finally:
        ev.close()


@pytest.mark.slow
def test_profiler_trace_window_writes_profile(tmp_path):
    """SURVEY §5.1: a session must be able to capture a jax.profiler
    trace window around chosen iterations and leave the TensorBoard
    profile artifacts under <folder>/telemetry/profiles/. The trigger file
    (`surreal_tpu profile <folder>`) asks for it; the fixed
    `session.profiler` window went with ISSUE 25."""
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.profile import write_trigger

    folder = str(tmp_path / "prof_run")
    os.makedirs(folder)
    write_trigger(folder, num_iters=2)
    cfg = Config(
        learner_config=Config(algo=Config(name="ppo", horizon=8)),
        env_config=Config(name="jax:cartpole", num_envs=8),
        session_config=Config(
            folder=folder,
            total_env_steps=8 * 8 * 6,  # 6 iterations
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    Trainer(cfg).run()
    trace_files = glob.glob(
        os.path.join(folder, "telemetry", "profiles", "**", "*"),
        recursive=True,
    )
    assert any(os.path.isfile(f) for f in trace_files), trace_files


def test_host_eval_metric_namespace_and_step_cap():
    """VERDICT r2 item 9: the host eval path must return the SAME metric
    namespace as the device path (eval/success included, 0.0 when the env
    never reports success) and honor a configurable step cap."""
    from surreal_tpu.envs.base import DiscreteSpec
    from surreal_tpu.launch.evaluator import Evaluator
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    env_cfg = Config(name="gym:CartPole-v1", num_envs=1).extend(BASE_ENV_CONFIG)
    specs = EnvSpecs(
        obs=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), n=2),
    )
    learner = build_learner(Config(algo=Config(name="ppo")), specs)
    state = learner.init(jax.random.key(0))
    ev = Evaluator(env_cfg, Config(episodes=2, mode="deterministic", max_steps=5), learner)
    try:
        out = ev.evaluate(state, jax.random.key(1))
        assert set(out) == {"eval/return", "eval/length", "eval/success"}
        assert out["eval/success"] == 0.0  # CartPole reports no success
        assert out["eval/length"] <= 5  # cap respected
    finally:
        ev.close()


def test_cli_eval_best_with_video_and_step_cap(tmp_path):
    """`eval --best --max-steps` through the CLI on a host env with video
    enabled: restores the keep-best checkpoint, records an episode video,
    and returns the full eval namespace (VERDICT r2 item 9)."""
    from surreal_tpu.main.launch import main

    folder = str(tmp_path / "exp")
    vdir = str(tmp_path / "videos")
    rc = main([
        "train", "ppo", "gym:CartPole-v1",
        "--folder", folder, "--num-envs", "4", "--total-steps", str(16 * 4 * 3),
        "--set",
        "learner_config.algo.horizon=16",
        "learner_config.algo.epochs=1",
        "session_config.backend=cpu",
        "session_config.metrics.every_n_iters=1",
        "session_config.metrics.tensorboard=false",
        "session_config.metrics.console=false",
        # eval cadence feeds the keep-best tracker during training
        "session_config.eval.every_n_iters=1",
        "session_config.eval.episodes=1",
        "session_config.eval.max_steps=50",
        "session_config.checkpoint.every_n_iters=1",
        f'session_config.eval.video_dir="{vdir}"',  # ignored key is fine
        f'env_config.video.enabled=true',
        f'env_config.video.dir="{vdir}"',
        "env_config.video.every_n_episodes=1",
    ])
    assert rc == 0
    assert os.path.exists(os.path.join(folder, "checkpoints", "best_metric.json"))
    rc = main(["eval", "--folder", folder, "--best", "--episodes", "1",
               "--max-steps", "30"])
    assert rc == 0
    files = os.listdir(vdir)
    assert any(f.startswith("episode_") for f in files), files


def test_cli_rejects_workers_for_incompatible_topology():
    """--workers (num_env_workers>0) with a jax env or ddpg must fail
    loudly instead of silently running a different topology."""
    from surreal_tpu.main.launch import select_trainer

    bad = Config(
        learner_config=Config(algo=Config(name="ppo")),
        env_config=Config(name="jax:cartpole", num_envs=8),
        session_config=Config(
            folder="/tmp/x", topology=Config(num_env_workers=4)
        ),
    ).extend(base_config())
    with pytest.raises(ValueError, match="HOST env"):
        select_trainer(bad)
    bad2 = Config(
        learner_config=Config(algo=Config(name="ddpg")),
        env_config=Config(name="gym:Pendulum-v1", num_envs=2),
        session_config=Config(
            folder="/tmp/x", topology=Config(num_env_workers=4)
        ),
    ).extend(base_config())
    with pytest.raises(ValueError, match="on-policy"):
        select_trainer(bad2)


def test_device_eval_records_video(tmp_path):
    """Device envs render eval videos from state (the reference recorded
    via a GL wrapper; jax envs rasterize instead): an Evaluator on
    jax:lift with video enabled must write an episode recording."""
    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.evaluator import Evaluator
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    vdir = str(tmp_path / "vids")
    env_cfg = Config(
        name="jax:lift",
        num_envs=1,
        video=Config(enabled=True, dir=vdir, every_n_episodes=1),
    ).extend(BASE_ENV_CONFIG)
    probe = make_env(env_cfg)
    learner = build_learner(Config(algo=Config(name="ppo")), probe.specs)
    state = learner.init(jax.random.key(0))
    ev = Evaluator(env_cfg, Config(episodes=2, mode="deterministic", max_steps=20), learner)
    try:
        out = ev.evaluate(state, jax.random.key(1))
        assert np.isfinite(out["eval/return"])
        files = os.listdir(vdir)
        assert any(f.startswith("episode_") for f in files), files
    finally:
        ev.close()


# -- driver artifact contract ------------------------------------------------

@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_chip_scripts_fail_without_a_chip(script):
    """chip_smoke.py is a chip tool: on a machine without a
    TPU it exits non-zero and prints no result — no CPU number under a
    device metric's name, no exit-0 error artifact, no ``"ok": true``."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo, timeout=300,
    )
    assert out.returncode != 0, out.stdout[-2000:]
    assert out.stdout.strip() == "", out.stdout[-2000:]
    assert "TPU" in out.stderr, out.stderr[-2000:]


def test_backend_tpu_refuses_any_other_resolved_platform(capsys):
    """``session_config.backend='tpu'`` (the default) means a TPU, not
    "whatever JAX resolves": without an explicit CPU selection in the
    process, a resolved platform other than 'tpu' is an error — and a
    ``--local-procs`` group, whose ranks would each initialise this
    host's TPU runtime, is refused before anything is spawned."""
    from surreal_tpu.main.launch import _require_platform, main

    _require_platform("tpu")  # conftest selected the CPU explicitly: fine
    _require_platform("cpu")
    argv = ["train", "ppo", "jax:cartpole", "--folder", "unused",
            "--local-procs", "2"]
    old = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", None)  # nobody chose the CPU
        with pytest.raises(RuntimeError, match="resolved platform 'cpu'"):
            _require_platform("tpu")
        _require_platform("cpu")
        assert main(argv) == 2
    finally:
        jax.config.update("jax_platforms", old)
    assert "a chip belongs to one process" in capsys.readouterr().err
    assert not os.path.exists("unused")
