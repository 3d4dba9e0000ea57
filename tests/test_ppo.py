"""PPO learner unit tests + the CartPole does-it-learn integration test
(SURVEY.md §4: "PPO on CartPole-v1 must reach reward >=475 within a
time-boxed budget" — BASELINE config ①)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs.base import ArraySpec, DiscreteSpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.launch.trainer import Trainer
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config


def _continuous_specs(obs_dim=6, act_dim=3):
    return EnvSpecs(
        obs=ArraySpec(shape=(obs_dim,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(act_dim,), dtype=np.dtype(np.float32)),
    )


def _fake_batch(key, T=8, B=4, obs_dim=6, act_dim=3):
    ks = jax.random.split(key, 4)
    return {
        "obs": jax.random.normal(ks[0], (T, B, obs_dim)),
        "next_obs": jax.random.normal(ks[1], (T, B, obs_dim)),
        "action": jax.random.normal(ks[2], (T, B, act_dim)),
        "reward": jax.random.normal(ks[3], (T, B)),
        "done": jnp.zeros((T, B), bool).at[3, 1].set(True),
        "terminated": jnp.zeros((T, B), bool).at[3, 1].set(True),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, act_dim)),
            "log_std": jnp.full((T, B, act_dim), -0.5),
        },
    }


def test_ppo_learn_updates_params_and_metrics_finite():
    learner = build_learner(Config(algo=Config(name="ppo")), _continuous_specs())
    state = learner.init(jax.random.key(0))
    batch = _fake_batch(jax.random.key(1))
    new_state, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(2))

    # params changed
    diffs = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), state.params, new_state.params
    )
    assert max(jax.tree.leaves(diffs)) > 0
    assert int(new_state.iteration) == 1
    for k, v in metrics.items():
        assert np.isfinite(float(v)), f"metric {k} not finite"
    # obs filter updated
    assert float(new_state.obs_stats.count) > float(state.obs_stats.count)


def test_ppo_value_bootstrap_shared_matches_exact_without_truncation():
    """`value_bootstrap='shared'` (one value forward over the shifted
    stack) is exactly the default path whenever next_obs[t] == obs[t+1]
    and episodes end by TERMINATION (bootstrap discount 0) — i.e. its
    documented bias is confined to truncation boundaries."""
    key = jax.random.key(1)
    T, B, obs_dim, act_dim = 8, 4, 6, 3
    ks = jax.random.split(key, 3)
    obs_stack = jax.random.normal(ks[0], (T + 1, B, obs_dim))
    batch = {
        "obs": obs_stack[:-1],
        "next_obs": obs_stack[1:],  # consistent successor chain
        "action": jax.random.normal(ks[1], (T, B, act_dim)),
        "reward": jax.random.normal(ks[2], (T, B)),
        # terminations only: v_next at those rows is masked by discount 0
        "done": jnp.zeros((T, B), bool).at[3, 1].set(True),
        "terminated": jnp.zeros((T, B), bool).at[3, 1].set(True),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, act_dim)),
            "log_std": jnp.full((T, B, act_dim), -0.5),
        },
    }
    results = {}
    for mode in ("exact", "shared"):
        learner = build_learner(
            Config(algo=Config(name="ppo", value_bootstrap=mode)),
            _continuous_specs(),
        )
        state = learner.init(jax.random.key(0))
        new_state, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(2))
        results[mode] = (new_state, metrics)
    for k in results["exact"][1]:
        np.testing.assert_allclose(
            float(results["exact"][1][k]),
            float(results["shared"][1][k]),
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"metric {k} diverges between value_bootstrap exact/shared",
        )


def test_ppo_adaptive_kl_mode_runs_and_adapts_beta():
    learner = build_learner(
        Config(algo=Config(name="ppo", ppo_mode="adapt", kl_target=1e-6)),
        _continuous_specs(),
    )
    state = learner.init(jax.random.key(0))
    batch = _fake_batch(jax.random.key(1))
    # kl_target tiny -> any movement overshoots -> beta must increase
    s1, m1 = jax.jit(learner.learn)(state, batch, jax.random.key(2))
    s2, m2 = jax.jit(learner.learn)(s1, batch, jax.random.key(3))
    assert float(s2.kl_beta) > float(state.kl_beta)


def test_ppo_act_modes_discrete():
    specs = EnvSpecs(
        obs=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), n=2),
    )
    learner = build_learner(Config(algo=Config(name="ppo")), specs)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (32, 4))
    a, info = learner.act(state, obs, jax.random.key(2), "training")
    assert a.shape == (32,) and a.dtype == jnp.int32
    assert info["logp"].shape == (32,)
    a_det, _ = learner.act(state, obs, jax.random.key(3), "eval_deterministic")
    a_det2, _ = learner.act(state, obs, jax.random.key(4), "eval_deterministic")
    assert bool(jnp.all(a_det == a_det2))  # deterministic ignores key


def test_ppo_early_stop_flag_halts_policy_movement():
    """With an absurdly low early-stop threshold the policy coefficient
    zeroes after minibatch 1, but value learning continues."""
    learner = build_learner(
        Config(algo=Config(name="ppo", kl_target=1e-9, kl_early_stop=1.0)),
        _continuous_specs(),
    )
    state = learner.init(jax.random.key(0))
    batch = _fake_batch(jax.random.key(1))
    _, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(2))
    assert float(metrics["policy/early_stopped"]) == 1.0


def test_learn_batch_shape_guard_fails_at_seam():
    """Wrong-shape batches must fail at the learn seam with a chex error,
    not deep inside an XLA lowering (SURVEY.md §5.2)."""
    learner = build_learner(
        Config(algo=Config(name="ppo")), _continuous_specs()
    )
    state = learner.init(jax.random.key(0))
    batch = _fake_batch(jax.random.key(1))
    batch["action"] = batch["action"][..., :-1]  # act_dim 3 -> 2
    with pytest.raises(AssertionError):
        jax.jit(learner.learn)(state, batch, jax.random.key(2))


def test_replay_insert_shape_guard_fails_at_seam():
    from surreal_tpu.replay.base import init_ring, ring_insert

    example = {"obs": jnp.zeros((4,)), "reward": jnp.zeros(())}
    state = init_ring(example, capacity=16)
    bad = {"obs": jnp.zeros((8, 3)), "reward": jnp.zeros((8,))}  # obs_dim 3 != 4
    with pytest.raises(AssertionError):
        ring_insert(state, bad, capacity=16)
    with pytest.raises(ValueError):  # structure mismatch: missing key
        ring_insert(state, {"obs": jnp.zeros((8, 4))}, capacity=16)


@pytest.mark.slow
def test_trainer_run_to_run_determinism():
    """SURVEY.md §4: fixed-PRNG end-to-end run twice -> identical metrics.
    Two fresh Trainers with the same seed must produce bitwise-equal losses
    and episode stats at every metrics sync."""

    def run_once(folder):
        cfg = Config(
            learner_config=Config(algo=Config(name="ppo", horizon=16)),
            env_config=Config(name="jax:cartpole", num_envs=8),
            session_config=Config(
                folder=folder,
                seed=123,
                total_env_steps=8 * 16 * 6,  # 6 iterations
                metrics=Config(every_n_iters=1, tensorboard=False, console=False),
                checkpoint=Config(every_n_iters=0),
                eval=Config(every_n_iters=0),
            ),
        ).extend(base_config())
        seen = []
        Trainer(cfg).run(
            on_metrics=lambda it, m: seen.append(
                {k: v for k, v in m.items() if not k.startswith("time/")}
            )
        )
        return seen

    a = run_once("/tmp/test_det_a")
    b = run_once("/tmp/test_det_b")
    assert len(a) == len(b) and len(a) >= 6
    for ma, mb in zip(a, b):
        assert ma.keys() == mb.keys()
        for k in ma:
            va, vb = ma[k], mb[k]
            if np.isnan(va) and np.isnan(vb):
                continue
            assert va == vb, f"{k}: {va} != {vb} (run-to-run nondeterminism)"


def test_trainer_host_mode_gym_end_to_end():
    """Host-mode Trainer.run (gym adapter, synchronous host rollout — the
    path BASELINE config ② uses for dm_control): loss finite, episode
    stats flow, env steps accounted (VERDICT r1 weak #3)."""
    cfg = Config(
        learner_config=Config(algo=Config(name="ppo", horizon=16, epochs=2)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder="/tmp/test_ppo_host",
            total_env_steps=16 * 4 * 4,  # 4 iterations
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    assert not trainer.device_mode
    state, metrics = trainer.run()
    assert np.isfinite(metrics["loss/pg"])
    assert np.isfinite(metrics["loss/value"])
    assert metrics["time/env_steps"] >= 16 * 4 * 4


@pytest.mark.slow
def test_ppo_cheetah_run_improves():
    """BASELINE config ② end-to-end: PPO on dm_control cheetah-run (host
    adapter, 16 envs) must IMPROVE — late-run episode return above the
    early-run mean (absolute thresholds would need hours; improvement in
    ~150k steps is the does-it-learn signal the reference validated with,
    SURVEY.md §4)."""
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=128, epochs=4),
        ),
        env_config=Config(name="dm_control:cheetah-run", num_envs=16),
        session_config=Config(
            folder="/tmp/test_ppo_cheetah",
            seed=3,
            total_env_steps=150_000,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    returns = []
    Trainer(cfg).run(
        on_metrics=lambda it, m: returns.append(m.get("episode/return", np.nan))
    )
    returns = np.asarray(returns, np.float64)
    valid = returns[np.isfinite(returns)]
    assert len(valid) >= 10, f"too few completed episodes: {returns}"
    early = valid[: max(3, len(valid) // 4)].mean()
    late = valid[-max(3, len(valid) // 4):].mean()
    assert late > early + 5.0 and late > 2 * early, (
        f"no improvement on cheetah-run: early {early:.1f} -> late {late:.1f}"
    )


@pytest.mark.slow
def test_trainer_host_mode_pixel_cnn_end_to_end():
    """Config ④ analog: pixel obs (rendered, resized, grayscale,
    frame-stacked) through the Nature-CNN PPO — two host-mode iterations
    run and produce finite losses."""
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=8, epochs=1, num_minibatches=1),
            model=Config(cnn=Config(enabled=True, dense=64)),
        ),
        env_config=Config(
            name="gym:CartPole-v1",
            num_envs=2,
            pixel_obs=True,
            grayscale=True,
            frame_stack=4,
            image_size=(84, 84),
        ),
        session_config=Config(
            folder="/tmp/test_ppo_pixel",
            total_env_steps=8 * 2 * 2,  # 2 iterations
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    assert trainer.env.specs.obs.shape == (84, 84, 4)
    state, metrics = trainer.run()
    assert np.isfinite(metrics["loss/pg"])
    assert np.isfinite(metrics["loss/value"])


@pytest.mark.slow
def test_ppo_cartpole_reaches_475():
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", epochs=4),
            optimizer=Config(lr=2.5e-3),
        ),
        env_config=Config(name="jax:cartpole", num_envs=16),
        session_config=Config(
            folder="/tmp/test_ppo_cartpole",
            total_env_steps=600_000,
            metrics=Config(every_n_iters=10, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)

    best = {"ret": 0.0}

    def cb(it, m):
        r = m.get("episode/return", float("nan"))
        if not np.isnan(r):
            best["ret"] = max(best["ret"], r)
        return best["ret"] >= 475.0  # early stop

    trainer.run(on_metrics=cb)
    assert best["ret"] >= 475.0, f"best return {best['ret']} < 475"


class _SleepEnv:
    """Host env whose step costs a fixed wall-clock sleep — the
    MuJoCo-latency stand-in for the overlap test (VERDICT r3 missing #4).
    Records a timestamp per step so the test can prove env stepping
    happened DURING device learning, not just around it."""

    def __init__(self, num_envs=4, step_sleep_s=0.004):
        import numpy as _np

        self.specs = EnvSpecs(
            obs=ArraySpec(shape=(6,), dtype=_np.dtype(_np.float32)),
            action=ArraySpec(shape=(2,), dtype=_np.dtype(_np.float32)),
        )
        self.num_envs = num_envs
        self._sleep = step_sleep_s
        self._t = 0
        self.step_times: list[float] = []
        self._rng = _np.random.default_rng(0)

    def reset(self, seed=None):
        self._t = 0
        return self._rng.normal(size=(self.num_envs, 6)).astype(np.float32)

    def step(self, actions):
        import time

        from surreal_tpu.envs.base import StepOutput

        time.sleep(self._sleep)
        self.step_times.append(time.monotonic())
        self._t += 1
        done = np.full(self.num_envs, self._t % 25 == 0)
        obs = self._rng.normal(size=(self.num_envs, 6)).astype(np.float32)
        return StepOutput(
            obs=obs,
            reward=np.ones(self.num_envs, np.float32),
            done=done,
            info={
                "terminal_obs": obs,
                "truncated": np.zeros(self.num_envs, bool),
                "episode_returns": [25.0] if done.any() else [],
                "episode_lengths": [25] if done.any() else [],
            },
        )

    def close(self):
        pass


def test_host_overlap_hides_rollout_latency(tmp_path, monkeypatch):
    """topology.overlap_rollouts (the default): a collector thread steps
    the host env for iteration k+1 while the device learns on k. Proof is
    structural — env-step timestamps land strictly INSIDE learn windows —
    plus a steady-state wall-clock bound: iteration period well below
    rollout + learn (the strict-alternation cost)."""
    import time

    env = _SleepEnv()
    monkeypatch.setattr(
        "surreal_tpu.launch.trainer.make_env", lambda cfg: env
    )
    horizon = 16
    iters = 12
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=1,
                        num_minibatches=1)
        ),
        env_config=Config(name="gym:Fake-v0", num_envs=env.num_envs),
        session_config=Config(
            folder=str(tmp_path),
            total_env_steps=horizon * env.num_envs * iters,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    assert not trainer.device_mode

    learn_sleep = 0.03
    learn_windows: list[tuple[float, float]] = []
    real_learn = trainer._learn

    def slow_learn(state, batch, key):
        t0 = time.monotonic()
        time.sleep(learn_sleep)  # stand-in for real device learn latency
        out = real_learn(state, batch, key)
        jax.block_until_ready(out[0].params)
        learn_windows.append((t0, time.monotonic()))
        return out

    trainer._learn = slow_learn
    state, metrics = trainer.run()
    assert metrics["time/env_steps"] == horizon * env.num_envs * iters
    assert np.isfinite(metrics["loss/pg"])

    # structural overlap proof: env steps happened DURING learn windows
    # (strict alternation is single-threaded and cannot produce this);
    # skip the first window — it includes the learn compile, during which
    # the collector is legitimately still filling the first buffers
    inside = sum(
        1
        for (a, b) in learn_windows[2:]
        for t in env.step_times
        if a < t < b
    )
    assert inside > 0, (
        f"no env step overlapped any learn window: windows={learn_windows[:4]}..."
    )

    # steady-state iteration period < rollout + learn (the alternation
    # cost). Both sides are MEASURED, not configured: on a loaded box the
    # nominal 4ms sleep stretches, and a bound built from the configured
    # floor flakes exactly when the suite saturates the core
    starts = [a for a, _ in learn_windows]
    periods = np.diff(starts)[3:]  # past compiles/warmup
    rollout_actual = horizon * float(np.median(np.diff(env.step_times)))
    learn_actual = float(np.median([b - a for a, b in learn_windows[2:]]))
    alternation = rollout_actual + learn_actual
    assert np.median(periods) < 0.9 * alternation, (
        f"median period {np.median(periods):.3f}s vs measured alternation "
        f"floor {alternation:.3f}s (rollout {rollout_actual:.3f} + learn "
        f"{learn_actual:.3f})"
    )


def test_block_layout_selection_rules():
    """The block-shuffle plan (learners/ppo.py _block_layout) — default
    minibatch semantics for every PPO user, so the gates get direct unit
    coverage: indivisible domains and fat rows MUST fall back to row mode
    (fat-row block gathers measured 63,000 ms vs 91 ms on nut_pixels),
    degenerate block counts too."""
    from surreal_tpu.learners.ppo import _block_layout

    assert _block_layout(1024 * 128, 4, 100) == 64   # standard geometry
    assert _block_layout(64, 4, 16) == 16            # small but blockable
    assert _block_layout(100, 8, 16) == 0            # domain % num_mb != 0
    assert _block_layout(1024 * 128, 4, 16384) == 0  # fat rows (pixels)
    assert _block_layout(1000, 4, 16) == 0           # only 2 blocks fit
    # divisibility invariant: chosen layout always tiles the domain
    # exactly (no statically-excluded tail rows)
    for domain, num_mb in [(1024 * 128, 4), (64, 4), (4096, 8)]:
        k = _block_layout(domain, num_mb, 100)
        if k:
            assert domain % (num_mb * k) == 0


@pytest.mark.parametrize(
    "envs,horizon,blocks_per_mb,block_len,pieces",
    [
        pytest.param(65536, 256, 64, 65536, 64, id="cells-65536x256-in-place"),
        pytest.param(8192, 256, 64, 8192, 64, id="8192x256-in-place"),
        pytest.param(4096, 256, 64, 4096, 1, id="4096x256-gather"),
        pytest.param(8, 16, 32, 1, 1, id="8x16-one-row-blocks-gather"),
        pytest.param(25, 10, 0, 0, 1, id="row-mode-one-piece"),
    ],
)
def test_mb_pieces_rule(envs, horizon, blocks_per_mb, block_len, pieces):
    """How ``_sgd_epochs`` consumes a minibatch is a function of the block
    length alone (learners/ppo.py::_mb_pieces): a block a trip, sliced
    where it lies, at the cells' 65 536 rows a block; one gather of the
    whole minibatch at 4096 rows and below, where 1024 small passes lose
    to it; row mode has no blocks to read in place."""
    from surreal_tpu.learners.ppo import _block_layout, _mb_pieces

    domain, num_mb = envs * horizon, 4
    assert _block_layout(domain, num_mb, 17 * 4) == blocks_per_mb
    if blocks_per_mb:
        assert domain // num_mb // blocks_per_mb == block_len
    assert _mb_pieces(blocks_per_mb, block_len) == pieces


@pytest.mark.parametrize("precision", ["f32", "mixed"])
@pytest.mark.parametrize("axis_name", [None, "dp"])
def test_in_place_blocks_match_gathered_minibatch(monkeypatch, axis_name, precision):
    """``learn`` with each minibatch read a block a trip (pieces =
    blocks_per_mb) against one gather of all its blocks (pieces = 1): the
    same permutation, rows, sixteen optimizer steps and KL stop, so the
    same new parameters, optimizer state and metrics up to the order of
    the float32 sum over blocks (under 'mixed' also up to where a weight
    gradient is rounded to bfloat16: once a block, not once a minibatch).
    With ``axis_name`` it runs under shard_map on the simulated mesh, two
    envs to a device: the psum a minibatch is the same one."""
    import surreal_tpu.learners.ppo as ppo
    from surreal_tpu.parallel import dp_learn, make_mesh

    T, B = 32, 16 if axis_name else 8
    learner = build_learner(
        Config(algo=Config(name="ppo", precision=precision)), _continuous_specs()
    )
    state = learner.init(jax.random.key(0))
    batch = _fake_batch(jax.random.key(1), T=T, B=B)
    # per device 32 x 2 (dp=8) or 32 x 8 samples: 16 or 64 a minibatch
    per_mb = ppo._block_layout(T * (2 if axis_name else B), 4, 6 * 4)
    assert per_mb >= 16

    def learn(pieces):
        monkeypatch.setattr(
            ppo, "_mb_pieces", lambda blocks, rows: pieces or blocks
        )
        if axis_name is None:
            step = jax.jit(learner.learn)
        else:
            mesh = make_mesh(Config(mesh=Config({"dp": 8})))
            step = dp_learn(learner, mesh, donate=False)
        out = step(state, batch, jax.random.key(2))
        text = step.lower(state, batch, jax.random.key(2)).as_text()
        return out, text

    (gathered, g_metrics), g_text = learn(1)
    (in_place, p_metrics), p_text = learn(0)
    # the in-place program holds one loop more (epochs, minibatches, blocks)
    assert p_text.count("stablehlo.while") == g_text.count("stablehlo.while") + 1

    assert set(p_metrics) == set(g_metrics)
    assert float(p_metrics["policy/early_stopped"]) == float(
        g_metrics["policy/early_stopped"]
    )
    if precision == "f32":
        for k in g_metrics:
            np.testing.assert_allclose(
                float(p_metrics[k]), float(g_metrics[k]), err_msg=k,
                rtol=2e-4, atol=2e-6,
            )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6),
            (in_place.params, in_place.opt_state),
            (gathered.params, gathered.opt_state),
        )
    else:
        # 'mixed' rounds a weight or bias gradient to bfloat16 where the
        # rows are contracted, so a float32 sum over 1-row blocks and one
        # bfloat16 sum over a minibatch differ by percents in an element
        # near zero, and Adam turns a flipped sign into 2 x lr a step
        # (seen here: 12 of 4096 elements of one kernel off by up to
        # 2.3e-3 after the 16 steps). A wrong row or a dropped block would
        # move most elements, so the bound is on the share that moved.
        for k in g_metrics:
            np.testing.assert_allclose(
                float(p_metrics[k]), float(g_metrics[k]), err_msg=k,
                rtol=5e-3, atol=1e-3,
            )
        moved = np.concatenate([
            np.abs(np.asarray(a) - np.asarray(b)).ravel() > 5e-4
            for a, b in zip(
                jax.tree.leaves(in_place.params), jax.tree.leaves(gathered.params)
            )
        ])
        assert moved.mean() < 0.02, moved.mean()
    assert int(in_place.iteration) == int(gathered.iteration) == 1


def test_shuffle_block_matches_row_for_single_minibatch():
    """With one minibatch per epoch both modes train on ALL rows in one
    gradient, so block and row must produce the same update (up to f32
    reduction order) — pins that block mode neither drops nor duplicates
    samples."""
    batch = _fake_batch(jax.random.key(1), T=16, B=8)
    results = {}
    for shuffle in ("row", "block"):
        learner = build_learner(
            Config(algo=Config(name="ppo", epochs=1, num_minibatches=1,
                               shuffle=shuffle)),
            _continuous_specs(),
        )
        state = learner.init(jax.random.key(0))
        new_state, metrics = jax.jit(learner.learn)(
            state, batch, jax.random.key(2)
        )
        results[shuffle] = (new_state, metrics)
    for k in results["row"][1]:
        # rtol 5e-3, not 1e-3: health/grad_norm sits downstream of a bf16
        # forward + a full-tree reduction, and this image's CPU backend
        # orders those reductions differently per gather layout (measured
        # delta 1.7e-3 relative — a platform reduction-order artifact, an
        # order of magnitude under the ~1e-3-scale per-row gradient signal
        # a dropped/duplicated sample would move params by; see the
        # params check below)
        np.testing.assert_allclose(
            float(results["row"][1][k]), float(results["block"][1][k]),
            rtol=5e-3, atol=1e-4,
            err_msg=f"metric {k} diverges between shuffle=row and block",
        )
    # bf16 activations + a different gather order shift reductions; on
    # this image's CPU backend the worst case lands on near-zero
    # Adam-updated weights at ~2.4e-4 absolute (rel is meaningless at
    # zero). atol 5e-4 absorbs that platform delta while a dropped or
    # duplicated minibatch row would still move params by the per-row
    # gradient scale (~1e-3 here), well past this
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-2, atol=5e-4),
        results["row"][0].params,
        results["block"][0].params,
    )


# -- the unroll keys change the program's shape, not its result --------------
# (tolerances: conftest.py::assert_same_update)
_FUSED = {}


def _fused_ppo(tmp_path, **algo_over):
    """Metrics and params after one fused PPO iteration on jax:pendulum
    (8 envs x horizon 8, 2 epochs x 2 minibatches), memoized per variant."""
    tag = tuple(sorted(algo_over.items()))
    if tag not in _FUSED:
        cfg = Config(
            learner_config=Config(algo=Config(
                name="ppo", horizon=8, epochs=2, num_minibatches=2, **algo_over
            )),
            env_config=Config(name="jax:pendulum", num_envs=8),
            session_config=Config(folder=str(tmp_path)),
        ).extend(base_config())
        t = Trainer(cfg)
        key, ik, ek = jax.random.split(jax.random.key(3), 3)
        state = t.learner.init(ik)
        if t.mesh is not None and t.mesh.size > 1:
            from surreal_tpu.parallel.mesh import replicate_state

            state = replicate_state(t.mesh, state)
        state, _, metrics = t._train_iter(
            state, t.init_loop_state(ek), jax.random.split(key)[1]
        )
        _FUSED[tag] = jax.device_get((metrics, state.params))
    return _FUSED[tag]


# the default's rollout scan runs four steps a trip here (a memoryless
# policy over jax:pendulum's vector observations: launch/rollout.py::
# rollout_unroll), so the variant that differs from it is an explicit 1
@pytest.mark.parametrize(
    "variant",
    [
        {"rollout_unroll": 1},
        {"sgd_unroll": 2},
        {"gae_unroll": 4},
        pytest.param({"rollout_unroll": 1, "sgd_unroll": 2, "gae_unroll": 2},
                     marks=pytest.mark.slow),
    ],
    ids=["rollout", "sgd", "gae", "all-unrolls"],
)
def test_ppo_unrolled_program_matches_default(
    tmp_path, variant, assert_same_update
):
    assert_same_update(_fused_ppo(tmp_path), _fused_ppo(tmp_path, **variant))


# -- the rollout scan chooses its own unroll ---------------------------------
_VECTOR_OBS = jax.ShapeDtypeStruct((8, 17), jnp.float32)
_PIXEL_OBS = jax.ShapeDtypeStruct((8, 84, 84, 4), jnp.uint8)
_NO_CARRY = None  # what a memoryless learner's act_init returns
_A_CARRY = {"cache": jax.ShapeDtypeStruct((8, 16, 32), jnp.bfloat16), "t": 0}
_TOY_TRAJECTORY = Config(
    encoder=Config(
        kind="trajectory", features=32, num_layers=1, num_heads=2, head_dim=8
    )
)


@pytest.mark.parametrize(
    "act_carry,obs,horizon,unroll,want",
    [
        (_NO_CARRY, _VECTOR_OBS, 256, 0, 4),
        (_NO_CARRY, _VECTOR_OBS, 2, 0, 2),
        ((), _VECTOR_OBS, 256, 0, 4),
        (_A_CARRY, _VECTOR_OBS, 256, 0, 1),
        (_NO_CARRY, _PIXEL_OBS, 256, 0, 1),
        (_A_CARRY, _PIXEL_OBS, 256, 0, 1),
        (_NO_CARRY, _VECTOR_OBS, 256, 1, 1),
        (_NO_CARRY, _VECTOR_OBS, 256, 8, 8),
        (_A_CARRY, _VECTOR_OBS, 256, 2, 2),
        (_NO_CARRY, _PIXEL_OBS, 256, 4, 4),
        (_NO_CARRY, _PIXEL_OBS, 2, 4, 2),
    ],
    ids=[
        "memoryless-vector", "memoryless-vector-short-horizon",
        "empty-tuple-carry", "acting-carry", "pixels", "acting-carry-pixels",
        "explicit-1-over-memoryless-vector", "explicit-8-over-memoryless-vector",
        "explicit-2-over-acting-carry", "explicit-4-over-pixels",
        "explicit-clamped-to-horizon",
    ],
)
def test_rollout_unroll_rule(act_carry, obs, horizon, unroll, want):
    """``launch/rollout.py::rollout_unroll``: four env steps a trip where the
    policy acts without a carry over vector observations, one where it
    carries a cache or a state or sees pixels, a user's number over either,
    all clamped to the horizon."""
    from surreal_tpu.launch.rollout import rollout_unroll

    assert rollout_unroll(act_carry, obs, horizon, unroll) == want


def test_rollout_unroll_rule_reads_the_learner_s_own_carry():
    """The two facts the rule reads, from real learners: a memoryless PPO's
    ``act_init`` is an empty pytree, a trajectory policy's is not."""
    from surreal_tpu.launch.rollout import rollout_unroll

    specs = EnvSpecs(
        obs=ArraySpec(shape=(17,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
    )
    algo = Config(name="ppo", horizon=8, epochs=1, num_minibatches=1)
    mlp = build_learner(Config(algo=algo), specs)
    seq = build_learner(Config(algo=algo, model=_TOY_TRAJECTORY), specs)
    assert not mlp.requires_act_carry and seq.requires_act_carry
    assert rollout_unroll(mlp.act_init(8), _VECTOR_OBS, 8) == 4
    assert rollout_unroll(seq.act_init(8), _VECTOR_OBS, 8) == 1


@pytest.mark.parametrize(
    "model,algo_over,trips",
    [
        (None, {}, 2),
        (None, {"rollout_unroll": 1}, 8),
        (_TOY_TRAJECTORY, {}, 8),
    ],
    ids=["memoryless", "memoryless-explicit-1", "trajectory"],
)
def test_fused_ppo_collect_scan_trips(collect_scan_trips, model, algo_over, trips):
    """Toy PPO on ``jax:lift``, horizon 8: the collect loop of the lowered
    iteration has ``horizon / 4`` trips for the MLP policy, ``horizon`` for a
    trajectory policy, and what a user's ``algo.rollout_unroll`` says."""
    assert trips == collect_scan_trips(
        "ppo", "jax:lift", model, epochs=1, num_minibatches=1, **algo_over
    )
