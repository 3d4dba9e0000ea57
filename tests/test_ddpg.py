"""DDPG learner / n-step aggregator / off-policy trainer tests
(SURVEY.md §4; BASELINE config ③ pairs DDPG with prioritized replay)."""

import os
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.learners.aggregator import nstep_transitions
from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config


def _specs(obs_dim=5, act_dim=2):
    return EnvSpecs(
        obs=ArraySpec(shape=(obs_dim,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(act_dim,), dtype=np.dtype(np.float32)),
    )


def _flat_batch(key, B=32, obs_dim=5, act_dim=2):
    ks = jax.random.split(key, 4)
    return {
        "obs": jax.random.normal(ks[0], (B, obs_dim)),
        "next_obs": jax.random.normal(ks[1], (B, obs_dim)),
        "action": jnp.clip(jax.random.normal(ks[2], (B, act_dim)), -1, 1),
        "reward": jax.random.normal(ks[3], (B,)),
        "discount": jnp.full((B,), 0.99),
    }


def test_ddpg_learn_updates_and_targets_move_softly():
    learner = build_learner(Config(algo=Config(name="ddpg")), _specs())
    state = learner.init(jax.random.key(0))
    batch = _flat_batch(jax.random.key(1))
    new_state, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(2))

    assert metrics.pop("priority/td_abs").shape == (32,)
    for k, v in metrics.items():
        assert np.isfinite(float(v)), k
    # live params moved
    moved = max(
        jax.tree.leaves(
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.critic_params, new_state.critic_params)
        )
    )
    assert moved > 0
    # targets moved by tau-fraction: strictly less than live movement
    t_moved = max(
        jax.tree.leaves(
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.target_critic_params, new_state.target_critic_params)
        )
    )
    assert 0 < t_moved < moved


def test_ddpg_hard_target_update_period():
    learner = build_learner(
        Config(algo=Config(name="ddpg", target=Config(mode="hard", hard_every=2))),
        _specs(),
    )
    state = learner.init(jax.random.key(0))
    batch = _flat_batch(jax.random.key(1))
    learn = jax.jit(learner.learn)
    s1, _ = learn(state, batch, jax.random.key(2))
    # iteration 1: no copy yet -> targets unchanged
    assert all(
        np.allclose(a, b)
        for a, b in zip(
            jax.tree.leaves(state.target_critic_params),
            jax.tree.leaves(s1.target_critic_params),
        )
    )
    s2, _ = learn(s1, batch, jax.random.key(3))
    # iteration 2: hard copy -> targets == live
    assert all(
        np.allclose(a, b)
        for a, b in zip(
            jax.tree.leaves(s2.critic_params),
            jax.tree.leaves(s2.target_critic_params),
        )
    )


def test_ddpg_is_weights_scale_gradient():
    learner = build_learner(Config(algo=Config(name="ddpg")), _specs())
    state = learner.init(jax.random.key(0))
    batch = _flat_batch(jax.random.key(1))
    zero_w = dict(batch, is_weights=jnp.zeros_like(batch["reward"]))
    new_state, _ = jax.jit(learner.learn)(state, zero_w, jax.random.key(2))
    # zero IS weights -> zero grads -> params unchanged (adam of 0 grad is 0)
    moved = max(
        jax.tree.leaves(
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.critic_params, new_state.critic_params)
        )
    )
    assert moved < 1e-7


def test_nstep_transitions_golden():
    """n-step folding vs a slow python reference on a trajectory with an
    episode boundary inside the window."""
    T, B, n, gamma = 5, 1, 3, 0.9
    reward = jnp.asarray([[1.0], [2.0], [3.0], [4.0], [5.0]])
    done = jnp.asarray([[0], [1], [0], [0], [0]], bool)        # episode ends at t=1
    term = jnp.asarray([[0], [1], [0], [0], [0]], bool)        # true termination
    obs = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.ones((T, 1, 2))
    next_obs = obs + 100.0
    action = jnp.zeros((T, B, 1))
    traj = dict(obs=obs, next_obs=next_obs, action=action, reward=reward,
                done=done, terminated=term)
    out = nstep_transitions(traj, gamma, n)
    # S = 3 window starts
    # t=0: r0 + g*r1 (dies at k=1, terminated) = 1 + .9*2 = 2.8; discount 0
    np.testing.assert_allclose(float(out["reward"][0]), 2.8, rtol=1e-6)
    np.testing.assert_allclose(float(out["discount"][0]), 0.0)
    np.testing.assert_allclose(np.asarray(out["next_obs"][0]), 101.0)  # next_obs[1]
    # t=1: dies immediately: r=2, discount 0, next_obs[1]
    np.testing.assert_allclose(float(out["reward"][1]), 2.0)
    np.testing.assert_allclose(float(out["discount"][1]), 0.0)
    # t=2: full window: 3 + .9*4 + .81*5 = 10.65; discount gamma^3; next_obs[4]
    np.testing.assert_allclose(float(out["reward"][2]), 10.65, rtol=1e-6)
    np.testing.assert_allclose(float(out["discount"][2]), gamma**3, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["next_obs"][2]), 104.0)


def test_scrub_fake_prefix_windows_removes_all_fabricated_rows():
    """The run's first chunk is folded with an all-zero fabricated tail
    prepended; every one of the (n-1)*B windows starting inside it must be
    replaced by the first REAL window block, per-env aligned (regression:
    the scrub once indexed window counts into the flattened [S*B] layout
    and left all fake rows in place for B > 1)."""
    from surreal_tpu.launch.offpolicy_trainer import scrub_fake_prefix_windows

    T, B, n, gamma = 4, 3, 3, 0.9
    # fabricated tail exactly as OffPolicyTrainer builds it
    fake = dict(
        obs=jnp.zeros((n - 1, B, 2)),
        next_obs=jnp.zeros((n - 1, B, 2)),
        action=jnp.zeros((n - 1, B, 1)),
        reward=jnp.zeros((n - 1, B)),
        done=jnp.ones((n - 1, B), bool),
        terminated=jnp.ones((n - 1, B), bool),
    )
    # real chunk: obs encodes (time, env) so rows are distinguishable
    t_idx = jnp.arange(1, T + 1, dtype=jnp.float32)[:, None, None]
    b_idx = jnp.arange(1, B + 1, dtype=jnp.float32)[None, :, None]
    obs = jnp.concatenate([t_idx * jnp.ones((T, B, 1)), b_idx * jnp.ones((T, B, 1))], -1)
    real = dict(
        obs=obs,
        next_obs=obs + 100.0,
        action=jnp.ones((T, B, 1)),
        reward=jnp.ones((T, B)),
        done=jnp.zeros((T, B), bool),
        terminated=jnp.zeros((T, B), bool),
    )
    full = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), fake, real)
    trans = nstep_transitions(full, gamma, n)
    out = scrub_fake_prefix_windows(trans, n, B)

    nb = (n - 1) * B
    # no all-zero obs row survives anywhere
    assert not bool(jnp.any(jnp.all(out["obs"] == 0.0, axis=-1)))
    # fake rows were replaced by the first real window block, env-aligned
    for s in range(n - 1):
        np.testing.assert_array_equal(
            np.asarray(out["obs"][s * B : (s + 1) * B]),
            np.asarray(out["obs"][nb : nb + B]),
        )
    # real rows untouched
    np.testing.assert_array_equal(
        np.asarray(out["obs"][nb:]), np.asarray(trans["obs"][nb:])
    )
    # the real block's per-env identity is intact (env column = 1..B)
    np.testing.assert_allclose(np.asarray(out["obs"][:B, 1]), np.arange(1, B + 1))


def test_nstep_truncation_keeps_bootstrap():
    """Truncated (not terminated) boundary: discount stays nonzero so the
    learner bootstraps from the terminal obs."""
    T, n, gamma = 3, 3, 0.9
    traj = dict(
        obs=jnp.zeros((T, 1, 2)),
        next_obs=jnp.ones((T, 1, 2)),
        action=jnp.zeros((T, 1, 1)),
        reward=jnp.ones((T, 1)),
        done=jnp.asarray([[0], [1], [0]], bool),
        terminated=jnp.asarray([[0], [0], [0]], bool),  # truncation at t=1
    )
    out = nstep_transitions(traj, gamma, n)
    np.testing.assert_allclose(float(out["reward"][0]), 1 + 0.9)
    np.testing.assert_allclose(float(out["discount"][0]), gamma**2, rtol=1e-6)


def test_ou_noise_mean_reverts():
    from surreal_tpu.learners.ddpg import ou_noise_step

    noise = jnp.full((4, 2), 5.0)
    key = jax.random.key(0)
    for i in range(200):
        key, k = jax.random.split(key)
        noise = ou_noise_step(noise, k, theta=0.15, sigma=0.2)
    assert float(jnp.abs(noise).mean()) < 2.0  # pulled back toward 0


@pytest.mark.slow
def test_ddpg_pendulum_improves():
    """DDPG + prioritized replay on jax:pendulum must clearly beat the
    random policy (~-1200 avg return) within a small budget."""
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ddpg"),
            # divisible by the 8-way dp mesh the trainer now defaults to
            replay=Config(kind="prioritized", capacity=50_048,
                          start_sample_size=512, batch_size=128),
        ),
        env_config=Config(name="jax:pendulum", num_envs=8),
        session_config=Config(
            folder="/tmp/test_ddpg_pendulum",
            total_env_steps=100_000,
            metrics=Config(every_n_iters=25, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = OffPolicyTrainer(cfg)
    returns = []

    def cb(it, m):
        r = m.get("episode/return", float("nan"))
        if not np.isnan(r):
            returns.append(r)
        return len(returns) >= 3 and max(returns[-3:]) > -400.0

    trainer.run(on_metrics=cb)
    assert returns and max(returns) > -400.0, f"returns {returns[-5:]}"


def test_offpolicy_host_mode_nstep_end_to_end():
    """Host-mode OffPolicyTrainer (gym adapter) with n_step>1: runs real
    updates, finite losses, and the first-chunk fabricated prefix is
    scrubbed on this path too (review r2: the scrub originally existed
    only in the device path)."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    cfg = Config(
        learner_config=Config(
            algo=Config(
                name="ddpg",
                horizon=8,
                n_step=3,
                updates_per_iter=2,
                exploration=Config(warmup_steps=0),
            ),
            replay=Config(
                kind="prioritized", capacity=512, start_sample_size=16, batch_size=32
            ),
        ),
        env_config=Config(name="gym:Pendulum-v1", num_envs=4),
        session_config=Config(
            folder="/tmp/test_ddpg_host",
            total_env_steps=8 * 4 * 5,  # 5 iterations
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = OffPolicyTrainer(cfg)
    assert not trainer.device_mode
    state, metrics = trainer.run()
    assert np.isfinite(metrics["loss/critic"])
    assert np.isfinite(metrics["loss/actor"])
    assert metrics["time/env_steps"] >= 8 * 4 * 5


@pytest.mark.slow
def test_offpolicy_replay_checkpoint_resume_skips_warmup(tmp_path):
    """checkpoint.include_replay (beyond-parity opt-in; the reference did
    NOT checkpoint replay, SURVEY §5.4): a resumed run must reload the
    buffer snapshot and do real SGD updates on its FIRST iteration,
    instead of skipping updates while the replay refills."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
    from surreal_tpu.session.default_configs import base_config

    def cfg(total_steps):
        return Config(
            learner_config=Config(
                algo=Config(
                    name="ddpg",
                    horizon=8,
                    updates_per_iter=2,
                    exploration=Config(warmup_steps=0),
                ),
                replay=Config(
                    kind="uniform",
                    capacity=4096,
                    # warmup needs TWO chunks (8*16=128 each): a fresh run's
                    # first iteration must SKIP updates, a resumed-with-
                    # replay run must not
                    start_sample_size=200,
                    batch_size=64,
                ),
            ),
            env_config=Config(name="jax:pendulum", num_envs=16),
            session_config=Config(
                folder=str(tmp_path / "exp"),
                total_env_steps=total_steps,
                metrics=Config(every_n_iters=1, tensorboard=False, console=False),
                checkpoint=Config(every_n_iters=2, include_replay=True),
                eval=Config(every_n_iters=0),
            ),
        ).extend(base_config())

    steps_per_iter = 8 * 16
    first_metrics: list = []
    OffPolicyTrainer(cfg(4 * steps_per_iter)).run(
        on_metrics=lambda it, m: first_metrics.append((it, m["q/mean_abs_td"]))
    )
    # sanity: the fresh run's first iteration skipped updates (warmup)
    assert first_metrics[0][1] == 0.0
    assert any(v != 0.0 for _, v in first_metrics)
    extra_dir = tmp_path / "exp" / "checkpoints" / "extra"
    assert extra_dir.is_dir() and any(d.isdigit() for d in os.listdir(extra_dir))

    resumed: list = []
    OffPolicyTrainer(cfg(6 * steps_per_iter)).run(
        on_metrics=lambda it, m: resumed.append((it, m["q/mean_abs_td"]))
    )
    assert resumed, "resume ran no iterations"
    assert resumed[0][0] > 4  # iteration counter continued
    # the buffer came back with the checkpoint: updates ran immediately
    assert resumed[0][1] != 0.0, resumed


def test_fused_prioritized_iteration_is_the_sequence_run_by_hand(monkeypatch):
    """The fused iteration keeps the draw's block sums in its update loop's
    carry (replay/prioritized.py). From a fixed seed it gives the state, the
    replay state and the metrics row of the sequence it fuses, sample ->
    learn -> update_priorities with no ``mass`` and one program a step, run
    here by hand on the ring as the iteration's insert left it."""
    one = jax.devices()[:1]  # one ring, not the suite's eight dp shards
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    updates, batch_size = 4, 32
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=8, updates_per_iter=updates,
                        exploration=Config(warmup_steps=0)),
            replay=Config(kind="prioritized", capacity=1000,
                          start_sample_size=64, batch_size=batch_size),
        ),
        env_config=Config(name="jax:pendulum", num_envs=8),
        session_config=Config(folder="/tmp/test_ddpg_by_hand"),
    ).extend(base_config())
    trainer = OffPolicyTrainer(cfg)
    assert trainer.prioritized and not trainer._batched_sampling
    replay, learner = trainer.replay, trainer.learner
    iterate = jax.jit(trainer._device_train_iter)
    key = jax.random.key(3)
    carry, replay_state = trainer.init_loop_state(key)
    state = learner.init(key)
    beta, warmup = jnp.float32(0.4), jnp.asarray(False)
    # two iterations of 64 rows fill the ring past start_sample_size and
    # leave priorities that TD errors have set
    for it in range(2):
        state, replay_state, carry, row = iterate(
            state, replay_state, carry, jax.random.fold_in(key, it),
            beta, warmup, jnp.asarray(it == 0),
        )
    assert row["loss/critic"] != 0.0
    args = (state, replay_state, carry, jax.random.fold_in(key, 2),
            beta, warmup, jnp.asarray(False))
    fused_state, fused_replay, _, fused_row = iterate(*args)

    # the iteration up to its update loop: collect, insert, no update
    monkeypatch.setattr(replay, "can_sample", lambda s: jnp.asarray(False))
    # (a function of its own: the bound method's trace is cached above)
    state, replay_state, _, skipped_row = jax.jit(
        lambda *a: trainer._device_train_iter(*a)
    )(*args)
    assert int(state.iteration) == int(args[0].iteration)
    monkeypatch.undo()
    sample = jax.jit(lambda s, k: replay.sample(s, k, beta=beta))
    learn, rescatter = jax.jit(learner.learn), jax.jit(replay.update_priorities)
    rows = []
    for update_key in jax.random.split(jax.random.split(args[3])[1], updates):
        replay_state, batch, info = sample(replay_state, update_key)
        state, metrics = learn(
            state, dict(batch, is_weights=info["is_weights"]), update_key
        )
        metrics["replay/sample_age_frac"] = replay.age_frac(replay_state, info["idx"])
        replay_state = rescatter(
            replay_state, info["idx"], metrics.pop("priority/td_abs")
        )
        rows.append(metrics)
    by_hand_row = dict(
        skipped_row,
        **{k: jnp.mean(jnp.stack([r[k] for r in rows])) for k in rows[0]},
        **replay.gauges(replay_state),
    )

    refreshed = float(fused_row.pop("replay/mass_blocks_refreshed"))
    assert 0 < refreshed <= batch_size
    by_hand_row.pop("replay/mass_blocks_refreshed")  # the skipped loop's zero
    jax.tree.map(
        np.testing.assert_array_equal,
        (fused_state, fused_replay), (state, replay_state),
    )
    assert set(fused_row) == set(by_hand_row)
    for k in fused_row:
        np.testing.assert_allclose(
            float(fused_row[k]), float(by_hand_row[k]), rtol=1e-6, err_msg=k
        )


# -- the unroll keys and the batched draw change the program, not its result --
# (tolerances: conftest.py::assert_same_update)
_FUSED = {}


def _small_ddpg_config(tmp_path, replay=None, **algo_over):
    """jax:pendulum, 8 envs x horizon 8, 4 updates of 16 rows an iteration:
    batch, start and capacity divide by the 8-way dp mesh the trainer takes
    on the suite's simulated devices."""
    return Config(
        learner_config=Config(
            algo=Config(
                name="ddpg", horizon=8, exploration=Config(warmup_steps=0),
                updates_per_iter=4, **algo_over,
            ),
            replay=Config(batch_size=16, start_sample_size=16, **(replay or {})),
        ),
        env_config=Config(name="jax:pendulum", num_envs=8),
        session_config=Config(folder=str(tmp_path)),
    ).extend(base_config())


def _fused_ddpg(tmp_path, **algo_over):
    """Metrics and both networks' params after one fused DDPG iteration,
    memoized per variant."""
    tag = tuple(sorted(algo_over.items()))
    if tag not in _FUSED:
        t = OffPolicyTrainer(_small_ddpg_config(tmp_path, **algo_over))
        key, ik, ek = jax.random.split(jax.random.key(3), 3)
        state = t.learner.init(ik)
        if t.mesh is not None and t.mesh.size > 1:
            from surreal_tpu.parallel.mesh import replicate_state

            state = replicate_state(t.mesh, state)
        carry, replay_state = t.init_loop_state(ek)
        state, _, _, metrics = t._train_iter(
            state, replay_state, carry, jax.random.split(key)[1],
            jnp.asarray(0.0, jnp.float32), jnp.asarray(False), jnp.asarray(True),
        )
        _FUSED[tag] = jax.device_get((
            metrics,
            {"actor": state.actor_params, "critic": state.critic_params},
        ))
    return _FUSED[tag]


@pytest.mark.parametrize(
    "variant",
    [
        {"rollout_unroll": 4},
        {"update_unroll": 4},
        pytest.param({"rollout_unroll": 2, "update_unroll": 2},
                     marks=pytest.mark.slow),
    ],
    ids=["rollout", "update", "both"],
)
def test_ddpg_unrolled_program_matches_default(
    tmp_path, variant, assert_same_update
):
    assert_same_update(_fused_ddpg(tmp_path), _fused_ddpg(tmp_path, **variant))


def test_ddpg_batched_sampling_record_equivalence(tmp_path, assert_same_update):
    """The uniform-replay fast path (one batched index draw + gather for
    the whole update loop) must train on the IDENTICAL record as the
    sequential path: same keys -> same indices -> same batches -> same
    updates. Index/batch equality is bit-exact (tests/test_replay.py);
    here the fused iteration's metrics and params must agree to float32
    fusion-reordering tolerance."""
    assert_same_update(
        _fused_ddpg(tmp_path, batched_uniform_sampling=False),
        _fused_ddpg(tmp_path, batched_uniform_sampling=True),
    )


def test_prioritized_replay_keeps_sequential_sampling(tmp_path):
    """Prioritized replay must NOT take the batched path: priorities
    change between updates, so draw k+1 depends on draw k's TD errors."""
    t = OffPolicyTrainer(
        _small_ddpg_config(tmp_path, replay={"kind": "prioritized"})
    )
    assert t.prioritized and not t._batched_sampling
