"""Dispatch-pipeline invariants: donated train states (HBM reuse + the
stale-reuse contract), where the persistent compile cache lives,
and the double-buffered host->device prefetcher."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config


def _donation_supported() -> bool:
    """Probe whether this backend actually implements buffer donation
    (older CPU runtimes silently ignore donate_argnums)."""
    x = jnp.ones((4,))
    jax.jit(lambda v: v + 1, donate_argnums=(0,))(x)
    return x.is_deleted()


def _trainer_cfg(folder, dp=None, **session_overrides):
    if dp is not None:
        session_overrides["topology"] = Config(mesh=Config(dp=dp, tp=1))
    return Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=8, epochs=1, num_minibatches=1)
        ),
        env_config=Config(name="jax:cartpole", num_envs=16),
        session_config=Config(
            folder=str(folder),
            total_env_steps=8 * 16 * 3,
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            **session_overrides,
        ),
    ).extend(base_config())


# -- donation -----------------------------------------------------------------

def test_fused_train_iter_donates_state_and_carry(tmp_path):
    """The donation invariant, both directions: the fused iteration's
    donated inputs are actually released (their HBM is reused, the whole
    point), and a driver bug that reads a donated reference after
    dispatch raises loudly instead of silently training on stale
    buffers. The stale reference is READ, not handed back to the
    program: on the 8-device CPU mesh jax 0.9.0 rejects a deleted
    argument per replica at launch, after other replicas started, and
    the half-launched collective wedges every later mesh program of the
    process."""
    if not _donation_supported():
        pytest.skip("backend ignores donate_argnums")
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.parallel.mesh import batch_sharded, replicate_state

    trainer = Trainer(_trainer_cfg(tmp_path / "don"))
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    # commit state/carry exactly as run() does — an UNCOMMITTED input's
    # donation is silently dropped by the reshard, which is why run()
    # commits both before the first iteration
    state0 = replicate_state(trainer.mesh, trainer.learner.init(init_key))
    carry0 = jax.device_put(
        init_device_carry(trainer.env, env_key, trainer.num_envs),
        batch_sharded(trainer.mesh),
    )

    state1, carry1, metrics = trainer._train_iter(state0, carry0, key)
    jax.block_until_ready(metrics)
    assert all(x.is_deleted() for x in jax.tree.leaves(state0.params))
    assert all(x.is_deleted() for x in jax.tree.leaves(carry0))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(jax.tree.leaves(state0.params)[0])
    # the chained (rebinding) call pattern every driver uses keeps working
    state2, carry2, m2 = trainer._train_iter(state1, carry1, key)
    jax.block_until_ready(m2)


def test_offpolicy_fused_iter_donates_replay_state(tmp_path):
    """Same contract for the off-policy fused iteration, whose donated
    replay storage is the largest allocation in the program."""
    if not _donation_supported():
        pytest.skip("backend ignores donate_argnums")
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=4, updates_per_iter=1,
                        exploration=Config(warmup_steps=0)),
            replay=Config(capacity=256, start_sample_size=16, batch_size=8),
        ),
        env_config=Config(name="jax:pendulum", num_envs=8),
        session_config=Config(
            folder=str(tmp_path / "don_off"),
            metrics=Config(every_n_iters=1, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    from jax.sharding import NamedSharding, PartitionSpec as P

    from surreal_tpu.parallel.dp import offpolicy_carry_specs
    from surreal_tpu.parallel.mesh import replicate_state
    from surreal_tpu.replay.sharded import sharded_replay_init

    trainer = OffPolicyTrainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    # committed exactly as run() commits them (see the on-policy test)
    state0 = replicate_state(trainer.mesh, trainer.learner.init(init_key))
    carry0 = jax.device_put(
        trainer._init_carry(env_key),
        jax.tree.map(
            lambda spec: NamedSharding(trainer.mesh, spec),
            offpolicy_carry_specs(trainer._init_carry(env_key)),
            is_leaf=lambda x: isinstance(x, P),
        ),
    )
    replay0 = sharded_replay_init(
        trainer.replay, trainer._replay_example(), trainer.mesh
    )
    args = (key, jnp.float32(0), jnp.asarray(False), jnp.asarray(True))
    state1, replay1, carry1, metrics = trainer._train_iter(
        state0, replay0, carry0, *args
    )
    jax.block_until_ready(metrics)
    assert all(x.is_deleted() for x in jax.tree.leaves(replay0.storage))
    # read, not relaunched: see the on-policy test's note
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(jax.tree.leaves(replay0.storage)[0])


def test_dp_learn_donate_flag_keeps_state_alive():
    """dp_learn(donate=False) — the SEED trainer's mode, where the
    inference server's act closure aliases the live state — must leave
    the input state readable after the step."""
    from surreal_tpu.envs.base import ArraySpec, EnvSpecs
    from surreal_tpu.learners import build_learner
    from surreal_tpu.parallel import dp_learn, make_mesh

    specs = EnvSpecs(
        obs=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
    )
    learner = build_learner(
        Config(algo=Config(name="ppo", epochs=1, num_minibatches=1)), specs
    )
    state = learner.init(jax.random.key(0))
    mesh = make_mesh(Config(mesh=Config(dp=8, tp=1)))
    T, B = 4, 16
    batch = {
        "obs": jnp.zeros((T, B, 4)), "next_obs": jnp.zeros((T, B, 4)),
        "action": jnp.zeros((T, B, 2)), "reward": jnp.zeros((T, B)),
        "done": jnp.zeros((T, B), bool), "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, 2)), "log_std": jnp.zeros((T, B, 2)),
        },
    }
    new_state, _ = dp_learn(learner, mesh, donate=False)(
        state, batch, jax.random.key(1)
    )
    # undonated: the old state stays readable (what a concurrent serve does)
    assert np.isfinite(
        float(jax.tree.leaves(state.params)[0].sum())
    )
    assert int(new_state.iteration) == 1


# -- persistent compile cache -------------------------------------------------

def test_compile_cache_fixed_path_plumbs_through(tmp_path, compile_cache_on):
    """No JAX_COMPILATION_CACHE_DIR: a session turns the cache on at the
    one fixed path (not under its own folder), jax's config points at it,
    hit/miss counts reach the telemetry log, and diag surfaces them."""
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.telemetry import diag_report, diag_summary

    folder = tmp_path / "exp_cache"
    Trainer(_trainer_cfg(folder)).run()
    assert os.path.isdir(compile_cache_on)
    assert jax.config.jax_compilation_cache_dir == compile_cache_on
    assert not os.path.exists(folder / "xla_cache")
    cc = diag_summary(str(folder))["compile_cache"]
    assert cc is not None and cc["dir"] == compile_cache_on
    # this run compiled its own fused program into an empty cache:
    # at least one miss must have been counted
    assert cc["misses"] >= 1
    assert "Compile cache" in diag_report(str(folder))


def test_compile_cache_env_var_wins_and_jax_switch_is_honoured(
    tmp_path, compile_cache_on, monkeypatch
):
    from surreal_tpu.utils.compat import enable_compile_cache

    # placed from outside: JAX's own reading of the variable stands (jax
    # reads it at import, so the test stands in for that) and the code
    # sets no directory — the fixed path is never even created
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    jax.config.update("jax_compilation_cache_dir", outside)
    assert enable_compile_cache() == outside
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(compile_cache_on)
    # the profile digest reads phases from a compiled program's op names:
    # they are part of the key (a cached executable never comes back with
    # stale ones), and the callers' stack is not (one entry per program)
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    assert jax.config.jax_traceback_in_locations_limit == 1
    import re

    import surreal_tpu

    here = os.path.abspath(surreal_tpu.__file__)
    assert re.sub(
        jax.config.jax_hlo_source_file_canonicalization_regex, "", here
    ) == "surreal_tpu/__init__.py"  # a second checkout has the same keys
    # JAX's own switch off (how conftest runs the suite): nothing changes
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(compile_cache_on)


# -- prefetcher ---------------------------------------------------------------

def test_prefetcher_orders_results_and_reraises(tmp_path):
    from surreal_tpu.learners.prefetch import Prefetcher

    n = [0]

    def produce():
        n[0] += 1
        if n[0] > 3:
            raise TimeoutError("source dried up")
        return n[0]

    p = Prefetcher(produce)
    try:
        assert [p.get(), p.get(), p.get()] == [1, 2, 3]
        with pytest.raises(TimeoutError, match="dried up"):
            p.get()
    finally:
        p.close()


def test_prefetcher_rejects_bad_depth():
    from surreal_tpu.learners.prefetch import Prefetcher

    with pytest.raises(ValueError):
        Prefetcher(lambda: None, depth=0)


def test_prefetcher_backpressures_at_depth(tmp_path):
    """depth=1 bounds the pipeline: at most one staged item plus one
    mid-produce run ahead of the consumer (depth+1 in flight) instead of
    queueing unboundedly stale batches."""
    import time

    from surreal_tpu.learners.prefetch import Prefetcher

    produced = []

    def produce():
        produced.append(len(produced))
        return produced[-1]

    p = Prefetcher(produce, depth=1)
    try:
        deadline = time.monotonic() + 5.0
        # one staged in the queue + one mid-produce ahead of any get()
        while len(produced) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # would grow unboundedly without backpressure
        assert len(produced) <= 3
        assert p.get() == 0
    finally:
        p.close()
