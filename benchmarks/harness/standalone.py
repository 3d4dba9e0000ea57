"""The layers the fused program hides, each timed alone.

One fused program per iteration means the trace cannot yet say where an
iteration's time goes by layer (no op carries a layer's name: PERF.md,
open questions). Until it can, a traced run times each layer as a
jitted program of its own, at the cell's per-chip shapes, through public
entry points only: ``device_rollout``, ``learner.learn``,
``replay.sample``. Each timing is the median of ``REPEATS`` calls, every
call fenced with ``block_until_ready``. On several chips the layers run
on the first chip at one chip's share of the envs, without collectives.
"""

from __future__ import annotations

import statistics
import time

REPEATS = 5


def timed(fn, *args) -> float:
    """Median seconds of ``REPEATS`` fenced calls, after one that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(REPEATS):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out)


def time_layers(cfg, run) -> dict:
    """``{"rollout_s", "learn_s", "replay_sample_s"}`` for what the cell's
    algorithm has; a layer it lacks is left out."""
    import jax

    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner

    env = make_env(training_env_config(cfg.env_config))
    learner = build_learner(cfg.learner_config, env.specs)
    envs = int(run.traffic["num_envs"]) // int(run.cell["chips"])
    key = jax.random.key(run.seed)
    state = learner.init(key)
    if learner.config.replay.kind == "fifo":
        return _on_policy(env, learner, state, envs, key)
    return _off_policy(env, learner, state, key)


def _on_policy(env, learner, state, envs: int, key) -> dict:
    import jax

    from surreal_tpu.launch.rollout import device_rollout, init_device_carry

    horizon = int(learner.config.algo.horizon)
    carry = init_device_carry(env, key, envs)
    rollout = jax.jit(
        lambda s, c, k: device_rollout(env, learner, s, c, k, horizon)
    )
    out = {"rollout_s": timed(rollout, state, carry, key)}
    _, batch = rollout(state, carry, key)
    learn_batch = {
        k: batch[k] for k in (
            "obs", "next_obs", "action", "reward", "done", "terminated",
            "behavior_logp", "behavior",
        )
    }
    del batch
    out["learn_s"] = timed(jax.jit(learner.learn), state, learn_batch, key)
    return out


def _off_policy(env, learner, state, key) -> dict:
    """A full ring of random rows; one ``sample``; then the iteration's
    ``updates_per_iter`` sequential ``learn`` calls on sampled batches."""
    import jax
    import jax.numpy as jnp

    from surreal_tpu.replay import build_replay

    replay = build_replay(learner.config.replay)
    obs_shape, act_dim = env.specs.obs.shape, int(env.specs.action.shape[0])
    example = {
        "obs": jnp.zeros(obs_shape, jnp.float32),
        "next_obs": jnp.zeros(obs_shape, jnp.float32),
        "action": jnp.zeros((act_dim,), jnp.float32),
        "reward": jnp.zeros((), jnp.float32),
        "discount": jnp.zeros((), jnp.float32),
    }
    chunk = min(replay.capacity, 1 << 20)

    @jax.jit
    def fill(rstate, k):
        rows = jax.tree.map(
            lambda x: jax.random.normal(k, (chunk, *x.shape), x.dtype), example
        )
        return replay.insert(rstate, rows)

    rstate = replay.init(example)
    for i in range(-(-replay.capacity // chunk)):
        rstate = fill(rstate, jax.random.fold_in(key, i))
    sample = jax.jit(lambda r, k: replay.sample(r, k)[1:])
    out = {"replay_sample_s": timed(sample, rstate, key)}
    updates = int(learner.config.algo.updates_per_iter)
    keys = jax.random.split(key, updates)
    drawn = [sample(rstate, k)[0] for k in keys]
    batches = jax.tree.map(lambda *xs: jnp.stack(xs), *drawn)
    del rstate, drawn

    @jax.jit
    def learn_all(s, bs, ks):
        def one(s, xs):
            s, m = learner.learn(s, xs[0], xs[1])
            return s, m["loss/critic"]

        return jax.lax.scan(one, s, (bs, ks))

    out["learn_s"] = timed(learn_all, state, batches, keys)
    return out
