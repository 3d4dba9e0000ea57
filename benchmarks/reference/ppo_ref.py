"""Plain reference for the fused PPO configuration (``ppo_lift``).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the published
description (Schulman et al. 2017; GAE, Schulman et al. 2016) and the
two-mask treatment of time limits (bootstrap through a truncation, not
through a termination). It reads the learner's parameter tree and nothing
else of the program: no model class, no ``ops/`` function.

``check`` runs on the chip, outside the window, at the published widths
on ``ENVS`` envs, through public entry points only (``device_rollout``,
``learner.act``, ``learner.learn``), and compares what they report that
does not depend on the minibatch shuffle.
"""

from __future__ import annotations

import math

from benchmarks.harness.checks import close
from benchmarks.harness.flops import mlp_macs

ENVS = 64
CLIP = 5.0        # the obs filter clips normalised observations here
FILTER_EPS = 1e-8

# Tolerances: about ten times the largest error seen on the chip at the
# published widths over 17 runs (PR 24; the configuration computes in
# bfloat16, 'mixed', and accumulates in float32), written as absolute
# bounds at the size each value has there, and far under what a dropped
# term moves (the log-std term of the log-prob is 4 x 0.5 = 2 nats; the
# termination mask changes every advantage upstream of a termination):
#   mean                |max| 0.04-0.07; largest error 2.2e-4
#   log-prob            up to 9 nats; largest error 1.4e-3
#   explained variance  0.29-0.39; largest error 5.0e-3 (a ratio of two
#                       variances of bfloat16 values: six times, not ten,
#                       so that the bound stays under a tenth of the value)
#   mean |advantage|    0.77-0.80; largest error 2.0e-4 (3.0e-4 on the CPU)
TOL = {
    "act/mean": dict(rtol=0.0, atol=2.5e-3),
    "act/log_std": dict(rtol=0.0, atol=1e-6),   # a parameter, not computed
    "act/logp": dict(rtol=0.0, atol=1.5e-2),
    "learn/explained_variance": dict(rtol=0.0, atol=3e-2),
    "learn/adv_mean_abs": dict(rtol=4e-3, atol=0.0),
}


def dense(p, x):
    import jax.numpy as jnp

    return jnp.dot(x, p["kernel"]) + p["bias"]


def tanh_mlp(p, x):
    """Dense -> tanh, for ``Dense_0``, ``Dense_1``, ... in order."""
    import jax.numpy as jnp

    for i in range(len(p)):
        x = jnp.tanh(dense(p[f"Dense_{i}"], x))
    return x


def fold_stats(count, mean, m2, batch):
    """Running mean / sum of squared deviations after folding ``batch``
    ``[..., obs]`` in (Chan et al.'s pairwise update)."""
    import jax.numpy as jnp

    flat = batch.reshape(-1, batch.shape[-1]).astype(jnp.float32)
    n = flat.shape[0]
    b_mean = flat.mean(0)
    b_m2 = ((flat - b_mean) ** 2).sum(0)
    tot = count + n
    delta = b_mean - mean
    return tot, mean + delta * n / tot, m2 + b_m2 + delta**2 * count * n / tot


def normalise(count, mean, m2, obs):
    import jax.numpy as jnp

    std = jnp.sqrt(m2 / max(count, 1) + FILTER_EPS)
    return jnp.clip((obs - mean) / std, -CLIP, CLIP)


def policy(params, obs_n):
    """Actor mean, state-independent log-std, critic value."""
    import jax.numpy as jnp

    p = params["params"]
    mean = dense(p["Dense_0"], tanh_mlp(p["MLP_0"], obs_n))
    value = dense(p["Dense_1"], tanh_mlp(p["MLP_1"], obs_n))[..., 0]
    return mean, jnp.broadcast_to(p["log_std"], mean.shape), value


def gauss_logp(mean, log_std, action, with_log_std_term: bool = True):
    """Log-density of a diagonal Gaussian, summed over action dims."""
    import jax.numpy as jnp

    z = (action - mean) / jnp.exp(log_std)
    per_dim = -0.5 * z**2 - 0.5 * math.log(2.0 * math.pi)
    if with_log_std_term:
        per_dim = per_dim - log_std
    return per_dim.sum(-1)


def gae(reward, value, value_next, done, terminated, gamma, lam,
        mask_terminations: bool = True):
    """Generalised advantage estimation over ``[T, B]``, backwards in
    time: ``delta_t = r_t + gamma (1 - terminated_t) V(s'_t) - V(s_t)``,
    ``A_t = delta_t + gamma lam (1 - done_t) A_{t+1}``. ``value_next`` is
    the value of the pre-reset successor, so a time limit bootstraps."""
    import numpy as np

    reward, value, value_next = (np.asarray(x, np.float64) for x in (reward, value, value_next))
    done = np.asarray(done, np.float64)
    term = np.asarray(terminated, np.float64) if mask_terminations else 0.0 * done
    delta = reward + gamma * (1.0 - term) * value_next - value
    adv = np.zeros_like(delta)
    nxt = np.zeros_like(delta[0])
    for t in range(delta.shape[0] - 1, -1, -1):
        nxt = delta[t] + gamma * lam * (1.0 - done[t]) * nxt
        adv[t] = nxt
    return adv, adv + value


def learn_report(state, batch, gamma, lam, mask_terminations: bool = True):
    """What ``learn`` reports that the shuffle cannot move: the explained
    variance of the value net before the update and the mean absolute
    normalised advantage."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        stats = fold_stats(
            int(state.obs_stats.count), state.obs_stats.mean,
            state.obs_stats.m2, batch["obs"],
        )
        _, _, value = policy(state.params, normalise(*stats, batch["obs"]))
        _, _, value_next = policy(state.params, normalise(*stats, batch["next_obs"]))
    adv, target = gae(
        batch["reward"], value, value_next, batch["done"], batch["terminated"],
        gamma, lam, mask_terminations,
    )
    value = np.asarray(value, np.float64)
    normed = (adv - adv.mean()) / (adv.std() + 1e-8)
    return {
        "learn/explained_variance":
            1.0 - np.var(target - value) / (np.var(target) + 1e-8),
        "learn/adv_mean_abs": np.abs(normed).mean(),
    }


def act_report(state, obs, action, with_log_std_term: bool = True):
    """Behaviour mean, log-std and the log-prob of ``action``."""
    import jax

    with jax.default_matmul_precision("highest"):
        stats = (int(state.obs_stats.count), state.obs_stats.mean, state.obs_stats.m2)
        mean, log_std, _ = policy(state.params, normalise(*stats, obs))
        logp = gauss_logp(mean, log_std, action, with_log_std_term)
    return {"act/mean": mean, "act/log_std": log_std, "act/logp": logp}


def compare(system: dict, reference: dict, tol: dict = TOL) -> dict:
    """``{"ok", "comparisons": {name: {ok, max_abs_err}}}``."""
    rows = {}
    for name, want in reference.items():
        ok, err = close(system[name], want, **tol[name])
        rows[name] = {"ok": ok, "max_abs_err": err}
    return {"ok": all(r["ok"] for r in rows.values()), "comparisons": rows}


def system_report(metrics: dict, info: dict) -> dict:
    """What ``learn`` and ``act`` said, under the comparisons' names."""
    return {
        "learn/explained_variance": float(metrics["value/explained_variance"]),
        "learn/adv_mean_abs": float(metrics["adv/mean_abs"]),
        "act/mean": info["mean"], "act/log_std": info["log_std"],
        "act/logp": info["logp"],
    }


def system_reports(learner, env, seed: int, envs: int, horizon: int):
    """Drive the learner through its public entry points on a seeded
    ``[horizon, envs]`` rollout; return (state before learn, batch, learn
    metrics, state after learn, fresh obs, act output)."""
    import jax
    import jax.numpy as jnp

    from surreal_tpu.launch.rollout import device_rollout, init_device_carry

    k_init, k_env, k_roll, k_flip, k_learn, k_act = jax.random.split(
        jax.random.key(seed), 6
    )
    state = learner.init(k_init)
    carry = init_device_carry(env, k_env, envs)
    carry, batch = jax.jit(
        lambda s, c, k: device_rollout(env, learner, s, c, k, horizon)
    )(state, carry, k_roll)
    batch = {
        k: batch[k] for k in (
            "obs", "next_obs", "action", "reward", "done", "terminated",
            "behavior_logp", "behavior",
        )
    }
    # a seeded 2% of steps end in a termination, so that both GAE masks
    # act on every batch whatever the env's own episode ends are
    flip = jax.random.bernoulli(k_flip, 0.02, batch["done"].shape)
    batch["done"] = batch["done"] | flip
    batch["terminated"] = batch["terminated"] | flip
    new_state, metrics = jax.jit(learner.learn)(state, batch, k_learn)
    obs = carry.obs
    action, info = jax.jit(learner.act)(new_state, obs, k_act)
    return state, batch, metrics, new_state, obs, action, info


def check(cfg, run) -> dict:
    """The on-chip reference check of one run (seeded from ``--seed``)."""
    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner

    env = make_env(training_env_config(cfg.env_config))
    learner = build_learner(cfg.learner_config, env.specs)
    algo = learner.config.algo
    state, batch, metrics, new_state, obs, action, info = system_reports(
        learner, env, run.seed, ENVS, int(algo.horizon)
    )
    reference = dict(
        learn_report(state, batch, float(algo.gamma), float(algo.lam)),
        **act_report(new_state, obs, action),
    )
    return compare(system_report(metrics, info), reference)


def iteration_cost(config: dict, traffic: dict) -> dict:
    """Required operations and bytes of one fused PPO iteration
    (harness/flops.py has the rules): a rollout forward of both nets per
    sample, two value forwards per sample for the two-mask GAE (obs and
    next_obs), and ``epochs`` forward+backward passes of both nets over
    every sample."""
    widths = config["widths"]
    actor = mlp_macs(widths["obs_dim"], widths["actor_hidden"], widths["action_dim"])
    critic = mlp_macs(widths["obs_dim"], widths["critic_hidden"], 1)
    samples = int(traffic["num_envs"]) * int(traffic["horizon"])
    rollout = samples * (actor + critic)
    gae = samples * 2 * critic
    sgd = samples * int(traffic["epochs"]) * 3 * (actor + critic)
    # required HBM traffic: every stored sample (obs, next_obs, action and
    # six scalars, float32) is written once by the rollout and read once
    # for GAE and once per epoch
    row = 4 * (2 * widths["obs_dim"] + 3 * widths["action_dim"] + 6)
    return {
        "samples": samples,
        "flops": 2 * (rollout + gae + sgd),
        "flops_rollout": 2 * rollout,
        "flops_learn": 2 * (gae + sgd),
        "bytes": samples * row * (2 + int(traffic["epochs"])),
    }
