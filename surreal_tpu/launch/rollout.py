"""Trajectory collection — the rebuild of the reference's actor rollout
loop (``run_agent``, SURVEY.md §3.2) minus the processes.

Two collectors, same batch contract (see learners/ppo.py docstring):

- :func:`device_rollout` — envs ARE device arrays (``jax:*``): one
  ``lax.scan`` over the horizon, vmapped over B envs, inside the same jit
  as the learner step if the caller fuses them. This is the path where the
  reference needed 1000 actor processes and ZMQ; here it is one XLA loop.
  Unless ``algo.rollout_unroll`` names a number, the scan chooses its own
  unroll (:func:`rollout_unroll`): four env steps a trip for a memoryless
  policy over vector observations, one otherwise.
- :func:`host_rollout` — host envs (gym/dm_control/robosuite-class): the
  SEED-RL pattern, batched obs -> one jitted ``act`` -> batched env.step;
  per-step numpy dicts are aggregated (learners/aggregator.py) into one
  ``device_put``.

Episode returns are tracked in-band: ``ep_return`` is nonzero only at done
steps (sum over the finished episode), so metrics need no side channel out
of jit.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from surreal_tpu.envs.base import HostEnv
from surreal_tpu.envs.jax.base import AutoReset, batch_step
from surreal_tpu.learners.base import TRAINING, Learner
from surreal_tpu.learners.aggregator import multistep_batch
from surreal_tpu.utils.phases import phase


class RolloutCarry(NamedTuple):
    env_state: Any
    obs: jax.Array
    ep_return: jax.Array  # [B] running episode return
    ep_length: jax.Array  # [B] running episode length


def successor_and_termination(obs2, done, step_info):
    """The two auto-reset invariants every collector must share:

    - the true successor obs at a done step is the PRE-reset terminal obs
      (``obs2`` is already the next episode's reset obs);
    - ``terminated`` is a genuine env termination — done minus truncation —
      which is what zeroes bootstrap targets.

    Centralised so device and host, on- and off-policy collectors cannot
    drift (these are the classic silent-bias spots, SURVEY.md §7).
    """
    terminal_obs = step_info["terminal_obs"]
    truncated = step_info["truncated"]
    done_b = done.reshape(done.shape + (1,) * (obs2.ndim - done.ndim))
    next_obs = jnp.where(done_b, terminal_obs, obs2)
    terminated = jnp.logical_and(done, jnp.logical_not(truncated))
    return next_obs, terminated


def rollout_unroll(act_carry, obs: jax.Array, horizon: int, unroll: int = 0) -> int:
    """The unroll of :func:`device_rollout`'s scan: env steps a trip.

    A number the caller names (``unroll`` >= 1) wins. Otherwise the rule
    reads two static facts of the scan's input, and no name:

    - **4** where the policy acts without a carry (``act_carry`` is an empty
      pytree) AND an env's observation is a vector (``obs`` is ``[B, D]``).
      A step is then many thin ops over ``[B, .]`` rows and the count of
      device ops binds it, not a peak. The fused PPO iteration at 65 536
      envs x 256 steps, 17 -> 64 -> 64 -> 4, compiled for the v5e: 75 ops
      an env step at unroll 1, of them 52 producing a ``[65536, .]`` array
      (ten bare or fused ``dynamic-update-slice`` writes of the transition
      among them); 68 / 30.5 at 2; **51.25 / 25 at 4**; 61.1 / 32.6 at 8:
      neighbouring steps' writes fuse into their producers and one step's
      broadcasts and concatenates into the next step's consumers. On the
      chip (PR 49's traced runs, ``ppo_lift_long``) ``phase_collect_ms``
      read 85.03 at 1, 64.29 at 2, **62.87 at 4**, and the iteration was
      slower again at 8 (208.2 ms against 199.9): 4 is the minimum.
    - **1** otherwise: a trajectory policy's step streams a gigabyte of
      weights through a cache or a state at 94-97% of the HBM peak, and a
      pixel policy's step is a few large convolutions (880 us a step, one
      conv 323 of them); neither has thin ops to merge, and each copy of
      the body is compile time. The chip read the pixel side too (PR 52,
      ``impala_pong_1k32`` with 4 written out): 557 699 -> 477 203 steps/s,
      ``collect`` 28.17 -> 34.83 ms and ``learn`` 28.32 -> 32.21, whose
      convolutions then read a ``[8, 4, 1024, 84, 84, 4]`` rollout.

    Clamped to the horizon.
    """
    if unroll < 1:
        memoryless = not jax.tree_util.tree_leaves(act_carry)
        unroll = 4 if memoryless and obs.ndim == 2 else 1
    return max(1, min(unroll, horizon))


def device_rollout(
    env: AutoReset,
    learner: Learner,
    state,
    carry: RolloutCarry,
    key: jax.Array,
    horizon: int,
    unroll: int = 0,
):
    """Collect ``horizon`` steps across B batched on-device envs.

    Returns (new_carry, batch): the learner batch contract, ``ep_return``/
    ``ep_done`` for metrics, ``acting`` (the rows ``learner.act_rows`` reads
    off the acting carry). Pure; callers jit it (fused with ``learn``).

    ``unroll`` is the rollout scan's unroll factor, program size for fewer
    sequential loop iterations: ``algo.rollout_unroll`` where a user set
    one, else 0, "the collector chooses" (:func:`rollout_unroll`).
    """

    def step(scan_carry, step_key):
        c, act_carry = scan_carry
        akey, skey = jax.random.split(step_key)
        with phase("collect/act"):
            action, info, act_carry = learner.act_step(
                state, act_carry, c.obs, akey, TRAINING
            )
        with phase("collect/env"):
            env_state, obs2, reward, done, step_info = batch_step(
                env, c.env_state, action
            )
        next_obs, terminated = successor_and_termination(obs2, done, step_info)
        ep_return = c.ep_return + reward
        ep_length = c.ep_length + 1
        trans = {
            "obs": c.obs,
            "next_obs": next_obs,
            "action": action,
            "reward": reward,
            "done": done,
            "terminated": terminated,
            "behavior_logp": info["logp"],
            "behavior": {
                k: v for k, v in info.items() if k in ("mean", "log_std", "logits")
            },
            "ep_return": jnp.where(done, ep_return, 0.0),
            "ep_done": done,
        }
        new_c = RolloutCarry(
            env_state=env_state,
            obs=obs2,
            ep_return=jnp.where(done, 0.0, ep_return),
            ep_length=jnp.where(done, 0, ep_length),
        )
        return (new_c, act_carry), trans

    # a FRESH act carry per rollout call: sequence policies' context is
    # segment-aligned (learn recomputes exactly this conditioning);
    # memoryless learners get None, which scans as an empty pytree
    with phase("collect"):
        keys = jax.random.split(key, horizon)
        act_carry = learner.act_init(carry.obs.shape[0])
        (new_carry, act_carry), batch = jax.lax.scan(
            step, (carry, act_carry), keys,
            unroll=rollout_unroll(act_carry, carry.obs, horizon, int(unroll)),
        )
    return new_carry, dict(batch, acting=learner.act_rows(act_carry))


def init_device_carry(env: AutoReset, key: jax.Array, num_envs: int) -> RolloutCarry:
    keys = jax.random.split(key, num_envs)
    env_state, obs = jax.vmap(env.reset)(keys)
    return RolloutCarry(
        env_state=env_state,
        obs=obs,
        ep_return=jnp.zeros(num_envs, jnp.float32),
        ep_length=jnp.zeros(num_envs, jnp.int32),
    )


def host_rollout(
    env: HostEnv,
    act_fn: Callable,  # pre-jitted (state, obs, key) -> (action, info)
    state,
    obs: np.ndarray,
    key: jax.Array,
    horizon: int,
):
    """Collect ``horizon`` steps from a batched host env (SEED-RL pattern:
    one device inference per step for ALL envs, not per-env processes).

    Returns (last_obs, batch, episode_stats) with batch on device.
    """
    steps = []
    ep_returns: list[float] = []
    ep_lengths: list[int] = []
    for _ in range(horizon):
        key, akey = jax.random.split(key)
        action, info = act_fn(state, jnp.asarray(obs), akey)
        action_np = np.asarray(action)
        out = env.step(action_np)
        terminal_obs = out.info.get("terminal_obs")
        truncated = np.asarray(out.info.get("truncated", np.zeros(len(out.done), bool)))
        if terminal_obs is not None and out.done.any():
            done_b = out.done.reshape(out.done.shape + (1,) * (out.obs.ndim - 1))
            next_obs = np.where(done_b, terminal_obs, out.obs)
        else:
            next_obs = out.obs
        steps.append(
            {
                "obs": obs,
                "next_obs": next_obs,
                "action": action_np,
                "reward": out.reward,
                "done": out.done,
                "terminated": out.done & ~truncated,
                "behavior_logp": np.asarray(info["logp"]),
                "behavior": {
                    k: np.asarray(v)
                    for k, v in info.items()
                    if k in ("mean", "log_std", "logits")
                },
            }
        )
        if "episode_returns" in out.info:
            ep_returns.extend(np.asarray(out.info["episode_returns"]).tolist())
            ep_lengths.extend(np.asarray(out.info["episode_lengths"]).tolist())
        obs = out.obs
    batch = multistep_batch(steps)
    return obs, batch, {"returns": ep_returns, "lengths": ep_lengths}
