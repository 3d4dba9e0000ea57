"""DDPG learner (parity: reference ``surreal/learner/ddpg.py``, SURVEY.md
§2.1 — critic TD loss with n-step returns, actor DPG loss, target networks
with soft-tau AND periodic-hard update modes; exploration noise per
``surreal/agent/ddpg_agent.py``).

Functional TPU design: one :class:`DDPGState` pytree carries live+target
params and both optimizers; ``learn`` consumes flat n-step transitions
(built by ``aggregator.nstep_transitions`` from time-major rollouts, the
reference aggregator's n-step helper relocated on-device) and optionally
IS weights from prioritized replay (BASELINE config ③), returning
per-sample |TD| for priority refresh. Everything jits; ``axis_name``
enables dp gradient pmean exactly as in the PPO learner.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from surreal_tpu.envs.base import EnvSpecs
from surreal_tpu.learners.base import (
    TRAINING,
    Learner,
    make_optimizer_chain,
    training_health,
)
from surreal_tpu.models.ddpg_net import DDPGActor, DDPGCritic
from surreal_tpu.ops.precision import current_loss_scale, loss_scale_metrics
from surreal_tpu.ops.running_stats import (
    RunningStats,
    init_stats,
    normalize,
    update_stats,
)
from surreal_tpu.session.config import Config
from surreal_tpu.utils.phases import phase

DDPG_LEARNER_CONFIG = Config(
    algo=Config(
        name="ddpg",
        n_step=1,             # >1 enables the aggregator's n-step folding
        actor_lr=1e-3,
        critic_lr=1e-3,
        target=Config(
            mode="soft",       # 'soft' (tau each step) | 'hard' (copy every N)
            tau=0.005,
            hard_every=500,
        ),
        exploration=Config(
            noise="ou",        # 'ou' | 'gaussian' (OU state lives in the rollout carry)
            sigma=0.2,
            ou_theta=0.15,
            ou_dt=1.0,
            warmup_steps=2000,  # uniform-random actions before policy acting
        ),
        updates_per_iter=64,   # SGD updates per collect chunk (off-policy ratio)
        update_unroll=1,       # update-loop scan unroll
        # uniform replay only: draw ALL updates_per_iter index sets in one
        # batched gather before the update scan instead of one gather per
        # scan step (record-equivalent — same keys, same indices; see
        # OffPolicyTrainer._device_train_iter). Prioritized replay keeps
        # the sequential path: priorities change between updates.
        batched_uniform_sampling=True,
        # replay gather implementation for the batched uniform fast
        # path: 'xla' = one fused XLA ring gather | 'pallas' =
        # scalar-prefetch gather
        # kernel (ops/pallas_replay.py; interpret mode off-TPU) — rows
        # DMA HBM->VMEM exactly once, driven by the index vector
        replay_gather="xla",
        horizon=16,            # collect chunk length per iteration
        use_layer_norm=True,
    ),
    replay=Config(kind="uniform"),
)


class DDPGState(NamedTuple):
    actor_params: dict
    critic_params: dict
    target_actor_params: dict
    target_critic_params: dict
    actor_opt: optax.OptState
    critic_opt: optax.OptState
    obs_stats: RunningStats
    iteration: jax.Array  # int32 learn-call counter (drives hard updates)


class DDPGLearner(Learner):
    def __init__(self, learner_config, env_specs: EnvSpecs):
        super().__init__(learner_config, env_specs)
        if env_specs.discrete:
            raise ValueError("DDPG requires a continuous action space")
        self.act_dim = int(env_specs.action.shape[0])
        # precision: model dtypes materialize from the resolved policy
        # (Learner.__init__), 'auto' knobs -> concrete per algo.precision
        model_cfg = self.policy.model_config(learner_config.model)
        self.actor = DDPGActor(model_cfg=model_cfg, act_dim=self.act_dim)
        self.critic = DDPGCritic(
            model_cfg=model_cfg, use_layer_norm=learner_config.algo.use_layer_norm
        )
        # the shared chain builder (learners/base.py): clip -> adam ->
        # recovery_scale on BOTH chains (a rollback slows actor and critic
        # together), each wrapped in its OWN dynamic loss scale when the
        # precision policy asks — the two losses overflow independently
        self.actor_tx = make_optimizer_chain(
            learner_config.algo.actor_lr,
            learner_config.optimizer.max_grad_norm,
            self.policy,
        )
        self.critic_tx = make_optimizer_chain(
            learner_config.algo.critic_lr,
            learner_config.optimizer.max_grad_norm,
            self.policy,
        )

    # -- state ---------------------------------------------------------------
    def init(self, key: jax.Array) -> DDPGState:
        ka, kc = jax.random.split(key)
        obs = jnp.zeros((1, *self.specs.obs.shape), self.specs.obs.dtype)
        act = jnp.zeros((1, self.act_dim), jnp.float32)
        actor_params = self.actor.init(ka, obs)
        critic_params = self.critic.init(kc, obs, act)
        return DDPGState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=jax.tree.map(jnp.copy, actor_params),
            target_critic_params=jax.tree.map(jnp.copy, critic_params),
            actor_opt=self.actor_tx.init(actor_params),
            critic_opt=self.critic_tx.init(critic_params),
            obs_stats=init_stats(self.specs.obs.shape)
            if self._use_obs_filter
            else init_stats((1,)),
            iteration=jnp.zeros((), jnp.int32),
        )

    @property
    def _use_obs_filter(self) -> bool:
        return (
            bool(self.config.algo.use_obs_filter)
            and self.specs.obs.dtype != np.uint8
        )

    def _norm_obs(self, stats: RunningStats, obs: jax.Array) -> jax.Array:
        if not self._use_obs_filter:
            return obs
        return normalize(stats, obs.astype(jnp.float32))

    # -- acting --------------------------------------------------------------
    def act(self, state: DDPGState, obs: jax.Array, key: jax.Array, mode: str = TRAINING):
        """Deterministic actor; training mode adds Gaussian exploration
        noise (OU noise is stateful — the off-policy collector carries it
        via :func:`ou_noise_step` and adds it outside)."""
        a = self.actor.apply(
            state.actor_params, self._norm_obs(state.obs_stats, obs)
        )
        if mode == TRAINING and self.config.algo.exploration.noise == "gaussian":
            a = a + self.config.algo.exploration.sigma * jax.random.normal(
                key, a.shape, a.dtype
            )
        return jnp.clip(a, -1.0, 1.0), {}

    def update_obs_stats(
        self, state: DDPGState, fresh_obs: jax.Array, axis_name=None
    ) -> DDPGState:
        """Fold FRESH trajectory obs into the normalizer, once per collect
        chunk (the reference ZFilter semantics). Deliberately NOT done in
        ``learn``: replayed minibatches resample transitions many times and
        under prioritized replay are biased toward high-|TD| states, which
        would skew and over-count the running stats."""
        if not self._use_obs_filter:
            return state
        return state._replace(
            obs_stats=update_stats(state.obs_stats, fresh_obs, axis_name=axis_name)
        )

    # -- learning ------------------------------------------------------------
    @phase("update")
    def learn(self, state: DDPGState, batch: dict, key: jax.Array, axis_name=None):
        """One SGD update on flat n-step transitions.

        batch: obs [B,...], action [B,A], reward [B] (n-step sum),
        next_obs [B,...] (s_{t+n}), discount [B] (gamma^k * not-terminated,
        0 past episode end), optional is_weights [B]. Obs-normalizer stats
        are read-only here; see :meth:`update_obs_stats`.
        """
        del key
        from surreal_tpu.utils.asserts import check_learn_batch

        check_learn_batch(batch, self.specs, name="ddpg.learn")
        algo = self.config.algo
        obs_stats = state.obs_stats
        obs = self._norm_obs(obs_stats, batch["obs"])
        next_obs = self._norm_obs(obs_stats, batch["next_obs"])
        is_w = batch.get("is_weights")
        if is_w is None:
            is_w = jnp.ones_like(batch["reward"])

        # precision: each chain carries its OWN dynamic loss scale (1.0
        # when the policy carries none — ops/precision.py); the scaled
        # losses differentiate, the chains divide the grads back down and
        # skip overflowed steps independently
        c_scale = current_loss_scale(state.critic_opt)
        a_scale = current_loss_scale(state.actor_opt)

        # critic: TD target from target networks
        next_a = self.actor.apply(state.target_actor_params, next_obs)
        q_next = self.critic.apply(state.target_critic_params, next_obs, next_a)
        target = batch["reward"] + batch["discount"] * q_next
        target = jax.lax.stop_gradient(target)

        def critic_loss_fn(critic_params):
            q = self.critic.apply(critic_params, obs, batch["action"])
            td = q - target
            return (is_w * td**2).mean() * c_scale, td

        (c_loss, td), c_grads = jax.value_and_grad(critic_loss_fn, has_aux=True)(
            state.critic_params
        )
        c_loss = c_loss / c_scale  # report the true loss (pow2 — exact)

        # actor: deterministic policy gradient through the live critic
        def actor_loss_fn(actor_params):
            a = self.actor.apply(actor_params, obs)
            return (
                -(is_w * self.critic.apply(state.critic_params, obs, a)).mean()
                * a_scale
            )

        a_loss, a_grads = jax.value_and_grad(actor_loss_fn)(state.actor_params)
        a_loss = a_loss / a_scale

        if axis_name is not None:
            c_grads = jax.lax.pmean(c_grads, axis_name)
            a_grads = jax.lax.pmean(a_grads, axis_name)

        c_updates, critic_opt = self.critic_tx.update(
            c_grads, state.critic_opt, state.critic_params
        )
        critic_params = optax.apply_updates(state.critic_params, c_updates)
        a_updates, actor_opt = self.actor_tx.update(
            a_grads, state.actor_opt, state.actor_params
        )
        actor_params = optax.apply_updates(state.actor_params, a_updates)

        # target update: soft every step, or hard copy every N
        iteration = state.iteration + 1
        if algo.target.mode == "soft":
            tau = algo.target.tau
            target_actor = optax.incremental_update(
                actor_params, state.target_actor_params, tau
            )
            target_critic = optax.incremental_update(
                critic_params, state.target_critic_params, tau
            )
        else:
            do_copy = (iteration % algo.target.hard_every) == 0

            def pick(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(do_copy, n, o), new, old
                )

            target_actor = pick(actor_params, state.target_actor_params)
            target_critic = pick(critic_params, state.target_critic_params)

        new_state = DDPGState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=target_actor,
            target_critic_params=target_critic,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            obs_stats=obs_stats,
            iteration=iteration,
        )
        metrics = {
            "loss/critic": c_loss,
            "loss/actor": a_loss,
            "q/mean_target": target.mean(),
            "q/mean_abs_td": jnp.abs(td).mean(),
            # one health set over BOTH trees (grads already pmean'd
            # above; each tree unscaled by its own power-of-two loss
            # scale so the norm is the TRUE magnitude — inf/nan survive)
            **training_health(
                {"actor": state.actor_params, "critic": state.critic_params},
                {"actor": actor_params, "critic": critic_params},
                optax.global_norm({
                    "actor": jax.tree.map(lambda g: g / a_scale, a_grads),
                    "critic": jax.tree.map(lambda g: g / c_scale, c_grads),
                }),
            ),
            # precision: loss-scale telemetry over both chains (empty
            # when the policy carries no scale)
            **loss_scale_metrics({"actor": actor_opt, "critic": critic_opt}),
        }
        if axis_name is not None:
            metrics = jax.lax.pmean(metrics, axis_name)
        # per-sample |TD| rides along for prioritized-replay refresh; the
        # off-policy trainer pops it before treating metrics as scalars
        metrics["priority/td_abs"] = jnp.abs(td)
        return new_state, metrics

    def default_config(self):
        return DDPG_LEARNER_CONFIG


def ou_noise_step(
    noise: jax.Array, key: jax.Array, theta: float, sigma: float, dt: float = 1.0
) -> jax.Array:
    """One Ornstein-Uhlenbeck step (parity: the reference DDPG agent's OU
    exploration). Carried by the collector: noise [B, act_dim]."""
    drift = -theta * noise * dt
    diffusion = sigma * jnp.sqrt(dt) * jax.random.normal(key, noise.shape, noise.dtype)
    return noise + drift + diffusion
