"""IMPALA/V-trace learner (BASELINE config ⑤ — beyond the reference, which
shipped PPO/DDPG only; SURVEY.md §6). Actor-learner decoupling with
off-policy correction: behavior-policy log-probs ride with the experience
(the reference's ``action_info`` pattern, SURVEY.md §3.2) and V-trace
corrects the staleness, which is exactly what the SEED-style serving path
introduces.

One update per batch (no epochs/minibatches — IMPALA's design), so the
whole learn is a single fused backward pass; V-trace is the reverse scan
in ``ops/vtrace.py``. Shares the PPO batch contract, so the same Trainer
and collectors drive it.

One forward gives both sides of the TD error. The rollout writes
``next_obs[t] = obs[t + 1]`` wherever ``done[t]`` is false, so there
``V(next_obs[t])`` is ``values[t + 1]`` of the forward over ``obs`` that
the loss runs anyway, shifted by one step. A model apply of its own runs
only over the steps whose successor is not in the batch: the last one,
and those an episode was truncated at (``next_obs`` is the terminal
observation there, ``obs[t + 1]`` the reset one). A loop visits them,
``B`` frames at a time (``_values_apart``), so the whole pass over
``next_obs`` that ``learn`` once always made is its limit, reached only by
an input with a cut in every step. ``impala/boot_rows`` and
``impala/boot_full`` in the metrics say how much of it ran.

Phases (``utils/phases.py``; with the rollout's ``collect`` that is four):
``bootstrap`` is that loop (no gradient); ``vtrace`` is
``_vtrace`` inside ``loss_fn``; ``learn`` is the rest of ``learn``: the
obs filter, the forward over ``obs``, the shift, the three losses, the
backward pass, the dp ``pmean`` (``learn/psum``), the optimizer apply, the
new state and the metrics. A trajectory policy reads its bootstrap values
from the one extended forward, so it has no ``bootstrap``.
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
from surreal_tpu.learners.seq_policy import SequenceActingMixin, build_seq_model
from surreal_tpu.models.attention import block_family
from surreal_tpu.models.ppo_net import CategoricalPPOModel, PPOModel
from surreal_tpu.ops import distributions as D
from surreal_tpu.ops.precision import current_loss_scale, loss_scale_metrics
from surreal_tpu.ops.running_stats import RunningStats, init_stats, normalize, update_stats
from surreal_tpu.ops.vtrace import vtrace_nextobs
from surreal_tpu.session.config import Config
from surreal_tpu.utils.phases import phase

IMPALA_LEARNER_CONFIG = Config(
    algo=Config(
        name="impala",
        horizon=64,           # unroll length per learner batch
        clip_rho=1.0,
        clip_c=1.0,
        clip_pg_rho=1.0,
        value_coeff=0.5,
        entropy_coeff=0.01,
        init_log_std=-0.5,    # continuous-action variant
    ),
    optimizer=Config(lr=6e-4),
    replay=Config(kind="fifo"),
)


class IMPALAState(NamedTuple):
    params: dict
    opt_state: optax.OptState
    obs_stats: RunningStats
    iteration: jax.Array


class IMPALALearner(SequenceActingMixin, Learner):
    supports_trajectory_encoder = True  # single-update-over-sequences
                                        # learn fits trajectory policies
                                        # with no minibatch surgery

    def __init__(self, learner_config, env_specs: EnvSpecs):
        super().__init__(learner_config, env_specs)
        self.discrete = env_specs.discrete
        enc = learner_config.model.get("encoder", None)
        self.seq_policy = bool(enc is not None and enc.get("kind") == "trajectory")
        self.requires_act_carry = self.seq_policy
        if self.seq_policy and block_family(enc) != "preln":
            raise ValueError(
                f"model.encoder.block={block_family(enc)!r} is wired into "
                "PPO alone (a routed family's statistics and bias rule, and "
                "a trunk's counters, ride PPO's minibatch steps: "
                "learners/ppo.py); IMPALA takes the "
                "'preln' blocks"
            )
        # precision: model dtypes materialize from the resolved policy
        # (Learner.__init__), 'auto' knobs -> concrete per algo.precision
        model_cfg = self.policy.model_config(learner_config.model)
        if self.seq_policy:
            self.model = build_seq_model(
                learner_config.model, env_specs,
                learner_config.algo.init_log_std,
                horizon=learner_config.algo.horizon,
                policy=self.policy,
            )
        elif self.discrete:
            self.model = CategoricalPPOModel(
                model_cfg=model_cfg,
                n_actions=env_specs.action.n,
            )
        else:
            self.model = PPOModel(
                model_cfg=model_cfg,
                act_dim=int(env_specs.action.shape[0]),
                init_log_std=learner_config.algo.init_log_std,
            )
        opt_cfg = learner_config.optimizer
        if opt_cfg.lr_schedule == "linear":
            lr = optax.linear_schedule(
                opt_cfg.lr, 0.0, transition_steps=opt_cfg.get("anneal_steps", 10_000)
            )
        else:
            lr = opt_cfg.lr
        # clip -> adam -> recovery_scale (+ dynamic loss scaling per the
        # precision policy) — the shared builder, learners/base.py
        self.tx = make_optimizer_chain(lr, opt_cfg.max_grad_norm, self.policy)

    def init(self, key: jax.Array) -> IMPALAState:
        if self.seq_policy:
            obs = jnp.zeros((1, 1, *self.specs.obs.shape), self.specs.obs.dtype)
        else:
            obs = jnp.zeros((1, *self.specs.obs.shape), self.specs.obs.dtype)
        params = self.model.init(key, obs)
        return IMPALAState(
            params=params,
            opt_state=self.tx.init(params),
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

    # -- acting (same behavior-info contract as PPO) --------------------------
    def act(self, state: IMPALAState, obs: jax.Array, key: jax.Array, mode: str = TRAINING):
        if self.seq_policy:
            raise RuntimeError(
                "trajectory policies condition on history: act through "
                "act_init/act_step (the device collectors, evaluator, and "
                "remote Agent.remote_act do); the stateless act() has no "
                "context to condition on"
            )
        out = self.model.apply(state.params, self._norm_obs(state.obs_stats, obs))
        return self._head_act(out, key, mode)

    # -- learning ------------------------------------------------------------
    def _values_apart(self, params, obs_stats, batch: dict):
        """``V(next_obs)`` of a memoryless policy on the steps where it is
        not ``V(obs)`` a step on: the last step, and every step that holds
        a truncated row (a terminated row's bootstrap is masked by
        V-trace, so it keeps the finite shifted value). A loop applies the
        model to those steps alone, ``B`` frames at a time where they lie
        (a slice of the leading axis: no frame is gathered or copied), so
        its cost follows what the input holds: one step of ``T`` when no
        episode was cut, every step (the whole ``next_obs`` pass) when
        every step holds a cut. Returns ``(evaluated, steps)``: float32
        values ``[T, B]``, defined on the steps that ``steps`` ``[T]``
        marks."""
        T, B = batch["done"].shape
        steps = (batch["done"] & ~batch["terminated"]).any(axis=1).at[-1].set(True)
        order = jnp.nonzero(steps, size=T, fill_value=0)[0]

        def one_step(i, evaluated):
            t = order[i]
            frames = jax.lax.dynamic_index_in_dim(batch["next_obs"], t, keepdims=False)
            v = self.model.apply(params, self._norm_obs(obs_stats, frames)).value
            return evaluated.at[t].set(v.astype(jnp.float32))

        evaluated = jax.lax.fori_loop(
            0, steps.sum(), one_step, jnp.zeros((T, B), jnp.float32)
        )
        return evaluated, steps

    def learn(self, state: IMPALAState, batch: dict, key: jax.Array, axis_name=None):
        """One V-trace update. A memoryless policy runs ONE forward over
        the batch (``obs``, differentiated) and reads ``V(next_obs)`` from
        it a step on; ``_values_apart`` evaluates the steps that have no
        successor in the batch. ``impala/boot_rows`` counts the rows so
        evaluated and ``impala/boot_full`` is 1.0 when they were all of
        ``next_obs`` (the whole pass)."""
        del key
        from surreal_tpu.utils.asserts import check_learn_batch

        check_learn_batch(batch, self.specs, name="impala.learn")
        algo = self.config.algo
        with phase("learn"):
            if self._use_obs_filter:
                obs_stats = update_stats(
                    state.obs_stats, batch["obs"], axis_name=axis_name
                )
            else:
                obs_stats = state.obs_stats
            obs = self._norm_obs(obs_stats, batch["obs"])

        T = batch["reward"].shape[0]
        # precision: dynamic loss scale from the carried opt_state (1.0
        # when the policy carries none — ops/precision.py); the chain
        # divides the grads back down and skips overflowed steps
        loss_scale = current_loss_scale(state.opt_state)

        if self.seq_policy:
            next_obs = self._norm_obs(obs_stats, batch["next_obs"])
            boot = {}
        else:
            # V(s'_t) enters V-trace as a constant: the forward's own
            # values a step on, and outside the differentiated function an
            # apply over the steps that have no successor in the batch
            with phase("bootstrap"):
                evaluated, steps = self._values_apart(state.params, obs_stats, batch)
                apart = steps.sum().astype(jnp.float32)
                boot = {
                    "impala/boot_rows": apart * batch["done"].shape[1],
                    "impala/boot_full": (apart == T).astype(jnp.float32),
                }

        def loss_fn(params):
            with phase("learn"):
                if self.seq_policy:
                    # ONE extended [B, T+1] apply: per-position outputs
                    # conditioned causally on the segment prefix (exactly the
                    # conditioning act_step used during the rollout), with
                    # the V-trace bootstrap read from the shifted positions —
                    # same truncation-boundary caveat as PPO's _learn_seq
                    obs_bt = jnp.swapaxes(obs, 0, 1)
                    ext = jnp.concatenate([obs_bt, next_obs[-1][:, None]], axis=1)
                    out_ext = self.model.apply(params, ext)
                    out = jax.tree.map(
                        lambda x: jnp.swapaxes(x[:, :T], 0, 1), out_ext
                    )
                    values = out.value
                    values_next = jnp.swapaxes(out_ext.value[:, 1:], 0, 1)
                else:
                    out = self.model.apply(params, obs)
                    values = out.value
                    values_next = jnp.where(
                        steps[:, None],
                        evaluated.astype(values.dtype),
                        jnp.concatenate([values[1:], values[-1:]]),
                    )
                if self.discrete:
                    logp = D.categorical_logp(out.logits, batch["action"])
                    entropy = D.categorical_entropy(out.logits).mean()
                else:
                    logp = D.diag_gauss_logp(out.mean, out.log_std, batch["action"])
                    entropy = D.diag_gauss_entropy(out.log_std).mean()

            with phase("vtrace"):
                vt = self._vtrace(
                    behaviour_logp=batch["behavior_logp"],
                    target_logp=jax.lax.stop_gradient(logp),
                    rewards=batch["reward"],
                    values=jax.lax.stop_gradient(values),
                    values_next=jax.lax.stop_gradient(values_next),
                    done=batch["done"],
                    terminated=batch["terminated"],
                )
            with phase("learn"):
                pg_loss = -(vt.pg_advantages * logp).mean()
                v_loss = 0.5 * ((values - vt.vs) ** 2).mean()
                total = (
                    pg_loss + algo.value_coeff * v_loss
                    - algo.entropy_coeff * entropy
                )
                return total * loss_scale, {
                    "pg_loss": pg_loss,
                    "v_loss": v_loss,
                    "entropy": entropy,
                    "rho_mean": jnp.exp(
                        jax.lax.stop_gradient(logp) - batch["behavior_logp"]
                    ).mean(),
                }

        grads, aux = jax.grad(loss_fn, has_aux=True)(state.params)
        with phase("learn"):
            if axis_name is not None:
                with phase("learn/psum"):
                    grads = jax.lax.pmean(grads, axis_name)
                    aux, boot = jax.lax.pmean((aux, boot), axis_name)
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)

            new_state = IMPALAState(
                params=params,
                opt_state=opt_state,
                obs_stats=obs_stats,
                iteration=state.iteration + 1,
            )
            metrics = {
                "loss/pg": aux["pg_loss"],
                "loss/value": aux["v_loss"],
                "policy/entropy": aux["entropy"],
                "policy/rho_mean": aux["rho_mean"],
                # rows evaluated apart for their successor value, and 1.0
                # when that was the whole next_obs pass (dp: shard means)
                **boot,
                # grads are already pmean'd, so the health scalars replicate;
                # the norm is divided by the (power-of-two) loss scale so
                # health thresholds see the true magnitude — inf/nan survive
                **training_health(
                    state.params, params, optax.global_norm(grads) / loss_scale
                ),
                # precision: loss-scale telemetry (empty when the policy
                # carries no scale)
                **loss_scale_metrics(opt_state),
            }
        return new_state, metrics

    def _vtrace(self, **kw):
        """V-trace with exact truncation handling
        (``ops/vtrace.py::vtrace_nextobs``, a reverse ``lax.scan``)."""
        algo = self.config.algo
        return vtrace_nextobs(
            **kw,
            gamma=algo.gamma, clip_rho=algo.clip_rho, clip_c=algo.clip_c,
            clip_pg_rho=algo.clip_pg_rho,
            # clamped in the op. `.get` keeps pre-knob configs loadable
            unroll=int(algo.get("gae_unroll", 1)),
        )

    def default_config(self):
        return IMPALA_LEARNER_CONFIG
