"""Learner base (parity: reference ``surreal/learner/base.py`` — the main
SGD loop owner with prefetch/publish/checkpoint hooks, SURVEY.md §2.1 and
§3.4), re-designed functionally for XLA.

The reference Learner was a stateful object with threads (batch prefetch,
parameter publishing). Here a learner is a pair of *pure jittable
functions* over an explicit :class:`LearnerState` pytree:

    state           = learner.init(key, specs)
    state, metrics  = learner.learn(state, batch, key)      # one SGD iter
    action, info    = learner.act(state, obs, key, mode)    # shared params

``act`` living on the same state is the TPU answer to the reference's
ParameterPublisher→ParameterServer→ParameterClient pipeline (SURVEY.md
§2.1 Parameter-server row): acting and learning share device memory, so
parameter "publishing" is a no-op. Checkpointing serializes the state
pytree (session/checkpoint.py); the driver loop lives in launch/trainer.py.
"""

from __future__ import annotations

import abc
from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import optax

from surreal_tpu.envs.base import EnvSpecs
from surreal_tpu.ops.precision import (
    PrecisionPolicy,
    dynamic_loss_scaling,
    resolve_policy,
)

# Agent modes (parity: reference agent modes on surreal/agent/base.py)
TRAINING = "training"
EVAL_DETERMINISTIC = "eval_deterministic"
EVAL_STOCHASTIC = "eval_stochastic"


def training_health(old_params, new_params, grad_norm: jax.Array) -> dict:
    """In-graph training-health diagnostics, shared by every learner's
    ``learn`` (the telemetry spine's health signals): grad norm, param
    norm, update ratio, and a NaN/inf guard.

    Every output is a DEVICE scalar computed inside the jitted step: it
    rides the metrics dict and reaches the host only when the existing
    ``metrics.every_n_iters`` cadence syncs, so the hot loop gains ZERO
    additional device->host syncs (tests/test_telemetry.py proves this
    with a transfer-guard test).

    ``grad_norm`` is supplied by the caller because the gradients live at
    different places per algorithm (PPO's sit inside its minibatch scan;
    DDPG has two trees). The nonfinite guard keys off the norms:
    ``optax.global_norm`` is nonfinite iff any element is (inf/nan
    propagate through the sum of squares), so one isfinite check covers
    the whole tree without a second reduction.
    """
    old_norm = optax.global_norm(old_params)
    new_norm = optax.global_norm(new_params)
    update_norm = optax.global_norm(
        jax.tree.map(lambda a, b: a - b, new_params, old_params)
    )
    finite = jnp.isfinite(grad_norm) & jnp.isfinite(new_norm)
    return {
        "health/grad_norm": grad_norm,
        "health/param_norm": new_norm,
        "health/update_ratio": update_norm / (old_norm + 1e-12),
        "health/nonfinite": 1.0 - finite.astype(jnp.float32),
    }


class RecoveryScaleState(NamedTuple):
    """State of :func:`recovery_scale`: one f32 scalar, 1.0 until a
    divergence rollback backs it off (launch/recovery.py)."""

    scale: jax.Array


def recovery_scale() -> optax.GradientTransformation:
    """Final link of every learner's optimizer chain: multiply the update
    by a state-resident scalar (1.0 by default, i.e. a no-op).

    This is the bounded-LR-backoff mechanism of the divergence-rollback
    policy: because the scalar lives in the optimizer state it is a
    *traced input* to the jitted learn program, so the recovery layer can
    shrink the effective learning rate between iterations by rewriting one
    leaf of the restored checkpoint — no learner rebuild, no recompile,
    and schedules (linear anneal) compose since the scale multiplies
    whatever update the upstream chain produced.
    """

    def init_fn(params):
        del params
        return RecoveryScaleState(scale=jnp.ones((), jnp.float32))

    def update_fn(updates, state, params=None):
        del params
        return jax.tree.map(lambda u: u * state.scale, updates), state

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer_chain(
    lr, max_grad_norm, policy: PrecisionPolicy
) -> optax.GradientTransformation:
    """THE optimizer-chain constructor every learner uses (ppo, impala,
    and both DDPG chains) — clip -> adam -> recovery_scale, wrapped in
    dynamic loss scaling when the precision policy asks for it. One
    builder so a new chain link (or a new policy) cannot be threaded into
    one algorithm and silently dropped from another.

    # precision: params and optimizer state stay float32 under every
    # policy; loss scaling wraps the WHOLE chain (ops/precision.py) so an
    # overflow skips the step without touching Adam moments, and its
    # state rides the pytree next to recovery_scale — the divergence
    # guard + rollback remain the second fence behind the skip logic.
    """
    inner = optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adam(lr),
        # divergence-rollback LR backoff: a no-op scale-by-1 until
        # launch/recovery.py writes a backed-off value into the state
        recovery_scale(),
    )
    if policy.loss_scaling:
        return dynamic_loss_scaling(inner, policy)
    return inner


def set_recovery_lr_scale(tree: Any, scale) -> Any:
    """Write ``scale`` into every :class:`RecoveryScaleState` leaf of a
    learner-state pytree (all optimizer chains at once — DDPG carries
    two). Host-side, between iterations only; a no-op for trees without
    the link. Each leaf gets its OWN scalar array: sharing one buffer
    across leaves would make a donating fused iteration see the same
    buffer twice in its flattened arguments — a hard XLA error."""
    is_leaf = lambda n: isinstance(n, RecoveryScaleState)  # noqa: E731
    return jax.tree.map(
        lambda n: (
            RecoveryScaleState(scale=jnp.full((), scale, jnp.float32))
            if is_leaf(n) else n
        ),
        tree,
        is_leaf=is_leaf,
    )


def get_recovery_lr_scale(tree: Any) -> float | None:
    """Current recovery LR scale (first link found), or None when the tree
    predates / lacks the link. One device->host sync; telemetry-path only."""
    found: list = []
    is_leaf = lambda n: isinstance(n, RecoveryScaleState)  # noqa: E731

    def visit(n):
        if is_leaf(n):
            found.append(n.scale)
        return n

    jax.tree.map(visit, tree, is_leaf=is_leaf)
    return float(found[0]) if found else None


class Learner(abc.ABC):
    """Algorithm = init + learn + act, all pure. Subclasses hold only
    static configuration (hyperparameters, model definitions) so their
    methods close over nothing traced."""

    def __init__(self, learner_config, env_specs: EnvSpecs):
        self.config = learner_config
        self.specs = env_specs
        # precision: resolved ONCE at build for every algorithm —
        # subclasses build models from policy.model_config(...) and
        # optimizer chains from make_optimizer_chain(...), drivers read
        # it for staging dtypes and checkpoint metadata (ops/precision.py)
        self.policy = resolve_policy(learner_config)
        # fail-fast-on-unwired-knobs convention: the trajectory encoder is
        # implemented by PPOLearner (which overrides this flag before it
        # can raise); any other algorithm silently ignoring the knob would
        # train a different model than the user configured
        enc = learner_config.get("model", None)
        enc = enc.get("encoder", None) if enc is not None else None
        if (
            enc is not None
            and enc.get("kind", "auto") == "trajectory"
            and not self.supports_trajectory_encoder
        ):
            raise ValueError(
                "model.encoder.kind='trajectory' is an on-policy seam "
                f"(ppo, impala; got algo {learner_config.algo.name!r}); "
                "ddpg uses its own actor/critic model build"
            )

    # -- state ---------------------------------------------------------------
    @abc.abstractmethod
    def init(self, key: jax.Array) -> Any:
        """Build the initial LearnerState pytree (params, optimizer, aux)."""

    # -- learning ------------------------------------------------------------
    @abc.abstractmethod
    def learn(self, state: Any, batch: Mapping[str, jax.Array], key: jax.Array):
        """One SGD iteration. Pure; jit/shard_map-safe.

        Returns (new_state, metrics dict of scalars).

        Donation contract (the dispatch pipeline's HBM-reuse invariant):
        drivers jit this with ``donate_argnums=(0,)`` wherever the state
        is loop-carried — state-in and state-out are shape-identical, so
        XLA updates the buffers in place. Implementations must therefore
        never stash ``state`` (or leaves of it) on ``self`` or in any
        closure that outlives the call; callers that keep the state
        aliased elsewhere (SEED's live act closure) jit with
        ``donate_argnums=()`` instead — see parallel/dp.py::dp_learn.
        """

    # -- acting --------------------------------------------------------------
    @abc.abstractmethod
    def act(self, state: Any, obs: jax.Array, key: jax.Array, mode: str = TRAINING):
        """Batched action selection from the current state.

        Returns (action, act_info) where act_info carries whatever the
        learner needs attached to experience (behavior-policy stats — the
        reference's ``action_info``, SURVEY.md §2.1 PPO-agent row).
        """

    # -- sequence/recurrent acting seam (SURVEY.md §5.7) ---------------------
    # Policies that condition on history (trajectory transformers; a
    # future RNN) thread a per-env acting carry through rollouts. The
    # memoryless default keeps `act_step` == `act`, so every existing
    # collector runs unchanged; drivers that cannot thread a carry (host
    # SEED plane, remote actors) gate on `requires_act_carry`.
    requires_act_carry: bool = False
    supports_trajectory_encoder: bool = False  # PPO/IMPALA implement it

    def act_init(self, num_envs: int) -> Any:
        """Fresh acting carry for a rollout segment (None = memoryless)."""
        return None

    def act_step(
        self, state: Any, act_carry: Any, obs: jax.Array, key: jax.Array,
        mode: str = TRAINING,
    ):
        """History-conditioned acting: (action, act_info, new_carry)."""
        action, info = self.act(state, obs, key, mode)
        return action, info, act_carry

    def act_rows(self, act_carry: Any) -> dict:
        """``{metrics row: scalar}`` that the acting steps behind
        ``act_carry`` counted into it (nothing for most learners): a fused
        rollout reads it where it ends, and the iteration's row takes it."""
        return {}

    # -- bookkeeping ---------------------------------------------------------
    def default_config(self):  # override per algorithm
        raise NotImplementedError
