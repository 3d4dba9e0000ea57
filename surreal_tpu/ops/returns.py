"""Return / advantage estimators as on-device scans.

Capability parity with the reference's advantage machinery, relocated from
its learners into a shared op library (SURVEY.md §5.7): the reference
computed GAE in ``surreal/learner/ppo.py`` and n-step TD targets in
``surreal/learner/aggregator.py`` with numpy/torch loops on host; here each
estimator is a ``jax.lax.scan`` (plus a log-depth ``associative_scan``
variant for long horizons) over time-major device arrays.

Conventions (all time-major):
- arrays are [T, ...] with arbitrary batch dims after T
- ``discounts[t]`` = gamma * (1 - done[t]): 0 at terminal steps, so every
  estimator is episode-boundary-correct under masking by construction
- ``values`` is [T+1, ...] (bootstrap value appended), or pass
  ``bootstrap_value`` separately to the n-step helper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def gae_advantages(
    rewards: jax.Array,
    discounts: jax.Array,
    values: jax.Array,
    lam: float,
    unroll: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """Generalized Advantage Estimation (reverse linear scan).

    Args:
      rewards:   [T, ...]
      discounts: [T, ...]  (= gamma * (1 - done))
      values:    [T+1, ...] value estimates incl. bootstrap at index T
      lam:       GAE lambda
      unroll:    scan unroll factor (``algo.gae_unroll``)

    Returns:
      (advantages [T, ...], value_targets [T, ...]) where targets = adv + v.
    """
    deltas = rewards + discounts * values[1:] - values[:-1]
    decay = discounts * lam

    def step(carry, xs):
        delta_t, decay_t = xs
        adv = delta_t + decay_t * carry
        return adv, adv

    _, advs_rev = lax.scan(
        step,
        jnp.zeros_like(deltas[0]),
        (deltas[::-1], decay[::-1]),
        unroll=max(1, min(int(unroll), deltas.shape[0])),
    )
    advantages = advs_rev[::-1]
    return advantages, advantages + values[:-1]


def reverse_linear_scan_assoc(coeffs: jax.Array, deltas: jax.Array) -> jax.Array:
    """Solve ``x_t = deltas_t + coeffs_t * x_{t+1}`` (x_T = 0) in O(log T)
    depth via ``associative_scan``: over reversed time the recurrence
    composes associatively as (c, d)∘(c', d') = (c*c', d' + c'*d).

    This is THE recurrence of return estimation — GAE, V-trace, and
    discounted returns are all instances — and, being an associative scan,
    it also shards over a sequence-parallel mesh axis (parallel/sp.py).
    """

    def combine(left, right):
        c_l, d_l = left
        c_r, d_r = right
        return c_l * c_r, d_r + c_r * d_l

    _, x_rev = lax.associative_scan(combine, (coeffs[::-1], deltas[::-1]))
    return x_rev[::-1]


def gae_advantages_assoc(
    rewards: jax.Array,
    discounts: jax.Array,
    values: jax.Array,
    lam: float,
) -> tuple[jax.Array, jax.Array]:
    """GAE via ``associative_scan`` — O(log T) depth for long horizons."""
    deltas = rewards + discounts * values[1:] - values[:-1]
    advantages = reverse_linear_scan_assoc(discounts * lam, deltas)
    return advantages, advantages + values[:-1]


def n_step_returns(
    rewards: jax.Array,
    discounts: jax.Array,
    bootstrap_values: jax.Array,
    n_step: int,
) -> jax.Array:
    """n-step bootstrapped TD targets (reference: DDPG aggregator's n-step
    helper).

    G_t = r_t + d_t r_{t+1} + ... + (prod d) * V(s_{t+n}), truncated at both
    episode ends (discounts=0) and the trajectory end (bootstrap with the
    last available value).

    Args:
      rewards:          [T, ...]
      discounts:        [T, ...]
      bootstrap_values: [T, ...] value of the state *after* step t, i.e.
                        V(s_{t+1}); the estimator looks ahead up to n steps.
      n_step:           lookahead horizon (n=1 -> one-step TD target)

    Returns: [T, ...] targets.
    """
    T = rewards.shape[0]
    if n_step == 1:
        return rewards + discounts * bootstrap_values

    # For n>1 compute directly with a vectorized window sum — O(T * n) work
    # but fully parallel on the MXU-free VPU and simplest to verify.
    padded_r = jnp.concatenate([rewards, jnp.zeros((n_step,) + rewards.shape[1:], rewards.dtype)])
    padded_d = jnp.concatenate([discounts, jnp.zeros((n_step,) + discounts.shape[1:], discounts.dtype)])
    padded_v = jnp.concatenate(
        [bootstrap_values, jnp.zeros((n_step,) + bootstrap_values.shape[1:], bootstrap_values.dtype)]
    )

    def target_at(t):
        g = jnp.zeros_like(rewards[0])
        disc = jnp.ones_like(discounts[0])
        for k in range(n_step):
            g = g + disc * padded_r[t + k]
            disc = disc * padded_d[t + k]
        # bootstrap with V(s_{t+n}) = bootstrap_values[t+n-1]; disc already 0
        # past episode end or trajectory end (padding), so this is safe.
        return g + disc * padded_v[t + n_step - 1]

    return jax.vmap(target_at)(jnp.arange(T))


def discounted_returns(
    rewards: jax.Array,
    discounts: jax.Array,
    bootstrap_value: jax.Array,
    unroll: int = 1,
) -> jax.Array:
    """Monte-Carlo discounted returns with bootstrap (eval/diagnostics)."""

    def step(carry, xs):
        r_t, d_t = xs
        ret = r_t + d_t * carry
        return ret, ret

    _, rets_rev = lax.scan(
        step, bootstrap_value, (rewards[::-1], discounts[::-1]),
        unroll=max(1, min(int(unroll), rewards.shape[0])),
    )
    return rets_rev[::-1]
