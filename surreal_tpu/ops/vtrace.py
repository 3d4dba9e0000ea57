"""V-trace off-policy correction (IMPALA), as an on-device reverse scan.

The reference shipped PPO and DDPG only; BASELINE config ⑤ (IMPALA/V-trace,
SEED-RL batched inference) requires this regardless (SURVEY.md §6). Follows
the IMPALA paper's recursion with truncated importance weights; everything
is time-major [T, ...] and runs under jit.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class VTraceOutput(NamedTuple):
    vs: jax.Array            # [T, ...] V-trace value targets
    pg_advantages: jax.Array  # [T, ...] policy-gradient advantages


def _pg_advantages(rhos, clip_pg_rho, rewards, discounts, vs, values):
    """Shared pg-advantage tail: q_t = r_t + gamma_t * vs_{t+1}, final step
    bootstrapped with V_T (``values`` is the [T+1] stack)."""
    vs_next = jnp.concatenate([vs[1:], values[-1:]], axis=0)
    clipped_pg_rhos = jnp.minimum(clip_pg_rho, rhos)
    return clipped_pg_rhos * (rewards + discounts * vs_next - values[:-1])


def vtrace(
    behaviour_logp: jax.Array,
    target_logp: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    values: jax.Array,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
    clip_pg_rho: float = 1.0,
    unroll: int = 1,
) -> VTraceOutput:
    """Args:
      behaviour_logp: [T, ...] log pi_b(a_t | s_t) of the acting policy
      target_logp:    [T, ...] log pi(a_t | s_t) of the learner policy
      rewards:        [T, ...]
      discounts:      [T, ...] gamma * (1 - done)
      values:         [T+1, ...] learner value estimates incl. bootstrap
      clip_rho/clip_c/clip_pg_rho: IS-weight truncation levels (rho_bar etc.)
      unroll: recurrence-scan unroll factor (``algo.gae_unroll``)
    """
    log_rhos = target_logp - behaviour_logp
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)

    deltas = clipped_rhos * (rewards + discounts * values[1:] - values[:-1])

    # vs_t - V_t = delta_t + gamma_t c_t (vs_{t+1} - V_{t+1}); reverse scan.
    def step(carry, xs):
        delta_t, disc_t, c_t = xs
        acc = delta_t + disc_t * c_t * carry
        return acc, acc

    _, acc_rev = lax.scan(
        step,
        jnp.zeros_like(values[-1]),
        (deltas[::-1], discounts[::-1], cs[::-1]),
        unroll=max(1, min(int(unroll), deltas.shape[0])),
    )
    vs_minus_v = acc_rev[::-1]
    vs = vs_minus_v + values[:-1]

    pg_advantages = _pg_advantages(rhos, clip_pg_rho, rewards, discounts, vs, values)
    return VTraceOutput(vs=lax.stop_gradient(vs), pg_advantages=lax.stop_gradient(pg_advantages))


def vtrace_assoc(
    behaviour_logp: jax.Array,
    target_logp: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    values: jax.Array,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
    clip_pg_rho: float = 1.0,
) -> VTraceOutput:
    """:func:`vtrace` via ``associative_scan`` — O(log T) depth.

    The recursion ``x_t = delta_t + (gamma_t c_t) x_{t+1}`` is the same
    first-order linear recurrence as GAE's (shared solver:
    ``ops.returns.reverse_linear_scan_assoc``), so it also shards over a
    sequence-parallel mesh axis (parallel/sp.py).
    """
    from surreal_tpu.ops.returns import reverse_linear_scan_assoc

    log_rhos = target_logp - behaviour_logp
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)

    deltas = clipped_rhos * (rewards + discounts * values[1:] - values[:-1])
    vs = reverse_linear_scan_assoc(discounts * cs, deltas) + values[:-1]

    pg_advantages = _pg_advantages(rhos, clip_pg_rho, rewards, discounts, vs, values)
    return VTraceOutput(
        vs=lax.stop_gradient(vs), pg_advantages=lax.stop_gradient(pg_advantages)
    )


def vtrace_nextobs(
    behaviour_logp: jax.Array,
    target_logp: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    values_next: jax.Array,
    done: jax.Array,
    terminated: jax.Array,
    gamma: float,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
    clip_pg_rho: float = 1.0,
    unroll: int = 1,
) -> VTraceOutput:
    """V-trace over auto-reset trajectories with exact truncation handling
    (the same two-mask scheme as the PPO learner's GAE):

    - bootstrap discount ``gamma*(1-terminated)`` pairs with
      ``values_next`` = V(pre-reset successor obs), so truncated episodes
      still bootstrap;
    - the recursion's cross-step correction is cut at EVERY episode
      boundary (``done``), so corrections never leak across resets.

    All args are time-major [T, ...]; ``values``/``values_next`` are the
    learner's V(s_t) / V(s'_t). ``unroll`` is the recurrence scan's unroll
    factor (``algo.gae_unroll``).
    """
    log_rhos = target_logp - behaviour_logp
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)

    # float32 masks, as PPO's ``_gae`` has them: bfloat16 rewards and values
    # are promoted where they meet one, and the recurrence accumulates in f32
    boot_disc = gamma * (1.0 - terminated.astype(jnp.float32))
    edge = 1.0 - done.astype(jnp.float32)

    deltas = clipped_rhos * (rewards + boot_disc * values_next - values)

    def step(carry, xs):
        delta_t, edge_t, c_t = xs
        acc = delta_t + gamma * edge_t * c_t * carry
        return acc, acc

    _, acc_rev = lax.scan(
        step,
        jnp.zeros_like(deltas[-1]),
        (deltas[::-1], edge[::-1], cs[::-1]),
        unroll=max(1, min(int(unroll), deltas.shape[0])),
    )
    vs = acc_rev[::-1] + values

    # pg advantage: q_t = r + boot_disc * (vs of the successor); at episode
    # boundaries the successor lives in the next episode, so fall back to
    # the value estimate of the terminal obs.
    vs_shift = jnp.concatenate([vs[1:], values_next[-1:]], axis=0)
    done_f = done.astype(rewards.dtype)
    vs_next = done_f * values_next + (1.0 - done_f) * vs_shift
    clipped_pg_rhos = jnp.minimum(clip_pg_rho, rhos)
    pg_advantages = clipped_pg_rhos * (rewards + boot_disc * vs_next - values)

    return VTraceOutput(
        vs=lax.stop_gradient(vs), pg_advantages=lax.stop_gradient(pg_advantages)
    )
