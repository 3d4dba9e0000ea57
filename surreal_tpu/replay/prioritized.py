"""Prioritized experience replay (BASELINE config ③ requires it beyond the
reference, which shipped only uniform/FIFO — SURVEY.md §6; semantics follow
Schaul et al. 2016: proportional priorities p^alpha, IS weights with
annealed beta, max-priority on fresh inserts).

TPU design decision (SURVEY.md §7 hard-parts list): no sum-tree. A binary
sum-tree is pointer-chasing that neither vectorizes nor maps to the MXU/VPU;
instead sampling is ``cumsum`` + ``searchsorted`` over the priority vector
— O(capacity) work but one fused, memory-bandwidth-bound pass that XLA
vectorizes perfectly, and for the 1e5–1e6 capacities the reference ran
(BASELINE configs) this is microseconds on HBM. Priority updates are pure
scatters.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from surreal_tpu.replay.base import (
    RingState,
    can_sample,
    init_ring,
    ring_gather,
    ring_gauges,
    ring_insert,
    sample_age_frac,
)
from surreal_tpu.utils.phases import phase


class PrioritizedState(NamedTuple):
    ring: RingState
    priorities: jax.Array    # [capacity] float32, 0 = empty slot
    max_priority: jax.Array  # scalar, priority given to fresh transitions


class PrioritizedReplay:
    def __init__(self, replay_config):
        self.capacity = int(replay_config.capacity)
        self.batch_size = int(replay_config.batch_size)
        self.start_sample_size = int(replay_config.start_sample_size)
        self.alpha = float(replay_config.priority_alpha)
        self.beta0 = float(replay_config.priority_beta0)
        self.eps = float(replay_config.priority_eps)
        # replay gather/scatter routing (see replay/uniform.py's note):
        # 'pallas' also routes the priority-refresh scatter through the
        # row-DMA kernel. `.get` keeps raw replay configs loadable.
        self.gather_impl = replay_config.get("gather_impl", "xla")

    def init(self, example_transition: Any) -> PrioritizedState:
        return PrioritizedState(
            ring=init_ring(example_transition, self.capacity),
            priorities=jnp.zeros(self.capacity, jnp.float32),
            max_priority=jnp.ones((), jnp.float32),
        )

    def insert(self, state: PrioritizedState, batch: Any) -> PrioritizedState:
        """New transitions enter at the current max priority (so they are
        seen at least once before their TD error takes over)."""
        n = jax.tree.leaves(batch)[0].shape[0]
        with phase("replay_insert"):
            idx = (
                state.ring.cursor + jnp.arange(n, dtype=jnp.int32)
            ) % self.capacity
            return PrioritizedState(
                ring=ring_insert(state.ring, batch, self.capacity),
                priorities=state.priorities.at[idx].set(state.max_priority),
                max_priority=state.max_priority,
            )

    def can_sample(self, state: PrioritizedState) -> jax.Array:
        return can_sample(state.ring.size, self.start_sample_size)

    def sample(
        self,
        state: PrioritizedState,
        key: jax.Array,
        batch_size: int | None = None,
        beta: jax.Array | float | None = None,
    ):
        """-> (state, batch, info) with info = {idx, is_weights}.

        ``beta`` is the IS-correction exponent (anneal 0.4 -> 1.0 over
        training from the caller; defaults to beta0).
        """
        bs = batch_size or self.batch_size
        beta = self.beta0 if beta is None else beta
        with phase("replay_sample"):
            with phase("replay_sample/mass"):
                p = state.priorities**self.alpha  # empty slots: 0^alpha = 0
                total = p.sum()
                cdf = jnp.cumsum(p)
            with phase("replay_sample/search"):
                # stratified sampling: one uniform draw per equal slice of
                # the mass
                u = (jnp.arange(bs) + jax.random.uniform(key, (bs,))) / bs * total
                idx = jnp.clip(
                    jnp.searchsorted(cdf, u), 0, self.capacity - 1
                ).astype(jnp.int32)

                probs = p[idx] / jnp.maximum(total, 1e-12)
                n = jnp.maximum(state.ring.size, 1).astype(jnp.float32)
                weights = (n * jnp.maximum(probs, 1e-12)) ** (-beta)
                weights = weights / jnp.maximum(weights.max(), 1e-12)
            with phase("replay_sample/gather"):
                batch = ring_gather(state.ring, idx, impl=self.gather_impl)
        return state, batch, {"idx": idx, "is_weights": weights}

    # -- telemetry gauges (device scalars; see replay/base.py) ---------------
    def gauges(self, state: PrioritizedState) -> dict:
        # callers reading max_priority after the dp pmax see the global one
        return dict(
            ring_gauges(state.ring, self.capacity),
            **{"replay/max_priority": state.max_priority},
        )

    def age_frac(self, state: PrioritizedState, idx: jax.Array) -> jax.Array:
        return sample_age_frac(state.ring, idx, self.capacity)

    def update_priorities(
        self, state: PrioritizedState, idx: jax.Array, td_errors: jax.Array
    ) -> PrioritizedState:
        with phase("replay_priority"):
            prio = jnp.abs(td_errors) + self.eps
            if self.gather_impl == "pallas":
                # scalar-prefetch row-DMA scatter (ops/pallas_replay.py),
                # in-place via input_output_aliases. Duplicate indices (a
                # stratified draw can repeat a high-mass slot) resolve
                # last-write-wins in grid order — the same "some write
                # wins" contract ``.at[].set`` documents as unspecified.
                from surreal_tpu.ops import pallas_interpret
                from surreal_tpu.ops.pallas_replay import scatter_rows_pallas

                priorities = scatter_rows_pallas(
                    state.priorities, idx, prio, interpret=pallas_interpret(),
                )
            else:
                priorities = state.priorities.at[idx].set(prio)
            return PrioritizedState(
                ring=state.ring,
                priorities=priorities,
                max_priority=jnp.maximum(state.max_priority, prio.max()),
            )
