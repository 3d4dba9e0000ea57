"""Uniform replay (parity: reference ``surreal/replay/uniform_replay.py``
— ring buffer + uniform sampling, the DDPG path; SURVEY.md §2.1)."""

from __future__ import annotations

from typing import Any

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


class UniformReplay:
    """Pure-function uniform replay over a device ring buffer."""

    def __init__(self, replay_config):
        self.capacity = int(replay_config.capacity)
        self.batch_size = int(replay_config.batch_size)
        self.start_sample_size = int(replay_config.start_sample_size)
        # replay-gather routing ('xla' | 'pallas' — the scalar-prefetch
        # row-DMA kernel, ops/pallas_replay.py); injected from
        # algo.replay_gather by the off-policy trainer. `.get` keeps raw
        # replay configs loadable.
        self.gather_impl = replay_config.get("gather_impl", "xla")

    def init(self, example_transition: Any) -> RingState:
        return init_ring(example_transition, self.capacity)

    def insert(self, state: RingState, batch: Any) -> RingState:
        return ring_insert(state, batch, self.capacity)

    def can_sample(self, state: RingState) -> jax.Array:
        return can_sample(state.size, self.start_sample_size)

    def sample(self, state: RingState, key: jax.Array, batch_size: int | None = None):
        """-> (state, batch, info). Uniform with replacement over the
        current fill; size is traced, so indices are ``randint % size``."""
        bs = batch_size or self.batch_size
        with phase("replay_sample"):
            with phase("replay_sample/search"):
                idx = jax.random.randint(
                    key, (bs,), 0, jnp.maximum(state.size, 1)
                )
            with phase("replay_sample/gather"):
                batch = ring_gather(state, idx, impl=self.gather_impl)
        return state, batch, {"idx": idx}

    def sample_many(
        self, state: RingState, keys: jax.Array, batch_size: int | None = None
    ):
        """-> (state, batches [K, bs, ...], idx [K, bs]): all K index sets
        drawn in one batched randint and gathered in ONE ring gather — the
        off-policy update loop's fast path (the sequential form pays a
        full-buffer gather dispatch per scan step; at the DDPG default
        that is 64 sequential draws).

        Record-equivalence contract: set k equals ``sample(state,
        keys[k])`` bit-for-bit — same randint shape/bounds per key, same
        storage gather — so the fused iteration's training record is
        IDENTICAL either way (tested in tests/test_replay.py /
        tests/test_ddpg.py). Uniform-only: the state doesn't change
        between draws, which is exactly what prioritized replay violates.
        """
        bs = batch_size or self.batch_size
        K = keys.shape[0]
        with phase("replay_sample"):
            with phase("replay_sample/search"):
                idx = jax.vmap(
                    lambda k: jax.random.randint(
                        k, (bs,), 0, jnp.maximum(state.size, 1)
                    )
                )(keys)                                     # [K, bs]
            # one gather for all sets (impl-routed: 'pallas' turns it into
            # K*bs scalar-prefetch row DMAs — see ring_gather)
            with phase("replay_sample/gather"):
                flat = ring_gather(
                    state, idx.reshape(-1), impl=self.gather_impl
                )
                batches = jax.tree.map(
                    lambda x: x.reshape(K, bs, *x.shape[1:]), flat
                )
        return state, batches, idx

    # -- telemetry gauges (device scalars; see replay/base.py) ---------------
    def gauges(self, state: RingState) -> dict:
        return ring_gauges(state, self.capacity)

    def age_frac(self, state: RingState, idx: jax.Array) -> jax.Array:
        return sample_age_frac(state, idx, self.capacity)
