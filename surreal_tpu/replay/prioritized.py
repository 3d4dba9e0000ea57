"""Prioritized experience replay (BASELINE config ③ requires it beyond the
reference, which shipped only uniform/FIFO — SURVEY.md §6; semantics follow
Schaul et al. 2016: proportional priorities p^alpha, IS weights with
annealed beta, max-priority on fresh inserts).

TPU design decision (SURVEY.md §7 hard-parts list): no sum-tree. A binary
sum-tree is pointer-chasing that neither vectorizes nor maps to the MXU/VPU,
and it is state that insert, ``update_priorities``, checkpoints and the
shard specs would have to maintain. The draw is stateless and goes in two
levels over blocks of :data:`BLOCK` slots (the lane width): one fused pass
reads the priority vector and reduces ``p^alpha`` to block sums (nothing of
the vector's size is written), a cumulative sum and a search over the block
sums pick each draw's block, and a cumulative sum and a search inside that
one gathered block pick its slot. Still O(capacity) reads per draw, but a
flat ``cumsum`` over the whole vector, which this replaces, is not the
"microseconds on HBM" this docstring once promised: on the TPU v5e it took
3.9 ms per draw over 20 971 520 priorities, 46% of the fused DDPG iteration
(PERF_LEDGER.jsonl, PR 25, ``ddpg_lift_per20m``). Priority updates are pure
scatters.

Stateless across iterations, that is. Inside the fused update loop
(``launch/offpolicy_trainer.py``: sample -> learn -> ``update_priorities``,
64 times an iteration) an update moves at most ``batch_size`` priorities, so
re-adding every block each time was 64 passes over the vector for 256
changed blocks: 12.37 ms an iteration, 28% of it (PERF_LEDGER.jsonl, PR 41,
``ddpg_lift_per20m``). There the block sums ride the loop's carry:
:meth:`PrioritizedReplay.block_mass` once after the insert, ``sample(...,
mass=)``, :meth:`PrioritizedReplay.refresh_mass` after each scatter, and
dropped when the loop ends. Not a field of :class:`PrioritizedState`: it
would be a derived array in checkpoints and shard specs (the argument
against the sum-tree above), and wrong in the hands of any caller that sets
``priorities`` directly, as the benchmark's reference check does. A carried
sum and a fresh one are one expression (``_block_sums``), so the loop draws
what the stateless draw would, to the bit.

The block level is where float32 runs out: a draw's position ``u`` and the
block cdf are as large as the total mass, whose ulp is a slot's mass over
10^4 slots and a block's over 10^7, and a draw that falls on the other side
of a slot's edge than exact arithmetic puts it changes which row is
replayed. So that level (only: a few arrays of ``capacity / 128`` and of
``batch_size`` entries) carries each number as an unevaluated sum of two
float32, ``hi + lo`` ("double-float": Dekker 1971, Knuth's TwoSum), which
needs nothing the TPU lacks; once the block's own start is subtracted, what
is left of ``u`` is small and plain float32 again.
"""

from __future__ import annotations

import math
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
    ring_write,
    sample_age_frac,
)
from surreal_tpu.utils.phases import phase


# Slots per block of the two-level draw: the TPU's lane width, and the
# shape XLA gives a scan anyway. A constant, not a config value.
BLOCK = 128


def mass_cdf(p: jax.Array, axis: int = 0) -> jax.Array:
    """Cumulative sum of the non-negative ``p`` that a left search can
    trust: non-decreasing, and exactly flat over entries without mass, so
    ``cdf[i - 1] < u <= cdf[i]`` implies ``p[i] > 0``. A backend's scan owes
    neither (it adds different prefixes in different orders: over 163 840
    block sums both the TPU's and the CPU's ``cumsum`` fall by an ulp here
    and there, and move over zeros); ``_monotone`` gives both. Inside one
    gathered block the scans behaved; there it is a precaution."""
    return _monotone(p, jnp.cumsum(p, axis=axis), axis)


def _monotone(p: jax.Array, cdf: jax.Array, axis: int = 0) -> jax.Array:
    """The running maximum of ``cdf`` over the entries with mass: it
    changes nothing where the scan was monotone and flat over zeros."""
    return jax.lax.cummax(jnp.where(p > 0, cdf, 0.0), axis=axis)


# -- double-float: a number as the unevaluated sum hi + lo of two float32 ----
# Only float32 adds and multiplies, each rounded to nearest as the CPU and
# the TPU round them; no step relies on a fused multiply-add, and none is
# changed by one (the products that a compiler could fuse are exact).

def _two_sum(a, b):
    """``a + b`` exactly, as (rounded sum, rounding error)."""
    s = a + b
    t = s - a
    return s, (a - (s - t)) + (b - t)


def _halves(x):
    """``x`` exactly, as two numbers of at most 12 significant bits, so
    that products of halves are exact in float32."""
    grid = jnp.ldexp(jnp.float32(1.0), jnp.frexp(x)[1] - 12)
    hi = jnp.round(x / grid) * grid
    return hi, x - hi


def _two_prod(a, b):
    """``a * b`` exactly, as (rounded product, rounding error)."""
    p = a * b
    (ah, al), (bh, bl) = _halves(a), _halves(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _dd_cumsum(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Cumulative sum of the non-negative float32 vector ``x`` in
    double-float, from three plain ``cumsum``s that have nothing to round:
    ``x`` is cut into a part on a grid so coarse that the whole sum is under
    2^24 grid steps, the like part of what is left on a grid finer by
    2^23 / len(x), and a remainder too small for its scan's rounding to
    count. Sums of grid points under 2^24 steps are exact in float32 in
    whatever order a backend's scan adds them (a scan with a double-float
    add of its own, ``lax.associative_scan``, took 0.38 ms more per draw on
    the chip over 163 840 entries: PERF.md section 6)."""
    # total < 2^e by a float32 estimate, so < 2^(e+1): under 2^23 steps of g0
    g0 = jnp.ldexp(jnp.float32(1.0), jnp.frexp(x.sum())[1] - 22)
    g1 = g0 * 2.0 ** (math.ceil(math.log2(x.shape[0])) - 23)
    x0 = jnp.round(x / g0) * g0
    x1 = jnp.round((x - x0) / g1) * g1
    s, err = _two_sum(jnp.cumsum(x0), jnp.cumsum(x1))
    err = err + jnp.cumsum((x - x0) - x1)
    hi = s + err
    return hi, err - (hi - s)


def _dd_mul(x, y):
    """Product of the double-floats ``x`` and ``y``."""
    p, e = _two_prod(x[0], y[0])
    e = e + (x[0] * y[1] + x[1] * y[0])
    hi = p + e
    return hi, e - (hi - p)


def _dd_div(x, d):
    """Double-float ``x`` over the float32 ``d``."""
    hi = x[0] / d
    p, e = _two_prod(hi, d)
    lo = (((x[0] - p) - e) + x[1]) / d
    s = hi + lo
    return s, lo - (s - hi)


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
            return PrioritizedState(
                ring=ring_insert(state.ring, batch, self.capacity),
                priorities=ring_write(
                    state.priorities,
                    jnp.full(n, state.max_priority),
                    state.ring.cursor,
                ),
                max_priority=state.max_priority,
            )

    def can_sample(self, state: PrioritizedState) -> jax.Array:
        return can_sample(state.ring.size, self.start_sample_size)

    def _blocks(self, priorities: jax.Array) -> jax.Array:
        """[blocks, BLOCK] view of the priorities; padding and empty slots
        are 0 and 0^alpha = 0, so they carry no mass."""
        return jnp.pad(priorities, (0, -self.capacity % BLOCK)).reshape(-1, BLOCK)

    def _block_sums(self, blocks: jax.Array) -> jax.Array:
        """The mass of each row of ``blocks``: the one expression a fresh
        pass and a refreshed block share, so both are the same arithmetic."""
        return (blocks**self.alpha).sum(axis=1)

    def block_mass(self, state: PrioritizedState) -> jax.Array:
        """-> f32[ceil(capacity / BLOCK)], the sum of ``p^alpha`` over each
        block: the one pass over the priority vector that ``sample`` makes
        when it is handed no ``mass``."""
        with phase("replay_sample/mass"):
            return self._block_sums(self._blocks(state.priorities))

    def refresh_mass(
        self, mass: jax.Array, state: PrioritizedState, idx: jax.Array
    ) -> jax.Array:
        """``mass`` with the blocks that hold the slots ``idx`` added up
        again from ``state.priorities`` (the updated ones). Recomputed from
        the block's slots, never adjusted by a difference, so nothing drifts
        and duplicate blocks write the same value whichever write wins.
        Reads ``len(idx)`` rows of the view ``sample`` gathers its blocks
        from: in place where the capacity is a multiple of :data:`BLOCK`
        (else through the padded copy that ``sample`` makes too). Rows, not
        slots: 256 x 128 gathered elements took the chip longer than the
        pass they stood in for (PERF.md section 6, PR 42)."""
        with phase("replay_priority"):
            b = idx // BLOCK
            return mass.at[b].set(
                self._block_sums(self._blocks(state.priorities)[b])
            )

    def blocks_touched(self, idx: jax.Array) -> jax.Array:
        """How many distinct blocks hold the slots ``idx`` (what one
        ``refresh_mass`` adds up again), as a float32 gauge."""
        with phase("replay_priority"):
            b = idx // BLOCK
            repeat = jnp.tril(b[:, None] == b[None, :], -1).any(axis=1)
            return (~repeat).sum().astype(jnp.float32)

    def sample(
        self,
        state: PrioritizedState,
        key: jax.Array,
        batch_size: int | None = None,
        beta: jax.Array | float | None = None,
        mass: jax.Array | None = None,
    ):
        """-> (state, batch, info) with info = {idx, is_weights}.

        ``beta`` is the IS-correction exponent (anneal 0.4 -> 1.0 over
        training from the caller; defaults to beta0). ``mass`` is the block
        sums of ``state.priorities`` where a caller keeps them
        (:meth:`block_mass`, :meth:`refresh_mass`); without it they are
        added up here.
        """
        bs = batch_size or self.batch_size
        beta = self.beta0 if beta is None else beta
        with phase("replay_sample"):
            with phase("replay_sample/mass"):
                blocks = self._blocks(state.priorities)
                # (the barrier: XLA would else read the vector a second time
                # to add up the total that ``_dd_cumsum`` takes its grid from)
                sums = (
                    jax.lax.optimization_barrier(self._block_sums(blocks))
                    if mass is None else mass
                )
                # the block cdf in double-float (module docstring); a left
                # search reads its hi part
                cdf_hi, cdf_lo = _dd_cumsum(sums)
                cdf_hi = _monotone(sums, cdf_hi)
                total = cdf_hi[-1]
            with phase("replay_sample/search"):
                # stratified sampling: one uniform draw per equal slice of
                # the mass, u = (k + uniform) / bs * total
                u = _dd_div(
                    _dd_mul(
                        _two_sum(
                            jnp.arange(bs, dtype=jnp.float32),
                            jax.random.uniform(key, (bs,)),
                        ),
                        (total, cdf_lo[-1]),
                    ),
                    jnp.float32(bs),
                )
                # left search at both levels: a run of zero mass is skipped
                # and u = 0 stays in block 0, as a search of the flat cdf would
                b = jnp.searchsorted(cdf_hi, jnp.minimum(u[0], total))
                # what is left of u inside block b, from the exclusive entry
                # itself, and small enough for float32 from here on
                start = jnp.maximum(b - 1, 0)
                residual = jnp.where(
                    b > 0, (u[0] - cdf_hi[start]) + (u[1] - cdf_lo[start]), u[0]
                )
                # never past the block's own sum, which is added up in
                # another order than the block cdf (a rounded-up residual
                # would land behind the block's last slot with mass), and
                # never zero or less, which the hi part's search cannot
                # exclude and which would stop on the block's first slot
                # whether it has mass or not
                cdf = mass_cdf(blocks[b] ** self.alpha, axis=1)
                residual = jnp.clip(
                    residual, jnp.finfo(jnp.float32).tiny, cdf[:, -1]
                )
                slot = (cdf < residual[:, None]).sum(axis=1)
                idx = jnp.clip(
                    b * BLOCK + slot, 0, self.capacity - 1
                ).astype(jnp.int32)

                probs = state.priorities[idx] ** self.alpha / jnp.maximum(
                    total, 1e-12
                )
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
