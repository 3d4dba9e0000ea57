"""Replay layer core (parity: reference ``surreal/replay/base.py`` —
collector/sampler service threads over ZMQ, SURVEY.md §2.1 and §3.3),
re-designed as HBM-resident ring buffers.

The reference ran replay as a separate process: a collector thread pulled
experience off ZMQ and ``insert()``-ed, a sampler thread served batches on
request, ``start_sample_condition`` gated early sampling, eviction was
FIFO. Here the buffer IS a device pytree and insert/sample are pure
jittable functions — the "service" threads disappear into the training
program's dataflow; under a dp mesh each device owns a shard of the buffer
(the reference's ShardedReplay, for free, see replay/sharded.py).

All buffers store flat transition dicts: {k: [capacity, ...]} with a write
cursor and size. Insertion is vectorized: a whole [N, ...] batch is
contiguous in the ring but for at most one wrap, so it lands as two
windows of N rows per leaf (:func:`ring_write`), read, merged and written
in place. Not a row scatter: XLA keeps a narrow ``[capacity, F]`` ring
capacity-minor on the TPU (ops/pallas_replay.py, "Known cost"), so a
scattered row is F single words a whole strip apart, while a window of
rows is F contiguous strips (PERF.md section 6, PR 29).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from surreal_tpu.utils.phases import phase


class RingState(NamedTuple):
    """Shared ring-buffer bookkeeping."""

    storage: Any       # {k: [capacity, ...]} pytree
    cursor: jax.Array  # int32 next write position
    size: jax.Array    # int32 current fill


def init_ring(example: Any, capacity: int) -> RingState:
    """Allocate storage from one example transition pytree {k: [...]}
    (leading batch dims stripped by the caller)."""
    storage = jax.tree.map(
        lambda x: jnp.zeros((capacity, *jnp.shape(x)), jnp.asarray(x).dtype), example
    )
    return RingState(
        storage=storage,
        cursor=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def ring_write(buf: jax.Array, new: jax.Array, cursor: jax.Array) -> jax.Array:
    """The ring ``buf`` of ``capacity = buf.shape[0]`` slots with the rows
    of ``new`` at slots ``(cursor + i) % capacity``, cast to ``buf.dtype``.

    ``n = new.shape[0]`` is static and at most ``capacity`` (more rows than
    slots is refused at trace time: the first of them would be evicted by
    the last of the same call). The last ``tail`` rows wrap to slot 0, so
    the rows are rolled by ``tail`` and written as two windows of ``n``
    slots, each keeping the ring's contents where the other's rows go: one
    at the cursor, pulled back to ``capacity - n`` where the rows wrap
    (``dynamic_update_slice`` clamps a start that runs past the end; it
    does not wrap), and one at slot 0, which rewrites what it read where
    nothing wraps.
    """
    n, capacity = new.shape[0], buf.shape[0]
    if n > capacity:
        raise ValueError(
            f"ring_write: {n} rows do not fit a ring of {capacity} slots"
        )
    tail = jnp.maximum(cursor + n - capacity, 0)
    rolled = jnp.roll(new.astype(buf.dtype), tail, axis=0)
    wrapped = jax.lax.broadcasted_iota(jnp.int32, new.shape, 0) < tail
    for start, keep in ((jnp.minimum(cursor, capacity - n), wrapped), (0, ~wrapped)):
        old = jax.lax.dynamic_slice_in_dim(buf, start, n)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, jnp.where(keep, old, rolled), start, axis=0
        )
    return buf


def ring_insert(state: RingState, batch: Any, capacity: int) -> RingState:
    """Insert a [N, ...] batch at the cursor with wraparound (FIFO evict).

    N is a static shape; row ``i`` lands at ``(cursor + i) % capacity`` —
    two window writes per leaf (:func:`ring_write`), fully on device.
    """
    from surreal_tpu.utils.asserts import check_insert_batch

    check_insert_batch(batch, state.storage, name="ring_insert")
    n = jax.tree.leaves(batch)[0].shape[0]
    with phase("replay_insert"):
        storage = jax.tree.map(
            lambda buf, new: ring_write(buf, new, state.cursor),
            state.storage, batch,
        )
        return RingState(
            storage=storage,
            cursor=(state.cursor + n) % capacity,
            size=jnp.minimum(state.size + n, capacity),
        )


def ring_gather(state: RingState, idx: jax.Array, impl: str = "xla") -> Any:
    """Gather transitions at ``idx`` -> {k: [B, ...]}.

    ``impl`` routes the data movement (``algo.replay_gather``): 'xla' =
    the fused XLA gather; 'pallas' = the scalar-prefetch row-DMA kernel
    (ops/pallas_replay.py; interpret mode off-TPU). Bit-equal outputs
    either way — the kernel copies rows verbatim.
    """
    if impl == "pallas":
        from surreal_tpu.ops import pallas_interpret
        from surreal_tpu.ops.pallas_replay import gather_rows_pallas

        return jax.tree.map(
            lambda buf: gather_rows_pallas(
                buf, idx, interpret=pallas_interpret()
            ),
            state.storage,
        )
    if impl != "xla":
        raise ValueError(f"replay gather impl {impl!r} not in xla|pallas")
    return jax.tree.map(lambda buf: buf[idx], state.storage)


def can_sample(size: jax.Array, start_sample_size: int) -> jax.Array:
    """The reference's ``start_sample_condition`` (min fill before the
    learner may draw)."""
    return size >= start_sample_size


# -- telemetry gauges (SURVEY.md §5.5: tensorplex tracked replay occupancy;
# the rebuild computes the gauges IN-GRAPH as device scalars that ride the
# metrics dict, syncing to host only at the metrics cadence) ----------------

def ring_gauges(state: RingState, capacity: int) -> dict:
    """Occupancy gauges for a ring buffer: absolute fill and fraction."""
    size = state.size.astype(jnp.float32)
    return {"replay/size": size, "replay/fill": size / capacity}


def sample_age_frac(state: RingState, idx: jax.Array, capacity: int) -> jax.Array:
    """Mean staleness of a sampled index batch, as a fraction of the
    current fill: 0 = just written, ~1 = the oldest transitions held.
    Ring age is distance behind the newest write, modulo wraparound."""
    newest = (state.cursor - 1) % capacity
    age = (newest - idx) % capacity
    return age.astype(jnp.float32).mean() / jnp.maximum(
        state.size.astype(jnp.float32), 1.0
    )
