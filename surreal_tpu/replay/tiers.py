"""Device-resident hot replay tier.

The top of the replay hierarchy (ROADMAP's "device-resident hot tiers"):
a fixed-capacity ring of the NEWEST transitions held as committed device
arrays — ``replay/base.py``'s ring semantics verbatim, jitted at this
seam — filled with the collector's already-device-resident transition
batches and drawn via the PR-7 Pallas gather kernels
(``ops/pallas_replay.py``), so a steady-state uniform sample never
touches the host: no wire frame, no ``spec.unpack``, no host->device
transfer (the in-network sampling argument, arXiv:2110.13506, applied
one level further down — sample where the data already lives).

Bit-equality contract (the PR-8 methodology extended to this tier): the
sample draw is the in-process ``UniformReplay.sample`` draw — the same
``jax.random.randint(key, (bs,), 0, max(size, 1))`` and the same
``ring_gather`` — so for the same capacity, insert stream, and keys a
hot-tier sample is BIT-EQUAL to ``UniformReplay`` (tested in
tests/test_tiers.py). Warm fan-in stays the distribution over the full
host ring; the hot tier is deliberately newest-only — that recency skew
is the tier policy, surfaced by ``hot_capacity``, not hidden.

The tier is lazy and allocation-free until the first append (storage
shapes/dtypes come from the first batch — lineage columns and staging
dtypes ride through with zero configuration) and the whole module is
dead code when ``replay.tiers`` is off.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from surreal_tpu.replay.base import RingState, init_ring, ring_gather, ring_insert


def default_gather_impl() -> str:
    """The hot tier's data-movement default: the PR-7 Pallas row-DMA
    kernel ON TPU (the point of a device-resident tier), plain XLA
    gather elsewhere — off-TPU the kernel only runs in interpret mode
    (a Python loop per draw), which is a correctness harness, not a
    sample path. ``ring_gather``'s bit-equality contract makes the
    routing invisible to the training record; ``tiers.hot.gather_impl``
    overrides it either way."""
    from surreal_tpu.ops import pallas_interpret

    return "xla" if pallas_interpret() else "pallas"


@partial(jax.jit, static_argnames=("capacity",), donate_argnums=(0,))
def _hot_insert(state: RingState, batch, capacity: int) -> RingState:
    # the ring state is loop-carried and nothing else aliases it between
    # appends (samples dispatched earlier on the same stream complete
    # first), so the capacity-sized buffers are donated instead of
    # double-buffered every append
    return ring_insert(state, batch, capacity)


@partial(jax.jit, static_argnames=("bs", "impl"), donate_argnums=())
def _hot_sample(state: RingState, key, bs: int, impl: str):
    # donate nothing: the state must survive for subsequent samples and
    # the next append — exactly UniformReplay.sample's draw + gather, the
    # bit-equality anchor
    idx = jax.random.randint(key, (bs,), 0, jnp.maximum(state.size, 1))
    return ring_gather(state, idx, impl=impl)


class HotTier:
    """Fixed-capacity device ring of the newest transitions.

    ``gather_impl`` routes the sample's data movement exactly like
    ``UniformReplay.gather_impl`` (None resolves via
    ``default_gather_impl``: the scalar-prefetch row-DMA kernel on TPU,
    XLA gather elsewhere — bit-equal either way, see ring_gather).
    """

    def __init__(
        self,
        capacity: int,
        batch_size: int,
        gather_impl: str | None = None,
        min_fill: int | None = None,
        example: Mapping[str, Any] | None = None,
    ):
        if gather_impl is None:
            gather_impl = default_gather_impl()
        if gather_impl not in ("xla", "pallas"):
            raise ValueError(
                f"hot tier gather_impl {gather_impl!r} not in xla|pallas"
            )
        self.capacity = int(capacity)
        self.batch_size = int(batch_size)
        if self.capacity < self.batch_size:
            raise ValueError(
                f"tiers.hot_capacity={capacity} is smaller than "
                f"batch_size={batch_size}"
            )
        self.gather_impl = gather_impl
        # minimum fill before the tier claims a hit (defaults to a full
        # batch: sampling a near-empty ring would oversample the first
        # few transitions far beyond the warm tier's recency skew)
        self.min_fill = int(min_fill) if min_fill is not None else self.batch_size
        self._state: RingState | None = None
        if example is not None:
            # eager allocation in the caller's staging dtypes (e.g. the
            # warm tier's bf16 obs example): ring_insert casts appended
            # f32 rollouts, so a hot sample is dtype-identical to a warm
            # fan-in batch
            self._state = init_ring(dict(example), self.capacity)
        self.size = 0       # host mirror of state.size (no device sync)
        self.appended = 0   # total rows ever appended
        # append donates the ring state while sample reads it; under the
        # overlapped host loop those run on different threads. The lock
        # makes "dispatch sample on current state" and "donate-and-swap
        # state" atomic — without it the sampler can grab the Array
        # object the appender just donated (deleted at the Python
        # level). Dispatched work is ordered by the device stream, so
        # holding the lock only for DISPATCH is enough.
        self._lock = threading.Lock()

    def append(self, rows: Mapping[str, Any]) -> None:
        """Insert one [n, ...] flat batch of (ideally device-resident)
        arrays. First append allocates the storage from the batch's own
        shapes/dtypes."""
        n = int(jax.tree.leaves(rows)[0].shape[0])
        with self._lock:
            if self._state is None:
                example = {k: v[0] for k, v in rows.items()}
                self._state = init_ring(example, self.capacity)
            self._state = _hot_insert(
                self._state, dict(rows), capacity=self.capacity
            )
            self.size = min(self.size + n, self.capacity)
            self.appended += n

    def ready(self) -> bool:
        return self._state is not None and self.size >= max(
            self.min_fill, self.batch_size
        )

    def sample(self, key) -> dict[str, jax.Array]:
        """One uniform batch, dispatched async — call at request time so
        the draw+gather overlaps the learner; the result is a dict of
        device arrays in flat field order."""
        with self._lock:
            if self._state is None:
                raise RuntimeError("hot tier sampled before first append")
            return _hot_sample(
                self._state, key, bs=self.batch_size, impl=self.gather_impl
            )

    def gauges(self) -> dict[str, float]:
        return {
            "tier/hot_size": float(self.size),
            "tier/hot_fill": float(self.size) / float(self.capacity),
        }
