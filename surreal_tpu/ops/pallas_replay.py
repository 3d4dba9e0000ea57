"""Replay gather/scatter as scalar-prefetch Pallas TPU kernels — the
fused data-movement members of the hot-kernel suite (ISSUE 7 tentpole,
piece 2), extending PR 4's ``sample_many`` batched gather.

``sample_many`` already collapsed the off-policy update loop's K
sequential full-buffer gathers into one batched XLA gather; these
kernels go one level lower: the index vector rides the grid as a
SCALAR-PREFETCH operand, so each sampled row is a single HBM->VMEM block
DMA addressed directly by ``idx[i]`` — no gather HLO, no index
materialization on the vector unit, and the scatter twin writes priority
refreshes back with the same addressing (``input_output_aliases`` keeps
it in-place). Selected per workload by ``algo.replay_gather='pallas'``.

Layout contract: kernels operate on [rows, 1, features] views — the
replay layer flattens each pytree leaf's trailing dims (and restores
them after). The singleton middle dim is what the TPU compiler needs: a
block's last two dims must be multiples of (8, 128) or equal the array's
own, and a ``(1, 1, F)`` block over ``[capacity, 1, F]`` is the latter
for every F (a ``(1, F)`` block over ``[capacity, F]`` is neither, and
Mosaic refuses it). Row contents are copied verbatim, so any float/int
leaf dtype works (the replay storage is float32/bfloat16 by
construction).

Known cost (PERF.md): XLA's own layout for a narrow ``[capacity, F]``
ring is not the row-major ``[capacity, 1, F]`` the kernel is handed, so
every call pays one relayout copy of the whole ring (two for the
scatter, which copies back).

Runs in interpret mode off-TPU (``interpret=True``), which is how the
CPU suite bit-validates both kernels against ``ring_gather`` /
``.at[idx].set`` (tests/test_precision.py); tests/test_tpu_compile.py
compiles both for a described v5e at the replay's real shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_row_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index maps, not the body
    out_ref[...] = src_ref[...]


def _scatter_row_kernel(idx_ref, dest_in_ref, upd_ref, dest_ref):
    del idx_ref, dest_in_ref  # index maps address the write; dest aliased
    dest_ref[...] = upd_ref[...]


def _rows(x: jax.Array) -> jax.Array:
    """[rows, ...] -> [rows, 1, features] (see the layout contract)."""
    return x.reshape(x.shape[0], 1, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_pallas(
    storage: jax.Array, idx: jax.Array, interpret: bool = False
) -> jax.Array:
    """``storage[idx]`` for a ``storage`` of [capacity, ...] and int
    ``idx`` ([n]): one row-block DMA per sampled index, addressed by the
    scalar-prefetched index vector. Bit-equal to ``storage[idx]``."""
    rows = _rows(storage)
    n, F = idx.shape[0], rows.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, F), lambda i, idx_ref: (idx_ref[i], 0, 0))
        ],
        out_specs=pl.BlockSpec((1, 1, F), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, F), storage.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), rows)
    return out.reshape(n, *storage.shape[1:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_rows_pallas(
    dest: jax.Array, idx: jax.Array, updates: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """``dest.at[idx].set(updates)`` for a ``dest`` of [capacity, ...]:
    one row-block DMA per index, written in grid order (duplicate
    indices resolve last-write-wins — the same contract ``.at[].set``
    documents as unspecified; the priority-refresh caller never issues
    duplicates in one batch). ``input_output_aliases`` makes the update
    in-place — the donation discipline of the fused iterations carries
    through the kernel."""
    rows = _rows(dest)
    n, F = idx.shape[0], rows.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # aliased dest (unread)
            pl.BlockSpec((1, 1, F), lambda i, idx_ref: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, F), lambda i, idx_ref: (idx_ref[i], 0, 0)
        ),
    )
    out = pl.pallas_call(
        _scatter_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, dest.dtype),
        # operand 1 (dest, after the scalar-prefetch idx) aliases output 0
        input_output_aliases={1: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), rows, _rows(updates).astype(dest.dtype))
    return out.reshape(dest.shape)
