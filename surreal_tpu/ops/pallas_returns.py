"""Reverse linear recurrence + discounted returns as Pallas TPU kernels —
the third member of the hot-kernel suite (ISSUE 7 tentpole, piece 2).

``x_t = deltas_t + coeffs_t * x_{t+1}`` is THE recurrence of return
estimation (ops/returns.py docstring): GAE, V-trace, and discounted
returns are all instances. This module provides the GENERIC solver as a
kernel — one HBM->VMEM load per 128-lane batch stripe, the whole
recurrence on-chip — plus the discounted-returns drop-in built on it.

Dtype contract: float32 in/out regardless of input dtype (the
recurrence accumulates T terms).

Runs in interpret mode off-TPU (``interpret=True``), which is how the
CPU test suite bit-validates both entry points against their XLA
references (tests/test_precision.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128  # VPU lane width; batch stripes tile to this


def _rev_scan_kernel(coeff_ref, delta_ref, init_ref, out_ref, *, T: int):
    def body(i, acc):
        t = T - 1 - i
        acc = delta_ref[pl.ds(t, 1), :] + coeff_ref[pl.ds(t, 1), :] * acc
        out_ref[pl.ds(t, 1), :] = acc
        return acc

    lax.fori_loop(0, T, body, init_ref[pl.ds(0, 1), :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def reverse_linear_scan_pallas(
    coeffs: jax.Array,
    deltas: jax.Array,
    init: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Solve ``x_t = deltas_t + coeffs_t * x_{t+1}`` over [T, B] arrays
    with ``x_T = init`` ([B], default zeros) — the fused twin of
    ``ops.returns.reverse_linear_scan_assoc`` (which restructures the
    recurrence instead; this kernel keeps it sequential but VMEM-resident
    with zero intermediate HBM traffic)."""
    T, B = deltas.shape
    f32 = lambda x: x.astype(jnp.float32)
    if init is None:
        init = jnp.zeros((B,), jnp.float32)
    arrs = [f32(coeffs), f32(deltas), f32(init)[None, :]]
    pad = (-B) % _LANES
    if pad:
        arrs = [jnp.pad(x, ((0, 0), (0, pad))) for x in arrs]
    Bp = B + pad

    stripe = lambda j: (0, j)  # block index along the batch grid
    out = pl.pallas_call(
        functools.partial(_rev_scan_kernel, T=T),
        grid=(Bp // _LANES,),
        in_specs=[
            pl.BlockSpec((T, _LANES), stripe),
            pl.BlockSpec((T, _LANES), stripe),
            pl.BlockSpec((1, _LANES), stripe),
        ],
        out_specs=pl.BlockSpec((T, _LANES), stripe),
        out_shape=jax.ShapeDtypeStruct((T, Bp), jnp.float32),
        interpret=interpret,
    )(*arrs)
    return out[:, :B]


@functools.partial(jax.jit, static_argnames=("interpret",))
def discounted_returns_pallas(
    rewards: jax.Array,
    discounts: jax.Array,
    bootstrap_value: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for :func:`ops.returns.discounted_returns` (Monte-Carlo
    returns with bootstrap; rewards/discounts [T, B], bootstrap [B]) as
    one fused Pallas pass: the recurrence is ``ret_t = r_t + d_t *
    ret_{t+1}`` with ``ret_T = bootstrap`` — exactly the generic solver
    seeded with the bootstrap carry."""
    return reverse_linear_scan_pallas(
        discounts, rewards, init=bootstrap_value, interpret=interpret
    )
