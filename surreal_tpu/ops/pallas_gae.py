"""Fused GAE as a Pallas TPU kernel — the one kernel candidate SURVEY.md
§2.3 flagged ("custom Pallas kernels only where XLA underperforms; none
expected for MLP/CNN PPO; candidate: fused GAE scan").

The kernel fuses delta computation, the reverse lambda-scan, and target
computation into a single VMEM-resident pass per batch stripe: inputs are
loaded HBM->VMEM once, the whole recurrence runs on-chip, and both outputs
are produced without intermediate HBM round trips. The grid tiles the
batch dim into 128-lane stripes (the VPU lane width); time stays whole in
VMEM (T x 128 x f32 x 7 arrays ~ 0.35 MB per stripe at T=256 — far under
the ~16 MB VMEM budget).

Two entry points share one kernel:

- :func:`gae_advantages_pallas` — the simple contract (one discount array,
  ``values`` as a [T+1] stack), drop-in for ``ops.returns.gae_advantages``.
- :func:`gae_advantages_pallas_masked` — the truncation-exact two-mask
  form the PPO learner uses (bootstrap discount ``gamma*(1-terminated)``
  for the TD delta, accumulation decay ``gamma*lam*(1-done)``, per-step
  ``v_next`` from the pre-reset terminal obs). Selected by
  ``learner_config.algo.gae_impl = 'pallas'``.

Dtype contract: inputs are cast to float32 and both outputs are float32,
regardless of input dtype — the lambda-recurrence accumulates T terms and
needs f32 precision (bf16 accumulation drifts); this matches what the XLA
path computes in practice since rewards/masks arrive as f32. Callers that
want bf16 downstream cast the outputs.

Status vs XLA: not measured on the current code. On the TPU v5e the
kernel compiles, and at [T=256, B=4096] its outputs are bit-equal to
``ops.returns.gae_advantages`` (chip_smoke.py, PR 23). XLA fuses the scan
itself, so the kernel is an ALTERNATIVE, selectable per config rather
than the default. Runs in interpret mode off-TPU so tests cover it
everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128  # VPU lane width; batch stripes tile to this


def _gae_kernel(r_ref, boot_ref, decay_ref, vt_ref, vn_ref, adv_ref, tgt_ref, *, T: int):
    def body(i, acc):
        t = T - 1 - i
        r = r_ref[pl.ds(t, 1), :]        # [1, LANES]
        boot = boot_ref[pl.ds(t, 1), :]
        decay = decay_ref[pl.ds(t, 1), :]
        v_t = vt_ref[pl.ds(t, 1), :]
        v_n = vn_ref[pl.ds(t, 1), :]
        delta = r + boot * v_n - v_t
        acc = delta + decay * acc
        adv_ref[pl.ds(t, 1), :] = acc
        tgt_ref[pl.ds(t, 1), :] = acc + v_t
        return acc

    jax.lax.fori_loop(0, T, body, jnp.zeros((1, _LANES), jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gae_advantages_pallas_masked(
    rewards: jax.Array,
    boot_disc: jax.Array,
    decay: jax.Array,
    values_t: jax.Array,
    values_next: jax.Array,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Truncation-exact GAE, all inputs [T, B] (see module docstring).

    ``interpret=True`` runs the kernel in the Pallas interpreter — exact
    same program, no TPU required (how the CPU test suite covers it).
    """
    T, B = rewards.shape
    f32 = lambda x: x.astype(jnp.float32)
    arrs = [f32(rewards), f32(boot_disc), f32(decay), f32(values_t), f32(values_next)]
    pad = (-B) % _LANES
    if pad:
        arrs = [jnp.pad(x, ((0, 0), (0, pad))) for x in arrs]
    Bp = B + pad

    kernel = functools.partial(_gae_kernel, T=T)
    stripe = lambda j: (0, j)  # block index along the batch grid
    adv, tgt = pl.pallas_call(
        kernel,
        grid=(Bp // _LANES,),
        in_specs=[pl.BlockSpec((T, _LANES), stripe)] * 5,
        out_specs=[
            pl.BlockSpec((T, _LANES), stripe),
            pl.BlockSpec((T, _LANES), stripe),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp), jnp.float32),
            jax.ShapeDtypeStruct((T, Bp), jnp.float32),
        ],
        interpret=interpret,
    )(*arrs)
    return adv[:, :B], tgt[:, :B]


@functools.partial(jax.jit, static_argnames=("lam", "interpret"))
def gae_advantages_pallas(
    rewards: jax.Array,
    discounts: jax.Array,
    values: jax.Array,
    lam: float,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Drop-in for :func:`ops.returns.gae_advantages` (same contract:
    rewards/discounts [T, B], values [T+1, B]; f32 outputs per the module
    dtype contract) as one fused Pallas pass."""
    return gae_advantages_pallas_masked(
        rewards,
        discounts,
        discounts * lam,
        values[:-1],
        values[1:],
        interpret=interpret,
    )
