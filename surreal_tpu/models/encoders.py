"""Shared network stems (parity: reference ``surreal/model/model_builders.py``
MLP/CNN builders, SURVEY.md §2.1), as flax modules.

TPU notes: parameters are kept in ``param_dtype`` (float32) while
activations run in ``compute_dtype`` (bfloat16 by default) so matmuls hit
the MXU at full rate; heads cast back to float32 before anything
numerically delicate (log-probs, losses).
"""

from __future__ import annotations

import math
from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "tanh": nn.tanh,
    "relu": nn.relu,
    "elu": nn.elu,
    "gelu": nn.gelu,
    "silu": nn.silu,
}


def orthogonal_init(scale: float = math.sqrt(2.0)):
    # math.sqrt, NOT jnp.sqrt: a default-arg expression is evaluated at import
    # time, and any jnp computation would initialise the JAX backend — taking
    # the chip — before callers can select a platform.
    return nn.initializers.orthogonal(scale)


def _dense_dot_general(use_fp8: bool):
    """The ``nn.Dense(dot_general=...)`` hook for the experimental fp8
    matmul path (ops/precision.py::fp8_dot_general): quantize-to-f8 both
    operands under the 'bf16_fp8' policy, flax's default otherwise."""
    if not use_fp8:
        return None
    from surreal_tpu.ops.precision import fp8_dot_general

    return fp8_dot_general


class MLP(nn.Module):
    """Plain MLP trunk with orthogonal init (standard for PPO-family)."""

    hidden: Sequence[int]
    activation: str = "tanh"
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    use_layer_norm: bool = False
    use_fp8: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = ACTIVATIONS[self.activation]
        x = x.astype(self.compute_dtype)
        for width in self.hidden:
            x = nn.Dense(
                width,
                kernel_init=orthogonal_init(),
                dtype=self.compute_dtype,
                param_dtype=self.param_dtype,
                dot_general=_dense_dot_general(self.use_fp8),
            )(x)
            if self.use_layer_norm:
                # reference shipped a LayerNorm module used in DDPG nets
                # (surreal/model/layer_norm.py)
                x = nn.LayerNorm(dtype=self.compute_dtype, param_dtype=self.param_dtype)(x)
            x = act(x)
        return x


class NatureCNN(nn.Module):
    """Nature-DQN conv stem for pixel observations (parity: the reference's
    shared conv encoder for frame-stacked 84x84 pixels).

    Input: [..., H, W, C] uint8 or float. uint8 is scaled to [0, 1] on
    device so the host ships compact bytes over DCN. A rank-5 input, a
    rollout's [T, B, H, W, C], keeps both leading axes through the
    convolutions (``_FramesConv``); the parameters are the same.
    """

    channels: Sequence[int] = (32, 64, 64)
    kernels: Sequence[int] = (8, 4, 3)
    strides: Sequence[int] = (4, 2, 1)
    dense: int = 512
    activation: str = "relu"
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    use_fp8: bool = False  # fp8 applies to the Dense matmul only: conv
                           # uses conv_general_dilated, which has no
                           # dot_general hook on this flax pin

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = ACTIVATIONS[self.activation]
        if x.dtype == jnp.uint8:
            x = x.astype(self.compute_dtype) / 255.0
        else:
            x = x.astype(self.compute_dtype)
        for i, (ch, k, s) in enumerate(
            zip(self.channels, self.kernels, self.strides)
        ):
            if x.ndim == 5:
                # a rollout's [T, B, H, W, C] is convolved where it lies:
                # nn.Conv would flatten T and B into one batch, and they
                # are not neighbours in the rollout's buffer
                x = _FramesConv(
                    ch, k, s, self.compute_dtype, self.param_dtype,
                    name=f"Conv_{i}",
                )(x)
            else:
                x = nn.Conv(
                    ch,
                    kernel_size=(k, k),
                    strides=(s, s),
                    padding="VALID",
                    kernel_init=orthogonal_init(),
                    dtype=self.compute_dtype,
                    param_dtype=self.param_dtype,
                )(x)
            x = act(x)
        x = x.reshape(*x.shape[:-3], -1)
        x = nn.Dense(
            self.dense,
            kernel_init=orthogonal_init(),
            dtype=self.compute_dtype,
            param_dtype=self.param_dtype,
            dot_general=_dense_dot_general(self.use_fp8),
        )(x)
        return act(x)


def concrete_dtype(value, fallback: str) -> jnp.dtype:
    """Resolve a model-config dtype knob to a concrete ``jnp.dtype``.
    Learners materialize 'auto' through the precision policy
    (ops/precision.py) before model build; this fallback covers direct
    model construction from raw config trees (tests, tooling) so 'auto'
    never reaches ``jnp.dtype``."""
    return jnp.dtype(fallback if value in (None, "auto") else value)


def cnn_from_config(
    cnn_cfg, compute_dtype, param_dtype, name=None, use_fp8: bool = False
) -> NatureCNN:
    """The one NatureCNN-from-``model.cnn``-subtree constructor — shared
    by the memoryless trunk and the trajectory encoder's per-frame stem,
    so a new cnn config key cannot be honored by one and dropped by the
    other."""
    return NatureCNN(
        channels=tuple(cnn_cfg["channels"]),
        kernels=tuple(cnn_cfg["kernels"]),
        strides=tuple(cnn_cfg["strides"]),
        dense=cnn_cfg["dense"],
        compute_dtype=compute_dtype,
        param_dtype=param_dtype,
        use_fp8=use_fp8,
        name=name,
    )


def make_trunk(model_cfg, hidden: Sequence[int]) -> nn.Module:
    """Build the obs trunk from a ``learner_config.model`` subtree: CNN stem
    for pixel obs, MLP otherwise.

    Item-style access throughout: flax module attributes holding Mappings
    are converted to FrozenDict, which has no attribute access.
    """
    compute_dtype = concrete_dtype(model_cfg["compute_dtype"], "bfloat16")
    param_dtype = concrete_dtype(model_cfg["dtype"], "float32")
    use_fp8 = bool(model_cfg.get("fp8", False))
    cnn = model_cfg["cnn"]
    if cnn["enabled"]:
        return cnn_from_config(cnn, compute_dtype, param_dtype, use_fp8=use_fp8)
    return MLP(
        hidden=tuple(hidden),
        activation=model_cfg["activation"],
        compute_dtype=compute_dtype,
        param_dtype=param_dtype,
        use_fp8=use_fp8,
    )


class _FramesConv(nn.Module):
    """One of ``NatureCNN``'s convolutions over ``[A, B, H, W, C]`` with the
    leading axes left apart: ``B`` is the batch and ``A`` a spatial axis of
    kernel 1 and stride 1, which adds no term to any sum. Flattened to one
    batch of ``A * B`` (what ``nn.Conv`` does) the frames of a rollout,
    whose envs lie on the lanes with ``H, W, C`` between them and the steps,
    are first written out in the compute dtype and transposed: 10.0 of
    ``impala_pong_1k32``'s 68.3 ms (PERF.md section 6, PR 45). Here XLA
    casts and scales them inside the convolution's read of the uint8
    buffer, and the activations keep the buffer's order.

    Declares ``nn.Conv``'s parameters (``kernel`` ``[k, k, in, out]``,
    ``bias`` ``[out]``, its initialisers) and is named ``Conv_<i>`` by its
    caller, so a model's tree is the same whichever rank initialised it.
    """

    features: int
    kernel: int
    stride: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        k = self.kernel
        kernel = self.param(
            "kernel", orthogonal_init(), (k, k, x.shape[-1], self.features),
            self.param_dtype,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            self.param_dtype,
        )
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = jax.lax.conv_general_dilated(
            x,
            kernel[None],
            window_strides=(1, self.stride, self.stride),
            padding="VALID",
            dimension_numbers=jax.lax.ConvDimensionNumbers(
                lhs_spec=(1, 4, 0, 2, 3),
                rhs_spec=(4, 3, 0, 1, 2),
                out_spec=(1, 4, 0, 2, 3),
            ),
        )
        return y + bias
