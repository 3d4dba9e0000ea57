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
    device so the host ships compact bytes over DCN.
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
        for ch, k, s in zip(self.channels, self.kernels, self.strides):
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
