"""Precision policy — ONE knob governing every dtype decision in the
training pipeline (ISSUE 7 tentpole, piece 1).

Before this module the repo's dtype story was a scattered pair of model
knobs (``model.dtype`` / ``model.compute_dtype``) that only the encoders
honored: batch staging, replay storage, and the SGD minibatch arrays all
stayed float32 regardless, and nothing guarded a low-precision run
against silent gradient overflow. ``algo.precision`` replaces that with a
named policy threaded through every learner (ppo/ddpg/impala), the
models, the fused trainer programs, and the replay staging path — no
per-driver forks.

Policies (params and optimizer state are float32 under ALL of them — the
Accelerated-Methods (arXiv:1803.02811) mixed-precision discipline):

- ``'f32'``   — compute float32, staging float32. The numerics baseline
  every equivalence test compares against.
- ``'mixed'`` — compute bfloat16, staging float32 (the pre-ISSUE-7
  default, kept as THE default so existing configs and checkpoints
  reproduce bit-for-bit: no loss-scale state enters the optimizer
  pytree).
- ``'bf16'``  — compute bfloat16 AND staging bfloat16 (trajectory obs,
  SGD minibatch arrays, replay obs storage move half the bytes), with
  dynamic loss scaling on by default.
- ``'bf16_fp8'`` — 'bf16' plus the experimental fp8 matmul path: Dense
  matmuls quantize both operands to float8_e4m3fn (per-tensor dynamic
  scale) before the dot. Behind this knob only.

Dynamic loss scaling (:func:`dynamic_loss_scaling`) wraps the whole
optimizer chain so an overflow SKIPS the step entirely (Adam moments
untouched, not fed zeros): the loss is multiplied by a power-of-two scale
before differentiation (learners read it via
:func:`current_loss_scale`), the wrapper unscales the incoming grads,
and a nonfinite gradient norm zeroes the update while backing the scale
off. Power-of-two scales make the scale/unscale round trip EXACT (pure
exponent shifts), so enabling loss scaling never perturbs healthy steps.
The :class:`LossScaleState` rides the optimizer pytree next to PR-5's
``recovery_scale`` leaf, which means a precision-induced divergence that
slips past the skip logic (NaN params, not NaN grads) is still caught by
the existing divergence guard and rolled back — loss scaling is the
first fence, recovery the second.

Checkpoint safety: the active policy (and whether loss-scale state is in
the pytree) is recorded in checkpoint run metadata and validated on
restore (session/checkpoint.py) — a policy mismatch is a clear error,
not a cryptic orbax structure mismatch.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

POLICY_NAMES = ("f32", "mixed", "bf16", "bf16_fp8")

# the f8 format's finite max (e4m3fn): per-tensor dynamic scaling maps
# each operand's absolute max onto it before quantization
_F8_MAX = 448.0


class PrecisionPolicy(NamedTuple):
    """Resolved, static precision decisions for one learner build.

    All fields are python scalars/strings — the policy is config, never
    traced; it selects programs, it does not ride them.
    """

    name: str            # 'f32' | 'mixed' | 'bf16' | 'bf16_fp8'
    param_dtype: str     # always 'float32' (optimizer state follows)
    compute_dtype: str   # model activations / matmul dtype
    data_dtype: str      # trajectory staging / SGD minibatch / replay obs
    fp8: bool            # experimental fp8 matmul path in Dense layers
    loss_scaling: bool   # dynamic loss scaling wraps the optimizer chain
    # loss-scale schedule (powers of two keep scaling numerically exact)
    ls_init: float = 2.0**15
    ls_growth_interval: int = 2000
    ls_growth_factor: float = 2.0
    ls_backoff_factor: float = 0.5
    ls_min: float = 1.0
    ls_max: float = 2.0**24

    # -- model wiring --------------------------------------------------------
    def model_config(self, model_cfg) -> dict:
        """Materialize a ``learner_config.model`` subtree into the concrete
        dict the flax model constructors consume: ``'auto'`` dtypes resolve
        from the policy, explicit values win (the pre-ISSUE-7 spelling
        stays honored), and the fp8 flag rides along for the encoders."""
        cfg = dict(model_cfg.to_dict() if hasattr(model_cfg, "to_dict") else model_cfg)
        if cfg.get("dtype", "auto") in (None, "auto"):
            cfg["dtype"] = self.param_dtype
        if cfg.get("compute_dtype", "auto") in (None, "auto"):
            cfg["compute_dtype"] = self.compute_dtype
        cfg["fp8"] = self.fp8
        return cfg

    # -- staging wiring ------------------------------------------------------
    def cast_stage(self, tree: Any, keys: tuple[str, ...] = ("obs", "next_obs")):
        """Cast the named float leaves of a batch dict to the staging
        dtype (no-op under f32/mixed). Only ever applied to tensors the
        models re-cast to ``compute_dtype`` anyway (obs-class arrays), so
        under bf16 the cast happens once at staging instead of once per
        minibatch read — the bytes win — at the SAME rounding point.
        Non-float leaves (uint8 pixels, bools) pass through untouched."""
        dd = jnp.dtype(self.data_dtype)
        if dd == jnp.float32:
            return tree
        out = dict(tree)
        for k in keys:
            v = out.get(k)
            if v is not None and jnp.issubdtype(v.dtype, jnp.floating):
                out[k] = v.astype(dd)
        return out

    # -- bookkeeping ---------------------------------------------------------
    def meta(self) -> dict:
        """What checkpoint restore must agree on: the pieces that change
        the checkpointed arrays, the optimizer pytree, or the trained
        numerics (param_dtype included — an explicit ``model.dtype``
        override changes the saved arrays themselves)."""
        return {
            "policy": self.name,
            "param_dtype": self.param_dtype,
            "compute_dtype": self.compute_dtype,
            "data_dtype": self.data_dtype,
            "loss_scaling": self.loss_scaling,
            "fp8": self.fp8,
        }

    def telemetry(self) -> dict:
        return self.meta()


def resolve_policy(learner_config) -> PrecisionPolicy:
    """Resolve the active :class:`PrecisionPolicy` from a learner config
    tree — the one constructor every learner calls at build.

    ``algo.precision`` names the policy; explicit ``model.dtype`` /
    ``model.compute_dtype`` values (anything other than ``'auto'``)
    override the derived dtypes for back-compat;
    ``optimizer.loss_scaling.enabled`` overrides the policy's loss-scale
    default ('auto' = on for bf16/bf16_fp8, off for f32/mixed)."""
    algo = learner_config.get("algo", None)
    name = (algo.get("precision", "mixed") if algo is not None else "mixed") or "mixed"
    if name not in POLICY_NAMES:
        raise ValueError(
            f"algo.precision {name!r} not in {'|'.join(POLICY_NAMES)}"
        )
    compute = "float32" if name == "f32" else "bfloat16"
    data = "bfloat16" if name in ("bf16", "bf16_fp8") else "float32"
    param = "float32"
    ls_default = name in ("bf16", "bf16_fp8")

    model = learner_config.get("model", None)
    if model is not None:
        explicit_c = model.get("compute_dtype", "auto")
        if explicit_c not in (None, "auto"):
            compute = str(explicit_c)
        # an explicit param dtype must reach the POLICY too, not only the
        # built model: params shape the checkpoint arrays, so the policy
        # meta the restore guard compares has to carry it — otherwise a
        # bf16-params session restored without the override dies in orbax
        # with exactly the cryptic mismatch this layer exists to name
        explicit_p = model.get("dtype", "auto")
        if explicit_p not in (None, "auto"):
            param = str(explicit_p)

    ls = None
    opt = learner_config.get("optimizer", None)
    if opt is not None:
        ls = opt.get("loss_scaling", None)
    enabled = ls.get("enabled", "auto") if ls is not None else "auto"
    loss_scaling = ls_default if enabled in (None, "auto") else bool(enabled)

    kwargs = {}
    if ls is not None:
        for cfg_key, field in (
            ("init", "ls_init"),
            ("growth_interval", "ls_growth_interval"),
            ("growth_factor", "ls_growth_factor"),
            ("backoff_factor", "ls_backoff_factor"),
            ("min", "ls_min"),
            ("max", "ls_max"),
        ):
            v = ls.get(cfg_key, None)
            if v is not None:
                kwargs[field] = type(PrecisionPolicy._field_defaults[field])(v)
    return PrecisionPolicy(
        name=name,
        param_dtype=param,
        compute_dtype=compute,
        data_dtype=data,
        fp8=(name == "bf16_fp8"),
        loss_scaling=loss_scaling,
        **kwargs,
    )


# -- dynamic loss scaling ----------------------------------------------------


class LossScaleState(NamedTuple):
    """State of :func:`dynamic_loss_scaling`: the live scale, the
    consecutive-finite-step counter driving growth, a cumulative overflow
    counter (telemetry), and the wrapped chain's own state."""

    scale: jax.Array       # f32 scalar, current loss scale (power of two)
    good_steps: jax.Array  # i32, finite steps since the last scale change
    overflows: jax.Array   # i32, cumulative skipped steps (telemetry)
    inner: Any             # wrapped optimizer chain's state


def dynamic_loss_scaling(
    inner: optax.GradientTransformation,
    policy: PrecisionPolicy,
) -> optax.GradientTransformation:
    """Wrap an optimizer chain with dynamic loss scaling.

    Contract with the learners: the loss passed to ``jax.grad`` is
    multiplied by :func:`current_loss_scale` (read from the CARRIED
    opt_state, so it is a traced input — scale changes never recompile),
    and this wrapper divides the incoming gradients back down. On a
    finite gradient norm the inner chain runs normally and the scale
    doubles after ``ls_growth_interval`` consecutive finite steps; on a
    nonfinite norm the ENTIRE step is skipped via ``lax.cond`` — inner
    state (Adam moments, recovery scale) untouched, update zero — and
    the scale backs off by ``ls_backoff_factor`` (floored at ``ls_min``).
    All factors are powers of two, so scaling is exact on healthy steps.
    """
    gi = jnp.int32(max(1, int(policy.ls_growth_interval)))
    growth = jnp.float32(policy.ls_growth_factor)
    backoff = jnp.float32(policy.ls_backoff_factor)
    lo = jnp.float32(policy.ls_min)
    hi = jnp.float32(policy.ls_max)

    def init_fn(params):
        return LossScaleState(
            scale=jnp.float32(policy.ls_init),
            good_steps=jnp.zeros((), jnp.int32),
            overflows=jnp.zeros((), jnp.int32),
            inner=inner.init(params),
        )

    def update_fn(scaled_grads, state: LossScaleState, params=None):
        grads = jax.tree.map(lambda g: g / state.scale, scaled_grads)
        # global_norm is nonfinite iff any element is (inf/nan propagate
        # through the sum of squares) — one reduction covers the tree
        finite = jnp.isfinite(optax.global_norm(grads))

        def ok(_):
            updates, inner_state = inner.update(grads, state.inner, params)
            good = state.good_steps + 1
            grow = good >= gi
            scale = jnp.where(grow, jnp.minimum(state.scale * growth, hi), state.scale)
            return updates, LossScaleState(
                scale=scale,
                good_steps=jnp.where(grow, 0, good),
                overflows=state.overflows,
                inner=inner_state,
            )

        def skip(_):
            # a true skip: zero update AND untouched inner state — feeding
            # zeros through Adam would still decay its moments
            return jax.tree.map(jnp.zeros_like, grads), LossScaleState(
                scale=jnp.maximum(state.scale * backoff, lo),
                good_steps=jnp.zeros((), jnp.int32),
                overflows=state.overflows + 1,
                inner=state.inner,
            )

        return jax.lax.cond(finite, ok, skip, None)

    return optax.GradientTransformation(init_fn, update_fn)


def _find_ls_states(tree: Any) -> list[LossScaleState]:
    found: list[LossScaleState] = []
    is_leaf = lambda n: isinstance(n, LossScaleState)  # noqa: E731

    def visit(n):
        if is_leaf(n):
            found.append(n)
        return n

    jax.tree.map(visit, tree, is_leaf=is_leaf)
    return found


def current_loss_scale(opt_state: Any) -> jax.Array:
    """The traced loss-scale scalar to multiply the loss by — 1.0 when the
    chain carries no :class:`LossScaleState` (f32/mixed policies), so
    every learner's loss math is policy-oblivious. First leaf wins (DDPG
    reads each chain's own state separately)."""
    found = _find_ls_states(opt_state)
    return found[0].scale if found else jnp.float32(1.0)


def loss_scale_metrics(opt_state: Any) -> dict:
    """Device-scalar telemetry of the loss-scale state (rides the metrics
    dict at the existing cadence — zero extra syncs). Empty when the
    chain carries no scale (keys must not flicker across lax.cond
    branches, so presence is decided at trace time by the policy)."""
    found = _find_ls_states(opt_state)
    if not found:
        return {}
    return {
        "precision/loss_scale": found[0].scale,
        "precision/overflows": sum(
            (s.overflows for s in found[1:]), found[0].overflows
        ).astype(jnp.float32),
    }


# -- experimental fp8 matmul path -------------------------------------------


def _quantize_f8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor dynamic quantization to float8_e4m3fn: map the absolute
    max onto the format's finite range, quantize, return (q, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / _F8_MAX
    return (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn), scale


def fp8_dot_general(
    lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None
):
    """Drop-in ``dot_general`` for flax ``nn.Dense(dot_general=...)``:
    both operands quantize to float8_e4m3fn with per-tensor dynamic
    scales, the dot accumulates in float32, and the output is rescaled
    and returned in the lhs activation dtype.

    Portable-by-construction: the quantized operands are upcast to
    bfloat16 for the dot itself, so the SAME program runs on backends
    without native f8 matmul units (this CPU test image included) while
    carrying the full fp8 rounding the real MXU path would apply — the
    numerics of fp8, everywhere; the native-f8 dot is a backend swap
    behind this one function when hardware support lands.
    """
    del precision
    lq, ls = _quantize_f8(lhs)
    rq, rs = _quantize_f8(rhs)
    out = jax.lax.dot_general(
        lq.astype(jnp.bfloat16),
        rq.astype(jnp.bfloat16),
        dimension_numbers,
        preferred_element_type=preferred_element_type or jnp.float32,
    )
    return (out * (ls * rs)).astype(lhs.dtype)
