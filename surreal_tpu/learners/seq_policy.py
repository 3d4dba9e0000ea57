"""Shared policy-head sampling + trajectory-policy acting, mixed into the
on-policy learners (PPO, IMPALA).

``PolicyHeadMixin`` owns the one place actions are sampled from head
outputs (diagonal-Gaussian or categorical — the reference duplicated this
across its agent classes). ``SequenceActingMixin`` owns the trajectory
policy's acting carry (SURVEY.md §5.7 long-context seam): segment-aligned
context so rollout-time conditioning is exactly what the learner
recomputes over whole segments (the importance-ratio contract), with two
interchangeable implementations selected by ``model.encoder.act_impl``:

- ``'kv'`` (default): incremental decode against per-layer K/V caches —
  O(T) attention per env step;
- ``'padded'``: re-encode the zero-padded segment and read one position —
  O(T^2) per step, the simple reference form the kv path is
  equivalence-tested against (tests/test_trajectory_policy.py).

Host classes provide: ``model`` (decode-capable when ``seq_policy``),
``config`` (algo.horizon, model.encoder), ``specs``, ``discrete``,
``seq_policy``, and ``_norm_obs``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from surreal_tpu.learners.base import EVAL_DETERMINISTIC, TRAINING
from surreal_tpu.models.attention import reset_recurrent
from surreal_tpu.ops import distributions as D


class PolicyHeadMixin:
    def _head_act(self, out, key: jax.Array, mode: str):
        """Sample/argmax + behavior info from head outputs (shared by the
        memoryless ``act`` and the sequence ``act_step``)."""
        if self.discrete:
            if mode == EVAL_DETERMINISTIC:
                action = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
            else:
                action = D.categorical_sample(key, out.logits).astype(jnp.int32)
            logp = D.categorical_logp(out.logits, action)
            info = {"logp": logp, "logits": out.logits, "value": out.value}
        else:
            if mode == EVAL_DETERMINISTIC:
                action = out.mean
            else:
                action = D.diag_gauss_sample(key, out.mean, out.log_std)
            logp = D.diag_gauss_logp(out.mean, out.log_std, action)
            info = {
                "logp": logp,
                "mean": out.mean,
                "log_std": out.log_std,
                "value": out.value,
            }
        return action, info


class SequenceActingMixin(PolicyHeadMixin):
    def rebind_mesh(self, mesh, sp_axis: str = "sp", batch_axis=None) -> None:
        """Route the trajectory encoder's attention through the ring over
        ``mesh[sp_axis]`` (ops/ring_attention.py) — params are unchanged
        (same module tree, different attention schedule), so this is safe
        after ``init``/restore. ``batch_axis`` additionally shards the
        batch dim of the ring over that mesh axis (dp x sp composed
        meshes). No-op for memoryless policies."""
        if self.seq_policy:
            self.model = build_seq_model(
                self.config.model, self.specs,
                self.config.algo.init_log_std, mesh=mesh, sp_axis=sp_axis,
                horizon=self.config.algo.horizon, batch_axis=batch_axis,
                policy=self.policy,
            )

    # -- sequence acting (model.encoder.kind='trajectory') -------------------
    def act_init(self, num_envs: int):
        """Segment context, reset at each rollout start so the policy's
        conditioning is exactly what the sequence learn recomputes (the
        importance-ratio contract). Carry form follows
        ``encoder.act_impl`` (see module docstring)."""
        if not self.seq_policy:
            return None
        enc = self.config.model.encoder
        T = int(self.config.algo.horizon)
        if enc.get("act_impl", "kv") == "padded":
            # pixels buffer as uint8 (the trajectory models keep uint8
            # raw into the CNN stem's /255); vector obs buffer in f32
            import numpy as np

            buf_dtype = (
                jnp.uint8
                if self.specs.obs.dtype == np.uint8
                else jnp.float32
            )
            return {
                "buf": jnp.zeros(
                    (num_envs, T, *self.specs.obs.shape), buf_dtype
                ),
                "pos": jnp.zeros((), jnp.int32),
            }
        # the cache's form is the model's own (models/attention.py
        # acting_cache): full keys and values for the 'preln' blocks, the
        # latent rows alone for 'mla_moe', state + ring + shared cache
        # for 'ssm_hybrid', full caches + rings for 'swa_moe', matrix
        # states + conv tails + latent rows for 'kda_moe'
        return {
            "cache": self.model.init_cache(num_envs, T),
            "pos": jnp.zeros((), jnp.int32),
        }

    def act_step(self, state, act_carry, obs, key, mode=TRAINING):
        """Sequence acting. Default ('kv'): incremental decode against
        per-layer K/V caches — O(T) attention per step. 'padded' re-runs
        the full zero-padded segment and reads one position — O(T^2) per
        step, kept as the simple reference form the kv path is
        equivalence-tested against; both reproduce the sequence learn's
        per-position conditioning (the importance-ratio contract)."""
        if not self.seq_policy:
            return super().act_step(state, act_carry, obs, key, mode)
        if "cache" in act_carry:
            # incremental decode: one position through the trunk against
            # the caches; positions > pos in a position-indexed cache are
            # masked, so the wrap reset only needs the index (stale K/V
            # rows are overwritten as the new segment advances). A
            # recurrent state has no position: the horizon is the
            # config's, not a leaf's shape, and the leaves a model marks
            # recurrent are zeroed at the wrap (those alone: a `where`
            # over the whole carry would re-write all of it every step)
            cache, pos = act_carry["cache"], act_carry["pos"]
            wrap = pos >= int(self.config.algo.horizon)
            pos = jnp.where(wrap, 0, pos)
            cache = reset_recurrent(self.model.encoder_cfg, cache, wrap)
            out_t, cache = self.model.apply(
                state.params,
                self._norm_obs(state.obs_stats, obs),
                cache=cache, pos=pos,
            )
            action, info = self._head_act(out_t, key, mode)
            return action, info, {"cache": cache, "pos": pos + 1}
        buf, pos = act_carry["buf"], act_carry["pos"]
        T = buf.shape[1]
        # long eval episodes outrun one segment: re-segment (fresh
        # context), matching how training segments the stream
        wrap = pos >= T
        buf = jnp.where(wrap, jnp.zeros_like(buf), buf)
        pos = jnp.where(wrap, 0, pos)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, obs.astype(buf.dtype)[:, None], pos, axis=1
        )
        # causal attention: position `pos` sees only the 0..pos prefix —
        # the zero padding at future positions is unread by construction.
        # replicate_ok: this is an ACTING batch (eval episodes / video) of
        # arbitrary width — on a dp x sp mesh an indivisible width falls
        # back to replication here, while the learn pass keeps the
        # divisibility assert (models/attention.py)
        out = self.model.apply(
            state.params, self._norm_obs(state.obs_stats, buf),
            replicate_ok=True,
        )
        at = lambda x: jax.lax.dynamic_index_in_dim(x, pos, axis=1, keepdims=False)
        out_t = jax.tree.map(at, out)
        action, info = self._head_act(out_t, key, mode)
        return action, info, {"buf": buf, "pos": pos + 1}

    def act_rows(self, act_carry) -> dict:
        """What a routed family's acting steps tallied in the carry's cache
        (``ops/moe.py::acting_rows``); nothing from a cache without the
        tally."""
        from surreal_tpu.ops import moe

        cache = act_carry.get("cache") if self.seq_policy else None
        if isinstance(cache, dict) and moe.EXPERTS_READ in cache:
            return moe.acting_rows(cache)
        return {}


# model.encoder keys only the 'preln' blocks read (their defaults are
# session/default_configs.py's). A family of models/attention.py's table
# reads its entry's ``defaults``, which default to None there, and the
# shared keys (kind, block, num_layers, num_heads, act_impl) but those its
# entry lists under ``not_read``.
_PRELN_KEYS = ("features", "head_dim", "max_len")


def family_config(enc_cfg: dict) -> dict:
    """``model.encoder`` as its block family reads it: a key of another
    family that was set is an error, not ignored, and a family of the table
    gets its unset keys' published values."""
    from surreal_tpu.models.attention import (
        FAMILY_MODULES, block_family, family_named,
    )
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    name = block_family(enc_cfg)
    families = {k: family_named(k) for k in FAMILY_MODULES}
    own = set(families[name].defaults) if name in families else set()
    stray = sorted({
        k for family in families.values() for k in family.defaults
        if k not in own and enc_cfg.get(k) is not None
    })
    if name not in families:
        if stray:
            raise ValueError(
                f"model.encoder.block='preln' does not read {stray}: set "
                f"model.encoder.block={' or '.join(families)}, or leave "
                "them unset"
            )
        return enc_cfg
    unset = BASE_LEARNER_CONFIG.model.encoder
    not_read = _PRELN_KEYS + tuple(families[name].not_read)
    stray += [k for k in not_read if enc_cfg.get(k, unset[k]) != unset[k]]
    if stray:
        raise ValueError(
            f"model.encoder.block={name!r} does not read {stray} (keys of "
            f"another family; its own are {sorted(own)})"
        )
    return families[name].resolve(enc_cfg)


def build_seq_model(
    model_config, specs, init_log_std, mesh=None, sp_axis="sp",
    horizon=None, batch_axis=None, policy=None,
):
    """Trajectory actor-critic from ``learner_config.model`` — shared by
    every learner that supports ``encoder.kind='trajectory'``. ``horizon``
    (algo.horizon, when the caller has it) is validated against
    ``encoder.max_len``: the extended learn pass runs T+1 positions, so
    pos_embed must cover horizon+1. ``policy`` is the learner's resolved
    precision policy (ops/precision.py) supplying the attention compute
    dtype; None keeps the bf16 default (direct test construction)."""
    from surreal_tpu.models.attention import (
        TrajectoryCategoricalPPOModel,
        TrajectoryPPOModel,
    )

    from surreal_tpu.models.attention import block_family

    enc_cfg = family_config(model_config.encoder.to_dict())
    family = block_family(enc_cfg)
    wide = family != "preln"   # a family at a published model's widths
    max_len = int(enc_cfg.get("max_len", 4096))
    # 'mla_moe' and 'swa_moe' have no learned positions (a rotary part
    # takes any index), 'ssm_hybrid' and 'kda_moe' no positional term at all
    if not wide and horizon is not None and int(horizon) + 1 > max_len:
        raise ValueError(
            f"algo.horizon={int(horizon)} needs model.encoder.max_len >= "
            f"{int(horizon) + 1} (the sequence learn pass extends the "
            f"segment by one bootstrap position); got max_len={max_len}"
        )
    if wide and (model_config.cnn.enabled or mesh is not None):
        raise ValueError(
            f"model.encoder.block={family!r} runs flat vector obs on one "
            "chip: no CNN stem and no sp mesh path yet (ROADMAP: a trunk "
            "over pixels at these widths; an expert axis in parallel/mesh.py)"
        )
    cnn_cfg = None
    if model_config.cnn.enabled:
        # PIXEL trajectories (round 5): a NatureCNN stem embeds each
        # frame per position before the causal attention — long-context
        # policies over pixel envs, not just vector obs
        if len(specs.obs.shape) != 3:
            raise ValueError(
                "model.encoder.kind='trajectory' with model.cnn.enabled "
                f"needs [H, W, C] pixel obs; got shape {specs.obs.shape}"
            )
        cnn_cfg = model_config.cnn.to_dict()
    elif len(specs.obs.shape) != 1:
        raise ValueError(
            "model.encoder.kind='trajectory' needs flat vector obs (or "
            "model.cnn.enabled for [H, W, C] pixels); got obs shape "
            f"{specs.obs.shape}"
        )
    compute_dtype = jnp.dtype(policy.compute_dtype) if policy else jnp.bfloat16
    if specs.discrete:
        return TrajectoryCategoricalPPOModel(
            encoder_cfg=enc_cfg, n_actions=specs.action.n,
            mesh=mesh, sp_axis=sp_axis, batch_axis=batch_axis,
            cnn_cfg=cnn_cfg, compute_dtype=compute_dtype,
        )
    return TrajectoryPPOModel(
        encoder_cfg=enc_cfg,
        act_dim=int(specs.action.shape[0]),
        init_log_std=init_log_std,
        mesh=mesh, sp_axis=sp_axis, batch_axis=batch_axis,
        cnn_cfg=cnn_cfg, compute_dtype=compute_dtype,
    )
