"""PPO learner (parity: reference ``surreal/learner/ppo.py``, SURVEY.md
§2.1 — GAE; clipped-surrogate AND adaptive-KL-penalty modes
(``ppo_mode: clip|adapt``); KL early-stop and beta adaptation; lr
annealing; grad-norm clip; ZFilter obs-normalizer update), re-designed as
one jittable ``learn`` over time-major device arrays.

TPU notes: GAE is a ``lax.scan`` (ops/returns.py); the epoch/minibatch
loop is a nested ``lax.scan`` so the entire SGD iteration is ONE compiled
program — no host round-trips between epochs. KL early-stop is a carried
boolean that zeroes the policy-loss coefficient (baseline updates continue,
matching the reference's separate policy/baseline epoch semantics without
leaving jit).

Batch layout (from launch/rollout.py or replay/fifo):
  obs [T,B,...], next_obs [T,B,...] (pre-reset terminal obs at dones),
  action [T,B,...], reward [T,B], done [T,B] (episode boundary),
  terminated [T,B] (true env termination, excludes truncation),
  behavior_logp [T,B], behavior: dist params ({mean,log_std} | {logits}).

Truncation is handled exactly: bootstrap discount gamma*(1-terminated)
pairs with V(next_obs) where next_obs is the pre-reset terminal obs, while
the GAE accumulation decay uses gamma*lam*(1-done).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

from surreal_tpu.envs.base import EnvSpecs
from surreal_tpu.learners.base import (
    EVAL_DETERMINISTIC,
    TRAINING,
    Learner,
    make_optimizer_chain,
    training_health,
)
from surreal_tpu.ops.precision import current_loss_scale, loss_scale_metrics
from surreal_tpu.learners.seq_policy import (
    SequenceActingMixin,
    build_seq_model,
    family_config,
)
from surreal_tpu.models.attention import (
    COUNTERS_COLLECTION,
    MOE_COLLECTION,
    family_of,
    read_counters,
)
from surreal_tpu.models.ppo_net import CategoricalPPOModel, PPOModel
from surreal_tpu.ops import distributions as D
from surreal_tpu.ops.running_stats import (
    RunningStats,
    init_stats,
    normalize,
    update_stats,
)
from surreal_tpu.session.config import Config
from surreal_tpu.utils.phases import part, phase

PPO_LEARNER_CONFIG = Config(
    algo=Config(
        name="ppo",
        ppo_mode="clip",      # 'clip' | 'adapt'  (both reference modes)
        lam=0.97,             # GAE lambda
        clip_ratio=0.2,
        kl_target=0.01,
        kl_early_stop=4.0,    # stop policy updates when kl > factor*target
        beta_init=1.0,        # adaptive-KL penalty coefficient
        beta_range=(1e-3, 35.0),
        beta_adjust=1.5,
        horizon=128,          # rollout length per SGD iteration
        epochs=4,
        num_minibatches=4,
        value_coeff=0.5,
        entropy_coeff=0.01,
        clip_value=True,      # PPO-style value clipping
        norm_adv=True,
        init_log_std=-0.5,
        sgd_unroll=1,         # minibatch-scan unroll inside _sgd_epochs
        shuffle="block",      # minibatch shuffling: 'block' permutes
                              # contiguous blocks, read where they lie
                              # when long enough (_sgd_epochs has the
                              # chip's readings) | 'row' (exact per-row
                              # reshuffles, the reference's semantics)
        # value forward for GAE: 'exact' runs a second model.apply over
        # next_obs so truncated episodes bootstrap off the TRUE pre-reset
        # terminal obs; 'shared' reuses one apply over [obs; last
        # next_obs] (shifted values) — half the GAE forward work, at the
        # cost of bootstrapping truncation boundaries off the post-reset
        # obs (terminations are exact either way: their discount is 0)
        value_bootstrap="exact",
    ),
    replay=Config(kind="fifo"),
)


def _block_layout(domain: int, num_mb: int, row_bytes: int) -> int:
    """Blocks per minibatch for block-shuffled SGD, or 0 to use row mode.

    Block mode needs: (a) ``domain`` exactly divisible by ``num_mb`` —
    otherwise a fixed tail of rows (end-of-horizon transitions in the
    flat layout) would be statically excluded from EVERY epoch, where row
    mode's per-epoch truncation drops a different random subset each
    time; (b) at least 4 blocks per minibatch, or the "shuffle" is just a
    permutation of minibatch order; (c) SKINNY rows — row shuffling is
    only slow for 4-byte-row leaves that walk the TPU scalar unit, while
    rows past ~4 KB (pixel obs, whole-env segments) already gather as
    efficient contiguous DMA, and block-gathering their megabyte slices
    was pathological where it was tried (nut_pixels, before PR 23 and not
    on this chip: a fused iteration of 91 ms by rows took 63,000 ms by
    blocks; no cell of BENCHMARK.json runs fat rows)."""
    if domain % num_mb != 0 or row_bytes > 4096:
        return 0
    mb_size = domain // num_mb
    blocks_per_mb = 1
    while blocks_per_mb < 64 and mb_size % (blocks_per_mb * 2) == 0:
        blocks_per_mb *= 2
    return blocks_per_mb if blocks_per_mb >= 4 else 0


# Rows a block needs before a minibatch is read a block a trip where the
# blocks lie (``_mb_pieces``). The fused iteration at horizon 256 on the
# v5e, gathered -> in place, fenced ms (my chip runs, PR 31): 2048 envs,
# so 2048 rows a block, 16.09 -> 22.92; 4096: 25.42 -> 25.80; 8192: 56.08
# -> 39.58; 16384: 126.08 -> 63.22; 32768: 340.49 -> 118.90; 65536 (the
# cells of BENCHMARK.json): 801.45 -> 223.77.
_IN_PLACE_BLOCK_ROWS = 8192
# Blocks per trip of that loop: against 1, 2 saves 1.2% of the iteration
# at 65536 rows a block, 2.6% at 16384, 6.5% at 8192; 4 costs 17% at 65536
# (my chip runs, PR 31).
_BLOCK_UNROLL = 2


def _mb_pieces(blocks_per_mb: int, block_len: int) -> int:
    """In how many pieces ``_sgd_epochs`` consumes a minibatch of
    ``blocks_per_mb`` permuted blocks of ``block_len`` rows: 1 gathers
    them into one array and differentiates once; ``blocks_per_mb`` takes a
    block a trip by a slice over the leading axis, so no minibatch is
    built, and pays a loop trip and a forward-backward pass per block.
    Nothing between: any piece of two blocks or more is a gather again,
    at the same cost a row. Row mode (``blocks_per_mb`` 0) is one piece."""
    if blocks_per_mb and block_len >= _IN_PLACE_BLOCK_ROWS:
        return blocks_per_mb
    return 1


class PPOState(NamedTuple):
    params: dict
    opt_state: optax.OptState
    obs_stats: RunningStats
    kl_beta: jax.Array    # scalar, adaptive-KL mode
    iteration: jax.Array  # int32


class PPOLearner(SequenceActingMixin, Learner):
    supports_trajectory_encoder = True

    def __init__(self, learner_config, env_specs: EnvSpecs):
        super().__init__(learner_config, env_specs)
        algo = learner_config.algo
        self.discrete = env_specs.discrete
        enc = learner_config.model.get("encoder", None)
        self.seq_policy = bool(enc is not None and enc.get("kind") == "trajectory")
        self.requires_act_carry = self.seq_policy
        # what the trajectory trunk's block family offers a learner
        # (models/attention.py::Family; None for the 'preln' blocks and a
        # memoryless policy). A routed family (``moe_stats``): every apply
        # that learns reads the router's statistics, and ``self.moe`` is
        # its resolved config; where it has a rule for the selection bias
        # the bias moves by it after each optimizer step. ``counters``:
        # scalars a trunk sows on every apply that learns, which ride the
        # minibatch steps' aux to the metrics row
        self.family = None
        self.moe = None
        self.counters = None
        if self.seq_policy:
            enc_cfg = family_config(enc.to_dict())
            self.family = family_of(enc_cfg)
        if self.family is not None:
            if self.family.moe_stats is not None:
                self.moe = enc_cfg
            self.counters = self.family.counters
        # precision: model dtypes materialize from the resolved policy
        # (Learner.__init__), 'auto' knobs -> concrete per algo.precision
        model_cfg = self.policy.model_config(learner_config.model)
        if self.seq_policy:
            self.model = build_seq_model(
                learner_config.model, env_specs, algo.init_log_std,
                horizon=algo.horizon, policy=self.policy,
            )
        elif self.discrete:
            self.model = CategoricalPPOModel(
                model_cfg=model_cfg,
                n_actions=env_specs.action.n,
            )
        else:
            act_dim = int(env_specs.action.shape[0])
            self.model = PPOModel(
                model_cfg=model_cfg,
                act_dim=act_dim,
                init_log_std=algo.init_log_std,
            )
        self.tx = self._make_optimizer(learner_config.optimizer)

    def _make_optimizer(self, opt_cfg) -> optax.GradientTransformation:
        if opt_cfg.lr_schedule == "linear":
            lr = optax.linear_schedule(
                opt_cfg.lr, 0.0, transition_steps=opt_cfg.get("anneal_steps", 10_000)
            )
        else:
            lr = opt_cfg.lr
        # clip -> adam -> recovery_scale, wrapped in dynamic loss scaling
        # when the precision policy stages in bf16 (learners/base.py)
        return make_optimizer_chain(lr, opt_cfg.max_grad_norm, self.policy)

    # -- state ---------------------------------------------------------------
    def init(self, key: jax.Array) -> PPOState:
        if self.seq_policy:
            obs = jnp.zeros((1, 1, *self.specs.obs.shape), self.specs.obs.dtype)
        else:
            obs = jnp.zeros((1, *self.specs.obs.shape), self.specs.obs.dtype)
        # the parameters alone: a routed-expert model's init also sows its
        # statistics, which belong to an apply, not to the state
        params = {"params": self.model.init(key, obs)["params"]}
        return PPOState(
            params=params,
            opt_state=self.tx.init(params),
            obs_stats=init_stats(self.specs.obs.shape)
            if self._use_obs_filter
            else init_stats((1,)),
            kl_beta=jnp.asarray(self.config.algo.beta_init, jnp.float32),
            iteration=jnp.zeros((), jnp.int32),
        )

    @property
    def _use_obs_filter(self) -> bool:
        # pixel obs are normalized by /255 in the CNN stem, not by ZFilter
        import numpy as np

        return (
            bool(self.config.algo.use_obs_filter)
            and self.specs.obs.dtype != np.uint8
        )

    def _norm_obs(self, stats: RunningStats, obs: jax.Array) -> jax.Array:
        if not self._use_obs_filter:
            return obs
        return normalize(stats, obs.astype(jnp.float32))

    def _apply(self, params, obs):
        """``(model output, the trunk's statistics)`` of one learn-side
        apply, one dict: ``"load" [layers, n_routed]`` and ``"overflow"``
        for a routed family, ``name: scalar`` for each counter a trunk
        sows; ``None`` for a trunk that does neither."""
        collections = []
        if self.moe is not None:
            collections.append(MOE_COLLECTION)
        if self.counters is not None:
            collections.append(COUNTERS_COLLECTION)
        if not collections:
            return self.model.apply(params, obs), None
        out, sown = self.model.apply(params, obs, mutable=collections)
        stats = {}
        if self.moe is not None:
            stats.update(self.family.moe_stats(sown[MOE_COLLECTION]))
        if self.counters is not None:
            stats.update(read_counters(sown[COUNTERS_COLLECTION]))
        return out, stats

    # -- acting --------------------------------------------------------------
    def act(self, state: PPOState, obs: jax.Array, key: jax.Array, mode: str = TRAINING):
        if self.seq_policy:
            raise RuntimeError(
                "trajectory policies condition on history: act through "
                "act_init/act_step (the device collectors, evaluator, and "
                "remote Agent.remote_act do); the stateless act() has no "
                "context to condition on"
            )
        out = self.model.apply(
            state.params, self._norm_obs(state.obs_stats, obs)
        )
        return self._head_act(out, key, mode)

    # -- learning ------------------------------------------------------------
    def learn(self, state: PPOState, batch: dict, key: jax.Array, axis_name=None):
        """One SGD iteration. When ``axis_name`` is set (running inside
        shard_map over a data-parallel mesh axis), gradients / obs-stats /
        advantage normalization are psum-merged so every replica applies the
        identical update — the TPU ICI replacement for the reference's
        single-GPU learner + parameter server (SURVEY.md §5.8)."""
        from surreal_tpu.utils.asserts import check_learn_batch

        check_learn_batch(batch, self.specs, name="ppo.learn")
        if self.seq_policy:
            return self._learn_seq(state, batch, key, axis_name)
        algo = self.config.algo
        T, B = batch["reward"].shape

        with phase("prepare"):
            # 1) obs-normalizer update (reference: ZFilter update then
            # broadcast; its dp psums are this phase's)
            if self._use_obs_filter:
                obs_stats = update_stats(
                    state.obs_stats, batch["obs"], axis_name=axis_name
                )
            else:
                obs_stats = state.obs_stats
            obs = self._norm_obs(obs_stats, batch["obs"])
            next_obs = self._norm_obs(obs_stats, batch["next_obs"])

            # 2) value forward for GAE (one shared pass, or the exact
            # two-pass form — see PPO_LEARNER_CONFIG value_bootstrap)
            if algo.get("value_bootstrap", "exact") == "shared":
                stack = jnp.concatenate([obs, next_obs[-1:]], axis=0)
                v_all = self.model.apply(state.params, stack).value
                values, v_next = v_all[:-1], v_all[1:]
            else:
                values = self.model.apply(state.params, obs).value
                v_next = self.model.apply(state.params, next_obs).value
            advantages, value_targets = self._gae(batch, values, v_next)
            advantages = self._norm_advantages(advantages, axis_name)

            # 3) flatten time x batch -> sample axis
            N = T * B
            flat = {
                "obs": obs.reshape(N, *obs.shape[2:]),
                "action": batch["action"].reshape(N, *batch["action"].shape[2:]),
                "behavior_logp": batch["behavior_logp"].reshape(N),
                "adv": advantages.reshape(N),
                "target": value_targets.reshape(N),
                "value_old": values.reshape(N),
            }
            if self.discrete:
                flat["b_logits"] = batch["behavior"]["logits"].reshape(N, -1)
            else:
                flat["b_mean"] = batch["behavior"]["mean"].reshape(N, -1)
                flat["b_log_std"] = batch["behavior"]["log_std"].reshape(N, -1)

            # precision: stage the obs minibatch array in the policy's data
            # dtype (bf16 under 'bf16'/'bf16_fp8') — the epochs x minibatch
            # gathers then move half the bytes, at the SAME rounding point
            # the model's compute-dtype cast would apply per read. The
            # numerically delicate scalars (logps, advantages, targets)
            # stay f32 under every policy.
            flat = self.policy.cast_stage(flat, keys=("obs",))

        sgd_out = self._sgd_epochs(
            state, flat, N, algo.num_minibatches, key, axis_name
        )
        return self._finalize(
            state, obs_stats, sgd_out, values, value_targets, advantages,
            axis_name,
        )

    # -- pieces shared by the memoryless and sequence learn paths ------------
    @phase("prepare/gae")
    def _gae(self, batch, values, v_next):
        """GAE over [T, B] arrays with the truncation-exact two-mask form
        (bootstrap discount gamma*(1-terminated) vs accumulation decay
        gamma*lam*(1-done)), as a reverse ``lax.scan``."""
        algo = self.config.algo
        gamma = jnp.asarray(algo.gamma, jnp.float32)
        boot_disc = gamma * (1.0 - batch["terminated"].astype(jnp.float32))
        decay = gamma * algo.lam * (1.0 - batch["done"].astype(jnp.float32))
        deltas = batch["reward"] + boot_disc * v_next - values

        def gae_step(carry, xs):
            delta_t, decay_t = xs
            adv = delta_t + decay_t * carry
            return adv, adv

        _, advs_rev = jax.lax.scan(
            gae_step, jnp.zeros_like(deltas[0]), (deltas[::-1], decay[::-1]),
            unroll=max(1, min(int(algo.get("gae_unroll", 1)), deltas.shape[0])),
        )
        advantages = advs_rev[::-1]
        return advantages, advantages + values

    def _norm_advantages(self, advantages, axis_name):
        if not self.config.algo.norm_adv:
            return advantages
        if axis_name is None:
            adv_mean, adv_var = advantages.mean(), advantages.var()
        else:
            adv_mean = jax.lax.pmean(advantages.mean(), axis_name)
            adv_var = (
                jax.lax.pmean((advantages**2).mean(), axis_name) - adv_mean**2
            )
        return (advantages - adv_mean) / (jnp.sqrt(adv_var) + 1e-8)

    def _loss_fn(self, params, mb, kl_beta, policy_coeff, loss_scale=1.0):
        """Clipped / adaptive-KL PPO loss. Every reduction is a
        full-tensor mean, so flat [N] minibatches (memoryless path) and
        [envs, T] segment minibatches (sequence path) share it verbatim.

        ``loss_scale`` is the dynamic loss scale read from the CARRIED
        optimizer state (ops/precision.py) — a power of two multiplying
        only the differentiated total (aux stays unscaled); the optimizer
        chain divides the gradients back down and skips overflowed steps.
        """
        algo = self.config.algo
        out, stats = self._apply(params, mb["obs"])
        if self.discrete:
            logp = D.categorical_logp(out.logits, mb["action"])
            kl = D.categorical_kl(mb["b_logits"], out.logits).mean()
            entropy = D.categorical_entropy(out.logits).mean()
        else:
            logp = D.diag_gauss_logp(out.mean, out.log_std, mb["action"])
            kl = D.diag_gauss_kl(
                mb["b_mean"], mb["b_log_std"], out.mean, out.log_std
            ).mean()
            entropy = D.diag_gauss_entropy(out.log_std).mean()

        ratio = jnp.exp(logp - mb["behavior_logp"])
        if algo.ppo_mode == "clip":
            clipped = jnp.clip(ratio, 1.0 - algo.clip_ratio, 1.0 + algo.clip_ratio)
            pg_loss = -jnp.minimum(ratio * mb["adv"], clipped * mb["adv"]).mean()
        else:  # adaptive KL penalty
            pg_loss = -(ratio * mb["adv"]).mean() + kl_beta * kl

        v = out.value
        if algo.clip_value:
            v_clip = mb["value_old"] + jnp.clip(
                v - mb["value_old"], -algo.clip_ratio, algo.clip_ratio
            )
            v_loss = 0.5 * jnp.maximum(
                (v - mb["target"]) ** 2, (v_clip - mb["target"]) ** 2
            ).mean()
        else:
            v_loss = 0.5 * ((v - mb["target"]) ** 2).mean()

        total = (
            policy_coeff * (pg_loss - algo.entropy_coeff * entropy)
            + algo.value_coeff * v_loss
        )
        aux = {
            "pg_loss": pg_loss,
            "v_loss": v_loss,
            "entropy": entropy,
            "kl": kl,
        }
        if self.moe is not None:
            aux["moe_load"] = jax.lax.stop_gradient(stats["load"])
            aux["moe_overflow"] = jax.lax.stop_gradient(stats["overflow"])
        if self.counters is not None:
            aux["counters"] = jax.lax.stop_gradient(
                {name: stats[name] for name in self.counters}
            )
        return total * loss_scale, aux

    @part("optimizer")
    def _optimizer_step(self, params, opt_state, grads, aux):
        """One optimizer step on a minibatch's gradient: ``(params,
        opt_state)``. Where the family has a rule for the router's selection
        bias, which has no gradient (Adam leaves it where it is), the bias
        then moves by it on this step's loads (``aux["moe_load"]``)."""
        updates, opt_state = self.tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if self.moe is not None and self.family.update_router_bias is not None:
            params = self.family.update_router_bias(
                params, aux["moe_load"], float(self.moe["bias_update_speed"])
            )
        return params, opt_state

    def _sgd_epochs(self, state, data, domain, num_mb, key, axis_name):
        """epochs x minibatches as one nested lax.scan with KL early-stop.
        ``data`` is any pytree indexed on its leading axis of size
        ``domain`` — flat (t, b) samples in the memoryless path, whole-env
        segments in the sequence path; the gather is the ONLY difference
        between the two training loops.

        ``algo.shuffle`` selects how minibatches are drawn:

        - 'block' (default): permute CONTIGUOUS BLOCKS (up to 64 per
          minibatch), not rows: the permutation is 256 ids where rows
          would be millions, and a flat-layout block is a same-timestep
          slab of independent envs, so within-block correlation is near
          zero. A block of ``_IN_PLACE_BLOCK_ROWS`` rows or more is read
          where it lies (``_mb_pieces``): a slice over the leading axis a
          trip, gradient and aux summed in float32 over the minibatch's
          blocks, one optimizer step a minibatch as ever. Gathering such
          blocks (``x[mb_idx]``) is the slow thing on this chip: at 65 536
          envs x 256 a block is one time step of the rollout's
          ``[T, B, ...]``, laid out time-major with the features on
          sublanes and the envs on lanes; XLA's gather relays the whole
          leaf so that the 256 blocks lie on sublanes, then fills the
          minibatch one sublane row of every (8,128) tile a trip, about
          10 GB/s of the chip's 819. ``ppo_lift_long`` read
          ``phase_shuffle_ms`` 335.90 and ``phase_sgd_ms`` 356.44 of
          ``fenced_iter_ms`` 803.89 gathered (PERF_LEDGER.jsonl, PR 30)
          and 14.48 and 98.70 of 227.09 in place (my chip run, PR 31):
          ``sgd`` falls too, its activations being ``[65536, 64]`` a
          block where they were ``[4194304, 64]``. Shorter blocks are
          gathered: 1024 small passes an iteration lose to it.
        - 'row': exact per-row reshuffling every epoch (the reference's
          semantics), for geometries too small/odd to block (also the
          automatic fallback when fewer than 4 blocks fit a minibatch).
        """
        algo = self.config.algo
        mb_size = domain // num_mb
        grad_fn = jax.grad(self._loss_fn, has_aux=True)

        shuffle = algo.get("shuffle", "block")
        if shuffle not in ("block", "row"):
            raise ValueError(f"algo.shuffle {shuffle!r} not in block|row")
        import math

        row_bytes = max(
            math.prod(x.shape[1:]) * x.dtype.itemsize
            for x in jax.tree.leaves(data)
        )
        blocks_per_mb = (
            _block_layout(domain, num_mb, row_bytes) if shuffle == "block" else 0
        )
        if blocks_per_mb:
            nblocks = num_mb * blocks_per_mb
            block_len = mb_size // blocks_per_mb
            with phase("shuffle"):
                data = jax.tree.map(
                    lambda x: x.reshape(nblocks, block_len, *x.shape[1:]), data
                )
            unblock = lambda x: x.reshape(-1, *x.shape[2:])
            perm_domain, idx_shape = nblocks, (num_mb, blocks_per_mb)
            pieces = _mb_pieces(blocks_per_mb, block_len)
        else:
            unblock = lambda x: x
            perm_domain, idx_shape = domain, (num_mb, mb_size)
            pieces = 1
        in_place = pieces > 1   # then a piece is one block (_mb_pieces)

        def mb_update(carry, mb_idx):
            params, opt_state, stopped = carry
            with phase("sgd"):
                policy_coeff = jnp.where(stopped, 0.0, 1.0)
                # precision: the loss scale rides the carried opt_state (a
                # traced input — scale changes never recompile); 1.0 when
                # the policy carries no scale
                scale = current_loss_scale(opt_state)

            def piece_grads(idx):
                """(grads, aux) of the rows ``idx`` names: one block id,
                sliced where the block lies, or all of the minibatch's
                ids, gathered."""
                with phase("shuffle"):
                    if in_place:
                        mb = jax.tree.map(
                            lambda x: jax.lax.dynamic_index_in_dim(
                                x, idx, 0, keepdims=False
                            ),
                            data,
                        )
                    else:
                        mb = jax.tree.map(lambda x: unblock(x[idx]), data)
                with phase("sgd"):
                    return grad_fn(
                        params, mb, state.kl_beta, policy_coeff, scale
                    )

            if in_place:
                # every reduction of _loss_fn is a mean and the blocks are
                # equal-sized, so the minibatch's gradient and aux are the
                # means of the blocks': summed in float32, divided once
                out = jax.eval_shape(piece_grads, mb_idx[0])

                def add_block(total, block_id):
                    block = piece_grads(block_id)
                    with phase("sgd"):
                        return jax.tree.map(
                            lambda t, x: t + x.astype(jnp.float32),
                            total, block,
                        ), None

                total, _ = jax.lax.scan(
                    add_block,
                    jax.tree.map(
                        lambda o: jnp.zeros(o.shape, jnp.float32), out
                    ),
                    mb_idx, unroll=_BLOCK_UNROLL,
                )
                with phase("sgd"):
                    grads, aux = jax.tree.map(
                        lambda t, o: (t / pieces).astype(o.dtype), total, out
                    )
            else:
                grads, aux = piece_grads(mb_idx)
            with phase("sgd"):
                if axis_name is not None:
                    with phase("sgd/psum"):
                        grads = jax.lax.pmean(grads, axis_name)
                        aux = jax.lax.pmean(aux, axis_name)
                # after the pmean so every replica reports the merged norm;
                # feeds the health/* diagnostics in _finalize. Divided by
                # the loss scale (a power of two — exact) so health
                # thresholds see the TRUE gradient magnitude; inf/nan
                # survive the division.
                aux["grad_norm"] = optax.global_norm(grads) / scale
                params, opt_state = self._optimizer_step(
                    params, opt_state, grads, aux
                )
                stopped = jnp.logical_or(
                    stopped, aux["kl"] > algo.kl_early_stop * algo.kl_target
                )
            return (params, opt_state, stopped), aux

        # minibatch-scan unroll (algo.sgd_unroll), clamped to the scan
        # length so a value set for a wider geometry cannot fail the trace
        sgd_unroll = max(1, min(int(algo.get("sgd_unroll", 1)), num_mb))

        def epoch_update(carry, epoch_key):
            # truncation covers row mode on domains not divisible by
            # num_mb; block mode divides exactly by construction
            with phase("shuffle"):
                perm = jax.random.permutation(epoch_key, perm_domain)
                perm = perm[: idx_shape[0] * idx_shape[1]].reshape(idx_shape)
            carry, auxs = jax.lax.scan(
                mb_update, carry, perm, unroll=sgd_unroll
            )
            return carry, auxs

        epoch_keys = jax.random.split(key, algo.epochs)
        # epoch scan: unroll=1 is the explicit decision — each epoch body
        # already contains the whole minibatch scan, so unrolling here
        # multiplies program size by epochs for no sequential-step savings
        return jax.lax.scan(
            epoch_update,
            (state.params, state.opt_state, jnp.asarray(False)),
            epoch_keys,
            unroll=1,
        )

    @phase("finalize")
    def _finalize(
        self, state, obs_stats, sgd_out, values, value_targets, advantages,
        axis_name, prepare_overflow=0.0,
    ):
        """Beta adaptation + new state + the shared metrics dict."""
        algo = self.config.algo
        (params, opt_state, stopped), auxs = sgd_out
        final_kl = auxs["kl"][-1, -1]

        beta = state.kl_beta
        if algo.ppo_mode == "adapt":
            lo, hi = algo.beta_range
            beta = jnp.where(
                final_kl > 2.0 * algo.kl_target,
                jnp.minimum(beta * algo.beta_adjust, hi),
                jnp.where(
                    final_kl < algo.kl_target / 2.0,
                    jnp.maximum(beta / algo.beta_adjust, lo),
                    beta,
                ),
            )

        new_state = PPOState(
            params=params,
            opt_state=opt_state,
            obs_stats=obs_stats,
            kl_beta=beta,
            iteration=state.iteration + 1,
        )
        ev_denom = jnp.var(value_targets) + 1e-8
        metrics: dict = {
            "loss/pg": auxs["pg_loss"].mean(),
            "loss/value": auxs["v_loss"].mean(),
            "policy/entropy": auxs["entropy"].mean(),
            "policy/kl": final_kl,
            "policy/kl_beta": beta,
            "policy/early_stopped": stopped.astype(jnp.float32),
            "value/explained_variance": 1.0
            - jnp.var(value_targets - values) / ev_denom,
            "adv/mean_abs": jnp.abs(advantages).mean(),
        }
        metrics.update(
            training_health(state.params, params, auxs["grad_norm"].mean())
        )
        if self.moe is not None:
            # assignments over every minibatch step and routed layer
            load = auxs["moe_load"].sum((0, 1, 2))
            first, held = int(self.moe["first_held"]), int(self.moe["num_held"])
            mine = load[first:first + held]
            metrics.update({
                "moe/held_share": mine.sum() / load.sum(),
                "moe/load_max_over_mean": mine.max() / mine.mean(),
                "moe/overflow": auxs["moe_overflow"].sum() + prepare_overflow,
            })
            if self.family.router_biases is not None:
                metrics["moe/bias_abs_max"] = jnp.stack([
                    jnp.abs(b).max()
                    for b in self.family.router_biases(params)
                ]).max()
        if self.counters is not None:
            # each over every minibatch step, reduced as the trunk declares
            metrics.update({
                row: getattr(jnp, how)(auxs["counters"][name])
                for name, (row, how) in self.counters.items()
            })
        # precision: loss-scale telemetry (device scalars riding the
        # metrics cadence); empty dict when the policy carries no scale
        metrics.update(loss_scale_metrics(opt_state))
        if axis_name is not None:
            # per-shard metrics (explained variance etc.) -> global mean so
            # the replicated out-spec is truthful
            metrics = jax.lax.pmean(metrics, axis_name)
        return new_state, metrics

    # -- sequence learning ---------------------------------------------------
    def _learn_seq(self, state: PPOState, batch: dict, key: jax.Array, axis_name=None):
        """One SGD iteration for the trajectory policy. Differences from
        the memoryless path, all forced by history conditioning:

        - the model applies over WHOLE segments [B, T, obs]; per-position
          outputs reproduce ``act_step``'s rollout-time conditioning —
          the same causal prefix per position (the PPO ratio contract).
          Agreement is exact in structure and bf16-tight in value: the
          default kv decode and the padded acting path both match this
          recompute within bf16 program-shape tolerance (tested);
        - minibatches are drawn over ENVS, never flat (t, b) samples — a
          shuffled sample has no prefix to condition on (the LSTM-PPO
          discipline, applied to attention);
        - the GAE bootstrap at position T-1 comes from one extended
          [B, T+1] pass (the final next_obs appended). At mid-segment
          TRUNCATIONS the bootstrap conditions on the post-reset obs
          rather than the pre-reset terminal obs: under sequence
          conditioning the terminal obs has no well-defined standalone
          context, and terminated steps (discount 0) are exact either
          way. Documented bias, zero for untruncated segments.
        """
        algo = self.config.algo
        T, B = batch["reward"].shape

        with phase("prepare"):
            obs_stats, values, value_targets, advantages, data, moe = (
                self._prepare_seq(state, batch, axis_name)
            )
        if B // algo.num_minibatches == 0:
            raise ValueError(
                f"num_minibatches={algo.num_minibatches} exceeds the env "
                f"batch width {B}: sequence minibatches are whole envs"
            )
        sgd_out = self._sgd_epochs(
            state, data, B, algo.num_minibatches, key, axis_name
        )
        return self._finalize(
            state, obs_stats, sgd_out, values, value_targets, advantages,
            axis_name,
            prepare_overflow=0.0 if self.moe is None else moe["overflow"],
        )

    def _prepare_seq(self, state, batch, axis_name):
        """The sequence path's ``prepare`` phase: obs filter, one
        extended value pass, GAE, advantage norm, env-major staging; last
        the value pass's router statistics (``None`` without experts)."""
        T, B = batch["reward"].shape
        if self._use_obs_filter:
            obs_stats = update_stats(
                state.obs_stats, batch["obs"], axis_name=axis_name
            )
        else:
            obs_stats = state.obs_stats
        # [T, B, ...] -> [B, T, ...]: the encoder is batch-major. Obs
        # dtype discipline lives in ONE place — the trajectory models'
        # _obs_dtype (uint8 pixels stay raw into the CNN stem's /255;
        # _norm_obs casts vector obs to f32 when the ZFilter is on).
        obs_bt = jnp.swapaxes(
            self._norm_obs(obs_stats, batch["obs"]), 0, 1
        )
        last_next = self._norm_obs(obs_stats, batch["next_obs"][-1])
        ext = jnp.concatenate([obs_bt, last_next[:, None]], axis=1)
        out_ext, moe = self._apply(state.params, ext)   # [B, T+1, ...]
        values = out_ext.value[:, :T].swapaxes(0, 1)    # [T, B]
        v_next = out_ext.value[:, 1:].swapaxes(0, 1)    # [T, B]

        advantages, value_targets = self._gae(batch, values, v_next)
        advantages = self._norm_advantages(advantages, axis_name)

        # env-major training arrays [B, T, ...]; minibatches gather WHOLE
        # envs, so _loss_fn recomputes full-segment conditioning
        bt = lambda x: jnp.swapaxes(x, 0, 1)
        data = {
            "obs": obs_bt,
            "action": bt(batch["action"]),
            "behavior_logp": bt(batch["behavior_logp"]),
            "adv": bt(advantages),
            "target": bt(value_targets),
            "value_old": bt(values),
        }
        if self.discrete:
            data["b_logits"] = bt(batch["behavior"]["logits"])
        else:
            data["b_mean"] = bt(batch["behavior"]["mean"])
            data["b_log_std"] = bt(batch["behavior"]["log_std"])
        # precision: same obs-staging cast as the memoryless path (the
        # trajectory models keep uint8 pixels raw — cast_stage skips
        # non-float leaves)
        data = self.policy.cast_stage(data, keys=("obs",))
        return obs_stats, values, value_targets, advantages, data, moe
