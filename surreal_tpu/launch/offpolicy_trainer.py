"""Off-policy training driver (DDPG-family): collect -> replay -> K SGD
updates, the reference's actor/replay/learner triangle (SURVEY.md §3.2-3.4)
as one program.

Device mode fuses the whole iteration — H env steps (with Gaussian or
carried-OU exploration noise), n-step folding, replay insert, and
``updates_per_iter`` sample+learn steps (plus prioritized-priority refresh)
— into ONE jitted function: the off-policy analogue of Trainer's fused
on-policy iteration. Replay warmup is a ``lax.cond`` (skip updates until
``start_sample_size``), so the compiled program is identical across the
warmup boundary. The fused program donates its loop-carried pytrees
(state, replay shards, env carry) so XLA updates their HBM in place.

Host mode double-buffers: the exploration rollout + its host->device
staging run on a prefetch thread while the device drains the SGD updates
(see ``_run_host``).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from surreal_tpu.engine import (
    EngineConfig,
    LoopEngine,
    LoopState,
    Outcome,
    StageSpec,
    overlap_collect,
    sideband_stages,
)
from surreal_tpu.envs import is_jax_env, make_env
from surreal_tpu.envs.jax.base import batch_step
from surreal_tpu.launch.hooks import SessionHooks, host_metrics, training_env_config
from surreal_tpu.launch.rollout import successor_and_termination
from surreal_tpu.learners import build_learner
from surreal_tpu.learners.aggregator import nstep_transitions
from surreal_tpu.learners.ddpg import ou_noise_step
from surreal_tpu.replay import build_replay
from surreal_tpu.session.config import Config
from surreal_tpu.utils import faults
from surreal_tpu.utils.phases import phase


class OffPolicyCarry(NamedTuple):
    env_state: Any
    obs: jax.Array
    noise: jax.Array      # [B, act_dim] OU state (zeros when gaussian)
    ep_return: jax.Array  # [B]
    ep_length: jax.Array  # [B]
    tail: Any             # last n_step-1 steps of the previous chunk (None if n=1)


TRANS_KEYS = ("obs", "next_obs", "action", "reward", "done", "terminated")


def scrub_fake_prefix_windows(trans, n: int, B: int):
    """Overwrite the n-1 fictitious leading windows of the run's FIRST
    folded chunk with its first real window.

    ``nstep_transitions`` flattens [S, B] windows row-major, so window s of
    env b is flat row ``s*B+b``: the fabricated rows (windows starting in
    the all-zero tail that seeds the cross-chunk carry) occupy
    ``[0, (n-1)*B)`` and the first real window block is ``[(n-1)*B, n*B)``.
    Tiling that block over the fake rows keeps per-env alignment and static
    shapes under jit; duplicating B real transitions n-1 times, once per
    run, is harmless — replay never holds made-up transitions.
    """
    nb = (n - 1) * B
    return jax.tree.map(
        lambda x: x.at[:nb].set(
            jnp.tile(x[nb : nb + B], (n - 1, *([1] * (x.ndim - 1))))
        ),
        trans,
    )


class OffPolicyTrainer:
    def __init__(self, config):
        self.config = config
        self.env = make_env(training_env_config(config.env_config))
        self.learner = build_learner(config.learner_config, self.env.specs)
        algo = self.learner.config.algo
        self.algo = algo
        # precision: the learner's resolved policy governs replay staging
        # (storage example dtype below) — one knob for models, learners,
        # AND replay dtypes (ops/precision.py). replay_gather routes the
        # ring gather/scatter through the pallas row-DMA kernels;
        # injected into the replay build config so the replay layer stays
        # algo-agnostic.
        self._replay_build_cfg = Config(
            gather_impl=algo.get("replay_gather", "xla")
        ).extend(self.learner.config.replay)
        # scan unrolls; `.get` keeps configs saved
        # before the knobs existed loadable. rollout_unroll's default, 0,
        # is "the collector chooses", and this 16-trip collector chooses 1
        # (the clamp in _rollout): launch/rollout.py's rule was read on the
        # chip for device_rollout's scan alone
        self._rollout_unroll = int(algo.get("rollout_unroll", 1))
        self._update_unroll = max(
            1, min(int(algo.get("update_unroll", 1)),
                   int(algo.get("updates_per_iter", 1))),
        )
        self.horizon = algo.horizon
        self.num_envs = config.env_config.num_envs
        self.device_mode = is_jax_env(self.env)
        self.seed = config.session_config.seed
        # remote experience plane (surreal_tpu/experience/): replay lives
        # in shard-server processes fed by an ExperienceSender and drained
        # by a prefetched ShardedSampler — `replay.kind='remote'` with
        # `replay.remote_kind` selecting the shard discipline. Host path
        # only: the device path's replay IS device memory (replay/sharded
        # dp shards); a host-memory shard tier behind a fused device loop
        # would reintroduce the per-iteration host sync the fusion removed.
        replay_kind = self.learner.config.replay.kind
        self.remote = replay_kind == "remote"
        if self.remote and self.device_mode:
            raise ValueError(
                "replay.kind='remote' (the sharded experience plane) runs "
                "the host off-policy path; device (jax:*) envs keep "
                "in-process device-resident replay — use a host env, or "
                "replay.kind='uniform'|'prioritized'"
            )
        self.prioritized = replay_kind == "prioritized" or (
            self.remote
            and self.learner.config.replay.get("remote_kind", "uniform")
            == "prioritized"
        )
        self.mesh = None
        if self.device_mode:
            from surreal_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(config.session_config.topology)
            if self.mesh.size > 1:
                # dp over the mesh: per-device replay shards (the
                # reference's ShardedReplay role, replay/sharded.py) +
                # gradient pmean inside learner.learn
                from surreal_tpu.parallel.dp import dp_offpolicy_iter
                from surreal_tpu.parallel.mesh import check_dp_divisible
                from surreal_tpu.replay.sharded import scale_replay_config

                dp = self.mesh.shape["dp"]
                check_dp_divisible(self.num_envs, dp)
                self.replay = build_replay(
                    scale_replay_config(self._replay_build_cfg, dp)
                )
                self._train_iter = dp_offpolicy_iter(
                    self._device_train_iter, self.mesh
                )
            else:
                self.replay = build_replay(self._replay_build_cfg)
                # donate the loop-carried state / replay shards / env
                # carry: XLA reuses their HBM (the replay storage is the
                # program's largest allocation) instead of holding two
                # copies live across the fused iteration; run() never
                # reads a pre-iteration reference again
                self._train_iter = jax.jit(
                    self._device_train_iter, donate_argnums=(0, 1, 2)
                )
        else:
            # remote plane: no in-process replay object — the buffer lives
            # in the shard servers (built inside _run_host_remote, where
            # the session's trace id exists)
            self.replay = (
                None if self.remote else build_replay(self._replay_build_cfg)
            )
            # acting reuses the same state every env step: never donate
            self._act = jax.jit(
                self.learner.act, static_argnames="mode", donate_argnums=()
            )
            # NOT donated: the overlapped host loop's staging thread acts
            # from the latest published state — the very buffers a
            # donating learn would invalidate mid-rollout
            self._learn = jax.jit(self.learner.learn, donate_argnums=())
            # NOT donated: at n_step=1 `full` IS the rollout traj, which
            # update_obs_stats still reads after the fold
            self._nstep = jax.jit(
                lambda traj: nstep_transitions(traj, algo.gamma, algo.n_step),
                donate_argnums=(),
            )
            if not self.remote:
                # replay state is loop-carried on the train thread only:
                # donate it through insert/sample/priority-refresh so the
                # host path updates the buffer in place too
                self._insert = jax.jit(self.replay.insert, donate_argnums=(0,))
                self._sample = jax.jit(self.replay.sample, donate_argnums=(0,))
                if self.prioritized:
                    self._update_prio = jax.jit(
                        self.replay.update_priorities, donate_argnums=(0,)
                    )
        # uniform-replay fast path (see run_updates in _device_train_iter):
        # one batched index draw + gather for the whole update loop.
        # hasattr gates replay kinds without a batched sampler (fifo).
        self._batched_sampling = (
            not self.prioritized
            and not self.remote
            and bool(algo.get("batched_uniform_sampling", True))
            and hasattr(self.replay, "sample_many")
        )

    # -- device (fused) path -------------------------------------------------
    def _init_carry(self, env_key: jax.Array) -> OffPolicyCarry:
        """Fresh rollout carry for ``num_envs`` envs. Pure and jittable —
        the multi-host driver runs it under jit with dp out-shardings so
        each process materializes only its addressable env shards."""
        act_dim = int(self.env.specs.action.shape[0])
        keys = jax.random.split(env_key, self.num_envs)
        env_state, obs = jax.vmap(self.env.reset)(keys)
        n = self.algo.n_step
        if n > 1:
            B = self.num_envs
            obs_shape = self.env.specs.obs.shape
            tail = {
                "obs": jnp.zeros((n - 1, B, *obs_shape), jnp.float32),
                "next_obs": jnp.zeros((n - 1, B, *obs_shape), jnp.float32),
                "action": jnp.zeros((n - 1, B, act_dim), jnp.float32),
                "reward": jnp.zeros((n - 1, B), jnp.float32),
                # done=True + terminated=True: windows starting in the
                # fake prefix die at once with reward 0 and discount 0
                "done": jnp.ones((n - 1, B), bool),
                "terminated": jnp.ones((n - 1, B), bool),
            }
        else:
            tail = None
        return OffPolicyCarry(
            env_state=env_state,
            obs=obs,
            noise=jnp.zeros((self.num_envs, act_dim), jnp.float32),
            ep_return=jnp.zeros(self.num_envs, jnp.float32),
            ep_length=jnp.zeros(self.num_envs, jnp.int32),
            tail=tail,
        )

    def committed_carry(self, env_key: jax.Array) -> OffPolicyCarry:
        """Fresh rollout carry committed to the active mesh — shared by
        init_loop_state and the divergence-rollback path (which re-seeds
        the env carry without re-allocating the replay storage)."""
        carry = self._init_carry(env_key)
        if self.mesh is not None and self.mesh.size > 1:
            # commit the carry with the shard_map's own specs at init
            # (same reason as Trainer.run: an uncommitted carry breaks
            # the first iteration's donation and pays a reshard)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from surreal_tpu.parallel.dp import offpolicy_carry_specs

            carry = jax.device_put(
                carry,
                jax.tree.map(
                    lambda spec: NamedSharding(self.mesh, spec),
                    offpolicy_carry_specs(carry),
                    is_leaf=lambda x: isinstance(x, P),
                ),
            )
        return carry

    def init_loop_state(self, env_key: jax.Array):
        """(carry, replay_state) committed to the active mesh — ONE
        constructor for run() and tests, so neither can drift from the
        dp path's sharding/donation contract."""
        carry = self.committed_carry(env_key)
        example = self._replay_example()
        if self.mesh is not None and self.mesh.size > 1:
            from surreal_tpu.replay.sharded import sharded_replay_init

            replay_state = sharded_replay_init(self.replay, example, self.mesh)
        else:
            replay_state = self.replay.init(example)
        return carry, replay_state

    def _replay_example(self) -> dict:
        """Single-transition example pytree sizing the replay storage.

        # precision: obs-class leaves allocate in the policy's staging
        # dtype (bf16 halves the buffer — the program's LARGEST
        # allocation; ``ring_insert`` casts incoming f32 rollouts to the
        # storage dtype). Reward/discount stay f32: the TD target sums
        # n-step rewards and bf16 accumulation drifts.
        """
        act_dim = int(self.env.specs.action.shape[0])
        obs_dtype = jnp.dtype(self.learner.policy.data_dtype)
        return {
            "obs": jnp.zeros(self.env.specs.obs.shape, obs_dtype),
            "next_obs": jnp.zeros(self.env.specs.obs.shape, obs_dtype),
            "action": jnp.zeros((act_dim,), jnp.float32),
            "reward": jnp.zeros((), jnp.float32),
            "discount": jnp.zeros((), jnp.float32),
        }

    def _rollout(self, state, carry: OffPolicyCarry, key: jax.Array, warmup):
        explo = self.algo.exploration

        def step(c: OffPolicyCarry, step_key):
            akey, nkey, wkey = jax.random.split(step_key, 3)
            with phase("collect/act"):
                if explo.noise == "ou":
                    a_det, _ = self.learner.act(
                        state, c.obs, akey, "eval_deterministic"
                    )
                    noise = ou_noise_step(
                        c.noise, nkey, explo.ou_theta, explo.sigma, explo.ou_dt
                    )
                    action = jnp.clip(a_det + noise, -1.0, 1.0)
                else:
                    action, _ = self.learner.act(state, c.obs, akey, "training")
                    noise = c.noise
                # exploration warmup: uniform-random actions until the
                # replay holds enough diverse data (classic off-policy
                # bootstrap fix)
                random_action = jax.random.uniform(
                    wkey, action.shape, action.dtype, -1.0, 1.0
                )
                action = jnp.where(warmup, random_action, action)
            with phase("collect/env"):
                env_state, obs2, reward, done, info = batch_step(
                    self.env, c.env_state, action
                )
            next_obs, terminated = successor_and_termination(obs2, done, info)
            ep_return = c.ep_return + reward
            ep_length = c.ep_length + 1
            trans = {
                "obs": c.obs,
                "next_obs": next_obs,
                "action": action,
                "reward": reward,
                "done": done,
                "terminated": terminated,
                "ep_return": jnp.where(done, ep_return, 0.0),
                "ep_done": done,
            }
            new_c = c._replace(
                env_state=env_state,
                obs=obs2,
                # reset OU state at episode boundaries; mask is rank-matched
                # to the [B, act_dim] noise, independent of the obs rank
                noise=jnp.where(done[:, None], 0.0, noise),
                ep_return=jnp.where(done, 0.0, ep_return),
                ep_length=jnp.where(done, 0, ep_length),
            )
            return new_c, trans

        keys = jax.random.split(key, self.horizon)
        # rollout-scan unroll (algo.rollout_unroll)
        return jax.lax.scan(
            step, carry, keys,
            unroll=max(1, min(self._rollout_unroll, self.horizon)),
        )

    def _device_train_iter(
        self, state, replay_state, carry, key, beta, warmup, first, axis_name=None
    ):
        rkey, ukey = jax.random.split(key)
        with phase("collect"):
            carry, traj = self._rollout(state, carry, rkey, warmup)
            chunk = {k: traj[k] for k in TRANS_KEYS}
            n = self.algo.n_step
            if n > 1:
                # prepend the previous chunk's tail so the n-1 steps at
                # every chunk boundary still become window STARTS (without
                # this they would silently never enter replay); carry the
                # new tail on.
                full = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=0),
                    carry.tail, chunk,
                )
                carry = carry._replace(
                    tail=jax.tree.map(lambda x: x[-(n - 1):], full)
                )
            else:
                full = chunk
            trans = nstep_transitions(full, self.algo.gamma, n)
            if n > 1:
                # the very first chunk's prepended tail is fabricated (no
                # previous chunk exists), so the n-1 windows starting
                # inside it are fictitious (obs=0, action=0) — scrub them
                # before insert.
                trans = jax.lax.cond(
                    first,
                    lambda t: scrub_fake_prefix_windows(
                        t, n, chunk["reward"].shape[1]
                    ),
                    lambda t: t,
                    trans,
                )
            # obs-normalizer: fold each fresh obs exactly once per chunk
            with phase("collect/obs_stats"):
                state = self.learner.update_obs_stats(
                    state, chunk["obs"], axis_name
                )
        replay_state = self.replay.insert(replay_state, trans)

        def run_updates(operand):
            state, replay_state = operand
            ukeys = jax.random.split(ukey, self.algo.updates_per_iter)

            if self._batched_sampling:
                # uniform-replay fast path: ALL updates_per_iter index
                # sets drawn in one batched randint + ONE ring gather,
                # instead of a full-buffer gather inside every scan step
                # (64 sequential draws at the DDPG default). Record-
                # equivalent by construction: sample_many derives set k
                # from ukeys[k] exactly as sample() would, and learn
                # consumes the same ukeys[k] — tests/test_replay.py pins
                # bit-equal indices/batches, tests/test_ddpg.py pins the
                # fused iteration against the sequential path. Prioritized
                # replay keeps the sequential path: priorities change
                # between updates, so later draws depend on earlier TDs.
                replay_state, batches, idx = self.replay.sample_many(
                    replay_state, ukeys
                )

                def one_update_batched(state, xs):
                    batch, update_key, idx_k = xs
                    state, metrics = self.learner.learn(
                        state, batch, update_key, axis_name
                    )
                    # same staleness gauge as the sequential path below
                    age = self.replay.age_frac(replay_state, idx_k)
                    if axis_name is not None:
                        age = jax.lax.pmean(age, axis_name)
                    metrics["replay/sample_age_frac"] = age
                    metrics.pop("priority/td_abs")
                    return state, metrics

                state, metrics = jax.lax.scan(
                    one_update_batched, state, (batches, ukeys, idx),
                    unroll=self._update_unroll,
                )
                return state, replay_state, jax.tree.map(jnp.mean, metrics)

            def one_update(c, update_key):
                state, replay_state, mass = c
                if self.prioritized:
                    replay_state, batch, info = self.replay.sample(
                        replay_state, update_key, beta=beta, mass=mass
                    )
                    batch = dict(batch, is_weights=info["is_weights"])
                else:
                    replay_state, batch, info = self.replay.sample(
                        replay_state, update_key
                    )
                state, metrics = self.learner.learn(
                    state, batch, update_key, axis_name
                )
                # sample-staleness gauge (device scalar, telemetry spine):
                # how old the drawn transitions are relative to the fill.
                # Each dp shard draws its own indices, so pmean keeps the
                # scalar genuinely replicated for the shard_map out spec
                age = self.replay.age_frac(replay_state, info["idx"])
                if axis_name is not None:
                    age = jax.lax.pmean(age, axis_name)
                metrics["replay/sample_age_frac"] = age
                td_abs = metrics.pop("priority/td_abs")
                if self.prioritized:
                    replay_state = self.replay.update_priorities(
                        replay_state, info["idx"], td_abs
                    )
                    # only the drawn slots' blocks changed: add those up
                    # again, not the ring (replay/prioritized.py)
                    mass = self.replay.refresh_mass(
                        mass, replay_state, info["idx"]
                    )
                    refreshed = self.replay.blocks_touched(info["idx"])
                    if axis_name is not None:
                        refreshed = jax.lax.pmean(refreshed, axis_name)
                    metrics["replay/mass_blocks_refreshed"] = refreshed
                return (state, replay_state, mass), metrics

            # the block sums of p^alpha ride the loop's carry: one pass
            # over the priorities an iteration, after the insert, and
            # dropped after the scan (None, an empty carry, without them)
            mass = (
                self.replay.block_mass(replay_state)
                if self.prioritized else None
            )
            # update-loop unroll (algo.update_unroll)
            (state, replay_state, _), metrics = jax.lax.scan(
                one_update,
                (state, replay_state, mass),
                ukeys,
                unroll=self._update_unroll,
            )
            return state, replay_state, jax.tree.map(jnp.mean, metrics)

        def skip_updates(operand):
            state, replay_state = operand
            # lax.cond branches must return one pytree structure: derive
            # the zero metrics tree from run_updates' OWN output shape
            # (abstract trace only — nothing executes), so new learner /
            # health / gauge keys can never desync the two branches
            metrics_shape = jax.eval_shape(lambda: run_updates(operand)[2])
            zero_metrics = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), metrics_shape
            )
            return state, replay_state, zero_metrics

        state, replay_state, metrics = jax.lax.cond(
            self.replay.can_sample(replay_state),
            run_updates,
            skip_updates,
            (state, replay_state),
        )
        if axis_name is not None and self.prioritized:
            # max_priority diverges across shards (each sees its own TDs);
            # pmax keeps the fresh-insert priority scale global, and keeps
            # the scalar genuinely replicated for the shard_map out spec
            replay_state = replay_state._replace(
                max_priority=jax.lax.pmax(replay_state.max_priority, axis_name)
            )
        # replay occupancy gauges, after the pmax so prioritized
        # max_priority is the globally-synced value (fills/sizes are
        # lockstep-identical across shards by construction)
        metrics.update(self.replay.gauges(replay_state))
        with phase("collect/episodes"):
            n_done = traj["ep_done"].sum()
            ep_return_sum = traj["ep_return"].sum()
            if axis_name is not None:
                n_done = jax.lax.psum(n_done, axis_name)
                ep_return_sum = jax.lax.psum(ep_return_sum, axis_name)
            metrics["episode/return"] = jnp.where(
                n_done > 0, ep_return_sum / jnp.maximum(n_done, 1), jnp.nan
            )
            metrics["episode/count"] = n_done.astype(jnp.float32)
        return state, replay_state, carry, metrics

    # -- main loop -----------------------------------------------------------
    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        # imported here and not at the top: see launch/trainer.py's run
        from surreal_tpu.session.telemetry import launch_span

        cfg = self.config.session_config
        total = max_env_steps or cfg.total_env_steps
        steps_per_iter = self.horizon * self.num_envs

        with launch_span("launch.state_init"):
            key = jax.random.key(self.seed)
            key, init_key, env_key = jax.random.split(key, 3)
            state = self.learner.init(init_key)
        # chaos harness: install (or RESET) the fault registry for this run
        faults.configure_from(self.config.session_config)
        # divergence-rollback fallback when no finite checkpoint exists yet
        self._fresh_init = lambda nonce: self.learner.init(
            jax.random.fold_in(init_key, nonce)
        )
        hooks = SessionHooks(self.config, self.learner)
        try:
            state, iteration, env_steps = hooks.restore(state)
            hooks.begin_run(iteration, env_steps)
            if not self.device_mode:
                runner = (
                    self._run_host_remote if self.remote else self._run_host
                )
                return runner(
                    total, on_metrics, hooks, state, iteration, env_steps
                )
            if self.mesh is not None and self.mesh.size > 1:
                from surreal_tpu.parallel.mesh import replicate_state

                state = replicate_state(self.mesh, state)
            with launch_span("launch.carry_init"):
                carry, replay_state = self.init_loop_state(env_key)
            if (
                cfg.checkpoint.get("include_replay", False)
                and hooks.ckpt is not None
            ):
                # snapshot the buffer at every checkpoint (closure reads
                # the loop's CURRENT replay_state) and, on resume, reload
                # the snapshot aligned to the restored step so learning
                # continues without a warmup refill
                hooks.extra_state_fn = lambda: {"replay": replay_state}
                if iteration > 0:
                    restored = hooks.ckpt.restore_extra(
                        {"replay": replay_state}, step=iteration
                    )
                    if restored is not None:
                        replay_state = restored["replay"]
            include_replay = bool(
                cfg.checkpoint.get("include_replay", False)
            ) and hooks.ckpt is not None
            # cost/MFU accounting: register the fused program once before
            # the first dispatch (host-side lower + HLO cost pass only)
            hooks.record_program_costs(
                "train_iter", self._train_iter, state, replay_state, carry,
                jax.random.fold_in(key, 0), jnp.float32(0),
                jnp.asarray(False), jnp.asarray(True),
                phase="train_iter",
            )
            # the fused iteration donates state+replay+carry: a deferred
            # boundary reads a jnp.copy snapshot of the param tree. The
            # replay-inclusive checkpoint closure must read the EXACT
            # iteration's ring, so include_replay pins the boundary
            # inline (EngineConfig.inline) — copying the buffer per
            # boundary would dwarf the win being bought.
            stages = (
                StageSpec("collect", donate=True),
                StageSpec("stage", donate=True),
                StageSpec("learn", donate=True),
            ) + sideband_stages()
            engine_cfg = EngineConfig.from_session(cfg)
            if include_replay and engine_cfg.pipeline_sidebands:
                hooks.log.warning(
                    "engine.pipeline_sidebands is pinned off: "
                    "checkpoint.include_replay snapshots the live ring"
                )
                engine_cfg = engine_cfg.inline()
            ls = LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
                extras={"replay": replay_state, "carry": carry,
                        "first_call": True},
            )
            if include_replay:
                # re-point the checkpoint closure at the loop-carried ring
                hooks.extra_state_fn = lambda: {"replay": ls.extras["replay"]}

            def step(ls):
                ls.key, it_key, hk_key = jax.random.split(ls.key, 3)
                beta = jnp.asarray(
                    self._beta(ls.env_steps, total), jnp.float32
                )
                warmup = jnp.asarray(
                    ls.env_steps < self.algo.exploration.warmup_steps
                )
                # unfenced dispatch span (see launch/trainer.py's note)
                with hooks.tracer.span("train_iter"):
                    (ls.state, ls.extras["replay"], ls.extras["carry"],
                     metrics) = self._train_iter(
                        ls.state, ls.extras["replay"], ls.extras["carry"],
                        it_key, beta, warmup,
                        jnp.asarray(ls.extras["first_call"]),
                    )
                ls.extras["first_call"] = False
                return Outcome(
                    metrics=metrics, hook_key=hk_key, steps=steps_per_iter,
                )

            def apply_fault(ls, f):
                ls.state = faults.apply_trainer_fault(f, ls.state)

            def on_rollback(ls):
                rb = hooks.recovery.rollback(
                    ls.state, fresh=self._fresh_init,
                    # replay rides the rollback when it was snapshotted;
                    # otherwise the buffer is kept — its contents are
                    # DATA (worst case: some poisoned-policy transitions
                    # that re-trip the bounded guard), not parameters
                    extra_template=(
                        {"replay": ls.extras["replay"]}
                        if include_replay else None
                    ),
                )
                ls.state, ls.iteration, ls.env_steps = (
                    rb.state, rb.iteration, rb.env_steps
                )
                if self.mesh is not None and self.mesh.size > 1:
                    from surreal_tpu.parallel.mesh import replicate_state

                    ls.state = replicate_state(self.mesh, ls.state)
                if rb.extra is not None:
                    ls.extras["replay"] = rb.extra["replay"]
                ls.key = jax.random.fold_in(ls.key, rb.nonce)
                ls.extras["carry"] = self.committed_carry(
                    jax.random.fold_in(env_key, rb.nonce)
                )
                # the fresh carry's n-step tail is fabricated again:
                # re-scrub the first folded chunk after the rollback
                ls.extras["first_call"] = True

            engine = LoopEngine(
                hooks, total, step, stages, engine_cfg,
                on_metrics=on_metrics, apply_fault=apply_fault,
                on_rollback=on_rollback,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            hooks.final_checkpoint(iteration, env_steps, state)
            return state, hooks.last_metrics
        finally:
            hooks.close()

    def _beta(self, env_steps: int, total: int) -> float:
        """Prioritized IS beta anneal beta0 -> 1.0 over training."""
        if not self.prioritized:
            return 0.0
        frac = min(env_steps / max(total, 1), 1.0)
        b0 = self.learner.config.replay.priority_beta0
        return b0 + (1.0 - b0) * frac

    # -- host path -----------------------------------------------------------
    def _explore_rollout(self, hooks, roll, a_state, warmup, act_dim):
        """One H-step exploration rollout, shared by the host paths
        (in-process ``collect_chunk`` and the remote plane's
        ``collect_and_send``): warmup/OU/training actions, terminal-obs
        and truncation handling, episode-reset noise masking. Mutates
        ``roll`` (key/obs/noise); returns (time-major numpy trajectory
        dict, completed-episode returns) — the returns ride the staged
        item so only the MAIN thread touches the metrics deque (extending
        it from the staging thread would race host_metrics' iteration of
        the deque, the same hazard trainer.py's overlap collector routes
        through its queue)."""
        explo = self.algo.exploration
        steps: list[dict] = []
        chunk_returns: list[float] = []
        obs, noise = roll["obs"], roll["noise"]
        with hooks.tracer.span("rollout"):
            for _ in range(self.horizon):
                roll["key"], akey, nkey = jax.random.split(roll["key"], 3)
                if warmup:
                    action = np.random.default_rng(
                        int(jax.random.randint(akey, (), 0, 2**31 - 1))
                    ).uniform(
                        -1.0, 1.0, (self.num_envs, act_dim)
                    ).astype(np.float32)
                elif explo.noise == "ou":
                    a_det, _ = self._act(
                        a_state, jnp.asarray(obs), akey,
                        mode="eval_deterministic",
                    )
                    # np.array (copy), NOT np.asarray: asarray of a jax
                    # array is a read-only view, and the episode-reset
                    # masking below writes into it
                    noise = np.array(ou_noise_step(
                        jnp.asarray(noise), nkey, explo.ou_theta,
                        explo.sigma, explo.ou_dt,
                    ))
                    action = np.clip(np.asarray(a_det) + noise, -1.0, 1.0)
                else:
                    a, _ = self._act(
                        a_state, jnp.asarray(obs), akey, mode="training"
                    )
                    action = np.asarray(a)
                out = self.env.step(action)
                term_obs = out.info.get("terminal_obs", out.obs)
                done_b = out.done.reshape(
                    out.done.shape + (1,) * (out.obs.ndim - 1)
                )
                truncated = np.asarray(out.info.get(
                    "truncated", np.zeros(len(out.done), bool)
                ))
                steps.append({
                    "obs": obs,
                    "next_obs": np.where(done_b, term_obs, out.obs),
                    "action": action,
                    "reward": out.reward,
                    "done": out.done,
                    "terminated": out.done & ~truncated,
                })
                if out.done.any():
                    noise[out.done] = 0.0
                if "episode_returns" in out.info:
                    chunk_returns.extend(
                        np.asarray(out.info["episode_returns"]).tolist()
                    )
                obs = out.obs
        roll["obs"], roll["noise"] = obs, noise
        traj = {k: np.stack([s[k] for s in steps]) for k in steps[0]}
        return traj, chunk_returns

    def _run_host(self, total, on_metrics, hooks, state, iteration, env_steps):
        """Host-env loop. With ``topology.overlap_rollouts`` (default on)
        the exploration rollout + its host->device staging run on a
        prefetch thread (learners/prefetch.py): while the device drains
        chunk k's ``updates_per_iter`` SGD steps, the staging thread
        simulates chunk k+1 and ships it as ONE ``device_put`` — iteration
        wall-clock ~max(rollout, updates) instead of their sum. The
        staging thread acts from the latest PUBLISHED state — with one
        chunk queued and one mid-collection, up to TWO iterations behind
        (off-policy by construction, the same bounded staleness the
        replay already serves; the warmup flag shares the bound);
        ``overlap_rollouts=false`` restores strict collect->update
        alternation with zero policy lag."""
        steps_per_iter = self.horizon * self.num_envs
        act_dim = int(self.env.specs.action.shape[0])

        base_key = jax.random.key(self.seed + 1)
        key = jax.random.fold_in(base_key, 0)  # update/sample chain
        replay_state = self.replay.init(self._replay_example())
        ckpt_cfg = self.config.session_config.checkpoint
        if ckpt_cfg.get("include_replay", False) and hooks.ckpt is not None:
            # same replay-snapshot contract as the device path
            hooks.extra_state_fn = lambda: {"replay": replay_state}
            if iteration > 0:
                restored = hooks.ckpt.restore_extra(
                    {"replay": replay_state}, step=iteration
                )
                if restored is not None:
                    replay_state = restored["replay"]
        explo = self.algo.exploration
        n = self.algo.n_step
        if n > 1:
            B = self.num_envs
            obs_shape = self.env.specs.obs.shape
            host_tail = {
                "obs": jnp.zeros((n - 1, B, *obs_shape), jnp.float32),
                "next_obs": jnp.zeros((n - 1, B, *obs_shape), jnp.float32),
                "action": jnp.zeros((n - 1, B, act_dim), jnp.float32),
                "reward": jnp.zeros((n - 1, B), jnp.float32),
                "done": jnp.ones((n - 1, B), bool),
                "terminated": jnp.ones((n - 1, B), bool),
            }
        else:
            host_tail = None

        from collections import deque

        from surreal_tpu.launch.hooks import HOST_METRICS_WINDOW
        from surreal_tpu.learners.prefetch import Prefetcher

        recent_returns: deque = deque(maxlen=HOST_METRICS_WINDOW)

        # rollout-side mutable state, owned by whichever thread runs
        # collect_chunk (the staging thread under overlap, this one
        # otherwise — never both); the holders publish the acting state
        # and consumed-step count across the seam
        roll = {
            "key": jax.random.fold_in(base_key, 1),
            "obs": self.env.reset(seed=self.config.env_config.seed),
            "noise": np.zeros((self.num_envs, act_dim), np.float32),
        }
        act_holder = [state]
        steps_holder = [env_steps]

        def collect_chunk():
            """One H-step exploration rollout (``_explore_rollout``),
            stacked time-major and shipped to device as one transfer.
            Returns (device_traj, completed-episode returns)."""
            traj, chunk_returns = self._explore_rollout(
                hooks, roll, act_holder[0],  # one coherent policy per chunk
                steps_holder[0] < explo.warmup_steps, act_dim,
            )
            with hooks.tracer.span("h2d-transfer"):
                return jax.device_put(traj), chunk_returns

        overlap = overlap_collect(self.config.session_config)
        prefetch = (
            Prefetcher(collect_chunk, name="offpolicy-stage") if overlap else None
        )
        include_replay = bool(
            ckpt_cfg.get("include_replay", False)
        ) and hooks.ckpt is not None
        # nothing donates on the host path (the staging thread acts from
        # act_holder[0]); include_replay still pins the boundary inline —
        # the checkpoint closure reads the live ring (see the device path)
        stages = (
            StageSpec("collect", donate=False, overlap=overlap),
            StageSpec("stage", donate=False, overlap=overlap),
            StageSpec("learn", donate=False),
        ) + sideband_stages()
        engine_cfg = EngineConfig.from_session(self.config.session_config)
        if include_replay and engine_cfg.pipeline_sidebands:
            hooks.log.warning(
                "engine.pipeline_sidebands is pinned off: "
                "checkpoint.include_replay snapshots the live ring"
            )
            engine_cfg = engine_cfg.inline()
        ls = LoopState(
            state=state, key=key, iteration=iteration, env_steps=env_steps,
            extras={"replay": replay_state, "first_chunk": True},
        )
        if ckpt_cfg.get("include_replay", False) and hooks.ckpt is not None:
            # re-point the checkpoint closure at the loop-carried ring
            hooks.extra_state_fn = lambda: {"replay": ls.extras["replay"]}

        def step(ls):
            nonlocal host_tail
            if prefetch is not None:
                with hooks.tracer.span("chunk-wait"):
                    traj, ep_returns = prefetch.get()
            else:
                # no chunk-wait span: collect_chunk records its own
                # rollout/h2d phases, and wrapping it here would count
                # the same wall time twice in the diag breakdown
                traj, ep_returns = collect_chunk()
            recent_returns.extend(ep_returns)
            if host_tail is not None:
                full = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), host_tail, traj
                )
                host_tail = jax.tree.map(
                    lambda x: x[-(self.algo.n_step - 1):], full
                )
            else:
                full = traj
            trans = self._nstep(full)
            if host_tail is not None and ls.extras["first_chunk"]:
                # same scrub as the device path: the run's first prepended
                # tail is fabricated, so its windows must not enter replay
                trans = scrub_fake_prefix_windows(
                    trans, self.algo.n_step, self.num_envs
                )
            ls.extras["first_chunk"] = False
            with hooks.tracer.span("replay-insert"):
                ls.extras["replay"] = self._insert(ls.extras["replay"], trans)
            ls.state = self.learner.update_obs_stats(ls.state, traj["obs"])
            if bool(self.replay.can_sample(ls.extras["replay"])):
                beta = jnp.asarray(
                    self._beta(ls.env_steps, total), jnp.float32
                )
                for _ in range(self.algo.updates_per_iter):
                    ls.key, skey = jax.random.split(ls.key)
                    with hooks.tracer.span("replay-sample"):
                        if self.prioritized:
                            ls.extras["replay"], batch, info = self._sample(
                                ls.extras["replay"], skey, beta=beta
                            )
                            batch = dict(batch, is_weights=info["is_weights"])
                        else:
                            ls.extras["replay"], batch, info = self._sample(
                                ls.extras["replay"], skey
                            )
                    with hooks.tracer.span("learn"):
                        ls.state, metrics = self._learn(ls.state, batch, skey)
                    # cost accounting, first update only (idempotent;
                    # needs a representative replay batch to lower)
                    hooks.record_program_costs(
                        "learn", self._learn, ls.state, batch, skey,
                        phase="learn",
                    )
                    td_abs = metrics.pop("priority/td_abs")
                    if self.prioritized:
                        ls.extras["replay"] = self._update_prio(
                            ls.extras["replay"], info["idx"], td_abs
                        )
                metrics["replay/sample_age_frac"] = self.replay.age_frac(
                    ls.extras["replay"], info["idx"]
                )
            else:
                metrics = {}
            metrics = dict(metrics, **self.replay.gauges(ls.extras["replay"]))
            # publish the updated acting state + consumed-step count to
            # the staging thread (its next chunk explores with them)
            act_holder[0] = ls.state
            steps_holder[0] = ls.env_steps + steps_per_iter
            ls.key, hk_key = jax.random.split(ls.key)
            return Outcome(
                metrics=host_metrics(metrics, recent_returns),
                hook_key=hk_key, steps=steps_per_iter,
            )

        def apply_fault(ls, f):
            ls.state = faults.apply_trainer_fault(f, ls.state)
            act_holder[0] = ls.state

        def on_rollback(ls):
            rb = hooks.recovery.rollback(
                ls.state, fresh=self._fresh_init,
                extra_template=(
                    {"replay": ls.extras["replay"]} if include_replay else None
                ),
            )
            ls.state, ls.iteration, ls.env_steps = (
                rb.state, rb.iteration, rb.env_steps
            )
            if rb.extra is not None:
                ls.extras["replay"] = rb.extra["replay"]
            # staging thread keeps collecting: hand it the restored
            # acting state + rolled-back step count; chunks already
            # staged from the poisoned policy are data the replay
            # (and the bounded guard) absorb
            act_holder[0] = ls.state
            steps_holder[0] = ls.env_steps
            ls.key = jax.random.fold_in(ls.key, rb.nonce)

        try:
            engine = LoopEngine(
                hooks, total, step, stages, engine_cfg,
                on_metrics=on_metrics, apply_fault=apply_fault,
                on_rollback=on_rollback,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            hooks.final_checkpoint(iteration, env_steps, state)
            return state, hooks.last_metrics
        finally:
            if prefetch is not None:
                prefetch.close()

    # -- remote experience plane (host path) ---------------------------------
    def _run_host_remote(self, total, on_metrics, hooks, state, iteration,
                         env_steps):
        """Host loop over the sharded experience plane
        (``replay.kind='remote'``, surreal_tpu/experience/): the collector
        thread hash-routes every folded transition to the shard servers
        through the ExperienceSender, and the learner consumes batches the
        ShardedSampler prefetched from ALL shards during the PREVIOUS
        iteration's SGD drain — the learner never waits on experience
        ingest (the residue is the experience/sample_wait_ms gauge).

        Pipeline discipline: iteration k requests its batches (watermarked
        at chunk k's per-shard row counts) and trains on the batches
        requested at iteration k-1 — one chunk of bounded sampling
        staleness, the same bounded-lag class as ``overlap_rollouts``'s
        acting staleness. Under ``overlap_rollouts=false`` the record is
        exactly reproducible run-to-run (watermark deferral at the shard
        — tests pin it)."""
        from collections import deque

        from surreal_tpu.experience import ExperiencePlane
        from surreal_tpu.launch.hooks import HOST_METRICS_WINDOW, host_metrics
        from surreal_tpu.learners.prefetch import Prefetcher

        steps_per_iter = self.horizon * self.num_envs
        act_dim = int(self.env.specs.action.shape[0])
        replay_cfg = self.learner.config.replay
        # replay tiers (ISSUE 18): `replay.tiers.hot` fronts the plane
        # with a device-resident ring, `replay.tiers.spill` turns the
        # shards' ingest into a durable WAL. tiers absent => tiers_cfg
        # None => the plane build below is byte-identical to today.
        tiers_cfg = replay_cfg.get("tiers", None)
        if tiers_cfg is not None:
            tiers_cfg = (
                tiers_cfg.to_dict()
                if hasattr(tiers_cfg, "to_dict") else dict(tiers_cfg)
            )
            spill_cfg = dict(tiers_cfg.get("spill") or {})
            if spill_cfg.get("enabled") and not spill_cfg.get("dir"):
                import os

                # default spill dir under the session folder, next to
                # telemetry/checkpoints — `replay_from_log` finds it there
                spill_cfg["dir"] = os.path.join(
                    self.config.session_config.folder, "spill"
                )
                tiers_cfg["spill"] = spill_cfg
        ckpt_cfg = self.config.session_config.checkpoint
        if ckpt_cfg.get("include_replay", False):
            hooks.log.warning(
                "checkpoint.include_replay is not supported with "
                "replay.kind='remote' (the buffer lives in the shard "
                "servers); resumes refill through warmup"
            )
        base_key = jax.random.key(self.seed + 1)
        key = jax.random.fold_in(base_key, 0)  # update/learn key chain
        explo = self.algo.exploration
        n = self.algo.n_step
        B = self.num_envs
        obs_shape = self.env.specs.obs.shape
        if n > 1:
            host_tail = {
                "obs": np.zeros((n - 1, B, *obs_shape), np.float32),
                "next_obs": np.zeros((n - 1, B, *obs_shape), np.float32),
                "action": np.zeros((n - 1, B, act_dim), np.float32),
                "reward": np.zeros((n - 1, B), np.float32),
                "done": np.ones((n - 1, B), bool),
                "terminated": np.ones((n - 1, B), bool),
            }
        else:
            host_tail = None

        # elastic data-parallel learner group (parallel/learner_group.py):
        # topology.learner_group.members > 0 routes draining + learn
        # through the group — M members over disjoint shard subsets,
        # gradient all-reduce, one fanout version stream, join/leave
        # mid-run. Absent config keeps the plane-wide sampler path
        # untouched.
        lg_cfg = self.config.session_config.topology.get(
            "learner_group", None
        )
        plane = ExperiencePlane(
            kind="prioritized" if self.prioritized else "uniform",
            example=jax.device_get(self._replay_example()),
            capacity=int(replay_cfg.capacity),
            batch_size=int(replay_cfg.batch_size),
            start_sample_size=int(replay_cfg.start_sample_size),
            updates_per_iter=int(self.algo.updates_per_iter),
            num_slots=B,
            # worst-case rows one chunk routes to ONE shard: every folded
            # window (tail prepend keeps window count == horizon)
            max_insert_rows=self.horizon * B,
            priority_alpha=float(replay_cfg.priority_alpha),
            priority_beta0=float(replay_cfg.priority_beta0),
            priority_eps=float(replay_cfg.priority_eps),
            cfg=self.config.session_config.topology.get(
                "experience_plane", None
            ),
            base_key=jax.random.fold_in(base_key, 2),
            trace_id=hooks.trace_id,
            build_sampler=lg_cfg is None,
            tiers=tiers_cfg,
        )
        # hot tier: device-resident newest-transition ring fronting the
        # shard fan-in (replay/tiers.py). Uniform + plane-wide sampler
        # only — the learner group partitions shards across members and
        # prioritized draws need live shard priority state.
        tiered = None
        hot_cfg = dict((tiers_cfg or {}).get("hot") or {})
        if hot_cfg.get("enabled"):
            if lg_cfg is not None or self.prioritized:
                hooks.log.warning(
                    "replay.tiers.hot ignored: requires uniform replay "
                    "and no learner group"
                )
            else:
                from surreal_tpu.experience.sampler import TieredSampler
                from surreal_tpu.replay.tiers import HotTier

                hot = HotTier(
                    capacity=int(
                        hot_cfg.get("capacity", replay_cfg.capacity)
                    ),
                    batch_size=int(replay_cfg.batch_size),
                    gather_impl=hot_cfg.get("gather_impl"),
                    min_fill=hot_cfg.get("min_fill"),
                    # storage in the WARM example's staging dtypes: a hot
                    # sample is dtype-identical to a warm fan-in batch
                    example=self._replay_example(),
                )
                tiered = TieredSampler(plane.sampler, hot)
                plane.attach_tiers(tiered)
        group = None
        if lg_cfg is not None:
            from surreal_tpu.parallel.learner_group import LearnerGroup

            group = LearnerGroup(
                learner=self.learner,
                plane=plane,
                batch_size=int(replay_cfg.batch_size),
                members=int(lg_cfg.get("members", 1)),
                # the SAME key chain the plane-wide sampler would own —
                # the 1-member group's record is bit-identical to it
                base_key=jax.random.fold_in(base_key, 2),
                single_learn=self._learn,
                fanout=hooks.fanout,
                recovery=hooks.recovery,
                on_event=hooks.learner_group_event,
                handoff_template=state,
            )
            hooks.bind_remediation_actuators(learner_group=group)
        sampler = group if group is not None else plane.sampler
        recent_returns: deque = deque(maxlen=HOST_METRICS_WINDOW)
        roll = {
            "key": jax.random.fold_in(base_key, 1),
            "obs": self.env.reset(seed=self.config.env_config.seed),
            "noise": np.zeros((B, act_dim), np.float32),
            "tail": host_tail,
            "first": True,
        }
        act_holder = [state]
        steps_holder = [env_steps]
        # row s*B+b of the flattened window fold belongs to env slot b
        row_slots = np.arange(self.horizon * B, dtype=np.int64) % B

        def collect_and_send():
            """One exploration chunk: rollout (``_explore_rollout``) ->
            n-step fold -> hash-route to the shards. Runs on the staging
            thread under overlap, so ingest (including the fold's device
            round trip) never blocks the learner. Returns (per-shard
            watermarks AFTER this chunk, the chunk's obs stack,
            completed-episode returns)."""
            traj, chunk_returns = self._explore_rollout(
                hooks, roll, act_holder[0],
                steps_holder[0] < explo.warmup_steps, act_dim,
            )
            if roll["tail"] is not None:
                full = {
                    k: np.concatenate([roll["tail"][k], traj[k]], axis=0)
                    for k in traj
                }
                roll["tail"] = {k: v[-(n - 1):] for k, v in full.items()}
            else:
                full = traj
            trans = self._nstep(full)
            if roll["tail"] is not None and roll["first"]:
                # the run's first prepended tail is fabricated — same
                # scrub as the in-process host path
                trans = scrub_fake_prefix_windows(trans, n, B)
            roll["first"] = False
            with hooks.tracer.span("experience-send"):
                wm = plane.sender.send_rows(
                    jax.device_get(trans), row_slots
                )
            if tiered is not None:
                # hot tier eats the SAME flat rows the shards just got,
                # but from the fold's still-device-resident output — the
                # append is a jitted ring insert, no host round trip
                tiered.append(dict(trans))
            return wm, traj["obs"], chunk_returns

        overlap = overlap_collect(self.config.session_config)
        prefetch = (
            Prefetcher(collect_and_send, name="offpolicy-xp-stage")
            if overlap else None
        )
        pending_jobs = [0]
        stages = (
            StageSpec("collect", donate=False, overlap=overlap),
            StageSpec("stage", donate=False, overlap=overlap),
            StageSpec("learn", donate=False),
        ) + sideband_stages()
        ls = LoopState(
            state=state, key=key, iteration=iteration, env_steps=env_steps,
        )

        def step(ls):
            # consume the batches prefetched during the PREVIOUS
            # iteration's learn drain (zero-wait in the steady state —
            # the sample-wait span/gauge measures the residue). This
            # runs BEFORE the next chunk is sent in strict mode, which
            # is exactly what makes the record deterministic: the
            # shard serves every watermarked sample at the precise
            # ring state the watermark names.
            staged = None
            if pending_jobs[0]:
                with hooks.tracer.span("sample-wait"):
                    staged = sampler.get_iteration()
                pending_jobs[0] -= 1
            if prefetch is not None:
                with hooks.tracer.span("chunk-wait"):
                    wm, obs_chunk, ep_returns = prefetch.get()
            else:
                wm, obs_chunk, ep_returns = collect_and_send()
            recent_returns.extend(ep_returns)
            ls.state = self.learner.update_obs_stats(ls.state, obs_chunk)
            if sum(wm) >= int(replay_cfg.start_sample_size):
                sampler.request_iteration(
                    wm, self._beta(ls.env_steps, total)
                )
                pending_jobs[0] += 1
            metrics = {}
            if staged:
                infos, tds = [], []
                for batch, skey, info in staged:
                    with hooks.tracer.span("learn"):
                        if group is not None:
                            ls.state, metrics = group.learn(
                                ls.state, batch, skey
                            )
                        else:
                            ls.state, metrics = self._learn(
                                ls.state, batch, skey
                            )
                            hooks.record_program_costs(
                                "learn", self._learn, ls.state, batch,
                                skey, phase="learn",
                            )
                    td_abs = metrics.pop("priority/td_abs")
                    infos.append(info)
                    tds.append(np.asarray(td_abs))
                if self.prioritized:
                    # ONE batched priority frame per shard per
                    # iteration (the sample_many discipline on-wire)
                    sampler.update_priorities(infos, tds)
            plane.supervise()
            if group is not None:
                group.supervise()
            act_holder[0] = ls.state
            steps_holder[0] = ls.env_steps + steps_per_iter
            ls.key, hk_key = jax.random.split(ls.key)
            base_build = host_metrics(metrics, recent_returns)

            def build_metrics(base=base_build):
                # plane.gauges() polls shard stats over the wire —
                # deferred into the metrics callable so it runs only
                # when the cadence fires
                row = dict(base(), **plane.gauges())
                if group is not None:
                    row.update(group.gauges())
                return row

            return Outcome(
                metrics=build_metrics, hook_key=hk_key,
                steps=steps_per_iter,
                post_metrics=lambda m_row: hooks.experience_event(
                    **plane.telemetry_event()
                ),
            )

        def apply_fault(ls, f):
            ls.state = faults.apply_trainer_fault(f, ls.state)
            act_holder[0] = ls.state

        def on_rollback(ls):
            rb = hooks.recovery.rollback(ls.state, fresh=self._fresh_init)
            ls.state, ls.iteration, ls.env_steps = (
                rb.state, rb.iteration, rb.env_steps
            )
            # shard contents are DATA (same rationale as the
            # in-process rollback path); the restored state re-arms
            # acting and the key chain re-seeds
            act_holder[0] = ls.state
            steps_holder[0] = ls.env_steps
            ls.key = jax.random.fold_in(ls.key, rb.nonce)

        try:
            engine = LoopEngine(
                hooks, total, step, stages,
                EngineConfig.from_session(self.config.session_config),
                on_metrics=on_metrics, apply_fault=apply_fault,
                on_rollback=on_rollback,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            hooks.final_checkpoint(iteration, env_steps, state)
            return state, hooks.last_metrics
        finally:
            # the collect stage (the only sender) ran on this thread, so
            # the ledger is quiesced here — record the close accounting
            # for the chaos exactly-once oracle before stopping the plane
            try:
                hooks.tracer.event(
                    "experience_close", quiesced=1.0, **plane.accounting()
                )
            except Exception:
                hooks.log.warning(
                    "experience_close accounting failed", exc_info=True
                )
            # unblock any bounded sender/sampler wait running on the
            # staging thread FIRST, so the prefetch join below succeeds
            # before plane.close() closes the sockets that thread is using
            plane._stop.set()
            if prefetch is not None:
                prefetch.close()
            if group is not None:
                group.close()
            plane.close()

    # -- replay-from-log (offline; spill tier as WAL) ------------------------
    def replay_from_log(self, log_path: str,
                        max_updates: int | None = None) -> dict:
        """Offline training replay from the spill tier's write-ahead log.

        Reads every ``shard*.log`` under ``log_path`` (or one explicit
        file) in the deterministic global segment order ``(seq, shard)``,
        streams the decoded transitions into an in-process
        ``UniformReplay`` ring, and runs the off-policy update schedule
        against it: once the ring passes ``start_sample_size``, each
        ingested segment is followed by ``updates_per_iter`` sample+learn
        steps on a key chain derived only from the session seed. Two
        invocations over the same log therefore produce bit-identical
        parameters (tested in tests/test_tiers.py) — the spill tier is a
        durable replay record, not just an archive.

        Torn segments (a crash mid-append, the ``experience.spill``
        chaos site) are skipped by the reader's magic-resync and counted
        in the returned ``torn_segments`` — never a crash, never silent.

        Returns {"state", "params_digest", "updates", "rows",
        "segments", "torn_segments", "metrics"}.
        """
        import hashlib

        from surreal_tpu.experience import wire
        from surreal_tpu.experience.spill import SpillLog
        from surreal_tpu.replay.uniform import UniformReplay

        if self.device_mode:
            raise ValueError(
                "replay-from-log is a host-path mode (the WAL is written "
                "by the remote plane's shard servers)"
            )
        replay = UniformReplay(self._replay_build_cfg)
        rstate = replay.init(self._replay_example())
        # loop-carried on this thread only: donate through insert/sample
        # like the in-process host path does
        insert = jax.jit(replay.insert, donate_argnums=(0,))
        sample = jax.jit(replay.sample, donate_argnums=(0,))
        key = jax.random.key(self.seed)
        key, init_key = jax.random.split(key)
        state = self.learner.init(init_key)
        log = SpillLog(log_path)
        start = int(self._replay_build_cfg.start_sample_size)
        upi = int(self.algo.updates_per_iter)
        updates = rows = segments = size = 0
        metrics: dict = {}
        for _header, flat, n in log.segments():
            batch = wire.unflatten_fields(
                {k: jnp.asarray(v) for k, v in flat.items()}
            )
            rstate = insert(rstate, batch)
            size = min(size + n, replay.capacity)
            rows += n
            segments += 1
            if size < start:
                continue
            done = False
            for _ in range(upi):
                if max_updates is not None and updates >= max_updates:
                    done = True
                    break
                key, skey, lkey = jax.random.split(key, 3)
                rstate, b, _ = sample(rstate, skey)
                state, metrics = self._learn(state, b, lkey)
                updates += 1
            if done:
                break
        digest = hashlib.sha256()
        for leaf in jax.tree.leaves(
            jax.device_get(getattr(state, "params", state))
        ):
            digest.update(np.ascontiguousarray(leaf).tobytes())
        return {
            "state": state,
            "params_digest": digest.hexdigest(),
            "updates": updates,
            "rows": rows,
            "segments": segments,
            "torn_segments": int(log.torn_segments),
            "metrics": {
                k: float(np.asarray(jax.device_get(v)).mean())
                for k, v in metrics.items()
            },
        }
