"""Training driver — the rebuild of the reference's learner main loop +
actor pool + replay plumbing as ONE program (SURVEY.md §3.4 and the
BASELINE north star: "learner+actors as one SPMD program instead of
separate ZMQ processes").

Two drive modes, chosen by the env family:

- **device mode** (``jax:*`` envs): collect-horizon + learn are fused into
  a single jitted ``train_iter``; the host only reads metrics every
  ``metrics.every_n_iters`` iterations (one device->host sync) — the hot
  loop never leaves the chip.
- **host mode** (gym/dm_control): SEED-style batched stepping on the host
  feeding jitted ``learn`` — the reference's actor/replay/learner triangle
  collapsed into an alternation.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

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
from surreal_tpu.launch.hooks import SessionHooks, host_metrics, training_env_config
from surreal_tpu.launch.rollout import (
    RolloutCarry,
    device_rollout,
    host_rollout,
    init_device_carry,
)
from surreal_tpu.learners import build_learner
from surreal_tpu.utils import faults
from surreal_tpu.utils.phases import phase


class Trainer:
    """On-policy trainer (PPO-family); off-policy (DDPG) routes through
    the replay layer instead of consuming rollouts directly."""

    def __init__(self, config):
        self.config = config
        self.env = make_env(training_env_config(config.env_config))
        self.learner = build_learner(config.learner_config, self.env.specs)
        # the learner holds the fully-extended tree (algo defaults applied)
        self.horizon = self.learner.config.algo.horizon
        # rollout-scan unroll: a user's number, or 0 = device_rollout
        # chooses (launch/rollout.py::rollout_unroll); `.get` keeps configs
        # saved before the knob existed loadable
        self._rollout_unroll = int(
            self.learner.config.algo.get("rollout_unroll", 0)
        )
        self.num_envs = config.env_config.num_envs
        self.device_mode = is_jax_env(self.env)
        self.seed = config.session_config.seed
        # precision: every jitted program below inherits the learner's
        # resolved policy (ops/precision.py) — model dtypes, SGD staging
        # casts, and loss scaling all live INSIDE learner.learn/act, so
        # the trainer needs no dtype forks; hooks records the policy into
        # checkpoint metadata and telemetry (launch/hooks.py)

        if self.device_mode:
            topo = config.session_config.topology
            from surreal_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(topo)
            sp = dict(self.mesh.shape).get("sp", 1)
            if sp > 1:
                # sequence-parallel fused trainer (SURVEY.md §5.7 long-
                # context seam as a TOPOLOGY knob): the trajectory
                # policy's full-segment attention rides ring attention
                # over mesh['sp'] (ops/ring_attention.py — K/V blocks
                # rotate via ppermute, online softmax), dividing the
                # quadratic attention FLOPs and the [T, T] score memory
                # across devices. The outer step is a plain jit: ring
                # attention brings its own shard_map (which cannot nest
                # inside the dp shard_map — it would rebind the same
                # mesh), so a composed dp x sp mesh instead shards the
                # ring over BOTH axes and lets GSPMD propagate/reduce
                # the rest of the step from the dp-sharded env carry.
                # With dp=1, non-attention compute replicates — the sp
                # axis targets the long-horizon regime where attention
                # dominates.
                if not getattr(self.learner, "requires_act_carry", False):
                    raise ValueError(
                        "topology.mesh sp>1 shards trajectory attention; "
                        "it requires model.encoder.kind='trajectory' "
                        "(memoryless policies have no sequence axis to "
                        "shard — use the dp axis instead)"
                    )
                dp = dict(self.mesh.shape).get("dp", 1)
                self._sp_carry_sharding = None
                if dp > 1:
                    # dp x sp composed mesh: the ring's shard_map tiles
                    # BOTH axes (batch over dp, time over sp — attention
                    # rows are independent in B, so the ring body is
                    # unchanged); the env batch is committed dp-sharded
                    # at carry init and GSPMD propagates/reduces the
                    # rest of the (plain-jit) step globally
                    from surreal_tpu.parallel.mesh import (
                        batch_sharded,
                        check_dp_divisible,
                    )

                    check_dp_divisible(self.num_envs, dp)
                    # PPO slices env-wise minibatches; each slice is the
                    # ring's batch-axis tile. IMPALA consumes the whole
                    # batch per update (no num_minibatches key) — the
                    # full-batch check above is the binding one there.
                    mb = self.learner.config.algo.get("num_minibatches", 1)
                    # models/attention.py re-asserts this same invariant at
                    # the learn-pass shape (B>1, T>1) inside the ring's
                    # batch-tiling fallback — the two sites must not drift
                    # (round-5 review: a mis-sized learn batch used to fall
                    # back to silent full replication)
                    check_dp_divisible(
                        self.num_envs // mb, dp,
                        what="num_envs/num_minibatches (the ring's "
                             "batch-axis tile)",
                        divisor="mesh dp",
                    )
                    self.learner.rebind_mesh(self.mesh, "sp", batch_axis="dp")
                    self._sp_carry_sharding = batch_sharded(self.mesh, "dp")
                else:
                    self.learner.rebind_mesh(self.mesh, "sp")
                # donate the loop-carried state + env carry: XLA reuses
                # their HBM across iterations instead of double-buffering
                # (run() never reads a pre-iteration reference again)
                self._train_iter = jax.jit(
                    self._device_train_iter, donate_argnums=(0, 1)
                )
            elif self.mesh.size > 1:
                from surreal_tpu.parallel.dp import dp_train_iter
                from surreal_tpu.parallel.mesh import check_dp_divisible

                check_dp_divisible(self.num_envs, self.mesh.shape["dp"])
                self._train_iter = dp_train_iter(
                    self._device_train_iter, self.learner, self.mesh
                )
            else:
                # same donation as the sp path (see comment above)
                self._train_iter = jax.jit(
                    self._device_train_iter, donate_argnums=(0, 1)
                )
        else:
            if getattr(self.learner, "requires_act_carry", False):
                raise ValueError(
                    "model.encoder.kind='trajectory' needs a device env "
                    "(jax:*): host loops act per-step without the "
                    "sequence context carry"
                )
            self.mesh = None
            # acting reuses the same state every env step: never donate
            self._act = jax.jit(
                partial(self.learner.act, mode="training"), donate_argnums=()
            )
            # NOT donated: the overlapped host loop's collector thread
            # acts from act_state[0] — the very state a donating learn
            # would invalidate while a rollout is mid-flight with it
            self._learn = jax.jit(self.learner.learn, donate_argnums=())

    # -- device (fused) path -------------------------------------------------
    def _device_train_iter(
        self, state, carry: RolloutCarry, key: jax.Array, axis_name=None
    ):
        ckey, lkey = jax.random.split(key)
        carry, batch = device_rollout(
            self.env, self.learner, state, carry, ckey, self.horizon,
            unroll=self._rollout_unroll,
        )
        learn_batch = {
            k: batch[k]
            for k in (
                "obs",
                "next_obs",
                "action",
                "reward",
                "done",
                "terminated",
                "behavior_logp",
                "behavior",
            )
        }
        state, metrics = self.learner.learn(state, learn_batch, lkey, axis_name)
        with phase("collect/episodes"):
            n_done = batch["ep_done"].sum()
            ep_return_sum = batch["ep_return"].sum()
            if axis_name is not None:
                n_done = jax.lax.psum(n_done, axis_name)
                ep_return_sum = jax.lax.psum(ep_return_sum, axis_name)
            metrics["episode/return"] = jnp.where(
                n_done > 0, ep_return_sum / jnp.maximum(n_done, 1), jnp.nan
            )
            metrics["episode/count"] = n_done.astype(jnp.float32)
        return state, carry, metrics | _over_mesh(batch["acting"], axis_name)

    def init_loop_state(self, env_key: jax.Array) -> RolloutCarry:
        """Device-mode rollout carry committed to the active mesh — ONE
        constructor for run() and tests, so neither can drift from the
        sharding/donation contract below."""
        carry = init_device_carry(self.env, env_key, self.num_envs)
        if getattr(self, "_sp_carry_sharding", None) is not None:
            # dp x sp path: commit the env batch dp-sharded (all
            # carry leaves lead with the env dim) so rollout work
            # splits over dp instead of replicating
            carry = jax.device_put(carry, self._sp_carry_sharding)
        elif self.mesh is not None and self.mesh.size > 1:
            # commit the carry dp-sharded at init so it matches
            # the fused iter's in/out shardings from the FIRST
            # call: an uncommitted carry forces a reshard whose
            # source buffers cannot alias the output, silently
            # dropping the donation for iteration 1
            from surreal_tpu.parallel.mesh import batch_sharded

            carry = jax.device_put(carry, batch_sharded(self.mesh))
        return carry

    # -- main loop -----------------------------------------------------------
    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        """Train until ``max_env_steps`` (default: session total_env_steps).

        Returns (final_state, last_metrics). ``on_metrics(iteration, dict)``
        fires every metrics.every_n_iters with host-side floats; returning
        truthy from it stops training (used by reward-target runs).
        """
        # imported here and not at the top: the compile cache's key holds
        # each traced op's source line, and every traced body of this file
        # lies above (PERF.md section 6, PR 23-25)
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
        # divergence-rollback fallback when no finite checkpoint exists yet:
        # restart from a nonce-distinct init (launch/recovery.py)
        self._fresh_init = lambda nonce: self.learner.init(
            jax.random.fold_in(init_key, nonce)
        )
        hooks = SessionHooks(self.config, self.learner)
        try:
            state, iteration, env_steps = hooks.restore(state)
            if self.mesh is not None and self.mesh.size > 1:
                # restored checkpoints come back committed to one device;
                # the dp shard_map needs the state replicated over the mesh
                from surreal_tpu.parallel.mesh import replicate_state

                state = replicate_state(self.mesh, state)
            hooks.begin_run(iteration, env_steps)

            if self.device_mode:
                with launch_span("launch.carry_init"):
                    carry = self.init_loop_state(env_key)
                # cost/MFU accounting: register the fused program's XLA
                # cost model once, before the first dispatch (host-side
                # lower + HLO cost pass — no compile, no transfers; the
                # 'train_iter' phase spans below time it)
                hooks.record_program_costs(
                    "train_iter", self._train_iter, state, carry,
                    jax.random.fold_in(key, 0), phase="train_iter",
                )
                # the fused iteration donates state+carry, so a DEFERRED
                # boundary reads a jnp.copy snapshot (engine/core.py)
                stages = (
                    StageSpec("collect", donate=True),
                    StageSpec("learn", donate=True),
                ) + sideband_stages()

                def step(ls):
                    ls.key, it_key, hk_key = jax.random.split(ls.key, 3)
                    # span is UNFENCED (dispatch time): fencing here would
                    # serialize the async pipeline; window totals are
                    # honest under backpressure and the cadence sync in
                    # end_iteration is the real fence (session/telemetry.py)
                    with hooks.tracer.span("train_iter"):
                        ls.state, ls.extras["carry"], metrics = (
                            self._train_iter(
                                ls.state, ls.extras["carry"], it_key
                            )
                        )
                    return Outcome(
                        metrics=metrics, hook_key=hk_key,
                        steps=steps_per_iter,
                    )

                def apply_fault(ls, f):
                    ls.state = faults.apply_trainer_fault(f, ls.state)

                def on_rollback(ls):
                    rb = hooks.recovery.rollback(
                        ls.state, fresh=self._fresh_init
                    )
                    ls.state, ls.iteration, ls.env_steps = (
                        rb.state, rb.iteration, rb.env_steps
                    )
                    if self.mesh is not None and self.mesh.size > 1:
                        from surreal_tpu.parallel.mesh import replicate_state

                        ls.state = replicate_state(self.mesh, ls.state)
                    # re-seed the offending batch: roll the key chain
                    # and the env carry so a deterministic workload
                    # cannot replay into the same divergence
                    ls.key = jax.random.fold_in(ls.key, rb.nonce)
                    ls.extras["carry"] = self.init_loop_state(
                        jax.random.fold_in(env_key, rb.nonce)
                    )

                engine = LoopEngine(
                    hooks, total, step, stages,
                    EngineConfig.from_session(cfg),
                    on_metrics=on_metrics, apply_fault=apply_fault,
                    on_rollback=on_rollback,
                )
                ls = engine.run(LoopState(
                    state=state, key=key, iteration=iteration,
                    env_steps=env_steps, extras={"carry": carry},
                ))
                state, iteration, env_steps = (
                    ls.state, ls.iteration, ls.env_steps
                )
            else:
                loop = (
                    self._host_loop_overlap if overlap_collect(cfg)
                    else self._host_loop_alternate
                )
                state, iteration, env_steps = loop(
                    state, iteration, env_steps, total, key, hooks, on_metrics
                )
            hooks.final_checkpoint(iteration, env_steps, state)
            return state, hooks.last_metrics
        finally:
            hooks.close()

    # -- host-env loops ------------------------------------------------------
    def _host_loop_alternate(
        self, state, iteration, env_steps, total, key, hooks, on_metrics
    ):
        """Strict rollout -> learn alternation (topology.overlap_rollouts
        = false): the chip idles during every env step, but policy lag is
        exactly zero — the conservative/debugging mode."""
        from collections import deque

        from surreal_tpu.launch.hooks import HOST_METRICS_WINDOW

        steps_per_iter = self.horizon * self.num_envs
        obs_holder = [self.env.reset(seed=self.config.env_config.seed)]
        recent_returns = deque(maxlen=HOST_METRICS_WINDOW)
        # host path: nothing donates (acting reuses the state every env
        # step), so a deferred boundary version-pins the state reference
        stages = (
            StageSpec("collect", donate=False),
            StageSpec("learn", donate=False),
        ) + sideband_stages()

        def step(ls):
            ls.key, r_key, l_key, hk_key = jax.random.split(ls.key, 4)
            with hooks.tracer.span("rollout"):
                obs_holder[0], batch, ep_stats = host_rollout(
                    self.env, self._act, ls.state, obs_holder[0], r_key,
                    self.horizon,
                )
            with hooks.tracer.span("learn"):
                ls.state, metrics = self._learn(ls.state, batch, l_key)
            # cost accounting, first iteration only (idempotent): the
            # learn program needs a representative batch to lower, and
            # the act program runs horizon times inside each 'rollout'
            # phase (its MFU contribution is a documented lower bound —
            # the phase also times env stepping)
            hooks.record_program_costs(
                "learn", self._learn, ls.state, batch, l_key, phase="learn"
            )
            hooks.record_program_costs(
                "act", self._act, ls.state, batch["obs"][0], l_key,
                phase="rollout", calls_per_phase=self.horizon,
            )
            recent_returns.extend(ep_stats["returns"])
            return Outcome(
                metrics=host_metrics(metrics, recent_returns),
                hook_key=hk_key, steps=steps_per_iter,
            )

        def apply_fault(ls, f):
            ls.state = faults.apply_trainer_fault(f, ls.state)

        def on_rollback(ls):
            rb = hooks.recovery.rollback(ls.state, fresh=self._fresh_init)
            ls.state, ls.iteration, ls.env_steps = (
                rb.state, rb.iteration, rb.env_steps
            )
            ls.key = jax.random.fold_in(ls.key, rb.nonce)
            # a NaN policy steps the env into garbage: reset it on a
            # nonce-distinct seed (the re-seeded offending batch)
            obs_holder[0] = self.env.reset(
                seed=self.config.env_config.seed + rb.nonce
            )

        engine = LoopEngine(
            hooks, total, step, stages,
            EngineConfig.from_session(self.config.session_config),
            on_metrics=on_metrics, apply_fault=apply_fault,
            on_rollback=on_rollback,
        )
        ls = engine.run(LoopState(
            state=state, key=key, iteration=iteration, env_steps=env_steps,
        ))
        return ls.state, ls.iteration, ls.env_steps

    def _host_loop_overlap(
        self, state, iteration, env_steps, total, key, hooks, on_metrics
    ):
        """Double-buffered host loop (SURVEY.md §3.4 — the reference's
        learner never waited on actors; §7 hard-part #1): a collector
        thread steps the env for iteration k+1 while the device learns on
        k, so iteration wall-clock is ~max(rollout, learn) instead of
        their sum. The collector reads the acting state ONCE per rollout
        (a coherent behavior policy per batch, recorded in behavior_logp),
        at most one update behind — exactly the staleness PPO's ratios /
        V-trace are built to absorb. At the stop boundary one in-flight
        rollout may be discarded; its env steps are not counted (same
        budget discipline as the SEED drop path)."""
        import queue as queue_mod
        import threading
        from collections import deque

        from surreal_tpu.launch.hooks import HOST_METRICS_WINDOW

        steps_per_iter = self.horizon * self.num_envs
        key, roll_key = jax.random.split(key)
        act_state = [state]  # collector reads latest; main thread writes
        out: queue_mod.Queue = queue_mod.Queue(maxsize=1)
        stop_evt = threading.Event()

        tracer = hooks.tracer  # thread-safe; the collector spans "rollout"

        def collect():
            obs = self.env.reset(seed=self.config.env_config.seed)
            k = roll_key
            try:
                while not stop_evt.is_set():
                    k, r_key = jax.random.split(k)
                    with tracer.span("rollout"):
                        obs, batch, ep_stats = host_rollout(
                            self.env, self._act, act_state[0], obs, r_key,
                            self.horizon,
                        )
                    item = (batch, ep_stats)
                    while not stop_evt.is_set():
                        try:
                            out.put(item, timeout=0.2)
                            break
                        except queue_mod.Full:
                            continue
            except BaseException as e:  # surface env/act crashes to main
                out.put(e)

        collector = threading.Thread(target=collect, daemon=True)
        collector.start()
        recent_returns = deque(maxlen=HOST_METRICS_WINDOW)
        # overlap=True is the rollout/learn-overlap bit that used to be
        # the topology.overlap_rollouts fork; nothing donates (the
        # collector acts from act_state[0] — the very state a donating
        # learn would invalidate mid-rollout)
        stages = (
            StageSpec("collect", donate=False, overlap=True),
            StageSpec("learn", donate=False),
        ) + sideband_stages()

        def step(ls):
            with tracer.span("chunk-wait"):
                got = out.get()
            if isinstance(got, BaseException):
                raise got
            batch, ep_stats = got
            ls.key, l_key, hk_key = jax.random.split(ls.key, 3)
            with tracer.span("learn"):
                ls.state, metrics = self._learn(ls.state, batch, l_key)
            act_state[0] = ls.state  # device-resident; no host copy
            # cost accounting, first iteration only (see the
            # alternation loop's note)
            hooks.record_program_costs(
                "learn", self._learn, ls.state, batch, l_key, phase="learn"
            )
            hooks.record_program_costs(
                "act", self._act, ls.state, batch["obs"][0], l_key,
                phase="rollout", calls_per_phase=self.horizon,
            )
            recent_returns.extend(ep_stats["returns"])
            return Outcome(
                metrics=host_metrics(metrics, recent_returns),
                hook_key=hk_key, steps=steps_per_iter,
            )

        def apply_fault(ls, f):
            ls.state = faults.apply_trainer_fault(f, ls.state)
            act_state[0] = ls.state

        def on_rollback(ls):
            rb = hooks.recovery.rollback(ls.state, fresh=self._fresh_init)
            ls.state, ls.iteration, ls.env_steps = (
                rb.state, rb.iteration, rb.env_steps
            )
            act_state[0] = ls.state  # collector acts healthy again
            ls.key = jax.random.fold_in(ls.key, rb.nonce)
            # drop any queued rollout collected by the poisoned
            # policy (data, not params — but no reason to learn on
            # it); the collector's own env obs cannot be reset from
            # here, so a run whose ENV state went nonfinite re-trips
            # and exhausts the bounded budget loudly
            try:
                out.get_nowait()
            except queue_mod.Empty:
                pass

        try:
            engine = LoopEngine(
                hooks, total, step, stages,
                EngineConfig.from_session(self.config.session_config),
                on_metrics=on_metrics, apply_fault=apply_fault,
                on_rollback=on_rollback,
            )
            ls = engine.run(LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
            ))
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
        finally:
            stop_evt.set()
            while True:  # unblock a collector waiting on the full queue
                try:
                    out.get_nowait()
                except queue_mod.Empty:
                    break
            collector.join(timeout=30)
        return state, iteration, env_steps


def _over_mesh(rows: dict, axis_name) -> dict:
    """Metrics rows a rollout counted on its own shard (``batch["acting"]``),
    averaged over the mesh axis the iteration runs under; as they are
    without one. (Down here so that no traced line above moves: the compile
    cache's key holds them.)"""
    return rows if axis_name is None else jax.lax.pmean(rows, axis_name)
