"""Multi-host (multi-controller) training driver — the loop that composes
the multi-host primitives in ``parallel/multihost.py`` into a runnable
trainer (parity: the reference scaled training across machines with
symphony-launched process groups — learner on one box, agent pools on
others, ZMQ between them, SURVEY.md §3.1/§5.8; the rebuild scales the JAX
way: every host runs THIS SAME program over ONE global device mesh and XLA
emits ICI collectives within a slice, DCN collectives across hosts).

# precision: dtype-transparent like parallel/dp.py — the precision
# policy (ops/precision.py) rides inside the learners every rank builds
# identically from the same config, so replicas stay bitwise-identical
# under any policy; rank 0's hooks record/validate it.

Per-process discipline (the multi-controller contract):

- **Same program, same seeds.** Every rank derives the identical PRNG key
  chain, so replicated jit inputs (learn keys, init keys) agree everywhere
  by construction. Per-host divergence (env seeding, exploration noise) is
  always an explicit ``fold_in`` of the rank or of the global env index.
- **Rank 0 owns the session.** Metrics, logs, checkpoints, and eval run on
  process 0 only, against a HOST-LOCAL numpy copy of the (replicated)
  state — so the session services stay single-controller and orbax never
  needs multi-process coordination. Ranks > 0 run no session services and
  do not even need the session folder mounted.
- **Restore-and-broadcast.** On startup rank 0 restores (auto-resume /
  warm-start, same rules as single-host), then broadcasts state + counters
  to all ranks via a device collective — kill ALL processes, relaunch with
  the same config, and the curve continues.
- **Per-host env feed.** For the fused/off-policy drivers
  ``env_config.num_envs`` is the GLOBAL batch width; each process
  contributes ``num_envs / process_count`` (the SEED driver keeps SEED's
  own per-worker convention — see ``MultiHostSEEDTrainer``):

  * device envs (``jax:*``): the env carry is created directly as a
    global array sharded over ``dp`` (a jitted SPMD init — each process
    materializes only its addressable shards), and the fused
    rollout+learn ``dp_train_iter`` runs on the global mesh unchanged;
  * host envs (gym/dm_control/robosuite-class): each process steps its
    OWN local env batch (the reference's per-machine agent pool), then
    ``local_batch_to_global`` assembles the global learn batch, every
    host's slice riding its own devices.

Stop discipline: a reward-target stop decided by rank 0's ``on_metrics``
is broadcast on metrics-cadence iterations (the only iterations a stop
can originate, and a schedule every rank computes locally) so all ranks
leave the collective schedule together — a rank stopping alone would
deadlock the others' next psum, and agreeing every iteration would
de-pipeline the async hot loop.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from surreal_tpu.engine import (
    EngineConfig,
    LoopEngine,
    LoopState,
    Outcome,
    StageSpec,
    sideband_stages,
)
from surreal_tpu.launch.hooks import SessionHooks, host_metrics
from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
from surreal_tpu.launch.rollout import host_rollout, init_device_carry
from surreal_tpu.launch.seed_trainer import SEEDTrainer
from surreal_tpu.launch.trainer import Trainer
from surreal_tpu.parallel.mesh import check_dp_divisible, replicate_state
from surreal_tpu.parallel.multihost import local_batch_to_global
from surreal_tpu.session.config import Config
from surreal_tpu.session.telemetry import HeartbeatWriter, Tracer

_COUNTER_SPLIT = 2**31  # int64 counters ride int32 collectives as (hi, lo)


def _to_host_local(tree):
    """Replicated global arrays -> host-local numpy (every process holds a
    full copy of a fully-replicated array, so this is a local read)."""
    return jax.tree.map(np.asarray, tree)


def _acting_refresh(act_base, state):
    """Host-local acting snapshot: read ONLY params + obs_stats from the
    replicated global ``state`` (a local read) and graft them onto the
    device-resident ``act_base`` built at run start — optimizer moments
    never cross the host boundary again (they'd triple the per-iteration
    refresh bytes for leaves acting never reads)."""
    params = jax.device_put(jax.tree.map(np.asarray, state.params))
    stats = jax.device_put(jax.tree.map(np.asarray, state.obs_stats))
    return act_base._replace(params=params, obs_stats=stats)


class _MultiHostSession:
    """The multi-controller session discipline shared by every multi-host
    driver: rank bookkeeping, restore-and-broadcast, and the once-compiled
    cross-rank stop agreement. Mixed into a Trainer-family class that sets
    ``self.mesh`` before the mixin methods run."""

    def _init_multihost(self, kind: str) -> None:
        self.rank = jax.process_index()
        self.nprocs = jax.process_count()
        self._agree_fn = None
        self._agree_sharding = None
        if self.nprocs < 2:
            raise ValueError(
                f"{kind} needs an initialized multi-process runtime "
                "(jax.process_count() >= 2); use the single-host driver"
            )

    # -- rank-0 session services + cross-rank agreement ---------------------
    def _broadcast_from_rank0(self, state, iteration: int, env_steps: int):
        """Ship rank 0's (restored) state + counters to every rank, so
        ranks > 0 need neither the session folder nor a shared FS."""
        from jax.experimental import multihost_utils

        counters = np.array(
            [
                iteration // _COUNTER_SPLIT, iteration % _COUNTER_SPLIT,
                env_steps // _COUNTER_SPLIT, env_steps % _COUNTER_SPLIT,
            ],
            np.int32,
        )
        state, counters = multihost_utils.broadcast_one_to_all(
            (_to_host_local(state), counters)
        )
        c = [int(x) for x in np.asarray(counters)]
        return state, c[0] * _COUNTER_SPLIT + c[1], c[2] * _COUNTER_SPLIT + c[3]

    def _agree_stop(self, stop: bool) -> bool:
        """All ranks adopt rank 0's stop decision (a lone stopper would
        deadlock everyone else's next collective).

        Hand-rolled rather than ``multihost_utils.broadcast_one_to_all``:
        that helper constructs a fresh jit per call, which would recompile
        (and open a new gloo/ICI context) EVERY iteration; this one jits
        once per run. Each process contributes its flag at its own mesh
        positions; the replicated sum broadcasts rank 0's decision (ranks
        > 0 contribute zeros)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._agree_fn is None:
            # one flag element per device (1-D dim mapped to ALL mesh axes),
            # so the local slice is exactly this process's device count
            self._agree_sharding = NamedSharding(
                self.mesh, P(tuple(self.mesh.axis_names))
            )
            self._agree_fn = jax.jit(
                lambda x: jnp.minimum(jnp.sum(x), 1),
                out_shardings=NamedSharding(self.mesh, P()),
                donate_argnums=(0,),  # flags are rebuilt fresh every call
            )
        n_local = len([d for d in self.mesh.devices.flat if d.process_index == self.rank])
        local = np.full(
            (n_local,), np.int32(1 if (stop and self.rank == 0) else 0)
        )
        flags = jax.make_array_from_process_local_data(self._agree_sharding, local)
        return bool(self._agree_fn(flags))

    def _maybe_agree_stop(self, iteration: int, stop: bool, metrics_every: int) -> bool:
        """A stop can only originate on metrics-cadence iterations (rank
        0's hooks gate ``on_metrics`` behind the metrics fire), and every
        rank computes that cadence locally — so the cross-host agreement
        runs only there and the hot loop stays async otherwise. Mirrors
        PeriodicTracker: fires when iteration % period == 0."""
        if iteration % metrics_every != 0:
            return False
        return self._agree_stop(stop)

    def _telemetry(self, hooks):
        """Per-rank telemetry handles: rank 0 spans through hooks' tracer
        (ranks > 0 get a disabled no-op tracer — same code path, zero
        cost), and EVERY rank gets a HeartbeatWriter appending liveness
        events to its own ``telemetry/heartbeat_rank<k>.jsonl``. Ranks
        whose host cannot write the session folder disable themselves
        silently (the folder need not be mounted off rank 0)."""
        cfg = self.config.session_config
        tel = cfg.get("telemetry", None)
        tracer = hooks.tracer if hooks is not None else Tracer(None, enabled=False)
        hb = HeartbeatWriter(
            cfg.folder,
            self.rank,
            every_s=float(tel.heartbeat_every_s) if tel is not None else 10.0,
            enabled=bool(tel.enabled) if tel is not None else True,
        )
        return tracer, hb

    def _begin_session(self, state):
        """Rank-0 session prologue shared by every multi-host run():
        restore on rank 0 -> broadcast to all ranks -> replicate over the
        mesh -> start counters. Returns (hooks, state, iteration,
        env_steps); hooks is None on ranks > 0.

        Preemption discipline: a preempting scheduler SIGTERMs the whole
        group. Rank 0's hooks own an interrupt sentinel and turn the latch
        into a stop that ``_maybe_agree_stop`` broadcasts at the next
        metrics-cadence iteration (interrupt latency is bounded by
        ``metrics.every_n_iters``); ranks > 0 install a latch-only
        sentinel here so the default SIGTERM handler cannot kill them
        mid-collective while rank 0 still needs their participation for
        that agreement (a second signal escalates, session/interrupt.py).
        Divergence ROLLBACK is downgraded to 'warn' on rank 0: restoring
        is a collective operation these loops cannot run per-rank — the
        multi-host recovery story is kill-and-relaunch with auto_resume,
        which now lands on the last FINITE checkpoint (the poisoned-save
        skip still applies)."""
        hooks = SessionHooks(self.config, self.learner) if self.rank == 0 else None
        self._rank_interrupt = None
        if hooks is None:
            # ranks > 0 never construct hooks, but every process compiles
            # the same programs — enable the persistent compile cache here
            from surreal_tpu.utils.compat import enable_compile_cache

            enable_compile_cache()
            from surreal_tpu.session.interrupt import InterruptSentinel

            rec = self.config.session_config.get("recovery", None)
            self._rank_interrupt = InterruptSentinel(
                enabled=bool(rec.get("interrupt", True)) if rec is not None else True
            )
        else:
            hooks.recovery.disable_rollback(
                "multi-host run: per-rank restore would desynchronize the "
                "collective schedule; relaunch with auto_resume instead"
            )
        try:
            iteration, env_steps = 0, 0
            if hooks is not None:
                state, iteration, env_steps = hooks.restore(state)
            state, iteration, env_steps = self._broadcast_from_rank0(
                state, iteration, env_steps
            )
            state = replicate_state(self.mesh, state)
            if hooks is not None:
                hooks.begin_run(iteration, env_steps)
        except BaseException:
            # the caller only closes hooks it received; a prologue failure
            # must not leak the writer/checkpoint manager
            if hooks is not None:
                hooks.close()
            raise
        return hooks, state, iteration, env_steps

    def _end_session(self, hooks, iteration: int, env_steps: int, lazy_host_state):
        """Run-end epilogue: rank 0 writes the final checkpoint (the
        emergency checkpoint, on the interrupt path), then ALL ranks leave
        the collective schedule together (rank 0 may still be writing
        while others would otherwise tear down the runtime)."""
        if hooks is not None:
            hooks.final_checkpoint(iteration, env_steps, lazy_host_state)
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("surreal_tpu:run_end")
        if self._rank_interrupt is not None:
            self._rank_interrupt.close()
        return hooks.last_metrics if hooks is not None else {}


class MultiHostTrainer(_MultiHostSession, Trainer):
    """On-policy multi-controller trainer (PPO / IMPALA families).

    Requires ``jax.distributed`` to be initialized first
    (``parallel.multihost.initialize_from_topology``) so ``jax.devices()``
    spans all hosts; ``Trainer.__init__`` then builds the GLOBAL mesh and
    the dp train step with no multi-host-specific code.
    """

    def __init__(self, config):
        self._init_multihost("MultiHostTrainer")
        global_envs = config.env_config.num_envs
        check_dp_divisible(
            global_envs, self.nprocs, "num_envs", "the process count"
        )
        self.global_num_envs = global_envs
        self.local_num_envs = global_envs // self.nprocs
        if config.env_config.name.startswith("jax:"):
            # device envs are global: the carry is one dp-sharded array, so
            # Trainer.__init__ sees the GLOBAL batch width (its dp check
            # must hold globally); carry creation is overridden in run()
            super().__init__(config)
        else:
            # host-env adapters size their worker batch from num_envs:
            # each process builds only ITS slice of the global env batch
            local_cfg = Config(
                env_config=Config(num_envs=self.local_num_envs)
            ).extend(config)
            super().__init__(local_cfg)
            # ...but step accounting stays global
            self.num_envs = self.global_num_envs
            self.config = config
        if self.device_mode:
            if self.mesh.size == 1:
                raise ValueError("multi-host run resolved a size-1 mesh")
        else:
            from surreal_tpu.parallel.dp import dp_learn
            from surreal_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(config.session_config.topology)
            check_dp_divisible(global_envs, self.mesh.shape["dp"])
            self._learn = dp_learn(self.learner, self.mesh)

    # -- main loop -----------------------------------------------------------
    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        """Multi-controller variant of ``Trainer.run``: same cadences and
        hook behavior, but session services fire on rank 0 only and all
        ranks stay on one collective schedule. ``on_metrics`` fires on
        rank 0; its stop decision is broadcast."""
        cfg = self.config.session_config
        total = max_env_steps or cfg.total_env_steps
        steps_per_iter = self.horizon * self.global_num_envs
        metrics_every = max(1, cfg.metrics.every_n_iters)

        def maybe_agree_stop(iteration: int, stop: bool) -> bool:
            return self._maybe_agree_stop(iteration, stop, metrics_every)

        key = jax.random.key(self.seed)  # identical chain on every rank
        key, init_key, env_key = jax.random.split(key, 3)
        state = self.learner.init(init_key)
        hooks = None
        try:
            hooks, state, iteration, env_steps = self._begin_session(state)
            tracer, heartbeat = self._telemetry(hooks)
            ls = LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
            )

            def lazy_host_state():
                return _to_host_local(ls.state)

            # the boundary stays inline on every rank (EngineConfig.inline):
            # a deferred, rank-local stop decision would race the agreed
            # collective stop schedule
            engine_cfg = EngineConfig.from_session(cfg).inline()

            def after_step(ls):
                heartbeat.beat(ls.iteration, ls.env_steps)

            if self.device_mode:
                from jax.sharding import NamedSharding, PartitionSpec as P

                # SPMD carry init: one jitted program over the global mesh;
                # every leaf is [B_global, ...] sharded over dp, and each
                # process computes only its addressable shards. Per-env
                # seeding comes from the global env index (the split inside
                # init_device_carry), so no rank folding is needed.
                ls.extras["carry"] = jax.jit(
                    lambda k: init_device_carry(
                        self.env, k, self.global_num_envs
                    ),
                    out_shardings=NamedSharding(self.mesh, P("dp")),
                    donate_argnums=(),  # one-shot init; nothing loop-carried
                )(env_key)
                if hooks is not None:
                    # cost/MFU accounting (rank 0): lower + HLO cost pass
                    # are rank-local — no collective, no compile
                    hooks.record_program_costs(
                        "train_iter", self._train_iter, state,
                        ls.extras["carry"], jax.random.fold_in(key, 0),
                        phase="train_iter",
                    )
                stages = (
                    StageSpec("collect", donate=True),
                    StageSpec("learn", donate=True),
                ) + sideband_stages()

                def step(ls):
                    ls.key, it_key, hk_key = jax.random.split(ls.key, 3)
                    # unfenced dispatch span (see launch/trainer.py's note)
                    with tracer.span("train_iter"):
                        ls.state, ls.extras["carry"], metrics = (
                            self._train_iter(
                                ls.state, ls.extras["carry"], it_key
                            )
                        )
                    return Outcome(
                        metrics=metrics, hook_key=hk_key,
                        steps=steps_per_iter,
                        state_for_hooks=lazy_host_state,
                    )
            else:
                obs_holder = [
                    self.env.reset(
                        seed=self.config.env_config.seed + self.rank
                    )
                ]
                from collections import deque

                from surreal_tpu.launch.hooks import HOST_METRICS_WINDOW

                recent_returns: deque = deque(maxlen=HOST_METRICS_WINDOW)
                # full local copy ONCE (moments land on device and stay);
                # per-iteration refreshes graft params + obs_stats only
                act_holder = [jax.device_put(lazy_host_state())]
                stages = (
                    StageSpec("collect", donate=False),
                    StageSpec("learn", donate=False),
                ) + sideband_stages()

                def step(ls):
                    ls.key, r_key, l_key, hk_key = jax.random.split(ls.key, 4)
                    # act against a host-local param copy (the SEED host
                    # loop is per-process; only learn is global), with
                    # per-rank exploration streams. One params+stats
                    # upload per ITERATION: shipping the numpy pytree
                    # straight into the per-step jitted act would re-pay
                    # it every env step of the rollout
                    act_holder[0] = _acting_refresh(act_holder[0], ls.state)
                    with tracer.span("rollout"):
                        obs_holder[0], batch, ep_stats = host_rollout(
                            self.env, self._act, act_holder[0],
                            obs_holder[0],
                            jax.random.fold_in(r_key, self.rank),
                            self.horizon,
                        )
                    gbatch = local_batch_to_global(
                        self.mesh, batch, batch_dim=1
                    )
                    with tracer.span("learn"):
                        ls.state, metrics = self._learn(
                            ls.state, gbatch, l_key
                        )
                    if hooks is not None:
                        # first iteration only (idempotent): the learn
                        # program needs a representative global batch
                        hooks.record_program_costs(
                            "learn", self._learn, ls.state, gbatch, l_key,
                            phase="learn",
                        )
                    recent_returns.extend(ep_stats["returns"])
                    # episode stats are rank-0-local (each host sees
                    # only its own episodes); learner metrics are
                    # global — the psum already crossed hosts
                    return Outcome(
                        metrics=host_metrics(metrics, recent_returns),
                        hook_key=hk_key, steps=steps_per_iter,
                        state_for_hooks=lazy_host_state,
                    )

            engine = LoopEngine(
                hooks, total, step, stages, engine_cfg,
                on_metrics=on_metrics, after_step=after_step,
                agree_stop=maybe_agree_stop, fire_faults=False,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            return state, self._end_session(
                hooks, iteration, env_steps, lazy_host_state
            )
        finally:
            if hooks is not None:
                hooks.close()


class MultiHostOffPolicyTrainer(_MultiHostSession, OffPolicyTrainer):
    """Off-policy (DDPG-family) multi-controller trainer: the same global
    mesh discipline as :class:`MultiHostTrainer`, with the replay data
    plane sharded across EVERY device of EVERY host (replay/sharded.py —
    the reference's ShardedReplay scaled past one machine; each host's
    devices hold their own buffer shards and sample locally, the gradient
    psum fans in across hosts).

    Device (``jax:*``) envs only: the fused rollout+replay+update program
    is one SPMD computation over the global mesh. Host-env off-policy
    stays single-controller (its replay lives on one host's devices) —
    the launcher routes that combination to OffPolicyTrainer.
    """

    def __init__(self, config):
        self._init_multihost("MultiHostOffPolicyTrainer")
        if not config.env_config.name.startswith("jax:"):
            raise ValueError(
                "multi-host off-policy training needs a device env "
                f"(jax:*); got {config.env_config.name!r} — host-env "
                "off-policy runs single-host (replay on one host)"
            )
        check_dp_divisible(
            config.env_config.num_envs, self.nprocs,
            "num_envs", "the process count",
        )
        if config.session_config.checkpoint.get("include_replay", False):
            raise ValueError(
                "checkpoint.include_replay is single-host only: the "
                "multi-host replay is sharded across every host's devices "
                "and rank-0 orbax cannot address the other hosts' shards "
                "— resume refills the buffer instead (the reference's own "
                "semantics, SURVEY.md §5.4)"
            )
        # OffPolicyTrainer.__init__ builds the GLOBAL mesh (jax.devices()
        # spans hosts once jax.distributed is up), the per-device-scaled
        # replay, and the dp_offpolicy_iter shard_map — unchanged.
        super().__init__(config)
        if self.mesh is None or self.mesh.size == 1:
            raise ValueError("multi-host run resolved a size-1 mesh")

    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from surreal_tpu.parallel.dp import offpolicy_carry_specs
        from surreal_tpu.replay.sharded import sharded_replay_init

        cfg = self.config.session_config
        total = max_env_steps or cfg.total_env_steps
        steps_per_iter = self.horizon * self.num_envs
        metrics_every = max(1, cfg.metrics.every_n_iters)

        key = jax.random.key(self.seed)  # identical chain on every rank
        key, init_key, env_key = jax.random.split(key, 3)
        state = self.learner.init(init_key)
        hooks = None
        try:
            hooks, state, iteration, env_steps = self._begin_session(state)
            tracer, heartbeat = self._telemetry(hooks)

            # SPMD carry init: one jitted program over the global mesh;
            # each process materializes only its addressable env shards.
            carry_shapes = jax.eval_shape(self._init_carry, env_key)
            carry_sh = jax.tree.map(
                lambda spec: NamedSharding(self.mesh, spec),
                offpolicy_carry_specs(carry_shapes, "dp"),
                is_leaf=lambda x: isinstance(x, P),
            )
            carry = jax.jit(
                self._init_carry, out_shardings=carry_sh,
                donate_argnums=(),  # one-shot init; nothing loop-carried
            )(env_key)
            # replay shards allocate per-device via shard_map (SPMD too)
            replay_state = sharded_replay_init(
                self.replay, self._replay_example(), self.mesh
            )

            import jax.numpy as jnp

            if hooks is not None:
                # cost/MFU accounting (rank 0; lower is rank-local)
                hooks.record_program_costs(
                    "train_iter", self._train_iter, state, replay_state,
                    carry, jax.random.fold_in(key, 0), jnp.float32(0),
                    jnp.asarray(False), jnp.asarray(True),
                    phase="train_iter",
                )

            ls = LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
                extras={
                    "replay": replay_state, "carry": carry,
                    "first_call": True,
                },
            )

            def lazy_host_state():
                return _to_host_local(ls.state)

            def after_step(ls):
                heartbeat.beat(ls.iteration, ls.env_steps)

            stages = (
                StageSpec("collect", donate=True),
                StageSpec("stage", donate=True),
                StageSpec("learn", donate=True),
            ) + sideband_stages()

            def step(ls):
                ls.key, it_key, hk_key = jax.random.split(ls.key, 3)
                # beta/warmup derive from env_steps, identical on every
                # rank (same counter chain) -> consistent replicated inputs
                beta = jnp.asarray(
                    self._beta(ls.env_steps, total), jnp.float32
                )
                warmup = jnp.asarray(
                    ls.env_steps < self.algo.exploration.warmup_steps
                )
                # unfenced dispatch span (see launch/trainer.py's note)
                with tracer.span("train_iter"):
                    (
                        ls.state, ls.extras["replay"], ls.extras["carry"],
                        metrics,
                    ) = self._train_iter(
                        ls.state, ls.extras["replay"], ls.extras["carry"],
                        it_key, beta, warmup,
                        jnp.asarray(ls.extras["first_call"]),
                    )
                ls.extras["first_call"] = False
                return Outcome(
                    metrics=metrics, hook_key=hk_key, steps=steps_per_iter,
                    state_for_hooks=lazy_host_state,
                )

            # inline boundary on every rank — see MultiHostTrainer.run
            engine = LoopEngine(
                hooks, total, step, stages,
                EngineConfig.from_session(cfg).inline(),
                on_metrics=on_metrics, after_step=after_step,
                agree_stop=lambda it, stop: self._maybe_agree_stop(
                    it, stop, metrics_every
                ),
                fire_faults=False,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            return state, self._end_session(
                hooks, iteration, env_steps, lazy_host_state
            )
        finally:
            if hooks is not None:
                hooks.close()


class MultiHostSEEDTrainer(_MultiHostSession, SEEDTrainer):
    """SEED topology across machines — the reference's truest scaling
    shape mapped to TPU: EVERY host runs its own inference server + env
    worker fleet (the per-machine agent pools), and each iteration every
    rank contributes its local trajectory chunk to ONE global dp learn
    (gradient psum across hosts over ICI/DCN).

    Collective-schedule discipline: staleness DROPS are disallowed
    (``max_staleness`` must stay None) — dropping is a per-rank decision,
    and a rank skipping a learn while others enter the psum would
    deadlock the mesh. IMPALA/V-trace absorbs the bounded staleness this
    topology produces by construction; the staleness METRIC still flows.
    Acting is strictly host-local: the server's policy closure runs on a
    host-local copy of ONLY the acting leaves (params + obs normalizer,
    refreshed after each global learn), never on the globally-sharded
    state — a per-request collective would stall every other rank, and
    shipping optimizer moments host-side every iteration would triple the
    refresh bytes for nothing.

    Batch-width semantics: ``env_config.num_envs`` keeps the SEED
    convention (PER-WORKER batch width, exactly as single-host SEED —
    NOT the global width the module docstring describes for the fused
    drivers). Each rank's chunk is [horizon, num_envs]; the global learn
    batch is num_envs x process_count (one chunk per rank), which must
    divide the dp axis.
    """

    def __init__(self, config):
        self._init_multihost("MultiHostSEEDTrainer")
        explicit_dp = int(config.session_config.topology.mesh.dp)
        if explicit_dp > 1:
            raise ValueError(
                "multi-host SEED uses the full global mesh (topology."
                f"mesh.dp=-1); explicit dp={explicit_dp} subset meshes are "
                "a single-host SEED feature"
            )
        SEEDTrainer.__init__(self, config)
        # pipelined sub-slices would halve the per-rank chunk width, and
        # the collective learn schedule is built on [horizon, num_envs]
        # chunks (one per rank, global width num_envs * nprocs checked
        # against dp below) — keep the documented width; round-trip
        # hiding matters least here since every rank acts host-locally
        self.pipeline_workers = False
        if self.max_staleness is not None:
            raise ValueError(
                "max_staleness is single-host SEED only: dropping a chunk "
                "is a per-rank decision that would desynchronize the "
                "collective learn schedule — rely on V-trace (IMPALA) to "
                "absorb bounded staleness in the multi-host topology"
            )
        from surreal_tpu.parallel.dp import dp_learn
        from surreal_tpu.parallel.mesh import check_dp_divisible, make_mesh

        self.mesh = make_mesh(config.session_config.topology)
        check_dp_divisible(
            config.env_config.num_envs * self.nprocs,
            self.mesh.shape["dp"],
            what="num_envs * process_count",
        )
        # donation is SAFE here, unlike single-host SEED: every rank's
        # inference server acts from its own host-local ``_act_base``
        # copy (params+obs_stats grafts), never from the globally-sharded
        # train state this learn donates
        self._learn = dp_learn(self.learner, self.mesh)

    def _worker_env_config(self, env_cfg):
        """Per-rank seed decorrelation: worker i exists on EVERY rank, so
        without an offset each rank's fleet would produce byte-identical
        env streams and the global learn batch would carry duplicated
        trajectories."""
        return Config(
            seed=env_cfg.seed + self.rank * max(1, self.num_workers)
        ).extend(env_cfg)

    def _refresh_act_state(self, state):
        """Params+obs_stats-only acting refresh (see ``_acting_refresh``)."""
        self._act_base = _acting_refresh(self._act_base, state)
        return self._act_base

    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        import threading

        cfg = self.config.session_config
        total = max_env_steps or cfg.total_env_steps
        metrics_every = max(1, cfg.metrics.every_n_iters)
        steps_per_iter = (
            self.algo.horizon * self.config.env_config.num_envs * self.nprocs
        )

        key = jax.random.key(cfg.seed)  # identical chain on every rank
        key, init_key, act_key = jax.random.split(key, 3)
        state = self.learner.init(init_key)
        hooks = None
        plane = None
        stop = threading.Event()
        try:
            hooks, state, iteration, env_steps = self._begin_session(state)
            tracer, heartbeat = self._telemetry(hooks)

            def lazy_host_state():
                return _to_host_local(state)

            # per-rank exploration streams; acting base lives on the LOCAL
            # default device (full initial copy once, then params-only
            # refreshes via _refresh_act_state)
            key_holder = [jax.random.fold_in(act_key, self.rank)]
            self._act_base = jax.device_put(lazy_host_state())
            # every rank's worker fleet inherits ITS tracer's trace id
            # (ranks > 0 mint one even with telemetry disabled)
            self._trace_id = tracer.trace_id
            plane = self._start_data_plane(
                self._make_act_fn(self._act_base, key_holder), stop,
                # first chunk waits out EVERY rank's compiles
                first_chunk_timeout=900.0,
            )
            # steady-state: the learn is COLLECTIVE, so this rank's next
            # chunk can wait on the slowest rank's fleet
            plane.steady_timeout = 120.0
            server = plane.server
            self._workers = plane.workers  # exposed for tests/fault injection

            from collections import deque

            from surreal_tpu.launch.seed_trainer import hop_event

            learn_ms: deque = deque(maxlen=256)
            ls = LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
            )

            def lazy_ls_state():
                return _to_host_local(ls.state)

            lazy_host_state = lazy_ls_state

            def after_step(ls):
                heartbeat.beat(ls.iteration, ls.env_steps)
                plane.supervise()

            stages = (
                StageSpec("collect", donate=False, overlap=True),
                StageSpec("learn", donate=True),
            ) + sideband_stages()

            def step(ls):
                with tracer.span("chunk-wait"):
                    chunk = plane.next_chunk()
                versions = chunk.pop("param_version")
                # lineage stamps / exemplar metadata are host-side only
                # (ISSUE 14) — they must not enter the collective batch
                chunk.pop("lineage", None)
                chunk.pop("_exemplar", None)
                staleness = server.version - int(versions.min())
                gbatch = local_batch_to_global(self.mesh, chunk, batch_dim=1)
                ls.key, lkey, hk_key = jax.random.split(ls.key, 3)
                t_learn0 = time.perf_counter()
                with tracer.span("learn"):
                    ls.state, metrics = self._learn(ls.state, gbatch, lkey)
                learn_ms.append((time.perf_counter() - t_learn0) * 1e3)
                if hooks is not None:
                    # first iteration only (idempotent)
                    hooks.record_program_costs(
                        "learn", self._learn, ls.state, gbatch, lkey,
                        phase="learn",
                    )
                with tracer.span("param-publish"):
                    server.set_act_fn(
                        self._make_act_fn(
                            self._refresh_act_state(ls.state), key_holder
                        )
                    )
                if hooks is not None:
                    # learner metrics are global (psum crossed hosts);
                    # server/episode stats are rank-0-local by design
                    metrics = dict(
                        metrics,
                        **{
                            "staleness/updates_behind": float(staleness),
                            "workers/respawns": float(plane.respawns),
                            "workers/respawn_backoff_s": float(
                                plane.respawn_backoff_s
                            ),
                            "server/chunk_age_s": float(plane.last_chunk_age_s),
                        },
                        **server.queue_stats(),
                        **(server.episode_stats() or {}),
                    )

                def post_metrics(m_row):
                    # per-hop latency percentiles (host deques only)
                    hooks.tracer.event(
                        "hops", **hop_event(server, plane, learn_ms)
                    )

                return Outcome(
                    metrics=metrics, hook_key=hk_key, steps=steps_per_iter,
                    state_for_hooks=lazy_ls_state,
                    post_metrics=post_metrics if hooks is not None else None,
                )

            # inline boundary on every rank — see MultiHostTrainer.run
            engine = LoopEngine(
                hooks, total, step, stages,
                EngineConfig.from_session(cfg).inline(),
                on_metrics=on_metrics, after_step=after_step,
                agree_stop=lambda it, stop: self._maybe_agree_stop(
                    it, stop, metrics_every
                ),
                fire_faults=False,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            return state, self._end_session(
                hooks, iteration, env_steps, lazy_host_state
            )
        finally:
            stop.set()
            if plane is not None:
                plane.close()
            if hooks is not None:
                hooks.close()
