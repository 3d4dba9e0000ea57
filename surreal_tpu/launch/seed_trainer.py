"""SEED trainer: central inference server + host env workers + learner —
the fully-disaggregated topology for envs that cannot live on device
(BASELINE config ⑤'s "SEED-RL batched inference"; reference call stack
SURVEY.md §3.2 with the actor pool collapsed).

Data flow:
  env workers --ZMQ/DCN--> InferenceServer (one batched policy forward)
     └─ trajectory chunks --queue--> staging thread (double-buffered
        host->device transfer, learners/prefetch.py) --> learner.learn
        (V-trace corrects the one-update staleness; works for IMPALA
        and, with staleness caveats, PPO)

Workers run as threads (fine for gym classic-control) or OS processes
(``worker_mode='process'`` — MuJoCo-heavy stepping releases the GIL
poorly, so real deployments fork the reference's actor-pool way; both
modes run the same ``run_env_worker``).

Staleness: every transition carries the params version that chose its
action (InferenceServer tags them; SURVEY.md §7 hard-parts). V-trace
(IMPALA) absorbs bounded staleness by construction; for PPO-over-SEED set
``max_staleness`` to drop chunks whose oldest transition was acted more
than that many updates ago instead of silently training on them.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
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
from surreal_tpu.distributed.env_worker import run_env_worker
from surreal_tpu.distributed.inference_server import InferenceServer
from surreal_tpu.learners import build_learner
from surreal_tpu.utils import faults


_FROM_CONFIG = object()  # sentinel: None is a meaningful max_staleness value


def hop_event(server, plane, learn_ms, gateway=None) -> dict:
    """Assemble the per-hop latency percentiles for one ``hops``
    telemetry event — the stitched cross-process timeline (worker step ->
    frame in flight -> serve batch -> queue dwell -> learn), rendered by
    ``surreal_tpu diag``. The learn hop measures DISPATCH time (the span
    discipline of session/telemetry.py), named accordingly. A live
    gateway joins with its act/transit/attach windows (ISSUE 13: GACT
    frames stamp t_send under the local-address clock guard)."""
    from surreal_tpu.session.telemetry import latency_percentiles

    hops = dict(server.hop_stats())
    p = latency_percentiles(list(plane.dwell_ms))
    if p is not None:
        hops["chunk_queue_dwell_ms"] = p
    p = latency_percentiles(list(learn_ms))
    if p is not None:
        hops["learn_dispatch_ms"] = p
    if gateway is not None:
        hops.update(gateway.hop_stats())
    return hops


class _DataPlane:
    """Running SEED data plane: server + worker fleet + supervision.

    ``next_chunk`` waits for experience while supervising workers on every
    empty poll — a dead SOLE worker must be respawned while waiting, not
    after a chunk it can no longer produce. ``respawns`` accumulates for
    the metrics stream. The chunk timeout resets to ``steady_timeout``
    after the first chunk (the first waits out XLA compiles; in the
    multi-host loop the steady wait also covers the slowest rank's fleet,
    since the learn is collective)."""

    # a respawn that survives this long clears its worker's failure streak
    # (the exponential backoff below targets CRASH LOOPS, not one-off kills)
    _HEALTHY_S = 10.0

    def __init__(
        self, trainer, server, workers, env_cfg, stop, first_timeout,
        respawn_backoff_s: float = 0.5, respawn_backoff_cap_s: float = 30.0,
    ):
        self.trainer = trainer
        self.server = server
        self.workers = workers
        self.env_cfg = env_cfg
        self.stop = stop
        self.respawns = 0
        self._timeout = first_timeout
        # the steady starvation deadline must COVER the worker-silence
        # recovery window: a worker wedged waiting on a reply that will
        # never come (e.g. its step frame dropped on the wire) only
        # self-kills after worker_silence_s, and the respawn that refills
        # the chunk queue happens on our own supervise() pass after that —
        # a deadline shorter than the budget makes the sole-worker
        # recovery path unreachable (found by the chaos campaign:
        # transport.send drop_frame wedged seed_experience forever)
        self.steady_timeout = max(
            30.0, float(getattr(trainer, "worker_silence_s", 0.0)) * 1.5
        )
        self.last_chunk_age_s = 0.0  # queue dwell of the last chunk served
        # rolling queue-dwell samples for the per-hop latency percentiles
        # (the 'hops' telemetry event; appended by whichever thread runs
        # next_chunk — GIL-atomic, snapshot via list() on the reader)
        self.dwell_ms: deque = deque(maxlen=256)
        # exponential respawn backoff (satellite of ISSUE 5): a worker that
        # dies at startup used to respawn-loop hot — burning CPU on env
        # construction and flooding the server with hellos. The schedule
        # (immediate first respawn, base * 2^k capped, healthy-streak
        # reset) is the shared utils/respawn.py state machine — one
        # implementation for workers, experience shards, and inference
        # replicas.
        from surreal_tpu.utils.respawn import RespawnSchedule

        self._sched = RespawnSchedule(
            len(workers), respawn_backoff_s, respawn_backoff_cap_s,
            healthy_s=self._HEALTHY_S,
        )
        self.respawn_backoff_s = 0.0  # gauge: backoff set by the last respawn
        # supervision runs from the prefetch staging thread (empty-poll
        # waits) AND the trainer thread (drop path / post-learn): without
        # the lock both could respawn the same dead worker
        self._supervise_lock = threading.Lock()

    def supervise(self) -> None:
        """Workers are expendable (SURVEY.md §5.3: the reference delegated
        actor recovery to Kubernetes restart policies; here the trainer IS
        the supervisor): any dead worker is replaced in-place, under the
        backoff schedule above. Safe because workers are stateless — a
        fresh worker re-opens its DEALER socket under the same identity
        and the server's first message from it (obs-only) replaces the
        stale pending state without fabricating a transition.

        With a serving TIER (``server`` is an InferenceFleet) the same
        pass also supervises replicas, and a respawned worker routes via
        ``address_for`` — a worker whose replica died re-hellos to a
        SURVIVOR, not to the corpse's address."""
        if hasattr(self.server, "supervise"):
            self.server.supervise()
        with self._supervise_lock:
            now = time.monotonic()
            for i, w in enumerate(self.workers):
                if w.is_alive():
                    self._sched.note_alive(i, now)
                    continue
                if not self._sched.due(i, now):
                    continue  # backing off a crash-looping worker
                self.workers[i] = self.trainer._spawn_one(
                    i, self.env_cfg, self.server, self.stop
                )
                self.respawns += 1
                self.respawn_backoff_s = self._sched.respawned(i, now)

    def next_chunk(self) -> dict:
        deadline = time.monotonic() + self._timeout
        self._timeout = self.steady_timeout
        while True:
            if self.stop.is_set():
                # teardown: the staging thread must not sit out its full
                # chunk timeout against a closed server
                raise TimeoutError("data plane stopped") from None
            try:
                chunk = self.server.chunks.get(timeout=2.0)
                # queue-latency gauge: how long the chunk waited for the
                # learner (the server stamps _t_ready at assembly)
                self.last_chunk_age_s = time.monotonic() - chunk.pop(
                    "_t_ready", time.monotonic()
                )
                self.dwell_ms.append(self.last_chunk_age_s * 1e3)
                return chunk
            except queue.Empty:
                self.supervise()
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        "no experience chunks arriving from workers"
                    ) from None

    def close(self) -> None:
        self.stop.set()
        self.server.close()
        for w in self.workers:
            if hasattr(w, "terminate"):  # subprocess workers
                w.terminate()
                w.join(timeout=5)


class SEEDTrainer:
    def __init__(
        self,
        config,
        worker_mode: str | None = None,
        max_staleness: int | None | object = _FROM_CONFIG,
    ):
        # config is the user-facing path (session.topology.worker_mode,
        # learner.algo.max_staleness — both CLI-reachable via --set); the
        # constructor args override for tests/embedding
        if worker_mode is None:
            worker_mode = config.session_config.topology.get("worker_mode", "thread")
        if worker_mode not in ("thread", "process"):
            raise ValueError(f"worker_mode {worker_mode!r} not in thread|process")
        algo_name = config.learner_config.algo.name
        if algo_name == "ddpg":
            # the server stitches chunks from behavior-policy info (logp);
            # DDPG's deterministic actor has none — its disaggregated
            # topology is OffPolicyTrainer's host mode (replay-driven)
            raise ValueError(
                "SEEDTrainer supports on-policy learners (ppo, impala); "
                "for ddpg use OffPolicyTrainer (host mode)"
            )
        self.config = config
        from surreal_tpu.envs import make_env

        # build one env to read specs, then close (workers build their own)
        probe = make_env(config.env_config)
        self.specs = probe.specs
        probe.close()
        self.learner = build_learner(config.learner_config, self.specs)
        if getattr(self.learner, "requires_act_carry", False):
            # Design note (round-5 VERDICT item 5): trajectory policies DO
            # act over the wire now — via Agent.remote_act / eval --follow,
            # where one process owns one lockstep env batch and the K/V
            # carry lives client-side. The SEED server stays unsupported
            # deliberately: its micro-batches mix worker slices that
            # advance asynchronously, while the act carry keeps a single
            # scalar segment position for the whole batch (lockstep by
            # construction — SequenceActingMixin.act_init). Server-side
            # carry would need per-row positions, per-row wrap, and
            # gather/scatter of K/V rows per micro-batch composition —
            # a different (and recompile-heavy) design for no current user.
            raise ValueError(
                "model.encoder.kind='trajectory' is not supported by the "
                "SEED inference server (its micro-batches mix worker "
                "slices that advance asynchronously; the segment carry is "
                "lockstep). Trajectory policies act via the fused device "
                "collectors, the evaluator, `surreal_tpu actor`, and "
                "`eval --follow`."
            )
        self.algo = self.learner.config.algo
        topo = config.session_config.topology
        self.num_workers = max(1, topo.num_env_workers)
        self.worker_mode = worker_mode
        # host data plane (distributed/shm_transport.py). `.get` keeps
        # configs saved before the knobs existed loadable. 'auto' resolves
        # to pickle for thread workers (in-process tests keep the original
        # wire) and to shm negotiation for process workers, which are
        # always spawned on this host.
        self.transport = topo.get("transport", "auto")
        if self.transport not in ("auto", "shm", "pickle"):
            raise ValueError(
                f"topology.transport {self.transport!r} not in auto|shm|pickle"
            )
        self.worker_transport = (
            "pickle"
            if self.transport == "auto" and worker_mode == "thread"
            else self.transport
        )
        self.worker_silence_s = float(topo.get("worker_silence_s", 120.0))
        # sharded experience plane, FIFO chunk-relay arm (ISSUE 8,
        # surreal_tpu/experience/): trajectory chunks route inference
        # server -> ExperienceSender -> ReplayShardServer -> the staging
        # thread's ShardedSampler over the negotiated experience wire —
        # the cross-host seam that lets the learner group live on a
        # different host than the actor fleet's server. `.get` keeps old
        # configs loadable.
        xp = topo.get("experience_plane", None)
        self.experience_plane_enabled = bool(
            xp.get("enabled", False)
        ) if xp is not None else False
        # chaos harness: worker indices whose FIRST process spawn already
        # carried the fault plan (see _spawn_one's respawn note)
        self._fault_plan_sent: set[int] = set()
        # cross-process trace correlation: run() sets this from hooks
        # before the data plane spawns, so every worker (thread or
        # process) inherits the run-scoped trace id via spawn kwargs
        self._trace_id: str | None = None
        # ops plane (ISSUE 13): run() sets this from hooks before the
        # data plane spawns; every wire tier (fleet replicas, experience
        # shards, gateway) inherits the aggregator address the same way
        self._ops_address: str | None = None
        # causal tracing + lineage (ISSUE 14): run() points the span sink
        # at the hooks tracer and reads the telemetry.trace.* knobs; the
        # defaults keep embedders (the multi-host subclass sets only
        # _trace_id) span-free but lineage-stamped
        self._span_sink = None
        self._trace_sample_n = 0
        self._lineage = True
        n_envs = int(config.env_config.num_envs)
        # pipelined sub-slices halve the per-chunk batch width, so the
        # learn program compiles once per width: keep widths uniform (even
        # split only) and dp-divisible
        self.pipeline_workers = bool(topo.get("pipeline_workers", True)) and (
            n_envs >= 2 and n_envs % 2 == 0
        )
        dp_axis = int(topo.mesh.dp)
        if self.pipeline_workers and dp_axis > 1 and (n_envs // 2) % dp_axis:
            self.pipeline_workers = False
        if max_staleness is _FROM_CONFIG:
            # read the EXTENDED algo tree (build_learner layered per-algo +
            # base defaults onto it), not the raw user overrides
            max_staleness = self.algo.get("max_staleness", None)
        self.max_staleness = max_staleness

        # acting reuses the same state every serve: never donate.
        # precision: the learner's resolved policy (ops/precision.py)
        # lives inside act/learn — SEED's serve path and learn program
        # need no dtype forks; hooks records/validates the policy
        self._jit_act = jax.jit(
            self.learner.act, static_argnames="mode", donate_argnums=()
        )
        # multi-chip learner: an EXPLICIT dp axis (topology.mesh.dp > 1;
        # the -1 "use everything" default stays single-device here because
        # SEED batch width is set by num_envs, which must divide dp) runs
        # learn under shard_map with gradient psum — same dp_learn as the
        # fused trainers; acting stays one forward over replicated params.
        self.mesh = None
        dp = int(config.session_config.topology.mesh.dp)
        if dp > 1:
            from surreal_tpu.parallel.dp import dp_learn
            from surreal_tpu.parallel.mesh import check_dp_divisible, make_mesh

            check_dp_divisible(
                config.env_config.num_envs, dp, what="env_config.num_envs"
            )
            tp = max(1, int(config.session_config.topology.mesh.tp))
            if dp * tp > jax.device_count():
                raise ValueError(
                    f"topology.mesh dp={dp} tp={tp} asks for {dp * tp} "
                    f"devices but only {jax.device_count()} exist"
                )
            # an explicit dp may use a SUBSET of devices (the rest serve
            # inference/other work); make_mesh itself demands all devices
            self.mesh = make_mesh(
                config.session_config.topology,
                devices=jax.devices()[: dp * tp],
            )
            # donate=False: the inference server's act_fn closure aliases
            # the live train state and serves from it CONCURRENTLY with
            # the next learn — a donating learn would invalidate buffers
            # mid-serve (the multi-host SEED subclass acts from a separate
            # host-local copy, but shares this builder)
            self._learn = dp_learn(self.learner, self.mesh, donate=False)
        else:
            # NOT donated — same aliasing as above (see dp_learn's note)
            self._learn = jax.jit(self.learner.learn, donate_argnums=())
        # learner-group learn program (parallel/learner_group.py): SEED
        # has no sharded replay plane to partition, so elastic membership
        # does not apply here — but the group's gradient-all-reduce learn
        # is the SAME program, so topology.learner_group.members > 1
        # routes SEED's learn through it when mesh.dp did not already
        # claim the learn seam. SEED learners carry no per-row TD
        # bookkeeping; the synthetic priority/td_abs vector group_learn
        # threads for out-tree stability is popped before metrics ride
        # the stream.
        lg = config.session_config.topology.get("learner_group", None)
        lg_m = int(lg.get("members", 1)) if lg is not None else 1
        if self.mesh is None and lg_m > 1:
            from jax.sharding import Mesh

            from surreal_tpu.parallel.learner_group import group_learn
            from surreal_tpu.parallel.mesh import check_dp_divisible

            check_dp_divisible(
                config.env_config.num_envs, lg_m, what="env_config.num_envs"
            )
            if lg_m > jax.device_count():
                raise ValueError(
                    f"topology.learner_group members={lg_m} asks for "
                    f"{lg_m} devices but only {jax.device_count()} exist"
                )
            # batch_dim=1: SEED stages time-major [T, B, ...] chunks —
            # the group shards the env-batch dim, never the trajectory
            _group = group_learn(
                self.learner,
                Mesh(np.asarray(jax.devices()[:lg_m]), ("lg",)),
                batch_dim=1,
            )

            def _lg_learn(state, batch, key):
                state, metrics = _group(state, batch, key)
                metrics.pop("priority/td_abs", None)
                return state, metrics

            self._learn = _lg_learn

    def _spawn_one(self, i: int, env_cfg, route, stop):
        """Start env worker ``i`` as a thread or subprocess.

        ``route`` is the serving endpoint: a plain address string, or the
        server/fleet object — whose ``address_for(i)`` applies the
        session-affinity map (a fleet hashes workers over ALIVE replicas,
        so a respawn after a replica death lands on a survivor).

        Process mode uses the ``spawn`` start method: forking after jax/zmq
        have started threads is unsafe, and workers only need numpy + the
        host env anyway.
        """
        address = (
            route.address_for(i) if hasattr(route, "address_for") else route
        )
        kwargs = dict(
            transport=self.worker_transport,
            pipeline=self.pipeline_workers,
            server_silence_s=self.worker_silence_s,
            trace_id=self._trace_id,
        )
        if self.worker_mode == "process":
            import multiprocessing as mp

            # chaos harness: a spawned worker starts with an empty fault
            # registry — forward the plan so worker-site injections
            # (kill_worker, drop_frame, corrupt_slab) reach process mode
            # too; thread workers share this process's registry already.
            # FIRST spawn per index only: a respawned process would restart
            # its call counters at zero and re-fire one-shot faults forever
            # (a kill_worker injection must kill once, not crash-loop the
            # respawn path it exists to test)
            plan = faults.get().plan
            if plan and i not in self._fault_plan_sent:
                kwargs["fault_plan"] = plan
                self._fault_plan_sent.add(i)
            ctx = mp.get_context("spawn")
            w = ctx.Process(
                target=run_env_worker,
                args=(env_cfg.to_dict(), address, i),
                kwargs=kwargs,
                daemon=True,
            )
        else:
            w = threading.Thread(
                target=run_env_worker,
                args=(env_cfg, address, i),
                kwargs=dict(kwargs, stop_event=stop),
                daemon=True,
            )
        w.start()
        return w

    def _spawn_workers(self, env_cfg, route, stop):
        return [
            self._spawn_one(i, env_cfg, route, stop)
            for i in range(self.num_workers)
        ]

    def _start_data_plane(self, act_fn, stop, first_chunk_timeout: float):
        """Spawn the inference server + worker fleet and return a
        :class:`_DataPlane` handle — the shared lifecycle for the
        single-host and multi-host SEED loops (supervision, chunk waits,
        teardown live in ONE place)."""
        from surreal_tpu.launch.hooks import training_env_config

        topo = self.config.session_config.topology
        common = dict(
            unroll_length=self.algo.horizon,
            max_wait_ms=5.0,
            transport="pickle" if self.worker_transport == "pickle" else "auto",
            trace_id=self._trace_id,
            # robustness: nonfinite obs payloads (a corrupt slab slot, a
            # worker gone insane) are sanitized + counted rather than
            # poisoning the whole micro-batch. `.get` keeps old configs
            # loadable.
            sanitize_obs=bool(topo.get("sanitize_obs", True)),
            # ops plane: replicas push their own rows to the aggregator
            ops_address=self._ops_address,
            # causal trace exemplars + per-transition lineage stamps
            span_sink=self._span_sink,
            trace_sample_n=self._trace_sample_n,
            lineage=self._lineage,
        )
        # serving tier (ISSUE 10, distributed/fleet.py): >1 replica (or
        # autoscale on) runs the replicated fleet with session-affinity
        # routing and per-replica coalescing budgets; the single-server
        # path below stays byte-identical to the pre-tier behavior.
        fc = topo.get("inference_fleet", None)
        n_replicas = int(fc.get("replicas", 1)) if fc is not None else 1
        fleet_on = fc is not None and (
            n_replicas > 1 or bool(fc.get("autoscale", False))
        )
        if fleet_on:
            from surreal_tpu.distributed.fleet import InferenceFleet

            server = InferenceFleet(
                act_fn,
                num_workers=self.num_workers,
                replicas=n_replicas,
                min_replicas=int(fc.get("min_replicas", 1)),
                max_replicas=int(fc.get("max_replicas", 4)),
                autoscale=bool(fc.get("autoscale", False)),
                scale_up_serve_ms=float(fc.get("scale_up_serve_ms", 40.0)),
                scale_down_serve_ms=float(fc.get("scale_down_serve_ms", 5.0)),
                scale_cooldown_s=float(fc.get("scale_cooldown_s", 30.0)),
                respawn_backoff_s=float(fc.get("respawn_backoff_s", 0.5)),
                respawn_backoff_cap_s=float(
                    fc.get("respawn_backoff_cap_s", 30.0)
                ),
                **common,
            )
        else:
            server = InferenceServer(
                act_fn=act_fn,
                # coalesce all workers into one forward per lockstep
                # round: with min_batch=1 a W-worker fleet degrades to ~W
                # serves per round, and serve latency (not compute) is
                # the bound. auto_tune keeps this true as the fleet
                # shrinks/regrows (worker death, respawn) and scales the
                # coalescing wait to the serve-latency EWMA. (The fleet
                # installs per-REPLICA budgets from its affinity map.)
                min_batch=self.num_workers,
                auto_tune=True,
                **common,
            )
        try:
            env_cfg = self._worker_env_config(
                training_env_config(self.config.env_config)
            )
            workers = self._spawn_workers(env_cfg, server, stop)
        except BaseException:
            # a failed spawn must not leak the ROUTER socket + serve thread
            server.close()
            raise
        return _DataPlane(
            self, server, workers, env_cfg, stop, first_chunk_timeout,
            respawn_backoff_s=float(topo.get("respawn_backoff_s", 0.5)),
            respawn_backoff_cap_s=float(topo.get("respawn_backoff_cap_s", 30.0)),
        )

    def _worker_env_config(self, env_cfg):
        """Hook: per-rank seed decorrelation in the multi-host subclass."""
        return env_cfg

    def _make_act_fn(self, state, key_holder):
        def act_fn(obs_np):
            # pad the micro-batch to the next power of two: the server
            # coalesces a VARIABLE number of worker requests per forward,
            # and every distinct batch size is a fresh XLA compile — with
            # padding the compile count is log2-bounded and the steady
            # state reuses one cached executable
            n = obs_np.shape[0]
            padded = 1 << (n - 1).bit_length()
            if padded != n:
                obs_np = np.concatenate(
                    [obs_np, np.repeat(obs_np[-1:], padded - n, axis=0)], axis=0
                )
            key_holder[0], sub = jax.random.split(key_holder[0])
            actions, info = self._jit_act(state, obs_np, sub, mode="training")
            # one transfer for the whole result pytree: per-array np.asarray
            # would pay the host<->device round trip once per array
            actions, info = jax.device_get((actions, info))
            return actions[:n], {k: v[:n] for k, v in info.items()}

        return act_fn

    def run(
        self,
        max_env_steps: int | None = None,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        cfg = self.config.session_config
        total = max_env_steps or cfg.total_env_steps

        key = jax.random.key(cfg.seed)
        key, init_key, act_key = jax.random.split(key, 3)
        state = self.learner.init(init_key)
        # chaos harness: install (or RESET) the fault registry for this run
        faults.configure_from(cfg)
        self._fresh_init = lambda nonce: self.learner.init(
            jax.random.fold_in(init_key, nonce)
        )
        from surreal_tpu.launch.hooks import SessionHooks

        hooks = SessionHooks(self.config, self.learner)
        plane = None
        prefetch = None
        xplane = None
        gateway = None
        stop = threading.Event()
        try:
            state, iteration, env_steps = hooks.restore(state)
            if self.mesh is not None:
                from surreal_tpu.parallel.mesh import replicate_state

                state = replicate_state(self.mesh, state)
            hooks.begin_run(iteration, env_steps)
            key_holder = [act_key]
            # workers inherit the run-scoped trace id via spawn kwargs
            self._trace_id = hooks.trace_id
            self._ops_address = hooks.ops.address
            # causal tracing + lineage (ISSUE 14): the hooks tracer is
            # the one span sink for every tier in this process, and the
            # telemetry.trace.* knobs set the head-sampling rate
            self._span_sink = hooks.tracer
            self._trace_sample_n = hooks.trace_sample_n
            self._lineage = hooks.lineage_enabled
            # the FIRST chunk waits out the policy's XLA compiles plus a
            # full unroll of round trips; workers keep their own 120s
            # liveness budget per step,
            # reset by each served reply
            plane = self._start_data_plane(
                self._make_act_fn(state, key_holder), stop,
                first_chunk_timeout=600.0,
            )
            # cost accounting for the act closure: one policy forward at
            # the coalesced fleet width, padded to the power of two the
            # act_fn actually compiles for. No tracer phase times it (it
            # serves on the server thread), so it is recorded for diag
            # but excluded from the live MFU gauges.
            total_envs = self.num_workers * int(self.config.env_config.num_envs)
            padded = 1 << max(total_envs - 1, 0).bit_length()
            hooks.record_program_costs(
                "act", self._jit_act, state,
                jax.ShapeDtypeStruct(
                    (padded, *self.specs.obs.shape), self.specs.obs.dtype
                ),
                jax.random.fold_in(act_key, 0), mode="training",
                phase=None,
            )
            server = plane.server
            self._workers = plane.workers  # exposed for tests/fault injection

            # session gateway (ISSUE 12, gateway/): the tenant-facing
            # session tier in front of the serving fleet. Opt-in (the
            # training loop's own workers never route through it) and
            # fleet-only — it needs version-aware serve_act ingress.
            topo = self.config.session_config.topology
            gw_cfg = topo.get("gateway", None)
            if (
                gw_cfg is not None
                and bool(gw_cfg.get("enabled", False))
                and hasattr(server, "serve_act")
            ):
                from surreal_tpu.gateway import GatewayServer

                gateway = GatewayServer(
                    server,
                    bind=gw_cfg.get("bind", None),
                    max_sessions=int(gw_cfg.get("max_sessions", 256)),
                    lease_s=float(gw_cfg.get("lease_s", 30.0)),
                    tenant_quotas=gw_cfg.get("tenant_quotas", None),
                    act_cache=int(gw_cfg.get("act_cache", 256)),
                    pin_versions=bool(gw_cfg.get("pin_versions", True)),
                    # the hooks-owned ParameterFanout: session pins also
                    # hold the pinned version's full frame publisher-side
                    fanout=hooks.fanout,
                    trace_id=hooks.trace_id,
                    respawn_backoff_s=float(
                        gw_cfg.get("respawn_backoff_s", 0.5)
                    ),
                    respawn_backoff_cap_s=float(
                        gw_cfg.get("respawn_backoff_cap_s", 30.0)
                    ),
                    ops_address=hooks.ops.address,
                    # head-sampled gateway.act root spans for sessions
                    # that negotiated the "trace" cap
                    span_sink=self._span_sink,
                    trace_sample_n=self._trace_sample_n,
                )
                self._gateway = gateway  # exposed for tests
                hooks.log.info("session gateway live at %s", gateway.address)
                # discovery file: how an external tenant finds — and
                # RE-finds, after a cold restart rebinds the port — the
                # live gateway (the param_server.json idiom: atomic
                # tmp+rename, pollers race this write). Unlinked at
                # close so a stale file never points tenants at a dead
                # endpoint; surviving a SIGKILL is fine, the relaunch
                # overwrites it before tenants can re-attach.
                import json as _json
                import os as _os

                gw_discovery = _os.path.join(
                    self.config.session_config.folder, "gateway.json"
                )
                tmp = gw_discovery + ".tmp"
                with open(tmp, "w") as f:
                    _json.dump(
                        {"address": gateway.address,
                         "lease_s": float(gw_cfg.get("lease_s", 30.0))},
                        f,
                    )
                _os.replace(tmp, gw_discovery)

            # experience-plane chunk relay (FIFO arm): a relay thread
            # ships every assembled chunk through the ExperienceSender;
            # the staging thread below pops from the shard tier instead
            # of the server's in-process queue. Locally this is a
            # loop-through; across hosts it is the learner-group seam.
            if self.experience_plane_enabled:
                from surreal_tpu.experience import ExperiencePlane

                topo = self.config.session_config.topology
                xplane = ExperiencePlane(
                    kind="fifo",
                    cfg=topo.get("experience_plane", None),
                    trace_id=hooks.trace_id,
                    ops_address=hooks.ops.address,
                )

                def relay_chunks():
                    while not stop.is_set():
                        try:
                            chunk = server.chunks.get(timeout=0.5)
                        except queue.Empty:
                            continue
                        chunk = dict(chunk)
                        chunk.pop("_t_ready", None)
                        # chunk METADATA (not a wire column): an adopted
                        # exemplar ends its tree at the relay hop here —
                        # the lineage COLUMNS still cross the wire as
                        # ordinary spec fields
                        ex = chunk.pop("_exemplar", None)
                        if ex is not None and self._span_sink is not None:
                            from surreal_tpu.session.telemetry import (
                                TraceContext,
                            )

                            self._span_sink.emit_span(
                                "xplane.relay",
                                TraceContext(
                                    ex["exemplar"],
                                    self._span_sink.next_span_id(),
                                    ex["parent"],
                                ),
                                tier="experience",
                            )
                        try:
                            xplane.sender.send_chunk(chunk)
                        except Exception as e:
                            # Prefetcher's discipline: a producer error is
                            # re-raised to the consumer — a silently dead
                            # relay would present as a misleading pop
                            # timeout with the root cause lost
                            relay_error.append(e)
                            return

                relay_error: list[Exception] = []
                relay_thread = threading.Thread(
                    target=relay_chunks, daemon=True, name="xp-relay"
                )
                relay_thread.start()

            # closed-loop remediation (ISSUE 16): hand the hooks-owned
            # engine its actuator surfaces now that every tier exists.
            hooks.bind_remediation_actuators(
                fleet=server if hasattr(server, "scale_up") else None,
                admission=getattr(gateway, "admission", None),
                restart={
                    k: v for k, v in {
                        "workers": plane.supervise,
                        "fleet": getattr(server, "supervise", None),
                        "gateway": (
                            gateway.supervise if gateway is not None
                            else None
                        ),
                        "experience": (
                            xplane.supervise if xplane is not None
                            else None
                        ),
                    }.items() if v is not None
                },
            )

            def next_chunk_from_xplane():
                """Pop one chunk from the shard tier, supervising BOTH
                planes while waiting (mirrors _DataPlane.next_chunk's
                contract: a dead sole worker or shard must be respawned
                while we wait, not after)."""
                deadline = time.monotonic() + plane._timeout
                plane._timeout = plane.steady_timeout
                while True:
                    if stop.is_set():
                        raise TimeoutError("data plane stopped") from None
                    if relay_error:
                        raise RuntimeError(
                            "experience-plane relay thread died"
                        ) from relay_error[0]
                    got = xplane.sampler.pop_chunk(timeout_s=2.0)
                    if got is not None:
                        rows, _n = got
                        return rows
                    plane.supervise()
                    xplane.supervise()
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            "no experience chunks arriving through the "
                            "experience plane"
                        ) from None

            # double-buffered staging (learners/prefetch.py): the staging
            # thread waits on the chunk queue AND pays the host->device
            # transfer for chunk k+1 while the learner crunches chunk k —
            # with the dp-committed sharding, so the jitted learn never
            # reshards. param_version stays HOST-side (the staleness
            # decision needs it before any device work would be useful).
            from surreal_tpu.learners.prefetch import Prefetcher

            def stage_next_chunk():
                chunk = (
                    next_chunk_from_xplane() if xplane is not None
                    else plane.next_chunk()
                )
                versions = chunk.pop("param_version")
                # lineage stamps and the adopted exemplar stay HOST-side
                # (the staleness/provenance decisions need them before
                # any device work; the transfer-guard proves the lineage
                # reduction adds no device->host syncs)
                lineage = chunk.pop("lineage", None)
                exemplar = chunk.pop("_exemplar", None)
                n_steps = int(
                    chunk["reward"].shape[0] * chunk["reward"].shape[1]
                )
                with hooks.tracer.span("h2d-transfer"):
                    if self.mesh is not None:
                        # split host->devices directly along the dp-sharded
                        # batch dim; a plain device_put would commit the
                        # whole chunk to device 0 and reshard inside the jit
                        from surreal_tpu.parallel.mesh import batch_sharded

                        batch = jax.device_put(
                            chunk, batch_sharded(self.mesh, batch_dim=1)
                        )
                    else:
                        batch = jax.device_put(chunk)
                return batch, versions, n_steps, lineage, exemplar

            prefetch = Prefetcher(stage_next_chunk, name="seed-stage")

            dropped_stale = 0
            discarded_steps = 0
            dp_event_emitted = False
            learn_ms: deque = deque(maxlen=256)  # learn-hop samples
            # exact per-update staleness from the per-transition acting
            # versions (ISSUE 14): host-side numpy reduction, replacing
            # the ops plane's fanout-vs-fleet approximation
            from surreal_tpu.session.telemetry import (
                LineageReducer,
                TraceContext,
            )

            lineage_reducer = LineageReducer()

            def data_plane_extras() -> dict:
                """One source of truth for the drop/eviction/episode
                accounting, used for every in-loop metrics row AND the
                run-end reconciliation (keeping the two in lockstep)."""
                return {
                    "staleness/dropped_chunks": float(dropped_stale),
                    "staleness/steps_discarded": float(discarded_steps),
                    "workers/respawns": float(plane.respawns),
                    "workers/respawn_backoff_s": float(plane.respawn_backoff_s),
                    "server/chunk_age_s": float(plane.last_chunk_age_s),
                    **server.queue_stats(),
                    **(server.episode_stats() or {}),
                }

            # the SEED collect stage is ALWAYS overlapped: workers stream
            # chunks into the server queue regardless of the engine knob
            stages = (
                StageSpec("collect", donate=False, overlap=True),
                StageSpec("learn", donate=False),
            ) + sideband_stages()
            ls = LoopState(
                state=state, key=key, iteration=iteration,
                env_steps=env_steps,
            )

            def step(ls):
                nonlocal dropped_stale, discarded_steps, dp_event_emitted
                with hooks.tracer.span("chunk-wait"):
                    batch, versions, n_steps, lineage, exemplar = (
                        prefetch.get()
                    )
                staleness = server.version - int(versions.min())
                # Accounting contract: trainer-side stale DROPS count into
                # env_steps (deterministic, the trainer chose to discard);
                # server-side queue EVICTIONS are surfaced as
                # server/evicted_* metrics but NOT folded into the budget —
                # they spike during the learner's first compiles, and
                # folding them would make run length race against XLA
                # compile time (observed: the respawn fault-injection test's
                # budget consumed before the supervisor could act).
                if self.max_staleness is not None and staleness > self.max_staleness:
                    # acted by a too-old policy: drop, don't train. The
                    # steps DID happen — count them, and keep supervising
                    # workers (a streak of stale chunks must not pause
                    # respawn or stretch wall-clock past the step budget).
                    # The prefetcher already paid this chunk's transfer —
                    # a bounded waste (drops are the exception path). The
                    # engine's skip path counts the steps, runs no
                    # boundary, and still honors the interrupt latch (a
                    # preemption must not sit out a stale streak).
                    dropped_stale += 1
                    discarded_steps += n_steps
                    plane.supervise()
                    return Outcome(
                        metrics=None, hook_key=None, steps=n_steps,
                        skip_boundary=True,
                    )
                ls.key, lkey, hk_key = jax.random.split(ls.key, 3)
                t_learn0 = time.perf_counter()
                with hooks.tracer.span("learn"):
                    ls.state, metrics = self._learn(ls.state, batch, lkey)
                learn_ms.append((time.perf_counter() - t_learn0) * 1e3)
                if exemplar is not None:
                    # the adopted exemplar's final hop: THIS learn step
                    # consumed the chunk the replica stamped — the tree
                    # now spans gateway/worker -> replica -> learner
                    hooks.tracer.emit_span(
                        "learn.dispatch",
                        TraceContext(
                            exemplar["exemplar"],
                            hooks.tracer.next_span_id(),
                            exemplar["parent"],
                        ),
                        tier="learner",
                        dur_ms=learn_ms[-1],
                        version=int(server.version),
                    )
                # cost accounting, first learn only (idempotent; needs a
                # representative staged chunk to lower)
                hooks.record_program_costs(
                    "learn", self._learn, ls.state, batch, lkey,
                    phase="learn",
                )
                with hooks.tracer.span("param-publish"):
                    server.set_act_fn(
                        self._make_act_fn(ls.state, key_holder)
                    )
                plane.supervise()
                if gateway is not None:
                    gateway.supervise()
                if not dp_event_emitted:
                    # negotiated data-plane shape, once the fleet settled
                    # (visible in `surreal_tpu diag` without a metrics row)
                    hooks.data_plane_event(
                        transport=self.worker_transport,
                        pipeline=self.pipeline_workers,
                        workers=self.num_workers,
                        **server.transport_stats(),
                    )
                    dp_event_emitted = True
                metrics = dict(
                    metrics,
                    **{"staleness/updates_behind": float(staleness)},
                    # exact per-update staleness distribution + the span
                    # counters; the ops plane's SLO staleness objective
                    # prefers the lineage gauges over its derived
                    # fanout-vs-fleet approximation when they are present
                    **(
                        lineage_reducer.reduce(server.version, versions)
                        if self._lineage else {}
                    ),
                    **(
                        hooks.tracer.trace_gauges()
                        if self._trace_sample_n > 0 else {}
                    ),
                    **data_plane_extras(),
                    # cached (last-cadence) plane gauges: the wire poll
                    # happens at the cadence (post_metrics), not per
                    # iteration
                    **(xplane.gauges(poll=False) if xplane is not None else {}),
                    **(gateway.gauges() if gateway is not None else {}),
                )

                def post_metrics(m_row):
                    # per-hop latency percentiles ride the metrics cadence
                    # (host-side deques only — no device work)
                    hooks.tracer.event(
                        "hops", **hop_event(server, plane, learn_ms, gateway)
                    )
                    if hasattr(server, "maybe_autoscale"):
                        # serving tier: one scale decision per cadence
                        # (cooldown-bounded, driven by the serve-latency
                        # EWMA) + the per-replica telemetry snapshot
                        server.maybe_autoscale()
                        hooks.serving_event(**server.tier_event())
                    if gateway is not None:
                        hooks.gateway_event(**gateway.event())
                    if xplane is not None:
                        xplane._poll_stats()
                        hooks.experience_event(**xplane.telemetry_event())

                return Outcome(
                    metrics=metrics, hook_key=hk_key, steps=n_steps,
                    post_metrics=post_metrics,
                )

            def apply_fault(ls, f):
                ls.state = faults.apply_trainer_fault(f, ls.state)

            def on_rollback(ls):
                rb = hooks.recovery.rollback(ls.state, fresh=self._fresh_init)
                ls.state, ls.iteration, ls.env_steps = (
                    rb.state, rb.iteration, rb.env_steps
                )
                if self.mesh is not None:
                    from surreal_tpu.parallel.mesh import replicate_state

                    ls.state = replicate_state(self.mesh, ls.state)
                # the live act closure aliases the poisoned state:
                # re-arm acting from the restored one immediately (the
                # version bump also marks in-flight chunks stale)
                server.set_act_fn(self._make_act_fn(ls.state, key_holder))
                ls.key = jax.random.fold_in(ls.key, rb.nonce)

            engine = LoopEngine(
                hooks, total, step, stages,
                EngineConfig.from_session(self.config.session_config),
                on_metrics=on_metrics, apply_fault=apply_fault,
                on_rollback=on_rollback,
            )
            ls = engine.run(ls)
            state, iteration, env_steps = ls.state, ls.iteration, ls.env_steps
            # the drop path consumes budget without firing the metrics
            # cadence; reconcile the trailing snapshot with reality (only
            # when it actually trails — an unconditional flush would
            # duplicate the final writer row at every_n_iters=1)
            if hooks.last_metrics.get("time/env_steps") != env_steps:
                hooks.final_metrics(env_steps, data_plane_extras())
            if dp_event_emitted:
                # settled end-of-run gauges (bytes/step over the whole run)
                hooks.data_plane_event(
                    transport=self.worker_transport,
                    pipeline=self.pipeline_workers,
                    workers=self.num_workers,
                    **server.transport_stats(),
                )
            hooks.final_checkpoint(iteration, env_steps, state)
            return state, hooks.last_metrics
        finally:
            stop.set()
            if prefetch is not None:
                prefetch.close()
            if xplane is not None:
                # quiesce the relay first (the driver stop is already
                # set) so the close accounting reads a settled ledger; a
                # relay wedged in a bounded sender wait is unblocked by
                # the plane stop below and the accounting marked
                # unquiesced (the chaos exactly-once oracle then skips
                # strict conservation for this run)
                relay_thread.join(timeout=5)
                try:
                    hooks.tracer.event(
                        "experience_close",
                        quiesced=float(not relay_thread.is_alive()),
                        **xplane.accounting(),
                    )
                except Exception:
                    hooks.log.warning(
                        "experience_close accounting failed", exc_info=True
                    )
                # unblock any remaining bounded sender waits and JOIN
                # before close() touches the DEALER sockets the relay
                # shares (zmq sockets are not thread-safe)
                xplane._stop.set()
                relay_thread.join(timeout=5)
                xplane.close()
            if gateway is not None:
                # sessions die with the run; close BEFORE the fleet so the
                # gateway never serves into torn-down replicas
                gateway.close()
                import os as _os

                try:
                    _os.unlink(_os.path.join(
                        self.config.session_config.folder, "gateway.json"
                    ))
                except OSError:
                    pass  # best-effort: never written, or already gone
            if plane is not None:
                plane.close()
            hooks.close()
