"""Session-services hooks shared by every training driver: metrics writing,
periodic checkpoint with keep-best, periodic eval, restore/auto-resume, and
on-demand profiler captures with their digest.

Parity map (SURVEY.md §3.4 learner loop + §2.1): the reference's learner
main loop interleaved ``tensorplex scalars``, ``PeriodicCheckpoint.save()``
and parameter publishing, while separate eval processes scored checkpoints
(§3.5) — here those side-bands are one :class:`SessionHooks` object called
once per iteration from Trainer / OffPolicyTrainer / SEEDTrainer, so the
three drivers cannot drift in their observability behavior.

Restore semantics (§5.3/§5.4): ``checkpoint.restore_from`` names another
session folder to warm-start from (the reference's ``restore_folder``);
``checkpoint.auto_resume`` (default on) resumes from this session's own
latest checkpoint when present — which is the whole failure-recovery
story: a killed job relaunched with the same config continues its curve.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time

import jax

from surreal_tpu.launch.recovery import RecoveryManager
from surreal_tpu.session.checkpoint import CheckpointManager, make_checkpoint_manager
from surreal_tpu.session.config import Config
from surreal_tpu.session.costs import CostAccountant
from surreal_tpu.session.interrupt import InterruptSentinel
from surreal_tpu.session.metrics import get_logger, make_metrics_writer
from surreal_tpu.session.opsplane import OpsAggregator
from surreal_tpu.session.profile import ProfileManager
from surreal_tpu.session.telemetry import Tracer, launch_close, launch_span
from surreal_tpu.session.tracker import PeriodicTracker
from surreal_tpu.utils import faults
from surreal_tpu.utils.compat import compile_cache_counts, enable_compile_cache


def _launch_session(method):
    """The call is a ``launch.session`` span of the launch record
    (session/telemetry.py) while the session's launch is open: here, so
    that every driver has it."""

    @functools.wraps(method)
    def spanned(self, *args, **kwargs):
        with self._launch_span("launch.session"):
            return method(self, *args, **kwargs)

    return spanned


class SessionHooks:
    """One per training run. Driver contract:

        hooks = SessionHooks(config, learner)
        try:
            state, it, steps = hooks.restore(state)    # once, before the loop
            hooks.begin_run(it, steps)
            while ...:
                ...train...
                m, stop = hooks.end_iteration(
                    it, steps, state, key, metrics, on_metrics
                )
                if stop: break
            hooks.final_checkpoint(it, steps, state)
        finally:
            hooks.close()

    ``end_iteration`` owns the metrics cadence: it syncs device scalars to
    host floats only when ``metrics.every_n_iters`` fires (keeping the hot
    loop async), fires eval/checkpoint/profiler on their own cadences, and
    forwards fired metrics to the caller's ``on_metrics``.
    """

    # until the first metrics-sync ends (or close(), for a run that ended
    # before): the launch record's spans are open to this session, and
    # closing it writes the session's one ``launch`` event
    _launching = True

    def _launch_span(self, name: str):
        return launch_span(name) if self._launching else contextlib.nullcontext()

    def _end_launch(self, closed: bool) -> None:
        self._launching = False
        launch_close(self.tracer, closed)

    @_launch_session
    def __init__(self, config, learner, name: str = "train"):
        self.config = config
        cfg = config.session_config
        os.makedirs(cfg.folder, exist_ok=True)
        self.log = get_logger(name, cfg.folder)
        self.writer = make_metrics_writer(cfg, name=name)
        # telemetry spine: span tracing + JSONL event log under
        # <folder>/telemetry/ (session/telemetry.py). Drivers record their
        # phase spans through hooks.tracer so Trainer / OffPolicyTrainer /
        # SEEDTrainer / the multi-host drivers cannot drift; hooks itself
        # spans its own side-bands (metrics-sync, publish, eval,
        # checkpoint) below. `.get` keeps configs saved before the knob
        # existed loadable.
        tel = cfg.get("telemetry", None)
        # causal tracing + lineage knobs (ISSUE 14): telemetry.trace.*
        # sets the exemplar head-sampling rate (1-in-N per stream; 0
        # disables span emission) and how many recent exemplars ride a
        # flight-recorder dump; telemetry.lineage toggles the
        # per-transition provenance stamps (on by default — the exact
        # staleness distribution depends on them)
        trace_cfg = tel.get("trace", None) if tel is not None else None
        self.trace_sample_n = int(
            trace_cfg.get("sample_n", 64) if trace_cfg is not None else 64
        )
        trace_keep = int(
            trace_cfg.get("keep", 8) if trace_cfg is not None else 8
        )
        self.lineage_enabled = bool(
            tel.get("lineage", True) if tel is not None else True
        )
        self.tracer = Tracer(
            cfg.folder,
            enabled=bool(tel.enabled) if tel is not None else True,
            name=name,
            # size-based JSONL rotation (ISSUE 13 satellite): a week-long
            # run must not grow events.jsonl without bound
            max_log_mb=tel.get("max_log_mb", None) if tel is not None else None,
            trace_sample_n=self.trace_sample_n,
            trace_keep=trace_keep,
        )
        # cross-process trace correlation: the run-scoped trace id every
        # telemetry event carries; spawned env workers / the inference
        # server / param clients inherit it (session/telemetry.py)
        self.trace_id = self.tracer.trace_id
        # cost/MFU accounting (session/costs.py): drivers register their
        # jitted hot programs via record_program_costs; the perf/* gauges
        # ride the metrics cadence in end_iteration below. The learner's
        # precision policy stamps every program_cost record so artifacts
        # carry per-policy rows (ops/precision.py).
        self.costs = CostAccountant(
            cfg, on_event=self.tracer.event, log=self.log,
            policy=getattr(learner, "policy", None),
        )
        # persistent XLA compile cache (utils/compat.py decides where):
        # on before the driver's first hot program compiles
        self.compile_cache_dir = enable_compile_cache()
        self._cache_counts: dict | None = None  # the last ones written
        if self.compile_cache_dir is not None:
            self.log.info(
                "persistent compile cache at %s", self.compile_cache_dir
            )
        self.ckpt: CheckpointManager | None = make_checkpoint_manager(
            cfg, on_event=self.tracer.event
        )
        # precision: the learner's resolved policy (ops/precision.py) —
        # recorded into checkpoint run metadata (restore fails loudly on
        # a policy mismatch), emitted as a 'precision' telemetry event in
        # begin_run, and rendered by `surreal_tpu diag`'s Performance
        # section
        pol = getattr(learner, "policy", None)
        self.precision = pol
        self._precision_meta = pol.meta() if pol is not None else None
        self._ckpt_every = PeriodicTracker(max(1, cfg.checkpoint.every_n_iters))
        # robustness layer (ISSUE 5): the preemption sentinel latches
        # SIGTERM/SIGINT and end_iteration turns it into a stop at the
        # next boundary — the driver's normal final checkpoint then IS the
        # emergency checkpoint, at most one iteration behind the signal.
        # The recovery manager is the divergence-guard policy on PR 1's
        # in-graph health/* signals (launch/recovery.py). `.get` keeps
        # configs saved before the knobs existed loadable.
        rec = cfg.get("recovery", None)
        self.interrupt = InterruptSentinel(
            enabled=bool(rec.get("interrupt", True)) if rec is not None else True
        )
        self.recovery = RecoveryManager(config, self.ckpt, self.tracer, self.log)
        # live ops plane (ISSUE 13): the run-scoped cross-tier aggregator.
        # Wire tiers (gateway, fleet replicas, experience shards) push
        # into ``ops.address`` — process tiers inherit it through spawn
        # kwargs like the trace id; learner-thread tiers land through
        # push_local below. ``snapshot()`` rides the metrics cadence.
        self.ops = OpsAggregator(
            cfg.folder, trace_id=self.trace_id,
            cfg=cfg.get("ops", None), slo_cfg=cfg.get("slo", None),
            on_event=self.tracer.event,
        )
        # the last-K causal exemplar span trees ride every flightrec
        # dump (ISSUE 14): a post-mortem sees individual request paths
        # from the minutes before the incident, not just gauges
        self.ops.flightrec.exemplar_source = self.tracer.recent_exemplar_spans
        self._interrupt_logged = False
        # optional step-aligned auxiliary state (the off-policy trainer
        # sets this to snapshot its replay buffer when
        # checkpoint.include_replay is on); zero-arg callable -> pytree
        self.extra_state_fn = None

        self.evaluator = None
        ev = cfg.eval
        if ev.every_n_iters and ev.every_n_iters > 0 and ev.episodes > 0:
            from surreal_tpu.launch.evaluator import Evaluator

            self.evaluator = Evaluator(config.env_config, ev, learner)
            self._eval_every = PeriodicTracker(ev.every_n_iters)
        if self.ckpt is not None:
            self.ckpt.best_key = (
                "eval/return" if self.evaluator else "episode/return"
            )

        # live parameter publishing (reference §3.4: the learner published
        # every publish_interval; external actors/evals attach to the run).
        # Multi-host drivers construct hooks on rank 0 only, so publishing
        # is single-controller for free.
        self._publisher = None
        self._param_server = None
        self._fanout = None
        pub = cfg.get("publish", None)
        if pub is not None and pub.enabled:
            from surreal_tpu.agents import make_agent
            from surreal_tpu.distributed.param_service import (
                ParameterPublisher,
                ParameterServer,
            )

            self._pub_agent = make_agent(learner)
            self._publisher = ParameterPublisher()
            # on_event: fetch requests carry a client span id; the server
            # mirrors each serve into the telemetry spine so diag's
            # cross-process timeline covers the param-service hop too
            self._param_server = ParameterServer(
                self._publisher.address, bind=pub.bind,
                on_event=self.tracer.event,
            )
            # parameter fanout (ISSUE 10, distributed/param_fanout.py):
            # versioned weight FRAMES over pub/sub — one encode + N
            # subscribes instead of N full-pytree fetch pickles, with
            # delta/bf16 wire arms. The publisher/server pair above STAYS
            # as the fallback/late-joiner fetch path. `.get` keeps old
            # configs loadable.
            fan = pub.get("fanout", None)
            if fan is not None and fan.get("enabled", False):
                from surreal_tpu.distributed.param_fanout import ParameterFanout

                self._fanout = ParameterFanout(
                    wire=str(fan.get("wire", "f32")),
                    delta=bool(fan.get("delta", True)),
                    ack_ttl_s=float(fan.get("ack_ttl_s", 60.0)),
                )
            self._pub_every = PeriodicTracker(max(1, pub.every_n_iters))
            # discovery file: how `surreal_tpu actor` / `eval --follow`
            # find a live session without the operator copying ports
            # around. Written atomically (tmp + rename): pollers race this
            # write, and a half-written json would crash them mid-read.
            import json

            self._discovery_path = os.path.join(cfg.folder, "param_server.json")
            tmp_path = self._discovery_path + ".tmp"
            discovery = {
                "addresses": self._param_server.addresses,
                "publisher": self._publisher.address,
            }
            if self._fanout is not None:
                discovery["fanout"] = self._fanout.address
                discovery["fanout_ack"] = self._fanout.ack_address
            with open(tmp_path, "w") as f:
                json.dump(discovery, f)
            os.replace(tmp_path, self._discovery_path)
            self.log.info(
                "parameter server live at %s (publish every %d iters)",
                self._param_server.addresses, self._pub_every.period,
            )

        # on-demand profiling (session/profile.py): trigger-file captures,
        # request() and the slow-iteration auto-trigger share one boundary
        # tick; each capture is reduced to a digest with the op -> phase,
        # part, sub-scope and kernel maps of the programs the cost
        # accountant registered
        self.profile = ProfileManager(
            cfg, cfg.folder, self.tracer, self.log,
            labels=self.costs.labels,
            # a digest's parse holds this thread too: not its tiers' silence
            on_hold=self.ops.excuse_pause,
        )
        # watchdog & incident engine (ISSUE 15): detector sweeps over each
        # merged ops snapshot, firings correlated into root-caused
        # incident records under telemetry/incidents/ (`surreal_tpu why`).
        # Both are pure host arithmetic at the metrics cadence.
        wd_cfg = cfg.get("watchdog", None)
        self.watchdog = None
        self.incidents = None
        if wd_cfg is None or wd_cfg.get("enabled", True):
            from surreal_tpu.session.incidents import IncidentEngine
            from surreal_tpu.session.watchdog import Watchdog

            self.watchdog = Watchdog(cfg=wd_cfg)
            self.incidents = IncidentEngine(
                folder=cfg.folder,
                cfg=wd_cfg,
                on_event=self.tracer.event,
                profile=self.profile,
                flightrec=self.ops.flightrec,
                exemplar_source=self.tracer.recent_exemplar_spans,
                trace_id=self.trace_id,
            )
        # closed-loop remediation (ISSUE 16): the incident stream's top
        # cause mapped to ONE bounded, journaled, counter-detected action
        # per sweep. Rides the incident engine (no incidents, nothing to
        # remediate); actuators are bound later by the driver
        # (bind_remediation_actuators) once the fleet/gateway exist.
        self.remediate = None
        rem_cfg = cfg.get("remediate", None)
        if self.incidents is not None and (
            rem_cfg is None or rem_cfg.get("enabled", True)
        ):
            from surreal_tpu.session.remediate import RemediationEngine

            self.remediate = RemediationEngine(
                folder=cfg.folder,
                cfg=rem_cfg,
                incidents=self.incidents,
                on_event=self.tracer.event,
                trace_id=self.trace_id,
            )
        self._last_eval: dict[str, float] = {}
        self._last_train: dict[str, float] = {}
        self._metrics_every = PeriodicTracker(max(1, cfg.metrics.every_n_iters))
        # (host clock, iteration) at the end of the last metrics-sync: the
        # fenced `cadence` phase runs from there to the end of the next
        self._sync_end: tuple[float, int] | None = None
        self._t0 = None
        self._steps0 = 0

    @property
    def fanout(self):
        """The live :class:`ParameterFanout` (None unless
        ``publish.fanout.enabled``) — the gateway's publisher-side
        pinned-version holds need it."""
        return self._fanout

    @property
    def last_metrics(self) -> dict[str, float]:
        """Latest synced train metrics merged with latest eval metrics."""
        return {**self._last_train, **self._last_eval}

    def bind_remediation_actuators(self, **surfaces) -> None:
        """Hand the remediation engine its actuator surfaces (fleet,
        admission, restart map, learner group) once the
        driver has built them — no-op when remediation is off. See
        :meth:`RemediationEngine.bind_actuators`."""
        if self.remediate is not None:
            self.remediate.bind_actuators(**surfaces)

    def data_plane_event(self, **info) -> None:
        """Record the SEED data plane's negotiated shape (transport mix,
        pipeline occupancy, wire bytes/step) as one log line + one
        telemetry ``data_plane`` event — `surreal_tpu diag` surfaces the
        last one, so a session folder answers "did shm actually engage?"
        without grepping metrics rows."""
        self.log.info(
            "data plane: %s",
            " ".join(f"{k}={v}" for k, v in sorted(info.items())),
        )
        self.tracer.event("data_plane", **info)

    def serving_event(self, **info) -> None:
        """Record the serving tier's per-replica snapshot (replica
        liveness/budgets/serve latency, scale decisions) as one telemetry
        ``serving_tier`` event per metrics row — ``surreal_tpu diag``'s
        "Serving tier" section renders the last one."""
        self.tracer.event("serving_tier", **info)
        # the merged fleet view is a learner-thread tier: no wire hop.
        # (per-replica liveness rides each replica's OWN wire row.)
        self.ops.push_local("fleet", body=info)

    def gateway_event(self, **info) -> None:
        """Record the session gateway's tenant-facing snapshot (sessions,
        admission counters, cache hit-rate, pinned versions) as one
        telemetry ``gateway`` event per metrics row — ``surreal_tpu
        diag``'s "Gateway" section renders the last one."""
        self.tracer.event("gateway", **info)

    def experience_event(self, **info) -> None:
        """Record the experience plane's settled shape (shard transports,
        per-shard fill/ingest, wire bytes/step, sample-wait) as one
        telemetry ``experience_plane`` event per metrics row —
        ``surreal_tpu diag``'s "Experience plane" section renders the
        last one plus the per-hop sender->shard->learner percentiles."""
        self.tracer.event("experience_plane", **info)
        self.ops.push_local("experience", body=info)

    def learner_group_event(self, **info) -> None:
        """Journal one learner-group membership transition (join/leave/
        member_failed/respawn/handoff with the shard assignment) as a
        telemetry ``learner_group`` event — the elastic-membership audit
        trail the chaos tests and post-mortems read."""
        self.log.info(
            "learner group: %s",
            " ".join(f"{k}={v}" for k, v in sorted(info.items())),
        )
        self.tracer.event("learner_group", **info)

    def record_program_costs(
        self, name: str, jitted, *args,
        phase: str | None = None, calls_per_phase: int = 1, **kwargs,
    ) -> None:
        """Register one jitted hot program with the cost accountant
        (idempotent per name — host-loop drivers call it after their
        first learn, when a representative batch exists). ``phase`` names
        the tracer phase whose window times this program; programs with
        no dedicated phase (the SEED act closure) pass None and are
        recorded for diag without contributing to the live gauges.
        Host-side work only (lower + HLO cost pass): safe before the
        first dispatch and on donated-arg programs. While the launch is
        open it is the span ``launch.cost_record``, whose counters say
        what this lowering and its cache read took beside the first
        dispatch's own."""
        with self._launch_span("launch.cost_record"):
            self.costs.record_program(
                name, jitted, *args,
                phase=phase, calls_per_phase=calls_per_phase, **kwargs,
            )

    def final_metrics(self, env_steps: int, extras=None) -> None:
        """Refresh the trailing metrics snapshot at run end. Drivers whose
        loop can consume env-step budget WITHOUT a metrics-cadence fire
        (the SEED drop path discards stale chunks but counts their steps)
        call this so ``last_metrics``/the writer reflect where the run
        actually ended, not the last learn."""
        m = dict(self._last_train)
        m.update({k: float(v) for k, v in (extras or {}).items()})
        m["time/env_steps"] = env_steps
        m["time/env_steps_per_s"] = (env_steps - self._steps0) / max(
            time.time() - (self._t0 or time.time()), 1e-9
        )
        self._last_train = m
        self.writer.write(env_steps, m)
        self.tracer.log_metrics(env_steps, m)

    # -- restore -------------------------------------------------------------
    @_launch_session
    def restore(self, init_state):
        """-> (state, start_iteration, start_env_steps).

        Own-folder auto-resume takes precedence over ``restore_from``: a
        warm-started job that crashes and relaunches with the same config
        must continue its OWN curve, not re-warm-start from the foreign
        folder; restore_from only seeds the very first run."""
        cfg = self.config.session_config.checkpoint
        if cfg.auto_resume and self.ckpt is not None:
            # precision guard FIRST: a policy mismatch must surface as
            # the named error, not as orbax's structure traceback from
            # the restore walk below (session/checkpoint.py). Inside the
            # auto_resume branch deliberately: a launch that will never
            # restore (auto_resume=False, fresh training into the same
            # folder) must not be blocked by the old run's policy —
            # begin_run then overwrites the sidecar with the new one.
            self.ckpt.check_precision(self._precision_meta)
            # newest FINITE checkpoint, not merely the newest readable one:
            # in warn mode (multi-host) a poisoned run-end save can exist,
            # and resuming into it would re-trip forever — the walk skips
            # damaged AND nonfinite steps (launch/recovery.py), emitting
            # recovery telemetry for each skip
            restored = self.recovery.restore_newest_finite(init_state)
            if restored is not None:
                state, meta, _step = restored
                self.log.info(
                    "auto-resumed at iteration %d (%d env steps)",
                    meta["iteration"], meta["env_steps"],
                )
                self._reseed_cadences(int(meta["iteration"]))
                return state, int(meta["iteration"]), int(meta["env_steps"])
        if cfg.restore_from:
            mgr = CheckpointManager(cfg.restore_from, on_event=self.tracer.event)
            # same precision guard for foreign warm-starts
            mgr.check_precision(self._precision_meta)
            restored = mgr.restore(init_state)
            mgr.close()
            if restored is None:
                raise FileNotFoundError(
                    f"checkpoint.restore_from={cfg.restore_from!r} has no checkpoint"
                )
            state, meta = restored
            self.log.info(
                "restored from %s at iteration %d (%d env steps)",
                cfg.restore_from, meta["iteration"], meta["env_steps"],
            )
            # warm-start from foreign folder: keep its counters so schedules
            # (lr anneal, beta anneal) continue rather than restart
            self._reseed_cadences(int(meta["iteration"]))
            return state, int(meta["iteration"]), int(meta["env_steps"])
        return init_state, 0, 0

    def _reseed_cadences(self, iteration: int) -> None:
        self._ckpt_every = PeriodicTracker(
            self._ckpt_every.period, init_count=iteration
        )
        if self.evaluator is not None:
            self._eval_every = PeriodicTracker(
                self._eval_every.period, init_count=iteration
            )
        if self._publisher is not None:
            self._pub_every = PeriodicTracker(
                self._pub_every.period, init_count=iteration
            )

    # -- per-iteration -------------------------------------------------------
    @_launch_session
    def begin_run(self, iteration: int, env_steps: int) -> None:
        """Start the wall-clock + cadence counters from the (possibly
        resumed) position."""
        self._metrics_every = PeriodicTracker(
            self._metrics_every.period, init_count=iteration
        )
        self._t0 = time.time()
        self._steps0 = env_steps
        # the device this run resolved, as JAX reports it: what
        # chip_smoke.py (and anyone reading the folder later) checks
        # instead of trusting that a 'tpu' config meant a TPU run
        dev = jax.devices()[0]
        self.tracer.event(
            "device", platform=str(dev.platform), kind=str(dev.device_kind),
            count=jax.device_count(),
        )
        if self._precision_meta is not None:
            # the active precision policy: one telemetry event per run
            # (diag renders it in Performance) + the checkpoint sidecar
            # restore validates against (written here, BEFORE the first
            # save, so even a run killed mid-first-interval leaves the
            # guard in place)
            self.tracer.event("precision", **self.precision.telemetry())
            self.log.info(
                "precision policy: %s",
                " ".join(
                    f"{k}={v}"
                    for k, v in sorted(self.precision.telemetry().items())
                ),
            )
            if self.ckpt is not None:
                self.ckpt.save_run_metadata(self._precision_meta)

    def end_iteration(
        self,
        iteration: int,
        env_steps: int,
        state,
        key: jax.Array,
        metrics=None,
        on_metrics=None,
    ):
        """Per-iteration side-bands, shared verbatim by every driver.

        ``metrics`` is the iteration's metric scalars — a dict of device
        scalars, or a zero-arg callable returning one (to defer assembling
        host-side extras) — synced to host floats only when the metrics
        cadence fires. ``state`` may likewise be a zero-arg callable
        resolved only when a state-consuming hook (eval, checkpoint)
        actually fires — multi-host drivers pass a lambda that pulls the
        replicated global state to host-local numpy, a transfer too costly
        to do every iteration. Returns (synced_metrics_or_None, stop)
        where stop echoes a truthy ``on_metrics(iteration, m)``.
        """
        state_box = [state]

        def resolve_state():
            if callable(state_box[0]):
                state_box[0] = state_box[0]()
            return state_box[0]

        m = None
        trip_reason = None
        if self._metrics_every.track_increment():
            # the ONE device->host sync of the cadence window: float() on
            # the device scalars blocks until the dispatched iterations
            # land, so this span is the fenced wall-time of the window tail
            with self.tracer.span("metrics-sync"):
                raw = metrics() if callable(metrics) else (metrics or {})
                m = {k: float(v) for k, v in raw.items()}
            if self._launching:
                # the launch ends with its first fenced point
                self._end_launch(closed=True)
            refuse_dropped_assignments(m)
            # fence to fence: the only span whose total is device time
            # (perf/* divide by it; a rollback's backward step is skipped)
            now = time.perf_counter()
            if self._sync_end is not None and iteration > self._sync_end[1]:
                self.tracer.add_phase(
                    "cadence", now - self._sync_end[0],
                    count=iteration - self._sync_end[1],
                )
            self._sync_end = (now, iteration)
            m["time/env_steps"] = env_steps
            m["time/env_steps_per_s"] = (env_steps - self._steps0) / max(
                time.time() - (self._t0 or time.time()), 1e-9
            )
            self._last_train = m
            self._emit_cache_event()
            # divergence guard: the health/* scalars just synced are the
            # detection signal (launch/recovery.py); in rollback mode a
            # trip sets recovery.pending, which the DRIVER resolves via
            # rollback()
            trip_reason = self.recovery.check(m, iteration, env_steps)
            if trip_reason is not None:
                # incident: freeze the minutes BEFORE the trip (the
                # flight recorder's ring) next to the trip itself
                self.ops.record_recovery({
                    "reason": str(trip_reason),
                    "iteration": int(iteration), "env_steps": int(env_steps),
                })
                self.ops.dump("recovery")
                if self.incidents is not None:
                    self.incidents.record_recovery({
                        "reason": str(trip_reason),
                        "iteration": int(iteration),
                        "env_steps": int(env_steps),
                    })
        # skip the state-consuming side-bands while the guard is tripped in
        # BOTH rollback and warn modes (warn is the multi-host setting — a
        # poisoned save would make auto_resume restore the poison).
        # last_window_tripped PERSISTS between cadence windows, so publish/
        # eval/checkpoint cadences firing on off-metrics iterations are
        # covered too; it clears on the next healthy window or rollback.
        tripped = (
            trip_reason is not None
            or self.recovery.pending is not None
            or self.recovery.last_window_tripped is not None
        )
        if (
            self._publisher is not None
            and self._pub_every.track_increment()
            and not tripped  # never publish poisoned params to live actors
        ):
            with self.tracer.span("param-publish", emit=True):
                view = self._pub_agent.acting_view(resolve_state())
                version = self._publisher.publish(view)
                if self._fanout is not None:
                    # broadcast the same view as a versioned frame
                    # (full/delta/bf16 per the fanout knobs); the
                    # publisher/server blob above stays the fetch
                    # fallback for late joiners
                    self._fanout.publish(view)
            # ops plane: the fanout tier's row — its published version vs
            # the fleet replicas' held versions is the staleness derivation
            self.ops.push_local(
                "param_fanout",
                gauges={
                    "version": float(version),
                    **(
                        self._fanout.gauges()
                        if self._fanout is not None else {}
                    ),
                },
            )
            if m is not None:
                m["publish/version"] = float(version)
                if self._fanout is not None:
                    m.update(self._fanout.gauges())
                self._last_train = m
        evaled: dict[str, float] = {}
        if (
            self.evaluator is not None
            and self._eval_every.track_increment()
            and not tripped  # a poisoned state's eval is wasted episodes
        ):
            with self.tracer.span("eval", emit=True):
                evaled = self.evaluator.evaluate(resolve_state(), key)
            self._last_eval = evaled
        if m is not None:
            # mirror the window's span accumulators as time/* scalars —
            # AFTER the publish/eval blocks so this window's side-band
            # spans land in this row, not the next (checkpoint fires after
            # the write by design and stays in the next window)
            m.update(self.tracer.flush_phases(env_steps))
            # perf/mfu + perf/membw_util over the same window: pure host
            # float arithmetic from the flushed phase times and the
            # startup-recorded program costs — zero device->host syncs
            # beyond the metrics already synced above (transfer-guard
            # tested in tests/test_telemetry.py)
            m.update(self.costs.gauges(self.tracer.last_window))
            # ops plane: the learner's own row, then the merged run
            # snapshot — pure host float/dict work on rows the tiers
            # already pushed, zero device->host syncs beyond the metrics
            # synced above (the same transfer-guard covers it)
            self.ops.push_local(
                "learner",
                gauges={
                    k: v for k, v in m.items()
                    if isinstance(v, (int, float))
                },
            )
            snap = self.ops.snapshot(int(iteration), int(env_steps))
            m.update(self.ops.gauges())
            # watchdog sweep over the snapshot just merged + incident
            # lifecycle — both pure host arithmetic on the snapshot dict
            # (no device state in reach), so the same transfer-guard test
            # covers them
            if self.watchdog is not None and snap is not None:
                firings = self.watchdog.evaluate(snap)
                self.incidents.observe(firings, snap)
                m.update(self.watchdog.gauges())
                m.update(self.incidents.gauges())
                # remediation decision sweep: the incident just observed
                # -> at most one bounded action + verification ticks for
                # the actions already in flight. Same pure-host-dict
                # discipline, same transfer-guard.
                if self.remediate is not None:
                    self.remediate.step(firings, snap)
                    m.update(self.remediate.gauges())
            self._last_train = m
        if m or evaled:
            self.writer.write(env_steps, {**(m or {}), **evaled})
            self.tracer.log_metrics(env_steps, {**(m or {}), **evaled})
        if self.ckpt is not None and self._ckpt_every.track_increment():
            if tripped:
                # a tripped window's state must never become "last good" —
                # the rollback about to happen would restore the poison
                self.log.warning(
                    "skipping checkpoint at iteration %d: divergence guard "
                    "tripped this window", iteration,
                )
            else:
                with self.tracer.span("checkpoint", emit=True):
                    self.ckpt.save(
                        iteration,
                        resolve_state(),
                        env_steps=env_steps,
                        metrics=self.last_metrics,
                    )
                    if self.extra_state_fn is not None:
                        self.ckpt.save_extra(iteration, self.extra_state_fn())
        # a capture starts and stops on an idle device, so that it holds
        # whole iterations: the state is the last dispatched one's output
        t_tick = time.monotonic()
        self.profile.tick(
            iteration, fence=lambda: jax.block_until_ready(resolve_state())
        )
        # ... and holds this thread while it does (a minute to write three
        # iterations of a thousand acting steps): not its tiers' silence
        held = time.monotonic() - t_tick
        if held > 1.0:
            self.ops.excuse_pause(held)
        # chaos-harness visibility: mirror any faults fired since the last
        # boundary into the telemetry spine (empty list in normal runs) —
        # and into the flight recorder, whose dump freezes the snapshots
        # leading up to the incident
        fired = faults.drain_fired()
        for ev in fired:
            self.tracer.event("fault", **ev)
            self.ops.record_fault(ev)
            if self.incidents is not None:
                self.incidents.record_fault(ev)
        if fired:
            self.ops.dump("fault")
        stop = m is not None and on_metrics is not None and bool(
            on_metrics(iteration, m)
        )
        if self.interrupt.fired:
            # preemption-safe shutdown: stop at THIS boundary; the driver's
            # final_checkpoint is the emergency save (no handler ever
            # touches orbax — session/interrupt.py)
            if not self._interrupt_logged:
                self._interrupt_logged = True
                self.log.warning(
                    "interrupt (signal %s) latched: stopping after iteration "
                    "%d, emergency checkpoint follows",
                    self.interrupt.signum, iteration,
                )
                self.tracer.event(
                    "recovery", kind="interrupt",
                    signum=self.interrupt.signum,
                    iteration=int(iteration), env_steps=int(env_steps),
                )
            stop = True
        return m, stop

    @property
    def interrupted(self) -> bool:
        """True once the preemption sentinel latched a signal — loops with
        iteration paths that bypass ``end_iteration`` (the SEED stale-drop
        path) poll this so an interrupt cannot get stuck behind a streak."""
        return self.interrupt.fired

    def final_checkpoint(self, iteration: int, env_steps: int, state) -> None:
        """Always leave a resumable checkpoint at run end — including the
        interrupt path, where this IS the emergency checkpoint. ``state``
        may be a zero-arg callable (see ``end_iteration``). Skipped when
        the divergence guard is pending OR the last synced window tripped
        (the warn-mode spelling, where pending is never set — multi-host):
        persisting poison would make the relaunch resume into the same
        NaNs the guard just caught."""
        if self.recovery.pending is not None or self.recovery.last_window_tripped:
            self.log.warning(
                "skipping final checkpoint: divergence guard %s "
                "(relaunch will resume from the last finite checkpoint)",
                "pending" if self.recovery.pending else "tripped on the "
                "last synced window",
            )
            return
        if self.ckpt is not None and self.ckpt.latest_step() != iteration:
            self.ckpt.save(
                iteration,
                state() if callable(state) else state,
                env_steps=env_steps,
                metrics={**self._last_train, **self._last_eval},
            )
            if self.extra_state_fn is not None:
                self.ckpt.save_extra(iteration, self.extra_state_fn())

    def _emit_cache_event(self) -> None:
        """Mirror the compile-cache hit/miss counters into the telemetry
        log when they changed: at the first metrics cadence (or at close,
        for a run shorter than one), and after that only for a recompile
        in the steady loop (`surreal_tpu diag` reports the last one).
        Host-side ints only — no device sync rides on this."""
        if self.compile_cache_dir is None:
            return
        counts = compile_cache_counts()
        if counts != self._cache_counts:
            self._cache_counts = counts
            self.tracer.event(
                "compile_cache", dir=self.compile_cache_dir, **counts
            )

    def close(self) -> None:
        self.interrupt.close()  # restore the process's previous handlers
        for ev in faults.drain_fired():  # tail faults since the last boundary
            self.tracer.event("fault", **ev)
            self.ops.record_fault(ev)
            if self.incidents is not None:
                self.incidents.record_fault(ev)
        # flush still-verifying actions (a run ending mid-verification is
        # itself evidence), then a still-open incident (closed_t stays
        # None — the record shows the run ended mid-incident), before the
        # planes they read from come down
        if self.remediate is not None:
            self.remediate.close()
        if self.incidents is not None:
            self.incidents.close()
        # stop the ops receiver BEFORE the tiers that push into it come
        # down (a pushed row into a closed PULL is just dropped, but the
        # join here keeps thread teardown deterministic)
        self.ops.close()
        self.profile.close()  # stop + record a capture cut short by exit
        if self._param_server is not None:
            self._param_server.close()
            self._param_server = None
            # a dead session must not advertise its ports: a relaunched
            # actor would otherwise latch onto the stale address and spend
            # its whole wait budget timing out against it
            try:
                os.unlink(self._discovery_path)
            except OSError:
                pass
        if self._fanout is not None:
            self._fanout.close()
            self._fanout = None
        if self._publisher is not None:
            self._publisher.close()
            self._publisher = None
        if self.evaluator is not None:
            self.evaluator.close()
        if self.ckpt is not None:
            self.ckpt.close()
        self.writer.close()
        self._emit_cache_event()  # final counts for runs shorter than a cadence
        if self._launching:  # ... whose launch never reached a fenced point
            self._end_launch(closed=False)
        self.tracer.close()
        # detach + close this session's file log handler: without this the
        # fd into <folder>/logs/ outlives the session for the rest of the
        # process (get_logger only retargets when a DIFFERENT folder
        # arrives) — the chaos residue oracle counts that as a leak
        for h in list(self.log.handlers):
            if str(getattr(h, "_surreal_id", "")).startswith("file:"):
                self.log.removeHandler(h)
                h.close()


HOST_METRICS_WINDOW = 20  # rolling episode-return window; host loops size
                          # their deque(maxlen=...) with this


def host_metrics(metrics, recent_returns, window: int = HOST_METRICS_WINDOW):
    """Deferred host-metrics assembly for host-env loops: the learner's
    metric scalars plus a rolling-mean ``episode/return`` from the env
    wrappers' completed-episode stats. Returns a zero-arg callable for
    ``SessionHooks.end_iteration`` (synced only when the cadence fires)."""
    import numpy as np

    def build():
        m = dict(metrics)
        if recent_returns:
            # list(...) first: callers pass a deque(maxlen=window), which
            # doesn't support slice indexing
            m["episode/return"] = float(np.mean(list(recent_returns)[-window:]))
        return m

    return build


def refuse_dropped_assignments(row: dict) -> None:
    """A routed-expert layer promises that no token routed to a held
    expert is dropped; its sorted buffer is a static bound under the worst
    case (ops/moe.py), and ``moe/overflow`` counts what fell outside it in
    the iteration the row reports. A non-zero is a broken promise, not a
    gauge."""
    dropped = row.get("moe/overflow", 0.0)
    if dropped > 0.0:
        raise RuntimeError(
            f"moe/overflow: {dropped:.0f} assignments to held experts fell "
            "outside the sorted buffer and were dropped; the router sends "
            "this chip more than ops/moe.py::CAPACITY_FACTOR times its even "
            "share"
        )


def training_env_config(env_config) -> Config:
    """The training env never records video — that is eval's job (the
    reference wired VideoWrapper only into ``run_eval``, SURVEY.md §3.5)."""
    return Config(video=Config(enabled=False)).extend(env_config)
