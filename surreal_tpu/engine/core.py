"""The loop engine: one iteration skeleton, software-pipelined boundary.

``LoopEngine.run`` owns the ``while env_steps < total`` skeleton every
driver used to hand-thread: chaos firing, the driver step closure,
counter bumps, the SessionHooks boundary (publish/checkpoint/recover/
observe), rollback dispatch, and the stop decision. With
``pipeline_sidebands`` off (default) the boundary runs inline and the
engine is bit-identical to the historical loops. With it on, the
boundary is submitted to a single-worker staging executor and overlaps
iteration k+1's collect/learn:

- **Donation-safe handoff**: when any declared stage donates its inputs
  (the fused device drivers jit with ``donate_argnums=(0, 1)``), the
  param tree handed to the deferred boundary is snapshotted with
  ``jax.tree.map(jnp.copy, state)`` BEFORE the next step dispatches —
  the runtime orders the copy ahead of the donating dispatch's buffer
  reuse. Non-donating (host) drivers pass the immutable state reference:
  rebinding, never mutation, is the loop discipline, so the reference IS
  a version pin.
- **Bounded lag, never silent**: stop/recovery decisions surface with at
  most one iteration of lag (the same bounded-staleness class as
  ``overlap_rollouts``). A wedged boundary (the ``engine.stage`` chaos
  site's ``delay_stage``) gets ``stage_timeout_s`` before the NEXT
  boundary is skipped — counted in ``engine/skipped_boundaries`` and
  logged, and the wedged boundary itself is still awaited on later
  iterations and at loop exit. The interrupt latch is checked inline
  every iteration regardless of mode, so SIGTERM stops at an iteration
  boundary with the emergency checkpoint intact even under overlap.
"""

from __future__ import annotations

import concurrent.futures
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from surreal_tpu.session.telemetry import (
    launch_begin,
    launch_span,
    step_annotation,
    trace_annotation,
)
from surreal_tpu.utils import faults


@dataclass
class LoopState:
    """The loop-carried record a driver's step closure mutates."""

    state: Any
    key: Any
    iteration: int
    env_steps: int
    extras: dict = field(default_factory=dict)


@dataclass
class Outcome:
    """What one driver step hands the boundary.

    ``metrics`` may be a dict or a zero-arg callable (resolved lazily at
    the metrics cadence, on the staging thread when pipelined — that is
    where the one ``float()`` device sync moves off the critical path).
    ``state_for_hooks`` defaults to ``ls.state``; drivers whose hooks
    state differs (multihost host-local conversion) pass a value or
    zero-arg callable. ``steps`` is the env-step advance this iteration.
    ``skip_boundary`` is the SEED stale-drop contract: count the steps,
    run no boundary, do not count an iteration (the inline interrupt
    check still fires so a preemption never sits out a stale streak).
    ``post_metrics(m_row)`` runs when the metrics cadence fired —
    drivers emit their per-cadence telemetry events there, which rides
    the deferred boundary when pipelining is on.
    """

    metrics: Any
    hook_key: Any
    steps: int
    state_for_hooks: Any = None
    skip_boundary: bool = False
    post_metrics: Callable[[dict], None] | None = None


class LoopEngine:
    """Composable iteration engine over declared stages (stages.py)."""

    def __init__(
        self,
        hooks,
        total: int,
        step: Callable[[LoopState], Outcome],
        stages,
        config,
        *,
        on_metrics=None,
        apply_fault: Callable[[LoopState, dict], None] | None = None,
        on_rollback: Callable[[LoopState], None] | None = None,
        after_step: Callable[[LoopState], None] | None = None,
        agree_stop: Callable[[int, bool], bool] | None = None,
        fire_faults: bool = True,
    ):
        from surreal_tpu.engine.stages import StageSpec

        stages = tuple(stages)
        if not stages:
            raise ValueError("LoopEngine needs at least one declared stage")
        for s in stages:
            if not isinstance(s, StageSpec):
                raise TypeError(f"stage {s!r} is not a StageSpec")
        self.hooks = hooks
        self.total = int(total)
        self.step = step
        self.stages = stages
        self.config = config
        self.on_metrics = on_metrics
        self.apply_fault = apply_fault
        self.on_rollback = on_rollback
        self.after_step = after_step
        self.agree_stop = agree_stop
        self.fire_faults = bool(fire_faults)
        self.donating = any(s.donate for s in stages)
        self.pipelined = bool(config.pipeline_sidebands) and any(
            s.deferrable for s in stages
        ) and hooks is not None
        self._executor = None
        self._pending = None  # (future, iteration) of the deferred boundary
        # observability (engine/* gauges + the `engine` telemetry event);
        # bounded windows — the gauges are a live view, not a history
        from collections import deque

        self._step_ms: deque = deque(maxlen=512)
        self._boundary_ms: deque = deque(maxlen=512)
        self._busy_ms = 0.0  # staging-worker busy time while pipelined
        self._deferred = 0
        self._skipped = 0
        self._kills = 0
        self._t0 = None
        self._warned_wedged = False
        self._launching = True  # until the first step has been dispatched

    # -- observability --------------------------------------------------------
    def gauge_row(self) -> dict[str, float]:
        """The engine/* gauges merged into every metrics row (registered
        in session/costs.py's GAUGE_REGISTRY)."""
        from surreal_tpu.session.telemetry import latency_percentiles

        b = latency_percentiles(tuple(self._boundary_ms)) or {}
        wall_ms = (
            (time.perf_counter() - self._t0) * 1e3 if self._t0 else 0.0
        )
        return {
            "engine/stage_p50_ms": float(b.get("p50", 0.0)),
            "engine/stage_p99_ms": float(b.get("p99", 0.0)),
            "engine/occupancy": (
                float(self._busy_ms / wall_ms) if wall_ms > 0 else 0.0
            ),
            "engine/queue_depth": 1.0 if self._pending is not None else 0.0,
            "engine/deferred_boundaries": float(self._deferred),
            "engine/skipped_boundaries": float(self._skipped),
            "engine/stage_kills": float(self._kills),
        }

    def _event_fields(self) -> dict:
        from surreal_tpu.session.telemetry import latency_percentiles

        return {
            "pipelined": bool(self.pipelined),
            "stages": [s.describe() for s in self.stages],
            "stage_ms": latency_percentiles(tuple(self._boundary_ms)),
            "step_ms": latency_percentiles(tuple(self._step_ms)),
            "occupancy": self.gauge_row()["engine/occupancy"],
            "deferred": self._deferred,
            "skipped": self._skipped,
            "kills": self._kills,
        }

    # -- the boundary ---------------------------------------------------------
    def _wrap_metrics(self, metrics):
        def build():
            base = metrics() if callable(metrics) else metrics
            row = dict(base) if base else {}
            row.update(self.gauge_row())
            return row

        return build

    def _run_boundary(self, iteration, env_steps, state_for_hooks, out):
        """end_iteration + the driver's per-cadence emits + the engine's
        own observability row. Runs inline, or on the staging worker when
        pipelined. Returns the boundary's stop decision."""
        f = faults.fire("engine.stage")
        if f is not None:
            kind = f.get("kind")
            if kind == "delay_stage":
                faults.sleep_ms(f)
            elif kind == "kill_stage":
                self._kills += 1
                raise faults.FaultInjected(f"engine.stage kill: {f}")
        t0 = time.perf_counter()
        try:
            # the interval _boundary_ms times, on any profile's host plane
            with trace_annotation("engine.boundary"):
                m_row, stop = self.hooks.end_iteration(
                    iteration, env_steps, state_for_hooks, out.hook_key,
                    self._wrap_metrics(out.metrics), self.on_metrics,
                )
                if m_row is not None:
                    if out.post_metrics is not None:
                        out.post_metrics(m_row)
                    self.hooks.tracer.event("engine", **self._event_fields())
                    self.hooks.ops.push_local(
                        "engine", gauges=self.gauge_row(),
                        body=self._event_fields(),
                    )
            return bool(stop)
        finally:
            dur = (time.perf_counter() - t0) * 1e3
            self._boundary_ms.append(dur)
            if self.pipelined:
                self._busy_ms += dur

    def _collect_pending(self, timeout: float):
        """Await the deferred boundary. Returns (resolved, stop):
        ``resolved=False`` means the boundary is still wedged after
        ``timeout`` — the caller skips this iteration's boundary (counted)
        and retries on the next one."""
        fut, it_prev = self._pending
        try:
            stop = fut.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            if self.hooks is not None and not self._warned_wedged:
                self._warned_wedged = True
                self.hooks.log.warning(
                    "engine: boundary of iteration %d wedged past the %.1fs "
                    "stage bound — learn continues, subsequent boundaries "
                    "are skipped and counted until it drains",
                    it_prev, timeout,
                )
            return False, False
        except faults.FaultInjected:
            # a killed side-band stage is an organic crash of that stage,
            # not of training: counted (self._kills, bumped at fire time)
            # and surfaced through drain_fired's `fault` event at the next
            # healthy boundary
            self._pending = None
            return True, False
        self._pending = None
        self._warned_wedged = False
        return True, bool(stop)

    def _pin_state(self, ls: LoopState, out: Outcome):
        """Resolve the state the boundary will read, donation-safely."""
        state = out.state_for_hooks if out.state_for_hooks is not None else ls.state
        if self.pipelined and self.donating and not callable(state):
            import jax
            import jax.numpy as jnp

            # device-side snapshot, dispatched BEFORE the next donating
            # step: the runtime orders the copy ahead of buffer reuse
            state = jax.tree.map(jnp.copy, state)
        return state

    def _recovery_pending(self) -> bool:
        return self.hooks is not None and self.hooks.recovery.pending

    def _stop_decision(self, iteration: int, stop: bool) -> bool:
        if self.agree_stop is not None:
            return bool(self.agree_stop(iteration, stop))
        return bool(stop)

    def _flush(self):
        """Drain the deferred boundary at loop exit (stop/interrupt/budget)
        so publish/checkpoint side-bands land before the run epilogue. A
        boundary wedged past the stage bound is abandoned to the daemon
        executor — counted, logged once, never blocking shutdown."""
        if self._pending is None:
            return None
        fut, it_prev = self._pending
        try:
            stop = fut.result(timeout=max(self.config.stage_timeout_s, 5.0))
        except concurrent.futures.TimeoutError:
            self._skipped += 1
            if self.hooks is not None:
                self.hooks.log.warning(
                    "engine: abandoning the wedged boundary of iteration %d "
                    "at loop exit (counted in engine/skipped_boundaries)",
                    it_prev,
                )
            return None
        except faults.FaultInjected:
            return None
        finally:
            self._pending = None
        return stop

    # -- the skeleton ---------------------------------------------------------
    def run(self, ls: LoopState) -> LoopState:
        self._t0 = time.perf_counter()
        try:
            while ls.env_steps < self.total:
                # one pass = one profiler step; engine.step and
                # engine.boundary (in _run_boundary) lie inside it
                with step_annotation("iteration", ls.iteration):
                    if self._pass(ls):
                        break
            return ls
        finally:
            self._flush()
            if self._executor is not None:
                self._executor.shutdown(wait=False)

    def _pass(self, ls: LoopState) -> bool:
        """One pass of the loop: chaos, the driver step, counters, the
        boundary. Returns whether the loop stops."""
        if self.fire_faults:
            f = faults.fire("trainer.iteration")
            if f is not None and self.apply_fault is not None:
                self.apply_fault(ls, f)
        t_step = time.perf_counter()
        if self._launching:
            self._launching = False
            out = self._first_step(ls)
        else:
            with trace_annotation("engine.step"):
                out = self.step(ls)
        self._step_ms.append((time.perf_counter() - t_step) * 1e3)
        if out.skip_boundary:
            ls.env_steps += out.steps
            return self.hooks is not None and self.hooks.interrupted
        ls.iteration += 1
        ls.env_steps += out.steps
        if self.after_step is not None:
            self.after_step(ls)
        if not self.pipelined:
            return self._inline_boundary(ls, out)
        return self._pipelined_boundary(ls, out)

    def _first_step(self, ls: LoopState) -> Outcome:
        """The launch's first dispatch as a span of the launch record
        (session/telemetry.py), here so that every driver has it: the
        trace, the lowering, the cache look-up and the executable's load.
        From its end to the end of the first metrics-sync, where
        SessionHooks closes the record, is ``launch.first_cadence``."""
        with launch_span("launch.first_dispatch"):
            with trace_annotation("engine.step"):
                out = self.step(ls)
        launch_begin("launch.first_cadence")
        return out

    def _inline_boundary(self, ls: LoopState, out: Outcome) -> bool:
        stop = False
        if self.hooks is not None:
            try:
                stop = self._run_boundary(
                    ls.iteration, ls.env_steps, self._pin_state(ls, out), out
                )
            except faults.FaultInjected:
                stop = False  # counted at fire time; see _collect_pending
            if self._recovery_pending():
                self.on_rollback(ls)
                return False
        return self._stop_decision(ls.iteration, stop)

    def _pipelined_boundary(self, ls: LoopState, out: Outcome) -> bool:
        # consume the PREVIOUS boundary first: its stop/recovery verdicts
        # land with exactly one iteration of lag
        if self._pending is not None:
            resolved, stop_prev = self._collect_pending(
                self.config.stage_timeout_s
            )
            if not resolved:
                # wedged past the bound: skip THIS boundary, counted
                self._skipped += 1
                if self.hooks.interrupted:
                    return True
                return False
            if self._recovery_pending():
                # roll back; the current outcome is the poisoned lineage's
                # last iteration — its boundary never runs (bounded lag)
                self.on_rollback(ls)
                return False
            if self._stop_decision(ls.iteration, stop_prev):
                return True
        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="engine-stage"
            )
        state_pinned = self._pin_state(ls, out)
        self._pending = (
            self._executor.submit(
                self._run_boundary, ls.iteration, ls.env_steps,
                state_pinned, out,
            ),
            ls.iteration,
        )
        self._deferred += 1
        # the interrupt latch is inline in BOTH modes: a SIGTERM stops at
        # this iteration boundary, _flush drains the just-submitted
        # boundary, and the driver epilogue writes the emergency checkpoint
        if self.hooks.interrupted:
            return True
        return False
