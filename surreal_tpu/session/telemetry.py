"""Telemetry spine: span tracing, a JSONL event log, per-host heartbeats,
and the ``diag`` report (parity-plus: the reference ran a whole
observability *process trio* — tensorplex/loggerplex/tensorboard,
SURVEY.md §5.5 — whose scalars flow through ``session/metrics.py``; this
module adds the structural signals that trio never had: phase-level wall
time, training-health summaries, and multi-host liveness, all readable
offline from ``<folder>/telemetry/``).

Fence discipline:

- host clocks and fences NEVER enter jitted-step modules — a
  ``time.time()`` traced inside jit runs once at compile and lies
  forever, and a ``jax.block_until_ready`` there serializes the async
  pipeline. ``tests/test_import_hygiene.py`` lints for both.
- hot-loop spans are UNFENCED: a span around an async-dispatched jit call
  measures dispatch time for that call, but jax's bounded in-flight queue
  applies backpressure, so per-window TOTALS converge to real wall time;
  the one true fence per window stays the metrics-cadence sync that
  already existed (``SessionHooks.end_iteration``'s ``float()``
  conversion). The ONE fenced total is the ``cadence`` phase: host time
  from the end of one ``metrics-sync`` to the end of the next, counted in
  iterations — device time, fence to fence (``perf/*`` divide by it).
- every span is also a ``jax.profiler.TraceAnnotation`` of the same name
  (disabled tracers included), so ANY profile of the process — an
  on-demand capture, the benchmark's trace — carries the program's spans
  on its host plane, in the profile's own timebase beside the device
  planes. With no profile active an annotation is a few hundred
  nanoseconds.
- JSONL volume is bounded by cadence, not by iteration rate: spans
  accumulate in-memory per phase and are written as ONE ``phases`` event
  per ``flush_phases`` call (the metrics cadence); only low-frequency
  side-band spans (eval, checkpoint, publish) emit individual ``span``
  events via ``emit=True``.

Event schema (``<folder>/telemetry/events.jsonl``, one JSON object per
line, ``t`` = unix seconds):

    {"type": "session",   "t": ..., "name": "train", "pid": ...}
    {"type": "phases",    "t": ..., "step": ..., "phases":
        {"<phase>": {"count": N, "total_s": S, "max_ms": M}}}
                    (a CheckpointManager writes one of its own at
                     construction, step -1 and the one phase
                     ``checkpoint-import``: the seconds its import of
                     orbax took, 0 when it was loaded already)
    {"type": "span",      "t": ..., "name": "...", "dur_s": ...}
                    (low-frequency side-band spans via span(emit=True);
                     ISSUE 14 adds CAUSAL spans from Tracer.emit_span —
                     the same type with {"exemplar": ..., "span": S,
                     "parent": P, "tier": "...", "dur_ms": ...} — one
                     head-sampled request's hop across tiers; the
                     `surreal_tpu trace` CLI assembles them into
                     per-exemplar span trees)
    {"type": "metrics",   "t": ..., "step": ..., "values": {...}}
    {"type": "device", "t": ..., "platform": "tpu", "kind": "...",
     "count": N}    (one per run, SessionHooks.begin_run: the device JAX
                     resolved, not the one the config asked for)
    {"type": "compile_cache", "t": ..., "dir": "...", "hits": H,
     "misses": M}   (cumulative; written by SessionHooks while the
                     persistent compile cache is on, utils/compat.py:
                     at the first metrics cadence, then only when a
                     count changed. After the launch a changed count is
                     a recompile in the steady loop)
    {"type": "launch", "t": ..., "origin": "os|import|session",
     "t0_unix": ..., "total_s": ..., "unattributed_s": ..., "closed":
     true, "spans": [{"name": "launch.<span>", "parent": "launch|<the
     enclosing span>", "start_s": ..., "end_s": ..., ["trace_s": ...,
     "lower_s": ..., "compile_s": ..., "cache_read_s": ..., "cache_hits":
     H, "cache_misses": M]}, ...], ["outside": {...the same six}]}
                    (one a session, written when its launch closes: the
                     end of the first ``metrics-sync``, or
                     SessionHooks.close with ``closed: false`` for a run
                     that ended before. The launch record below: every
                     time in seconds since the origin, which is the
                     process's start as the OS has it (``os``), the
                     package's import where the OS gives none
                     (``import``), or the first span of a later session
                     of one process (``session``). ``unattributed_s`` is
                     ``total_s`` less the union of the spans whose parent
                     is ``launch``. The compiler's seconds are JAX's own
                     duration events, added to the innermost span open
                     when each fired (``outside`` where none was):
                     ``compile_s`` is the backend's compile or, on a
                     cache hit, the read that ``cache_read_s`` counts
                     alone; a function traced inside another's trace
                     counts once)
    {"type": "data_plane", "t": ..., "transport": "...", "pipeline": ...,
     "shm_workers": N, "pickle_workers": M, "wire_bytes_per_step": B,
     ...}           (SEED drivers via SessionHooks.data_plane_event; the
                     last event reflects the settled negotiation)
    {"type": "recovery", "t": ..., "kind": "interrupt|tripped|rollback|
     checkpoint_fallback|skipped_nonfinite_checkpoint|giveup", ...}
                    (the fault-tolerance layer: preemption sentinel stops,
                     divergence-guard trips/rollbacks with lr_scale and
                     the restored step, damaged-checkpoint fallbacks —
                     session/interrupt.py, launch/recovery.py,
                     session/checkpoint.py)
    {"type": "fault", "t": ..., "site": "...", "kind": "...", "call": N}
                    (chaos-harness injections that actually fired,
                     utils/faults.py — drained into the spine by
                     SessionHooks so a chaos run documents what it
                     survived)
    {"type": "program_cost", "t": ..., "name": "...", "flops": F,
     "bytes_accessed": B, "arithmetic_intensity": AI, "phase": "...",
     "peak_flops": ..., "peak_membw": ..., ...}
                    (cost/MFU accounting, session/costs.py: one per
                     registered hot program, recorded at driver startup)
    {"type": "precision", "t": ..., "policy": "f32|mixed|bf16|bf16_fp8",
     "compute_dtype": "...", "data_dtype": "...", "loss_scaling": ...,
     "fp8": ...}
                    (the active precision policy, ops/precision.py —
                     emitted once per run by SessionHooks.begin_run;
                     diag's Performance section leads with it)
    {"type": "hops", "t": ..., "<hop>_ms": {"p50": ..., "p90": ...,
     "p99": ..., "n": N}, ...}
                    (per-hop latency percentiles of the SEED
                     cross-process timeline: worker_to_server,
                     serve_batch, chunk_queue_dwell, learn_dispatch —
                     emitted at the metrics cadence)
    {"type": "profile", "t": ..., "dir": "...", "reason":
     "trigger_file|slow_iter(...)|<requester>", "start_iter": ...,
     "end_iter": ..., "digest": {"devices": D, "steps": N, "window_s":
     ..., "busy_s": ..., "idle_s": ..., "phases": {"<phase>|unattributed":
     {"ms_per_iter": ..., "share_of_busy": ..., "top_ops": [[name, ms],
     ...], "ops_per_iter": ..., "short_ops": {"per_iter": ...,
     "ms_per_iter": ...}}}, "parts": {"<part>|unattributed":
     {"ms_per_iter": ..., "share_of_busy": ..., "top_ops": [...]}},
     "subphases": {"<phase>": {"<sub>|rest": ms}}, "parts_by_phase":
     {"<part>|unattributed": {"<phase>|unattributed": ms}}, "kernels":
     {"<pl.pallas_call name>": {"ms_per_iter": ..., "calls_per_iter":
     ..., "sites": ..., "part": "...", "by_phase": {"<phase>": ms}}},
     "idle_by_span": {"<span>|none": seconds}, "host_spans":
     {"<span>": count}, "trace_bytes": ..., "digest_s": ...}}
                    (on-demand profiler captures, session/profile.py —
                     the trace artifact lives under dir; ``digest`` is the
                     capture reduced by the program itself: device SELF
                     time per phase of utils/phases.py on one device, per
                     iteration, and every device idle gap charged to the
                     innermost program span covering it on the loop's
                     thread; a phase's time by its sub-scopes, a model
                     part's by phase, each Pallas kernel's over its call
                     sites, and a phase's count of op events with those
                     under 1 us. Every op is in ``<dir>/ops.json``.
                     ``digest_error`` replaces it when the reduction
                     failed; diag's Performance section renders the
                     newest digest)
    {"type": "param_fetch", "t": ..., "span": S, "version": V,
     "unchanged": ..., "bytes": B}
                    (parameter-service hop: span-tagged client fetches
                     mirrored by ParameterServer when SessionHooks owns
                     it)
    {"type": "serving_tier", "t": ..., "replicas": {"0": {state,
     address, min_batch, serve_ms, workers, queue_depth, ...}, ...},
     "autoscale": ..., "num_workers": N, "fleet/...": ...}
                    (the act-serving tier's per-replica snapshot —
                     distributed/fleet.py, one per metrics row while an
                     InferenceFleet is active; rendered by diag's
                     "Serving tier" section)
    {"type": "experience_plane", "t": ..., "kind": "...",
     "num_shards": N, "shard_mode": "...", "transports": [...],
     "shards": {"0": {fill, ingested_rows, samples_served,
     ingest_transit_ms: {p50,...}, ...}, ...}, "sender": {...},
     "sampler": {...}, ...}
                    (the sharded experience plane's settled shape —
                     per-shard replay gauges + sender->shard->learner
                     hops; one per metrics row, the last one wins.
                     surreal_tpu/experience/, rendered by diag's
                     "Experience plane" section)
    {"type": "gateway", "t": ..., "address": "...", "tenants": {"name":
     {sessions, max_sessions, rate, acts, queued, throttled, evicted,
     rejected}, ...}, "pinned_versions": {...}, "cache_hit_rate": ...,
     "gateway/...": ...}
                    (the session gateway's tenant-facing snapshot —
                     surreal_tpu/gateway/, one per metrics row while the
                     gateway is live; rendered by diag's "Gateway"
                     section)
    {"type": "ops_snapshot", "t": ..., "seq": N, "tiers": T, "dead": D,
     "breaches": B, "bad_frames": ...}
                    (one per metrics cadence while the ops plane is
                     live — a summary POINTER; the full merged snapshot
                     lives in telemetry/ops_snapshot.json, which
                     `surreal_tpu top` renders. session/opsplane.py)
    {"type": "slo_breach", "t": ..., "tenant": "...", "objective": "...",
     "measured": ..., "target": ..., "budget_used": ..., "exhausted": ...}
                    (one per breached evaluation window per (tenant,
                     objective) — counted, never silent.
                     session/slo.py via the OpsAggregator)
    {"type": "ops_flightrec", "t": ..., "trigger":
     "recovery|fault|slo|...", "dir": "...", "snapshots": K, "events": M}
                    (a flight-recorder dump landed on disk under
                     telemetry/flightrec/<trigger>/ — the pre-incident
                     snapshot ring + fault/recovery events, trace-
                     correlated. session/opsplane.py)

Every event additionally carries ``trace`` (the run-scoped trace id
SessionHooks mints and spawned components inherit) and ``seq`` (a
per-process span-sequence counter) — the correlation keys diag uses to
stitch one cross-process timeline.

Heartbeats live per rank in ``telemetry/heartbeat_rank<k>.jsonl``:

    {"type": "heartbeat", "t": ..., "rank": R, "iteration": I,
     "env_steps": E}

``python -m surreal_tpu diag <folder>`` (``main/launch.py``) renders
:func:`diag_report` over these files: phase-time breakdown, health-signal
summary (the in-graph ``health/*`` diagnostics from
``learners/base.py::training_health``), and a last-heartbeat table.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager

# jax.profiler's annotation classes, resolved once on first use: this
# module stays importable (and diag runnable) without jax, and a span does
# not pay an attribute walk per call
_ANNOTATIONS: tuple | None = None


def _annotations() -> tuple:
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
    return _ANNOTATIONS


def trace_annotation(name: str):
    """A host span named ``name`` on the host plane of any active
    profile (``jax.profiler.TraceAnnotation``); nothing but a few hundred
    nanoseconds when none is."""
    return _annotations()[0](name)


def step_annotation(name: str, step_num: int):
    """One pass of a loop as a profiler step
    (``jax.profiler.StepTraceAnnotation``)."""
    return _annotations()[1](name, step_num=step_num)


TELEMETRY_DIR = "telemetry"
EVENTS_FILE = "events.jsonl"
PROFILES_DIR = "profiles"  # <folder>/telemetry/profiles/<tag>/ captures

# every event ``type`` any module may emit, name -> emitting layer. The
# GAUGE_REGISTRY discipline (session/costs.py) extended to events: an
# emit site using a type not documented here fails
# tests/test_import_hygiene.py's registry lint, so the schema docstring
# above and diag can never silently drift from what the code writes.
EVENT_REGISTRY = {
    "session": "Tracer.__init__ (session/telemetry.py)",
    "phases": "Tracer.flush_phases (session/telemetry.py); a "
              "CheckpointManager's import of orbax, the one phase "
              "'checkpoint-import' with step -1 (session/checkpoint.py)",
    "span": "Tracer.span(emit=True) side-bands + Tracer.emit_span causal "
            "trace exemplars (session/telemetry.py)",
    "metrics": "Tracer.log_metrics (session/telemetry.py)",
    "heartbeat": "HeartbeatWriter (session/telemetry.py, own file)",
    "compile_cache": "SessionHooks compile-cache counters, when they "
                     "changed (launch/hooks.py)",
    "launch": "the launch record's spans, process start to the first "
              "metrics-sync's end (LaunchRecord.close, "
              "session/telemetry.py; SessionHooks closes it)",
    "device": "the platform/kind/count JAX resolved for the run "
              "(SessionHooks.begin_run)",
    "data_plane": "SEED drivers via SessionHooks.data_plane_event",
    "recovery": "fault-tolerance layer (session/interrupt.py, "
                "launch/recovery.py, session/checkpoint.py)",
    "fault": "chaos firings drained by SessionHooks (utils/faults.py)",
    "program_cost": "cost/MFU accounting (session/costs.py)",
    "precision": "active precision policy (launch/hooks.py begin_run)",
    "hops": "cross-process hop percentiles (launch/seed_trainer.py)",
    "profile": "on-demand profiler captures with their digest: device "
               "time per phase per iteration, idle by host span "
               "(session/profile.py)",
    "param_fetch": "parameter-service fetches (distributed/param_service.py)",
    "serving_tier": "inference-fleet snapshot (distributed/fleet.py)",
    "experience_plane": "sharded experience plane (experience/plane.py)",
    "experience_close": "final exactly-once row accounting at plane "
                        "teardown (experience/plane.py::accounting via "
                        "the drivers' close paths) — the chaos "
                        "conservation oracle's input",
    "chaos_campaign": "chaos campaign run summary: seed, profile, plan, "
                      "oracle verdicts (chaos/campaign.py)",
    "chaos_violation": "one invariant-oracle violation found by a chaos "
                       "campaign run, with its (shrunk) schedule "
                       "(chaos/campaign.py)",
    "gateway": "session gateway tenant snapshot (gateway/server.py)",
    "ops_snapshot": "ops-plane merged-snapshot pointer (session/opsplane.py)",
    "slo_breach": "per-tenant SLO window breach (session/slo.py)",
    "ops_flightrec": "flight-recorder dump record (session/opsplane.py)",
    "incident_open": "watchdog firings opened an incident "
                     "(session/incidents.py)",
    "incident_update": "open incident absorbed further firings "
                       "(session/incidents.py, rate-bounded)",
    "incident_close": "incident closed on sustained-healthy windows "
                      "(session/incidents.py)",
    "remediation": "remediation engine action executed/suppressed/errored "
                   "(session/remediate.py)",
    "remediation_verdict": "counter-detector verdict on a completed "
                           "verification window (session/remediate.py)",
    "loadgen": "tenant load generator stop summary (gateway/loadgen.py)",
    "learner_group": "elastic learner-group membership transitions "
                     "(parallel/learner_group.py via "
                     "SessionHooks.learner_group_event)",
    "engine": "loop-engine stage snapshot: declared stages, boundary/step "
              "latency percentiles, staging occupancy, deferred/skipped/"
              "killed boundary counters (engine/core.py, metrics cadence)",
}


def latency_percentiles(samples) -> dict[str, float] | None:
    """{p50, p90, p99, n} of a latency sample window (pure python — used
    by the inference server's hop stats and the SEED data plane; no numpy
    so the server thread never allocates for bookkeeping)."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        return None
    n = len(xs)

    def pct(p: float) -> float:
        return xs[min(n - 1, int(p * (n - 1) + 0.5))]

    return {"p50": pct(0.50), "p90": pct(0.90), "p99": pct(0.99), "n": n}


class TraceContext:
    """One head-sampled request's position in its causal span tree
    (ISSUE 14): the exemplar id names the tree, ``span_id`` this hop,
    ``parent_id`` the hop that caused it. Pure data — emitters pass it
    across tier boundaries (gateway -> fleet replica -> learner chunk)
    and call :meth:`Tracer.emit_span` at each hop."""

    __slots__ = ("exemplar", "span_id", "parent_id")

    def __init__(self, exemplar: str, span_id: int,
                 parent_id: int | None = None):
        self.exemplar = str(exemplar)
        self.span_id = int(span_id)
        self.parent_id = None if parent_id is None else int(parent_id)

    def child(self, span_id: int) -> "TraceContext":
        return TraceContext(self.exemplar, span_id, self.span_id)


def head_sampled(counter: int, sample_n: int) -> bool:
    """The 1-in-N head-sampling rule shared by every trace emitter: the
    FIRST request of a stream (counter 1) is always an exemplar, then
    every ``sample_n``-th after it. ``sample_n <= 0`` disables."""
    if sample_n <= 0:
        return False
    return (int(counter) - 1) % int(sample_n) == 0


class LineageReducer:
    """Exact per-update staleness from per-transition lineage stamps
    (ISSUE 14 tentpole, piece 2): every transition carries the param
    version that ACTED it; the reducer turns one update's version column
    into the exact staleness distribution the SLO plane previously only
    approximated from fanout-vs-fleet version gaps.

    Transfer-guard discipline: the version column is already host memory
    (the trainer pops it before ``device_put``) and the reduction is
    ``np.unique`` + integer arithmetic — no device values are ever
    touched, so the exact path adds zero device->host syncs.

    Percentiles use the same exact-index formula as
    :func:`latency_percentiles` (``xs[min(n-1, int(p*(n-1)+0.5))]``) over
    the sorted staleness multiset, walked via version counts instead of
    materializing 32k-element sorted lists — bit-matchable by hand."""

    def __init__(self):
        self.updates = 0
        self.last: dict[str, float] = {}

    def reduce(self, current_version: int, versions) -> dict[str, float]:
        """One update's ``lineage/*`` gauges from its acting-version
        column (any-shape host int array). Empty dict when the column is
        empty (nothing consumed, nothing to claim)."""
        import numpy as np

        arr = np.asarray(versions).reshape(-1)
        if arr.size == 0:
            return {}
        vals, counts = np.unique(arr.astype(np.int64), return_counts=True)
        cur = int(current_version)
        # staleness sorted ascending = current - version, versions walked
        # DESCENDING; cumulative counts give the element at any exact index
        stal = [int(cur - v) for v in vals[::-1]]
        cnts = [int(c) for c in counts[::-1]]
        n = int(arr.size)

        def pct(p: float) -> int:
            k = min(n - 1, int(p * (n - 1) + 0.5))
            seen = 0
            for s, c in zip(stal, cnts):
                seen += c
                if k < seen:
                    return s
            return stal[-1]

        self.updates += 1
        self.last = {
            "lineage/staleness_p50": float(pct(0.50)),
            "lineage/staleness_p99": float(pct(0.99)),
            "lineage/staleness_max": float(stal[-1]),
            "lineage/versions_per_batch": float(len(stal)),
        }
        return dict(self.last)


# -- the launch record --------------------------------------------------------
#
# A launch is over before most of a session exists: imports, the TPU
# client's start and the trainer's build happen before there is a folder
# to write to. So its spans are kept process-wide, in memory, from the
# first launch_span on, and the session's Tracer writes them as ONE
# ``launch`` event when the first ``metrics-sync`` ends. The ten spans
# whose parent is ``launch``, each opened inside the function it times:
#
#   launch.process         process start -> surreal_tpu/__init__.py
#   launch.import          -> main/launch.py::build_config (launch_imported)
#   launch.backend         main/launch.py::_apply_backend, _require_platform
#   launch.build           main/launch.py::select_trainer (and run_train's
#                          multi-host constructors)
#   launch.state_init      learner.init in Trainer.run / OffPolicyTrainer.run
#   launch.session         SessionHooks.__init__, restore, begin_run
#   launch.carry_init      init_loop_state in the two fused drivers
#   launch.cost_record     SessionHooks.record_program_costs
#   launch.first_dispatch  the engine's first step (engine/core.py)
#   launch.first_cadence   -> the first metrics-sync's end (end_iteration)
#
# Spans of one name add up (a caller that makes the calls one by one
# records what run_train records); a driver without some of them reports
# that time as unattributed.

LAUNCH_SPANS = (
    "launch.process", "launch.import", "launch.backend", "launch.build",
    "launch.state_init", "launch.session", "launch.carry_init",
    "launch.cost_record", "launch.first_dispatch", "launch.first_cadence",
)
# what utils/compat.py's jax.monitoring listeners add to a span
LAUNCH_COUNTERS = (
    "trace_s", "lower_s", "compile_s", "cache_read_s",
    "cache_hits", "cache_misses",
)


def _process_age_s() -> float | None:
    """Seconds since the OS started this process: field 22 of
    ``/proc/self/stat`` (clock ticks after boot) against the boot clock.
    None where the OS gives none."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name may hold spaces: fields count from its ')'
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (
            time.clock_gettime(time.CLOCK_BOOTTIME)
            - start_ticks / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class LaunchRecord:
    """One launch's spans, in memory until :meth:`close` hands them to a
    Tracer. Times are seconds since ``t0`` (a ``perf_counter`` reading).
    Thread-safe: with ``engine.pipeline_sidebands`` on, the staging
    thread closes the record while the loop's thread may be compiling."""

    def __init__(self, origin: str, t0: float):
        self.origin = origin
        self.t0 = t0
        self.t0_unix = time.time() - (time.perf_counter() - t0)
        self.spans: list[dict] = []   # in start order
        self.outside: dict = {}       # counters that fired between spans
        self.closed = False
        self._open: list[dict] = []   # innermost last
        # (a span's id, counter) -> its (start, end) that no later one held
        self._intervals: dict[tuple, list] = {}
        self._lock = threading.Lock()

    def _past(self, name: str, start: float, end: float) -> None:
        """A span that was over before the record existed."""
        self.spans.append({
            "name": name, "parent": "launch",
            "start_s": start - self.t0, "end_s": end - self.t0,
        })

    def begin(self, name: str) -> dict:
        """Open a span and leave it open: :meth:`end` or :meth:`close`
        ends it (``launch.first_cadence`` begins in the engine and ends
        in SessionHooks, on another thread when the boundary is
        deferred, so it is no annotation)."""
        with self._lock:
            span = {
                "name": name,
                "parent": self._open[-1]["name"] if self._open else "launch",
                "start_s": time.perf_counter() - self.t0, "end_s": None,
            }
            self.spans.append(span)
            self._open.append(span)
        return span

    def end(self, span: dict) -> None:
        with self._lock:
            if span["end_s"] is None:  # close() may have ended it
                span["end_s"] = time.perf_counter() - self.t0
            self._open = [s for s in self._open if s is not span]

    @contextmanager
    def span(self, name: str):
        span = self.begin(name)
        try:
            with trace_annotation(name):
                yield
        finally:
            self.end(span)

    def _into(self) -> dict | None:
        """Where a counter goes now (the lock is held): the innermost open
        span, ``outside`` between spans, nowhere once closed."""
        if self.closed:
            return None
        return self._open[-1] if self._open else self.outside

    def add(self, counter: str, amount: float) -> None:
        """Add to the innermost open span's ``counter``."""
        with self._lock:
            into = self._into()
            if into is not None:
                into[counter] = into.get(counter, 0) + amount

    def add_interval(self, counter: str, start: float, end: float) -> None:
        """Add ``end - start`` seconds to the innermost open span's
        ``counter``, less what intervals inside it already added: JAX
        times a function traced inside another's trace twice, the inner
        one first. ``start`` and ``end`` are on any one clock."""
        with self._lock:
            into = self._into()
            if into is None:
                return
            kept = self._intervals.setdefault((id(into), counter), [])
            seconds = end - start
            # they end in order, so what this one holds is the list's tail
            while kept and start <= kept[-1][0] and kept[-1][1] <= end:
                inner = kept.pop()
                seconds -= inner[1] - inner[0]
            kept.append((start, end))
            into[counter] = into.get(counter, 0) + seconds

    def close(self, tracer: "Tracer", closed: bool = True) -> None:
        """End what is still open and write the ``launch`` event through
        ``tracer`` (nothing, where it is disabled). ``closed=False``: the
        run ended before its first metrics-sync."""
        with self._lock:
            if self.closed:
                return
            self.closed = True
            total = time.perf_counter() - self.t0
            for span in self._open:
                span["end_s"] = total
            self._open.clear()
            self._intervals.clear()
        covered, edge = 0.0, 0.0
        for span in self.spans:  # in start order; top-level ones may touch
            if span["parent"] == "launch":
                covered += max(span["end_s"], edge) - max(span["start_s"], edge)
                edge = max(edge, span["end_s"])
        fields = {"outside": self.outside} if self.outside else {}
        tracer.event(
            "launch", origin=self.origin, t0_unix=self.t0_unix,
            total_s=total, unattributed_s=total - covered, closed=closed,
            spans=self.spans, **fields,
        )


_LAUNCH: LaunchRecord | None = None   # the process's open (or last) record
_LAUNCH_LOCK = threading.Lock()


def _process_launch() -> LaunchRecord:
    """The process's first record: its origin is the process's start as
    the OS records it, so the interpreter's start and whatever the caller
    imported before the package are inside ``launch.process``."""
    from surreal_tpu import IMPORTED_AT

    now = time.perf_counter()
    age = _process_age_s()
    if age is not None and age >= now - IMPORTED_AT:
        rec = LaunchRecord("os", now - age)
        rec._past("launch.process", rec.t0, IMPORTED_AT)
    else:
        rec = LaunchRecord("import", IMPORTED_AT)
    rec._past("launch.import", IMPORTED_AT, now)
    return rec


def launch_record() -> LaunchRecord:
    """The open launch record. Where there is none: the process's own the
    first time, and from then on (a second session of one process) one
    whose origin is this call, the start of its first span."""
    global _LAUNCH
    with _LAUNCH_LOCK:
        if _LAUNCH is None:
            _LAUNCH = _process_launch()
        elif _LAUNCH.closed:
            _LAUNCH = LaunchRecord("session", time.perf_counter())
        return _LAUNCH


def launch_imported() -> None:
    """The entry point's first call: ``launch.import`` ends here (or at
    the first launch span, where a caller builds no config through
    ``main/launch.py``). Nothing after the process's first record."""
    if _LAUNCH is None:
        launch_record()


@contextmanager
def launch_span(name: str):
    """A span of the open launch record (one is opened where none is)
    and, like every ``Tracer.span``, a ``trace_annotation`` of the same
    name. Usable before a Tracer exists, and as a decorator."""
    with launch_record().span(name):
        yield


def launch_begin(name: str) -> None:
    """Open a span that the record's close ends (see
    :meth:`LaunchRecord.begin`)."""
    launch_record().begin(name)


def launch_add(counter: str, amount: float) -> None:
    """Add to the innermost open launch span's ``counter`` (one of
    ``LAUNCH_COUNTERS``); nothing once the launch has closed."""
    rec = _LAUNCH
    if rec is not None and not rec.closed:
        rec.add(counter, amount)


def launch_add_interval(counter: str, start: float, end: float) -> None:
    """:func:`launch_add` for a timed interval that may lie inside
    another of the same counter (see
    :meth:`LaunchRecord.add_interval`)."""
    rec = _LAUNCH
    if rec is not None and not rec.closed:
        rec.add_interval(counter, start, end)


def launch_close(tracer: "Tracer", closed: bool = True) -> None:
    """Close the open launch record into ``tracer``'s log, if one is
    open."""
    rec = _LAUNCH
    if rec is not None:
        rec.close(tracer, closed)


class Tracer:
    """Span tracing + JSONL event log for one session (rank 0 owns it,
    exactly like the MetricsWriter; disabled tracers are free no-ops so
    driver loops on ranks > 0 share the same code path).

    Thread-safe: the host-overlap collector thread and the SEED server
    side-bands record spans concurrently with the main loop.
    """

    def __init__(self, folder: str | None, enabled: bool = True,
                 name: str = "train", trace_id: str | None = None,
                 max_log_mb: float | None = None,
                 trace_sample_n: int = 64, trace_keep: int = 8):
        self.enabled = bool(enabled) and folder is not None
        self._lock = threading.Lock()
        self._phases: dict[str, list] = {}  # name -> [count, total_s, max_s]
        self._f = None
        self.path = None
        # size-based rotation (ISSUE 13): a production-length run must not
        # grow events.jsonl without bound. When the log passes max_log_mb
        # it rotates to <path>.1 (one generation — the previous .1 is
        # dropped) and _iter_jsonl/diag read the segments in order.
        self._max_bytes = (
            int(float(max_log_mb) * 1e6) if max_log_mb else None
        )
        self._bytes = 0
        self.rotations = 0
        # cross-process trace correlation (ISSUE 6): a run-scoped trace id
        # stamped (with a per-process span-sequence counter) into every
        # event; spawned env workers / the inference server / the param
        # service inherit it so diag can stitch one cross-process
        # timeline. Minted even when disabled — ranks > 0 still forward
        # it to the components they spawn.
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self._seq = 0
        # causal span trees (ISSUE 14): head-sample cadence every emitter
        # shares, a run-unique span-id counter (all emitters are threads
        # of the session process, handed THIS tracer as their span sink),
        # the chaos-counted drop tally, and the last-K exemplar ring the
        # flight recorder snapshots into its dumps
        self.trace_sample_n = int(trace_sample_n)
        self.dropped_spans = 0
        self.spans_emitted = 0
        self._span_ids = 0
        self._recent_exemplars: "deque[dict]" = deque(
            maxlen=max(1, int(trace_keep))
        )
        # last flushed phase window ({name: {count, total_s, max_ms}}) —
        # the cost accountant (session/costs.py) derives the perf/* gauges
        # from it without re-reading the event log
        self.last_window: dict[str, dict] = {}
        # every span name this tracer has seen: the profile digest tells
        # the program's spans from the runtime's own host events by it
        self.span_names: set[str] = set()
        if self.enabled:
            try:
                tel_dir = os.path.join(folder, TELEMETRY_DIR)
                os.makedirs(tel_dir, exist_ok=True)
                self.path = os.path.join(tel_dir, EVENTS_FILE)
                self._f = open(self.path, "a", buffering=1)  # line-buffered
                self._bytes = os.path.getsize(self.path)  # resumed session
            except OSError:
                # telemetry must never kill training (e.g. read-only FS)
                self.enabled = False
                self._f = None
        if self.enabled:
            self.event("session", name=name, pid=os.getpid())

    # -- raw events ----------------------------------------------------------
    def event(self, type_: str, **fields) -> None:
        """Append one event line. Fields must be JSON-serializable."""
        if not self.enabled:
            return
        with self._lock:
            if self._f is None:
                return
            self._seq += 1
            line = json.dumps(
                {
                    "type": type_, "t": time.time(),
                    "trace": self.trace_id, "seq": self._seq,
                    **fields,
                },
                default=float,
            )
            try:
                self._f.write(line + "\n")
                self._bytes += len(line) + 1
                if self._max_bytes and self._bytes > self._max_bytes:
                    # rotate under the same lock the write holds: close,
                    # shift to .1 (dropping the previous .1 — two
                    # generations bound the disk at ~2x max_log_mb),
                    # reopen fresh
                    self._f.close()
                    os.replace(self.path, self.path + ".1")
                    self._f = open(self.path, "a", buffering=1)
                    self._bytes = 0
                    self.rotations += 1
            except OSError:
                # telemetry must never kill training: a mid-run disk-full/
                # mount hiccup disables the log instead of propagating
                try:
                    if self._f is not None:
                        self._f.close()
                except OSError:
                    pass
                self._f = None
                self.enabled = False

    # -- spans ---------------------------------------------------------------
    @contextmanager
    def span(self, name: str, emit: bool = False):
        """Time a region into the ``name`` phase accumulator and
        annotate it on the host plane of any active profile (a disabled
        tracer still annotates: ranks > 0 are profiled too).

        ``emit=True`` additionally writes an individual ``span`` event
        (low-frequency side-bands only).
        """
        self.span_names.add(name)
        if not self.enabled:
            with trace_annotation(name):
                yield
            return
        t0 = time.perf_counter()
        try:
            with trace_annotation(name):
                yield
        finally:
            dur = time.perf_counter() - t0
            self.add_phase(name, dur)
            if emit:
                self.event("span", name=name, dur_s=dur)

    def add_phase(self, name: str, dur_s: float, count: int = 1) -> None:
        """Add ``dur_s`` seconds over ``count`` calls to the ``name``
        phase accumulator: a region the caller timed itself (the fenced
        ``cadence`` span of SessionHooks)."""
        if not self.enabled:
            return
        with self._lock:
            st = self._phases.setdefault(name, [0, 0.0, 0.0])
            st[0] += count
            st[1] += dur_s
            st[2] = max(st[2], dur_s)

    # -- causal trace exemplars (ISSUE 14) -----------------------------------
    def next_span_id(self) -> int:
        """A run-unique span id (every trace emitter is a thread of the
        session process sharing this tracer, so one locked counter is
        globally unique within a run's event log)."""
        with self._lock:
            self._span_ids += 1
            return self._span_ids

    def trace_context(self, exemplar: str) -> TraceContext:
        """Mint a ROOT context for a newly head-sampled request."""
        return TraceContext(exemplar, self.next_span_id(), None)

    def emit_span(self, name: str, ctx: TraceContext, *,
                  tier: str | None = None, dur_ms: float | None = None,
                  **fields) -> None:
        """Emit one causal ``span`` event for hop ``ctx`` of its exemplar
        tree. The ``trace.emit`` chaos site fires here: ``drop_span``
        swallows the event but COUNTS it (``trace/dropped_spans``) and the
        span id stays allocated, so children still reference the missing
        hop and the trace CLI renders the tear instead of hiding it;
        ``delay`` stalls the emit (spans are side-band — a slow emit must
        never be mistaken for a slow hop, so callers pass dur_ms measured
        BEFORE calling)."""
        if not self.enabled:
            return
        from surreal_tpu.utils import faults

        f = faults.fire("trace.emit")
        if f is not None:
            if f["kind"] == "drop_span":
                with self._lock:
                    self.dropped_spans += 1
                return
            if f["kind"] == "delay":
                faults.sleep_ms(f)
        rec = {
            "name": name, "exemplar": ctx.exemplar, "span": ctx.span_id,
            "parent": ctx.parent_id, **fields,
        }
        if tier is not None:
            rec["tier"] = tier
        if dur_ms is not None:
            rec["dur_ms"] = float(dur_ms)
        with self._lock:
            self.spans_emitted += 1
            self._recent_exemplars.append(dict(rec, t=time.time()))
        self.event("span", **rec)

    def trace_gauges(self) -> dict[str, float]:
        """The ``trace/*`` gauge family (GAUGE_REGISTRY documents each);
        merged into the learner's metrics row each cadence."""
        return {
            "trace/spans": float(self.spans_emitted),
            "trace/dropped_spans": float(self.dropped_spans),
        }

    def recent_exemplar_spans(self) -> list[dict]:
        """The last-K exemplar span records (newest last) — the flight
        recorder writes them into every dump so a frozen incident carries
        the requests that flew through it."""
        with self._lock:
            return [dict(r) for r in self._recent_exemplars]

    def flush_phases(self, step) -> dict[str, float]:
        """Write one ``phases`` event for the window since the last flush
        and return ``time/<phase>_ms`` mean-per-call scalars — the mirror
        the caller merges into the MetricsWriter stream. Resets the
        window. Called at the metrics cadence by SessionHooks."""
        with self._lock:
            phases = {
                k: {"count": c, "total_s": t, "max_ms": mx * 1e3}
                for k, (c, t, mx) in self._phases.items()
            }
            self._phases.clear()
        self.last_window = phases
        if not phases:
            return {}
        self.event("phases", step=int(step), phases=phases)
        return {
            f"time/{k}_ms": v["total_s"] / max(v["count"], 1) * 1e3
            for k, v in phases.items()
        }

    def log_metrics(self, step, metrics) -> None:
        """Mirror one synced metrics row into the event log (what ``diag``
        reads for the health summary)."""
        if not self.enabled or not metrics:
            return
        self.event(
            "metrics", step=int(step),
            values={k: float(v) for k, v in metrics.items()},
        )

    def close(self) -> None:
        # flush the tail window first: a run shorter than one metrics
        # cadence (or one that crashed into its finally-close) must still
        # record the spans it accumulated. step=-1 marks an at-close
        # flush; diag ignores it for last-step reporting.
        self.flush_phases(step=-1)
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None
        self.enabled = False


class HeartbeatWriter:
    """Per-host liveness events for multi-host runs: each rank appends to
    its OWN ``telemetry/heartbeat_rank<k>.jsonl`` (no cross-rank
    coordination — a wedged rank is visible precisely because it stops
    writing). Ranks whose host cannot write the session folder disable
    themselves silently: ranks > 0 are not required to mount it
    (launch/multihost_trainer.py's session discipline)."""

    def __init__(self, folder: str | None, rank: int, every_s: float = 10.0,
                 enabled: bool = True):
        self.rank = int(rank)
        self.every_s = float(every_s)
        self._last: float | None = None
        self._path = None
        if enabled and folder:
            try:
                tel_dir = os.path.join(folder, TELEMETRY_DIR)
                os.makedirs(tel_dir, exist_ok=True)
                self._path = os.path.join(
                    tel_dir, f"heartbeat_rank{self.rank}.jsonl"
                )
                with open(self._path, "a"):
                    pass  # probe writability up front
            except OSError:
                self._path = None

    def beat(self, iteration: int, env_steps: int, force: bool = False) -> None:
        """Append a heartbeat, time-throttled to ``every_s`` (call it every
        iteration; it is a no-op between beats)."""
        if self._path is None:
            return
        now = time.monotonic()
        if not force and self._last is not None and now - self._last < self.every_s:
            return
        self._last = now
        rec = {
            "type": "heartbeat", "t": time.time(), "rank": self.rank,
            "iteration": int(iteration), "env_steps": int(env_steps),
            # cadence rides in the record so diag can flag a rank whose
            # newest beat is older than 3x its own cadence as DEAD
            "every_s": self.every_s,
        }
        try:
            with open(self._path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError:
            self._path = None  # host lost the folder; stop trying


# -- diag --------------------------------------------------------------------

_HEALTH_PREFIXES = ("health/", "loss/", "policy/kl", "episode/return")


def _iter_jsonl(path, rotated: bool = True):
    """Yield one JSON object per parseable line, tolerating a
    partially-written trailing line. Two torn-tail shapes exist after a
    chaos-harness kill (PR 5) mid-``write``: an incomplete JSON text
    (JSONDecodeError — skipped per line) and a line truncated INSIDE a
    multi-byte UTF-8 sequence, which raises UnicodeDecodeError from the
    file iterator itself unless decoding is lossy — ``errors='replace'``
    turns it into a replacement char the per-line parse then skips.

    ``rotated``: the Tracer's size-based rotation (ISSUE 13) shifts a
    full log to ``<path>.1``; the rotated segment is older, so it is
    read FIRST and the live file second — one chronological stream. A
    rotation racing this read at worst repeats or drops lines across
    the segment boundary; every line still parses (diag's mid-rotation
    test pins this down)."""
    paths = [path]
    if rotated and os.path.exists(path + ".1"):
        paths.insert(0, path + ".1")
    for p in paths:
        try:
            with open(p, errors="replace") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail line from a live/killed session
        except OSError:
            continue


def diag_summary(folder: str) -> dict | None:
    """Aggregate the session's telemetry files into one dict, or None when
    no event log exists. Pure file reading — no jax, safe off-chip."""
    events_path = os.path.join(folder, TELEMETRY_DIR, EVENTS_FILE)
    events = list(_iter_jsonl(events_path))
    hb_paths = sorted(
        glob.glob(os.path.join(folder, TELEMETRY_DIR, "heartbeat_rank*.jsonl"))
    )
    if not events and not hb_paths:
        return None

    phases: dict[str, dict] = {}
    health: dict[str, dict] = {}
    compile_cache = None
    launch = None
    data_plane = None
    experience = None
    serving = None
    gateway = None
    engine = None
    trace_id = None
    programs: dict[str, dict] = {}   # program_cost events (last per name)
    precision = None                 # last 'precision' event (active policy)
    perf_last: dict[str, float] = {}  # perf/*, replay/* gauges, last row
    hops = None                      # last 'hops' event's percentiles
    profiles: list[dict] = []        # 'profile' capture events
    recovery_counts: dict[str, int] = {}
    recovery_last = None
    fault_count = 0
    fault_sites: dict[str, int] = {}
    fault_last = None
    nonfinite_windows = 0
    t_first = t_last = None
    last_step = None
    for ev in events:
        t = ev.get("t")
        if isinstance(t, (int, float)):
            t_first = t if t_first is None else min(t_first, t)
            t_last = t if t_last is None else max(t_last, t)
        if trace_id is None and ev.get("trace"):
            trace_id = ev["trace"]
        if ev.get("type") == "phases":
            step = ev.get("step")
            if isinstance(step, int) and step >= 0:  # -1 = at-close flush
                last_step = step
            for name, st in (ev.get("phases") or {}).items():
                agg = phases.setdefault(
                    name, {"count": 0, "total_s": 0.0, "max_ms": 0.0}
                )
                agg["count"] += int(st.get("count", 0))
                agg["total_s"] += float(st.get("total_s", 0.0))
                agg["max_ms"] = max(agg["max_ms"], float(st.get("max_ms", 0.0)))
        elif ev.get("type") == "compile_cache":
            # counters are cumulative; the last event is the session total
            compile_cache = {
                "dir": ev.get("dir"),
                "hits": int(ev.get("hits", 0)),
                "misses": int(ev.get("misses", 0)),
            }
        elif ev.get("type") == "launch":
            # one a session; a folder relaunched into holds one a launch,
            # and the newest is the one an operator asks about
            launch = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "data_plane":
            # the last event is the settled negotiation (SEED drivers emit
            # one after the first learn and one at run end)
            data_plane = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "serving_tier":
            # the last event is the settled tier shape (one per metrics
            # row while an InferenceFleet is active)
            serving = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "experience_plane":
            # the last event is the settled plane shape (one per metrics
            # row while a sharded experience plane is active)
            experience = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "gateway":
            # the last event is the settled tenant picture (one per
            # metrics row while the session gateway is live)
            gateway = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "engine":
            # the last event is the settled loop-engine picture (one per
            # metrics row; counters are cumulative)
            engine = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "recovery":
            kind = str(ev.get("kind", "?"))
            recovery_counts[kind] = recovery_counts.get(kind, 0) + 1
            recovery_last = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "fault":
            fault_count += 1
            site = str(ev.get("site", "?"))
            fault_sites[site] = fault_sites.get(site, 0) + 1
            fault_last = {
                k: v for k, v in ev.items() if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "program_cost":
            name = str(ev.get("name", "?"))
            programs[name] = {
                k: v for k, v in ev.items()
                if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "precision":
            # last event wins (one per run; a resumed session re-emits)
            precision = {
                k: v for k, v in ev.items()
                if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "hops":
            # last event wins: the window's rolling-deque percentiles
            hops = {
                k: v for k, v in ev.items()
                if k not in ("type", "t", "trace", "seq")
            }
        elif ev.get("type") == "profile":
            profiles.append({
                k: v for k, v in ev.items()
                if k not in ("type", "t", "trace", "seq")
            })
        elif ev.get("type") == "metrics":
            last_step = ev.get("step", last_step)
            vals = ev.get("values") or {}
            for k, v in vals.items():
                if (
                    k.startswith(("perf/", "lineage/", "trace/", "replay/"))
                    and isinstance(v, (int, float))
                ):
                    perf_last[k] = v
            if vals.get("health/nonfinite", 0):
                nonfinite_windows += 1
            for k, v in vals.items():
                if not isinstance(v, (int, float)):
                    continue
                if not any(k.startswith(p) or k == p for p in _HEALTH_PREFIXES):
                    continue
                if v != v:  # NaN rows carry no summary information
                    continue
                h = health.setdefault(
                    k, {"last": v, "min": v, "max": v, "n": 0}
                )
                h["last"] = v
                h["min"] = min(h["min"], v)
                h["max"] = max(h["max"], v)
                h["n"] += 1

    heartbeats = {}
    now = time.time()
    for path in hb_paths:
        last = None
        prev_t = None
        deltas: list[float] = []
        for rec in _iter_jsonl(path):
            if rec.get("type") == "heartbeat":
                t = rec.get("t")
                if isinstance(t, (int, float)) and prev_t is not None:
                    deltas.append(t - prev_t)
                prev_t = t if isinstance(t, (int, float)) else prev_t
                last = rec
        if last is not None:
            # staleness: a rank whose newest beat is older than 3x its
            # cadence is flagged DEAD instead of silently looking fine.
            # Cadence comes from the record (new runs), else is inferred
            # from the observed beat deltas (old logs), else defaults.
            cadence = last.get("every_s")
            if not isinstance(cadence, (int, float)) or cadence <= 0:
                cadence = (
                    sorted(deltas)[len(deltas) // 2] if deltas else 10.0
                )
            age = now - float(last.get("t", now))
            heartbeats[int(last.get("rank", -1))] = {
                **last,
                "age_s": age,
                "cadence_s": float(cadence),
                "dead": age > 3.0 * float(cadence),
            }

    return {
        "folder": folder,
        "trace_id": trace_id,
        "events": len(events),
        "wall_s": (t_last - t_first) if (t_first is not None and t_last is not None) else 0.0,
        "last_step": last_step,
        "phases": phases,
        "health": health,
        "compile_cache": compile_cache,
        "launch": launch,
        "data_plane": data_plane,
        "experience": experience,
        "serving": serving,
        "gateway": gateway,
        "engine": engine,
        "recovery": (
            {"counts": recovery_counts, "last": recovery_last}
            if recovery_counts else None
        ),
        "faults": (
            {"count": fault_count, "by_site": fault_sites, "last": fault_last}
            if fault_count else None
        ),
        "nonfinite_windows": nonfinite_windows,
        "heartbeats": heartbeats,
        "programs": programs,
        "precision": precision,
        "perf": perf_last,
        "hops": hops,
        "profiles": profiles,
    }


def diag_report(folder: str) -> str | None:
    """Human-readable diag: phase-time breakdown, health summary,
    last-heartbeat table. None when the folder has no telemetry."""
    s = diag_summary(folder)
    if s is None:
        return None
    wall = s["wall_s"]
    lines = [
        f"Telemetry diag — {s['folder']}",
        f"{s['events']} events over {wall:.1f} s"
        + (f", last step {s['last_step']}" if s["last_step"] is not None else "")
        + (f", trace {s['trace_id']}" if s.get("trace_id") else ""),
        "",
        "Phase-time breakdown",
    ]
    if s["phases"]:
        lines.append(
            f"  {'phase':<20} {'calls':>8} {'total s':>10} {'mean ms':>10} "
            f"{'max ms':>10} {'% wall':>7}"
        )
        for name, st in sorted(
            s["phases"].items(), key=lambda kv: -kv[1]["total_s"]
        ):
            mean_ms = st["total_s"] / max(st["count"], 1) * 1e3
            pct = 100.0 * st["total_s"] / wall if wall > 0 else 0.0
            lines.append(
                f"  {name:<20} {st['count']:>8} {st['total_s']:>10.2f} "
                f"{mean_ms:>10.2f} {st['max_ms']:>10.2f} {pct:>6.1f}%"
            )
        lines.append(
            "  (device-loop phases measure async dispatch; window totals "
            "are honest under backpressure — see session/telemetry.py)"
        )
    else:
        lines.append("  (no phase windows recorded)")
    launch_lines = _launch_lines(s)
    if launch_lines:
        lines += [""] + launch_lines
    cc = s.get("compile_cache")
    if cc is not None:
        total = cc["hits"] + cc["misses"]
        lines += [
            "",
            f"Compile cache — {cc.get('dir')}",
            f"  {cc['hits']} hits / {cc['misses']} misses"
            + (
                f" ({100.0 * cc['hits'] / total:.0f}% warm)"
                if total else ""
            ),
        ]
    dpl = s.get("data_plane")
    if dpl is not None:
        lines += [
            "",
            "Data plane — "
            + ", ".join(f"{k}={dpl[k]}" for k in sorted(dpl)),
        ]
    eng_lines = _engine_lines(s)
    if eng_lines:
        lines += ["", "Loop engine"] + eng_lines
    tier_lines = _serving_tier_lines(s)
    if tier_lines:
        lines += ["", "Serving tier"] + tier_lines
    xp_lines = _experience_plane_lines(s)
    if xp_lines:
        lines += ["", "Experience plane"] + xp_lines
    gw_lines = _gateway_lines(s)
    if gw_lines:
        lines += ["", "Gateway"] + gw_lines
    perf_lines = _performance_lines(s)
    if perf_lines:
        lines += ["", "Performance"] + perf_lines
    rec = s.get("recovery")
    if rec is not None:
        counts = ", ".join(
            f"{k}={rec['counts'][k]}" for k in sorted(rec["counts"])
        )
        lines += ["", f"Recovery — {counts}"]
        last = rec.get("last") or {}
        if last:
            lines.append(
                "  last: "
                + ", ".join(f"{k}={last[k]}" for k in sorted(last))
            )
    flt = s.get("faults")
    if flt is not None:
        sites = ", ".join(
            f"{k}: {flt['by_site'][k]}" for k in sorted(flt["by_site"])
        )
        lines += [
            "",
            f"Faults injected (chaos harness) — {flt['count']} fired "
            f"({sites})",
        ]
    lines += ["", "Training health"]
    if s["health"]:
        lines.append(
            f"  {'signal':<26} {'last':>12} {'min':>12} {'max':>12} {'rows':>6}"
        )
        for k in sorted(s["health"]):
            h = s["health"][k]
            lines.append(
                f"  {k:<26} {h['last']:>12.4g} {h['min']:>12.4g} "
                f"{h['max']:>12.4g} {h['n']:>6}"
            )
        if s["nonfinite_windows"]:
            lines.append(
                f"  !! {s['nonfinite_windows']} metrics window(s) flagged "
                "health/nonfinite > 0 — NaN/inf hit the grads or params"
            )
        else:
            lines.append("  nonfinite guard: clean (no window flagged)")
    else:
        lines.append("  (no metrics rows recorded)")
    lines += ["", "Heartbeats"]
    if s["heartbeats"]:
        lines.append(
            f"  {'rank':>4} {'age s':>8} {'iteration':>10} {'env_steps':>12}"
            f"  status"
        )
        dead_ranks = []
        for rank in sorted(s["heartbeats"]):
            hb = s["heartbeats"][rank]
            age = float(hb.get("age_s", 0.0))
            dead = bool(hb.get("dead"))
            if dead:
                dead_ranks.append(rank)
            lines.append(
                f"  {rank:>4} {age:>8.1f} {hb.get('iteration', 0):>10} "
                f"{hb.get('env_steps', 0):>12}  "
                + (
                    f"DEAD (> 3x {hb.get('cadence_s', 0.0):.0f}s cadence)"
                    if dead else "alive"
                )
            )
        if dead_ranks:
            lines.append(
                f"  !! rank(s) {', '.join(str(r) for r in dead_ranks)} "
                "stopped heartbeating — wedged, killed, or the run ended"
            )
    else:
        lines.append("  (none recorded — single-host session)")
    # watchdog incidents (ISSUE 15): the `surreal_tpu why` brief, one
    # line per incident — the full root-cause report is `why`'s job.
    # Local import: incidents.py pulls in costs.py, and diag must stay a
    # pure-file-reading path that works even if that import breaks.
    try:
        from surreal_tpu.session.incidents import incidents_brief

        inc_lines = incidents_brief(s["folder"])
    except Exception:
        inc_lines = []
    if inc_lines:
        lines += ["", "Incidents (surreal_tpu why for the full report)"]
        lines += inc_lines
    return "\n".join(lines)


def _launch_lines(s: dict) -> list[str]:
    """The diag 'Launch' section, from the session's ``launch`` event: one
    row a span name in order of first start (spans of one name add up; a
    nested span is indented under its parent's share), its seconds, its
    share of the launch, and the compiler's seconds inside it where there
    were any; then the unattributed rest and the cache's hits and misses.
    Empty list when the session wrote no such event."""
    launch = s.get("launch")
    if not launch:
        return []
    total = float(launch.get("total_s", 0.0))
    origin = {
        "os": "process start", "import": "the package's import",
        "session": "the session's first span",
    }.get(launch.get("origin"), str(launch.get("origin")))
    lines = [
        f"Launch — {total:.2f} s from {origin} to "
        + ("the first metrics-sync's end" if launch.get("closed", True)
           else "the run's end (no metrics-sync was reached)"),
        f"  {'span':<26} {'seconds':>9} {'share':>7}  "
        "trace / lower / compile / cache read s",
    ]
    rows: dict[tuple, dict] = {}
    for span in launch.get("spans") or []:
        nested = span.get("parent", "launch") != "launch"
        row = rows.setdefault((span.get("name", "?"), nested), {"s": 0.0})
        row["s"] += float(span.get("end_s", 0.0)) - float(span.get("start_s", 0.0))
        for k in LAUNCH_COUNTERS:
            row[k] = row.get(k, 0) + span.get(k, 0)
    outside = launch.get("outside") or {}
    rows[("unattributed", False)] = {
        "s": float(launch.get("unattributed_s", 0.0)), **outside,
    }
    hits = misses = 0
    for (name, nested), row in rows.items():
        hits += row.get("cache_hits", 0)
        misses += row.get("cache_misses", 0)
        seconds = [row.get(k, 0.0) for k in LAUNCH_COUNTERS[:4]]
        lines.append(
            f"  {('  ' if nested else '') + name:<26} {row['s']:>9.2f} "
            f"{100.0 * row['s'] / total if total > 0 else 0.0:>6.1f}%"
            + (
                "  " + " / ".join(f"{x:.2f}" for x in seconds)
                if any(seconds) else ""
            )
        )
    lines.append(f"  compile cache in the launch: {hits} hits / {misses} misses")
    return lines


def _engine_lines(s: dict) -> list[str]:
    """The diag 'Loop engine' section: declared stage table (donate /
    deferrable / overlap bits), boundary + step latency percentiles,
    staging occupancy, and the deferred/skipped/killed boundary counters
    from the last ``engine`` event. Empty list when the session predates
    the engine (no event recorded)."""
    eng = s.get("engine")
    if not eng:
        return []
    lines = [
        "  pipelined={p} — {d} boundaries deferred, {sk} skipped "
        "(wedged past the stage bound), {k} stage kills".format(
            p=bool(eng.get("pipelined")),
            d=int(eng.get("deferred", 0)),
            sk=int(eng.get("skipped", 0)),
            k=int(eng.get("kills", 0)),
        ),
    ]
    st = eng.get("stage_ms") or {}
    sp = eng.get("step_ms") or {}
    if st or sp:
        lines.append(
            "  boundary p50/p99 {a:.2f}/{b:.2f} ms, step p50/p99 "
            "{c:.2f}/{d:.2f} ms, staging occupancy {o:.1%}".format(
                a=float(st.get("p50", 0.0)), b=float(st.get("p99", 0.0)),
                c=float(sp.get("p50", 0.0)), d=float(sp.get("p99", 0.0)),
                o=float(eng.get("occupancy", 0.0)),
            )
        )
    stages = eng.get("stages") or []
    if stages:
        lines.append(
            f"  {'stage':<12} {'donate':>7} {'deferrable':>11} {'overlap':>8}"
        )
        for spec in stages:
            lines.append(
                f"  {str(spec.get('name', '?')):<12} "
                f"{str(bool(spec.get('donate'))):>7} "
                f"{str(bool(spec.get('deferrable'))):>11} "
                f"{str(bool(spec.get('overlap'))):>8}"
            )
    return lines


def _serving_tier_lines(s: dict) -> list[str]:
    """The diag 'Serving tier' section: replica liveness/budget table,
    fleet-mean serve latency, scale/respawn counters from the last
    ``serving_tier`` event. Empty list when the session ran no fleet."""
    tier = s.get("serving")
    if not tier:
        return []
    lines = [
        "  {n} replica(s) alive over {w} workers — respawns {r:g}, "
        "scale ups {u:g} / downs {d:g}, autoscale {a}".format(
            n=int(tier.get("fleet/replicas_live", 0)),
            w=tier.get("num_workers", "?"),
            r=float(tier.get("fleet/respawns", 0)),
            u=float(tier.get("fleet/scale_ups", 0)),
            d=float(tier.get("fleet/scale_downs", 0)),
            a="on" if tier.get("autoscale") else "off",
        ),
    ]
    if tier.get("fleet/serve_ms") is not None:
        lines.append(
            f"  fleet serve EWMA {float(tier['fleet/serve_ms']):.2f} ms, "
            f"queue depth {float(tier.get('fleet/queue_depth', 0)):g}"
        )
    replicas = tier.get("replicas") or {}
    if replicas:
        lines.append(
            f"  {'replica':>8} {'state':<8} {'workers':>8} "
            f"{'min_batch':>10} {'serve ms':>9} {'evicted':>8}"
        )
        for rid in sorted(replicas, key=lambda x: int(x)):
            r = replicas[rid]
            serve = r.get("serve_ms")
            lines.append(
                f"  {rid:>8} {r.get('state', '?'):<8} "
                f"{r.get('workers', 0):>8} {r.get('min_batch', 0):>10} "
                + (f"{float(serve):>9.2f}" if serve is not None else f"{'n/a':>9}")
                + f" {r.get('evicted_chunks', 0):>8}"
            )
    return lines


def _experience_plane_lines(s: dict) -> list[str]:
    """The diag 'Experience plane' section: shard geometry/transport mix,
    per-shard replay gauges (fill, ingested rows, samples served, sample
    queue depth), the learner's sample-wait, and per-hop
    sender->shard->learner percentiles from the last ``experience_plane``
    event. Empty list when the session ran no plane."""
    xp = s.get("experience")
    if not xp:
        return []
    lines = [
        f"  {xp.get('kind', '?')} x {xp.get('num_shards', '?')} shards "
        f"({xp.get('shard_mode', '?')} mode), transports "
        f"{xp.get('transports', [])}",
        f"  wire {xp.get('wire_bytes_per_step', 0):.1f} B/step, learner "
        f"sample-wait {xp.get('sample_wait_ms', 0):.2f} ms (EWMA)",
    ]
    shards = xp.get("shards") or {}
    if shards:
        lines.append(
            f"  {'shard':>6} {'fill':>7} {'rows':>10} {'samples':>9} "
            f"{'queue':>6} {'ingest p50/p90/p99 ms':>24}"
        )
        for sid in sorted(shards, key=lambda x: int(x)):
            sh = shards[sid]
            tr = sh.get("ingest_transit_ms") or {}
            hop = (
                f"{tr.get('p50', 0):.2f}/{tr.get('p90', 0):.2f}/"
                f"{tr.get('p99', 0):.2f}" if tr else "n/a"
            )
            lines.append(
                f"  {sid:>6} {float(sh.get('fill', 0)):>7.2f} "
                f"{int(sh.get('ingested_rows', 0)):>10} "
                f"{int(sh.get('samples_served', 0)):>9} "
                f"{int(sh.get('sample_queue_depth', 0)):>6} {hop:>24}"
            )
    snd = xp.get("sender") or {}
    smp = xp.get("sampler") or {}
    if snd or smp:
        lines.append(
            "  sender: "
            + ", ".join(f"{k}={snd[k]:g}" for k in sorted(snd))
            + " | sampler: "
            + ", ".join(f"{k}={smp[k]:g}" for k in sorted(smp))
        )
    return lines


def _gateway_lines(s: dict) -> list[str]:
    """The diag 'Gateway' section: session/act totals, act-cache hit
    rate, migration/catch-up counters, pinned-version census, and the
    per-tenant admission table from the last ``gateway`` event. Empty
    list when the session ran no gateway."""
    gw = s.get("gateway")
    if not gw:
        return []
    acts = float(gw.get("gateway/acts", 0))
    lines = [
        "  {n:g} session(s) live at {a} — attaches {at:g} "
        "(+{re:g} re-attach), detaches {d:g}, expired {ex:g}".format(
            n=float(gw.get("gateway/sessions", 0)),
            a=gw.get("address", "?"),
            at=float(gw.get("gateway/attaches", 0)),
            re=float(gw.get("gateway/reattaches", 0)),
            d=float(gw.get("gateway/detaches", 0)),
            ex=float(gw.get("gateway/expired_leases", 0)),
        ),
        "  {ac:g} acts, cache hit-rate {hr:.0%} ({h:g} hits / {m:g} "
        "misses), migrations {mi:g}, catch-ups {cu:g}".format(
            ac=acts,
            hr=float(gw.get("cache_hit_rate", 0.0)),
            h=float(gw.get("gateway/cache_hits", 0)),
            m=float(gw.get("gateway/cache_misses", 0)),
            mi=float(gw.get("gateway/migrations", 0)),
            cu=float(gw.get("gateway/catch_ups", 0)),
        ),
    ]
    pins = gw.get("pinned_versions") or {}
    if pins:
        lines.append(
            "  pinned versions: "
            + ", ".join(
                f"v{v}×{pins[v]}" for v in sorted(pins, key=lambda x: int(x))
            )
        )
    tenants = gw.get("tenants") or {}
    if tenants:
        lines.append(
            f"  {'tenant':<12} {'sessions':>9} {'quota':>6} {'queued':>7} "
            f"{'throttled':>10} {'evicted':>8} {'rejected':>9}"
        )
        for name in sorted(tenants):
            t = tenants[name]
            quota = int(t.get("max_sessions", 0))
            lines.append(
                f"  {name:<12} {int(t.get('sessions', 0)):>9} "
                + (f"{quota:>6}" if quota else f"{'inf':>6}")
                + f" {int(t.get('queued', 0)):>7} "
                f"{int(t.get('throttled', 0)):>10} "
                f"{int(t.get('evicted', 0)):>8} "
                f"{int(t.get('rejected', 0)):>9}"
            )
    return lines


def _performance_lines(s: dict) -> list[str]:
    """The diag 'Performance' section: per-program roofline numbers
    (FLOPs / bytes / arithmetic intensity from program_cost events), the
    live perf/* and replay/* gauges from the last metrics row, per-hop latency
    percentiles (the stitched cross-process timeline), and captured
    profiler traces. Empty list when the session recorded none of them."""
    progs = s.get("programs") or {}
    prec = s.get("precision") or {}
    perf = s.get("perf") or {}
    hops = s.get("hops") or {}
    profiles = s.get("profiles") or []
    lines: list[str] = []
    if prec:
        # the active precision policy leads: every roofline number below
        # was produced under it (ops/precision.py)
        lines.append(
            f"  precision policy: {prec.get('policy', '?')} "
            f"(compute {prec.get('compute_dtype', '?')}, "
            f"staging {prec.get('data_dtype', '?')}, params "
            f"{prec.get('param_dtype', 'float32')}, loss scaling "
            + ("on" if prec.get("loss_scaling") else "off")
            + (", fp8 matmuls" if prec.get("fp8") else "")
            + ")"
        )
    if progs:
        any_rec = next(iter(progs.values()))
        kind = any_rec.get("device_kind", "?")
        pk_f, pk_b = any_rec.get("peak_flops"), any_rec.get("peak_membw")
        src = any_rec.get("peak_source", "?")
        lines.append(
            f"  device {kind} — peak "
            + (f"{pk_f / 1e12:.1f} TFLOP/s" if pk_f else "? FLOP/s")
            + ", "
            + (f"{pk_b / 1e9:.0f} GB/s" if pk_b else "? B/s")
            + f" ({src})"
        )
        lines.append(
            f"  {'program':<16} {'GFLOPs/call':>12} {'MB/call':>10} "
            f"{'arith int':>10} {'phase':<12}"
        )
        for name in sorted(progs):
            p = progs[name]
            ai = p.get("arithmetic_intensity")
            lines.append(
                f"  {name:<16} {p.get('flops', 0) / 1e9:>12.3f} "
                f"{p.get('bytes_accessed', 0) / 1e6:>10.2f} "
                + (f"{ai:>10.2f} " if ai else f"{'n/a':>10} ")
                + f"{p.get('phase') or '(unphased)':<12}"
            )
    if perf:
        bits = []
        if "perf/mfu" in perf:
            bits.append(f"mfu {perf['perf/mfu'] * 100:.3f}%")
        if "perf/membw_util" in perf:
            bits.append(f"membw_util {perf['perf/membw_util'] * 100:.2f}%")
        if "perf/flops_per_s" in perf:
            bits.append(
                f"flops/s {perf['perf/flops_per_s'] / 1e9:.2f} G"
            )
        if bits:
            lines.append("  gauges (last metrics row): " + ", ".join(bits))
    if "replay/fill" in perf:
        bits = [f"fill {perf['replay/fill'] * 100:.1f}%"]
        if "replay/sample_age_frac" in perf:
            bits.append(f"sample age {perf['replay/sample_age_frac']:.3f} of the fill")
        if "replay/max_priority" in perf:
            bits.append(f"max priority {perf['replay/max_priority']:.4g}")
        if "replay/mass_blocks_refreshed" in perf:
            # 0 = the fused update loop is not carrying the draw's block sums
            bits.append(
                "block sums refreshed an update "
                f"{perf['replay/mass_blocks_refreshed']:.1f}"
            )
        lines.append("  replay (last metrics row): " + ", ".join(bits))
    lin_p50 = perf.get("lineage/staleness_p50")
    if lin_p50 is not None:
        lines.append(
            "  lineage (exact per-update staleness, in updates): "
            f"p50 {lin_p50:g}, p99 {perf.get('lineage/staleness_p99', 0):g}, "
            f"max {perf.get('lineage/staleness_max', 0):g}, "
            f"{perf.get('lineage/versions_per_batch', 0):g} version(s)/batch"
        )
    if perf.get("trace/spans"):
        lines.append(
            f"  trace exemplars: {perf['trace/spans']:g} span(s) emitted, "
            f"{perf.get('trace/dropped_spans', 0):g} dropped (chaos)"
        )
    if hops:
        lines.append("  per-hop latency (cross-process timeline):")
        for hop in sorted(hops):
            st = hops[hop]
            if not isinstance(st, dict):
                continue
            lines.append(
                f"    {hop:<24} p50 {st.get('p50', 0):>8.2f} ms  "
                f"p90 {st.get('p90', 0):>8.2f}  p99 {st.get('p99', 0):>8.2f}"
                f"  (n={st.get('n', 0)})"
            )
    if profiles:
        lines.append(f"  profiler captures ({len(profiles)}):")
        for p in profiles[-8:]:
            lines.append(
                f"    {p.get('dir', '?')} — reason={p.get('reason', '?')}"
                + (
                    f", iters {p.get('start_iter')}-{p.get('end_iter')}"
                    if p.get("start_iter") is not None else ""
                )
                + (
                    f", digest failed: {p['digest_error']}"
                    if p.get("digest_error") else ""
                )
            )
        digests = [p for p in profiles if p.get("digest")]
        if digests:
            lines += _digest_lines(digests[-1])
    return lines


def _digest_lines(profile: dict) -> list[str]:
    """The newest capture's digest (session/profile.py): device time per
    phase per iteration with its share of busy, its count of ops and its
    sub-scopes beneath it; the same by model part with a part's phases
    beneath it; the Pallas kernels; then device idle time by the host span
    that covers it."""
    d = profile["digest"]
    lines = [
        "  digest of iters {a}-{b}: {n} iteration(s) on {dev} device(s), "
        "{tr:.1f} MB trace reduced in {ds:.1f} s; host spans seen: {hs}".format(
            a=profile.get("start_iter"), b=profile.get("end_iter"),
            n=d.get("steps", 0), dev=d.get("devices", 0),
            tr=d.get("trace_bytes", 0) / 1e6, ds=d.get("digest_s", 0.0),
            hs=", ".join(
                f"{k} x{v}" for k, v in sorted((d.get("host_spans") or {}).items())
            ) or "none",
        )
    ]
    phases = d.get("phases")
    if not phases:
        lines.append("    (no device plane in the capture: no phase split)")
        return lines
    per_iter = 1e3 / max(int(d.get("steps", 0)), 1)
    lines.append(
        "    window {w:.2f} ms/iter, busy {b:.2f}, idle {i:.3f}".format(
            w=d["window_s"] * per_iter, b=d["busy_s"] * per_iter,
            i=d["idle_s"] * per_iter,
        )
    )
    splits = [("phase", phases, d.get("subphases") or {})]
    parts = d.get("parts") or {}
    if len(parts) > 1:  # a model that scopes its parts (utils/phases.py)
        splits.append(("model part", parts, {
            # a part that runs in one phase has nothing to part
            k: v for k, v in (d.get("parts_by_phase") or {}).items()
            if sum(1 for ms in v.values() if ms) > 1
        }))
    for title, split, finer in splits:
        lines.append(
            f"    {title:<16} {'ms/iter':>10} {'% busy':>7}  largest ops (ms/iter)"
            + ("; op events/iter, those under 1 us" if title == "phase" else "")
        )
        for name, ph in sorted(
            split.items(), key=lambda kv: -kv[1]["ms_per_iter"]
        ):
            ops = ", ".join(f"{n} {ms:.2f}" for n, ms in ph.get("top_ops", []))
            short = ph.get("short_ops")
            if short:
                ops += (
                    f"; {ph['ops_per_iter']:.1f} ops, {short['per_iter']:.1f} "
                    f"under 1 us own {short['ms_per_iter']:.3f} ms"
                )
            lines.append(
                f"    {name:<16} {ph['ms_per_iter']:>10.3f} "
                f"{100.0 * ph['share_of_busy']:>6.1f}%  {ops}"
            )
            for sub, ms in sorted(
                finer.get(name, {}).items(), key=lambda kv: -kv[1]
            ):
                lines.append(f"      {sub:<14} {ms:>10.3f}")
    kernels = d.get("kernels") or {}
    if kernels:
        lines.append(
            f"    {'kernel':<24} {'ms/iter':>10} {'calls/iter':>10} "
            f"{'sites':>5}  part, ms/iter by phase"
        )
        for name, k in sorted(
            kernels.items(), key=lambda kv: -kv[1]["ms_per_iter"]
        ):
            by_phase = ", ".join(
                f"{p} {ms:.3f}" for p, ms in sorted(
                    k["by_phase"].items(), key=lambda kv: -kv[1]
                )
            )
            lines.append(
                f"    {name:<24} {k['ms_per_iter']:>10.3f} "
                f"{k['calls_per_iter']:>10.1f} {k['sites']:>5}  "
                f"{k['part']}: {by_phase}"
            )
    idle = d.get("idle_by_span") or {}
    if idle:
        lines.append(
            "    idle by span: " + ", ".join(
                f"{k} {v * 1e3:.3f} ms"
                for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
            )
        )
    return lines


# -- trace (causal span trees, ISSUE 14) --------------------------------------


def trace_summary(folder: str) -> dict | None:
    """Collect every causal span event (the ones ``Tracer.emit_span``
    stamps with an ``exemplar`` id) from the session's event log into
    per-exemplar groups. Pure file reading — no jax, safe off-chip. None
    when no event log exists."""
    events_path = os.path.join(folder, TELEMETRY_DIR, EVENTS_FILE)
    if not (os.path.exists(events_path)
            or os.path.exists(events_path + ".1")):
        return None
    exemplars: dict[str, list[dict]] = {}
    trace_id = None
    dropped = spans = None
    for ev in _iter_jsonl(events_path):
        if trace_id is None and ev.get("trace"):
            trace_id = ev["trace"]
        if ev.get("type") == "span" and ev.get("exemplar"):
            exemplars.setdefault(str(ev["exemplar"]), []).append(ev)
        elif ev.get("type") == "metrics":
            vals = ev.get("values") or {}
            if "trace/dropped_spans" in vals:
                dropped = vals["trace/dropped_spans"]
            if "trace/spans" in vals:
                spans = vals["trace/spans"]
    return {
        "folder": folder,
        "trace_id": trace_id,
        "exemplars": exemplars,
        "spans": spans,
        "dropped_spans": dropped,
    }


def _render_exemplar(spans: list[dict]) -> list[str]:
    """One exemplar's span tree, children indented under parents, ordered
    by wall time within a level. A span whose parent id was never emitted
    (chaos ``drop_span``, a crashed tier) is NOT hidden: it renders as a
    root with the missing hop marked — a torn tree is evidence."""
    by_id = {int(s["span"]): s for s in spans if s.get("span") is not None}
    kids: dict[int | None, list[dict]] = {}
    for s in sorted(spans, key=lambda x: (x.get("t", 0), x.get("seq", 0))):
        parent = s.get("parent")
        if parent is not None and int(parent) not in by_id:
            parent = ("missing", int(parent))  # torn: render as a root
        elif parent is not None:
            parent = int(parent)
        kids.setdefault(parent, []).append(s)
    t0 = min((s.get("t", 0) for s in spans), default=0)
    lines: list[str] = []

    def emit(s: dict, depth: int, missing_parent: int | None) -> None:
        dur = s.get("dur_ms")
        rel = (s.get("t", t0) - t0) * 1e3
        lines.append(
            f"  {'  ' * depth}[+{rel:8.2f} ms] {s.get('name', '?'):<22} "
            f"span {s.get('span')}  tier {s.get('tier', '?')}"
            + (f"  {float(dur):.3f} ms" if dur is not None else "")
            + (
                f"  !! parent span {missing_parent} MISSING "
                "(dropped/torn hop)" if missing_parent is not None else ""
            )
        )
        for child in kids.get(int(s["span"]), []) if s.get("span") is not None else []:
            emit(child, depth + 1, None)

    for root in kids.get(None, []):
        emit(root, 0, None)
    for parent_key in sorted(
        (k for k in kids if isinstance(k, tuple)),
        key=lambda k: k[1],
    ):
        for orphan in kids[parent_key]:
            emit(orphan, 0, parent_key[1])
    return lines


def trace_report(folder: str, limit: int = 16) -> str | None:
    """Human-readable causal trace timelines for ``surreal_tpu trace``:
    one span tree per head-sampled exemplar, newest last, torn hops
    marked. None when the folder has no telemetry."""
    s = trace_summary(folder)
    if s is None:
        return None
    exemplars = s["exemplars"]
    header = f"Causal trace exemplars — {s['folder']}"
    if s.get("trace_id"):
        header += f" (trace {s['trace_id']})"
    lines = [header]
    total = sum(len(v) for v in exemplars.values())
    summary = f"{len(exemplars)} exemplar(s), {total} span event(s)"
    if s.get("dropped_spans"):
        summary += (
            f"; {s['dropped_spans']:g} span(s) DROPPED by chaos — "
            "trees below may be torn"
        )
    lines.append(summary)
    if not exemplars:
        lines.append("  (no causal spans recorded — telemetry.trace "
                     "disabled or nothing sampled yet)")
        return "\n".join(lines)
    ordered = sorted(
        exemplars.items(),
        key=lambda kv: min(s.get("t", 0) for s in kv[1]),
    )
    if len(ordered) > limit:
        lines.append(f"  (showing oldest {limit} of {len(ordered)})")
        ordered = ordered[:limit]
    for name, spans in ordered:
        tiers = []
        for sp in sorted(spans, key=lambda x: (x.get("t", 0),
                                               x.get("seq", 0))):
            tier = sp.get("tier", "?")
            if tier not in tiers:
                tiers.append(tier)
        lines.append("")
        lines.append(
            f"exemplar {name} — {len(spans)} span(s), tiers: "
            + " -> ".join(tiers)
        )
        lines += _render_exemplar(spans)
    return "\n".join(lines)
