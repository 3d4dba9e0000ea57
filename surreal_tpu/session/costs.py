"""In-graph cost / MFU accounting (ISSUE 6 tentpole, piece 1).

The telemetry spine (PR 1) records wall-clock *phases* per process; this
module adds the compute-cost axis HEPPO-GAE (arXiv:2501.12703) used to
justify hardware-pipelined GAE: per-program FLOPs, bytes accessed, and
arithmetic intensity pulled from XLA's own cost model, resolved against a
per-backend peak-FLOPs/bandwidth table so the metrics stream carries live
``perf/mfu`` and ``perf/membw_util`` gauges — the instrument panel the
">=10x MFU" roadmap item is measured on.

Design constraints (the transfer-guard tests enforce the first):

- ZERO extra device->host syncs. Program costs come from
  ``jitted.lower(*args).cost_analysis()`` — tracing plus an HLO cost pass,
  both host-side — recorded ONCE per program at driver startup; the live
  gauges are pure host float arithmetic over the tracer's already-recorded
  phase windows (``Tracer.last_window``). Nothing here ever touches a
  device value. The TPU's client cannot cost unoptimized HLO (first chip
  run, PR 23: every program came back "no cost model", so no ``perf/mfu``
  had ever been emitted on a chip); there the numbers come from the
  COMPILED program. On jax 0.9.0 that compile is not a second one: the
  first dispatch of the same jitted function reuses it in-process
  (measured on the CPU backend: one compile-cache miss for the pair).
- ``memory_analysis()`` needs the compiled program too:
  ``session.perf.memory_analysis = 'auto'`` takes it only when the
  persistent compile cache is active; ``True``/``False`` force it.
- Honesty over coverage: the denominator is the window's fenced
  ``cadence`` seconds (end of one metrics sync to the end of the next:
  device time, where a program's own span only times its dispatch), so a
  gauge is a utilization of the wall clock; the numerator is XLA's STATIC
  count, which takes a loop body once whatever its trip count, so it is a
  lower bound (6.49e11 of the 5.01e12 FLOPs a fused PPO iteration
  requires; PERF.md section 7). Programs with no phase at all (the SEED
  act closure serves on its own thread) are recorded for ``diag`` but
  excluded from the live gauges rather than guessed at.

Gauge registry: every ``perf/*`` scalar the codebase emits MUST be listed
in :data:`GAUGE_REGISTRY` — ``tests/test_import_hygiene.py`` lints source
literals against it, so a new gauge cannot ship undocumented.
"""

from __future__ import annotations

from typing import Any

# the units a registered gauge may declare. The watchdog's threshold
# arithmetic keys off these (counters vs latencies vs ratios), and
# `surreal_tpu why` renders firing values with them — so the unit lint
# in tests/test_import_hygiene.py rejects anything outside this set.
GAUGE_UNITS = frozenset(
    {"ms", "bytes", "count", "ratio", "steps/s", "flops/s", "scalar"}
)


def _g(unit: str, desc: str) -> dict[str, str]:
    """One GAUGE_REGISTRY record: a documented description plus the
    machine-readable unit (ISSUE 15 — units used to live only in
    prose)."""
    return {"unit": unit, "desc": desc}


def gauge_unit(name: str) -> str | None:
    """The declared unit of a registered gauge, None for unregistered
    names (per-instance body keys the tiers invent)."""
    rec = GAUGE_REGISTRY.get(name)
    return rec.get("unit") if isinstance(rec, dict) else None


# Documented registry of every perf/*, replay/*, experience/*, fleet/*,
# param/*, and gateway/* gauge the codebase may emit.
# tests/test_import_hygiene.py::test_perf_gauges_appear_in_registry scans
# the package source for whole "<prefix>/<name>" literals and fails on
# any not listed here; every record carries a {unit, desc} dict (the unit
# lint rejects a bare string). Keep descriptions current — diag and
# README point here. Per-shard detail for the experience plane rides the
# 'experience_plane' telemetry EVENT (diag's "Experience plane" section);
# the metrics-row gauges below are the fleet aggregates.
GAUGE_REGISTRY = {
    "perf/mfu": _g("ratio",
        'model FLOP utilization over the metrics window: sum over '
        'registered programs of (flops/call x calls) / (fenced `cadence` '
        'seconds x peak FLOP/s). Lower bound, static count: XLA counts a '
        'loop body once, whatever its trip count.'),
    "perf/membw_util": _g("ratio",
        'memory-bandwidth utilization over the metrics window: bytes '
        'accessed (XLA cost model; lower bound, static count) per fenced '
        '`cadence` second / peak bytes/s.'),
    "perf/flops_per_s": _g("flops/s",
        'achieved model FLOP/s over the fenced `cadence` seconds of the '
        'metrics window (the MFU numerator: lower bound, static count; '
        'emitted even when no peak spec is known for the device).'),
    # -- replay occupancy (replay/base.py ring gauges; device scalars) ------
    "replay/size": _g("count",
        'absolute ring fill (transitions currently held).'),
    "replay/fill": _g("ratio", 'ring fill as a fraction of capacity.'),
    "replay/max_priority": _g("scalar",
        "prioritized replay's fresh-insert priority scale (pmax-synced "
        'across dp shards).'),
    "replay/mass_blocks_refreshed": _g("count",
        "prioritized replay inside the fused update loop: distinct blocks "
        "of 128 slots whose sum of p^alpha an update's priority scatter "
        'made the loop add up again (mean over the iteration\'s updates; at '
        'most batch_size). 0 = no update ran, or the loop is not carrying the '
        'block sums.'),
    "replay/sample_age_frac": _g("ratio",
        'mean staleness of a sampled index batch as a fraction of the '
        'current fill (0 = just written).'),
    # -- experience plane (surreal_tpu/experience/; fleet aggregates) -------
    "experience/shards_live": _g("count",
        'replay shard servers currently alive.'),
    "experience/respawns": _g("count",
        'shard respawns performed by the plane supervisor this run.'),
    "experience/rows": _g("count",
        'total transitions ingested across all shards.'),
    "experience/fill": _g("ratio", 'mean shard ring fill fraction.'),
    "experience/ingest_rows_per_s": _g("steps/s",
        'summed shard ingestion rate (the actor-fleet throughput the plane '
        'absorbs).'),
    "experience/wire_bytes_per_step": _g("bytes",
        'shard-side wire bytes (in+out) per ingested transition — the '
        'zero-copy success metric (control frames vs shipped arrays).'),
    "experience/sample_queue_depth": _g("count",
        'sample requests deferred at shards (watermark not yet ingested).'),
    "experience/sample_wait_ms": _g("ms",
        "EWMA of the learner's wait for a prefetched iteration of batches — "
        '~0 means the learner never waits on experience ingest.'),
    "experience/dropped_rows": _g("count",
        "transitions dropped after the sender's bounded retry budget "
        'exhausted against a dead shard.'),
    "experience/sent_rows": _g("count",
        "sender-side transitions handed to the wire (watermark units — "
        're-based to the shard ledger on a re-hello); with ingested + '
        'dropped + inflight it closes the exactly-once conservation law '
        'the chaos oracle checks.'),
    # -- serving tier (distributed/fleet.py; fleet aggregates) --------------
    "fleet/replicas_live": _g("count",
        'inference-server replicas currently alive.'),
    "fleet/respawns": _g("count",
        'replica respawns performed by the fleet supervisor this run (in '
        'place, fixed address, exponential backoff).'),
    "fleet/scale_ups": _g("count", 'autoscale replica additions this run.'),
    "fleet/scale_downs": _g("count", 'autoscale replica drains this run.'),
    "fleet/serve_ms": _g("ms",
        "fleet-mean serve-latency EWMA — the autoscaler's up/down signal."),
    "fleet/queue_depth": _g("count",
        'summed trajectory-chunk queue depth across replicas.'),
    # -- parameter fanout (distributed/param_fanout.py) ---------------------
    "param/publishes": _g("count",
        'weight frames broadcast by the fanout this run.'),
    "param/full_frames": _g("count", 'full (key) frames among them.'),
    "param/delta_frames": _g("count", 'delta frames among them.'),
    "param/rekeys": _g("count",
        'full frames FORCED by a stale/absent subscriber ack (a dropped '
        'frame or late joiner re-keys the delta stream).'),
    "param/bytes_last_publish": _g("bytes", 'wire bytes of the newest frame.'),
    "param/bytes_published": _g("bytes",
        'cumulative fanout wire bytes this run.'),
    "param/subscribers": _g("count",
        'subscribers with a fresh (ttl-bounded) ack.'),
    # subscriber-side counters (ParameterSubscriber.gauges — actor/eval
    # processes and tests; not part of the trainer's metrics rows)
    "param/applied_frames": _g("count", 'frames this subscriber applied.'),
    "param/stale_frames": _g("count",
        'inapplicable deltas this subscriber dropped (missed frame / fresh '
        'join) — each flags needs_resync toward the fetch fallback.'),
    "param/fallback_fetches": _g("count",
        'ParameterClient.fetch catch-ups this subscriber performed (the '
        'late-joiner / dropped-frame path; counted, never silent).'),
    "param/holds": _g("count",
        'param versions the fanout currently holds pinned for gateway '
        'sessions (full frames retained until every pin releases).'),
    # -- session gateway (surreal_tpu/gateway/; tenant-facing tier) ---------
    "gateway/sessions": _g("count",
        'sessions currently attached across all tenants.'),
    "gateway/attaches": _g("count",
        'sessions admitted this run (first attach only).'),
    "gateway/reattaches": _g("count",
        're-attaches onto a live session id (client reconnect; the session '
        'record and its replica binding survive).'),
    "gateway/detaches": _g("count", 'explicit tenant detaches this run.'),
    "gateway/acts": _g("count", 'act requests served (cache hits included).'),
    "gateway/cache_hits": _g("count",
        'acts answered from the bounded (version, obs-digest) act cache '
        'without touching a fleet replica.'),
    "gateway/cache_misses": _g("count",
        'acts that paid a fleet serve_act forward.'),
    "gateway/migrations": _g("count",
        'session rebinds performed after a replica death (invisible '
        'failover; counted per moved session).'),
    "gateway/catch_ups": _g("count",
        'pinned sessions force-unpinned because their param version was '
        "evicted from the fleet's act history (flagged on the reply — "
        'counted, never silent).'),
    "gateway/pinned_sessions": _g("count",
        'sessions currently pinned to a param version.'),
    "gateway/dropped_replies": _g("count",
        'act replies swallowed by fault injection (gateway.session '
        "drop_frame); the client's bounded resend redelivers."),
    "gateway/bad_frames": _g("count",
        "malformed/hostile tenant frames dropped at the serve loop's frame "
        'boundary (truncated headers, bad obs bodies, undecodable or '
        'un-negotiated pickle fallbacks) — counted, never a crash.'),
    "gateway/respawns": _g("count",
        'gateway serve-thread respawns performed by its supervisor (in '
        'place, fixed address, shared backoff schedule).'),
    # admission plane (gateway/admission.py)
    "gateway/rejected_sessions": _g("count",
        'attach attempts refused — by session quota (global or per-tenant) '
        'or by the re-attach tenant/token credential check.'),
    "gateway/throttled_acts": _g("count",
        "acts past a tenant's token-bucket rate, parked in its bounded "
        'queue instead of served immediately.'),
    "gateway/evicted_requests": _g("count",
        "oldest queued acts evicted when a tenant's backpressure queue "
        'overflowed (each gets an ACT_ERR — counted, never silent).'),
    "gateway/expired_leases": _g("count",
        'sessions reaped idle past their lease.'),
    "gateway/queued_acts": _g("count",
        'acts currently parked across tenant queues.'),
    # -- live ops plane (session/opsplane.py; ISSUE 13) ---------------------
    "ops/tiers": _g("count",
        'tiers that have pushed at least one row to the run aggregator '
        '(gateway, fleet replicas, experience shards, learner, fanout).'),
    "ops/bad_frames": _g("count",
        "undecodable/hostile rows dropped at the aggregator's PULL boundary "
        '— counted, never a crash.'),
    "ops/snapshots": _g("count",
        'merged run snapshots written to telemetry/ops_snapshot.json (one '
        'per metrics cadence; the file `surreal_tpu top` renders).'),
    "ops/flightrec_dumps": _g("count",
        'flight-recorder dumps written under telemetry/flightrec/ (recovery '
        'trip, chaos fault, SLO budget exhaustion, or an opened incident; '
        'at most one per trigger per cooldown).'),
    # watchdog & incident engine (session/watchdog.py, session/incidents.py)
    "ops/watchdog_evals": _g("count",
        'detector sweeps run over merged ops snapshots (one per metrics '
        'cadence while session_config.watchdog.enabled).'),
    "ops/watchdog_dropped_evals": _g("count",
        'detector sweeps skipped by the watchdog.eval chaos site '
        '(drop_eval) — counted, never silent.'),
    "ops/watchdog_firings": _g("count",
        'detector firings across all sweeps this run (breakout, '
        'saturation, growth, liveness).'),
    "ops/incidents_open": _g("count",
        'whether an incident is currently open (0/1 — the engine holds at '
        'most one open incident, extending it while detectors keep '
        'firing).'),
    "ops/incidents_total": _g("count",
        'incidents opened this run (each persisted under '
        'telemetry/incidents/incident-<n>.json and rendered by '
        '`surreal_tpu why`).'),
    # per-tenant SLOs (session/slo.py)
    "slo/breaches": _g("count",
        'SLO evaluation windows that breached a declared objective (every '
        'one is also a counted slo_breach telemetry event).'),
    "slo/exhaustions": _g("count",
        'error budgets exhausted this run (edge-triggered: one per '
        'incident, each freezing a flightrec/slo dump).'),
    "slo/objectives": _g("count",
        'objectives armed via session_config.slo.* targets.'),
    "lineage/staleness_p50": _g("count",
        'exact per-update staleness median: p50 over (current version - '
        'acting version) of every transition in the batch that entered this '
        'gradient, from the collection-time lineage stamps. Host numpy over '
        'the already-fetched version column — no device sync.'),
    "lineage/staleness_p99": _g("count",
        "exact per-update staleness p99 over the batch's acting-policy "
        "versions (the SLO plane's staleness objective prefers this over "
        'the published-vs-held approximation when lineage is on).'),
    "lineage/staleness_max": _g("count",
        'oldest transition that entered this update, in version lags.'),
    "lineage/versions_per_batch": _g("count",
        "distinct acting-policy versions mixed into this update's batch (1 "
        '== perfectly on-policy data).'),
    "trace/spans": _g("count",
        "causal spans emitted so far by this process's tracer (head-sampled "
        'exemplars, telemetry.trace.sample_n).'),
    "trace/dropped_spans": _g("count",
        'spans dropped by the trace.emit chaos site — counted, never '
        "silent; the exemplar's tree renders with the torn hop marked."),
    # -- closed-loop remediation (session/remediate.py; ISSUE 16) -----------
    "remediation/actions": _g("count",
        'bounded actions executed by the remediation engine (each a '
        'remediation event, an atomic telemetry/actions/action-<n>.json '
        "record, and evidence on the incident that triggered it)."),
    "remediation/suppressed": _g("count",
        'would-be actions stopped by the global max_actions budget or a '
        'per-kind cooldown — loud (a counted remediation event), never a '
        'silent retry loop.'),
    "remediation/unmapped": _g("count",
        "decision sweeps where the open incident's top cause had no bound "
        'actuator or no actionable target — counted, never guessed.'),
    "remediation/reverted": _g("count",
        'actions undone by the counter-detector after their triggering '
        'objective regressed further (quota restored, replica drained, '
        'overrides rolled back).'),
    "remediation/ineffective": _g("count",
        'actions the counter-detector judged ineffective over '
        'verify_windows post-action sweeps.'),
    "remediation/effective": _g("count",
        'actions whose triggering objective did NOT regress further over '
        'the verification window.'),
    "remediation/errors": _g("count",
        'actuator calls that raised (execute or revert) — journaled and '
        'counted; actuation must never kill training.'),
    "remediation/active": _g("count",
        'actions currently inside their verification window.'),
    # -- elastic learner group (parallel/learner_group.py; ISSUE 17) --------
    "lgroup/members": _g("count",
        'alive data-parallel learner-group members draining the '
        'experience plane.'),
    "lgroup/rebalances": _g("count",
        'shard-subset repartitions (join/leave/failure/respawn each '
        'costs one rebalance, not a run).'),
    "lgroup/rekeys": _g("count",
        'fanout full-frame re-keys forced by membership changes (each '
        'also counts into param/rekeys on the one distribution tree).'),
    "lgroup/joins": _g("count", 'members that joined mid-run.'),
    "lgroup/leaves": _g("count",
        'members removed mid-run (planned scale-down).'),
    "lgroup/respawns": _g("count",
        'crashed members revived under the RespawnSchedule backoff.'),
    "lgroup/respawn_backoff_s": _g("scalar",
        'current member-respawn backoff (exponential, capped).'),
    "lgroup/sample_wait_ms": _g("ms",
        "slowest member's EWMA batch-stitch wait — the group analogue "
        'of experience/sample_wait_ms.'),
    "lgroup/allreduce_learns": _g("count",
        'SGD updates run through the shard_map gradient all-reduce '
        '(M members on >= M devices).'),
    "lgroup/fallback_learns": _g("count",
        'M>1 updates degraded to ONE full-batch learn (single device / '
        'indivisible batch) — the honesty counter: artifacts report a '
        'ratio, never a fabricated speedup.'),
    # -- tenant load generator (gateway/loadgen.py; ISSUE 16) ---------------
    "gateway/quota_changes": _g("count",
        'runtime per-tenant quota mutations via AdmissionController.'
        'set_quota (operator reconfigs and remediation throttles alike).'),
    "loadgen/tenants": _g("count",
        'tenant threads in the generator mix (steady + abusive profiles).'),
    "loadgen/attaches": _g("count",
        'sessions the generator attached across all tenants.'),
    "loadgen/detaches": _g("count",
        'sessions the generator detached (attach_storm churns these).'),
    "loadgen/acts": _g("count",
        'acts served to generator tenants end-to-end.'),
    "loadgen/act_errors": _g("count",
        'acts answered with a counted gateway rejection (throttle '
        'eviction, quota, dead session) — the expected outcome for the '
        'abusive profiles.'),
    "loadgen/rejected": _g("count",
        'attach attempts denied by admission control.'),
    "loadgen/timeouts": _g("count",
        'acts that exhausted client retries without a reply.'),
    "loadgen/hostile_frames": _g("count",
        'malformed frames the adversarial profile put on the wire (each '
        "must land in the server's gateway/bad_frames, never a crash)."),
    "loadgen/act_rtt_ms": _g("ms",
        'mean client-observed act round-trip across generator tenants.'),
    # -- replay tiers (replay/tiers.py, experience/spill.py; ISSUE 18) ------
    "tier/hot_size": _g("count",
        "transitions resident in the device hot ring."),
    "tier/hot_fill": _g("ratio",
        "hot ring occupancy (size / hot_capacity)."),
    "tier/hot_hits": _g("count",
        'updates whose batch was drawn on-device from the hot tier '
        '(no wire frame, no host->device transfer).'),
    "tier/hot_misses": _g("count",
        'updates that fell back to the warm shard fan-in (hot ring '
        'still filling) — counted, never silent.'),
    "tier/spill_segments": _g("count",
        'WAL segments appended across shards (experience/spill.py).'),
    "tier/spill_rows": _g("count",
        'transitions spilled to the WAL across shards.'),
    "tier/spill_bytes": _g("bytes",
        'total WAL bytes on disk across shards (framed, after '
        'quantization).'),
    "tier/spill_errors": _g("count",
        'WAL appends that failed (ENOSPC, IO error) — the writer '
        'degrades and the warm ring keeps serving.'),
    "tier/spill_failed": _g("count",
        'shards whose writer latched off after consecutive append '
        'failures (1 per latched shard).'),
    "tier/cold_bytes_per_row": _g("bytes",
        'encoded WAL bytes per transition (the quantization win vs the '
        'raw f32 row; tests/test_tiers.py holds the ratio).'),
    "tier/torn_segments": _g("count",
        'torn WAL segments skipped by magic-resync on read (crash '
        'mid-append; the experience.spill chaos site drives this).'),
    # ---- loop engine (engine/core.py, ISSUE 19) ----
    "engine/stage_p50_ms": _g("ms",
        'median deferred-boundary duration (publish/checkpoint/observe '
        'side-bands + metrics materialization), last 512 boundaries.'),
    "engine/stage_p99_ms": _g("ms",
        'p99 deferred-boundary duration over the same window.'),
    "engine/occupancy": _g("ratio",
        'staging-worker busy fraction of wall time while pipelining — '
        'the off-critical-path work actually reclaimed.'),
    "engine/queue_depth": _g("count",
        'deferred boundaries in flight (bounded at 1: one pending slot).'),
    "engine/deferred_boundaries": _g("count",
        'boundaries submitted to the staging executor this run.'),
    "engine/skipped_boundaries": _g("count",
        'boundaries skipped because the previous one wedged past '
        'stage_timeout_s (never silent — warned and counted).'),
    "engine/stage_kills": _g("count",
        'engine.stage kill_stage chaos firings absorbed by the boundary '
        '(the stage crashed; training continued).'),
    # ---- chaos campaigns (chaos/campaign.py, ISSUE 20) ----
    "chaos/schedules": _g("count",
        'seeded multi-site fault schedules executed by this campaign.'),
    "chaos/violations": _g("count",
        'invariant-oracle violations across the campaign (the gate '
        'requires zero in the committed artifact).'),
    "chaos/faults_injected": _g("count",
        'fault firings actually delivered across all campaign runs '
        '(plan entries whose site reached its scheduled call count).'),
    "chaos/sites_covered": _g("count",
        'distinct fault sites that FIRED at least once this campaign '
        '(the artifact gate requires >= 10).'),
    "chaos/shrink_iters": _g("count",
        're-runs spent by the greedy shrinker reducing failing '
        'schedules to minimal form (0 on a clean campaign).'),
    "chaos/run_ms": _g("ms", 'campaign wall-clock, all runs + shrinking.'),
}

# Published per-chip peaks, keyed by the ``device_kind`` string JAX
# reports (the spellings of jax's own pallas tpu_info table): (peak
# FLOP/s in bf16, peak HBM bytes/s). Source: Google Cloud TPU
# documentation, the system-architecture page of each generation ("TPU
# v5e": 197 TFLOP/s bf16, 819 GB/s HBM). A device that is not here has no
# peak: a session then reports ``perf/flops_per_s`` and no utilisation
# gauge (or the ``session.perf.peak_flops``/``peak_membw`` it was given),
# and a bench path fails (:func:`published_peak`).
PEAK_SPECS: dict[str, tuple[float, float]] = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),   # v5e
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),  # v6e (Trillium)
}


def published_peak(kind: str) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of ``kind`` for a bench path, where a
    device without a published peak is an error, never a default."""
    if kind not in PEAK_SPECS:
        raise RuntimeError(
            f"no published peak for device kind {kind!r} "
            f"(session/costs.py::PEAK_SPECS has {sorted(PEAK_SPECS)}): "
            "a benchmark runs on a chip whose peak is known"
        )
    return PEAK_SPECS[kind]


class PeakSpec:
    """Resolved peak numbers for the active backend."""

    __slots__ = ("flops", "membw", "device_kind", "source")

    def __init__(self, flops, membw, device_kind: str, source: str):
        self.flops = float(flops) if flops else None
        self.membw = float(membw) if membw else None
        self.device_kind = device_kind
        self.source = source  # 'override' | 'table' | 'unknown'

    def to_dict(self) -> dict:
        return {
            "peak_flops": self.flops,
            "peak_membw": self.membw,
            "device_kind": self.device_kind,
            "peak_source": self.source,
        }


def resolve_peak_spec(session_cfg) -> PeakSpec:
    """Peak FLOP/s + bytes/s for the active backend: the
    ``session.perf.peak_flops``/``peak_membw`` overrides win (a partial
    override fills the other half from the table); otherwise the
    :data:`PEAK_SPECS` row of this ``device_kind``; otherwise an
    'unknown' spec (costs still recorded, utilization gauges limited to
    ``perf/flops_per_s``)."""
    from surreal_tpu.utils.compat import device_kind

    kind = device_kind()
    perf = session_cfg.get("perf", None) if session_cfg is not None else None
    over_f = perf.get("peak_flops", None) if perf is not None else None
    over_b = perf.get("peak_membw", None) if perf is not None else None
    t_f, t_b = PEAK_SPECS.get(kind, (None, None))
    if over_f or over_b:
        return PeakSpec(over_f or t_f, over_b or t_b, kind, "override")
    return PeakSpec(t_f, t_b, kind, "table" if t_f else "unknown")


def _cost_dict(ca) -> dict | None:
    if isinstance(ca, (list, tuple)):  # some backends wrap per-device
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or "flops" not in ca:
        return None
    flops = float(ca["flops"])
    byts = float(ca.get("bytes accessed", 0.0))
    return {
        "flops": flops,
        "bytes_accessed": byts,
        "arithmetic_intensity": (flops / byts) if byts > 0 else None,
    }


def _memory_dict(ma) -> dict | None:
    out = {}
    for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k.replace("_in_bytes", "")] = int(v)
    return out or None


def analyze_program(jitted, *args, memory: bool = False, **kwargs):
    """(costs, memory, hlo_text) of one jitted program at these arg
    shapes, from ONE lowering. ``costs`` is XLA's cost model —
    ``{"flops", "bytes_accessed", "arithmetic_intensity"}`` — of the
    unoptimized HLO where the backend's client can cost it (host-side
    only: no compile, no device work, no transfers; safe before the first
    dispatch and on donated-arg programs, since lowering consumes no
    buffers), and of the compiled program where it cannot (the TPU).
    ``memory`` (argument/output/temp bytes) always needs the compile and
    is taken only when asked for. Either is None when unavailable.
    ``hlo_text()`` returns the COMPILED program's HLO text (its
    instruction names are a profile's device-op names), compiling only if
    nothing above had to, and only when called. A program that cannot be
    lowered or compiled here yields (None, None, None) and fails where it
    is dispatched, with its own error."""
    try:
        lowered = jitted.lower(*args, **kwargs)
        ca = lowered.cost_analysis()
        compiled = lowered.compile() if (ca is None or memory) else None
        if ca is None:
            ca = compiled.cost_analysis()
        ma = compiled.memory_analysis() if memory else None
    except Exception:
        return None, None, None

    # held for the session: the executable alone where there is one
    hlo_text = (
        compiled.as_text if compiled is not None
        else lambda: lowered.compile().as_text()
    )
    return (
        _cost_dict(ca), _memory_dict(ma) if ma is not None else None, hlo_text
    )


def program_costs(jitted, *args, **kwargs) -> dict | None:
    """The ``costs`` half of :func:`analyze_program`."""
    return analyze_program(jitted, *args, **kwargs)[0]


class CostAccountant:
    """Per-session registry of hot-program costs + the live perf gauges.

    Drivers register each jitted hot program once, before (or right after)
    its first dispatch, naming the tracer phase that measures it::

        hooks.record_program_costs(
            "train_iter", self._train_iter, state, carry, key,
            phase="train_iter",
        )

    ``gauges(window)`` then turns any flushed phase window (the
    ``{name: {count, total_s, ...}}`` dict ``Tracer.flush_phases``
    snapshots into ``Tracer.last_window``) into ``perf/*`` host floats.
    """

    def __init__(self, session_cfg, on_event=None, log=None, policy=None):
        # policy: the learner's resolved PrecisionPolicy (ops/precision.py)
        # — stamped into every program_cost record/event so committed
        # artifacts carry bytes/MFU rows PER PRECISION POLICY, never
        # silently mixed across policy arms
        self.policy = policy
        self._cfg = session_cfg
        self.enabled = True
        perf = session_cfg.get("perf", None) if session_cfg is not None else None
        if perf is not None and not perf.get("enabled", True):
            self.enabled = False
        self._mem_mode = (
            perf.get("memory_analysis", "auto") if perf is not None else "auto"
        )
        self._on_event = on_event
        self._log = log
        self._programs: dict[str, dict] = {}
        # name -> zero-arg source of the compiled program's HLO text, and
        # the {instruction: label} maps parsed from them on first demand
        # (labels())
        self._hlo: dict[str, Any] = {}
        self._labels: dict[str, dict[str, dict[str, str]]] | None = None
        self._failed: set[str] = set()  # don't re-lower every iteration
        # when a backend reports no cost model (record sites in host/SEED
        # loops call record_program once per iteration, idempotently)
        self.peak: PeakSpec | None = None  # resolved lazily (first record
        # touches jax.devices(); constructing hooks must not)

    @property
    def programs(self) -> dict[str, dict]:
        return dict(self._programs)

    def _memory_analysis_ok(self) -> bool:
        if self._mem_mode is True:
            return True
        if not self._mem_mode:  # False/None
            return False
        # 'auto': only when the extra AOT compile is known-cheap — a
        # persistent compile cache turns it into a disk deserialize
        # (either order: AOT first warms the cache for the jit call, or
        # vice versa). Without the cache it is a real second XLA compile
        # of the largest program in the process — minutes on a chip, and
        # a measurable tax even on the CPU test image — so 'auto' stays
        # off. Multi-process compilation may coordinate: always off there.
        import jax

        if jax.process_count() > 1:
            return False
        from surreal_tpu.utils.compat import compile_cache_active

        return compile_cache_active()

    def record_program(
        self, name: str, jitted, *args,
        phase: str | None = None, calls_per_phase: int = 1, **kwargs,
    ) -> dict | None:
        """Record one program's cost analysis (idempotent per ``name``).
        Emits a ``program_cost`` telemetry event via ``on_event``. Returns
        the record, or None when disabled / the backend reports nothing."""
        if not self.enabled or name in self._failed:
            return None
        if name in self._programs:
            return self._programs[name]
        if self.peak is None:
            # resolved on first use, not at construction: this touches
            # jax.devices(), and hooks must stay constructible pre-backend
            self.peak = resolve_peak_spec(self._cfg)
        costs, mem, hlo_text = analyze_program(
            jitted, *args, memory=self._memory_analysis_ok(), **kwargs
        )
        if hlo_text is not None:
            self._hlo[name] = hlo_text
            self._labels = None
        if costs is None:
            self._failed.add(name)
            if self._log is not None:
                self._log.info(
                    "cost accounting: backend reports no cost model for "
                    "program %r", name,
                )
            return None
        rec = {
            "name": name,
            "phase": phase,
            "calls_per_phase": int(calls_per_phase),
            **costs,
        }
        if self.policy is not None:
            rec["precision"] = getattr(self.policy, "name", str(self.policy))
        if mem is not None:
            rec["memory"] = mem
        self._programs[name] = rec
        if self._log is not None:
            self._log.info(
                "program cost %r: %.3g FLOPs/call, %.3g bytes/call%s",
                name, rec["flops"], rec["bytes_accessed"],
                (
                    f", AI {rec['arithmetic_intensity']:.2f}"
                    if rec.get("arithmetic_intensity") else ""
                ),
            )
        if self._on_event is not None:
            self._on_event("program_cost", **rec, **self.peak.to_dict())
        return rec

    def labels(self) -> dict[str, dict[str, dict[str, str]]]:
        """The profile digest's label maps (``session/profile.py``
        ``LABELS``), ``{"phases" | "parts" | "subphases" | "kernels": {HLO
        module name: {instruction name: label}}}`` over every registered
        program: an instruction's phase, model part (``utils/phases.py``
        ``PARTS``; none in a program whose model scopes none) and sub-scope
        of its phase (``SUBPHASES``: ``collect/act``), and a Pallas call's
        kernel (``hlo_kernels``). All four come from one reading of each
        program's compiled HLO text, on first demand (the digest's, off
        the loop's thread). A program whose text cannot be had is left
        out."""
        if self._labels is not None:
            return self._labels
        from surreal_tpu.session.profile import LABELS, hlo_kernels, hlo_op_phases
        from surreal_tpu.utils.phases import part_of, phase_of, subphase_of

        maps: dict = {k: {} for k in LABELS}
        for name, hlo_text in list(self._hlo.items()):
            try:
                text = hlo_text()
                module, *found = hlo_op_phases(text, phase_of, part_of, subphase_of)
                found.append(hlo_kernels(text)[1])
            except Exception as e:
                if self._log is not None:
                    self._log.warning(
                        "no HLO text for program %r: %s", name, e
                    )
                continue
            for k, ops in zip(LABELS, found):
                maps[k][module] = ops
            if not found[0] and self._log is not None:
                self._log.warning(
                    "program %r carries no phase name: the digest will "
                    "call its ops unattributed", name,
                )
        self._labels = maps
        return maps

    def gauges(self, window: dict | None) -> dict[str, float]:
        """``perf/*`` scalars for one flushed phase window — pure host
        float arithmetic (the transfer-guard tests run this under
        ``disallow_device_to_host``). The denominator is the window's
        fenced ``cadence`` seconds: a window without one (the first of a
        run) has no gauges. Programs whose phase did not fire in the
        window contribute nothing; an empty result means no registered
        program ran."""
        if not self.enabled or not window or not self._programs:
            return {}
        flops = 0.0
        byts = 0.0
        denom_s = float(window.get("cadence", {}).get("total_s", 0.0))
        for rec in self._programs.values():
            ph = rec.get("phase")
            if ph is None or ph not in window:
                continue
            count = float(window[ph].get("count", 0))
            flops += rec["flops"] * count * rec["calls_per_phase"]
            byts += rec["bytes_accessed"] * count * rec["calls_per_phase"]
        if denom_s <= 0.0 or (flops <= 0.0 and byts <= 0.0):
            return {}
        out = {"perf/flops_per_s": flops / denom_s}
        peak = self.peak
        if peak is not None and peak.flops:
            out["perf/mfu"] = flops / denom_s / peak.flops
        if peak is not None and peak.membw and byts > 0.0:
            out["perf/membw_util"] = byts / denom_s / peak.membw
        return out
