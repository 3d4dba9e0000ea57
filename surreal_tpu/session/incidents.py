"""Incident engine: detector firings -> root-caused incident reports
(ISSUE 15).

The :class:`~surreal_tpu.session.watchdog.Watchdog` says *something is
anomalous*; this module says *what probably caused it*. Once per ops
snapshot the engine consumes the sweep's firings:

- **lifecycle** — firings with no open incident OPEN one; further
  firings extend it; ``close_windows`` consecutive clean sweeps CLOSE it
  (sustained-healthy, not first-quiet-window). Each transition is a
  counted telemetry event (``incident_open`` / ``incident_update`` /
  ``incident_close``) and the full record is (re)written atomically to
  ``<folder>/telemetry/incidents/incident-<n>.json``.
- **correlation** — evidence inside a bounded time window around the
  incident: chaos fault injections, recovery-guard trips, per-tenant SLO
  breaches from the snapshot's table, DEAD tiers, and the slowest recent
  exemplar span trees (trace ids included, so ``surreal_tpu trace``
  picks up where ``why`` leaves off).
- **causality** — a static dataflow graph of the tiers
  (workers->fleet->gateway for the act path; sender->shard->sampler->
  learner->fanout->fleet for the experience/param loop) ranks cause
  hypotheses upstream-first: a tier with hard evidence (injected fault,
  DEAD) that sits upstream of the symptomatic tiers outranks the tier
  that merely *shows* the symptom.
- **auto-capture** — one ProfileManager capture + one flight-recorder
  dump per incident, cooldown- and count-bounded, linked from the
  incident record.

``incidents_report`` / ``incidents_brief`` at the bottom are the
``surreal_tpu why`` renderers — pure file reading (no jax, no zmq),
same discipline as ``top``/``trace``, reused by ``diag``/``top``'s
"Incidents" section.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque

from surreal_tpu.session.costs import GAUGE_REGISTRY

INCIDENTS_DIR = "incidents"  # <folder>/telemetry/incidents/

# static dataflow causality graph: tier -> the tiers immediately UPSTREAM
# of it (the ones whose failure would surface as this tier's symptom).
# Act path: workers -> fleet -> gateway. Experience/param loop: workers
# (senders) -> experience (shards/sampler) -> learner -> param_fanout ->
# fleet (replicas apply the published weights).
UPSTREAM = {
    "gateway": ("fleet",),
    "fleet": ("workers", "param_fanout"),
    "learner": ("experience",),
    "experience": ("workers",),
    "param_fanout": ("learner",),
    "workers": (),
}

# chaos site -> the dataflow tier it injects into (utils/faults.py SITES)
SITE_TIER = {
    "trainer.iteration": "learner",
    "env_worker.step": "workers",
    "transport.send": "workers",
    "server.serve": "fleet",
    "param_service.reply": "param_fanout",
    "experience.shard": "experience",
    "experience.sample": "experience",
    "experience.send": "experience",
    "fleet.replica": "fleet",
    "param.publish": "param_fanout",
    "gateway.session": "gateway",
    "ops.push": "learner",
    "trace.emit": "learner",
    "watchdog.eval": "learner",
}

# SLO objective -> the tier that owns the contract
OBJECTIVE_TIER = {
    "act_rtt_p99_ms": "gateway",
    "attach_p99_ms": "gateway",
    "throttle_rate": "gateway",
    "staleness_updates": "param_fanout",
}


def upstream_closure(tier: str) -> set[str]:
    """Every tier transitively upstream of ``tier`` in the static graph."""
    seen: set[str] = set()
    stack = list(UPSTREAM.get(tier, ()))
    while stack:
        u = stack.pop()
        if u in seen:
            continue
        seen.add(u)
        stack.extend(UPSTREAM.get(u, ()))
    return seen


def unit_for(signal: str) -> str | None:
    """The display/threshold unit of a detector signal: the registered
    gauge unit when the signal IS a gauge, a suffix convention for the
    derived signals (``*_ms``, ``*_per_s``)."""
    rec = GAUGE_REGISTRY.get(signal)
    if isinstance(rec, dict):
        return rec.get("unit")
    if signal.endswith("_ms"):
        return "ms"
    if signal.endswith("_per_s") or signal == "throughput":
        return "steps/s"
    if signal == "mfu":
        return "ratio"
    return None


def rank_causes(detector_counts: dict, evidence: dict) -> list[dict]:
    """Upstream-first cause hypotheses from the accumulated detector
    firings and correlated evidence. Returns ``[{tier, score, reasons}]``
    best-first. Pure dict arithmetic (shared by the live engine and any
    offline re-ranking)."""
    scores: dict[str, float] = {}
    reasons: dict[str, list[str]] = {}

    def add(tier, pts, why):
        if not tier:
            return
        scores[tier] = scores.get(tier, 0.0) + pts
        r = reasons.setdefault(tier, [])
        if why not in r:
            r.append(why)

    fault_tiers: dict[str, int] = {}
    for ev in evidence.get("faults", ()):
        tier = SITE_TIER.get(str(ev.get("site", "")))
        if tier is None:
            continue
        n = fault_tiers.get(tier, 0)
        fault_tiers[tier] = n + 1
        add(
            tier, 3.0 if n == 0 else 0.5,
            f"injected fault {ev.get('kind', '?')} @ {ev.get('site')}",
        )
    dead_seen: set[str] = set()
    for name in evidence.get("dead_tiers", ()):
        tier = str(name).split(".", 1)[0]
        if tier in dead_seen:
            add(tier, 0.5, f"tier {name} DEAD")
        else:
            dead_seen.add(tier)
            add(tier, 2.5, f"tier {name} DEAD (3x cadence silent)")
    for key, n in (detector_counts or {}).items():
        det, _, rest = str(key).partition(":")
        if det == "liveness":
            continue  # dead tiers already scored above
        tier, _, signal = rest.partition(":")
        add(tier, 1.0, f"{det} firing on {signal} (x{n})")
    slo_objs: set[tuple] = set()
    for ev in evidence.get("slo_breaches", ()):
        key = (ev.get("tenant"), ev.get("objective"))
        if key in slo_objs:
            continue
        slo_objs.add(key)
        add(
            OBJECTIVE_TIER.get(str(ev.get("objective"))), 0.75,
            f"SLO breach {ev.get('objective')} (tenant {ev.get('tenant')})",
        )
    if evidence.get("recoveries"):
        add("learner", 1.5, "recovery guard tripped")

    # upstream-first: hard evidence upstream of a symptomatic tier
    # explains it — boost the upstream hypothesis per downstream symptom
    implicated = set(scores)
    for tier in list(implicated):
        ups = upstream_closure(tier)
        for upstream in ups & implicated:
            add(
                upstream, 0.5,
                f"upstream of symptomatic tier {tier}",
            )
    out = [
        {"tier": t, "score": round(s, 2), "reasons": reasons.get(t, [])}
        for t, s in scores.items()
    ]
    out.sort(key=lambda h: (-h["score"], h["tier"]))
    return out


class IncidentEngine:
    """Owns the incident lifecycle for one run (constructed by
    SessionHooks next to the Watchdog)."""

    def __init__(self, folder=None, cfg=None, on_event=None, profile=None,
                 flightrec=None, exemplar_source=None, trace_id=None):
        cfg = cfg or {}
        get = cfg.get if hasattr(cfg, "get") else lambda k, d=None: d
        self.folder = folder
        self.trace_id = trace_id
        self._on_event = on_event
        self._profile = profile
        self._flightrec = flightrec
        self._exemplar_source = exemplar_source
        self.close_windows = max(1, int(get("close_windows", 5)))
        self.evidence_window_s = float(get("evidence_window_s", 120.0))
        self.update_every = max(1, int(get("update_every", 5)))
        self.max_captures = int(get("max_captures", 4))
        self.capture_cooldown_s = float(get("capture_cooldown_s", 60.0))
        self.max_detectors = int(get("max_detectors", 64))
        self._faults: deque = deque(maxlen=256)
        self._recoveries: deque = deque(maxlen=64)
        self._next_id = 1
        self._open: dict | None = None
        self._captures = 0
        self._last_capture = -1e18
        self.opened = 0
        self.closed = 0
        self._write_ok = folder is not None

    @property
    def open_incident(self) -> dict | None:
        """The currently open incident record (None between incidents) —
        the remediation engine's read surface."""
        return self._open

    def attach_action(self, summary: dict) -> None:
        """First-class action evidence (ISSUE 16): fold a remediation
        action summary into the open incident and persist immediately —
        an action must be visible in the record it answered, not only in
        the action journal. Verdict updates for an action id replace the
        earlier summary in place (one line per action in ``why``)."""
        inc = self._open
        if inc is None:
            return
        actions = inc["evidence"].setdefault("actions", [])
        summary = dict(summary)
        for i, a in enumerate(actions):
            if a.get("action") == summary.get("action"):
                actions[i] = summary
                break
        else:
            actions.append(summary)
        del actions[32:]
        self._write(inc)

    # -- evidence feeds (called by SessionHooks next to the ops feeds) -------
    def record_fault(self, ev: dict) -> None:
        rec = dict(ev)
        rec.setdefault("t", time.time())
        self._faults.append(rec)

    def record_recovery(self, ev: dict) -> None:
        rec = dict(ev)
        rec.setdefault("t", time.time())
        self._recoveries.append(rec)

    def _recent(self, dq, now: float) -> list[dict]:
        lo = now - self.evidence_window_s
        return [dict(ev) for ev in dq if float(ev.get("t", now)) >= lo]

    def _slowest_exemplars(self, limit: int = 4) -> list[dict]:
        if self._exemplar_source is None:
            return []
        try:
            spans = list(self._exemplar_source() or ())
        except Exception:
            return []
        timed = [s for s in spans if s.get("dur_ms") is not None]
        timed.sort(key=lambda s: -float(s["dur_ms"]))
        return [
            {
                "exemplar": s.get("exemplar"),
                "name": s.get("name"),
                "span": s.get("span"),
                "tier": s.get("tier"),
                "dur_ms": round(float(s["dur_ms"]), 3),
            }
            for s in timed[:limit]
        ]

    # -- lifecycle -----------------------------------------------------------
    def observe(self, firings: list[dict], snap: dict | None = None) -> None:
        """One post-sweep step: open/extend/close the incident and keep
        its persisted record current. Pure host work."""
        now = time.time()
        snap = snap or {}
        if self._open is None:
            if not firings:
                return
            self._open_incident(firings, snap, now)
            return
        inc = self._open
        if firings:
            inc["healthy_windows"] = 0
            inc["last_firing_t"] = now
            self._absorb(inc, firings, snap, now)
            inc["updates"] += 1
            if inc["updates"] % self.update_every == 0:
                top = inc["causes"][0] if inc["causes"] else {}
                if self._on_event is not None:
                    self._on_event("incident_update", id=inc["id"],
                                   detectors=len(inc["detector_counts"]),
                                   top_cause=top.get("tier"),
                                   updates=inc["updates"])
                self._write(inc)
        else:
            inc["healthy_windows"] += 1
            if inc["healthy_windows"] >= self.close_windows:
                self._close_incident(inc, now)
                return
        # backfill the auto-capture link once the profiler window lands
        prof = self._profile
        if (prof is not None
                and inc["artifacts"].get("profile") == "pending"
                and getattr(prof, "last_capture_dir", None)
                and os.path.basename(
                    str(prof.last_capture_dir)
                ) not in str(inc["artifacts"])):
            inc["artifacts"]["profile"] = prof.last_capture_dir
            self._write(inc)

    def _absorb(self, inc: dict, firings: list[dict], snap: dict,
                now: float) -> None:
        """Fold a sweep's firings + the snapshot's correlatable state
        into the open incident, re-ranking causes."""
        for f in firings:
            key = (
                f"{f.get('detector')}:{f.get('tier')}:{f.get('signal')}"
            )
            inc["detector_counts"][key] = (
                inc["detector_counts"].get(key, 0) + 1
            )
            f = dict(f)
            f.setdefault("unit", unit_for(str(f.get("signal"))))
            inc["detectors"].append(f)
            if f.get("detector") == "liveness":
                name = str(f.get("signal"))
                if name not in inc["evidence"]["dead_tiers"]:
                    inc["evidence"]["dead_tiers"].append(name)
        del inc["detectors"][:-self.max_detectors]
        inc["evidence"]["faults"] = self._recent(self._faults, now)
        inc["evidence"]["recoveries"] = self._recent(self._recoveries, now)
        breaches = inc["evidence"]["slo_breaches"]
        for tenant, row in (snap.get("slo") or {}).items():
            for objective, o in (row or {}).items():
                if not (isinstance(o, dict) and o.get("breached")):
                    continue
                rec = {
                    "tenant": tenant, "objective": objective,
                    "measured": o.get("measured"), "target": o.get("target"),
                    "t": now,
                }
                if not any(
                    b["tenant"] == tenant and b["objective"] == objective
                    for b in breaches
                ):
                    breaches.append(rec)
        del breaches[32:]
        inc["causes"] = rank_causes(inc["detector_counts"], inc["evidence"])

    def _open_incident(self, firings: list[dict], snap: dict,
                       now: float) -> None:
        n = self._next_id
        self._next_id += 1
        self.opened += 1
        inc = {
            "id": n, "status": "open", "trace": self.trace_id,
            "opened_t": now, "last_firing_t": now, "closed_t": None,
            "opened_iteration": snap.get("iteration"),
            "opened_seq": snap.get("seq"),
            "detectors": [], "detector_counts": {},
            "evidence": {
                "faults": [], "recoveries": [], "slo_breaches": [],
                "exemplars": self._slowest_exemplars(),
                "dead_tiers": [],
            },
            "causes": [], "artifacts": {"profile": None, "flightrec": None},
            "updates": 0, "healthy_windows": 0,
        }
        self._absorb(inc, firings, snap, now)
        # one profile capture + one flightrec dump per incident, bounded
        # by a run-wide count and a cooldown after the last capture, be it
        # an incident's or an operator's: a capture's fences, its write and
        # its digest disturb the iterations after it, and an incident
        # opened by that would capture the disturbance of its own capture
        last = max(
            self._last_capture,
            getattr(self._profile, "last_capture_t", None) or 0.0,
        )
        if (self._captures < self.max_captures
                and now - last >= self.capture_cooldown_s):
            self._captures += 1
            self._last_capture = now
            if self._profile is not None and self._profile.request(
                f"incident{n}"
            ):
                inc["artifacts"]["profile"] = "pending"
            if self._flightrec is not None:
                inc["artifacts"]["flightrec"] = self._flightrec.dump(
                    "incident"
                )
        self._open = inc
        top = inc["causes"][0] if inc["causes"] else {}
        if self._on_event is not None:
            self._on_event(
                "incident_open", id=n,
                detectors=sorted(inc["detector_counts"]),
                top_cause=top.get("tier"), score=top.get("score"),
                iteration=snap.get("iteration"),
            )
        self._write(inc)

    def _close_incident(self, inc: dict, now: float) -> None:
        inc["status"] = "closed"
        inc["closed_t"] = now
        inc["causes"] = rank_causes(inc["detector_counts"], inc["evidence"])
        self.closed += 1
        self._open = None
        top = inc["causes"][0] if inc["causes"] else {}
        if self._on_event is not None:
            self._on_event(
                "incident_close", id=inc["id"],
                duration_s=round(now - inc["opened_t"], 3),
                top_cause=top.get("tier"),
                healthy_windows=inc["healthy_windows"],
            )
        self._write(inc)

    def close(self) -> None:
        """Session teardown: flush the open incident as-is (still
        ``open`` — a run ending mid-incident is itself evidence)."""
        if self._open is not None:
            self._write(self._open)

    # -- persistence ---------------------------------------------------------
    def _write(self, inc: dict) -> None:
        if not self._write_ok:
            return
        from surreal_tpu.session.telemetry import TELEMETRY_DIR

        folder = os.path.join(self.folder, TELEMETRY_DIR, INCIDENTS_DIR)
        path = os.path.join(folder, f"incident-{inc['id']}.json")
        try:
            os.makedirs(folder, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(inc, f, default=float)
            os.replace(tmp, path)  # readers never see a torn record
        except OSError:
            self._write_ok = False  # diagnosis must never kill training

    def gauges(self) -> dict[str, float]:
        """The engine's ``ops/*`` counters (GAUGE_REGISTRY documents
        each); merged into the learner's metrics row."""
        return {
            "ops/incidents_open": 1.0 if self._open is not None else 0.0,
            "ops/incidents_total": float(self.opened),
        }


# -- why (pure file reading, like top/trace) ----------------------------------


def load_incidents(folder: str) -> list[dict]:
    """Every persisted incident record under
    ``<folder>/telemetry/incidents/``, id order. Hostile-tolerant: a
    torn/foreign file is skipped, never a crash."""
    from surreal_tpu.session.telemetry import TELEMETRY_DIR

    inc_dir = os.path.join(folder, TELEMETRY_DIR, INCIDENTS_DIR)
    out = []
    try:
        names = os.listdir(inc_dir)
    except OSError:
        return out
    for name in names:
        if not (name.startswith("incident-") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(inc_dir, name)) as f:
                rec = json.load(f)
            if isinstance(rec, dict) and rec.get("id") is not None:
                out.append(rec)
        except (OSError, json.JSONDecodeError):
            continue
    out.sort(key=lambda r: int(r["id"]))
    return out


def _fmt_value(v, unit) -> str:
    if v is None:
        return "?"
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    s = f"{f:g}" if abs(f) < 1e6 else f"{f:,.0f}"
    return f"{s} {unit}" if unit else s


def _incident_lines(inc: dict, verbose: bool = True) -> list[str]:
    """One incident rendered for ``why`` (verbose) or the diag/top
    "Incidents" section (brief). The same renderer serves both so the
    views cannot drift."""
    opened = inc.get("opened_t")
    opened_s = (
        time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(float(opened)))
        if opened else "?"
    )
    status = str(inc.get("status", "open")).upper()
    dur = None
    if inc.get("closed_t") and opened:
        dur = float(inc["closed_t"]) - float(opened)
    head = (
        f"incident #{inc.get('id')} — {status}, opened {opened_s}"
        + (
            f" (iteration {inc['opened_iteration']})"
            if inc.get("opened_iteration") is not None else ""
        )
        + (f", closed after {dur:.1f} s" if dur is not None else "")
    )
    lines = [head]
    causes = inc.get("causes") or []
    ev = inc.get("evidence") or {}
    counts = inc.get("detector_counts") or {}
    if not verbose:
        top = causes[0] if causes else None
        lines.append(
            "  top cause: "
            + (
                f"{top['tier']} (score {top['score']:g})" if top
                else "(unranked)"
            )
            + " — evidence: "
            + ", ".join(
                f"{len(ev.get(k) or [])} {k}"
                for k in ("faults", "slo_breaches", "exemplars",
                          "dead_tiers", "recoveries", "actions")
                if ev.get(k)
            )
            + (f"; {len(counts)} detector(s)" if counts else "")
        )
        return lines
    if counts:
        lines.append("  detectors fired:")
        for key in sorted(counts):
            det, _, rest = key.partition(":")
            tier, _, signal = rest.partition(":")
            unit = unit_for(signal)
            last = next(
                (
                    d for d in reversed(inc.get("detectors") or [])
                    if d.get("signal") == signal
                    and d.get("detector") == det
                ),
                None,
            )
            detail = ""
            if last is not None:
                detail = (
                    f" — last {_fmt_value(last.get('value'), unit)}"
                    f" vs baseline "
                    f"{_fmt_value(last.get('baseline'), unit)}"
                )
            lines.append(
                f"    {det:<10} {signal:<28} tier {tier:<12} "
                f"x{counts[key]}{detail}"
            )
    if causes:
        lines.append("  ranked causes (upstream-first):")
        for i, c in enumerate(causes[:5], 1):
            lines.append(
                f"    {i}. {c.get('tier'):<12} score {c.get('score'):g}"
            )
            for r in (c.get("reasons") or [])[:4]:
                lines.append(f"       - {r}")
    kinds = []
    for kind, rows in (
        ("fault", ev.get("faults")),
        ("recovery", ev.get("recoveries")),
        ("slo_breach", ev.get("slo_breaches")),
        ("exemplar", ev.get("exemplars")),
    ):
        for row in rows or []:
            kinds.append((kind, row))
    if kinds or ev.get("dead_tiers"):
        lines.append("  correlated evidence:")
        for name in ev.get("dead_tiers") or []:
            lines.append(f"    dead_tier   {name}")
        for kind, row in kinds[:16]:
            if kind == "fault":
                lines.append(
                    f"    fault       {row.get('kind', '?')} @ "
                    f"{row.get('site', '?')}"
                )
            elif kind == "recovery":
                lines.append(
                    f"    recovery    {row.get('reason', '?')}"
                    + (
                        f" (iteration {row.get('iteration')})"
                        if row.get("iteration") is not None else ""
                    )
                )
            elif kind == "slo_breach":
                lines.append(
                    f"    slo_breach  {row.get('objective')} tenant "
                    f"{row.get('tenant')}: measured "
                    f"{_fmt_value(row.get('measured'), unit_for(str(row.get('objective'))))}"
                    f" > target "
                    f"{_fmt_value(row.get('target'), unit_for(str(row.get('objective'))))}"
                )
            else:
                lines.append(
                    f"    exemplar    {row.get('name', '?')} span "
                    f"{row.get('span')} ({row.get('exemplar')}) — "
                    f"{_fmt_value(row.get('dur_ms'), 'ms')}, tier "
                    f"{row.get('tier', '?')}"
                )
    actions = ev.get("actions") or []
    if actions:
        lines.append("  actions taken (cause -> action -> verdict):")
        for a in actions[:8]:
            lines.append(
                f"    #{a.get('action')} {a.get('cause_tier', '?'):<12}"
                f" -> {a.get('kind', '?'):<18}"
                f" {a.get('detail', '')}"
                f" -> {a.get('verdict') or 'verifying'}"
                + (" (reverted)" if a.get("reverted") else "")
            )
    arts = inc.get("artifacts") or {}
    art_bits = [
        f"{k} {v}" for k, v in sorted(arts.items())
        if v and v != "pending"
    ]
    if art_bits:
        lines.append("  captured artifacts: " + "; ".join(art_bits))
    elif arts.get("profile") == "pending":
        lines.append("  captured artifacts: profile capture pending")
    return lines


def incidents_report(folder: str, incident: int | None = None) -> str | None:
    """The ``surreal_tpu why`` view: every incident's timeline —
    detector firings, ranked causes, correlated evidence with trace ids,
    artifact links. ``incident`` narrows to one id. None when the folder
    has no telemetry at all (mirrors ``trace``); a telemetry folder with
    zero incidents renders an explicit all-clear."""
    from surreal_tpu.session.telemetry import TELEMETRY_DIR

    if not os.path.isdir(os.path.join(folder, TELEMETRY_DIR)):
        return None
    incidents = load_incidents(folder)
    header = f"surreal_tpu why — {folder}"
    trace = next((i.get("trace") for i in incidents if i.get("trace")), None)
    if trace:
        header += f" (trace {trace})"
    lines = [header]
    if incident is not None:
        incidents = [i for i in incidents if int(i["id"]) == int(incident)]
        if not incidents:
            lines.append(f"  no incident #{incident} recorded")
            return "\n".join(lines)
    if not incidents:
        lines.append(
            "  no incidents recorded — every watchdog sweep came back "
            "healthy (or session_config.watchdog.enabled=false)"
        )
        return "\n".join(lines)
    n_open = sum(1 for i in incidents if i.get("status") == "open")
    lines.append(
        f"{len(incidents)} incident(s), {n_open} open"
    )
    for inc in incidents:
        lines.append("")
        lines += _incident_lines(inc, verbose=True)
    # the run-level Actions section (ISSUE 16): the remediation journal
    # rendered cause -> action -> verdict, incident-filtered when one id
    # was requested (pure file reading, same discipline as the rest)
    from surreal_tpu.session.remediate import actions_report_lines

    act_lines = actions_report_lines(folder, incident=incident)
    if act_lines:
        lines.append("")
        lines += act_lines
    return "\n".join(lines)


def incidents_brief(folder: str, limit: int = 4) -> list[str]:
    """The diag/top "Incidents" section: newest ``limit`` incidents, one
    brief block each (same renderer as ``why``). Empty list when none
    were recorded — the section simply doesn't appear."""
    incidents = load_incidents(folder)
    if not incidents:
        return []
    n_open = sum(1 for i in incidents if i.get("status") == "open")
    lines = [
        f"  {len(incidents)} incident(s) recorded, {n_open} open "
        "(full report: `surreal_tpu why <folder>`)"
    ]
    for inc in incidents[-limit:]:
        for ln in _incident_lines(inc, verbose=False):
            lines.append("  " + ln)
    return lines
