"""Remediation engine: root-caused incidents -> bounded actions on the
existing actuator surfaces (ISSUE 16).

PR 15's incident engine says *what probably caused it*; this module
closes the loop and says *what was done about it*. Once per ops
snapshot — after the watchdog sweep and the incident observe — the
engine reads the open incident's top-ranked cause tier and maps it to
ONE bounded action on an actuator the system already has:

    cause tier   action             actuator                    revert
    ----------   ----------------   -------------------------   --------------
    fleet        fleet_scale_up     InferenceFleet.scale_up     scale_down
    gateway      tenant_throttle    AdmissionController          restore the
                 (budget-burning     .set_quota (runtime)        previous quota
                 tenant)
    DEAD tier    targeted_restart   the tier's supervise()       (irreversible)
                                    (RespawnSchedule-backed)
    learner      learner_scale_up   LearnerGroup.scale_up        scale_down
    (saturated/                     (parallel/learner_group.py:  (remove the
    lagging)                        join a member, rebalance)    joined member)

Discipline (the PR-15 false-positive guard, extended to actuation):

- **Journaled, first-class evidence** — every action is a counted
  ``remediation`` telemetry event, a ``remediation/*`` gauge bump, an
  atomic ``telemetry/actions/action-<n>.json`` record, AND an entry in
  the open incident's evidence (``surreal_tpu why`` renders
  cause -> action -> verdict).
- **Bounded** — per-action-kind cooldowns and a global ``max_actions``
  budget; a suppressed action is loud (``remediation/suppressed`` +
  event), never a silent retry loop.
- **Counter-detected** — each action watches its triggering objective
  for ``verify_windows`` post-action sweeps; if the objective regresses
  further, the action is marked ineffective, reverted where reversible
  (re-add the drained replica, restore the quota), and counted.

Pure host arithmetic over the snapshot dict (the same transfer-guard
that covers the watchdog covers this); persistence mirrors the incident
records (atomic tmp+replace, a failed write disables itself — the
control plane must never kill training). The report helpers at the
bottom are pure file reading, reused by ``why`` and ``top``.
"""

from __future__ import annotations

import json
import os
import time

ACTIONS_DIR = "actions"  # <folder>/telemetry/actions/

# verification objectives preferred when choosing which breached SLO row
# an action answers (latency/staleness contracts recover when the action
# works; throttle_rate on the throttled tenant moves the WRONG way under
# a shed, so it is last)
_SLO_PREFERENCE = (
    "act_rtt_p99_ms", "attach_p99_ms", "staleness_updates", "throttle_rate",
)


def _mean(xs) -> float | None:
    xs = [float(x) for x in xs if x is not None]
    return sum(xs) / len(xs) if xs else None


class RemediationEngine:
    """Owns incident-driven actuation for one run (constructed by
    SessionHooks next to the IncidentEngine, stepped once per metrics
    cadence after ``incidents.observe``).

    Actuators are bound AFTER construction (``bind_actuators``) because
    the fleet/gateway exist only inside the driver's run(); an unbound
    surface simply makes its actions unmappable — counted, never an
    error."""

    def __init__(self, folder=None, cfg=None, incidents=None, on_event=None,
                 trace_id=None):
        cfg = cfg or {}
        get = cfg.get if hasattr(cfg, "get") else lambda k, d=None: d
        self.folder = folder
        self.trace_id = trace_id
        self.enabled = bool(get("enabled", True))
        self.max_actions = int(get("max_actions", 8))
        self.cooldown_s = float(get("cooldown_s", 30.0))
        self.verify_windows = max(1, int(get("verify_windows", 4)))
        # "regressed further": post-action mean beyond baseline by this
        # relative margin (plus a tiny absolute floor for ~0 baselines)
        self.regress_margin = float(get("regress_margin", 0.1))
        self.throttle_factor = float(get("throttle_factor", 0.5))
        self.min_rate = float(get("min_rate", 1.0))
        # rate applied when shedding a tenant whose quota was unlimited
        # (rate=0 disables the bucket, so a multiplicative throttle has
        # nothing to scale)
        self.shed_rate = float(get("shed_rate", 50.0))
        self._incidents = incidents
        self._on_event = on_event
        # bound actuator surfaces (None/empty until bind_actuators)
        self._fleet = None
        self._admission = None
        self._restart: dict = {}
        self._learner_group = None
        # bookkeeping
        self._next_id = 1
        self._active: list[dict] = []   # actions still under verification
        self._last_t: dict[str, float] = {}  # action kind -> last exec time
        self.executed = 0
        self.suppressed = 0
        self.unmapped = 0
        self.reverted = 0
        self.ineffective = 0
        self.effective = 0
        self.errors = 0
        self._write_ok = folder is not None

    def bind_actuators(self, fleet=None, admission=None, restart=None,
                       learner_group=None) -> None:
        """Hand the engine its actuator surfaces: ``fleet`` duck-types
        ``scale_up()/scale_down()`` (InferenceFleet), ``admission``
        duck-types ``quota_of()/set_quota()`` (AdmissionController),
        ``restart`` maps tier name -> zero-arg supervise callable (the
        RespawnSchedule-backed supervisors), and ``learner_group``
        duck-types ``scale_up() -> member_id / scale_down(member_id)``
        (the elastic LearnerGroup — ROADMAP's "scale the named tier"
        reservation for learners)."""
        if fleet is not None:
            self._fleet = fleet
        if admission is not None:
            self._admission = admission
        if restart:
            self._restart.update(restart)
        if learner_group is not None:
            self._learner_group = learner_group

    # -- the per-cadence decision sweep --------------------------------------
    def step(self, firings: list[dict] | None, snap: dict | None) -> None:
        """One decision sweep: verify the active actions against this
        snapshot, then map the open incident's top cause to at most one
        new bounded action. ``firings``, this sweep's, are not read: the
        open incident has folded them in and is what the decision reads
        (ROADMAP D14). Pure host work; every non-action outcome is counted."""
        if not self.enabled:
            return
        now = time.time()
        snap = snap or {}
        self._verify(snap, now)
        inc = (
            self._incidents.open_incident
            if self._incidents is not None else None
        )
        if inc is None or not inc.get("causes"):
            return
        if any(a["incident"] == inc["id"] for a in self._active):
            return  # an answer is already under verification — wait
        tier = str(inc["causes"][0].get("tier"))
        plan = self._map_action(tier, inc, snap)
        if plan is None:
            self.unmapped += 1
            return
        kind = plan["kind"]
        if self.executed >= self.max_actions:
            self._suppress(kind, inc, now,
                           f"action budget exhausted "
                           f"({self.executed}/{self.max_actions})")
            return
        last = self._last_t.get(kind)
        if last is not None and now - last < self.cooldown_s:
            self._suppress(
                kind, inc, now,
                f"cooldown ({now - last:.1f} s of {self.cooldown_s:.1f} s)",
            )
            return
        self._execute(plan, tier, inc, snap, now)

    def _suppress(self, kind: str, inc: dict, now: float,
                  reason: str) -> None:
        """A would-be action stopped by a bound — loud, never a silent
        retry loop."""
        self.suppressed += 1
        if self._on_event is not None:
            self._on_event("remediation", status="suppressed", kind=kind,
                           incident=inc["id"], reason=reason)

    # -- cause tier -> action plan -------------------------------------------
    def _map_action(self, tier: str, inc: dict, snap: dict) -> dict | None:
        """The action table. Returns ``{kind, detail, run, revert_info,
        reversible, objective fields...}`` or None (no bound actuator /
        no actionable target — counted unmapped by the caller)."""
        dead = [
            str(n) for n in inc.get("evidence", {}).get("dead_tiers", ())
            if str(n).split(".", 1)[0] == tier
        ]
        if tier == "fleet" and self._fleet is not None:
            return {
                "kind": "fleet_scale_up",
                "detail": (
                    f"re-arm/add a replica (dead: {', '.join(dead)})"
                    if dead else "add a serving replica"
                ),
                "objective": "fleet_serve_ms",
            }
        if tier == "gateway" and self._admission is not None:
            target = self._burning_tenant(snap)
            if target is None:
                return None
            tenant, objective = target
            return {
                "kind": "tenant_throttle",
                "detail": f"throttle tenant {tenant!r} "
                          f"(burning {objective} budget)",
                "objective": "slo_budget_used",
                "tenant": tenant,
                "slo_objective": objective,
            }
        if dead and tier in self._restart:
            return {
                "kind": "targeted_restart",
                "detail": f"supervise/restart {', '.join(dead)}",
                "objective": "tier_dead",
                "tier": tier,
            }
        if tier == "learner" and self._learner_group is not None:
            # a cause naming the learner tier = it can't keep up: add a
            # group member under the same cooldown + max-actions +
            # counter-detection discipline; revert = remove the joined
            # member
            return {
                "kind": "learner_scale_up",
                "detail": "join a learner-group member "
                          "(shard rebalance + fanout re-key)",
                "objective": "throughput",
            }
        return None

    def _burning_tenant(self, snap: dict) -> tuple[str, str] | None:
        """(tenant, objective) burning the most error budget in this
        snapshot's SLO table — the throttle target. Latency/staleness
        objectives are preferred for verification (see _SLO_PREFERENCE)."""
        best = None
        for tenant, row in (snap.get("slo") or {}).items():
            for objective, o in (row or {}).items():
                if not (isinstance(o, dict) and (o.get("breached")
                                                 or o.get("exhausted"))):
                    continue
                pref = (
                    _SLO_PREFERENCE.index(objective)
                    if objective in _SLO_PREFERENCE else len(_SLO_PREFERENCE)
                )
                score = (float(o.get("budget_used", 0.0)), -pref)
                if best is None or score > best[0]:
                    best = (score, str(tenant), str(objective))
        return (best[1], best[2]) if best else None

    # -- execution + journal -------------------------------------------------
    def _execute(self, plan: dict, tier: str, inc: dict, snap: dict,
                 now: float) -> None:
        kind = plan["kind"]
        reversible = True
        revert_info: dict = {}
        try:
            if kind == "fleet_scale_up":
                revert_info["replica"] = int(self._fleet.scale_up())
            elif kind == "tenant_throttle":
                tenant = plan["tenant"]
                old = self._admission.quota_of(tenant)
                new = dict(old)
                rate = float(old.get("rate", 0.0))
                new["rate"] = (
                    max(self.min_rate, rate * self.throttle_factor)
                    if rate > 0 else self.shed_rate
                )
                burst = float(old.get("burst", 1.0))
                new["burst"] = max(1.0, burst * self.throttle_factor)
                self._admission.set_quota(tenant, new)
                revert_info = {"tenant": tenant, "quota": old,
                               "applied": new}
            elif kind == "targeted_restart":
                self._restart[plan["tier"]]()
                reversible = False  # a restart cannot be un-run
            elif kind == "learner_scale_up":
                revert_info["member"] = int(self._learner_group.scale_up())
            else:  # pragma: no cover — _map_action emits only the above
                raise ValueError(f"unknown action kind {kind}")
        except Exception as e:  # noqa: BLE001 — actuation must never
            # kill training; the failure is journaled and counted
            self.errors += 1
            if self._on_event is not None:
                self._on_event("remediation", status="error", kind=kind,
                               incident=inc["id"],
                               reason=f"{type(e).__name__}: {e}")
            return
        n = self._next_id
        self._next_id += 1
        self.executed += 1
        self._last_t[kind] = now
        act = {
            "action": n, "t": now, "status": "verifying", "verdict": None,
            "trace": self.trace_id, "incident": int(inc["id"]),
            "cause_tier": tier, "cause_score": inc["causes"][0].get("score"),
            "kind": kind, "detail": plan["detail"],
            "objective": plan["objective"],
            "tenant": plan.get("tenant"),
            "slo_objective": plan.get("slo_objective"),
            "tier": plan.get("tier"),
            "baseline": self._objective_value(plan, snap),
            "samples": [], "verify_left": int(self.verify_windows),
            "reversible": reversible, "revert_info": revert_info,
            "reverted": False,
            "iteration": snap.get("iteration"),
        }
        self._active.append(act)
        self._write(act)
        if self._on_event is not None:
            self._on_event(
                "remediation", status="executed", action=n, kind=kind,
                incident=inc["id"], cause_tier=tier, detail=plan["detail"],
                baseline=act["baseline"],
            )
        self._attach(act)

    def _attach(self, act: dict) -> None:
        """Mirror the action into the incident it answered (first-class
        evidence; no-op once that incident is no longer the open one)."""
        if self._incidents is None:
            return
        inc = self._incidents.open_incident
        if inc is None or int(inc["id"]) != int(act["incident"]):
            return
        self._incidents.attach_action({
            "action": act["action"], "t": act["t"],
            "cause_tier": act["cause_tier"], "kind": act["kind"],
            "detail": act["detail"], "verdict": act["verdict"],
            "reverted": act["reverted"],
        })

    # -- the counter-detector ------------------------------------------------
    def _objective_value(self, act: dict, snap: dict) -> float | None:
        """The triggering objective's value in this snapshot (None = no
        data this sweep — never a verdict input). Lower is better for
        every objective except throughput."""
        obj = act.get("objective")
        tiers = snap.get("tiers") or {}
        if obj == "fleet_serve_ms":
            vals = [
                (row.get("gauges") or {}).get("fleet/serve_ms")
                for name, row in tiers.items()
                if str(name).split(".", 1)[0] == "fleet"
            ]
            return _mean(vals)
        if obj == "slo_budget_used":
            row = (snap.get("slo") or {}).get(act.get("tenant")) or {}
            o = row.get(act.get("slo_objective"))
            if isinstance(o, dict) and o.get("budget_used") is not None:
                return float(o["budget_used"])
            # tenant gone quiet: its budget stopped burning by definition
            return None
        if obj == "tier_dead":
            rows = [
                row for name, row in tiers.items()
                if str(name).split(".", 1)[0] == act.get("tier")
            ]
            if not rows:
                return None
            return _mean([1.0 if r.get("dead") else 0.0 for r in rows])
        if obj == "throughput":
            v = (
                (tiers.get("learner") or {}).get("gauges") or {}
            ).get("time/env_steps_per_s")
            return float(v) if v is not None else None
        return None

    def _verify(self, snap: dict, now: float) -> None:
        """One verification tick for every active action; verdicts after
        ``verify_windows`` sweeps, reverting what regressed further."""
        for act in list(self._active):
            v = self._objective_value(act, snap)
            if v is not None:
                act["samples"].append(round(float(v), 6))
            act["verify_left"] -= 1
            if act["verify_left"] > 0:
                continue
            self._active.remove(act)
            act["status"] = "done"
            act["verdict"] = self._judge(act)
            if act["verdict"] == "ineffective":
                self.ineffective += 1
                if act["reversible"]:
                    self._revert(act)
            elif act["verdict"] == "effective":
                self.effective += 1
            self._write(act)
            if self._on_event is not None:
                self._on_event(
                    "remediation_verdict", action=act["action"],
                    kind=act["kind"], verdict=act["verdict"],
                    incident=act["incident"], baseline=act["baseline"],
                    post_mean=_mean(act["samples"]),
                    reverted=act["reverted"],
                )
            self._attach(act)

    def _judge(self, act: dict) -> str:
        """ineffective = the objective regressed FURTHER past its
        at-action baseline; effective otherwise; unverified when either
        side carried no data (no data is never a revert trigger)."""
        baseline = act.get("baseline")
        post = _mean(act["samples"])
        if baseline is None or post is None:
            return "unverified"
        baseline = float(baseline)
        floor = 1e-6  # ~0 baselines: relative margin alone is a tautology
        if act.get("objective") == "throughput":  # higher is better
            return (
                "ineffective"
                if post < baseline * (1.0 - self.regress_margin) - floor
                else "effective"
            )
        return (
            "ineffective"
            if post > baseline * (1.0 + self.regress_margin) + floor
            else "effective"
        )

    def _revert(self, act: dict) -> None:
        kind = act["kind"]
        info = act.get("revert_info") or {}
        try:
            if kind == "fleet_scale_up":
                self._fleet.scale_down()
            elif kind == "tenant_throttle":
                self._admission.set_quota(info["tenant"], info["quota"])
            elif kind == "learner_scale_up":
                self._learner_group.scale_down(info.get("member"))
            else:
                return
        except Exception as e:  # noqa: BLE001 — a failed revert is
            # journaled evidence, not a crash
            self.errors += 1
            act["revert_error"] = f"{type(e).__name__}: {e}"
            return
        act["reverted"] = True
        self.reverted += 1

    # -- teardown + persistence ----------------------------------------------
    def close(self) -> None:
        """Session teardown: flush still-verifying actions as-is (a run
        ending mid-verification is itself evidence)."""
        for act in self._active:
            self._write(act)

    def _write(self, act: dict) -> None:
        if not self._write_ok:
            return
        from surreal_tpu.session.telemetry import TELEMETRY_DIR

        folder = os.path.join(self.folder, TELEMETRY_DIR, ACTIONS_DIR)
        path = os.path.join(folder, f"action-{act['action']}.json")
        try:
            os.makedirs(folder, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(act, f, default=float)
            os.replace(tmp, path)  # readers never see a torn record
        except OSError:
            self._write_ok = False  # actuation telemetry must never
            # kill training

    def gauges(self) -> dict[str, float]:
        """The engine's ``remediation/*`` counters (GAUGE_REGISTRY
        documents each); merged into the learner's metrics row."""
        return {
            "remediation/actions": float(self.executed),
            "remediation/suppressed": float(self.suppressed),
            "remediation/unmapped": float(self.unmapped),
            "remediation/reverted": float(self.reverted),
            "remediation/ineffective": float(self.ineffective),
            "remediation/effective": float(self.effective),
            "remediation/errors": float(self.errors),
            "remediation/active": float(len(self._active)),
        }


# -- report helpers (pure file reading, like why/top/trace) -------------------


def load_actions(folder: str) -> list[dict]:
    """Every persisted action record under ``<folder>/telemetry/actions/``,
    id order. Hostile-tolerant: a torn/foreign file is skipped."""
    from surreal_tpu.session.telemetry import TELEMETRY_DIR

    act_dir = os.path.join(folder, TELEMETRY_DIR, ACTIONS_DIR)
    out = []
    try:
        names = os.listdir(act_dir)
    except OSError:
        return out
    for name in names:
        if not (name.startswith("action-") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(act_dir, name)) as f:
                rec = json.load(f)
            if isinstance(rec, dict) and rec.get("action") is not None:
                out.append(rec)
        except (OSError, json.JSONDecodeError):
            continue
    out.sort(key=lambda r: int(r["action"]))
    return out


def _action_line(a: dict) -> str:
    verdict = a.get("verdict") or a.get("status", "?")
    return (
        f"  #{a.get('action')} incident {a.get('incident')} "
        f"{a.get('cause_tier', '?'):<12} -> {a.get('kind', '?'):<18} "
        f"{a.get('detail', '')} -> {verdict}"
        + (" (reverted)" if a.get("reverted") else "")
    )


def actions_report_lines(folder: str,
                         incident: int | None = None) -> list[str]:
    """The ``surreal_tpu why`` Actions section: the remediation journal
    rendered cause -> action -> verdict (empty when no action was ever
    taken — the section simply doesn't appear)."""
    actions = load_actions(folder)
    if incident is not None:
        actions = [
            a for a in actions if int(a.get("incident", -1)) == int(incident)
        ]
    if not actions:
        return []
    n_rev = sum(1 for a in actions if a.get("reverted"))
    lines = [
        f"Actions — {len(actions)} remediation action(s), "
        f"{n_rev} reverted (journal: telemetry/actions/)"
    ]
    for a in actions:
        lines.append(_action_line(a))
    return lines


def actions_brief(folder: str, limit: int = 4) -> list[str]:
    """The ``top`` live-action section: newest ``limit`` actions, one
    line each (same renderer as ``why``'s Actions section)."""
    actions = load_actions(folder)
    if not actions:
        return []
    active = sum(1 for a in actions if a.get("status") == "verifying")
    lines = [
        f"  {len(actions)} action(s) taken, {active} verifying "
        "(full journal: `surreal_tpu why <folder>`)"
    ]
    for a in actions[-limit:]:
        lines.append("  " + _action_line(a))
    return lines
