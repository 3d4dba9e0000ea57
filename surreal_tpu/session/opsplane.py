"""Run-wide live ops plane (ISSUE 13): cross-tier metrics aggregation,
per-tenant SLO evaluation, and a fault flight recorder.

Everything before this PR was post-hoc — per-process JSONL that
``surreal_tpu diag`` replays after the fact. This module gives a running
multi-tier session (gateway, inference fleet, experience shards,
parameter fanout, learner) ONE live merged view:

- **OpsPusher** — one per pushing thread (zmq sockets are not
  thread-safe, so every tier thread owns its own PUSH socket — the
  control-wire discipline the data planes already follow). Pushes are
  cadence-bounded and non-blocking; a full queue DROPS the row and
  counts it, never stalls a serve loop. Process tiers (experience
  shards, fleet replicas) inherit the aggregator address through their
  spawn kwargs exactly like the PR-6 trace id.
- **OpsAggregator** — the learner-side PULL collector. A dedicated
  receiver thread keeps the latest row per tier; ``snapshot()`` (called
  at the metrics cadence by SessionHooks) merges them with the learner's
  own rows into one trace-id-stamped run snapshot, evaluates per-tenant
  SLOs (session/slo.py), feeds the flight recorder, and atomically
  replaces ``<folder>/telemetry/ops_snapshot.json`` — the file
  ``surreal_tpu top`` renders live, with no full-log replay.
- **FlightRecorder** — a bounded in-memory ring of the last K snapshots
  plus fault/recovery events, dumped to
  ``<folder>/telemetry/flightrec/<trigger>/`` when the RecoveryManager
  trips, a chaos fault fires, or an SLO budget exhausts — post-mortems
  see the minutes *before* the incident, not just the trip itself.

Tier liveness reuses the heartbeat rule: each pushed row carries its own
``cadence_s``; a tier whose newest row is older than 3x its cadence is
rendered DEAD instead of silently looking fine.

Pure host python on the snapshot path — no jax imports, no device
syncs (the transfer-guard test runs end_iteration, snapshot included,
under a zero-transfer assertion). ``zmq`` is imported lazily inside the
pusher/aggregator so ``top``/``load_snapshot`` stay importable off-chip
with no messaging stack at all.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

from surreal_tpu.session.slo import SLOTracker
from surreal_tpu.session.telemetry import TELEMETRY_DIR
from surreal_tpu.utils import faults
from surreal_tpu.utils.net import alloc_address

SNAPSHOT_FILE = "ops_snapshot.json"
FLIGHTREC_DIR = "flightrec"
# a row with no self-declared cadence is judged against this one
DEFAULT_CADENCE_S = 10.0


def snapshot_path(folder: str) -> str:
    return os.path.join(folder, TELEMETRY_DIR, SNAPSHOT_FILE)


def load_snapshot(folder: str) -> dict | None:
    """Read the aggregator's snapshot file, tolerating the hostile shapes
    a live/killed run leaves behind: missing file, a torn half-written
    JSON text (the writer is atomic via os.replace, but a copied or
    truncated folder is not), or bytes cut inside a UTF-8 sequence."""
    try:
        with open(snapshot_path(folder), errors="replace") as f:
            snap = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    return snap if isinstance(snap, dict) else None


class OpsPusher:
    """One tier thread's PUSH half of the ops wire.

    ``push`` is cadence-bounded (at most one row per ``min_interval_s``
    unless forced) and never blocks: the socket runs a small send
    high-water mark and a full queue or closed peer drops the row,
    counted in ``dropped``. The ``ops.push`` chaos site lets tests drop
    or delay rows deterministically.
    """

    def __init__(self, address: str, tier: str, trace_id: str | None = None,
                 min_interval_s: float = 1.0):
        import zmq

        self.tier = str(tier)
        self.trace_id = trace_id
        self.min_interval_s = float(min_interval_s)
        self._zmq = zmq
        self._sock = zmq.Context.instance().socket(zmq.PUSH)
        self._sock.setsockopt(zmq.LINGER, 0)
        self._sock.setsockopt(zmq.SNDHWM, 8)  # stats, not data: drop early
        self._sock.connect(address)
        self._last = 0.0
        self.pushes = 0
        self.dropped = 0

    def push(self, gauges: dict | None = None, hops: dict | None = None,
             body: dict | None = None, force: bool = False) -> bool:
        """Send one row ``{tier, t, trace, cadence_s, gauges, hops,
        body}``; returns whether it left this process."""
        now = time.monotonic()
        if not force and now - self._last < self.min_interval_s:
            return False  # cadence bound, not a drop
        spec = faults.fire("ops.push")
        if spec is not None:
            if spec["kind"] == "drop_frame":
                self.dropped += 1  # counted, never silent
                return False
            if spec["kind"] == "delay":
                faults.sleep_ms(spec)
        row = {
            "tier": self.tier, "t": time.time(), "trace": self.trace_id,
            "cadence_s": self.min_interval_s,
            "gauges": gauges or {}, "hops": hops or {},
        }
        if body is not None:
            row["body"] = body
        try:
            self._sock.send(
                json.dumps(row, default=float).encode(),
                flags=self._zmq.NOBLOCK,
            )
        except (self._zmq.ZMQError, TypeError, ValueError):
            self.dropped += 1  # full HWM / closed ctx / unserializable row
            return False
        self._last = now
        self.pushes += 1
        return True

    def close(self) -> None:
        try:
            self._sock.close(0)
        except Exception:  # noqa: BLE001 — ctx may already be terminated
            pass


class FlightRecorder:
    """Bounded ring of snapshots + fault/recovery events with cooldown-
    limited dumps (a chaos storm must not turn the recorder into an IO
    fault of its own: at most one dump per trigger per
    ``min_dump_interval_s``; the dump directory for a trigger is
    overwritten by a later incident — the last incident wins, the ring
    inside it covers the minutes before)."""

    def __init__(self, folder: str | None, ring: int = 64,
                 min_dump_interval_s: float = 5.0, on_event=None):
        self.folder = folder
        self._snaps: deque = deque(maxlen=max(1, int(ring)))
        self._events: deque = deque(maxlen=max(4, int(ring) * 4))
        self._min_dump_interval_s = float(min_dump_interval_s)
        self._last_dump: dict[str, float] = {}
        self._on_event = on_event
        self.dumps = 0
        # callable returning the tracer's last-K exemplar span trees
        # (list of {exemplar, spans}); dumped as exemplars.jsonl so a
        # post-mortem sees WHAT the system was doing per-request, not
        # just aggregate gauges. None == tracing absent, nothing written
        self.exemplar_source = None

    def record_snapshot(self, snap: dict) -> None:
        self._snaps.append(snap)

    def record_event(self, kind: str, ev: dict) -> None:
        row = dict(ev)
        # a fault spec's own "kind" (kill/delay/...) must not clobber
        # the recorder's event kind — it rides as the detail field
        if "kind" in row:
            row["detail"] = row.pop("kind")
        self._events.append({"kind": kind, "t": time.time(), **row})

    def dump(self, trigger: str) -> str | None:
        """Write the rings to ``telemetry/flightrec/<trigger>/`` and
        return the directory (None when throttled/disabled/unwritable)."""
        if self.folder is None:
            return None
        now = time.monotonic()
        last = self._last_dump.get(trigger)
        if last is not None and now - last < self._min_dump_interval_s:
            return None
        self._last_dump[trigger] = now
        out = os.path.join(self.folder, TELEMETRY_DIR, FLIGHTREC_DIR, trigger)
        try:
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "snapshots.jsonl"), "w") as f:
                for snap in self._snaps:
                    f.write(json.dumps(snap, default=float) + "\n")
            with open(os.path.join(out, "events.jsonl"), "w") as f:
                for ev in self._events:
                    f.write(json.dumps(ev, default=float) + "\n")
            exemplars = []
            if self.exemplar_source is not None:
                try:
                    exemplars = list(self.exemplar_source() or ())
                except Exception:  # noqa: BLE001 — tracer must not kill a dump
                    exemplars = []
            if exemplars:
                with open(os.path.join(out, "exemplars.jsonl"), "w") as f:
                    for ex in exemplars:
                        f.write(json.dumps(ex, default=float) + "\n")
            meta = {
                "trigger": trigger, "t": time.time(),
                "snapshots": len(self._snaps), "events": len(self._events),
                "exemplars": len(exemplars),
            }
            with open(os.path.join(out, "meta.json"), "w") as f:
                json.dump(meta, f, indent=2)
        except OSError:
            return None  # telemetry must never kill training
        self.dumps += 1
        if self._on_event is not None:
            self._on_event(
                "ops_flightrec", trigger=trigger, dir=out,
                snapshots=len(self._snaps), events=len(self._events),
            )
        return out


class OpsAggregator:
    """The run-scoped collector: PULL socket on a dedicated receiver
    thread (latest row per tier), snapshot merge + SLO + flight recorder
    on the learner thread at the metrics cadence."""

    def __init__(self, folder: str | None, trace_id: str | None = None,
                 cfg=None, slo_cfg=None, on_event=None):
        cfg = cfg or {}
        get = cfg.get if hasattr(cfg, "get") else lambda k, d=None: d
        self.enabled = bool(get("enabled", True))
        self.folder = folder
        self.trace_id = trace_id
        self._on_event = on_event
        self._lock = threading.Lock()
        self._tiers: dict[str, dict] = {}  # tier -> {row, t_recv}
        self._stop = threading.Event()
        self._thread = None
        self.address = None
        self.bad_frames = 0
        self.snapshots = 0
        self._seq = 0
        self._write_ok = folder is not None
        self.slo = SLOTracker(slo_cfg, on_event=on_event)
        self.flightrec = FlightRecorder(
            folder,
            ring=int(get("ring", 64)),
            min_dump_interval_s=float(get("min_dump_interval_s", 5.0)),
            on_event=on_event,
        )
        if self.enabled:
            # fixed address allocated up front (utils/net.py discipline)
            # so process tiers can inherit it through spawn kwargs before
            # the receiver thread has bound
            self.address = alloc_address()
            self._thread = threading.Thread(
                target=self._recv_loop, name="ops-aggregator", daemon=True
            )
            self._thread.start()

    # -- receive (dedicated thread, owns the PULL socket) --------------------
    def _recv_loop(self) -> None:
        import zmq

        sock = zmq.Context.instance().socket(zmq.PULL)
        sock.setsockopt(zmq.LINGER, 0)
        sock.setsockopt(zmq.RCVHWM, 64)
        try:
            sock.bind(self.address)
            poller = zmq.Poller()
            poller.register(sock, zmq.POLLIN)
            while not self._stop.is_set():
                try:
                    if not dict(poller.poll(100)):
                        continue
                    raw = sock.recv(zmq.NOBLOCK)
                except zmq.ZMQError:
                    if self._stop.is_set():
                        break
                    continue
                try:
                    row = json.loads(raw.decode(errors="replace"))
                    tier = row["tier"]
                    if not isinstance(tier, str):
                        raise TypeError("tier must be a string")
                except (ValueError, KeyError, TypeError):
                    with self._lock:
                        self.bad_frames += 1  # counted, never silent
                    continue
                with self._lock:
                    self._tiers[tier] = {
                        "row": row, "t_recv": time.monotonic()
                    }
        finally:
            sock.close(0)

    # -- local rows (learner-thread tiers skip the wire) ---------------------
    def push_local(self, tier: str, gauges: dict | None = None,
                   hops: dict | None = None, body: dict | None = None,
                   cadence_s: float | None = None) -> None:
        """Store a row for a tier that lives on the learner thread (the
        learner loop itself, the merged fleet/experience/fanout views) —
        same schema as the wire, no socket round-trip."""
        row = {
            "tier": tier, "t": time.time(), "trace": self.trace_id,
            "cadence_s": float(cadence_s or DEFAULT_CADENCE_S),
            "gauges": gauges or {}, "hops": hops or {},
        }
        if body is not None:
            row["body"] = body
        with self._lock:
            self._tiers[tier] = {
                "row": row, "t_recv": time.monotonic(), "local": True,
            }

    def excuse_pause(self, seconds: float) -> None:
        """The learner thread was held for ``seconds`` by work of the
        session's own (a profiler capture's fences and trace write: a
        minute for three 4 s iterations of a thousand acting steps each).
        The tiers that live on that thread could not have reported
        meanwhile, so their rows are not that much older: without this the
        next snapshot reads them DEAD, opens an incident and captures
        again. Rows that came over the wire are judged as ever."""
        if seconds <= 0.0:
            return
        with self._lock:
            for rec in self._tiers.values():
                if rec.get("local"):
                    rec["t_recv"] += seconds

    # -- incidents -----------------------------------------------------------
    def record_fault(self, ev: dict) -> None:
        self.flightrec.record_event("fault", dict(ev))

    def record_recovery(self, ev: dict) -> None:
        self.flightrec.record_event("recovery", dict(ev))

    def dump(self, trigger: str) -> str | None:
        return self.flightrec.dump(trigger)

    # -- snapshot (learner thread, metrics cadence) --------------------------
    def _derived(self, tiers: dict) -> dict:
        """Cross-tier derived measurements. Staleness prefers the
        learner's exact per-update lineage reduction (``lineage/
        staleness_p99`` — measured over the versions that actually
        entered the gradient) and only falls back to the PR-13
        approximation (newest published version minus the oldest version
        any fleet replica still serves) when lineage is disabled or the
        learner has not reported yet. ``staleness_source`` records which
        path fed the SLO evaluation."""
        learner = tiers.get("learner", {}).get("row", {})
        exact = (learner.get("gauges") or {}).get("lineage/staleness_p99")
        if exact is not None:
            return {
                "staleness_updates": max(0, int(exact)),
                "staleness_source": "lineage",
            }
        fanout = tiers.get("param_fanout", {}).get("row", {})
        published = (fanout.get("gauges") or {}).get("version")
        if published is None:
            return {}
        held = []
        fleet = tiers.get("fleet", {}).get("row", {}).get("body") or {}
        for rep in (fleet.get("replicas") or {}).values():
            v = rep.get("param_version")
            if v is not None:
                held.append(int(v))
        if not held:
            return {}
        return {
            "staleness_updates": max(0, int(published) - min(held)),
            "staleness_source": "derived",
        }

    def snapshot(self, iteration: int | None = None,
                 env_steps: int | None = None) -> dict:
        """Merge the latest per-tier rows into one run snapshot, evaluate
        SLOs, feed the flight recorder, atomically replace the snapshot
        file, and return the snapshot dict."""
        now_mono = time.monotonic()
        with self._lock:
            tiers = {k: dict(v) for k, v in self._tiers.items()}
            bad = self.bad_frames
        rows: dict[str, dict] = {}
        merged_hops: dict[str, dict] = {}
        for tier, rec in tiers.items():
            row = rec["row"]
            cadence = float(row.get("cadence_s") or DEFAULT_CADENCE_S)
            age = now_mono - rec["t_recv"]
            out = dict(row)
            out["age_s"] = round(age, 3)
            # the heartbeat rule: silent for 3x your own cadence == DEAD
            out["dead"] = age > 3.0 * cadence
            rows[tier] = out
            for hop, st in (row.get("hops") or {}).items():
                if isinstance(st, dict):
                    merged_hops[hop] = st
        gw = rows.get("gateway", {}).get("body") or {}
        derived = self._derived(tiers)
        slo_table, newly_exhausted = self.slo.evaluate(
            gw.get("tenants") or {}, merged_hops, derived
        )
        self._seq += 1
        snap = {
            "type": "ops_snapshot", "t": time.time(),
            "trace": self.trace_id, "seq": self._seq,
            "iteration": iteration, "env_steps": env_steps,
            "tiers": rows, "hops": merged_hops, "slo": slo_table,
            "slo_counters": self.slo.gauges(), "bad_frames": bad,
            "derived": derived,
        }
        self.flightrec.record_snapshot(snap)
        self._write(snap)
        self.snapshots += 1
        if self._on_event is not None:
            # bounded by the metrics cadence, like ``phases`` events
            self._on_event(
                "ops_snapshot", seq=self._seq, tiers=len(rows),
                dead=sum(1 for r in rows.values() if r["dead"]),
                breaches=self.slo.breaches, bad_frames=bad,
            )
        for tenant, objective in newly_exhausted:
            self.dump("slo")
            break  # one incident dump covers every pair this window
        return snap

    def _write(self, snap: dict) -> None:
        if not self._write_ok:
            return
        path = snapshot_path(self.folder)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snap, f, default=float)
            os.replace(tmp, path)  # readers never see a torn file
        except OSError:
            self._write_ok = False  # telemetry must never kill training

    def gauges(self) -> dict[str, float]:
        with self._lock:
            bad = float(self.bad_frames)
            tiers = float(len(self._tiers))
        return {
            "ops/tiers": tiers,
            "ops/bad_frames": bad,
            "ops/snapshots": float(self.snapshots),
            "ops/flightrec_dumps": float(self.flightrec.dumps),
            **self.slo.gauges(),
        }

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self.enabled = False


# -- top ----------------------------------------------------------------------


def top_report(snap: dict | None, folder: str | None = None) -> str:
    """Render one merged snapshot as the ``surreal_tpu top`` view:
    per-tier health, per-tenant SLO/budget table, hop latencies, MFU —
    reusing diag's section renderers over the snapshot's tier bodies
    instead of a full event-log replay."""
    from surreal_tpu.session.telemetry import (
        _engine_lines,
        _experience_plane_lines,
        _gateway_lines,
        _performance_lines,
        _serving_tier_lines,
    )

    if snap is None:
        return (
            f"surreal_tpu top — no ops snapshot"
            + (f" under {folder}" if folder else "")
            + "\n(the run has not written telemetry/ops_snapshot.json yet,"
            " or the file is torn — retrying helps for a live run)"
        )
    age = time.time() - float(snap.get("t", 0.0))
    lines = [
        "surreal_tpu top — run snapshot"
        + (f" #{snap.get('seq')}" if snap.get("seq") is not None else "")
        + (f", trace {snap['trace']}" if snap.get("trace") else ""),
        f"  written {age:.1f} s ago"
        + (
            f", iteration {snap['iteration']}"
            if snap.get("iteration") is not None else ""
        )
        + (
            f", env_steps {snap['env_steps']}"
            if snap.get("env_steps") is not None else ""
        )
        + (
            f", {snap['bad_frames']} bad frame(s) dropped"
            if snap.get("bad_frames") else ""
        ),
        "",
        "Tiers",
    ]
    tiers = snap.get("tiers") or {}
    if tiers:
        lines.append(f"  {'tier':<24} {'age s':>8} {'cadence':>8}  status")
        for name in sorted(tiers):
            row = tiers[name]
            dead = bool(row.get("dead"))
            lines.append(
                f"  {name:<24} {float(row.get('age_s', 0.0)):>8.1f} "
                f"{float(row.get('cadence_s', 0.0)):>8.1f}  "
                + ("DEAD (> 3x cadence)" if dead else "alive")
            )
        dead_tiers = [n for n, r in sorted(tiers.items()) if r.get("dead")]
        if dead_tiers:
            lines.append(
                f"  !! tier(s) {', '.join(dead_tiers)} stopped pushing — "
                "wedged, killed, or respawning"
            )
    else:
        lines.append("  (no tier has pushed a row yet)")
    lines += _slo_lines(snap)
    # diag's renderers, fed from the snapshot's tier bodies
    eng_body = (tiers.get("engine") or {}).get("body")
    eng_lines = _engine_lines({"engine": eng_body}) if eng_body else []
    if eng_lines:
        lines += ["", "Loop engine"] + eng_lines
    gw_body = (tiers.get("gateway") or {}).get("body")
    gw_lines = _gateway_lines({"gateway": gw_body}) if gw_body else []
    if gw_lines:
        lines += ["", "Gateway"] + gw_lines
    fleet_body = (tiers.get("fleet") or {}).get("body")
    tier_lines = _serving_tier_lines({"serving": fleet_body}) if fleet_body else []
    if tier_lines:
        lines += ["", "Serving tier"] + tier_lines
    xp_body = (tiers.get("experience") or {}).get("body")
    xp_lines = _experience_plane_lines({"experience": xp_body}) if xp_body else []
    if xp_lines:
        lines += ["", "Experience plane"] + xp_lines
    learner = tiers.get("learner") or {}
    perf_lines = _performance_lines({
        "perf": {
            k: v for k, v in (learner.get("gauges") or {}).items()
            if k.startswith("perf/")
        },
        "hops": snap.get("hops") or {},
    })
    if perf_lines:
        lines += ["", "Performance"] + perf_lines
    # watchdog incidents (ISSUE 15): same brief as diag's Incidents
    # section — pure file reading under <folder>/telemetry/incidents/,
    # so a live `top` shows an opened incident within one refresh
    if folder:
        try:
            from surreal_tpu.session.incidents import incidents_brief

            inc_lines = incidents_brief(folder)
        except Exception:
            inc_lines = []
        if inc_lines:
            lines += [
                "", "Incidents (surreal_tpu why for the full report)",
            ] + inc_lines
        # live remediation state (ISSUE 16): the newest journaled actions
        # under <folder>/telemetry/actions/ — an executing/verifying
        # action shows up within one refresh, same pure-file-read rule
        try:
            from surreal_tpu.session.remediate import actions_brief

            act_lines = actions_brief(folder)
        except Exception:
            act_lines = []
        if act_lines:
            lines += ["", "Remediation"] + act_lines
    return "\n".join(lines)


def _slo_lines(snap: dict) -> list[str]:
    table = snap.get("slo") or {}
    counters = snap.get("slo_counters") or {}
    if not table and not counters.get("slo/objectives"):
        return []
    lines = [
        "",
        "SLOs — {b:g} breach(es), {e:g} budget exhaustion(s)".format(
            b=float(counters.get("slo/breaches", 0)),
            e=float(counters.get("slo/exhaustions", 0)),
        ),
    ]
    if table:
        lines.append(
            f"  {'tenant':<12} {'objective':<20} {'measured':>10} "
            f"{'target':>10} {'budget':>8}  status"
        )
        for tenant in sorted(table):
            for name in sorted(table[tenant]):
                o = table[tenant][name]
                status = (
                    "EXHAUSTED" if o.get("exhausted")
                    else "BREACH" if o.get("breached") else "ok"
                )
                lines.append(
                    f"  {tenant:<12} {name:<20} "
                    f"{float(o.get('measured', 0)):>10.3f} "
                    f"{float(o.get('target', 0)):>10.3f} "
                    f"{float(o.get('budget_used', 0)):>7.0%}  {status}"
                )
    else:
        lines.append("  (objectives declared; no tenant data this window)")
    return lines
