"""Anomaly detectors over the live ops-plane snapshot (ISSUE 15).

PRs 13-14 collect; this module *interprets*. ``Watchdog.evaluate(snap)``
runs once per ops-plane snapshot (the metrics cadence) over the merged
snapshot dict the :class:`~surreal_tpu.session.opsplane.OpsAggregator`
just built — pure host arithmetic on already-synced floats, so the
transfer-guard proof that covers the snapshot path covers the detectors
too (zero device->host syncs added).

Detector families (each firing is a plain dict the incident engine
consumes):

- **breakout** — robust EWMA/median + MAD deviation on the latency and
  throughput signals: derived iteration time, env steps/s, the learner's
  sample-wait, the gateway act-RTT p99 hop, the fleet serve EWMA. A
  value ``mad_k`` MADs AND ``min_rel`` relative off the window median,
  in the bad direction, for ``sustain`` consecutive snapshots, fires.
- **saturation** — absolute ceilings on queue depths / backpressure
  (fleet chunk queue, shard sample queue, gateway act queue) and on the
  respawn *rate* (fleet/experience/gateway respawns per history window).
- **growth** — monotonic-growth on every ``*dropped*`` / ``*bad_frames``
  counter found anywhere in the snapshot (they are all
  counted-never-silent failure counters: sustained growth is never
  benign), and on ``lineage/staleness_p99`` once it exceeds
  ``staleness_floor`` (a staleness ramp past pipeline-depth scale means
  the param path is falling behind; the startup climb toward steady
  state stays below the floor and never fires).
- **liveness** — any tier the aggregator marked DEAD (silent for 3x its
  own declared cadence).

Every evaluation honors the ``watchdog.eval`` chaos site: ``drop_eval``
skips the sweep (counted in ``ops/watchdog_dropped_evals``, never
silent), ``delay`` sleeps first. Knobs: ``session_config.watchdog.*``
(session/default_configs.py).
"""

from __future__ import annotations

import time

from surreal_tpu.utils import faults

# breakout signal specs: (name, tier blamed, direction). 'high' fires on
# values above the window median, 'low' below (throughput collapses down).
# Values are pulled from the snapshot by key — gauges/body of any tier
# row for plain keys, hop percentiles for ('hop', name, pctl) specs,
# 'derived' for snapshot-to-snapshot derivations done here.
BREAKOUT_SIGNALS = (
    ("iter_ms", "learner", "high", ("derived", "iter_ms")),
    ("env_steps_per_s", "learner", "low", ("gauge", "time/env_steps_per_s")),
    ("sample_wait_ms", "learner", "high",
     ("gauge", "experience/sample_wait_ms")),
    ("act_rtt_p99_ms", "gateway", "high", ("hop", "gateway_act_ms", "p99")),
    ("fleet_serve_ms", "fleet", "high", ("gauge", "fleet/serve_ms")),
)

# saturation ceilings: gauge key -> tier blamed (threshold from config)
QUEUE_SIGNALS = {
    "fleet/queue_depth": "fleet",
    "experience/sample_queue_depth": "experience",
    "gateway/queued_acts": "gateway",
}
RESPAWN_COUNTERS = {
    "fleet/respawns": "fleet",
    "experience/respawns": "experience",
    "gateway/respawns": "gateway",
}

# growth counters are attributed to the tier their family belongs to
# (the dataflow graph in session/incidents.py then walks upstream)
_PREFIX_TIER = {
    "gateway": "gateway",
    "fleet": "fleet",
    "experience": "experience",
    "param": "param_fanout",
    "lineage": "param_fanout",
    "ops": "learner",
    "trace": "learner",
    "replay": "learner",
    "perf": "learner",
    "slo": "gateway",
}


def _family_tier(key: str) -> str:
    return _PREFIX_TIER.get(str(key).split("/", 1)[0], "learner")


def base_tier(name: str) -> str:
    """Collapse a per-instance tier row name to its dataflow-graph node:
    ``fleet.replica1`` -> ``fleet``, ``experience.shard0`` ->
    ``experience``."""
    return str(name).split(".", 1)[0]


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


class _Breakout:
    """One robust-deviation detector: rolling window median + MAD, fires
    after ``sustain`` consecutive bad-direction outliers past warmup."""

    def __init__(self, name, tier, direction, cfg):
        self.name = name
        self.tier = tier
        self.direction = direction
        self.window = int(cfg["window"])
        self.warmup = int(cfg["warmup"])
        self.mad_k = float(cfg["mad_k"])
        self.min_rel = float(cfg["min_rel"])
        self.sustain = max(1, int(cfg["sustain"]))
        self._hist: list[float] = []
        self._streak = 0

    def observe(self, value) -> dict | None:
        if value is None:
            # a signal that stopped reporting is the liveness detector's
            # job; breakouts only judge values that arrived
            self._streak = 0
            return None
        v = float(value)
        hist = self._hist
        firing = None
        if len(hist) >= self.warmup:
            med = _median(hist)
            mad = _median([abs(x - med) for x in hist])
            # MAD floor: a perfectly flat warmup window (synthetic rigs,
            # quantized ms readings) must not make every jitter an outlier
            floor = max(mad, 1e-9, abs(med) * 0.01)
            dev = (v - med) if self.direction == "high" else (med - v)
            rel = dev / max(abs(med), 1e-9)
            if dev > self.mad_k * floor and rel > self.min_rel:
                self._streak += 1
            else:
                self._streak = 0
            if self._streak >= self.sustain:
                firing = {
                    "detector": "breakout",
                    "signal": self.name,
                    "tier": self.tier,
                    "value": round(v, 4),
                    "baseline": round(med, 4),
                    "direction": self.direction,
                    "deviation_mads": round(dev / floor, 2),
                }
        else:
            self._streak = 0
        hist.append(v)
        if len(hist) > self.window:
            del hist[0]
        return firing


class _Counter:
    """Rolling history of a monotonic counter; reports the per-window
    deltas so growth/rate detectors share one bookkeeping shape."""

    def __init__(self, window: int):
        self.window = max(2, int(window))
        self._vals: list[float] = []

    def observe(self, value: float) -> list[float]:
        self._vals.append(float(value))
        if len(self._vals) > self.window:
            del self._vals[0]
        return [
            self._vals[i + 1] - self._vals[i]
            for i in range(len(self._vals) - 1)
        ]


class Watchdog:
    """The detector sweep. Construct once per run (launch/hooks.py),
    call :meth:`evaluate` with each merged ops snapshot; returns the
    list of firing dicts for the incident engine."""

    def __init__(self, cfg=None):
        cfg = cfg or {}
        get = cfg.get if hasattr(cfg, "get") else lambda k, d=None: d
        self.enabled = bool(get("enabled", True))
        bo = {
            "window": int(get("window", 32)),
            "warmup": int(get("warmup", 8)),
            "mad_k": float(get("mad_k", 6.0)),
            "min_rel": float(get("min_rel", 0.25)),
            "sustain": int(get("sustain", 2)),
        }
        self._breakouts = [
            _Breakout(name, tier, direction, bo)
            for name, tier, direction, _ in BREAKOUT_SIGNALS
        ]
        self._specs = {s[0]: s[3] for s in BREAKOUT_SIGNALS}
        self.queue_depth_max = float(get("queue_depth_max", 512.0))
        self.respawn_burst = max(1, int(get("respawn_burst", 2)))
        self.growth_windows = max(1, int(get("growth_windows", 2)))
        self.staleness_growth_windows = max(
            2, int(get("staleness_growth_windows", 4))
        )
        # absolute floor before a staleness ramp counts as growth: live
        # runs legitimately climb from 0 toward steady-state pipeline
        # depth at startup (the sample queue still holds early-version
        # experience); a stalled fanout grows one version per update
        # without bound and crosses any depth-scale floor quickly.
        self.staleness_floor = float(get("staleness_floor", 64.0))
        self._queue_streaks: dict[str, int] = {}
        self._counters: dict[str, _Counter] = {}
        self._counter_window = bo["window"]
        # snapshot-to-snapshot derivations (iteration time)
        self._last_t: float | None = None
        self._last_iter: int | None = None
        self.evals = 0
        self.dropped_evals = 0
        self.firings = 0

    # -- snapshot value extraction (pure dict walks) -------------------------
    @staticmethod
    def _find_gauge(snap: dict, key: str):
        for row in (snap.get("tiers") or {}).values():
            for src in (row.get("gauges"), row.get("body")):
                if src and key in src:
                    v = src[key]
                    if isinstance(v, (int, float)):
                        return float(v)
        return None

    def _signal_value(self, name: str, snap: dict):
        spec = self._specs[name]
        if spec[0] == "gauge":
            return self._find_gauge(snap, spec[1])
        if spec[0] == "hop":
            st = (snap.get("hops") or {}).get(spec[1])
            if isinstance(st, dict) and st.get(spec[2]) is not None:
                return float(st[spec[2]])
            return None
        # derived: wall seconds per iteration between snapshots
        t, it = snap.get("t"), snap.get("iteration")
        out = None
        if (t is not None and it is not None
                and self._last_t is not None and self._last_iter is not None
                and int(it) > int(self._last_iter)):
            out = (
                (float(t) - self._last_t)
                / (int(it) - self._last_iter) * 1e3
            )
        if t is not None and it is not None:
            self._last_t, self._last_iter = float(t), int(it)
        return out

    # -- the sweep -----------------------------------------------------------
    def evaluate(self, snap: dict | None) -> list[dict]:
        """One detector sweep over one merged snapshot. Returns the
        firings (possibly empty). Honors the ``watchdog.eval`` chaos
        site: ``drop_eval`` is counted, never silent."""
        if not self.enabled or not snap:
            return []
        spec = faults.fire("watchdog.eval")
        if spec is not None:
            kind = spec.get("kind")
            if kind == "drop_eval":
                self.dropped_evals += 1
                return []
            if kind == "delay":
                faults.sleep_ms(spec)
        self.evals += 1
        firings: list[dict] = []
        tiers = snap.get("tiers") or {}

        # liveness: the aggregator already applied the 3x-cadence rule
        for name, row in sorted(tiers.items()):
            if row.get("dead"):
                firings.append({
                    "detector": "liveness",
                    "signal": name,
                    "tier": base_tier(name),
                    "value": float(row.get("age_s", 0.0)),
                    "baseline": 3.0 * float(row.get("cadence_s", 0.0)),
                    "direction": "high",
                })

        # breakouts
        for det in self._breakouts:
            firing = det.observe(self._signal_value(det.name, snap))
            if firing is not None:
                firings.append(firing)

        # saturation: queue ceilings (sustained 2 windows) + respawn rate
        for key, tier in QUEUE_SIGNALS.items():
            v = self._find_gauge(snap, key)
            if v is not None and v >= self.queue_depth_max:
                self._queue_streaks[key] = self._queue_streaks.get(key, 0) + 1
            else:
                self._queue_streaks[key] = 0
            if self._queue_streaks.get(key, 0) >= 2:
                firings.append({
                    "detector": "saturation",
                    "signal": key,
                    "tier": tier,
                    "value": round(float(v), 2),
                    "baseline": self.queue_depth_max,
                    "direction": "high",
                })
        for key, tier in RESPAWN_COUNTERS.items():
            v = self._find_gauge(snap, key)
            if v is None:
                continue
            deltas = self._counters.setdefault(
                key, _Counter(self._counter_window)
            ).observe(v)
            burst = sum(d for d in deltas if d > 0)
            if burst >= self.respawn_burst:
                firings.append({
                    "detector": "saturation",
                    "signal": key,
                    "tier": tier,
                    "value": burst,
                    "baseline": self.respawn_burst,
                    "direction": "high",
                })

        # monotonic growth: every counted-never-silent failure counter
        # found anywhere in the snapshot, plus the snapshot-level
        # aggregator drop count and the lineage staleness ramp
        growth: dict[str, float] = {}
        for row in tiers.values():
            for src in (row.get("gauges"), row.get("body")):
                for key, v in (src or {}).items():
                    if not isinstance(v, (int, float)):
                        continue
                    k = str(key)
                    if "dropped" in k or "bad_frames" in k:
                        growth[k] = max(growth.get(k, 0.0), float(v))
        if snap.get("bad_frames") is not None:
            growth["ops/bad_frames"] = max(
                growth.get("ops/bad_frames", 0.0),
                float(snap["bad_frames"]),
            )
        for key in sorted(growth):
            deltas = self._counters.setdefault(
                key, _Counter(self._counter_window)
            ).observe(growth[key])
            recent = deltas[-self.growth_windows:]
            if (len(recent) >= self.growth_windows
                    and all(d > 0 for d in recent)):
                firings.append({
                    "detector": "growth",
                    "signal": key,
                    "tier": _family_tier(key),
                    "value": growth[key],
                    "baseline": growth[key] - sum(recent),
                    "direction": "high",
                })
        stale = self._find_gauge(snap, "lineage/staleness_p99")
        if stale is not None:
            deltas = self._counters.setdefault(
                "lineage/staleness_p99", _Counter(self._counter_window)
            ).observe(stale)
            recent = deltas[-self.staleness_growth_windows:]
            if (stale > self.staleness_floor
                    and len(recent) >= self.staleness_growth_windows
                    and all(d > 0 for d in recent)):
                firings.append({
                    "detector": "growth",
                    "signal": "lineage/staleness_p99",
                    "tier": "param_fanout",
                    "value": stale,
                    "baseline": stale - sum(recent),
                    "direction": "high",
                })

        self.firings += len(firings)
        for f in firings:
            f["t"] = time.time()
        return firings

    def gauges(self) -> dict[str, float]:
        """The watchdog's own ``ops/*`` counters (GAUGE_REGISTRY
        documents each); merged into the learner's metrics row."""
        return {
            "ops/watchdog_evals": float(self.evals),
            "ops/watchdog_dropped_evals": float(self.dropped_evals),
            "ops/watchdog_firings": float(self.firings),
        }
