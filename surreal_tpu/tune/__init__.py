"""Program autotuner — measured scan-unroll and program-geometry search
with a persistent per-workload tuning cache.

PERF.md's attribution says every graded workload is LATENCY-BOUND on long
``lax.scan``s of tiny elementwise ops (headline PPO: 0.64% MFU, rollout
25.1 of 30.8 ms/iter), yet the repo's scan-unroll factors and geometry
choices (``gae_impl``, minibatch shuffle layout, update-loop shape) were
hand-set defaults. Accelerated-RL systems SEARCH these knobs instead of
guessing (Stooke & Abbeel, *Accelerated Methods for Deep RL*, 1803.02811;
HEPPO-GAE's hardware-shaped GAE pipeline) — and PR 2's persistent XLA
compile cache makes the search's extra compiles a once-per-fingerprint
cost, so measuring-and-picking is now cheaper than shipping one static
guess.

Three layers, mirroring the compile cache's design:

- :mod:`fingerprint` — a workload fingerprint (algo + model + geometry +
  backend + jax version, MINUS the searched knobs themselves) keys every
  cache entry, so a tuned config can never leak onto a workload it was
  not measured on.
- :mod:`cache` — a JSON tuning cache beside the compile cache
  (``session.tuning_cache_dir``; relative paths resolve under the session
  folder, absolute paths share one cache across sessions). Atomic writes;
  corrupt/missing entries read as misses.
- :mod:`search` (+ :mod:`space`) — greedy coordinate descent over the
  declared candidate space (rollout-scan ``unroll``, SGD/update-loop
  ``unroll``, ``gae_impl`` incl. the pallas kernel, shuffle layout), each
  candidate timed over a fenced window of chained iterations (warm-up
  calls first, ``block_until_ready`` on the last outputs) through the
  REAL fused trainer program.

Trainers consult the cache at build time via ``algo.autotune``:

- ``'off'``   (default) — hand-set knobs, no cache traffic;
- ``'cache'`` — apply a cached winner when the fingerprint hits, fall
  back to the static defaults on a miss (never pays search cost);
- ``'search'``— on a miss, run the search at build time and persist the
  winner. Device (``jax:*``) envs search the full space against the
  fused iteration; host envs (gym/dm_control/SEED) search the
  learn-phase subset against the jitted learn program alone
  (search.LEARN_PHASE_DIMS — their rollout is host python with no scan
  to unroll); workloads with nothing searchable keep defaults.

The decision lands in telemetry as a ``tune`` event (hit/miss, chosen
config, candidate timings from the search), rendered by
``surreal_tpu diag``; ``python -m surreal_tpu tune <algo> <env>`` runs
the search standalone and writes the shared artifact.
"""

from __future__ import annotations

from typing import NamedTuple

from surreal_tpu.tune.cache import TuningCache, resolve_tuning_cache_dir
from surreal_tpu.tune.fingerprint import TUNABLE_KEYS, workload_fingerprint

AUTOTUNE_MODES = ("off", "cache", "search")


class TuneDecision(NamedTuple):
    """What the autotuner decided at trainer build time."""

    mode: str             # 'off' | 'cache' | 'search'
    key: str | None       # workload fingerprint key (None when off)
    hit: bool | None      # cache hit (None when off)
    applied: dict         # tuned knobs merged into the learner config
    source: str           # 'default' | 'cache' | 'search'
    cache_dir: str | None
    note: str = ""        # e.g. search degraded to cache for a host env

    def telemetry(self) -> dict:
        """The ``tune`` event payload (hooks.tune_event / diag)."""
        out = {
            "mode": self.mode,
            "key": self.key,
            "hit": bool(self.hit),
            "source": self.source,
            "cache_dir": self.cache_dir,
            "config": dict(self.applied),
        }
        if self.note:
            out["note"] = self.note
        return out

    def artifact(self) -> dict:
        """Compact record for bench/wallclock artifacts, so a perf row can
        never silently mix tuned and untuned arms."""
        return {
            "mode": self.mode,
            "hit": self.hit,
            "source": self.source,
            "config": dict(self.applied),
            "key": self.key,
        }


_OFF = TuneDecision(
    mode="off", key=None, hit=None, applied={}, source="default",
    cache_dir=None,
)


def _apply_tuned(config, tuned: dict) -> None:
    """Merge tuned knobs into the RAW learner override tree (the one
    ``build_learner`` extends), so a rebuild picks them up. Tuned values
    deliberately override hand-set ones: ``autotune != 'off'`` hands the
    searched keys to the tuner; pin them manually with ``autotune='off'``.
    """
    from surreal_tpu.session.config import Config

    algo = config.learner_config.get("algo", None)
    if algo is None:
        config.learner_config.algo = Config()
        algo = config.learner_config.algo
    for k, v in tuned.items():
        algo[k] = v


def resolve_autotune(config, extended_learner_config) -> TuneDecision:
    """Consult (or populate) the tuning cache for this workload; called by
    every trainer constructor BEFORE its jitted programs are built.

    ``extended_learner_config`` is the fully-extended learner tree (the
    built learner's ``.config``) — the raw user tree lacks the defaults
    the fingerprint needs. On a decision with ``applied`` non-empty the
    caller rebuilds its learner from ``config.learner_config``, which this
    function has updated in place.
    """
    algo = extended_learner_config.algo
    mode = algo.get("autotune", "off") or "off"
    if mode not in AUTOTUNE_MODES:
        raise ValueError(
            f"algo.autotune {mode!r} not in {'|'.join(AUTOTUNE_MODES)}"
        )
    if mode == "off":
        return _OFF

    key, _fp = workload_fingerprint(extended_learner_config, config.env_config)
    cache_dir = resolve_tuning_cache_dir(config.session_config)
    cache = TuningCache(cache_dir)
    entry = cache.lookup(key)
    if entry is not None:
        tuned = dict(entry.get("config", {}))
        _apply_tuned(config, tuned)
        return TuneDecision(mode, key, True, tuned, "cache", cache_dir)
    if mode == "cache":
        return TuneDecision(mode, key, False, {}, "default", cache_dir)

    # mode == 'search': run the measurement at build time and persist.
    from surreal_tpu.tune.search import search_space_for

    if not search_space_for(config, extended_learner_config):
        # e.g. host-env DDPG: the update loop runs as individual jitted
        # learns from a host loop — no searchable dimension exists
        return TuneDecision(
            mode, key, False, {}, "default", cache_dir,
            note="no searchable dimensions for this workload; "
                 "static defaults kept",
        )
    import jax

    if jax.process_count() > 1:
        # ranks would each measure with independent timing noise and pick
        # DIVERGENT programs — a collective deadlock. The cache path is
        # deterministic across ranks (same shared file), so require it.
        raise ValueError(
            "algo.autotune='search' is single-process only (per-rank "
            "timing noise would pick divergent programs): run "
            "`surreal_tpu tune` once against the shared tuning cache, "
            "then train with algo.autotune='cache'"
        )
    from surreal_tpu.tune.search import tune_workload

    result = tune_workload(config)
    tuned = dict(result.get("config", {}))
    _apply_tuned(config, tuned)
    return TuneDecision(mode, key, False, tuned, "search", cache_dir)


__all__ = [
    "AUTOTUNE_MODES",
    "TUNABLE_KEYS",
    "TuneDecision",
    "TuningCache",
    "resolve_autotune",
    "resolve_tuning_cache_dir",
    "workload_fingerprint",
]
