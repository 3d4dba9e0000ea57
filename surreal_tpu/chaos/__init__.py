"""Randomized chaos campaigns (ISSUE 20): seeded multi-site fault
schedules drawn over the ``utils/faults.py`` registry, run-wide invariant
oracles evaluated from a finished run's telemetry/artifacts, and a greedy
schedule shrinker that reduces any failing schedule to minimal form.

- :mod:`surreal_tpu.chaos.schedule` — the deterministic generator
- :mod:`surreal_tpu.chaos.invariants` — the post-run oracles
- :mod:`surreal_tpu.chaos.campaign` — N seeded real runs + shrinking

CLI: ``surreal_tpu chaos <algo> <env> --seeds N`` (``--out`` writes the
campaign's record where the operator asks).
"""

from surreal_tpu.chaos.schedule import PROFILES, generate_schedule
from surreal_tpu.chaos.invariants import ORACLES, RunRecord, evaluate
from surreal_tpu.chaos.campaign import run_campaign, shrink

__all__ = [
    "PROFILES",
    "generate_schedule",
    "ORACLES",
    "RunRecord",
    "evaluate",
    "run_campaign",
    "shrink",
]
