"""``device_rollout`` alone at the cell's per-chip shapes, fenced
(harness/standalone.py). Only where the algorithm collects through it."""

NAME = "rollout_ms"


def read(run):
    s = run.standalone.get("rollout_s")
    return None if s is None else 1e3 * s
