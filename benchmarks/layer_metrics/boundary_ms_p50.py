"""Median host time of one iteration boundary (hooks, cadences, the
engine's own row): ``stage_ms`` in the run's last ``engine`` event."""

NAME = "boundary_ms_p50"


def read(run):
    return run.engine_p50("stage_ms")
