"""Median host time to dispatch one iteration (the loop engine's
``step_ms`` in the run's last ``engine`` event). Matters end to end only
where the device is idle for it: read beside the idle share."""

NAME = "dispatch_ms_p50"


def read(run):
    return run.engine_p50("step_ms")
