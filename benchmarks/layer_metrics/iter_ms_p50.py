"""Milliseconds per fused iteration: the median cadence window of the
measured window over its iterations, on the host clock between fenced
stamps."""

NAME = "iter_ms_p50"


def read(run):
    if not run.window:
        return None
    return 1e3 * run.iteration_seconds()
