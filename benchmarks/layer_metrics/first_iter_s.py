"""Seconds from process start to the end of the first iteration: config,
trainer build, tracing, lowering, cache retrieval or compile. ``launch_s``
minus the nine steady iterations that follow inside the first cadence."""

NAME = "first_iter_s"


def read(run):
    if not run.window or not run.stamps:
        return None
    return run.launch_s - (run.stamps[0].iteration - 1) * run.iteration_seconds()
