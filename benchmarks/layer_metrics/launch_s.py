"""Seconds from process start (taken at the top of ``run.py``, before JAX
loads) to the first fenced stamp: imports, config, trainer build, tracing,
lowering, cache retrieval or compile, and the first cadence of iterations.
What every (re)launch waits. Not gated end to end: on the chip host it
spreads by several percent from run to run (PERF.md section 2)."""

NAME = "launch_s"


def read(run):
    return run.launch_s
