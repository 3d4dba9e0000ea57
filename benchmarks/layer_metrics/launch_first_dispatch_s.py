"""Seconds of the loop engine's first step (``engine/core.py``): the first
call of the jitted iteration, with whatever trace, lowering, cache look-up
and executable load the cost record left it to do. The
``launch.first_dispatch`` span of the program's ``launch`` event
(harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_first_dispatch_s"


def read(run):
    return launch_spans.span_s(run, "launch.first_dispatch")
