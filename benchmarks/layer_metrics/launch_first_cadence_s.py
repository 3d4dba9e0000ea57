"""Seconds from the end of the first dispatch to the end of the first
``metrics-sync``: the first cadence of iterations on the device, fenced by
the sync's ``float()``. The ``launch.first_cadence`` span of the program's
``launch`` event (harness/launch_spans.py), closed by
``SessionHooks.end_iteration``."""

from benchmarks.harness import launch_spans

NAME = "launch_first_cadence_s"


def read(run):
    return launch_spans.span_s(run, "launch.first_cadence")
