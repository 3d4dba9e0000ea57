"""Seconds inside ``select_trainer`` (``main/launch.py``): the driver's
module imported, the env, the learner and the jitted programs built,
nothing traced yet. The ``launch.build`` span of the program's ``launch``
event (harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_build_s"


def read(run):
    return launch_spans.span_s(run, "launch.build")
