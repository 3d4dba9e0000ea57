"""Seconds inside ``SessionHooks.record_program_costs`` while the launch is
open: the fused iteration traced and lowered for the cost record, and
compiled or read from the cache where its memory is asked for
(``session/costs.py``). The ``launch.cost_record`` span of the program's
``launch`` event (harness/launch_spans.py); its own counters say how much
of it was the lowering and how much the cache's read."""

from benchmarks.harness import launch_spans

NAME = "launch_cost_record_s"


def read(run):
    return launch_spans.span_s(run, "launch.cost_record")
