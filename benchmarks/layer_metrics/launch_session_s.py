"""Seconds inside ``SessionHooks.__init__``, ``restore`` and ``begin_run``
(``launch/hooks.py``): the folder, the writers, the tracer, the ops plane,
the watchdog, a checkpoint manager where checkpointing is on (and orbax's
import with it). The ``launch.session`` spans of the program's ``launch``
event (harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_session_s"


def read(run):
    return launch_spans.span_s(run, "launch.session")
