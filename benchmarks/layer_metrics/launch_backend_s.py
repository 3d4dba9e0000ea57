"""Seconds inside ``_apply_backend`` and ``_require_platform``
(``main/launch.py``): the compile cache's set-up and the TPU client's
start, the runtime's own and one opaque span. The ``launch.backend`` spans
of the program's ``launch`` event (harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_backend_s"


def read(run):
    return launch_spans.span_s(run, "launch.backend")
