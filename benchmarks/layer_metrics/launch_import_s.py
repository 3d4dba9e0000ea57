"""Seconds from the process's start, as the OS records it, to the entry
point's first call: the interpreter's start and whatever was imported
before the package (``launch.process``: the harness and ``import jax``
here) and the package's own imports up to ``build_config``
(``launch.import``). Spans of the program's ``launch`` event
(harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_import_s"


def read(run):
    return launch_spans.span_s(run, "launch.process", "launch.import")
