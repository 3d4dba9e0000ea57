"""Seconds the launch spent tracing functions to jaxprs and lowering them to
MLIR, by JAX's own timing events (``jaxpr_trace_duration``, a function
traced inside another's trace counted once, and
``jaxpr_to_mlir_module_duration``): the sum of ``trace_s`` and ``lower_s``
over the spans of the program's ``launch`` event
(harness/launch_spans.py; ``utils/compat.py`` counts them)."""

from benchmarks.harness import launch_spans

NAME = "launch_lower_s"


def read(run):
    return launch_spans.counter_s(run, "trace_s", "lower_s")
