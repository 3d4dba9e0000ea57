"""Seconds the launch spent reading executables from the persistent compile
cache, by JAX's own ``cache_retrieval_time_sec`` events: the sum of
``cache_read_s`` over the spans of the program's ``launch`` event
(harness/launch_spans.py; ``utils/compat.py`` counts them). Part of each
span's ``compile_s`` too, which is the backend's compile-or-read."""

from benchmarks.harness import launch_spans

NAME = "launch_cache_read_s"


def read(run):
    return launch_spans.counter_s(run, "cache_read_s")
