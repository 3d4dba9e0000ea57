"""Seconds the backend spent compiling in this process, from JAX's own
compile-duration events. Near zero where the persistent cache holds every
program; a cold checkout shows here."""

NAME = "compile_s"


def read(run):
    end = run.window_t0
    return sum(d for t, d in run.compiles if t <= end)
