"""Milliseconds per iteration by the program's own fenced span: the
``cadence`` phase (end of one metrics sync to the end of the next, over
its iterations) of the MEASURED run's ``phases`` telemetry events. Only
the cadences that lie wholly inside the window count (the one that ends at
the window's first stamp began at the window's start, not at a sync's end), and of those
the low median: in a traced run of three cadences the profiler's stop
stretches one of the two that remain. Beside ``iter_ms_p50``, which the
harness's stamps give."""

import statistics

NAME = "fenced_iter_ms"


def read(run):
    if not run.window:
        return None
    first, last = run.window[0].env_steps, run.window[-1].env_steps
    per_iteration = [
        ev["phases"]["cadence"]["total_s"] / ev["phases"]["cadence"]["count"]
        for ev in run.events.get("phases", [])
        if first < ev.get("step", -1) <= last
        and ev.get("phases", {}).get("cadence", {}).get("count", 0) > 0
    ]
    return 1e3 * statistics.median_low(per_iteration) if per_iteration else None
