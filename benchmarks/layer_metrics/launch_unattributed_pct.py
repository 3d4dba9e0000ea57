"""Share of the launch, in percent, that no span of the program covers:
``unattributed_s`` over ``total_s`` of the program's ``launch`` event
(harness/launch_spans.py), the gaps between the ten spans from the
process's start to the end of the first ``metrics-sync``."""

from benchmarks.harness import launch_spans

NAME = "launch_unattributed_pct"


def read(run):
    ev = launch_spans.event(run)
    if ev is None or not ev["total_s"] > 0.0:
        return None
    return 100.0 * float(ev["unattributed_s"]) / float(ev["total_s"])
