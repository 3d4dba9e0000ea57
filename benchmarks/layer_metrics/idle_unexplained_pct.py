"""Share of the device's idle time, in the phase session's capture, that no
span of the program covers on the loop's thread (``idle_by_span.none``
over ``idle_s``): the idle time still without a host cause. 0 where the
device never idled (harness/phase_session.py)."""

from benchmarks.harness import phase_session

NAME = "idle_unexplained_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    digest = (phase_session.record(run) or {}).get("digest", {})
    if "idle_by_span" not in digest:
        return None
    idle = float(digest["idle_s"])
    if idle <= 0.0:
        return 0.0
    return 100.0 * float(digest["idle_by_span"].get("none", 0.0)) / idle
