"""What the ``launch_*`` readers share: the measured session's ``launch``
telemetry event (``surreal_tpu/session/telemetry.py``), the spans the
program itself recorded from the process's start to the end of the first
``metrics-sync``, each with the compiler's seconds that fell inside it.
``benchmarks/LAUNCH.md`` lists the readers. A program that records no
launch (every one before PR 41) writes no such event: the readers get
``None``."""

from __future__ import annotations


def event(run) -> dict | None:
    """The measured session's ``launch`` event: the first of its folder
    (the folder is fresh, and a session writes one)."""
    events = run.events.get("launch")
    return events[0] if events else None


def span_s(run, *names: str) -> float | None:
    """Seconds of the spans called one of ``names``; spans of one name
    add up."""
    ev = event(run)
    if ev is None:
        return None
    return float(sum(
        s["end_s"] - s["start_s"] for s in ev["spans"] if s["name"] in names
    ))


def counter_s(run, *counters: str) -> float | None:
    """The sum, over every span, of ``counters``: seconds of JAX's own
    timing events that fired while the span was the innermost open one."""
    ev = event(run)
    if ev is None:
        return None
    return float(sum(s.get(c, 0.0) for s in ev["spans"] for c in counters))
