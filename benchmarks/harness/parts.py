"""The model-part split of a cell, from the same digest the phase readers
use (``phase_session.record``): device milliseconds per iteration owned by
the ops whose path names a part of ``surreal_tpu/utils/phases.py``
(``PARTS``). A program without parts (every one before PR 33, and every
model that scopes none) has no such split: the readers get ``None``."""

from __future__ import annotations

from benchmarks.harness import phase_session


def part_ms(run, name: str) -> float | None:
    rec = phase_session.record(run)
    entry = (rec or {}).get("digest", {}).get("parts", {}).get(name)
    return None if entry is None else float(entry["ms_per_iter"])


def last_row(run, key: str) -> float | None:
    """A counter of the window's last metrics row, ``None`` without it."""
    if not run.window:
        return None
    value = run.window[-1].row.get(key)
    return None if value is None else float(value)
