"""The finer tables of the phase session's digest (``phase_session.record``):
a phase's sub-scopes, a model part's phases, a Pallas kernel's calls summed,
and the count of op events (``surreal_tpu/session/profile.py``: ``subphases``,
``parts_by_phase``, ``kernels``, and each phase's ``ops_per_iter`` and
``short_ops``). A digest without the table or the key (every program before
PR 59, a rehearsal's capture without a device plane, a session that failed)
reads as ``None``; nothing here raises."""

from __future__ import annotations

from benchmarks.harness import phase_session


def table(run, name: str) -> dict | None:
    """Table ``name`` of the run's digest, ``None`` without one."""
    found = ((phase_session.record(run) or {}).get("digest") or {}).get(name)
    return found if isinstance(found, dict) else None


def _at(run, name: str, *keys: str) -> float | None:
    found = table(run, name)
    for key in keys:
        found = found.get(key) if isinstance(found, dict) else None
    return None if found is None or isinstance(found, dict) else float(found)


def subphase_ms(run, phase: str, sub: str) -> float | None:
    """Device ms per iteration of sub-scope ``phase/sub``."""
    return _at(run, "subphases", phase, sub)


def part_phase_ms(run, part: str, phase: str) -> float | None:
    """Device ms per iteration of model part ``part`` inside ``phase``."""
    return _at(run, "parts_by_phase", part, phase)


def part_other_phases_ms(run, part: str, phase: str) -> float | None:
    """The same of ``part`` in every phase but ``phase``, summed."""
    row = (table(run, "parts_by_phase") or {}).get(part)
    if not isinstance(row, dict):
        return None
    return float(sum(ms for p, ms in row.items() if p != phase))


def kernel_ms(run, *kernels: str) -> float | None:
    """Device ms per iteration of the named Pallas kernels, every call
    site summed; ``None`` unless the digest holds each of them."""
    each = [_at(run, "kernels", k, "ms_per_iter") for k in kernels]
    return None if None in each else float(sum(each))


def over_phases(run, *keys: str) -> float | None:
    """The sum over the phases (``unattributed`` with them) of an entry's
    ``keys``: ``ops_per_iter``, or ``short_ops`` and a key of it."""
    each = [_at(run, "phases", p, *keys) for p in table(run, "phases") or {}]
    return None if not each or None in each else float(sum(each))
