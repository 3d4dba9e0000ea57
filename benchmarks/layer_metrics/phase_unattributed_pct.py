"""Share of the device's busy time, in the phase session's capture, owned by
ops outside every phase of ``surreal_tpu/utils/phases.py``: what the phase
split cannot name (harness/phase_session.py)."""

from benchmarks.harness import phase_session

NAME = "phase_unattributed_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    entry = (phase_session.record(run) or {}).get("digest", {}).get(
        "phases", {}
    ).get("unattributed")
    return None if entry is None else 100.0 * float(entry["share_of_busy"])
