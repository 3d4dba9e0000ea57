"""The phase split of a cell, asked of the program as an operator would.

``runner.py`` removes the measured window's trace before any reader runs
and ``trace_reduce.py`` reads op names only, so no reader can split that
trace by phase (PERF.md, open questions). What a reader can see is the
program's own telemetry. So, after the measured run and in the same
process, this builds the cell's config again (``runner.train_argv``) into a
fresh folder ``<run.folder>_phases`` and trains through
``select_trainer(cfg).run(on_metrics=cb)``. ``cb`` waits until the ring is
full (``runner.ring_full``), drops the trigger file that ``surreal_tpu
profile <folder>`` writes, and ends the run at the first cadence at which
the capture has been written. The record is that session's ``profile``
telemetry event, whose ``digest`` the program reduced itself
(``surreal_tpu/session/profile.py``).

One session per ``Run``, shared by all readers; nothing of it in a
``--trace 0`` run. It never raises into ``run.py``: on any failure, or on
a program that has no digest, the readers get ``None`` and the reason goes
to standard error. A rehearsal walks the same path; the CPU has no device
plane, so its digest holds host spans and no phases.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

CAPTURE_ITERATIONS = 3   # whole iterations in the capture
# without a capture on disk, the session ends this long after the trigger:
# both must pass (the program looks for the trigger once a second, which at
# toy sizes is many cadences)
GIVE_UP_CADENCES = 8
GIVE_UP_SECONDS = 10.0


def _say(reason: str) -> None:
    print(f"benchmarks/harness/phase_session.py: {reason}", file=sys.stderr)


def _session(run) -> dict | None:
    from benchmarks.harness import runner
    from surreal_tpu.main import launch
    from surreal_tpu.session import profile
    from surreal_tpu.session.telemetry import PROFILES_DIR, TELEMETRY_DIR

    if not hasattr(profile, "digest_capture"):
        _say("this program's profiler captures only: no digest to read")
        return None
    folder = run.folder + "_phases"
    shutil.rmtree(folder, ignore_errors=True)
    argv = runner.train_argv(
        run.config, run.cell, folder, run.seed, run.rehearse
    )
    cfg = launch.build_config(launch.build_parser().parse_args(argv))
    captures = os.path.join(folder, TELEMETRY_DIR, PROFILES_DIR)
    triggered = None  # (cadences seen, host clock) at the trigger
    cadences = 0

    def cb(iteration: int, row: dict) -> bool:
        nonlocal triggered, cadences
        cadences += 1
        if triggered is None:
            if runner.ring_full(row):
                profile.write_trigger(folder, num_iters=CAPTURE_ITERATIONS)
                triggered = (cadences, time.monotonic())
            return False
        written = glob.glob(
            os.path.join(captures, "*", "plugins", "profile", "*", "*.xplane.pb")
        )
        return bool(written) or (
            cadences - triggered[0] >= GIVE_UP_CADENCES
            and time.monotonic() - triggered[1] >= GIVE_UP_SECONDS
        )

    try:
        launch.select_trainer(cfg).run(on_metrics=cb)
        events = runner.read_events(folder).get("profile", [])
    finally:
        # tens of MB a capture; the digest is kept in the event
        shutil.rmtree(captures, ignore_errors=True)
    if not events:
        _say(
            f"no capture within {GIVE_UP_CADENCES} cadences and "
            f"{GIVE_UP_SECONDS:.0f} s of the trigger"
        )
        return None
    if "digest" not in events[-1]:
        _say(f"capture without a digest: {events[-1].get('digest_error')}")
        return None
    return events[-1]


def record(run) -> dict | None:
    """The phase session's ``profile`` event for ``run``, or ``None``;
    the session runs at the first call only."""
    if not hasattr(run, "_phase_session"):
        try:
            run._phase_session = _session(run)
        except Exception as e:  # a reader never raises into run.py
            _say(f"session failed: {type(e).__name__}: {e}")
            run._phase_session = None
    return run._phase_session


def phase_ms(run, name: str) -> float | None:
    """Device milliseconds per iteration the ops of phase ``name`` own in
    the digest (``unattributed`` for the ops outside every phase)."""
    rec = record(run)
    entry = (rec or {}).get("digest", {}).get("phases", {}).get(name)
    return None if entry is None else float(entry["ms_per_iter"])
