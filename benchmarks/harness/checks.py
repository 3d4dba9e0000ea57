"""What ``correct`` means: every check by name, each true or false, and
the run is correct only if all are. A rehearsal is never correct."""

from __future__ import annotations

import math


def close(got, want, rtol, atol) -> tuple[bool, float]:
    """Whether ``got`` is within ``atol + rtol |want|`` of ``want``
    everywhere, and the largest absolute error (the references' yardstick)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want)
    return bool(np.all(err <= atol + rtol * np.abs(want))), float(err.max())


def return_at_mark(run) -> float | None:
    """Mean ``episode/return`` of the rows within the cell's half-width of
    its env-step mark (rows in which no episode ended carry NaN, by
    design, and are left out). None where no such row has a return."""
    mark = run.cell["learning"]["mark_env_steps"]
    halfwidth = run.cell["learning"]["halfwidth_env_steps"]
    near = [
        s.row.get("episode/return", math.nan) for s in run.stamps
        if abs(s.env_steps - mark) <= halfwidth
    ]
    near = [r for r in near if math.isfinite(r)]
    return sum(near) / len(near) if near else None


def first_return(run) -> float | None:
    for s in run.stamps:
        r = s.row.get("episode/return", math.nan)
        if math.isfinite(r):
            return r
    return None


def bad_rows(stamps) -> int:
    """Cadence windows whose row has a non-finite loss or health value, a
    set non-finite flag, or parameters that did not move."""
    bad = 0
    for s in stamps:
        watched = [
            v for k, v in s.row.items() if k.startswith(("loss/", "health/"))
        ]
        ok = (
            bool(watched)
            and all(math.isfinite(v) for v in watched)
            and s.row.get("health/nonfinite") == 0.0
            and s.row.get("health/update_ratio", 0.0) > 0.0
        )
        bad += not ok
    return bad


def steps_exact(run) -> bool:
    """Env steps advanced by exactly iterations x envs x horizon between
    every pair of stamps."""
    per_iter = int(run.traffic["num_envs"]) * int(run.traffic["horizon"])
    pairs = zip(run.stamps, run.stamps[1:])
    return all(
        b.env_steps - a.env_steps == (b.iteration - a.iteration) * per_iter
        for a, b in pairs
    ) and run.stamps[0].env_steps == run.stamps[0].iteration * per_iter


def evaluate(run) -> dict:
    """name -> bool for every check behind ``correct``."""
    device_events = run.events.get("device", [])
    checks = {
        "device_is_tpu_in_peak_table": bool(run.peaks),
        "device_event_agrees": len(device_events) == 1 and all(
            device_events[0].get(k) == run.device[k]
            for k in ("platform", "kind", "count")
        ),
        "no_compile_in_window": run.compiles_in_window() == 0,
        "rows_finite_and_moving": bad_rows(run.stamps) == 0,
        "env_steps_exact": steps_exact(run),
        "reference_agrees": bool(run.reference.get("ok")),
    }
    learning = run.cell["learning"]
    if learning.get("margin") is not None:
        at_mark, first = return_at_mark(run), first_return(run)
        checks["return_rose"] = (
            at_mark is not None and first is not None
            and at_mark - first >= learning["margin"]
        )
    return checks
