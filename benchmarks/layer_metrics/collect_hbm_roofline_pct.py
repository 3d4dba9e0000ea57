"""The acting scan's required bytes over what HBM could move in the
device time of phase ``collect``: the bfloat16 weights once a step, the
latent cache read up to the step's position and one row written
(``iteration_cost``'s ``collect_bytes``), over ``phase_collect_ms`` x the
HBM peak (harness/peaks.json). Required bytes only (float32 weights
re-read, re-cast copies and the env's own traffic are time, not work),
so the share cannot pass 100."""

from benchmarks.harness import phase_session

NAME = "collect_hbm_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = phase_session.phase_ms(run, "collect")
    if not ms or not run.peaks or "collect_bytes" not in run.cost:
        return None
    return 100.0 * run.cost["collect_bytes"] / (
        1e-3 * ms * run.peaks["hbm_bytes_per_s"]
    )
