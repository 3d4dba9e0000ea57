"""The acting scan's required bytes over what HBM could move in the
device time of phase ``collect``: the bfloat16 weights once a step, the
ring and the shared cache read to the step's reach, the states read and
written (``iteration_cost``'s ``collect_bytes``), over ``phase_collect_ms``
x the HBM peak (harness/peaks.json). Required bytes only, so the share
cannot pass 100. As ``collect_hbm_roofline_pct`` reads it for
``ppo_lift_joyai_128x128``."""

from benchmarks.harness import phase_session

NAME = "collect_stream_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = phase_session.phase_ms(run, "collect")
    if not ms or not run.peaks or "collect_bytes" not in run.cost:
        return None
    return 100.0 * run.cost["collect_bytes"] / (
        1e-3 * ms * run.peaks["hbm_bytes_per_s"]
    )
