"""The acting scan's required bytes over what HBM could move in the device
time of phase ``collect``: the bfloat16 weights once a step, the four matrix
states (33.5 MB each) and their conv tails read and written, the latent cache
read to the step's reach and a row written
(``ppo_kimilinear_ref.iteration_cost``'s ``collect_bytes``), over
``phase_collect_ms`` x the HBM peak (harness/peaks.json). Required bytes only,
so the share cannot pass 100. As ``laguna_collect_stream_roofline_pct`` reads
it for ``ppo_lift_laguna_16x1024``, whose list may not be edited."""

from benchmarks.harness import phase_session

NAME = "kimi_collect_stream_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = phase_session.phase_ms(run, "collect")
    if not ms or not run.peaks or "collect_bytes" not in run.cost:
        return None
    return 100.0 * run.cost["collect_bytes"] / (
        1e-3 * ms * run.peaks["hbm_bytes_per_s"]
    )
