"""Model FLOP/s utilisation: the operations an iteration requires
(harness/flops.py, from shapes; never XLA's count) times iterations per
second (from the median cadence window, which the profiler's start and
stop do not stretch), over chips times the chip's published bf16 peak
(harness/peaks.json). End-to-end utilisation, not a kernel's roofline."""

NAME = "mfu_pct"
CHIP_ONLY = True  # needs the chip's published peak


def read(run):
    if not run.window or not run.peaks:
        return None
    peak = run.device["count"] * run.peaks["bf16_flops_per_s"]
    return 100.0 * run.cost["flops"] / run.iteration_seconds() / peak
