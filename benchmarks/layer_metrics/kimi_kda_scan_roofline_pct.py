"""The delta rule's required work over what the chip could do in the device
time of part ``kda_scan``, which runs in acting and in the learn passes
alike: the larger of two floors on that time. One is the rule's products as
its equation reads (three ``128 x 128`` products a head a token and the
convs' taps: ``ppo_kimilinear_ref.iteration_cost``'s ``scan_flops``) over the
bf16 peak. The other is ``scan_bytes`` over the HBM peak (harness/peaks.json):
the rule's inputs read and output written once in every forward and twice in
every backward (73 856 bytes a token a layer: 38.7 GB an iteration), and the
four float32 matrix states and their conv tails read and written by each of
the 1024 acting steps (278 MB a step, 284.5 GB an iteration: a step cannot
keep 134 MB of state on the chip). At these shapes the bytes bound is the
higher by far, 395 ms an iteration against 9 of the matrix unit, so the share
says how far from memory's roof the part runs. Required counts only (a
chunk's recomputed products, its triangular system, the pairwise decays, the
norms and the gates are time, not work), so the share cannot pass 100."""

from benchmarks.harness import parts

NAME = "kimi_kda_scan_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = parts.part_ms(run, "kda_scan")
    cost = run.cost
    if not ms or not run.peaks or "scan_flops" not in cost:
        return None
    floor_s = max(
        cost["scan_flops"] / run.peaks["bf16_flops_per_s"],
        cost["scan_bytes"] / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * floor_s / (1e-3 * ms)
