"""The selective scan's required bytes over what HBM could move in the
device time of part ``ssm_scan``: the recurrence's inputs read and outputs
written once in every learn-side forward and twice in every backward
(``iteration_cost``'s ``scan_bytes``), over ``ssm_scan_part_ms`` x the HBM
peak (harness/peaks.json): the one roof the table has for a kernel with no
matrix product. Required bytes only (the states a chunk recomputes, the
conv and the gate are time, not work), so the share cannot pass 100; the
scan is bound by the vector unit, and this says how far from memory's roof
that leaves it."""

from benchmarks.harness import parts

NAME = "ssm_scan_hbm_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = parts.part_ms(run, "ssm_scan")
    if not ms or not run.peaks or "scan_bytes" not in run.cost:
        return None
    return 100.0 * run.cost["scan_bytes"] / (
        1e-3 * ms * run.peaks["hbm_bytes_per_s"]
    )
