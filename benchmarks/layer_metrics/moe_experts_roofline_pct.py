"""The expert products' required operations over what the chip could do
in the device time of part ``moe_experts``: (held assignments x one
expert's forward, from the run's own ``moe/held_share`` and the
reference's ``iteration_cost``, plus the shared expert for every token) x
the iteration's forward equivalents, over ``moe_experts_part_ms`` x the
bf16 peak (harness/peaks.json). Required counts only: rows of the sorted
buffer beyond the assignments, the gather and the combine are time, not
work, so the share cannot pass 100."""

from benchmarks.harness import parts

NAME = "moe_experts_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = parts.part_ms(run, "moe_experts")
    share = parts.last_row(run, "moe/held_share")
    if not ms or share is None or not run.peaks:
        return None
    cost, widths = run.cost, run.config["widths"]
    routed_layers = int(widths["num_hidden_layers"]) - int(
        widths["first_k_dense_replace"]
    )
    assignments = (
        share * cost["samples"] * int(widths["num_experts_per_tok"])
        * routed_layers
    )
    shared = cost["samples"] * routed_layers * int(widths["n_shared_experts"])
    flops = (
        (assignments + shared) * cost["expert_flops_per_assignment"]
        * cost["forward_equivalents"]
    )
    return 100.0 * flops / (1e-3 * ms * run.peaks["bf16_flops_per_s"])
