"""The expert products' required operations over what the chip could do in
the device time of part ``moe_experts``: (the held assignments, from the run's
own ``moe/held_share``, x one expert's forward + the shared expert's for every
token) x the routed layers x the iteration's forward equivalents
(``ppo_kimilinear_ref.iteration_cost``), over ``kimi_moe_experts_part_ms`` x
the bf16 peak (harness/peaks.json). Required counts only: rows of the sorted
buffer beyond the assignments, the held experts an acting step runs on tokens
that did not choose them, the gather and the combine are time, not work, so
the share cannot pass 100. As ``laguna_moe_experts_roofline_pct`` reads it for
``ppo_lift_laguna_16x1024``."""

from benchmarks.harness import parts

NAME = "kimi_moe_experts_roofline_pct"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    ms = parts.part_ms(run, "moe_experts")
    share = parts.last_row(run, "moe/held_share")
    cost = run.cost
    if not ms or share is None or not run.peaks or "routed_layers" not in cost:
        return None
    widths = run.config["widths"]
    per_token = (
        share * int(widths["num_experts_per_token"])
        * cost["expert_flops_per_assignment"] + cost["shared_flops_per_token"]
    )
    flops = (
        per_token * cost["samples"] * cost["routed_layers"]
        * cost["forward_equivalents"]
    )
    return 100.0 * flops / (1e-3 * ms * run.peaks["bf16_flops_per_s"])
