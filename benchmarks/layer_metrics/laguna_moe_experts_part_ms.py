"""Device milliseconds per iteration owned by the ops of model part
``moe_experts``, whatever phase runs them: the four routed layers' experts:
the row gather, the 8 held experts' grouped products (every held expert
densely in an acting step), the weighted combine, and the shared expert over
every token. As ``moe_experts_part_ms`` reads it for
``ppo_lift_joyai_128x128``. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names); a
program without the part reads nothing."""

from benchmarks.harness import parts

NAME = "laguna_moe_experts_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_experts")
