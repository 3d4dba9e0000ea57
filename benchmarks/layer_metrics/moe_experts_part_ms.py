"""Device milliseconds per iteration owned by the ops of model part
``moe_experts``, whatever phase runs them: the row gather, the held experts' grouped products, the weighted
combine and the shared expert. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names)."""

from benchmarks.harness import parts

NAME = "moe_experts_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_experts")
