"""Device milliseconds per iteration owned by the ops of model part
``moe_route``, whatever phase runs them: router scores, the biased top-8, the weights and the sort of the held
assignments by expert. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names)."""

from benchmarks.harness import parts

NAME = "moe_route_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_route")
