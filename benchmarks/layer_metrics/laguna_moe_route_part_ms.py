"""Device milliseconds per iteration owned by the ops of model part
``moe_route``, whatever phase runs them: the four routed layers' routing:
the router's float32 product over 256 outputs, the softmax, the top 10, the
weights, and in a learn pass the sort by expert into 5120 rows. As
``moe_route_part_ms`` reads it for ``ppo_lift_joyai_128x128``, whose list
may not be edited. From the ``parts`` split of the phase session's digest
(harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names); a program
without the part reads nothing."""

from benchmarks.harness import parts

NAME = "laguna_moe_route_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_route")
