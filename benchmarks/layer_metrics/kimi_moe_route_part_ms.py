"""Device milliseconds per iteration owned by the ops of model part
``moe_route``, whatever phase runs them: the four routed layers' routing:
the router's float32 product over 256 outputs, the sigmoid, the 8 largest of
score + selection bias, the renormalised weights, and in a learn pass the
sort by expert into the rows the held experts compute. As
``moe_route_part_ms`` reads it for ``ppo_lift_joyai_128x128`` and
``laguna_moe_route_part_ms`` for ``ppo_lift_laguna_16x1024``, whose lists may
not be edited. From the ``parts`` split of the phase session's digest
(harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names); a program
without the part reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_moe_route_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_route")
