"""Device milliseconds per iteration owned by the ops of model part
``moe_experts``, whatever phase runs them: the four routed layers' row gather,
the 8 held experts' grouped products over 8192 sorted rows (the dense form in
acting), the weighted combine and the shared expert. As
``laguna_moe_experts_part_ms`` reads it for ``ppo_lift_laguna_16x1024``.
From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_moe_experts_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "moe_experts")
