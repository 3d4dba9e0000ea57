"""Device milliseconds per iteration owned by the ops of model part
``optimizer``, whatever phase runs them: global-norm clip, Adam and the
parameter apply over 508M float32 parameters and the routers' bias rule, four
times an iteration. As ``optimizer_part_ms`` reads it for
``ppo_lift_joyai_128x128``. From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_optimizer_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "optimizer")
