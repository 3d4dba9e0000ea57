"""Device milliseconds per iteration owned by the ops of model part
``attn``, whatever phase runs them: layer 4's latent attention without rotary
(one query product, the latent pair, expanded keys and values in the learn
pass, the absorbed decode against ``[16, 1024, 576]`` rows in acting). As
``attn_part_ms`` reads it for ``ppo_lift_joyai_128x128``. From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_attn_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "attn")
