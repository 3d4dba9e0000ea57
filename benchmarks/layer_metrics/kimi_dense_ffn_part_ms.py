"""Device milliseconds per iteration owned by the ops of model part
``dense_ffn``, whatever phase runs them: layer 1's SwiGLU of 9216. As
``dense_ffn_part_ms`` reads it for ``ppo_lift_phi4flash_16x1024``.
From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_dense_ffn_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "dense_ffn")
