"""Device milliseconds per iteration owned by the ops of model part
``kda_proj``, whatever phase runs them: the four Kimi Delta Attention layers'
products: q, k, v, the decay's and the gate's low-rank pairs, ``beta`` and
the output projection (39.46M parameters a layer). From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_kda_proj_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "kda_proj")
