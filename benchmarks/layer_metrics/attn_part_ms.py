"""Device milliseconds per iteration owned by the ops of model part
``attn``, whatever phase runs them: latent attention, expanded in the learn passes and absorbed against the
latent cache in the acting scan. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names)."""

from benchmarks.harness import parts

NAME = "attn_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "attn")
