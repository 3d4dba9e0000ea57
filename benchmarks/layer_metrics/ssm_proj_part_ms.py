"""Device milliseconds per iteration owned by the ops of model part
``ssm_proj``, whatever phase runs them: a state-space layer's four products (in, x, dt, out), in the learn passes
and in the acting scan. From the ``parts`` split of
the phase session's digest (harness/parts.py; ``surreal_tpu/utils/phases.py``
has the names)."""

from benchmarks.harness import parts

NAME = "ssm_proj_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "ssm_proj")
