"""Device milliseconds per iteration owned by the ops of model part
``gmu``, whatever phase runs them: a gated memory unit: both products and the gate over the middle
state-space layer's memory. From the ``parts`` split of
the phase session's digest (harness/parts.py; ``surreal_tpu/utils/phases.py``
has the names)."""

from benchmarks.harness import parts

NAME = "gmu_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "gmu")
