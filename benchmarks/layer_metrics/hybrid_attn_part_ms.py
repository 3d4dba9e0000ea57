"""Device milliseconds per iteration owned by the ops of model part
``attn``, whatever phase runs them: the hybrid trunk's window, full and cross attention: blocks of queries in
the learn passes, a ring and the shared cache in the acting scan. As
``attn_part_ms`` reads it for ``ppo_lift_joyai_128x128``. From the ``parts`` split of
the phase session's digest (harness/parts.py; ``surreal_tpu/utils/phases.py``
has the names)."""

from benchmarks.harness import parts

NAME = "hybrid_attn_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "attn")
