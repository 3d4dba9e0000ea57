"""Device milliseconds per iteration owned by the ops of model part
``dense_ffn``, whatever phase runs them: the SwiGLU of every layer, with the LayerNorm before it. From the ``parts`` split of
the phase session's digest (harness/parts.py; ``surreal_tpu/utils/phases.py``
has the names)."""

from benchmarks.harness import parts

NAME = "dense_ffn_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "dense_ffn")
