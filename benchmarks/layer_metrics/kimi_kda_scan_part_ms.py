"""Device milliseconds per iteration owned by the ops of model part
``kda_scan``, whatever phase runs them: the four Kimi Delta Attention layers'
convs, SiLU, L2 norms, the decay's ``softplus`` and ``exp``, the chunked delta
rule of ``ops/delta_rule.py`` (its triangular systems and products; a step of
it on the matrix state in acting), the output norm and gate. From the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "kimi_kda_scan_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "kda_scan")
