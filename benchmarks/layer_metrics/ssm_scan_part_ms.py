"""Device milliseconds per iteration owned by the ops of model part
``ssm_scan``, whatever phase runs them: a state-space layer's conv, ``softplus``, the selective scan (chunked in
the learn passes, one position against the carried state in the acting
scan), the skip and the gate. From the ``parts`` split of
the phase session's digest (harness/parts.py; ``surreal_tpu/utils/phases.py``
has the names)."""

from benchmarks.harness import parts

NAME = "ssm_scan_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "ssm_scan")
