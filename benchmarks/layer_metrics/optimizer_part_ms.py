"""Device milliseconds per iteration owned by the ops of model part
``optimizer``, whatever phase runs them: global-norm clip, Adam, the parameter apply and the router-bias rule. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names)."""

from benchmarks.harness import parts

NAME = "optimizer_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "optimizer")
