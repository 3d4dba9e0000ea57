"""Device milliseconds per iteration owned by the ops of model part
``moe_experts`` inside phase ``collect``: the acting steps' routed layers
(the held experts' dense form or the live experts' kernel, the combine and
the shared expert). From the ``parts_by_phase`` table of the phase session's
digest (harness/digest_tables.py; the program reduces its own capture:
``surreal_tpu/session/profile.py``); a program without the table reads
nothing."""

from benchmarks.harness import digest_tables

NAME = "moe_experts_acting_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.part_phase_ms(run, "moe_experts", "collect")
