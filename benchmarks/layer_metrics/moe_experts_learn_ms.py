"""Device milliseconds per iteration owned by the ops of model part
``moe_experts`` in every phase but ``collect``, summed: the learn passes'
row gather, grouped products, combine and shared expert (``prepare``'s value
forward and ``sgd``), so that this and ``moe_experts_acting_ms`` sum to the
cell's ``moe_experts`` part. From the ``parts_by_phase`` table of the phase
session's digest (harness/digest_tables.py; the program reduces its own
capture: ``surreal_tpu/session/profile.py``); a program without the table
reads nothing."""

from benchmarks.harness import digest_tables

NAME = "moe_experts_learn_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.part_other_phases_ms(run, "moe_experts", "collect")
