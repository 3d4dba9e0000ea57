"""Device milliseconds per iteration owned by the Pallas kernel
``held_experts_live`` (the ``name=`` of its ``pl.pallas_call``), every call
site summed: the acting step's held experts, read only where a token chose
them (``ops/moe.py``). From the ``kernels`` table of the phase session's
digest (harness/digest_tables.py; the program reduces its own capture:
``surreal_tpu/session/profile.py``); a program without the table reads
nothing."""

from benchmarks.harness import digest_tables

NAME = "kernel_held_experts_live_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.kernel_ms(run, "held_experts_live")
