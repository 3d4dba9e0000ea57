"""Device milliseconds per iteration owned by the Pallas kernels
``decayed_gram`` and ``decayed_gram_bwd`` (the ``name=`` of their
``pl.pallas_call``), every call site summed: the delta rule's decayed Gram
matrices and their cotangents (``ops/delta_rule.py``). From the ``kernels``
table of the phase session's digest (harness/digest_tables.py; the program
reduces its own capture: ``surreal_tpu/session/profile.py``); a program
without the table reads nothing."""

from benchmarks.harness import digest_tables

NAME = "kernel_decayed_gram_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.kernel_ms(run, "decayed_gram", "decayed_gram_bwd")
