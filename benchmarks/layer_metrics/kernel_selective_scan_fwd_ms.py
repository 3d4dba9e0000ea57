"""Device milliseconds per iteration owned by the Pallas kernel
``selective_scan_fwd`` (the ``name=`` of its ``pl.pallas_call``), every call
site summed: the selective scan's forward chunk walk
(``ops/selective_scan.py``). From the ``kernels`` table of the phase
session's digest (harness/digest_tables.py; the program reduces its own
capture: ``surreal_tpu/session/profile.py``); a program without the table
reads nothing."""

from benchmarks.harness import digest_tables

NAME = "kernel_selective_scan_fwd_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.kernel_ms(run, "selective_scan_fwd")
