"""Device op events per iteration on the first device, summed over the phases
and ``unattributed``: how many ops the fused program runs, where the count
and not the bytes binds. From the ``phases`` table's ``ops_per_iter`` of the
phase session's digest (harness/digest_tables.py; the program reduces its
own capture: ``surreal_tpu/session/profile.py``); a program without the
table reads nothing."""

from benchmarks.harness import digest_tables

NAME = "device_ops_per_iter"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.over_phases(run, "ops_per_iter")
