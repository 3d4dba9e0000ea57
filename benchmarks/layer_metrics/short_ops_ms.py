"""Device milliseconds per iteration owned by the op events shorter than 1 us
(``surreal_tpu/session/profile.py`` ``SHORT_OP_NS``), summed over the phases
and ``unattributed``. From the ``phases`` table's ``short_ops`` of the phase
session's digest (harness/digest_tables.py; the program reduces its own
capture: ``surreal_tpu/session/profile.py``); a program without the table
reads nothing."""

from benchmarks.harness import digest_tables

NAME = "short_ops_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.over_phases(run, "short_ops", "ms_per_iter")
