"""Device milliseconds per iteration owned by the ops of sub-scope
``collect/act`` (``surreal_tpu/utils/phases.py`` ``SUBPHASES``): the
policy's forward and its sampling inside the rollout scan. From the
``subphases`` table of the phase session's digest (harness/digest_tables.py;
the program reduces its own capture: ``surreal_tpu/session/profile.py``); a
program without the table reads nothing."""

from benchmarks.harness import digest_tables

NAME = "collect_act_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.subphase_ms(run, "collect", "act")
