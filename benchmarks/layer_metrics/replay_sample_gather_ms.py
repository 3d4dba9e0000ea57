"""Device milliseconds per iteration owned by the ops of sub-scope
``replay_sample/gather`` (``surreal_tpu/utils/phases.py`` ``SUBPHASES``):
the sampled rows read from the ring. From the ``subphases`` table of the
phase session's digest (harness/digest_tables.py; the program reduces its
own capture: ``surreal_tpu/session/profile.py``); a program without the
table reads nothing."""

from benchmarks.harness import digest_tables

NAME = "replay_sample_gather_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return digest_tables.subphase_ms(run, "replay_sample", "gather")
