"""Device milliseconds per iteration owned by the ops of phase ``prepare``:
the obs filter, one value pass over 16 x 1025 positions (the chunked rule over
17 chunks a segment, the routed layers sorted), GAE and the advantage norm. As
``phase_prepare_ms`` reads it for the ``ppo_lift`` cells and
``laguna_prepare_ms`` for ``ppo_lift_laguna_16x1024``, whose lists may not be
edited. From the digest of the phase session's capture
(harness/phase_session.py)."""

from benchmarks.harness import phase_session

NAME = "kimi_prepare_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "prepare")
