"""Device milliseconds per iteration owned by the ops of phase ``prepare``:
PPO's obs filter, the value forward over the T + 1 positions of every
segment (the chunked scan's ragged tail), GAE and the advantage norm.
From the digest of the phase session's capture (harness/phase_session.py).
As ``phase_prepare_ms`` reads it for the ``ppo_lift`` cells and
``prepare_phase_ms`` for ``ppo_lift_joyai_128x128``, whose lists may not be
edited."""

from benchmarks.harness import phase_session

NAME = "hybrid_prepare_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "prepare")
