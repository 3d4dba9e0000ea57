"""Device milliseconds per iteration owned by the ops of phase ``shuffle``:
the epoch's permutation and the gather of a minibatch's eight whole envs.
From the digest of the phase session's capture (harness/phase_session.py).
As ``phase_shuffle_ms`` reads it for the ``ppo_lift`` cells and
``hybrid_shuffle_ms`` for ``ppo_lift_phi4flash_16x1024``, whose lists may not
be edited."""

from benchmarks.harness import phase_session

NAME = "kimi_shuffle_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "shuffle")
