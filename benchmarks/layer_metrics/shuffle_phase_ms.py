"""Device milliseconds per iteration owned by the ops of phase ``shuffle``:
PPO's per-epoch permutation and the gather of each minibatch's whole
envs (``x[mb_idx]``, the gather side of ``_mb_pieces``). From the digest of the phase
session's capture (harness/phase_session.py).
As ``phase_shuffle_ms`` reads it for the ``ppo_lift`` cells."""

from benchmarks.harness import phase_session

NAME = "shuffle_phase_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "shuffle")
