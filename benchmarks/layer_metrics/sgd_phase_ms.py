"""Device milliseconds per iteration owned by the ops of phase ``sgd``:
PPO's loss, forward and backward over each minibatch, and the optimizer
apply. From the digest of the phase
session's capture (harness/phase_session.py).
As ``phase_sgd_ms`` reads it for the ``ppo_lift`` cells."""

from benchmarks.harness import phase_session

NAME = "sgd_phase_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "sgd")
