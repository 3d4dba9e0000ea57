"""Device milliseconds per iteration owned by the ops of phase ``sgd``: the
loss's forward over a minibatch's 4096 tokens, its backward with each layer
recomputed, and the optimizer step, eight times an iteration. As
``phase_sgd_ms`` reads it for the ``ppo_lift`` cells and ``hybrid_sgd_ms``
for ``ppo_lift_phi4flash_16x1024``. From the digest of the phase session's
capture (harness/phase_session.py)."""

from benchmarks.harness import phase_session

NAME = "laguna_sgd_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "sgd")
