"""Device milliseconds per iteration owned by the ops of phase ``sgd``: the
loss's forward over a minibatch's 8192 tokens, its backward with each layer
and each chunk of the rule recomputed, and the optimizer step, four times an
iteration. As ``phase_sgd_ms`` reads it for the ``ppo_lift`` cells and
``laguna_sgd_ms`` for ``ppo_lift_laguna_16x1024``. From the digest of the
phase session's capture (harness/phase_session.py)."""

from benchmarks.harness import phase_session

NAME = "kimi_sgd_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "sgd")
