"""Device milliseconds per iteration owned by the ops of phase ``learn``:
IMPALA's forward over ``obs``, the three losses, the backward pass and the
optimizer apply. From the digest of the phase session's capture
(harness/phase_session.py; the program names its ops' phases with
``jax.named_scope`` and reduces its own capture)."""

from benchmarks.harness import phase_session

NAME = "learn_phase_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "learn")
