"""Device milliseconds per iteration owned by the ops of phase ``vtrace``:
IMPALA's importance ratios, clips, reverse recurrence and policy-gradient
advantages. From the digest of the phase session's capture
(harness/phase_session.py; the program names its ops' phases with
``jax.named_scope`` and reduces its own capture)."""

from benchmarks.harness import phase_session

NAME = "vtrace_phase_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return phase_session.phase_ms(run, "vtrace")
