"""The largest entry of a state-space state at a segment's end, over the
learn passes of the window's last row's iteration (``ssm/state_abs_max``):
a recurrence that blows up shows here before the loss does. 1.6 at the
initialisation; it has no better side, only a finite one."""

from benchmarks.harness import parts

NAME = "ssm_state_abs_max"


def read(run):
    return parts.last_row(run, "ssm/state_abs_max")
