"""The largest entry of a Kimi Delta Attention layer's matrix state at the
end of a learn pass's segments, over the four layers and the minibatch steps
of the window's last row's iteration (``kda/state_abs_max``): a rule that
blows up (a decay past 1, an erase that adds) shows here before the loss
does. As ``ssm_state_abs_max`` reads ``ssm/state_abs_max`` for
``ppo_lift_phi4flash_16x1024``."""

from benchmarks.harness import parts

NAME = "kimi_state_abs_max"


def read(run):
    return parts.last_row(run, "kda/state_abs_max")
