"""Share of the 8 held experts whose weights an acting step's routed layer
read, the mean over the acting steps and routed layers of the window's last
row's iteration (``moe/acting_live_share``; ``ops/moe.py``): the live experts'
kernel reads an expert only if a token of the step chose it (0.47 expected at
16 tokens x top-10 over 256 experts under even routing), and the collect
phase's weight stream follows it. As ``kimi_acting_live_share`` reads it for
``ppo_lift_kimilinear_16x1024``."""

from benchmarks.harness import parts

NAME = "laguna_acting_live_share"


def read(run):
    return parts.last_row(run, "moe/acting_live_share")
