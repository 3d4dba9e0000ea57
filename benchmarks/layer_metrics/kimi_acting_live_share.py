"""Share of the 8 held experts whose weights an acting step's routed layer
read, the mean over the acting steps and routed layers of the window's last
row's iteration (``moe/acting_live_share``; ``ops/moe.py``): the live experts'
kernel reads an expert only if a token of the step chose it (0.39 expected at
16 tokens x top-8 over 256 experts under even routing), and the collect
phase's weight stream follows it. 1.0 where every step reads every held
expert; nothing from a program without the counter."""

from benchmarks.harness import parts

NAME = "kimi_acting_live_share"


def read(run):
    return parts.last_row(run, "moe/acting_live_share")
