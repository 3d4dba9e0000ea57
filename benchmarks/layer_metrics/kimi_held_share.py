"""Share of the router's assignments that land on the 8 experts this chip
holds, over every minibatch step and routed layer of the window's last row's
iteration (``moe/held_share``): 1/32 when routing is even, which the selection
bias's rule steers towards. As ``moe_held_share`` reads it for
``ppo_lift_joyai_128x128``."""

from benchmarks.harness import parts

NAME = "kimi_held_share"


def read(run):
    return parts.last_row(run, "moe/held_share")
