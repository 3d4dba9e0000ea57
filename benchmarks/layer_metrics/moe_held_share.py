"""Share of the router's assignments that land on the experts this chip
holds, over every minibatch step and routed layer of the window's last
row's iteration (``moe/held_share``): 1/16 when routing is even, and what
the selection-bias rule steers towards."""

from benchmarks.harness import parts

NAME = "moe_held_share"


def read(run):
    return parts.last_row(run, "moe/held_share")
