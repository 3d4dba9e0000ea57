"""The busiest held expert's assignments over the held experts' mean, over
every minibatch step and routed layer of the window's last row's iteration
(``moe/load_max_over_mean``): 1 when the held experts share their load
evenly. As ``moe_load_max_over_mean`` reads it for
``ppo_lift_joyai_128x128``."""

from benchmarks.harness import parts

NAME = "kimi_load_max_over_mean"


def read(run):
    return parts.last_row(run, "moe/load_max_over_mean")
