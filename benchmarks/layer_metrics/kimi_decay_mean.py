"""Mean of the decay ``alpha = exp(g)`` a channel a position over a learn
pass, the four Kimi Delta Attention layers and the minibatch steps of the
window's last row's iteration (``kda/decay_mean``): 1 forgets nothing, 0
everything a step; about 0.84 at the initialisation."""

from benchmarks.harness import parts

NAME = "kimi_decay_mean"


def read(run):
    return parts.last_row(run, "kda/decay_mean")
