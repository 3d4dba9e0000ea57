"""Mean of the heads' sigmoid gates over the positions of a learn pass, the
five layers and the minibatch steps of the window's last row's iteration
(``attn/gate_mean``): 0.5 at the initialisation; a gate that closes (towards
0) takes its head out of the layer."""

from benchmarks.harness import parts

NAME = "laguna_gate_mean"


def read(run):
    return parts.last_row(run, "attn/gate_mean")
