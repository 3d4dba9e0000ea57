"""Share of the 32 held experts whose weights an acting step's routed layer
read, the mean over the acting steps and the four routed layers of the
window's last row's iteration (``moe/acting_live_share``; ``ops/moe.py``):
the live experts' kernel reads an expert only if a token of the step chose it
(0.27 expected at 16 tokens x top-10 over 512 experts under even routing),
and the collect phase's weight stream follows it: across this cell's seeds
the rate and this share move together (0.280-0.291 at 6352 steps/s,
0.243-0.258 at 6439-6450: my chip runs, PR 63), which is why the cell
launches from listed seeds of one rate. 1.0 where every step reads every
held expert; nothing from a program without the counter."""

from benchmarks.harness import parts

NAME = "qwen3next_acting_live_share"


def read(run):
    return parts.last_row(run, "moe/acting_live_share")
