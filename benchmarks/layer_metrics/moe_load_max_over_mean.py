"""Busiest held expert's assignments over the held experts' mean, in the
window's last row (``moe/load_max_over_mean``): 1 when the 16 are even;
the ragged product's longest group over its mean."""

from benchmarks.harness import parts

NAME = "moe_load_max_over_mean"


def read(run):
    return parts.last_row(run, "moe/load_max_over_mean")
