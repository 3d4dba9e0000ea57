"""Mean ``episode/return`` of the rows around the cell's env-step mark
(harness/checks.py). Read beside steps/s: speed bought by learning less
per step shows here. Its spread across seeds is RL's, not the code's."""

from benchmarks.harness import checks

NAME = "return_at_mark"


def read(run):
    return checks.return_at_mark(run)
