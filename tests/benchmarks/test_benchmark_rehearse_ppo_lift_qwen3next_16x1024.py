"""The rehearsal of one cell: fixtures and tests are benchmark_rehearsal.py's."""

from benchmark_rehearsal import *  # noqa: F401,F403

CELL = "ppo_lift_qwen3next_16x1024"
