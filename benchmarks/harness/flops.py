"""Operations and bytes one training iteration requires, from its shapes.

Counted by hand from the algorithm, never taken from XLA's cost model
(which counts a loop body once: PERF.md section 7). A multiply-accumulate
is two operations. A backward pass through a dense layer costs two
forward passes (input gradient + weight gradient); where only the input
gradient is needed (the DDPG actor loss through the critic) it costs one.
Recomputed operations do not count, nor do elementwise ones (tanh, layer
norm, Adam): at these widths the matrix products are the required work.

What every algorithm shares is here; each algorithm's own count is
``iteration_cost(config, traffic)`` of its reference module
(``benchmarks/reference/``), beside the equations it counts: ``samples``,
``flops`` (``flops_rollout`` + ``flops_learn``) and ``bytes`` per iteration.
"""

from __future__ import annotations


def mlp_macs(in_dim: int, hidden, out_dim: int) -> int:
    """Multiply-accumulates of one forward pass of one sample through
    dense layers ``in_dim -> hidden... -> out_dim``."""
    dims = [int(in_dim), *[int(h) for h in hidden], int(out_dim)]
    return sum(a * b for a, b in zip(dims, dims[1:]))
