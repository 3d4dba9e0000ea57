"""Routing over all experts and the held experts' grouped product.

An expert layer on one chip of an expert-parallel group is told which
experts it holds (``first_held``, and as many as its weights have), routes
every token over ALL ``n_routed`` experts, and computes its own experts'
part of the result; what the absent experts would add is left out (the
all-to-all that would fetch it is ``parallel/mesh.py``'s to grow: ROADMAP).

Routing (DeepSeek-V3, arXiv:2412.19437 section 2.1.2, ``noaux_tc`` with one
group): ``s = sigmoid(logits)`` in float32; the ``top_k`` largest of
``s + b`` are taken, ``b`` the selection bias, which enters nothing else
and takes no gradient; the weights are ``s_i / sum_{j in top_k} s_j x
scale``, normalised over all ``top_k`` whether held here or not.
``models/swa_moe.py`` routes by a softmax over all experts instead, with no
bias (:func:`route`); everything after the routing is shared.

The product is ragged: expert ``e`` gets however many tokens chose it. Two
forms compute it, chosen from the pass's static shape (:func:`dense_form`):

- **sorted** (a learn pass, thousands of tokens): the held assignments are
  sorted by expert into ``rows`` rows (a stable argsort of ``top_k x N``
  small integers), the rows of ``x`` gathered, three
  ``jax.lax.ragged_dot`` products run over the groups (XLA:TPU has a
  kernel for it) and the weighted rows scatter-added back onto their
  tokens. ``rows`` is a static bound (:func:`row_bound`):
  ``CAPACITY_FACTOR`` times what even routing would send here, which is
  what one chip's memory holds at the published widths (the worst case,
  ``N x min(top_k, held)``, is four times that again). Assignments past it
  would be dropped, last experts first; ``overflow`` counts them, it
  reaches the metric row ``moe/overflow``, and the session raises on a
  non-zero (``launch/hooks.py``): no token is dropped silently.
  The last group takes the bound's slack (zero rows in, nothing out), so
  the kernel computes all ``rows`` rows and an iteration takes the same
  time whatever the router does. That is work burnt: a tenth of the
  benchmark cell's iteration (PERF.md section 6, PR 33). It stands because
  the cell has to repeat within 0.5% over seeded weights to be admitted to
  the benchmark, and with the work following the load the same cell moved
  by 4% between seeds (the held share of a random router is 0.03-0.09).
  Take the one marked line out to let the work follow the load;
- **dense** (an acting step, a few hundred tokens at most): every held
  expert on every token, weighted by the token's weight for it or zero.
  Under the chip's ridge point an expert's products hide behind the stream
  of its weights from HBM, which a step reads whatever the routing;
  nothing is sorted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route(logits, bias, top_k: int, scale: float, scoring: str = "sigmoid"):
    """``logits [N, E]`` float32 -> ``(idx [N, top_k] int32, weights
    [N, top_k] float32, scores [N, E])``. ``scoring`` 'sigmoid' (the module
    docstring's; ``bias [E]`` enters the selection alone) or 'softmax'
    (``p = softmax(logits)`` over all ``E``, the ``top_k`` largest, the
    weights normalised over them as above: the Qwen-MoE lineage's
    ``norm_topk_prob``); ``bias`` None selects by the scores alone."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r} not in sigmoid|softmax")
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    picked = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(picked, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True) * scale
    return idx.astype(jnp.int32), weights, scores


def check_held(first_held: int, num_held: int, n_routed: int) -> None:
    """Refuse a share ``[first_held, first_held + num_held)`` that is empty
    or lies outside the ``n_routed`` experts."""
    held_end = int(first_held) + int(num_held)
    if not 0 <= int(first_held) < held_end <= int(n_routed):
        raise ValueError(
            f"held experts [{first_held}, {held_end}) lie outside the "
            f"{n_routed} routed"
        )


def expert_load(idx, n_routed: int):
    """Assignments per expert over the tokens of ``idx [N, top_k]``:
    ``[n_routed]`` float32 (a comparison and a sum; no scatter)."""
    hit = idx[..., None] == jnp.arange(n_routed, dtype=idx.dtype)
    return hit.sum((0, 1)).astype(jnp.float32)


# rows of the sorted buffer over what even routing would send to the held
# experts. The cell's random routers put 0.6-1.5 times that on them, the
# reference check's skewed ones up to 2.04 times (PERF.md, PR 33)
CAPACITY_FACTOR = 4.0
# a pass of at most this many tokens runs every held expert on every token:
# under the ridge point (v5e: 197 TFLOP/s over 819 GB/s = 240 rows a
# bfloat16 weight) the products wait for the weights, which stream anyway
DENSE_MAX_TOKENS = 256


def dense_form(tokens: int) -> bool:
    """Whether a pass over ``tokens`` tokens takes the dense form."""
    return tokens <= DENSE_MAX_TOKENS


def row_bound(tokens: int, top_k: int, held: int, n_routed: int) -> int:
    """Rows the sorted buffer gets for ``tokens`` tokens:
    ``CAPACITY_FACTOR`` times the even-routing expectation (a multiple of
    128), never more than the worst case ``tokens x min(top_k, held)``."""
    worst = tokens * min(top_k, held)
    expected = tokens * top_k * held / n_routed
    want = -(-int(CAPACITY_FACTOR * expected + 0.5) // 128) * 128
    return int(min(worst, want))


def sort_by_expert(idx, weights, first_held: int, held: int, rows: int):
    """The held assignments of ``idx``/``weights [N, top_k]``, sorted by
    expert into ``rows`` rows: ``(token [rows], weight [rows], valid
    [rows], group_sizes [held], overflow)``. Row ``r`` is valid while it
    holds an assignment; the rest carry weight 0 and belong to the last
    group, so that the groups always cover all ``rows``."""
    top_k = idx.shape[-1]
    local = idx - first_held
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)[:rows]
    counts = (
        key[:, None] == jnp.arange(held, dtype=key.dtype)
    ).sum(0).astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    # the slack (module docstring): without this line the work follows the load
    group_sizes = group_sizes.at[-1].add(rows - ends[-1])
    valid = jnp.arange(rows) < ends[-1]
    weight = jnp.where(valid, weights.reshape(-1)[order], 0.0)
    overflow = jnp.maximum(counts.sum() - rows, 0)
    return order // top_k, weight, valid, group_sizes, overflow


def swiglu(x, gate, up, down):
    """``(silu(x gate) * x up) down`` in ``x``'s dtype."""
    h = jax.nn.silu(x @ gate.astype(x.dtype)) * (x @ up.astype(x.dtype))
    return h @ down.astype(x.dtype)


def held_experts_dense(x, idx, weights, first_held: int, gate, up, down):
    """The same sum with every held expert applied to every token:
    ``x [N, D]`` -> ``[N, D]``; a token's weight for an expert it did not
    choose is zero."""
    dt = x.dtype
    held = gate.shape[0]
    experts = first_held + jnp.arange(held, dtype=idx.dtype)
    w = (weights[..., None] * (idx[..., None] == experts)).sum(1)   # [N, held]
    h = jax.nn.silu(jnp.einsum("nd,gdf->gnf", x, gate.astype(dt))) * jnp.einsum(
        "nd,gdf->gnf", x, up.astype(dt)
    )
    ys = jnp.einsum("gnf,gfd->gnd", h, down.astype(dt)).astype(jnp.float32)
    return (ys * w.T[..., None]).sum(0).astype(dt)


def held_experts(x, token, weight, valid, group_sizes, gate, up, down):
    """Sum over the sorted rows of ``weight x E_e(x[token])`` back onto
    the tokens: ``x [N, D]`` -> ``[N, D]``; ``gate``, ``up`` ``[held, D,
    F]`` and ``down [held, F, D]`` are the held experts' SwiGLU weights.
    Rows outside the groups are masked on the way in and out: what a
    kernel leaves in them never reaches a value or a gradient."""
    dt = x.dtype
    live = valid[:, None]
    xs = jnp.where(live, x[token], 0)
    dot = lambda a, w: jax.lax.ragged_dot(
        a, w.astype(dt), group_sizes, preferred_element_type=dt
    )
    ys = dot(jax.nn.silu(dot(xs, gate)) * dot(xs, up), down)
    ys = jnp.where(live, ys, 0).astype(jnp.float32) * weight[:, None]
    # a token's (up to top_k) rows add up in float32
    return jnp.zeros(x.shape, jnp.float32).at[token].add(ys).astype(dt)
