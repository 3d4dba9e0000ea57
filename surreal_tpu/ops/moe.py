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
- **dense** (an acting step, a few hundred tokens at most): nothing is
  sorted; a held expert runs over every token of the pass, weighted by the
  token's weight for it or zero. Under the chip's ridge point an expert's
  products hide behind the stream of its weights from HBM, so the form's
  cost is the bytes it reads, and two forms read them
  (:func:`held_experts_dense` chooses, from the pass's shape and where the
  program is lowered, by no key):

  - **every held expert** (``lax``: three einsums over ``[held, D, F]``).
    XLA's fusions stream the weights at the HBM's peak (141 us for a
    layer's 113 MB at 2304 wide, 198 for 151 MB at 3072: the readings
    beside ``LIVE_SHARE_MAX``), whatever the routing;
  - **the live experts alone** (:func:`_live_pallas`, a Pallas TPU kernel).
    An expert that no token of the pass chose has weight zero on every
    row, so leaving it out is exact. The held experts some token chose are
    compacted to the front of a list (:func:`live_experts`); the list and
    its length are scalar-prefetched and the kernel's grid walks ``held``
    slots x tiles of ``F``: a live slot's ``index_map`` names its expert's
    tiles of ``gate``, ``up`` and ``down``; a slot past the count names the
    block the last live step left in VMEM, so no copy is issued for it, and
    ``pl.when`` skips its products. ``out [N, D]`` float32 stays in VMEM
    over the whole grid. The rounding points are the ``lax`` form's (the
    two products, ``h`` and an expert's ``y`` in the compute dtype, the
    weighted sum in float32), so acting and the learn pass go on differing
    exactly as they did. A cotangent takes the ``lax`` form
    (``jax.custom_vjp``).

  The kernel runs where the program is lowered for a TPU
  (``jax.lax.platform_dependent``), the lanes divide ``D`` and ``F`` and
  the sublanes the tokens, and the share of held experts the pass expects to be live under even routing,
  ``1 - (1 - 1 / n_routed) ^ (tokens x top_k)``
  (:func:`expected_live_share`), is at most ``LIVE_SHARE_MAX``: 0.39 for
  ``ppo_lift_kimilinear_16x1024``'s step (16 tokens x top-8 over 256), 0.47
  for ``ppo_lift_laguna_16x1024``'s (top-10), 0.98 for
  ``ppo_lift_joyai_128x128``'s 128 tokens, which keeps XLA's form: with
  nothing to skip the kernel, which streams at four fifths of the peak,
  only loses. :func:`held_experts_dense` also says what share of the held
  experts' weights the pass read; the routed layers of an acting step add
  it to a tally in the acting carry's cache (:func:`count_reads`), a fused
  rollout reads the tally where it ends (``Learner.act_rows``) and the
  iteration's metrics row carries it as ``moe/acting_live_share``. The
  price: an acting step's time now follows the router (the sorted form's
  does not, above); a step's count is averaged over an iteration's
  thousands of layer-steps and a seed moves only its mean (PERF.md
  section 7 has the spread measured over seeds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
# a dense pass takes the live experts' kernel while the share of held experts
# it expects to be live is at most this. Chip readings (PR 48, v5e, a scan of
# 256 steps x 4 routed layers, us a layer and step, XLA's dense form / the
# kernel at the live share measured): 2304 wide, top-8: 16 tokens 141.0 /
# 74.3 at 0.41, 32 tokens 144.2 / 109.0 at 0.64, 64 tokens 161.5 / 137.4 at
# 0.87, 128 tokens 166.2 / 173.6 at 0.98; 3072 wide, top-10: 197.6 / 106.2 at
# 0.47, 196.7 / 155.5 at 0.72, 223.0 / 224.1 at 0.92, 220.0 / 223.7 at 0.99.
# XLA's fusions stream all eight experts at the HBM's peak and the kernel its
# live ones at four fifths of it, so above nine tenths nothing is left to win
LIVE_SHARE_MAX = 0.9
# the kernel's tile of an expert's width F: three [D, 256] bfloat16 tiles
# double-buffered are 7-9 MB of VMEM at D = 2304-3072, inside the 16 MiB a
# kernel gets without asking, so the compiler's own use of VMEM around the
# call stays as it was (an acting step's carried matrix states: the compile
# for the described v5e pins three of four there, tests/test_tpu_compile.py).
# Tiles of 512 (14-19 MB) read 68.4 for 74.3 us at 2304 wide and 104.5 for
# 106.2 at 3072; a hand-made DMA pipeline three to six tiles deep over the
# live tiles alone 73.6-70.9 and 103.6-100.7 (my chip runs, PR 48): what is
# left over the bytes' 57 and 87 us is a tile's copy before the first product
# and a third of a microsecond a dead slot, not the pipeline's depth
WIDTH_TILE = 256


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


def expected_live_share(tokens: int, top_k: int, n_routed: int) -> float:
    """The share of held experts that some token of a pass chooses, under
    even routing: ``1 - (1 - 1 / n_routed) ^ (tokens x top_k)``."""
    return 1.0 - (1.0 - 1.0 / n_routed) ** (tokens * top_k)


def streams_live_only(tokens: int, top_k: int, n_routed: int, D: int, F: int) -> bool:
    """Whether a dense pass of this shape takes the kernel where it is
    lowered for a TPU (the module docstring's static rule)."""
    return (
        D % 128 == 0 and F % 128 == 0 and tokens % 8 == 0
        and expected_live_share(tokens, top_k, n_routed) <= LIVE_SHARE_MAX
    )


def live_experts(hit):
    """``hit [N, top_k, held]`` (assignment x held expert) -> ``(ids [held]
    int32, count)``: the held experts some token chose, in order, at the
    front of ``ids`` (the rest 0), and how many they are. Comparisons and
    sums over ``held x held``: no sort, no scan."""
    held = hit.shape[-1]
    live = hit.any((0, 1))
    at = jnp.arange(held, dtype=jnp.int32)
    rank = (live[None, :] & (at[None, :] < at[:, None])).sum(1)     # live before e
    slot = live[:, None] & (rank[:, None] == at[None, :])           # [expert, slot]
    ids = (slot * at[:, None]).sum(0).astype(jnp.int32)
    return ids, live.sum().astype(jnp.int32)


def _dense_lax(x, w, gate, up, down):
    """Every held expert on every token: ``sum_e w[:, e] x E_e(x)``, ``w [N,
    held]`` float32 (zero where a token did not choose the expert)."""
    dt = x.dtype
    h = jax.nn.silu(jnp.einsum("nd,gdf->gnf", x, gate.astype(dt))) * jnp.einsum(
        "nd,gdf->gnf", x, up.astype(dt)
    )
    ys = jnp.einsum("gnf,gfd->gnd", h, down.astype(dt)).astype(jnp.float32)
    return (ys * w.T[..., None]).sum(0).astype(dt)


def _live_kernel(ids_ref, count_ref, x_ref, w_ref, gate_ref, up_ref, down_ref,
                 out_ref, acc_ref):
    """Grid step (slot, tile of ``F``): the slot's expert's ``[D, tile]``
    of ``gate`` and ``up`` and ``[tile, D]`` of ``down`` are here; ``out [N,
    D]`` float32 stays over the whole grid. The rounding points are
    :func:`_dense_lax`'s: the two products, ``h`` and an expert's ``y`` in
    ``x``'s dtype, the weighted sum over experts in float32."""
    del ids_ref     # the index maps read it
    slot, tile = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    dot = functools.partial(
        jnp.dot, preferred_element_type=f32, precision=jax.lax.Precision.DEFAULT
    )

    @pl.when((slot == 0) & (tile == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slot < count_ref[0])
    def _():
        x = x_ref[...]
        dt = x.dtype
        a = dot(x, gate_ref[...]).astype(dt).astype(f32)
        b = dot(x, up_ref[...]).astype(dt).astype(f32)
        h = (jax.nn.silu(a).astype(dt).astype(f32) * b).astype(dt)
        y = dot(h, down_ref[...])

        @pl.when(tile == 0)
        def _():
            acc_ref[...] = y

        @pl.when(tile > 0)
        def _():
            acc_ref[...] += y

        @pl.when(tile == pl.num_programs(1) - 1)
        def _():
            out_ref[...] += acc_ref[...].astype(dt).astype(f32) * w_ref[...]


# jitted so that a program traces and lowers the kernel once a shape, not
# once a routed layer
@functools.partial(jax.jit, static_argnames=("interpret",))
def _live_pallas(x, w, ids, count, gate, up, down, interpret=False):
    """The same sum from the live experts alone: slot ``s`` of the grid
    takes expert ``ids[s]``'s weights while ``s < count``; a slot past the
    count names the block the last live step left in VMEM, so nothing is
    copied for it, and computes nothing."""
    dt = x.dtype
    (N, D), (held, _, F) = x.shape, gate.shape
    tile = next(t for t in (WIDTH_TILE, 128) if F % t == 0)
    tiles = F // tile

    def at(s, j, ids, count):
        live = s < count[0]
        expert = ids[jnp.where(live, s, jnp.maximum(count[0] - 1, 0))]
        return expert, jnp.where(live, j, tiles - 1)

    def wide(s, j, ids, count):        # gate, up: [held, D, F]
        expert, j = at(s, j, ids, count)
        return expert, 0, j

    def narrow(s, j, ids, count):      # down: [held, F, D]
        expert, j = at(s, j, ids, count)
        return expert, j, 0

    def column(s, j, ids, count):      # the expert's weights a token: [held, N, 1]
        return at(s, j, ids, count)[0], 0, 0

    whole = lambda s, j, ids, count: (0, 0)     # noqa: E731
    out = pl.pallas_call(
        _live_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, tiles),
            in_specs=[
                pl.BlockSpec((N, D), whole),
                pl.BlockSpec((None, N, 1), column),
                pl.BlockSpec((None, D, tile), wide),
                pl.BlockSpec((None, D, tile), wide),
                pl.BlockSpec((None, tile, D), narrow),
            ],
            out_specs=pl.BlockSpec((N, D), whole),
            scratch_shapes=[pltpu.VMEM((N, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="held_experts_live",
    )(
        ids, count.reshape(1), x, w.T[..., None],
        gate.astype(dt), up.astype(dt), down.astype(dt),
    )
    return out.astype(dt)


@jax.custom_vjp
def _dense_where_lowered(x, w, ids, count, gate, up, down):
    return jax.lax.platform_dependent(
        x, w, ids, count, gate, up, down, tpu=_live_pallas,
        default=lambda x, w, ids, count, gate, up, down: _dense_lax(
            x, w, gate, up, down
        ),
    )


def _dense_where_lowered_fwd(x, w, ids, count, gate, up, down):
    out = _dense_where_lowered(x, w, ids, count, gate, up, down)
    return out, (x, w, gate, up, down)


def _dense_where_lowered_bwd(res, d):
    # a cotangent takes the lax form whatever computed the value
    dx, dw, *dweights = jax.vjp(_dense_lax, *res)[1](d)
    return dx, dw, None, None, *dweights


_dense_where_lowered.defvjp(_dense_where_lowered_fwd, _dense_where_lowered_bwd)


def held_experts_dense(x, idx, weights, first_held: int, n_routed: int,
                       gate, up, down):
    """The same sum by the dense form: ``x [N, D]`` -> ``([N, D], the share
    of the held experts whose weights the pass reads)``; a token's weight
    for an expert it did not choose is zero. Where the pass's shape leaves
    experts unchosen and the program is lowered for a TPU
    (:func:`streams_live_only`, the module docstring), the live experts
    alone, and the share is theirs; else all of them, and the share is 1."""
    held = gate.shape[0]
    experts = first_held + jnp.arange(held, dtype=idx.dtype)
    hit = idx[..., None] == experts                                 # [N, top_k, held]
    w = (weights[..., None] * hit).sum(1)                           # [N, held]
    if not streams_live_only(
        x.shape[0], idx.shape[-1], n_routed, x.shape[1], gate.shape[2]
    ):
        return _dense_lax(x, w, gate, up, down), jnp.float32(1.0)
    ids, count = live_experts(hit)
    share = jax.lax.platform_dependent(
        count, tpu=lambda count: count.astype(jnp.float32) / held,
        default=lambda count: jnp.float32(1.0),
    )
    return _dense_where_lowered(x, w, ids, count, gate, up, down), share


# the acting carry's leaf where the routed layers tally what they read, and
# the metrics row it becomes
EXPERTS_READ = "experts_read"


def no_reads():
    """A fresh tally: ``[the read shares' sum, how many were added]``."""
    return jnp.zeros((2,), jnp.float32)


def count_reads(tally, shares):
    """``tally`` with an acting step's routed layers added, one add a step:
    ``shares`` holds what :func:`held_experts_dense` said each read, and
    None for a layer that routes nothing."""
    shares = [s for s in shares if s is not None]
    return tally + jnp.stack([sum(shares), jnp.float32(len(shares))])


def acting_rows(cache: dict) -> dict:
    """The metrics row of an acting carry's ``cache``: the mean, over the
    acting steps and routed layers tallied in it, of the share of the held
    experts whose weights a step read (1.0 where every step reads all)."""
    total, passes = cache[EXPERTS_READ]
    return {"moe/acting_live_share": total / jnp.maximum(passes, 1.0)}


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
