"""Grouped-query attention whose keys a learned indexer selects, over
softmax-routed experts, as a trajectory trunk.

The fifth block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='dsa_moe'``; ``models/attention.py`` has the table
of families and the heads every family shares). Its layers are those of
Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, ``config.json``: hidden
2048, 32 query heads over 4 key-value heads of 128, ``rope_theta`` 1e7,
every layer routed: 128 experts of 768, 8 a token, ``norm_topk_prob``, no
shared expert; and in every layer ``sa_config``: an indexer of 16 heads of
64 over one key head, ``topk`` 2048, ``rms_norm_eps`` 1e-6). ``x`` the
residual stream, every layer

    x += Attn(RMSNorm(x))        x += MoE(RMSNorm(x))

then a last RMSNorm in float32; the input is ``Dense(obs -> hidden)``. No
biases but the index key's LayerNorm.

**Attention**, ``h`` the normed input: ``q = W_q h`` as ``[T, H, hd]``, ``k
= W_k h``, ``v = W_v h`` as ``[T, G, hd]``; ``q`` and ``k`` each RMSNorm a
head (a weight of ``hd`` each), then turned over the whole head at theta
``ROPE_THETA``, pairs ``(x[i], x[i + hd / 2])``, at the position in the
segment (every position is a text position, so the config's three
``mrope_section``s turn by one index: plain rotary). **The indexer**:
``qI = W_qI h`` as ``[T, J, d]``; ``kI = LayerNorm(W_kI h)`` ``[T, d]``,
one key head all ``J`` share; both turned the same way over the whole ``d``;
``w = (W_w h) J^-1/2 d^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])``. A query keeps the ``index_topk`` causal keys with the largest
``I`` (all while it has no more; equal scores to the lower position) and
``softmax(q k^T / sqrt(hd))`` runs over the kept keys alone
(``ops/sparse_select.py`` has the score, the exact k-th value and the mask).

**No gradient reaches the indexer.** The selection is piecewise constant
and the config has no coefficient for a loss that would align the index
score with the attention map (ROADMAP R6), so ``W_qI``, ``W_kI``, ``W_w``
and the LayerNorm take a zero gradient and plain Adam leaves them where
the initialisation put them. **The loss stops at the router's product**
too, for ``models/latent_moe.py``'s reason (one chip of an expert-parallel
group). The routed layer is ``models/swa_moe.py::routed_ffn``'s without a
shared expert: softmax over all ``n_routed_experts``, the
``num_experts_per_tok`` largest, weights renormalised over them, the held
experts' part alone added.

**Two paths compute it**, from one parameter tree and the same functions.
The learn pass runs whole segments, ``QUERY_BLOCK`` queries at a time
(``ops/ring_attention.py::blocked_attention`` with a keep-mask a block:
on a TPU at a 128-wide head in bfloat16 its two Pallas kernels, the mask
one byte a pair and no score in HBM, anywhere else its ``lax`` form; the
row ``attn/scores_in_vmem`` says which):
a block whose last query has no more than ``index_topk`` keys selects
nothing, by its static shape; any other scores its queries against the
keys up to its end and searches each query's k-th value. An acting step
runs one position against the carry (:func:`acting_cache`): a layer's
rotated keys and values ``[envs, T, G, hd]`` and the indexer's own key
cache, ``LANES / d`` positions a 128-lane row (:func:`index_rows`: a
64-wide row would put the slots on the lanes and make the one-row write
touch every tile, ``models/ssm_hybrid.py::heads_per_row``); the step writes
all three at ``pos``, scores the new query against the whole index cache,
keeps ``min(pos + 1, index_topk)`` and attends under that mask. No leaf is
recurrent: a wrap moves the position and the masks hide what is stale.

Precision (``mixed``): products take bfloat16 operands and accumulate
float32, **the indexer's too** (the model's own run in FP8); the norms, the
rotary tables, ``relu``, the index score's sum over heads, the k-th-value
search, softmax and the router are float32.

**Recomputation** past :data:`REMAT_ABOVE_BYTES` of estimated residuals
(:func:`residual_bytes`, no key), a layer a ``jax.checkpoint``
(``models/attention.py::recomputed``); the selection is then searched again
in the backward.

Matrices initialise normal(0, ``INIT_STD``), norms 1, the LayerNorm's bias 0.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from surreal_tpu.models.attention import (
    COUNTERS_COLLECTION, MOE_COLLECTION, ROUTING_COLLECTION, Family,
    recomputed,
)
from surreal_tpu.models.ssm_hybrid import LANES, Leaves, _attend_one, _heads
from surreal_tpu.models.swa_moe import (
    _normal, _ONES, moe_stats, rms_norm, rotate, routed_ffn, routing_of,
)
from surreal_tpu.ops import moe
from surreal_tpu.ops.ring_attention import blocked_attention, scores_in_vmem
from surreal_tpu.ops.sparse_select import (
    index_scores, keep_mask, weighted_heads,
)
from surreal_tpu.utils.phases import part

# one value in use, so constants and no keys: the config's rope_theta, and
# sa_config's q_chunk_size = kv_chunk_size, read as the tile the scores are
# computed in (selection is a key at a time)
ROPE_THETA = 1.0e7
QUERY_BLOCK = 512
REMAT_ABOVE_BYTES = 2**30
_ZEROS = nn.initializers.zeros

# model.encoder keys this family reads beside the shared ones (kind, block,
# num_layers, num_heads, act_impl), with the values an unset (None) key
# takes: Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, one chip of 8
FAMILY_DEFAULTS = dict(
    hidden_size=2048,
    num_kv_heads=4,
    attn_head_dim=128,
    index_n_heads=16,
    index_head_dim=64,
    index_topk=2048,
    moe_intermediate_size=768,
    n_routed_experts=128,
    num_experts_per_tok=8,
    rms_norm_eps=1e-6,
    first_held=0,
    num_held=16,
)
# what a whole-segment apply sows, one scalar each: kept keys over causal
# keys (1 while no query has more than index_topk), the share of the
# queries that have more, and whether the attention's scores stayed in VMEM
# (1 in the Pallas kernels, 0 in the ``lax`` form)
COUNTERS = {
    "kept_share": ("attn/kept_share", "mean"),
    "selecting_share": ("attn/selecting_share", "mean"),
    "scores_in_vmem": ("attn/scores_in_vmem", "mean"),
}


def resolve(encoder_cfg: dict) -> dict:
    """``encoder_cfg`` with this family's unset keys at their defaults."""
    out = dict(encoder_cfg)
    for k, v in FAMILY_DEFAULTS.items():
        if out.get(k) is None:
            out[k] = v
    if int(out["num_heads"]) % int(out["num_kv_heads"]):
        raise ValueError(
            f"num_heads={out['num_heads']} must be a multiple of "
            f"num_kv_heads={out['num_kv_heads']}"
        )
    for key in ("attn_head_dim", "index_head_dim"):
        if int(out[key]) % 2:
            raise ValueError(f"{key}: the whole head is turned, in pairs")
    if int(out["index_topk"]) < 1:
        raise ValueError("index_topk: a query keeps at least one key")
    moe.check_held(out["first_held"], out["num_held"], out["n_routed_experts"])
    return out


def _sizes(cfg: dict) -> dict:
    return dict(
        D=int(cfg["hidden_size"]), H=int(cfg["num_heads"]),
        G=int(cfg["num_kv_heads"]), hd=int(cfg["attn_head_dim"]),
        J=int(cfg["index_n_heads"]), d=int(cfg["index_head_dim"]),
        topk=int(cfg["index_topk"]), Fm=int(cfg["moe_intermediate_size"]),
        E=int(cfg["n_routed_experts"]), K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["num_held"]), first=int(cfg["first_held"]),
        scale=1.0, eps=float(cfg["rms_norm_eps"]),
    )


def rope_table(dim: int, positions):
    """``(cos, sin) [T, dim / 2]`` float32 at ``positions [T]``: the whole
    ``dim`` turns, frequency ``ROPE_THETA^(-2i / dim)`` of pair ``i``."""
    freq = ROPE_THETA ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


# -- parameters ----------------------------------------------------------------

class LayerLeaves(nn.Module):
    """One layer's float32 leaves: ``{"attn_norm", "attn", "index",
    "ffn_norm", "moe"}``."""

    cfg: dict

    @nn.compact
    def __call__(self) -> dict:
        s = _sizes(self.cfg)
        D, H, G, hd, J, d = (s[k] for k in ("D", "H", "G", "hd", "J", "d"))
        norm = (("scale", (D,), _ONES),)
        attn = (
            ("q", (D, H, hd), _normal), ("k", (D, G, hd), _normal),
            ("v", (D, G, hd), _normal), ("o", (H, hd, D), _normal),
            ("q_norm", (hd,), _ONES), ("k_norm", (hd,), _ONES),
        )
        index = (
            ("q", (D, J, d), _normal), ("k", (D, d), _normal),
            ("w", (D, J), _normal),
            ("k_norm_scale", (d,), _ONES), ("k_norm_bias", (d,), _ZEROS),
        )
        held, Fm = s["held"], s["Fm"]
        routed = (
            ("router", (D, s["E"]), _normal),
            ("gate", (held, D, Fm), _normal), ("up", (held, D, Fm), _normal),
            ("down", (held, Fm, D), _normal),
        )
        return {
            "attn_norm": Leaves(norm, name="attn_norm")(),
            "attn": Leaves(attn, name="attn")(),
            "index": Leaves(index, name="index")(),
            "ffn_norm": Leaves(norm, name="ffn_norm")(),
            "moe": Leaves(routed, name="moe")(),
        }


# -- the layer, as functions of its leaves -------------------------------------

def _head_norm(scale, x, eps: float):
    """RMSNorm over a head's width with a weight of that width; float32
    inside, ``x``'s dtype out."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _qkv(p, h, positions, s, dt):
    """``(q [.., T, H, hd], k, v [.., T, G, hd])``: q and k normed a head
    and turned at ``positions [T]``."""
    cos, sin = rope_table(s["hd"], positions)
    q = rotate(_head_norm(p["q_norm"], _heads(p["q"], h, dt), s["eps"]), cos, sin)
    k = rotate(_head_norm(p["k_norm"], _heads(p["k"], h, dt), s["eps"]), cos, sin)
    return q, k, _heads(p["v"], h, dt)


def index_inputs(p, h, positions, s, dt):
    """The indexer's three products of ``h [B, T, D]``, no gradient through
    any: ``(qI [B, T, J, d], kI [B, T, d]`` in the compute dtype, ``w [B, T,
    J]`` float32 with the model's ``J^-1/2 d^-1/2`` in it)``."""
    p, h = jax.lax.stop_gradient((p, h))
    cos, sin = rope_table(s["d"], positions)
    q = rotate(_heads(p["q"], h, dt), cos, sin)
    k32 = (h @ p["k"].astype(dt)).astype(jnp.float32)
    mean = k32.mean(-1, keepdims=True)
    var = ((k32 - mean) ** 2).mean(-1, keepdims=True)
    k32 = (k32 - mean) * jax.lax.rsqrt(var + s["eps"])
    k32 = k32 * p["k_norm_scale"] + p["k_norm_bias"]
    # one key head: rotate's head axis has one entry
    k = rotate(k32[..., None, :], cos, sin)[..., 0, :].astype(dt)
    w = (h @ p["w"].astype(dt)).astype(jnp.float32) * (
        s["J"] ** -0.5 * s["d"] ** -0.5
    )
    return q, k, w


def attention_mixer(p, pi, h, s, dt):
    """A layer's attention over ``h [B, T, D]``: ``(out, kept keys over
    causal keys, the [B, T, T] keep-mask under the causal one)``."""
    B, T = h.shape[:2]
    positions = jnp.arange(T)
    causal = positions[None] <= positions[:, None]
    # a block whose last query has no more than topk keys selects nothing,
    # which its static shape says; the others' masks are made here, outside
    # the attention's own scope (an op has one part: the first on its path)
    kept = {}
    with part("attn_index"):
        if T > s["topk"]:
            qI, kI, w = index_inputs(pi, h, positions, s, dt)
        for lo in range(0, T, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, T)
            if hi > s["topk"]:
                scores = index_scores(qI[:, lo:hi], kI[:, :hi], w[:, lo:hi])
                kept[lo] = keep_mask(scores, causal[lo:hi, :hi], s["topk"])
    with part("attn"):
        q, k, v = _qkv(p, h, positions, s, dt)
        out, seen = blocked_attention(
            q, k, v, block=QUERY_BLOCK,
            keep=(lambda lo, hi, first: kept.get(lo)) if kept else None,
        )
        out = jnp.einsum("bthe,hed->btd", out, p["o"].astype(dt))
    # for the check alone (ROUTING_COLLECTION): unread, it is never built
    rows = lambda lo: causal[lo:lo + QUERY_BLOCK]  # noqa: E731
    whole = jnp.concatenate([
        jnp.pad(kept[lo], ((0, 0), (0, 0), (0, T - kept[lo].shape[2])))
        if lo in kept else jnp.broadcast_to(rows(lo), (B, *rows(lo).shape))
        for lo in range(0, T, QUERY_BLOCK)
    ], 1)
    return out, seen / ((T + 1) / 2.0), whole


def index_rows(horizon: int, d: int) -> tuple:
    """``(rows, positions a row)`` of an index-key cache: ``LANES / d``
    positions share a 128-lane row where that fills it exactly, position
    ``t`` at row ``t mod rows`` in lanes ``(t div rows) d ..``, so that a
    row's parts are whole runs of positions and the scores of the parts
    concatenate in order; any other width keeps a position a row."""
    per = LANES // d if d < LANES and LANES % d == 0 else 1
    return -(-horizon // per), per


def attention_step(p, pi, h, cache, pos, s, dt):
    """One position ``h [B, D]``: the key, turned at ``pos``, the value and
    the index key go to ``pos`` of ``cache {"k", "v" [B, S, G, hd], "index"
    [B, rows, per x d]}``; the query attends to the slots the selection
    kept of those written in this segment: ``(out [B, D], cache, the kept
    mask [B, S])``."""
    S = cache["k"].shape[1]
    rows, width = cache["index"].shape[1:]
    d = s["d"]
    per = width // d
    with part("attn_index"):
        qI, kI, w = index_inputs(pi, h[:, None], pos[None], s, dt)
        # the row is read, one part of it replaced, and written whole
        row, lane_part = pos % rows, pos // rows
        old = jax.lax.dynamic_slice_in_dim(cache["index"], row, 1, axis=1)
        mine = (jnp.arange(width) // d == lane_part)
        new = jnp.where(mine, jnp.tile(kI, (1, 1, per)).astype(old.dtype), old)
        index = jax.lax.dynamic_update_slice_in_dim(
            cache["index"], new, row, axis=1
        )
        valid = jnp.arange(S) <= pos
        if S > s["topk"]:
            # part a of a row holds positions a rows .. (a + 1) rows - 1: a
            # query in part a's lanes of a zero row scores those alone
            own = jnp.arange(per)[:, None] == jnp.arange(width)[None] // d
            q_parts = jnp.where(
                own[:, None], jnp.tile(qI[:, 0], (1, 1, per))[:, None], 0
            )                                           # [B, per, J, width]
            dots = jnp.einsum(
                "bajd,bsd->bjas", q_parts, index,
                preferred_element_type=jnp.float32,
            ).reshape(h.shape[0], s["J"], per * rows)[..., :S]
            kept = keep_mask(weighted_heads(dots, w[:, 0]), valid, s["topk"])
        else:
            kept = jnp.broadcast_to(valid, (h.shape[0], S))
    with part("attn"):
        q, k, v = _qkv(p, h[:, None], pos[None], s, dt)
        put = lambda c, x: jax.lax.dynamic_update_slice_in_dim(
            c, x.astype(c.dtype), pos, axis=1
        )
        cache = {"k": put(cache["k"], k), "v": put(cache["v"], v), "index": index}
        out = _attend_one(q[:, 0], cache["k"], cache["v"], kept[:, None, None])
        return jnp.einsum("bhe,hed->bd", out, p["o"].astype(dt)), cache, kept


def _ffn(p, x, s, dt):
    h = rms_norm(p["ffn_norm"], x, s["eps"], dt)
    y, stats = routed_ffn(p["moe"], None, h.reshape(-1, h.shape[-1]), s)
    return x + y.reshape(x.shape), stats


def residual_bytes(cfg: dict, tokens: int) -> int:
    """Roughly what a differentiated pass over ``tokens`` tokens keeps
    without recomputation, in the compute dtype: per layer the residual
    stream and its two normed copies, queries, outputs, keys and values, and
    the held experts' three wide tensors over the sorted buffer's rows."""
    s = _sizes(cfg)
    rows = moe.row_bound(tokens, s["K"], s["held"], s["E"]) / max(tokens, 1)
    per_token = (
        4 * s["D"] + 2 * (s["H"] + s["G"]) * s["hd"]
        + rows * (3 * s["Fm"] + s["D"])
    )
    return int(2 * tokens * per_token * int(cfg["num_layers"]))


def forward(params: dict, x, cfg: dict, dt, residual: int):
    """The learn pass: ``x [B, T, D]`` -> ``(x, stats)``; ``params`` is
    ``{"layer<i>": leaves}``, ``stats`` the counters, a list of what
    ``routed_ffn`` reports and a list of the layers' keep-masks."""
    s = _sizes(cfg)
    T = x.shape[1]
    shares, routed, kept = [], [], []
    for i in range(int(cfg["num_layers"])):
        def layer(p, x):
            h = rms_norm(p["attn_norm"], x, s["eps"], dt)
            out, share, mask = attention_mixer(p["attn"], p["index"], h, s, dt)
            x, stats = _ffn(p, x + out, s, dt)
            return x, share, mask, stats

        layer = recomputed(layer, residual, REMAT_ABOVE_BYTES)
        x, share, mask, stats = layer(params[f"layer{i}"], x)
        shares.append(share)
        kept.append(mask)
        routed.append(stats)
    return x, {
        "kept_share": jnp.stack(shares).mean(),
        "selecting_share": jnp.float32(max(T - s["topk"], 0) / T),
        "scores_in_vmem": scores_in_vmem(jax.ShapeDtypeStruct((s["hd"],), dt)),
        "routed": routed, "kept": kept,
    }


def decode(params: dict, x, cache: dict, pos, cfg: dict, dt):
    """An acting step: ``x [B, D]`` at ``pos`` -> ``(x, new cache, what
    ``routed_ffn`` reports a layer, the kept mask a layer)``."""
    s = _sizes(cfg)
    layers, routed, kept = list(cache["layers"]), [], []
    for i in range(int(cfg["num_layers"])):
        p = params[f"layer{i}"]
        h = rms_norm(p["attn_norm"], x, s["eps"], dt)
        out, layers[i], mask = attention_step(
            p["attn"], p["index"], h, layers[i], pos, s, dt
        )
        x, stats = _ffn(p, x + out, s, dt)
        routed.append(stats)
        kept.append(mask)
    tally = moe.count_reads(cache[moe.EXPERTS_READ], [r["read"] for r in routed])
    return x, {"layers": layers, moe.EXPERTS_READ: tally}, routed, kept


class DsaMoETrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``. A whole-segment apply sows :data:`COUNTERS` into
    the counters collection, ``load`` and ``overflow`` into the ``moe``
    collection and, on request, each token's chosen experts, the routers'
    inputs and each layer's keep-mask into ``moe_routing``
    (``models/attention.py`` names the three); an acting step the chosen
    experts and the kept masks."""

    cfg: dict               # resolve()d model.encoder subtree
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, obs, *, cache=None, pos=None, replicate_ok: bool = False):
        del replicate_ok    # no mesh path: one chip's share runs unsharded
        c, dt = self.cfg, self.compute_dtype
        eps = float(c["rms_norm_eps"])
        x = nn.Dense(
            int(c["hidden_size"]), use_bias=False, dtype=dt,
            param_dtype=jnp.float32, name="embed", kernel_init=_normal,
        )(obs.astype(dt))
        params = {
            f"layer{i}": LayerLeaves(c, name=f"layer{i}")()
            for i in range(int(c["num_layers"]))
        }
        norm = Leaves(
            (("scale", (int(c["hidden_size"]),), _ONES),), name="norm"
        )()
        if cache is not None:
            x, cache, routed, kept = decode(params, x, cache, pos, c, dt)
            self.sow(ROUTING_COLLECTION, "experts", [r["experts"] for r in routed])
            self.sow(ROUTING_COLLECTION, "kept", kept)
            return rms_norm(norm, x, eps, jnp.float32), cache
        tokens = x.shape[0] * x.shape[1]
        x, stats = forward(
            params, x, c, dt, residual=residual_bytes(c, tokens),
        )
        routed, kept = stats.pop("routed"), stats.pop("kept")
        for name, value in stats.items():
            self.sow(COUNTERS_COLLECTION, name, value)
        self.sow(MOE_COLLECTION, "load", jnp.stack([r["load"] for r in routed]))
        self.sow(MOE_COLLECTION, "overflow", sum(r["overflow"] for r in routed))
        for what in ("experts", "inputs"):
            self.sow(ROUTING_COLLECTION, what, [r[what] for r in routed])
        self.sow(ROUTING_COLLECTION, "kept", kept)
        return rms_norm(norm, x, eps, jnp.float32)


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache: ``{"layers": [{"k", "v" [envs, horizon, G,
    hd], "index" [envs, rows, per x d]}, ...]}``, a dict a layer, in the
    compute dtype; keys and index keys are held rotated
    (:func:`index_rows` has the index cache's geometry). Beside them the
    routed layers' tally of the experts they read
    (``ops/moe.py::no_reads``)."""
    s = _sizes(cfg)
    rows, per = index_rows(horizon, s["d"])
    layer = lambda: {
        "k": jnp.zeros((num_envs, horizon, s["G"], s["hd"]), dtype),
        "v": jnp.zeros((num_envs, horizon, s["G"], s["hd"]), dtype),
        "index": jnp.zeros((num_envs, rows, per * s["d"]), dtype),
    }
    return {
        "layers": [layer() for _ in range(int(cfg["num_layers"]))],
        moe.EXPERTS_READ: moe.no_reads(),
    }


def kept_of(collection: dict) -> list:
    """``[layers]`` keep-masks (``[B, T, T]`` of a whole-segment apply, ``[B,
    S]`` of an acting step) from the ``moe_routing`` collection of one
    ``apply`` (the benchmark's reference check reads it; no training path
    does)."""
    return list(collection["trunk"]["kept"][-1])


# no leaf of the carry is recurrent and nothing moves the router: no
# ``reset_recurrent``, no bias rule
FAMILY = Family(
    trunk=DsaMoETrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, counters=COUNTERS, moe_stats=moe_stats,
)
