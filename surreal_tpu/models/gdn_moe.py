"""Gated DeltaNet layers three to one gated attention layer, over
softmax-routed experts beside a gated shared one, as a trajectory trunk.

The seventh block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='gdn_moe'``; ``models/attention.py`` has the table of
families and the heads every family shares). Its layers are those of
Qwen3-Next-80B-A3B-Instruct (Qwen, ``config.json``, ``model_type``
``qwen3_next``: hidden 2048, ``full_attention_interval`` 4, linear layers of
``linear_num_key_heads`` 16 under ``linear_num_value_heads`` 32, both of
128, ``linear_conv_kernel_dim`` 4; full layers of 16 query over 2 key-value
heads of ``head_dim`` 256, ``partial_rotary_factor`` 0.25, ``rope_theta``
1e7; every layer routed: 512 experts of 512, 10 a token, ``norm_topk_prob``,
a shared expert of 512; ``rms_norm_eps`` 1e-6). Counting from zero, layer
``l`` is full where ``(l + 1)`` is a multiple of :data:`PERIOD`, else linear.
``x`` the residual stream, every layer

    x += Mixer_l(N(x))        x += FFN_l(N(x))

then a last ``N`` in float32; the input is ``Dense(obs -> hidden)``. **N is
the zero-centred RMSNorm**, ``N(x) = x / sqrt(mean(x^2) + eps) (1 + w)``,
``w`` initialised 0; the one norm with a plain weight is the linear mixer's
output norm. No bias anywhere but ``dt_bias``.

**Linear mixer (Gated DeltaNet**, Yang et al. 2024, arXiv:2412.06464),
``h`` its normed input, ``Hk`` key heads under ``Hv`` value heads of ``K``:
``[q | k | v | z] = h W_qkvz`` (q, k ``[Hk, K]``; v, z ``[Hv, K]``), ``[b |
a] = h W_ba`` (``Hv`` each); ``[q | k | v] <- SiLU(conv([q | k | v]))``, one
causal depthwise conv over the last ``short_conv_kernel_size`` positions of
the concatenated channels; ``q`` and ``k`` L2-normalised a head (float32),
``q`` times ``K^-1/2``, both repeated so that value head ``j`` reads key
head ``j // (Hv / Hk)``; ``beta = sigmoid(b)``; the log-decay **one a value
head** ``g = -exp(A_log[j]) softplus(a + dt_bias[j])`` (float32); the
recurrence of ``ops/delta_rule.py`` with that head-wide decay gives ``o``;
out ``W_o (RMSNorm_head(o) * SiLU(z))``.

**Full mixer (gated attention)**: ``[q | gate] = h W_q``, a head's ``2 hd``
split in two; ``k = h W_k``, ``v = h W_v`` as ``[G, hd]``; ``q`` and ``k``
each ``N`` a head, then turned over the first ``partial_rotary_factor x hd``
of the head, pairs ``(x[i], x[i + rot / 2])``, at the position in the
segment; causal softmax of ``q k^T / sqrt(hd)`` within the group in float32;
``sigmoid(gate)``, one a channel, multiplies the heads' outputs before
``W_o``.

**The routed layer** is ``models/swa_moe.py::routed_ffn``'s: softmax over
all ``n_routed_experts`` (float32 at ``Precision.HIGHEST``), the
``num_experts_per_tok`` largest, weights renormalised over them, the held
experts' part alone added, plus ``sigmoid(y . w_g)`` a token times the
shared expert. **The loss stops at the router's product**, for
``models/latent_moe.py``'s reason (one chip of an expert-parallel group),
and there is no bias rule and no auxiliary loss, so nothing moves the router
on one chip; the shared expert's gate takes its gradient. This chip holds
``num_held`` experts from ``first_held`` on (``ops/moe.py``).

**Two paths compute it**, from one parameter tree and the same functions of
it (:func:`forward`, :func:`decode`). The learn pass runs whole segments:
the chunked rule, ``ops/ring_attention.py::blocked_attention``
(:data:`QUERY_BLOCK` queries at a time), the routed layers sorted by
expert. An acting step runs one position against a carry of two kinds side
by side (:func:`acting_cache`): per linear layer a float32 matrix state
``[envs, Hv, K, K]`` and the last ``taps - 1`` positions of the
concatenated q | k | v, constant in size; per full layer rotated keys and
values, a position's ``G`` heads side by side in one row ``[envs, T, 1, G x
hd]`` (a row of ``G = 2`` heads on the sublanes would be padded to the
tile: ``models/ssm_hybrid.py::heads_per_row``'s lesson; ``_attend_one``
reads the geometry off the row's width). A wrap to a new segment zeroes the
linear leaves (:func:`reset_recurrent`) and only moves the position for the
caches, whose stale rows the mask hides.

**Precision** (``compute_dtype`` bfloat16 under 'mixed'): products take
bfloat16 operands; every norm, the conv's sum, ``softplus``, the decay,
``beta``, the state, the triangular system, the rotary table, softmax, both
sigmoid gates and the router are float32.

**Recomputation**: the shape rule of ``models/attention.py::recomputed``,
past :data:`REMAT_ABOVE_BYTES` of estimated residuals
(:func:`residual_bytes`).

Init (the config gives ``initializer_range`` 0.02 and no more): every matrix
normal(0, ``INIT_STD``); ``A_log`` and ``dt_bias`` as ``models/kda_moe.py``
(the lineage's), conv taps as ``models/ssm_hybrid.py``; ``w`` 0, the output
norm 1.
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
from surreal_tpu.models.kda_moe import _a_log_init, _l2
from surreal_tpu.models.ssm_hybrid import (
    Leaves, _attend_one, _conv_init, _dt_bias_init, _heads,
)
# ``routing_of`` is handed on as it is: the benchmark's check reads a trunk's
# chosen experts through its family's module
from surreal_tpu.models.swa_moe import (  # noqa: F401
    _normal, _ONES, moe_stats, rotate, routed_ffn, routing_of,
)
from surreal_tpu.ops import moe
from surreal_tpu.ops.delta_rule import (
    delta_rule, delta_step, gram_in_vmem, walk_in_vmem,
)
from surreal_tpu.ops.ring_attention import blocked_attention, scores_in_vmem
from surreal_tpu.utils.phases import part

BLOCK = "gdn_moe"
# one value in use, so constants and no keys: a full layer every fourth
# (``full_attention_interval``)
PERIOD = 4
QUERY_BLOCK = 256
# which form of ``blocked_attention`` a full layer's learn pass takes at its
# 256-wide head: the Pallas pair, 256 queries a block (512 overrun the
# kernels' default 16 MiB of VMEM at this width; on the v5e at [8, 1024, 16,
# 256] over 2 key-value heads a forward is 1.98 ms where the ``lax`` form's is
# 3.28, ``jax.grad`` 4.42 against 4.26: benchmarks/QWEN3NEXT.md)
ATTENTION_KERNELS = True
REMAT_ABOVE_BYTES = 2**30
_ZEROS = nn.initializers.zeros

# model.encoder keys this family reads beside the shared ones (kind, block,
# num_layers, num_heads: a full layer's query heads, act_impl), with the
# values an unset (None) key takes: Qwen/Qwen3-Next-80B-A3B-Instruct
# config.json, one chip of 16
FAMILY_DEFAULTS = dict(
    hidden_size=2048,
    linear_num_key_heads=16,
    linear_num_value_heads=32,
    linear_head_dim=128,
    short_conv_kernel_size=4,
    num_kv_heads=2,
    attn_head_dim=256,
    partial_rotary_factor=0.25,
    rope_theta=1.0e7,
    moe_intermediate_size=512,
    shared_intermediate_size=512,
    n_routed_experts=512,
    num_experts_per_tok=10,
    rms_norm_eps=1e-6,
    first_held=0,
    num_held=32,
)
# what a whole-segment apply sows, one scalar each (``{sown name: (metrics
# row, how the row reduces it over an iteration's minibatch steps)}``): the
# linear layers' counters as ``models/kda_moe.py``'s (the largest entry of a
# matrix state a segment ended with, the mean decay and ``beta``, which form
# of the rule's Gram matrices and of its walk ran), the mean of the full
# layers' output gates and of the shared experts' (0.5 at the
# initialisation; a gate that closes or saturates shows before the loss
# does), and whether the attention's scores stayed in VMEM
COUNTERS = {
    "state_abs_max": ("gdn/state_abs_max", "max"),
    "decay_mean": ("gdn/decay_mean", "mean"),
    "beta_mean": ("gdn/beta_mean", "mean"),
    "gram_in_vmem": ("gdn/gram_in_vmem", "mean"),
    "walk_in_vmem": ("gdn/walk_in_vmem", "mean"),
    "gate_mean": ("attn/gate_mean", "mean"),
    "scores_in_vmem": ("attn/scores_in_vmem", "mean"),
    "shared_gate_mean": ("moe/shared_gate_mean", "mean"),
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
    if int(out["linear_num_value_heads"]) % int(out["linear_num_key_heads"]):
        raise ValueError(
            f"linear_num_value_heads={out['linear_num_value_heads']} must be "
            f"a multiple of linear_num_key_heads={out['linear_num_key_heads']}"
        )
    rot = float(out["partial_rotary_factor"]) * int(out["attn_head_dim"])
    if not 0 < rot <= int(out["attn_head_dim"]) or rot % 2:
        raise ValueError(
            "partial_rotary_factor x attn_head_dim: an even part of the "
            f"head turns, in pairs (got {rot})"
        )
    if int(out["short_conv_kernel_size"]) < 2:
        raise ValueError("short_conv_kernel_size: a conv of at least 2 taps")
    if int(out["num_layers"]) < 1:
        raise ValueError("num_layers: at least one layer")
    moe.check_held(out["first_held"], out["num_held"], out["n_routed_experts"])
    return out


def layer_kinds(cfg: dict) -> list:
    """The trunk's layers in order: 'full' or 'linear'."""
    return [
        "full" if (i + 1) % PERIOD == 0 else "linear"
        for i in range(int(cfg["num_layers"]))
    ]


def _sizes(cfg: dict) -> dict:
    hd = int(cfg["attn_head_dim"])
    return dict(
        D=int(cfg["hidden_size"]), H=int(cfg["num_heads"]),
        G=int(cfg["num_kv_heads"]), hd=hd,
        rot=int(float(cfg["partial_rotary_factor"]) * hd),
        theta=float(cfg["rope_theta"]),
        Hk=int(cfg["linear_num_key_heads"]),
        Hv=int(cfg["linear_num_value_heads"]), dk=int(cfg["linear_head_dim"]),
        taps=int(cfg["short_conv_kernel_size"]),
        Fm=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["shared_intermediate_size"]),
        E=int(cfg["n_routed_experts"]), K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["num_held"]), first=int(cfg["first_held"]),
        scale=1.0, eps=float(cfg["rms_norm_eps"]),
    )


def rope_table(s: dict, positions):
    """``(cos, sin) [T, rot / 2]`` float32 at ``positions [T]``: the first
    ``rot`` of a head turn, frequency ``theta^(-2i / rot)`` of pair ``i``."""
    rot = s["rot"]
    freq = s["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = positions.astype(jnp.float32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


# -- parameters ----------------------------------------------------------------

class LayerLeaves(nn.Module):
    """One layer's float32 leaves: ``{"attn_norm", "gdn" | "attn",
    "ffn_norm", "moe", "shared"}``."""

    kind: str
    cfg: dict

    @nn.compact
    def __call__(self) -> dict:
        s = _sizes(self.cfg)
        D, H, G, hd = s["D"], s["H"], s["G"], s["hd"]
        Hk, Hv, dk, taps = s["Hk"], s["Hv"], s["dk"], s["taps"]
        norm = (("w", (D,), _ZEROS),)
        conv = (2 * Hk + Hv) * dk
        if self.kind == "linear":
            name, mixer = "gdn", (
                ("qkvz", (D, conv + Hv * dk), _normal),
                ("ba", (D, 2 * Hv), _normal),
                ("conv", (taps, conv), _conv_init),
                ("dt_bias", (Hv,), _dt_bias_init),
                ("A_log", (Hv,), _a_log_init),
                ("o_norm", (dk,), _ONES), ("o", (Hv, dk, D), _normal),
            )
        else:
            name, mixer = "attn", (
                ("q", (D, H, 2 * hd), _normal), ("k", (D, G, hd), _normal),
                ("v", (D, G, hd), _normal), ("o", (H, hd, D), _normal),
                ("q_norm", (hd,), _ZEROS), ("k_norm", (hd,), _ZEROS),
            )
        held, Fm, Fs = s["held"], s["Fm"], s["Fs"]
        routed = (
            ("router", (D, s["E"]), _normal),
            ("gate", (held, D, Fm), _normal), ("up", (held, D, Fm), _normal),
            ("down", (held, Fm, D), _normal),
        )
        shared = (
            ("gate", (D, Fs), _normal), ("up", (D, Fs), _normal),
            ("down", (Fs, D), _normal), ("token_gate", (D,), _normal),
        )
        return {
            "attn_norm": Leaves(norm, name="attn_norm")(),
            name: Leaves(mixer, name=name)(),
            "ffn_norm": Leaves(norm, name="ffn_norm")(),
            "moe": Leaves(routed, name="moe")(),
            "shared": Leaves(shared, name="shared")(),
        }


# -- the layers, as functions of their leaves ----------------------------------

def zc_norm(w, x, eps: float, dtype):
    """The zero-centred RMSNorm over the last axis, ``(1 + w)`` its weight;
    float32 inside."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w)).astype(dtype)


def _rule_inputs(p, conv, ba, s, dt):
    """``(q, k [.., Hv, K] float32, v [.., Hv, K] in ``dt``, g, beta [..,
    Hv] float32)`` as the rule takes them, from the conv's sum ``conv [..,
    (2 Hk + Hv) K]`` (float32, before the SiLU) and ``ba [.., 2 Hv]``."""
    Hk, Hv, dk = s["Hk"], s["Hv"], s["dk"]
    x = jax.nn.silu(conv)
    lead = x.shape[:-1]
    q = x[..., :Hk * dk].reshape(*lead, Hk, dk)
    k = x[..., Hk * dk:2 * Hk * dk].reshape(*lead, Hk, dk)
    v = x[..., 2 * Hk * dk:].reshape(*lead, Hv, dk)
    # value head j reads key head j // (Hv / Hk)
    q = jnp.repeat(_l2(q) * dk ** -0.5, Hv // Hk, axis=-2)
    k = jnp.repeat(_l2(k), Hv // Hk, axis=-2)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    return q, k, v.astype(dt), g, jax.nn.sigmoid(ba[..., :Hv])


def _gdn_products(p, h, s, dt):
    """``(the conv's input [.., (2 Hk + Hv) K], z [.., Hv, K]`` in ``dt``,
    ``ba [.., 2 Hv]`` float32)`` of ``h [.., D]``."""
    with part("gdn_proj"):
        mixed = h @ p["qkvz"].astype(dt)
        ba = jnp.dot(h, p["ba"].astype(dt), preferred_element_type=jnp.float32)
    conv = (2 * s["Hk"] + s["Hv"]) * s["dk"]
    z = mixed[..., conv:].reshape(*mixed.shape[:-1], s["Hv"], s["dk"])
    return mixed[..., :conv], z, ba


def _gdn_out(p, o, z, s, dt):
    """``W_o (RMSNorm_head(o) * SiLU(z))``, ``o, z [.., Hv, K]``; the norm's
    weight is plain."""
    with part("gdn_scan"):
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + s["eps"])
        o = (o * p["o_norm"] * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    with part("gdn_proj"):
        return jnp.einsum("...hk,hkd->...d", o, p["o"].astype(dt))


def gdn_mixer(p, h, s, dt):
    """A linear layer over ``h [B, T, D]`` from a zero state and a zero conv
    tail: ``(out, counters)``."""
    T = h.shape[1]
    qkv, z, ba = _gdn_products(p, h, s, dt)
    with part("gdn_scan"):
        taps = s["taps"]
        padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(
            p["conv"][j] * padded[:, j:j + T].astype(jnp.float32)
            for j in range(taps)
        )
        q, k, v, g, beta = _rule_inputs(p, conv, ba, s, dt)
        o, state = delta_rule(q, k, v, g, beta)
        stats = {
            "state_abs_max": jnp.abs(state).max(),
            "decay_mean": jnp.exp(g).mean(), "beta_mean": beta.mean(),
            "gram_in_vmem": gram_in_vmem(q),
            "walk_in_vmem": walk_in_vmem(q, v),
        }
    return _gdn_out(p, o, z, s, dt), stats


def gdn_step(p, h, carry, s, dt):
    """One position ``h [B, D]`` against ``carry {"state" [B, Hv, K, K]
    float32, "conv" [B, taps - 1, (2 Hk + Hv) K]}``: ``(out [B, D], new
    carry)``."""
    qkv, z, ba = _gdn_products(p, h, s, dt)
    with part("gdn_scan"):
        held = carry["conv"]
        taps = jnp.concatenate([held, qkv[:, None].astype(held.dtype)], 1)
        conv = (p["conv"][None] * taps.astype(jnp.float32)).sum(1)
        q, k, v, g, beta = _rule_inputs(p, conv, ba, s, dt)
        o, state = delta_step(q, k, v, g, beta, carry["state"])
    return _gdn_out(p, o, z, s, dt), {"state": state, "conv": taps[:, 1:]}


def _qkv(p, h, positions, s, dt):
    """``(q [.., T, H, hd], its gate [.., T, H, hd] float32 after the
    sigmoid, k, v [.., T, G, hd])``: q and k normed a head and turned at
    ``positions [T]``."""
    cos, sin = rope_table(s, positions)
    both = _heads(p["q"], h, dt)
    q, gate = both[..., :s["hd"]], both[..., s["hd"]:]
    k = _heads(p["k"], h, dt)
    q = rotate(zc_norm(p["q_norm"], q, s["eps"], dt), cos, sin)
    k = rotate(zc_norm(p["k_norm"], k, s["eps"], dt), cos, sin)
    return q, jax.nn.sigmoid(gate.astype(jnp.float32)), k, _heads(p["v"], h, dt)


def attention_mixer(p, h, s, dt):
    """A full layer over ``h [B, T, D]``: ``(out, the gates' mean)``."""
    with part("attn"):
        q, gate, k, v = _qkv(p, h, jnp.arange(h.shape[1]), s, dt)
        out, _ = blocked_attention(
            q, k, v, block=QUERY_BLOCK, kernels=ATTENTION_KERNELS
        )
        out = (out.astype(jnp.float32) * gate).astype(dt)
        return jnp.einsum("bthe,hed->btd", out, p["o"].astype(dt)), gate.mean()


def attention_step(p, h, cache, pos, s, dt):
    """One position ``h [B, D]`` of a full layer: its key, turned at
    ``pos``, and value go to slot ``pos`` of ``cache {"k", "v"} [B, S, 1, G x
    hd]``; the query attends to the slots written in this segment."""
    with part("attn"):
        B, S = cache["k"].shape[:2]
        q, gate, k, v = _qkv(p, h[:, None], pos[None], s, dt)
        put = lambda c, row: jax.lax.dynamic_update_slice_in_dim(   # noqa: E731
            c, row.reshape(B, 1, 1, -1).astype(c.dtype), pos, axis=1
        )
        cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
        out = _attend_one(q[:, 0], cache["k"], cache["v"], jnp.arange(S) <= pos)
        out = (out.astype(jnp.float32) * gate[:, 0]).astype(dt)
        return jnp.einsum("bhe,hed->bd", out, p["o"].astype(dt)), cache


def _ffn(p, x, s, dt):
    """``(x + FFN(N(x)), the routed statistics)`` for ``x [..., D]``."""
    h = zc_norm(p["ffn_norm"]["w"], x, s["eps"], dt)
    y, stats = routed_ffn(p["moe"], p["shared"], h.reshape(-1, h.shape[-1]), s)
    return x + y.reshape(x.shape), stats


def residual_bytes(cfg: dict, tokens: int) -> int:
    """Roughly what a differentiated pass over ``tokens`` tokens keeps
    without recomputation, in the compute dtype unless said: per layer the
    residual stream and its two normed copies; a linear mixer's one wide
    product, the conv's sum and the rule's inputs (float32), the rule's
    output and the gated one; a full mixer's queries with their gates, keys,
    values and output; the shared expert's three wide tensors over every
    token and the held experts' over the sorted buffer's rows."""
    s = _sizes(cfg)
    conv = (2 * s["Hk"] + s["Hv"]) * s["dk"]
    wide = s["Hv"] * s["dk"]
    rows = moe.row_bound(tokens, s["K"], s["held"], s["E"]) / max(tokens, 1)
    total = 0
    for kind in layer_kinds(cfg):
        per_token = 2 * 4 * s["D"]
        if kind == "linear":
            per_token += 2 * (conv + wide) + 4 * (conv + 3 * wide) + 2 * 2 * wide
        else:
            per_token += 2 * (3 * s["H"] + 2 * s["G"]) * s["hd"]
        per_token += 2 * (3 * s["Fs"] + rows * (3 * s["Fm"] + s["D"]))
        total += tokens * per_token
    return int(total)


def forward(params: dict, x, cfg: dict, dt, residual: int):
    """The learn pass: ``x [B, T, D]`` -> ``(x, stats)``; ``params`` is
    ``{"layer<i>": leaves}``, ``stats`` the counters and, a list a layer,
    what ``routed_ffn`` reports."""
    s = _sizes(cfg)
    linear, gates, routed = [], [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def layer(p, x, kind=kind):
            h = zc_norm(p["attn_norm"]["w"], x, s["eps"], dt)
            if kind == "linear":
                out, seen = gdn_mixer(p["gdn"], h, s, dt)
            else:
                out, seen = attention_mixer(p["attn"], h, s, dt)
            x, stats = _ffn(p, x + out, s, dt)
            return x, seen, stats

        layer = recomputed(layer, residual, REMAT_ABOVE_BYTES)
        x, seen, stats = layer(params[f"layer{i}"], x)
        (linear if kind == "linear" else gates).append(seen)
        routed.append(stats)
    pick = lambda name: jnp.stack([st[name] for st in linear])  # noqa: E731
    return x, {
        "state_abs_max": pick("state_abs_max").max(),
        **{
            name: pick(name).mean() for name in (
                "decay_mean", "beta_mean", "gram_in_vmem", "walk_in_vmem",
            )
        },
        # a cut of fewer than PERIOD layers has no full layer: 0 then
        "gate_mean": jnp.stack(gates).mean() if gates else jnp.float32(0.0),
        "scores_in_vmem": scores_in_vmem(
            jax.ShapeDtypeStruct((s["hd"],), dt), ATTENTION_KERNELS
        ) if gates else jnp.float32(0.0),
        "shared_gate_mean": jnp.stack(
            [r["shared_gate"] for r in routed]
        ).mean(),
        "routed": routed,
    }


def decode(params: dict, x, cache: dict, pos, cfg: dict, dt):
    """An acting step: ``x [B, D]`` at ``pos`` -> ``(x, new cache, what
    ``routed_ffn`` reports, a list a layer)``."""
    s = _sizes(cfg)
    new = {"linear": list(cache["linear"]), "full": list(cache["full"])}
    seen = {"linear": 0, "full": 0}
    routed = []
    for i, kind in enumerate(layer_kinds(cfg)):
        p = params[f"layer{i}"]
        h = zc_norm(p["attn_norm"]["w"], x, s["eps"], dt)
        n = seen[kind]
        if kind == "linear":
            out, new[kind][n] = gdn_step(p["gdn"], h, new[kind][n], s, dt)
        else:
            out, new[kind][n] = attention_step(
                p["attn"], h, new[kind][n], pos, s, dt
            )
        seen[kind] = n + 1
        x, stats = _ffn(p, x + out, s, dt)
        routed.append(stats)
    tally = moe.count_reads(cache[moe.EXPERTS_READ], [r["read"] for r in routed])
    return x, {**new, moe.EXPERTS_READ: tally}, routed


class GdnMoETrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``. A whole-segment apply sows :data:`COUNTERS` into
    the counters collection, ``load [layers, n_routed]`` and ``overflow``
    into the ``moe`` collection and, on request, each token's chosen experts
    and the routers' inputs into ``moe_routing`` (``models/attention.py``
    names the three); an acting step the chosen experts alone."""

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
            f"layer{i}": LayerLeaves(kind, c, name=f"layer{i}")()
            for i, kind in enumerate(layer_kinds(c))
        }
        norm = Leaves((("w", (int(c["hidden_size"]),), _ZEROS),), name="norm")()
        if cache is not None:
            x, cache, routed = decode(params, x, cache, pos, c, dt)
            self.sow(ROUTING_COLLECTION, "experts", [r["experts"] for r in routed])
            return zc_norm(norm["w"], x, eps, jnp.float32), cache
        tokens = x.shape[0] * x.shape[1]
        x, stats = forward(params, x, c, dt, residual=residual_bytes(c, tokens))
        routed = stats.pop("routed")
        for name, value in stats.items():
            self.sow(COUNTERS_COLLECTION, name, value)
        self.sow(MOE_COLLECTION, "load", jnp.stack([r["load"] for r in routed]))
        self.sow(MOE_COLLECTION, "overflow", sum(r["overflow"] for r in routed))
        for what in ("experts", "inputs"):
            self.sow(ROUTING_COLLECTION, what, [r[what] for r in routed])
        return zc_norm(norm["w"], x, eps, jnp.float32)


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache, two kinds side by side: ``{"linear":
    [{"state" [envs, Hv, K, K] float32, "conv" [envs, taps - 1, (2 Hk + Hv)
    K]}, ...], "full": [{"k", "v" [envs, horizon, 1, G x hd]}, ...]}``, a
    dict a layer of the kind; the conv tails and the caches in the compute
    dtype, keys held rotated. Beside them the routed layers' tally of the
    experts they read (``ops/moe.py::no_reads``)."""
    s = _sizes(cfg)
    kinds = layer_kinds(cfg)
    conv = (2 * s["Hk"] + s["Hv"]) * s["dk"]
    return {
        "linear": [
            {
                "state": jnp.zeros(
                    (num_envs, s["Hv"], s["dk"], s["dk"]), jnp.float32
                ),
                "conv": jnp.zeros((num_envs, s["taps"] - 1, conv), dtype),
            }
            for _ in range(kinds.count("linear"))
        ],
        "full": [
            {
                name: jnp.zeros((num_envs, horizon, 1, s["G"] * s["hd"]), dtype)
                for name in ("k", "v")
            }
            for _ in range(kinds.count("full"))
        ],
        moe.EXPERTS_READ: moe.no_reads(),
    }


def reset_recurrent(cache: dict, wrap) -> dict:
    """``cache`` with the linear leaves (matrix states and conv tails)
    zeroed where ``wrap`` (a scalar bool) is set: neither has a position a
    mask could hide. The full layers' rows are left as they are: the
    position masks what is stale."""
    zero = lambda x: jnp.where(wrap, jnp.zeros_like(x), x)   # noqa: E731
    return dict(cache, linear=jax.tree.map(zero, cache["linear"]))


# nothing moves the router: no bias rule
FAMILY = Family(
    trunk=GdnMoETrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, reset_recurrent=reset_recurrent, counters=COUNTERS,
    moe_stats=moe_stats,
)
