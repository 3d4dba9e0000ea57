"""Window and full grouped-query layers with a gate a head, over
softmax-routed experts beside a shared one, as a trajectory trunk.

The fourth block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='swa_moe'``; ``models/attention.py`` has the table
of families and the heads every family shares). Its layers are those of
Laguna-S-2.1 (poolside, ``config.json``: hidden 3072, ``layer_types`` full :
sliding 1 : 3, 48 query heads in a full layer and 72 in a sliding one over
8 key-value heads of 128, ``sliding_window`` 512, ``gating`` per-head, a
rotary table a layer type, ``mlp_only_layers`` [0] with a SwiGLU of 12288,
then 256 routed experts of 1024, 10 a token, ``norm_topk_prob``,
``moe_routed_scaling_factor`` 2.5, one shared expert of 1024,
``rms_norm_eps`` 1e-6). Layer ``l`` is full when ``l`` is a multiple of
:data:`PERIOD`, else sliding; the first ``first_k_dense_replace`` layers
are dense, the rest routed. ``x`` the residual stream, every layer

    x += Attn_l(RMSNorm(x))        x += FFN_l(RMSNorm(x))

then a last RMSNorm in float32; the input is ``Dense(obs -> hidden)``. No
biases anywhere.

**Attention** of a layer with ``n`` query heads (``num_heads`` full,
``window_heads`` sliding), ``h`` its normed input: ``q = W_q h`` as
``[T, n, hd]``, ``k = W_k h``, ``v = W_v h`` as ``[T, G, hd]``; ``q`` and
``k`` turned by the layer type's rotary table (:data:`ROPE`,
:func:`inv_freq`) at the position in the segment: a sliding layer all of
the head at theta 10 000; a full layer the first half of the head at YaRN
frequencies, ``cos`` and ``sin`` times the table's ``attention_factor``.
Pairs are ``(x[i], x[i + rot / 2])``. Scores ``q k^T / sqrt(hd)`` within
the group, causal, in a sliding layer over keys ``t - window + 1 .. t``;
softmax in float32; ``g = sigmoid(W_g h)``, one a head, multiplies the
head's output before ``W_o``.

**FFN**: a dense layer's SwiGLU of ``intermediate_size``; a routed layer's
``logits = W_r h`` (float32 at ``Precision.HIGHEST``: near-ties decide
which experts a token gets), ``p = softmax(logits)`` over all
``n_routed_experts``, the ``num_experts_per_tok`` largest, ``w_i = p_i /
sum_top p x routed_scaling_factor``, normalised over all of them whether
held here or not; ``y = sum_{i held} w_i E_i(h) + S(h)``, ``E_i`` SwiGLU of
``moe_intermediate_size`` and ``S`` of ``shared_intermediate_size``. This
chip holds ``num_held`` experts from ``first_held`` on (``ops/moe.py``
says what that means and has both forms of the product).

**The loss stops at the router's product**, here and not by a key, for
``models/latent_moe.py``'s reason: on one chip of an expert-parallel group
the held experts' outputs alone would steer the router. There is no
selection bias and no auxiliary loss (the config gives no coefficient), so
nothing moves the router on one chip; an expert axis lifts that (ROADMAP).

**Two paths compute it**, from one parameter tree and the same functions
of it (:func:`forward`, :func:`decode`). The learn pass runs whole
segments: attention a block of queries at a time
(``ops/ring_attention.py::blocked_attention``) after the rotation, the
routed layer sorted by expert. An acting step runs one position against
the carry (:func:`acting_cache`): for each full layer ``[envs, T, G, hd]``
keys and values, for each sliding layer a ring of ``sliding_window`` slots
(slot = position mod window), keys stored already rotated at their own
positions; the held experts in their dense form. No leaf is recurrent: a
wrap to a new segment moves the position and the masks hide what is stale.

**Recomputation.** Past :data:`REMAT_ABOVE_BYTES` of estimated residuals
(:func:`residual_bytes`, from the pass's own shapes, no key) each layer is
a ``jax.checkpoint`` (``models/attention.py::recomputed`` has the rule).
At the published widths a 4096-token minibatch's five layers keep about
2 GB beside 11.7 GB of parameters, gradients and Adam moments.

Matrices initialise normal(0, ``INIT_STD``), norms 1 (the config gives no
range).
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from surreal_tpu.models.attention import (
    COUNTERS_COLLECTION, MOE_COLLECTION, ROUTING_COLLECTION, Family,
    recomputed,
)
from surreal_tpu.models.ssm_hybrid import Leaves, _attend_one, _heads
from surreal_tpu.ops import moe
from surreal_tpu.ops.ring_attention import blocked_attention
from surreal_tpu.utils.phases import part

BLOCK = "swa_moe"
INIT_STD = 0.02
# one value in use, so constants and no keys: a full layer every fourth
# (``layer_types``), and the config's ``rope_parameters``, a table a type
PERIOD = 4
ROPE = {
    "full": dict(
        theta=500_000.0, partial=0.5, factor=128.0, original=8192,
        beta_fast=32.0, beta_slow=1.0, attention_factor=1.4852030263919618,
    ),
    "window": dict(theta=10_000.0, partial=1.0),
}
# estimated residual bytes of a differentiated pass past which each layer
# is recomputed in the backward: a sixteenth of a v5e's memory, a fifth of
# what the training state leaves at the published widths
REMAT_ABOVE_BYTES = 2**30
QUERY_BLOCK = 256
# the part of utils/phases.py a layer type's attention is scoped under: the
# two head counts are the mechanism
PART = {"full": "attn_full", "window": "attn_window"}

# model.encoder keys this family reads beside the shared ones (kind, block,
# num_layers, num_heads: a FULL layer's query heads, act_impl), with the
# values an unset (None) key takes: poolside/Laguna-S-2.1 config.json, one
# chip of 32
FAMILY_DEFAULTS = dict(
    hidden_size=3072,
    window_heads=72,
    num_kv_heads=8,
    attn_head_dim=128,
    sliding_window=512,
    intermediate_size=12288,
    moe_intermediate_size=1024,
    shared_intermediate_size=1024,
    n_routed_experts=256,
    num_experts_per_tok=10,
    routed_scaling_factor=2.5,
    first_k_dense_replace=1,
    rms_norm_eps=1e-6,
    first_held=0,
    num_held=8,
)
# what a whole-segment apply sows, one scalar each (``{sown name: (metrics
# row, how the row reduces it over an iteration's minibatch steps)}``): the
# keys a windowed query saw, and the mean of the heads' gates (0.5 at the
# initialisation; a gate that closes or saturates shows before the loss does)
COUNTERS = {
    "window_keys_mean": ("attn/window_keys_mean", "mean"),
    "gate_mean": ("attn/gate_mean", "mean"),
}


def resolve(encoder_cfg: dict) -> dict:
    """``encoder_cfg`` with this family's unset keys at their defaults."""
    out = dict(encoder_cfg)
    for k, v in FAMILY_DEFAULTS.items():
        if out.get(k) is None:
            out[k] = v
    G = int(out["num_kv_heads"])
    for key in ("num_heads", "window_heads"):
        if int(out[key]) % G:
            raise ValueError(
                f"{key}={out[key]} must be a multiple of num_kv_heads={G}"
            )
    if int(out["attn_head_dim"]) % 4:
        raise ValueError("attn_head_dim: a full layer turns half of it, in pairs")
    if int(out["num_layers"]) <= int(out["first_k_dense_replace"]):
        raise ValueError(
            f"num_layers={out['num_layers']} leaves no routed layer after "
            f"first_k_dense_replace={out['first_k_dense_replace']}"
        )
    moe.check_held(out["first_held"], out["num_held"], out["n_routed_experts"])
    return out


def layer_kinds(cfg: dict) -> list:
    """The trunk's layers in order: ``(kind, dense)``, ``kind`` 'full' or
    'window', ``dense`` true for a leading dense layer."""
    first = int(cfg["first_k_dense_replace"])
    return [
        ("full" if i % PERIOD == 0 else "window", i < first)
        for i in range(int(cfg["num_layers"]))
    ]


def _sizes(cfg: dict) -> dict:
    return dict(
        D=int(cfg["hidden_size"]), G=int(cfg["num_kv_heads"]),
        hd=int(cfg["attn_head_dim"]), W=int(cfg["sliding_window"]),
        H={"full": int(cfg["num_heads"]), "window": int(cfg["window_heads"])},
        F=int(cfg["intermediate_size"]), Fm=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["shared_intermediate_size"]),
        E=int(cfg["n_routed_experts"]), K=int(cfg["num_experts_per_tok"]),
        held=int(cfg["num_held"]), first=int(cfg["first_held"]),
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
    )


# -- the rotary tables ---------------------------------------------------------

def inv_freq(kind: str, head_dim: int) -> np.ndarray:
    """The ``rot / 2`` frequencies of a layer type's table (float64),
    ``rot = head_dim x partial``. 'window': ``theta^(-2i / rot)``. 'full',
    YaRN (Peng et al. 2023, arXiv:2309.00071, section 3.2, as the config's
    keys parametrise it): that frequency and it over ``factor``, blended
    by a linear ramp over the pair index between the correction dimensions
    of ``beta_fast`` and ``beta_slow`` turns in ``original`` positions (a
    pair that turns more than ``beta_fast`` times keeps its frequency, one
    under ``beta_slow`` is interpolated)."""
    t = ROPE[kind]
    rot = int(head_dim * t["partial"])
    plain = t["theta"] ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if "factor" not in t:
        return plain

    def correction_dim(turns: float) -> float:
        return rot * math.log(t["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(t["theta"])
        )

    low = max(math.floor(correction_dim(t["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(t["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return plain / t["factor"] * ramp + plain * (1.0 - ramp)


def rope_table(kind: str, head_dim: int, positions):
    """``(cos, sin) [T, rot / 2]`` float32 of a layer type at ``positions
    [T]``, times the table's ``attention_factor`` where it has one."""
    freq = jnp.asarray(inv_freq(kind, head_dim), jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * freq
    scale = jnp.float32(ROPE[kind].get("attention_factor", 1.0))
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate(x, cos, sin):
    """``x [..., T, H, hd]`` with its first ``rot = 2 x cos.shape[-1]``
    dimensions turned, pairs ``(x[i], x[i + rot / 2])``, the rest as they
    are; float32 inside, ``x``'s dtype out."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [a * c - b * s, b * c + a * s, x32[..., 2 * half:]], axis=-1
    ).astype(x.dtype)


# -- parameters ----------------------------------------------------------------

def _normal(key, shape, dtype=jnp.float32):
    return INIT_STD * jax.random.normal(key, shape, dtype)


_ONES = nn.initializers.ones


def _swiglu_spec(D: int, F: int) -> tuple:
    return (("gate", (D, F), _normal), ("up", (D, F), _normal),
            ("down", (F, D), _normal))


class LayerLeaves(nn.Module):
    """One layer's float32 leaves: ``{"attn_norm", "attn", "ffn_norm",
    "ffn" | ("moe", "shared")}``."""

    kind: str
    dense: bool
    cfg: dict

    @nn.compact
    def __call__(self) -> dict:
        s = _sizes(self.cfg)
        D, H, G, hd = s["D"], s["H"][self.kind], s["G"], s["hd"]
        norm = (("scale", (D,), _ONES),)
        attn = (
            ("q", (D, H, hd), _normal), ("k", (D, G, hd), _normal),
            ("v", (D, G, hd), _normal), ("gate", (D, H), _normal),
            ("o", (H, hd, D), _normal),
        )
        out = {
            "attn_norm": Leaves(norm, name="attn_norm")(),
            "attn": Leaves(attn, name="attn")(),
            "ffn_norm": Leaves(norm, name="ffn_norm")(),
        }
        if self.dense:
            out["ffn"] = Leaves(_swiglu_spec(D, s["F"]), name="ffn")()
            return out
        held, Fm = s["held"], s["Fm"]
        routed = (
            ("router", (D, s["E"]), _normal),
            ("gate", (held, D, Fm), _normal), ("up", (held, D, Fm), _normal),
            ("down", (held, Fm, D), _normal),
        )
        out["moe"] = Leaves(routed, name="moe")()
        out["shared"] = Leaves(_swiglu_spec(D, s["Fs"]), name="shared")()
        return out


# -- the layers, as functions of their leaves ----------------------------------

def rms_norm(p, x, eps: float, dtype):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(dtype)


def _head_gates(p, h, dt):
    """``sigmoid(W_g h)``, one a head, float32."""
    return jax.nn.sigmoid((h @ p["gate"].astype(dt)).astype(jnp.float32))


def attention_mixer(p, h, s, dt, kind: str):
    """A full or window layer over ``h [B, T, D]``: ``(out, keys a query
    saw on average, the gates' mean)``."""
    with part(PART[kind]):
        cos, sin = rope_table(kind, s["hd"], jnp.arange(h.shape[1]))
        q = rotate(_heads(p["q"], h, dt), cos, sin)
        k = rotate(_heads(p["k"], h, dt), cos, sin)
        # kernels=False: this family's blocks keep the ``lax`` form, and its
        # program is the one it had, until ppo_lift_laguna_16x1024's check can
        # tell a rounding from a fault: it compares learn rows through the
        # value clip's kinks, and a change of the learn pass's rounding draws
        # its listed seeds again (PERF.md section 7, first item; ROADMAP S6 y)
        out, seen = blocked_attention(
            q, k, _heads(p["v"], h, dt),
            window=s["W"] if kind == "window" else None, block=QUERY_BLOCK,
            kernels=False,
        )
        g = _head_gates(p, h, dt)
        out = (out.astype(jnp.float32) * g[..., None]).astype(dt)
        return jnp.einsum("bthe,hed->btd", out, p["o"].astype(dt)), seen, g.mean()


def attention_step(p, h, cache, pos, s, dt, kind: str):
    """One position ``h [B, D]`` of a full or window layer: its key, turned at
    ``pos``, and value go to slot ``pos`` (``pos mod S`` in a window layer's
    ring) of ``cache {"k", "v"} [B, S, G, hd]``, a head a row (``_attend_one``
    reads that off the row's width); the query attends to the slots written in
    this segment (``models/ssm_hybrid.py::attention_step`` argues the ring)."""
    with part(PART[kind]):
        S = cache["k"].shape[1]
        slot = pos % S if kind == "window" else pos
        cos, sin = rope_table(kind, s["hd"], pos[None])
        one = lambda w: _heads(w, h, dt)[:, None]      # [B, 1, heads, hd]
        put = lambda c, row: jax.lax.dynamic_update_slice_in_dim(
            c, row.astype(c.dtype), slot, axis=1
        )
        cache = {
            "k": put(cache["k"], rotate(one(p["k"]), cos, sin)),
            "v": put(cache["v"], one(p["v"])),
        }
        q = rotate(one(p["q"]), cos, sin)[:, 0]
        valid = jnp.arange(S) <= pos    # every slot once pos >= S: a ring
        out = _attend_one(q, cache["k"], cache["v"], valid)
        out = (out.astype(jnp.float32) * _head_gates(p, h, dt)[..., None]).astype(dt)
        return jnp.einsum("bhe,hed->bd", out, p["o"].astype(dt)), cache


def routed_ffn(p, shared, x, s):
    """A routed layer over ``x [N, D]`` (``shared`` None: no shared expert,
    ``models/dsa_moe.py``; with a leaf ``"token_gate" [D]`` the shared
    expert's output is times ``sigmoid(x . token_gate)`` a token, float32,
    and the gate's mean is in the statistics as ``"shared_gate"``:
    ``models/gdn_moe.py``): ``(y, {"load" [E], "overflow",
    "experts" [N, K], "inputs" [N, D], "read": the share of the held
    experts whose weights the pass read})``."""
    E, K, held, first = s["E"], s["K"], s["held"], s["first"]
    with part("moe_route"):
        # the loss stops here (module docstring)
        logits = jax.lax.stop_gradient(jnp.dot(
            x.astype(jnp.float32), p["router"],
            precision=jax.lax.Precision.HIGHEST,
        ))
        idx, weights, _ = moe.route(logits, None, K, s["scale"], "softmax")
        # an acting step's few tokens run every held expert; a learn pass
        # sorts (ops/moe.py has both forms and why)
        dense = moe.dense_form(x.shape[0])
        if dense:
            overflow = jnp.zeros((), jnp.float32)
        else:
            token, weight, valid, sizes, overflow = moe.sort_by_expert(
                idx, weights, first, held,
                moe.row_bound(x.shape[0], K, held, E),
            )
        stats = {
            "load": moe.expert_load(idx, E),
            "overflow": overflow.astype(jnp.float32),
            "experts": idx, "inputs": x,
        }
    with part("moe_experts"):
        if dense:
            y, stats["read"] = moe.held_experts_dense(
                x, idx, weights, first, E, p["gate"], p["up"], p["down"]
            )
        else:
            y, stats["read"] = moe.held_experts(
                x, token, weight, valid, sizes, p["gate"], p["up"], p["down"]
            ), jnp.float32(1.0)
        if shared is not None:
            extra = moe.swiglu(x, shared["gate"], shared["up"], shared["down"])
            if "token_gate" in shared:
                open_ = jax.nn.sigmoid(jnp.dot(
                    x, shared["token_gate"].astype(x.dtype),
                    preferred_element_type=jnp.float32,
                ))
                stats["shared_gate"] = open_.mean()
                extra = (extra.astype(jnp.float32) * open_[:, None]).astype(extra.dtype)
            y = y + extra
    return y, stats


def _ffn(p, x, s, dt):
    """``(x + FFN(RMSNorm(x)), the routed statistics or {})`` for ``x [...,
    D]``."""
    h = rms_norm(p["ffn_norm"], x, s["eps"], dt)
    if "ffn" in p:
        with part("dense_ffn"):
            f = p["ffn"]
            return x + moe.swiglu(h, f["gate"], f["up"], f["down"]), {}
    y, stats = routed_ffn(p["moe"], p["shared"], h.reshape(-1, h.shape[-1]), s)
    return x + y.reshape(x.shape), stats


def residual_bytes(cfg: dict, tokens: int) -> int:
    """Roughly what a differentiated pass over ``tokens`` tokens keeps
    without recomputation, in the compute dtype: per layer the residual
    stream and its two normed copies, queries, outputs, keys and values;
    a SwiGLU's three wide tensors, over every token in a dense layer and a
    shared expert, over the sorted buffer's rows in the held experts."""
    s = _sizes(cfg)
    total = 0
    for kind, dense in layer_kinds(cfg):
        per_token = 4 * s["D"] + 2 * (s["H"][kind] + s["G"]) * s["hd"]
        if dense:
            per_token += 3 * s["F"]
        else:
            rows = moe.row_bound(tokens, s["K"], s["held"], s["E"]) / max(tokens, 1)
            per_token += 3 * s["Fs"] + rows * (3 * s["Fm"] + s["D"])
        total += 2 * tokens * per_token
    return int(total)


def forward(params: dict, x, cfg: dict, dt, residual: int):
    """The learn pass: ``x [B, T, D]`` -> ``(x, stats)``; ``params`` is
    ``{"layer<i>": leaves}``, ``stats`` the counters and, a list a routed
    layer, what :func:`routed_ffn` reports; ``residual`` the estimate the
    recomputation rule reads."""
    s = _sizes(cfg)
    seen, gates, routed = [], [], []
    for i, (kind, _) in enumerate(layer_kinds(cfg)):
        def layer(p, x, kind=kind):
            h = rms_norm(p["attn_norm"], x, s["eps"], dt)
            out, keys, gate = attention_mixer(p["attn"], h, s, dt, kind)
            x, stats = _ffn(p, x + out, s, dt)
            return x, keys, gate, stats

        layer = recomputed(layer, residual, REMAT_ABOVE_BYTES)
        x, keys, gate, stats = layer(params[f"layer{i}"], x)
        gates.append(gate)
        if kind == "window":
            seen.append(keys)
        if stats:
            routed.append(stats)
    return x, {
        "window_keys_mean": jnp.stack(seen).mean(),
        "gate_mean": jnp.stack(gates).mean(),
        "routed": routed,
    }


def decode(params: dict, x, cache: dict, pos, cfg: dict, dt):
    """An acting step: ``x [B, D]`` at ``pos`` -> ``(x, new cache, what
    :func:`routed_ffn` reports, a list a routed layer)``."""
    s = _sizes(cfg)
    new = {"full": list(cache["full"]), "window": list(cache["window"])}
    seen = {"full": 0, "window": 0}
    routed = []
    for i, (kind, _) in enumerate(layer_kinds(cfg)):
        p = params[f"layer{i}"]
        h = rms_norm(p["attn_norm"], x, s["eps"], dt)
        n = seen[kind]
        out, new[kind][n] = attention_step(
            p["attn"], h, new[kind][n], pos, s, dt, kind
        )
        seen[kind] = n + 1
        x, stats = _ffn(p, x + out, s, dt)
        if stats:
            routed.append(stats)
    tally = moe.count_reads(cache[moe.EXPERTS_READ], [r["read"] for r in routed])
    return x, {**new, moe.EXPERTS_READ: tally}, routed


class SwaMoETrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``. A whole-segment apply sows :data:`COUNTERS` into
    the counters collection, ``load [routed layers, n_routed]`` and
    ``overflow`` into the ``moe`` collection and, on request, each token's
    chosen experts and the router's input into ``moe_routing``
    (``models/attention.py`` names the three); an acting step the chosen
    experts alone."""

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
            f"layer{i}": LayerLeaves(kind, dense, c, name=f"layer{i}")()
            for i, (kind, dense) in enumerate(layer_kinds(c))
        }
        norm = Leaves(
            (("scale", (int(c["hidden_size"]),), _ONES),), name="norm"
        )()
        if cache is not None:
            x, cache, routed = decode(params, x, cache, pos, c, dt)
            self.sow(ROUTING_COLLECTION, "experts", [r["experts"] for r in routed])
            return rms_norm(norm, x, eps, jnp.float32), cache
        tokens = x.shape[0] * x.shape[1]
        x, stats = forward(
            params, x, c, dt,
            residual=residual_bytes(c, tokens),
        )
        routed = stats.pop("routed")
        for name, value in stats.items():
            self.sow(COUNTERS_COLLECTION, name, value)
        self.sow(MOE_COLLECTION, "load", jnp.stack([r["load"] for r in routed]))
        self.sow(MOE_COLLECTION, "overflow", sum(r["overflow"] for r in routed))
        for what in ("experts", "inputs"):
            self.sow(ROUTING_COLLECTION, what, [r[what] for r in routed])
        return rms_norm(norm, x, eps, jnp.float32)


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache, two kinds side by side: ``{"full": [{"k",
    "v" [envs, horizon, G, hd]}, ...], "window": [{"k", "v" [envs,
    min(window, horizon), G, hd]}, ...]}``, a dict a layer of the kind, in
    the compute dtype; keys are held rotated. Beside them the routed layers'
    tally of the experts they read (``ops/moe.py::no_reads``)."""
    s = _sizes(cfg)
    kinds = [k for k, _ in layer_kinds(cfg)]
    kv = lambda slots: {
        name: jnp.zeros((num_envs, slots, s["G"], s["hd"]), dtype)
        for name in ("k", "v")
    }
    return {
        "full": [kv(horizon) for _ in range(kinds.count("full"))],
        "window": [
            kv(min(s["W"], horizon)) for _ in range(kinds.count("window"))
        ],
        moe.EXPERTS_READ: moe.no_reads(),
    }


def moe_stats(collection: dict) -> dict:
    """``{"load": [routed layers, n_routed], "overflow": scalar}`` from the
    ``moe`` collection of one whole-segment ``apply``."""
    trunk = collection["trunk"]
    return {"load": trunk["load"][-1], "overflow": trunk["overflow"][-1]}


def routing_of(collection: dict, what: str = "experts") -> list:
    """``[routed layers][N, top_k]`` chosen experts (or, ``what='inputs'``,
    the ``[routed layers][N, hidden]`` the router scored) from the
    ``moe_routing`` collection of one ``apply`` (the benchmark's reference
    check reads it; no training path does)."""
    return list(collection["trunk"][what][-1])


# no leaf of the carry is recurrent and nothing moves the router: no
# ``reset_recurrent``, no bias rule
FAMILY = Family(
    trunk=SwaMoETrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, counters=COUNTERS, moe_stats=moe_stats,
)
