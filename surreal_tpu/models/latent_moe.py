"""Latent-attention, routed-expert blocks as a trajectory trunk.

The second block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='mla_moe'``; ``models/attention.py`` has the first
and the heads both share). Per layer, ``x`` the residual stream:

    h = x + MLA(RMSNorm(x))        y = h + FFN(RMSNorm(h))

then a final RMSNorm in float32. The input is ``Dense(obs -> hidden)``;
positions enter through the rotary part alone. No biases anywhere.

**MLA** (multi-head latent attention, DeepSeek-V2 arXiv:2405.04434):
``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` as heads of ``[q_nope | q_pe]``;
``[c_kv | k_pe] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; rotary embedding
(interleaved pairs, position = index in the segment) on ``q_pe`` and on the
one ``k_pe`` all heads share; ``[k_nope | v]_h = c_kv W_kvb,h``; scores
``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``, causal, softmax in
float32; ``o = concat_h(P v) W_o``.

Two paths compute it. The learn pass expands keys and values and goes
through ``ops/ring_attention.py::full_attention``. The acting step caches
``[c_kv | k_pe]`` alone, ``[envs, T, kv_lora + rope]`` a layer, and attends
in the latent space with ``W_kvb`` absorbed: ``q~_h = q_nope,h
(W_kvb,h^K)^T``, scores ``(q~_h . c_kv + q_pe . k_pe) / sqrt(nope + rope)``,
``o_h = (P c_kv) W_kvb,h^V``. Nothing per head is held across steps.

Two cases beside the published JoyAI-LLM-Flash one, both read from the
layer's own ``cfg`` and used by ``models/kda_moe.py`` (a family that leaves
the keys out; this family's :func:`resolve` always fills them):
``q_lora_rank`` none projects the queries in one product ``q = x W_q`` (no
low-rank pair, no query norm), and ``rope_theta`` none turns nothing
(``k_pe`` is a plain shared key part, ``q_pe`` a plain query part), in the
expanded pass and the absorbed decode alike.

**FFN**: the first ``first_k_dense_replace`` layers a SwiGLU of
``intermediate_size``; the others the routed layer of ``ops/moe.py``, told
which experts this chip holds (``first_held``, ``num_held`` of
``n_routed_experts``), plus ``n_shared_experts`` shared SwiGLU of
``moe_intermediate_size`` every token passes. The router's product runs in
float32 at ``Precision.HIGHEST``: near-ties decide which experts a token
gets. Each routed layer sows ``load [n_routed]`` and ``overflow`` into the
``moe`` collection (``apply(..., mutable=['moe'])`` to read them).

The selection bias ``e_score_correction_bias`` is a parameter without a
gradient; :func:`update_router_bias` is its rule, applied by the learner
after each optimizer step.

The loss stops at the router's product: on one chip of an expert-parallel
group it would reach the router through the held experts' outputs alone
(the other chips' terms are absent with their experts), and Adam then
steers the tokens off the experts held here or onto them: the held share
fell from 1/16 to 0.0005 in 30 iterations on the chip and rose to 0.3 at
toy widths (PERF.md, PR 33). An expert axis that all-reduces the router's
gradient over the group lifts this (ROADMAP); the bias rule runs either way.

Trunk matrices initialise normal(0, ``INIT_STD``): the source gives no
range, and an orthogonal init is a QR a matrix (200 of them here).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from surreal_tpu.models.attention import (
    MOE_COLLECTION, ROUTING_COLLECTION, Family,
)
from surreal_tpu.ops import moe
from surreal_tpu.ops.ring_attention import _NEG_BIG, full_attention
from surreal_tpu.utils.phases import part

BLOCK = "mla_moe"
# the statistics are sown in MOE_COLLECTION, each token's chosen experts and
# the router's input, on request, in ROUTING_COLLECTION (models/attention.py)
ROUTED = "moe"           # a routed layer's submodule, in params and there
BIAS_NAME = "e_score_correction_bias"
INIT_STD = 0.02

# model.encoder keys this family reads beside the shared ones (kind,
# block, num_layers, num_heads, act_impl), with the values an unset (None)
# key takes: jdopensource/JoyAI-LLM-Flash config.json, one chip of 16
FAMILY_DEFAULTS = dict(
    hidden_size=2048,
    # this family always has the query's low-rank pair and the rotary turn:
    # LatentAttention's other two cases (q_lora_rank none, rope_theta none)
    # are reached by a family that leaves the keys out (models/kda_moe.py)
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    intermediate_size=7168,
    moe_intermediate_size=768,
    n_routed_experts=256,
    num_experts_per_tok=8,
    n_shared_experts=1,
    routed_scaling_factor=2.5,
    first_k_dense_replace=1,
    rope_theta=32e6,
    rms_norm_eps=1e-6,
    first_held=0,
    num_held=16,
    bias_update_speed=0.001,
)


def resolve(encoder_cfg: dict) -> dict:
    """``encoder_cfg`` with this family's unset keys at their defaults."""
    out = dict(encoder_cfg)
    for k, v in FAMILY_DEFAULTS.items():
        if out.get(k) is None:
            out[k] = v
    moe.check_held(out["first_held"], out["num_held"], out["n_routed_experts"])
    return out


def rope(x, positions, theta: float, heads: bool = False):
    """Rotary embedding over interleaved pairs: ``(x[2i], x[2i+1])`` turns
    by ``position x theta^(-2i/d)``. ``x [..., T, d]``, or ``[..., T, H,
    d]`` with ``heads``, at ``positions [T]``; float32 inside, ``x``'s
    dtype out."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq      # [T, d/2]
    if heads:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


class LatentAttention(nn.Module):
    """``cfg`` keys: hidden_size, num_heads, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, kv_lora_rank, rms_norm_eps; ``q_lora_rank``
    (none or absent: one query product ``q``, else the pair ``q_a``, ``q_b``
    around ``q_a_norm``) and ``rope_theta`` (none or absent: no rotary turn)."""

    cfg: dict
    dtype: Any = jnp.bfloat16

    def setup(self):
        c = self.cfg
        D, H = int(c["hidden_size"]), int(c["num_heads"])
        self.nope, self.rot = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
        self.vd, self.lat = int(c["v_head_dim"]), int(c["kv_lora_rank"])
        init = nn.initializers.normal(INIT_STD)
        mat = lambda name, shape: self.param(name, init, shape, jnp.float32)
        eps = float(c["rms_norm_eps"])
        if c.get("q_lora_rank") is None:
            self.q = mat("q", (D, H, self.nope + self.rot))
        else:
            self.q_a = mat("q_a", (D, int(c["q_lora_rank"])))
            self.q_b = mat("q_b", (int(c["q_lora_rank"]), H, self.nope + self.rot))
            self.q_norm = RMSNorm(eps, self.dtype, name="q_a_norm")
        self.kv_a = mat("kv_a", (D, self.lat + self.rot))
        self.kv_b = mat("kv_b", (self.lat, H, self.nope + self.vd))
        self.o = mat("o", (H, self.vd, D))
        self.kv_norm = RMSNorm(eps, self.dtype, name="kv_a_norm")

    def _project(self, x, positions):
        """``x [..., T, D]`` at ``positions [T]`` -> (``q_nope``, ``q_pe``
        ``[..., T, H, .]``, normed ``c_kv [..., T, lat]``, ``k_pe [..., T,
        rot]``), the two ``pe`` parts rotated where the layer has a
        ``rope_theta``."""
        dt, theta = self.dtype, self.cfg.get("rope_theta")
        if self.cfg.get("q_lora_rank") is None:
            q = jnp.einsum("...d,dhe->...he", x, self.q.astype(dt))
        else:
            q = jnp.einsum(
                "...r,rhd->...hd", self.q_norm(x @ self.q_a.astype(dt)),
                self.q_b.astype(dt),
            )
        ckv = x @ self.kv_a.astype(dt)
        turn = lambda part, heads=False: part if theta is None else rope(  # noqa: E731
            part, positions, float(theta), heads=heads
        )
        return (
            q[..., : self.nope],
            turn(q[..., self.nope:], heads=True),
            self.kv_norm(ckv[..., : self.lat]),
            turn(ckv[..., self.lat:]),
        )

    def __call__(self, x):
        """Expanded path: ``x [B, T, D]`` -> ``[B, T, D]``."""
        dt = self.dtype
        B, T, _ = x.shape
        q_nope, q_pe, c_kv, k_pe = self._project(x, jnp.arange(T))
        kv = jnp.einsum("btc,chd->bthd", c_kv, self.kv_b.astype(dt))
        k_pe = jnp.broadcast_to(k_pe[:, :, None], (*q_pe.shape[:3], self.rot))
        out = full_attention(
            jnp.concatenate([q_nope, q_pe], -1),
            jnp.concatenate([kv[..., : self.nope], k_pe], -1),
            kv[..., self.nope:], causal=True,
        )
        return jnp.einsum("bthd,hdm->btm", out, self.o.astype(dt))

    def decode(self, x, cache, pos):
        """Absorbed path: one position ``x [B, D]`` against the latent
        cache ``[B, T, lat + rot]``; returns ``([B, D], cache)`` with row
        ``pos`` written. Rows past ``pos`` are masked: their weight is an
        exact zero, so any finite values there (a wrapped segment's stale
        rows) change nothing, and a NaN there is a NaN in the output. The
        cache therefore starts as real zeros, handed in as an argument: the
        chip's compiler leaves out zeros made inside the jit when ``pos`` is
        a scan's own counter (tests/test_tpu_compile.py)."""
        dt = self.dtype
        q_nope, q_pe, c_kv, k_pe = self._project(x[:, None], pos[None])
        row = jnp.concatenate([c_kv, k_pe], -1).astype(cache.dtype)
        cache = jax.lax.dynamic_update_slice_in_dim(cache, row, pos, axis=1)
        lat, rot = cache[..., : self.lat], cache[..., self.lat:]
        kv_b = self.kv_b.astype(dt)
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], kv_b[..., : self.nope])
        scores = (
            jnp.einsum("bhc,btc->bht", q_lat, lat)
            + jnp.einsum("bhr,btr->bht", q_pe[:, 0], rot)
        ).astype(jnp.float32) / jnp.sqrt(jnp.float32(self.nope + self.rot))
        mask = jnp.arange(cache.shape[1]) <= pos
        p = jax.nn.softmax(jnp.where(mask[None, None], scores, _NEG_BIG), axis=-1)
        o_lat = jnp.einsum("bht,btc->bhc", p, lat.astype(jnp.float32)).astype(dt)
        out = jnp.einsum("bhc,chd->bhd", o_lat, kv_b[..., self.nope:])
        return jnp.einsum("bhd,hdm->bm", out, self.o.astype(dt)), cache


class SwiGLU(nn.Module):
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.normal(INIT_STD)
        D = x.shape[-1]
        return moe.swiglu(
            x,
            self.param("gate", init, (D, self.width), jnp.float32),
            self.param("up", init, (D, self.width), jnp.float32),
            self.param("down", init, (self.width, D), jnp.float32),
        )


class RoutedExperts(nn.Module):
    """Router over all experts, the held experts' part, the shared expert."""

    cfg: dict
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        """``x [..., D]`` -> ``([..., D], the share of the held experts whose
        weights the pass read)`` (``ops/moe.py``: under 1 where an acting
        step streams its live experts alone)."""
        c = self.cfg
        lead, D = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, D)
        E, K = int(c["n_routed_experts"]), int(c["num_experts_per_tok"])
        G, F = int(c["num_held"]), int(c["moe_intermediate_size"])
        init = nn.initializers.normal(INIT_STD)
        router = self.param("router", init, (D, E), jnp.float32)
        bias = self.param(BIAS_NAME, nn.initializers.zeros, (E,), jnp.float32)
        gate = self.param("gate", init, (G, D, F), jnp.float32)
        up = self.param("up", init, (G, D, F), jnp.float32)
        down = self.param("down", init, (G, F, D), jnp.float32)
        with part("moe_route"):
            # the loss stops here (module docstring)
            logits = jax.lax.stop_gradient(jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST,
            ))
            idx, weights, _ = moe.route(
                logits, bias, K, float(c["routed_scaling_factor"])
            )
            # an acting step's few tokens run every held expert; a learn
            # pass sorts (ops/moe.py has both forms and why)
            dense = moe.dense_form(x.shape[0])
            first = int(c["first_held"])
            if dense:
                overflow = jnp.zeros((), jnp.float32)
            else:
                token, weight, valid, sizes, overflow = moe.sort_by_expert(
                    idx, weights, first, G, moe.row_bound(x.shape[0], K, G, E)
                )
            self.sow(ROUTING_COLLECTION, "experts", idx)
            self.sow(ROUTING_COLLECTION, "inputs", x)
            self.sow(MOE_COLLECTION, "load", moe.expert_load(idx, E))
            self.sow(MOE_COLLECTION, "overflow", overflow.astype(jnp.float32))
        with part("moe_experts"):
            if dense:
                y, read = moe.held_experts_dense(
                    x, idx, weights, first, E, gate, up, down
                )
            else:
                y, read = moe.held_experts(
                    x, token, weight, valid, sizes, gate, up, down
                ), jnp.float32(1.0)
            for i in range(int(c["n_shared_experts"])):
                y = y + SwiGLU(F, self.dtype, name=f"shared{i}")(x)
        return y.reshape(*lead, D), read


class Block(nn.Module):
    cfg: dict
    dense: bool
    dtype: Any = jnp.bfloat16

    def setup(self):
        c = self.cfg
        eps = float(c["rms_norm_eps"])
        self.attn_norm = RMSNorm(eps, self.dtype)
        self.ffn_norm = RMSNorm(eps, self.dtype)
        self.attn = LatentAttention(c, self.dtype)
        if self.dense:
            self.ffn = SwiGLU(int(c["intermediate_size"]), self.dtype)
        else:
            self.moe = RoutedExperts(c, self.dtype)

    def _ffn(self, h):
        """``(h + FFN(RMSNorm(h)), the routed experts' read share or None)``."""
        if self.dense:
            with part("dense_ffn"):
                return h + self.ffn(self.ffn_norm(h)), None
        y, read = self.moe(self.ffn_norm(h))
        return h + y, read

    def __call__(self, x):
        with part("attn"):
            h = x + self.attn(self.attn_norm(x))
        return self._ffn(h)[0]

    def decode(self, x, cache, pos):
        """``(x, the routed experts' read share or None, the layer's
        cache)``."""
        with part("attn"):
            a, cache = self.attn.decode(self.attn_norm(x), cache, pos)
        return *self._ffn(x + a), cache


class LatentMoETrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``."""

    cfg: dict               # resolve()d model.encoder subtree
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, obs, *, cache=None, pos=None, replicate_ok: bool = False):
        del replicate_ok    # no mesh path: one chip's share runs unsharded
        c, dt = self.cfg, self.compute_dtype
        x = nn.Dense(
            int(c["hidden_size"]), use_bias=False, dtype=dt,
            param_dtype=jnp.float32, name="embed",
            kernel_init=nn.initializers.normal(INIT_STD),
        )(obs.astype(dt))
        rows, reads = [], []
        for i in range(int(c["num_layers"])):
            layer = Block(
                c, i < int(c["first_k_dense_replace"]), dt, name=f"layer{i}"
            )
            if cache is None:
                x = layer(x)
            else:
                x, read, c_i = layer.decode(x, cache["latent"][i], pos)
                rows.append(c_i)
                reads.append(read)
        out = RMSNorm(float(c["rms_norm_eps"]), jnp.float32, name="norm")(x)
        if cache is None:
            return out
        tally = moe.count_reads(cache[moe.EXPERTS_READ], reads)
        return out, {"latent": rows, moe.EXPERTS_READ: tally}


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache: ``{"latent": [[num_envs, horizon, kv_lora +
    rope], ...]}``, a leaf a layer in the compute dtype, beside the routed
    layers' tally of the experts they read (``ops/moe.py::no_reads``)."""
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    return {
        "latent": [
            jnp.zeros((num_envs, horizon, width), dtype)
            for _ in range(int(cfg["num_layers"]))
        ],
        moe.EXPERTS_READ: moe.no_reads(),
    }


# -- what the learner does with the sown statistics and the bias -------------

def moe_stats(collection: dict) -> dict:
    """``{"load": [layers, n_routed], "overflow": scalar}`` from the
    ``moe`` collection of one ``apply`` (layers in order)."""
    trunk = collection["trunk"]
    sown = [trunk[k][ROUTED] for k in _routed_layers(trunk)]
    return {
        "load": jnp.stack([s["load"][-1] for s in sown]),
        "overflow": sum(s["overflow"][-1] for s in sown),
    }


def routing_of(collection: dict, what: str = "experts") -> list:
    """``[layers][N, top_k]`` chosen experts (or, ``what='inputs'``, the
    ``[layers][N, hidden]`` the router scored) from the ``moe_routing``
    collection of one ``apply`` (the benchmark's reference check reads
    it; no training path does)."""
    trunk = collection["trunk"]
    return [trunk[k][ROUTED][what][-1] for k in _routed_layers(trunk)]


def _routed_layers(trunk) -> list:
    return sorted(
        (k for k in trunk if k.startswith("layer") and ROUTED in trunk[k]),
        key=lambda k: int(k.removeprefix("layer")),
    )


def update_router_bias(params, load, speed: float):
    """``b += speed x sign(mean load - load)`` per routed layer (DeepSeek-V3
    section 2.1.2: an overloaded expert's bias falls, an underloaded one's
    rises), over the optimizer step's tokens; ``load [layers, n_routed]``
    in the order of :func:`moe_stats`. Outside every gradient."""
    trunk = dict(params["params"]["trunk"])
    for row, k in zip(load, _routed_layers(trunk)):
        layer = dict(trunk[k])
        routed = dict(layer[ROUTED])
        routed[BIAS_NAME] = routed[BIAS_NAME] + speed * jnp.sign(row.mean() - row)
        layer[ROUTED] = routed
        trunk[k] = layer
    return {**params, "params": {**params["params"], "trunk": trunk}}


def router_biases(params) -> list:
    trunk = params["params"]["trunk"]
    return [trunk[k][ROUTED][BIAS_NAME] for k in _routed_layers(trunk)]


FAMILY = Family(
    trunk=LatentMoETrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, moe_stats=moe_stats,
    update_router_bias=update_router_bias, router_biases=router_biases,
)
