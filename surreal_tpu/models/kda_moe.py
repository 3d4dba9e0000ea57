"""Kimi Delta Attention layers three to one latent-attention layer, over
sigmoid-routed experts beside a shared one, as a trajectory trunk.

The fifth block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='kda_moe'``; ``models/attention.py`` has the table of
families and the heads every family shares). Its layers are those of
Kimi-Linear-48B-A3B-Instruct (moonshotai, ``config.json``; the Kimi Linear
report, arXiv:2510.26692: hidden 2304, ``linear_attn_config`` 32 heads of 128
with ``short_conv_kernel_size`` 4, every fourth layer latent attention without
rotary (``mla_use_nope``, no ``q_lora_rank``, ``kv_lora_rank`` 512, 32 heads of
128 + 64 / 128), ``first_k_dense_replace`` 1 with a SwiGLU of 9216, then 256
routed experts of 1024, 8 a token, sigmoid scores, renormalised, x 2.446, one
shared expert, ``rms_norm_eps`` 1e-5). Counting from one, layer ``l`` is
latent where ``l`` is a multiple of :data:`PERIOD`, else KDA; the first
``first_k_dense_replace`` layers are dense, the rest routed. ``x`` the
residual stream, every layer

    x += Mixer_l(RMSNorm(x))        x += FFN_l(RMSNorm(x))

then a last RMSNorm in float32; the input is ``Dense(obs -> hidden)``. No
biases but ``dt_bias`` and the routers' selection bias.

**KDA mixer**, ``h`` its normed input, ``H`` heads of ``K`` (the keys' and
the values' size alike): ``q, k, v = SiLU(conv(W_q h)), SiLU(conv(W_k h)),
SiLU(conv(W_v h))``, ``conv`` causal and depthwise over the last
``short_conv_kernel_size`` positions; ``q`` and ``k`` L2-normalised a head
(float32), ``q`` times ``K^-1/2``; the log-decay a channel ``g = -exp(A_log[h])
softplus(W_fb (W_fa h) + dt_bias)`` (float32; the pair's inner size is ``K``);
``beta = sigmoid(W_b h)``, one a head; the recurrence of ``ops/delta_rule.py``
in its channel-wise case (``g`` of ``q``'s rank; ``models/gdn_moe.py`` runs the
head-wise one) gives ``o``; out ``W_o (RMSNorm_head(o) * sigmoid(W_gb (W_ga
h)))``.

**Latent attention** is ``models/latent_moe.py::LatentAttention`` with no
low-rank query pair and no rotary turn; **the feed-forward**, the bias rule
and the cut of the loss at the router's product are that file's too
(``SwiGLU``, ``RoutedExperts``, ``update_router_bias``), imported and not
copied: this chip holds ``num_held`` experts from ``first_held`` on
(``ops/moe.py`` says what that means).

**Two paths compute it**, from one parameter tree. The learn pass runs whole
segments: the chunked rule, expanded latent attention, the routed layers
sorted by expert. An acting step runs one position against a carry of two
kinds side by side (:func:`acting_cache`): per KDA layer a float32 matrix
state ``[envs, H, K, K]`` and the last ``taps - 1`` positions of the three
projections, constant in size; per latent layer ``[envs, T, kv_lora + rope]``
rows. A wrap to a new segment zeroes the KDA leaves (:func:`reset_recurrent`)
and only moves the position for the latent cache, whose stale rows the mask
hides. The state spans episode ends inside a segment and is zero at the
segment's start, as ``models/ssm_hybrid.py``'s.

**Precision** (``compute_dtype`` bfloat16 under 'mixed'): every product
takes bfloat16 operands; the conv's sum, the L2 norms, ``softplus``, the
decay, ``beta``, the state, the triangular system, the output norm and the
softmax are float32.

**Recomputation**: the shape rule of ``models/attention.py::recomputed``,
past :data:`REMAT_ABOVE_BYTES` of estimated residuals
(:func:`residual_bytes`).

Init (the config gives ``initializer_range`` 0.02 and no more; the lineage's
code for the rest): every matrix normal(0, ``INIT_STD``); ``A_log =
log(uniform(1, 16))`` a head; ``dt_bias`` so that ``softplus(dt_bias)`` is
log-uniform in [1e-3, 1e-1]; conv taps uniform in +-1/sqrt(taps); norms 1.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from surreal_tpu.models import latent_moe
from surreal_tpu.models.attention import (
    COUNTERS_COLLECTION, Family, recomputed,
)
from surreal_tpu.models.latent_moe import (
    INIT_STD, LatentAttention, RMSNorm, RoutedExperts, SwiGLU,
)
# Mamba's inits, which the lineage's code takes too: ``softplus(dt_bias)``
# log-uniform in [1e-3, 1e-1], conv taps uniform in +-1/sqrt(taps)
from surreal_tpu.models.ssm_hybrid import _conv_init, _dt_bias_init
from surreal_tpu.ops import moe
from surreal_tpu.ops.delta_rule import (
    delta_rule, delta_step, gram_in_vmem, walk_in_vmem,
)
from surreal_tpu.utils.phases import part

BLOCK = "kda_moe"
# one value in use, so constants and no keys: a latent layer every fourth
# (``full_attn_layers`` 4, 8, ...; the published trunk's last layer, 27, is
# the one exception and lies outside any cut that starts at layer 1)
PERIOD = 4
A_MAX = 16.0
L2_EPS = 1e-6
# estimated residual bytes of a differentiated pass past which each layer is
# recomputed in the backward: a sixteenth of a v5e's memory
REMAT_ABOVE_BYTES = 2**30

# model.encoder keys this family reads beside the shared ones (kind, block,
# num_layers, num_heads: both mixers' heads, act_impl), with the values an
# unset (None) key takes: moonshotai/Kimi-Linear-48B-A3B-Instruct
# config.json, one chip of 32. It reads neither q_lora_rank nor rope_theta:
# LatentAttention's cases for a layer without them
FAMILY_DEFAULTS = dict(
    hidden_size=2304,
    kda_head_dim=128,
    short_conv_kernel_size=4,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    intermediate_size=9216,
    moe_intermediate_size=1024,
    n_routed_experts=256,
    num_experts_per_tok=8,
    n_shared_experts=1,
    routed_scaling_factor=2.446,
    first_k_dense_replace=1,
    rms_norm_eps=1e-5,
    first_held=0,
    num_held=8,
    bias_update_speed=0.001,
)
# what a whole-segment apply sows, one scalar each (``{sown name: (metrics
# row, how the row reduces it over an iteration's minibatch steps)}``): the
# largest entry of a matrix state a segment ended with (a rule that blows up
# shows before the loss does), the mean decay a channel a step (1 forgets
# nothing), the mean share of a key's content a step rewrites, and which form
# of the rule's Gram matrices and of its walk over a segment's chunks ran (1
# the kernels that keep a chunk's pairwise decays, and the matrix state from
# chunk to chunk, in VMEM, 0 the ``lax`` forms: ops/delta_rule.py chooses from
# the device and the shapes)
COUNTERS = {
    "state_abs_max": ("kda/state_abs_max", "max"),
    "decay_mean": ("kda/decay_mean", "mean"),
    "beta_mean": ("kda/beta_mean", "mean"),
    "gram_in_vmem": ("kda/gram_in_vmem", "mean"),
    "walk_in_vmem": ("kda/walk_in_vmem", "mean"),
}


def resolve(encoder_cfg: dict) -> dict:
    """``encoder_cfg`` with this family's unset keys at their defaults."""
    out = dict(encoder_cfg)
    for k, v in FAMILY_DEFAULTS.items():
        if out.get(k) is None:
            out[k] = v
    if int(out["num_layers"]) <= int(out["first_k_dense_replace"]):
        raise ValueError(
            f"num_layers={out['num_layers']} leaves no routed layer after "
            f"first_k_dense_replace={out['first_k_dense_replace']}"
        )
    if int(out["short_conv_kernel_size"]) < 2:
        raise ValueError("short_conv_kernel_size: a conv of at least 2 taps")
    moe.check_held(out["first_held"], out["num_held"], out["n_routed_experts"])
    return out


def layer_kinds(cfg: dict) -> list:
    """The trunk's layers in order: ``(kind, dense)``, ``kind`` 'kda' or
    'latent', ``dense`` true for a leading dense layer."""
    first = int(cfg["first_k_dense_replace"])
    return [
        ("latent" if (i + 1) % PERIOD == 0 else "kda", i < first)
        for i in range(int(cfg["num_layers"]))
    ]


# -- the KDA mixer -------------------------------------------------------------

def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, A_MAX)).astype(dtype)


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


class DeltaAttention(nn.Module):
    """A KDA mixer (module docstring). ``__call__``: ``h [B, T, D]`` ->
    ``(out [B, T, D], counters)`` from a zero state and a zero conv tail;
    :meth:`decode`: one position ``h [B, D]`` against ``carry {"state",
    "conv": {"q", "k", "v"}}`` -> ``(out [B, D], new carry)``."""

    cfg: dict
    dtype: Any = jnp.bfloat16

    def setup(self):
        c = self.cfg
        D, H = int(c["hidden_size"]), int(c["num_heads"])
        K, taps = int(c["kda_head_dim"]), int(c["short_conv_kernel_size"])
        self.K = K
        normal = nn.initializers.normal(INIT_STD)
        leaf = lambda name, shape, init=normal: self.param(   # noqa: E731
            name, init, shape, jnp.float32
        )
        self.proj = {n: leaf(n, (D, H, K)) for n in ("q", "k", "v")}
        self.conv = {
            n: leaf(f"conv_{n}", (taps, H, K), _conv_init) for n in ("q", "k", "v")
        }
        self.f_a, self.f_b = leaf("f_a", (D, K)), leaf("f_b", (K, H, K))
        self.dt_bias = leaf("dt_bias", (H, K), _dt_bias_init)
        self.A_log = leaf("A_log", (H,), _a_log_init)
        self.b = leaf("b", (D, H))
        self.g_a, self.g_b = leaf("g_a", (D, K)), leaf("g_b", (K, H, K))
        self.o_norm = leaf("o_norm", (K,), nn.initializers.ones)
        self.o = leaf("o", (H, K, D))

    def _products(self, h):
        """The mixer's products of ``h [..., D]``: ``(q, k, v before the
        conv [..., H, K] in the compute dtype, the decay's and the gate's
        pre-activations [..., H, K] and beta's [..., H], float32)``."""
        dt, f32 = self.dtype, jnp.float32
        with part("kda_proj"):
            qkv = {
                n: jnp.einsum("...d,dhk->...hk", h, w.astype(dt))
                for n, w in self.proj.items()
            }
            pair = lambda a, b: jnp.einsum(   # noqa: E731
                "...r,rhk->...hk", h @ a.astype(dt), b.astype(dt),
                preferred_element_type=f32,
            )
            return (
                qkv, pair(self.f_a, self.f_b), pair(self.g_a, self.g_b),
                jnp.dot(h, self.b.astype(dt), preferred_element_type=f32),
            )

    def _rule_inputs(self, conv, f, b):
        """``(q, k, v, g, beta)`` as the rule takes them, from the convs'
        sums ``conv {"q", "k", "v"}`` (float32, before the SiLU)."""
        q, k, v = (jax.nn.silu(conv[n]) for n in ("q", "k", "v"))
        q = _l2(q) * self.K ** -0.5
        g = -jnp.exp(self.A_log)[:, None] * jax.nn.softplus(f + self.dt_bias)
        return q, _l2(k), v.astype(self.dtype), g, jax.nn.sigmoid(b)

    def _out(self, o, gate):
        """``W_o (RMSNorm_head(o) * sigmoid(gate))``, ``o [..., H, K]``."""
        with part("kda_scan"):
            eps = float(self.cfg["rms_norm_eps"])
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
            o = (o * self.o_norm * jax.nn.sigmoid(gate)).astype(self.dtype)
        with part("kda_proj"):
            return jnp.einsum("...hk,hkd->...d", o, self.o.astype(self.dtype))

    def __call__(self, h):
        T = h.shape[1]
        qkv, f, gate, b = self._products(h)
        with part("kda_scan"):
            taps = int(self.cfg["short_conv_kernel_size"])
            conv = {}
            for n, x in qkv.items():
                padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
                conv[n] = sum(
                    self.conv[n][j] * padded[:, j:j + T].astype(jnp.float32)
                    for j in range(taps)
                )
            q, k, v, g, beta = self._rule_inputs(conv, f, b)
            o, state = delta_rule(q, k, v, g, beta)
            stats = {
                "state_abs_max": jnp.abs(state).max(),
                "decay_mean": jnp.exp(g).mean(), "beta_mean": beta.mean(),
                "gram_in_vmem": gram_in_vmem(q),
                "walk_in_vmem": walk_in_vmem(q, v),
            }
        return self._out(o, gate), stats

    def decode(self, h, carry):
        qkv, f, gate, b = self._products(h)
        with part("kda_scan"):
            tails, conv = {}, {}
            for n, x in qkv.items():
                held = carry["conv"][n]
                taps = jnp.concatenate([held, x[:, None].astype(held.dtype)], 1)
                conv[n] = (self.conv[n][None] * taps.astype(jnp.float32)).sum(1)
                tails[n] = taps[:, 1:]
            q, k, v, g, beta = self._rule_inputs(conv, f, b)
            o, state = delta_step(q, k, v, g, beta, carry["state"])
        return self._out(o, gate), {"state": state, "conv": tails}


# -- the layers ----------------------------------------------------------------

class Block(nn.Module):
    """One layer: ``__call__`` over a whole segment -> ``(x, the KDA
    counters or {})``; :meth:`decode` one position against the layer's leaf
    of the carry -> ``(x, the routed experts' read share or None, new
    leaf)``."""

    cfg: dict
    kind: str
    dense: bool
    dtype: Any = jnp.bfloat16

    def setup(self):
        c = self.cfg
        eps = float(c["rms_norm_eps"])
        self.attn_norm = RMSNorm(eps, self.dtype)
        self.ffn_norm = RMSNorm(eps, self.dtype)
        if self.kind == "kda":
            self.kda = DeltaAttention(c, self.dtype)
        else:
            self.attn = LatentAttention(c, self.dtype)
        if self.dense:
            self.ffn = SwiGLU(int(c["intermediate_size"]), self.dtype)
        else:
            self.moe = RoutedExperts(c, self.dtype)

    def _ffn(self, x):
        """``(x + FFN(RMSNorm(x)), the routed experts' read share or None)``."""
        if self.dense:
            with part("dense_ffn"):
                return x + self.ffn(self.ffn_norm(x)), None
        y, read = self.moe(self.ffn_norm(x))
        return x + y, read

    def __call__(self, x):
        if self.kind == "kda":
            out, stats = self.kda(self.attn_norm(x))
        else:
            with part("attn"):
                out, stats = self.attn(self.attn_norm(x)), {}
        return self._ffn(x + out)[0], stats

    def decode(self, x, leaf, pos):
        if self.kind == "kda":
            out, leaf = self.kda.decode(self.attn_norm(x), leaf)
        else:
            with part("attn"):
                out, leaf = self.attn.decode(self.attn_norm(x), leaf, pos)
        return *self._ffn(x + out), leaf


def residual_bytes(cfg: dict, tokens: int) -> int:
    """Roughly what a differentiated pass over ``tokens`` tokens keeps
    without recomputation, in the compute dtype unless said: per layer the
    residual stream and its two normed copies; a KDA mixer's three
    projections before and (float32) after the conv, the decay and the gate
    (float32), the rule's output and the gated one; a latent mixer's queries,
    expanded keys and values and output; a SwiGLU's three wide tensors, over
    every token in a dense layer and a shared expert, over the sorted
    buffer's rows in the held experts."""
    D, H = int(cfg["hidden_size"]), int(cfg["num_heads"])
    HK = H * int(cfg["kda_head_dim"])
    nope, rot = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    latent = H * (2 * (nope + rot) + 2 * int(cfg["v_head_dim"]))
    E, top = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    held, Fm = int(cfg["num_held"]), int(cfg["moe_intermediate_size"])
    total = 0
    for kind, dense in layer_kinds(cfg):
        per_token = 2 * 4 * D
        per_token += (2 * 3 + 4 * 5 + 2 * 2) * HK if kind == "kda" else 2 * latent
        if dense:
            per_token += 2 * 3 * int(cfg["intermediate_size"])
        else:
            rows = moe.row_bound(tokens, top, held, E) / max(tokens, 1)
            per_token += 2 * (3 * Fm + rows * (3 * Fm + D))
        total += tokens * per_token
    return int(total)


class KDAMoETrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``. A whole-segment apply sows :data:`COUNTERS` into
    the counters collection; the routed layers sow as
    ``models/latent_moe.py``'s do."""

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
        norm = RMSNorm(float(c["rms_norm_eps"]), jnp.float32, name="norm")
        kinds = layer_kinds(c)
        if cache is not None:
            new = {"kda": list(cache["kda"]), "latent": list(cache["latent"])}
            seen = {"kda": 0, "latent": 0}
            reads = []
            for i, (kind, dense) in enumerate(kinds):
                n = seen[kind]
                x, read, new[kind][n] = Block(
                    c, kind, dense, dt, name=f"layer{i}"
                ).decode(x, new[kind][n], pos)
                seen[kind] = n + 1
                reads.append(read)
            tally = moe.count_reads(cache[moe.EXPERTS_READ], reads)
            return norm(x), {**new, moe.EXPERTS_READ: tally}
        block = recomputed(
            Block, residual_bytes(c, x.shape[0] * x.shape[1]), REMAT_ABOVE_BYTES
        )
        stats = []
        for i, (kind, dense) in enumerate(kinds):
            x, st = block(c, kind, dense, dt, name=f"layer{i}")(x)
            if st:
                stats.append(st)
        pick = lambda name: jnp.stack([st[name] for st in stats])  # noqa: E731
        self.sow(COUNTERS_COLLECTION, "state_abs_max", pick("state_abs_max").max())
        for name in ("decay_mean", "beta_mean", "gram_in_vmem", "walk_in_vmem"):
            self.sow(COUNTERS_COLLECTION, name, pick(name).mean())
        return norm(x)


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache, two kinds side by side: ``{"kda": [{"state"
    [envs, H, K, K] float32, "conv": {"q", "k", "v" [envs, taps - 1, H, K]}},
    ...], "latent": [[envs, horizon, kv_lora + rope], ...]}``, a leaf a layer
    of the kind; the conv tails and the latent rows in the compute dtype.
    Beside them the routed layers' tally of the experts they read
    (``ops/moe.py::no_reads``)."""
    H, K = int(cfg["num_heads"]), int(cfg["kda_head_dim"])
    taps = int(cfg["short_conv_kernel_size"])
    kinds = [k for k, _ in layer_kinds(cfg)]
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    return {
        "kda": [
            {
                "state": jnp.zeros((num_envs, H, K, K), jnp.float32),
                "conv": {
                    n: jnp.zeros((num_envs, taps - 1, H, K), dtype)
                    for n in ("q", "k", "v")
                },
            }
            for _ in range(kinds.count("kda"))
        ],
        "latent": [
            jnp.zeros((num_envs, horizon, width), dtype)
            for _ in range(kinds.count("latent"))
        ],
        moe.EXPERTS_READ: moe.no_reads(),
    }


def reset_recurrent(cache: dict, wrap) -> dict:
    """``cache`` with the KDA leaves (matrix states and conv tails) zeroed
    where ``wrap`` (a scalar bool) is set: neither has a position a mask
    could hide. The latent rows are left as they are: the position masks
    what is stale."""
    zero = lambda x: jnp.where(wrap, jnp.zeros_like(x), x)   # noqa: E731
    return dict(cache, kda=jax.tree.map(zero, cache["kda"]))


# the routed layers are latent_moe's own modules under latent_moe's names, so
# its readers of the sown statistics and its bias rule serve this tree as it is
routing_of = latent_moe.routing_of
FAMILY = Family(
    trunk=KDAMoETrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, reset_recurrent=reset_recurrent, counters=COUNTERS,
    moe_stats=latent_moe.moe_stats,
    update_router_bias=latent_moe.update_router_bias,
    router_biases=latent_moe.router_biases,
)
