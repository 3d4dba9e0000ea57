"""State-space, window, full, gated-memory and cross-attention layers in
one trajectory trunk.

The third block family of ``model.encoder.kind='trajectory'``
(``model.encoder.block='ssm_hybrid'``; ``models/attention.py`` has the
selectors and the heads every family shares). Its layers are those of
Phi-4-mini-flash-reasoning (microsoft, ``config.json``: hidden 2560, 40
query over 20 key-value heads of 64, SwiGLU 10240, ``sliding_window`` 512,
``layer_norm_eps`` 1e-5; no positional term). With ``a = pairs_before``
pairs before the middle and ``b = pairs_after`` after it (published 8 and
7), the trunk is, in order,

    [ssm, window] x a,  ssm (the memory's source),  full,  [gmu, cross] x b

and every layer is ``x += Mixer(LN(x)); x += SwiGLU(LN(x))``, LayerNorm
with weight and bias. The mixers (``h = LN(x)``):

- **ssm**: ``[u, z] = W_in h``; ``u' = SiLU(conv(u) + b_c)`` (causal,
  depthwise, :data:`CONV_TAPS` taps); ``[d, B, C] = W_x u'``; ``delta =
  softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; the recurrence of
  ``ops/selective_scan.py`` gives ``y``; out ``W_out (y * SiLU(z))``. The
  middle one also hands on ``y`` **before the gate** as the memory ``m``.
- **window**: grouped-query softmax attention, causal, a query at ``t``
  sees keys ``t - sliding_window + 1 .. t``.
- **full**: the same without the window; its keys and values are kept.
- **gmu** (gated memory unit): ``W_out (m * SiLU(W_in h))``, ``m`` the
  middle state-space layer's at the same position.
- **cross**: queries ``W_q h`` alone, attending causally to the keys and
  values the full layer kept; ``W_o``.

So the later layers read what the middle pair kept: the trunk is no loop
over independent blocks, in the learn pass or in an acting step.

**Two paths compute it**, both from the same parameter tree and the same
pure functions of it (:func:`forward`, :func:`decode`). The learn pass
runs whole segments: the chunked scan, attention a block of queries at a
time (``ops/ring_attention.py::blocked_attention``). An acting step runs
one position against a carry of three kinds side by side
(:func:`acting_cache`): per state-space layer a float32 ``[envs, N, C]``
state and a ``[envs, taps - 1, C]`` conv tail, constant in size; per
window layer a ring of ``sliding_window`` slots that forgets (slot =
position mod window; a slot counts only if written in this segment, which
the position says); for the full layer the keys and values of ``T`` slots
that the cross layers read and never copy. A slot's row is 128 lanes wide
where the heads allow it (:func:`heads_per_row`): at the published 20
heads of 64, ``[envs, T, 10, 128]`` with two neighbouring heads side by
side. The gated-memory and cross layers hold nothing. A wrap to a new
segment zeroes the state-space leaves (:func:`reset_recurrent`) and only
moves the position for the others, whose stale rows the masks hide.

The state spans episode ends inside a segment and is zero at the segment's
start, exactly as attention's context here (``learners/ppo.py::_learn_seq``
says why): acting and the learn pass condition alike.

**Precision** (``compute_dtype`` bfloat16 under 'mixed'): every product
takes bfloat16 operands; LayerNorm, the conv's sum, ``softplus``, ``exp(delta
A)``, the state and the scan's accumulations, and the softmax are float32.

**Recomputation.** Past :data:`REMAT_ABOVE_BYTES` of estimated residuals
(from the pass's own shapes, no key) each layer is a ``jax.checkpoint``
(``models/attention.py::recomputed`` has the rule):
the backward keeps a layer's input and recomputes the rest, one layer at a
time. At 8192 tokens six layers' residuals are 6 GB beside 10 GB of
parameters, gradients and Adam moments.

Init (the config gives none: Mamba's own, arXiv:2312.00752 section 3.6 and
its code): ``A_log = log(1..N)`` in every channel, ``D = 1``, ``b_dt`` so
that ``softplus(b_dt)`` is log-uniform in [1e-3, 1e-1], conv taps uniform
in +-1/sqrt(taps); every matrix normal(0, ``INIT_STD``); norms 1 and 0.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from surreal_tpu.models.attention import (
    COUNTERS_COLLECTION, Family, recomputed,
)
from surreal_tpu.ops import moe
from surreal_tpu.ops.ring_attention import _NEG_BIG, blocked_attention
from surreal_tpu.ops.selective_scan import (
    scan_in_vmem, selective_scan, selective_step,
)
from surreal_tpu.utils.phases import part

INIT_STD = 0.02
# one value in use, so constants and no keys: Mamba-1's conv taps and
# expansion (the published config gives neither), the config's layer_norm_eps
CONV_TAPS = 4
EXPAND = 2
NORM_EPS = 1e-5
DT_MIN, DT_MAX = 1e-3, 1e-1
# estimated residual bytes of a differentiated pass past which each layer
# is recomputed in the backward: an eighth of a v5e's memory
REMAT_ABOVE_BYTES = 2 * 2**30
QUERY_BLOCK = 256
# the lanes of a TPU's vector register: the minor axis of an array's tiles
LANES = 128

# model.encoder keys this family reads beside the shared ones (kind, block,
# num_heads, act_impl), with the values an unset (None) key takes:
# microsoft/Phi-4-mini-flash-reasoning config.json, and Mamba-1's sizes by
# the family's convention where the config gives none (state 16, dt rank
# ceil(hidden / 16))
FAMILY_DEFAULTS = dict(
    hidden_size=2560,
    num_kv_heads=20,
    intermediate_size=10240,
    sliding_window=512,
    ssm_state_size=16,
    ssm_dt_rank=None,      # ceil(hidden_size / 16)
    pairs_before=8,
    pairs_after=7,
)
KINDS = ("ssm", "window", "full", "gmu", "cross")
# what a whole-segment apply sows, one scalar each: ``{sown name: (metrics
# row, how the row reduces it over an iteration's minibatch steps)}``: the
# largest entry of a state a segment ended with (a recurrence that blows up
# shows before the loss does), the keys a windowed query saw, and how many
# key-value heads share a row of the acting caches (static: which form of
# the decode's attention this trunk runs, heads_per_row), and which form of
# the selective scan ran (1 the Pallas kernels that keep the state in VMEM, 0
# the ``lax`` form: ops/selective_scan.py chooses from the device and the
# shapes)
COUNTERS = {
    "state_abs_max": ("ssm/state_abs_max", "max"),
    "scan_in_vmem": ("ssm/scan_in_vmem", "mean"),
    "window_keys_mean": ("attn/window_keys_mean", "mean"),
    "cache_heads_per_row": ("attn/cache_heads_per_row", "max"),
}


def resolve(encoder_cfg: dict) -> dict:
    """``encoder_cfg`` with this family's unset keys at their defaults."""
    out = dict(encoder_cfg)
    for k, v in FAMILY_DEFAULTS.items():
        if out.get(k) is None:
            out[k] = v
    if out["ssm_dt_rank"] is None:
        out["ssm_dt_rank"] = math.ceil(int(out["hidden_size"]) / 16)
    H, G = int(out["num_heads"]), int(out["num_kv_heads"])
    if H % G or int(out["hidden_size"]) % H:
        raise ValueError(
            f"num_heads={H} must divide hidden_size={out['hidden_size']} "
            f"and be a multiple of num_kv_heads={G}"
        )
    if int(out["pairs_before"]) < 0 or int(out["pairs_after"]) < 0:
        raise ValueError("pairs_before and pairs_after count layers: >= 0")
    return out


def layer_kinds(cfg: dict) -> list:
    """The trunk's layers in order: ``(kind, keeps)`` with ``keeps`` true
    for the middle pair, whose ``y`` / keys and values later layers read."""
    a, b = int(cfg["pairs_before"]), int(cfg["pairs_after"])
    return (
        [("ssm", False), ("window", False)] * a
        + [("ssm", True), ("full", True)]
        + [("gmu", False), ("cross", False)] * b
    )


def _sizes(cfg: dict) -> dict:
    D, H = int(cfg["hidden_size"]), int(cfg["num_heads"])
    return dict(
        D=D, H=H, G=int(cfg["num_kv_heads"]), hd=D // H,
        F=int(cfg["intermediate_size"]), C=EXPAND * D,
        N=int(cfg["ssm_state_size"]), K=CONV_TAPS,
        R=int(cfg["ssm_dt_rank"]), W=int(cfg["sliding_window"]),
        eps=NORM_EPS,
    )


# -- parameters ----------------------------------------------------------------

def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``b`` with ``softplus(b)`` log-uniform in [DT_MIN, DT_MAX]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(DT_MIN), math.log(DT_MAX)
    ))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape
    ).astype(dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _normal(key, shape, dtype=jnp.float32):
    return INIT_STD * jax.random.normal(key, shape, dtype)


_ONES, _ZEROS = nn.initializers.ones, nn.initializers.zeros


def mixer_spec(kind: str, cfg: dict) -> tuple:
    """``((name, shape, init), ...)`` of one mixer's leaves."""
    s = _sizes(cfg)
    D, C, N, H, G, hd = s["D"], s["C"], s["N"], s["H"], s["G"], s["hd"]
    if kind == "ssm":
        return (
            ("in_proj", (D, 2 * C), _normal),
            ("conv", (s["K"], C), _conv_init), ("conv_bias", (C,), _ZEROS),
            ("x_proj", (C, s["R"] + 2 * N), _normal),
            ("dt_proj", (s["R"], C), _normal), ("dt_bias", (C,), _dt_bias_init),
            ("A_log", (C, N), _a_log_init), ("D", (C,), _ONES),
            ("out_proj", (C, D), _normal),
        )
    if kind in ("window", "full"):
        return (
            ("q", (D, H, hd), _normal), ("k", (D, G, hd), _normal),
            ("v", (D, G, hd), _normal), ("o", (H, hd, D), _normal),
        )
    if kind == "gmu":
        return (("in_proj", (D, C), _normal), ("out_proj", (C, D), _normal))
    if kind == "cross":
        return (("q", (D, H, hd), _normal), ("o", (H, hd, D), _normal))
    raise ValueError(f"layer kind {kind!r} not in {KINDS}")


def _norm_spec(D: int) -> tuple:
    return (("scale", (D,), _ONES), ("bias", (D,), _ZEROS))


class Leaves(nn.Module):
    """A dict of float32 parameters under one name: the trunk's layers are
    pure functions of such dicts (and so is the benchmark's reference)."""

    spec: tuple

    @nn.compact
    def __call__(self) -> dict:
        return {
            name: self.param(name, init, shape, jnp.float32)
            for name, shape, init in self.spec
        }


class LayerLeaves(nn.Module):
    kind: str
    cfg: dict

    @nn.compact
    def __call__(self) -> dict:
        s = _sizes(self.cfg)
        D, F = s["D"], s["F"]
        ffn = (
            ("gate", (D, F), _normal), ("up", (D, F), _normal),
            ("down", (F, D), _normal),
        )
        return {
            "mixer_norm": Leaves(_norm_spec(D), name="mixer_norm")(),
            "mixer": Leaves(mixer_spec(self.kind, self.cfg), name="mixer")(),
            "ffn_norm": Leaves(_norm_spec(D), name="ffn_norm")(),
            "ffn": Leaves(ffn, name="ffn")(),
        }


# -- the layers, as functions of their leaves ----------------------------------

def layer_norm(p, x, eps: float, dtype):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def _ssm_inputs(p, u, s, dt):
    """What the recurrence takes, from the conv's output ``u [..., C]``
    (float32, before the SiLU): ``(u', delta, B, C)``."""
    with part("ssm_scan"):
        u = jax.nn.silu(u).astype(dt)
    with part("ssm_proj"):
        dbc = u @ p["x_proj"].astype(dt)
        R, N = s["R"], s["N"]
        pre = jnp.dot(
            dbc[..., :R], p["dt_proj"].astype(dt),
            preferred_element_type=jnp.float32,
        )
    with part("ssm_scan"):
        delta = jax.nn.softplus(pre + p["dt_bias"])
    return u, delta, dbc[..., R:R + N], dbc[..., R + N:]


def _ssm_out(p, y, z, dt):
    with part("ssm_scan"):
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    with part("ssm_proj"):
        return gated @ p["out_proj"].astype(dt)


def ssm_mixer(p, h, s, dt):
    """``h [B, T, D]`` -> ``(out [B, T, D], y [B, T, C] before the gate in
    ``dt``, final state [B, N, C])``, from a zero state and a zero tail."""
    C, K = s["C"], s["K"]
    T = h.shape[1]
    with part("ssm_proj"):
        uz = h @ p["in_proj"].astype(dt)
        u, z = uz[..., :C], uz[..., C:]
    with part("ssm_scan"):
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
        conv = p["conv_bias"] + sum(
            p["conv"][k] * padded[:, k:k + T] for k in range(K)
        )
    u, delta, Bm, Cm = _ssm_inputs(p, conv, s, dt)
    with part("ssm_scan"):
        y, state = selective_scan(
            u, delta, -jnp.exp(p["A_log"]).T, Bm, Cm, p["D"]
        )
    return _ssm_out(p, y, z, dt), y.astype(dt), state


def ssm_step(p, h, carry, s, dt):
    """One position ``h [B, D]`` against ``carry {"state", "conv"}``:
    ``(out [B, D], y [B, C], new carry)``."""
    C = s["C"]
    with part("ssm_proj"):
        uz = h @ p["in_proj"].astype(dt)
        u, z = uz[..., :C], uz[..., C:]
    with part("ssm_scan"):
        taps = jnp.concatenate(
            [carry["conv"], u[:, None].astype(carry["conv"].dtype)], axis=1
        )
        conv = p["conv_bias"] + (
            p["conv"][None] * taps.astype(jnp.float32)
        ).sum(1)
    u, delta, b_t, c_t = _ssm_inputs(p, conv, s, dt)
    with part("ssm_scan"):
        y, state = selective_step(
            u, delta, -jnp.exp(p["A_log"]).T, b_t, c_t, p["D"], carry["state"]
        )
    return (
        _ssm_out(p, y, z, dt), y.astype(dt),
        {"state": state, "conv": taps[:, 1:]},
    )


def _heads(w, h, dt):
    return jnp.einsum("...d,dhe->...he", h, w.astype(dt))


def attention_mixer(p, h, s, dt, window, kv=None):
    """Window, full (``window`` None) or cross (``kv`` the kept keys and
    values) attention over ``h [B, T, D]``: ``(out, (k, v), keys a query
    saw on average)``."""
    with part("attn"):
        q = _heads(p["q"], h, dt)
        if kv is None:
            kv = (_heads(p["k"], h, dt), _heads(p["v"], h, dt))
        out, seen = blocked_attention(q, *kv, window=window, block=QUERY_BLOCK)
        return jnp.einsum("bthe,hed->btd", out, p["o"].astype(dt)), kv, seen


def heads_per_row(G: int, hd: int) -> int:
    """How many neighbouring key-value heads one row of an acting cache
    holds. A head narrower than the :data:`LANES` would leave the slot
    axis the only one that fills them, and the compiler then lays the cache
    out with the SLOTS on the lanes: a step's one-slot write touches every
    tile of the array (72-76 us for 40 KB at 20 heads of 64). ``p`` heads
    that fill the lanes exactly share a row, ``[envs, slots, G / p, 128]``;
    any other geometry keeps a head a row (1)."""
    p = LANES // hd
    return p if p > 1 and p * hd == LANES and G % p == 0 else 1


def _attend_one(q, k, v, valid):
    """One query a row ``q [B, H, hd]`` over cached ``k, v [B, S, G / p,
    p * hd]`` (``p`` key-value heads a row, :func:`heads_per_row`; the
    width says which), slots where ``valid [S]``: ``[B, H, hd]``; rounds as
    ``blocked_attention`` does. Where heads share a row the products
    contract over the whole row: a query sits in its own head's lanes of a
    zero row and keeps its own head's lanes of the result, so all the wider
    products add to a head's sums are exact zeros."""
    B, H, hd = q.shape
    J, p = k.shape[2], k.shape[3] // hd
    q = q.reshape(B, J, H // J, hd)
    if p > 1:
        # head p*j + a of row j owns lanes a*hd .. (a+1)*hd
        own = jnp.eye(p, dtype=bool)[:, None, :, None]
        q = jnp.where(own, q.reshape(B, J, p, -1, 1, hd), 0).reshape(
            B, J, H // J, p * hd
        )
    scores = jnp.einsum(
        "bgrd,bkgd->bgrk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(hd))
    prob = jax.nn.softmax(jnp.where(valid, scores, _NEG_BIG), axis=-1)
    out = jnp.einsum(
        "bgrk,bkgd->bgrd", prob.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    if p > 1:
        out = jnp.where(own, out.reshape(B, J, p, -1, p, hd), 0).sum(4)
    return out.astype(q.dtype).reshape(B, H, hd)


def attention_step(p, h, cache, pos, dt, ring: bool):
    """One position of a window (``ring``) or full layer: writes its key
    and value at slot ``pos mod S`` of ``cache {"k", "v"} [B, S, G / p,
    p * hd]`` (one row: the heads of a row are neighbours already) and
    attends to the slots written in this segment that the query may see.
    In a ring every such slot lies inside the window: the one ``window``
    positions back is the one just overwritten."""
    with part("attn"):
        B, S = cache["k"].shape[:2]
        slot = pos % S if ring else pos
        put = lambda c, w: jax.lax.dynamic_update_slice_in_dim(
            c, _heads(w, h, dt).reshape(B, 1, *c.shape[2:]).astype(c.dtype),
            slot, axis=1,
        )
        cache = {"k": put(cache["k"], p["k"]), "v": put(cache["v"], p["v"])}
        return cross_step(p, h, cache, pos, dt), cache


def cross_step(p, h, cache, pos, dt):
    """One query against a cache another layer wrote up to ``pos``."""
    with part("attn"):
        S = cache["k"].shape[1]
        valid = jnp.arange(S) <= pos    # every slot once pos >= S: a ring
        out = _attend_one(_heads(p["q"], h, dt), cache["k"], cache["v"], valid)
        return jnp.einsum("bhe,hed->bd", out, p["o"].astype(dt))


def gmu_mixer(p, h, m, dt):
    with part("gmu"):
        gate = jax.nn.silu((h @ p["in_proj"].astype(dt)).astype(jnp.float32))
        return (m * gate).astype(dt) @ p["out_proj"].astype(dt)


def _ffn(p, x, s, dt):
    with part("dense_ffn"):
        h = layer_norm(p["ffn_norm"], x, s["eps"], dt)
        f = p["ffn"]
        return x + moe.swiglu(h, f["gate"], f["up"], f["down"])


def _layer(kind: str, keeps: bool, s: dict, dt, p, x, kept):
    """One whole layer of the learn pass: ``(x, kept, stats)``. ``kept``
    holds what the middle pair hands on (``m``; ``k``, ``v``), ``stats``
    this layer's counters."""
    h = layer_norm(p["mixer_norm"], x, s["eps"], dt)
    stats = {}
    if kind == "ssm":
        out, y, state = ssm_mixer(p["mixer"], h, s, dt)
        stats["state_abs_max"] = jnp.abs(state).max()
        # from the shapes the scan saw: y's are u's, A is A_log turned
        stats["scan_in_vmem"] = scan_in_vmem(y, p["mixer"]["A_log"].T)
        if keeps:
            kept = dict(kept, m=y)
    elif kind in ("window", "full"):
        out, kv, seen = attention_mixer(
            p["mixer"], h, s, dt, s["W"] if kind == "window" else None
        )
        if kind == "window":
            stats["window_keys"] = seen
        if keeps:
            kept = dict(kept, k=kv[0], v=kv[1])
    elif kind == "gmu":
        out = gmu_mixer(p["mixer"], h, kept["m"], dt)
    else:
        out, _, _ = attention_mixer(
            p["mixer"], h, s, dt, None, kv=(kept["k"], kept["v"])
        )
    return _ffn(p, x + out, s, dt), kept, stats


def residual_bytes(cfg: dict, tokens: int) -> int:
    """Roughly what a differentiated pass over ``tokens`` tokens keeps
    without recomputation: in the compute dtype, per layer, the SwiGLU's
    three wide tensors and a state-space mixer's six."""
    s = _sizes(cfg)
    per_token = 2 * (3 * s["F"] + 6 * s["C"] + 4 * s["D"])
    return tokens * per_token * len(layer_kinds(cfg))


def forward(params: dict, x, cfg: dict, dt, residual: int):
    """The learn pass: ``x [B, T, D]`` -> ``(x, stats)``; ``params`` is
    ``{"layer<i>": leaves}``, ``residual`` the estimate the recomputation
    rule reads."""
    s = _sizes(cfg)
    kept, stats = {}, []
    for i, (kind, keeps) in enumerate(layer_kinds(cfg)):
        def layer(p, x, kept, kind=kind, keeps=keeps):
            return _layer(kind, keeps, s, dt, p, x, kept)

        layer = recomputed(layer, residual, REMAT_ABOVE_BYTES)
        x, kept, st = layer(params[f"layer{i}"], x, kept)
        stats.append(st)
    pick = lambda name: jnp.stack([st[name] for st in stats if name in st])  # noqa: E731
    windows = [st["window_keys"] for st in stats if "window_keys" in st]
    return x, {
        "state_abs_max": pick("state_abs_max").max(),
        "scan_in_vmem": pick("scan_in_vmem").mean(),
        # a trunk without a window layer (pairs_before 0) has no such query
        "window_keys_mean": jnp.stack(windows).mean() if windows
        else jnp.zeros((), jnp.float32),
        "cache_heads_per_row": jnp.float32(heads_per_row(s["G"], s["hd"])),
    }


def decode(params: dict, x, cache: dict, pos, cfg: dict, dt):
    """An acting step: ``x [B, D]`` at ``pos`` -> ``(x, new cache)``."""
    s = _sizes(cfg)
    ssm, ring = list(cache["ssm"]), list(cache["ring"])
    shared, m = cache["shared"], None
    n_ssm = n_ring = 0
    for i, (kind, keeps) in enumerate(layer_kinds(cfg)):
        p = params[f"layer{i}"]
        h = layer_norm(p["mixer_norm"], x, s["eps"], dt)
        if kind == "ssm":
            out, y, ssm[n_ssm] = ssm_step(p["mixer"], h, ssm[n_ssm], s, dt)
            n_ssm += 1
            if keeps:
                m = y
        elif kind == "window":
            out, ring[n_ring] = attention_step(
                p["mixer"], h, ring[n_ring], pos, dt, ring=True
            )
            n_ring += 1
        elif kind == "full":
            out, shared = attention_step(
                p["mixer"], h, shared, pos, dt, ring=False
            )
        elif kind == "gmu":
            out = gmu_mixer(p["mixer"], h, m, dt)
        else:
            out = cross_step(p["mixer"], h, shared, pos, dt)
        x = _ffn(p, x + out, s, dt)
    return x, {"ssm": ssm, "ring": ring, "shared": shared}


class SSMHybridTrunk(nn.Module):
    """``[B, T, obs] -> [B, T, hidden]`` (float32, after the last norm);
    with ``cache`` (:func:`acting_cache`) and ``pos``, ``[B, obs] -> ([B,
    hidden], new cache)``. A whole-segment apply sows :data:`COUNTERS` into
    the trunk's counters collection (``models/attention.py``)."""

    cfg: dict               # resolve()d model.encoder subtree
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, obs, *, cache=None, pos=None, replicate_ok: bool = False):
        del replicate_ok    # no mesh path
        c, dt = self.cfg, self.compute_dtype
        x = nn.Dense(
            int(c["hidden_size"]), use_bias=False, dtype=dt,
            param_dtype=jnp.float32, name="embed", kernel_init=_normal,
        )(obs.astype(dt))
        params = {
            f"layer{i}": LayerLeaves(kind, c, name=f"layer{i}")()
            for i, (kind, _) in enumerate(layer_kinds(c))
        }
        norm = Leaves(_norm_spec(int(c["hidden_size"])), name="norm")()
        if cache is not None:
            x, cache = decode(params, x, cache, pos, c, dt)
            return layer_norm(norm, x, NORM_EPS, jnp.float32), cache
        tokens = x.shape[0] * x.shape[1]
        x, stats = forward(
            params, x, c, dt,
            residual=residual_bytes(c, tokens),
        )
        for name, value in stats.items():
            self.sow(COUNTERS_COLLECTION, name, value)
        return layer_norm(norm, x, NORM_EPS, jnp.float32)


def acting_cache(cfg: dict, num_envs: int, horizon: int, dtype) -> dict:
    """The acting carry's cache, three kinds side by side: ``{"ssm":
    [{"state" [envs, N, C] float32, "conv" [envs, taps - 1, C]}, ...],
    "ring": [{"k", "v" [envs, min(window, horizon), G / p, p * hd]}, ...],
    "shared": {"k", "v" [envs, horizon, G / p, p * hd]}}``, ``p`` heads a
    row (:func:`heads_per_row`); keys, values and the conv tail in the
    compute dtype."""
    s = _sizes(cfg)
    kinds = [k for k, _ in layer_kinds(cfg)]
    p = heads_per_row(s["G"], s["hd"])
    kv = lambda slots: {
        name: jnp.zeros((num_envs, slots, s["G"] // p, p * s["hd"]), dtype)
        for name in ("k", "v")
    }
    return {
        "ssm": [
            {
                "state": jnp.zeros((num_envs, s["N"], s["C"]), jnp.float32),
                "conv": jnp.zeros((num_envs, s["K"] - 1, s["C"]), dtype),
            }
            for _ in range(kinds.count("ssm"))
        ],
        "ring": [kv(min(s["W"], horizon)) for _ in range(kinds.count("window"))],
        "shared": kv(horizon),
    }


def reset_recurrent(cache: dict, wrap) -> dict:
    """``cache`` with the state-space leaves zeroed where ``wrap`` (a
    scalar bool) is set: a state has no position a mask could hide, so a
    new segment starts it from zero. The rings and the shared cache are
    left as they are: their stale rows are masked by the position."""
    zero = lambda x: jnp.where(wrap, jnp.zeros_like(x), x)
    return dict(cache, ssm=jax.tree.map(zero, cache["ssm"]))


# it counts its layers in pairs_before / pairs_after, not num_layers
FAMILY = Family(
    trunk=SSMHybridTrunk, acting_cache=acting_cache, defaults=FAMILY_DEFAULTS,
    resolve=resolve, not_read=("num_layers",), reset_recurrent=reset_recurrent,
    counters=COUNTERS,
)
