"""Plain reference for the Laguna-S-2.1 trajectory policy under PPO
(``ppo_lift_laguna``).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the published config
(poolside/Laguna-S-2.1 ``config.json``) and the papers its parts come from:
grouped-query attention with a sliding window, rotary embeddings (Su et al.
2021, arXiv:2104.09864) with YaRN's frequencies in the full layers (Peng et
al. 2023, arXiv:2309.00071, section 3.2), and softmax top-k routing beside a
shared expert (the Qwen-MoE lineage's, whose key names the config uses). It
reads the learner's parameter tree and the configuration file, and nothing
else of the program: no flax module, no ``ops/`` function. No cache, no
sort, no blocks: attention is an explicit ``[T, T]`` mask, and a routed layer
runs every held expert on every token times the token's weight for it, or
zero. ``x`` the residual stream, ``h = RMSNorm(x)`` (a weight, eps 1e-6),
every layer ``x += Attn(h); x += FFN(RMSNorm(x))``; layer ``l`` of the five:

    attention   n = 48 query heads where l is a multiple of 4 (full), else
                72 (sliding); q = h W_q [T, n, 128], k = h W_k, v = h W_v
                [T, 8, 128]; q, k turned at the position in the segment by the
                layer type's table (``rope_parameters`` of the config file);
                softmax(q k^T / sqrt(128)) over keys 0 .. t (full) or
                t-511 .. t (sliding), query head j on key-value head
                j // (n / 8); g = sigmoid(h W_g) [T, n];
                out = concat_j(g_j o_j) W_o
    FFN, l = 0  (SiLU(h W_gate) * h W_up) W_down, width 12288
    FFN, l > 0  p = softmax(h W_r) over 256; the 10 largest;
                w_i = 2.5 p_i / sum_top p;  sum_{i held} w_i E_i(h) + S(h),
                E_i, S SwiGLU of 1024; the gradient stops at h W_r

then a last RMSNorm and the float32 heads: ``mean``, ``value`` (dense with
bias) and a state-independent ``log_std``.

Kept from the repo, and stated in the configuration: attention spans episode
ends inside a segment; the obs filter of ``ppo_lift`` normalises the 17
observations; the PPO loss is the repo's (clipped surrogate, clipped value
loss, entropy bonus 0.01); GAE has two masks.

``check`` runs on the chip, outside the window, and compares what the timed
path itself produces at the timed sizes, as ``ppo_phi4flash_ref.check`` does
and with its machinery (the sessions, the host's Adam, the order of the
minibatches are that file's): the second iteration of the measured session,
16 envs x 1024 positions and 2 x 4 minibatches of 4 envs, trained again from
the session's seed through ``select_trainer(cfg).run``. Of that iteration:

(a) ``act/*``: what the decode through the full caches and the rings
    produced at every position of the rollout (mean, value, the behaviour
    log-prob) against one whole-segment reference forward, apart for
    positions under and past the window (``.../under``, ``.../over``), so a
    ring that forgets wrongly, or keys turned at the wrong position, shows.
    ``act/wrap_is_fresh``: the step after a wrap is position 0 of a fresh
    segment; ``collect/rollout_is_session``: the rollout run alone is the
    session's;
(b) ``prepare/*``: ``_prepare_seq``'s values, advantages and targets on that
    batch, and the fused row's own ``adv/mean_abs``;
(c) ``learn/*``: the whole ``learn`` of the fused iteration, both epochs and
    the four minibatches of each, recomputation on, Adam from the moments
    the session held, against the same eight steps in float32 (a
    minibatch's envs one at a time, Adam on the host):
    ``learn/param_change`` is the norm of (the program's change of the
    parameters - the reference's) over the norm of the reference's, whole
    and by group of leaves; 1 is what a state left unchanged reads. The
    routers take no gradient, so neither side may move them
    (``learn/router_still``);
(d) routing. A token whose 10th and 11th experts nearly tie gets another
    expert from bfloat16 activations than from float32 ones, and a swap on a
    held expert moves that token's output by a tenth. So in (a), (b) and (c)
    the reference takes **the program's choice of experts** and its own
    weights for them, and the choice is held apart: ``route/agree_share``
    (the share of (token, layer) pairs of the acting and the prepare pass
    whose ten agree with the reference's own), ``route/tie_gap`` (how far,
    in logits, a swapped expert lies from the reference's 10th) and
    ``route/score_agree`` (the reference's own ten on the very inputs the
    program's routers scored: what is left is the precision of the product,
    the softmax and the selection);
(e) the fused row's counters: ``attn/window_keys_mean`` against the mask's
    count (384.25 at 1024 positions and a window of 512), ``attn/gate_mean``
    against the reference's gates over the batch, ``moe/held_share`` against
    the count of the program's own choices, ``moe/overflow`` 0.
"""

from __future__ import annotations

import math
import os
import shutil
import time

from benchmarks.harness.checks import close

ENV_BLOCK = 2       # envs a reference forward takes at once
KL_BAND = 4.0e-3    # a tenth of the threshold kl_early_stop x kl_target

GROUPS = (
    "attn_full", "attn_window", "dense_ffn", "experts", "shared", "norms",
    "ends",
)

# Tolerances, each between two readings on the chip (my chip runs, PR 44):
# the largest of eight seeds' second iterations (2147491001-7 from the
# committed files, the fourth traced, and 2147491021) and the smallest a
# control that moves the row gave (seed 2147485102, the forwards alone; the
# two precision controls on 2147491021). Rows over positions are held by
# QUANTILE. `reading | limit | weakest control`:
#   act/mean under, over       3.7e-4, 4.2e-4 | 2e-3 | 7.1e-3, 7.7e-3 (the
#                              sliding layers at theta 500 000)
#   act/value under, over      0.037, 0.041 | 0.15 | 0.50, 0.72 (the same;
#                              the dropped 2.5 reads 0.209 under the window
#                              and is caught by the route/* rows)
#   act/logp under, over       1.7e-3, 1.8e-3 | 7e-3 | 2.9e-2, 3.3e-2
#   prepare/values             0.046 | 0.17 | 0.66
#   prepare/advantages         0.032 | 0.12 | 0.44
#   prepare/targets            0.033 | 0.1 | 0.37 (the dropped 2.5: 0.124)
#   prepare/adv_mean_abs       1.0e-4 | 6e-4 | 3.3e-3 (the shared expert)
#   attn/window_keys_mean      0 | 1e-3 | 0.50 (a window of 511 or 513, which
#                              moves no other row past its limit)
#   attn/gate_mean             5.0e-3 (2.2e-3 the least) | 2e-2 | the row's
#                              mean is over the eight learn steps, in which
#                              Adam moves the gates' weights (the mean falls
#                              1-2.6e-3 an iteration from 0.5), the
#                              reference's at the iteration's start
#   moe/held_share             1.9e-2 of the share | a tenth of it
#   learn/loss_pg              8.4e-6 | 1e-4 | its own scale 1.7-2.8e-3
#   learn/loss_value           3.3e-3 of it | 2.4e-2
#   learn/entropy              5.4e-7 | 2e-5 | 1.5e-4 (a log_std left where
#                              it was: four dimensions x 1e-5 x 8 steps, over
#                              the steps' mean)
#   learn/kl                   7.2e-6 | 5e-5 | its own scale 5.3e-4-1.1e-3
#   learn/grad_norm            5.1e-3 of it | 5e-2
#   learn/param_change         0.064 whole; attn_full 0.044, attn_window
#                              0.059, dense_ffn 0.043, experts 0.081, shared
#                              0.043, norms 0.038 | 0.25 | 1 (a state left
#                              unchanged); ends 0.027-0.203 | 0.6 | 1 (the
#                              projection in, 17 x 3072, is the worst leaf in
#                              seven seeds of eight: small gradients whose
#                              signs Adam's step follows)
#   learn/leaf_moved           0.039 | 0.5 | 1
#   route/agree_share          0.8746 the least (0.8805 the most) | 0.83 |
#                              0.775 (the dropped 2.5)
#   route/tie_gap              0.094 (0.069 the least) | 0.2 | 0.43 (the same)
#   route/score_agree          0.99998 the least | 0.995 | 0.9758 (the routing
#                              softmax in bfloat16), 0.9492 (the router's
#                              product in bfloat16): **the precision below is
#                              not correct by this row alone**; both leave
#                              every other row inside its limit
#   collect/rollout_is_session 4.7e-5 of the return (2.2e-6 the least; seven
#                              of the eight over the 1e-5 that
#                              ppo_phi4flash_ref holds its cell to) | 1e-3 |
#                              2.1e-2 (the nearest two of the seeds' returns,
#                              9.03-11.54: what another rollout reads). The
#                              rollout run alone is not bit for bit the fused
#                              iteration's on the chip, though the decode
#                              replayed in a third program is the rollout's
#                              (act/replay_is_rollout 0.0 in all eight) and on
#                              the CPU all three agree: two compilations
#                              round the acting step apart, and of 65 536
#                              routing decisions a rollout the near-ties flip.
#                              Which op, not found (PERF.md section 7)
# Each other control's readings (act/value under / over the window,
# prepare/values, route/agree_share, route/tie_gap): the gate dropped 4.15 /
# 2.74, 4.00, 0.0, 3.87; the shared expert dropped 2.17 / 1.38, 2.07, 0.23,
# 1.97; a full layer turning the whole head 2.67 / 3.29, 2.98, 0.002, 5.70;
# YaRN's blend dropped 0.81 / 1.63, 1.66, 0.18, 3.53; the attention factor
# dropped 1.62 / 1.70, 1.74, 0.014, 3.19. The left-out minibatch was not read
# on the chip (a second copy of the state does not fit the host's 40 GiB);
# ``tests/benchmarks/test_benchmark_laguna_reference.py`` holds every control
# at the rehearsal's widths.
TOL = {
    "act/mean/under": dict(rtol=0.0, atol=2.0e-3),
    "act/mean/over": dict(rtol=0.0, atol=2.0e-3),
    "act/value/under": dict(rtol=0.0, atol=1.5e-1),
    "act/value/over": dict(rtol=0.0, atol=1.5e-1),
    "act/logp/under": dict(rtol=0.0, atol=7.0e-3),
    "act/logp/over": dict(rtol=0.0, atol=7.0e-3),
    "prepare/values": dict(rtol=0.0, atol=1.7e-1),
    "prepare/advantages": dict(rtol=0.0, atol=1.2e-1),
    "prepare/targets": dict(rtol=0.0, atol=1.0e-1),
    "prepare/adv_mean_abs": dict(rtol=0.0, atol=6.0e-4),
    "learn/loss_pg": dict(rtol=0.0, atol=1.0e-4),
    "learn/loss_value": dict(rtol=2.4e-2, atol=0.0),
    "learn/entropy": dict(rtol=0.0, atol=2.0e-5),
    "learn/kl": dict(rtol=0.0, atol=5.0e-5),
    "learn/grad_norm": dict(rtol=5.0e-2, atol=0.0),
    "learn/param_change": dict(rtol=0.0, atol=2.5e-1),
    **{
        f"learn/param_change/{g}": dict(rtol=0.0, atol=2.5e-1)
        for g in GROUPS if g != "ends"
    },
    "learn/param_change/ends": dict(rtol=0.0, atol=6.0e-1),
    "learn/leaf_moved": dict(rtol=0.0, atol=5.0e-1),
    "attn/window_keys_mean": dict(rtol=0.0, atol=1.0e-3),
    "attn/gate_mean": dict(rtol=0.0, atol=2.0e-2),
    "moe/held_share": dict(rtol=1.0e-1, atol=0.0),
}
# (d): the share of pairs whose ten agree, the logit gap a swap is admitted
# under, and the share on the program's own router inputs
AGREE_SHARE_MIN = 0.83
TIE_GAP = 2.0e-1
SCORE_AGREE_MIN = 0.995
# the share of a batch's positions whose error a row over positions is held
# by: all but 16 of 16 x 1024 (ppo_phi4flash_ref.py says why)
QUANTILE = 0.999
EPISODES_RTOL = 1e-3
WRAP_ATOL = 1e-6
LEAF_MIN_SIZE = 256
# every term a comparison has to catch when it is dropped, changed or
# computed in the precision below
TERMS = (
    "gate", "shared_expert", "routed_scale", "full_rotates_whole",
    "yarn_plain", "attention_factor", "theta_swapped", "window_511",
    "window_513", "router_bf16", "softmax_bf16", "second_minibatch",
)


def phi_ref():
    """The sessions, the host's trees and Adam, the minibatches' order and
    the replayed decode are ``ppo_lift_phi4flash``'s: its reference has
    them, written for any trajectory learner."""
    from benchmarks.harness import manifest

    return manifest.load_reference("ppo_phi4flash_ref")


def ppo_ref():
    return phi_ref().ppo_ref()


def static(d: dict):
    return phi_ref().joyai_ref()._Static(d)


# -- the layers ----------------------------------------------------------------

def rms_norm(p, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def swiglu(p, x):
    import jax

    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def as_bf16(a):
    """bfloat16's 8 bits as an op of its own (a cast pair is one XLA may
    elide)."""
    import jax

    return jax.lax.reduce_precision(a, 8, 7)


def frequencies(table: dict, head_dim: int, dropped=None) -> list:
    """The rotary frequencies of one ``rope_parameters`` table, a pair
    each of the ``rot = head_dim x partial_rotary_factor`` turned
    dimensions: ``theta^(-2i / rot)``; under ``rope_type`` yarn that
    frequency where a pair turns more than ``beta_fast`` times in
    ``original_max_position_embeddings`` positions, it over ``factor`` where
    it turns fewer than ``beta_slow`` times, and a linear blend over the
    pair index between the two correction dimensions."""
    rot = int(head_dim * table["partial_rotary_factor"])
    theta = float(table["rope_theta"])
    plain = [theta ** (-2.0 * i / rot) for i in range(rot // 2)]
    if table["rope_type"] != "yarn" or dropped == "yarn_plain":
        return plain
    original = table["original_max_position_embeddings"]

    def pair_turning(turns: float) -> float:
        # the pair index whose wavelength fits `turns` times into `original`
        return rot * math.log(original / (turns * 2.0 * math.pi)) / (
            2.0 * math.log(theta)
        )

    low = max(math.floor(pair_turning(table["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(table["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        interpolated = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / table["factor"] * interpolated + f * (1.0 - interpolated))
    return out


def turn(x, table: dict, dropped=None):
    """``x [B, T, H, hd]`` with its first ``rot`` dimensions turned by the
    position ``t``: pairs ``(x[i], x[i + rot / 2])``, angle ``t x
    frequency_i``, ``cos`` and ``sin`` times the table's
    ``attention_factor``."""
    import jax.numpy as jnp

    freq = jnp.asarray(frequencies(table, x.shape[-1], dropped), jnp.float32)
    half = freq.shape[0]
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    factor = table.get("attention_factor", 1.0)
    if dropped == "attention_factor":
        factor = 1.0
    cos = (jnp.cos(angle) * factor)[None, :, None, :]
    sin = (jnp.sin(angle) * factor)[None, :, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], -1
    )


def rope_table(w, kind: str, dropped=None) -> dict:
    tables = w["rope_parameters"]
    key = {"full": "full_attention", "window": "sliding_attention"}[kind]
    if dropped == "theta_swapped" and kind == "window":
        return dict(tables[key], rope_theta=tables["full_attention"]["rope_theta"])
    if dropped == "full_rotates_whole" and kind == "full":
        return dict(tables[key], partial_rotary_factor=1)
    return tables[key]


def attention(p, h, w, kind: str, dropped=None):
    """``(out [B, T, D], the gates' mean)``: grouped-query softmax attention
    with an explicit ``[T, T]`` mask, causal, in a sliding layer keys ``t -
    window + 1 .. t`` alone; a sigmoid gate a head before ``W_o``."""
    import jax
    import jax.numpy as jnp

    table = rope_table(w, kind, dropped)
    q = turn(jnp.einsum("btd,dhe->bthe", h, p["q"]), table, dropped)
    k = turn(jnp.einsum("btd,dge->btge", h, p["k"]), table, dropped)
    v = jnp.einsum("btd,dge->btge", h, p["v"])
    B, T, H, hd = q.shape
    G = k.shape[2]
    rep = lambda a: jnp.repeat(a, H // G, axis=2)  # key-value head j // (H / G)
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, rep(k)) / math.sqrt(hd)
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]
    if kind == "window":
        window = int(w["sliding_window"]) + {
            "window_511": -1, "window_513": 1,
        }.get(dropped, 0)
        mask &= t[None, :] > t[:, None] - window
    scores = jnp.where(mask, scores, -jnp.inf)
    e = jnp.exp(scores - scores.max(-1, keepdims=True))
    out = jnp.einsum("bhqk,bkhe->bqhe", e / e.sum(-1, keepdims=True), rep(v))
    g = jax.nn.sigmoid(h @ p["gate"])
    if dropped != "gate":
        out = out * g[..., None]
    return jnp.einsum("bqhe,hed->bqd", out, p["o"]), g.mean()


def router_logits(p, x, dropped=None):
    if dropped == "router_bf16":
        return as_bf16(as_bf16(x) @ as_bf16(p["router"]))
    return x @ p["router"]


def top_experts(logits, w, dropped=None):
    """``(p [N, E], the top_k experts [N, top_k])``, ``p`` the softmax
    over all experts."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(logits, axis=-1)
    if dropped == "softmax_bf16":
        prob = as_bf16(prob)
    K = int(w["num_experts_per_tok"])
    return prob, jnp.argsort(-prob, axis=-1)[:, :K]


def routed(p, shared, h, w, forced=None, dropped=None):
    """``(y [N, D], info)`` for ``h [N, D]``: every held expert on every
    token, times the token's weight for it or zero, plus the shared expert.
    ``forced [N, top_k]`` stands for the reference's own choice (the
    program's: (d) in the module docstring); the weights are the
    reference's for those experts."""
    import jax
    import jax.numpy as jnp

    logits = jax.lax.stop_gradient(router_logits(p, h, dropped))
    prob, own = top_experts(logits, w, dropped)
    used = own if forced is None else forced
    chosen = jnp.take_along_axis(prob, used, axis=-1)
    scale = 1.0 if dropped == "routed_scale" else float(w["routed_scaling_factor"])
    weights = chosen / chosen.sum(-1, keepdims=True) * scale
    y = jnp.zeros_like(h)
    first = int(w["first_held"])
    for e in range(p["gate"].shape[0]):
        w_e = (weights * (used == first + e)).sum(-1)            # [N]
        y = y + w_e[:, None] * swiglu(
            {k: p[k][e] for k in ("gate", "up", "down")}, h
        )
    if dropped != "shared_expert":
        y = y + swiglu(shared, h)
    return y, {"own": own, "used": used, "logits": logits}


def layer_kinds(w) -> list:
    """``(kind, dense)`` a layer, from the config's own ``layer_types`` and
    ``mlp_only_layers``."""
    n = int(w["num_layers"])
    return [
        (
            "full" if w["layer_types"][i] == "full_attention" else "window",
            i in w["mlp_only_layers"],
        )
        for i in range(n)
    ]


def trunk(params, obs, w, forced=None, dropped=None, checkpoint=False):
    """``obs [B, T, 17]`` (normalised) -> ``(h [B, T, D]`` after the last
    norm, the routing infos a routed layer, the gates' mean)``. ``forced``:
    ``[routed layers][B, T, top_k]`` or None. ``checkpoint`` recomputes a
    layer in the backward, which changes no value."""
    import jax
    import jax.numpy as jnp

    p = params["params"]["trunk"]
    eps = float(w["rms_norm_eps"])
    x = obs @ p["embed"]["kernel"]
    B, T, D = x.shape
    infos, gates, routed_seen = [], [], 0
    for i, (kind, dense) in enumerate(layer_kinds(w)):
        choice = None
        if not dense:
            choice = None if forced is None else forced[routed_seen]
            routed_seen += 1

        def layer(lp, x, choice, kind=kind, dense=dense):
            out, gate = attention(
                lp["attn"], rms_norm(lp["attn_norm"], x, eps), w, kind, dropped
            )
            x = x + out
            h = rms_norm(lp["ffn_norm"], x, eps)
            if dense:
                return x + swiglu(lp["ffn"], h), gate, None
            y, info = routed(
                lp["moe"], lp["shared"], h.reshape(B * T, D), w,
                None if choice is None else choice.reshape(B * T, -1), dropped,
            )
            return x + y.reshape(B, T, D), gate, info

        if checkpoint:
            layer = jax.checkpoint(layer)
        x, gate, info = layer(p[f"layer{i}"], x, choice)
        gates.append(gate)
        if info is not None:
            infos.append(info)
    return rms_norm(p["norm"], x, eps), infos, jnp.stack(gates).mean()


def policy(params, obs, w, forced=None, dropped=None, checkpoint=False):
    """``(mean [B, T, A], log_std [B, T, A], value [B, T], routing infos,
    the gates' mean)``."""
    import jax.numpy as jnp

    p = params["params"]
    h, infos, gate = trunk(params, obs, w, forced, dropped, checkpoint)
    mean = h @ p["mean"]["kernel"] + p["mean"]["bias"]
    value = (h @ p["value"]["kernel"] + p["value"]["bias"])[..., 0]
    return mean, jnp.broadcast_to(p["log_std"], mean.shape), value, infos, gate


def window_keys_mean(T: int, window: int) -> float:
    """Keys a windowed causal query sees, averaged over ``T`` positions."""
    return sum(min(t + 1, window) for t in range(T)) / T


# -- PPO around them -----------------------------------------------------------

def ppo_loss(params, mb, forced, w, algo, dropped, policy_coeff):
    """The total PPO differentiates and ``(pg, value loss, entropy, KL)``;
    ``mb`` env-major ``[B, T, ...]``; ``policy_coeff`` 0 once a minibatch's
    KL has stopped the policy's steps."""
    import jax.numpy as jnp

    mean, log_std, value, _, _ = policy(
        params, mb["obs"], w, forced, dropped, checkpoint=True
    )
    logp = ppo_ref().gauss_logp(mean, log_std, mb["action"])
    var_b, var = jnp.exp(2.0 * mb["b_log_std"]), jnp.exp(2.0 * log_std)
    kl = (
        log_std - mb["b_log_std"]
        + (var_b + (mb["b_mean"] - mean) ** 2) / (2.0 * var) - 0.5
    ).sum(-1).mean()
    entropy = (log_std + 0.5 * (math.log(2.0 * math.pi) + 1.0)).sum(-1).mean()
    ratio = jnp.exp(logp - mb["behavior_logp"])
    eps = algo["clip_ratio"]
    pg = -jnp.minimum(
        ratio * mb["adv"], jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * mb["adv"]
    ).mean()
    v_clip = mb["value_old"] + jnp.clip(value - mb["value_old"], -eps, eps)
    v_loss = 0.5 * jnp.maximum(
        (value - mb["target"]) ** 2, (v_clip - mb["target"]) ** 2
    ).mean()
    total = (
        policy_coeff * (pg - algo["entropy_coeff"] * entropy)
        + algo["value_coeff"] * v_loss
    )
    return total, (pg, v_loss, entropy, kl)


def group_of(path: str, w) -> str:
    """The group a parameter's path lies in: a layer's attention by the
    layer's type, a dense layer's SwiGLU, the held experts (with the router,
    which does not move), the shared expert, the layers' norms, and
    ``ends`` outside the layers (the projection in, the last norm, the
    heads)."""
    for i, (kind, _) in enumerate(layer_kinds(w)):
        if f"['layer{i}']" in path:
            for leaf, group in (
                ("['attn']", f"attn_{kind}"), ("['ffn']", "dense_ffn"),
                ("['moe']", "experts"), ("['shared']", "shared"),
            ):
                if leaf in path:
                    return group
            return "norms"
    return "ends"


def change_errors(got: dict, want: dict, w) -> dict:
    """How far the program's change of the parameters ``got {leaf: array}``
    lies from the reference's ``want``: ``|got - want| / |want|`` over the
    whole tree (``all``) and each group of leaves, the worst leaf's ``|
    |got| / |want| - 1 |`` among leaves of ``LEAF_MIN_SIZE`` elements or
    more, the leaves the program left where they were though the reference
    moved them (``unmoved_leaves``) and the other way round (``moved_alone``:
    a router has no gradient and neither side may move it)."""
    phi = phi_ref()
    diff = {g: 0.0 for g in GROUPS}
    ref = {g: 0.0 for g in GROUPS}
    worst, worst_leaf, still, alone, at_rest = 0.0, None, [], [], []
    norms = phi.over(
        lambda leaf: (
            phi.sq_sum(got[leaf], want[leaf]), phi.sq_sum(want[leaf]),
            phi.sq_sum(got[leaf]),
        ),
        want,
    )
    for (leaf, d_want), (sq_diff, sq_want, sq_got) in zip(want.items(), norms):
        group = group_of(leaf, w)
        diff[group] += sq_diff
        ref[group] += sq_want
        n_got, n_want = math.sqrt(sq_got), math.sqrt(sq_want)
        if n_want == 0.0:
            (alone if n_got > 0.0 else at_rest).append(leaf)
            continue
        if n_got == 0.0:
            still.append(leaf)
        if d_want.size >= LEAF_MIN_SIZE and abs(n_got / n_want - 1.0) > worst:
            worst, worst_leaf = abs(n_got / n_want - 1.0), leaf
    out = {g: math.sqrt(diff[g] / ref[g]) for g in GROUPS}
    out["all"] = math.sqrt(sum(diff.values()) / sum(ref.values()))
    return {
        "groups": out, "leaf_moved": worst, "worst_leaf": worst_leaf,
        "unmoved_leaves": still, "moved_alone": alone, "at_rest": at_rest,
        "leaves": len(want),
    }


# -- the program's side --------------------------------------------------------

ROW_PREFIXES = (
    "loss/", "policy/", "value/", "adv/", "health/", "moe/", "attn/", "episode/",
)


def rows_differ(a: dict, b: dict) -> tuple:
    """The largest relative difference between two metrics rows over what
    the fused program computed (no clocks), and how many values that is."""
    shared = [
        k for k in a if k.startswith(ROW_PREFIXES)
        and math.isfinite(a[k]) and math.isfinite(b.get(k, math.nan))
    ]
    return max(
        abs(a[k] - b[k]) / max(abs(b[k]), 1e-6) for k in shared
    ), len(shared)


def decode_replay(learner, state, obs_tb):
    """The rollout's acting again over the rollout's own observations ``[T,
    B, obs]``, through the model's decode path against the carry ``act_init``
    makes, asked also for the value and the experts each step chose: ``(the
    carry after the last step, (mean [T, B, A], value [T, B], experts [T,
    routed layers, B, top_k]))``."""
    import jax
    import jax.numpy as jnp

    from surreal_tpu.models.attention import ROUTING_COLLECTION
    from surreal_tpu.models.swa_moe import routing_of

    def step(carry, obs):
        (out, cache), sown = learner.model.apply(
            state.params, learner._norm_obs(state.obs_stats, obs),
            cache=carry["cache"], pos=carry["pos"],
            mutable=[ROUTING_COLLECTION],
        )
        experts = jnp.stack(routing_of(sown[ROUTING_COLLECTION]))
        return {"cache": cache, "pos": carry["pos"] + 1}, (
            out.mean, out.value, experts,
        )

    return jax.lax.scan(step, learner.act_init(obs_tb.shape[1]), obs_tb)


def wrap_replay(learner, state, carry, obs):
    """One step more with the segment's first observation from ``carry``,
    which has reached the horizon, and the same step from a fresh carry,
    both by ONE executable (``ppo_phi4flash_ref.wrap_replay`` says why):
    the carry wraps and the step must be position 0 of a fresh segment,
    every stale slot masked to an exact zero. ``((wrapped, fresh) (mean,
    value), the position after the wrap step)``."""
    import jax
    import jax.numpy as jnp

    phi = phi_ref()
    strong = lambda tree: jax.tree.map(   # noqa: E731  (no weak types)
        lambda x: jnp.asarray(x, x.dtype), tree
    )
    carry, fresh = strong(carry), strong(learner.act_init(obs.shape[0]))
    step = jax.jit(
        lambda s, c, o: phi.decode_step(learner, s, c, o)
    ).lower(state, carry, obs).compile()
    wrapped, wrapped_out = step(state, carry, obs)
    _, first_out = step(state, fresh, obs)
    return (wrapped_out, first_out), wrapped["pos"]


def prepare_routing(learner, state, batch):
    """The prepare pass's apply again, as ``_prepare_seq`` builds its
    input (the filter's statistics with the batch folded in, the segment
    with the bootstrap position appended), asked for the experts it chose
    and what each router scored: ``([routed layers][B x (T + 1), top_k],
    [routed layers][B x (T + 1), hidden])``, tokens env-major."""
    import jax.numpy as jnp

    from surreal_tpu.models.attention import ROUTING_COLLECTION
    from surreal_tpu.models.swa_moe import routing_of
    from surreal_tpu.ops.running_stats import update_stats

    stats = update_stats(state.obs_stats, batch["obs"], axis_name=None)
    obs_bt = jnp.swapaxes(learner._norm_obs(stats, batch["obs"]), 0, 1)
    last = learner._norm_obs(stats, batch["next_obs"][-1])
    ext = jnp.concatenate([obs_bt, last[:, None]], axis=1)
    _, sown = learner.model.apply(
        state.params, ext, mutable=[ROUTING_COLLECTION]
    )
    sown = sown[ROUTING_COLLECTION]
    return routing_of(sown), routing_of(sown, "inputs")


BATCH_KEYS = (
    "obs", "next_obs", "action", "reward", "done", "terminated",
    "behavior_logp", "behavior",
)


def widths_of(config: dict, enc: dict):
    """What the reference reads: the sizes the session resolved (the
    rehearsal's are toy), and the tables and the layer pattern of the
    configuration file itself."""
    w = {
        k: enc[k] for k in (
            "hidden_size", "num_layers", "num_heads", "window_heads",
            "num_kv_heads", "attn_head_dim", "sliding_window",
            "intermediate_size", "moe_intermediate_size",
            "shared_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
            "first_held", "num_held",
        )
    }
    w["rope_parameters"] = config["rope_parameters"]
    w["layer_types"] = tuple(config["layer_types"])
    w["mlp_only_layers"] = tuple(config["mlp_only_layers"])
    return static(w)


def system_reports(config: dict, cell: dict, folder: str, seed: int,
                   rehearse: bool, extra: tuple = ()) -> dict:
    """The second iteration of the cell's session from ``seed``, as the
    session itself runs it; ``ppo_phi4flash_ref.system_reports`` says how a
    fused iteration, which returns neither its batch nor the state it was
    given, is taken apart (two sessions through ``select_trainer(cfg).run``
    at a cadence of one, the rollout between them run once more alone)."""
    import jax
    import numpy as np

    from benchmarks.harness import runner
    from surreal_tpu.launch.rollout import device_rollout
    from surreal_tpu.learners.seq_policy import family_config
    from surreal_tpu.main import launch

    phi = phi_ref()

    def cfg_of(name: str):
        argv = runner.train_argv(
            config, cell, os.path.join(folder, name), seed, rehearse
        )
        argv += ["session_config.metrics.every_n_iters=1", *extra]
        return launch.build_config(launch.build_parser().parse_args(argv))

    shutil.rmtree(folder, ignore_errors=True)
    key = jax.random.key(int(seed))
    key, init_key, env_key = jax.random.split(key, 3)
    collect_keys = []
    for _ in range(2):
        key, it_key, _ = jax.random.split(key, 3)
        ckey, lkey = jax.random.split(it_key)     # the fused iteration's own
        collect_keys.append(ckey)

    # the first iteration's rollout alone, for the env carry it ends with
    trainer = launch.select_trainer(cfg_of("first"))
    learner, env = trainer.learner, trainer.env
    T = int(learner.config.algo.horizon)
    rollout = jax.jit(
        lambda s, c, k: device_rollout(
            env, learner, s, c, k, T,
            unroll=int(learner.config.algo.get("rollout_unroll", 1)),
        )
    )
    state = learner.init(init_key)._replace(opt_state=None)
    carry, _ = rollout(state, trainer.init_loop_state(env_key), collect_keys[0])
    del state, trainer

    _, state, first_rows = phi.train(cfg_of("first"), 1)
    count, mu, nu = phi.adam_moments(state.opt_state)
    before = {
        "params": phi.flat(state.params), "mu": phi.flat(mu), "nu": phi.flat(nu),
        "count": count,
        "obs_stats": jax.tree.map(np.array, state.obs_stats),
        "treedef": jax.tree.structure(state.params),
    }
    del mu, nu
    _, batch = rollout(state, carry, collect_keys[1])
    n_done = float(batch["ep_done"].sum())
    episodes = {
        "episode/count": n_done,
        "episode/return": float(
            np.float32(batch["ep_return"].sum()) / np.float32(n_done)
        ) if n_done else math.nan,
    }
    batch = {k: batch[k] for k in BATCH_KEYS}
    acting, (mean_again, value, act_experts) = jax.jit(
        lambda s, o: decode_replay(learner, s, o)
    )(state, batch["obs"])
    wrapped, wrap_pos = wrap_replay(learner, state, acting, batch["obs"][0])
    del acting
    _, values, targets, advantages, data, _ = jax.jit(
        lambda s, b: learner._prepare_seq(s, b, None)
    )(state, batch)
    prep_experts, router_inputs = jax.jit(
        lambda s, b: prepare_routing(learner, s, b)
    )(state, batch)
    host = jax.device_get
    batch, data = host(batch), host(data)
    small = host((mean_again, value, act_experts, wrapped, wrap_pos,
                  values, targets, advantages, prep_experts))
    mean_again, value, act_experts, wrapped, wrap_pos = small[:5]
    values, targets, advantages, prep_experts = small[5:]
    router_inputs = [np.asarray(x, np.float32) for x in host(router_inputs)]
    del state, carry

    # the second iteration itself
    _, state, rows = phi.train(cfg_of("second"), 2)
    metrics = rows[2]
    moved = phi.flat(state.params)
    phi.over(lambda leaf: np.subtract(
        moved[leaf], before["params"][leaf], out=moved[leaf]
    ), moved)
    del state
    shutil.rmtree(folder, ignore_errors=True)
    algo, opt = learner.config.algo, learner.config.optimizer
    enc = family_config(learner.config.model.encoder.to_dict())
    envs = batch["obs"].shape[1]
    K = int(enc["num_experts_per_tok"])
    # [T, L, B, K] -> [L][B, T, K]
    act_experts = [np.asarray(e) for e in act_experts.transpose(1, 2, 0, 3)]
    prep_experts = [np.asarray(e).reshape(envs, T + 1, K) for e in prep_experts]
    return {
        "before": before, "batch": batch, "data": data, "moved": moved,
        "metrics": metrics, "learn_key": lkey, "episodes": episodes,
        "first_rows": (first_rows[1], rows[1]),
        "widths": widths_of(config, enc), "learner": learner,
        "algo": {
            k: float(algo[k]) for k in (
                "gamma", "lam", "clip_ratio", "value_coeff", "entropy_coeff",
                "kl_target", "kl_early_stop",
            )
        },
        "epochs": int(algo.epochs), "num_minibatches": int(algo.num_minibatches),
        "lr": float(opt.lr), "max_grad_norm": float(opt.max_grad_norm),
        "wrap": {"step": wrapped[0], "first": wrapped[1], "pos": wrap_pos},
        "routing": {
            "act": act_experts, "prepare": prep_experts,
            "router_inputs": router_inputs,
        },
        "values": {
            "act/mean": batch["behavior"]["mean"].swapaxes(0, 1),
            "act/mean_again": mean_again.swapaxes(0, 1),
            "act/value": value.swapaxes(0, 1),
            "act/logp": batch["behavior_logp"].swapaxes(0, 1),
            "prepare/values": values, "prepare/advantages": advantages,
            "prepare/targets": targets,
            "prepare/adv_mean_abs": metrics["adv/mean_abs"],
            "learn/loss_pg": metrics["loss/pg"],
            "learn/loss_value": metrics["loss/value"],
            "learn/entropy": metrics["policy/entropy"],
            "learn/kl": metrics["policy/kl"],
            "learn/grad_norm": metrics["health/grad_norm"],
        },
    }


# -- the reference's side ------------------------------------------------------

def program_choice(sys: dict):
    """The experts the program's own learn-side apply chooses for a
    minibatch under given parameters, ``(params, obs [B, T, obs]) -> [routed
    layers][B, T, top_k]``: what (c) forces on the reference, step by step
    (the fused program does not hand out its own; the reference's parameters
    lie within its ``learn/param_change`` of the program's at every step)."""
    import jax

    from surreal_tpu.models.attention import ROUTING_COLLECTION
    from surreal_tpu.models.swa_moe import routing_of

    model = sys["learner"].model

    def chosen(params, obs_bt):
        # the program's products in the program's precision: the reference
        # calls this inside its own ``highest``, which Mosaic's ragged
        # product refuses for bfloat16 operands
        with jax.default_matmul_precision(None):
            _, sown = model.apply(params, obs_bt, mutable=[ROUTING_COLLECTION])
        B, T = obs_bt.shape[:2]
        return [
            e.reshape(B, T, -1) for e in routing_of(sown[ROUTING_COLLECTION])
        ]

    return jax.jit(chosen)


def learn_reference(sys: dict, obs_bt, dropped, in_place: bool) -> dict:
    """The iteration's ``learn`` again in float32: ``epochs x
    num_minibatches`` Adam steps from the state the program started from,
    the gradient of each over its minibatch's envs one at a time, the
    experts of each step the program's own choice under the reference's
    parameters. Where a decision to stop the policy's steps is within
    ``KL_BAND`` of its threshold and the program's row says one was taken,
    both decisions are followed; of the results, the one nearest the
    program's change. ``in_place`` trains in ``sys["before"]`` itself (8.8 GB
    at the published widths) where a copy is taken otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    phi = phi_ref()
    w, data, before = sys["widths"], sys["data"], sys["before"]
    algo = static({
        k: sys["algo"][k] for k in ("clip_ratio", "value_coeff", "entropy_coeff")
    })
    threshold = sys["algo"]["kl_early_stop"] * sys["algo"]["kl_target"]
    program_stopped = sys["metrics"]["policy/early_stopped"] > 0.0
    envs = obs_bt.shape[0]
    order = phi.minibatch_order(
        sys["learn_key"], envs, sys["epochs"], sys["num_minibatches"]
    )
    if dropped == "second_minibatch":
        order = [mb for i, mb in enumerate(order) if i % sys["num_minibatches"] != 1]
    mb_all = {
        "obs": obs_bt,
        # the loss's inputs are the program's own prepare outputs, so (c)
        # tests the learn step and not (b) again
        **{
            k: jnp.asarray(data[k]) for k in (
                "action", "behavior_logp", "b_mean", "b_log_std", "adv",
                "target", "value_old",
            )
        },
    }
    grad_fn = jax.jit(
        jax.grad(ppo_loss, has_aux=True), static_argnums=(3, 4, 5)
    )
    choose = program_choice(sys)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    share = jax.jit(
        lambda a, n: jax.tree.map(lambda x: x / n, a), donate_argnums=0
    )

    def grads_of(params: dict, ids: list, coeff: float):
        """The minibatch's gradient ``{leaf: array}`` on the host and its
        ``(pg, value loss, entropy, KL)``: every reduction of the loss is a
        mean over equal blocks, so both are the envs' means."""
        tree = jax.tree.unflatten(before["treedef"], list(params.values()))
        tree, total, terms = jax.device_put(tree), None, np.zeros(4)
        # the program sees the minibatch's obs as it staged them
        experts = choose(tree, jnp.asarray(data["obs"])[np.asarray(ids)])
        for n, e in enumerate(ids):
            one = jax.tree.map(lambda x: x[e:e + 1], mb_all)
            forced = [layer[n:n + 1] for layer in experts]
            g, aux = grad_fn(tree, one, forced, w, algo, dropped, jnp.float32(coeff))
            total = g if total is None else add(total, g)
            terms += np.asarray([float(a) for a in aux]) / len(ids)
        return phi.flat(share(total, jnp.float32(len(ids))), copy=False), terms

    def fresh(work: dict) -> dict:
        return {
            k: dict(zip(work[k], phi.over(np.copy, work[k].values())))
            for k in ("params", "mu", "nu", "delta")
        } | {"count": work["count"]}

    results = []
    seconds = {"gradients": 0.0, "adam": 0.0}

    def run(work: dict, step: int, stopped: bool, trail: list) -> None:
        while step < len(order):
            t0 = time.perf_counter()
            grads, terms = grads_of(
                work["params"], order[step], 0.0 if stopped else 1.0
            )
            t1 = time.perf_counter()
            norm = phi.adam_step(work, grads, sys["lr"], sys["max_grad_norm"])
            del grads
            seconds["gradients"] += t1 - t0
            seconds["adam"] += time.perf_counter() - t1
            kl = float(terms[3])
            trail = trail + [(*terms, norm)]
            step += 1
            over, near = kl > threshold, abs(kl - threshold) <= KL_BAND
            if near and not stopped:
                if program_stopped and step < len(order):
                    run(fresh(work), step, not over, trail)
                elif not program_stopped:
                    over = False
            stopped = stopped or over
        rows = np.asarray(trail)
        results.append({
            "change": change_errors(sys["moved"], work["delta"], w),
            "early_stopped": bool(stopped),
            "kl_steps": rows[:, 3].tolist(),
            "values": {
                "learn/loss_pg": rows[:, 0].mean(),
                "learn/loss_value": rows[:, 1].mean(),
                "learn/entropy": rows[:, 2].mean(),
                "learn/kl": rows[-1, 3],
                "learn/grad_norm": rows[:, 4].mean(),
            },
        })

    zeros = dict(zip(
        before["params"], phi.over(np.zeros_like, before["params"].values())
    ))
    with jax.default_matmul_precision("highest"):
        start = dict(before, delta=zeros)
        run(start if in_place else fresh(start), 0, False, [])
    best = min(results, key=lambda r: r["change"]["groups"]["all"])
    return dict(best, branches=len(results), threshold=threshold, seconds=seconds)


def routing_rows(infos: list) -> dict:
    """(d) from the reference's routing infos of forwards whose experts were
    the program's: the share of (token, layer) pairs whose sets agree, and
    the largest distance, in logits, of a swapped expert from the
    reference's own last-chosen."""
    import numpy as np

    agree, pairs, gap = 0, 0, 0.0
    for info in infos:
        own = np.sort(np.asarray(info["own"]), -1)
        used = np.sort(np.asarray(info["used"]), -1)
        logits = np.asarray(info["logits"], np.float64)
        same = (own == used).all(-1)
        agree += int(same.sum())
        pairs += same.size
        for n in np.nonzero(~same)[0]:
            last = np.sort(logits[n])[-own.shape[-1]]
            swapped = np.setxor1d(own[n], used[n])
            gap = max(gap, float(np.abs(logits[n][swapped] - last).max()))
    return {"agree_share": agree / max(pairs, 1), "tie_gap": gap}


def score_agreement(sys: dict, params, dropped=None) -> float:
    """(d), the scoring alone: the reference's own top ten on the very
    inputs the program's routers scored in the prepare pass, against the
    program's choice there; the share of (token, layer) pairs whose sets
    agree."""
    import jax
    import numpy as np

    w = sys["widths"]
    routed_layers = [i for i, (_, dense) in enumerate(layer_kinds(w)) if not dense]

    @jax.jit
    def own(p, x):
        return top_experts(router_logits(p, x, dropped), w, dropped)[1]

    agree = pairs = 0
    routing = sys["routing"]
    with jax.default_matmul_precision("highest"):
        for i, x, used in zip(
            routed_layers, routing["router_inputs"], routing["prepare"]
        ):
            layer = params["params"]["trunk"][f"layer{i}"]["moe"]
            mine = np.sort(np.asarray(own(layer, x)), -1)
            used = np.sort(np.asarray(used).reshape(mine.shape), -1)
            same = (mine == used).all(-1)
            agree += int(same.sum())
            pairs += same.size
    return agree / max(pairs, 1)


def held_share(sys: dict) -> float:
    """The share of the prepare pass's assignments, as the program chose
    them, that land on the held experts: what ``moe/held_share`` of a pass
    is."""
    import numpy as np

    w = sys["widths"]
    first, held = int(w["first_held"]), int(w["num_held"])
    chosen = np.stack(sys["routing"]["prepare"])
    return float(((chosen >= first) & (chosen < first + held)).mean())


def reference_reports(sys: dict, dropped: str | None = None,
                      learn: bool = True, in_place: bool = False) -> dict:
    """The reference's values under the comparisons' names; without
    ``learn``, what the forwards give (``act/*``, ``prepare/*``, routing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    w, batch, before = sys["widths"], sys["batch"], sys["before"]
    params = jax.device_put(
        jax.tree.unflatten(before["treedef"], list(before["params"].values()))
    )
    fwd = jax.jit(policy, static_argnums=(2, 4))
    # the obs filter, the reference's own: acting saw the statistics the
    # state held, prepare and the loss see them with the batch folded in
    ppo = ppo_ref()
    stats = before["obs_stats"]
    held = (int(stats.count), stats.mean, stats.m2)
    folded = ppo.fold_stats(*held, batch["obs"])
    acting_obs = ppo.normalise(*held, batch["obs"]).swapaxes(0, 1)
    obs_bt = ppo.normalise(*folded, batch["obs"]).swapaxes(0, 1)
    ext = jnp.concatenate(
        [obs_bt, ppo.normalise(*folded, batch["next_obs"][-1])[:, None]], 1
    )
    envs = obs_bt.shape[0]
    blocks = [slice(e, e + ENV_BLOCK) for e in range(0, envs, ENV_BLOCK)]
    cat = lambda xs: np.concatenate([np.asarray(x) for x in xs])  # noqa: E731
    routing = sys["routing"]
    force = lambda which, b: [jnp.asarray(layer[b]) for layer in routing[which]]  # noqa: E731
    infos = []

    def keep(out):
        infos.extend(jax.device_get(out[3]))
        return out

    with jax.default_matmul_precision("highest"):
        acted = [
            keep(fwd(params, acting_obs[b], w, force("act", b), dropped))
            for b in blocks
        ]
        mean, log_std, value = (cat([a[i] for a in acted]) for i in range(3))
        logp = ppo.gauss_logp(mean, log_std, batch["action"].swapaxes(0, 1))
        prepared = [
            keep(fwd(params, ext[b], w, force("prepare", b), dropped))
            for b in blocks
        ]
        v_ext = cat([a[2] for a in prepared])
        gate_mean = float(np.mean([float(a[4]) for a in prepared]))
        score_agree = score_agreement(sys, params, dropped)
    del params, acted, prepared
    values, v_next = v_ext[:, :-1].T, v_ext[:, 1:].T
    algo = sys["algo"]
    adv, target = ppo.gae(
        batch["reward"], values, v_next, batch["done"],
        batch["terminated"], algo["gamma"], algo["lam"],
    )
    normed = (adv - adv.mean()) / (adv.std() + 1e-8)
    T = obs_bt.shape[1]
    window = int(w["sliding_window"]) + {
        "window_511": -1, "window_513": 1,
    }.get(dropped, 0)
    out = {
        "window_keys_mean": window_keys_mean(T, window),
        "gate_mean": gate_mean,
        "routing": dict(routing_rows(infos), score_agree=score_agree),
        "values": {
            "act/mean": mean, "act/value": value, "act/logp": logp,
            "prepare/values": values, "prepare/advantages": normed,
            "prepare/targets": target,
            "prepare/adv_mean_abs": float(np.abs(normed).mean()),
        },
    }
    out["seconds"] = {"forwards": time.perf_counter() - t0}
    if learn:
        out["learn"] = learn_reference(sys, obs_bt, dropped, in_place)
        out["values"].update(out["learn"].pop("values"))
        out["seconds"].update(out["learn"].pop("seconds"))
    return out


def compare(sys: dict, reference: dict, tol: dict = TOL,
            session_row: dict | None = None) -> dict:
    """``{"ok", "comparisons": {name: {ok, ...}}}``: every row by its
    tolerance with the largest error and the reference's scale beside it."""
    import numpy as np

    rows = {}

    def row(name, got, want, limit=None):
        ok, err = close(got, want, **tol[limit or name])
        rows[name] = {
            "ok": ok, "max_abs_err": err, "tol": tol[limit or name],
            "scale": float(np.abs(np.asarray(want, np.float64)).max()),
        }

    def spread_row(name, got, want):
        """A row over every position of the batch, held by the error that
        all but ``1 - QUANTILE`` of the positions stay under (the largest
        is beside it)."""
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        err, scale = np.abs(got - want), float(np.abs(want).max())
        spread = float(np.quantile(err, QUANTILE))
        rows[name] = {
            "ok": spread <= tol[name]["atol"] + tol[name]["rtol"] * scale,
            "p999_abs_err": spread, "max_abs_err": float(err.max()),
            "tol": tol[name], "scale": scale,
        }

    window = int(sys["widths"]["sliding_window"])
    for name, want in reference["values"].items():
        got = sys["values"][name]
        if name.startswith("act/"):
            # positions a ring has not yet forgotten anything at, and the rest
            got, want = np.asarray(got), np.asarray(want)
            spread_row(f"{name}/under", got[:, :window], want[:, :window])
            if got.shape[1] > window:
                spread_row(f"{name}/over", got[:, window:], want[:, window:])
        elif np.ndim(want):
            spread_row(name, got, want)
        else:
            row(name, got, want)
    metrics = sys["metrics"]
    row("attn/window_keys_mean", metrics["attn/window_keys_mean"],
        reference["window_keys_mean"])
    row("attn/gate_mean", metrics["attn/gate_mean"], reference["gate_mean"])
    routing = reference["routing"]
    rows["route/agree_share"] = {
        "ok": routing["agree_share"] >= AGREE_SHARE_MIN,
        "value": routing["agree_share"], "min": AGREE_SHARE_MIN,
    }
    rows["route/tie_gap"] = {
        "ok": routing["tie_gap"] <= TIE_GAP, "value": routing["tie_gap"],
        "max": TIE_GAP,
    }
    rows["route/score_agree"] = {
        "ok": routing["score_agree"] >= SCORE_AGREE_MIN,
        "value": routing["score_agree"], "min": SCORE_AGREE_MIN,
    }
    rows["moe/overflow"] = {
        "ok": metrics["moe/overflow"] == 0.0, "value": metrics["moe/overflow"],
    }
    # the replayed decode is the rollout's decode: the same program on the
    # same observations
    replay_err = float(np.abs(
        np.asarray(sys["values"]["act/mean_again"], np.float64)
        - np.asarray(sys["values"]["act/mean"], np.float64)
    ).max())
    rows["act/replay_is_rollout"] = {
        "ok": replay_err <= 1e-6, "max_abs_err": replay_err,
    }
    # and the rollout run alone is the session's
    got, want = sys["episodes"], metrics
    same = got["episode/count"] == want["episode/count"] and (
        abs(got["episode/return"] - want["episode/return"])
        <= EPISODES_RTOL * abs(want["episode/return"])
        or got["episode/count"] == 0.0
    )
    rows["collect/rollout_is_session"] = {"ok": same, "alone": got, "row": {
        k: want[k] for k in got
    }}
    err, n = rows_differ(*sys["first_rows"])
    rows["session/repeats"] = {"ok": err == 0.0, "max_rel_err": err, "keys": n}
    # the step after a wrap is position 0 of a fresh segment
    wrap = sys["wrap"]
    wrap_errs = [
        float(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).max())
        for a, b in zip(wrap["step"], wrap["first"])
    ]
    rows["act/wrap_is_fresh"] = {
        "ok": max(wrap_errs) <= WRAP_ATOL and int(wrap["pos"]) == 1,
        "max_abs_err": max(wrap_errs), "tol": WRAP_ATOL,
        "pos_after": int(wrap["pos"]),
    }
    if "learn" in reference:
        learn = reference["learn"]
        change = learn["change"]
        for group, err in change["groups"].items():
            name = "learn/param_change" + ("" if group == "all" else f"/{group}")
            row(name, err, 0.0)
        row("learn/leaf_moved", change["leaf_moved"], 0.0)
        rows["learn/leaf_moved"].update(
            worst_leaf=change["worst_leaf"], leaves=change["leaves"],
            unmoved_leaves=change["unmoved_leaves"],
        )
        rows["learn/leaf_moved"]["ok"] &= not change["unmoved_leaves"]
        # a leaf without a gradient (the routers) rests on both sides
        rows["learn/router_still"] = {
            "ok": not change["moved_alone"] and all(
                "['router']" in leaf for leaf in change["at_rest"]
            ),
            "moved_alone": change["moved_alone"], "at_rest": change["at_rest"],
        }
        # the row's share is over the iteration's eight learn passes, whose
        # routers and (up to the steps taken) inputs are the prepare pass's
        row("moe/held_share", metrics["moe/held_share"], held_share(sys))
        stopped = metrics["policy/early_stopped"] > 0.0
        near = any(
            abs(kl - learn["threshold"]) <= KL_BAND for kl in learn["kl_steps"]
        )
        rows["learn/early_stopped"] = {
            "ok": stopped == learn["early_stopped"] or near,
            "program": stopped, "reference": learn["early_stopped"],
            "kl_steps": learn["kl_steps"], "threshold": learn["threshold"],
            "branches": learn["branches"],
        }
    if session_row is not None:
        # and the measured session's first row is that iteration's
        err, n = rows_differ(metrics, session_row)
        rows["session/replayed"] = {"ok": err == 0.0, "max_rel_err": err, "keys": n}
    return {"ok": all(r["ok"] for r in rows.values()), "comparisons": rows}


def check(cfg, run) -> dict:
    """The on-chip reference check of one run (seeded as its session)."""
    t0 = time.perf_counter()
    sys = system_reports(
        run.config, run.cell, run.folder + "_check", run.seed, run.rehearse
    )
    t1 = time.perf_counter()
    reference = reference_reports(sys, in_place=True)
    first = run.stamps[0] if run.stamps else None
    out = compare(
        sys, reference,
        session_row=first.row if first and first.iteration == 2 else None,
    )
    out["parameters"] = sum(int(x.size) for x in sys["before"]["params"].values())
    # where the check's own time went: a run has 360 s in all
    out["seconds"] = {
        "system": t1 - t0, **reference["seconds"],
        "check": time.perf_counter() - t0,
    }
    return out


# -- operations and bytes ------------------------------------------------------

def require_program() -> None:
    """A program without the 'swa_moe' blocks cannot run this configuration:
    its config system takes the unknown keys and launches a toy policy
    instead. Say so before anything launches (the harness asks for the
    iteration's cost first, before JAX loads)."""
    import importlib.util

    from benchmarks.harness.manifest import ManifestError

    if importlib.util.find_spec("surreal_tpu.models.swa_moe") is None:
        raise ManifestError(
            "benchmarks/reference/ppo_laguna_ref.py: this program has no "
            "model.encoder.block='swa_moe' (surreal_tpu/models/swa_moe.py)"
        )


def run_layers(widths: dict) -> list:
    """``(kind, dense)`` of the layers as run, from ``widths``."""
    return [
        ("full" if t == "full_attention" else "window", i in widths["mlp_only_layers"])
        for i, t in enumerate(widths["layer_types"])
    ]


def layer_params(widths: dict) -> dict:
    """Parameters of one attention of each type, a dense SwiGLU, a routed
    layer's router, held experts and shared expert, and a layer's two norms."""
    D, hd = int(widths["hidden_size"]), int(widths["head_dim"])
    G = int(widths["num_key_value_heads"])
    attn = lambda H: 2 * D * H * hd + 2 * D * G * hd + D * H  # noqa: E731
    expert = 3 * D * int(widths["moe_intermediate_size"])
    return {
        "attn_full": attn(int(widths["full_attention_heads"])),
        "attn_window": attn(int(widths["sliding_attention_heads"])),
        "dense_ffn": 3 * D * int(widths["intermediate_size"]),
        "router": D * int(widths["router_outputs"]),
        "expert": expert,
        "held_experts": int(widths["num_held"]) * expert,
        "shared": 3 * D * int(widths["shared_expert_intermediate_size"]),
        "norms": 2 * D,
    }


def parameters(widths: dict) -> dict:
    """By group (``by_group``), the layers in all (``layers``: what the
    issue's 733 943 808 counts) and with them the projection in, the last
    norm and the heads (``total``, what ``learner.init`` holds)."""
    per = layer_params(widths)
    by_group = {k: 0 for k in (
        "attn_full", "attn_window", "dense_ffn", "router", "held_experts",
        "shared", "norms",
    )}
    for kind, dense in run_layers(widths):
        by_group[f"attn_{kind}"] += per[f"attn_{kind}"]
        by_group["norms"] += per["norms"]
        if dense:
            by_group["dense_ffn"] += per["dense_ffn"]
        else:
            for k in ("router", "held_experts", "shared"):
                by_group[k] += per[k]
    D, A = int(widths["hidden_size"]), int(widths["action_dim"])
    ends = int(widths["obs_dim"]) * D + D + D * (A + 1) + (A + 1) + A
    layers = sum(by_group.values())
    return {"by_group": by_group, "layers": layers, "total": layers + ends}


def token_macs(widths: dict, T: int) -> dict:
    """One token's forward through the trunk as run here, by part, the
    attention layers at their average reach over a ``T``-position segment
    (sliding: ``window_keys_mean``; full: ``(T + 1) / 2``), the held experts
    at even routing (``num_experts_per_tok x num_held / router_outputs``
    assignments a token a layer: 0.3125). Products only: norms, the
    rotation, the softmaxes and the gates' sigmoid are not counted
    (harness/flops.py)."""
    D, hd = int(widths["hidden_size"]), int(widths["head_dim"])
    per = layer_params(widths)
    reach = {
        "full": (T + 1) / 2.0,
        "window": window_keys_mean(T, int(widths["sliding_window"])),
    }
    heads = {
        "full": int(widths["full_attention_heads"]),
        "window": int(widths["sliding_attention_heads"]),
    }
    even = (
        int(widths["num_experts_per_tok"]) * int(widths["num_held"])
        / int(widths["router_outputs"])
    )
    parts = {k: 0.0 for k in (
        "attn_full", "attn_window", "dense_ffn", "moe_route", "moe_experts",
    )}
    for kind, dense in run_layers(widths):
        parts[f"attn_{kind}"] += (
            per[f"attn_{kind}"] + 2 * heads[kind] * hd * reach[kind]
        )
        if dense:
            parts["dense_ffn"] += per["dense_ffn"]
        else:
            parts["moe_route"] += per["router"]
            parts["moe_experts"] += even * per["expert"] + per["shared"]
    ends = int(widths["obs_dim"]) * D + D * (int(widths["action_dim"]) + 1)
    return dict(parts, forward=ends + sum(parts.values()))


def iteration_cost(config: dict, traffic: dict) -> dict:
    """Required operations and bytes of one fused iteration
    (harness/flops.py has the rules). Forward equivalents a sample: 1 to act,
    1 in prepare (``T + 1`` positions a segment), ``epochs`` x 3 in sgd (a
    backward pass is two forwards; the recomputed forward is not counted).
    ``collect_bytes``: the acting scan reads the bfloat16 weights once a
    step, each full layer's cache and each sliding layer's ring up to the
    step's reach, and writes a row in each. ``expert_flops_per_assignment``:
    one expert's forward over one token."""
    require_program()
    widths = config["widths"]
    envs, T = int(traffic["num_envs"]), int(traffic["horizon"])
    epochs, mbs = int(traffic["epochs"]), int(traffic["num_minibatches"])
    samples = envs * T
    tok = token_macs(widths, T)
    rollout = samples * tok["forward"]
    prepare = envs * (T + 1) * tok["forward"]
    sgd = samples * epochs * 3 * tok["forward"]
    n = parameters(widths)
    kinds = [k for k, _ in run_layers(widths)]
    G, hd = int(widths["num_key_value_heads"]), int(widths["head_dim"])
    W = int(widths["sliding_window"])
    row = 2 * 2 * G * hd                      # a position's keys and values, bfloat16
    reach_full = sum(range(1, T + 1))
    reach_window = sum(min(t + 1, W) for t in range(T))
    cache_read = envs * row * (
        kinds.count("window") * reach_window + kinds.count("full") * reach_full
    )
    cache_write = T * envs * row * len(kinds)
    collect_bytes = T * 2 * n["total"] + cache_read + cache_write
    optimizer_bytes = epochs * mbs * n["total"] * (4 * 7)
    return {
        "samples": samples,
        "flops": 2 * (rollout + prepare + sgd),
        "flops_rollout": 2 * rollout,
        "flops_learn": 2 * (prepare + sgd),
        "bytes": collect_bytes + optimizer_bytes,
        "collect_bytes": collect_bytes,
        "optimizer_bytes": optimizer_bytes,
        "forward_equivalents": 2 + 3 * epochs,
        "expert_flops_per_assignment": 2 * layer_params(widths)["expert"],
        "shared_flops_per_token": 2 * layer_params(widths)["shared"],
        "routed_layers": sum(1 for _, dense in run_layers(widths) if not dense),
        "token_forward_macs": tok,
        "parameters": n,
    }
