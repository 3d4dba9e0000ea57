"""Plain reference for the Phi-4-mini-flash-reasoning trajectory policy
under PPO (``ppo_lift_phi4flash``).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the published config
(microsoft/Phi-4-mini-flash-reasoning ``config.json``) and the papers its
layers come from: the selective state-space layer of Mamba (Gu & Dao 2023,
arXiv:2312.00752, algorithm 2 and section 3.6), sliding-window and full
grouped-query attention, and the gated memory unit and cross-layer
attention of SambaY (Ren et al. 2025, arXiv:2507.06607). It reads the
learner's parameter tree and nothing else of the program: no flax module,
no ``ops/`` function. The recurrence is a sequential ``lax.scan`` over
positions, the conv four shifted products, attention an explicit ``[T, T]``
mask; no cache, no chunking, no recomputation. ``x`` the residual stream,
``h = LN(x)`` (weight and bias, eps 1e-5), every layer
``x += Mixer(h); x += (SiLU(h' W_gate) * h' W_up) W_down`` with ``h' =
LN(x)``; the six layers in order:

    0 state-space   [u, z] = h W_in;  u' = SiLU(sum_k w_k u_{t-3+k} + b_c)
                    [d, B, C] = u' W_x;  delta = softplus(d W_dt + b_dt)
                    s_t = exp(delta_t A) s_{t-1} + (delta_t u'_t) B_t^T,  A = -exp(A_log)
                    y_t = s_t C_t + D u'_t;  out = (y * SiLU(z)) W_out
    1 window        softmax((q k^T) / 8 over keys t-511 .. t) v W_o, 40 query
                    heads over 20 key-value heads of 64
    2 state-space   as 0; its y before the gate is the memory m
    3 full          as 1 over keys 0 .. t; its k, v are kept
    4 gated memory  (m * SiLU(h W_in)) W_out
    5 cross         q = h W_q over layer 3's k, v, keys 0 .. t;  W_o

then a final LayerNorm and the float32 heads: ``mean``, ``value`` (dense
with bias) and a state-independent ``log_std``. No positional term.

Kept from the repo, and stated in the configuration: the state and
attention span episode ends inside a segment; the obs filter of
``ppo_lift`` normalises the 17 observations; the PPO loss is the repo's
(clipped surrogate, clipped value loss, entropy bonus 0.01); GAE has two
masks.

``check`` runs on the chip, outside the window, and compares what the
timed path itself produces at the timed sizes: the second iteration of the
measured session, 16 envs x 1024 positions and 2 x 2 minibatches of 8
envs, trained again from ``--seed`` through ``select_trainer(cfg).run``
(the harness has freed the measured session before the check runs, and a
fused iteration returns neither its batch nor the state it was given:
:func:`system_reports` says how the iteration is taken apart). The row the
second iteration ends with has to be the measured session's first row
(``session/replayed``: the cell's cadence is two iterations), so what is
compared is what the window's session ran. Of that iteration:

(a) ``act/*``: what the decode through state, ring and shared cache
    produced at every position of the iteration's rollout (mean, value, the
    behaviour log-prob) against one full reference forward; reported apart
    for positions under and past the window (``.../under``, ``.../over``),
    so a ring that forgets wrongly shows. ``act/ssm_state/layer<i>``: the
    float32 state the acting carry holds after the last step against the
    reference's recurrence, as a share of its largest entry: the row that
    sees the state's precision (a state or a decay held in bfloat16 reads
    ten times the rounding of the products around it, whose errors a
    thousand steps average out where a state's own accumulate).
    ``act/wrap_is_fresh``: the step after a wrap is position 0 of a fresh
    segment. The rollout runs once more as a program of its own for its
    batch; ``collect/rollout_is_session`` holds the returns of the episodes
    that ended in it to the session's row;
(b) ``prepare/*``: ``_prepare_seq``'s values, advantages and targets on
    that batch, and the fused row's own ``adv/mean_abs``;
(c) ``learn/*``: the whole ``learn`` of the fused iteration: both epochs
    and both minibatches of each, recomputation on, Adam from the moments
    the session held. The reference takes the same four steps in float32
    (the envs of a minibatch one at a time: the losses are means over equal
    blocks, and the sequential scan's autodiff keeps every state, 0.34 GB a
    tensor an env), Adam on the host in numpy (a block of rows at a time on
    the host's threads: ``blocks``), and ``learn/param_change``
    is the norm of (the program's change of the parameters - the
    reference's) over the norm of the reference's, whole and by kind of
    layer: 1 is what a state left unchanged reads, and a minibatch left
    out reads near it. ``learn/leaf_moved`` holds every leaf's change in
    norm to the reference's; the row's losses, KL and gradient norm are
    the means ``_finalize`` reports;
(d) ``attn/window_keys_mean`` and ``ssm/state_abs_max`` of the fused row
    against the mask's count (384.25 at 1024 positions and a window of 512)
    and for being finite.

The steps after a minibatch's KL passes ``kl_early_stop x kl_target`` train
the value alone. The reference decides that by its own KL; where that lies
within ``KL_BAND`` of the threshold and the program's row says a step
stopped, it follows both decisions and the nearer result is compared.
"""

from __future__ import annotations

import math
import os
import shutil
import time

from benchmarks.harness.checks import close

ENV_BLOCK = 2       # envs a reference forward takes at once
KL_BAND = 4.0e-3    # a tenth of the threshold kl_early_stop x kl_target

KINDS = ("ssm", "window", "full", "gmu", "cross")
GROUPS = KINDS + ("ends",)   # the projection in, the last norm, the heads

# Tolerances, from the chip at the published widths (my chip runs, PR 39: the
# session's second iteration at 16 x 1024, seeds 2147485101, 2147485111 and
# 2147485112; every control on seed 2147485101's iteration in the same
# process). What the program read, largest of the three:
#       act/mean            |max| 0.17-0.19 (the mean head at its own
#                           scale);  largest error 0.0043
#       act/value           |max| 11-16;  0.115
#       act/logp            up to 15.6 nats;  0.0156
#       act/ssm_state       0.0071 of the state's largest entry
#       prepare/values, /advantages, /targets   0.309, 0.152, 0.047
#       prepare/adv_mean_abs  0.71-0.76;  4.2e-5 (the rehearsal's 48
#                           samples read 6e-4 to 9e-4; advantages left
#                           unnormalised read 0.1 and more)
#       learn/loss_pg       -0.0014 to -0.0033;  1.2e-5
#       learn/loss_value    6.1-7.6;  3.9e-4 of its value
#       learn/entropy       3.67;  4.2e-6
#       learn/kl            0.010-0.024 (a step's largest 0.031; the
#                           threshold is 0.04);  1.2e-4
#       learn/grad_norm     27-37;  1.35e-3 of its value
#       learn/param_change  0.0134 whole; 0.015 in the layers of a kind;
#                           **0.174-0.188 in the ends on every seed** (the
#                           projection in, the last norm, the heads): jax:lift
#                           holds one observation at 0.02 for ever, the
#                           filter normalises it to the rounding of its
#                           running mean, and Adam makes whole steps of the
#                           projection's row for it; at the rehearsal's widths
#                           four units in the mean's last place move the ends
#                           from 2.4e-5 to 0.042 and nothing else (PERF.md
#                           section 7)
#       learn/leaf_moved    0.0083 (the projection in)
#       the two session rows and act/replay_is_rollout, act/wrap_is_fresh  0
#                           (act/wrap_is_fresh read 0.0024 on one seed of
#                           thirty-three while its two steps were traced into
#                           one program: wrap_replay says why)
# **A row over every position is held by the error all but a thousandth of
# its positions stay under** (QUANTILE), the largest beside it: seed
# 2147485111's prepare/values read 0.309 at one position of 16 384 (env 12,
# step 123, its neighbours 0.11 and 0.10, act/value 0.098 at the same spot)
# where the next largest was 0.17 and the thousandth-largest 0.084. That
# seed's arrays, by that rule: act/mean 0.0019, act/value 0.063, act/logp
# 0.0081, prepare/values 0.084, /advantages 0.040, /targets 0.028; the limits
# are three times these, and the other two seeds' LARGEST errors are under
# them too.
# Five more seeds, the first by QUANTILE on the chip (PR 39's fix round,
# 2147486211-15, one of them traced), largest: act/mean 0.0028 (its largest
# error 0.0054), act/value 0.098 (0.187), act/logp 0.011 (0.018),
# act/ssm_state 0.0086, prepare/values 0.101 (0.23), /advantages 0.061
# (0.127), /targets 0.047 (0.083), /adv_mean_abs 1.1e-4, learn/loss_pg
# 1.1e-4, learn/loss_value 0.2% of its value, learn/kl 4.1e-4, learn/grad_norm
# 0.6% of its value, learn/param_change 0.027 whole and 0.021-0.030 by kind,
# 0.198 in the ends, learn/leaf_moved 0.010.
# What a control moves, as its largest error (seed 2147485101; under / over;
# by QUANTILE not measured on the chip: the budget was spent): the state in
# bfloat16 act/ssm_state 0.180, 0.192 and act/value 0.45 / 2.09; exp(delta A)
# in bfloat16 0.333, 0.318 and 0.38 / 2.10; the window ignored act/mean/over
# 0.053, act/value/over 1.62, act/logp/over 0.176, act/ssm_state/layer2
# 0.202, prepare/values 1.30 (every /under row as with nothing dropped); a
# cross layer given its own keys act/mean 0.032 / 0.0085, act/value 1.97 /
# 0.87, act/logp 0.063 / 0.025, prepare/values 1.92; the memory taken after
# the gate act/mean 0.022 / 0.024, act/value 0.80 / 0.61, act/logp 0.112 /
# 0.071, prepare/values 0.68; the second minibatch of each epoch left out
# learn/param_change 0.838 whole, 0.79-0.90 by group, learn/leaf_moved 1.09,
# learn/loss_pg 0.0153, learn/loss_value 26%, learn/kl 0.0087,
# learn/grad_norm 51%. (The conv, the D skip, the gate and dt's bias moved
# act/value by 1.8-4.6 in the first form's readings: 2 envs x 1024 from the
# initialisation, twenty-seven readings, largest act/value 0.098.)
# learn/param_change and learn/leaf_moved: between the reading and 1, which
# a state left unchanged reads, with the more room above the reading (the
# rehearsal's toy widths read 0.32 of learn/leaf_moved in 'mixed': a state of
# 3e-4 leaves A_log a gradient at the rounding's own size).
TOL = {
    "act/mean/under": dict(rtol=0.0, atol=6.0e-3),
    "act/mean/over": dict(rtol=0.0, atol=6.0e-3),
    "act/value/under": dict(rtol=0.0, atol=2.0e-1),
    "act/value/over": dict(rtol=0.0, atol=2.0e-1),
    "act/logp/under": dict(rtol=0.0, atol=2.5e-2),
    "act/logp/over": dict(rtol=0.0, atol=2.5e-2),
    "act/ssm_state": dict(rtol=0.0, atol=3.0e-2),
    "prepare/values": dict(rtol=0.0, atol=2.5e-1),
    "prepare/advantages": dict(rtol=0.0, atol=1.5e-1),
    "prepare/targets": dict(rtol=0.0, atol=1.0e-1),
    "prepare/adv_mean_abs": dict(rtol=0.0, atol=2.0e-3),
    "learn/loss_pg": dict(rtol=0.0, atol=1.0e-3),
    "learn/loss_value": dict(rtol=2.4e-2, atol=0.0),
    "learn/entropy": dict(rtol=0.0, atol=1e-3),
    "learn/kl": dict(rtol=0.0, atol=1.0e-3),
    "learn/grad_norm": dict(rtol=5.0e-2, atol=0.0),
    "learn/param_change": dict(rtol=0.0, atol=2.5e-1),
    **{f"learn/param_change/{g}": dict(rtol=0.0, atol=2.5e-1) for g in KINDS},
    "learn/param_change/ends": dict(rtol=0.0, atol=6.0e-1),
    "learn/leaf_moved": dict(rtol=0.0, atol=5.0e-1),
    "attn/window_keys_mean": dict(rtol=0.0, atol=1.0e-3),
}
# the share of a batch's positions whose error a row over positions is held
# by: all but 16 of 16 x 1024
QUANTILE = 0.999
# the returns of the episodes that ended in the rollout run alone, against
# the row's: sums of 16 384 float32 rewards in two programs' orders
EPISODES_RTOL = 1e-5
WRAP_ATOL = 1e-6
# a leaf with fewer elements moves by a handful of signs: its norm is held
# within its group's direction, and to have moved at all
LEAF_MIN_SIZE = 256
# every term a comparison has to catch when it is dropped or, of the last
# four, computed in the precision below, with the window ignored, or with a
# minibatch of each epoch left out
TERMS = (
    "window_mask", "conv", "d_skip", "gate", "dt_bias",
    "memory_after_gate", "cross_own_keys", "state_bf16", "decay_bf16",
    "second_minibatch",
)


# -- the layers ----------------------------------------------------------------

def layer_norm(p, x, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def swiglu(p, x):
    import jax

    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def as_bf16(a):
    """bfloat16's 8 bits as an op of its own: a plain cast pair is one XLA
    may elide (xla_allow_excess_precision)."""
    import jax

    return jax.lax.reduce_precision(a, 8, 7)


def ssm_inputs(p, h, w, dropped=None):
    """What the recurrence takes: ``(u' [B, T, C], delta [B, T, C], B, C
    [B, T, N], z [B, T, C])``."""
    import jax
    import jax.numpy as jnp

    C = int(w["ssm_expand"]) * int(w["hidden_size"])
    K, N, R = int(w["ssm_conv_kernel"]), int(w["ssm_state_size"]), int(w["ssm_dt_rank"])
    T = h.shape[1]
    uz = h @ p["in_proj"]
    u, z = uz[..., :C], uz[..., C:]
    if dropped != "conv":
        # tap k multiplies the input K - 1 - k positions back
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        u = sum(p["conv"][k] * padded[:, k:k + T] for k in range(K)) + p["conv_bias"]
    u = jax.nn.silu(u)
    dbc = u @ p["x_proj"]
    pre = dbc[..., :R] @ p["dt_proj"]
    if dropped != "dt_bias":
        pre = pre + p["dt_bias"]
    return u, jax.nn.softplus(pre), dbc[..., R:R + N], dbc[..., R + N:], z


def recurrence(p, u, delta, Bm, Cm, dropped=None, state=None):
    """The selective recurrence, one position at a time: ``(y [B, T, C],
    final state [B, C, N])``."""
    import jax
    import jax.numpy as jnp

    A = -jnp.exp(p["A_log"])                       # [C, N]
    if state is None:
        state = jnp.zeros((u.shape[0], *A.shape), jnp.float32)

    def step(s, x):
        u_t, d_t, b_t, c_t = x                     # [B, C], [B, C], [B, N], [B, N]
        decay = jnp.exp(d_t[..., None] * A)
        if dropped == "decay_bf16":
            decay = as_bf16(decay)
        s = decay * s + (d_t * u_t)[..., None] * b_t[:, None, :]
        if dropped == "state_bf16":
            s = as_bf16(s)
        y = (s * c_t[:, None, :]).sum(-1)
        if dropped != "d_skip":
            y = y + p["D"] * u_t
        return s, y

    tm = lambda a: a.swapaxes(0, 1)
    state, y = jax.lax.scan(step, state, (tm(u), tm(delta), tm(Bm), tm(Cm)))
    return tm(y), state


def ssm(p, h, w, dropped=None):
    """``(out [B, T, D], y before the gate, final state [B, C, N])``."""
    import jax

    u, delta, Bm, Cm, z = ssm_inputs(p, h, w, dropped)
    y, state = recurrence(p, u, delta, Bm, Cm, dropped)
    gated = y if dropped == "gate" else y * jax.nn.silu(z)
    return (
        gated @ p["out_proj"],
        gated if dropped == "memory_after_gate" else y,
        state,
    )


def attention(p, h, w, window=None, kv=None):
    """Grouped-query softmax attention with an explicit ``[T, T]`` mask:
    causal, and with ``window`` keys ``t - window + 1 .. t`` alone;
    ``kv`` another layer's kept keys and values. ``(out, (k, v))``."""
    import jax.numpy as jnp

    q = jnp.einsum("btd,dhe->bthe", h, p["q"])
    if kv is None:
        kv = (jnp.einsum("btd,dge->btge", h, p["k"]),
              jnp.einsum("btd,dge->btge", h, p["v"]))
    k, v = kv
    B, T, H, hd = q.shape
    G = k.shape[2]
    rep = lambda a: jnp.repeat(a, H // G, axis=2)  # key-value head h // (H / G)
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, rep(k)) / math.sqrt(hd)
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask &= t[None, :] > t[:, None] - window
    scores = jnp.where(mask, scores, -jnp.inf)
    e = jnp.exp(scores - scores.max(-1, keepdims=True))
    prob = e / e.sum(-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhe->bqhe", prob, rep(v))
    return jnp.einsum("bqhe,hed->bqd", out, p["o"]), kv


def gmu(p, h, m):
    import jax

    return (m * jax.nn.silu(h @ p["in_proj"])) @ p["out_proj"]


def layer_kinds(w) -> list:
    a, b = int(w["pairs_before"]), int(w["pairs_after"])
    return (
        [("ssm", False), ("window", False)] * a
        + [("ssm", True), ("full", True)]
        + [("gmu", False), ("cross", False)] * b
    )


def trunk(params, obs, w, dropped=None):
    """``obs [B, T, 17]`` (normalised) -> ``(h [B, T, D]`` after the last
    norm, the state-space layers' final states ``{layer index: [B, C,
    N]})``."""
    p = params["params"]["trunk"]
    eps = float(w["layer_norm_eps"])
    x = obs @ p["embed"]["kernel"]
    m = kept = None
    states = {}
    for i, (kind, keeps) in enumerate(layer_kinds(w)):
        layer = p[f"layer{i}"]
        mixer = layer["mixer"]
        h = layer_norm(layer["mixer_norm"], x, eps)
        if kind == "ssm":
            out, y, states[i] = ssm(mixer, h, w, dropped)
            if keeps:
                m = y
        elif kind in ("window", "full"):
            window = int(w["sliding_window"]) if kind == "window" else None
            if dropped == "window_mask":
                window = None
            out, kv = attention(mixer, h, w, window)
            if keeps:
                kept = kv
        elif kind == "gmu":
            out = gmu(mixer, h, m)
        elif dropped == "cross_own_keys":
            # a cross layer given keys and values of its own input, through
            # the full layer's projections
            full = next(
                p[f"layer{j}"]["mixer"]
                for j, (k, _) in enumerate(layer_kinds(w)) if k == "full"
            )
            out, _ = attention(dict(full, q=mixer["q"], o=mixer["o"]), h, w)
        else:
            out, _ = attention(mixer, h, w, None, kv=kept)
        x = x + out
        x = x + swiglu(layer["ffn"], layer_norm(layer["ffn_norm"], x, eps))
    return layer_norm(p["norm"], x, eps), states


def policy(params, obs, w, dropped=None):
    """``(mean [B, T, A], log_std [B, T, A], value [B, T], the state-space
    layers' final states)``."""
    import jax.numpy as jnp

    p = params["params"]
    h, states = trunk(params, obs, w, dropped)
    mean = h @ p["mean"]["kernel"] + p["mean"]["bias"]
    value = (h @ p["value"]["kernel"] + p["value"]["bias"])[..., 0]
    return mean, jnp.broadcast_to(p["log_std"], mean.shape), value, states


def window_keys_mean(T: int, window: int) -> float:
    """Keys a windowed causal query sees, averaged over ``T`` positions."""
    return sum(min(t + 1, window) for t in range(T)) / T


# -- PPO around them -----------------------------------------------------------

def ppo_ref():
    """The obs filter, the Gaussian log-prob and two-mask GAE are
    ``ppo_lift``'s: its reference has them."""
    from benchmarks.harness import manifest

    return manifest.load_reference("ppo_ref")


def joyai_ref():
    """``_Static``, a dict as a hashable static argument, is
    ``ppo_lift_joyai``'s: its reference has it."""
    from benchmarks.harness import manifest

    return manifest.load_reference("ppo_joyai_ref")


def ppo_loss(params, mb, w, algo, dropped, policy_coeff):
    """The total PPO differentiates and ``(pg, value loss, entropy, KL)``;
    ``mb`` env-major ``[B, T, ...]``; ``policy_coeff`` 0 once a minibatch's
    KL has stopped the policy's steps."""
    import jax.numpy as jnp

    mean, log_std, value, _ = policy(params, mb["obs"], w, dropped)
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
    """The kind of layer a parameter's path lies in (its mixer, norms and
    SwiGLU alike), ``ends`` outside the layers."""
    for i, (kind, _) in enumerate(layer_kinds(w)):
        if f"['layer{i}']" in path:
            return kind
    return "ends"


# The host's share of the check is arithmetic over 633M-element trees. A
# fresh 2.5 GB array costs more in page faults than its arithmetic does, so
# every leaf-sized pass below works in place or in temporaries of CHUNK
# elements (1 MB: reused by the allocator, and in the cache), leaves side by
# side on the host's threads (numpy releases the lock): Adam's step over the
# tree took 16 s as whole-leaf expressions on one thread and takes 1.8 s so
# (8 cores; the arithmetic of an element is the same).
HOST_THREADS = 8
CHUNK = 1 << 18
_POOL = None


def over(fn, items) -> list:
    """``[fn(x) for x in items]`` on the host's threads."""
    global _POOL
    if _POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _POOL = ThreadPoolExecutor(HOST_THREADS)
    return list(_POOL.map(fn, items))


def blocks(a) -> list:
    """``a`` (no scalar) cut along its first axis into views of about CHUNK
    elements. Views whatever the strides: a leaf fetched from the chip comes
    in the device's layout, which is not always row-major, and a reshape of
    such a leaf is a copy that takes an in-place step with it."""
    rows = max(1, CHUNK // max(1, a[:1].size))
    return [slice(i, i + rows) for i in range(0, a.shape[0], rows)]


def sq_sum(*terms) -> float:
    """``sum((a - b - ...) ** 2)`` in float64 over whole leaves, a block at a
    time."""
    import numpy as np

    first, *rest = (np.atleast_1d(t) for t in terms)
    total = 0.0
    for s in blocks(first):
        a = first[s]
        for t in rest:
            a = a - t[s]
        total += float(np.square(a, dtype=np.float64).sum())
    return total


def flat(tree, copy: bool = True) -> dict:
    """``{leaf path: float32 numpy array}`` on the host, each a copy of
    its own (a donated buffer is not aliased, and Adam writes in place);
    without ``copy`` the transfer's own buffer, which is read-only."""
    import jax
    import numpy as np

    paths, leaves = zip(*jax.tree_util.tree_leaves_with_path(tree))
    for x in leaves:
        x.copy_to_host_async()
    return dict(zip(
        map(jax.tree_util.keystr, paths),
        over(np.array if copy else np.asarray, leaves),
    ))


def adam_step(work: dict, grads: dict, lr: float, max_norm: float,
              b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8) -> float:
    """Plain Adam (Kingma & Ba 2015, algorithm 1, with the bias corrections)
    on ``grads`` clipped to a global norm of ``max_norm``, in place on
    ``work {"params", "mu", "nu", "delta": {leaf: array}, "count"}``, numpy
    float32 a leaf at a time; ``delta`` sums the steps. The gradient's norm
    before the clip."""
    import numpy as np

    norm = math.sqrt(sum(over(sq_sum, grads.values())))
    clip = np.float32(1.0 if norm < max_norm else max_norm / norm)
    work["count"] += 1
    c1 = np.float32(1.0 - b1 ** work["count"])
    c2 = np.float32(1.0 - b2 ** work["count"])

    def step_leaf(leaf: str) -> None:
        g_all = grads[leaf]
        param, mu_all, nu_all, delta = (
            work[k][leaf] for k in ("params", "mu", "nu", "delta")
        )
        assert g_all.ndim and g_all.shape == param.shape, leaf
        for s in blocks(g_all):
            g = g_all[s] * clip
            mu, nu = mu_all[s], nu_all[s]
            mu *= np.float32(b1)
            mu += np.float32(1.0 - b1) * g
            nu *= np.float32(b2)
            nu += np.float32(1.0 - b2) * g * g
            step = np.float32(-lr) * (mu / c1) / (np.sqrt(nu / c2) + np.float32(eps))
            param[s] += step
            delta[s] += step

    over(step_leaf, grads)
    return norm


def adam_moments(opt_state) -> tuple:
    """``(count, mu, nu)`` of the chain's one Adam link."""
    import jax
    import optax

    is_adam = lambda n: isinstance(n, optax.ScaleByAdamState)  # noqa: E731
    (adam,) = [n for n in jax.tree.leaves(opt_state, is_leaf=is_adam) if is_adam(n)]
    return int(adam.count), adam.mu, adam.nu


def minibatch_order(key, envs: int, epochs: int, num_mb: int) -> list:
    """The env ids of each optimizer step of one ``learn``, in order:
    whole-env rows are shuffled by rows, a fresh permutation an epoch cut
    into ``num_mb`` runs (``learners/ppo.py::_sgd_epochs``, row mode). Every
    env is in exactly one minibatch of every epoch."""
    import jax
    import numpy as np

    size = envs // num_mb
    steps = []
    for epoch_key in jax.random.split(key, epochs):
        perm = np.asarray(jax.random.permutation(epoch_key, envs))
        assert sorted(perm.tolist()) == list(range(envs))
        steps += [perm[i * size:(i + 1) * size].tolist() for i in range(num_mb)]
    assert envs % num_mb == 0, "whole-env minibatches of equal size"
    return steps


def change_errors(got: dict, want: dict, w) -> dict:
    """How far the program's change of the parameters ``got {leaf: array}``
    lies from the reference's ``want``: ``|got - want| / |want|`` over the
    whole tree (``all``) and each group of leaves, and the worst leaf's
    ``| |got| / |want| - 1 |`` among leaves of ``LEAF_MIN_SIZE`` elements or
    more. A state left unchanged reads 1 everywhere."""
    diff = {g: 0.0 for g in GROUPS}
    ref = {g: 0.0 for g in GROUPS}
    worst, worst_leaf, still = 0.0, None, []
    norms = over(
        lambda leaf: (
            sq_sum(got[leaf], want[leaf]), sq_sum(want[leaf]), sq_sum(got[leaf])
        ),
        want,
    )
    for (leaf, d_want), (sq_diff, sq_want, sq_got) in zip(want.items(), norms):
        group = group_of(leaf, w)
        diff[group] += sq_diff
        ref[group] += sq_want
        n_got, n_want = math.sqrt(sq_got), math.sqrt(sq_want)
        if n_got == 0.0:
            still.append(leaf)
        if d_want.size >= LEAF_MIN_SIZE and abs(n_got / n_want - 1.0) > worst:
            worst, worst_leaf = abs(n_got / n_want - 1.0), leaf
    out = {g: math.sqrt(diff[g] / ref[g]) for g in GROUPS}
    out["all"] = math.sqrt(sum(diff.values()) / sum(ref.values()))
    return {
        "groups": out, "leaf_moved": worst, "worst_leaf": worst_leaf,
        "unmoved_leaves": still, "leaves": len(want),
    }


# -- the program's side --------------------------------------------------------

def decode_step(learner, state, carry, obs):
    """One acting step through ``act_step`` itself, asked also for the value
    (a batch does not carry it): ``(new carry, (mean, value))``."""
    import jax

    from surreal_tpu.learners.base import EVAL_DETERMINISTIC

    _, info, carry = learner.act_step(
        state, carry, obs, jax.random.key(0),   # deterministic: reads no key
        EVAL_DETERMINISTIC,
    )
    return carry, (info["mean"], info["value"])


def decode_replay(learner, state, obs_tb):
    """The rollout's acting again over the rollout's own observations
    ``[T, B, obs]``, through ``act_step`` and its carry: ``(the carry after
    the last step, (mean [T, B, A], value [T, B]))``."""
    import jax

    return jax.lax.scan(
        lambda carry, obs: decode_step(learner, state, carry, obs),
        learner.act_init(obs_tb.shape[1]), obs_tb,
    )


def wrap_replay(learner, state, carry, obs):
    """One step more with the segment's first observation from ``carry``,
    which has reached the horizon, and the same step from a fresh carry:
    the carry wraps, and the step must be position 0 of a fresh segment.
    **One executable runs both**, so equal is equal to the bit: every stale
    slot is masked to an exact zero. (As two steps traced into one program
    XLA compiled them apart, and one seed in thirty-three then read 0.0024
    where the others read 0: one activation at a bfloat16 tie rounded the
    other way.) ``((wrapped, fresh) (mean, value), (the recurrent leaves
    after the wrap step, after step 0, the position after the wrap
    step))``."""
    import jax
    import jax.numpy as jnp

    strong = lambda tree: jax.tree.map(   # noqa: E731  (no weak types)
        lambda x: jnp.asarray(x, x.dtype), tree
    )
    carry, fresh = strong(carry), strong(learner.act_init(obs.shape[0]))
    step = jax.jit(
        lambda s, c, o: decode_step(learner, s, c, o)
    ).lower(state, carry, obs).compile()
    wrapped, wrapped_out = step(state, carry, obs)
    first, first_out = step(state, fresh, obs)
    return (wrapped_out, first_out), (
        wrapped["cache"]["ssm"], first["cache"]["ssm"], wrapped["pos"],
    )


BATCH_KEYS = (
    "obs", "next_obs", "action", "reward", "done", "terminated",
    "behavior_logp", "behavior",
)


ROW_PREFIXES = (
    "loss/", "policy/", "value/", "adv/", "health/", "ssm/", "attn/", "episode/",
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


def train(cfg, stop_at: int) -> tuple:
    """A session of the cell through ``select_trainer(cfg).run``, ended at
    the cadence of iteration ``stop_at``: ``(trainer, final state, {iteration:
    metrics row})``."""
    from surreal_tpu.main import launch

    rows = {}

    def at_cadence(iteration: int, row: dict) -> bool:
        rows[int(iteration)] = {
            k: float(v) for k, v in row.items() if isinstance(v, (int, float))
        }
        return int(iteration) >= stop_at

    trainer = launch.select_trainer(cfg)
    state, _ = trainer.run(on_metrics=at_cadence)
    return trainer, state, rows


def system_reports(config: dict, cell: dict, folder: str, seed: int,
                   rehearse: bool, extra: tuple = ()) -> dict:
    """The second iteration of the cell's session from ``seed``, as the
    session itself runs it. The fused program donates its state and returns
    neither that nor its batch, so two sessions are trained through
    ``select_trainer(cfg).run`` at a cadence of one iteration: one ended
    after the first iteration, whose final state is what the second starts
    from (kept on the host), one after the second, whose final state and
    row are what it produced. Between them the second iteration's rollout
    runs once more as a program of its own, from that state, the env carry
    of the first iteration's rollout run the same way, and the keys as
    ``Trainer.run`` and the fused iteration draw them: its batch, decode
    outputs and ``_prepare_seq``. The episodes' returns of that batch have
    to be the row's (``collect/rollout_is_session``), and the first
    session's row the second's first (``session/repeats``)."""
    import jax
    import numpy as np

    from benchmarks.harness import runner
    from surreal_tpu.launch.rollout import device_rollout
    from surreal_tpu.learners.seq_policy import family_config
    from surreal_tpu.main import launch

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

    _, state, first_rows = train(cfg_of("first"), 1)
    count, mu, nu = adam_moments(state.opt_state)
    before = {
        "params": flat(state.params), "mu": flat(mu), "nu": flat(nu),
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
    acting, (mean_again, value) = jax.jit(
        lambda s, o: decode_replay(learner, s, o)
    )(state, batch["obs"])
    # the state-space states [B, N, C] the carry held after the last step
    ssm_states = [layer["state"] for layer in acting["cache"]["ssm"]]
    wrapped, recurrent = wrap_replay(learner, state, acting, batch["obs"][0])
    del acting
    _, values, targets, advantages, data, _ = jax.jit(
        lambda s, b: learner._prepare_seq(s, b, None)
    )(state, batch)
    host = jax.device_get
    batch, data = host(batch), host(data)
    small = host((mean_again, value, wrapped, recurrent, ssm_states,
                  values, targets, advantages))
    mean_again, value, wrapped, recurrent, ssm_states = small[:5]
    values, targets, advantages = small[5:]
    del state, carry

    # the second iteration itself
    _, state, rows = train(cfg_of("second"), 2)
    metrics = rows[2]
    moved = flat(state.params)
    over(lambda leaf: np.subtract(
        moved[leaf], before["params"][leaf], out=moved[leaf]
    ), moved)
    del state
    shutil.rmtree(folder, ignore_errors=True)
    algo, opt = learner.config.algo, learner.config.optimizer
    enc = family_config(learner.config.model.encoder.to_dict())
    widths = config["widths"]
    w = {k: widths[k] for k in ("layer_norm_eps", "ssm_conv_kernel", "ssm_expand")}
    w.update({
        k: enc[k] for k in (
            "hidden_size", "sliding_window", "ssm_state_size", "ssm_dt_rank",
            "pairs_before", "pairs_after",
        )
    })
    return {
        "before": before, "batch": batch, "data": data, "moved": moved,
        "metrics": metrics, "learn_key": lkey, "episodes": episodes,
        "first_rows": (first_rows[1], rows[1]),
        "widths": joyai_ref()._Static(w),
        "algo": {
            k: float(algo[k]) for k in (
                "gamma", "lam", "clip_ratio", "value_coeff", "entropy_coeff",
                "kl_target", "kl_early_stop",
            )
        },
        "epochs": int(algo.epochs), "num_minibatches": int(algo.num_minibatches),
        "lr": float(opt.lr), "max_grad_norm": float(opt.max_grad_norm),
        "wrap": {
            "step": wrapped[0], "first": wrapped[1], "recurrent": recurrent,
        },
        "values": {
            "act/mean": batch["behavior"]["mean"].swapaxes(0, 1),
            "act/mean_again": mean_again.swapaxes(0, 1),
            "act/value": value.swapaxes(0, 1),
            "act/logp": batch["behavior_logp"].swapaxes(0, 1),
            "act/ssm_state": ssm_states,
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

def learn_reference(sys: dict, obs_bt, dropped, in_place: bool) -> dict:
    """The iteration's ``learn`` again in float32: ``epochs x
    num_minibatches`` Adam steps from the state the program started from,
    the gradient of each over its minibatch's envs one at a time. Where a
    decision to stop the policy's steps is within ``KL_BAND`` of its
    threshold and the program's row says one was taken, both decisions are
    followed; of the results, the one nearest the program's change.
    ``in_place`` trains in ``sys["before"]`` itself (7.6 GB at the published
    widths) where a copy is taken otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    static = joyai_ref()._Static
    w, data, before = sys["widths"], sys["data"], sys["before"]
    algo = static({
        k: sys["algo"][k] for k in ("clip_ratio", "value_coeff", "entropy_coeff")
    })
    threshold = sys["algo"]["kl_early_stop"] * sys["algo"]["kl_target"]
    program_stopped = sys["metrics"]["policy/early_stopped"] > 0.0
    envs = obs_bt.shape[0]
    order = minibatch_order(
        sys["learn_key"], envs, sys["epochs"], sys["num_minibatches"]
    )
    if dropped == "second_minibatch":
        order = [mb for i, mb in enumerate(order) if i % sys["num_minibatches"] == 0]
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
        jax.grad(ppo_loss, has_aux=True), static_argnums=(2, 3, 4)
    )
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
        for e in ids:
            one = jax.tree.map(lambda x: x[e:e + 1], mb_all)
            g, aux = grad_fn(tree, one, w, algo, dropped, jnp.float32(coeff))
            total = g if total is None else add(total, g)
            terms += np.asarray([float(a) for a in aux]) / len(ids)
        return flat(share(total, jnp.float32(len(ids))), copy=False), terms

    def fresh(work: dict) -> dict:
        return {
            k: dict(zip(work[k], over(np.copy, work[k].values())))
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
            norm = adam_step(
                work, grads, sys["lr"], sys["max_grad_norm"]
            )
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

    zeros = dict(zip(before["params"], over(np.zeros_like, before["params"].values())))
    with jax.default_matmul_precision("highest"):
        start = dict(before, delta=zeros)
        run(start if in_place else fresh(start), 0, False, [])
    best = min(results, key=lambda r: r["change"]["groups"]["all"])
    return dict(best, branches=len(results), threshold=threshold, seconds=seconds)


def reference_reports(sys: dict, dropped: str | None = None,
                      learn: bool = True, in_place: bool = False) -> dict:
    """The reference's values under the comparisons' names; without
    ``learn``, what one forward gives (``act/*``, ``prepare/*``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    w, batch, before = sys["widths"], sys["batch"], sys["before"]
    params = jax.device_put(
        jax.tree.unflatten(before["treedef"], list(before["params"].values()))
    )
    fwd = jax.jit(policy, static_argnums=(2, 3))
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
    with jax.default_matmul_precision("highest"):
        acted = [fwd(params, acting_obs[b], w, dropped) for b in blocks]
        mean, log_std, value = (cat([a[i] for a in acted]) for i in range(3))
        states = {
            i: cat([a[3][i] for a in acted]) for i in acted[0][3]
        }
        logp = ppo.gauss_logp(mean, log_std, batch["action"].swapaxes(0, 1))
        v_ext = cat([fwd(params, ext[b], w, dropped)[2] for b in blocks])
    del params, acted
    values, v_next = v_ext[:, :-1].T, v_ext[:, 1:].T
    algo = sys["algo"]
    adv, target = ppo.gae(
        batch["reward"], values, v_next, batch["done"],
        batch["terminated"], algo["gamma"], algo["lam"],
    )
    normed = (adv - adv.mean()) / (adv.std() + 1e-8)
    T = obs_bt.shape[1]
    out = {
        "window_keys_mean": window_keys_mean(T, int(w["sliding_window"])),
        "values": {
            "act/mean": mean, "act/value": value, "act/logp": logp,
            # the program's layout: [B, N, C]
            "act/ssm_state": {i: s.swapaxes(1, 2) for i, s in states.items()},
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
        is beside it): one position in 16 384 reads three times the next
        (PERF.md section 6)."""
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
        if name == "act/ssm_state":
            # a share of the layer's largest entry
            for (i, w_i), g_i in zip(sorted(want.items()), got):
                top = float(np.abs(w_i).max())
                row(f"{name}/layer{i}", np.asarray(g_i) / top, w_i / top, name)
        elif name.startswith("act/"):
            # positions a ring has not yet forgotten anything at, and the rest
            got, want = np.asarray(got), np.asarray(want)
            spread_row(f"{name}/under", got[:, :window], want[:, :window])
            if got.shape[1] > window:
                spread_row(f"{name}/over", got[:, window:], want[:, window:])
        elif np.ndim(want):
            spread_row(name, got, want)
        else:
            row(name, got, want)
    row("attn/window_keys_mean", sys["metrics"]["attn/window_keys_mean"],
        reference["window_keys_mean"])
    # the replayed decode is the rollout's decode: the same program on the
    # same observations
    replay_err = float(np.abs(
        np.asarray(sys["values"]["act/mean_again"], np.float64)
        - np.asarray(sys["values"]["act/mean"], np.float64)
    ).max())
    rows["act/replay_is_rollout"] = {
        "ok": replay_err <= 1e-6, "max_abs_err": replay_err,
    }
    # and the rollout run alone is the session's: as many episodes ended
    # in it, with the row's mean return (a rollout that left the session's
    # by a rounding is another trajectory a thousand steps on)
    got, want = sys["episodes"], sys["metrics"]
    same = got["episode/count"] == want["episode/count"] and (
        abs(got["episode/return"] - want["episode/return"])
        <= EPISODES_RTOL * abs(want["episode/return"])
        or got["episode/count"] == 0.0
    )
    rows["collect/rollout_is_session"] = {"ok": same, "alone": got, "row": {
        k: want[k] for k in got
    }}
    # a session repeats itself: the state the first session ended with is
    # what the second's second iteration started from
    err, n = rows_differ(*sys["first_rows"])
    rows["session/repeats"] = {"ok": err == 0.0, "max_rel_err": err, "keys": n}
    # the step after a wrap is position 0 of a fresh segment: the outputs,
    # the position, and the recurrent leaves it left behind
    wrap = sys["wrap"]
    after, fresh, pos = wrap["recurrent"]
    wrap_errs = [
        float(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).max())
        for a, b in zip(
            [*wrap["step"], *_leaves(after)], [*wrap["first"], *_leaves(fresh)]
        )
    ]
    rows["act/wrap_is_fresh"] = {
        "ok": max(wrap_errs) <= WRAP_ATOL and int(pos) == 1,
        "max_abs_err": max(wrap_errs), "tol": WRAP_ATOL, "pos_after": int(pos),
        # mean, value, then the recurrent leaves in the carry's order
        "errs": wrap_errs,
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
        stopped = sys["metrics"]["policy/early_stopped"] > 0.0
        near = any(
            abs(kl - learn["threshold"]) <= KL_BAND for kl in learn["kl_steps"]
        )
        rows["learn/early_stopped"] = {
            "ok": stopped == learn["early_stopped"] or near,
            "program": stopped, "reference": learn["early_stopped"],
            "kl_steps": learn["kl_steps"], "threshold": learn["threshold"],
            "branches": learn["branches"],
        }
    state_max = sys["metrics"]["ssm/state_abs_max"]
    rows["ssm/state_abs_max"] = {
        "ok": math.isfinite(state_max) and state_max > 0.0, "value": state_max,
    }
    if session_row is not None:
        # and the measured session's first row is that iteration's
        err, n = rows_differ(sys["metrics"], session_row)
        rows["session/replayed"] = {"ok": err == 0.0, "max_rel_err": err, "keys": n}
    return {"ok": all(r["ok"] for r in rows.values()), "comparisons": rows}


def _leaves(tree) -> list:
    import jax

    return jax.tree.leaves(tree)


def check(cfg, run) -> dict:
    """The on-chip reference check of one run (seeded from ``--seed``)."""
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
    """A program without the 'ssm_hybrid' blocks cannot run this
    configuration: its config system takes the unknown keys and launches a
    toy policy instead. Say so before anything launches (the harness asks
    for the iteration's cost first, before JAX loads)."""
    import importlib.util

    from benchmarks.harness.manifest import ManifestError

    if importlib.util.find_spec("surreal_tpu.models.ssm_hybrid") is None:
        raise ManifestError(
            "benchmarks/reference/ppo_phi4flash_ref.py: this program has no "
            "model.encoder.block='ssm_hybrid' (surreal_tpu/models/ssm_hybrid.py)"
        )


def layer_params(widths: dict) -> dict:
    """Parameters of one layer of each kind, its two LayerNorms and SwiGLU
    with it, and of the SwiGLU alone (``ffn``)."""
    D = int(widths["hidden_size"])
    C, N = int(widths["ssm_expand"]) * D, int(widths["ssm_state_size"])
    K, R = int(widths["ssm_conv_kernel"]), int(widths["ssm_dt_rank"])
    H, G = int(widths["num_attention_heads"]), int(widths["num_key_value_heads"])
    hd = int(widths["head_dim"])
    ffn = 3 * D * int(widths["intermediate_size"])
    rest = ffn + 4 * D
    mixers = {
        "ssm": D * 2 * C + K * C + C + C * (R + 2 * N) + R * C + C + C * N + C + C * D,
        "window": D * H * hd + 2 * D * G * hd + H * hd * D,
        "gmu": 2 * D * C,
        "cross": 2 * D * H * hd,
    }
    mixers["full"] = mixers["window"]
    return dict({k: v + rest for k, v in mixers.items()}, ffn=ffn, mixers=mixers)


def parameters(widths: dict) -> dict:
    """By kind of layer (``by_kind``), the layers in all (``layers``: what
    the issue's 633.2M counts) and with them the projection in, the last
    norm and the heads (``total``, what ``learner.init`` holds)."""
    per = layer_params(widths)
    a, b = int(widths["pairs_before"]), int(widths["pairs_after"])
    count = {"ssm": a + 1, "window": a, "full": 1, "gmu": b, "cross": b}
    by_kind = {k: n * per[k] for k, n in count.items()}
    D, A = int(widths["hidden_size"]), int(widths["action_dim"])
    ends = int(widths["obs_dim"]) * D + 2 * D + D * (A + 1) + (A + 1) + A
    layers = sum(by_kind.values())
    return {"by_kind": by_kind, "layers": layers, "total": layers + ends}


def token_macs(widths: dict, T: int) -> dict:
    """One token's forward through the trunk as run here, by part, the
    attention layers at their average reach over a ``T``-position segment
    (window: ``window_keys_mean``; full and cross: ``(T + 1) / 2``).
    Products only: norms, the conv, the recurrence (elementwise, on the
    vector unit) and the softmax are not counted (harness/flops.py)."""
    D = int(widths["hidden_size"])
    C, N = int(widths["ssm_expand"]) * D, int(widths["ssm_state_size"])
    R = int(widths["ssm_dt_rank"])
    H, hd = int(widths["num_attention_heads"]), int(widths["head_dim"])
    G = int(widths["num_key_value_heads"])
    a, b = int(widths["pairs_before"]), int(widths["pairs_after"])
    causal = (T + 1) / 2.0
    windowed = window_keys_mean(T, int(widths["sliding_window"]))
    qo, kv = 2 * D * H * hd, 2 * D * G * hd
    parts = {
        "ssm_proj": (a + 1) * (D * 2 * C + C * (R + 2 * N) + R * C + C * D),
        "attn": (
            a * (qo + kv + 2 * H * hd * windowed)
            + (qo + kv + 2 * H * hd * causal)
            + b * (qo + 2 * H * hd * causal)
        ),
        "gmu": b * 2 * D * C,
        "dense_ffn": (2 * a + 2 + 2 * b) * 3 * D * int(widths["intermediate_size"]),
    }
    ends = int(widths["obs_dim"]) * D + D * (int(widths["action_dim"]) + 1)
    return dict(parts, forward=ends + sum(parts.values()))


def scan_bytes_per_token(widths: dict) -> int:
    """What one state-space layer's recurrence must move for one token,
    forward: ``u'`` (bfloat16), ``delta`` (float32), ``B`` and ``C``
    (bfloat16) read, ``y`` (float32) written; the state stays on the chip."""
    C = int(widths["ssm_expand"]) * int(widths["hidden_size"])
    N = int(widths["ssm_state_size"])
    return 2 * C + 4 * C + 2 * 2 * N + 4 * C


def iteration_cost(config: dict, traffic: dict) -> dict:
    """Required operations and bytes of one fused iteration
    (harness/flops.py has the rules). Forward equivalents a sample: 1 to
    act, 1 in prepare (``T + 1`` positions a segment), ``epochs`` x 3 in
    sgd (a backward pass is two forwards; the recomputed forward is not
    counted). ``collect_bytes``: the acting scan reads the bfloat16 weights
    once a step, the ring and the shared cache (twice: the full layer and
    each cross layer) up to the step's reach, and reads and writes the
    states and conv tails. ``scan_bytes``: the recurrence's inputs read
    and outputs written once in every learn-side forward and twice in
    every backward (the cotangents the other way)."""
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
    a, b = int(widths["pairs_before"]), int(widths["pairs_after"])
    D = int(widths["hidden_size"])
    C, N = int(widths["ssm_expand"]) * D, int(widths["ssm_state_size"])
    K = int(widths["ssm_conv_kernel"])
    G, hd = int(widths["num_key_value_heads"]), int(widths["head_dim"])
    W = int(widths["sliding_window"])
    row = 2 * 2 * G * hd                      # a position's keys and values, bfloat16
    reach_full = sum(range(1, T + 1))
    reach_window = sum(min(t + 1, W) for t in range(T))
    cache_read = envs * row * (a * reach_window + (1 + b) * reach_full)
    cache_write = T * envs * row * (a + 1)
    state = (a + 1) * envs * (4 * N * C + 2 * (K - 1) * C)
    collect_bytes = T * (2 * n["total"] + 2 * state) + cache_read + cache_write
    scan_bytes = (
        (a + 1) * scan_bytes_per_token(widths)
        * (envs * (T + 1) + samples * epochs * 3)
    )
    optimizer_bytes = epochs * mbs * n["total"] * (4 * 7)
    return {
        "samples": samples,
        "flops": 2 * (rollout + prepare + sgd),
        "flops_rollout": 2 * rollout,
        "flops_learn": 2 * (prepare + sgd),
        "bytes": collect_bytes + optimizer_bytes + scan_bytes,
        "collect_bytes": collect_bytes,
        "scan_bytes": scan_bytes,
        "optimizer_bytes": optimizer_bytes,
        "forward_equivalents": 2 + 3 * epochs,
        "token_forward_macs": tok,
        "parameters": n,
    }
