"""Plain reference for the IMPALA configuration (``impala_pong``).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")`` and a float64 NumPy loop for the
recurrence, written from the published descriptions: the network of Mnih
et al. 2015 (Methods, "Model architecture": 84 x 84 x 4 input scaled to
[0, 1], conv 32 of 8x8 stride 4, conv 64 of 4x4 stride 2, conv 64 of 3x3
stride 1, dense 512, one output per action, ReLU between) with a value
head beside the logits, and the V-trace actor-critic of Espeholt et al.
2018 (section 4.1, eq. 1 and Remark 1's recursion; section 4.2's three
losses). It reads the learner's parameter tree and nothing else of the
program: no flax module, no ``ops/`` function.

The convolutions are written out, as the strided patch at every kernel
offset and one ``einsum`` over them: ``out[b, i, j, o] = sum_{di, dj, c}
x[b, i s + di, j s + dj, c] w[di, dj, c, o]``. Not ``lax.conv_general_dilated``: that is
the primitive the program's own ``nn.Conv`` lowers to, so a fault in how
it pads, strides or lays out channels would be on both sides. The flatten
order is the parameters' own: ``Dense_0``'s 3136 rows are ``(row, column,
channel)`` of the last ``[7, 7, 64]`` feature map, row-major.

Where the repo departs from the paper (the configuration's ``assumed``
lists the same): the losses are means over the batch where the paper
sums; the trace is cut at every ``done`` and the bootstrap is
``V(next_obs)`` through a truncation and 0 through a termination (the
paper has one discount); at a ``done`` step the policy-gradient target
bootstraps from ``V(next_obs)`` and not from the next row's ``vs``.

``check`` runs on the chip, outside the window, at the published widths
on ``ENVS`` envs, through public entry points only (``device_rollout``,
``learner.act``, ``learner.learn``), on an OFF-POLICY batch: collected
under the initial parameters and learnt from the same parameters with the
policy head scaled by ``HEAD_SCALE`` and the value head by
``VALUE_SCALE``, so that rho is far from 1, the clips bind on part of the
batch and a value is the size of a reward.
"""

from __future__ import annotations

from benchmarks.harness.checks import close
from benchmarks.harness.flops import mlp_macs

ENVS = 64
# The checked ``learn`` starts from the collecting parameters with the
# policy head (kernel and bias of ``Dense_0``) times this. The head is
# initialised at scale 0.01, so the collecting policy and any policy a few
# updates later lie within 0.02 nats of uniform and rho within 1 +- 0.01:
# a dropped clip then moves ``loss/pg`` by 0.7%, under bfloat16's own 1.2%
# (PERF.md section 6, PR 28). Times 1000 the logits spread over about a
# nat and rho over 0 to 3.
HEAD_SCALE = 1000.0
# ... and with the value head (``Dense_1``) times this: a fresh value is
# about 0.03, a thirtieth of a reward, and bootstrapping through the steps
# that terminate then moves ``loss/value`` by 0.15%, under any honest
# bound. Times 30 a typical value is the size of a reward.
VALUE_SCALE = 30.0
# seeded shares of steps turned into episode ends, so that both masks act
# on every batch (a Pong game to 21 seldom ends inside 32 steps). What the
# termination mask moves is in proportion to the steps that terminate: at
# 3% it moved ``loss/value`` by 3%, at this share by 14% (PERF.md section 6)
FLIP_TERMINATED = 0.15
FLIP_TRUNCATED = 0.05
# the check is not ok unless the share of steps with rho > 1 lies here:
# on-policy, V-trace's correction is a no-op and a broken clip would pass.
# A step counts once rho passes 1 by RHO_MARGIN: on-policy, rounding alone
# leaves rho a few ulps over 1 on a third of the steps.
RHO_SHARE = (0.05, 0.95)
RHO_MARGIN = 0.01

# Tolerances: about ten times the largest error seen on the chip at the
# published widths over 12 batches of 64 envs x 32 steps (PR 28, seeds
# 2147485001-12; the configuration computes in bfloat16, 'mixed', and
# rounds logits and value to bfloat16), written beside what was seen.
# ``loss/value`` and the gradient norm grow with the batch's values, so
# theirs are relative; ``loss/pg`` is a signed mean that passes through
# zero, so its is absolute, like the rest:
#   logits              |max| 1.0-2.5; largest error 1.05e-2
#   log-prob            up to 3.1 nats; largest error 1.02e-2
#   value               |max| 4.3-13.8; largest error 4.6e-2
#   loss/pg             0.017-1.06 in size; largest error 1.97e-3
#   loss/value          0.20-1.02; largest error 0.36% of its value
#   policy/entropy      0.96-1.08; largest error 3.7e-4
#   policy/rho_mean     0.985-1.011; largest error 1.2e-4
#   health/grad_norm    1.5-23; largest error 1.44% of its value
# What a dropped term moves at that size (CPU, bfloat16, 64 x 32, seed 21):
# the rho clip +91% of loss/value, the c product +50%, the cut at done
# +61%, the termination mask +13.7% (and -20% of the gradient norm), the
# normaliser 2.5 nats of log-prob, the / 255 hundreds of nats of logits.
TOL = {
    "act/logits": dict(rtol=0.0, atol=1e-1),
    "act/logp": dict(rtol=0.0, atol=1e-1),
    "act/value": dict(rtol=0.0, atol=4.5e-1),
    "learn/loss_pg": dict(rtol=0.0, atol=2e-2),
    "learn/loss_value": dict(rtol=3.5e-2, atol=0.0),
    "learn/entropy": dict(rtol=0.0, atol=3.7e-3),
    "learn/rho_mean": dict(rtol=0.0, atol=1.2e-3),
    "learn/grad_norm": dict(rtol=1.4e-1, atol=0.0),
}
# every term a comparison has to catch when it is dropped
TERMS = (
    "rho_clip", "c_product", "done_cut", "termination_mask",
    "softmax_normaliser", "scale_255",
)


def conv_macs(in_hw: int, in_ch: int, out_ch: int, kernel: int, stride: int):
    """(multiply-accumulates of one frame through one VALID convolution,
    its output height = width)."""
    out_hw = (int(in_hw) - int(kernel)) // int(stride) + 1
    return out_hw * out_hw * int(out_ch) * int(kernel) ** 2 * int(in_ch), out_hw


def frame_macs(widths: dict) -> dict:
    """One frame's forward pass by layer: multiply-accumulates
    ``{"convs": [...], "dense", "heads", "forward"}``, the element count of
    the activations the backward pass needs (``"activations"``) and the
    number of parameters, kernels and biases (``"parameters"``)."""
    hw, _, ch = widths["input"]
    d, outputs = int(widths["dense"]), int(widths["actions"]) + 1
    convs, activations, parameters = [], 0, 0
    for out_ch, k, s in zip(widths["channels"], widths["kernels"], widths["strides"]):
        macs, hw = conv_macs(hw, ch, out_ch, k, s)
        convs.append(macs)
        parameters += k * k * ch * out_ch + out_ch
        ch = out_ch
        activations += hw * hw * ch
    flat = hw * hw * ch
    return {
        "convs": convs, "dense": flat * d, "heads": d * outputs,
        "forward": sum(convs) + mlp_macs(flat, [d], outputs),
        "activations": activations + d,
        "parameters": parameters + flat * d + d + d * outputs + outputs,
    }


def iteration_cost(config: dict, traffic: dict) -> dict:
    """Required operations and bytes of one fused IMPALA iteration
    (harness/flops.py has the rules). Per env step: one forward to act; in
    ``learn`` a forward over ``obs`` and one backward pass, which costs two
    forwards less the first convolution's input gradient (the pixels need
    none). A step's successor value is the next step's own, so the one
    further forward is over the last step's ``num_envs`` successor frames
    (a truncated row elsewhere is the input's doing, not required work). A
    frame's forward is 9 345 024 MACs; an env step of 32, 34 395 328."""
    widths = config["widths"]
    m = frame_macs(widths)
    envs, horizon = int(traffic["num_envs"]), int(traffic["horizon"])
    samples = envs * horizon
    backward = 2 * m["forward"] - m["convs"][0]
    rollout = samples * m["forward"]
    learn = samples * (m["forward"] + backward) + envs * m["forward"]
    # required HBM traffic per stored step: obs (uint8) written once by the
    # rollout and read once by learn, as are the last step's successor
    # frames; the activations the backward pass needs, written by the
    # forward over obs and read back once in the compute dtype (bfloat16);
    # action, reward, two flags, behaviour log-prob and logits written, read
    h, w, c = widths["input"]
    obs_row = h * w * c
    row = 2 * obs_row + 4 * m["activations"] + 8 * (5 + widths["actions"])
    # parameters (float32): read by every act, by learn's two forwards and
    # its backward; gradient and Adam's moments written and read; one write
    params = 4 * m["parameters"] * (horizon + 3 + 2 + 4 + 1)
    return {
        "samples": samples,
        "flops": 2 * (rollout + learn),
        "flops_rollout": 2 * rollout,
        "flops_learn": 2 * learn,
        "bytes": samples * row + envs * 2 * obs_row + params,
    }


# -- the network ------------------------------------------------------------

def conv(p, x, stride: int):
    """VALID convolution of ``[B, H, W, C]`` by ``kernel [k, k, C, O]``,
    written out: the strided patch at every kernel offset, then one sum
    of products over (offset row, offset column, channel)."""
    import jax.numpy as jnp

    w = p["kernel"]
    k = w.shape[0]
    n = (x.shape[1] - k) // stride + 1
    span = stride * (n - 1) + 1
    patches = jnp.stack([
        jnp.stack([
            x[:, di:di + span:stride, dj:dj + span:stride, :] for dj in range(k)
        ], axis=3) for di in range(k)
    ], axis=3)  # [B, n, n, k, k, C]
    return jnp.einsum("bijdec,deco->bijo", patches, w) + p["bias"]


def relu(x):
    """max(x, 0) with derivative 0 at 0, the usual convention. It matters
    here: a black patch under a zero bias gives exactly 0, which is most of
    the first feature map of a fresh network, and ``maximum``'s derivative
    there is one half."""
    import jax.numpy as jnp

    return jnp.where(x > 0, x, 0.0)


def network(params, obs, strides, scale_255: bool = True):
    """``obs [..., H, W, C]`` uint8 -> (logits ``[..., A]``, value
    ``[...]``), float32."""
    import jax.numpy as jnp

    p = params["params"]
    lead = obs.shape[:-3]
    x = obs.reshape((-1,) + obs.shape[-3:]).astype(jnp.float32)
    if scale_255:
        x = x / 255.0
    stem = p["NatureCNN_0"]
    for i, s in enumerate(strides):
        x = relu(conv(stem[f"Conv_{i}"], x, int(s)))
    x = x.reshape(x.shape[0], -1)  # (row, column, channel), row-major
    h = relu(x @ stem["Dense_0"]["kernel"] + stem["Dense_0"]["bias"])
    logits = h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
    value = (h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"])[:, 0]
    return logits.reshape(lead + logits.shape[-1:]), value.reshape(lead)


def log_probs(logits, normalise: bool = True):
    """Log-softmax over the last axis."""
    import jax.numpy as jnp

    if not normalise:
        return logits
    top = logits.max(-1, keepdims=True)
    return logits - top - jnp.log(jnp.exp(logits - top).sum(-1, keepdims=True))


def taken(logp_all, action):
    import jax.numpy as jnp

    return jnp.take_along_axis(logp_all, action[..., None], axis=-1)[..., 0]


# -- V-trace ----------------------------------------------------------------

def vtrace(rho, reward, value, value_next, done, terminated, gamma,
           clip_rho=1.0, clip_c=1.0, clip_pg_rho=1.0, dropped=None):
    """V-trace targets and policy-gradient advantages over ``[T, B]``,
    backwards in time (Espeholt et al. 2018, Remark 1), with the repo's
    two masks:

        delta_t = min(rho_bar, rho_t) (r_t + gamma (1 - terminated_t) V(x'_t) - V(x_t))
        a_t     = delta_t + gamma (1 - done_t) min(c_bar, rho_t) a_{t+1},  a_T = 0
        vs_t    = V(x_t) + a_t
        adv_t   = min(rho_pg, rho_t) (r_t + gamma (1 - terminated_t) vs'_t - V(x_t))

    where ``x'_t`` is the successor before any reset and ``vs'_t`` is
    ``vs_{t+1}``, or ``V(x'_t)`` at a ``done`` step and at the last row.
    ``dropped`` names one term to leave out (tests)."""
    import numpy as np

    rho, reward, value, value_next = (
        np.asarray(x, np.float64) for x in (rho, reward, value, value_next)
    )
    done = np.asarray(done, np.float64)
    term = np.asarray(terminated, np.float64)
    if dropped == "termination_mask":
        term = 0.0 * term
    edge = 1.0 - done if dropped != "done_cut" else 1.0 + 0.0 * done
    if dropped == "rho_clip":
        clip_rho = clip_pg_rho = np.inf
    cs = np.minimum(clip_c, rho) if dropped != "c_product" else 1.0 + 0.0 * rho
    boot = gamma * (1.0 - term)
    delta = np.minimum(clip_rho, rho) * (reward + boot * value_next - value)
    a = np.zeros_like(delta)
    nxt = np.zeros_like(delta[0])
    for t in range(delta.shape[0] - 1, -1, -1):
        nxt = delta[t] + gamma * edge[t] * cs[t] * nxt
        a[t] = nxt
    vs = value + a
    vs_shift = np.concatenate([vs[1:], value_next[-1:]], axis=0)
    vs_next = np.where(edge == 0.0, value_next, vs_shift)
    adv = np.minimum(clip_pg_rho, rho) * (reward + boot * vs_next - value)
    return vs, adv


# -- what the system reports, recomputed --------------------------------------

def learn_report(params, batch, algo: dict, strides, dropped: str | None = None):
    """The four scalars ``learn`` reports and the global norm of the
    gradient of the total loss, from the parameters ``learn`` starts
    from; and the share of steps with rho > 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scale = dropped != "scale_255"
    normalise = dropped != "softmax_normaliser"

    def forward(p, obs, action):
        logits, value = network(p, obs, strides, scale)
        logp_all = log_probs(logits, normalise)
        entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
        return taken(logp_all, action), value, entropy

    def total(p, obs, action, vs, adv):
        logp, value, entropy = forward(p, obs, action)
        pg = -(adv * logp).mean()
        v = 0.5 * ((value - vs) ** 2).mean()
        return (
            pg + algo["value_coeff"] * v - algo["entropy_coeff"] * entropy,
            (pg, v, entropy),
        )

    with jax.default_matmul_precision("highest"):
        jit_forward = jax.jit(forward)  # one program for both passes
        logp, value, _ = jit_forward(params, batch["obs"], batch["action"])
        _, value_next, _ = jit_forward(params, batch["next_obs"], batch["action"])
        rho = np.exp(
            np.asarray(logp, np.float64)
            - np.asarray(batch["behavior_logp"], np.float64)
        )
        vs, adv = vtrace(
            rho, batch["reward"], value, value_next, batch["done"],
            batch["terminated"], algo["gamma"], algo["clip_rho"],
            algo["clip_c"], algo["clip_pg_rho"], dropped,
        )
        grads, (pg, v, entropy) = jax.jit(jax.grad(total, has_aux=True))(
            params, batch["obs"], batch["action"],
            jnp.asarray(vs, jnp.float32), jnp.asarray(adv, jnp.float32),
        )
    norm = np.sqrt(sum(
        float((np.asarray(g, np.float64) ** 2).sum()) for g in jax.tree.leaves(grads)
    ))
    return {
        "learn/loss_pg": float(pg), "learn/loss_value": float(v),
        "learn/entropy": float(entropy), "learn/rho_mean": float(rho.mean()),
        "learn/grad_norm": norm,
    }, float((rho > 1.0 + RHO_MARGIN).mean())


def act_report(params, obs, action, strides, dropped: str | None = None):
    """Logits, value and the log-prob of ``action`` at fresh observations."""
    import jax

    with jax.default_matmul_precision("highest"):
        logits, value = jax.jit(
            lambda p, o: network(p, o, strides, dropped != "scale_255")
        )(params, obs)
        logp = taken(log_probs(logits, dropped != "softmax_normaliser"), action)
    return {"act/logits": logits, "act/logp": logp, "act/value": value}


def system_report(metrics: dict, info: dict) -> dict:
    """What ``learn`` and ``act`` said, under the comparisons' names."""
    return {
        "learn/loss_pg": float(metrics["loss/pg"]),
        "learn/loss_value": float(metrics["loss/value"]),
        "learn/entropy": float(metrics["policy/entropy"]),
        "learn/rho_mean": float(metrics["policy/rho_mean"]),
        "learn/grad_norm": float(metrics["health/grad_norm"]),
        "act/logits": info["logits"], "act/logp": info["logp"],
        "act/value": info["value"],
    }


def system_reports(learner, env, seed: int, envs: int, horizon: int):
    """Drive the learner through its public entry points on a seeded
    ``[horizon, envs]`` rollout collected under the initial parameters;
    then the checked ``learn`` on it from the parameters with the scaled
    heads, so off-policy, and one ``act`` at fresh observations.
    Returns (state the checked learn starts from, batch, its metrics,
    state after it, fresh obs, action, act info)."""
    import jax

    from surreal_tpu.launch.rollout import device_rollout, init_device_carry

    k_init, k_env, k_roll, k_flip, k_cut, k_learn, k_act = jax.random.split(
        jax.random.key(seed), 7
    )
    state = learner.init(k_init)
    carry = init_device_carry(env, k_env, envs)
    carry, batch = jax.jit(
        lambda s, c, k: device_rollout(env, learner, s, c, k, horizon)
    )(state, carry, k_roll)
    batch = {
        k: batch[k] for k in (
            "obs", "next_obs", "action", "reward", "done", "terminated",
            "behavior_logp", "behavior",
        )
    }
    ended = jax.random.bernoulli(k_flip, FLIP_TERMINATED, batch["done"].shape)
    cut = jax.random.bernoulli(k_cut, FLIP_TRUNCATED, batch["done"].shape)
    batch["done"] = batch["done"] | ended | cut
    batch["terminated"] = batch["terminated"] | ended
    params = dict(state.params["params"])
    params["Dense_0"] = jax.tree.map(lambda x: HEAD_SCALE * x, params["Dense_0"])
    params["Dense_1"] = jax.tree.map(lambda x: VALUE_SCALE * x, params["Dense_1"])
    state = state._replace(params={"params": params})
    new_state, metrics = jax.jit(learner.learn)(state, batch, k_learn)
    obs = carry.obs
    action, info = jax.jit(learner.act)(new_state, obs, k_act)
    return state, batch, metrics, new_state, obs, action, info


def compare(system: dict, reference: dict, rho_share: float,
            tol: dict = TOL) -> dict:
    """``{"ok", "comparisons": {name: {ok, max_abs_err, scale}}}``, scale
    the largest magnitude the reference has there; the batch's share of
    rho > 1 is a row of its own, ok inside ``RHO_SHARE``."""
    import numpy as np

    rows = {}
    for name, want in reference.items():
        ok, err = close(system[name], want, **tol[name])
        rows[name] = {
            "ok": ok, "max_abs_err": err,
            "scale": float(np.abs(np.asarray(want, np.float64)).max()),
        }
    rows["batch/rho_above_one_share"] = {
        "ok": RHO_SHARE[0] <= rho_share <= RHO_SHARE[1], "value": rho_share,
    }
    return {"ok": all(r["ok"] for r in rows.values()), "comparisons": rows}


def reference_reports(learner, reports, dropped: str | None = None):
    """(reference values under the comparisons' names, share of rho > 1)
    for what ``system_reports`` returned."""
    state, batch, _, new_state, obs, action, _ = reports
    algo = {
        k: float(learner.config.algo[k]) for k in (
            "gamma", "clip_rho", "clip_c", "clip_pg_rho", "value_coeff",
            "entropy_coeff",
        )
    }
    strides = tuple(learner.config.model.cnn.strides)
    learnt, share = learn_report(state.params, batch, algo, strides, dropped)
    acted = act_report(new_state.params, obs, action, strides, dropped)
    return dict(learnt, **acted), share


def check(cfg, run) -> dict:
    """The on-chip reference check of one run (seeded from ``--seed``)."""
    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner

    env = make_env(training_env_config(cfg.env_config))
    learner = build_learner(cfg.learner_config, env.specs)
    reports = system_reports(
        learner, env, run.seed, ENVS, int(learner.config.algo.horizon)
    )
    reference, share = reference_reports(learner, reports)
    return compare(system_report(reports[2], reports[6]), reference, share)
