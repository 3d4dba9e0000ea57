"""Plain reference for the fused DDPG configuration (``ddpg_lift``).

Float32 ``jax.numpy`` / NumPy under ``default_matmul_precision("highest")``,
from the published descriptions: the critic and actor losses of Lillicrap
et al. 2015 (action taken in at the critic's second layer), layer norm on
the critic as SURREAL's DDPG has it, and proportional prioritized sampling
with importance weights (Schaul et al. 2016) by a plain cumulative sum. It
reads the learner's parameter tree and the replay's priority vector, and
nothing else of the program.

``check`` runs on the chip, outside the window, at the published widths on
a ring of ``RING`` slots, through public entry points only
(``learner.learn``, ``learner.update_obs_stats``, the replay classes); a
prioritized cell's draw is checked once more over a full ring of the cell's
own capacity. ``iteration_cost`` counts the work the equations require.
"""

from __future__ import annotations

from benchmarks.harness.checks import close
from benchmarks.harness.flops import mlp_macs

RING = 65536      # the check's ring; partly filled, so empty slots are there
ROWS = 40000
CLIP = 5.0
FILTER_EPS = 1e-8
LN_EPS = 1e-6     # flax's LayerNorm default

# Tolerances: about ten times the largest error seen on the chip at the
# published widths ('mixed': bfloat16 products, float32 sums; 25 runs, PR
# 24), written as absolute bounds at the size each value has there, so
# that a lower precision or a dropped small term fails:
#   critic loss  about 1 (unit-variance rewards); largest error 1.5e-5
#   actor loss   2.6e-4 to 2.9e-3 (the nets' last layers start near 0);
#                largest error 6.7e-6 on the chip, 1.2e-5 on the CPU
#   IS weights   in (0, 1]; largest error 3.7e-6
# The sampled indices come from a float32 cumulative sum on the device
# against a float64 one here. On the check's ring at most 2 of 256 draws
# fell on the other side of a slot's edge (10 runs); without the alpha
# exponent or the stratification most do.
TOL = {
    "learn/critic_loss": dict(rtol=2e-4, atol=0.0),
    "learn/actor_loss": dict(rtol=0.0, atol=1e-4),
    "sample/is_weights": dict(rtol=0.0, atol=4e-5),
}
MAX_INDEX_MISMATCH = 0.02
# At the cell's own capacity float32 cannot tell neighbouring slots apart
# (over 20 971 520 slots the chip's draws fell up to 5 slots from the
# float64 ones, 1.6e-7 of the mass: PERF.md section 6), so there the draw
# is held to the mass, not to the slot: the drawn slot's own stretch of
# the float64 cumulative mass has to lie within this share of the total
# of where the draw points. About ten times what was seen.
DRAW_MASS_TOL = 2e-6
# The mass says where on the ring a draw fell, not whether the heavier of
# neighbouring slots was preferred. Under proportional sampling the mean
# p^alpha of the drawn slots is sum p^2alpha / sum p^alpha (1.24 times the
# plain mean for the seeded |N(0,1)| priorities); 256 draws scatter 2.4%
# about it (one standard deviation), so the band is five of those.
SIZE_BIAS_BAND = 0.12


def dense(p, x):
    import jax.numpy as jnp

    return jnp.dot(x, p["kernel"]) + p["bias"]


def layer_norm(p, x):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def normalise(stats, obs):
    import jax.numpy as jnp

    std = jnp.sqrt(stats.m2 / max(int(stats.count), 1) + FILTER_EPS)
    return jnp.clip((obs - stats.mean) / std, -CLIP, CLIP)


def actor(params, obs_n, act):
    """obs -> hidden layers -> tanh-squashed action."""
    import jax.numpy as jnp

    p = params["params"]
    h = obs_n
    for i in range(len(p["MLP_0"])):
        h = act(dense(p["MLP_0"][f"Dense_{i}"], h))
    return jnp.tanh(dense(p["Dense_0"], h))


def critic(params, obs_n, action, act):
    """Q(s, a): the first layer sees the observation only, the action joins
    before the second; layer norm after every hidden dense layer."""
    import jax.numpy as jnp

    p = params["params"]
    n_hidden = sum(1 for k in p if k.startswith("Dense_")) - 1
    h = obs_n
    for i in range(n_hidden):
        if i == 1:
            h = jnp.concatenate([h, action], axis=-1)
        h = dense(p[f"Dense_{i}"], h)
        if f"LayerNorm_{i}" in p:
            h = layer_norm(p[f"LayerNorm_{i}"], h)
        h = act(h)
    return dense(p[f"Dense_{n_hidden}"], h)[..., 0]


def losses(state, batch, act, with_is_weights: bool = True):
    """Critic and actor loss of one update on ``batch`` (before it)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        obs = normalise(state.obs_stats, batch["obs"])
        nxt = normalise(state.obs_stats, batch["next_obs"])
        w = batch["is_weights"] if with_is_weights else jnp.ones_like(batch["reward"])
        target = batch["reward"] + batch["discount"] * critic(
            state.target_critic_params, nxt,
            actor(state.target_actor_params, nxt, act), act,
        )
        td = critic(state.critic_params, obs, batch["action"], act) - target
        q_pi = critic(
            state.critic_params, obs, actor(state.actor_params, obs, act), act
        )
        return {
            "learn/critic_loss": float((w * td**2).mean()),
            "learn/actor_loss": float(-(w * q_pi).mean()),
        }


def prioritized_draw(priorities, uniforms, size, alpha, beta,
                     with_alpha: bool = True):
    """Stratified proportional sampling: draw ``k`` falls in the ``k``-th
    equal slice of the mass of ``p_i^alpha``; weight
    ``(N P(i))^-beta / max``. ``uniforms`` are the draws' positions inside
    their slices."""
    import numpy as np

    p = np.asarray(priorities, np.float64) ** (alpha if with_alpha else 1.0)
    cdf = np.cumsum(p)
    bs = len(uniforms)
    u = (np.arange(bs) + np.asarray(uniforms, np.float64)) / bs * cdf[-1]
    idx = np.minimum(np.searchsorted(cdf, u), len(p) - 1)
    w = (max(int(size), 1) * np.maximum(p[idx] / cdf[-1], 1e-12)) ** (-beta)
    return idx, w / w.max()


def seeded_rows(key, n: int, obs_dim: int, act_dim: int) -> dict:
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    return {
        "obs": jax.random.normal(ks[0], (n, obs_dim)),
        "next_obs": jax.random.normal(ks[1], (n, obs_dim)),
        "action": jax.random.uniform(ks[2], (n, act_dim), minval=-1.0, maxval=1.0),
        "reward": jax.random.normal(ks[3], (n,)),
        "discount": 0.99 * jax.random.bernoulli(ks[4], 0.95, (n,)).astype(jnp.float32),
    }


def system_reports(learner, replay, seed: int, beta: float):
    """A partly filled ring of seeded rows with seeded priorities, one
    ``sample`` and one ``learn`` on it, all through public entry points.
    Returns (state, rows, priorities, sample key, batch, info, metrics)."""
    import jax
    import jax.numpy as jnp

    obs_dim = int(learner.specs.obs.shape[0])
    k_init, k_rows, k_prio, k_sample, k_learn = jax.random.split(
        jax.random.key(seed), 5
    )
    rows = seeded_rows(k_rows, ROWS, obs_dim, learner.act_dim)
    state = learner.update_obs_stats(learner.init(k_init), rows["obs"])
    rstate = replay.insert(replay.init(jax.tree.map(lambda x: x[0], rows)), rows)
    prioritized = hasattr(replay, "update_priorities")
    if prioritized:
        rstate = replay.update_priorities(
            rstate, jnp.arange(ROWS, dtype=jnp.int32),
            jnp.abs(jax.random.normal(k_prio, (ROWS,))),
        )
        rstate, batch, info = replay.sample(rstate, k_sample, beta=beta)
        batch = dict(batch, is_weights=info["is_weights"])
        priorities = rstate.priorities
    else:
        rstate, batch, info = replay.sample(rstate, k_sample)
        priorities = None
    _, metrics = jax.jit(learner.learn)(state, batch, k_learn)
    return state, rows, priorities, k_sample, batch, info, metrics


def compare(learner, replay, state, rows, priorities, k_sample, batch, info,
            metrics, beta: float) -> dict:
    import jax
    import numpy as np

    import jax.numpy as jnp

    act = {"tanh": jnp.tanh, "relu": lambda x: jnp.maximum(x, 0.0)}[
        learner.config.model.activation
    ]
    out = {}
    idx = np.asarray(info["idx"])
    if priorities is not None:
        uniforms = jax.random.uniform(k_sample, (replay.batch_size,))
        ref_idx, ref_w = prioritized_draw(
            priorities, uniforms, ROWS, replay.alpha, beta
        )
        same = idx == ref_idx
        out["sample/indices"] = {
            "ok": bool(1.0 - same.mean() <= MAX_INDEX_MISMATCH),
            "mismatch_share": float(1.0 - same.mean()),
        }
        ok, err = close(
            np.asarray(info["is_weights"])[same], ref_w[same],
            **TOL["sample/is_weights"],
        )
        out["sample/is_weights"] = {"ok": ok and bool(same.any()), "max_abs_err": err}
    # the sampled rows are the stored rows (insertion started at slot 0)
    out["sample/rows"] = {"ok": all(
        np.array_equal(np.asarray(batch[k]), np.asarray(rows[k])[idx])
        for k in rows
    )}
    if "is_weights" not in batch:
        batch = dict(batch, is_weights=np.ones_like(np.asarray(batch["reward"])))
    want = losses(state, batch, act)
    for name, key in (("learn/critic_loss", "loss/critic"),
                      ("learn/actor_loss", "loss/actor")):
        ok, err = close(float(metrics[key]), want[name], **TOL[name])
        out[name] = {"ok": ok, "max_abs_err": err}
    return {"ok": all(r["ok"] for r in out.values()), "comparisons": out}


def draw_mass_error(priorities, alpha: float, idx, uniforms):
    """How far each drawn slot lies from where a float64 cumulative sum
    puts its draw, as a share of the total mass: 0 where the draw points
    into the slot's own interval. ``(largest error, slots off at most)``."""
    import numpy as np

    p = np.asarray(priorities, np.float64) ** alpha
    cdf = np.cumsum(p)
    bs = len(uniforms)
    u = (np.arange(bs) + np.asarray(uniforms, np.float64)) / bs * cdf[-1]
    idx = np.asarray(idx)
    err = np.maximum(np.maximum(cdf[idx] - p[idx] - u, u - cdf[idx]), 0.0)
    want = np.minimum(np.searchsorted(cdf, u), len(p) - 1)
    return float(err.max() / cdf[-1]), int(np.abs(idx - want).max())


def size_bias(priorities, alpha: float, idx) -> float:
    """Mean ``p^alpha`` of the drawn slots over what proportional sampling
    expects of it: 1 where heavier slots are preferred as they should be."""
    import numpy as np

    p = np.asarray(priorities, np.float64) ** alpha
    return float(p[np.asarray(idx)].mean() / ((p * p).sum() / p.sum()))


def full_ring_draw(replay, key, beta: float) -> dict:
    """One prioritized ``sample`` over a full ring of the replay's own
    capacity (rows of one float, seeded priorities), against the float64
    cumulative sum: where each draw landed, and the IS weights of the
    slots it drew."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    k_prio, k_sample = jax.random.split(key)
    state = replay.init({"x": jnp.zeros((), jnp.float32)})
    prio = jnp.abs(jax.random.normal(k_prio, (replay.capacity,))) + replay.eps
    state = state._replace(
        ring=state.ring._replace(size=jnp.asarray(replay.capacity, jnp.int32)),
        priorities=prio, max_priority=prio.max(),
    )
    info = jax.jit(lambda s, k: replay.sample(s, k, beta=beta)[2])(state, k_sample)
    idx = np.asarray(info["idx"])
    uniforms = jax.random.uniform(k_sample, (replay.batch_size,))
    mass_err, slots_off = draw_mass_error(prio, replay.alpha, idx, uniforms)
    p = np.asarray(prio, np.float64) ** replay.alpha
    w = (replay.capacity * np.maximum(p[idx] / p.sum(), 1e-12)) ** (-beta)
    ok, err = close(info["is_weights"], w / w.max(), **TOL["sample/is_weights"])
    bias = size_bias(prio, replay.alpha, idx)
    return {
        "sample/full_ring_mass": {
            "ok": mass_err <= DRAW_MASS_TOL, "capacity": replay.capacity,
            "max_mass_err": mass_err, "max_slots_off": slots_off,
        },
        "sample/full_ring_size_bias": {
            "ok": abs(bias - 1.0) <= SIZE_BIAS_BAND, "ratio": bias,
        },
        "sample/full_ring_is_weights": {"ok": ok, "max_abs_err": err},
    }


def build(cfg):
    """The cell's learner, its replay discipline on the check's ring, and
    the same discipline at the cell's own capacity."""
    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner
    from surreal_tpu.replay import build_replay
    from surreal_tpu.session.config import Config

    env = make_env(training_env_config(cfg.env_config))
    learner = build_learner(cfg.learner_config, env.specs)
    small = Config(capacity=RING, start_sample_size=1).extend(learner.config.replay)
    return learner, build_replay(small), build_replay(learner.config.replay)


def check(cfg, run) -> dict:
    """The on-chip reference check of one run (seeded from ``--seed``)."""
    import jax

    learner, replay, cell_replay = build(cfg)
    beta = float(learner.config.replay.priority_beta0)
    reports = system_reports(learner, replay, run.seed, beta)
    result = compare(learner, replay, *reports, beta)
    if hasattr(cell_replay, "update_priorities"):
        result["comparisons"].update(
            full_ring_draw(cell_replay, jax.random.key(run.seed + 1), beta)
        )
        result["ok"] = all(r["ok"] for r in result["comparisons"].values())
    return result


def ddpg_critic_macs(obs_dim: int, act_dim: int, hidden) -> int:
    """The critic sees the observation first and takes the action in at
    its second layer (models/ddpg_net.py, as in Lillicrap et al.)."""
    h = [int(x) for x in hidden]
    dims = [h[0] + int(act_dim), *h[1:], 1]
    return int(obs_dim) * h[0] + sum(a * b for a, b in zip(dims, dims[1:]))


def ddpg_update_macs(widths: dict) -> int:
    """One sample through one DDPG update: target actor and critic forward;
    critic forward+backward; actor forward+backward through a critic
    forward and its input gradient."""
    actor = mlp_macs(widths["obs_dim"], widths["actor_hidden"], widths["action_dim"])
    critic = ddpg_critic_macs(
        widths["obs_dim"], widths["action_dim"], widths["critic_hidden"]
    )
    return (actor + critic) + 3 * critic + (3 * actor + 2 * critic)


def iteration_cost(config: dict, traffic: dict) -> dict:
    """Required operations and bytes of one fused DDPG iteration
    (harness/flops.py has the rules): ``horizon`` actor forwards per env,
    then ``updates_per_iter`` updates on ``batch_size`` replayed rows. Required
    replay bytes per update: the sampled rows (obs, next_obs, action,
    reward, discount) and, where sampling is prioritized, one read of the
    priority vector and the scatter of the new priorities."""
    widths = config["widths"]
    actor = mlp_macs(widths["obs_dim"], widths["actor_hidden"], widths["action_dim"])
    samples = int(traffic["num_envs"]) * int(traffic["horizon"])
    updates, batch = int(traffic["updates_per_iter"]), int(traffic["batch_size"])
    learn = updates * batch * ddpg_update_macs(widths)
    row = 4 * (2 * widths["obs_dim"] + widths["action_dim"] + 2)
    per_update = batch * row
    if traffic["replay_kind"] == "prioritized":
        capacity = traffic.get("replay_capacity", config["replay_capacity"])
        per_update += 4 * int(capacity) + 4 * batch
    return {
        "samples": samples,
        "flops": 2 * (samples * actor + learn),
        "flops_rollout": 2 * samples * actor,
        "flops_learn": 2 * learn,
        "bytes": samples * row + updates * per_update,
        "replay_bytes_per_update": per_update,
    }
