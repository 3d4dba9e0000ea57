"""The plain references against the learners and the prioritized replay
at toy widths on the CPU (float32, so the tolerances are tight), each with
a term removed to show that the comparison would catch it; and the
operation counts against a count by hand."""

import functools

import pytest

from benchmarks.harness import flops, manifest

ppo_ref = manifest.load_reference("ppo_ref")
ddpg_ref = manifest.load_reference("ddpg_ref")

F32 = dict(rtol=1e-4, atol=1e-5)


def _cfg(name: str, algo: dict | None = None, **learner):
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    return Config(
        learner_config=Config(
            algo=Config(name=name, precision="f32", **(algo or {})),
            model=Config(actor_hidden=(16, 16), critic_hidden=(16, 16)),
            **learner,
        ),
        env_config=Config(name="jax:lift", num_envs=8),
        session_config=Config(folder="unused"),
    ).extend(base_config())


@pytest.fixture(scope="module")
def ppo():
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner

    cfg = _cfg("ppo", algo={"horizon": 16})
    env = make_env(cfg.env_config)
    learner = build_learner(cfg.learner_config, env.specs)
    reports = ppo_ref.system_reports(learner, env, seed=3, envs=8, horizon=16)
    return learner, reports


def _ppo_compare(ppo, tol, dropped: str | None = None):
    learner, (state, batch, metrics, new_state, obs, action, info) = ppo
    algo = learner.config.algo
    reference = dict(
        ppo_ref.learn_report(
            state, batch, float(algo.gamma), float(algo.lam),
            mask_terminations=dropped != "termination_mask",
        ),
        **ppo_ref.act_report(
            new_state, obs, action, with_log_std_term=dropped != "log_std_term"
        ),
    )
    return ppo_ref.compare(ppo_ref.system_report(metrics, info), reference, tol)


TIGHT = {k: F32 for k in ppo_ref.TOL}


def test_ppo_reference_agrees_with_act_and_learn(ppo):
    result = _ppo_compare(ppo, TIGHT)
    assert result["ok"], result
    assert bool(ppo[1][1]["terminated"].any())  # the masks had work to do


@pytest.mark.parametrize("dropped,caught_by", [
    ("log_std_term", "act/logp"),
    ("termination_mask", "learn/explained_variance"),
])
def test_ppo_reference_fails_without_a_term(ppo, dropped, caught_by):
    result = _ppo_compare(ppo, TIGHT, dropped)
    assert not result["ok"]
    assert not result["comparisons"][caught_by]["ok"], result


@pytest.mark.parametrize("dropped", ["log_std_term", "termination_mask"])
def test_ppo_chip_tolerances_still_catch_a_dropped_term(ppo, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert _ppo_compare(ppo, ppo_ref.TOL)["ok"]
    assert not _ppo_compare(ppo, ppo_ref.TOL, dropped)["ok"]


@functools.lru_cache(maxsize=None)
def _ddpg(kind: str):
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.replay import build_replay
    from surreal_tpu.session.config import Config

    cfg = _cfg("ddpg", replay=Config(kind=kind, batch_size=64))
    env = make_env(cfg.env_config)
    learner = build_learner(cfg.learner_config, env.specs)
    replay = build_replay(
        Config(capacity=ddpg_ref.RING, start_sample_size=1).extend(
            learner.config.replay
        )
    )
    return learner, replay, ddpg_ref.system_reports(learner, replay, 5, 0.4)


@pytest.mark.parametrize("kind", ["prioritized", "uniform"])
def test_ddpg_reference_agrees_with_sample_and_learn(kind, monkeypatch):
    learner, replay, reports = _ddpg(kind)
    monkeypatch.setattr(ddpg_ref, "TOL", {k: F32 for k in ddpg_ref.TOL})
    result = ddpg_ref.compare(learner, replay, *reports, 0.4)
    assert result["ok"], result
    prioritized = hasattr(replay, "update_priorities")
    assert ("sample/indices" in result["comparisons"]) == prioritized


def test_ddpg_losses_fail_without_is_weights():
    import jax.numpy as jnp

    learner, replay, (state, _, _, _, batch, _, metrics) = _ddpg("prioritized")
    with_w = ddpg_ref.losses(state, batch, jnp.tanh)
    without = ddpg_ref.losses(state, batch, jnp.tanh, with_is_weights=False)
    tol = ddpg_ref.TOL["learn/critic_loss"]  # the chip's own, looser bound
    assert ddpg_ref.close(float(metrics["loss/critic"]), with_w["learn/critic_loss"], **tol)[0]
    assert not ddpg_ref.close(float(metrics["loss/critic"]), without["learn/critic_loss"], **tol)[0]


def test_prioritized_draw_fails_without_alpha():
    import jax
    import numpy as np

    learner, replay, (_, _, priorities, k_sample, _, info, _) = _ddpg("prioritized")
    uniforms = jax.random.uniform(k_sample, (replay.batch_size,))
    idx, _ = ddpg_ref.prioritized_draw(
        priorities, uniforms, ddpg_ref.ROWS, replay.alpha, 0.4
    )
    flat, _ = ddpg_ref.prioritized_draw(
        priorities, uniforms, ddpg_ref.ROWS, replay.alpha, 0.4, with_alpha=False
    )
    got = np.asarray(info["idx"])
    assert (got != idx).mean() <= ddpg_ref.MAX_INDEX_MISMATCH
    assert (got != flat).mean() > 0.5


def _toy_prioritized(capacity: int):
    from surreal_tpu.replay import build_replay
    from surreal_tpu.session.config import Config

    learner, _, _ = _ddpg("prioritized")
    return build_replay(
        Config(capacity=capacity, start_sample_size=1).extend(learner.config.replay)
    )


def test_full_ring_draw_agrees_at_the_replays_own_capacity():
    import jax

    result = ddpg_ref.full_ring_draw(_toy_prioritized(50_000), jax.random.key(2), 0.4)
    assert all(r["ok"] for r in result.values()), result
    mass = result["sample/full_ring_mass"]
    assert mass["capacity"] == 50_000 and mass["max_mass_err"] <= 1e-6, mass


@pytest.mark.parametrize("broken,caught_by", [
    ("stratification", "mass"), ("priorities_within_a_block", "size_bias"),
])
def test_full_ring_checks_fail_on_a_wrong_draw(broken, caught_by):
    """A draw that is not stratified lands far from where the float64
    cumulative sum points; one that finds the right stretch of the ring
    and ignores the priorities inside it does not prefer heavy slots."""
    import jax
    import numpy as np

    n, bs, alpha = 50_000, 256, 0.6
    k_prio, k_u, k_off = jax.random.split(jax.random.key(4), 3)
    prio = np.abs(np.asarray(jax.random.normal(k_prio, (n,)))) + 1e-6
    uniforms = np.asarray(jax.random.uniform(k_u, (bs,)))
    good, _ = ddpg_ref.prioritized_draw(prio, uniforms, n, alpha, 0.4)
    assert ddpg_ref.draw_mass_error(prio, alpha, good, uniforms)[0] == 0.0
    assert abs(ddpg_ref.size_bias(prio, alpha, good) - 1.0) <= ddpg_ref.SIZE_BIAS_BAND
    if broken == "stratification":  # the k-th draw is not in the k-th slice
        cdf = np.cumsum(prio.astype(np.float64) ** alpha)
        bad = np.minimum(np.searchsorted(cdf, uniforms * cdf[-1]), n - 1)
    else:
        offsets = np.asarray(jax.random.randint(k_off, (bs,), 0, 16))
        bad = good // 16 * 16 + offsets
    if caught_by == "mass":
        mass = ddpg_ref.draw_mass_error(prio, alpha, bad, uniforms)[0]
        assert mass > 100 * ddpg_ref.DRAW_MASS_TOL
    else:
        assert abs(ddpg_ref.size_bias(prio, alpha, bad) - 1.0) > ddpg_ref.SIZE_BIAS_BAND


# -- operation counts ---------------------------------------------------------

def test_mlp_macs_by_hand():
    # 64-64 nets on 17 observations: actor to 4 actions, critic to 1 value
    assert flops.mlp_macs(17, (64, 64), 4) == 17 * 64 + 64 * 64 + 64 * 4 == 5440
    assert flops.mlp_macs(17, (64, 64), 1) == 17 * 64 + 64 * 64 + 64 == 5248
    # 400-300 nets: the actor plain, the critic with the action at layer 2
    assert flops.mlp_macs(17, (400, 300), 4) == 6800 + 120000 + 1200 == 128000
    assert ddpg_ref.ddpg_critic_macs(17, 4, (400, 300)) == 6800 + 404 * 300 + 300 == 128300


def test_ppo_iteration_by_hand():
    cost = ppo_ref.iteration_cost(
        manifest.load_config("ppo_lift"),
        {"num_envs": 4096, "horizon": 256, "epochs": 4},
    )
    n = 4096 * 256
    # rollout: both nets forward; GAE: the critic twice; SGD: 4 epochs of
    # forward + backward (3x) of both nets
    macs = n * (10688 + 2 * 5248 + 4 * 3 * 10688)
    assert cost["flops"] == 2 * macs
    assert cost["flops"] == pytest.approx(313.4e9, rel=1e-3)  # "about 300 GFLOP"
    assert cost["flops_rollout"] + cost["flops_learn"] == cost["flops"]


@pytest.mark.parametrize("kind,extra", [("uniform", 0), ("prioritized", 1)])
def test_ddpg_iteration_by_hand(kind, extra):
    config = manifest.load_config("ddpg_lift")
    traffic = {
        "num_envs": 2048, "horizon": 16, "updates_per_iter": 64,
        "batch_size": 256, "replay_kind": kind, "replay_capacity": 1_000_000,
    }
    cost = ddpg_ref.iteration_cost(config, traffic)
    a, c = 128000, 128300
    per_sample = (a + c) + 3 * c + (3 * a + 2 * c)
    assert ddpg_ref.ddpg_update_macs(config["widths"]) == per_sample == 1281800
    assert cost["flops_learn"] == 2 * 64 * 256 * per_sample
    assert cost["flops_rollout"] == 2 * 2048 * 16 * a
    row = 4 * (17 + 17 + 4 + 2)
    assert cost["replay_bytes_per_update"] == 256 * row + extra * (4_000_000 + 1024)
    # a cell that names no capacity has the configuration's ring
    traffic.pop("replay_capacity")
    own = ddpg_ref.iteration_cost(config, traffic)["replay_bytes_per_update"]
    assert own == 256 * row + extra * (4 * config["replay_capacity"] + 1024)
