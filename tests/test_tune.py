"""Program-autotuner tests (surreal_tpu/tune/): fingerprint keying, the
persistent tuning cache, trainer build-time resolution, the pure-cache-hit
contract of a second search, unroll/impl equivalence of tuned programs,
and the uniform-replay batched-sampling record equivalence.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs import make_env
from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
from surreal_tpu.launch.trainer import Trainer
from surreal_tpu.learners import build_learner
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.tune import (
    TuningCache,
    resolve_tuning_cache_dir,
    workload_fingerprint,
)
from surreal_tpu.tune.search import tune_workload


def bundle(tmp_path, algo="ppo", env="jax:pendulum", num_envs=8, *,
           session=None, **algo_over):
    over = dict(algo_over)
    cfg = Config(
        learner_config=Config(algo=Config(name=algo, **over)),
        env_config=Config(name=env, num_envs=num_envs),
        session_config=Config(
            folder=str(tmp_path),
            metrics=Config(every_n_iters=10_000, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
            **(session or {}),
        ),
    ).extend(base_config())
    return cfg


def extended_learner(cfg):
    env = make_env(cfg.env_config)
    return build_learner(cfg.learner_config, env.specs).config


# -- fingerprint --------------------------------------------------------------

def test_fingerprint_stable_and_geometry_sensitive(tmp_path):
    cfg = bundle(tmp_path, horizon=8)
    ext = extended_learner(cfg)
    k1, fp1 = workload_fingerprint(ext, cfg.env_config)
    k2, _ = workload_fingerprint(ext, cfg.env_config)
    assert k1 == k2 and len(k1) == 16
    assert fp1["env"]["num_envs"] == 8

    # geometry changes the key ...
    cfg_wide = bundle(tmp_path, horizon=8, num_envs=16)
    k3, _ = workload_fingerprint(ext, cfg_wide.env_config)
    assert k3 != k1
    ext_h = extended_learner(bundle(tmp_path, horizon=16))
    k4, _ = workload_fingerprint(ext_h, cfg.env_config)
    assert k4 != k1


def test_fingerprint_excludes_tuned_knobs(tmp_path):
    """Applying a cached winner must not move the key it was stored
    under, or the second lookup would miss its own result."""
    cfg = bundle(tmp_path, horizon=8)
    k_default, _ = workload_fingerprint(extended_learner(cfg), cfg.env_config)
    cfg_tuned = bundle(
        tmp_path, horizon=8, rollout_unroll=8, gae_impl="assoc",
        sgd_unroll=4, shuffle="row", autotune="cache",
    )
    k_tuned, _ = workload_fingerprint(
        extended_learner(cfg_tuned), cfg_tuned.env_config
    )
    assert k_tuned == k_default


# -- cache --------------------------------------------------------------------

def test_cache_roundtrip_and_corrupt_reads_as_miss(tmp_path):
    cache = TuningCache(str(tmp_path / "tc"))
    assert cache.lookup("abc") is None
    path = cache.store("abc", {"config": {"rollout_unroll": 4}, "chosen_ms": 1.0})
    assert cache.lookup("abc")["config"] == {"rollout_unroll": 4}
    with open(path, "w") as f:
        f.write("{torn json")
    assert cache.lookup("abc") is None  # corrupt entry = miss, not crash


def test_resolve_tuning_cache_dir(tmp_path):
    s = Config(folder=str(tmp_path), tuning_cache_dir=None)
    assert resolve_tuning_cache_dir(s) == str(tmp_path / "tuning_cache")
    s2 = Config(folder=str(tmp_path), tuning_cache_dir="rel")
    assert resolve_tuning_cache_dir(s2) == str(tmp_path / "rel")
    s3 = Config(folder=str(tmp_path), tuning_cache_dir="/abs/tc")
    assert resolve_tuning_cache_dir(s3) == "/abs/tc"


# -- trainer build-time resolution -------------------------------------------

def test_autotune_off_is_a_noop(tmp_path):
    cfg = bundle(tmp_path, horizon=8)
    t = Trainer(cfg)
    assert t.tune_decision.mode == "off"
    assert t.tune_decision.applied == {}
    assert "rollout_unroll" not in cfg.learner_config.algo


def test_autotune_cache_hit_applies_tuned_config(tmp_path):
    cfg = bundle(tmp_path, horizon=8)
    key, fp = workload_fingerprint(extended_learner(cfg), cfg.env_config)
    cache = TuningCache(resolve_tuning_cache_dir(cfg.session_config))
    cache.store(key, {
        "config": {"rollout_unroll": 4, "gae_impl": "assoc"},
        "fingerprint": fp,
    })

    cfg2 = bundle(tmp_path, horizon=8, autotune="cache")
    t = Trainer(cfg2)
    assert t.tune_decision.hit is True
    assert t.tune_decision.source == "cache"
    assert t.learner.config.algo.rollout_unroll == 4
    assert t.learner.config.algo.gae_impl == "assoc"
    assert t._rollout_unroll == 4


def test_autotune_cache_miss_keeps_defaults(tmp_path):
    cfg = bundle(tmp_path, horizon=8, autotune="cache")
    t = Trainer(cfg)
    assert t.tune_decision.hit is False
    assert t.tune_decision.applied == {}
    assert t.learner.config.algo.gae_impl == "xla"
    assert t._rollout_unroll == 1


def test_autotune_rejects_unknown_mode(tmp_path):
    with pytest.raises(ValueError, match="autotune"):
        Trainer(bundle(tmp_path, horizon=8, autotune="always"))


# -- search -------------------------------------------------------------------

def test_search_persists_winner_and_second_run_is_pure_hit(tmp_path):
    cfg = bundle(tmp_path, horizon=8, epochs=1)
    first = tune_workload(
        cfg, dims=[("rollout_unroll", [1, 2])], warmup=1,
        iters=1,
    )
    assert first["cache_hit"] is False
    assert first["measured"] == 2  # default + one candidate
    assert set(first["config"]) == {"rollout_unroll"}
    cache = TuningCache(resolve_tuning_cache_dir(cfg.session_config))
    assert cache.lookup(first["key"]) is not None

    # the pure-hit contract: zero measurements the second time
    second = tune_workload(
        cfg, dims=[("rollout_unroll", [1, 2])], warmup=1,
        iters=1,
    )
    assert second["cache_hit"] is True
    assert second["measured"] == 0
    assert second["config"] == first["config"]

    # and a trainer in cache mode builds with it, search cost zero
    cfg3 = bundle(tmp_path, horizon=8, epochs=1, autotune="cache")
    t = Trainer(cfg3)
    assert t.tune_decision.hit is True
    assert t.learner.config.algo.rollout_unroll == first["config"]["rollout_unroll"]


def test_trainer_search_mode_measures_applies_and_persists(tmp_path, monkeypatch):
    """algo.autotune='search': a cache miss at build time runs the search,
    applies the winner to THIS trainer, and persists it — the next build
    (even in search mode) is a pure cache hit."""
    import surreal_tpu.tune.search as search_mod

    monkeypatch.setattr(
        search_mod, "candidate_space",
        lambda ext: [("rollout_unroll", [1, 2])],
    )
    t = Trainer(bundle(tmp_path, horizon=8, epochs=1, autotune="search"))
    assert t.tune_decision.source == "search"
    assert t.tune_decision.hit is False
    assert "rollout_unroll" in t.tune_decision.applied
    assert t._rollout_unroll == t.tune_decision.applied["rollout_unroll"]

    t2 = Trainer(bundle(tmp_path, horizon=8, epochs=1, autotune="search"))
    assert t2.tune_decision.hit is True
    assert t2.tune_decision.applied == t.tune_decision.applied


def test_search_host_env_uses_learn_surface(tmp_path):
    """Host envs (gym/dm_control — the SEED fingerprints) have no fused
    device iteration; the search surface is the jitted learn program
    alone, and the entry records it — this is what makes the SEED
    trainer's cache consult satisfiable (`surreal_tpu tune ppo
    dm_control:...` populates exactly that fingerprint)."""
    cfg = bundle(tmp_path, env="gym:CartPole-v1", horizon=8, epochs=1)
    out = tune_workload(
        cfg, dims=[("sgd_unroll", [1, 2])], warmup=1, iters=1
    )
    assert out["cache_hit"] is False
    assert out["measure"]["surface"] == "learn"
    assert set(out["config"]) == {"sgd_unroll"}

    # and a SEED-shaped trainer in cache mode picks the entry up
    from surreal_tpu.launch.seed_trainer import SEEDTrainer

    cfg2 = bundle(tmp_path, env="gym:CartPole-v1", horizon=8, epochs=1,
                  autotune="cache",
                  session={"topology": Config(num_env_workers=1)})
    t = SEEDTrainer(cfg2)
    assert t.tune_decision.hit is True
    assert t.learner.config.algo.sgd_unroll == out["config"]["sgd_unroll"]


def test_trainer_search_on_host_env_searches_learn_phase(tmp_path, monkeypatch):
    import surreal_tpu.tune.search as search_mod

    monkeypatch.setattr(
        search_mod, "candidate_space",
        lambda ext: [("sgd_unroll", [1, 2])],
    )
    t = Trainer(bundle(tmp_path, env="gym:CartPole-v1", horizon=8,
                       epochs=1, autotune="search"))
    assert t.tune_decision.source == "search"
    assert "sgd_unroll" in t.tune_decision.applied


def test_search_degrades_when_nothing_searchable(tmp_path):
    """Host-env DDPG has no searchable dimension (its update loop runs as
    individual jitted learns from a host loop): tune_workload refuses
    loudly, and a trainer in search mode keeps defaults with the reason
    recorded instead of crashing."""
    cfg = bundle(tmp_path, algo="ddpg", env="gym:Pendulum-v1", horizon=8,
                 exploration=Config(warmup_steps=0))
    with pytest.raises(ValueError, match="no searchable"):
        tune_workload(cfg)

    t = OffPolicyTrainer(
        bundle(tmp_path, algo="ddpg", env="gym:Pendulum-v1", horizon=8,
               autotune="search", exploration=Config(warmup_steps=0))
    )
    assert t.tune_decision.source == "default"
    assert "no searchable" in t.tune_decision.note


# -- CLI ----------------------------------------------------------------------

def test_tune_cli_writes_cache_artifact_and_telemetry(tmp_path, capsys):
    from surreal_tpu.main.launch import main

    folder = str(tmp_path / "sess")
    out = str(tmp_path / "tune.json")
    argv = [
        "tune", "ppo", "jax:pendulum", "--folder", folder,
        "--num-envs", "8",
        "--set", "learner_config.algo.horizon=8",
        "learner_config.algo.epochs=1",
        "--iters", "1", "--warmup", "1",
        "--dims", "rollout_unroll=1,2",
        "--out", out,
    ]
    assert main(argv) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["cache_hit"] is False and row["measured"] == 2
    assert row["default_ms"] > 0

    with open(out) as f:
        artifact = json.load(f)
    assert artifact["platform"] == "cpu"  # honesty field (bench discipline)
    assert len(artifact["workloads"]) == 1
    assert artifact["workloads"][0]["key"] == row["key"]

    # second run: pure cache hit, telemetry records it, diag renders it
    assert main(argv) == 0
    row2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row2["cache_hit"] is True and row2["measured"] == 0

    from surreal_tpu.session.telemetry import diag_report, diag_summary

    s = diag_summary(folder)
    assert s["tune"]["hit"] is True
    assert s["tune_hits"] == 1 and s["tune_misses"] == 1
    assert "Autotuner" in diag_report(folder)


# -- tuned-program equivalence ------------------------------------------------
#
# Tolerance contract (documented here, referenced by README's Autotuner
# section): rtol 5e-3 / atol 1e-3 against the unroll=1 fused iteration,
# for BOTH unroll and impl variants — the same platform-reduction-order
# budget the dispatch-pipeline PR's shuffle-tolerance test documents.
# Unroll changes are semantically identical programs, but XLA fuses the
# unrolled bodies differently (reordered f32 reductions), and one learn
# already CHAINS epochs x minibatches sequential SGD updates through
# adam, so ulp-level reorder noise amplifies to ~0.1-0.5% on grad-norm
# scalars within a single fused iteration (measured on this image).
# Impl variants (gae_impl='assoc' reassociates the recurrence into
# log-depth combines, 'pallas' runs the fused kernel) reorder the
# advantage accumulation itself and sit in the same budget.
UNROLL_RTOL, UNROLL_ATOL = 5e-3, 1e-3
IMPL_RTOL, IMPL_ATOL = 5e-3, 1e-3
# Params are compared ABSOLUTELY, bounded by Adam step sizes: Adam's
# per-step update is ~lr for every coordinate regardless of gradient
# magnitude, so an ulp-level reorder of a near-zero gradient coordinate
# can flip that coordinate's update DIRECTION — relative tolerance is
# meaningless there, and the honest bound after k chained updates is
# |delta| <= ~2*lr*k (ppo lr 3e-4 x 4 updates, ddpg lr 1e-3 x 4).
PARAM_ATOL = 1e-2


def assert_metrics_close(a, b, rtol, atol):
    assert a.keys() == b.keys()
    for k in a:
        va, vb = np.asarray(a[k]), np.asarray(b[k])
        if np.isnan(va).all() and np.isnan(vb).all():
            continue
        np.testing.assert_allclose(va, vb, rtol=rtol, atol=atol, err_msg=k)


def _replicated_init(t, ik):
    state = t.learner.init(ik)
    if t.mesh is not None and t.mesh.size > 1:
        from surreal_tpu.parallel.mesh import replicate_state

        state = replicate_state(t.mesh, state)
    return state


def _fused_ppo(tmp_path, iters=1, **algo_over):
    return _fused_ppo_like(
        tmp_path, "ppo", iters, epochs=2, num_minibatches=2, **algo_over
    )


def _fused_impala(tmp_path, iters=1, **algo_over):
    return _fused_ppo_like(tmp_path, "impala", iters, **algo_over)


def _fused_ppo_like(tmp_path, algo, iters, **algo_over):
    cfg = bundle(tmp_path, algo=algo, horizon=8, **algo_over)
    t = Trainer(cfg)
    key = jax.random.key(3)
    key, ik, ek = jax.random.split(key, 3)
    state = _replicated_init(t, ik)
    carry = t.init_loop_state(ek)
    metrics = None
    for _ in range(iters):
        key, it_key = jax.random.split(key)
        state, carry, metrics = t._train_iter(state, carry, it_key)
    return jax.device_get(metrics), jax.device_get(state.params)


def _fused_ddpg(tmp_path, iters=1, **algo_over):
    cfg = bundle(
        tmp_path, algo="ddpg", horizon=8,
        exploration=Config(warmup_steps=0), updates_per_iter=4,
        **algo_over,
    )
    # batch/start/capacity all divisible by the 8-way dp mesh the
    # trainer defaults to on the simulated-device suite
    cfg = Config(
        learner_config=Config(replay=Config(batch_size=16,
                                            start_sample_size=16))
    ).extend(cfg)
    t = OffPolicyTrainer(cfg)
    key = jax.random.key(3)
    key, ik, ek = jax.random.split(key, 3)
    state = _replicated_init(t, ik)
    carry, replay_state = t.init_loop_state(ek)
    beta = jnp.asarray(0.0, jnp.float32)
    warm = jnp.asarray(False)
    metrics = None
    first = True
    for _ in range(iters):
        key, it_key = jax.random.split(key)
        state, replay_state, carry, metrics = t._train_iter(
            state, replay_state, carry, it_key, beta, warm,
            jnp.asarray(first),
        )
        first = False
    return (
        jax.device_get(metrics),
        jax.device_get({"actor": state.actor_params,
                        "critic": state.critic_params}),
    )


def _assert_trees_close(a, b, rtol, atol):
    for (pa, la), (pb, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol,
            err_msg=str(pa),
        )


@pytest.mark.parametrize(
    "variant, rtol, atol",
    [
        # tier-1 keeps the combined unroll variant (exercises all three
        # unroll knobs in one program) and the pallas impl (the most
        # distinct codepath); the single-knob variants and the assoc
        # impl compile the same fused program with the same equivalence
        # arithmetic and ride the slow tier (ISSUE 17 suite-wall
        # headroom satellite, same precedent as the ddpg sweep below)
        pytest.param({"rollout_unroll": 4}, UNROLL_RTOL, UNROLL_ATOL,
                     marks=pytest.mark.slow),
        pytest.param({"sgd_unroll": 2}, UNROLL_RTOL, UNROLL_ATOL,
                     marks=pytest.mark.slow),
        pytest.param({"gae_unroll": 4}, UNROLL_RTOL, UNROLL_ATOL,
                     marks=pytest.mark.slow),
        # the combined-unroll variant joined the slow tier in the ISSUE 18
        # headroom pass: every knob it exercises is individually covered
        # above, and tier-1 retains unroll equivalence through the ddpg
        # rollout variant below plus the pallas impl here
        pytest.param({"rollout_unroll": 8, "sgd_unroll": 2, "gae_unroll": 2},
                     UNROLL_RTOL, UNROLL_ATOL, marks=pytest.mark.slow),
        pytest.param({"gae_impl": "assoc"}, IMPL_RTOL, IMPL_ATOL,
                     marks=pytest.mark.slow),
        ({"gae_impl": "pallas"}, IMPL_RTOL, IMPL_ATOL),
    ],
    ids=["rollout", "sgd", "gae", "all-unrolls", "assoc", "pallas"],
)
def test_ppo_tuned_program_matches_default(tmp_path, variant, rtol, atol):
    base_m, base_p = _fused_ppo(tmp_path / "a")
    var_m, var_p = _fused_ppo(tmp_path / "b", **variant)
    assert_metrics_close(base_m, var_m, rtol, atol)
    _assert_trees_close(base_p, var_p, 0.0, PARAM_ATOL)


@pytest.mark.parametrize(
    "variant",
    [
        # tier-1 keeps ONE ddpg variant (the rollout unroll — the knob
        # the autotuner searches first); the other two compile the same
        # fused program with the same equivalence arithmetic and ride
        # the slow tier (ISSUE 16 suite-wall headroom satellite)
        {"rollout_unroll": 4},
        pytest.param({"update_unroll": 4}, marks=pytest.mark.slow),
        pytest.param({"rollout_unroll": 2, "update_unroll": 2},
                     marks=pytest.mark.slow),
    ],
    ids=["rollout", "update", "both"],
)
def test_ddpg_tuned_program_matches_default(tmp_path, variant):
    base_m, base_p = _fused_ddpg(tmp_path / "a")
    var_m, var_p = _fused_ddpg(tmp_path / "b", **variant)
    assert_metrics_close(base_m, var_m, UNROLL_RTOL, UNROLL_ATOL)
    _assert_trees_close(base_p, var_p, 0.0, PARAM_ATOL)


@pytest.mark.parametrize(
    "variant",
    [
        # tier-1 keeps the vtrace-unroll variant — the recurrence is
        # impala's distinct arithmetic; rollout-unroll equivalence stays
        # tier-1-covered by the ddpg rollout variant above (ISSUE 18
        # suite-wall headroom pass, same precedent as the ddpg sweep)
        pytest.param({"rollout_unroll": 4}, marks=pytest.mark.slow),
        {"gae_unroll": 4},
    ],
    ids=["rollout", "vtrace"],
)
def test_impala_tuned_program_matches_default(tmp_path, variant):
    base_m, base_p = _fused_impala(tmp_path / "a")
    var_m, var_p = _fused_impala(tmp_path / "b", **variant)
    assert_metrics_close(base_m, var_m, UNROLL_RTOL, UNROLL_ATOL)
    _assert_trees_close(base_p, var_p, 0.0, PARAM_ATOL)


def test_ddpg_batched_sampling_record_equivalence(tmp_path):
    """The uniform-replay fast path (one batched index draw + gather for
    the whole update loop) must train on the IDENTICAL record as the
    sequential path: same keys -> same indices -> same batches -> same
    updates. Index/batch equality is bit-exact (tests/test_replay.py);
    here the fused iteration's metrics and params must agree to float32
    fusion-reordering tolerance."""
    seq_m, seq_p = _fused_ddpg(tmp_path / "a", batched_uniform_sampling=False)
    fast_m, fast_p = _fused_ddpg(tmp_path / "b", batched_uniform_sampling=True)
    assert_metrics_close(seq_m, fast_m, UNROLL_RTOL, UNROLL_ATOL)
    _assert_trees_close(seq_p, fast_p, 0.0, PARAM_ATOL)


def test_prioritized_replay_keeps_sequential_sampling(tmp_path):
    """Prioritized replay must NOT take the batched path: priorities
    change between updates, so draw k+1 depends on draw k's TD errors."""
    cfg = bundle(
        tmp_path, algo="ddpg", horizon=8,
        exploration=Config(warmup_steps=0), updates_per_iter=4,
    )
    cfg = Config(
        learner_config=Config(
            replay=Config(kind="prioritized", batch_size=16,
                          start_sample_size=16))
    ).extend(cfg)
    t = OffPolicyTrainer(cfg)
    assert t.prioritized and not t._batched_sampling
