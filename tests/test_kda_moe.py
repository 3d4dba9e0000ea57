"""The fifth block family of the trajectory seam (``model.encoder.block=
'kda_moe'``: models/kda_moe.py, ops/delta_rule.py, models/latent_moe.py's
latent attention without rotary and its routed experts) at toy widths on the
CPU: the trunk against the benchmark's plain reference (forward and gradient,
recomputation on and off, a segment of several chunks), acting through the
matrix states, the conv tails and the latent cache against the whole-segment
forward, the wrap, the share test, the table of families and what the family
refuses, PPO's rows, and its parts in the compiled program. (A session
through ``main/launch.py``, ``select_trainer`` and ``Trainer.run`` is the
cell's rehearsal:
tests/benchmarks/test_benchmark_rehearse_ppo_lift_kimilinear_16x1024.py.)"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import attention, kda_moe, latent_moe
from surreal_tpu.models.attention import TrajectoryPPOModel
from surreal_tpu.ops import moe
from surreal_tpu.session.config import Config

ref = manifest.load_reference("ppo_kimilinear_ref")
CONFIG = manifest.load_config("ppo_lift_kimilinear")

T, B = 12, 3
TOY = dict(
    kind="trajectory", block="kda_moe", num_layers=5, num_heads=2,
    hidden_size=32, kda_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
    moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
    num_held=2,
)
SPECS = EnvSpecs(
    obs=ArraySpec(shape=(5,), dtype=np.dtype(np.float32)),
    action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
)
INIT_STD = 0.125    # a product keeps its input's size at 32 wide


def _cfg(**encoder):
    from surreal_tpu.learners.seq_policy import family_config

    return family_config({**TOY, **encoder})


def _widths(cfg):
    return ref.widths_of(CONFIG, cfg)


def _model(cfg, dtype=jnp.float32):
    return TrajectoryPPOModel(encoder_cfg=cfg, act_dim=2, compute_dtype=dtype)


@pytest.fixture(autouse=True)
def _init(monkeypatch):
    # the family's matrices are latent_moe's and its own, from one constant
    monkeypatch.setattr(latent_moe, "INIT_STD", INIT_STD)
    monkeypatch.setattr(kda_moe, "INIT_STD", INIT_STD)


def _params(model, seed=0):
    return {"params": model.init(
        jax.random.key(seed), jnp.zeros((1, 1, 5))
    )["params"]}


def _obs(b=B, t=T, seed=1):
    return jax.random.normal(jax.random.key(seed), (b, t, 5), jnp.float32)


def _learner(horizon=T, precision="f32", **encoder):
    cfg = Config(
        algo=Config(
            name="ppo", horizon=horizon, epochs=2, num_minibatches=2,
            precision=precision,
        ),
        model=Config(encoder=Config(**{**TOY, **encoder})),
    )
    return build_learner(cfg, SPECS)


# -- the trunk against the reference ---------------------------------------------

@pytest.mark.parametrize("b,t,form,remat", [
    (B, T, "dense", False), (2, 150, "sorted", True),
])
def test_forward_and_gradient_equal_the_reference(b, t, form, remat, monkeypatch):
    # the gradient where each layer is recomputed: the harder of the two
    """The chunked rule inside the trunk against the recurrence a position at
    a time (150 positions: three chunks and a padded tail), both forms of the
    held experts' product, each layer recomputed or not (flax's lifted remat
    around a block that sows): the outputs and the gradient of every leaf are
    the plain reference's."""
    assert moe.dense_form(b * t) == (form == "dense")
    if remat:
        monkeypatch.setattr(kda_moe, "REMAT_ABOVE_BYTES", 0)
    cfg = _cfg()
    model, w = _model(cfg), _widths(cfg)
    params, obs = _params(model), _obs(b, t)

    def ours(p):
        out = model.apply(p, obs)
        return (out.value ** 2).sum() + (out.mean ** 2).sum(), out

    def theirs(p):
        mean, _, value, _, _ = ref.policy(p, obs, w)
        return (value ** 2).sum() + (mean ** 2).sum(), (mean, value)

    with jax.default_matmul_precision("highest"):
        if not remat:
            out, (mean, value) = jax.jit(ours)(params)[1], jax.jit(theirs)(params)[1]
        else:
            (_, out), g = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
            (_, (mean, value)), g_ref = jax.jit(
                jax.value_and_grad(theirs, has_aux=True)
            )(params)
    np.testing.assert_allclose(out.mean, mean, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out.value, value, rtol=1e-4, atol=1e-5)
    if not remat:
        return
    flat, flat_ref = (
        dict(jax.tree_util.tree_leaves_with_path(x)) for x in (g, g_ref)
    )
    assert flat.keys() == flat_ref.keys()
    for path, leaf in flat.items():
        scale = float(jnp.abs(flat_ref[path]).max())
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            leaf, flat_ref[path], rtol=5e-4, atol=5e-5 * max(scale, 1e-3),
            err_msg=name,
        )
        # the loss stops at the router's product, the selection bias has no
        # gradient (and this scalar does not read log_std)
        still = ("router" in name or ref.BIAS_LEAF in name or "log_std" in name)
        assert (scale == 0.0) == still, name


def test_the_parameter_tree_is_the_published_layer_list():
    """Layers 1, 2, 3 and 5 mix with KDA and layer 4 with latent attention
    (no query pair, no rotary); layer 1 is dense, the rest routed with a
    selection bias and one shared expert; a KDA mixer's leaves at the
    published widths count 39 514 272 and a latent mixer's 29 114 880."""
    trunk = _params(_model(_cfg()))["params"]["trunk"]
    assert [("kda" in trunk[f"layer{i}"], "ffn" in trunk[f"layer{i}"])
            for i in range(5)] == [
        (True, True), (True, False), (True, False), (False, False), (True, False),
    ]
    assert set(trunk["layer3"]["attn"]) == {"q", "kv_a", "kv_b", "o", "kv_a_norm"}
    assert set(trunk["layer1"]["moe"]) == {
        "router", ref.BIAS_LEAF, "gate", "up", "down", "shared0",
    }
    assert set(trunk["layer0"]["kda"]) == {
        "q", "k", "v", "conv_q", "conv_k", "conv_v", "f_a", "f_b", "dt_bias",
        "A_log", "b", "g_a", "g_b", "o_norm", "o",
    }
    # the same leaves at the published widths (tests/test_tpu_compile.py
    # counts the whole tree the learner builds there)
    per = ref.layer_params(CONFIG["widths"])
    assert per["kda_proj"] + per["kda_small"] == 39_514_272
    assert per["latent"] == 29_114_880
    n = ref.parameters(CONFIG["widths"])
    assert n["layers"] == 508_060_288 == CONFIG["parameters"]["trunk"]
    assert n["total"] == n["layers"] + 17 * 2304 + 2304 + 2304 * 5 + 5 + 4
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    toy = dict(
        CONFIG["widths"], hidden_size=32, kda_num_heads=2, kda_head_dim=8,
        num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, kv_lora_rank=16, intermediate_size=64,
        moe_intermediate_size=16, router_outputs=8, num_held=2, obs_dim=5,
        action_dim=2,
    )
    assert ref.parameters(toy)["layers"] == sum(
        size(trunk[f"layer{i}"]) for i in range(5)
    )


def test_acting_through_states_tails_and_cache_is_the_whole_segment_forward():
    """What ``act_step`` produced through the matrix states, the conv tails
    and the latent rows, a position at a time over a segment of more than a
    block of the chunk, is what one whole-segment apply recomputes (the
    importance-ratio contract)."""
    horizon, envs, tol = 20, 4, 2e-5
    learner = _learner(horizon)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (horizon, envs, 5), jnp.float32)
    carry = learner.act_init(envs)
    assert set(carry["cache"]) == {"kda", "latent", moe.EXPERTS_READ}
    assert len(carry["cache"]["kda"]) == 4 and len(carry["cache"]["latent"]) == 1
    leaf = carry["cache"]["kda"][0]
    assert leaf["state"].shape == (envs, 2, 8, 8)
    assert leaf["state"].dtype == jnp.float32
    assert leaf["conv"]["q"].shape == (envs, 3, 2, 8)
    assert carry["cache"]["latent"][0].shape == (envs, horizon, 20)
    step = jax.jit(
        lambda s, c, o: learner.act_step(s, c, o, jax.random.key(0), "eval_deterministic")
    )
    means, values = [], []
    for t in range(horizon):
        _, info, carry = step(state, carry, obs[t])
        means.append(info["mean"])
        values.append(info["value"])
    out = learner.model.apply(
        state.params, learner._norm_obs(state.obs_stats, obs.swapaxes(0, 1))
    )
    assert float(jnp.abs(jnp.stack(means, 1) - out.mean).max()) < tol
    assert float(jnp.abs(jnp.stack(values, 1) - out.value).max()) < tol


def test_a_wrap_zeroes_states_and_tails_and_leaves_the_cache_to_its_mask():
    """At the horizon the carry wraps: the step after it is position 0 of a
    fresh segment to the bit, though the latent rows still hold the old
    segment's; ``reset_recurrent`` zeroes the KDA leaves alone."""
    horizon, envs = 6, 2
    learner = _learner(horizon)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (horizon + 1, envs, 5))
    step = jax.jit(
        lambda c, o: learner.act_step(state, c, o, jax.random.key(0), "eval_deterministic")
    )
    carry = learner.act_init(envs)
    for t in range(horizon):
        _, _, carry = step(carry, obs[t])
    assert int(carry["pos"]) == horizon
    assert float(jnp.abs(carry["cache"]["kda"][0]["state"]).max()) > 0.0
    _, wrapped, after = step(carry, obs[horizon])
    _, first, _ = step(learner.act_init(envs), obs[horizon])
    assert int(after["pos"]) == 1
    np.testing.assert_array_equal(wrapped["mean"], first["mean"])
    np.testing.assert_array_equal(wrapped["value"], first["value"])
    cache = carry["cache"]
    zeroed = kda_moe.reset_recurrent(cache, jnp.bool_(True))
    assert all(float(jnp.abs(x).max()) == 0.0 for x in jax.tree.leaves(zeroed["kda"]))
    np.testing.assert_array_equal(zeroed["latent"][0], cache["latent"][0])
    kept = kda_moe.reset_recurrent(cache, jnp.bool_(False))
    jax.tree.map(np.testing.assert_array_equal, kept, cache)


# -- the share test ------------------------------------------------------------------

@pytest.mark.parametrize("tokens", [60, 320])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tokens):
    """Eight experts over four chips of two. Every chip computes the mixer
    and the shared expert alike: the program's KDA mixer is the reference's,
    counted once; what each share's routed layer gives beyond the shared
    expert (its held experts' part) adds up, with the shared expert once, to
    the uncut reference's layer with all eight held. Dense form and sorted
    form."""
    b, t = 2, tokens // 2
    assert moe.dense_form(b * t) == (tokens == 60)
    cfg = _cfg()
    layer = dict(_params(_model(cfg))["params"]["trunk"]["layer1"])
    E = 8
    keys = jax.random.split(jax.random.key(3), 4)
    normal = lambda k, *shape: INIT_STD * jax.random.normal(k, shape)  # noqa: E731
    whole = dict(
        layer["moe"], router=8 * normal(keys[0], 32, E),   # no two scores tie
        gate=normal(keys[1], E, 32, 16), up=normal(keys[2], E, 32, 16),
        down=normal(keys[3], E, 16, 32),
    )
    whole[ref.BIAS_LEAF] = 0.05 * jnp.arange(E, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(4), (b, t, 32))
    w_all = _widths(dict(cfg, first_held=0, num_held=E))
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        mixer, _ = kda_moe.DeltaAttention(cfg, jnp.float32).apply(
            {"params": layer["kda"]}, ref.rms_norm(layer["attn_norm"], x, eps)
        )
        want = ref.kda(layer["kda"], ref.rms_norm(layer["attn_norm"], x, eps), w_all)[0]
        np.testing.assert_allclose(mixer, want, rtol=2e-4, atol=2e-5)
        mixed = x + mixer
        h = ref.rms_norm(layer["ffn_norm"], mixed, eps).reshape(-1, 32)
        shared_out = ref.swiglu(whole["shared0"], h)
        total, loads = jnp.zeros_like(h), []
        for share in range(4):
            mine = dict(whole, **{
                k: whole[k][2 * share:2 * share + 2] for k in ("gate", "up", "down")
            })
            (y, _), sown = latent_moe.RoutedExperts(
                dict(cfg, first_held=2 * share), jnp.float32
            ).apply({"params": mine}, h, mutable=["moe", "moe_routing"])
            assert float(sown["moe"]["overflow"][0]) == 0.0
            loads.append(sown["moe"]["load"][0])
            total = total + (y - shared_out)
        total = total + shared_out
        uncut, info = ref.routed(whole, h, w_all)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=2e-5)
    # every chip routes over all eight alike, two a token
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 2
    # the bias enters the selection and not the weights
    s, picked = ref.biased_scores(whole, h)
    np.testing.assert_array_equal(info["own"], jnp.argsort(-picked, -1)[:, :2])
    assert bool((jnp.argsort(-s, -1)[:, :2] != info["own"]).any())


# -- the table of families -----------------------------------------------------------

def test_the_table_has_the_family_and_the_selectors_read_it():
    assert attention.BLOCK_FAMILIES[-1] == "kda_moe"
    family = attention.family_of({"block": "kda_moe"})
    assert family is kda_moe.FAMILY
    assert family.defaults is kda_moe.FAMILY_DEFAULTS
    # what it offers a learner: a recurrent reset, counters, the routed rows
    # and latent_moe's own bias rule
    assert family.reset_recurrent is kda_moe.reset_recurrent
    assert set(family.counters) == {
        "state_abs_max", "decay_mean", "beta_mean", "gram_in_vmem",
        "walk_in_vmem",
    }
    assert family.moe_stats is latent_moe.moe_stats
    assert family.update_router_bias is latent_moe.update_router_bias
    assert family.router_biases is latent_moe.router_biases
    assert family.not_read == ()
    # its latent layers have neither a query pair nor a rotary base
    assert not {"q_lora_rank", "rope_theta"} & set(kda_moe.FAMILY_DEFAULTS)


@pytest.mark.parametrize("encoder,message", [
    (dict(kind="trajectory", kda_head_dim=8), "'preln' does not read"),
    (dict(TOY, features=128), "'kda_moe' does not read"),
    (dict(TOY, q_lora_rank=24), "'kda_moe' does not read"),
    (dict(TOY, rope_theta=1e4), "'kda_moe' does not read"),
    (dict(TOY, window_heads=6), "'kda_moe' does not read"),
    (dict(TOY, pairs_before=2), "'kda_moe' does not read"),
    (dict(TOY, num_layers=1), "leaves no routed layer"),
    (dict(TOY, short_conv_kernel_size=1), "at least 2 taps"),
    (dict(TOY, first_held=7), "lie outside"),
    (dict(TOY, block="mla_moe", num_layers=3, q_lora_rank=24, kda_head_dim=8),
     "'mla_moe' does not read"),
    (dict(TOY, block="swa_moe", short_conv_kernel_size=4), "'swa_moe' does not read"),
])
def test_a_key_of_another_family_or_a_bad_size_is_refused(encoder, message):
    from surreal_tpu.learners.seq_policy import family_config

    with pytest.raises(ValueError, match=message):
        family_config(encoder)


def test_the_default_config_has_the_familys_keys_unset_and_its_layer_list():
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    enc = BASE_LEARNER_CONFIG.model.encoder
    for key in kda_moe.FAMILY_DEFAULTS:
        assert key in enc and enc[key] is None, key
    resolved = kda_moe.resolve({"num_heads": 32, "num_layers": 9})
    assert kda_moe.layer_kinds(resolved) == [
        ("kda", True), ("kda", False), ("kda", False), ("latent", False),
        ("kda", False), ("kda", False), ("kda", False), ("latent", False),
        ("kda", False),
    ]
    # the published pattern, but the last layer (27), which is latent out of
    # period and outside any cut that starts at layer 1
    published = CONFIG["linear_attn_config"]
    kinds = kda_moe.layer_kinds(dict(resolved, num_layers=26))
    assert [i + 1 for i, (k, _) in enumerate(kinds) if k == "latent"] == [
        l for l in published["full_attn_layers"] if l != 27
    ]
    assert [i + 1 for i, (k, _) in enumerate(kinds) if k == "kda"] == (
        published["kda_layers"]
    )


def test_the_program_defaults_are_the_published_widths():
    w = CONFIG["widths"]
    published = {
        "hidden_size": w["hidden_size"], "kda_head_dim": w["kda_head_dim"],
        "short_conv_kernel_size": w["short_conv_kernel_size"],
        "kv_lora_rank": w["kv_lora_rank"],
        "qk_nope_head_dim": w["qk_nope_head_dim"],
        "qk_rope_head_dim": w["qk_rope_head_dim"], "v_head_dim": w["v_head_dim"],
        "intermediate_size": w["intermediate_size"],
        "moe_intermediate_size": w["moe_intermediate_size"],
        "n_routed_experts": w["router_outputs"],
        "num_experts_per_tok": w["num_experts_per_token"],
        "n_shared_experts": w["num_shared_experts"],
        "routed_scaling_factor": w["routed_scaling_factor"],
        "first_k_dense_replace": w["first_k_dense_replace"],
        "rms_norm_eps": w["rms_norm_eps"],
        "first_held": w["first_held"], "num_held": w["num_held"],
        "bias_update_speed": 0.001,
    }
    assert {k: float(v) for k, v in kda_moe.FAMILY_DEFAULTS.items()} == {
        k: float(v) for k, v in published.items()
    }


def test_the_family_refuses_the_stem_the_mesh_and_impala():
    from surreal_tpu.learners.seq_policy import build_seq_model

    pixels = EnvSpecs(
        obs=ArraySpec(shape=(84, 84, 4), dtype=np.dtype(np.uint8)),
        action=SPECS.action,
    )
    with_stem = Config(
        algo=Config(name="ppo", horizon=8),
        model=Config(encoder=Config(**TOY), cnn=Config(enabled=True)),
    )
    with pytest.raises(ValueError, match="'kda_moe' runs flat vector obs"):
        build_learner(with_stem, pixels)
    learner = _learner(8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="no sp mesh path"):
        build_seq_model(
            learner.config.model, SPECS, -0.5, mesh=mesh, horizon=8,
        )
    with pytest.raises(ValueError, match="'kda_moe' is wired into PPO alone"):
        build_learner(
            Config(
                algo=Config(name="impala", horizon=8),
                model=Config(encoder=Config(**TOY)),
            ), SPECS,
        )


def test_recomputation_is_the_shared_rule_chosen_from_the_pass():
    """One rule for the family, in ``models/attention.py``, for the others to
    move to: a function or a module class is wrapped past the threshold and
    left as it is under it; no key switches it."""
    published = kda_moe.resolve({"num_heads": 32, "num_layers": 5})
    # a minibatch's 8192 tokens do not fit beside 8.1 GB of state
    assert 4e9 < kda_moe.residual_bytes(published, 8192) < 8e9
    assert kda_moe.residual_bytes(_cfg(), B * T) < kda_moe.REMAT_ABOVE_BYTES
    assert not {"remat", "recompute", "checkpoint"} & set(kda_moe.FAMILY_DEFAULTS)
    fn = lambda x: x + 1  # noqa: E731
    assert attention.recomputed(fn, 10, 10) is fn
    assert attention.recomputed(fn, 11, 10) is not fn
    assert attention.recomputed(kda_moe.Block, 10, 10) is kda_moe.Block
    wrapped = attention.recomputed(kda_moe.Block, 11, 10)
    assert wrapped is not kda_moe.Block and issubclass(wrapped, kda_moe.Block)
    # the other two families keep their own thresholds
    from surreal_tpu.models import ssm_hybrid, swa_moe

    assert ssm_hybrid.REMAT_ABOVE_BYTES == 2 * 2**30
    assert swa_moe.REMAT_ABOVE_BYTES == 2**30


# -- under PPO -------------------------------------------------------------------------

def _batch(learner, state, envs=4, horizon=T, seed=2):
    keys = jax.random.split(jax.random.key(seed), 4)
    obs = jax.random.normal(keys[0], (horizon + 1, envs, 5))
    carry = learner.act_init(envs)
    step = jax.jit(learner.act_step)
    actions, logps, means = [], [], []
    for t in range(horizon):
        action, info, carry = step(
            state, carry, obs[t], jax.random.fold_in(keys[1], t)
        )
        actions.append(action)
        logps.append(info["logp"])
        means.append(info["mean"])
    done = jax.random.bernoulli(keys[2], 0.1, (horizon, envs))
    return {
        "obs": obs[:-1], "next_obs": obs[1:], "action": jnp.stack(actions),
        "reward": jax.random.normal(keys[3], (horizon, envs)),
        "done": done, "terminated": done,
        "behavior_logp": jnp.stack(logps),
        "behavior": {
            "mean": jnp.stack(means),
            "log_std": jnp.broadcast_to(
                state.params["params"]["log_std"], (horizon, envs, 2)
            ),
        },
    }


@pytest.fixture(scope="module")
def learned():
    """One compiled ``learn`` for the rows and for the parts."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent_moe, "INIT_STD", INIT_STD)
        patch.setattr(kda_moe, "INIT_STD", INIT_STD)
        learner = _learner()
        state = learner.init(jax.random.key(0))
        batch = _batch(learner, state)
        key = jax.random.key(5)
        compiled = jax.jit(learner.learn).lower(state, batch, key).compile()
        yield learner, state, batch, compiled, compiled(state, batch, key)


def test_learn_reports_the_rows_moves_the_bias_and_no_router_and_the_ratio_is_one(
    learned,
):
    learner, state, batch, _, (new, metrics) = learned
    # what acting computed through the carry is what the learn pass
    # recomputes over the whole segment: the first step's ratio is 1
    out, stats = learner._apply(
        state.params,
        learner._norm_obs(state.obs_stats, batch["obs"]).swapaxes(0, 1),
    )
    from surreal_tpu.ops import distributions as D

    logp = D.diag_gauss_logp(out.mean, out.log_std, batch["action"].swapaxes(0, 1))
    ratio = jnp.exp(logp - batch["behavior_logp"].swapaxes(0, 1))
    assert float(jnp.abs(ratio - 1).max()) < 1e-4
    assert set(stats) == {
        "load", "overflow", "state_abs_max", "decay_mean", "beta_mean",
        "gram_in_vmem", "walk_in_vmem",
    }
    assert stats["load"].shape == (4, 8)
    metrics = {k: float(v) for k, v in metrics.items()}
    assert 0.0 <= metrics["moe/held_share"] <= 1.0
    assert metrics["moe/overflow"] == 0.0
    assert metrics["moe/bias_abs_max"] == pytest.approx(0.004)   # four steps
    assert 0.0 < metrics["kda/state_abs_max"] < 10.0
    assert 0.5 < metrics["kda/decay_mean"] < 1.0
    assert metrics["kda/beta_mean"] == pytest.approx(0.5, abs=0.1)
    # heads of 8 channels on the CPU: the lax form of the Gram matrices and
    # of the walk over the chunks
    assert metrics["kda/gram_in_vmem"] == 0.0
    assert metrics["kda/walk_in_vmem"] == 0.0
    assert math.isfinite(metrics["loss/pg"]) and metrics["health/update_ratio"] > 0
    before, after = state.params["params"]["trunk"], new.params["params"]["trunk"]
    for i in range(1, 5):
        np.testing.assert_array_equal(
            before[f"layer{i}"]["moe"]["router"], after[f"layer{i}"]["moe"]["router"]
        )
        assert float(jnp.abs(after[f"layer{i}"]["moe"][ref.BIAS_LEAF]).max()) > 0.0
        assert float(jnp.abs(
            before[f"layer{i}"]["moe"]["gate"] - after[f"layer{i}"]["moe"]["gate"]
        ).max()) > 0.0
    assert float(jnp.abs(
        before["layer0"]["kda"]["A_log"] - after["layer0"]["kda"]["A_log"]
    ).max()) > 0.0


# -- parts -----------------------------------------------------------------------------

KIMI_PARTS = {
    "kda_scan", "kda_proj", "attn", "moe_route", "moe_experts", "dense_ffn",
    "optimizer",
}


def test_the_compiled_program_names_the_familys_parts(learned):
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import PARTS, part, part_of

    assert {"kda_scan", "kda_proj"} < set(PARTS)
    assert part_of("jit(learn)/sgd/transpose(jvp(kda_scan))/while/body/dot") == "kda_scan"
    assert part_of("jit(learn)/sgd/remat(kda_proj)/dot") == "kda_proj"
    with pytest.raises(ValueError, match="not in the vocabulary"):
        part("kda")
    text = learned[3].as_text()
    parts = hlo_op_phases(text, part_of)[1]
    phases = hlo_op_phases(text)[1]
    assert set(parts.values()) == KIMI_PARTS
    for name in ("kda_scan", "kda_proj", "attn"):
        seen = {phases[i] for i, p in parts.items() if p == name and i in phases}
        assert {"prepare", "sgd"} <= seen, (name, seen)
