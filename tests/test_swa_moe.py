"""The fourth block family of the trajectory seam (``model.encoder.block=
'swa_moe'``: models/swa_moe.py, ops/moe.py's softmax routing) at toy widths
on the CPU: the trunk against the benchmark's plain reference in both
forms of the expert product (forward and gradient, recomputation on and
off), acting through the full caches and the rings against the
whole-segment forward past the window, the rotary tables against the
formula, the share test, ``route``'s sigmoid form against the jaxpr it had,
the table of families and what the family refuses, PPO's rows, and its
parts in the compiled program."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import swa_moe
from surreal_tpu.models.attention import TrajectoryPPOModel
from surreal_tpu.ops import moe
from surreal_tpu.session.config import Config

ref = manifest.load_reference("ppo_laguna_ref")
CONFIG = manifest.load_config("ppo_lift_laguna")

WINDOW, T, B = 4, 12, 3
TOY = dict(
    kind="trajectory", block="swa_moe", num_layers=5, num_heads=4,
    window_heads=6, num_kv_heads=2, attn_head_dim=8, hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16,
    shared_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
    num_held=2, sliding_window=WINDOW,
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
    """What the reference reads: the resolved sizes and the configuration
    file's own tables and layer pattern."""
    return ref.widths_of(CONFIG, cfg)


def _model(cfg, dtype=jnp.float32):
    return TrajectoryPPOModel(encoder_cfg=cfg, act_dim=2, compute_dtype=dtype)


@pytest.fixture(autouse=True)
def _init(monkeypatch):
    monkeypatch.setattr(swa_moe, "INIT_STD", INIT_STD)


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

@pytest.mark.parametrize("b,t,form", [(B, T, "dense"), (2, 160, "sorted")])
@pytest.mark.parametrize("remat", [False, True])
def test_forward_and_gradient_equal_the_reference(b, t, form, remat, monkeypatch):
    """Both forms of the held experts' product (an acting step's and a
    learn pass's: ops/moe.py), each layer recomputed or not: the outputs and
    the gradient of every leaf are the plain reference's."""
    assert moe.dense_form(b * t) == (form == "dense")
    if remat:
        monkeypatch.setattr(swa_moe, "REMAT_ABOVE_BYTES", 0)
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
        (_, out), g = jax.value_and_grad(ours, has_aux=True)(params)
        (_, (mean, value)), g_ref = jax.value_and_grad(theirs, has_aux=True)(params)
    np.testing.assert_allclose(out.mean, mean, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out.value, value, rtol=1e-4, atol=1e-5)
    flat, flat_ref = (
        dict(jax.tree_util.tree_leaves_with_path(x)) for x in (g, g_ref)
    )
    assert flat.keys() == flat_ref.keys()
    for path, leaf in flat.items():
        scale = float(jnp.abs(flat_ref[path]).max())
        np.testing.assert_allclose(
            leaf, flat_ref[path], rtol=2e-4, atol=2e-5 * max(scale, 1e-3),
            err_msg=jax.tree_util.keystr(path),
        )
        name = jax.tree_util.keystr(path)
        # the loss stops at the router's product (and this scalar does not
        # read log_std)
        assert (scale == 0.0) == ("router" in name or "log_std" in name), name


@pytest.mark.parametrize("window,horizon", [(WINDOW, T), (512, 520)])
def test_acting_through_rings_and_caches_is_the_whole_segment_forward(
    window, horizon,
):
    """A position at a time against two full caches and three rings of
    rotated keys, past the window (the rings forget) and, at the published
    window, past step 512: every position's outputs are the whole-segment
    forward's."""
    cfg = _cfg(sliding_window=window)
    model = _model(cfg)
    params, obs = _params(model), _obs(2, horizon)
    cache = model.init_cache(2, horizon)
    assert [c["k"].shape for c in cache["full"]] == [(2, horizon, 2, 8)] * 2
    assert [c["k"].shape for c in cache["window"]] == [
        (2, min(window, horizon), 2, 8)
    ] * 3

    def step(cache, xs):
        o, pos = xs
        out, cache = model.apply(params, o, cache=cache, pos=pos)
        return cache, (out.mean, out.value)

    with jax.default_matmul_precision("highest"):
        whole = model.apply(params, obs)
        _, (mean, value) = jax.lax.scan(
            step, cache, (obs.swapaxes(0, 1), jnp.arange(horizon))
        )
    np.testing.assert_allclose(mean.swapaxes(0, 1), whole.mean, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(value.swapaxes(0, 1), whole.value, rtol=1e-4, atol=2e-5)
    assert horizon > window   # or nothing was forgotten


def test_a_stale_carry_is_masked_after_the_wrap():
    """No leaf is recurrent: position 0 against a carry full of another
    segment's rows is position 0 of a fresh one."""
    cfg = _cfg()
    model = _model(cfg)
    params, obs = _params(model), _obs(2, 1)[:, 0]
    fresh = model.init_cache(2, T)
    stale = jax.tree.map(lambda x: x + 3.0, fresh)
    a, _ = model.apply(params, obs, cache=fresh, pos=jnp.int32(0))
    b, _ = model.apply(params, obs, cache=stale, pos=jnp.int32(0))
    np.testing.assert_array_equal(a.value, b.value)
    from surreal_tpu.models.attention import reset_recurrent

    assert reset_recurrent(cfg, stale, jnp.bool_(True)) is stale


# -- the rotary tables -------------------------------------------------------------

def test_yarn_table_is_the_formula_and_the_sliding_table_is_plain():
    full = CONFIG["rope_parameters"]["full_attention"]
    theta, rot = full["rope_theta"], 64       # half of a head of 128
    got = swa_moe.inv_freq("full", 128)
    assert got.shape == (32,)
    plain = np.array([theta ** (-2 * i / rot) for i in range(32)])
    # beta_fast 32 and beta_slow 1 turns in 8192 positions: pairs under 9
    # keep their frequency, pairs from 18 on are stretched 128 times, the
    # ramp between is linear in the pair's index
    low = rot * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(theta))
    high = rot * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(low), math.ceil(high)) == (9, 18)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], plain[18:] / 128, rtol=1e-12)
    for i in range(10, 18):
        blend = (i - 9) / 9
        assert got[i] == pytest.approx(
            plain[i] * (1 - blend) + plain[i] / 128 * blend, rel=1e-12
        )
    np.testing.assert_allclose(got, ref.frequencies(full, 128), rtol=1e-12)
    # the sliding table: every dimension, theta 10 000, no YaRN, no factor
    sliding = swa_moe.inv_freq("window", 128)
    np.testing.assert_allclose(
        sliding, [10_000.0 ** (-2 * i / 128) for i in range(64)], rtol=1e-12
    )
    np.testing.assert_allclose(
        sliding,
        ref.frequencies(CONFIG["rope_parameters"]["sliding_attention"], 128),
        rtol=1e-12,
    )
    pos = jnp.arange(3)
    cos_f, sin_f = swa_moe.rope_table("full", 128, pos)
    cos_w, _ = swa_moe.rope_table("window", 128, pos)
    assert cos_f.shape == (3, 32) and cos_w.shape == (3, 64)
    assert float(cos_f[0, 0]) == pytest.approx(full["attention_factor"], rel=1e-6)
    assert float(cos_w[0, 0]) == 1.0 and float(sin_f[0, 0]) == 0.0


def test_a_full_layer_turns_the_first_half_and_a_sliding_layer_all():
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 128))
    pos = jnp.arange(5) + 7
    full = swa_moe.rotate(x, *swa_moe.rope_table("full", 128, pos))
    np.testing.assert_array_equal(full[..., 64:], x[..., 64:])
    assert float(jnp.abs(full[..., :64] - x[..., :64]).min()) > 0.0
    sliding = swa_moe.rotate(x, *swa_moe.rope_table("window", 128, pos))
    assert float(jnp.abs(sliding[..., 64:] - x[..., 64:]).max()) > 0.1
    # pairs (i, i + rot / 2), as the reference turns them
    table = CONFIG["rope_parameters"]["sliding_attention"]
    want = ref.turn(jnp.pad(x, ((0, 0), (7, 0), (0, 0), (0, 0))), table)[:, 7:]
    np.testing.assert_allclose(sliding, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dropped,moves", [
    (None, False), ("yarn_plain", True), ("full_rotates_whole", True),
    ("attention_factor", True),
])
def test_a_full_layer_at_the_published_head_against_the_reference(dropped, moves):
    """At a head of 128 over 512 positions (a toy head turns two pairs and
    YaRN leaves both alone): the layer is the reference's, and the
    reference without YaRN's blend, turning the whole head, or without the
    attention factor is another layer."""
    cfg = _cfg(attn_head_dim=128, num_heads=2, num_kv_heads=1)
    s, w = swa_moe._sizes(cfg), _widths(cfg)
    leaves = swa_moe.LayerLeaves("full", True, cfg).init(jax.random.key(0))
    p = leaves["params"]["attn"]
    h = jax.random.normal(jax.random.key(1), (1, 512, 32))
    with jax.default_matmul_precision("highest"):
        ours, _, gate = swa_moe.attention_mixer(p, h, s, jnp.float32, "full")
        theirs, gate_ref = ref.attention(p, h, w, "full", dropped)
    err = float(jnp.abs(ours - theirs).max()) / float(jnp.abs(theirs).max())
    # YaRN's blend alone moves it by 0.04 at 512 positions, the others more
    assert (err > 0.02) if moves else (err < 1e-4), err
    assert float(gate) == pytest.approx(float(gate_ref), rel=1e-5)


# -- the share ---------------------------------------------------------------------

@pytest.mark.parametrize("tokens", [64, 320])
def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(tokens):
    """Eight experts over four chips of two: what each share's routed layer
    gives (its held experts' part and the shared expert, which every chip
    computes alike), the shared expert counted once, adds up to the uncut
    reference's layer with all eight held. Dense form and sorted form."""
    assert moe.dense_form(tokens) == (tokens == 64)
    cfg = _cfg()
    D, E, F = 32, 8, 16
    keys = jax.random.split(jax.random.key(0), 8)
    normal = lambda k, *shape: INIT_STD * jax.random.normal(k, shape)
    whole = {
        "router": 8 * normal(keys[0], D, E),   # logits of size 1: no two tie
        "gate": normal(keys[1], E, D, F), "up": normal(keys[2], E, D, F),
        "down": normal(keys[3], E, F, D),
    }
    shared = {
        "gate": normal(keys[4], D, F), "up": normal(keys[5], D, F),
        "down": normal(keys[6], F, D),
    }
    x = jax.random.normal(keys[7], (tokens, D))
    with jax.default_matmul_precision("highest"):
        shared_out = moe.swiglu(x, shared["gate"], shared["up"], shared["down"])
        total = jnp.zeros_like(x)
        loads = []
        for share in range(4):
            s = swa_moe._sizes(dict(cfg, first_held=2 * share))
            mine = dict(whole, **{
                k: whole[k][2 * share:2 * share + 2] for k in ("gate", "up", "down")
            })
            y, stats = swa_moe.routed_ffn(mine, shared, x, s)
            assert float(stats["overflow"]) == 0.0
            # either form, off the TPU: every held expert's weights are read
            assert float(stats["read"]) == 1.0
            loads.append(stats["load"])
            total = total + (y - shared_out)
        total = total + shared_out
        w = _widths(dict(cfg, first_held=0, num_held=E))
        uncut, info = ref.routed(whole, shared, x, w)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    # every chip routes over all eight alike, two a token
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 2
    # the weights are normalised over both chosen whether held or not: x 2.5
    prob = jax.nn.softmax(info["logits"], -1)
    chosen = jnp.take_along_axis(prob, info["own"], -1)
    assert float(jnp.abs(chosen / chosen.sum(-1, keepdims=True) * 2.5).sum(-1).max()
                 ) == pytest.approx(2.5, rel=1e-5)


# -- ops/moe.py::route ---------------------------------------------------------------

def _parent_route(logits, bias, top_k: int, scale: float):
    """``ops/moe.py::route`` as it stood before it took a scoring."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True) * scale
    return idx.astype(jnp.int32), weights, scores


def test_route_in_its_sigmoid_form_lowers_to_the_jaxpr_it_had():
    logits = jnp.zeros((64, 256), jnp.float32)
    bias = jnp.zeros((256,), jnp.float32)
    was = jax.make_jaxpr(lambda l, b: _parent_route(l, b, 8, 2.5))(logits, bias)
    now = jax.make_jaxpr(lambda l, b: moe.route(l, b, 8, 2.5))(logits, bias)
    named = jax.make_jaxpr(
        lambda l, b: moe.route(l, b, 8, 2.5, "sigmoid")
    )(logits, bias)
    assert str(now) == str(was) == str(named)
    with pytest.raises(ValueError, match="sigmoid|softmax"):
        moe.route(logits, bias, 8, 2.5, "tanh")


def test_route_softmax_takes_the_largest_and_normalises_over_them():
    logits = jax.random.normal(jax.random.key(0), (32, 16))
    idx, weights, scores = moe.route(logits, None, 4, 2.5, "softmax")
    np.testing.assert_allclose(scores.sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_array_equal(
        np.sort(idx, -1), np.sort(np.argsort(-np.asarray(logits), -1)[:, :4], -1)
    )
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    p = np.asarray(scores)
    want = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(
        weights, want / want.sum(-1, keepdims=True) * 2.5, rtol=1e-5
    )


# -- the table of families -------------------------------------------------------------

def test_the_table_has_one_entry_a_family_and_the_selectors_read_it():
    from surreal_tpu.models import attention, latent_moe, ssm_hybrid

    assert attention.BLOCK_FAMILIES == (
        "preln", "mla_moe", "ssm_hybrid", "swa_moe", "kda_moe", "dsa_moe",
        "gdn_moe",
    )
    assert attention.family_of({"block": "preln"}) is None
    assert attention.family_of({}) is None
    for name, module in (
        ("mla_moe", latent_moe), ("ssm_hybrid", ssm_hybrid), ("swa_moe", swa_moe),
    ):
        family = attention.family_of({"block": name})
        assert family is module.FAMILY
        assert family.defaults is module.FAMILY_DEFAULTS
    # what each offers a learner
    assert latent_moe.FAMILY.update_router_bias and latent_moe.FAMILY.moe_stats
    assert not latent_moe.FAMILY.counters and not latent_moe.FAMILY.reset_recurrent
    assert ssm_hybrid.FAMILY.reset_recurrent and not ssm_hybrid.FAMILY.moe_stats
    assert ssm_hybrid.FAMILY.not_read == ("num_layers",)
    assert swa_moe.FAMILY.moe_stats and swa_moe.FAMILY.counters
    assert swa_moe.FAMILY.update_router_bias is None
    assert swa_moe.FAMILY.reset_recurrent is None
    with pytest.raises(ValueError, match="not in preln"):
        attention.block_family({"block": "mamba"})


@pytest.mark.parametrize("encoder,message", [
    (dict(kind="trajectory", window_heads=6), "'preln' does not read"),
    (dict(TOY, features=128), "'swa_moe' does not read"),
    (dict(TOY, kv_lora_rank=16), "'swa_moe' does not read"),
    (dict(TOY, pairs_before=2), "'swa_moe' does not read"),
    (dict(TOY, window_heads=5), "multiple of num_kv_heads"),
    (dict(TOY, num_layers=1), "leaves no routed layer"),
    (dict(TOY, first_held=7), "lie outside"),
    (dict(TOY, block="mla_moe", num_layers=3, shared_intermediate_size=16),
     "'mla_moe' does not read"),
])
def test_a_key_of_another_family_or_a_bad_size_is_refused(encoder, message):
    from surreal_tpu.learners.seq_policy import family_config

    with pytest.raises(ValueError, match=message):
        family_config(encoder)


def test_the_default_config_has_the_familys_keys_unset():
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    enc = BASE_LEARNER_CONFIG.model.encoder
    for key in swa_moe.FAMILY_DEFAULTS:
        assert key in enc and enc[key] is None, key
    resolved = swa_moe.resolve({"num_heads": 48, "num_layers": 5})
    assert swa_moe.layer_kinds(resolved) == [
        ("full", True), ("window", False), ("window", False),
        ("window", False), ("full", False),
    ]


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
    with pytest.raises(ValueError, match="'swa_moe' runs flat vector obs"):
        build_learner(with_stem, pixels)
    learner = _learner(8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="no sp mesh path"):
        build_seq_model(
            learner.config.model, SPECS, -0.5, mesh=mesh, horizon=8,
        )
    with pytest.raises(ValueError, match="'swa_moe' is wired into PPO alone"):
        build_learner(
            Config(
                algo=Config(name="impala", horizon=8),
                model=Config(encoder=Config(**TOY)),
            ), SPECS,
        )


def test_recomputation_is_chosen_from_the_pass_and_not_by_a_key():
    published = swa_moe.resolve({"num_heads": 48, "num_layers": 5})
    # a 4096-token minibatch's residuals do not fit beside 11.7 GB of state
    assert swa_moe.residual_bytes(published, 4096) > swa_moe.REMAT_ABOVE_BYTES
    assert 1.5e9 < swa_moe.residual_bytes(published, 4096) < 2.5e9
    assert swa_moe.residual_bytes(_cfg(), B * T) < swa_moe.REMAT_ABOVE_BYTES
    assert not {"remat", "recompute", "checkpoint"} & set(swa_moe.FAMILY_DEFAULTS)


# -- under PPO -------------------------------------------------------------------------

def _batch(learner, state, envs=4, horizon=T, seed=2):
    from surreal_tpu.envs.jax.base import JaxEnv  # noqa: F401  (the rollout's types)

    keys = jax.random.split(jax.random.key(seed), 4)
    obs = jax.random.normal(keys[0], (horizon + 1, envs, 5))
    carry = learner.act_init(envs)
    actions, logps, means = [], [], []
    for t in range(horizon):
        action, info, carry = learner.act_step(
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


def test_learn_reports_the_routed_rows_and_the_counters_and_moves_no_router():
    learner = _learner()
    state = learner.init(jax.random.key(0))
    batch = _batch(learner, state)
    new, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(5))
    metrics = {k: float(v) for k, v in metrics.items()}
    assert 0.0 <= metrics["moe/held_share"] <= 1.0
    assert metrics["moe/load_max_over_mean"] >= 1.0 or metrics["moe/held_share"] == 0
    assert metrics["moe/overflow"] == 0.0
    # no selection bias in this family: no rule runs, no row reports one
    assert "moe/bias_abs_max" not in metrics
    assert metrics["attn/window_keys_mean"] == pytest.approx(
        ref.window_keys_mean(T, WINDOW)
    )
    assert metrics["attn/gate_mean"] == pytest.approx(0.5, abs=0.1)
    assert math.isfinite(metrics["loss/pg"]) and metrics["health/update_ratio"] > 0
    before, after = state.params["params"]["trunk"], new.params["params"]["trunk"]
    for i in range(1, 5):
        np.testing.assert_array_equal(
            before[f"layer{i}"]["moe"]["router"], after[f"layer{i}"]["moe"]["router"]
        )
        assert float(jnp.abs(
            before[f"layer{i}"]["moe"]["gate"] - after[f"layer{i}"]["moe"]["gate"]
        ).max()) > 0.0
    assert "ffn" in before["layer0"] and "moe" not in before["layer0"]


def test_ppo_first_epoch_ratio_is_one():
    """What acting computed through the rings and caches is what the learn
    pass recomputes over the whole segment: the importance ratio of the
    first minibatch step is 1."""
    learner = _learner()
    state = learner.init(jax.random.key(0))
    batch = _batch(learner, state)
    out, stats = learner._apply(
        state.params,
        learner._norm_obs(state.obs_stats, batch["obs"]).swapaxes(0, 1),
    )
    from surreal_tpu.ops import distributions as D

    logp = D.diag_gauss_logp(out.mean, out.log_std, batch["action"].swapaxes(0, 1))
    ratio = jnp.exp(logp - batch["behavior_logp"].swapaxes(0, 1))
    assert float(jnp.abs(ratio - 1).max()) < 1e-4
    assert set(stats) == {"load", "overflow", "window_keys_mean", "gate_mean"}
    assert stats["load"].shape == (4, 8)


# -- parts -----------------------------------------------------------------------------

LAGUNA_PARTS = {
    "attn_window", "attn_full", "moe_route", "moe_experts", "dense_ffn",
    "optimizer",
}


def test_the_compiled_program_names_the_familys_parts():
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import PARTS, part_of

    assert {"attn_window", "attn_full"} < set(PARTS)
    learner = _learner(8, "mixed")
    state = jax.eval_shape(learner.init, jax.random.key(0))
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    flag = jax.ShapeDtypeStruct((8, 4), bool)
    batch = {
        "obs": f32(8, 4, 5), "next_obs": f32(8, 4, 5), "action": f32(8, 4, 2),
        "reward": f32(8, 4), "done": flag, "terminated": flag,
        "behavior_logp": f32(8, 4),
        "behavior": {"mean": f32(8, 4, 2), "log_std": f32(8, 4, 2)},
    }
    text = jax.jit(learner.learn).lower(
        state, batch, jax.eval_shape(lambda: jax.random.key(0))
    ).compile().as_text()
    parts = hlo_op_phases(text, part_of)[1]
    phases = hlo_op_phases(text)[1]
    assert set(parts.values()) == LAGUNA_PARTS
    # 'attn' stays what the other families use
    assert part_of("jit(learn)/sgd/jvp(attn_window)/dot") == "attn_window"
    assert part_of("jit(learn)/sgd/attn/dot") == "attn"
    for name in ("attn_window", "attn_full"):
        seen = {phases[i] for i, p in parts.items() if p == name and i in phases}
        assert {"prepare", "sgd"} <= seen, (name, seen)
