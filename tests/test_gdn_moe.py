"""The seventh block family of the trajectory seam (``model.encoder.block=
'gdn_moe'``: models/gdn_moe.py, ops/delta_rule.py with a head-wide decay,
ops/ring_attention.py's blocked attention, models/swa_moe.py's routed layer
with a gated shared expert) at toy widths on the CPU: the trunk against the
benchmark's plain reference (forward and gradient, recomputation on and off,
a segment of several chunks), acting through the matrix states, the conv
tails and the key-value caches against the whole-segment forward, the wrap,
the share test, the table of families and what the family refuses, PPO's
rows, its parts in the compiled program, and the families that were there
lowering to the programs they had. (A session through ``main/launch.py``,
``select_trainer`` and ``Trainer.run`` is the cell's rehearsal:
tests/benchmarks/test_benchmark_rehearse_ppo_lift_qwen3next_16x1024.py.)"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import attention, gdn_moe, swa_moe
from surreal_tpu.models.attention import TrajectoryPPOModel
from surreal_tpu.ops import moe
from surreal_tpu.session.config import Config

ref = manifest.load_reference("ppo_qwen3next_ref")
CONFIG = manifest.load_config("ppo_lift_qwen3next")

T, B = 12, 3
# 2 value heads a key head, a rotary part smaller than the head
TOY = dict(
    kind="trajectory", block="gdn_moe", num_layers=4, num_heads=4,
    num_kv_heads=2, attn_head_dim=8, partial_rotary_factor=0.5,
    hidden_size=32, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_head_dim=8, moe_intermediate_size=16, shared_intermediate_size=16,
    n_routed_experts=8, num_experts_per_tok=2, num_held=2,
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
    # the family's matrices are normal(0, swa_moe.INIT_STD)
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

@pytest.mark.parametrize("b,t,form,remat", [
    (B, T, "dense", False), (2, 150, "sorted", True),
])
def test_forward_and_gradient_equal_the_reference(b, t, form, remat, monkeypatch):
    """The chunked rule with a head's decay spread a channel, inside the
    trunk, against the recurrence a position at a time with a scalar decay
    and key heads indexed ``j // 2`` (150 positions: three chunks and a
    padded tail), blocked attention against one masked map, both forms of
    the held experts' product, each layer recomputed or not: the outputs and
    the gradient of every leaf are the plain reference's."""
    assert moe.dense_form(b * t) == (form == "dense")
    if remat:
        monkeypatch.setattr(gdn_moe, "REMAT_ABOVE_BYTES", 0)
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
        # the loss stops at the router's product (and this scalar does not
        # read log_std); the shared expert's gate takes its gradient
        still = "router" in name or "log_std" in name
        assert (scale == 0.0) == still, name
    gate = "['params']['trunk']['layer1']['shared']['token_gate']"
    assert any(jax.tree_util.keystr(p) == gate for p in flat)


def test_the_parameter_tree_is_the_published_layer_list():
    """Layers 0-2 mix with the gated delta rule and layer 3 with gated
    attention; every layer is routed beside a shared expert with a gate a
    token; a linear mixer's leaves at the published widths count 33 718 464
    and a full mixer's 27 263 488."""
    trunk = _params(_model(_cfg()))["params"]["trunk"]
    assert ["gdn" in trunk[f"layer{i}"] for i in range(4)] == [
        True, True, True, False,
    ]
    assert set(trunk["layer3"]["attn"]) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert trunk["layer3"]["attn"]["q"].shape == (32, 4, 16)    # q | gate
    assert set(trunk["layer0"]["gdn"]) == {
        "qkvz", "ba", "conv", "dt_bias", "A_log", "o_norm", "o",
    }
    assert trunk["layer0"]["gdn"]["qkvz"].shape == (32, (2 + 2 + 4 + 4) * 8)
    assert trunk["layer0"]["gdn"]["conv"].shape == (4, (2 + 2 + 4) * 8)
    for i in range(4):
        assert set(trunk[f"layer{i}"]["moe"]) == {"router", "gate", "up", "down"}
        assert set(trunk[f"layer{i}"]["shared"]) == {
            "gate", "up", "down", "token_gate",
        }
        # the zero-centred norms start at 0, the output norm at 1
        assert float(jnp.abs(trunk[f"layer{i}"]["attn_norm"]["w"]).max()) == 0.0
    assert float(trunk["layer0"]["gdn"]["o_norm"].min()) == 1.0
    per = ref.layer_params(CONFIG["widths"])
    assert per["gdn_proj"] + per["gdn_small"] == 33_718_464
    assert per["full"] == 27_263_488
    n = ref.parameters(CONFIG["widths"])
    assert n["layers"] == 547_873_856 == CONFIG["parameters"]["trunk"]
    assert n["total"] == n["layers"] + 17 * 2048 + 2048 + 2048 * 5 + 5 + 4
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    toy = dict(
        CONFIG["widths"], hidden_size=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        router_outputs=8, num_held=2, obs_dim=5, action_dim=2,
    )
    assert ref.parameters(toy)["layers"] == sum(
        size(trunk[f"layer{i}"]) for i in range(4)
    )


def test_acting_through_states_tails_and_caches_is_the_whole_segment_forward():
    """What ``act_step`` produced through the matrix states, the conv tails
    and the key-value rows, a position at a time over a segment of more than
    a block of the chunk, is what one whole-segment apply recomputes (the
    importance-ratio contract)."""
    horizon, envs, tol = 20, 4, 2e-5
    learner = _learner(horizon)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (horizon, envs, 5), jnp.float32)
    carry = learner.act_init(envs)
    assert set(carry["cache"]) == {"linear", "full", moe.EXPERTS_READ}
    assert len(carry["cache"]["linear"]) == 3 and len(carry["cache"]["full"]) == 1
    leaf = carry["cache"]["linear"][0]
    assert leaf["state"].shape == (envs, 4, 8, 8)
    assert leaf["state"].dtype == jnp.float32
    assert leaf["conv"].shape == (envs, 3, 64)          # one tail: q | k | v
    # a position's two heads side by side in one row
    assert carry["cache"]["full"][0]["k"].shape == (envs, horizon, 1, 16)
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


def test_a_wrap_zeroes_states_and_tails_and_leaves_the_caches_to_their_mask():
    """At the horizon the carry wraps: the step after it is position 0 of a
    fresh segment to the bit, though the key-value rows still hold the old
    segment's; ``reset_recurrent`` zeroes the linear leaves alone."""
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
    assert float(jnp.abs(carry["cache"]["linear"][0]["state"]).max()) > 0.0
    _, wrapped, after = step(carry, obs[horizon])
    _, first, _ = step(learner.act_init(envs), obs[horizon])
    assert int(after["pos"]) == 1
    np.testing.assert_array_equal(wrapped["mean"], first["mean"])
    np.testing.assert_array_equal(wrapped["value"], first["value"])
    cache = carry["cache"]
    zeroed = gdn_moe.reset_recurrent(cache, jnp.bool_(True))
    assert all(
        float(jnp.abs(x).max()) == 0.0 for x in jax.tree.leaves(zeroed["linear"])
    )
    np.testing.assert_array_equal(zeroed["full"][0]["k"], cache["full"][0]["k"])
    kept = gdn_moe.reset_recurrent(cache, jnp.bool_(False))
    jax.tree.map(np.testing.assert_array_equal, kept, cache)


# -- the share test ------------------------------------------------------------------

@pytest.mark.parametrize("tokens", [60, 320])
def test_the_sixteen_shares_of_a_routed_layer_add_up_to_the_uncut_layer(tokens):
    """Thirty-two experts over sixteen chips of two. Every chip computes the
    gated shared expert alike: what each share's routed layer gives beyond
    it (its held experts' part) adds up, with the gated shared expert once,
    to the uncut reference's layer with all thirty-two held. Dense form and
    sorted form."""
    assert moe.dense_form(tokens) == (tokens == 60)
    E, shares = 32, 16
    cfg = _cfg(n_routed_experts=E)
    s = gdn_moe._sizes(cfg)
    layer = _params(_model(cfg))["params"]["trunk"]["layer1"]
    keys = jax.random.split(jax.random.key(3), 4)
    normal = lambda k, *shape: INIT_STD * jax.random.normal(k, shape)  # noqa: E731
    whole = dict(
        layer["moe"], router=8 * normal(keys[0], 32, E),   # no two scores tie
        gate=normal(keys[1], E, 32, 16), up=normal(keys[2], E, 32, 16),
        down=normal(keys[3], E, 16, 32),
    )
    h = jax.random.normal(jax.random.key(4), (tokens, 32))
    w_all = _widths(dict(cfg, first_held=0, num_held=E))
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.sigmoid(h @ layer["shared"]["token_gate"])
        shared_out = gate[:, None] * ref.swiglu(layer["shared"], h)
        total, loads = jnp.zeros_like(h), []
        for share in range(shares):
            mine = dict(whole, **{
                k: whole[k][2 * share:2 * share + 2] for k in ("gate", "up", "down")
            })
            y, stats = swa_moe.routed_ffn(
                mine, layer["shared"], h, dict(s, first=2 * share)
            )
            assert float(stats["overflow"]) == 0.0
            assert float(stats["shared_gate"]) == pytest.approx(float(gate.mean()))
            loads.append(stats["load"])
            total = total + (y - shared_out)
        total = total + shared_out
        uncut, info = ref.routed(whole, layer["shared"], h, w_all)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=2e-5)
    # every chip routes over all thirty-two alike, two a token
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 2
    assert info["own"].shape == (tokens, 2)


# -- the table of families -----------------------------------------------------------

def test_the_table_has_the_family_and_the_selectors_read_it():
    assert attention.BLOCK_FAMILIES[-1] == "gdn_moe"
    family = attention.family_of({"block": "gdn_moe"})
    assert family is gdn_moe.FAMILY
    assert family.defaults is gdn_moe.FAMILY_DEFAULTS
    # what it offers a learner: a recurrent reset, counters and the routed
    # rows; nothing moves the router
    assert family.reset_recurrent is gdn_moe.reset_recurrent
    assert {row for row, _ in family.counters.values()} == {
        "gdn/state_abs_max", "gdn/decay_mean", "gdn/beta_mean",
        "gdn/gram_in_vmem", "gdn/walk_in_vmem", "attn/gate_mean",
        "attn/scores_in_vmem", "moe/shared_gate_mean",
    }
    assert family.counters["state_abs_max"][1] == "max"
    assert family.moe_stats is swa_moe.moe_stats
    assert family.update_router_bias is None and family.router_biases is None
    assert family.not_read == ()


@pytest.mark.parametrize("encoder,message", [
    (dict(kind="trajectory", linear_head_dim=8), "'preln' does not read"),
    (dict(TOY, features=128), "'gdn_moe' does not read"),
    (dict(TOY, kv_lora_rank=16), "'gdn_moe' does not read"),
    (dict(TOY, kda_head_dim=8), "'gdn_moe' does not read"),
    (dict(TOY, index_topk=8), "'gdn_moe' does not read"),
    (dict(TOY, num_heads=5), "multiple of num_kv_heads"),
    (dict(TOY, linear_num_value_heads=3), "multiple of linear_num_key_heads"),
    (dict(TOY, partial_rotary_factor=0.375), "an even part of the head"),
    (dict(TOY, short_conv_kernel_size=1), "at least 2 taps"),
    (dict(TOY, first_held=7), "lie outside"),
    (dict(TOY, block="kda_moe", num_layers=5, num_heads=2, kda_head_dim=8),
     "'kda_moe' does not read"),
    (dict(TOY, block="dsa_moe"), "'dsa_moe' does not read"),
])
def test_a_key_of_another_family_or_a_bad_size_is_refused(encoder, message):
    from surreal_tpu.learners.seq_policy import family_config

    with pytest.raises(ValueError, match=message):
        family_config(encoder)


def test_the_default_config_has_the_familys_keys_unset_and_its_layer_list():
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    enc = BASE_LEARNER_CONFIG.model.encoder
    for key in gdn_moe.FAMILY_DEFAULTS:
        assert key in enc and enc[key] is None, key
    resolved = gdn_moe.resolve({"num_heads": 16, "num_layers": 9})
    assert gdn_moe.layer_kinds(resolved) == [
        "linear", "linear", "linear", "full", "linear", "linear", "linear",
        "full", "linear",
    ]
    assert gdn_moe.PERIOD == CONFIG["full_attention_interval"]


def test_the_program_defaults_are_the_published_widths():
    w = CONFIG["widths"]
    published = {
        "hidden_size": w["hidden_size"],
        "linear_num_key_heads": w["linear_num_key_heads"],
        "linear_num_value_heads": w["linear_num_value_heads"],
        "linear_head_dim": w["linear_key_head_dim"],
        "short_conv_kernel_size": w["linear_conv_kernel_dim"],
        "num_kv_heads": w["num_key_value_heads"],
        "attn_head_dim": w["head_dim"],
        "partial_rotary_factor": w["partial_rotary_factor"],
        "rope_theta": w["rope_theta"],
        "moe_intermediate_size": w["moe_intermediate_size"],
        "shared_intermediate_size": w["shared_expert_intermediate_size"],
        "n_routed_experts": w["router_outputs"],
        "num_experts_per_tok": w["num_experts_per_tok"],
        "rms_norm_eps": w["rms_norm_eps"],
        "first_held": w["first_held"], "num_held": w["num_held"],
    }
    assert w["linear_key_head_dim"] == w["linear_value_head_dim"]
    assert {k: float(v) for k, v in gdn_moe.FAMILY_DEFAULTS.items()} == {
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
    with pytest.raises(ValueError, match="'gdn_moe' runs flat vector obs"):
        build_learner(with_stem, pixels)
    learner = _learner(8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="no sp mesh path"):
        build_seq_model(
            learner.config.model, SPECS, -0.5, mesh=mesh, horizon=8,
        )
    with pytest.raises(ValueError, match="'gdn_moe' is wired into PPO alone"):
        build_learner(
            Config(
                algo=Config(name="impala", horizon=8),
                model=Config(encoder=Config(**TOY)),
            ), SPECS,
        )


def test_recomputation_is_the_shared_rule_chosen_from_the_pass():
    published = gdn_moe.resolve({"num_heads": 16, "num_layers": 4})
    # a minibatch's 8192 tokens do not fit beside 8.8 GB of state
    assert 3e9 < gdn_moe.residual_bytes(published, 8192) < 8e9
    assert gdn_moe.residual_bytes(_cfg(), B * T) < gdn_moe.REMAT_ABOVE_BYTES
    assert not {"remat", "recompute", "checkpoint"} & set(gdn_moe.FAMILY_DEFAULTS)


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
        patch.setattr(swa_moe, "INIT_STD", INIT_STD)
        learner = _learner()
        state = learner.init(jax.random.key(0))
        batch = _batch(learner, state)
        key = jax.random.key(5)
        compiled = jax.jit(learner.learn).lower(state, batch, key).compile()
        yield learner, state, batch, compiled, compiled(state, batch, key)


def test_learn_reports_the_rows_moves_the_gate_and_no_router_and_the_ratio_is_one(
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
    assert set(stats) == {"load", "overflow", *gdn_moe.COUNTERS}
    assert stats["load"].shape == (4, 8)
    metrics = {k: float(v) for k, v in metrics.items()}
    assert 0.0 <= metrics["moe/held_share"] <= 1.0
    assert metrics["moe/overflow"] == 0.0
    assert 0.0 < metrics["gdn/state_abs_max"] < 10.0
    assert 0.5 < metrics["gdn/decay_mean"] < 1.0
    for row in ("gdn/beta_mean", "attn/gate_mean", "moe/shared_gate_mean"):
        assert metrics[row] == pytest.approx(0.5, abs=0.1), row
    # heads of 8 channels on the CPU: the lax forms
    for row in ("gdn/gram_in_vmem", "gdn/walk_in_vmem", "attn/scores_in_vmem"):
        assert metrics[row] == 0.0, row
    assert math.isfinite(metrics["loss/pg"]) and metrics["health/update_ratio"] > 0
    before, after = state.params["params"]["trunk"], new.params["params"]["trunk"]
    moved = lambda *path: float(jnp.abs(  # noqa: E731
        _at(before, path) - _at(after, path)
    ).max())
    for i in range(4):
        # the routers take a zero gradient, the shared gate a non-zero one
        assert moved(f"layer{i}", "moe", "router") == 0.0
        assert moved(f"layer{i}", "shared", "token_gate") > 0.0
        assert moved(f"layer{i}", "moe", "gate") > 0.0
    assert moved("layer0", "gdn", "A_log") > 0.0
    assert moved("layer3", "attn", "q_norm") > 0.0


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# -- parts -----------------------------------------------------------------------------

GDN_PARTS = {
    "gdn_scan", "gdn_proj", "attn", "moe_route", "moe_experts", "optimizer",
}


def test_the_compiled_program_names_the_familys_parts(learned):
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import PARTS, part, part_of

    assert {"gdn_scan", "gdn_proj"} < set(PARTS)
    assert part_of("jit(learn)/sgd/transpose(jvp(gdn_scan))/while/body/dot") == "gdn_scan"
    assert part_of("jit(learn)/sgd/remat(gdn_proj)/dot") == "gdn_proj"
    with pytest.raises(ValueError, match="not in the vocabulary"):
        part("gdn")
    text = learned[3].as_text()
    parts = hlo_op_phases(text, part_of)[1]
    phases = hlo_op_phases(text)[1]
    assert set(parts.values()) == GDN_PARTS
    for name in ("gdn_scan", "gdn_proj", "attn"):
        seen = {phases[i] for i, p in parts.items() if p == name and i in phases}
        assert {"prepare", "sgd"} <= seen, (name, seen)


# -- the families that were there ------------------------------------------------------

# sha256 (first 16 hex) of the StableHLO text of ``learn`` and of ``act_step``
# of 'dsa_moe' at tests/test_dsa_moe.py's toy widths, 4 envs x 12 positions,
# ``mixed``, as the PARENT commit of PR 63 lowers them (jax 0.9.0; read off a
# ``git archive`` of it): the shared expert's optional gate in ``routed_ffn``
# and the rule's head-wide decay change no program that was there
# (tests/test_dsa_moe.py holds the four other wide families the same way)
DSA_PARENT_LOWERING = ("56d720c4b468a7a8", "b12def0fccc8b9ac")


def test_dsa_moe_lowers_to_the_program_it_had():
    import test_dsa_moe

    assert test_dsa_moe.lowering_hashes(test_dsa_moe.TOY) == DSA_PARENT_LOWERING
