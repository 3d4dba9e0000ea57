"""The plain Laguna-S-2.1 reference against the cell's own fused iteration
at toy widths on the CPU in float32 (under tight bounds and under the
chip's own): the rehearsal's geometry, 4 envs x 12 positions with a window
of 4, so that the rings forget, 2 x 4 minibatches of one env, the second
iteration of a session replayed; each term of the mathematics removed or
changed in turn, and a minibatch of each epoch left out, to show that the
comparison would catch it; the operation, byte and parameter counts against
a count by hand at the published widths; and the configuration file against
the catalog row, key by key."""

import copy
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, runner

ref = manifest.load_reference("ppo_laguna_ref")

CELL = "ppo_lift_laguna_16x1024"
F32 = dict(rtol=1e-3, atol=5e-4)
# the change of the parameters in float32 against float32: 3e-5 of its norm
TIGHT = {
    k: dict(rtol=0.0, atol=2e-3) if k.startswith(("learn/param", "learn/leaf"))
    else F32 for k in ref.TOL
}
# the row's share is over the learn passes, the reference's count over the
# prepare pass (the bootstrap position with it): no precision tightens it
TIGHT["moe/held_share"] = ref.TOL["moe/held_share"]
# a norm's scale is 1 and a step of Adam 1e-5 beside it: float32 rounds each
# of the eight steps to 6e-8, a thousandth of the change
TIGHT["learn/param_change/norms"] = dict(rtol=0.0, atol=5e-3)
SEED = 2147485011
# a matrix product keeps its input's size, as 0.02 does at 3072 wide: at
# 0.02 here every block would vanish beside the projection
INIT_STD = 0.125
# what notices each changed term first at 12 positions
CAUGHT_BY = {
    "gate": "act/value/under",
    "shared_expert": "act/value/under",
    "routed_scale": "act/value/under",
    "full_rotates_whole": "act/value/under",
    "yarn_plain": "act/value/under",
    "attention_factor": "act/value/under",
    "theta_swapped": "act/value/under",
    "window_511": "attn/window_keys_mean",
    "window_513": "attn/window_keys_mean",
    "router_bf16": "route/score_agree",
    "softmax_bf16": "route/score_agree",
    "second_minibatch": "learn/param_change",
}
# nothing to see at 12 positions and toy widths: a head of 8 turns two pairs
# and YaRN's ramp leaves both as they are; eight experts leave no near-ties.
# tests/test_swa_moe.py holds the table to the formula at the published head
# and test_router_precision_shows_at_the_published_router_width the scores
TOY_BLIND = ("yarn_plain", "router_bf16", "softmax_bf16")
# and under the chip's limits, which are for 1024 positions, the sliding
# table's theta (four pairs over 12 positions); the chip's own readings are
# in the reference's notes
CHIP_SIZE_ONLY = TOY_BLIND + ("theta_swapped",)


@pytest.fixture(scope="module")
def laguna(tmp_path_factory):
    """The cell's rehearsal, as ``benchmarks/run.py --rehearse`` sizes it,
    in float32."""
    import jax

    from surreal_tpu.models import swa_moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(swa_moe, "INIT_STD", INIT_STD)
        # the suite simulates eight devices; the cell has one chip
        one = jax.devices()[:1]
        patch.setattr(jax, "devices", lambda *a, **k: one)
        cell = runner.sized(manifest.load_cell(CELL), True)
        sys = ref.system_reports(
            manifest.load_config(cell["config"]), cell,
            str(tmp_path_factory.mktemp("laguna")), SEED, True,
            extra=("learner_config.algo.precision=f32",),
        )
    # a leaf fetched from the chip comes in the device's layout, which is
    # not always row-major: the host's passes may not count on it
    for tree in (*(sys["before"][k] for k in ("params", "mu", "nu")), sys["moved"]):
        tree.update({k: np.asfortranarray(v) for k, v in tree.items()})
    yield sys


def _compare(sys, tol, dropped=None, learn=True):
    return ref.compare(sys, ref.reference_reports(sys, dropped, learn=learn), tol)


def test_laguna_reference_agrees_with_the_fused_iteration(laguna):
    result = _compare(laguna, TIGHT)
    assert result["ok"], {
        k: r for k, r in result["comparisons"].items() if not r["ok"]
    }
    rows = result["comparisons"]
    assert set(rows) == (
        set(ref.TOL) | {
            "route/agree_share", "route/tie_gap", "route/score_agree",
            "moe/overflow", "act/replay_is_rollout",
            "collect/rollout_is_session", "session/repeats",
            "act/wrap_is_fresh", "learn/router_still", "learn/early_stopped",
        }
    )
    assert rows["collect/rollout_is_session"]["alone"]["episode/count"] > 0
    assert rows["session/repeats"]["keys"] >= 14
    # the state the iteration started from is a session's: Adam's moments
    # hold the first iteration's eight steps
    assert laguna["before"]["count"] == 8
    # episodes end inside the segment (the rehearsal's time limit of 8)
    batch = laguna["batch"]
    assert bool((batch["done"] & ~batch["terminated"]).any())
    assert rows["attn/window_keys_mean"]["scale"] == pytest.approx(
        (1 + 2 + 3 + 4 * 9) / 12
    )
    assert rows["attn/gate_mean"]["scale"] == pytest.approx(0.5, abs=0.05)
    # float32 on both sides: the program's ten are the reference's
    assert rows["route/agree_share"]["value"] == 1.0
    assert rows["route/score_agree"]["value"] == 1.0
    assert rows["learn/leaf_moved"]["leaves"] == len(laguna["moved"]) == 73
    # the four routers rest on both sides, and nothing else does
    assert len(rows["learn/router_still"]["at_rest"]) == 4
    assert rows["learn/early_stopped"]["branches"] == 1
    assert len(rows["learn/early_stopped"]["kl_steps"]) == 8
    assert rows["act/wrap_is_fresh"]["pos_after"] == 1


@pytest.mark.parametrize("dropped", [t for t in ref.TERMS if t not in TOY_BLIND])
def test_laguna_reference_fails_without_a_term(laguna, dropped):
    result = _compare(
        laguna, TIGHT, dropped, learn=dropped == "second_minibatch"
    )
    assert not result["ok"], dropped
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], {
        k: r for k, r in result["comparisons"].items() if not r["ok"]
    }


@pytest.mark.parametrize(
    "dropped", [t for t in ref.TERMS if t not in CHIP_SIZE_ONLY]
)
def test_laguna_chip_tolerances_still_catch_a_changed_term(laguna, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert not _compare(
        laguna, ref.TOL, dropped, learn=dropped == "second_minibatch"
    )["ok"]


def test_laguna_chip_tolerances_pass_the_program(laguna):
    assert _compare(laguna, ref.TOL)["ok"]


def test_a_router_that_moved_fails(laguna):
    """The loss stops at the router's product: a router the program moved
    is a fault, not a gain."""
    leaf = "['params']['trunk']['layer2']['moe']['router']"
    moved = copy.copy(laguna)
    moved["moved"] = dict(
        laguna["moved"], **{leaf: laguna["moved"][leaf] + 1e-4}
    )
    rows = _compare(moved, ref.TOL)["comparisons"]
    assert not rows["learn/router_still"]["ok"]
    assert rows["learn/router_still"]["moved_alone"] == [leaf]


@pytest.mark.parametrize("leaf", [
    "['params']['trunk']['layer1']['attn']['gate']",
    "['params']['trunk']['layer4']['shared']['down']",
    "['params']['log_std']",
])
def test_a_leaf_left_where_it_was_fails(laguna, leaf):
    still = copy.copy(laguna)
    still["moved"] = dict(
        laguna["moved"], **{leaf: np.zeros_like(laguna["moved"][leaf])}
    )
    rows = _compare(still, ref.TOL)["comparisons"]
    assert not rows["learn/leaf_moved"]["ok"]
    assert rows["learn/leaf_moved"]["unmoved_leaves"] == [leaf]


def test_a_swapped_expert_counts_against_the_agreement(laguna):
    """Routing the program chose otherwise than the reference would: the
    share falls and the gap is the swapped expert's distance in logits."""
    swapped = copy.copy(laguna)
    act = [layer.copy() for layer in laguna["routing"]["act"]]
    # every token's second expert replaced by one it did not choose
    E = int(laguna["widths"]["n_routed_experts"])
    for layer in act:
        chosen = layer[..., 0]
        layer[..., 1] = (chosen + 1 + (layer[..., 1] == (chosen + 1) % E)) % E
    swapped["routing"] = dict(laguna["routing"], act=act)
    rows = _compare(swapped, ref.TOL, learn=False)["comparisons"]
    assert not rows["route/agree_share"]["ok"]
    assert rows["route/agree_share"]["value"] < 0.7
    assert not rows["route/tie_gap"]["ok"]


@pytest.mark.parametrize("dropped", ["router_bf16", "softmax_bf16"])
def test_router_precision_shows_at_the_published_router_width(dropped):
    """256 outputs, ten a token, logits of the size a 0.02 router gives a
    normed token: scores or a softmax held in bfloat16 tie neighbours, and
    the reference's ten on the same inputs are no longer the program's."""
    import jax
    import jax.numpy as jnp

    w = ref.static({
        "num_experts_per_tok": 10, "num_layers": 2,
        "layer_types": ("full_attention", "sliding_attention"),
        "mlp_only_layers": (0,),
    })
    k_x, k_r = jax.random.split(jax.random.key(3))
    x = np.asarray(jax.random.normal(k_x, (4096, 64)), np.float32)
    router = jax.random.normal(k_r, (64, 256)) * (1.1 / 8.0)
    params = {"params": {"trunk": {"layer1": {"moe": {"router": router}}}}}
    with jax.default_matmul_precision("highest"):
        used = np.asarray(
            ref.top_experts(jnp.asarray(x) @ router, w)[1]
        ).reshape(4, 1024, 10)
    sys = {"widths": w, "routing": {"router_inputs": [x], "prepare": [used]}}
    assert ref.score_agreement(sys, params) == 1.0
    # 0.944 and 0.975 here
    assert ref.score_agreement(sys, params, dropped) < ref.SCORE_AGREE_MIN - 0.01


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("ppo_lift_laguna")
    cell = manifest.load_cell(CELL)
    w = config["widths"]
    kv = 2 * 3072 * 8 * 128
    full = 2 * 3072 * 48 * 128 + kv + 3072 * 48
    window = 2 * 3072 * 72 * 128 + kv + 3072 * 72
    dense, expert = 3 * 3072 * 12288, 3 * 3072 * 1024
    routed = 3072 * 256 + 8 * expert + expert
    n = ref.parameters(w)
    assert n["by_group"] == {
        "attn_full": 2 * full, "attn_window": 3 * window, "dense_ffn": dense,
        "router": 4 * 3072 * 256, "held_experts": 32 * expert,
        "shared": 4 * expert, "norms": 10 * 3072,
    }
    assert (full, window, dense, routed) == (
        44_187_648, 63_135_744, 113_246_208, 85_721_088
    )
    assert n["layers"] == 733_943_808          # the issue's five, to the parameter
    assert n["total"] == n["layers"] + 17 * 3072 + 3072 + 3072 * 5 + 5 + 4
    assert config["parameters"]["trunk"] == n["layers"]
    tok = ref.token_macs(w, 1024)
    assert tok["attn_full"] == 2 * (full + 2 * 48 * 128 * 512.5)
    assert tok["attn_window"] == 3 * (window + 2 * 72 * 128 * 384.25)
    assert tok["dense_ffn"] == dense
    assert tok["moe_route"] == 4 * 3072 * 256
    # 10 x 8 / 256 assignments a token a layer at even routing, and the shared
    assert tok["moe_experts"] == 4 * (0.3125 * expert + expert)
    # attention 65% of a token's products: an expert here sees a thirty-second
    # of its deployment's tokens
    attn = tok["attn_full"] + tok["attn_window"]
    assert attn / tok["forward"] == pytest.approx(0.652, abs=0.002)
    assert tok["forward"] == pytest.approx(477.6e6, rel=1e-3)   # 955 MFLOP
    cost = ref.iteration_cost(config, cell["traffic"])
    assert cost["samples"] == 16384
    assert cost["flops"] == 2 * tok["forward"] * (16384 * 7 + 16 * 1025)
    assert cost["flops"] == pytest.approx(125.2e12, rel=1e-3)
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    assert cost["forward_equivalents"] == 8 and cost["routed_layers"] == 4
    assert cost["expert_flops_per_assignment"] == 2 * expert
    assert cost["shared_flops_per_token"] == 2 * expert
    # acting: bfloat16 weights once a step; three rings to min(t + 1, 512)
    # and two full caches to t + 1; a row written in each of five
    row = 2 * 2 * 8 * 128
    assert cost["collect_bytes"] == (
        1024 * 2 * n["total"]
        + 16 * row * (3 * int(384.25 * 1024) + 2 * (1024 * 1025 // 2))
        + 1024 * 16 * row * 5
    )
    assert cost["collect_bytes"] == pytest.approx(1.650e12, rel=1e-3)
    assert cost["bytes"] == cost["collect_bytes"] + 8 * 28 * n["total"]


def test_config_file_carries_the_catalog_row():
    config = manifest.load_config("ppo_lift_laguna")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(catalog)]
    row = next(r for r in rows if r["name"] == "Laguna-S-2.1")
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 0)
    assert config["deployment"]["chips_sharing_a_routed_layer"] == 32
    # the widths the cost is counted from and the overrides that run are
    # the file's own top-level numbers
    w = config["widths"]
    for key, value in w.items():
        if key in config and key != "layer_types":
            assert config[key] == value, key
    assert w["layer_types"] == config["layer_types"][:5]
    assert (w["full_attention_heads"], w["sliding_attention_heads"]) == (
        config["num_attention_heads_per_layer"][0],
        config["num_attention_heads_per_layer"][1],
    )
    assert w["router_outputs"] == config["published"]["num_experts"]
    assert w["num_held"] == config["num_experts"]
    assert w["shared_expert_intermediate_size"] == config[
        "shared_expert_intermediate_size"]
    assert w["moe_routed_scaling_factor"] == config["moe_routed_scaling_factor"]
    sets = dict(o.split("=", 1) for o in config["overrides"])
    enc = "learner_config.model.encoder."
    for key, name in (
        ("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("shared_intermediate_size", "shared_expert_intermediate_size"),
        ("sliding_window", "sliding_window"), ("num_kv_heads", "num_key_value_heads"),
        ("attn_head_dim", "head_dim"), ("num_heads", "full_attention_heads"),
        ("window_heads", "sliding_attention_heads"),
        ("n_routed_experts", "router_outputs"), ("num_held", "num_held"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("routed_scaling_factor", "moe_routed_scaling_factor"),
        ("num_layers", "num_hidden_layers"),
    ):
        assert float(sets[enc + key]) == float(w[name]), key
    # one value in use: constants of the program, not keys; the same tables
    from surreal_tpu.models import swa_moe

    tables = config["rope_parameters"]
    full, sliding = tables["full_attention"], tables["sliding_attention"]
    assert swa_moe.ROPE["full"] == dict(
        theta=full["rope_theta"], partial=full["partial_rotary_factor"],
        factor=full["factor"], original=full["original_max_position_embeddings"],
        beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
        attention_factor=full["attention_factor"],
    )
    assert swa_moe.ROPE["window"] == dict(
        theta=sliding["rope_theta"], partial=sliding["partial_rotary_factor"]
    )
    kinds = [
        "full" if t == "full_attention" else "window"
        for t in config["layer_types"]
    ]
    assert kinds == [
        "full" if i % swa_moe.PERIOD == 0 else "window" for i in range(48)
    ]
    for key in config["reduced"] + [
        "gate", "window edge", "rotary", "routing", "shared expert",
        "auxiliary loss", "router gradient", "init", "recomputation",
    ]:
        assert key in config["assumed"], key


def test_the_program_defaults_are_the_published_widths():
    from surreal_tpu.models.swa_moe import FAMILY_DEFAULTS, resolve

    config = manifest.load_config("ppo_lift_laguna")
    w = config["widths"]
    published = {
        "hidden_size": w["hidden_size"],
        "window_heads": w["sliding_attention_heads"],
        "num_kv_heads": w["num_key_value_heads"],
        "attn_head_dim": w["head_dim"],
        "sliding_window": w["sliding_window"],
        "intermediate_size": w["intermediate_size"],
        "moe_intermediate_size": w["moe_intermediate_size"],
        "shared_intermediate_size": w["shared_expert_intermediate_size"],
        "n_routed_experts": w["router_outputs"],
        "num_experts_per_tok": w["num_experts_per_tok"],
        "routed_scaling_factor": w["moe_routed_scaling_factor"],
        "first_k_dense_replace": len(w["mlp_only_layers"]),
        "rms_norm_eps": w["rms_norm_eps"],
        "first_held": w["first_held"],
        "num_held": w["num_held"],
    }
    assert {k: float(v) for k, v in FAMILY_DEFAULTS.items()} == {
        k: float(v) for k, v in published.items()
    }
    resolved = resolve({"num_heads": 48, "num_layers": 5})
    assert resolved["window_heads"] == 72 and resolved["num_held"] == 8


def test_a_program_without_the_family_is_refused_before_anything_launches(
    monkeypatch,
):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("swa_moe") else real(name, *a),
    )
    config = manifest.load_config("ppo_lift_laguna")
    with pytest.raises(manifest.ManifestError, match="swa_moe"):
        ref.iteration_cost(config, {"num_envs": 16, "horizon": 1024,
                                    "epochs": 2, "num_minibatches": 4})
