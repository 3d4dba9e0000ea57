"""The plain Kimi-Linear reference against the cell's own fused iteration at
toy widths on the CPU in float32 (under tight bounds and under the chip's
own): the rehearsal's geometry, 4 envs x 20 positions (more than a block of
the rule's chunk), 2 x 2 minibatches of two envs, the second iteration of a
session replayed; each term of the mathematics removed or changed in turn, and
a minibatch of each epoch left out, to show that the comparison would catch
it; the operation, byte and parameter counts against a count by hand at the
published widths; and the configuration file against the catalog row, key by
key."""

import copy
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, runner

ref = manifest.load_reference("ppo_kimilinear_ref")

CELL = "ppo_lift_kimilinear_16x1024"
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
# of the four steps to 6e-8, a thousandth of the change
TIGHT["learn/param_change/norms"] = dict(rtol=0.0, atol=5e-3)
SEED = 2147485011
# a matrix product keeps its input's size, as 0.02 does at 2304 wide: at
# 0.02 here every block would vanish beside the projection
INIT_STD = 0.125
# what notices each changed term first at 20 positions
CAUGHT_BY = {
    "decay": "act/value/first",
    "beta_erase": "act/value/first",
    "l2_norm": "act/value/first",
    "output_gate": "act/value/first",
    "state_bf16": "act/value/last",
    "conv_tail": "act/value/first",
    "latent_rotary": "act/value/first",
    "topk_renorm": "act/value/first",
    "second_minibatch": "learn/param_change",
    "all_bf16": "act/value/first",
}
# under the chip's limits, which are for bfloat16 products over 1024
# positions, 20 steps of a bfloat16 state do not show: that control fails at
# the cell's own size, where the chip read it (CHIP_READINGS, below)
CHIP_SIZE_ONLY = ("state_bf16",)
# what the check read on the v5e at 16 x 1024 and the published widths: the
# sound reference and the two controls of the precision below
CHIP_READINGS = json.load(open(os.path.join(
    os.path.dirname(__file__), "kimilinear_chip_controls.json"
)))


@pytest.fixture(scope="module")
def kimi(tmp_path_factory):
    """The cell's rehearsal, as ``benchmarks/run.py --rehearse`` sizes it,
    in float32."""
    import jax

    from surreal_tpu.models import kda_moe, latent_moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent_moe, "INIT_STD", INIT_STD)
        patch.setattr(kda_moe, "INIT_STD", INIT_STD)
        # the suite simulates eight devices; the cell has one chip
        one = jax.devices()[:1]
        patch.setattr(jax, "devices", lambda *a, **k: one)
        cell = runner.sized(manifest.load_cell(CELL), True)
        sys = ref.system_reports(
            manifest.load_config(cell["config"]), cell,
            str(tmp_path_factory.mktemp("kimi")), SEED, True,
            extra=("learner_config.algo.precision=f32",),
        )
    # a leaf fetched from the chip comes in the device's layout, which is
    # not always row-major: the host's passes may not count on it
    for tree in (*(sys["before"][k] for k in ("params", "mu", "nu")), sys["moved"]):
        tree.update({k: np.asfortranarray(v) for k, v in tree.items()})
    yield sys


def _compare(sys, tol, dropped=None, learn=True):
    return ref.compare(sys, ref.reference_reports(sys, dropped, learn=learn), tol)


def test_kimilinear_reference_agrees_with_the_fused_iteration(kimi):
    result = _compare(kimi, TIGHT)
    assert result["ok"], {
        k: r for k, r in result["comparisons"].items() if not r["ok"]
    }
    rows = result["comparisons"]
    assert set(rows) == (
        set(ref.TOL) | {
            "route/agree_share", "route/tie_gap", "route/score_agree",
            "moe/overflow", "act/replay_is_rollout",
            "collect/rollout_is_session", "session/repeats",
            "act/wrap_is_fresh", "learn/router_still", "learn/bias_step",
            "learn/early_stopped",
        }
    )
    assert rows["collect/rollout_is_session"]["alone"]["episode/count"] > 0
    assert rows["session/repeats"]["keys"] >= 14
    # the state the iteration started from is a session's: Adam's moments
    # hold the first iteration's four steps
    assert kimi["before"]["count"] == 4
    # episodes end inside the segment (the rehearsal's time limit of 8)
    batch = kimi["batch"]
    assert bool((batch["done"] & ~batch["terminated"]).any())
    assert 0.5 < rows["kda/decay_mean"]["scale"] < 1.0
    assert rows["kda/beta_mean"]["scale"] == pytest.approx(0.5, abs=0.1)
    # the row's largest state is over four learn steps in which Adam moves
    # the mixers, and so is the reference's: float32 against float32 agrees
    # (TIGHT), where the state at the iteration's start lies further off
    state = rows["kda/state_abs_max"]
    assert state["scale"] > 0.0
    assert state["max_abs_err"] < abs(
        kimi["metrics"]["kda/state_abs_max"] - state["reference_at_start"]
    )
    # float32 on both sides: the program's eight are the reference's
    assert rows["route/agree_share"]["value"] == 1.0
    assert rows["route/score_agree"]["value"] == 1.0
    assert rows["learn/bias_step"]["value"] == 1.0
    assert rows["learn/leaf_moved"]["leaves"] == len(kimi["moved"]) == 117
    # the four routers rest on both sides, and nothing else does
    assert len(rows["learn/router_still"]["at_rest"]) == 4
    assert rows["learn/early_stopped"]["branches"] == 1
    assert len(rows["learn/early_stopped"]["kl_steps"]) == 4
    assert rows["act/wrap_is_fresh"]["pos_after"] == 1


@pytest.mark.parametrize("dropped", ref.TERMS)
def test_kimilinear_reference_fails_without_a_term(kimi, dropped):
    result = _compare(
        kimi, TIGHT, dropped, learn=dropped == "second_minibatch"
    )
    assert not result["ok"], dropped
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], {
        k: r for k, r in result["comparisons"].items() if not r["ok"]
    }


@pytest.mark.parametrize(
    "dropped", [t for t in ref.TERMS if t not in CHIP_SIZE_ONLY]
)
def test_kimilinear_chip_tolerances_still_catch_a_changed_term(kimi, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert not _compare(
        kimi, ref.TOL, dropped, learn=dropped == "second_minibatch"
    )["ok"]


def test_a_bfloat16_state_shows_over_a_segment_of_the_published_length():
    """The precision below the configuration's: a matrix state rounded to
    bfloat16 after every step, over 1024 positions of one toy layer, moves
    the layer's output by far more than a float32 state's rounding does; 20
    positions hide it, which is why the cell's check reads the segment's last
    steps apart."""
    import jax
    import jax.numpy as jnp

    D, H, K, T = 32, 2, 8, 1024
    keys = iter(jax.random.split(jax.random.key(0), 20))
    normal = lambda *shape: INIT_STD * jax.random.normal(next(keys), shape)  # noqa: E731
    p = {
        **{n: normal(D, H, K) for n in "qkv"},
        **{f"conv_{n}": 0.5 * jnp.ones((4, H, K)) for n in "qkv"},
        "f_a": normal(D, K), "f_b": normal(K, H, K),
        "dt_bias": jnp.full((H, K), -4.0), "A_log": jnp.zeros((H,)),
        "b": normal(D, H), "g_a": normal(D, K), "g_b": normal(K, H, K),
        "o_norm": jnp.ones((K,)), "o": normal(H, K, D),
    }
    h = jax.random.normal(next(keys), (1, T, D))
    w = ref.static({"rms_norm_eps": 1e-5})
    run = jax.jit(ref.kda, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        exact = run(p, h, w, None)[0]
        rounded = run(p, h, w, "state_bf16")[0]
    err = jnp.abs(rounded - exact).max(-1)[0]
    scale = float(jnp.abs(exact).max())
    assert float(err[T // 2:].max()) > 2e-3 * scale
    assert float(err[T // 2:].max()) > 2.0 * float(err[:20].max())


def _within(name: str, row: dict) -> bool:
    """``compare``'s verdict on a row the chip read, under today's limits."""
    if name == "route/agree_share":
        return row["value"] >= ref.AGREE_SHARE_MIN
    if name == "route/tie_gap":
        return row["value"] <= ref.TIE_GAP
    if name == "route/score_agree":
        return row["value"] >= ref.SCORE_AGREE_MIN
    tol = ref.TOL[name]
    err = row.get("p999_abs_err", row["max_abs_err"])
    return err <= tol["atol"] + tol["rtol"] * row["scale"]


@pytest.mark.parametrize("control", ["state_bf16", "all_bf16"])
def test_the_precision_below_is_not_correct_at_the_cells_own_size(control):
    """The limits against what the chip read at 16 envs x 1024 positions and
    the published widths (my chip run, PR 46, seed 2147493001): the sound
    reference passes every row, each control of the precision below fails
    by the rows of the forwards (the bfloat16 state by the segment's second
    half, where up to 1024 steps of rounding are in it), and a limit a
    control fails lies between the two readings with room on both sides.
    (The learn step's rows keep the accepted cells' limits: the reference's
    notes say why.)"""
    sound, read = CHIP_READINGS["sound"], CHIP_READINGS[control]
    assert set(ref.TOL) <= set(sound)
    assert all(_within(name, row) for name, row in sound.items()), [
        name for name, row in sound.items() if not _within(name, row)
    ]
    failed = [name for name, row in read.items() if not _within(name, row)]
    assert "act/value/last" in failed and "prepare/values" in failed, failed
    assert "route/agree_share" in failed and "route/tie_gap" in failed
    for name in failed:
        key = "value" if name.startswith("route/") else (
            "p999_abs_err" if "p999_abs_err" in read[name] else "max_abs_err"
        )
        lo, hi = sorted((sound[name][key], read[name][key]))
        if name == "route/agree_share":
            assert lo + 0.02 < ref.AGREE_SHARE_MIN < hi - 0.02
            continue
        tol = ref.TIE_GAP if name == "route/tie_gap" else (
            ref.TOL[name]["atol"] + ref.TOL[name]["rtol"] * read[name]["scale"]
        )
        # the control fails by a twentieth at the least; the sound reading
        # has two fifths of room and more
        assert 1.4 * lo <= tol <= hi / 1.05, (name, lo, tol, hi)


def test_kimilinear_chip_tolerances_pass_the_program(kimi):
    assert _compare(kimi, ref.TOL)["ok"]


def test_a_router_that_moved_fails(kimi):
    """The loss stops at the router's product: a router the program moved
    is a fault, not a gain."""
    leaf = "['params']['trunk']['layer2']['moe']['router']"
    moved = copy.copy(kimi)
    moved["moved"] = dict(kimi["moved"], **{leaf: kimi["moved"][leaf] + 1e-4})
    rows = _compare(moved, ref.TOL)["comparisons"]
    assert not rows["learn/router_still"]["ok"]
    assert rows["learn/router_still"]["moved_alone"] == [leaf]


def test_a_selection_bias_left_where_it_was_fails(kimi):
    """The bias rule runs after every optimizer step: a bias the program left
    alone does not move as the rule says."""
    still = copy.copy(kimi)
    still["moved"] = dict(kimi["moved"], **{
        leaf: np.zeros_like(v) for leaf, v in kimi["moved"].items()
        if ref.BIAS_LEAF in leaf
    })
    rows = _compare(still, ref.TOL)["comparisons"]
    assert not rows["learn/bias_step"]["ok"]
    # an entry that went up twice and down twice rests on both sides
    assert rows["learn/bias_step"]["value"] <= 0.75


@pytest.mark.parametrize("leaf", [
    "['params']['trunk']['layer0']['kda']['A_log']",
    "['params']['trunk']['layer3']['attn']['q']",
    "['params']['log_std']",
])
def test_a_leaf_left_where_it_was_fails(kimi, leaf):
    still = copy.copy(kimi)
    still["moved"] = dict(
        kimi["moved"], **{leaf: np.zeros_like(kimi["moved"][leaf])}
    )
    rows = _compare(still, ref.TOL)["comparisons"]
    assert not rows["learn/leaf_moved"]["ok"]
    assert rows["learn/leaf_moved"]["unmoved_leaves"] == [leaf]


def test_a_swapped_expert_counts_against_the_agreement(kimi):
    """Routing the program chose otherwise than the reference would: the
    share falls and the gap is the swapped expert's distance in the biased
    score."""
    swapped = copy.copy(kimi)
    act = [layer.copy() for layer in kimi["routing"]["act"]]
    # every token's second expert replaced by one it did not choose
    E = int(kimi["widths"]["n_routed_experts"])
    for layer in act:
        chosen = layer[..., 0]
        layer[..., 1] = (chosen + 1 + (layer[..., 1] == (chosen + 1) % E)) % E
    swapped["routing"] = dict(kimi["routing"], act=act)
    rows = _compare(swapped, ref.TOL, learn=False)["comparisons"]
    assert not rows["route/agree_share"]["ok"]
    assert rows["route/agree_share"]["value"] < 0.7
    assert not rows["route/tie_gap"]["ok"]


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("ppo_lift_kimilinear")
    cell = manifest.load_cell(CELL)
    w = config["widths"]
    D, HK = 2304, 32 * 128
    kda_proj = 3 * D * HK + 2 * (D * 128 + 128 * HK) + D * 32 + HK * D
    kda = kda_proj + 3 * 4 * HK + HK + 32 + 128
    latent = D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + 32 * 128 * D
    dense, expert = 3 * D * 9216, 3 * D * 1024
    routed = D * 256 + 256 + 8 * expert + expert
    n = ref.parameters(w)
    assert n["by_group"] == {
        "kda": 4 * kda, "latent": latent, "dense_ffn": dense,
        "router": 4 * (D * 256 + 256), "held_experts": 32 * expert,
        "shared": 4 * expert, "norms": 10 * D,
    }
    assert (kda, latent, dense, routed) == (
        39_514_272, 29_114_880, 63_700_992, 64_291_072
    )
    assert n["layers"] == 508_060_288          # the issue's five, to the parameter
    assert n["total"] == n["layers"] + 17 * D + D + D * 5 + 5 + 4
    assert config["parameters"]["trunk"] == n["layers"]
    tok = ref.token_macs(w, 1024)
    scan = 3 * 32 * 128 * 128 + 3 * 4 * HK
    assert tok["kda_proj"] == 4 * kda_proj and tok["kda_scan"] == 4 * scan
    assert tok["attn"] == latent - 512 + 32 * (192 + 128) * 512.5
    assert tok["dense_ffn"] == dense and tok["moe_route"] == 4 * D * 256
    # 8 x 8 / 256 assignments a token a layer at even routing, and the shared
    assert tok["moe_experts"] == 4 * (0.25 * expert + expert)
    # the four KDA mixers are 55% of a token's products, their rule 2.2%: an
    # expert here sees a thirty-second of its deployment's tokens
    assert (tok["kda_proj"] + tok["kda_scan"]) / tok["forward"] == pytest.approx(
        0.547, abs=0.002
    )
    assert tok["kda_scan"] / tok["forward"] == pytest.approx(0.0216, abs=0.0005)
    assert tok["forward"] == pytest.approx(300.2e6, rel=1e-3)   # 600 MFLOP
    cost = ref.iteration_cost(config, cell["traffic"])
    assert cost["samples"] == 16384
    assert cost["flops"] == 2 * tok["forward"] * (16384 * 7 + 16 * 1025)
    assert cost["flops"] == pytest.approx(78.70e12, rel=1e-3)
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    assert cost["forward_equivalents"] == 8 and cost["routed_layers"] == 4
    assert cost["expert_flops_per_assignment"] == 2 * expert
    assert cost["shared_flops_per_token"] == 2 * expert
    passes = 16384 * 7 + 16 * 1025
    assert cost["scan_flops"] == 2 * 4 * scan * passes
    # acting: bfloat16 weights once a step; four matrix states and their
    # tails read and written; the latent cache to t + 1 and a row written
    state = 4 * 16 * (4 * 32 * 128 * 128 + 2 * 3 * 3 * HK)
    # part kda_scan runs the acting steps too, whose states go through HBM
    # every step: 284.5 GB beside 38.7 GB of inputs and outputs
    assert cost["scan_stream_bytes"] == 4 * (18 * HK + 4 * 32) * passes
    assert cost["scan_state_bytes"] == 1024 * 2 * state
    assert cost["scan_bytes"] == (
        cost["scan_stream_bytes"] + cost["scan_state_bytes"]
    )
    assert cost["scan_bytes"] == pytest.approx(323.3e9, rel=1e-3)
    # the bytes bound is the higher at these shapes: 395 ms of HBM an
    # iteration against 9 of the matrix unit
    assert cost["scan_bytes"] / 819e9 > 40 * cost["scan_flops"] / 197e12
    assert cost["collect_bytes"] == (
        1024 * (2 * n["total"] + 2 * state)
        + 16 * 2 * 576 * (1024 * 1025 // 2 + 1024)
    )
    assert cost["collect_bytes"] == pytest.approx(1.335e12, rel=1e-3)
    # the acting states are in collect_bytes already: counted once
    assert cost["bytes"] == (
        cost["collect_bytes"] + 4 * 28 * n["total"] + cost["scan_stream_bytes"]
    )


def test_config_file_carries_the_catalog_row():
    config = manifest.load_config("ppo_lift_kimilinear")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(catalog)]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
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
    # the widths the cost is counted from and the overrides that run are the
    # file's own numbers
    w, linear = config["widths"], config["linear_attn_config"]
    for key, value in w.items():
        if key in config:
            assert config[key] == value, key
    assert (w["kda_num_heads"], w["kda_head_dim"], w["short_conv_kernel_size"]) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    )
    assert w["kda_layers"] == [l for l in linear["kda_layers"] if l <= 5]
    assert w["full_attn_layers"] == [l for l in linear["full_attn_layers"] if l <= 5]
    assert w["router_outputs"] == config["published"]["num_experts"]
    assert w["num_held"] == config["num_experts"]
    assert config["q_lora_rank"] is None and config["mla_use_nope"] is True
    sets = dict(o.split("=", 1) for o in config["overrides"])
    enc = "learner_config.model.encoder."
    assert sets[enc + "block"] == "kda_moe"
    assert enc + "q_lora_rank" not in sets and enc + "rope_theta" not in sets
    for key, name in (
        ("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("kda_head_dim", "kda_head_dim"), ("num_heads", "kda_num_heads"),
        ("num_heads", "num_attention_heads"),
        ("short_conv_kernel_size", "short_conv_kernel_size"),
        ("kv_lora_rank", "kv_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
        ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
        ("n_routed_experts", "router_outputs"), ("num_held", "num_held"),
        ("num_experts_per_tok", "num_experts_per_token"),
        ("n_shared_experts", "num_shared_experts"),
        ("routed_scaling_factor", "routed_scaling_factor"),
        ("first_k_dense_replace", "first_k_dense_replace"),
        ("rms_norm_eps", "rms_norm_eps"), ("num_layers", "num_hidden_layers"),
    ):
        assert float(sets[enc + key]) == float(w[name]), key
    for key in config["reduced"] + [
        "KDA projections", "conv", "normalisation of q and k", "decay",
        "order inside a step", "output", "latent attention", "routing",
        "bias_update_speed", "shared expert", "auxiliary loss",
        "router gradient", "init", "positions", "optimizer", "recomputation",
    ]:
        assert key in config["assumed"], key


def test_a_program_without_the_family_is_refused_before_anything_launches(
    monkeypatch,
):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("kda_moe") else real(name, *a),
    )
    config = manifest.load_config("ppo_lift_kimilinear")
    with pytest.raises(manifest.ManifestError, match="kda_moe"):
        ref.iteration_cost(config, {"num_envs": 16, "horizon": 1024,
                                    "epochs": 2, "num_minibatches": 2})
