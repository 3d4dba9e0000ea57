"""The plain Qwen3-Next reference against the cell's own fused iteration at
toy widths on the CPU in float32 (under tight bounds and under the chip's
own): the rehearsal's geometry, 4 envs x 20 positions (more than a block of
the rule's chunk), 2 x 2 minibatches of two envs, the second iteration of a
session replayed; each term of the mathematics removed or changed in turn to
show that the comparison would catch it; the operation, byte and parameter
counts against a count by hand at the published widths; and the
configuration file against the catalog row, key by key."""

import copy
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, runner

ref = manifest.load_reference("ppo_qwen3next_ref")

CELL = "ppo_lift_qwen3next_16x1024"
F32 = dict(rtol=1e-3, atol=5e-4)
# the change of the parameters in float32 against float32: 3e-5 of its norm
TIGHT = {
    k: dict(rtol=0.0, atol=2e-3) if k.startswith(("learn/param", "learn/leaf"))
    else F32 for k in ref.TOL
}
# the row's share is over the learn passes, the reference's count over the
# prepare pass (the bootstrap position with it): no precision tightens it
TIGHT["moe/held_share"] = ref.TOL["moe/held_share"]
# a norm's weight starts at 0 and a step of Adam 1e-5 moves it: float32
# rounds each of the four steps to a thousandth of the change
TIGHT["learn/param_change/norms"] = dict(rtol=0.0, atol=5e-3)
SEED = 2147485011
# a matrix product keeps its input's size, as 0.02 does at 2048 wide: at
# 0.02 here every block would vanish beside the projection
INIT_STD = 0.125
# what notices each changed term at 20 positions
CAUGHT_BY = {
    "decay": "act/value/first",
    "decay_a_channel": "act/value/first",
    "beta_one": "act/value/first",
    "l2_norm": "act/value/first",
    "key_head_mod": "act/value/first",
    "output_silu": "act/value/first",
    "conv_silu": "act/value/first",
    "rotary_whole": "act/value/first",
    "attn_gate": "act/value/first",
    "one_plus_w": "act/value/first",
    "shared_gate": "act/value/first",
    "topk_renorm": "act/value/first",
    "all_bf16": "act/value/first",
}


@pytest.fixture(scope="module")
def q3n(tmp_path_factory):
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
            str(tmp_path_factory.mktemp("q3n")), SEED, True,
            extra=("learner_config.algo.precision=f32",),
        )
    # a leaf fetched from the chip comes in the device's layout, which is
    # not always row-major: the host's passes may not count on it
    for tree in (*(sys["before"][k] for k in ("params", "mu", "nu")), sys["moved"]):
        tree.update({k: np.asfortranarray(v) for k, v in tree.items()})
    yield sys


@pytest.fixture(scope="module")
def sound(q3n):
    """The sound reference's reports of the program as it ran, learn rows
    with them, made once for the two tests that hold them to two sets of
    limits."""
    return ref.reference_reports(q3n, None)


def _reports_and_change(sys, edit=None):
    """The sound reference's reports of ``sys`` and the reference's own
    change of the parameters ``{leaf: array}`` over the iteration's four
    steps; ``edit(steps)`` first changes which envs each optimizer step
    takes (a planted fault of the minibatches)."""
    phi = ref.phi_ref()
    errors, order, kept = ref.change_errors, phi.minibatch_order, []

    def spy(got, want, w):
        kept.append({k: np.copy(v) for k, v in want.items()})
        return errors(got, want, w)

    def edited(*args):
        return (edit or list)(order(*args))

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ref, "change_errors", spy)
        patch.setattr(phi, "minibatch_order", edited)
        # a reference is loaded anew at every call: hand out the edited one
        patch.setattr(ref, "phi_ref", lambda: phi)
        reports = ref.reference_reports(sys, None)
    (change,) = kept    # one branch: no step lies near the KL threshold
    return reports, change


@pytest.fixture(scope="module")
def sound_change(q3n):
    return _reports_and_change(q3n)[1]


def _compare(sys, tol, reference=None):
    """``reference`` None: made anew (its learn rows hold how far ``sys``'s
    change of the parameters lies from the reference's own)."""
    return ref.compare(sys, reference or ref.reference_reports(sys, None), tol)


def test_qwen3next_reference_agrees_with_the_fused_iteration(q3n, sound):
    result = _compare(q3n, TIGHT, sound)
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
    # hold the first iteration's four steps
    assert q3n["before"]["count"] == 4
    # episodes end inside the segment (the rehearsal's time limit of 8)
    batch = q3n["batch"]
    assert bool((batch["done"] & ~batch["terminated"]).any())
    assert 0.5 < rows["gdn/decay_mean"]["scale"] < 1.0
    for row in ("gdn/beta_mean", "attn/gate_mean", "moe/shared_gate_mean"):
        assert rows[row]["scale"] == pytest.approx(0.5, abs=0.1), row
    state = rows["gdn/state_abs_max"]
    assert state["scale"] > 0.0 and "reference_at_start" in state
    # float32 on both sides: the program's two are the reference's
    assert rows["route/agree_share"]["value"] == 1.0
    assert rows["route/score_agree"]["value"] == 1.0
    assert rows["learn/leaf_moved"]["leaves"] == len(q3n["moved"])
    # the four routers rest on both sides, and nothing else does
    assert len(rows["learn/router_still"]["at_rest"]) == 4
    assert rows["learn/early_stopped"]["branches"] == 1
    assert len(rows["learn/early_stopped"]["kl_steps"]) == 4
    assert rows["act/wrap_is_fresh"]["pos_after"] == 1
    # the learn rows are the first step's: nothing is clipped there
    first = rows["learn/early_stopped"]["steps"][0]
    assert q3n["values"]["learn/kl"] == pytest.approx(first[3], abs=1e-6)


@pytest.mark.parametrize("dropped", ref.TERMS)
def test_qwen3next_reference_fails_without_a_term(q3n, dropped):
    """Under the tight bounds and under the looser ones the chip run uses
    (bfloat16 compute)."""
    reference = ref.reference_reports(q3n, dropped, learn=False)
    result = ref.compare(q3n, reference, TIGHT)
    assert not result["ok"], dropped
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], {
        k: r for k, r in result["comparisons"].items() if not r["ok"]
    }
    assert not ref.compare(q3n, reference, ref.TOL)["ok"], dropped


def test_qwen3next_chip_tolerances_pass_the_program(q3n, sound):
    assert _compare(q3n, ref.TOL, sound)["ok"]


def test_a_router_that_moved_fails(q3n):
    """The loss stops at the router's product: a router the program moved
    is a fault, not a gain."""
    leaf = "['params']['trunk']['layer2']['moe']['router']"
    moved = copy.copy(q3n)
    moved["moved"] = dict(q3n["moved"], **{leaf: q3n["moved"][leaf] + 1e-4})
    rows = _compare(moved, ref.TOL)["comparisons"]
    assert not rows["learn/router_still"]["ok"]
    assert rows["learn/router_still"]["moved_alone"] == [leaf]


@pytest.mark.parametrize("leaf", [
    "['params']['trunk']['layer0']['gdn']['A_log']",
    "['params']['trunk']['layer3']['attn']['q']",
    "['params']['trunk']['layer1']['shared']['token_gate']",
])
def test_a_leaf_left_where_it_was_fails(q3n, leaf):
    still = copy.copy(q3n)
    still["moved"] = dict(
        q3n["moved"], **{leaf: np.zeros_like(q3n["moved"][leaf])}
    )
    rows = _compare(still, ref.TOL)["comparisons"]
    assert not rows["learn/leaf_moved"]["ok"]
    assert rows["learn/leaf_moved"]["unmoved_leaves"] == [leaf]


def test_a_state_left_unchanged_reads_one(q3n):
    """``learn/param_change`` of a program that did not train is 1, which
    the limits lie under."""
    still = copy.copy(q3n)
    still["moved"] = {k: np.zeros_like(v) for k, v in q3n["moved"].items()}
    rows = _compare(still, ref.TOL)["comparisons"]
    assert rows["learn/param_change"]["max_abs_err"] == pytest.approx(1.0)
    assert not rows["learn/param_change"]["ok"]


@pytest.mark.parametrize("step, at_least", [(0, 0.3), (3, 0.06)])
def test_half_a_minibatch_left_out_of_one_step_moves_the_change(
    q3n, sound_change, step, at_least
):
    """A fault that only ``learn/param_change`` can see: the first step's
    rows and the forwards come from the check's own programs, the fused
    iteration's wiring of its minibatches is seen through the parameters it
    moved alone. Planted: one optimizer step of the four takes half of its
    minibatch's envs. The program's change with the plant's difference added
    is then held to the sound reference's. In float32 both plants read two
    orders over the sound program (0.42 in the first step, whose moments the
    three later steps inherit; 0.09 in the last, which moves one step of
    four; 4e-4 sound). What they read at the cell's own size on the chip,
    beside a sound program's 0.11-0.19 there, and which limits each fails
    by: ``test_benchmark_qwen3next_seeds.py`` (``planted``)."""

    def halved(steps):
        steps = list(steps)
        steps[step] = steps[step][:len(steps[step]) // 2]
        return steps

    planted = _reports_and_change(q3n, halved)[1]
    faulty = {
        k: q3n["moved"][k] + (planted[k] - sound_change[k]) for k in planted
    }
    w = q3n["widths"]
    sound = ref.change_errors(q3n["moved"], sound_change, w)["groups"]
    read = ref.change_errors(faulty, sound_change, w)
    assert sound["all"] < TIGHT["learn/param_change"]["atol"]
    for group in ("all", "gdn", "attn", "shared", "experts"):
        assert read["groups"][group] > at_least, (group, read["groups"])
    # the routers rest on both sides as before: the plant is no other fault
    assert read["moved_alone"] == [] and read["unmoved_leaves"] == []


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("ppo_lift_qwen3next")
    cell = manifest.load_cell(CELL)
    w = config["widths"]
    D, conv, wide = 2048, 8192, 4096
    gdn_proj = D * (conv + wide) + D * 64 + wide * D
    gdn = gdn_proj + 4 * conv + 32 + 32 + 128
    full = D * 16 * 512 + 2 * D * 2 * 256 + 16 * 256 * D + 2 * 256
    expert = 3 * D * 512
    routed = D * 512 + 32 * expert + expert + D
    n = ref.parameters(w)
    assert n["by_group"] == {
        "gdn": 3 * gdn, "full": full, "router": 4 * D * 512,
        "held_experts": 4 * 32 * expert, "shared": 4 * (expert + D),
        "norms": 8 * D,
    }
    assert (gdn, full, routed) == (33_718_464, 27_263_488, 104_859_648)
    assert gdn + routed + 2 * D == 138_582_208
    assert full + routed + 2 * D == 132_127_232
    assert n["layers"] == 547_873_856          # the issue's four, to the parameter
    assert n["total"] == n["layers"] + 17 * D + D + D * 5 + 5 + 4
    assert config["parameters"]["trunk"] == n["layers"]
    tok = ref.token_macs(w, 1024)
    scan = 3 * 32 * 128 * 128 + 4 * conv
    assert tok["gdn_proj"] == 3 * gdn_proj and tok["gdn_scan"] == 3 * scan
    assert tok["attn"] == full - 512 + 16 * 512 * 512.5
    assert tok["moe_route"] == 4 * D * 512
    # 10 x 32 / 512 assignments a token a layer at even routing, the shared
    assert tok["moe_experts"] == 4 * (0.625 * expert + expert + D)
    # the mixers are 85% of a token's products: an expert here sees a
    # sixteenth of its deployment's tokens
    mixers = tok["gdn_proj"] + tok["gdn_scan"] + tok["attn"]
    assert mixers / tok["forward"] == pytest.approx(0.848, abs=0.003)
    assert tok["forward"] == pytest.approx(162.0e6, rel=1e-3)   # 0.32 GFLOP
    cost = ref.iteration_cost(config, cell["traffic"])
    assert cost["samples"] == 16384
    passes = 16384 * 7 + 16 * 1025
    assert cost["flops"] == 2 * tok["forward"] * passes
    assert cost["flops"] == pytest.approx(42.48e12, rel=1e-3)
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    assert cost["forward_equivalents"] == 8 and cost["routed_layers"] == 4
    assert cost["expert_flops_per_assignment"] == 2 * expert
    assert cost["scan_flops"] == 2 * 3 * scan * passes
    # acting: three conv tails read and written through HBM a step; the
    # three matrix states (100.7 MB) stay on the chip and are in no sum
    tails = 3 * 16 * 2 * 3 * conv
    matrices = 3 * 16 * 4 * 32 * 128 * 128
    stream = 2 * 4 * 16 * 128 + 2 * wide + 4 * wide + 2 * 4 * 32
    assert cost["scan_stream_bytes"] == 3 * stream * passes
    assert cost["scan_start_bytes"] == 3 * (4 * 32 * 128 * 128 // 64) * 16384 * 2 * 2
    assert matrices < ref.ON_CHIP_BYTES
    assert cost["scan_state_bytes"] == 1024 * 2 * tails
    assert cost["scan_state_on_chip_bytes"] == 1024 * 2 * matrices
    assert cost["scan_bytes"] == (
        cost["scan_stream_bytes"] + cost["scan_start_bytes"]
        + cost["scan_state_bytes"]
    )
    assert cost["scan_bytes"] == pytest.approx(27.48e9, rel=1e-3)
    # the bytes bound is the higher at these shapes
    assert cost["scan_bytes"] / 819e9 > 5 * cost["scan_flops"] / 197e12
    live = 1.0 - (1.0 - 10 / 512) ** 16
    assert cost["expected_live_share"] == pytest.approx(live) == pytest.approx(
        0.2706, abs=1e-4
    )
    held = 4 * 32 * expert
    assert cost["collect_bytes"] == pytest.approx(
        1024 * (2 * (n["total"] - held) + 2 * live * held + 2 * tails)
        + 16 * 2 * 2 * 512 * (1024 * 1025 // 2 + 1024)
    )
    # the conv tails are in collect_bytes already: counted once
    assert cost["bytes"] == pytest.approx(
        cost["collect_bytes"] + 4 * 28 * n["total"] + cost["scan_stream_bytes"]
        + cost["scan_start_bytes"]
    )


def test_config_file_carries_the_catalog_row():
    config = manifest.load_config("ppo_lift_qwen3next")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(catalog)]
    row = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 0)
    assert config["deployment"]["chips_sharing_a_routed_layer"] == 16
    # the widths the cost is counted from and the overrides that run are the
    # file's own numbers
    w = config["widths"]
    for key, value in w.items():
        if key in config:
            assert config[key] == value, key
    assert w["router_outputs"] == config["published"]["num_experts"]
    assert w["num_held"] == config["num_experts"]
    assert w["shared_expert_intermediate_size"] == (
        config["shared_expert_intermediate_size"]
    )
    assert config["mlp_only_layers"] == [] and config["norm_topk_prob"] is True
    sets = dict(o.split("=", 1) for o in config["overrides"])
    enc = "learner_config.model.encoder."
    assert sets[enc + "block"] == "gdn_moe"
    for key, name in (
        ("hidden_size", "hidden_size"),
        ("linear_num_key_heads", "linear_num_key_heads"),
        ("linear_num_value_heads", "linear_num_value_heads"),
        ("linear_head_dim", "linear_key_head_dim"),
        ("linear_head_dim", "linear_value_head_dim"),
        ("short_conv_kernel_size", "linear_conv_kernel_dim"),
        ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"), ("attn_head_dim", "head_dim"),
        ("partial_rotary_factor", "partial_rotary_factor"),
        ("rope_theta", "rope_theta"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("shared_intermediate_size", "shared_expert_intermediate_size"),
        ("n_routed_experts", "router_outputs"), ("num_held", "num_held"),
        ("first_held", "first_held"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("rms_norm_eps", "rms_norm_eps"), ("num_layers", "num_hidden_layers"),
    ):
        assert float(sets[enc + key]) == float(w[name]), key
    assert float(sets["learner_config.optimizer.lr"]) == 1e-5
    for key in config["reduced"] + [
        "intermediate_size", "in_proj_qkvz columns", "conv",
        "normalisation of q and k", "decay", "order inside a step", "output",
        "norms", "full attention", "routing", "shared expert",
        "auxiliary loss", "router gradient", "init", "positions", "optimizer",
        "precision", "recomputation",
    ]:
        assert key in config["assumed"], key


def test_a_program_without_the_family_is_refused_before_anything_launches(
    monkeypatch,
):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("gdn_moe") else real(name, *a),
    )
    config = manifest.load_config("ppo_lift_qwen3next")
    with pytest.raises(manifest.ManifestError, match="gdn_moe"):
        ref.iteration_cost(config, {"num_envs": 16, "horizon": 1024,
                                    "epochs": 2, "num_minibatches": 2})
