"""The plain Phi-4-mini-flash-reasoning reference against the cell's own
fused iteration at toy widths on the CPU in float32 (under tight bounds and
under the chip's own): the rehearsal's geometry, 4 envs x 12 positions
with a window of 4, so that the ring forgets, 2 x 2 minibatches of 2 envs,
the second iteration of a session replayed; each term of the mathematics
removed in turn, and a minibatch of each epoch left out, to show that the
comparison would catch it; the reference's Adam against optax's; the
operation, byte and parameter counts against a count by hand at the
published widths; and the configuration file against the catalog row, key
by key."""

import copy
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest, runner

phi_ref = manifest.load_reference("ppo_phi4flash_ref")

CELL = "ppo_lift_phi4flash_16x1024"
F32 = dict(rtol=1e-3, atol=5e-4)
# the change of the parameters in float32 against float32: 3e-5 of its norm
TIGHT = {
    k: dict(rtol=0.0, atol=2e-3) if k.startswith(("learn/param", "learn/leaf"))
    else F32 for k in phi_ref.TOL
}
SEED = 2147485011
# a matrix product keeps its input's size, as 0.02 does at 2560 wide: at
# 0.02 here every block would vanish beside the projection
INIT_STD = 0.125
# what notices each dropped term first (12 positions: a state held in
# bfloat16 has a thousandth of its size to lose, where 1024 lose a tenth)
CAUGHT_BY = {
    "window_mask": "act/value/over",
    "conv": "act/value/under",
    "d_skip": "act/value/under",
    "gate": "act/value/under",
    "dt_bias": "act/ssm_state/layer0",
    "memory_after_gate": "act/value/under",
    "cross_own_keys": "act/value/under",
    "state_bf16": "act/ssm_state/layer0",
    "decay_bf16": "act/ssm_state/layer0",
    "second_minibatch": "learn/param_change",
}
# too small to see at 12 positions under the chip's limits, which are for
# 1024: the chip's own readings are in the reference's notes
HORIZON_BOUND = ("state_bf16", "decay_bf16")


@pytest.fixture(scope="module")
def phi(tmp_path_factory):
    """The cell's rehearsal, as ``benchmarks/run.py --rehearse`` sizes it,
    in float32."""
    import jax

    from surreal_tpu.models import ssm_hybrid

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssm_hybrid, "INIT_STD", INIT_STD)
        # the suite simulates eight devices; the cell has one chip
        one = jax.devices()[:1]
        patch.setattr(jax, "devices", lambda *a, **k: one)
        cell = runner.sized(manifest.load_cell(CELL), True)
        sys = phi_ref.system_reports(
            manifest.load_config(cell["config"]), cell,
            str(tmp_path_factory.mktemp("phi")), SEED, True,
            extra=("learner_config.algo.precision=f32",),
        )
    # a leaf fetched from the chip comes in the device's layout, which is
    # not always row-major: the host's passes may not count on it
    for tree in (*(sys["before"][k] for k in ("params", "mu", "nu")), sys["moved"]):
        tree.update({k: np.asfortranarray(v) for k, v in tree.items()})
    yield sys


def _compare(sys, tol, dropped=None):
    return phi_ref.compare(sys, phi_ref.reference_reports(sys, dropped), tol)


def test_phi_reference_agrees_with_the_fused_iteration(phi):
    result = _compare(phi, TIGHT)
    assert result["ok"], result
    rows = result["comparisons"]
    assert set(rows) == (set(phi_ref.TOL) - {"act/ssm_state"}) | {
        "act/ssm_state/layer0", "act/ssm_state/layer2",
        "act/replay_is_rollout", "collect/rollout_is_session",
        "session/repeats", "act/wrap_is_fresh", "learn/early_stopped",
        "ssm/state_abs_max",
    }
    assert rows["collect/rollout_is_session"]["alone"]["episode/count"] > 0
    assert rows["session/repeats"]["keys"] >= 12
    # the state the iteration started from is a session's: Adam's moments
    # hold the first iteration's four steps
    assert phi["before"]["count"] == 4
    # episodes end inside the segment (the rehearsal's time limit of 8)
    batch = phi["batch"]
    assert bool((batch["done"] & ~batch["terminated"]).any())
    assert rows["attn/window_keys_mean"]["scale"] == pytest.approx(
        (1 + 2 + 3 + 4 * 9) / 12
    )
    assert rows["learn/leaf_moved"]["leaves"] == len(phi["moved"]) == 80
    assert rows["learn/early_stopped"]["branches"] == 1
    assert len(rows["learn/early_stopped"]["kl_steps"]) == 4
    assert rows["act/wrap_is_fresh"]["pos_after"] == 1


@pytest.mark.parametrize("dropped", phi_ref.TERMS)
def test_phi_reference_fails_without_a_term(phi, dropped):
    result = _compare(phi, TIGHT, dropped)
    assert not result["ok"], dropped
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], result


@pytest.mark.parametrize(
    "dropped", [t for t in phi_ref.TERMS if t not in HORIZON_BOUND]
)
def test_phi_chip_tolerances_still_catch_a_dropped_term(phi, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert not _compare(phi, phi_ref.TOL, dropped)["ok"]


def test_phi_chip_tolerances_pass_the_program(phi):
    assert _compare(phi, phi_ref.TOL)["ok"]


def test_a_carry_that_is_not_reset_fails_the_wrap(phi):
    stale = copy.copy(phi)
    after, fresh, pos = phi["wrap"]["recurrent"]
    bumped = [dict(layer, state=layer["state"] + 1e-3) for layer in after]
    stale["wrap"] = dict(phi["wrap"], recurrent=(bumped, fresh, pos))
    rows = phi_ref.compare(stale, phi_ref.reference_reports(phi, learn=False))
    assert [k for k, r in rows["comparisons"].items() if not r["ok"]] == [
        "act/wrap_is_fresh"
    ]


@pytest.mark.parametrize("leaf", [
    "['params']['trunk']['layer2']['mixer']['A_log']",
    "['params']['trunk']['layer5']['ffn_norm']['scale']",
    "['params']['log_std']",
])
def test_a_leaf_left_where_it_was_fails(phi, leaf):
    """Under the chip's own limits: a leaf the optimizer did not move reads
    1 of its norm, a small one is named among the unmoved."""
    still = copy.copy(phi)
    still["moved"] = dict(phi["moved"], **{leaf: np.zeros_like(phi["moved"][leaf])})
    rows = _compare(still, phi_ref.TOL)["comparisons"]
    assert not rows["learn/leaf_moved"]["ok"]
    assert rows["learn/leaf_moved"]["unmoved_leaves"] == [leaf]


def test_a_stop_within_the_band_is_followed_both_ways(phi, monkeypatch):
    """A minibatch's KL on the threshold, and a program that says it
    stopped: the reference trains on with and without the policy's terms,
    and the nearer result is the one compared (here the program did not
    stop, so that is the branch that went on)."""
    kl = _compare(phi, TIGHT)["comparisons"]["learn/early_stopped"]["kl_steps"]
    # the toy's four KLs lie 1.5e-5 and more apart: a band that holds one
    monkeypatch.setattr(phi_ref, "KL_BAND", 2e-6)
    edge = copy.copy(phi)
    edge["algo"] = dict(phi["algo"], kl_target=kl[2], kl_early_stop=1.0)
    edge["metrics"] = dict(phi["metrics"], **{"policy/early_stopped": 1.0})
    rows = _compare(edge, TIGHT)["comparisons"]
    assert rows["learn/early_stopped"]["branches"] == 2
    assert rows["learn/param_change"]["ok"]
    # without the program's word for it, the reference follows its own KL
    alone = copy.copy(edge)
    alone["algo"] = dict(edge["algo"], kl_target=kl[2] - 2 * phi_ref.KL_BAND)
    rows = _compare(alone, TIGHT)["comparisons"]
    assert rows["learn/early_stopped"]["branches"] == 1
    assert not rows["learn/param_change"]["ok"]


@pytest.mark.parametrize("layout", ["row_major", "as_the_chip_hands_it"])
def test_the_reference_adam_is_optax_adam(layout):
    """Also over leaves that are not row-major, as a leaf fetched from the
    chip can be (the step is taken in place, a block of rows at a time: on a
    reshaped copy it would be lost), and over a leaf of more than one block."""
    import jax
    import jax.numpy as jnp
    import optax

    rng = np.random.default_rng(0)
    order = "C" if layout == "row_major" else "F"
    lay = lambda v: np.array(v, order=order)  # noqa: E731  (a copy, always)
    shapes = {"a": (7, 5), "b": (3,), "c": (600, 25, 20)}
    assert len(phi_ref.blocks(np.empty(shapes["c"]))) > 1
    params = {k: rng.normal(size=s).astype(np.float32) for k, s in shapes.items()}
    tx = optax.chain(optax.clip_by_global_norm(0.5), optax.adam(3e-4))
    opt, want = tx.init(params), dict(params)
    work = {
        "params": {k: lay(v) for k, v in params.items()},
        "mu": {k: lay(np.zeros_like(v)) for k, v in params.items()},
        "nu": {k: lay(np.zeros_like(v)) for k, v in params.items()},
        "delta": {k: lay(np.zeros_like(v)) for k, v in params.items()},
        "count": 0,
    }
    for step in range(5):
        # the third under the clip's norm, the others over it
        scale = 0.01 if step == 2 else 3.0
        grads = {
            k: (scale * rng.normal(size=s)).astype(np.float32)
            for k, s in shapes.items()
        }
        updates, opt = tx.update(jax.tree.map(jnp.asarray, grads), opt, want)
        want = optax.apply_updates(want, updates)
        norm = phi_ref.adam_step(
            work, {k: lay(g) for k, g in grads.items()}, 3e-4, 0.5
        )
        assert norm == pytest.approx(float(optax.global_norm(grads)), rel=1e-6)
    for k in shapes:
        # five steps of 3e-4 each; a parameter of 4 rounds by 4.8e-7
        np.testing.assert_allclose(work["params"][k], want[k], rtol=0, atol=1e-6)
        np.testing.assert_allclose(
            work["delta"][k], work["params"][k] - params[k], rtol=0, atol=1e-6
        )
        assert np.abs(work["delta"][k]).min() > 0.0
        moved = want[k] - params[k]
        assert phi_ref.sq_sum(lay(moved), work["delta"][k]) == pytest.approx(
            float(np.square(moved - work["delta"][k], dtype=np.float64).sum())
        )


def test_every_env_is_in_one_minibatch_of_every_epoch():
    import jax

    steps = phi_ref.minibatch_order(jax.random.key(3), 16, 2, 2)
    assert [len(s) for s in steps] == [8, 8, 8, 8]
    assert sorted(steps[0] + steps[1]) == sorted(steps[2] + steps[3]) == list(range(16))
    assert steps[:2] != steps[2:]


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("ppo_lift_phi4flash")
    cell = manifest.load_cell("ppo_lift_phi4flash_16x1024")
    w = config["widths"]
    swiglu, norms = 3 * 2560 * 10240, 4 * 2560
    ssm = (
        2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560
    )
    attn, gmu, cross = 3 * 2560 * 2560, 2 * 2560 * 5120, 2 * 2560 * 2560
    n = phi_ref.parameters(w)
    rest = swiglu + norms
    assert n["by_kind"] == {
        "ssm": 2 * (ssm + rest), "window": attn + rest, "full": attn + rest,
        "gmu": gmu + rest, "cross": cross + rest,
    }
    assert n["layers"] == 633_047_040          # the issue's six, to the parameter
    assert n["total"] == n["layers"] + 17 * 2560 + 2 * 2560 + 2560 * 5 + 5 + 4
    tok = phi_ref.token_macs(w, 1024)
    assert tok["ssm_proj"] == 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert tok["dense_ffn"] == 6 * swiglu
    assert tok["gmu"] == gmu
    assert tok["attn"] == (
        attn + 2 * 2560 * 384.25 + attn + 2 * 2560 * 512.5
        + cross + 2 * 2560 * 512.5
    )
    # SwiGLU 74% of a token's products, the mixers the rest
    assert tok["dense_ffn"] / tok["forward"] == pytest.approx(0.737, abs=0.002)
    assert tok["forward"] == pytest.approx(640.0e6, rel=1e-3)   # 1280 MFLOP
    cost = phi_ref.iteration_cost(config, cell["traffic"])
    assert cost["samples"] == 16384
    assert cost["flops"] == 2 * tok["forward"] * (16384 * 7 + 16 * 1025)
    assert cost["flops"] == pytest.approx(167.8e12, rel=1e-3)   # the issue's 167
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    # acting: bfloat16 weights once a step; the ring to min(t + 1, 512) and
    # the shared cache, read by two layers, to t + 1; a row written in each
    # of two caches; two layers' states and conv tails read and written
    row = 2 * 2 * 20 * 64
    state = 2 * 16 * (4 * 16 * 5120 + 2 * 3 * 5120)
    assert cost["collect_bytes"] == (
        1024 * (2 * n["total"] + 2 * state)
        + 16 * row * (int(384.25 * 1024) + 2 * (1024 * 1025 // 2))
        + 1024 * 16 * row * 2
    )
    assert cost["collect_bytes"] == pytest.approx(1.438e12, rel=1e-3)
    # the scan: u' and B, C in bfloat16, delta and y in float32, a token a layer
    per_token = 2 * 5120 + 4 * 5120 + 4 * 16 + 4 * 5120
    assert cost["scan_bytes"] == 2 * per_token * (16 * 1025 + 16384 * 6)
    assert cost["scan_bytes"] == pytest.approx(11.8e9, rel=1e-2)
    assert cost["bytes"] == (
        cost["collect_bytes"] + cost["scan_bytes"] + 4 * 28 * n["total"]
    )


def test_config_file_carries_the_catalog_row():
    config = manifest.load_config("ppo_lift_phi4flash")
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064,
    }
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        row = next(r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning")
        assert config["source"] == row["source_url"]
        published = row["config"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 6 and config["vocab_size"] == 0
    # the widths the cost is counted from and the overrides that run are
    # the file's own top-level numbers
    w = config["widths"]
    for key, value in w.items():
        if key in config:
            assert config[key] == value, key
    assert (w["hidden_size"], w["num_attention_heads"], w["num_key_value_heads"],
            w["head_dim"], w["intermediate_size"], w["sliding_window"]) == (
        2560, 40, 20, 64, 10240, 512)
    assert (w["ssm_state_size"], w["ssm_conv_kernel"], w["ssm_expand"],
            w["ssm_dt_rank"]) == (16, 4, 2, 160)
    assert w["num_hidden_layers"] == 2 * (w["pairs_before"] + w["pairs_after"]) + 2
    sets = dict(o.split("=", 1) for o in config["overrides"])
    enc = "learner_config.model.encoder."
    for key in ("hidden_size", "intermediate_size", "sliding_window",
                "ssm_state_size", "ssm_dt_rank", "pairs_before", "pairs_after"):
        assert float(sets[enc + key]) == float(w[key]), key
    # one value in use: constants of the program, not keys
    from surreal_tpu.models import ssm_hybrid

    assert (w["ssm_conv_kernel"], w["ssm_expand"], w["layer_norm_eps"]) == (
        ssm_hybrid.CONV_TAPS, ssm_hybrid.EXPAND, ssm_hybrid.NORM_EPS)
    assert int(sets[enc + "num_heads"]) == config["num_attention_heads"]
    assert int(sets[enc + "num_kv_heads"]) == config["num_key_value_heads"]
    for key in config["reduced"] + ["state-space sizes", "window edge",
                                    "attention", "init", "recomputation"]:
        assert key in config["assumed"], key


def test_the_program_defaults_are_the_published_widths():
    from surreal_tpu.models.ssm_hybrid import FAMILY_DEFAULTS, resolve

    config = manifest.load_config("ppo_lift_phi4flash")
    for key, value in FAMILY_DEFAULTS.items():
        if key in config and value is not None:
            assert float(config[key]) == float(value), key
    resolved = resolve({"num_heads": 40})
    assert resolved["ssm_dt_rank"] == 160 == config["widths"]["ssm_dt_rank"]
    assert resolved["num_kv_heads"] == config["num_key_value_heads"]
    # the published depth: 8 pairs, the middle pair, 7 pairs
    assert 2 * (resolved["pairs_before"] + resolved["pairs_after"]) + 2 == 32


def test_a_program_without_the_family_is_refused_before_anything_launches(
    monkeypatch,
):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("ssm_hybrid") else real(name, *a),
    )
    config = manifest.load_config("ppo_lift_phi4flash")
    with pytest.raises(manifest.ManifestError, match="ssm_hybrid"):
        phi_ref.iteration_cost(config, {"num_envs": 16, "horizon": 1024,
                                        "epochs": 2, "num_minibatches": 2})
