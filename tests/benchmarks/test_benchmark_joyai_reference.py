"""The plain JoyAI-LLM-Flash reference against the learner at toy widths on
the CPU in float32 (under tight bounds and under the chip's own), 4 envs x
16 positions, the router at its published 8 of 256 with 16 held and uneven
routing as on the chip; each term of the
mathematics removed in turn to show that the comparison would catch it; the
operation and parameter counts against a count by hand at the published
widths; and the configuration file against the catalog row."""

import json
import os

import pytest

from benchmarks.harness import manifest

joyai_ref = manifest.load_reference("ppo_joyai_ref")

F32 = dict(rtol=1e-3, atol=5e-4)
TIGHT = {k: F32 for k in joyai_ref.TOL}
ENVS, HORIZON = 4, 16
SEED = 2147485011
TOY = dict(
    kind="trajectory", block="mla_moe", num_layers=5, num_heads=4,
    hidden_size=64, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    # the router at its published width and share: 8 of 256, 16 held
    moe_intermediate_size=32, n_routed_experts=256, num_experts_per_tok=8,
    num_held=16,
)
# a matrix product keeps its input's size, as 0.02 does at 2048 wide: at
# 0.02 here every block would vanish beside the projection
INIT_STD = 0.125
# 64 tokens: prepare and the loss sort (the form the cell's learn passes
# take), the 4-token acting step runs the held experts densely
DENSE_MAX_TOKENS = 16
# what notices each dropped term first
CAUGHT_BY = {
    "shared_expert": "act/value",
    "scaling_factor": "act/value",
    "rope_score": "act/value",
    "latent_norm": "act/value",
    "norm_over_all": "act/value",
    "scores_bf16": "routing/score_agree_share",
}


def _learner(precision, **algo):
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    config = manifest.load_config("ppo_lift_joyai")
    cfg = Config(
        learner_config=Config(
            algo=Config(
                name=config["algo"], horizon=HORIZON, precision=precision, **algo
            ),
            model=Config(encoder=Config(**TOY)),
        ),
        env_config=Config(name=config["env"], num_envs=ENVS),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    env = make_env(cfg.env_config)
    assert env.specs.obs.shape == (config["widths"]["obs_dim"],)
    assert env.specs.action.shape == (config["widths"]["action_dim"],)
    return build_learner(cfg.learner_config, env.specs), env


@pytest.fixture(scope="module")
def joyai():
    from surreal_tpu.models import latent_moe
    from surreal_tpu.ops import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent_moe, "INIT_STD", INIT_STD)
        patch.setattr(moe, "DENSE_MAX_TOKENS", DENSE_MAX_TOKENS)
        # 64 steps where the chip has 1024: more of them end, so that both
        # masks still act
        patch.setattr(joyai_ref, "FLIP_TERMINATED", 0.1)
        patch.setattr(joyai_ref, "FLIP_TRUNCATED", 0.1)
        learner, env = _learner("f32")
        sys = joyai_ref.system_reports(learner, env, SEED, ENVS, HORIZON)
        yield "f32", learner, sys


def _compare(joyai, tol, dropped=None, force=True):
    _, learner, sys = joyai
    return joyai_ref.compare(
        sys, joyai_ref.reference_reports(learner, sys, dropped, force), tol
    )


def test_joyai_reference_agrees_with_decode_prepare_and_loss(joyai):
    result = _compare(joyai, TIGHT)
    assert result["ok"], result
    rows = result["comparisons"]
    assert set(rows) == set(joyai_ref.TOL) | {
        "act/replay_is_rollout", "routing/agree_share", "routing/tie_gap",
        "routing/score_agree_share",
        "routing/busiest_over_mean", "moe/overflow",
        "learn/update_norm", "learn/bias_step",
    }
    # every leaf but the four biases moves as plain Adam moves it; the
    # routers, which the loss does not reach, not at all
    assert rows["learn/update_norm"]["leaves"] == len(joyai[2]["update"]["moved"]) - 4
    moved = joyai[2]["update"]["moved"]
    assert all(v == 0.0 for k, v in moved.items() if "router" in k)
    assert sum(v > 0.0 for v in moved.values()) > 40
    batch = joyai[2]["batch"]
    assert bool(batch["terminated"].any())
    assert bool((batch["done"] & ~batch["terminated"]).any())
    assert rows["learn/kl"]["scale"] > 1e-3      # off the collecting policy
    assert rows["routing/busiest_over_mean"]["value"] >= 2.0
    assert rows["routing/agree_share"]["value"] == 1.0
    # and with its own choice of experts the reference says the same
    assert _compare(joyai, TIGHT, force=False)["ok"]


@pytest.mark.parametrize("dropped", joyai_ref.TERMS)
def test_joyai_reference_fails_without_a_term(joyai, dropped):
    result = _compare(joyai, TIGHT, dropped)
    assert not result["ok"], dropped
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], result


@pytest.mark.parametrize("dropped", joyai_ref.TERMS)
def test_joyai_chip_tolerances_still_catch_a_dropped_term(joyai, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert _compare(joyai, joyai_ref.TOL)["ok"]
    assert not _compare(joyai, joyai_ref.TOL, dropped)["ok"]


@pytest.mark.parametrize("wrong,row", [
    ("rate_doubled", "learn/update_norm"),
    ("no_bias_correction", "learn/update_norm"),
    ("router_learns", "learn/update_norm"),
    ("bias_rule_reversed", "learn/bias_step"),
    ("bias_speed_tenfold", "learn/bias_step"),
])
def test_joyai_reference_fails_on_a_wrong_optimizer_step(joyai, wrong, row):
    """The step's comparisons under the chip's own limits: a plain Adam or
    a bias rule that differs from the program's in one thing fails its
    row, and only that."""
    _, learner, sys = joyai
    reference = joyai_ref.reference_reports(learner, sys)
    update = reference["update"] = dict(reference["update"])
    if wrong == "rate_doubled":
        update["moved"] = {k: 2.0 * v for k, v in update["moved"].items()}
    elif wrong == "no_bias_correction":
        # m / (sqrt(v) + eps) without the corrections: (1 - b1) / sqrt(1 - b2)
        update["moved"] = {k: 0.1 / 0.001 ** 0.5 * v for k, v in update["moved"].items()}
    elif wrong == "router_learns":
        router = next(k for k in update["moved"] if "router" in k)
        update["moved"] = dict(update["moved"], **{router: 10 * update["lr"]})
    else:
        before, _ = sys["update"]["biases"]
        speed = -0.001 if wrong == "bias_rule_reversed" else 0.01
        update["biases"] = [
            joyai_ref.bias_rule(b, load, speed)
            for b, load in zip(before, sys["load"])
        ]
    rows = joyai_ref.compare(sys, reference, joyai_ref.TOL)["comparisons"]
    assert [k for k, r in rows.items() if not r["ok"]] == [row]


def test_learn_takes_the_step_the_check_compares(joyai):
    """The check differentiates ``_loss_fn`` and calls ``_optimizer_step``
    itself, for the memory a whole ``learn`` would take at the published
    widths. Here ``learn`` runs whole, one epoch of one minibatch (its
    ``x[mb_idx]`` a permutation of all envs): every leaf moves by what the
    check's step moved it, and the losses are the check's."""
    import jax

    _, _, sys = joyai
    learner, _ = _learner("f32", epochs=1, num_minibatches=1)
    state = sys["state"]._replace(
        params=sys["learn_params"],
        opt_state=learner.tx.init(sys["learn_params"]),
    )
    new, metrics = jax.jit(learner.learn)(state, sys["batch"], jax.random.key(7))
    moved = {
        jax.tree_util.keystr(path): float(((a - b) ** 2).sum() ** 0.5)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(new.params),
            jax.tree.leaves(state.params),
        )
    }
    want = sys["update"]["moved"]
    assert moved.keys() == want.keys()
    for leaf, norm in want.items():
        assert moved[leaf] == pytest.approx(norm, rel=1e-3, abs=1e-7), leaf
    assert float(metrics["loss/pg"]) == pytest.approx(
        sys["values"]["learn/loss_pg"], rel=1e-4, abs=1e-6
    )
    assert float(metrics["health/grad_norm"]) == pytest.approx(
        sys["values"]["learn/grad_norm"], rel=1e-4
    )


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("ppo_lift_joyai")
    cell = manifest.load_cell("ppo_lift_joyai_128x128")
    w = config["widths"]
    attn = (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048
    )
    assert attn == 26_345_472
    expert, router, dense = 3 * 2048 * 768, 2048 * 256, 3 * 2048 * 7168
    n = joyai_ref.parameters(w)
    assert n["matrices"] == 5 * attn + dense + 4 * (router + 17 * expert)
    assert n["matrices"] == 498_728_960      # the issue's 498.7M
    assert n["total"] == 498_807_817         # what learner.init holds
    tok = joyai_ref.token_macs(w, 64.5)
    scores = 32 * 64.5 * (192 + 128)
    assert tok["attn"] == 5 * (attn + scores)
    assert tok["moe_experts"] == 4 * (0.5 * expert + expert)
    assert tok["forward"] == pytest.approx(209.5e6, rel=2e-3)   # 418 MFLOP
    cost = joyai_ref.iteration_cost(config, cell["traffic"])
    assert cost["samples"] == 16384
    assert cost["flops"] == 2 * tok["forward"] * (
        16384 * 7 + 128 * 129
    )
    assert cost["flops"] == pytest.approx(54.8e12, rel=5e-3)     # the issue's
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    # acting: bfloat16 weights once a step, the cache read to pos, one row
    cache = 128 * 5 * 576 * 2
    assert cost["collect_bytes"] == (
        128 * 2 * n["total"] + cache * (128 * 129 // 2) + 128 * cache
    )
    assert cost["bytes"] == cost["collect_bytes"] + 4 * 28 * n["total"]


def test_config_file_carries_the_catalog_row():
    config = manifest.load_config("ppo_lift_joyai")
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 32000000,
        "routed_scaling_factor": 2.5, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 129280, "ep_size": 1, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc",
    }
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        row = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
        assert config["source"] == row["source_url"]
        published = row["config"]
    for key, value in published.items():
        if key in config["reduced"] and key != "n_routed_experts":
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 5 and config["vocab_size"] == 0
    # the widths the cost is counted from and the overrides that run are
    # the file's own top-level numbers
    for key, value in config["widths"].items():
        if key in config:
            assert config[key] == value, key
    sets = dict(o.split("=", 1) for o in config["overrides"])
    enc = "learner_config.model.encoder."
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "n_routed_experts",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "first_k_dense_replace",
                "rope_theta", "rms_norm_eps"):
        assert float(sets[enc + key]) == float(config[key]), key
    assert int(sets[enc + "num_layers"]) == config["num_hidden_layers"]
    assert int(sets[enc + "num_heads"]) == config["num_attention_heads"]
    assert int(sets[enc + "num_held"]) == config["widths"]["num_held_experts"]


def test_the_program_defaults_are_the_published_widths():
    from surreal_tpu.models.latent_moe import FAMILY_DEFAULTS

    config = manifest.load_config("ppo_lift_joyai")
    for key, value in FAMILY_DEFAULTS.items():
        if key in config:
            assert float(config[key]) == float(value), key
