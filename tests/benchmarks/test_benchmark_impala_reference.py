"""The plain IMPALA reference against the learner at the published widths
(84 x 84 x 4, the Nature CNN at its defaults) on the CPU in float32, 4 envs
x 8 steps, off-policy as on the chip; each term of the mathematics removed
in turn to show that the comparison would catch it, under tight bounds and
under the chip's own; and the operation counts against a count by hand."""

import pytest

from benchmarks.harness import manifest

impala_ref = manifest.load_reference("impala_ref")

F32 = dict(rtol=1e-4, atol=1e-6)
TIGHT = {k: F32 for k in impala_ref.TOL}
ENVS, HORIZON = 4, 8
SEED = 11  # its 32 steps hold terminations and truncations (asserted below)
# what notices each dropped term first
CAUGHT_BY = {
    "rho_clip": "learn/loss_value",
    "c_product": "learn/loss_value",
    "done_cut": "learn/loss_value",
    "termination_mask": "learn/loss_value",
    "softmax_normaliser": "act/logp",
    "scale_255": "act/logits",
}


def _learner():
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    config = manifest.load_config("impala_pong")
    cfg = Config(
        learner_config=Config(
            algo=Config(name=config["algo"], horizon=HORIZON, precision="f32"),
            model=Config(cnn=Config(enabled=True)),
        ),
        env_config=Config(name=config["env"], num_envs=ENVS),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    env = make_env(cfg.env_config)
    learner = build_learner(cfg.learner_config, env.specs)
    widths, cnn = config["widths"], learner.config.model.cnn
    assert list(env.specs.obs.shape) == widths["input"]
    assert env.specs.action.n == widths["actions"] and cnn.dense == widths["dense"]
    for key in ("channels", "kernels", "strides"):
        assert list(cnn[key]) == widths[key]
    return learner, env


@pytest.fixture(scope="module")
def impala():
    learner, env = _learner()
    return learner, impala_ref.system_reports(learner, env, SEED, ENVS, HORIZON)


def _compare(impala, tol, dropped=None):
    learner, reports = impala
    reference, share = impala_ref.reference_reports(learner, reports, dropped)
    return impala_ref.compare(
        impala_ref.system_report(reports[2], reports[6]), reference, share, tol
    )


def test_impala_reference_agrees_with_act_and_learn(impala):
    result = _compare(impala, TIGHT)
    assert result["ok"], result
    batch = impala[1][1]
    # both masks had work to do, and the batch is off-policy
    assert bool(batch["terminated"].any())
    assert bool((batch["done"] & ~batch["terminated"]).any())
    share = result["comparisons"]["batch/rho_above_one_share"]["value"]
    assert impala_ref.RHO_SHARE[0] <= share <= impala_ref.RHO_SHARE[1]
    assert set(result["comparisons"]) == set(impala_ref.TOL) | {
        "batch/rho_above_one_share"
    }


@pytest.mark.parametrize("dropped", impala_ref.TERMS)
def test_impala_reference_fails_without_a_term(impala, dropped):
    result = _compare(impala, TIGHT, dropped)
    assert not result["ok"]
    assert not result["comparisons"][CAUGHT_BY[dropped]]["ok"], result


@pytest.mark.parametrize("dropped", impala_ref.TERMS)
def test_impala_chip_tolerances_still_catch_a_dropped_term(impala, dropped):
    """Under the looser bounds the chip run uses (bfloat16 compute)."""
    assert _compare(impala, impala_ref.TOL)["ok"]
    assert not _compare(impala, impala_ref.TOL, dropped)["ok"]


def test_an_on_policy_batch_is_not_ok(monkeypatch):
    """Learnt from the parameters that collected it, rho is 1 to rounding
    and no clip binds: everything agrees and the check still says no."""
    monkeypatch.setattr(impala_ref, "HEAD_SCALE", 1.0)
    learner, env = _learner()
    reports = impala_ref.system_reports(learner, env, SEED, ENVS, HORIZON)
    result = _compare((learner, reports), TIGHT)
    rows = result["comparisons"]
    assert not result["ok"] and not rows["batch/rho_above_one_share"]["ok"]
    assert all(r["ok"] for k, r in rows.items() if k in impala_ref.TOL), rows
    assert abs(float(reports[2]["policy/rho_mean"]) - 1.0) < 1e-5


@pytest.mark.parametrize("layer,macs,out_hw", [
    ((84, 4, 32, 8, 4), 400 * 32 * 256, 20),
    ((20, 32, 64, 4, 2), 81 * 64 * 512, 9),
    ((9, 64, 64, 3, 1), 49 * 64 * 576, 7),
])
def test_conv_macs_against_a_count_by_hand(layer, macs, out_hw):
    assert impala_ref.conv_macs(*layer) == (macs, out_hw)


def test_iteration_cost_against_a_count_by_hand():
    config = manifest.load_config("impala_pong")
    cell = manifest.load_cell("impala_pong_1k32")
    m = impala_ref.frame_macs(config["widths"])
    assert m["convs"] == [3_276_800, 2_654_208, 1_806_336]
    assert m["dense"] == 3136 * 512 and m["heads"] == 512 * 4
    assert m["forward"] == 9_345_024
    assert m["parameters"] == 1_686_180
    cost = impala_ref.iteration_cost(config, cell["traffic"])
    samples = cell["traffic"]["num_envs"] * 32
    assert cost["samples"] == samples
    # act: one forward. learn: one forward over obs, a backward of two
    # forwards less the first convolution's input gradient, and one forward
    # over the last step's 1024 successor frames, the only rows whose
    # successor value no row of the batch holds (PR 36): 3.68 forwards an
    # env step, where a second whole pass over next_obs made it 4.65
    backward = 2 * 9_345_024 - 3_276_800
    assert cost["flops_rollout"] == 2 * samples * 9_345_024
    assert cost["flops_learn"] == 2 * (
        samples * (9_345_024 + backward) + 1024 * 9_345_024
    )
    assert cost["flops"] == 2 * samples * 34_395_328
    assert cost["flops"] == cost["flops_rollout"] + cost["flops_learn"]
    # obs written and read, and the last step's successor frames; the
    # stored activations (bfloat16) written and read; eight scalars a
    # step; then the parameters' passes
    row = 2 * 84 * 84 * 4 + 4 * (12800 + 5184 + 3136 + 512) + 2 * 4 * 8
    assert cost["bytes"] == (
        samples * row + 1024 * 2 * 84 * 84 * 4 + 4 * 1_686_180 * (32 + 10)
    )
