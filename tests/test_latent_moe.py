"""The second block family of the trajectory seam (``model.encoder.block=
'mla_moe'``: models/latent_moe.py, ops/moe.py) at toy widths on the CPU:
the absorbed latent-cache decode against the expanded forward, the held
experts' share of a routed layer, the ragged product's bound, the
selection-bias rule, the acting carry, and the parts vocabulary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import latent_moe
from surreal_tpu.ops import moe
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config

TOY = dict(
    kind="trajectory", block="mla_moe", num_layers=3, num_heads=2,
    hidden_size=32, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
    moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
    num_held=2,
)
SPECS = EnvSpecs(
    obs=ArraySpec(shape=(5,), dtype=np.dtype(np.float32)),
    action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
)


def _learner(horizon=8, precision="f32", **encoder):
    cfg = Config(
        algo=Config(
            name="ppo", horizon=horizon, epochs=2, num_minibatches=2,
            precision=precision,
        ),
        model=Config(encoder=Config(**{**TOY, **encoder})),
    )
    return build_learner(cfg, SPECS)


@pytest.mark.parametrize("precision,tol", [("f32", 2e-5), ("mixed", 6e-2)])
def test_latent_cache_decode_equals_the_full_forward(precision, tol):
    """Absorbed against expanded, at every position: what ``act_step``
    produced through the ``[envs, T, kv_lora + rope]`` cache is what one
    whole-segment apply recomputes (the importance-ratio contract)."""
    T, B = 8, 4
    learner = _learner(T, precision)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (T, B, 5), jnp.float32)
    carry = learner.act_init(B)
    means, values = [], []
    for t in range(T):
        _, info, carry = learner.act_step(
            state, carry, obs[t], jax.random.key(t), "eval_deterministic"
        )
        means.append(info["mean"])
        values.append(info["value"])
    out = learner.model.apply(
        state.params, learner._norm_obs(state.obs_stats, obs).swapaxes(0, 1)
    )
    np.testing.assert_allclose(
        jnp.stack(means, 1), out.mean, rtol=0, atol=tol
    )
    np.testing.assert_allclose(
        jnp.stack(values, 1), out.value, rtol=0, atol=tol
    )
    assert float(jnp.abs(out.value).max()) > 0.1  # not a comparison of zeros


@pytest.mark.parametrize("block", ["preln", "mla_moe"])
def test_act_init_takes_its_carry_from_the_model(block):
    T, B = 8, 3
    if block == "mla_moe":
        learner = _learner(T, "mixed")
        # the latent rows, nothing per head; the routed layers' tally
        want = [(2,)] + [(B, T, 16 + 4)] * 3
    else:
        learner = build_learner(
            Config(
                algo=Config(name="ppo", horizon=T),
                model=Config(encoder=Config(
                    kind="trajectory", features=32, num_layers=2,
                    num_heads=2, head_dim=8,
                )),
            ), SPECS,
        )
        want = [(B, T, 2, 8)] * 4     # k and v of each layer
    carry = learner.act_init(B)
    cache = learner.model.init_cache(B, T)
    got = [x.shape for x in jax.tree.leaves(carry["cache"])]
    assert got == want == [x.shape for x in jax.tree.leaves(cache)]
    assert all(
        x.dtype == jnp.bfloat16
        for x in jax.tree.leaves(carry["cache"]) if x.shape != (2,)
    )
    assert int(carry["pos"]) == 0


@pytest.mark.parametrize("encoder,match", [
    (dict(kind="trajectory", hidden_size=32), "'preln' does not read"),
    (dict(kind="trajectory", block="preln", num_held=2), "'preln' does not read"),
    (dict(TOY, features=128), "'mla_moe' does not read"),
    (dict(TOY, first_held=7, num_held=2), "lie outside"),
    (dict(kind="trajectory", block="other"), "not in preln"),
])
def test_a_key_of_the_other_family_is_an_error(encoder, match):
    cfg = Config(
        algo=Config(name="ppo", horizon=8), model=Config(encoder=Config(**encoder))
    )
    with pytest.raises(ValueError, match=match):
        build_learner(cfg, SPECS)


def test_impala_refuses_the_expert_blocks():
    cfg = Config(
        algo=Config(name="impala", horizon=8),
        model=Config(encoder=Config(**TOY)),
    )
    with pytest.raises(ValueError, match="wired into PPO alone"):
        build_learner(cfg, SPECS)


# -- the routed layer -----------------------------------------------------------

SHARE_CFG = latent_moe.resolve(dict(
    TOY, n_routed_experts=32, num_experts_per_tok=8, num_held=32,
))


def _routed(cfg, params, x):
    layer = latent_moe.RoutedExperts(cfg, jnp.float32)
    (y, read), sown = layer.apply(
        {"params": params}, x, mutable=["moe", "moe_routing"]
    )
    assert float(read) == 1.0       # off the TPU every held expert is read
    return y, sown["moe"]


# both forms of the held experts' product (ops/moe.py): these passes' few
# tokens take the dense one; with the threshold at 0 every pass sorts
@pytest.fixture(params=["sorted", "dense"])
def form(request, monkeypatch):
    if request.param == "sorted":
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
    return request.param


def test_four_shares_and_the_shared_expert_once_equal_the_uncut_layer(form):
    """32 experts in 4 shares of 8: each share routes over all 32 and
    computes its own experts' part; the parts and the shared expert add up
    to the layer that holds all 32."""
    x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
    whole = latent_moe.RoutedExperts(SHARE_CFG, jnp.float32).init(
        jax.random.key(1), x
    )["params"]
    whole = dict(whole, router=5.0 * whole["router"])   # decisive scores
    uncut, stats = _routed(SHARE_CFG, whole, x)
    assert float(stats["load"][-1].sum()) == 64 * 8
    total = moe.swiglu(x, *(whole["shared0"][k] for k in ("gate", "up", "down")))
    for share in range(4):
        cfg = dict(SHARE_CFG, first_held=8 * share, num_held=8, n_shared_experts=0)
        mine = {
            k: v[8 * share:8 * share + 8] if k in ("gate", "up", "down") else v
            for k, v in whole.items() if k != "shared0"
        }
        part, stats = _routed(cfg, mine, x)
        assert float(stats["overflow"][-1]) == 0.0
        total = total + part
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-6)


def test_no_token_is_dropped_when_all_route_to_one_held_expert(form):
    """Every token's first choice is held expert 3 (a router column that
    always wins); the other seven choices scatter. Nothing overflows, and
    the output is the plain sum over each token's held choices."""
    cfg = dict(SHARE_CFG, first_held=0, num_held=8, n_shared_experts=0)
    x = jnp.abs(jax.random.normal(jax.random.key(0), (96, 32), jnp.float32))
    params = latent_moe.RoutedExperts(cfg, jnp.float32).init(
        jax.random.key(1), x
    )["params"]
    params = dict(params, router=params["router"].at[:, 3].set(1.0))
    y, stats = _routed(cfg, params, x)
    assert float(stats["load"][-1][3]) == 96 and float(stats["overflow"][-1]) == 0
    idx, weights, _ = moe.route(
        x @ params["router"], params["e_score_correction_bias"], 8, 2.5
    )
    want = jnp.zeros_like(x)
    for e in range(8):
        w_e = (weights * (idx == e)).sum(-1)
        want = want + w_e[:, None] * moe.swiglu(
            x, params["gate"][e], params["up"][e], params["down"][e]
        )
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_a_bound_under_the_worst_case_counts_what_it_drops():
    idx = jnp.zeros((64, 2), jnp.int32).at[:, 1].set(1)   # all on experts 0, 1
    weights = jnp.ones((64, 2), jnp.float32)
    token, weight, valid, sizes, overflow = moe.sort_by_expert(
        idx, weights, 0, 2, rows=100
    )
    assert int(overflow) == 28 and sizes.tolist() == [64, 36]
    assert int(valid.sum()) == 100 and float(weight.sum()) == 100.0
    # under the bound, the last group takes the slack: the groups cover
    # every row, so the kernel's work is the bound whatever the load
    some = idx[:20].at[:, 1].set(5)      # expert 5 is not held
    *_, valid, sizes, overflow = moe.sort_by_expert(some, weights[:20], 0, 2, 32)
    assert int(overflow) == 0 and sizes.tolist() == [20, 12]
    assert int(valid.sum()) == 20
    # a learn pass's bound is the capacity factor over the even
    # expectation, and never more than the worst case
    assert moe.row_bound(8192, 8, 16, 256) == 16384   # the cell's minibatch
    assert moe.row_bound(16512, 8, 16, 256) == 33024  # its value pass
    assert moe.row_bound(64, 2, 2, 8) == 128 == 64 * 2
    assert moe.row_bound(64, 8, 2, 8) == 128 == 64 * 2  # min(top_k, held)


@pytest.mark.parametrize("tokens,dense", [
    (128, True),      # the cell's acting step: 128 envs
    (256, True),      # still under the chip's ridge point
    (1024, False),    # the reference check's passes: 8 envs x 128
    (8192, False),    # the cell's minibatch
])
def test_the_form_follows_the_shape_of_the_pass(tokens, dense, monkeypatch):
    """No key chooses between the dense and the sorted product: the
    number of tokens of the pass does, and a routed layer built at either
    size sows an overflow only the sorted form can raise."""
    assert moe.dense_form(tokens) is dense
    seen = []
    monkeypatch.setattr(
        moe, "sort_by_expert",
        lambda *a, **k: seen.append(a[-1]) or moe_sort(*a, **k),
    )
    cfg = dict(SHARE_CFG, first_held=0, num_held=8, n_shared_experts=0)
    x = jax.ShapeDtypeStruct((tokens, 32), jnp.float32)
    layer = latent_moe.RoutedExperts(cfg, jnp.float32)
    jax.eval_shape(lambda x: layer.init(jax.random.key(0), x), x)
    assert seen == ([] if dense else [moe.row_bound(tokens, 8, 8, 32)])


moe_sort = moe.sort_by_expert


# -- the live experts' kernel (ops/moe.py), interpreted -------------------------

LIVE_FIRST, LIVE_HELD, LIVE_ROUTED, LIVE_TOP = 8, 8, 64, 4


def _live_case(name):
    """``(x [16, 256] bfloat16, idx [16, 4], weights, gate, up, down)`` of 8
    held experts (8..15 of 64) of width 128, routed as ``name`` says."""
    k = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(k[0], (16, 256), jnp.bfloat16)
    gate, up = (
        0.05 * jax.random.normal(k[i], (LIVE_HELD, 256, 128)) for i in (1, 2)
    )
    down = 0.05 * jax.random.normal(k[3], (LIVE_HELD, 128, 256))
    # every token chooses experts 40..43: none of them held
    idx = jnp.broadcast_to(40 + jnp.arange(LIVE_TOP, dtype=jnp.int32), (16, LIVE_TOP))
    if name == "one":
        idx = idx.at[3, 0].set(LIVE_FIRST + 5)
    elif name == "two_of_one_token":
        idx = idx.at[3, 0].set(LIVE_FIRST + 6).at[3, 2].set(LIVE_FIRST + 1)
        idx = idx.at[9, 1].set(LIVE_FIRST + 6)
    elif name == "all":
        idx = LIVE_FIRST + (
            jnp.arange(16, dtype=jnp.int32)[:, None] + jnp.arange(LIVE_TOP)
        ) % LIVE_HELD
    weights = jax.nn.softmax(jax.random.normal(k[4], idx.shape), -1)
    return x, idx, weights, gate, up, down


@pytest.mark.parametrize("name,ids,served", [
    ("none", [], []),
    ("one", [5], [3]),
    ("two_of_one_token", [1, 6], [3, 9]),
    ("all", list(range(8)), list(range(16))),
])
def test_the_kernel_reads_the_live_experts_and_equals_the_dense_form(
    name, ids, served
):
    """The acting kernel, interpreted, against the lax form it stands in
    for: the live experts' ids in order at the front of the list and their
    count; the same sum to the rounding of bfloat16 (one step of it at the
    largest output); and exact zeros for a token that chose no held
    expert."""
    x, idx, weights, gate, up, down = _live_case(name)
    hit = idx[..., None] == LIVE_FIRST + jnp.arange(LIVE_HELD)
    w = (weights[..., None] * hit).sum(1)
    got_ids, count = moe.live_experts(hit)
    assert int(count) == len(ids)
    assert got_ids.tolist() == ids + [0] * (LIVE_HELD - len(ids))
    want = moe._dense_lax(x, w, gate, up, down).astype(jnp.float32)
    got = moe._live_pallas(
        x, w, got_ids, count, gate, up, down, interpret=True
    )
    assert got.dtype == x.dtype and got.shape == x.shape
    got = got.astype(jnp.float32)
    step = 2.0 ** -7 * max(float(jnp.abs(want).max()), 1e-3)
    np.testing.assert_allclose(got, want, rtol=0, atol=step)
    idle = np.setdiff1d(np.arange(16), served)
    assert not np.asarray(got)[idle].any() and not np.asarray(want)[idle].any()
    if served:
        assert np.abs(np.asarray(got)[served]).min(0).max() > 0


@pytest.mark.parametrize("name", ["none", "two_of_one_token", "all"])
def test_the_dispatching_form_differentiates_as_the_dense_form(name, monkeypatch):
    """``held_experts_dense`` at a shape the kernel takes: off the TPU its
    value is the lax form's to the bit and it says it read every expert;
    its gradient (inputs, weights of the routing, the experts' weights) is
    the lax form's, whatever computes the value."""
    x, idx, weights, gate, up, down = _live_case(name)
    assert moe.streams_live_only(16, LIVE_TOP, LIVE_ROUTED, 256, 128)

    def dispatched(x, weights, gate, up, down):
        y, read = moe.held_experts_dense(
            x, idx, weights, LIVE_FIRST, LIVE_ROUTED, gate, up, down
        )
        assert float(read) == 1.0
        return y

    def lax_form(x, weights, gate, up, down):
        hit = idx[..., None] == LIVE_FIRST + jnp.arange(LIVE_HELD)
        return moe._dense_lax(x, (weights[..., None] * hit).sum(1), gate, up, down)

    args = (x, weights, gate, up, down)
    np.testing.assert_array_equal(dispatched(*args), lax_form(*args))
    loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) ** 2).sum()  # noqa: E731
    got = jax.grad(loss(dispatched), argnums=range(5))(*args)
    want = jax.grad(loss(lax_form), argnums=range(5))(*args)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    # the value may come from the kernel, the cotangent takes the lax form
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *a, tpu, default: (
            tpu(*a, interpret=True) if tpu is moe._live_pallas else default(*a)
        ),
    )
    through_kernel = jax.grad(loss(dispatched), argnums=range(5))(*args)
    for g, w in zip(through_kernel, want):
        scale = max(float(jnp.abs(w.astype(jnp.float32)).max()), 1e-6)
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32), rtol=0, atol=0.05 * scale
        )


@pytest.mark.parametrize("tokens,top_k,routed,D,F,live,kernel", [
    (16, 8, 256, 2304, 1024, 0.39, True),     # ppo_lift_kimilinear's step
    (16, 10, 256, 3072, 1024, 0.47, True),    # ppo_lift_laguna's
    (128, 8, 256, 2048, 768, 0.98, False),    # ppo_lift_joyai's: none to skip
    (16, 2, 8, 32, 16, 0.99, False),          # a rehearsal's widths
    (16, 8, 256, 2304, 1000, 0.39, False),    # a width the tiles do not divide
])
def test_the_acting_form_follows_the_shape_of_the_pass(
    tokens, top_k, routed, D, F, live, kernel
):
    """No key chooses the acting form: the share of held experts a pass of
    this shape expects to be live, and whether the lanes divide the widths."""
    assert abs(moe.expected_live_share(tokens, top_k, routed) - live) < 0.01
    assert moe.streams_live_only(tokens, top_k, routed, D, F) is kernel


_WIDE = dict(
    hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
    n_routed_experts=8, num_experts_per_tok=2, num_held=2,
)
TALLY_TOYS = {
    "preln": dict(kind="trajectory", features=32, num_layers=2, num_heads=2,
                  head_dim=8),
    "mla_moe": TOY,
    "swa_moe": dict(
        _WIDE, kind="trajectory", block="swa_moe", num_layers=5, num_heads=4,
        window_heads=6, num_kv_heads=2, attn_head_dim=8,
        shared_intermediate_size=16, sliding_window=4,
    ),
    "kda_moe": dict(
        _WIDE, kind="trajectory", block="kda_moe", num_layers=5, num_heads=2,
        kda_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8,
    ),
}


@pytest.mark.parametrize("block,routed", [
    ("preln", 0), ("mla_moe", 2), ("swa_moe", 4), ("kda_moe", 4),
])
def test_a_rollout_reports_what_its_acting_steps_read(block, routed):
    """The routed layers of an acting step tally the share of the held
    experts they read in the carry's cache; the rollout reads the tally
    where it ends and the iteration's row carries it as
    ``moe/acting_live_share``: 1.0 off the TPU, where every step reads
    every held expert. A family without routed layers has no such row."""
    from surreal_tpu.launch.rollout import device_rollout, init_device_carry
    from surreal_tpu.launch.trainer import Trainer

    horizon, envs = 6, 8      # the suite's eight CPU devices divide the envs
    trainer = Trainer(Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=1, num_minibatches=1),
            model=Config(encoder=Config(**TALLY_TOYS[block])),
        ),
        env_config=Config(name="jax:lift", num_envs=envs),
        session_config=Config(folder="unused"),
    ).extend(base_config()))
    learner = trainer.learner
    state = learner.init(jax.random.key(0))
    carry = learner.act_init(envs)
    assert learner.act_rows(carry) == ({"moe/acting_live_share": 0.0} if routed else {})
    step = jax.jit(learner.act_step)
    for t in range(3):
        obs = jax.random.normal(jax.random.key(t), (envs, 17))
        _, _, carry = step(state, carry, obs, jax.random.key(t))
    if routed:
        assert carry["cache"][moe.EXPERTS_READ].tolist() == [3 * routed, 3 * routed]
    env_carry = init_device_carry(trainer.env, jax.random.key(1), envs)
    if block == "mla_moe":      # one family through the whole iteration's row
        _, _, rows = jax.jit(trainer._device_train_iter)(
            state, env_carry, jax.random.key(2)
        )
        assert "loss/pg" in rows
    else:
        rows = jax.jit(
            lambda s, c, k: device_rollout(trainer.env, learner, s, c, k, horizon)
        )(state, env_carry, jax.random.key(2))[1]["acting"]
    if routed:
        assert float(rows["moe/acting_live_share"]) == 1.0
    else:
        assert rows == {}


def test_the_selection_bias_moves_toward_balance_and_takes_no_gradient():
    learner = _learner(8, "f32")
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (4, 8, 5), jnp.float32)

    def loss(params):
        out, stats = learner._apply(params, obs)
        return (out.value ** 2).mean() + (out.mean ** 2).mean(), stats

    grads, stats = jax.grad(loss, has_aux=True)(state.params)
    for g in latent_moe.router_biases(grads):
        assert float(jnp.abs(g).max()) == 0.0
    # the loss stops at the router's product (one chip's slice of the
    # experts: models/latent_moe.py), so Adam never moves the router
    router = grads["params"]["trunk"]["layer1"]["moe"]["router"]
    assert float(jnp.abs(router).max()) == 0.0
    experts = grads["params"]["trunk"]["layer1"]["moe"]["gate"]
    assert float(jnp.abs(experts).max()) > 0.0
    load = stats["load"]                          # [2 routed layers, 8]
    assert load.shape == (2, 8) and float(load.sum()) == 2 * 32 * 2
    new = latent_moe.update_router_bias(state.params, load, 0.001)
    for b, row in zip(latent_moe.router_biases(new), load):
        np.testing.assert_allclose(
            b, 0.001 * jnp.sign(row.mean() - row), rtol=0, atol=1e-9
        )
    # the rule balances: repeated steps on the same 128 tokens bring the
    # busiest expert's load over the mean down (1.39 -> 1.07 here)
    obs = jax.random.normal(jax.random.key(1), (16, 8, 5), jnp.float32)
    loads = jax.jit(lambda p: learner._apply(p, obs)[1]["load"])
    params, spread = state.params, []
    for _ in range(150):
        load = loads(params)
        spread.append(float((load.max(-1) / load.mean(-1)).mean()))
        params = latent_moe.update_router_bias(params, load, 0.001)
    assert np.mean(spread[-20:]) < 1.0 + 0.5 * (spread[0] - 1.0)


def test_learn_moves_the_bias_by_its_rule_and_reports_the_router(form):
    learner = _learner(8, "mixed")
    state = learner.init(jax.random.key(0))
    T, B = 8, 4
    k = jax.random.split(jax.random.key(2), 4)
    batch = {
        "obs": jax.random.normal(k[0], (T, B, 5)),
        "next_obs": jax.random.normal(k[1], (T, B, 5)),
        "action": jax.random.normal(k[2], (T, B, 2)),
        "reward": jax.random.normal(k[3], (T, B)),
        "done": jnp.zeros((T, B), bool), "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, 2)), "log_std": jnp.full((T, B, 2), -0.5),
        },
    }
    new, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(3))
    # 2 epochs x 2 minibatches: each bias moved by at most 4 steps of 0.001
    assert 0.0 < float(metrics["moe/bias_abs_max"]) <= 4 * 0.001 + 1e-9
    assert float(metrics["moe/overflow"]) == 0.0
    assert 0.0 < float(metrics["moe/held_share"]) < 1.0
    assert float(metrics["moe/load_max_over_mean"]) >= 1.0
    # Adam left the bias where the rule put it: no gradient, no moment
    mu = new.opt_state[1][0].mu
    for m in latent_moe.router_biases(mu):
        assert float(jnp.abs(m).max()) == 0.0
    assert float(metrics["health/update_ratio"]) > 0.0


def test_a_dropped_assignment_ends_the_session():
    """``moe/overflow`` is not a gauge to watch: the cadence's metrics
    sync raises on a non-zero (launch/hooks.py)."""
    from surreal_tpu.launch.hooks import refuse_dropped_assignments

    refuse_dropped_assignments({"loss/pg": 0.1})
    refuse_dropped_assignments({"moe/overflow": 0.0})
    with pytest.raises(RuntimeError, match="168 assignments"):
        refuse_dropped_assignments({"moe/overflow": 168.0})


# -- parts -----------------------------------------------------------------------

def test_part_refuses_a_foreign_name_and_reads_through_transforms():
    from surreal_tpu.utils.phases import PARTS, PHASES, part, part_of, phase_of

    assert not set(PARTS) & set(PHASES)
    with pytest.raises(ValueError, match="not in the vocabulary"):
        part("attention")
    with pytest.raises(ValueError, match="not in the vocabulary"):
        part("sgd")
    name = "jit(train_iter)/sgd/while/body/transpose(jvp(attn))/dot_general"
    assert part_of(name) == "attn" and phase_of(name) == "sgd"
    assert part_of("jit(train_iter)/collect/while/body/env/add") == "unattributed"


def test_parts_sum_with_unattributed_to_busy():
    from surreal_tpu.session.profile import reduce_digest

    ops = [
        (0, 100, "while.1", "sgd", "unattributed"),
        (10, 40, "fusion.1", "sgd", "attn"),
        (40, 70, "ragged-dot.2", "sgd", "moe_experts"),
        (120, 150, "fusion.3", "collect", "attn"),
        (150, 160, "fusion.4", "collect"),          # an old 4-tuple: no part
    ]
    d = reduce_digest({"/device:TPU:0": ops}, [], steps=1)
    parts, phases = d["parts"], d["phases"]
    busy_ms = d["busy_s"] * 1e3
    assert sum(p["ms_per_iter"] for p in parts.values()) == pytest.approx(busy_ms)
    assert sum(p["ms_per_iter"] for p in phases.values()) == pytest.approx(busy_ms)
    assert parts["attn"]["ms_per_iter"] == pytest.approx(60e-6)
    assert parts["moe_experts"]["ms_per_iter"] == pytest.approx(30e-6)
    assert parts["unattributed"]["ms_per_iter"] == pytest.approx(50e-6)
    assert parts["attn"]["top_ops"][0][0] in ("fusion.1", "fusion.3")
    assert sum(p["share_of_busy"] for p in parts.values()) == pytest.approx(1.0)


def test_diag_prints_the_parts_beside_the_phases_only_where_a_model_has_them():
    from surreal_tpu.session.profile import reduce_digest
    from surreal_tpu.session.telemetry import _digest_lines

    ops = [
        (0, 40, "fusion.1", "sgd", "attn"),
        (40, 70, "ragged-dot.2", "sgd", "moe_experts"),
        (70, 100, "fusion.3", "collect", "unattributed"),
    ]
    with_parts = reduce_digest({"/device:TPU:0": ops}, [], steps=1)
    text = "\n".join(_digest_lines({"digest": with_parts}))
    assert "model part" in text and "moe_experts" in text and "phase" in text
    without = reduce_digest({"/device:TPU:0": [op[:4] for op in ops]}, [], steps=1)
    assert set(without["parts"]) == {"unattributed"}
    assert "model part" not in "\n".join(_digest_lines({"digest": without}))


def test_the_compiled_program_names_every_part():
    """The scopes reach the ops of a jitted learn step: the HLO maps hold
    each part of the vocabulary, under phase ``sgd``."""
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import PARTS, part_of

    learner = _learner(8, "mixed")
    state = jax.eval_shape(learner.init, jax.random.key(0))
    T, B = 8, 4
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    batch = {
        "obs": f32(T, B, 5), "next_obs": f32(T, B, 5), "action": f32(T, B, 2),
        "reward": f32(T, B), "done": jax.ShapeDtypeStruct((T, B), bool),
        "terminated": jax.ShapeDtypeStruct((T, B), bool),
        "behavior_logp": f32(T, B),
        "behavior": {"mean": f32(T, B, 2), "log_std": f32(T, B, 2)},
    }
    text = jax.jit(learner.learn).lower(
        state, batch, jax.eval_shape(lambda: jax.random.key(0))
    ).compile().as_text()
    _, parts = hlo_op_phases(text, part_of)
    _, phases = hlo_op_phases(text)
    # (the vocabulary also holds the state-space hybrid's parts:
    # tests/test_ssm_hybrid.py)
    assert set(parts.values()) == {
        "attn", "moe_route", "moe_experts", "dense_ffn", "optimizer",
    } < set(PARTS)
    both = [phases[i] for i, p in parts.items() if p == "optimizer" and i in phases]
    # (XLA fuses a few of them into an op it names after a neighbour)
    assert both and max(set(both), key=both.count) == "sgd"


# -- LatentAttention's other two cases (models/kda_moe.py's latent layers) ------

_ATTN = dict(
    hidden_size=32, num_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, rms_norm_eps=1e-6,
)


@pytest.mark.parametrize("q_lora_rank", [None, 24])
@pytest.mark.parametrize("rope_theta", [None, 1e4])
def test_expanded_pass_equals_absorbed_decode_in_every_case(q_lora_rank, rope_theta):
    """With or without the query's low-rank pair, with or without the rotary
    turn (a key that is none or absent): one position at a time through the
    latent cache with ``W_kvb`` absorbed is the expanded whole-segment pass,
    and the parameters are the case's own."""
    cfg = dict(_ATTN)
    if q_lora_rank is not None:
        cfg["q_lora_rank"] = q_lora_rank
    if rope_theta is not None:
        cfg["rope_theta"] = rope_theta
    attn = latent_moe.LatentAttention(cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(0), (3, 9, 32))
    params = attn.init(jax.random.key(1), x)
    names = set(params["params"])
    assert ("q" in names) == (q_lora_rank is None)
    assert ({"q_a", "q_b", "q_a_norm"} <= names) == (q_lora_rank is not None)
    with jax.default_matmul_precision("highest"):
        whole = attn.apply(params, x)
        cache = jnp.zeros((3, 9, 16 + 4))
        outs = []
        for t in range(9):
            out, cache = attn.apply(
                params, x[:, t], cache, jnp.int32(t), method=attn.decode
            )
            outs.append(out)
        # a layer without the turn does not see positions: the same segment
        # two places later in the cache gives the same outputs
        if rope_theta is None:
            late = jnp.zeros((3, 11, 20)).at[:, :2].set(cache[:, :2])
            for t in range(2, 4):
                shifted, late = attn.apply(
                    params, x[:, t], late, jnp.int32(t), method=attn.decode
                )
            np.testing.assert_allclose(shifted, outs[3], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.stack(outs, 1), whole, rtol=2e-5, atol=2e-6)


# sha256 (16 hex) of the StableHLO of the published JoyAI case at _ATTN's
# widths, recorded once on PR 46's parent (jax 0.9.0), where LatentAttention
# had that one case: the whole-segment pass with its gradient, and the
# absorbed decode step
_JOYAI_ATTENTION_ON_THE_PARENT = ("352a2d227a78bd68", "8e16ba876dee6c80")


def test_joyais_attention_lowers_to_the_program_it_had():
    """The published JoyAI case (``q_lora_rank`` and ``rope_theta`` set) is
    unchanged by the two new cases: the whole-segment pass with its gradient
    and the absorbed decode step lower to the parent's StableHLO, to the
    byte. (The builder of PR 46 compared the whole ``learn`` and ``act_step``
    of the toy learner above on the parent's checkout and this one:
    identical.)"""
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes were recorded under jax 0.9.0")
    cfg = dict(_ATTN, q_lora_rank=24, rope_theta=32e6)
    x = jax.ShapeDtypeStruct((3, 9, 32), jnp.bfloat16)
    attn = latent_moe.LatentAttention(cfg)
    params = jax.eval_shape(attn.init, jax.random.key(0), x)

    def loss(p, x):
        return attn.apply(p, x).astype(jnp.float32).sum()

    def step(p, x, c, t):
        return attn.apply(p, x, c, t, method=attn.decode)

    texts = (
        jax.jit(jax.grad(loss)).lower(params, x).as_text(),
        jax.jit(step).lower(
            params, jax.ShapeDtypeStruct((3, 32), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 9, 20), jnp.bfloat16),
            jax.ShapeDtypeStruct((), jnp.int32),
        ).as_text(),
    )
    assert tuple(
        hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts
    ) == _JOYAI_ATTENTION_ON_THE_PARENT
    assert "cosine" in texts[0] and texts[0].count("dot_general") >= 10


def test_a_masked_row_of_the_cache_has_to_be_finite():
    """What the decode path's mask promises, and what it does not: rows past
    ``pos`` are weighted by an exact zero, so whatever finite values they
    hold (a wrapped segment's stale rows) change nothing; a NaN there is a
    NaN in the output all the same, so the cache has to start as real zeros
    (tests/test_tpu_compile.py has the case in which the chip's compiler
    leaves them out)."""
    attn = latent_moe.LatentAttention(dict(_ATTN), jnp.float32)
    x = jax.random.normal(jax.random.key(0), (3, 32))
    params = attn.init(jax.random.key(1), x[:, None])
    step = lambda cache: attn.apply(   # noqa: E731
        params, x, cache, jnp.int32(2), method=attn.decode
    )[0]
    clean = step(jnp.zeros((3, 9, 20)))
    stale = step(jnp.zeros((3, 9, 20)).at[:, 3:].set(7.0))
    np.testing.assert_array_equal(stale, clean)
    poisoned = step(jnp.zeros((3, 9, 20)).at[1, 5].set(jnp.nan))
    assert bool(jnp.isnan(poisoned[1]).all())
    assert not bool(jnp.isnan(poisoned[::2]).any())
