"""The sixth block family of the trajectory seam (``model.encoder.block=
'dsa_moe'``: models/dsa_moe.py, ops/sparse_select.py) at toy widths on the
CPU: the trunk against the benchmark's plain reference (forward and
gradient, both forms of the expert product, recomputation on and off), with
``index_topk`` at or past the length against attention without selection,
acting through the key, value and index caches against the whole-segment
forward at every position, the wrap, the zero gradient of the indexer and
the routers, the share test, the table of families and what the family
refuses, PPO's rows, and its parts in the compiled program."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import dsa_moe, swa_moe
from surreal_tpu.models.attention import ROUTING_COLLECTION, TrajectoryPPOModel
from surreal_tpu.ops import moe
from surreal_tpu.ops.ring_attention import blocked_attention
from surreal_tpu.session.config import Config

ref = manifest.load_reference("ppo_keye_ref")
CONFIG = manifest.load_config("ppo_lift_keye")

TOPK, T, B = 5, 21, 3
TOY = dict(
    kind="trajectory", block="dsa_moe", num_layers=3, num_heads=4,
    num_kv_heads=2, attn_head_dim=8, hidden_size=32, index_n_heads=4,
    index_head_dim=8, index_topk=TOPK, moe_intermediate_size=16,
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
    # the family initialises by swa_moe's ``_normal``; toy blocks of queries,
    # so that a segment of 21 has blocks that select and blocks that do not
    monkeypatch.setattr(swa_moe, "INIT_STD", INIT_STD)
    monkeypatch.setattr(dsa_moe, "QUERY_BLOCK", 8)


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
    """Both forms of the held experts' product, each layer recomputed or not:
    the outputs, the kept sets and the gradient of every leaf are the plain
    reference's, whose selection is a full sort."""
    assert moe.dense_form(b * t) == (form == "dense")
    if remat:
        monkeypatch.setattr(dsa_moe, "REMAT_ABOVE_BYTES", 0)
    # three blocks of queries either way: one that selects nothing, two that do
    monkeypatch.setattr(dsa_moe, "QUERY_BLOCK", 8 if t == T else 64)
    cfg = _cfg(index_topk=TOPK if t == T else 60)
    model, w = _model(cfg), _widths(cfg)
    params, obs = _params(model), _obs(b, t)

    def ours(p):
        out = model.apply(p, obs)
        return (out.value ** 2).sum() + (out.mean ** 2).sum(), out

    def theirs(p):
        mean, _, value, _, selects = ref.policy(p, obs, w)
        return (value ** 2).sum() + (mean ** 2).sum(), (mean, value, selects)

    with jax.default_matmul_precision("highest"):
        (_, out), g = jax.jit(jax.value_and_grad(ours, has_aux=True))(params)
        (_, (mean, value, selects)), g_ref = jax.jit(jax.value_and_grad(
            theirs, has_aux=True
        ))(params)
        _, sown = jax.jit(
            lambda p: model.apply(p, obs, mutable=[ROUTING_COLLECTION])
        )(params)
    np.testing.assert_allclose(out.mean, mean, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out.value, value, rtol=1e-4, atol=1e-5)
    # the program's search keeps what the reference's sort keeps
    kept = dsa_moe.kept_of(sown[ROUTING_COLLECTION])
    assert len(kept) == 3 and kept[0].shape == (b, t, t)
    for mask, info in zip(kept, selects):
        np.testing.assert_array_equal(np.asarray(mask).sum(-1), np.asarray(info["own"]))
        assert int(info["own"][0, -1]) == int(cfg["index_topk"])
    flat, flat_ref = (
        dict(jax.tree_util.tree_leaves_with_path(x)) for x in (g, g_ref)
    )
    assert flat.keys() == flat_ref.keys()
    for path, leaf in flat.items():
        scale = float(jnp.abs(flat_ref[path]).max())
        np.testing.assert_allclose(
            leaf, flat_ref[path], rtol=2e-3, atol=2e-4 * max(scale, 1e-3),
            err_msg=jax.tree_util.keystr(path),
        )


def test_the_indexer_and_the_routers_take_a_zero_gradient():
    cfg = _cfg()
    model = _model(cfg)
    params, obs = _params(model), _obs()
    def loss(p):
        out = model.apply(p, obs)
        return (out.value ** 2).sum() + (out.mean ** 2).sum()

    g = jax.jit(jax.grad(loss))(params)["params"]["trunk"]
    for i in range(3):
        layer = g[f"layer{i}"]
        assert set(layer["index"]) == {
            "q", "k", "w", "k_norm_scale", "k_norm_bias",
        }
        for name, leaf in layer["index"].items():
            assert float(jnp.abs(leaf).max()) == 0.0, name
        assert float(jnp.abs(layer["moe"]["router"]).max()) == 0.0
        for name in ("q", "k", "v", "o", "q_norm", "k_norm"):
            assert float(jnp.abs(layer["attn"][name]).max()) > 0.0, name


@pytest.mark.parametrize("topk", [T, T + 7])
def test_with_topk_at_or_past_the_length_the_layer_is_attention_without_selection(topk):
    """No query has more than ``index_topk`` keys: the mixer is
    ``blocked_attention`` over q, k and v with no keep-mask, the counters
    read 1 and 0, and the indexer is not run (its leaves may hold
    anything)."""
    cfg = _cfg(index_topk=topk)
    s = dsa_moe._sizes(cfg)
    model = _model(cfg)
    layer = _params(model)["params"]["trunk"]["layer0"]
    h = jax.random.normal(jax.random.key(2), (B, T, 32))
    with jax.default_matmul_precision("highest"):
        out, share, mask = dsa_moe.attention_mixer(
            layer["attn"], layer["index"], h, s, jnp.float32
        )
        q, k, v = dsa_moe._qkv(layer["attn"], h, jnp.arange(T), s, jnp.float32)
        plain, _ = blocked_attention(q, k, v, block=8)
        want = jnp.einsum("bthe,hed->btd", plain, layer["attn"]["o"])
        poisoned = jax.tree.map(lambda x: x * jnp.nan, layer["index"])
        again, _, _ = dsa_moe.attention_mixer(
            layer["attn"], poisoned, h, s, jnp.float32
        )
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))
    assert float(share) == pytest.approx(1.0)
    assert bool((mask == jnp.tril(jnp.ones((T, T), bool))).all())
    _, sown = model.apply(_params(model), _obs(), mutable=["counters"])
    counters = {k: float(v[-1]) for k, v in sown["counters"]["trunk"].items()}
    assert counters == {
        "kept_share": pytest.approx(1.0), "selecting_share": 0.0,
        "scores_in_vmem": 0.0,
    }


def test_the_counters_are_the_arithmetic():
    model = _model(_cfg())
    _, sown = model.apply(_params(model), _obs(), mutable=["counters"])
    counters = {k: float(v[-1]) for k, v in sown["counters"]["trunk"].items()}
    kept, selecting = ref.kept_share_of(T, TOPK)
    assert counters["kept_share"] == pytest.approx(kept, abs=1e-6)
    assert counters["selecting_share"] == pytest.approx(selecting) == (T - TOPK) / T


# -- acting --------------------------------------------------------------------------

@pytest.mark.parametrize("horizon,head,topk", [
    (T, 8, TOPK), (20, 64, 7), (9, 128, 4), (6, 8, 6),
])
def test_acting_through_the_three_caches_is_the_whole_segment_forward(
    horizon, head, topk,
):
    """Decode through the key, value and index caches gives, at every
    position, the whole-segment forward's outputs and kept set: odd and even
    horizons, an index head that shares its 128-lane row sixteen ways, two
    ways (the published 64) and not at all, and a horizon within ``topk``,
    where nothing selects (the rehearsal's 24 x ``topk`` 10 runs in
    ``tests/benchmarks/test_benchmark_keye_reference.py``)."""
    cfg = _cfg(index_head_dim=head, index_topk=topk)
    model = _model(cfg)
    params, obs = _params(model), _obs(2, horizon)
    rows, per = dsa_moe.index_rows(horizon, head)
    assert per == max(128 // head, 1) and rows == -(-horizon // per)
    cache = model.init_cache(2, horizon)
    assert cache["layers"][0]["index"].shape == (2, rows, per * head)
    assert cache["layers"][0]["k"].shape == (2, horizon, 2, 8)
    with jax.default_matmul_precision("highest"):
        out, sown = model.apply(params, obs, mutable=[ROUTING_COLLECTION])
        kept = dsa_moe.kept_of(sown[ROUTING_COLLECTION])
        step = jax.jit(lambda c, o, pos: model.apply(
            params, o, cache=c, pos=pos, mutable=[ROUTING_COLLECTION]
        ))
        for t in range(horizon):
            (o, cache), sown_t = step(cache, obs[:, t], jnp.int32(t))
            np.testing.assert_allclose(o.mean, out.mean[:, t], rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(o.value, out.value[:, t], rtol=1e-4, atol=1e-6)
            for layer, mask in zip(dsa_moe.kept_of(sown_t[ROUTING_COLLECTION]), kept):
                np.testing.assert_array_equal(
                    np.asarray(layer), np.asarray(mask[:, t])
                )
                assert int(layer.sum(-1)[0]) == min(t + 1, topk)


def test_a_stale_carry_is_masked_after_the_wrap():
    learner = _learner(horizon=8)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(3), (9, 2, 5))
    carry = learner.act_init(2)
    step = jax.jit(lambda c, o: learner.act_step(state, c, o, jax.random.key(0)))
    _, first, _ = step(carry, obs[0])
    for t in range(8):
        _, _, carry = step(carry, obs[t])
    assert int(carry["pos"]) == 8
    _, wrapped, carry = step(carry, obs[0])
    assert int(carry["pos"]) == 1
    np.testing.assert_array_equal(wrapped["mean"], first["mean"])
    from surreal_tpu.models import attention

    assert attention.family_of({"block": "dsa_moe"}).reset_recurrent is None


# -- the share -----------------------------------------------------------------------

@pytest.mark.parametrize("tokens", [64, 320])
def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer(tokens):
    """Sixteen experts over eight chips of two, as the deployment shares a
    layer's 128 over eight chips of sixteen: what each share's routed layer
    gives (its held experts' part; there is no shared expert, so nothing is
    computed alike and counted once but the router) adds up to the uncut
    reference's layer with all sixteen held. Dense form and sorted form."""
    assert moe.dense_form(tokens) == (tokens == 64)
    cfg = _cfg(n_routed_experts=16, num_experts_per_tok=4)
    D, E, F = 32, 16, 16
    keys = jax.random.split(jax.random.key(0), 5)
    normal = lambda k, *shape: INIT_STD * jax.random.normal(k, shape)  # noqa: E731
    whole = {
        "router": 8 * normal(keys[0], D, E),   # logits of size 1: no two tie
        "gate": normal(keys[1], E, D, F), "up": normal(keys[2], E, D, F),
        "down": normal(keys[3], E, F, D),
    }
    x = jax.random.normal(keys[4], (tokens, D))
    with jax.default_matmul_precision("highest"):
        total, loads = jnp.zeros_like(x), []
        for share in range(8):
            s = dsa_moe._sizes(dict(cfg, first_held=2 * share))
            mine = dict(whole, **{
                k: whole[k][2 * share:2 * share + 2] for k in ("gate", "up", "down")
            })
            y, stats = swa_moe.routed_ffn(mine, None, x, s)
            assert float(stats["overflow"]) == 0.0
            loads.append(stats["load"])
            total = total + y
        w = _widths(dict(cfg, first_held=0, num_held=E))
        uncut, info = ref.routed(whole, x, w)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    # every chip routes over all sixteen alike, four a token
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 4
    # norm_topk_prob: the weights add up to 1 over the four chosen, held here
    # or not, and scale by nothing
    prob = jax.nn.softmax(info["logits"], -1)
    chosen = jnp.take_along_axis(prob, info["own"], -1)
    np.testing.assert_allclose((chosen / chosen.sum(-1, keepdims=True)).sum(-1), 1.0, rtol=1e-6)
    assert dsa_moe._sizes(cfg)["scale"] == 1.0


# -- the table of families -------------------------------------------------------------

def test_the_table_has_the_family_and_the_selectors_read_it():
    from surreal_tpu.models import attention

    assert "dsa_moe" in attention.BLOCK_FAMILIES
    family = attention.family_of({"block": "dsa_moe"})
    assert family is dsa_moe.FAMILY
    assert family.defaults is dsa_moe.FAMILY_DEFAULTS
    # what it offers a learner: counters and the routed rows; no recurrent
    # leaf, no bias rule
    assert family.counters is dsa_moe.COUNTERS and family.moe_stats
    assert family.reset_recurrent is None and family.update_router_bias is None
    assert family.not_read == ()


@pytest.mark.parametrize("encoder,message", [
    (dict(kind="trajectory", index_topk=8), "'preln' does not read"),
    (dict(TOY, features=128), "'dsa_moe' does not read"),
    (dict(TOY, kv_lora_rank=16), "'dsa_moe' does not read"),
    (dict(TOY, sliding_window=4), "'dsa_moe' does not read"),
    (dict(TOY, num_heads=5), "multiple of num_kv_heads"),
    (dict(TOY, index_head_dim=7), "turned, in pairs"),
    (dict(TOY, index_topk=0), "keeps at least one key"),
    (dict(TOY, first_held=7), "lie outside"),
    (dict(TOY, block="swa_moe", num_layers=5, index_topk=8),
     "'swa_moe' does not read"),
])
def test_a_key_of_another_family_or_a_bad_size_is_refused(encoder, message):
    from surreal_tpu.learners.seq_policy import family_config

    with pytest.raises(ValueError, match=message):
        family_config(encoder)


def test_the_default_config_has_the_familys_keys_unset_and_the_published_widths():
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    enc = BASE_LEARNER_CONFIG.model.encoder
    for key in dsa_moe.FAMILY_DEFAULTS:
        assert key in enc and enc[key] is None, key
    published = dsa_moe.resolve({"num_heads": 32, "num_layers": 5})
    w = CONFIG["widths"]
    assert [published[k] for k in (
        "hidden_size", "num_kv_heads", "attn_head_dim", "index_n_heads",
        "index_head_dim", "index_topk", "moe_intermediate_size",
        "n_routed_experts", "num_experts_per_tok", "num_held", "rms_norm_eps",
    )] == [w[k] for k in (
        "hidden_size", "num_key_value_heads", "head_dim", "indexer_num_heads",
        "indexer_head_dim", "topk", "moe_intermediate_size", "router_outputs",
        "num_experts_per_tok", "num_held", "rms_norm_eps",
    )]
    # the published index head shares a 128-lane row two ways
    assert dsa_moe.index_rows(4096, 64) == (2048, 2)


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
    with pytest.raises(ValueError, match="'dsa_moe' runs flat vector obs"):
        build_learner(with_stem, pixels)
    learner = _learner(8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="no sp mesh path"):
        build_seq_model(
            learner.config.model, SPECS, -0.5, mesh=mesh, horizon=8,
        )
    with pytest.raises(ValueError, match="'dsa_moe' is wired into PPO alone"):
        build_learner(
            Config(
                algo=Config(name="impala", horizon=8),
                model=Config(encoder=Config(**TOY)),
            ), SPECS,
        )


def test_recomputation_is_chosen_from_the_pass_and_not_by_a_key():
    published = dsa_moe.resolve({"num_heads": 32, "num_layers": 5})
    # an 8192-token minibatch's residuals do not fit beside 8.7 GB of state
    assert dsa_moe.residual_bytes(published, 8192) > dsa_moe.REMAT_ABOVE_BYTES
    assert 2.0e9 < dsa_moe.residual_bytes(published, 8192) < 4.0e9
    assert dsa_moe.residual_bytes(_cfg(), B * T) < dsa_moe.REMAT_ABOVE_BYTES
    assert not {"remat", "recompute", "checkpoint"} & set(dsa_moe.FAMILY_DEFAULTS)


# -- under PPO -------------------------------------------------------------------------

def _batch(learner, state, envs=4, horizon=T, seed=2):
    keys = jax.random.split(jax.random.key(seed), 4)
    obs = jax.random.normal(keys[0], (horizon + 1, envs, 5))
    carry = learner.act_init(envs)
    actions, logps, means = [], [], []
    step = jax.jit(lambda c, o, k: learner.act_step(state, c, o, k))
    for t in range(horizon):
        action, info, carry = step(carry, obs[t], jax.random.fold_in(keys[1], t))
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


def test_learn_reports_the_rows_and_moves_neither_indexer_nor_router():
    learner = _learner()
    state = learner.init(jax.random.key(0))
    batch = _batch(learner, state)
    new, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(5))
    metrics = {k: float(v) for k, v in metrics.items()}
    assert 0.0 <= metrics["moe/held_share"] <= 1.0
    assert metrics["moe/overflow"] == 0.0
    assert "moe/bias_abs_max" not in metrics
    kept, selecting = ref.kept_share_of(T, TOPK)
    assert metrics["attn/kept_share"] == pytest.approx(kept, abs=1e-6)
    assert metrics["attn/selecting_share"] == pytest.approx(selecting)
    assert metrics["attn/scores_in_vmem"] == 0.0    # toy widths, and the CPU
    assert math.isfinite(metrics["loss/pg"]) and metrics["health/update_ratio"] > 0
    before, after = state.params["params"]["trunk"], new.params["params"]["trunk"]
    for i in range(3):
        np.testing.assert_array_equal(
            before[f"layer{i}"]["moe"]["router"], after[f"layer{i}"]["moe"]["router"]
        )
        for name in before[f"layer{i}"]["index"]:
            np.testing.assert_array_equal(
                before[f"layer{i}"]["index"][name], after[f"layer{i}"]["index"][name]
            )
        assert float(jnp.abs(
            before[f"layer{i}"]["moe"]["gate"] - after[f"layer{i}"]["moe"]["gate"]
        ).max()) > 0.0
        assert float(jnp.abs(
            before[f"layer{i}"]["attn"]["q_norm"] - after[f"layer{i}"]["attn"]["q_norm"]
        ).max()) > 0.0
    # the acting steps' tally reaches the carry's rows
    assert learner.act_rows(learner.act_init(4)) != {}


def test_ppo_first_epoch_ratio_is_one():
    """What acting computed through the three caches is what the learn pass
    recomputes over the whole segment: the importance ratio of the first
    minibatch step is 1."""
    learner = _learner()
    state = learner.init(jax.random.key(0))
    batch = _batch(learner, state)
    out, stats = jax.jit(learner._apply)(
        state.params,
        learner._norm_obs(state.obs_stats, batch["obs"]).swapaxes(0, 1),
    )
    from surreal_tpu.ops import distributions as D

    logp = D.diag_gauss_logp(out.mean, out.log_std, batch["action"].swapaxes(0, 1))
    ratio = jnp.exp(logp - batch["behavior_logp"].swapaxes(0, 1))
    assert float(jnp.abs(ratio - 1).max()) < 1e-4
    assert set(stats) == {
        "load", "overflow", "kept_share", "selecting_share", "scores_in_vmem",
    }
    assert float(stats["scores_in_vmem"]) == 0.0    # the CPU's form
    assert stats["load"].shape == (3, 8)


# -- parts -----------------------------------------------------------------------------

KEYE_PARTS = {"attn", "attn_index", "moe_route", "moe_experts", "optimizer"}


def test_the_compiled_program_names_the_familys_parts():
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import PARTS, part_of

    assert "attn_index" in PARTS
    learner = _learner(8, "mixed")
    state = jax.eval_shape(learner.init, jax.random.key(0))
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
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
    assert set(parts.values()) == KEYE_PARTS
    # the indexer's ops are its own part though the layer is attention
    assert part_of("jit(learn)/sgd/jvp(attn_index)/while/body/ge") == "attn_index"
    assert part_of("jit(learn)/sgd/attn/dot") == "attn"
    for name in ("attn", "attn_index"):
        seen = {phases[i] for i, p in parts.items() if p == name and i in phases}
        assert {"prepare", "sgd"} <= seen, (name, seen)
    # and in an acting step
    carry = jax.eval_shape(lambda: learner.act_init(4))
    text = jax.jit(
        lambda s, c, o, k: learner.act_step(s, c, o, k)
    ).lower(state, carry, f32(4, 5), jax.eval_shape(lambda: jax.random.key(0))
            ).compile().as_text()
    acting = set(hlo_op_phases(text, part_of)[1].values())
    assert {"attn", "attn_index", "moe_route", "moe_experts"} <= acting


# -- the four wide families that were there ------------------------------------------

OTHER_FAMILIES = {
    "mla_moe": dict(
        num_layers=3, num_heads=2, hidden_size=32, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, num_held=2,
    ),
    "ssm_hybrid": dict(
        num_heads=4, hidden_size=32, num_kv_heads=2, sliding_window=4,
        intermediate_size=64, ssm_state_size=4, pairs_before=1, pairs_after=1,
    ),
    "swa_moe": dict(
        num_layers=5, num_heads=4, window_heads=6, num_kv_heads=2,
        attn_head_dim=8, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, shared_intermediate_size=16,
        n_routed_experts=8, num_experts_per_tok=2, num_held=2, sliding_window=4,
    ),
    "kda_moe": dict(
        num_layers=5, num_heads=2, hidden_size=32, kda_head_dim=8,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, num_held=2,
    ),
}
# sha256 (first 16 hex) of the StableHLO text of ``learn`` and of ``act_step``
# at these toy widths, 4 envs x 12 positions, ``mixed``, as the PARENT commit
# of PR 61 lowers them (jax 0.9.0): the keep-mask ``blocked_attention``
# learnt, ``routed_ffn``'s absent shared expert and part ``attn_index`` change
# no program that was there. A PR that changes one of these families on
# purpose reads the new text off its own tree and says so.
PARENT_LOWERING = {
    "mla_moe": ("7608da70cdbf6adc", "d00c242bb94723ea"),
    "ssm_hybrid": ("38f07ea7aa042a71", "d60ac0f82d8ebd8f"),
    "swa_moe": ("cbe244c5bb39ac3f", "cab4dd71c3d5d6a1"),
    "kda_moe": ("734d46aea467ffc8", "944bf74ba8670f54"),
}


def lowering_hashes(encoder: dict) -> tuple:
    """sha256 (first 16 hex) of the StableHLO text of ``learn`` and of
    ``act_step`` of a trajectory policy with ``model.encoder`` = ``encoder``,
    4 envs x 12 positions, ``mixed`` (tests/test_gdn_moe.py holds 'dsa_moe'
    itself through it)."""
    import hashlib

    cfg = Config(
        algo=Config(
            name="ppo", horizon=12, epochs=2, num_minibatches=2, precision="mixed",
        ),
        model=Config(encoder=Config(**encoder)),
    )
    learner = build_learner(cfg, SPECS)
    state = jax.eval_shape(learner.init, jax.random.key(0))
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    flag = jax.ShapeDtypeStruct((12, 4), bool)
    batch = {
        "obs": f32(12, 4, 5), "next_obs": f32(12, 4, 5), "action": f32(12, 4, 2),
        "reward": f32(12, 4), "done": flag, "terminated": flag,
        "behavior_logp": f32(12, 4),
        "behavior": {"mean": f32(12, 4, 2), "log_std": f32(12, 4, 2)},
    }
    key = jax.eval_shape(lambda: jax.random.key(0))
    learn = jax.jit(learner.learn).lower(state, batch, key).as_text()
    carry = jax.eval_shape(lambda: learner.act_init(4))
    act = jax.jit(
        lambda s, c, o, k: learner.act_step(s, c, o, k)
    ).lower(state, carry, f32(4, 5), key).as_text()
    sha = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]  # noqa: E731
    return sha(learn), sha(act)


@pytest.mark.parametrize("family", sorted(OTHER_FAMILIES))
def test_a_wide_family_that_was_there_lowers_to_the_program_it_had(family):
    assert lowering_hashes(
        dict(kind="trajectory", block=family, **OTHER_FAMILIES[family])
    ) == PARENT_LOWERING[family]

