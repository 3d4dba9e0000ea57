"""The third block family of the trajectory seam (``model.encoder.block=
'ssm_hybrid'``: models/ssm_hybrid.py, ops/selective_scan.py) at toy widths
on the CPU: each mixer against the benchmark's plain reference, the decode
through state, ring and shared cache against the full forward with a
window shorter than the segment, the wrap, recomputation, the parameter
count at the published widths, PPO's ratio contract, what the family
refuses, and its parts in the compiled program and in ``diag``'s table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.models import ssm_hybrid
from surreal_tpu.session.config import Config

ref = manifest.load_reference("ppo_phi4flash_ref")

WINDOW, T, B = 4, 12, 3
TOY = dict(
    kind="trajectory", block="ssm_hybrid", num_heads=4, num_kv_heads=2,
    hidden_size=32, intermediate_size=64, sliding_window=WINDOW,
    ssm_state_size=4, ssm_dt_rank=3, pairs_before=1, pairs_after=1,
)
SPECS = EnvSpecs(
    obs=ArraySpec(shape=(5,), dtype=np.dtype(np.float32)),
    action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
)
# ``{case: (encoder overrides, key-value heads a cache row, a row's shape)}``:
# TOY's heads of 8 keep a head a row; two heads of 64 fill a row's 128 lanes
# and the acting caches pack them (ssm_hybrid.heads_per_row)
ROWS = {
    "a head a row": ({}, 1, (2, 8)),
    "two heads a row": (dict(hidden_size=256), 2, (1, 128)),
}
CFG = ssm_hybrid.resolve(TOY)
SIZES = ssm_hybrid._sizes(CFG)
# the reference reads every width from its configuration's file: the three
# the program holds as constants too
REF_W = dict(
    CFG, ssm_conv_kernel=ssm_hybrid.CONV_TAPS, ssm_expand=ssm_hybrid.EXPAND,
    layer_norm_eps=ssm_hybrid.NORM_EPS,
)


def _learner(horizon=T, precision="f32", **encoder):
    cfg = Config(
        algo=Config(
            name="ppo", horizon=horizon, epochs=2, num_minibatches=2,
            precision=precision,
        ),
        model=Config(encoder=Config(**{**TOY, **encoder})),
    )
    return build_learner(cfg, SPECS)


def _leaves(kind, seed=0):
    """One mixer's leaves as the trunk initialises them, matrices at a
    size that keeps a product's input's size at 32 wide."""
    params = ssm_hybrid.Leaves(ssm_hybrid.mixer_spec(kind, CFG)).init(
        jax.random.key(seed)
    )["params"]
    return jax.tree.map(
        lambda x: x * (8.0 if x.ndim >= 2 and x.shape[0] >= 4 else 1.0), params
    )


def _h(seed=1):
    return jax.random.normal(jax.random.key(seed), (B, T, 32), jnp.float32)


# -- the five mixers against the reference ---------------------------------------

@pytest.mark.parametrize("kind", ssm_hybrid.KINDS)
def test_mixer_equals_the_reference(kind):
    p, h, f32 = _leaves(kind), _h(), jnp.float32
    with jax.default_matmul_precision("highest"):
        if kind == "ssm":
            out, y, state = ssm_hybrid.ssm_mixer(p, h, SIZES, f32)
            want, want_y, _ = ref.ssm(p, h, REF_W)
            np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5)
            assert float(jnp.abs(state).max()) > 0
        elif kind in ("window", "full"):
            window = WINDOW if kind == "window" else None
            out, kv, seen = ssm_hybrid.attention_mixer(p, h, SIZES, f32, window)
            want, want_kv = ref.attention(p, h, REF_W, window)
            np.testing.assert_allclose(kv[0], want_kv[0], rtol=1e-4, atol=1e-5)
            assert float(seen) == pytest.approx(
                ref.window_keys_mean(T, window or T)
            )
        elif kind == "gmu":
            m = _h(2)[..., :1].repeat(64, -1) * _h(3).repeat(2, -1)
            out, want = ssm_hybrid.gmu_mixer(p, h, m, f32), ref.gmu(p, h, m)
        else:
            full = _leaves("full", 5)
            _, kv = ref.attention(full, _h(4), REF_W)
            out, _, _ = ssm_hybrid.attention_mixer(p, h, SIZES, f32, None, kv=kv)
            want, _ = ref.attention(p, h, REF_W, None, kv=kv)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-2   # not a comparison of zeros


@pytest.mark.parametrize("block", [1, 5, 256])
def test_blocked_attention_is_the_masked_softmax_whatever_the_block(block):
    from surreal_tpu.ops.ring_attention import blocked_attention

    p, h = _leaves("window"), _h()
    q, k, v = (jnp.einsum("btd,dhe->bthe", h, p[n]) for n in ("q", "k", "v"))
    out, seen = blocked_attention(q, k, v, window=WINDOW, block=block)
    want, _ = ref.attention(dict(p, o=jnp.eye(32).reshape(4, 8, 32)), h, REF_W, WINDOW)
    np.testing.assert_allclose(out.reshape(B, T, 32), want, rtol=1e-4, atol=1e-5)
    assert float(seen) == pytest.approx(ref.window_keys_mean(T, WINDOW))


# -- acting through the carry -----------------------------------------------------

def _act(learner, state, obs, carry=None):
    carry = learner.act_init(obs.shape[1]) if carry is None else carry
    step = jax.jit(
        lambda c, o: learner.act_step(
            state, c, o, jax.random.key(0), "eval_deterministic"
        )
    )
    infos = []
    for t in range(obs.shape[0]):
        _, info, carry = step(carry, obs[t])
        infos.append(info)
    return jax.tree.map(lambda *x: jnp.stack(x, 1), *infos), carry


@pytest.mark.parametrize("row", ROWS)
@pytest.mark.parametrize("precision,tol", [("f32", 2e-5), ("mixed", 2e-2)])
def test_decode_through_state_ring_and_shared_cache_equals_the_full_forward(
    precision, tol, row
):
    """At every one of 12 positions with a window of 4 (the ring wraps
    twice): what ``act_step`` produced through the constant-size state, the
    ring that forgets and the shared cache is what one whole-segment apply
    recomputes, with a head a cache row and with two heads sharing one."""
    encoder, heads_a_row, kv_row = ROWS[row]
    learner = _learner(T, precision, **encoder)
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (T, B, 5), jnp.float32)
    info, carry = _act(learner, state, obs)
    out, stats = learner._apply(
        state.params, learner._norm_obs(state.obs_stats, obs).swapaxes(0, 1)
    )
    np.testing.assert_allclose(info["mean"], out.mean, rtol=0, atol=tol)
    np.testing.assert_allclose(info["value"], out.value, rtol=0, atol=tol)
    assert float(jnp.abs(out.value).max()) > 0.1
    assert int(carry["pos"]) == T
    # the counter says which row the decode ran on
    assert float(stats["cache_heads_per_row"]) == heads_a_row
    assert carry["cache"]["ring"][0]["v"].shape == (B, WINDOW, *kv_row)
    # and the window matters here: ignoring it moves the outputs
    wide = _learner(T, precision, sliding_window=T, **encoder)
    other = wide.model.apply(
        state.params, learner._norm_obs(state.obs_stats, obs).swapaxes(0, 1)
    )
    assert float(jnp.abs(other.value - out.value)[:, WINDOW:].max()) > 3 * tol


def _attend_plain(q, k, v, valid):
    """A head a row, ``k, v [B, S, G, hd]``: the decode's attention as it
    was before rows were shared, kept here as the packed form's reference."""
    B, H, hd = q.shape
    G = k.shape[2]
    q = q.reshape(B, G, H // G, hd)
    scores = jnp.einsum(
        "bgrd,bkgd->bgrk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(hd))
    prob = jax.nn.softmax(
        jnp.where(valid, scores, ssm_hybrid._NEG_BIG), axis=-1
    )
    out = jnp.einsum(
        "bgrk,bkgd->bgrd", prob.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype).reshape(B, H, hd)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G,hd,H,p", [
    (2, 64, 4, 2), (6, 64, 12, 2), (4, 32, 8, 4),       # rows of 128 lanes
    (3, 64, 6, 1), (2, 128, 4, 1), (2, 8, 4, 1),        # a head a row
])
def test_heads_sharing_a_row_attend_bit_for_bit_as_a_head_a_row(G, hd, H, p, dtype):
    """The query in its own head's lanes of a zero row adds exact zeros to
    the same sums: the packed read IS the unpacked one on the CPU, in both
    dtypes; a geometry that does not fill 128 lanes keeps a head a row."""
    assert ssm_hybrid.heads_per_row(G, hd) == p
    S, keys = 24, jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (B, H, hd), dtype)
    k, v = (jax.random.normal(key, (B, S, G, hd), dtype) for key in keys[1:])
    valid = jnp.arange(S) <= 17
    row = lambda x: x.reshape(B, S, G // p, p * hd)
    got = jax.jit(ssm_hybrid._attend_one)(q, row(k), row(v), valid)
    want = jax.jit(_attend_plain)(q, k, v, valid)
    assert got.dtype == want.dtype and got.shape == (B, H, hd)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.1
    # a masked slot counts for nothing, whichever row holds it
    moved = jax.jit(ssm_hybrid._attend_one)(
        q, row(k.at[:, 18:].add(1)), row(v.at[:, 18:].add(1)), valid
    )
    np.testing.assert_array_equal(
        np.asarray(moved, np.float32), np.asarray(got, np.float32)
    )


@pytest.mark.parametrize("row", ROWS)
def test_the_carry_holds_three_kinds_of_state_side_by_side(row):
    encoder, _, kv_row = ROWS[row]
    width = {**TOY, **encoder}["hidden_size"]
    learner = _learner(T, "mixed", **encoder)
    cache = learner.act_init(B)["cache"]
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype.name), cache)
    kv = lambda slots: {
        n: ((B, slots, *kv_row), "bfloat16") for n in ("k", "v")
    }
    assert shapes == {
        "ssm": [{
            "state": ((B, 4, 2 * width), "float32"),
            "conv": ((B, 3, 2 * width), "bfloat16"),
        }] * 2,
        "ring": [kv(WINDOW)],
        "shared": kv(T),
    }
    # the ring is the window's size whatever the horizon; a horizon inside
    # the window needs no more slots than it has positions
    ring = lambda horizon: _learner(horizon, **encoder).act_init(B)["cache"]["ring"]
    assert ring(64)[0]["k"].shape[1] == WINDOW
    assert ring(2)[0]["k"].shape[1] == 2


def test_a_wrap_zeroes_the_recurrent_leaves_and_leaves_the_rest():
    """The step after the horizon is position 0 of a fresh segment: the
    state-space leaves restart from zero (they have no position a mask
    could hide), the ring and the shared cache keep their stale rows, which
    the position masks."""
    learner = _learner(T, "f32")
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (T, B, 5), jnp.float32)
    info, full = _act(learner, state, obs)
    assert float(jnp.abs(full["cache"]["ssm"][0]["state"]).max()) > 0
    wrapped_info, wrapped = _act(learner, state, obs[:1], carry=full)
    fresh_info, fresh = _act(learner, state, obs[:1])
    assert int(wrapped["pos"]) == 1
    for a, b in zip(jax.tree.leaves(wrapped_info), jax.tree.leaves(fresh_info)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    for a, b in zip(
        jax.tree.leaves(wrapped["cache"]["ssm"]), jax.tree.leaves(fresh["cache"]["ssm"])
    ):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)
    # slot 0 was written; every other row is what the full segment left
    for kind in ("ring", "shared"):
        for a, b in zip(
            jax.tree.leaves(wrapped["cache"][kind]), jax.tree.leaves(full["cache"][kind])
        ):
            np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
    # reset_recurrent itself: the marked leaves alone, and only on a wrap
    cache = full["cache"]
    same = ssm_hybrid.reset_recurrent(cache, jnp.asarray(False))
    zeroed = ssm_hybrid.reset_recurrent(cache, jnp.asarray(True))
    assert all(
        bool((a == b).all())
        for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(cache))
    )
    assert all(float(jnp.abs(x).max()) == 0 for x in jax.tree.leaves(zeroed["ssm"]))
    assert zeroed["ring"] is cache["ring"] and zeroed["shared"] is cache["shared"]


def test_other_families_carry_is_untouched_at_a_wrap():
    from surreal_tpu.models.attention import reset_recurrent

    cache = [{"k": jnp.ones((2, 3))}]
    for block in ("preln", "mla_moe"):
        assert reset_recurrent({"block": block}, cache, jnp.asarray(True)) is cache


# -- the learn pass ---------------------------------------------------------------

def _batch(learner, state, seed=3):
    """A rollout's batch from ``act_step`` itself on random observations."""
    obs = jax.random.normal(jax.random.key(seed), (T, B + 1, 5), jnp.float32)
    carry = learner.act_init(B + 1)
    rows = []
    for t in range(T):
        action, info, carry = learner.act_step(
            state, carry, obs[t], jax.random.key(100 + t)
        )
        rows.append((action, info))
    action, info = jax.tree.map(lambda *x: jnp.stack(x), *rows)
    reward = jax.random.normal(jax.random.key(seed + 1), (T, B + 1))
    return {
        "obs": obs, "next_obs": jnp.roll(obs, -1, 0), "action": action,
        "reward": reward, "done": jnp.zeros((T, B + 1), bool),
        "terminated": jnp.zeros((T, B + 1), bool),
        "behavior_logp": info["logp"],
        "behavior": {"mean": info["mean"], "log_std": info["log_std"]},
    }


@pytest.mark.parametrize("precision,tol", [("f32", 1e-4), ("mixed", 5e-2)])
def test_ppo_first_epoch_ratio_is_one(precision, tol):
    """Acting and the learn pass condition alike: the log-probability the
    loss recomputes over whole segments is the behaviour's, so the ratio of
    the first minibatch step is 1 within the precision's tolerance."""
    learner = _learner(T, precision)
    state = learner.init(jax.random.key(0))
    batch = _batch(learner, state)
    _, _, _, _, data, stats = jax.jit(
        lambda s, b: learner._prepare_seq(s, b, None)
    )(state._replace(obs_stats=state.obs_stats), batch)
    out, _ = learner._apply(
        state.params,
        learner._norm_obs(state.obs_stats, batch["obs"]).swapaxes(0, 1),
    )
    from surreal_tpu.ops import distributions as D

    logp = D.diag_gauss_logp(out.mean, out.log_std, batch["action"].swapaxes(0, 1))
    ratio = jnp.exp(logp - batch["behavior_logp"].swapaxes(0, 1))
    # (the obs filter is off the comparison: the state's statistics are
    # init's in both, as a first iteration's acting has them)
    assert float(jnp.abs(ratio - 1).max()) < tol
    assert set(stats) == set(ssm_hybrid.COUNTERS)
    # the extended pass has T + 1 positions
    assert float(stats["window_keys_mean"]) == pytest.approx(
        ref.window_keys_mean(T + 1, WINDOW)
    )


def test_learn_reports_the_three_counters():
    learner = _learner(T, "mixed")
    state = learner.init(jax.random.key(0))
    new, metrics = jax.jit(learner.learn)(
        state, _batch(learner, state), jax.random.key(1)
    )
    assert float(metrics["attn/window_keys_mean"]) == pytest.approx(
        ref.window_keys_mean(T, WINDOW)
    )
    assert 0 < float(metrics["ssm/state_abs_max"]) < 1e3
    assert float(metrics["attn/cache_heads_per_row"]) == 1.0    # heads of 8
    # the CPU runs the ``lax`` form of the scan at any width
    assert float(metrics["ssm/scan_in_vmem"]) == 0.0
    assert float(metrics["health/update_ratio"]) > 0
    assert "moe/overflow" not in metrics


def test_recomputation_leaves_loss_and_gradients_equal(monkeypatch):
    learner = _learner(T, "f32")
    state = learner.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (B, T, 5), jnp.float32)

    def loss(params):
        out = learner.model.apply(params, obs)
        return (out.value ** 2).mean() + (out.mean ** 2).mean()

    assert ssm_hybrid.residual_bytes(CFG, B * T) < ssm_hybrid.REMAT_ABOVE_BYTES
    plain = jax.jit(jax.value_and_grad(loss))(state.params)
    plain_text = str(jax.make_jaxpr(jax.grad(loss))(state.params))
    monkeypatch.setattr(ssm_hybrid, "REMAT_ABOVE_BYTES", 0)
    again = jax.jit(jax.value_and_grad(loss))(state.params)
    remat_text = str(jax.make_jaxpr(jax.grad(loss))(state.params))
    # (attention's blocks of queries are checkpoints in both)
    assert remat_text.count("remat2") >= plain_text.count("remat2") + 6
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(again[1]), jax.tree.leaves(plain[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_recomputation_is_chosen_from_the_shapes():
    """At the published widths a minibatch of the cell (8 envs x 1024)
    recomputes and an acting batch's worth of tokens does not."""
    cfg = ssm_hybrid.resolve(dict(TOY_PUBLISHED))
    assert ssm_hybrid.residual_bytes(cfg, 8 * 1024) > ssm_hybrid.REMAT_ABOVE_BYTES
    assert ssm_hybrid.residual_bytes(cfg, 16) < ssm_hybrid.REMAT_ABOVE_BYTES


TOY_PUBLISHED = dict(
    kind="trajectory", block="ssm_hybrid", num_heads=40,
    pairs_before=1, pairs_after=1,
)


def test_published_widths_count_633m_by_kind():
    """``eval_shape`` of the learner's own init at the published widths
    (unset keys take them) against the count by hand in the issue."""
    spec = EnvSpecs(
        obs=ArraySpec(shape=(17,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
    )
    learner = build_learner(
        Config(
            algo=Config(name="ppo", horizon=1024, precision="mixed"),
            model=Config(encoder=Config(**TOY_PUBLISHED)),
        ), spec,
    )
    state = jax.eval_shape(learner.init, jax.random.key(0))
    trunk = state.params["params"]["trunk"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    by_layer = [count(trunk[f"layer{i}"]) for i in range(6)]
    assert [round(n / 1e6, 1) for n in by_layer] == [
        119.9, 98.3, 119.9, 98.3, 104.9, 91.8,
    ]
    # the six to the parameter (the issue adds the rounded six to 633.2)
    assert sum(by_layer) == 633_047_040
    mixer = trunk["layer0"]["mixer"]
    assert {k: tuple(v.shape) for k, v in mixer.items()} == {
        "in_proj": (2560, 10240), "conv": (4, 5120), "conv_bias": (5120,),
        "x_proj": (5120, 192), "dt_proj": (160, 5120), "dt_bias": (5120,),
        "A_log": (5120, 16), "D": (5120,), "out_proj": (5120, 2560),
    }
    assert tuple(trunk["layer1"]["mixer"]["k"].shape) == (2560, 20, 64)
    assert tuple(trunk["layer5"]["mixer"]["q"].shape) == (2560, 40, 64)
    assert set(trunk["layer5"]["mixer"]) == {"q", "o"}
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(state.params))
    total = count(state.params)
    assert total == ref.parameters(
        manifest.load_config("ppo_lift_phi4flash")["widths"]
    )["total"]
    # the acting carry of 16 envs x 1024: 2 x 5.7 + 42 + 84 MB
    carry = jax.eval_shape(lambda: learner.act_init(16))
    nbytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(carry["cache"])
    )
    assert round(nbytes / 1e6) == 137


def test_init_is_mambas():
    learner = _learner()
    mixer = learner.init(jax.random.key(0)).params["params"]["trunk"]["layer0"]["mixer"]
    np.testing.assert_allclose(
        jnp.exp(mixer["A_log"]), jnp.broadcast_to(jnp.arange(1.0, 5.0), (64, 4)),
        rtol=1e-6,
    )
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 1e-1 * 1.001
    assert float(mixer["D"].min()) == 1.0 == float(mixer["D"].max())


# -- what the family refuses ------------------------------------------------------

@pytest.mark.parametrize("encoder,match", [
    (dict(TOY, features=128), "'ssm_hybrid' does not read"),
    (dict(TOY, num_layers=6), "'ssm_hybrid' does not read"),
    (dict(TOY, kv_lora_rank=16), "'ssm_hybrid' does not read"),
    (dict(kind="trajectory", sliding_window=4), "'preln' does not read"),
    (dict(kind="trajectory", block="mla_moe", num_heads=2, ssm_state_size=4),
     "'mla_moe' does not read"),
    (dict(TOY, num_kv_heads=3), "multiple of num_kv_heads"),
])
def test_a_key_of_another_family_is_an_error(encoder, match):
    cfg = Config(
        algo=Config(name="ppo", horizon=8), model=Config(encoder=Config(**encoder))
    )
    with pytest.raises(ValueError, match=match):
        build_learner(cfg, SPECS)


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
    with pytest.raises(ValueError, match="'ssm_hybrid' runs flat vector obs"):
        build_learner(with_stem, pixels)
    learner = _learner(8)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="no sp mesh path"):
        build_seq_model(
            learner.config.model, SPECS, -0.5, mesh=mesh, horizon=8,
        )
    with pytest.raises(ValueError, match="'ssm_hybrid' is wired into PPO alone"):
        build_learner(
            Config(
                algo=Config(name="impala", horizon=8),
                model=Config(encoder=Config(**TOY)),
            ), SPECS,
        )


# -- parts ------------------------------------------------------------------------

HYBRID_PARTS = {"ssm_scan", "ssm_proj", "gmu", "attn", "dense_ffn", "optimizer"}


@pytest.fixture(scope="module")
def learn_program_parts():
    """``{instruction: part}`` and ``{instruction: phase}`` of a compiled
    learn step of the family."""
    from surreal_tpu.session.profile import hlo_op_phases
    from surreal_tpu.utils.phases import part_of

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
    return hlo_op_phases(text, part_of)[1], hlo_op_phases(text)[1]


def test_the_compiled_program_names_the_familys_parts(learn_program_parts):
    parts, phases = learn_program_parts
    assert set(parts.values()) == HYBRID_PARTS
    scan = [phases[i] for i, p in parts.items() if p == "ssm_scan" and i in phases]
    assert {"prepare", "sgd"} <= set(scan)


def test_diag_prints_the_new_parts_with_the_old(learn_program_parts):
    """The table ``surreal_tpu diag`` prints for a session of the family:
    a digest reduced from ops named as the compiled program names them has
    the ``ssm_scan`` row beside ``attn`` and ``dense_ffn``."""
    from surreal_tpu.session.profile import reduce_digest
    from surreal_tpu.session.telemetry import _digest_lines

    parts, phases = learn_program_parts
    ops = [
        (10 * n, 10 * n + 10, name, phases.get(name, "sgd"), part)
        for n, (name, part) in enumerate(sorted(parts.items()))
    ]
    digest = reduce_digest({"/device:TPU:0": ops}, [], steps=1)
    assert set(digest["parts"]) >= HYBRID_PARTS
    text = "\n".join(_digest_lines({"digest": digest}))
    assert "model part" in text
    for name in HYBRID_PARTS:
        assert any(line.split()[:1] == [name] for line in text.splitlines()), name
