"""Model layer shape/dtype/init tests (SURVEY.md §4 unit-test plan)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.models import (
    CategoricalPPOModel,
    DDPGActor,
    DDPGCritic,
    PPOModel,
)
from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG


def model_cfg(**overrides):
    cfg = BASE_LEARNER_CONFIG.model
    from surreal_tpu.session.config import Config

    return Config(overrides).extend(cfg) if overrides else cfg


def test_ppo_model_shapes_and_dtypes():
    model = PPOModel(model_cfg=model_cfg(), act_dim=6)
    obs = jnp.zeros((32, 17))
    params = model.init(jax.random.key(0), obs)
    out = jax.jit(model.apply)(params, obs)
    assert out.mean.shape == (32, 6)
    assert out.log_std.shape == (32, 6)
    assert out.value.shape == (32,)
    # heads must be float32 regardless of bfloat16 compute
    assert out.mean.dtype == jnp.float32
    assert out.value.dtype == jnp.float32
    # params stored in float32
    leaves = jax.tree.leaves(params)
    assert all(l.dtype == jnp.float32 for l in leaves)


def test_ppo_model_cnn_pixels():
    cfg = model_cfg(cnn={"enabled": True})
    model = PPOModel(model_cfg=cfg, act_dim=4)
    obs = jnp.zeros((8, 84, 84, 12), jnp.uint8)  # frame-stacked pixels
    params = model.init(jax.random.key(0), obs)
    out = model.apply(params, obs)
    assert out.mean.shape == (8, 4)
    assert out.value.shape == (8,)


def test_categorical_model():
    model = CategoricalPPOModel(model_cfg=model_cfg(), n_actions=2)
    obs = jnp.zeros((16, 4))
    params = model.init(jax.random.key(0), obs)
    out = model.apply(params, obs)
    assert out.logits.shape == (16, 2)
    assert out.value.shape == (16,)


def test_ddpg_actor_bounds():
    model = DDPGActor(model_cfg=model_cfg(activation="relu"), act_dim=3)
    obs = jax.random.normal(jax.random.key(1), (64, 10)) * 100.0
    params = model.init(jax.random.key(0), obs)
    act = model.apply(params, obs)
    assert act.shape == (64, 3)
    assert bool(jnp.all(jnp.abs(act) <= 1.0))


def test_ddpg_critic_action_injection():
    model = DDPGCritic(model_cfg=model_cfg(activation="relu"))
    obs = jnp.zeros((64, 10))
    act = jnp.zeros((64, 3))
    params = model.init(jax.random.key(0), obs, act)
    q = model.apply(params, obs, act)
    assert q.shape == (64,)
    # Q must actually depend on the action (mid-network injection wired up)
    q2 = model.apply(params, obs, jnp.ones_like(act))
    assert not np.allclose(np.asarray(q), np.asarray(q2))


def test_ppo_model_works_under_vmap_scan():
    """Acting path: model must trace under vmap+scan (SEED-style rollout)."""
    model = PPOModel(model_cfg=model_cfg(), act_dim=2)
    obs = jnp.zeros((4, 8))
    params = model.init(jax.random.key(0), obs)

    def step(carry, _):
        out = model.apply(params, carry)
        return carry, out.value

    _, values = jax.lax.scan(step, obs, None, length=3)
    assert values.shape == (3, 4)


@pytest.mark.slow
def test_trajectory_encoder_sp_matches_single_device():
    """The sequence-parallel seam is transparent: TrajectoryEncoder with a
    4-way sp mesh (ring attention, T sharded) must produce the same output
    and gradients as the single-device full-attention path."""
    import numpy as np
    from jax.sharding import Mesh

    from surreal_tpu.models.attention import TrajectoryEncoder

    B, T, obs_dim = 2, 32, 10
    rng = np.random.default_rng(31)
    obs = jnp.asarray(rng.normal(size=(B, T, obs_dim)), jnp.float32)

    # f32 compute so the comparison isolates the parallelism, not bf16
    single = TrajectoryEncoder(compute_dtype=jnp.float32)
    params = single.init(jax.random.key(0), obs)
    out_single = single.apply(params, obs)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sp",))
    sharded = TrajectoryEncoder(mesh=mesh, compute_dtype=jnp.float32)
    out_sharded = sharded.apply(params, obs)  # same params: same module tree
    np.testing.assert_allclose(
        np.asarray(out_sharded), np.asarray(out_single), rtol=2e-5, atol=2e-5
    )

    # gradients flow through the ring path and match
    def loss(p, enc):
        return (enc.apply(p, obs) ** 2).sum()

    g_single = jax.grad(loss)(params, single)
    g_sharded = jax.grad(loss)(params, sharded)
    for a, b in zip(jax.tree.leaves(g_single), jax.tree.leaves(g_sharded)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=5e-4
        )


def test_ring_batch_indivisible_learn_shape_raises():
    """Round-5 review: on a dp x sp mesh, a NON-trivial batch (B>1, T>1)
    that does not divide the batch axis must raise instead of silently
    replicating (the quiet perf cliff); the known tiny-batch callers —
    init's [1, 1, obs] dummy and the evaluator's B=1 episode — still fall
    back to replication. Model-side twin of the Trainer's
    check_dp_divisible."""
    import numpy as np
    import pytest
    from jax.sharding import Mesh

    from surreal_tpu.models.attention import TrajectoryEncoder

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
    enc = TrajectoryEncoder(
        mesh=mesh, batch_axis="dp", compute_dtype=jnp.float32
    )
    obs_ok = jnp.zeros((1, 1, 10), jnp.float32)  # init dummy: replicates
    params = enc.init(jax.random.key(0), obs_ok)
    enc.apply(params, jnp.zeros((1, 8, 10), jnp.float32))  # B=1 eval: ok
    with pytest.raises(ValueError, match="not divisible"):
        enc.apply(params, jnp.zeros((3, 8, 10), jnp.float32))  # 3 % 2 != 0
    # acting callers (padded act over an eval batch of any width) opt into
    # the replication fallback explicitly — seq_policy.py passes this
    enc.apply(params, jnp.zeros((3, 8, 10), jnp.float32), replicate_ok=True)


def test_trajectory_encoder_is_causal():
    """Changing a LATER timestep must not change earlier outputs."""
    import numpy as np

    from surreal_tpu.models.attention import TrajectoryEncoder

    B, T, obs_dim = 1, 16, 6
    rng = np.random.default_rng(32)
    obs = jnp.asarray(rng.normal(size=(B, T, obs_dim)), jnp.float32)
    enc = TrajectoryEncoder(compute_dtype=jnp.float32)
    params = enc.init(jax.random.key(1), obs)
    out = enc.apply(params, obs)
    obs2 = obs.at[:, T - 1].set(obs[:, T - 1] + 10.0)
    out2 = enc.apply(params, obs2)
    np.testing.assert_allclose(
        np.asarray(out2[:, : T - 1]), np.asarray(out[:, : T - 1]),
        rtol=1e-5, atol=1e-5,
    )
    assert not np.allclose(np.asarray(out2[:, T - 1]), np.asarray(out[:, T - 1]))


def _nature_cnn():
    from surreal_tpu.models import NatureCNN

    return NatureCNN(compute_dtype=jnp.float32)


def _frames(shape, dtype):
    x = jax.random.randint(jax.random.key(1), shape, 0, 256)
    return x.astype(jnp.uint8) if dtype == jnp.uint8 else x / 255.0


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.float32], ids=["uint8", "float32"])
@pytest.mark.parametrize("T,B", [(1, 3), (4, 2), (3, 1)])
def test_nature_cnn_over_a_rollout_is_the_flattened_pass(T, B, dtype):
    """A rank-5 ``[T, B, H, W, C]`` input keeps its leading axes through
    the three convolutions (``_FramesConv``: ``B`` the batch, ``T`` a
    spatial axis of kernel 1). Same parameters, same products, same sums
    as the ``[T*B, H, W, C]`` pass: outputs and parameter gradients."""
    cnn = _nature_cnn()
    x = _frames((T, B, 84, 84, 4), dtype)
    flat = x.reshape(T * B, 84, 84, 4)
    params = cnn.init(jax.random.key(0), flat)
    w = jax.random.normal(jax.random.key(2), (T, B, 512))

    def loss(p, frames):
        y = cnn.apply(p, frames)
        return (y.reshape(T, B, 512) * w).sum(), y

    (_, y5), g5 = jax.value_and_grad(loss, has_aux=True)(params, x)
    (_, y4), g4 = jax.value_and_grad(loss, has_aux=True)(params, flat)
    assert y5.shape == (T, B, 512)
    np.testing.assert_allclose(y5, y4.reshape(T, B, 512), rtol=1e-5, atol=1e-5)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g5), jax.tree.leaves(g4)
    ):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(
            a, b, atol=1e-5 * scale, rtol=1e-4, err_msg=jax.tree_util.keystr(path)
        )


def test_nature_cnn_tree_is_the_same_from_either_rank():
    """``init`` over a rollout yields the tree (paths, shapes, values) that
    ``init`` over a batch of frames does, so a checkpoint written by one
    restores into the other."""
    cnn = _nature_cnn()
    p4 = cnn.init(jax.random.key(0), jnp.zeros((2, 84, 84, 4), jnp.uint8))
    p5 = cnn.init(jax.random.key(0), jnp.zeros((3, 2, 84, 84, 4), jnp.uint8))
    shapes = {
        jax.tree_util.keystr(k): v.shape
        for k, v in jax.tree_util.tree_leaves_with_path(p4)
    }
    assert shapes == {
        "['params']['Conv_0']['bias']": (32,),
        "['params']['Conv_0']['kernel']": (8, 8, 4, 32),
        "['params']['Conv_1']['bias']": (64,),
        "['params']['Conv_1']['kernel']": (4, 4, 32, 64),
        "['params']['Conv_2']['bias']": (64,),
        "['params']['Conv_2']['kernel']": (3, 3, 64, 64),
        "['params']['Dense_0']['bias']": (512,),
        "['params']['Dense_0']['kernel']": (3136, 512),
    }
    assert jax.tree.structure(p4) == jax.tree.structure(p5)
    for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p5)):
        np.testing.assert_array_equal(a, b)
