import pytest

from surreal_tpu.session.config import REQUIRED, Config, ConfigError
from surreal_tpu.session.default_configs import base_config


def test_attribute_access_nested():
    c = Config(a=1, b={"c": 2, "d": {"e": 3}})
    assert c.a == 1
    assert c.b.c == 2
    assert c.b.d.e == 3
    c.b.d.e = 7
    assert c["b"]["d"]["e"] == 7


def test_extend_merges_defaults():
    base = Config(lr=1e-3, model={"hidden": (64, 64), "act": "tanh"})
    out = Config(model={"act": "relu"}).extend(base)
    assert out.lr == 1e-3
    assert out.model.hidden == (64, 64)
    assert out.model.act == "relu"
    # base untouched
    assert base.model.act == "tanh"


def test_extend_required_enforced():
    base = Config(name=REQUIRED, x=1)
    with pytest.raises(ConfigError, match="name"):
        Config(x=2).extend(base)
    out = Config(name="ppo").extend(base)
    assert out.name == "ppo"


def test_extend_rejects_scalar_over_dict():
    base = Config(model={"hidden": 64})
    with pytest.raises(ConfigError):
        Config(model=5).extend(base)


def test_dotlist_override():
    c = Config(a={"b": 1}, x="s")
    c.override_from_dotlist(["a.b=2", "x=hello", "new.key=[1,2]"])
    assert c.a.b == 2
    assert c.x == "hello"
    assert c.new.key == [1, 2]


def test_base_config_trees_exist():
    cfg = base_config()
    assert "learner_config" in cfg
    assert "env_config" in cfg
    assert "session_config" in cfg
    assert cfg.session_config.topology.mesh.dp == -1


def test_flatten():
    c = Config(a={"b": 1, "c": {"d": 2}})
    assert c.flatten() == {"a.b": 1, "a.c.d": 2}


@pytest.mark.parametrize(
    "algo, key",
    [("ppo", "gae_impl"), ("impala", "vtrace_impl"), ("ppo", "autotune")],
    ids=["ppo-gae_impl", "impala-vtrace_impl", "ppo-autotune"],
)
def test_a_left_over_key_is_inert(algo, key):
    """A key the program no longer reads may still sit in a saved session's
    config or a benchmark cell's overrides (``Config.extend`` keeps a key
    the defaults do not have): the learner builds, and ``learn`` lowers to
    the program it lowers to without the key."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from surreal_tpu.envs.base import ArraySpec, EnvSpecs
    from surreal_tpu.learners import build_learner

    specs = EnvSpecs(
        obs=ArraySpec(shape=(6,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
    )
    T, B = 4, 2
    batch = {
        "obs": jnp.zeros((T, B, 6)), "next_obs": jnp.zeros((T, B, 6)),
        "action": jnp.zeros((T, B, 3)), "reward": jnp.zeros((T, B)),
        "done": jnp.zeros((T, B), bool), "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.zeros((T, B)),
        "behavior": {"mean": jnp.zeros((T, B, 3)),
                     "log_std": jnp.zeros((T, B, 3))},
    }

    def lowered(**left_over):
        learner = build_learner(
            Config(algo=Config(name=algo, **left_over)), specs
        )
        assert all(learner.config.algo[k] == v for k, v in left_over.items())
        state = learner.init(jax.random.key(0))
        return jax.jit(learner.learn).lower(
            state, batch, jax.random.key(1)
        ).as_text()

    assert lowered(**{key: "a-value-nothing-reads"}) == lowered()
