"""Test harness: simulate an 8-device mesh on CPU.

Must set XLA flags BEFORE jax initializes (SURVEY.md §4): every
pmap/shard_map collective path is unit-testable this way without TPU
hardware. Bench and production run on real TPU; tests are platform-CPU.
"""

import os

# Tests run on the local CPU with eight simulated devices. The tier-1
# command sets JAX_PLATFORMS=cpu; the config update below makes the same
# choice for a bare `pytest`, and is the explicit CPU selection that lets
# CLI runs with the default session_config.backend='tpu' through
# (main/launch.py::_require_platform).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# JAX's own switch, inherited by every child the tests spawn: the suite
# neither reads nor writes the checkout's persistent compile cache
# (utils/compat.py::enable_compile_cache), so a run never depends on what
# an earlier run left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

assert jax.devices()[0].platform == "cpu", (
    f"tests must run on simulated CPU devices, got {jax.devices()}"
)
assert jax.device_count() == 8, (
    f"expected 8 simulated devices, got {jax.device_count()} "
    "(XLA_FLAGS was read before conftest could set it?)"
)

import pytest  # noqa: E402

# Cases a file of the benchmark gets wrong, until a `benchmark` PR may edit
# it (tests/benchmarks/ is in BENCHMARK.json's `paths`; no other kind of PR
# touches what is there): node id -> why.
KNOWN_WRONG: dict = {}


def pytest_collection_modifyitems(config, items):
    for item in items:
        why = KNOWN_WRONG.get(item.nodeid)
        if why is not None:
            item.add_marker(pytest.mark.xfail(reason=why))


@pytest.fixture
def compile_cache_on(tmp_path, monkeypatch):
    """The suite runs with JAX's cache switch off (above); a test that takes
    this turns it on with the checkout's fixed path moved under tmp_path, and
    put everything back. reset_cache() on both sides: JAX latches
    whether the cache is used at the process's first compile, and keeps
    an initialised cache object serving its old directory."""
    from jax.experimental.compilation_cache.compilation_cache import reset_cache

    from surreal_tpu.utils import compat

    fixed = str(tmp_path / "fixed_cache")
    monkeypatch.setattr(compat, "_CACHE_DIR", fixed)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = {
        k: getattr(jax.config, k)
        for k in (
            "jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_traceback_in_locations_limit",
            "jax_hlo_source_file_canonicalization_regex",
        )
    }
    jax.config.update("jax_enable_compilation_cache", True)
    reset_cache()
    try:
        yield fixed
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        reset_cache()


@pytest.fixture
def assert_same_update():
    """``check(base, variant)`` over two ``(metrics, params)`` records of one
    fused iteration whose programs differ in shape only (an unroll key, the
    batched replay draw). An unrolled scan is the same arithmetic, but XLA
    fuses the unrolled bodies differently (reordered f32 reductions), and
    one iteration chains several Adam updates, so ulp-level reorder noise
    grows to 0.1-0.5% on grad-norm scalars (measured on this image):
    metrics to rtol 5e-3 / atol 1e-3. Params are compared absolutely: Adam's
    step is about lr for every coordinate, so a reorder of a near-zero
    gradient can flip a coordinate's direction, and after k chained updates
    |delta| <= about 2*lr*k (ppo 3e-4 x 4, ddpg 1e-3 x 4): atol 1e-2."""
    import numpy as np

    def check(base, variant):
        (base_m, base_p), (var_m, var_p) = base, variant
        assert base_m.keys() == var_m.keys()
        for k in base_m:
            if not (np.isnan(base_m[k]).all() and np.isnan(var_m[k]).all()):
                np.testing.assert_allclose(
                    base_m[k], var_m[k], rtol=5e-3, atol=1e-3, err_msg=k
                )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=0.0, atol=1e-2),
            base_p, var_p,
        )

    return check


@pytest.fixture
def collect_scan_trips(tmp_path):
    """``trips(algo, env, model=None, **algo_over)``: the trips of the rollout
    scan's loop in the LOWERED fused iteration of a toy trainer (8 envs x
    horizon 8): the bound of the ``while`` that ``lax.scan`` lowers to under
    scope ``collect``. A scan of ``horizon`` steps at unroll u is a loop of
    ``horizon // u`` trips over a body of u steps."""
    import re

    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    def trips(algo, env, model=None, **algo_over):
        learner = {"algo": Config(name=algo, horizon=8, **algo_over)}
        if model is not None:
            learner["model"] = model
        trainer = Trainer(
            Config(
                learner_config=Config(**learner),
                env_config=Config(name=env, num_envs=8),
                session_config=Config(folder=str(tmp_path)),
            ).extend(base_config())
        )
        key = jax.random.key(0)
        text = jax.jit(trainer._device_train_iter).lower(
            jax.eval_shape(trainer.learner.init, key),
            jax.eval_shape(trainer.init_loop_state, key),
            key,
        ).as_text(debug_info=True)
        (loc,) = re.findall(
            r'(#loc\d+) = loc\("[^"]*/collect/while/cond/lt"', text
        )
        (bound,) = re.findall(
            r"dense<(\d+)> : tensor<i32>[^\n]*\n\s*%\d+ = stablehlo\.compare\s+"
            r"LT,[^\n]*loc\(" + loc + r"\)\n",
            text,
        )
        return int(bound)

    return trips
