"""Measurement-driven search over the declared candidate space.

Timing discipline: warmup calls absorb XLA compilation, and
the measured window is a CHAINED loop (each iteration consumes the
previous state) fenced by ``jax.block_until_ready`` on its last outputs.
Candidates are timed through the REAL
fused trainer programs (``Trainer._train_iter`` /
``OffPolicyTrainer._device_train_iter``), not proxies, so the winner is
the winner of the program that will actually run.

Search strategy: greedy coordinate descent in the space's declared order
— measure the static default as the incumbent, then walk one dimension at
a time, adopting a candidate only when it beats the incumbent by
``min_gain`` (2% default; below that is window-to-window noise and the
default keeps the compile-cache-warm program). A full cartesian sweep of
the PPO space would be ~72 compiles; the greedy walk is ~12 and each
adopted knob compounds into the later dimensions' baseline.
"""

from __future__ import annotations

import copy
import sys
import time

from surreal_tpu.tune.cache import TuningCache, resolve_tuning_cache_dir
from surreal_tpu.tune.fingerprint import workload_fingerprint
from surreal_tpu.tune.space import candidate_space, skip_dimension

WARMUP = 2       # compile + first-dispatch absorption (unmeasured)
ITERS = 8        # measured chained iterations per candidate
MIN_GAIN = 0.02  # adoption threshold vs the incumbent (noise floor)

# The dims that live inside the jitted LEARN program alone — the search
# surface for HOST-env workloads (gym/dm_control/SEED), whose rollout is
# host python with no device scan to unroll. The learn program is a
# device computation regardless of where the envs live, so these knobs
# are measurable (and cacheable) for host fingerprints too. precision
# and vtrace_impl qualify: the policy's dtypes and the V-trace kernel
# both live inside the jitted learn.
LEARN_PHASE_DIMS = (
    "gae_impl", "gae_unroll", "sgd_unroll", "shuffle",
    "precision", "vtrace_impl",
)


def search_space_for(config, extended_learner_config) -> list[tuple[str, list]]:
    """The dims :func:`tune_workload` will search for this workload: the
    full declared space for device (``jax:*``) envs, the learn-phase
    subset for host envs. Empty means the workload has nothing searchable
    (e.g. host-env DDPG: its update loop runs as individual jitted learns
    from a host loop) — callers treat that as 'stay on defaults'."""
    space = candidate_space(extended_learner_config)
    if not str(config.env_config.name).startswith("jax:"):
        if extended_learner_config.algo.name == "ddpg":
            # host-env DDPG stays unsearchable even though 'precision'
            # is a learn-phase dim: its update loop runs as individual
            # jitted learns over n-step REPLAY batches, which the
            # synthetic learn-batch harness (_synthetic_learn_batch,
            # PPO/IMPALA trajectory contract) cannot fabricate
            return []
        space = [(n, v) for n, v in space if n in LEARN_PHASE_DIMS]
    return space


def _candidate_config(config, point: dict):
    """A deep-copied config bundle with the candidate knobs pinned and the
    autotuner disabled (the measured trainer must not recurse into the
    cache it is populating)."""
    from surreal_tpu.session.config import Config

    cfg = copy.deepcopy(config)
    algo = cfg.learner_config.get("algo", None)
    if algo is None:
        cfg.learner_config.algo = Config()
        algo = cfg.learner_config.algo
    for k, v in point.items():
        algo[k] = v
    algo["autotune"] = "off"
    return cfg


def _measure_onpolicy(cfg, warmup: int, iters: int) -> float:
    """ms/iter of the fused on-policy iteration (PPO / IMPALA)."""
    import jax

    from surreal_tpu.launch.trainer import Trainer

    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    if trainer.mesh is not None and trainer.mesh.size > 1:
        from surreal_tpu.parallel.mesh import replicate_state

        state = replicate_state(trainer.mesh, state)
    carry = trainer.init_loop_state(env_key)
    metrics = None
    for _ in range(warmup):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    return (time.perf_counter() - t0) / iters * 1e3


def _measure_offpolicy(cfg, warmup: int, iters: int) -> float:
    """ms/iter of the fused off-policy iteration (DDPG).

    The measurement copy caps ``replay.start_sample_size`` at one chunk so
    the timed window exercises the ``updates_per_iter`` loop (otherwise a
    large start gate would time rollout-only iterations and the update
    knobs would measure as no-ops); the gate is a traced ``lax.cond``
    predicate, so the compiled program is identical to production's.
    """
    import jax
    import jax.numpy as jnp

    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
    from surreal_tpu.session.config import Config

    steps_per_chunk = int(cfg.env_config.num_envs) * int(
        cfg.learner_config.algo.get("horizon", 16)
    )
    cfg = Config(
        learner_config=Config(
            replay=Config(start_sample_size=min(1000, steps_per_chunk)),
        )
    ).extend(cfg)
    trainer = OffPolicyTrainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    if trainer.mesh is not None and trainer.mesh.size > 1:
        from surreal_tpu.parallel.mesh import replicate_state

        state = replicate_state(trainer.mesh, state)
    carry, replay_state = trainer.init_loop_state(env_key)
    beta = jnp.asarray(0.5, jnp.float32)
    off = jnp.asarray(False)
    metrics = None
    first = True
    for _ in range(warmup):
        key, it_key = jax.random.split(key)
        state, replay_state, carry, metrics = trainer._train_iter(
            state, replay_state, carry, it_key, beta, off, jnp.asarray(first)
        )
        first = False
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        key, it_key = jax.random.split(key)
        state, replay_state, carry, metrics = trainer._train_iter(
            state, replay_state, carry, it_key, beta, off, jnp.asarray(False)
        )
    jax.block_until_ready(metrics)
    return (time.perf_counter() - t0) / iters * 1e3


def _synthetic_learn_batch(specs, T: int, B: int, seed: int = 0) -> dict:
    """A [T, B] learner batch matching the PPO/IMPALA batch contract
    (utils/asserts.check_learn_batch), shapes/dtypes from the env specs,
    values from a fixed-seed RNG — the timed learn program is
    shape-determined, values only have to be plausible (finite logps,
    sparse episode boundaries)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    obs_shape = (T, B, *specs.obs.shape)
    if np.dtype(specs.obs.dtype) == np.uint8:
        obs = rng.integers(0, 256, obs_shape, dtype=np.uint8)
        next_obs = rng.integers(0, 256, obs_shape, dtype=np.uint8)
    else:
        obs = rng.standard_normal(obs_shape, dtype=np.float32)
        next_obs = rng.standard_normal(obs_shape, dtype=np.float32)
    done = rng.random((T, B)) < 1.0 / 50.0  # ~one boundary per 50 steps
    batch = {
        "obs": obs,
        "next_obs": next_obs,
        "reward": rng.standard_normal((T, B), dtype=np.float32),
        "done": done,
        "terminated": done & (rng.random((T, B)) < 0.5),
        "behavior_logp": rng.normal(-1.0, 0.1, (T, B)).astype(np.float32),
    }
    if specs.discrete:
        n = int(specs.action.n)
        batch["action"] = rng.integers(0, n, (T, B), dtype=np.int32)
        batch["behavior"] = {
            "logits": rng.normal(0.0, 0.1, (T, B, n)).astype(np.float32)
        }
    else:
        a = int(specs.action.shape[0])
        batch["action"] = rng.uniform(-1.0, 1.0, (T, B, a)).astype(np.float32)
        batch["behavior"] = {
            "mean": rng.normal(0.0, 0.1, (T, B, a)).astype(np.float32),
            "log_std": np.full((T, B, a), -0.5, np.float32),
        }
    return batch


def _measure_learn(cfg, warmup: int, iters: int) -> float:
    """ms/iter of the jitted LEARN program alone, on a synthetic batch —
    the host-env measurement surface (there is no fused device iteration
    to time when envs step on the host).

    Geometry note: the batch is [algo.horizon, env_config.num_envs] — the
    trainer-facing chunk of the host loops and the non-pipelined SEED
    plane. SEED's pipelined sub-slices halve the chunk width; for
    exact-geometry winners there, tune with num_envs set to the chunk
    width you train (or pipeline_workers=false).
    """
    import jax

    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner

    probe = make_env(training_env_config(cfg.env_config))
    specs = probe.specs
    if hasattr(probe, "close"):
        probe.close()
    learner = build_learner(cfg.learner_config, specs)
    T = int(learner.config.algo.horizon)
    B = int(cfg.env_config.num_envs)
    batch = jax.device_put(_synthetic_learn_batch(specs, T, B))
    # state is chained (each call consumes the previous output), so the
    # loop-carried state donates exactly like the production learn paths
    learn = jax.jit(learner.learn, donate_argnums=(0,))
    key = jax.random.key(0)
    key, ik = jax.random.split(key)
    state = learner.init(ik)
    metrics = None
    for _ in range(warmup):
        key, lk = jax.random.split(key)
        state, metrics = learn(state, batch, lk)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        key, lk = jax.random.split(key)
        state, metrics = learn(state, batch, lk)
    jax.block_until_ready(metrics)
    return (time.perf_counter() - t0) / iters * 1e3


def measure_point(
    config,
    point: dict,
    warmup: int = WARMUP,
    iters: int = ITERS,
    surface: str = "fused",
) -> float:
    """ms/iter of the workload's measured program with ``point`` pinned:
    the fused device iteration (``surface='fused'``), or the learn-only
    program (``surface='learn'`` — the host-env surface)."""
    cfg = _candidate_config(config, point)
    if surface == "learn":
        return _measure_learn(cfg, warmup, iters)
    if cfg.learner_config.algo.name == "ddpg":
        return _measure_offpolicy(cfg, warmup, iters)
    return _measure_onpolicy(cfg, warmup, iters)


def tune_workload(
    config,
    *,
    dims: list[tuple[str, list]] | None = None,
    warmup: int = WARMUP,
    iters: int = ITERS,
    min_gain: float = MIN_GAIN,
    force: bool = False,
    verbose: bool = False,
) -> dict:
    """Search this workload's candidate space and persist the winner.

    Returns the cache entry plus ``cache_hit`` (True means a stored entry
    was returned with ZERO measurements — the pure-hit contract the second
    ``surreal_tpu tune`` run relies on) and ``measured`` (trial count).
    ``dims`` overrides the declared space (tests / bounded CLI runs).
    """
    import jax

    env_name = str(config.env_config.name)
    # host envs (gym/dm_control/SEED) have no fused device iteration to
    # time — their search surface is the jitted learn program alone, over
    # the learn-phase subset of the space (_measure_learn)
    surface = "fused" if env_name.startswith("jax:") else "learn"
    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.hooks import training_env_config
    from surreal_tpu.learners import build_learner

    probe = make_env(training_env_config(config.env_config))
    learner = build_learner(config.learner_config, probe.specs)
    if hasattr(probe, "close"):
        probe.close()
    extended = learner.config
    key, fp = workload_fingerprint(extended, config.env_config)
    cache_dir = resolve_tuning_cache_dir(config.session_config)
    cache = TuningCache(cache_dir)
    if not force:
        entry = cache.lookup(key)
        if entry is not None:
            return dict(entry, cache_hit=True, measured=0)

    space = dims if dims is not None else search_space_for(config, extended)
    if not space:
        raise ValueError(
            f"no searchable dimensions for algo "
            f"{extended.algo.name!r} on {env_name!r} (host-env workloads "
            "search the learn-phase subset only — "
            f"{', '.join(LEARN_PHASE_DIMS)}); nothing to tune"
        )
    point = {name: extended.algo.get(name) for name, _ in space}

    def note(msg):
        if verbose:
            print(f"tune: {msg}", file=sys.stderr, flush=True)

    note(f"fingerprint {key} ({env_name}, algo={extended.algo.name}, "
         f"surface={surface}); searching {[n for n, _ in space]}")
    trials = []

    def run_trial(p):
        ms = measure_point(config, p, warmup, iters,
                           surface=surface)
        trials.append({"config": dict(p), "iter_ms": ms})
        note(f"{p} -> {ms:.2f} ms/iter")
        return ms

    default_snapshot = dict(point)
    default_ms = run_trial(point)
    incumbent_ms = default_ms
    for name, values in space:
        if skip_dimension(name, point, extended):
            note(f"skip {name} (moot under {point})")
            continue
        best_val, best_ms = None, None
        for val in values:
            if val == point.get(name):
                continue  # the incumbent's value is already measured
            ms = run_trial({**point, name: val})
            if best_ms is None or ms < best_ms:
                best_val, best_ms = val, ms
        if best_ms is not None and best_ms < incumbent_ms * (1.0 - min_gain):
            note(f"adopt {name}={best_val} "
                 f"({incumbent_ms:.2f} -> {best_ms:.2f} ms)")
            point[name] = best_val
            incumbent_ms = best_ms

    entry = {
        "key": key,
        "fingerprint": fp,
        "config": dict(point),        # the full chosen point (pins every
                                      # searched dim, defaults included)
        "default": default_snapshot,
        "default_ms": default_ms,
        "chosen_ms": incumbent_ms,
        "speedup": default_ms / max(incumbent_ms, 1e-9),
        "trials": trials,
        "platform": str(jax.default_backend()),
        "device_kind": str(jax.devices()[0].device_kind),
        "jax": jax.__version__,
        "measure": {
            "surface": surface,  # 'fused' device iteration | 'learn'
                                 # (host-env learn-only program)
            "warmup": warmup,
            "iters": iters,
            "min_gain": min_gain,
            "timing": "fenced chained window",
        },
        "created_t": time.time(),
    }
    cache.store(key, entry)
    return dict(entry, cache_hit=False, measured=len(trials))
