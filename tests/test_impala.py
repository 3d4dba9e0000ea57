"""IMPALA/V-trace tests: golden vtrace_nextobs checks + 256-env CartPole
learning (BASELINE config ⑤'s SEED-style batched acting, on-device)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.envs.base import ArraySpec, DiscreteSpec, EnvSpecs
from surreal_tpu.launch.trainer import Trainer
from surreal_tpu.learners import build_learner
from surreal_tpu.learners.impala import IMPALALearner
from surreal_tpu.ops.vtrace import vtrace, vtrace_nextobs
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config


def test_vtrace_nextobs_matches_classic_without_boundaries():
    """With no dones and next_obs[t] == obs[t+1], the two-mask variant must
    reproduce the classic values[T+1] formulation exactly."""
    T, B = 7, 3
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    values_full = jax.random.normal(ks[0], (T + 1, B))
    rewards = jax.random.normal(ks[1], (T, B))
    b_logp = -1.0 + 0.1 * jax.random.normal(ks[2], (T, B))
    t_logp = -1.0 + 0.1 * jax.random.normal(ks[3], (T, B))
    gamma = 0.95

    classic = vtrace(
        b_logp, t_logp, rewards, jnp.full((T, B), gamma), values_full
    )
    two_mask = vtrace_nextobs(
        b_logp,
        t_logp,
        rewards,
        values=values_full[:-1],
        values_next=values_full[1:],
        done=jnp.zeros((T, B), bool),
        terminated=jnp.zeros((T, B), bool),
        gamma=gamma,
    )
    np.testing.assert_allclose(
        np.asarray(classic.vs), np.asarray(two_mask.vs), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(classic.pg_advantages),
        np.asarray(two_mask.pg_advantages),
        rtol=1e-5,
    )


def test_vtrace_nextobs_cuts_recursion_at_done():
    """A done at t must stop corrections from leaking into earlier steps'
    vs beyond the boundary step itself."""
    T = 4
    values = jnp.zeros((T, 1))
    values_next = jnp.ones((T, 1)) * 10.0
    rewards = jnp.ones((T, 1))
    done = jnp.asarray([[0], [1], [0], [0]], bool)
    term = jnp.asarray([[0], [1], [0], [0]], bool)
    out = vtrace_nextobs(
        jnp.zeros((T, 1)), jnp.zeros((T, 1)), rewards,
        values, values_next, done, term, gamma=0.9,
    )
    # step1 terminated: vs_1 = r = 1 (no bootstrap)
    np.testing.assert_allclose(float(out.vs[1, 0]), 1.0)
    # step0: vs_0 = r + gamma*values_next0 + gamma*c*(vs1 - V1) -> on-policy
    # rho=c=1: delta0 = 1 + .9*10 - 0 = 10; vs0 = 10 + .9*1*(1-0) = 10.9
    np.testing.assert_allclose(float(out.vs[0, 0]), 10.9, rtol=1e-6)


def test_impala_learn_moves_params():
    specs = EnvSpecs(
        obs=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), n=3),
    )
    learner = build_learner(Config(algo=Config(name="impala")), specs)
    state = learner.init(jax.random.key(0))
    T, B = 8, 16
    ks = jax.random.split(jax.random.key(1), 3)
    batch = {
        "obs": jax.random.normal(ks[0], (T, B, 4)),
        "next_obs": jax.random.normal(ks[1], (T, B, 4)),
        "action": jax.random.randint(ks[2], (T, B), 0, 3),
        "reward": jnp.ones((T, B)),
        "done": jnp.zeros((T, B), bool),
        "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.full((T, B), -1.1),
        "behavior": {"logits": jnp.zeros((T, B, 3))},
    }
    new_state, metrics = jax.jit(learner.learn)(state, batch, jax.random.key(2))
    moved = max(
        jax.tree.leaves(
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.params, new_state.params)
        )
    )
    assert moved > 0
    for k, v in metrics.items():
        assert np.isfinite(float(v)), k


@pytest.mark.slow
def test_impala_cartpole_256_envs_learns():
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=32)),
        env_config=Config(name="jax:cartpole", num_envs=256),
        session_config=Config(
            folder="/tmp/test_impala",
            total_env_steps=4_000_000,
            metrics=Config(every_n_iters=20, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    best = {"ret": 0.0}

    def cb(it, m):
        r = m.get("episode/return", float("nan"))
        if not np.isnan(r):
            best["ret"] = max(best["ret"], r)
        return best["ret"] >= 400.0

    trainer.run(on_metrics=cb)
    assert best["ret"] >= 400.0, f"best return {best['ret']}"


# -- successor values from the forward over obs (one pass, not two) ----------
#
# The reference, written here: apply the model to EVERY next_obs frame, as
# `learn` did before it read V(next_obs[t]) from values[t + 1].

class _WholePassIMPALA(IMPALALearner):
    def _values_apart(self, params, obs_stats, batch):
        v = self.model.apply(
            params, self._norm_obs(obs_stats, batch["next_obs"])
        ).value
        return v, jnp.ones(v.shape[0], bool)


_CNN = Config(enabled=True, channels=(4, 8), kernels=(4, 3), strides=(2, 1), dense=16)


def _successor_setup(kind, T, B, cut, terminated):
    """A learner of ``kind`` ('mlp' float obs with the obs filter, 'cnn'
    uint8 frames), its whole-pass twin, a state and a batch laid out as the
    rollout lays it out: ``next_obs[t]`` IS ``obs[t + 1]`` except where
    ``done[t]``, where it is a terminal frame of its own and ``obs[t + 1]``
    is the reset one. ``cut``/``terminated`` are lists of (t, b)."""
    pixels = kind == "cnn"
    shape, dtype = ((12, 12, 2), np.uint8) if pixels else ((5,), np.float32)
    specs = EnvSpecs(
        obs=ArraySpec(shape=shape, dtype=np.dtype(dtype)),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), n=3),
    )
    cfg = Config(
        algo=Config(name="impala", precision="f32"),
        model=Config(cnn=_CNN if pixels else Config(enabled=False)),
    )
    learner = build_learner(cfg, specs)
    whole = _WholePassIMPALA(learner.config, specs)
    ks = jax.random.split(jax.random.key(T * 100 + B), 6)

    def frames(key, lead):
        if pixels:
            return jax.random.randint(key, (*lead, *shape), 0, 256).astype(jnp.uint8)
        return jax.random.normal(key, (*lead, *shape))

    walk = frames(ks[0], (T + 1, B))
    terminal = frames(ks[1], (T, B))
    done = np.zeros((T, B), bool)
    term = np.zeros((T, B), bool)
    for t, b in list(cut) + list(terminated):
        done[t, b] = True
    for t, b in terminated:
        term[t, b] = True
    mask = jnp.asarray(done).reshape(T, B, *([1] * len(shape)))
    logits = jax.random.normal(ks[2], (T, B, 3))
    action = jax.random.randint(ks[3], (T, B), 0, 3)
    batch = {
        "obs": walk[:-1],
        "next_obs": jnp.where(mask, terminal, walk[1:]),
        "action": action,
        "reward": jax.random.normal(ks[4], (T, B)),
        "done": jnp.asarray(done),
        "terminated": jnp.asarray(term),
        "behavior_logp": jnp.take_along_axis(
            jax.nn.log_softmax(logits), action[..., None], -1
        )[..., 0],
        "behavior": {"logits": logits},
    }
    state = learner.init(jax.random.key(7))
    # a value head of the size of a reward, so the bootstrap moves the loss
    state = state._replace(
        params=jax.tree.map(lambda p: p * 3.0, state.params)
    )
    return learner, whole, state, batch


def _learn_and_spy(learner, state, batch, step=None):
    """(new state, metrics, the values_next V-trace was fed)."""
    seen = {}
    inner = learner._vtrace

    def spy(**kw):
        jax.debug.callback(
            lambda v: seen.__setitem__("values_next", np.asarray(v)),
            kw["values_next"],
        )
        return inner(**kw)

    learner._vtrace = spy
    try:
        step = step or jax.jit(learner.learn)
        new_state, metrics = step(state, batch, jax.random.key(2))
        jax.block_until_ready(new_state)
        jax.effects_barrier()
    finally:
        del learner._vtrace
    return new_state, metrics, seen["values_next"]


_T, _B = 6, 4
_SUCCESSOR_CASES = {
    # name: (T, B, truncated rows, terminated rows, steps evaluated apart)
    "no_cut": (_T, _B, [], [], 1),
    "terminated_mid": (_T, _B, [], [(2, 1)], 1),
    "truncated_mid": (_T, _B, [(3, 0)], [], 2),
    "truncated_at_the_last_step": (_T, _B, [(_T - 1, 2)], [], 1),
    "all_envs_truncated_at_one_step": (_T, _B, [(2, b) for b in range(_B)], [], 2),
    "cuts_in_three_steps": (_T, _B, [(0, 1), (1, 3), (4, 0), (4, 2)], [(0, 3)], 4),
    "a_cut_in_every_step": (_T, _B, [(t, t % _B) for t in range(_T)], [(1, 0)], _T),
    "T_is_1": (1, _B, [], [], 1),
}


@pytest.mark.parametrize("kind", ["mlp", "cnn"])
@pytest.mark.parametrize("case", list(_SUCCESSOR_CASES))
def test_successor_values_match_the_whole_next_obs_pass(case, kind):
    """`learn` reads V(next_obs[t]) from values[t + 1] and evaluates apart
    only the steps with no successor in the batch; the result is the whole
    pass's: parameters, every metric, and the values_next fed to V-trace."""
    T, B, cut, terminated, steps = _SUCCESSOR_CASES[case]
    learner, whole, state, batch = _successor_setup(kind, T, B, cut, terminated)
    new, metrics, values_next = _learn_and_spy(learner, state, batch)
    ref, ref_metrics, ref_values_next = _learn_and_spy(whole, state, batch)

    # a terminated row's bootstrap is masked by V-trace; anywhere else
    # values_next is V(next_obs), to float32 rounding
    live = ~np.asarray(batch["terminated"])
    assert np.isfinite(values_next).all()
    np.testing.assert_allclose(
        values_next[live], ref_values_next[live], rtol=1e-5, atol=1e-5
    )
    assert np.abs(ref_values_next).max() > 0.05  # the comparison has teeth
    for a, b in zip(jax.tree.leaves(new.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(new.obs_stats), jax.tree.leaves(ref.obs_stats)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(metrics) == set(ref_metrics)
    for k in ref_metrics:
        if not k.startswith("impala/"):
            np.testing.assert_allclose(
                float(metrics[k]), float(ref_metrics[k]), rtol=1e-4, atol=1e-6,
                err_msg=k,
            )
    assert float(metrics["impala/boot_rows"]) == steps * B
    assert float(metrics["impala/boot_full"]) == float(steps == T)


def _conv_batches(jaxpr, in_loop=False, out=None):
    """[(frames, inside a while loop)] of every convolution: its batch
    size, times any spatial axis its kernel is 1 wide along (a rollout's
    ``[T, B, ...]`` is convolved with ``T`` as such an axis)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            dims = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            frames = lhs[dims.lhs_spec[0]]
            for l, r in zip(dims.lhs_spec[2:], dims.rhs_spec[2:]):
                frames *= lhs[l] if rhs[r] == 1 else 1
            out.append((frames, in_loop))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _conv_batches(sub, in_loop or eqn.primitive.name == "while", out)
    return out


def test_learn_holds_no_convolution_over_the_whole_of_next_obs():
    """`learn` traces one convolution stack over T * B frames fewer than
    the whole-pass reference (the next_obs pass); in its place stands one
    stack over the B frames of a step, inside the loop over the steps that
    have no successor in the batch."""
    T, B, layers = _T, _B, len(_CNN.channels)
    learner, whole, state, batch = _successor_setup("cnn", T, B, [], [])
    key = jax.random.key(2)
    got = _conv_batches(jax.make_jaxpr(learner.learn)(state, batch, key).jaxpr)
    ref = _conv_batches(jax.make_jaxpr(whole.learn)(state, batch, key).jaxpr)
    assert not any(in_loop for _, in_loop in ref)
    outside = sorted(n for n, in_loop in got if not in_loop)
    ref_outside = sorted(n for n, _ in ref)
    assert ref_outside.count(T * B) - outside.count(T * B) == layers
    assert len(ref_outside) - len(outside) == layers
    assert [n for n, in_loop in got if in_loop] == [B] * layers


def test_successor_values_under_dp_match_one_device():
    """Each dp shard shifts inside its own [T, B_local] and visits the
    steps its own rows were cut at: the update is the one-device update."""
    from surreal_tpu.parallel import dp_learn, make_mesh

    T, B = 6, 16
    # shard 0 (envs 0, 1) holds cuts in three steps, shards 2 and 4 in one
    cut = [(1, 0), (1, 1), (3, 0), (0, 1), (2, 5), (4, 9)]
    learner, _, state, batch = _successor_setup("mlp", T, B, cut, [(0, 12)])
    one, one_metrics, _ = _learn_and_spy(learner, state, batch)

    mesh = make_mesh(Config(mesh=Config({"dp": 8})))
    dp, dp_metrics, _ = _learn_and_spy(
        learner, state, batch, step=dp_learn(learner, mesh, donate=False)
    )
    for a, b in zip(jax.tree.leaves(one.params), jax.tree.leaves(dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-6)
    for k in ("loss/pg", "loss/value", "policy/entropy", "policy/rho_mean"):
        np.testing.assert_allclose(
            float(one_metrics[k]), float(dp_metrics[k]), rtol=1e-4, atol=1e-6,
            err_msg=k,
        )
    # one device visits the 5 cut steps and the last; the counters of the
    # mesh are means over its shards: 4 + 2 + 2 + 5 x 1 steps of 2 rows
    assert float(one_metrics["impala/boot_rows"]) == 6 * B
    assert float(one_metrics["impala/boot_full"]) == 1.0
    assert float(dp_metrics["impala/boot_rows"]) == 13 * 2 / 8
    assert float(dp_metrics["impala/boot_full"]) == 0.0


# -- the unroll keys change the program's shape, not its result --------------
# (tolerances: conftest.py::assert_same_update)
_FUSED = {}


def _fused_impala(tmp_path, **algo_over):
    """Metrics and params after one fused IMPALA iteration on jax:pendulum
    (8 envs x horizon 8), memoized per variant."""
    tag = tuple(sorted(algo_over.items()))
    if tag not in _FUSED:
        cfg = Config(
            learner_config=Config(
                algo=Config(name="impala", horizon=8, **algo_over)
            ),
            env_config=Config(name="jax:pendulum", num_envs=8),
            session_config=Config(folder=str(tmp_path)),
        ).extend(base_config())
        t = Trainer(cfg)
        key, ik, ek = jax.random.split(jax.random.key(3), 3)
        state = t.learner.init(ik)
        if t.mesh is not None and t.mesh.size > 1:
            from surreal_tpu.parallel.mesh import replicate_state

            state = replicate_state(t.mesh, state)
        state, _, metrics = t._train_iter(
            state, t.init_loop_state(ek), jax.random.split(key)[1]
        )
        _FUSED[tag] = jax.device_get((metrics, state.params))
    return _FUSED[tag]


# the default's rollout scan runs four steps a trip on jax:pendulum (a
# memoryless policy over vector observations: launch/rollout.py::
# rollout_unroll), so the variant that differs from it is an explicit 1
@pytest.mark.parametrize(
    "variant", [{"rollout_unroll": 1}, {"gae_unroll": 4}],
    ids=["rollout", "vtrace"],
)
def test_impala_unrolled_program_matches_default(
    tmp_path, variant, assert_same_update
):
    assert_same_update(
        _fused_impala(tmp_path), _fused_impala(tmp_path, **variant)
    )


@pytest.mark.parametrize(
    "env,model,trips",
    [
        ("jax:pong84", Config(cnn=Config(enabled=True)), 8),
        ("jax:pendulum", None, 2),
    ],
    ids=["pixels", "vector"],
)
def test_fused_impala_collect_scan_trips(collect_scan_trips, env, model, trips):
    """Toy IMPALA, horizon 8: over ``jax:pong84``'s frames the collect loop
    of the lowered iteration keeps ``horizon`` trips; over a vector
    observation it runs four steps a trip, as PPO's does (the rule reads the
    scan's input, not the algorithm)."""
    assert collect_scan_trips("impala", env, model) == trips
