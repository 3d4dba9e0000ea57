"""Agent layer tests: mode-bound views and the remote-actor loop
(ParameterPublisher -> ParameterServer -> Agent.connect/remote_act — the
reference agent's periodic param fetch, SURVEY.md §3.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.agents import Agent, DDPGAgent, PPOAgent
from surreal_tpu.distributed import ParameterPublisher, ParameterServer
from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.learners import build_learner
from surreal_tpu.session.config import Config


def _specs(obs_dim=4, act_dim=2):
    return EnvSpecs(
        obs=ArraySpec(shape=(obs_dim,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(act_dim,), dtype=np.dtype(np.float32)),
    )


def test_ppo_remote_agent_fetches_published_params_and_stamps_version():
    """A remote PPOAgent must act on the LEARNER's published params (not
    its local init) after connect, track the published version, and stamp
    it into the behavior info it attaches to experience."""
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    learner_state = learner.init(jax.random.key(0))

    pub = ParameterPublisher()
    ps = ParameterServer(pub.address)
    agent = None
    try:
        # actor process side: own init (different key -> different params)
        agent = PPOAgent(learner).connect(
            ps.address, learner.init(jax.random.key(42)), fetch_every=2
        )
        obs = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)

        # nothing published yet: acting proceeds on the local stale copy
        a0, info0 = agent.remote_act(obs, jax.random.key(1))
        assert agent.param_version == 0
        assert np.all(info0["param_version"] == 0)

        pub.publish(agent.acting_view(learner_state))
        import time

        deadline = time.time() + 5
        while agent.param_version == 0 and time.time() < deadline:
            agent.fetch_params()
            time.sleep(0.05)
        assert agent.param_version == 1
        # the merged params ARE the learner's
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(agent.state.params)[0]),
            np.asarray(jax.tree.leaves(learner_state.params)[0]),
        )
        _, info1 = agent.remote_act(obs, jax.random.key(2))
        assert np.all(info1["param_version"] == 1)
        assert info1["logp"].shape == (8,)  # behavior stats still attached
    finally:
        if agent is not None:
            agent.close()
        ps.close()
        pub.close()


def test_ddpg_agent_actor_only_wire_view():
    """A remote DDPG actor ships actor params + obs normalizer only —
    never critic/target/optimizer state."""
    learner = build_learner(Config(algo=Config(name="ddpg")), _specs())
    state = learner.init(jax.random.key(0))
    view = DDPGAgent(learner).acting_view(state)
    assert set(view) == {"actor_params", "obs_stats"}
    # and the view round-trips through _replace
    merged = state._replace(**view)
    assert merged.critic_params is state.critic_params


def test_ddpg_agent_ou_noise_is_stateful_and_resets_on_done():
    """OU exploration is a correlated process carried by the agent: the
    same obs/key must yield different actions on consecutive acts (noise
    state advanced), eval modes must be noise-free, and a done mask must
    zero the finished env's noise row."""
    learner = build_learner(
        Config(algo=Config(name="ddpg", exploration=Config(noise="ou", sigma=0.3))),
        _specs(),
    )
    state = learner.init(jax.random.key(0))
    agent = DDPGAgent(learner)  # training mode
    obs = jnp.zeros((3, 4))
    key = jax.random.key(7)
    a1, _ = agent.act(state, obs, key)
    a2, _ = agent.act(state, obs, key)  # same key: only noise state differs
    assert not np.allclose(np.asarray(a1), np.asarray(a2))

    noise_before = np.asarray(agent._noise)
    agent.mask_noise_on_reset(jnp.array([True, False, False]))
    noise_after = np.asarray(agent._noise)
    np.testing.assert_allclose(noise_after[0], 0.0)
    np.testing.assert_allclose(noise_after[1:], noise_before[1:])

    # eval view: pure deterministic actor, repeatable
    ev = agent.eval_view(deterministic=True)
    e1, _ = ev.act(state, obs, jax.random.key(1))
    e2, _ = ev.act(state, obs, jax.random.key(2))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2))


def test_remote_agent_fetch_cadence_every_act():
    """fetch_every=1 must re-fetch on EVERY act (regression: an off-by-one
    made the true period fetch_every+1, so actors ran one publish behind
    half the time)."""
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    state = learner.init(jax.random.key(0))
    pub = ParameterPublisher()
    ps = ParameterServer(pub.address)
    agent = None
    try:
        agent = PPOAgent(learner).connect(
            ps.address, learner.init(jax.random.key(1)), fetch_every=1
        )
        obs = np.zeros((2, 4), np.float32)
        import time

        for expected in (1, 2):
            pub.publish(agent.acting_view(state))
            deadline = time.time() + 5
            while agent.param_version < expected and time.time() < deadline:
                agent.remote_act(obs, jax.random.key(expected))
                time.sleep(0.02)
            assert agent.param_version == expected
    finally:
        if agent is not None:
            agent.close()
        ps.close()
        pub.close()


def test_param_client_recovers_socket_after_timeout():
    """A silent server must not wedge the REQ socket: fetch raises
    TimeoutError but the NEXT fetch works once a server appears (strict
    REQ would otherwise fail with EFSM forever), and Agent.fetch_params
    turns the timeout into best-effort False."""
    from surreal_tpu.distributed import ParameterClient

    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    state = learner.init(jax.random.key(0))
    template = {"params": state.params, "obs_stats": state.obs_stats}
    # nobody bound here: both fetches must time out, neither may EFSM
    client = ParameterClient("tcp://127.0.0.1:19", template)
    try:
        for _ in range(2):
            with pytest.raises(TimeoutError):
                client.fetch(timeout_ms=100)
    finally:
        client.close()

    pub = ParameterPublisher()
    ps = ParameterServer(pub.address)
    agent = None
    try:
        agent = PPOAgent(learner).connect(ps.address, state)
        # monkey-patch a one-shot timeout, then confirm best-effort acting
        real_fetch = agent._client.fetch
        agent._client.fetch = lambda *a, **k: (_ for _ in ()).throw(TimeoutError())
        assert agent.fetch_params() is False  # stale copy kept, no raise
        agent._client.fetch = real_fetch
        import time

        # PUB/SUB drops what is published before the server's SUB has
        # joined: repeat the snapshot, as a learner does at every cadence,
        # until the recovered client fetches it (bounded at a minute)
        deadline = time.time() + 60
        ok = False
        while not ok and time.time() < deadline:
            pub.publish(agent.acting_view(state))
            time.sleep(0.05)
            ok = agent.fetch_params()
        assert ok
    finally:
        if agent is not None:
            agent.close()
        ps.close()
        pub.close()


def test_agent_remote_guards():
    learner = build_learner(Config(algo=Config(name="ppo")), _specs())
    agent = Agent(learner)
    with pytest.raises(RuntimeError, match="connect"):
        agent.remote_act(np.zeros((1, 4), np.float32), jax.random.key(0))
    with pytest.raises(ValueError, match="fetch_every"):
        agent.connect("tcp://127.0.0.1:1", learner.init(jax.random.key(0)), 0)


def _traj_learner(horizon=8, **encoder):
    cfg = Config(
        algo=Config(name="ppo", horizon=horizon),
        model=Config(
            encoder=Config(
                kind="trajectory", features=32, num_layers=1,
                num_heads=2, head_dim=8, **encoder,
            )
        ),
    )
    return build_learner(cfg, _specs())


def test_trajectory_remote_agent_acts_with_carry():
    """Round-5 VERDICT item 5: trajectory policies act over the wire.
    The remote agent routes through act_init/act_step with a client-side
    K/V carry; the action stream must equal a hand-stepped act_step loop
    on the same state/keys, and (like the reference's recurrent agents)
    the carry must survive a param fetch instead of resetting."""
    learner = _traj_learner()
    local_state = learner.init(jax.random.key(42))

    pub = ParameterPublisher()
    ps = ParameterServer(pub.address)
    agent = None
    try:
        agent = PPOAgent(learner).connect(ps.address, local_state, fetch_every=3)
        B = 4
        rng = np.random.default_rng(0)
        obs = [rng.normal(size=(B, 4)).astype(np.float32) for _ in range(5)]
        keys = [jax.random.key(100 + t) for t in range(5)]

        remote_actions = []
        for t in range(3):
            a, info = agent.remote_act(obs[t], keys[t])
            assert np.isfinite(np.asarray(a)).all()
            assert np.isfinite(np.asarray(info["logp"])).all()
            remote_actions.append(np.asarray(a))
        assert int(agent._act_carry["pos"]) == 3

        # reference loop: same state, same keys, explicit carry (jitted
        # like the agent's path — the bf16 trunk makes jit-vs-eager drift
        # ~1e-4, and this test checks plumbing, not compiler numerics)
        from functools import partial

        ref_step = jax.jit(partial(learner.act_step, mode=agent.mode))
        carry = learner.act_init(B)
        for t in range(3):
            a_ref, _, carry = ref_step(
                local_state, carry, jnp.asarray(obs[t]), keys[t]
            )
            np.testing.assert_allclose(
                remote_actions[t], np.asarray(a_ref), atol=1e-5, rtol=1e-5
            )

        # a published update is fetched mid-segment; context persists
        other_state = learner.init(jax.random.key(7))
        pub.publish(agent.acting_view(other_state))
        import time

        deadline = time.time() + 5
        while agent.param_version == 0 and time.time() < deadline:
            agent.fetch_params()
            time.sleep(0.05)
        assert agent.param_version == 1
        a, _ = agent.remote_act(obs[3], keys[3])
        assert np.isfinite(np.asarray(a)).all()
        assert int(agent._act_carry["pos"]) == 4  # not reset by the fetch
    finally:
        if agent is not None:
            agent.close()
        ps.close()
        pub.close()


def test_trajectory_encoder_max_len_forwarded_and_validated():
    """Advisor r4: encoder.max_len must reach TrajectoryEncoder's
    pos_embed, and horizon+1 > max_len must fail at build with a clear
    message instead of an opaque broadcast error inside the learn pass."""
    learner = _traj_learner(horizon=8, max_len=16)
    state = learner.init(jax.random.key(0))
    flat = {"/".join(map(str, p)): v for p, v in
            jax.tree_util.tree_flatten_with_path(state.params)[0]}
    pe = [v for k, v in flat.items() if "pos_embed" in k]
    assert pe and pe[0].shape[0] == 16

    with pytest.raises(ValueError, match="max_len"):
        _traj_learner(horizon=64, max_len=32)


def test_pixel_trajectory_remote_agent_acts():
    """Remote acting composes with PIXEL trajectories: the client-side
    K/V carry + uint8 frames through the per-frame CNN stem."""
    specs = EnvSpecs(
        obs=ArraySpec(shape=(16, 16, 2), dtype=np.dtype(np.uint8)),
        action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
    )
    cfg = Config(
        algo=Config(name="ppo", horizon=8),
        model=Config(
            cnn=Config(enabled=True, channels=(8, 16), kernels=(4, 3),
                       strides=(2, 1), dense=32),
            encoder=Config(kind="trajectory", features=32, num_layers=1,
                           num_heads=2, head_dim=8),
        ),
    )
    learner = build_learner(cfg, specs)
    state = learner.init(jax.random.key(0))
    pub = ParameterPublisher()
    ps = ParameterServer(pub.address)
    agent = None
    try:
        agent = PPOAgent(learner).connect(ps.address, state, fetch_every=5)
        B = 2
        obs = np.random.default_rng(0).integers(
            0, 255, size=(B, 16, 16, 2), dtype=np.uint8
        )
        for t in range(3):
            a, info = agent.remote_act(obs, jax.random.key(t))
            assert np.isfinite(np.asarray(a)).all()
            assert np.isfinite(np.asarray(info["logp"])).all()
        assert int(agent._act_carry["pos"]) == 3
    finally:
        if agent is not None:
            agent.close()
        ps.close()
        pub.close()
