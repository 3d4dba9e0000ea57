"""Elastic learner group (ISSUE 17, parallel/learner_group.py): the
shard-partitioning seam, the gradient-all-reduce learn program on the
8-device CPU sim, M=1 bit-parity with the single-learner path, the
fanout membership re-key, mid-run join/leave/crash chaos, and the
remediation scale actuator."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.experience.sampler import partition_shards
from surreal_tpu.parallel.learner_group import group_learn
from surreal_tpu.replay.sharded import check_group_divisible
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.utils import faults


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.configure(None)  # never leak a plan into the next test


# -- the partitioning seam ----------------------------------------------------

def test_partition_shards_disjoint_covering_contiguous():
    for num_shards in (1, 2, 3, 4, 8):
        for members in range(1, num_shards + 1):
            subsets = partition_shards(num_shards, members)
            assert len(subsets) == members
            flat = [s for sub in subsets for s in sub]
            # disjoint + covering + shard-major contiguous: the group's
            # stitched batch stays in global shard order
            assert flat == list(range(num_shards))
            assert all(sub for sub in subsets)
            # earlier members absorb the remainder, never the tail
            sizes = [len(sub) for sub in subsets]
            assert sizes == sorted(sizes, reverse=True)


def test_partition_shards_rejects_bad_member_counts():
    with pytest.raises(ValueError):
        partition_shards(4, 0)
    with pytest.raises(ValueError):
        partition_shards(4, 5)  # one shard subset per member, minimum 1


def test_check_group_divisible():
    assert check_group_divisible(48, 4, 3) == 12
    with pytest.raises(ValueError):
        check_group_divisible(48, 4, 5)  # 48 % 5 != 0
    with pytest.raises(ValueError):
        check_group_divisible(50, 4, 2)  # 50 % 4 != 0
    with pytest.raises(ValueError):
        check_group_divisible(48, 4, 0)


# -- the all-reduce learn program ---------------------------------------------

def _specs():
    from surreal_tpu.envs.base import ArraySpec, EnvSpecs

    return EnvSpecs(
        obs=ArraySpec(shape=(6,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
    )


def _traj_batch(key, T=4, B=16):
    ks = jax.random.split(key, 4)
    return {
        "obs": jax.random.normal(ks[0], (T, B, 6)),
        "next_obs": jax.random.normal(ks[1], (T, B, 6)),
        "action": jax.random.normal(ks[2], (T, B, 3)),
        "reward": jax.random.normal(ks[3], (T, B)),
        "done": jnp.zeros((T, B), bool),
        "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, 3)),
            "log_std": jnp.full((T, B, 3), -0.5),
        },
    }


def test_group_learn_matches_single_learn():
    """The M=2 all-reduce update equals the single full-batch update on
    the same global batch (mean of member-shard grad means == global
    grad mean) — the fallback path's correctness argument, run forward.
    Time-major chunks shard on the env-batch dim (batch_dim=1), the
    SEED learn-seam geometry."""
    from jax.sharding import Mesh
    from surreal_tpu.learners import build_learner

    learner = build_learner(
        Config(algo=Config(name="ppo", epochs=1, num_minibatches=1)),
        _specs(),
    )
    state = learner.init(jax.random.key(0))
    batch = _traj_batch(jax.random.key(1))
    key = jax.random.key(2)

    single_state, _ = jax.jit(learner.learn)(state, batch, key)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("lg",))
    g_state, g_metrics = group_learn(learner, mesh, batch_dim=1)(
        state, batch, key
    )

    for a, b in zip(
        jax.tree.leaves(single_state.params), jax.tree.leaves(g_state.params)
    ):
        # bf16 compute + psum-of-partial-means reduction-order noise:
        # semantic equality, not bitwise (the parallel/dp.py bound)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-3
        )
    # a learner without per-row TD bookkeeping still yields the static
    # out-tree: a zero [B] vector in global shard order
    td = np.asarray(g_metrics["priority/td_abs"])
    assert td.shape == (16,) and not td.any()


# -- fanout membership re-key -------------------------------------------------

def test_fanout_force_rekey_breaks_delta_chain():
    import time

    from surreal_tpu.distributed.param_fanout import (
        ParameterFanout, ParameterSubscriber,
    )

    rng = np.random.default_rng(3)
    p = {"w": rng.normal(size=(32, 32)).astype(np.float32)}
    fan = ParameterFanout(wire="f32", delta=True)
    sub = ParameterSubscriber(fan.address, fan.ack_address, p)
    time.sleep(0.3)  # SUB join (zmq slow-joiner)
    try:
        def pub():
            nonlocal p
            p = {"w": p["w"] + 1e-3 * rng.normal(size=(32, 32)).astype(
                np.float32)}
            info = fan.publish(p)
            deadline = time.time() + 10
            while sub.version < info["version"] and time.time() < deadline:
                sub.poll(timeout_ms=100)
            time.sleep(0.05)  # let the ack land
            return info

        assert pub()["kind"] == "full"   # v1 keys the stream
        assert pub()["kind"] == "delta"  # acked subscriber gets deltas
        before = fan.rekeys
        fan.force_rekey()
        # the membership re-key: next frame is FULL despite fresh acks,
        # and one-shot — the frame after resumes the delta chain
        assert pub()["kind"] == "full"
        assert fan.rekeys == before + 1
        assert pub()["kind"] == "delta"
    finally:
        sub.close()
        fan.close()


# -- trainer integration ------------------------------------------------------

def _remote_cfg(folder, *, lg=None, iters=3, num_shards=2, batch_size=32,
                fault_plan=None):
    topo = Config(
        overlap_rollouts=False,
        experience_plane=Config(
            num_shards=num_shards, shard_mode="thread", transport="shm",
            respawn_backoff_s=0.05,
        ),
    )
    if lg is not None:
        topo = topo.extend(Config(learner_group=Config(members=lg)))
    sess = Config(
        folder=str(folder),
        total_env_steps=8 * 4 * iters,
        metrics=Config(every_n_iters=1, tensorboard=False, console=False),
        checkpoint=Config(every_n_iters=0),
        eval=Config(every_n_iters=0),
        # live fanout on: membership changes must re-key the ONE
        # param-distribution tree (the rekeys == rebalances assertion)
        publish=Config(enabled=True, every_n_iters=1,
                       fanout=Config(enabled=True)),
        topology=topo,
    )
    if fault_plan is not None:
        sess = sess.extend(Config(faults=Config(plan=fault_plan)))
    return Config(
        learner_config=Config(
            algo=Config(name="ddpg", horizon=8, updates_per_iter=2,
                        exploration=Config(warmup_steps=0)),
            replay=Config(kind="remote", remote_kind="uniform",
                          capacity=512, start_sample_size=16,
                          batch_size=batch_size),
        ),
        env_config=Config(name="gym:Pendulum-v1", num_envs=4),
        session_config=sess,
    ).extend(base_config())


def test_m1_group_is_bit_identical_to_single_learner(tmp_path):
    """The M=1 acceptance: a one-member group covering the whole plane
    IS the single-learner path — same sampler key, same learn program,
    bit-identical training record and fanout version stream."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    _, legacy = OffPolicyTrainer(_remote_cfg(tmp_path / "legacy")).run()
    _, grouped = OffPolicyTrainer(_remote_cfg(tmp_path / "g1", lg=1)).run()
    for k in ("loss/critic", "loss/actor", "health/grad_norm",
              "experience/rows"):
        assert legacy[k] == grouped[k], (k, legacy[k], grouped[k])
    # same fanout versions: publish count rides the metrics stream
    for k in ("param/publishes", "param/full_frames"):
        if k in legacy:
            assert legacy[k] == grouped[k], k
    assert grouped["lgroup/members"] == 1.0
    assert grouped["lgroup/rebalances"] == 0.0
    assert grouped["lgroup/fallback_learns"] == 0.0


def test_membership_chaos_join_leave_crash_mid_run(tmp_path):
    """The membership chaos acceptance in ONE deterministic run: a
    member joins mid-run (fault plan, supervise call 2), the group
    scales back down (call 4), and a member crashes (call 6) and
    respawns under backoff — each completing without aborting the run,
    journaled in telemetry, with no transition double-consumed and no
    false incidents."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    folder = tmp_path / "chaos"
    cfg = _remote_cfg(
        folder, lg=2, iters=10, num_shards=4, batch_size=48,
        fault_plan=[
            {"site": "lgroup.member", "kind": "join_member", "at": 2},
            {"site": "lgroup.member", "kind": "leave_member", "at": 4},
            {"site": "lgroup.member", "kind": "kill_member", "at": 6},
        ],
    )
    _, metrics = OffPolicyTrainer(cfg).run()
    assert np.isfinite(metrics["loss/critic"])
    assert metrics["time/env_steps"] >= 8 * 4 * 10
    assert metrics["lgroup/joins"] >= 1.0
    assert metrics["lgroup/leaves"] >= 1.0
    assert metrics["lgroup/respawns"] >= 1.0, metrics
    # every membership change rebalanced AND re-keyed the one fanout tree
    assert metrics["lgroup/rebalances"] >= 4.0
    assert metrics["lgroup/rekeys"] == metrics["lgroup/rebalances"]
    # exactly-once on the insert wire survives the rebalances: every row
    # the workers sent landed in exactly one shard, none dropped/duped
    assert metrics["experience/dropped_rows"] == 0.0
    assert metrics["experience/rows"] > 0
    # staleness gauges recover: the final row's values are finite
    for k in ("lineage/staleness_p99", "experience/sample_wait_ms"):
        if k in metrics:
            assert np.isfinite(metrics[k]), k
    assert not glob.glob("/dev/shm/surreal_xp_*"), "chaos run leaked shm"
    # the journal: membership ops + the joiner's state handoff, and NO
    # incident opened on planned membership changes
    with open(os.path.join(str(folder), "telemetry", "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    lg_events = [e for e in events if e.get("type") == "learner_group"]
    ops = {e.get("op") for e in lg_events}
    assert {"join", "leave", "member_failed", "respawn", "handoff"} <= ops, ops
    assert not [e for e in events if e.get("type") == "incident_open"]


# -- remediation actuator -----------------------------------------------------

def test_remediation_scales_learner_group_and_reverts(tmp_path):
    """A learner-tier cause (saturation) maps to
    learner_scale_up when a group is bound; an ineffective verdict
    (throughput fell further) reverts by removing the joined member."""
    from surreal_tpu.session.remediate import RemediationEngine, load_actions

    class _StubGroup:
        def __init__(self):
            self.joined = []
            self.left = []
            self._next = 7

        def scale_up(self):
            self.joined.append(self._next)
            return self._next

        def scale_down(self, member_id=None):
            self.left.append(member_id)
            return member_id

    class _StubIncidents:
        def __init__(self, incident):
            self._open = incident
            self.attached = []

        @property
        def open_incident(self):
            return self._open

        def attach_action(self, summary):
            self.attached.append(dict(summary))

    def snap(i, steps_per_s):
        return {
            "type": "ops_snapshot", "t": 1000.0 + i, "seq": i,
            "iteration": i, "env_steps": i * 512, "trace": "tr-test",
            "tiers": {"learner": {
                "age_s": 0.0, "dead": False, "cadence_s": 1.0,
                "gauges": {"time/env_steps_per_s": steps_per_s},
            }},
            "hops": {}, "slo": {}, "bad_frames": 0,
        }

    group = _StubGroup()
    stub = _StubIncidents({
        "id": 1,
        "causes": [{"tier": "learner", "score": 2.0, "reasons": []}],
        "evidence": {"dead_tiers": []}, "detector_counts": {},
    })
    rem = RemediationEngine(
        folder=str(tmp_path), cfg={"cooldown_s": 300.0, "verify_windows": 2},
        incidents=stub, trace_id="tr-test",
    )
    rem.bind_actuators(learner_group=group)
    # a learner-tier cause -> scale up the group
    rem.step([{"detector": "breakout", "tier": "learner"}],
             snap(0, 2000.0))
    assert group.joined == [7]
    # throughput fell further over the verification window -> revert:
    # the joined member leaves
    rem.step([], snap(1, 1000.0))
    rem.step([], snap(2, 900.0))
    assert group.left == [7]
    (act,) = load_actions(str(tmp_path))
    assert act["kind"] == "learner_scale_up"
    assert act["verdict"] == "ineffective" and act["reverted"] is True
