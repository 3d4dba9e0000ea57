"""Perf report: the utilization story behind bench.py's headline number
(VERDICT r2 item 4 — "turn one number into a utilization story").

Runs four graded-workload-class benchmarks on the chip and writes the
detailed report to ``chiprun_out/PERF_report.md`` (the directory the chip
tool brings back). The repo's ``PERF.md`` is kept by hand to its own
outline and cites this report; it is never written from here.

1. PPO + MLP on ``jax:lift``  (the headline: BASELINE config ③/north-star
   class) — steps/s, XLA-reported FLOP/s, MFU, and a rollout-vs-learn
   top-line breakdown, plus a jax.profiler trace window.
2. IMPALA + NatureCNN on ``jax:pong``  (BASELINE config ⑤ class).
3. DDPG + prioritized replay on ``jax:lift``  (BASELINE config ③ class).
4. PPO + NatureCNN from pixels on ``jax:nut_pixels``  (BASELINE config ④
   class — envs rendered AND learned on device).

MFU uses the published peak of the device JAX reports
(session/costs.py::PEAK_SPECS; a device without one is refused). The
headline metric remains env steps/s/chip (BASELINE.json). All timing is
a chained window after a compile warm-up, fenced by
``jax.block_until_ready`` on the window's last outputs.

Usage:  python perf_report.py                # writes the report + README table
        python perf_report.py --sync-readme  # citation-only: re-point
            README's 'artifact of record' at the newest BENCH_r*.json
            (no benchmarks; tests/test_perf_docs.py fails when stale)
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from bench import _iter_flops

WARMUP = 2
ITERS = 10  # match bench.py's window
REPORT_PATH = "chiprun_out/PERF_report.md"


def _peak_flops() -> float:
    """Published bf16 peak of the device being measured (raises for a
    device without one: this report is a chip tool)."""
    from surreal_tpu.session.costs import published_peak

    return published_peak(str(jax.devices()[0].device_kind))[0]


def _timeit_chained(step, carry0, key, iters=ITERS):
    """Time ``iters`` CHAINED calls: each call consumes the previous
    call's outputs, so launches cannot overlap on the device.

    The window ends in ``jax.block_until_ready`` of the last call's
    outputs, which the chain makes depend on every earlier call (check
    after any change: time grows linearly in ``iters``, and the implied
    FLOP/s stays below the chip's peak).

    ``step(carry, key) -> (carry, observable)``; returns (seconds, carry).
    """
    k = key
    carry = carry0
    obs = None
    t0 = time.perf_counter()
    for _ in range(iters):
        k, sub = jax.random.split(k)
        carry, obs = step(carry, sub)
    jax.block_until_ready((carry, obs))
    return time.perf_counter() - t0, carry


def ppo_lift_headline() -> dict:
    from surreal_tpu.launch.rollout import device_rollout, init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 4096, 256
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=4, num_minibatches=4),
        ),
        env_config=Config(name="jax:lift", num_envs=num_envs),
        session_config=Config(
            folder="/tmp/perf_lift",
            metrics=Config(every_n_iters=10_000),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, num_envs)

    for _ in range(WARMUP):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    flops = _iter_flops(trainer._train_iter, state, carry, key)

    def fused_step(sc, k):
        s, c = sc
        s, c, m = trainer._train_iter(s, c, k)
        return (s, c), m

    dt, (state, carry) = _timeit_chained(fused_step, (state, carry), key)
    sps = ITERS * num_envs * horizon / dt

    # top-line breakdown: rollout-only vs learn-only compiled separately
    # (the fused iter overlaps them in one program; this is the attribution)
    roll = jax.jit(
        lambda s, c, k: device_rollout(
            trainer.env, trainer.learner, s, c, k, horizon
        )
    )
    key, rk = jax.random.split(key)
    carry2, batch = roll(state, carry, rk)
    jax.block_until_ready(batch["reward"][-1])

    def roll_step(c, k):
        c2, b = roll(state, c, k)
        return c2, b["reward"][-1]

    dt_roll, _ = _timeit_chained(roll_step, carry, key)

    learn_batch = {
        k: batch[k]
        for k in ("obs", "next_obs", "action", "reward", "done", "terminated",
                  "behavior_logp", "behavior")
    }
    learn = jax.jit(trainer.learner.learn)
    key, lk = jax.random.split(key)
    s2, m2 = learn(state, learn_batch, lk)
    jax.block_until_ready(m2["loss/pg"])

    def learn_step(s, k):
        s2, m = learn(s, learn_batch, k)
        return s2, m

    dt_learn, _ = _timeit_chained(learn_step, state, key)

    attrib = _learn_attribution(trainer, state, learn_batch, key)

    # no jax.profiler.trace here: tracing slows the host, so the report's
    # trace runs LAST in main(), after all measurements
    out = {
        "attrib": attrib,
        "workload": "PPO+MLP jax:lift (BASELINE ③/north-star class)",
        "geometry": f"{num_envs} envs x {horizon} horizon, 4 epochs x 4 minibatches",
        "env_steps_per_s": sps,
        "iter_ms": dt / ITERS * 1e3,
        "rollout_only_ms": dt_roll / ITERS * 1e3,
        "learn_only_ms": dt_learn / ITERS * 1e3,
        "_trace_fn": lambda: _capture_trace(trainer, state, carry, key),
    }
    if flops is not None:
        out["flops_per_iter"] = flops
        out["model_flops_per_s"] = flops * ITERS / dt
        out["mfu"] = out["model_flops_per_s"] / _peak_flops()
    return out


def _learn_attribution(trainer, state, learn_batch, key) -> dict:
    """Where the learn phase's milliseconds go (round-4 VERDICT weak #1).

    Sub-programs compiled and timed separately at the headline geometry.
    The round-4 finding this documents: with row shuffling (the
    reference's per-epoch reshuffle semantics), ~70% of learn time was
    the per-epoch 1M-element argsort permutation + random row gathers
    (4-byte-row leaves walk the TPU scalar unit); ALL sixteen grad steps
    cost ~20 ms. algo.shuffle='block' (now the default) permutes
    contiguous blocks instead and collapses the learn phase ~17x.
    """
    import jax.numpy as jnp
    import optax

    learner = trainer.learner
    out = {}

    # learn-only under the reference-semantics row shuffle (the A/B)
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config

    row_learner = build_learner(
        Config(algo=Config(shuffle="row")).extend(trainer.learner.config),
        trainer.env.specs,
    )
    learn_row = jax.jit(row_learner.learn)
    key, k0 = jax.random.split(key)
    s0, m0 = learn_row(state, learn_batch, k0)
    jax.block_until_ready(m0["loss/pg"])

    def row_step(s, k):
        s2, m = learn_row(s, learn_batch, k)
        return s2, m["loss/pg"]

    dt_row, _ = _timeit_chained(row_step, state, key)
    out["learn_row_ms"] = dt_row / ITERS * 1e3

    # sub-programs (block learner), each chained + fenced
    obs_n = learner._norm_obs(state.obs_stats, learn_batch["obs"])
    values = learner.model.apply(state.params, obs_n).value
    v_next = learner.model.apply(
        state.params, learner._norm_obs(state.obs_stats, learn_batch["next_obs"])
    ).value
    jax.block_until_ready(values[-1, -1])
    vf = jax.jit(
        lambda s, c: learner.model.apply(
            s.params, learner._norm_obs(s.obs_stats, learn_batch["obs"]) + c
        ).value
        + learner.model.apply(
            s.params, learner._norm_obs(s.obs_stats, learn_batch["next_obs"])
        ).value
    )
    jax.block_until_ready(vf(state, jnp.float32(0))[-1, -1])

    def vf_step(c, k):
        v = vf(state, c)
        # the carry MUST consume the output (the chaining contract): a
        # carry independent of v would let the backend overlap launches
        return v[-1, -1] * 0.0, v[-1, -1]

    _timeit_chained(vf_step, jnp.float32(0), key, iters=2)
    dt_vf, _ = _timeit_chained(vf_step, jnp.float32(0), key)
    out["value_forwards_ms"] = dt_vf / ITERS * 1e3

    # GAE alone
    gb = {k_: learn_batch[k_] for k_ in ("reward", "done", "terminated")}
    g = jax.jit(lambda c: learner._gae(gb, values + c, v_next)[0])
    jax.block_until_ready(g(jnp.float32(0))[-1, -1])

    def g_step(c, k):
        a = g(c)
        return a[-1, -1] * 0.0, a[-1, -1]  # carry consumes the output

    _timeit_chained(g_step, jnp.float32(0), key, iters=2)
    dt_g, _ = _timeit_chained(g_step, jnp.float32(0), key)
    out["gae_ms"] = dt_g / ITERS * 1e3

    # grad steps with NO shuffling/gathers: 16 steps on one fixed slice
    adv, tgt = learner._gae(gb, values, v_next)
    N = adv.size
    flat = {
        "obs": obs_n.reshape(N, *obs_n.shape[2:]),
        "action": learn_batch["action"].reshape(N, -1),
        "behavior_logp": learn_batch["behavior_logp"].reshape(N),
        "adv": adv.reshape(N),
        "target": tgt.reshape(N),
        "value_old": values.reshape(N),
        "b_mean": learn_batch["behavior"]["mean"].reshape(N, -1),
        "b_log_std": learn_batch["behavior"]["log_std"].reshape(N, -1),
    }
    mb0 = jax.tree.map(lambda x: x[: N // 4], flat)
    grad_fn = jax.grad(learner._loss_fn, has_aux=True)

    def steps16(s, k):
        def body(carry, _):
            params, opt_state = carry
            grads, aux = grad_fn(params, mb0, s.kl_beta, jnp.float32(1.0))
            updates, opt_state = learner.tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), aux["kl"]

        (p, o), kls = jax.lax.scan(body, (s.params, s.opt_state), None, length=16)
        return s._replace(params=p, opt_state=o), kls[-1]

    sj = jax.jit(steps16)
    s1, kl1 = sj(state, key)
    jax.block_until_ready(kl1)
    _timeit_chained(lambda s, k: sj(s, k), state, key, iters=2)
    dt_s, _ = _timeit_chained(lambda s, k: sj(s, k), state, key)
    out["gradsteps16_nogather_ms"] = dt_s / ITERS * 1e3
    return out


def impala_pong() -> dict:
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 1024, 32
    cfg = Config(
        learner_config=Config(
            algo=Config(name="impala", horizon=horizon),
            model=Config(cnn=Config(enabled=True)),
        ),
        env_config=Config(name="jax:pong", num_envs=num_envs),
        session_config=Config(
            folder="/tmp/perf_pong",
            metrics=Config(every_n_iters=10_000),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, num_envs)
    for _ in range(WARMUP):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    flops = _iter_flops(trainer._train_iter, state, carry, key)

    def fused_step(sc, k):
        s, c = sc
        s, c, m = trainer._train_iter(s, c, k)
        return (s, c), m

    dt, _ = _timeit_chained(fused_step, (state, carry), key)
    sps = ITERS * num_envs * horizon / dt
    out = {
        "workload": "IMPALA+NatureCNN jax:pong pixels (BASELINE ⑤ class)",
        "geometry": f"{num_envs} envs x {horizon} unroll, 42x42x2 uint8 pixels",
        "env_steps_per_s": sps,
        "iter_ms": dt / ITERS * 1e3,
        "pong_attrib": _pong_attribution(
            trainer, sc_w[0], sc_w[1], key, num_envs, horizon
        ),
    }
    if flops is not None:
        out["flops_per_iter"] = flops
        out["model_flops_per_s"] = flops * ITERS / dt
        out["mfu"] = out["model_flops_per_s"] / _peak_flops()
    return out


def _pong_attribution(trainer, state, carry, key, num_envs, horizon) -> dict:
    """Where the pixel iteration's milliseconds go (round-5 VERDICT weak
    #4: the CNN paths sat at ~3% MFU with no decomposition). Sub-programs
    compiled and timed separately at the pong geometry:

    - env-only: the rollout scan with RANDOM actions (no policy) — pixel
      rendering + game logic;
    - act-only: the NatureCNN policy forward on a fixed [B, 42, 42, 2]
      frame, scanned x horizon — the acting compute;
    - rollout (policy act + env step, the real collector);
    - learn-only: V-trace + one CNN fwd/bwd over the [T, B] batch.
    """
    from surreal_tpu.envs.jax.base import batch_step
    from surreal_tpu.launch.rollout import RolloutCarry, device_rollout

    env = trainer.env
    learner = trainer.learner
    n_actions = env.specs.action.n

    roll = jax.jit(
        lambda s, c, k: device_rollout(env, learner, s, c, k, horizon)
    )
    key, rk = jax.random.split(key)
    carry2, batch = roll(state, carry, rk)
    jax.block_until_ready(batch["reward"][-1])

    def roll_step(c, k):
        c2, b = roll(state, c, k)
        return c2, b["reward"][-1]

    _, cw = _timeit_chained(roll_step, carry, key, iters=2)
    dt_roll, _ = _timeit_chained(roll_step, cw, key)

    def _env_only(c, k):
        def step(cc, k_):
            a = jax.random.randint(k_, (num_envs,), 0, n_actions)
            env_state, obs2, reward, done, _ = batch_step(env, cc.env_state, a)
            return (
                RolloutCarry(env_state, obs2, cc.ep_return, cc.ep_length),
                reward,
            )

        c2, rs = jax.lax.scan(step, c, jax.random.split(k, horizon))
        return c2, rs[-1]

    env_only = jax.jit(_env_only)
    c2, r = env_only(carry, key)
    jax.block_until_ready(r)
    _, cw = _timeit_chained(env_only, carry, key, iters=2)
    dt_env, _ = _timeit_chained(env_only, cw, key)

    obs_fixed = carry.obs

    def _act_only(tot, k):
        def step(t, k_):
            a, info = learner.act(state, obs_fixed, k_, "training")
            return t + info["logp"].sum(), a

        t2, _ = jax.lax.scan(step, tot, jax.random.split(k, horizon))
        return t2, t2

    act_only = jax.jit(_act_only)
    t2, _ = act_only(jnp.zeros(()), key)
    jax.block_until_ready(t2)
    _, tw = _timeit_chained(act_only, jnp.zeros(()), key, iters=2)
    dt_act, _ = _timeit_chained(act_only, tw, key)

    learn_batch = {
        k: batch[k]
        for k in ("obs", "next_obs", "action", "reward", "done", "terminated",
                  "behavior_logp", "behavior")
    }
    learn = jax.jit(learner.learn)
    key, lk = jax.random.split(key)
    s2, m2 = learn(state, learn_batch, lk)
    jax.block_until_ready(m2["loss/pg"])

    def learn_step(s, k):
        s2, m = learn(s, learn_batch, k)
        return s2, m["loss/pg"]

    _, sw = _timeit_chained(learn_step, state, key, iters=2)
    dt_learn, _ = _timeit_chained(learn_step, sw, key)

    return {
        "num_envs": num_envs,
        "horizon": horizon,
        "rollout_ms": dt_roll / ITERS * 1e3,
        "env_only_ms": dt_env / ITERS * 1e3,
        "act_only_ms": dt_act / ITERS * 1e3,
        "learn_ms": dt_learn / ITERS * 1e3,
    }


def ppo_cnn_nut_pixels() -> dict:
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 512, 32
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=2, num_minibatches=4),
            model=Config(cnn=Config(enabled=True)),
        ),
        env_config=Config(name="jax:nut_pixels", num_envs=num_envs),
        session_config=Config(
            folder="/tmp/perf_nut_pixels",
            metrics=Config(every_n_iters=10_000),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, num_envs)
    for _ in range(WARMUP):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    flops = _iter_flops(trainer._train_iter, state, carry, key)

    def fused_step(sc, k):
        s, c = sc
        s, c, m = trainer._train_iter(s, c, k)
        return (s, c), m

    dt, _ = _timeit_chained(fused_step, (state, carry), key)
    sps = ITERS * num_envs * horizon / dt
    out = {
        "workload": "PPO+NatureCNN jax:nut_pixels (BASELINE ④ class, on-device rendering)",
        "geometry": f"{num_envs} envs x {horizon} horizon, 64x64x4 uint8 pixels",
        "env_steps_per_s": sps,
        "iter_ms": dt / ITERS * 1e3,
    }
    if flops is not None:
        out["flops_per_iter"] = flops
        out["model_flops_per_s"] = flops * ITERS / dt
        out["mfu"] = out["model_flops_per_s"] / _peak_flops()
    return out


def ddpg_prioritized_lift(capacity: int = 200_000) -> dict:
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 2048, 16
    steps_per_iter = num_envs * horizon

    def make_trainer():
        cfg = Config(
            learner_config=Config(
                algo=Config(name="ddpg", horizon=horizon,
                            exploration=Config(warmup_steps=0)),
                replay=Config(kind="prioritized", capacity=capacity,
                              start_sample_size=steps_per_iter,
                              batch_size=256),
            ),
            env_config=Config(name="jax:lift", num_envs=num_envs),
            session_config=Config(
                folder="/tmp/perf_ddpg",
                metrics=Config(every_n_iters=10_000, tensorboard=False,
                               console=False),
                checkpoint=Config(every_n_iters=0),
                eval=Config(every_n_iters=0),
            ),
        ).extend(base_config())
        return OffPolicyTrainer(cfg)

    trainer = make_trainer()
    # warmup run: compile everything (jit cache lives on the trainer)
    trainer.run(max_env_steps=2 * steps_per_iter)
    t0 = time.perf_counter()
    trainer.run(max_env_steps=ITERS * steps_per_iter)
    dt = time.perf_counter() - t0
    sps = ITERS * steps_per_iter / dt
    cap_txt = f"{capacity // 1000}k" if capacity < 10**6 else f"{capacity / 1e6:.0f}M"
    return {
        "workload": "DDPG+prioritized replay jax:lift (BASELINE ③ class)"
        + (" — reference-scale 1e6 buffer" if capacity >= 10**6 else ""),
        "geometry": (
            f"{num_envs} envs x {horizon} collect, 64 updates/iter x 256 batch, "
            f"{cap_txt} prioritized replay"
        ),
        "env_steps_per_s": sps,
        "iter_ms": dt / ITERS * 1e3,
    }


def ddpg_prioritized_lift_1m() -> dict:
    """Round-5 VERDICT missing-measurement #7: the cumsum+searchsorted
    sampler (no sum-tree — replay/prioritized.py design note) measured at
    the reference-scale 1e6 capacity ON CHIP. The per-sample cost is one
    fused O(N) bandwidth-bound pass (~8 MB through HBM at 1e6 x f32); if
    this row collapses vs the 200k row, the two-level segmented cumsum is
    the planned fix — the measurement decides."""
    return ddpg_prioritized_lift(capacity=1_000_000)


def headline_scaling() -> list[dict]:
    """Throughput vs geometry for the headline workload — how far the
    batch amortizes per-iteration dispatch before compute saturates."""
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    rows = []
    for num_envs, horizon in (
        (1024, 256), (2048, 256), (4096, 256), (8192, 256), (16384, 256)
    ):
        cfg = Config(
            learner_config=Config(
                algo=Config(name="ppo", horizon=horizon, epochs=4, num_minibatches=4),
            ),
            env_config=Config(name="jax:lift", num_envs=num_envs),
            session_config=Config(
                folder="/tmp/perf_scaling",
                metrics=Config(every_n_iters=10_000),
                checkpoint=Config(every_n_iters=0),
                eval=Config(every_n_iters=0),
            ),
        ).extend(base_config())
        trainer = Trainer(cfg)
        key = jax.random.key(0)
        key, init_key, env_key = jax.random.split(key, 3)
        state = trainer.learner.init(init_key)
        carry = init_device_carry(trainer.env, env_key, num_envs)
        for _ in range(WARMUP):
            key, it_key = jax.random.split(key)
            state, carry, metrics = trainer._train_iter(state, carry, it_key)
        jax.block_until_ready(metrics)

        def fused_step(sc, k, _t=trainer):
            s, c = sc
            s, c, m = _t._train_iter(s, c, k)
            return (s, c), m

        dt, _ = _timeit_chained(fused_step, (state, carry), key)
        rows.append(
            {
                "geometry": f"{num_envs} x {horizon}",
                "env_steps_per_s": ITERS * num_envs * horizon / dt,
                "iter_ms": dt / ITERS * 1e3,
            }
        )
        print(json.dumps(rows[-1], default=float))
    return rows


def ppo_trajectory_pendulum() -> dict:
    """The long-context path's own cost (round-4/5 capability —
    model.encoder.kind='trajectory'): fused rollout with KV-cached
    incremental acting (O(T) attention per env step) + whole-segment
    sequence learn, on the trajectory-tested pendulum workload. No
    BASELINE class covers this (the reference has no attention policies);
    the row documents what the capability costs next to the MLP headline."""
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 1024, 128
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=2, num_minibatches=2),
            model=Config(
                encoder=Config(
                    kind="trajectory", features=64, num_layers=2,
                    num_heads=4, head_dim=16,
                )
            ),
        ),
        env_config=Config(name="jax:pendulum", num_envs=num_envs),
        session_config=Config(
            folder="/tmp/perf_traj",
            metrics=Config(every_n_iters=10_000),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    carry = init_device_carry(trainer.env, env_key, num_envs)
    for _ in range(WARMUP):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)
    flops = _iter_flops(trainer._train_iter, state, carry, key)

    def fused_step(sc, k):
        s, c = sc
        s, c, m = trainer._train_iter(s, c, k)
        return (s, c), m

    _, sc_w = _timeit_chained(fused_step, (state, carry), key, iters=2)
    dt, _ = _timeit_chained(fused_step, sc_w, key)
    sps = ITERS * num_envs * horizon / dt
    out = {
        "workload": "PPO+trajectory-transformer jax:pendulum (long-context "
                    "path; beyond-reference capability)",
        "geometry": f"{num_envs} envs x {horizon} horizon, 2-layer causal "
                    "attention, KV-cached acting",
        "env_steps_per_s": sps,
        "iter_ms": dt / ITERS * 1e3,
    }
    if flops is not None:
        out["flops_per_iter"] = flops
        out["model_flops_per_s"] = flops * ITERS / dt
        out["mfu"] = out["model_flops_per_s"] / _peak_flops()
    return out


def host_env_cheetah():
    """BASELINE config ② (PPO on dm_control cheetah-run, 32 actors) — the
    reference's ACTUAL operating shape: CPU MuJoCo envs feeding the chip
    per step (upstream `surreal/agent/base.py` actors + `surreal/replay/
    base.py` over ZMQ; SURVEY.md §3.2-3.3). Round-5 VERDICT missing #1:
    this was the one perf surface with no on-chip number.

    Measures three drive modes on the real chip, plus a per-phase
    attribution of the alternation iteration:

    - host-alternation Trainer, ``topology.overlap_rollouts=false``
      (strict rollout -> learn; the chip idles during env stepping);
    - the same with ``overlap_rollouts=true`` (double-buffered collector
      thread — iteration ~ max(rollout, learn));
    - the SEED path (``num_env_workers`` OS processes -> InferenceServer
      -> learner), the reference's disaggregated fleet shape.
    """
    try:
        import dm_control  # noqa: F401
    except Exception:
        print("dm_control unavailable; skipping host-env workload")
        return None
    import shutil
    import tempfile
    from functools import partial

    import numpy as np

    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.rollout import host_rollout
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    num_envs, horizon = 32, 64

    def _cfg(folder, overlap, workers=0, worker_envs=None):
        return Config(
            learner_config=Config(
                algo=Config(name="ppo", horizon=horizon, epochs=4,
                            num_minibatches=4),
            ),
            env_config=Config(
                name="dm_control:cheetah-run",
                num_envs=worker_envs if worker_envs else num_envs,
            ),
            session_config=Config(
                folder=folder,
                total_env_steps=10**12,
                metrics=Config(every_n_iters=1, tensorboard=False,
                               console=False),
                checkpoint=Config(every_n_iters=0),
                eval=Config(every_n_iters=0),
                topology=Config(
                    overlap_rollouts=overlap,
                    num_env_workers=workers,
                    worker_mode="process",
                ),
            ),
        ).extend(base_config())

    # -- per-phase attribution (hand-rolled alternation loop) ---------------
    cfg0 = _cfg("/tmp/perf_cheetah_attrib", overlap=False)
    env = make_env(cfg0.env_config)
    learner = build_learner(cfg0.learner_config, env.specs)
    act = jax.jit(partial(learner.act, mode="training"))
    learn = jax.jit(learner.learn)
    key = jax.random.key(0)
    key, ik, rk, lk = jax.random.split(key, 4)
    state = learner.init(ik)
    obs = env.reset(seed=0)
    # warmup: compile act + learn
    obs, batch, _ = host_rollout(env, act, state, obs, rk, horizon)
    state, m = learn(state, batch, lk)
    jax.block_until_ready(m["loss/pg"])

    def t_phase(fn, n):
        t0 = time.perf_counter()
        for i in range(n):
            fn(i)
        return (time.perf_counter() - t0) / n * 1e3  # ms per call

    # policy act: TWO device round trips per env step — the obs upload
    # (numpy -> device, exactly what host_rollout's jnp.asarray does per
    # step) and the action download (the device_get). Passing the numpy
    # obs into the jit makes the upload part of the measured call.
    obs_np = np.asarray(obs)
    akeys = jax.random.split(key, 64)
    act_ms = t_phase(
        lambda i: jax.device_get(act(state, obs_np, akeys[i])[0]), 64
    )
    # env step: 32 serial MuJoCo steps on the host
    fixed_action = np.zeros((num_envs, *env.specs.action.shape), np.float32)
    env_ms = t_phase(lambda i: env.step(fixed_action), 64)
    # learn: fenced
    def learn_once(i):
        nonlocal state
        state, mm = learn(state, batch, akeys[i])
        jax.block_until_ready(mm["loss/pg"])
    learn_ms = t_phase(learn_once, 5)
    host_attrib = {
        "act_ms_per_step": act_ms,
        "env_ms_per_step": env_ms,
        "learn_ms_per_iter": learn_ms,
        "rollout_projected_ms": (act_ms + env_ms) * horizon,
    }
    env.close()

    # -- whole-trainer wall-clock, three drive modes ------------------------
    WARM_ITERS, MEAS_ITERS = 3, 12

    def timed_run(trainer_cls, config):
        trainer = trainer_cls(config)
        marks = []  # (t, env_steps): measured steps, not an assumed
        # per-iteration width (SEED chunk width halves under pipelining)

        def on_m(it, m):
            marks.append((time.perf_counter(), m["time/env_steps"]))
            return len(marks) >= WARM_ITERS + MEAS_ITERS

        trainer.run(on_metrics=on_m)
        if hasattr(trainer, "env") and hasattr(trainer.env, "close"):
            trainer.env.close()
        n = len(marks) - WARM_ITERS
        (t0, s0), (t1, s1) = marks[WARM_ITERS - 1], marks[-1]
        return (s1 - s0) / (t1 - t0), (t1 - t0) / n * 1e3

    folders = [tempfile.mkdtemp(prefix="perf_cheetah_") for _ in range(3)]
    try:
        sps_alt, iter_alt = timed_run(Trainer, _cfg(folders[0], overlap=False))
        print(json.dumps({"host_env_alternate_sps": sps_alt,
                          "iter_ms": iter_alt}, default=float))
        sps_ovl, iter_ovl = timed_run(Trainer, _cfg(folders[1], overlap=True))
        print(json.dumps({"host_env_overlap_sps": sps_ovl,
                          "iter_ms": iter_ovl}, default=float))
        from surreal_tpu.launch.seed_trainer import SEEDTrainer

        # 4 worker processes x 8 envs = the same 32-env fleet (chunk
        # geometry [horizon, 4] per pipelined sub-slice)
        sps_seed, iter_seed = timed_run(
            SEEDTrainer, _cfg(folders[2], overlap=False, workers=4, worker_envs=8)
        )
        print(json.dumps({"host_env_seed_sps": sps_seed,
                          "iter_ms": iter_seed}, default=float))
    finally:
        for f in folders:
            shutil.rmtree(f, ignore_errors=True)

    host_attrib.update(
        alternate_sps=sps_alt, alternate_iter_ms=iter_alt,
        overlap_sps=sps_ovl, overlap_iter_ms=iter_ovl,
        seed_sps=sps_seed, seed_iter_ms=iter_seed,
    )
    best = max(sps_alt, sps_ovl, sps_seed)
    return {
        "host_attrib": host_attrib,
        "workload": "PPO dm_control:cheetah-run — HOST MuJoCo envs feeding "
                    "the chip (BASELINE ② — the reference's operating shape)",
        "geometry": f"{num_envs} CPU envs x {horizon} horizon, best of "
                    "alternate/overlap/SEED-4-proc",
        "env_steps_per_s": best,
        "iter_ms": iter_ovl if best == sps_ovl else (
            iter_alt if best == sps_alt else iter_seed
        ),
    }


def _load_host_bench():
    """Load the host data-plane artifact (`BENCH_host.json`, written by
    `perf_wallclock.py --host-path` / `bench.py --host-path`) if present —
    like block_vs_row.json, keeping it as an artifact lets PERF.md regens
    preserve the measured section without re-running the campaign."""
    try:
        with open("BENCH_host.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or "value" not in data:
        return None  # failed-round artifact ({"error": ..., "parsed": null})
    return data


def _host_data_plane_lines() -> list[str]:
    """The 'Host data plane rebuild' PERF.md section: static mechanism
    text plus the measured table from the BENCH_host.json artifact when
    one exists. One function so `main()` and the standalone section
    patcher cannot drift."""
    lines = [
        "",
        "## Host data plane rebuild (zero-copy shm transport + pipelined "
        "env workers)",
        "",
        "The SEED host path was rebuilt end to end "
        "(`distributed/shm_transport.py`), attacking the 288 steps/s row "
        "above — which paid a full pickle of the obs/reward/done dict, a "
        "TCP round trip carrying those bytes, and an action re-pickle on "
        "EVERY worker step, with each worker idle for the whole server "
        "round trip:",
        "",
        "- **Zero-copy transport** — per-worker shared-memory slabs "
        "(obs/reward/done/truncated/terminal_obs in, actions out) "
        "negotiated at a hello handshake; afterwards ZMQ carries only "
        "~20-byte control frames (slot index, flags, latency/occupancy "
        "gauges, episode-stat floats). The server OWNS every segment — "
        "created at hello, reused when a respawned worker re-negotiates "
        "through ROUTER_HANDOVER, unlinked at close — so a SIGKILLed "
        "worker cannot leak `/dev/shm` (tests assert this). The original "
        "pickle wire remains the negotiated fallback (thread-mode tests, "
        "remote workers), per worker and invisible to the trainer; a "
        "record-equivalence test proves both transports assemble "
        "byte-identical trajectory chunks for the same seed.",
        "- **Pipelined workers** — `run_env_worker` splits its env slice "
        "into two sub-slices and keeps one sub-slice's request in flight "
        "while stepping the other (double-buffered acting, Stooke & "
        "Abbeel 1803.02811), hiding the act round trip that the old "
        "strictly-serial send→poll→step loop ate per step "
        "(`topology.pipeline_workers`).",
        "- **Copy-free server assembly + auto-tuned coalescing** — "
        "`_serve_batch` reads worker slabs straight into one preallocated "
        "scratch batch (no per-serve `np.concatenate`, no per-slice "
        "pickling), writes action slices directly into each worker's "
        "action slab, and retunes `min_batch`/`max_wait_ms` from the "
        "live connected-worker count and its serve-latency EWMA, so the "
        "fleet keeps coalescing into one forward per lockstep round "
        "through worker death and respawn.",
    ]
    hostdp = _load_host_bench()
    if hostdp:
        shm_r, pkl_r = hostdp.get("shm", {}), hostdp.get("pickle", {})
        lines += [
            "",
            f"Measured through the real SEED trainer at the record's "
            f"geometry ({hostdp['geometry']}; `BENCH_host.json`, platform "
            f"`{hostdp.get('platform')}`; warm iterations discarded):",
            "",
            "| Transport | env steps/s | wire bytes/step | iter ms |",
            "|---|---|---|---|",
            "| shm (negotiated; pipelined sub-slices) | "
            f"{shm_r.get('env_steps_per_s', 0):,.0f} | "
            f"{shm_r.get('transport', {}).get('wire_bytes_per_step', 0):,.1f} | "
            f"{shm_r.get('iter_ms', 0):,.1f} |",
            "| pickle fallback (same geometry) | "
            f"{pkl_r.get('env_steps_per_s', 0):,.0f} | "
            f"{pkl_r.get('transport', {}).get('wire_bytes_per_step', 0):,.1f} | "
            f"{pkl_r.get('iter_ms', 0):,.1f} |",
            "",
            f"**{hostdp['vs_host_baseline']:.0f}x the 288 steps/s "
            "round-5 record** with the shm transport active at the same "
            "32-env x 64-horizon dm_control geometry. Honesty notes: "
            "this artifact was measured on "
            f"`{hostdp.get('platform')}` (no chip in the round), "
            "and on this one-core box BOTH transports now saturate the "
            "LEARNER, not the wire — their steps/s agree to within the "
            "run-to-run spread (a cheaper send lets workers outrun the "
            "saturated learner and burn the shared core on steps the "
            "eviction path discards), and the transport's direct win "
            "shows in the wire gauge (the bytes column: control frames "
            "vs pickled arrays, "
            f"~{pkl_r.get('transport', {}).get('wire_bytes_per_step', 0) / max(shm_r.get('transport', {}).get('wire_bytes_per_step', 1), 1e-9):,.0f}"
            "x less traffic) and in the serve path doing zero "
            "serialization work. The old 288 record was transport/latency"
            "-bound; the rebuilt plane moved the bottleneck back to "
            "compute, which is the point.",
        ]
    return lines


def _load_experience_bench():
    """Load the experience-plane artifact (``BENCH_experience.json``,
    written by ``bench.py --experience-plane``) if present — like
    BENCH_host.json, keeping it as an artifact lets PERF.md regens
    preserve the measured section without re-running the campaign."""
    try:
        with open("BENCH_experience.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact ({"error": ..., "parsed": null})
    return data


def _experience_plane_lines() -> list[str]:
    """The 'Sharded experience plane' PERF.md section: static mechanism
    text plus the measured per-transport table from the
    BENCH_experience.json artifact when one exists. One function so
    ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Sharded experience plane (cross-host replay shards + "
        "never-blocking learner sampler)",
        "",
        "The ExperienceSender -> ShardedReplay path the reference ran as "
        "separate processes behind a caraml proxy, rebuilt as "
        "`surreal_tpu/experience/` (ISSUE 8): `ReplayShardServer` "
        "processes own host-memory NumPy rings mirroring `replay/base.py` "
        "semantics (uniform sampling BIT-EQUAL to the in-process replay "
        "for the same keys — tested; prioritized within a documented f32 "
        "tolerance), actors hash-route env slots to shards through an "
        "`ExperienceSender` with bounded retry/backoff and slab/window "
        "backpressure, and the learner's `ShardedSampler` fans in every "
        "iteration's batches through a `Prefetcher` during the PREVIOUS "
        "iteration's SGD drain — the learner never waits on experience "
        "ingest (the residue is the `experience/sample_wait_ms` gauge, "
        "gated by perf_gate). The wire negotiates per peer at a hello "
        "carrying the run trace id: shm slabs same-host, a length-framed "
        "tcp codec cross-host, pickle as the fallback (sampling-near-the-"
        "data per arXiv:2110.13506; the disaggregated tier shape of "
        "RollArt, arXiv:2512.22560). Priority updates ship as ONE batched "
        "frame per shard per iteration (`sample_many`'s discipline "
        "on-wire); sample requests carry ingestion watermarks so "
        "strict-mode training records are exactly reproducible.",
    ]
    xp = _load_experience_bench()
    if xp:
        lines += [
            "",
            f"Measured through the real off-policy trainer at the "
            f"local-shards geometry ({xp['geometry']}; "
            f"`BENCH_experience.json`, platform `{xp.get('platform')}`; "
            "warm iterations discarded):",
            "",
            "| Arm | env steps/s | iter ms | wire B/step | learner "
            "sample-wait ms | final return |",
            "|---|---|---|---|---|---|",
        ]
        for name in ("inprocess", "shm", "tcp", "pickle"):
            r = xp.get(name) or {}
            wire = r.get("wire_bytes_per_step")
            wait = r.get("sample_wait_ms")
            lines.append(
                "| {a} | {s:,.0f} | {ms:.1f} | {w} | {sw} | {fr} |".format(
                    a=r.get("arm", name),
                    s=float(r.get("env_steps_per_s", 0)),
                    ms=float(r.get("iter_ms", 0)),
                    w=f"{float(wire):.1f}" if wire is not None else "n/a (in-process)",
                    sw=f"{float(wait):.2f}" if wait is not None else "n/a",
                    fr=(
                        f"{float(r['final_return']):.0f}"
                        if r.get("final_return") is not None else "n/a"
                    ),
                )
            )
        shm = xp.get("shm") or {}
        record = float(xp.get("shm_wire_record_bps", 5.8))
        wire = float(shm.get("wire_bytes_per_step") or 0.0)
        lines += [
            "",
            f"The shm arm's wire carries {wire:.1f} B per ingested "
            f"transition (control frames + sample requests only; the "
            f"PR-3 slab record is {record:.1f} B/step — the gate commits "
            f"to <= 2x), and the learner's sample-wait is "
            f"{float(shm.get('sample_wait_ms') or 0):.2f} ms against a "
            f"{float(shm.get('iter_ms') or 0):.1f} ms iteration: the "
            "prefetched fan-in keeps the learner fed from batches staged "
            "during the previous drain. The fixed-seed reward "
            "trajectories of the remote arms ride the artifact next to "
            "the in-process reference's (the curves track each other; "
            "per-shard sampling is the same stratified-composition "
            "change the dp-sharded device replay documents). Honesty "
            "notes: this box measures LOCAL thread shards — the "
            "cross-host claim is the negotiated tcp codec itself, "
            "exercised as a first-class arm; and on one core the remote "
            "arms pay the shard servers' CPU time out of the same core "
            "the learner uses, so steps/s differences between arms are "
            "dominated by that contention, not by the wire.",
        ]
    return lines


def _load_act_bench():
    """Load the act-serving-tier artifact (``BENCH_act.json``, written by
    ``bench.py --act-path``) if present — the BENCH_host.json discipline:
    PERF.md regens preserve the measured section without re-running."""
    try:
        with open("BENCH_act.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _act_path_lines() -> list[str]:
    """The 'Autoscaling act-serving tier' PERF.md section: static
    mechanism text plus the measured 1-vs-N replica table and the
    fanout bytes-per-publish table from the BENCH_act.json artifact.
    One function so ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Autoscaling act-serving tier (replicated inference servers "
        "+ versioned parameter fanout)",
        "",
        "The last unscaled hop after the experience plane: one "
        "`InferenceServer` process owned the whole act path, and "
        "`ParameterClient.fetch` shipped a full msgpack pytree "
        "point-to-point per client. `distributed/fleet.py` replicates "
        "the server (ISSUE 10; the disaggregated inference tier of "
        "RollArt, arXiv:2512.22560, on the act-throughput discipline of "
        "Accelerated Methods, arXiv:1803.02811): workers "
        "rendezvous-hash to a replica at spawn and stay there (session "
        "affinity — trajectory streams and shm slabs keep one owner), "
        "each replica coalesces with its OWN `min_batch` budget (its "
        "affinity share, auto-tuned against per-replica liveness), a "
        "dead replica respawns in place under the PR-5 exponential "
        "backoff while its workers re-hello to survivors "
        "(chaos-tested), and autoscaling adds/drains replicas off the "
        "serve-latency EWMA within `[min_replicas, max_replicas]`. "
        "Parameter distribution becomes a broadcast "
        "(`distributed/param_fanout.py`): versioned weight frames over "
        "pub/sub — one encode + N subscribes — with a zlib'd "
        "delta arm keyed to subscriber acks (a stale ack re-keys with a "
        "full frame) and a bf16 wire arm (f32 reconstruct, exactly the "
        "bf16-rounded value); `ParameterClient.fetch` stays as the "
        "late-joiner/fallback path, counted never silent.",
    ]
    act = _load_act_bench()
    if act:
        single, fleet = act.get("single") or {}, act.get("fleet") or {}
        lines += [
            "",
            f"Measured through the real SEED trainer at the act-path "
            f"geometry ({act.get('geometry', 'unrecorded')}; "
            f"`BENCH_act.json`, platform "
            f"`{act.get('platform')}`; warm iterations discarded):",
            "",
            "| Replicas | env steps/s | iter ms | serve p50 ms | "
            "serve p99 ms |",
            "|---|---|---|---|---|",
        ]
        for r in (single, fleet):
            p50, p99 = r.get("serve_ms_p50"), r.get("serve_ms_p99")
            lines.append(
                "| {n} | {s:,.0f} | {ms:.1f} | {p50} | {p99} |".format(
                    n=r.get("replicas", "?"),
                    s=float(r.get("env_steps_per_s", 0)),
                    ms=float(r.get("iter_ms", 0)),
                    p50=f"{float(p50):.2f}" if p50 is not None else "n/a",
                    p99=f"{float(p99):.2f}" if p99 is not None else "n/a",
                )
            )
        fan = act.get("fanout") or {}
        arms = fan.get("arms") or {}
        if arms:
            lines += [
                "",
                f"Fanout bytes per publish (acting view of a "
                f"{'x'.join(str(h) for h in fan.get('model_hidden', []))} "
                f"MLP policy; point-to-point baseline = one "
                f"`ParameterClient.fetch` blob per client, "
                f"{float(fan.get('pointtopoint_fetch_bytes', 0)):,.0f} B "
                "x N clients; steady bytes exclude the first key frame):",
                "",
                "| Arm | steady B/publish | first frame B | reconstruct "
                "max abs err |",
                "|---|---|---|---|",
            ]
            for name in ("full_f32", "delta", "bf16", "delta_bf16"):
                a = arms.get(name) or {}
                if not a:
                    continue
                lines.append(
                    "| {n} | {b:,.0f} | {f:,.0f} | {e:.2e} |".format(
                        n=name,
                        b=float(a.get("bytes_per_publish", 0)),
                        f=float(a.get("first_frame_bytes", 0)),
                        e=float(a.get("reconstruct_abs_err_max", 0)),
                    )
                )
        ratio = None
        if single.get("env_steps_per_s") and fleet.get("env_steps_per_s"):
            ratio = (
                float(fleet["env_steps_per_s"])
                / float(single["env_steps_per_s"])
            )
        lines += [
            "",
            "Honesty notes: this box has ONE core, so the "
            f"{fleet.get('replicas', 'N')}-replica arm cannot win here "
            "by construction — each lockstep round's single coalesced "
            "forward becomes N SERIAL smaller forwards (per-dispatch "
            "overhead dominates a small CPU act), and the extra serve "
            "thread contends with the learner for the same core. The "
            "gated commitment locally is that replication does not "
            "COLLAPSE throughput "
            + (
                f"(measured ratio {ratio:.2f} vs the "
                f">= {float(act.get('act_honesty_ratio', 0.5)):.2f} "
                "bound); " if ratio is not None else "; "
            )
            + "the scaling claim is the tier mechanism itself — "
            "affinity routing, per-replica budgets, survivor re-hello — "
            "exercised for real, with cross-core speedups to be "
            "recorded on a multi-core measurement round. The fanout "
            "bytes table is platform-independent (codec arithmetic, no "
            "timed window); delta/bf16 both sit below the full-f32 "
            "frame, which itself replaces N per-client fetch blobs "
            "with one encode (gated by `perf_gate.gate_act`).",
        ]
    return lines


def _load_gateway_bench():
    """Load the session-gateway artifact (``BENCH_gateway.json``, written
    by ``bench.py --gateway``) if present — same BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running the campaign."""
    try:
        with open("BENCH_gateway.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _gateway_lines() -> list[str]:
    """The 'Production session gateway' PERF.md section: static mechanism
    text plus the measured attach/RTT/cache table from the
    BENCH_gateway.json artifact. One function so ``main()`` and the
    committed PERF.md cannot drift."""
    lines = [
        "",
        "## Production session gateway (multi-tenant act serving)",
        "",
        "The fleet's act path was internal-only: workers rendezvous-hash "
        "to a replica at spawn and speak the private worker protocol. "
        "`gateway/` (ISSUE 12) puts a tenant-facing front on it: "
        "`GatewayServer` owns attach/detach sessions with ids and "
        "leases (a silent tenant is reaped, counted), admission control "
        "per tenant (token-bucket act rates, max-session quotas, "
        "bounded backpressure queues that evict oldest, counted never "
        "silent), and a session table whose journal of wire frames "
        "self-compacts and replays onto a survivor when a replica dies "
        "— the tenant's next act lands on the new replica without the "
        "session id changing (chaos-tested: invisible failover). "
        "Sessions may pin a parameter version; the fanout holds pinned "
        "versions until released, and an evicted pin triggers a counted "
        "`catch_up` to the live version instead of a silent swap. A "
        "bounded LRU act cache keyed on (version, obs digest) serves "
        "repeat observations without a forward.",
    ]
    gw = _load_gateway_bench()
    if gw:
        attach = gw.get("attach_ms") or {}
        rtt = gw.get("act_rtt_ms") or {}
        direct = gw.get("direct_ms") or {}
        cache = gw.get("cache") or {}
        hit = cache.get("hit_ms") or {}
        served = cache.get("served_ms") or {}
        lines += [
            "",
            f"Measured against a live 2-replica fleet serving the "
            f"{gw.get('policy', 'benchmark')} policy "
            f"(`BENCH_gateway.json`, platform `{gw.get('platform')}`; "
            "warm iterations discarded):",
            "",
            "| Path | p50 ms | p99 ms |",
            "|---|---|---|",
        ]
        for name, row in (
            ("attach", attach),
            ("act RTT (gateway, cache off)", rtt),
            ("act (direct `fleet.serve_act`)", direct),
            ("act RTT (cache hit)", hit),
            ("act RTT (cache miss -> forward)", served),
        ):
            if not row:
                continue
            p50, p99 = row.get("p50"), row.get("p99")
            lines.append(
                "| {n} | {a} | {b} |".format(
                    n=name,
                    a=f"{float(p50):.3f}" if p50 is not None else "n/a",
                    b=f"{float(p99):.3f}" if p99 is not None else "n/a",
                )
            )
        ratio = gw.get("rtt_ratio_p50")
        lines += [
            "",
            "Honesty notes: this box has ONE core, so the gateway hop "
            "(client thread + gateway serve thread + fleet replica all "
            "contending for it) is measured at its WORST — the gated "
            "commitment is that the wire hop does not double the act "
            + (
                f"(measured RTT/direct p50 ratio {float(ratio):.2f} vs "
                f"the <= {float(gw.get('rtt_ratio_max', 2.0)):.1f}x "
                "bound), " if ratio is not None else ", "
            )
            + "and that a cache hit is STRICTLY faster than a served "
            "forward"
            + (
                f" (hit-rate {float(cache.get('hit_rate', 0)):.2f} on "
                "the duplicated-obs workload)"
                if cache.get("hit_rate") is not None else ""
            )
            + " — both gated by `perf_gate.gate_gateway`, folded into "
            "`gate()`.",
        ]
    return lines


def _load_ops_bench():
    """Load the ops-plane artifact (``BENCH_ops.json``, written by
    ``bench.py --ops-plane``) if present — same BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running the campaign."""
    try:
        with open("BENCH_ops.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _ops_plane_lines() -> list[str]:
    """The 'Live ops plane' PERF.md section: static mechanism text plus
    the measured per-cadence cost table from the BENCH_ops.json
    artifact. One function so ``main()`` and the committed PERF.md
    cannot drift."""
    lines = [
        "",
        "## Live ops plane (cross-tier aggregation, per-tenant SLOs, "
        "flight recorder)",
        "",
        "Telemetry was post-hoc: per-process JSONL that `diag` replays "
        "after the run. `session/opsplane.py` (ISSUE 13) gives a "
        "running multi-tier session ONE live view: every tier (gateway "
        "serve loop, fleet replicas, experience shards, parameter "
        "fanout, learner) pushes its gauge/hop rows over its OWN "
        "cadence-bounded PUSH socket (zmq sockets are not thread-safe; "
        "process tiers inherit the address through spawn kwargs like "
        "the trace id), and the learner-side aggregator merges the "
        "latest row per tier into a trace-id-stamped snapshot at the "
        "metrics cadence — atomically replaced on disk, rendered live "
        "by `surreal_tpu top <folder>`. Declared `session.slo.*` "
        "objectives (act RTT p99, attach p99, per-tenant throttle "
        "rate, parameter staleness) are evaluated per snapshot window "
        "with rolling error budgets: every breached window is a "
        "counted `slo_breach` event, and a budget exhaustion — like a "
        "recovery trip or a chaos fault — dumps the flight recorder's "
        "bounded ring of pre-incident snapshots + fault events to "
        "`telemetry/flightrec/<trigger>/`. A tier silent for 3x its "
        "own declared cadence renders DEAD, never silently fine.",
    ]
    ops = _load_ops_bench()
    if ops:
        snap = ops.get("snapshot_ms") or {}
        push = ops.get("push_ms") or {}
        lines += [
            "",
            f"Measured at a production tier census "
            f"({ops.get('workload', 'benchmark workload')}; "
            f"`BENCH_ops.json`, platform `{ops.get('platform')}`):",
            "",
            "| Cost | p50 ms | p99 ms |",
            "|---|---|---|",
        ]
        for name, row in (
            ("snapshot build (merge + SLO eval + atomic write)", snap),
            ("tier push (serve-loop side, one row)", push),
        ):
            if not row:
                continue
            p50, p99 = row.get("p50"), row.get("p99")
            lines.append(
                "| {n} | {a} | {b} |".format(
                    n=name,
                    a=f"{float(p50):.4f}" if p50 is not None else "n/a",
                    b=f"{float(p99):.4f}" if p99 is not None else "n/a",
                )
            )
        frac = ops.get("snapshot_frac_of_iter")
        iter_ms = ops.get("iter_ms")
        lines += [
            "",
            "Overhead commitment: the whole snapshot path is pure host "
            "python (the transfer-guard suite runs it under "
            "`disallow_device_to_host` — zero device syncs added)"
            + (
                f", and one snapshot costs {float(frac):.2%} of the "
                f"{float(iter_ms):.0f} ms steady-state iteration at the "
                "committed headline geometry (commitment <= "
                f"{float(ops.get('snapshot_frac_max', 0.05)):.0%}"
                if frac is not None and iter_ms is not None else "("
            )
            + "); a tier push is non-blocking with a small HWM — a full "
            "queue drops the row, counted, never stalls a serve loop. "
            "Both gated by `perf_gate.gate_ops`, folded into `gate()`.",
        ]
    return lines


def _load_trace_bench():
    """Load the causal-tracing artifact (``BENCH_trace.json``, written by
    ``bench.py --trace``) if present — same BENCH_host.json discipline:
    PERF.md regens preserve the measured section without re-running the
    campaign."""
    try:
        with open("BENCH_trace.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _trace_lines() -> list[str]:
    """The 'Causal tracing & lineage' PERF.md section: static mechanism
    text plus the measured span/lineage cost table from the
    BENCH_trace.json artifact. One function so ``main()`` and the
    committed PERF.md cannot drift."""
    lines = [
        "",
        "## Causal tracing & experience lineage",
        "",
        "Aggregate gauges say a tier is slow; they cannot say what ONE "
        "request did. `session/telemetry.py` (ISSUE 14) head-samples "
        "exemplars (1-in-`telemetry.trace.sample_n` per gateway session "
        "and per worker stream) and threads a `TraceContext` "
        "(trace/span/parent ids) through every hop it touches — gateway "
        "act frame -> fleet replica's coalesced forward -> reply, and "
        "worker STEP -> inference server -> experience chunk -> the "
        "learner dispatch that consumed it. Each hop emits a `span` "
        "event; `surreal_tpu trace <folder>` assembles them into "
        "per-exemplar span-tree timelines (pure file reading, like "
        "`top`), with chaos-dropped hops counted in "
        "`trace/dropped_spans` and rendered as torn, never hidden. "
        "Independently, every transition is stamped at collection with "
        "its lineage (worker, episode, step range, acting policy "
        "version); the learner reduces each batch's version column into "
        "the EXACT per-update staleness distribution (`lineage/*` "
        "gauges, pure host numpy over an already-fetched column — zero "
        "device syncs), which replaces the ops plane's "
        "published-vs-held staleness approximation in the SLO "
        "evaluation (`staleness_source: lineage`).",
    ]
    tr = _load_trace_bench()
    if tr:
        span = tr.get("span_emit_ms") or {}
        lin = tr.get("lineage_reduce_ms") or {}
        lines += [
            "",
            f"Measured at the headline census ({tr.get('workload', 'benchmark workload')}; "
            f"`BENCH_trace.json`, platform `{tr.get('platform')}`):",
            "",
            "| Cost | p50 ms | p99 ms |",
            "|---|---|---|",
        ]
        for name, row in (
            ("span emit (JSONL append + exemplar ring)", span),
            (f"lineage reduce ({tr.get('lineage_rows', '?')} rows)", lin),
        ):
            if not row:
                continue
            p50, p99 = row.get("p50"), row.get("p99")
            lines.append(
                "| {n} | {a} | {b} |".format(
                    n=name,
                    a=f"{float(p50):.4f}" if p50 is not None else "n/a",
                    b=f"{float(p99):.4f}" if p99 is not None else "n/a",
                )
            )
        frac = tr.get("overhead_frac_of_iter")
        iter_ms = tr.get("iter_ms")
        lines += [
            "",
            f"One span costs {float(tr.get('bytes_per_span', 0)):.0f} B "
            f"on disk at {float(tr.get('spans_per_s', 0)):,.0f} spans/s"
            + (
                f"; the modeled per-iteration census "
                f"({tr.get('spans_per_iter')} spans priced at p99 + one "
                f"full lineage reduction) costs {float(frac):.3%} of the "
                f"{float(iter_ms):.0f} ms steady-state iteration "
                f"(commitment <= "
                f"{float(tr.get('overhead_frac_max', 0.02)):.0%})"
                if frac is not None and iter_ms is not None else ""
            )
            + ". Gated by `perf_gate.gate_trace`, folded into `gate()`.",
        ]
    return lines


def _load_watchdog_bench():
    """Load the watchdog artifact (``BENCH_watchdog.json``, written by
    ``bench.py --watchdog``) if present — same BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running the campaign."""
    try:
        with open("BENCH_watchdog.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _watchdog_lines() -> list[str]:
    """The 'Watchdog & incidents' PERF.md section: static mechanism text
    plus the measured sweep-cost table from the BENCH_watchdog.json
    artifact. One function so ``main()`` and the committed PERF.md
    cannot drift."""
    lines = [
        "",
        "## Watchdog & incident engine",
        "",
        "PRs 13-14 collect; ISSUE 15 interprets. `session/watchdog.py` "
        "runs a detector sweep over every merged ops snapshot (the "
        "metrics cadence): robust median/MAD breakouts on the headline "
        "latencies and throughputs (iteration time, env steps/s, "
        "sample-wait, gateway act-RTT p99, fleet serve), queue/"
        "backpressure saturation and respawn-rate bursts, monotonic "
        "growth of every counted-never-silent `*dropped*`/`*bad_frames` "
        "counter plus the `lineage/staleness_p99` ramp, tier liveness "
        "from the ops plane's DEAD rendering, and online regression "
        "against the committed BENCH baseline for the live platform "
        "fingerprint (`perf_gate.load_rows`). Firings feed "
        "`session/incidents.py`, which opens root-caused incidents: "
        "evidence correlated in a bounded window (chaos faults, "
        "recovery trips, SLO breaches, slowest exemplar spans, dead "
        "tiers), cause hypotheses ranked upstream-first over the static "
        "tier dataflow graph, one auto-captured profiler window + "
        "flight-recorder dump per incident (cooldown-bounded), closed "
        "only on sustained-healthy windows. `surreal_tpu why <folder>` "
        "renders the records (pure file reading, like `top`/`trace`); "
        "every sweep is pure host arithmetic over the snapshot dict — "
        "zero added device->host syncs (transfer-guard tested).",
    ]
    wd = _load_watchdog_bench()
    if wd:
        ev = wd.get("eval_ms") or {}
        lines += [
            "",
            f"Measured at the production census ({wd.get('workload', 'benchmark workload')}; "
            f"`BENCH_watchdog.json`, platform `{wd.get('platform')}`):",
            "",
            "| Cost | p50 ms | p99 ms |",
            "|---|---|---|",
        ]
        p50, p99 = ev.get("p50"), ev.get("p99")
        lines.append(
            "| detector sweep + incident observe | {a} | {b} |".format(
                a=f"{float(p50):.4f}" if p50 is not None else "n/a",
                b=f"{float(p99):.4f}" if p99 is not None else "n/a",
            )
        )
        open_ms = wd.get("incident_open_ms")
        if open_ms is not None:
            lines.append(
                f"| incident open e2e (sweep -> ranked record on disk) "
                f"| {float(open_ms):.4f} | — |"
            )
        frac = wd.get("eval_frac_of_iter")
        iter_ms = wd.get("iter_ms")
        lines += [
            "",
            (
                f"The sweep p99 costs {float(frac):.3%} of the "
                f"{float(iter_ms):.0f} ms steady-state iteration "
                f"(commitment <= "
                f"{float(wd.get('eval_frac_max', 0.01)):.0%})"
                if frac is not None and iter_ms is not None
                else "The overhead fraction was not recorded"
            )
            + ". Gated by `perf_gate.gate_watchdog`, folded into "
            "`gate()`.",
        ]
    return lines


def _load_control_bench():
    """Load the control-loop artifact (``BENCH_control.json``, written
    by ``bench.py --control``) if present — same BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running the campaign."""
    try:
        with open("BENCH_control.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _control_lines() -> list[str]:
    """The 'Closed-loop control' PERF.md section: static mechanism text
    plus the measured decision-sweep table from the BENCH_control.json
    artifact. One function so ``main()`` and the committed PERF.md
    cannot drift."""
    lines = [
        "",
        "## Closed-loop control & load generator",
        "",
        "ISSUE 15 diagnoses; ISSUE 16 acts. `session/remediate.py` runs "
        "one bounded decision sweep per metrics cadence: the open "
        "incident's top-ranked cause tier maps to exactly one action on "
        "an existing actuator (fleet `scale_up`, per-tenant admission "
        "`set_quota` throttle/shed, RespawnSchedule-backed targeted "
        "restart, learner batch/precision downshift), guarded in order "
        "by a per-run action budget, per-kind cooldowns, and one-action-"
        "per-incident in flight. Every action is journaled atomically "
        "(`telemetry/actions/action-<n>.json`, `remediation` events, "
        "`remediation/*` gauges) and watched by a counter-detector: the "
        "action's objective is sampled for `verify_windows` post-action "
        "sweeps, and an action whose objective regressed further is "
        "ruled ineffective and reverted where reversible — counted, "
        "never silent. `gateway/loadgen.py` replays the PR-12 chaos "
        "sites as tenant traffic (steady pacing, attach storms, hot-key "
        "hammering, act bursts, adversarial frames) so the loop is "
        "exercised against production-shaped load.",
    ]
    ct = _load_control_bench()
    if ct:
        dec = ct.get("decide_ms") or {}
        lines += [
            "",
            f"Measured at the production census ({ct.get('workload', 'benchmark workload')}; "
            f"`BENCH_control.json`, platform `{ct.get('platform')}`):",
            "",
            "| Cost | p50 ms | p99 ms |",
            "|---|---|---|",
        ]
        p50, p99 = dec.get("p50"), dec.get("p99")
        lines.append(
            "| remediation decision sweep (action in flight) | {a} | {b} |".format(
                a=f"{float(p50):.4f}" if p50 is not None else "n/a",
                b=f"{float(p99):.4f}" if p99 is not None else "n/a",
            )
        )
        e2e = ct.get("incident_to_action_ms")
        if e2e is not None:
            lines.append(
                f"| incident -> journaled action e2e (detect + map + "
                f"actuate + write) | {float(e2e):.4f} | — |"
            )
        lg = ct.get("loadgen") or {}
        if lg.get("acts_per_s") is not None:
            lines += [
                "",
                (
                    f"The load generator sustained "
                    f"{float(lg['acts_per_s']):.1f} acts/s against a "
                    f"live fleet + gateway "
                    f"(offered {float(lg.get('offered_hz', 0)):.0f} Hz, "
                    f"client act RTT "
                    f"{float(lg.get('act_rtt_ms', 0)):.2f} ms mean)."
                ),
            ]
        frac = ct.get("decide_frac_of_iter")
        iter_ms = ct.get("iter_ms")
        lines += [
            "",
            (
                f"The decision sweep p99 costs {float(frac):.3%} of the "
                f"{float(iter_ms):.0f} ms steady-state iteration "
                f"(commitment <= "
                f"{float(ct.get('decide_frac_max', 0.01)):.0%})"
                if frac is not None and iter_ms is not None
                else "The overhead fraction was not recorded"
            )
            + ". Gated by `perf_gate.gate_control`, folded into "
            "`gate()`.",
        ]
    return lines


def _load_tune_bench():
    """Load the autotuner artifact (``BENCH_tune.json``, written by
    ``surreal_tpu tune ... --out BENCH_tune.json``) if present — like
    BENCH_host.json, keeping it as an artifact lets PERF.md regens
    preserve the measured section without re-running the search."""
    try:
        with open("BENCH_tune.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if (
        not isinstance(data, dict)
        or not isinstance(data.get("workloads"), list)
        or not data["workloads"]
    ):
        return None
    return data


def _load_tiers_bench():
    """Load the replay-tiers artifact (``BENCH_tiers.json``, written by
    ``bench.py --replay-tiers``) if present — the BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running."""
    try:
        with open("BENCH_tiers.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or data.get("value") is None:
        return None  # failed-campaign artifact
    return data


def _replay_tiers_lines() -> list[str]:
    """The 'Hierarchical replay tiers' PERF.md section: static mechanism
    text plus the measured warm-vs-hot table from the BENCH_tiers.json
    artifact. One function so ``main()`` and the committed PERF.md
    cannot drift."""
    lines = [
        "",
        "## Hierarchical replay tiers (device-resident hot ring, "
        "quantized spill WAL)",
        "",
        "The replay hierarchy (ISSUE 18): `replay.tiers.hot` fronts the "
        "PR-8 shard fan-in with a fixed-capacity ring of the NEWEST "
        "transitions held as committed device arrays "
        "(`replay/tiers.py`), filled from the collector's "
        "still-device-resident n-step fold and drawn by the same "
        "`jax.random.randint` + `ring_gather` as the in-process "
        "`UniformReplay` (BIT-EQUAL for the same keys — tested; the "
        "PR-7 Pallas row-DMA kernel carries the gather on TPU), so a "
        "steady-state uniform sample never touches the host: no wire "
        "frame, no `spec.unpack`, no host->device transfer. Misses "
        "while the ring fills fall back to the warm shard fan-in with "
        "the SAME key chain — counted in `tier/hot_misses`, never "
        "silent. `replay.tiers.spill` turns shard ingest into a durable "
        "write-ahead log (`experience/spill.py`): length-framed, "
        "CRC-checked segments in global `(seq, shard)` order, cold "
        "rewards/values quantized to uint8 against per-segment ranges "
        "(HEPPO-GAE, arXiv:2501.12703) with the error bound recorded in "
        "the header, other f32 columns as f16. "
        "`OffPolicyTrainer.replay_from_log` replays the WAL into a "
        "fresh ring and reruns the update schedule — two passes are "
        "bit-identical (tested), and torn tail segments (crash "
        "mid-append; the `experience.spill` chaos site) are skipped by "
        "magic-resync and counted in `tier/torn_segments`. Tiers off is "
        "bit-identical to the untiered plane (tested).",
    ]
    tb = _load_tiers_bench()
    if tb:
        warm, hot = tb.get("warm") or {}, tb.get("hot") or {}
        lines += [
            "",
            f"Measured through the real off-policy trainer "
            f"({tb['geometry']}; `BENCH_tiers.json`, platform "
            f"`{tb.get('platform')}`; warm iterations discarded):",
            "",
            "| Arm | env steps/s | iter ms | learner sample-wait ms | "
            "wire B/step |",
            "|---|---|---|---|---|",
        ]
        for r in (warm, hot):
            lines.append(
                "| {a} | {s:,.0f} | {ms:.1f} | {sw:.3f} | {w:.2f} |".format(
                    a=r.get("arm"),
                    s=float(r.get("env_steps_per_s", 0)),
                    ms=float(r.get("iter_ms", 0)),
                    sw=float(r.get("sample_wait_ms", 0)),
                    w=float(r.get("wire_bytes_per_step", 0)),
                )
            )
        lines += [
            "",
            "The hot arm served {hits:,.0f}/{tot:,.0f} updates from the "
            "device ring (sample-wait {hw:.3f} ms vs the warm arm's "
            "{ww:.2f} ms — the draw dispatches on-device at request "
            "time and overlaps the learner), while the spill WAL "
            "appended {wal:.1f} B/env-step at {cold:.0f} B/transition "
            "against the {raw} B raw f32 row ({ratio:.2f}x, gate "
            "commits <= 0.75). One-core honesty: both arms share one "
            "CPU core with the shard servers, so arm-to-arm steps/s "
            "differences are contention-dominated; the committed wins "
            "are the sample path and the cold bytes.".format(
                hits=float(tb.get("hot_hits") or 0),
                tot=float(tb.get("hot_hits") or 0)
                + float(tb.get("hot_misses") or 0),
                hw=float(hot.get("sample_wait_ms") or 0),
                ww=float(warm.get("sample_wait_ms") or 0),
                wal=float(tb.get("wal_bytes_per_step") or 0),
                cold=float(tb.get("cold_bytes_per_transition") or 0),
                raw=tb.get("raw_bytes_per_transition"),
                ratio=float(tb.get("cold_vs_raw_ratio") or 0),
            ),
        ]
    return lines


def _load_engine_bench():
    """Load the loop-engine artifact (``BENCH_engine.json``, written by
    ``bench.py --loop-engine``) if present — the BENCH_host.json
    discipline: PERF.md regens preserve the measured section without
    re-running."""
    try:
        with open("BENCH_engine.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(data, dict) or not data.get("drivers"):
        return None  # failed-campaign artifact
    return data


def _engine_lines() -> list[str]:
    """The 'Loop engine' PERF.md section: static mechanism text plus the
    per-driver off-vs-on table from the BENCH_engine.json artifact. One
    function so ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Loop engine (software-pipelined iteration boundary)",
        "",
        "All five single-host driver loops (fused/alternate/overlap PPO, "
        "device/host off-policy, SEED) and the three multi-host "
        "subclasses run on ONE iteration skeleton "
        "(`engine/core.py::LoopEngine`): each driver declares its stages "
        "(`collect -> stage -> learn` plus the shared "
        "`publish/checkpoint/recover/observe` side-bands) as `StageSpec` "
        "rows with an EXPLICIT donation bit, and hands the engine a step "
        "closure. With `session_config.engine.pipeline_sidebands` off "
        "(default) the boundary runs inline and the engine is "
        "bit-identical to the historical loops (tested per driver, "
        "params digest + metrics rows + checkpoint bytes). With it on, "
        "the boundary — metrics sync (the one `float()` device fence), "
        "publish, checkpoint, tracer/ops emits — is submitted to a "
        "single staging worker and overlaps iteration k+1's "
        "collect/learn. Donation safety: when any declared stage "
        "donates (the fused device programs jit with "
        "`donate_argnums=(0, 1)`), the param tree is snapshotted with "
        "`jax.tree.map(jnp.copy, ...)` BEFORE the next donating "
        "dispatch can reuse the buffers; host drivers pass the "
        "reference (rebinding, never mutation, is the loop discipline). "
        "Stop/recovery verdicts land with at most one iteration of lag; "
        "a wedged boundary (the `engine.stage` chaos site) gets "
        "`stage_timeout_s` before subsequent boundaries are skipped — "
        "counted in `engine/skipped_boundaries`, never silent — and the "
        "SIGTERM latch is checked inline every iteration, so preemption "
        "stops at an iteration boundary with the emergency checkpoint "
        "intact under overlap (tested).",
    ]
    eb = _load_engine_bench()
    if eb:
        lines += [
            "",
            f"Measured through the real drivers ({eb['geometry']}; "
            f"`BENCH_engine.json`, platform `{eb.get('platform')}`, "
            f"{eb.get('cores', '?')} core(s), mode `{eb.get('mode')}`; "
            f"median of {eb.get('meas_iters')} steady-state iterations):",
            "",
            "| Driver | geometry | legacy iter ms | pipelined iter ms | "
            "ratio | boundary share reclaimed |",
            "|---|---|---|---|---|---|",
        ]
        for name in sorted(eb["drivers"]):
            r = eb["drivers"][name]
            off, on = r.get("off") or {}, r.get("on") or {}
            rec = r.get("reclaimed_frac")
            lines.append(
                "| {n} | {g} | {o:.1f} | {p:.1f} | {ra:.3f} | {re} |".format(
                    n=name, g=r.get("geometry"),
                    o=float(off.get("iter_ms", 0)),
                    p=float(on.get("iter_ms", 0)),
                    ra=float(r.get("iter_ratio_on_vs_off") or 0),
                    re=f"{float(rec):.1%}" if rec is not None else "-",
                )
            )
        if eb.get("mode") != "overlap":
            lines += [
                "",
                "One-core honesty: this box has "
                f"{eb.get('cores', 1)} CPU core(s), so the staging "
                "worker time-slices the compute thread and the arms "
                "measure bookkeeping overhead, not overlap — the "
                "`perf_gate.gate_engine` <= bound is enforced only "
                "under mode `overlap` (>= 2 cores). The committed win "
                "on this image is the reclaimed-share column: the "
                "boundary work that LEAVES the critical path once a "
                "second core exists.",
            ]
    return lines


def _chaos_lines() -> list[str]:
    """The 'Chaos campaigns' PERF.md section: static mechanism text plus
    the campaign summary from the committed CHAOS_campaign.json. One
    function so ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Chaos campaigns (randomized multi-site fault schedules)",
        "",
        "`surreal_tpu chaos <algo|all> [env] --seeds N` runs N seeded "
        "short REAL training runs, each under a deterministic multi-site "
        "fault schedule drawn by `chaos/schedule.py` over the "
        "`utils/faults.py` site registry (per-site kind vocabulary, "
        "kill/nan caps, exclusive co-fire groups, a per-schedule "
        "injected-delay budget). Every run is judged post-hoc by the "
        "`chaos/invariants.py` oracles — exactly-once row conservation "
        "at the quiesced close boundary, counted-never-silent (every "
        "delivered fault leaves a declared counter delta), monotone "
        "published/served param versions and cumulative counters, zero "
        "thread/shm/fd residue after teardown, newest-checkpoint finite "
        "restorability, spill-WAL re-read consistency, and fault "
        "surfacing (every delivered fault appears as a `fault` telemetry "
        "event). A failing schedule is greedily shrunk (drop one spec, "
        "re-run deterministically) to a 1-minimal repro and recorded "
        "with its `(profile, seed)` replay key. "
        "`perf_gate.gate_chaos` holds the committed campaign to >= 25 "
        "schedules over >= 10 distinct FIRED sites with zero violations.",
    ]
    try:
        with open("CHAOS_campaign.json") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return lines
    if not isinstance(data, dict) or data.get("kind") != "chaos_campaign":
        return lines
    g = data.get("gauges") or {}
    by_prof: dict[str, int] = {}
    for s in data.get("schedules") or ():
        by_prof[s.get("profile", "?")] = by_prof.get(
            s.get("profile", "?"), 0) + 1
    lines += [
        "",
        f"Committed campaign (`CHAOS_campaign.json`): "
        f"{int(g.get('chaos/schedules', 0))} schedules over profiles "
        + ", ".join(f"`{p}` ({n})" for p, n in sorted(by_prof.items()))
        + f"; {int(g.get('chaos/faults_injected', 0))} faults delivered "
        f"across {int(g.get('chaos/sites_covered', 0))} distinct sites; "
        f"{int(g.get('chaos/violations', 0))} invariant violations; "
        f"wall {float(g.get('chaos/run_ms', 0)) / 1e3:,.0f} s.",
        "",
        "Fired sites: "
        + ", ".join(f"`{s}`" for s in data.get("sites_covered") or ())
        + ".",
    ]
    return lines


def _autotuner_lines() -> list[str]:
    """The 'Program autotuner' PERF.md section: static mechanism text plus
    the measured table from the BENCH_tune.json artifact when one exists.
    One function so ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Program autotuner (searched scan-unroll + program geometry, "
        "persistent per-workload tuning cache)",
        "",
        "Every graded workload is latency-bound on long `lax.scan`s of "
        "tiny elementwise ops, yet scan-unroll factors and geometry "
        "choices (`gae_impl`, minibatch shuffle layout, update-loop "
        "shape) were hand-set defaults. `surreal_tpu/tune/` searches "
        "them instead (Stooke & Abbeel 1803.02811's measure-and-pick "
        "discipline): greedy coordinate descent over the declared "
        "candidate space (`tune/space.py` — rollout/SGD/update-loop "
        "`unroll`, `gae_impl` incl. the pallas kernel, `shuffle`), each "
        "candidate timed through the REAL trainer programs with bench.py's "
        "fenced chained-window discipline — the fused device "
        "iteration for `jax:*` envs, the jitted learn program alone for "
        "host-env (gym/dm_control/SEED) fingerprints, whose rollout is "
        "host python with no scan to unroll — winner "
        "persisted in a JSON tuning cache beside the compile cache "
        "(`session.tuning_cache_dir`), keyed by workload fingerprint "
        "(algo + model + geometry + backend + jax version, minus the "
        "searched knobs). Trainers consult the cache at build time "
        "(`algo.autotune='off'|'cache'|'search'`); a second `surreal_tpu "
        "tune` run on the same fingerprint is a pure cache hit (zero "
        "measurements), and decisions land in telemetry as `tune` events "
        "(`surreal_tpu diag` renders hit/miss + candidate timings). "
        "bench.py / perf_wallclock.py record the active decision per "
        "artifact row, so tuned and untuned arms can never silently mix.",
    ]
    tb = _load_tune_bench()
    if tb:
        lines += [
            "",
            f"Measured winners (`BENCH_tune.json`, platform "
            f"`{tb.get('platform')}`; adoption threshold 2% vs the "
            "static default — at or under it the default keeps the "
            "compile-cache-warm program):",
            "",
            "| Workload | Geometry | default ms/iter | tuned ms/iter | "
            "speedup | adopted knobs |",
            "|---|---|---|---|---|---|",
        ]
        for w in tb["workloads"]:
            chosen = w.get("config") or {}
            default = w.get("default") or {}
            diff = {
                k: v for k, v in chosen.items() if default.get(k) != v
            }
            lines.append(
                "| {wl} | {g} | {d:.1f} | {c:.1f} | {s:.2f}x | {k} |".format(
                    wl=w.get("workload", "?"),
                    g=w.get("geometry", "?"),
                    d=float(w.get("default_ms") or 0.0),
                    c=float(w.get("chosen_ms") or 0.0),
                    s=float(w.get("speedup") or 1.0),
                    k=", ".join(f"`{k}={v}`" for k, v in sorted(diff.items()))
                    or "(static defaults already optimal)",
                )
            )
    return lines


def _perf_observability_lines() -> list[str]:
    """The 'Performance observability' PERF.md section: static mechanism
    text plus an MFU-per-committed-BENCH-artifact table, so regeneration
    keeps the observability story and the measured MFU trail together.
    One function so ``main()`` and the committed PERF.md cannot drift."""
    lines = [
        "",
        "## Performance observability (in-graph cost/MFU accounting, "
        "trace correlation, on-demand profiling)",
        "",
        "The measurement layer under every number above "
        "(`session/costs.py`, `session/profile.py`, telemetry spine "
        "extensions): each driver registers its jitted hot programs with "
        "XLA's cost model at startup (per-program FLOPs / bytes accessed "
        "/ arithmetic intensity as `program_cost` telemetry events) and "
        "emits live `perf/mfu` + `perf/membw_util` gauges at the metrics "
        "cadence — pure host arithmetic over already-recorded phase "
        "windows, transfer-guard proven to add zero device->host syncs. "
        "The SEED data plane stamps a run-scoped trace id plus span ids "
        "into its control frames so `surreal_tpu diag` stitches a "
        "cross-process timeline (worker step -> frame in flight -> serve "
        "batch -> queue dwell -> learn) with p50/p90/p99 per hop, and "
        "`surreal_tpu profile <folder>` captures an on-demand "
        "`jax.profiler` window into `<folder>/telemetry/profiles/`. "
        "`perf_gate.py` turns the committed artifact trail below into a "
        "CI gate (>10% regression on the same workload fingerprint "
        "exits nonzero).",
        "",
        "MFU per committed BENCH artifact (XLA cost model / "
        "the published bf16 peak of the row's device; 'n/a' predates "
        "the cost accounting or is a failed round). Geometry and arm ride "
        "every row because the trail is NOT one curve: a row measured at "
        "a different geometry, precision arm, or platform is a different "
        "workload, and reading it against the headline rows as a "
        "regression (or a win) is exactly the mistake this column "
        "exists to prevent — perf_gate fingerprints rows the same way:",
        "",
        "| Artifact | metric | geometry | arm (platform) | env steps/s | MFU |",
        "|---|---|---|---|---|---|",
    ]
    # one artifact parser for the gate and this table (perf_gate.py):
    # the CI gate and PERF.md must never classify the same row differently
    from perf_gate import load_rows

    for row in load_rows("."):
        if row.get("failed"):
            lines.append(
                f"| `{row['file']}` | (failed round) | n/a | n/a | n/a | n/a |"
            )
            continue
        mfu = row.get("mfu")
        arm_bits = [b for b in (row.get("arm"), row.get("platform")) if b]
        lines.append(
            "| `{p}` | {m} | {g} | {a} | {v:,.0f} | {mfu} |".format(
                p=row["file"], m=row.get("metric", "?"),
                g=row.get("geometry") or "not recorded",
                a=(
                    f"{row.get('arm') or '?'} (`{row.get('platform') or '?'}`)"
                    if arm_bits else "not recorded"
                ),
                v=row["value"],
                mfu=f"{float(mfu) * 100:.3f}%" if mfu is not None else "n/a",
            )
        )
    return lines


def _precision_lines() -> list[str]:
    """The 'Precision policy' PERF.md section: static mechanism text plus
    the per-policy wall-clock / bytes-accessed table from the newest
    committed artifact carrying a precision sweep (bench.py
    --sweep-precision -> BENCH_r06.json). One function so ``main()`` and
    the committed PERF.md cannot drift — the autotuner/observability
    sections' discipline."""
    lines = [
        "",
        "## Precision policy (f32 / mixed / bf16 / bf16+fp8, dynamic "
        "loss scaling, Pallas hot-kernel suite)",
        "",
        "`algo.precision` (ops/precision.py) is ONE knob governing model "
        "compute dtype, trajectory/SGD/replay staging dtype, and dynamic "
        "loss scaling, threaded through every learner and trainer with "
        "no per-driver forks — and a searched autotuner dimension "
        "(tune/space.py, searched FIRST so later unroll knobs re-measure "
        "under the adopted policy). Params and optimizer state stay f32 "
        "under every policy. 'bf16' stages obs-class arrays in bfloat16 "
        "(the epochs x minibatch gathers and the replay buffer move half "
        "the bytes) and wraps every optimizer chain in dynamic loss "
        "scaling: power-of-two scales make healthy steps EXACT, an "
        "overflow skips the step (Adam moments untouched) and backs the "
        "scale off, and the scale state rides the optimizer pytree next "
        "to PR-5's recovery_scale so a divergence that slips the skip "
        "logic still hits the existing guard + rollback. Checkpoint "
        "run-metadata records the policy; restore across a mismatch is "
        "a named PrecisionMismatchError, not an orbax structure "
        "traceback. The kernel suite grew past GAE: fused V-trace "
        "(ops/pallas_vtrace.py, `vtrace_impl`), the generic reverse "
        "recurrence + discounted returns (ops/pallas_returns.py), and "
        "scalar-prefetch replay gather/scatter row-DMA kernels "
        "(ops/pallas_replay.py, `replay_gather`) — all with interpret-"
        "mode fallbacks, validated against their XLA references on every "
        "backend, adopted per workload only when measured faster.",
    ]
    art = newest_bench_artifact()
    sweep = (art[1].get("precision_sweep") if art else None) or {}
    arms = sweep.get("arms") or []
    costs = sweep.get("headline_costs") or []
    if arms or costs:
        plat = arms[0].get("platform") if arms else None
        # the narrative must match the platform the artifact actually
        # recorded — the same branch gate_precision takes: on a
        # bf16-emulating host f32 outruns any bf16 arm by construction;
        # on TPU bf16 must win its keep against the true f32 baseline
        plat_note = (
            "this host emulates bf16, so f32 outruns any bf16-computing "
            "arm here; on TPU the MXU inverts that"
            if plat != "tpu"
            else "native bf16 MXU — the f32 arm is the true baseline"
        )
        lines += [
            "",
            f"Per-policy measurements (`{art[0]}`; platform "
            f"{plat} recorded honestly — {plat_note}. "
            "Bytes-accessed rows are the PR-6 cost accountant at the "
            "TRUE headline geometry, deterministic, no timed window):",
            "",
            "| policy | timed geometry | steps/s | headline bytes/iter |",
            "|---|---|---|---|",
        ]
        cost_by = {c.get("precision"): c for c in costs}
        for a in arms:
            c = cost_by.get(a.get("precision"), {})
            byts = c.get("bytes_accessed_per_iter")
            lines.append(
                "| {p} | {g} | {v:,.0f} | {b} |".format(
                    p=a.get("precision"),
                    g=f"{a.get('num_envs')}x{a.get('horizon')}",
                    v=a.get("value", 0),
                    b=f"{byts / 1e9:.2f} GB" if byts else "n/a",
                )
            )
        cf = cost_by.get("f32", {}).get("bytes_accessed_per_iter")
        cb = cost_by.get("bf16", {}).get("bytes_accessed_per_iter")
        if cf and cb:
            lines.append(
                f"\nbf16 policy: {(1 - cb / cf) * 100:.1f}% lower "
                "bytes-accessed per headline iteration than f32 "
                "(commitment >= 25%, gated by perf_gate.py as a tier-1 "
                "test)."
            )
    return lines


def _load_block_vs_row():
    """Load perf_curves.py's artifact if present — the comparison is a
    slow chip-bound campaign run separately; keeping it as a JSON artifact
    lets PERF.md regens preserve the section without re-running it."""
    try:
        with open("block_vs_row.json") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _block_vs_row_verdict(s) -> str:
    bm, rm = s["block"]["final_median"], s["row"]["final_median"]
    b_lo = min(s["block"]["final_returns"])
    b_hi = max(s["block"]["final_returns"])
    r_lo = min(s["row"]["final_returns"])
    r_hi = max(s["row"]["final_returns"])
    overlap = not (b_hi < r_lo or r_hi < b_lo)
    spread = max(b_hi - b_lo, r_hi - r_lo)
    benign = overlap and abs(bm - rm) <= spread
    if benign:
        return (
            "The per-seed final-return ranges OVERLAP "
            f"(block [{b_lo:,.0f}-{b_hi:,.0f}] vs row "
            f"[{r_lo:,.0f}-{r_hi:,.0f}]) and the median gap "
            f"({abs(bm - rm):,.0f}) is within the larger arm's seed "
            f"spread ({spread:,.0f}): at the real multi-minibatch "
            "geometry the block co-grouping is statistically benign — "
            "the direct evidence the round-4 docstring argument "
            "promised. 'row' stays selectable for exact reference "
            "semantics."
        )
    return (
        f"The arms separate (block median {bm:,.0f} vs row {rm:,.0f}; "
        f"ranges block [{b_lo:,.0f}-{b_hi:,.0f}] vs row "
        f"[{r_lo:,.0f}-{r_hi:,.0f}]): the block co-grouping has a "
        "measurable learning cost at this geometry — documented honestly "
        "here; weigh the 13x throughput win against it per workload, or "
        "set `algo.shuffle='row'` for exact reference semantics."
    )


def _capture_trace(trainer, state, carry, key) -> str | None:
    """Profiler window over two fused iters (SURVEY.md §5.1). MUST run
    after every measurement: tracing slows the host."""
    trace_dir = "/tmp/perf_lift/profile"
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(2):
                key, it_key = jax.random.split(key)
                state, carry, metrics = trainer._train_iter(state, carry, it_key)
            jax.block_until_ready(metrics)
        return trace_dir
    except Exception:
        return None


def main(argv=None) -> None:
    import sys

    argv = sys.argv[1:] if argv is None else argv
    if "--sync-readme" in argv:
        # citation-only sync (no benchmarks, works off-chip) — see
        # sync_readme_artifact's docstring for why this exists
        sync_readme_artifact()
        return
    from surreal_tpu.utils.compat import enable_compile_cache

    enable_compile_cache()
    _peak_flops()  # refuse a device without a published peak up front
    rows = []
    trace_fn = None
    for fn in (
        ppo_lift_headline, impala_pong, ddpg_prioritized_lift,
        ddpg_prioritized_lift_1m, ppo_cnn_nut_pixels,
        ppo_trajectory_pendulum, host_env_cheetah,
    ):
        r = fn()
        if r is None:
            continue
        trace_fn = r.pop("_trace_fn", None) or trace_fn  # not JSON-able
        rows.append(r)
        print(json.dumps(r, default=float))
    scaling = headline_scaling() if "--scaling" in argv else None
    # trace LAST, after every measurement
    rows[0]["trace_dir"] = trace_fn() if trace_fn else None

    dev = jax.devices()[0]
    lines = [
        "# PERF — measured utilization report",
        "",
        f"Device: `{dev.device_kind}` ({jax.device_count()} chip(s)). "
        f"MFU denominator: {_peak_flops() / 1e12:.0f} TFLOP/s (the "
        "device's published bf16 peak). FLOPs are XLA's own `cost_analysis()` of the "
        "compiled training iteration — model + env + optimizer, everything "
        "in the program.",
        "",
        "All timings are chained windows after a compile warm-up, fenced "
        "by `jax.block_until_ready` on the window's last outputs. The "
        "graded metric is env steps/s/chip; MFU is reported beside it.",
        "",
        "| Workload | Geometry | env steps/s/chip | iter ms | FLOP/s | MFU |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        fl = r.get("model_flops_per_s")
        mfu = r.get("mfu")
        lines.append(
            "| {w} | {g} | {s:,.0f} | {ms:.1f} | {fl} | {mfu} |".format(
                w=r["workload"],
                g=r["geometry"],
                s=r["env_steps_per_s"],
                ms=r["iter_ms"],
                fl=f"{fl / 1e12:.2f} TFLOP/s" if fl else "n/a",
                mfu=f"{mfu * 100:.2f}%" if mfu else "n/a",
            )
        )
    head = rows[0]
    parts_sum = head["rollout_only_ms"] + head["learn_only_ms"]
    if head["iter_ms"] < 0.9 * parts_sum:
        verdict = (
            "The fused iteration beats rollout+learn compiled separately "
            f"({head['iter_ms']:.2f} ms vs {parts_sum:.2f} ms summed): one "
            "program lets XLA overlap env stepping with learning work and "
            "keep intermediates in HBM/VMEM instead of round-tripping "
            "between dispatches — the reason the trainer fuses the whole "
            "iteration."
        )
    else:
        verdict = (
            "Rollout and learn compiled separately sum close to the fused "
            f"iteration ({parts_sum:.2f} ms vs {head['iter_ms']:.2f} ms): "
            "fusion is not load-bearing at this geometry; the split shows "
            "which half dominates."
        )
    lines += [
        "",
        "## Top-line breakdown (headline workload)",
        "",
        f"- fused train iteration: {head['iter_ms']:.2f} ms",
        f"- rollout-only program (policy forward + env step x 256): "
        f"{head['rollout_only_ms']:.2f} ms",
        f"- learn-only program (GAE + 4x4 minibatch SGD): "
        f"{head['learn_only_ms']:.2f} ms",
        "",
        verdict,
    ]
    at = head.get("attrib")
    if at:
        lines += [
            "",
            "## Learn-phase attribution (round-4 finding)",
            "",
            "Sub-programs compiled and timed separately at the headline "
            "geometry (fenced, chained):",
            "",
            "| Component | ms/iter |",
            "|---|---|",
            f"| learn-only, `algo.shuffle='row'` (reference semantics: per-epoch row reshuffle) | {at['learn_row_ms']:.1f} |",
            f"| learn-only, `algo.shuffle='block'` (default) | {head['learn_only_ms']:.1f} |",
            f"| value forwards (2x model.apply over [T, B]) | {at['value_forwards_ms']:.1f} |",
            f"| GAE recurrence | {at['gae_ms']:.1f} |",
            f"| ALL 16 grad steps (4 epochs x 4 minibatches), no shuffling/gathers | {at['gradsteps16_nogather_ms']:.1f} |",
            "",
            "With row shuffling, learn time was dominated NOT by training "
            "compute but by minibatch assembly: a ~1M-element argsort "
            "permutation per epoch plus random row gathers whose "
            "4-byte-row leaves (advantages, logps) walk the TPU scalar "
            "unit. `algo.shuffle='block'` (learners/ppo.py `_sgd_epochs`) "
            "permutes contiguous blocks instead — statistically benign "
            "here because a flat-layout block is a same-timestep slab of "
            "independent envs — and removes that cost wholesale; 'row' "
            "remains selectable for exact reference semantics.",
        ]
    pong = next((r for r in rows if r.get("pong_attrib")), None)
    if pong:
        pa = pong["pong_attrib"]
        fused = pong["iter_ms"]
        # decision logic rendered with the numbers: which phase owns the
        # iteration, and what (if anything) a kernel-level fix could buy
        dominant = max(
            ("env rendering+logic", pa["env_only_ms"]),
            ("CNN acting", max(pa["act_only_ms"], 0.0)),
            ("learn (V-trace + CNN fwd/bwd)", pa["learn_ms"]),
            key=lambda t: t[1],
        )
        B, T = pa.get("num_envs", "?"), pa.get("horizon", "?")
        lines += [
            "",
            "## Pixel-path attribution (pong, round-5)",
            "",
            "Sub-programs compiled and timed separately at the pong "
            f"geometry ({pong['geometry']}; fenced, chained):",
            "",
            "| Component | ms/iter |",
            "|---|---|",
            f"| fused train iteration | {fused:.1f} |",
            f"| rollout only (CNN act + env step x {T}) | {pa['rollout_ms']:.1f} |",
            f"| env only (random actions: pixel render + game logic x {T}) | {pa['env_only_ms']:.1f} |",
            f"| CNN acting only (NatureCNN forward x {T}, fixed frame) | {pa['act_only_ms']:.1f} |",
            f"| learn only (V-trace + CNN fwd/bwd over [{T}, {B}]) | {pa['learn_ms']:.1f} |",
            "",
            f"The iteration is owned by **{dominant[0]}** "
            f"({dominant[1]:.1f} ms of {fused:.1f}). "
            + (
                "The ~3% MFU on pixel workloads is a ROOFLINE property, "
                "not a missed optimization: the env scan writes uint8 "
                "frames elementwise (bandwidth, not MXU), and the "
                "NatureCNN on 42x42 frames does small-spatial convs whose "
                "im2col tiles underfill the 128x128 systolic array. "
                "Decision recorded: no pallas kernel for the conv path — "
                "the phase a kernel could accelerate is not where the "
                "milliseconds are; pixel-throughput work should target "
                "the env scan's frame writes if it ever becomes the "
                "bottleneck at larger batch."
                if dominant[0] == "env rendering+logic"
                else
                "The conv path owns the iteration at this geometry. "
                "Decision recorded after checking the stem: it already "
                "computes in bf16 (models/encoders.py NatureCNN), so the "
                "remaining kernel levers are channel-padded layouts or a "
                "fused pallas stem — NOT pursued, because the low MFU is "
                "structural at this shape (the first conv's C_in=2 "
                "underfills the 128-lane MXU regardless of kernel, and "
                "XLA already pads); a pallas conv would re-derive XLA's "
                "own schedule for single-digit-ms stakes. Revisit only "
                "if pixel workloads scale to larger frames/channels "
                "where the conv becomes tens of ms."
            ),
        ]
    bvr = _load_block_vs_row()
    if bvr and all(
        bvr["summary"][m]["final_returns"] for m in ("block", "row")
    ):
        s = bvr["summary"]
        lines += [
            "",
            "## Block-vs-row shuffle: direct learning-curve A/B "
            "(round-5 validation of the round-4 13x win)",
            "",
            f"Geometry {s['geometry']}, {s['n_iters']} iterations per run, "
            f"{len(s['block']['final_returns'])} seeds per arm, arms "
            "interleaved (perf_curves.py; artifact `block_vs_row.json`"
            + (
                f"; final performance = {s['final_estimator']}"
                if s.get("final_estimator") else ""
            )
            + ").",
            "",
            "| Shuffle mode | final returns (per seed, sorted) | median |",
            "|---|---|---|",
            "| `block` (TPU default) | "
            + ", ".join(f"{v:,.0f}" for v in s["block"]["final_returns"])
            + f" | {s['block']['final_median']:,.0f} |",
            "| `row` (reference semantics) | "
            + ", ".join(f"{v:,.0f}" for v in s["row"]["final_returns"])
            + f" | {s['row']['final_median']:,.0f} |",
            "",
            _block_vs_row_verdict(s),
        ]
    # static section: the dispatch-pipeline levers are mechanism-proven by
    # test (tier-1 is CPU); regenerating PERF.md on a measurement round
    # must not drop their documentation
    lines += [
        "",
        "## Dispatch pipeline (donation, persistent compile cache, "
        "prefetch staging)",
        "",
        "Three levers added by the dispatch-pipeline PR; mechanisms "
        "proven by test on this image (tier-1 runs on CPU — chip-side "
        "wall-clock numbers are for the next on-TPU measurement round to "
        "record):",
        "",
        "- **Donation** — every fused train/learn jit donates its "
        "loop-carried pytrees (`donate_argnums`): train state, env "
        "carry, replay shards. For the off-policy fused program the "
        "replay storage is the single largest HBM allocation, so "
        "donation halves its steady-state footprint (one live copy "
        "instead of input+output across each iteration) and removes the "
        "copy XLA otherwise schedules. Drivers commit carries to the "
        "mesh sharding at init so the aliasing holds from iteration 1 "
        "(an uncommitted input's donation is silently dropped by the "
        "reshard). Invariant enforced two ways: "
        "`tests/test_dispatch_pipeline.py` (donated inputs actually "
        "released; stale reuse raises) and the `test_import_hygiene` "
        "donation lint (every `jax.jit` in a learner/trainer step "
        "module must state its donation decision; the deliberate "
        "non-donations — SEED's live act closure, the host overlap "
        "collectors — are declared `donate_argnums=()` with the alias "
        "named).",
        "- **Persistent compile cache** — `session.compile_cache_dir` "
        "enables `jax_compilation_cache_dir` (+ relaxed eligibility "
        "thresholds, via `utils/compat.py` for the pinned jax, "
        "including the reset of jax's once-per-process cache-used "
        "latch). WALLCLOCK_r05 context: the pong 2.5-vs-4.5-minute "
        "spread was compile time, not train time — a warm cache "
        "converts that compile into executable deserialization. Measure "
        "with `python perf_wallclock.py --compile-cache /tmp/xla_cache` "
        "twice: run 1 (cold, empty dir) vs run 2 (warm) — compare "
        "`summary.seed0_compile_s`; per-row `compile_cache` hit/miss "
        "counters make the artifacts self-describing, and `surreal_tpu "
        "diag` reports the same counters for any training session.",
        "- **Prefetch staging** (`learners/prefetch.py`) — SEED: the "
        "staging thread waits on the chunk queue and pays the "
        "host→device transfer (with the committed dp sharding) for "
        "chunk k+1 while the learner runs chunk k, so steady-state "
        "iteration ≈ max(stage, learn) instead of stage+learn. "
        "Off-policy host loop: the whole exploration rollout + its "
        "single `device_put` runs on the staging thread while the "
        "device drains `updates_per_iter` SGD steps "
        "(`topology.overlap_rollouts`; the host-env caveat in the table "
        "below — one-core boxes see ~1x — applies to this overlap too). "
        "Transfer-guard tests prove staging adds zero device→host "
        "syncs.",
    ]
    # static section + artifact table: the autotuner is documented
    # unconditionally; the measured table rides the BENCH_tune.json
    # artifact so a regen without the search keeps the last measured run
    lines += _autotuner_lines()
    # static section + artifact table: the observability layer is
    # documented unconditionally; the MFU trail rides the committed
    # BENCH_r*.json artifacts
    lines += _perf_observability_lines()
    # static section + per-policy table riding the newest precision-sweep
    # artifact (BENCH_r06.json)
    lines += _precision_lines()
    host = next((r for r in rows if r.get("host_attrib")), None)
    if host:
        ha = host["host_attrib"]
        roll_ms = ha["rollout_projected_ms"]
        win = ha["alternate_iter_ms"] / ha["overlap_iter_ms"]
        lines += [
            "",
            "## Host-env data plane (BASELINE ② — the reference's operating shape)",
            "",
            "CPU MuJoCo envs (dm_control cheetah-run, 32 envs) feeding the "
            "chip per step — the reference's defining workload (actors + "
            "ZMQ replay, SURVEY.md §3.2-3.3). Three drive modes, measured "
            "end-to-end through the real trainers (wall-clock between "
            "metrics fences, first 3 iterations discarded as compile/warm):",
            "",
            "| Drive mode | env steps/s | iter ms |",
            "|---|---|---|",
            f"| strict alternation (`overlap_rollouts=false`) | {ha['alternate_sps']:,.0f} | {ha['alternate_iter_ms']:.0f} |",
            f"| overlapped collector (`overlap_rollouts=true`, default) | {ha['overlap_sps']:,.0f} | {ha['overlap_iter_ms']:.0f} |",
            f"| SEED (4 worker processes x 8 envs -> InferenceServer) | {ha['seed_sps']:,.0f} | {ha['seed_iter_ms']:.0f} |",
            "",
            "Per-phase attribution of one alternation iteration "
            f"(horizon {64}):",
            "",
            "| Phase | ms |",
            "|---|---|",
            f"| policy act, per env step (obs upload + forward + action download, fenced) | {ha['act_ms_per_step']:.2f} |",
            f"| env.step, per env step (32 serial MuJoCo steps on 1 host core) | {ha['env_ms_per_step']:.2f} |",
            f"| rollout projected (act+env) x 64 | {roll_ms:.0f} |",
            f"| learn, per iteration (4 epochs x 4 minibatches, fenced) | {ha['learn_ms_per_iter']:.0f} |",
            "",
            (
                f"The overlapped loop runs {win:.2f}x the strict "
                "alternation — hiding the learn phase behind the "
                "collector thread captures the available win."
                if win > 1.02
                else
                f"Overlap measured {win:.2f}x vs strict alternation — on "
                "THIS box it does not pay: the projected rollout "
                f"({roll_ms:.0f} ms) is ~"
                f"{roll_ms / max(ha['learn_ms_per_iter'], 1e-9):.0f}x the "
                f"learn phase ({ha['learn_ms_per_iter']:.0f} ms), so "
                "there is almost nothing to hide, and the collector "
                "thread's device round trips contend with the learner's "
                "on one host core. The feature targets the reference's "
                "balance (env+learn comparable); `overlap_rollouts="
                "false` is the right setting here."
            )
            + (
                " The SEED plane is the fastest mode measured here "
                f"({ha['seed_sps']:,.0f} steps/s vs "
                f"{max(ha['alternate_sps'], ha['overlap_sps']):,.0f} for "
                "the best in-process loop): workers step envs "
                "continuously instead of waiting for the learn, and the "
                "server coalesces the fleet into one batched forward per "
                f"round, so the ~{ha['act_ms_per_step']:.0f} ms per-act "
                "device round trip is paid once per SERVER step, not "
                "once per trainer env step."
                if ha["seed_sps"] >= max(ha["alternate_sps"], ha["overlap_sps"])
                else
                f" SEED measured {ha['seed_sps']:,.0f} steps/s vs "
                f"{max(ha['alternate_sps'], ha['overlap_sps']):,.0f} for "
                "the best in-process loop — on this box the in-process "
                "loop wins; see the attribution rows for where its time "
                "goes."
            )
            + f" NOTE the host has {__import__('os').cpu_count()} CPU "
            "core(s): with one, the 32 MuJoCo envs step serially and "
            "SEED's 4 worker processes time-slice it. The numbers are "
            "for THIS box; the mode ranking the table records is the "
            "measured one.",
        ]
    # static section + artifact table: the host data-plane rebuild is
    # documented unconditionally (mechanism proven by test on this CPU
    # image); the measured table rides the BENCH_host.json artifact so a
    # regen without the campaign keeps the last measured numbers
    lines += _host_data_plane_lines()
    lines += _experience_plane_lines()
    lines += _act_path_lines()
    lines += _gateway_lines()
    lines += _ops_plane_lines()
    lines += _trace_lines()
    lines += _watchdog_lines()
    lines += _control_lines()
    lines += _replay_tiers_lines()
    lines += _engine_lines()
    lines += _chaos_lines()
    if scaling:
        lines += [
            "",
            "## Headline geometry scaling (`--scaling`)",
            "",
            "| Geometry (envs x horizon) | env steps/s/chip | iter ms |",
            "|---|---|---|",
        ]
        for r in scaling:
            lines.append(
                f"| {r['geometry']} | {r['env_steps_per_s']:,.0f} "
                f"| {r['iter_ms']:.2f} |"
            )
        lines += [
            "",
            "Horizon costs linearly (the env scan is sequential) and width "
            "costs linearly once elementwise env ops saturate, so "
            "throughput is flat-to-declining past the knee. bench.py "
            "records the headline at its own swept knee (4096 x 256 since "
            "the round-4 block-shuffle change); this sweep holds horizon "
            "at 256 to show the width axis in isolation.",
        ]
    if head.get("trace_dir"):
        lines += [
            "",
            f"A `jax.profiler` trace of two fused iterations was captured to "
            f"`{head['trace_dir']}` (TensorBoard profile plugin format; not "
            "committed — rerun `python perf_report.py` to regenerate).",
        ]
    lines += [
        "",
        "_Generated by `perf_report.py`; bench.py prints the headline line "
        "with `mfu` for the driver's BENCH artifact._",
        "",
    ]
    import os

    os.makedirs(os.path.dirname(REPORT_PATH), exist_ok=True)
    with open(REPORT_PATH, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {REPORT_PATH}")
    _update_readme(rows)


def newest_bench_artifact():
    """(basename, parsed-bench-line) of the newest BENCH_r*.json on disk,
    or None. The single source of truth for 'artifact of record' — used
    by the README regen, the ``--sync-readme`` mode, and the anti-drift
    test (tests/test_perf_docs.py)."""
    import glob
    import os

    bench_files = sorted(glob.glob("BENCH_r*.json"))
    for path in reversed(bench_files):
        try:
            with open(path) as f:
                data = json.load(f)
            # driver artifacts wrap the bench line under "parsed"; a
            # FAILED round writes "parsed": null — `or data` (not a
            # default) so null falls back too, and the isinstance guard
            # lets any non-dict artifact fall through to the newest
            # VALID bench file instead of raising TypeError (ADVICE r5)
            parsed = (data.get("parsed") or data) if isinstance(data, dict) else None
            if isinstance(parsed, dict) and "value" in parsed:
                return os.path.basename(path), parsed
        except (OSError, json.JSONDecodeError):
            continue
    return None


def sync_readme_artifact() -> bool:
    """Rewrite ONLY the 'Driver artifact of record' citation inside
    README's marked perf block to the newest BENCH_r*.json — no
    benchmarks run, so this works off-chip. Round-4 VERDICT weak #2: the
    regen-on-measure guard couldn't fire for an artifact captured AFTER
    the last measurement run (the driver writes BENCH_r{N} when the round
    ends); this mode + the suite's anti-drift test close that hole.
    Returns True if README changed."""
    import re

    art = newest_bench_artifact()
    if art is None:
        return False
    name, parsed = art
    vsb = parsed.get("vs_baseline", parsed["value"] / 1e5)
    # same qualification rules as _update_readme: significant digits for
    # sub-10x rows, platform/precision arms carried into the citation so
    # a CPU sweep row can never read like a chip record
    vsb_txt = f"{vsb:,.0f}x" if vsb >= 10 else f"{vsb:.3g}x"
    quals = [str(parsed[k]) for k in ("platform", "precision") if parsed.get(k)]
    qual_txt = f" ({', '.join(quals)} arm)" if quals else ""
    new_cite = (
        f"Driver artifact of record `{name}`: "
        f"{parsed['value']:,.0f} steps/s{qual_txt} ({vsb_txt} target)."
    )
    with open("README.md") as f:
        readme = f.read()
    out, n = re.subn(
        r"Driver artifact of record `BENCH_r\d+\.json`: [\d,]+ steps/s"
        r"(?: \([^)]*arm\))? \([\d.,]+x target\)\.",
        new_cite,
        readme,
    )
    if n and out != readme:
        with open("README.md", "w") as f:
            f.write(out)
        print(f"README artifact-of-record synced to {name}")
        return True
    if n == 0:
        print(
            "WARNING: README's 'Driver artifact of record' sentence did "
            "not match the expected format — nothing synced. Re-run "
            "`python perf_report.py` (full regen) or restore the "
            "footnote's wording.",
        )
    return False


def _update_readme(rows) -> None:
    """Regenerate README's measured-throughput table from THIS run plus
    the newest driver BENCH artifact on disk, so the three sources
    (README / PERF.md / BENCH_r0N.json) cannot drift (round-3 VERDICT
    weak #2). Rewrites only the marked block; wall-clock learning rows
    outside the markers are separate end-to-end runs and stay manual."""
    start, end = "<!-- PERF-TABLE-START -->", "<!-- PERF-TABLE-END -->"
    try:
        with open("README.md") as f:
            readme = f.read()
    except OSError:
        return
    if start not in readme or end not in readme:
        print("README markers not found; table not updated")
        return

    artifact = newest_bench_artifact()

    head = rows[0]
    art_txt = ""
    if artifact:
        vsb = artifact[1].get("vs_baseline", artifact[1]["value"] / 1e5)
        # sub-10x artifacts keep significant digits (same rule as the
        # table rows), and rows that record platform/precision arms
        # (bench.py --precision) carry them into the citation — a CPU
        # sweep row must never read like a chip record
        vsb_txt = f"{vsb:,.0f}x" if vsb >= 10 else f"{vsb:.3g}x"
        quals = [
            str(artifact[1][k])
            for k in ("platform", "precision")
            if artifact[1].get(k)
        ]
        qual_txt = f" ({', '.join(quals)} arm)" if quals else ""
        art_txt = (
            f" Driver artifact of record `{artifact[0]}`: "
            f"{artifact[1]['value']:,.0f} steps/s{qual_txt} "
            f"({vsb_txt} target)."
        )
    body = [
        "| Workload (BASELINE config class) | Geometry | env steps/s/chip | vs 100k north star |",
        "|---|---|---|---|",
    ]
    for r in rows:
        x = r["env_steps_per_s"] / 1e5
        body.append(
            "| {w} | {g} | **{s:,.0f}** | {x} |".format(
                w=r["workload"], g=r["geometry"],
                s=r["env_steps_per_s"],
                # sub-1x rows (the host-env plane pays a device round trip
                # per step) get significant digits instead of rounding to a
                # bogus "0x" — %g keeps tiny ratios visible (0.004x)
                x=f"{x:,.0f}x" if x >= 10 else f"{x:.3g}x",
            )
        )
    body += [
        "",
        f"_Table generated by `perf_report.py` (fenced, this "
        f"run's measurements; headline iter {head['iter_ms']:.1f} ms, "
        f"MFU {head.get('mfu', 0) * 100:.2f}%).{art_txt} Full breakdown "
        f"and per-phase attributions: `{REPORT_PATH}`._",
    ]
    new = (
        readme[: readme.index(start) + len(start)]
        + "\n"
        + "\n".join(body)
        + "\n"
        + readme[readme.index(end):]
    )
    with open("README.md", "w") as f:
        f.write(new)
    print("updated README.md perf table")


if __name__ == "__main__":
    main()
