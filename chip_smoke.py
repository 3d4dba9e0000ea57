"""Chip smoke: does the system still start, train and agree with itself on
the TPU? The quickest proof, not a benchmark — no number printed here is a
performance record.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host: the dp=4 path only

One chip, one process, these phases:

- ``kernels``  each of the three Pallas entry points, compiled by Mosaic
  (never interpreted), against its XLA twin at the shapes the trainers
  use: discounted returns 256 x 4096, the row gather over every leaf of
  the DDPG ``jax:lift`` replay at capacity 200 000 (batch 256), the
  priority scatter over ``[200 000]``.
- ``ppo``      ``train ppo jax:lift --num-envs 4096``, horizon 256, four
  fused iterations, through ``surreal_tpu.main.launch.main(argv)``.
- ``ddpg`` / ``ddpg_pallas``  ``train ddpg jax:lift --num-envs 2048`` with
  prioritized replay of 200 000, 64 updates x 256 batch per iteration,
  four iterations — with the default gather, then with the Pallas
  gather/scatter (``algo.replay_gather=pallas``).

Each training phase must end with finite ``loss/*``, parameters that moved
(``health/update_ratio`` > 0) and a ``device`` telemetry event, written by
the run itself, that says ``tpu``. With ``--chips 4`` it runs two phases
and no other: ``ppo_dp4`` (the same PPO run over a dp=4 mesh: shards on
four devices, replicas bitwise equal) and ``dp_learn`` (one learn step of
``parallel/dp.py::dp_learn`` on four chips against the un-meshed learner
on ``devices[0]``, to tests/test_parallel.py's tolerances).

Earlier lines: one JSON object per phase. Last line, only when every
phase passed on a TPU: ``{"ok": true, "device": {...}}``. Without a TPU
the script exits non-zero before any phase and prints no result.
``--rehearse`` walks the same phases at toy sizes on whatever JAX finds
(``JAX_PLATFORMS=cpu``; add ``XLA_FLAGS=--xla_force_host_platform_
device_count=4`` for ``--chips 4``) to find wrong paths before chip time
is spent; it never prints ``"ok": true`` and always exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from surreal_tpu.utils.compat import (  # noqa: E402
    compile_cache_counts,
    enable_compile_cache,
)

# the chip tool brings this directory back; .gitignore lists it
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Sizes:
    """Phase sizes: the published widths on the chip, toys in rehearsal."""

    def __init__(self, rehearse: bool):
        self.ppo_envs, self.horizon = (64, 8) if rehearse else (4096, 256)
        self.ddpg_envs = 16 if rehearse else 2048
        self.capacity = 2048 if rehearse else 200_000
        self.batch = 32 if rehearse else 256
        self.updates = 2 if rehearse else 64
        self.iters = 4


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


@contextlib.contextmanager
def phase(name: str, results: list):
    """Time one phase, count its compiles and compile-cache traffic, and
    print its line whether it passed or not."""
    compile_s = [0.0]

    def on_duration(event, duration, **_kw):
        if event == COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    before = compile_cache_counts()
    line = {"phase": name, "ok": False}
    t0 = time.perf_counter()
    try:
        yield line
        line["ok"] = True
    except Exception as e:  # noqa: BLE001 — reported, and fails the smoke
        import traceback

        traceback.print_exc()
        line["error"] = f"{type(e).__name__}: {e}"[:400]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        after = compile_cache_counts()
        line["seconds"] = round(time.perf_counter() - t0, 3)
        line["compile_seconds"] = round(compile_s[0], 3)
        line["compile_cache"] = {
            k: after[k] - before[k] for k in ("hits", "misses")
        }
        results.append(line)
        print(json.dumps(line, default=float), flush=True)


# -- kernels ------------------------------------------------------------------

def _has_kernel(fn, *args, **kwargs) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kwargs).as_text()


def kernels_phase(line: dict, sz: Sizes, rng, on_tpu: bool) -> None:
    from surreal_tpu.ops import pallas_interpret
    from surreal_tpu.ops import returns as R
    from surreal_tpu.ops.pallas_replay import (
        gather_rows_pallas,
        scatter_rows_pallas,
    )
    from surreal_tpu.ops.pallas_returns import discounted_returns_pallas

    interp = pallas_interpret()
    check(interp != on_tpu, "pallas_interpret() must be False on the TPU")
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    coin = lambda p, *s: jnp.asarray(rng.random(s) < p)
    report = {}

    def close(name, got, want, tol=1e-4):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape, f"{name}: shape {got.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: nonfinite")
        diff = float(np.abs(got - want).max())
        report[name] = diff
        check(
            bool(np.allclose(got, want, rtol=tol, atol=tol)),
            f"{name}: max |kernel - xla| = {diff:g} > {tol:g}",
        )

    def compiled(name, fn, *args, **kwargs):
        if on_tpu:
            check(
                _has_kernel(fn, *args, **kwargs), f"{name}: no tpu_custom_call"
            )

    gamma = 0.99
    T, B = sz.horizon, sz.ppo_envs
    rewards, values = f(T, B), f(T + 1, B)
    disc = gamma * (1.0 - coin(0.05, T, B).astype(jnp.float32))
    ret_args = (rewards, disc, values[-1])
    compiled("returns", discounted_returns_pallas, *ret_args)
    close(
        "returns",
        discounted_returns_pallas(*ret_args, interpret=interp),
        R.discounted_returns(*ret_args),
    )

    # replay rows: bit-equal to indexing, on every leaf shape of the
    # DDPG jax:lift replay (obs/next_obs [17], action [4], reward/discount [])
    idx = jnp.asarray(rng.integers(0, sz.capacity, sz.batch), jnp.int32)
    for leaf, trailing in (("obs", (17,)), ("action", (4,)), ("reward", ())):
        storage = f(sz.capacity, *trailing)
        compiled(f"gather/{leaf}", gather_rows_pallas, storage, idx)
        got = gather_rows_pallas(storage, idx, interpret=interp)
        check(
            bool(jnp.array_equal(got, storage[idx])),
            f"gather/{leaf}: not bit-equal to storage[idx]",
        )
    prios = jnp.abs(f(sz.capacity))
    uniq = jnp.asarray(
        rng.permutation(sz.capacity)[: sz.batch], jnp.int32
    )
    upd = jnp.abs(f(sz.batch))
    compiled("scatter", scatter_rows_pallas, prios, uniq, upd)
    got = scatter_rows_pallas(prios, uniq, upd, interpret=interp)
    check(
        bool(jnp.array_equal(got, prios.at[uniq].set(upd))),
        "scatter: not bit-equal to .at[idx].set",
    )
    line["tpu_custom_call"] = on_tpu
    line["max_abs_diff"] = report
    line["bit_equal"] = ["gather/obs", "gather/action", "gather/reward", "scatter"]


# -- training through the CLI entry point -------------------------------------

def _events(folder: str, kind: str) -> list[dict]:
    from surreal_tpu.session.telemetry import EVENTS_FILE, TELEMETRY_DIR

    out = []
    with open(os.path.join(folder, TELEMETRY_DIR, EVENTS_FILE)) as fh:
        for raw in fh:
            rec = json.loads(raw)
            if rec.get("type") == kind:
                out.append(rec)
    return out


def _train_argv(algo, folder, num_envs, total_steps, sets, iters) -> list:
    return [
        "train", algo, "jax:lift", "--folder", folder,
        "--num-envs", str(num_envs), "--total-steps", str(total_steps),
        "--set", *sets,
        # metrics read once, at the end; no checkpoint or eval cadence
        f"session_config.metrics.every_n_iters={iters}",
        "session_config.metrics.tensorboard=false",
        "session_config.metrics.console=false",
        "session_config.checkpoint.every_n_iters=0",
        "session_config.eval.every_n_iters=0",
    ]


def _check_run(line: dict, folder: str, metrics: dict, expect: dict) -> None:
    losses = {k: v for k, v in metrics.items() if k.startswith("loss/")}
    line["losses"] = losses
    check(bool(losses), "no loss/* metrics")
    check(all(np.isfinite(v) for v in losses.values()), f"nonfinite {losses}")
    check(metrics.get("health/nonfinite") == 0.0, "health/nonfinite set")
    line["update_ratio"] = metrics.get("health/update_ratio")
    check(
        metrics.get("health/update_ratio", 0.0) > 0.0,
        "parameters did not change (health/update_ratio)",
    )
    line["env_steps"] = metrics.get("time/env_steps")
    dev = _events(folder, "device")
    check(len(dev) == 1, f"{len(dev)} device events in {folder}")
    line["device"] = {k: dev[0][k] for k in ("platform", "kind", "count")}
    check(line["device"] == expect, f"run recorded {line['device']}")


def _lowered_train_iter_has_kernel(folder: str) -> bool:
    """Lower (never compile) the fused iteration of the run in ``folder``,
    rebuilt from the config it saved, and look for a Mosaic kernel."""
    from surreal_tpu.main.launch import _load_session_config, select_trainer

    trainer = select_trainer(_load_session_config(folder))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(trainer.learner.init, key)
    loop = jax.eval_shape(trainer.init_loop_state, key)
    if hasattr(trainer, "replay"):  # off-policy: (carry, replay state)
        carry, replay = loop
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        flag = jax.ShapeDtypeStruct((), jnp.bool_)
        args = (state, replay, carry, key, scalar, flag, flag)
    else:
        args = (state, loop, key)
    return _has_kernel(trainer._train_iter, *args)


def train_phase(line, name, argv, expect, on_tpu, want_kernel=False) -> None:
    from surreal_tpu.main.launch import main

    folder = argv[argv.index("--folder") + 1]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(argv)
    check(rc == 0, f"main({name}) returned {rc}")
    metrics = json.loads(out.getvalue().strip().splitlines()[-1])
    _check_run(line, folder, metrics, expect)
    line["tpu_custom_call"] = _lowered_train_iter_has_kernel(folder)
    if on_tpu:
        check(
            line["tpu_custom_call"] == want_kernel,
            f"tpu_custom_call in the lowered iteration: "
            f"{line['tpu_custom_call']}, expected {want_kernel}",
        )


def ddpg_sets(sz: Sizes, gather: str) -> list:
    return [
        "learner_config.replay.kind=prioritized",
        f"learner_config.replay.capacity={sz.capacity}",
        f"learner_config.replay.batch_size={sz.batch}",
        f"learner_config.algo.updates_per_iter={sz.updates}",
        f"learner_config.algo.replay_gather={gather}",
    ]


# -- four chips ---------------------------------------------------------------

def _distinct_shard_devices(tree) -> int:
    return min(
        len({s.device for s in leaf.addressable_shards})
        for leaf in jax.tree.leaves(tree)
    )


def _replicas_bitwise_equal(tree) -> bool:
    for leaf in jax.tree.leaves(tree):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if len(shards) != jax.device_count():
            return False
        if any(s.tobytes() != shards[0].tobytes() for s in shards[1:]):
            return False
    return True


def ppo_dp4_phase(line, argv, expect) -> None:
    """The Quickstart command on a four-chip host: the same config and
    driver ``main(argv)`` builds, kept in hand so that the carry's shards
    and the final state's replicas can be looked at."""
    from surreal_tpu.main import launch

    args = launch.build_parser().parse_args(argv)
    config = launch.build_config(args)
    launch._apply_backend(config.session_config.backend)
    launch._require_platform(config.session_config.backend)
    trainer = launch.select_trainer(config)
    n = jax.device_count()
    check(trainer.mesh.shape["dp"] == n, f"mesh {dict(trainer.mesh.shape)}")
    carry = trainer.init_loop_state(jax.random.key(1))
    line["carry_shard_devices"] = _distinct_shard_devices(carry)
    check(line["carry_shard_devices"] == n, "env carry not on every device")
    state, metrics = trainer.run()
    _check_run(line, args.folder, metrics, expect)
    line["replicas_bitwise_equal"] = _replicas_bitwise_equal(state)
    check(line["replicas_bitwise_equal"], "replicated state differs by chip")


def dp_learn_phase(line, sz: Sizes, seed: int) -> None:
    """One learn step on one fixed [T, B] batch: dp_learn over every chip
    against the un-meshed learner on devices[0], held to
    tests/test_parallel.py::test_dp_learn_matches_single_device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.parallel import dp_learn, make_mesh
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    specs = make_env(Config(name="jax:lift").extend(BASE_ENV_CONFIG)).specs
    # one epoch, one minibatch: the dp update equals the global update
    learner = build_learner(
        Config(algo=Config(name="ppo", epochs=1, num_minibatches=1)), specs
    )
    T, B = sz.horizon, sz.ppo_envs
    obs, act = specs.obs.shape[0], specs.action.shape[0]
    ks = jax.random.split(jax.random.key(seed), 6)
    batch = {
        "obs": jax.random.normal(ks[0], (T, B, obs)),
        "next_obs": jax.random.normal(ks[1], (T, B, obs)),
        "action": jax.random.normal(ks[2], (T, B, act)),
        "reward": jax.random.normal(ks[3], (T, B)),
        "done": jnp.zeros((T, B), bool),
        "terminated": jnp.zeros((T, B), bool),
        "behavior_logp": jnp.full((T, B), -2.0),
        "behavior": {
            "mean": jnp.zeros((T, B, act)),
            "log_std": jnp.full((T, B, act), -0.5),
        },
    }
    state, key = learner.init(ks[4]), ks[5]
    dev0 = jax.devices()[0]
    one_state, one_metrics = jax.jit(learner.learn)(
        *jax.device_put((state, batch, key), dev0)
    )
    mesh = make_mesh(Config(mesh=Config(dp=jax.device_count(), tp=1)))
    sharded = jax.device_put(batch, NamedSharding(mesh, P(None, "dp")))
    line["batch_shard_devices"] = _distinct_shard_devices(sharded)
    check(
        line["batch_shard_devices"] == jax.device_count(),
        "learn batch not on every device",
    )
    dp_state, dp_metrics = dp_learn(learner, mesh, donate=False)(
        jax.device_put(state, NamedSharding(mesh, P())), sharded, key
    )
    pairs = [
        (np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(one_state.params), jax.tree.leaves(dp_state.params)
        )
    ]
    line["max_abs_param_diff"] = max(float(np.abs(a - b).max()) for a, b in pairs)
    check(
        all(np.allclose(a, b, rtol=2e-2, atol=1e-3) for a, b in pairs),
        f"params differ: max {line['max_abs_param_diff']:g}",
    )
    kl = (float(one_metrics["policy/kl"]), float(dp_metrics["policy/kl"]))
    line["policy_kl"] = kl
    check(abs(kl[0] - kl[1]) <= 1e-4, f"policy/kl {kl}")
    check(
        bool(np.allclose(
            np.asarray(one_state.obs_stats.mean),
            np.asarray(dp_state.obs_stats.mean), rtol=1e-5,
        )),
        "obs stats differ",
    )
    line["replicas_bitwise_equal"] = _replicas_bitwise_equal(dp_state)
    check(line["replicas_bitwise_equal"], "dp_learn state differs by chip")


# -- entry --------------------------------------------------------------------

def run(args) -> int:
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
    }
    on_tpu = device["platform"] == "tpu"
    if on_tpu == args.rehearse:
        print(
            f"chip_smoke: found {device}; "
            + ("--rehearse is for a machine without a TPU" if on_tpu
               else "no TPU, nothing run (--rehearse walks the phases at "
                    "toy sizes, and still fails)"),
            file=sys.stderr,
        )
        return 2
    if device["count"] != args.chips:
        print(
            f"chip_smoke: found {device['count']} device(s), --chips says "
            f"{args.chips}", file=sys.stderr,
        )
        return 2
    sz = Sizes(args.rehearse)
    rng = np.random.default_rng(args.seed)
    shutil.rmtree(OUT_DIR, ignore_errors=True)  # an earlier smoke's sessions
    session = lambda name: os.path.join(OUT_DIR, name)
    results: list[dict] = []
    print(json.dumps({
        "phase": "start", "device": device, "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start":
            len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir) else 0,
        "rehearsal": args.rehearse,
    }), flush=True)
    ppo_steps = sz.ppo_envs * sz.horizon * sz.iters
    ppo_sets = [f"learner_config.algo.horizon={sz.horizon}"]
    if args.chips == 1:
        with phase("kernels", results) as line:
            kernels_phase(line, sz, rng, on_tpu)
        with phase("ppo", results) as line:
            train_phase(line, "ppo", _train_argv(
                "ppo", session("ppo"), sz.ppo_envs, ppo_steps, ppo_sets,
                sz.iters,
            ), device, on_tpu)
        ddpg_steps = sz.ddpg_envs * 16 * sz.iters  # DDPG's horizon is 16
        for name, gather in (("ddpg", "xla"), ("ddpg_pallas", "pallas")):
            with phase(name, results) as line:
                train_phase(line, name, _train_argv(
                    "ddpg", session(name), sz.ddpg_envs, ddpg_steps,
                    ddpg_sets(sz, gather), sz.iters,
                ), device, on_tpu, want_kernel=gather == "pallas")
    else:
        with phase("ppo_dp4", results) as line:
            ppo_dp4_phase(line, _train_argv(
                "ppo", session("ppo_dp4"), sz.ppo_envs, ppo_steps,
                ppo_sets + [f"session_config.topology.mesh.dp={args.chips}"],
                sz.iters,
            ), device)
        with phase("dp_learn", results) as line:
            dp_learn_phase(line, sz, args.seed)
    if not all(r["ok"] for r in results):
        failed = [r["phase"] for r in results if not r["ok"]]
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    if not on_tpu:
        print("chip_smoke: rehearsal passed; not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
