"""Experiment launcher — L7 entry point (parity: reference
``surreal/main/launch.py`` ``SurrealDefaultLauncher`` + the
``surreal-tmux``/``surreal-subproc``/``surreal-kube`` cluster CLIs,
SURVEY.md §2.1 Main-dispatch/Cluster-CLI rows and §3.1).

The reference CLI built a symphony process group — agents, learner,
replay(-shards), ps, evals, tensorplex, loggerplex, tensorboard — and
launched one OS process per component. In the TPU rebuild those components
are modules of ONE SPMD program, so the launcher's job collapses to:

    parse (algo, env, overrides) -> three config trees -> pick the driver
    -> run with checkpoint + metrics + eval wired (SessionHooks).

Component-role map (for auditability against the reference dispatch):
    run_agent / run_agent-batch -> rollout collectors inside the driver
                                   (launch/rollout.py, SEED inference server);
                                   standalone: `surreal_tpu actor` vs a live
                                   session's parameter server
    run_learner                 -> learner step inside the driver
    run_replay                  -> HBM replay (replay/) inside the driver
    run_ps                      -> device-resident params (no process); host
                                   plane: distributed/param_service.py, LIVE
                                   via session_config.publish (SessionHooks
                                   publishes the acting view every N iters)
    run_eval(s)                 -> launch/evaluator.py via SessionHooks;
                                   standalone: `surreal_tpu eval` (checkpoint)
                                   or `eval --follow` (live published params)
    run_tensorboard/tensorplex/loggerplex -> session/metrics.py writers
    tmux/kube/subproc cluster   -> session_config.topology (mesh axes +
                                   env-worker processes), no external CLI

Usage:
    python -m surreal_tpu train ppo jax:lift --folder /tmp/exp1
    python -m surreal_tpu train ddpg jax:lift --folder /tmp/exp2 \
        --num-envs 256 --set learner_config.algo.n_step=3
    python -m surreal_tpu eval --folder /tmp/exp1 --episodes 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.telemetry import launch_imported, launch_span

ALGOS = ("ppo", "ddpg", "impala")


def build_config(args) -> Config:
    """CLI args -> fully-extended three-tree config bundle. An entry
    point's first call: the launch record's ``launch.import`` ends here
    (session/telemetry.py)."""
    launch_imported()
    overrides = Config(
        learner_config=Config(algo=Config(name=args.algo)),
        env_config=Config(name=args.env, num_envs=args.num_envs),
        session_config=Config(folder=args.folder),
    )
    if args.total_steps is not None:
        overrides.session_config.total_env_steps = args.total_steps
    if args.restore_from is not None:
        overrides.session_config.checkpoint = Config(restore_from=args.restore_from)
    if getattr(args, "workers", None) is not None:
        overrides.session_config.topology = Config(num_env_workers=args.workers)
    if args.set:
        overrides.override_from_dotlist(args.set)
    return overrides.extend(base_config())


@launch_span("launch.backend")
def _apply_backend(backend: str) -> None:
    """``session_config.backend``, before first jax use: 'cpu' selects the
    host CPU for this process; 'tpu' (default) selects nothing here and
    :func:`_require_platform` holds the resolved platform to it. Also
    turns on the persistent compile cache (utils/compat.py decides
    where), so the process's first compile already goes through it."""
    import jax

    if backend == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif backend != "tpu":
        raise ValueError(f"session_config.backend {backend!r} not in tpu|cpu")
    from surreal_tpu.utils.compat import enable_compile_cache

    enable_compile_cache()


def _cpu_selected() -> bool:
    """An explicit CPU selection in this process: ``JAX_PLATFORMS=cpu``
    (JAX reads it into ``jax_platforms`` at import) or a
    ``jax.config.update("jax_platforms", "cpu")``, which is how the test
    suite runs. Touches no backend."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


@launch_span("launch.backend")
def _require_platform(backend: str) -> None:
    """A chip that fails to initialise must not become a CPU run that
    exits 0: with ``backend='tpu'`` and no explicit CPU selection
    (:func:`_cpu_selected`), a resolved platform other than 'tpu' is an
    error. Initialises the backend — multi-host runs call it after
    joining the distributed runtime."""
    import jax

    if backend == "tpu" and not _cpu_selected():
        resolved = jax.default_backend()
        if resolved != "tpu":
            raise RuntimeError(
                f"session_config.backend='tpu' but JAX resolved platform "
                f"{resolved!r} ({jax.devices()[0].device_kind}): the TPU did "
                "not initialise (is another process holding the chip?). To "
                "run on the CPU on purpose, say so: JAX_PLATFORMS=cpu or "
                "--set session_config.backend=cpu"
            )


def _validate_seed_topology(config) -> int:
    """The SEED inference-server topology needs a HOST env and an
    on-policy algo — one rule for the single- AND multi-host gates (fail
    loudly rather than silently running a different topology than the one
    the user configured). Returns num_env_workers."""
    algo = config.learner_config.algo.name
    env_name = config.env_config.name
    workers = config.session_config.topology.num_env_workers
    if workers > 0 and (algo == "ddpg" or env_name.startswith("jax:")):
        raise ValueError(
            f"topology.num_env_workers={workers} selects the SEED "
            "inference-server topology, which needs a HOST env (gym:/"
            "dm_control:/robosuite:) and an on-policy algo (ppo, impala); "
            f"got algo={algo!r}, env={env_name!r} — drop --workers, or "
            "use a host env / on-policy algo"
        )
    return workers


@launch_span("launch.build")
def select_trainer(config):
    """Map config -> driver (the component-dispatch role of the reference's
    launcher, collapsed to one decision):

    - off-policy algos (ddpg) -> OffPolicyTrainer (replay-driven)
    - host envs with env workers configured -> SEEDTrainer (batched
      inference server + worker processes/threads)
    - everything else -> Trainer (fused device loop, or host alternation)
    """
    algo = config.learner_config.algo.name
    workers = _validate_seed_topology(config)
    if algo == "ddpg":
        from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

        return OffPolicyTrainer(config)
    if workers > 0:
        from surreal_tpu.launch.seed_trainer import SEEDTrainer

        return SEEDTrainer(config)
    from surreal_tpu.launch.trainer import Trainer

    return Trainer(config)


def spawn_rank(
    cli_argv,
    rank: int,
    num_processes: int,
    coordinator: str,
    *,
    env: dict | None = None,
    stdout=None,
    stderr=None,
    cwd=None,
):
    """Spawn ONE rank of a ``surreal_tpu`` process group as an OS process
    carrying the jax.distributed env-var contract
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID — the
    GKE/xmanager launcher shape that ``parallel/multihost.py`` consumes as
    its config fallback). Shared by the ``--local-procs`` supervisor and
    the multi-host test harness, so product and tests launch ranks the
    same way."""
    import subprocess

    e = dict(os.environ if env is None else env)
    e["JAX_COORDINATOR_ADDRESS"] = coordinator
    e["JAX_NUM_PROCESSES"] = str(num_processes)
    e["JAX_PROCESS_ID"] = str(rank)
    return subprocess.Popen(
        [sys.executable, "-m", "surreal_tpu", *cli_argv],
        env=e, stdout=stdout, stderr=stderr, cwd=cwd, text=True,
    )


def _strip_local_procs(argv):
    """Child ranks run the SAME command minus the supervisor flag."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
        elif a == "--local-procs":
            skip = True
        elif not a.startswith("--local-procs="):
            out.append(a)
    return out


def _run_local_group(args) -> int:
    """One-command process groups (parity: the reference's symphony /
    ``surreal-subproc`` CLI materialized the whole experiment's process
    group with one command, SURVEY.md §3.1): spawn N ranks of THIS train
    command locally, wire the coordinator, forward signals, reap children.
    Rank 0 inherits this terminal; ranks > 0 log to <folder>/rank<i>.log.
    A non-zero child exit tears the whole group down (a half-dead process
    group would deadlock the survivors' next collective).

    The ranks are CPU processes, said out loud (``JAX_PLATFORMS=cpu`` or
    ``session_config.backend=cpu`` — a simulated multi-host group, how the
    tests use it). With the default ``backend='tpu'`` every rank would
    initialise the TPU runtime of THIS host, and a chip belongs to one
    process at a time: the group is refused before anything is spawned.
    On a TPU host one process drives all local chips (``topology.mesh``);
    real multi-host runs start one process per host themselves
    (``topology.multihost`` / the JAX_COORDINATOR_ADDRESS contract)."""
    if build_config(args).session_config.backend == "tpu" and not _cpu_selected():
        print(
            f"--local-procs {args.local_procs} would start "
            f"{args.local_procs} processes that each initialise the TPU "
            "runtime on this host, and a chip belongs to one process at a "
            "time. Local ranks are CPU processes: set JAX_PLATFORMS=cpu (or "
            "--set session_config.backend=cpu). On a TPU host run ONE "
            "process: it drives every local chip through "
            "session_config.topology.mesh.",
            file=sys.stderr,
        )
        return 2
    # Picking the coordinator port by bind-then-close is a TOCTOU race:
    # another process can grab it before rank 0 binds. One retry with a
    # fresh port (when the group dies inside the startup window AND the
    # failure looks like the coordinator, not a deterministic startup
    # error) makes the race a non-event instead of a failed launch.
    code = _spawn_local_group_once(args, retry_early_failure=True)
    if code == _EARLY_GROUP_FAILURE:
        print(
            "local group failed during startup and the failed rank's log "
            "matches a JAX coordinator bind/connect failure (or the log is "
            "not inspectable); retrying once with a fresh port. The retry "
            "is SPECULATIVE — a deterministic failure will simply repeat.",
            file=sys.stderr,
        )
        code = _spawn_local_group_once(args, retry_early_failure=False)
    return code


_EARLY_GROUP_FAILURE = -255  # sentinel: group died inside the startup window

# error signatures of the jax.distributed coordinator losing its port race
# (rank 0's bind, other ranks' connect/handshake against a dead address) —
# deterministic startup failures (bad flag, import error, config typo) match
# none of these and must NOT respawn the group (round-5 review)
_COORDINATOR_FAILURE_RE = None  # compiled lazily (keeps module import light)


def _log_suggests_coordinator_race(folder: str, rank: int) -> bool:
    """Inspect the failed rank's log tail for the coordinator bind/connect
    signature. Rank 0 owns the terminal (no log file) — and rank 0 is
    exactly where the bind race fires — so an uninspectable log keeps the
    retry allowed rather than suppressing it."""
    global _COORDINATOR_FAILURE_RE
    if rank == 0:
        return True
    if _COORDINATOR_FAILURE_RE is None:
        import re

        _COORDINATOR_FAILURE_RE = re.compile(
            r"coordination service|coordinator|jax\.distributed|"
            r"Failed to bind|Address already in use|errno 98|"
            r"UNAVAILABLE|DEADLINE_EXCEEDED|failed to connect|"
            r"Connection refused|barrier timed out",
            re.IGNORECASE,
        )
    path = os.path.join(folder, f"rank{rank}.log")
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - 8192))
            tail = f.read().decode("utf-8", "replace")
    except OSError:
        return True  # can't inspect -> keep the (speculative) retry
    return bool(_COORDINATOR_FAILURE_RE.search(tail))


def _spawn_local_group_once(args, retry_early_failure: bool) -> int:
    import signal
    import socket
    import subprocess
    import time

    n = int(args.local_procs)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    child_argv = _strip_local_procs(args.raw_argv)
    os.makedirs(args.folder, exist_ok=True)
    procs, logs = [], []
    start = time.monotonic()
    try:
        for i in range(n):
            if i == 0:
                out_i, err_i = None, None  # rank 0 owns this terminal
            else:
                f = open(os.path.join(args.folder, f"rank{i}.log"), "w")
                logs.append(f)
                out_i, err_i = f, subprocess.STDOUT
            procs.append(
                spawn_rank(child_argv, i, n, f"127.0.0.1:{port}",
                           stdout=out_i, stderr=err_i)
            )

        def forward(sig, _frame):
            for p in procs:
                if p.poll() is None:
                    p.send_signal(sig)

        old = {
            s_: signal.signal(s_, forward)
            for s_ in (signal.SIGINT, signal.SIGTERM)
        }
        try:
            while True:
                codes = [p.poll() for p in procs]
                bad_rank = next(
                    (i for i, c in enumerate(codes) if c not in (None, 0)),
                    None,
                )
                if bad_rank is not None:
                    bad = codes[bad_rank]
                    for p in procs:
                        if p.poll() is None:
                            p.terminate()
                    deadline = time.monotonic() + 10
                    for p in procs:
                        while p.poll() is None and time.monotonic() < deadline:
                            time.sleep(0.1)
                        if p.poll() is None:
                            p.kill()
                    # retry only plausible port races: a child that died
                    # from a signal (bad < 0, e.g. the user's Ctrl+C
                    # forwarded to the group) must not respawn the group,
                    # and neither must a deterministic startup failure —
                    # the failed rank's log tail must match the jax
                    # coordinator bind/connect signature
                    if (
                        retry_early_failure
                        and bad > 0
                        and time.monotonic() - start < 15
                        and _log_suggests_coordinator_race(
                            args.folder, bad_rank
                        )
                    ):
                        return _EARLY_GROUP_FAILURE
                    return int(bad)
                if all(c == 0 for c in codes):
                    return 0
                time.sleep(0.2)
        finally:
            for s_, h in old.items():
                signal.signal(s_, h)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()


def run_train(args) -> int:
    if getattr(args, "local_procs", None) and args.local_procs > 1:
        return _run_local_group(args)
    config = build_config(args)
    _apply_backend(config.session_config.backend)
    # must precede first jax use: joins this process into the global
    # device runtime when a multi-host topology is configured
    from surreal_tpu.parallel.multihost import initialize_from_topology

    multihost = initialize_from_topology(config.session_config.topology)
    _require_platform(config.session_config.backend)
    if multihost:
        algo = config.learner_config.algo.name
        env_name = config.env_config.name
        _validate_seed_topology(config)  # one rule with select_trainer
        if algo == "ddpg" and not env_name.startswith("jax:"):
            # fail loudly: host-env off-policy keeps its replay on one
            # host's devices — single-controller by design
            raise ValueError(
                "multi-host ddpg needs a device env (jax:*); host-env "
                f"off-policy runs single-host (got env={env_name!r})"
            )
    import jax

    rank0 = jax.process_index() == 0  # trivially True single-host
    if rank0:
        os.makedirs(config.session_config.folder, exist_ok=True)
        # persist the resolved config so `eval` (and future resumes) can
        # rebuild the exact learner/env without re-supplying CLI flags.
        # tmp + rename: actor/eval processes poll for this file and must
        # never observe a half-written json
        cfg_path = os.path.join(config.session_config.folder, "config.json")
        with open(cfg_path + ".tmp", "w") as f:
            f.write(config.dumps())
        os.replace(cfg_path + ".tmp", cfg_path)
    if multihost:
        with launch_span("launch.build"):
            if config.session_config.topology.num_env_workers > 0:
                from surreal_tpu.launch.multihost_trainer import (
                    MultiHostSEEDTrainer,
                )

                trainer = MultiHostSEEDTrainer(config)
            elif config.learner_config.algo.name == "ddpg":
                from surreal_tpu.launch.multihost_trainer import (
                    MultiHostOffPolicyTrainer,
                )

                trainer = MultiHostOffPolicyTrainer(config)
            else:
                from surreal_tpu.launch.multihost_trainer import MultiHostTrainer

                trainer = MultiHostTrainer(config)
    else:
        trainer = select_trainer(config)
    state, metrics = trainer.run()
    if rank0:
        print(json.dumps({k: v for k, v in sorted(metrics.items())}, default=float))
    return 0


def _load_session_config(folder: str, wait_s: float = 0.0):
    """Read the session's persisted config.json; with ``wait_s`` poll for
    it (actor/eval processes may launch before the trainer wrote it).
    Writes are atomic (tmp+rename), but sessions trained by older builds
    may have written in place — treat a bad parse as not-there-yet."""
    import time

    cfg_path = os.path.join(folder, "config.json")
    deadline = time.monotonic() + wait_s
    while True:
        if os.path.exists(cfg_path):
            try:
                with open(cfg_path) as f:
                    return Config(json.load(f))
            except (json.JSONDecodeError, OSError):
                pass
        if time.monotonic() >= deadline:
            return None
        time.sleep(0.2)


def _discover_param_server(folder: str, connect: str | None, wait_s: float) -> str:
    """Resolve the live session's parameter-server address: --connect wins;
    otherwise poll <folder>/param_server.json (written by SessionHooks when
    session_config.publish.enabled)."""
    import time

    if connect:
        return connect
    path = os.path.join(folder, "param_server.json")
    deadline = time.monotonic() + wait_s
    while True:
        if os.path.exists(path):
            try:
                with open(path) as f:
                    return json.load(f)["addresses"][0]
            except (json.JSONDecodeError, OSError, KeyError, IndexError):
                pass  # racing the atomic replace; retry
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"no {path} after {wait_s:.0f}s — is a training session "
                "with session_config.publish.enabled=true running? "
                "(or pass --connect tcp://host:port)"
            )
        time.sleep(0.2)


_ACTOR_MODES = {
    "training": "training",
    "deterministic": "eval_deterministic",
    "stochastic": "eval_stochastic",
}


def _wait_for_publish(
    agent, folder, connect, address, wait_s, *, min_version=1, fetch_every=1
):
    """Block until a published view with version >= ``min_version`` has
    been FETCHED into ``agent``. Polls with version-only probes (no blob
    transfer), and — unless the address was pinned with --connect —
    re-resolves the discovery file between retries, so a stale
    param_server.json from a dead session cannot eat the wait budget once
    a new session rewrites it. Returns True on success, False on
    timeout."""
    import time

    deadline = time.monotonic() + wait_s
    while True:
        try:
            if (
                agent.peek_published_version(timeout_ms=2000) >= min_version
                and agent.fetch_params()
            ):
                return True
        except TimeoutError:
            pass
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.3)
        if not connect:
            try:
                new_addr = _discover_param_server(folder, None, 0.0)
            except TimeoutError:
                continue
            if new_addr != address:
                address = new_addr
                state = agent.state
                agent.close()
                agent.connect(address, state, fetch_every=fetch_every)


def run_actor(args) -> int:
    """Standalone actor process against a LIVE training session (parity:
    reference ``run_agent`` — a separate OS process acting with params
    periodically re-fetched from the parameter server, SURVEY.md §3.2).

    Prints one JSON line per finished episode ({episode, return, length,
    param_version}) and a final summary line; ``actor/versions_seen`` > 1
    is the proof the actor tracked a LIVE learner, not a snapshot."""
    config = _load_session_config(args.folder, wait_s=args.wait)
    if config is None:
        print(f"no config.json under {args.folder!r} (launch training first)",
              file=sys.stderr)
        return 2
    backend = config.session_config.get("backend", "tpu")
    _apply_backend(backend)
    _require_platform(backend)
    address = _discover_param_server(args.folder, args.connect, args.wait)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from surreal_tpu.agents import make_agent
    from surreal_tpu.envs import is_jax_env, make_env
    from surreal_tpu.learners import build_learner

    env_cfg = config.env_config
    if args.num_envs is not None:
        env_cfg = Config(num_envs=args.num_envs).extend(env_cfg)
    if args.video_dir:
        if env_cfg.name.startswith("jax:"):
            raise ValueError(
                "--video-dir records through the host VideoWrapper; device "
                "(jax:*) env episodes are recorded by eval's state renderer "
                "(env_config.video on the training session) instead"
            )
        env_cfg = Config(
            video=Config(enabled=True, dir=args.video_dir, every_n_episodes=1)
        ).extend(env_cfg)
    env = make_env(env_cfg)
    learner = build_learner(config.learner_config, env.specs)
    agent = make_agent(learner, _ACTOR_MODES[args.mode])
    agent.connect(
        address, learner.init(jax.random.key(args.seed)),
        fetch_every=args.fetch_every,
    )
    # block until a published view >= --min-version lands (the learner may
    # still be compiling its first iterations; min-version lets an actor
    # wait for a warm policy instead of acting from the init snapshot)
    if not _wait_for_publish(
        agent, args.folder, args.connect, address, args.wait,
        min_version=max(1, args.min_version), fetch_every=args.fetch_every,
    ):
        print(
            f"nothing published (>= version {args.min_version}) on "
            f"{address} after {args.wait:.0f}s",
            file=sys.stderr,
        )
        return 2

    B = env_cfg.num_envs
    key = jax.random.key(args.seed + 1)
    ep_ret = np.zeros(B, np.float64)
    ep_len = np.zeros(B, np.int64)
    episodes_done = 0
    versions_seen: set[int] = set()

    def on_step(reward: np.ndarray, done: np.ndarray) -> None:
        nonlocal episodes_done
        ep_ret[:] += reward
        ep_len[:] += 1
        versions_seen.add(agent.param_version)
        for i in np.nonzero(done)[0]:
            episodes_done += 1
            print(json.dumps({
                "episode": episodes_done,
                "return": float(ep_ret[i]),
                "length": int(ep_len[i]),
                "param_version": agent.param_version,
            }), flush=True)
            ep_ret[i] = 0.0
            ep_len[i] = 0
        if hasattr(agent, "mask_noise_on_reset"):
            # DDPG's OU exploration state must not leak across resets
            agent.mask_noise_on_reset(done)

    act_steps = 0  # across the batch: each loop pass acts B envs
    cap = args.max_steps if args.max_steps is not None else 10**9
    final_version = agent.param_version
    try:
        if is_jax_env(env):
            from surreal_tpu.envs.jax.base import batch_reset, batch_step

            key, rkey = jax.random.split(key)
            env_state, obs = batch_reset(env, jax.random.split(rkey, B))
            step_fn = jax.jit(lambda s, a: batch_step(env, s, a))
            while episodes_done < args.episodes and act_steps < cap:
                key, akey = jax.random.split(key)
                action, _ = agent.remote_act(obs, akey)
                env_state, obs, reward, done, _ = step_fn(env_state, action)
                on_step(np.asarray(reward), np.asarray(done))
                act_steps += B
        else:
            obs = env.reset(seed=env_cfg.seed)
            while episodes_done < args.episodes and act_steps < cap:
                key, akey = jax.random.split(key)
                action, _ = agent.remote_act(jnp.asarray(obs), akey)
                out = env.step(np.asarray(action))
                on_step(out.reward, out.done)
                obs = out.obs
                act_steps += B
    finally:
        final_version = max(final_version, agent.param_version)
        agent.close()
        if hasattr(env, "close"):
            env.close()
    print(json.dumps({
        "actor/episodes": episodes_done,
        "actor/steps": act_steps,
        "actor/param_version": final_version,
        "actor/versions_seen": len(versions_seen),
    }), flush=True)
    return 0


def run_eval(args) -> int:
    """Score a trained session folder (reference ``run_eval`` as a CLI) —
    or, with ``--follow``, attach to a LIVE session's parameter server and
    score freshly-fetched params each round (the reference's standing eval
    workers, SURVEY.md §3.5)."""
    import jax

    from surreal_tpu.envs import make_env
    from surreal_tpu.launch.evaluator import Evaluator
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.checkpoint import CheckpointManager

    config = _load_session_config(
        args.folder, wait_s=args.wait if args.follow else 0.0
    )
    if config is None:
        print(f"no config.json under {args.folder!r} (was it trained via the CLI?)",
              file=sys.stderr)
        return 2
    # eval must run on the backend the session trained on; sessions saved
    # before the backend knob existed default to tpu (the old behavior)
    backend = config.session_config.get("backend", "tpu")
    _apply_backend(backend)
    _require_platform(backend)
    probe = make_env(config.env_config)
    learner = build_learner(config.learner_config, probe.specs)
    if hasattr(probe, "close"):
        probe.close()

    eval_cfg = Config(
        episodes=args.episodes, mode=args.mode, max_steps=args.max_steps
    )
    if args.follow:
        import time

        from surreal_tpu.agents import make_agent

        address = _discover_param_server(args.folder, args.connect, args.wait)
        agent = make_agent(learner, _ACTOR_MODES[args.mode])
        agent.connect(address, learner.init(jax.random.key(0)))
        if not _wait_for_publish(
            agent, args.folder, args.connect, address, args.wait
        ):
            print(f"nothing published on {address} after {args.wait:.0f}s",
                  file=sys.stderr)
            agent.close()
            return 2
        ev = Evaluator(config.env_config, eval_cfg, learner)
        try:
            for rnd in range(args.rounds):
                if rnd:
                    agent.fetch_params()  # freshest published view per round
                out = ev.evaluate(
                    agent.state,
                    jax.random.fold_in(jax.random.key(args.seed), rnd),
                )
                out["param_version"] = agent.param_version
                print(json.dumps(
                    {k: v for k, v in sorted(out.items())}, default=float
                ), flush=True)
        finally:
            ev.close()
            agent.close()
        return 0

    mgr = CheckpointManager(config.session_config.folder)
    template = learner.init(jax.random.key(0))
    restored = (
        mgr.restore_best(template) if args.best else mgr.restore(template)
    )
    if restored is None:
        print(f"no {'best ' if args.best else ''}checkpoint under {args.folder!r}",
              file=sys.stderr)
        mgr.close()
        return 2
    state, meta = restored
    mgr.close()

    ev = Evaluator(config.env_config, eval_cfg, learner)
    out = ev.evaluate(state, jax.random.key(args.seed))
    ev.close()
    out["checkpoint/iteration"] = meta["iteration"]
    out["checkpoint/env_steps"] = meta["env_steps"]
    print(json.dumps({k: v for k, v in sorted(out.items())}, default=float))
    return 0


def run_profile(args) -> int:
    """Request an on-demand profiler capture from a LIVE training session:
    drops ``<folder>/profile.trigger``, which the session's ProfileManager
    (session/profile.py) polls at iteration boundaries — the capture
    lands under ``<folder>/telemetry/profiles/`` and is announced as a
    ``profile`` telemetry event (``surreal_tpu diag`` lists it). Pure
    file writing: works off-chip, requires no connection to the session."""
    if not os.path.isdir(args.folder):
        print(f"no session folder {args.folder!r}", file=sys.stderr)
        return 2
    from surreal_tpu.session.profile import write_trigger

    path = write_trigger(args.folder, num_iters=args.iters)
    print(
        f"profile trigger written: {path}\n"
        "a live session (session_config.profile.trigger_file=true, the "
        "default) will capture at its next iteration boundary; check "
        f"`surreal_tpu diag {args.folder}` for the capture."
    )
    return 0


def run_diag(args) -> int:
    """Offline session diagnosis from the telemetry spine's JSONL logs
    (session/telemetry.py): phase-time breakdown, training-health
    summary, last-heartbeat table. Pure file reading — no jax backend is
    touched, so it runs off-chip and against LIVE sessions."""
    from surreal_tpu.session.telemetry import diag_report, diag_summary

    if args.json:
        summary = diag_summary(args.folder)
        if summary is None:
            print(f"no telemetry under {args.folder!r} "
                  "(session_config.telemetry.enabled=false, or not a "
                  "session folder?)", file=sys.stderr)
            return 2
        print(json.dumps(summary, default=float))
        return 0
    report = diag_report(args.folder)
    if report is None:
        print(f"no telemetry under {args.folder!r} "
              "(session_config.telemetry.enabled=false, or not a "
              "session folder?)", file=sys.stderr)
        return 2
    print(report)
    return 0


def run_top(args) -> int:
    """Live cross-tier ops view from the aggregator's merged snapshot
    file (session/opsplane.py): per-tier health, per-tenant SLO/budget
    table, hop latencies, MFU. Pure file reading — no jax, no zmq — so
    it runs off-chip against a LIVE run, refreshing at ``--interval``
    until interrupted (or printing once with ``--once``)."""
    from surreal_tpu.session.opsplane import load_snapshot, top_report

    if not os.path.isdir(args.folder):
        print(f"no session folder {args.folder!r}", file=sys.stderr)
        return 2
    if args.once:
        snap = load_snapshot(args.folder)
        print(top_report(snap, args.folder))
        return 0 if snap is not None else 2
    try:
        while True:
            report = top_report(load_snapshot(args.folder), args.folder)
            # clear-screen + home, like top(1); falls back to plain
            # scrolling output when stdout is not a terminal
            if sys.stdout.isatty():
                print("\x1b[2J\x1b[H", end="")
            print(report, flush=True)
            time.sleep(max(0.2, float(args.interval)))
    except KeyboardInterrupt:
        return 0


def run_trace(args) -> int:
    """Causal span-tree timelines for the head-sampled exemplars
    (session/telemetry.py trace_report): one tree per exemplar, spans
    correlated across tiers by trace/span ids, torn hops marked. Pure
    file reading over the telemetry event log — no jax, no zmq — so it
    works off-chip and against a live run, like ``diag``/``top``."""
    from surreal_tpu.session.telemetry import trace_report

    if not os.path.isdir(args.folder):
        print(f"no session folder {args.folder!r}", file=sys.stderr)
        return 2
    report = trace_report(args.folder, limit=args.limit)
    if report is None:
        print(f"no telemetry under {args.folder!r} (is this a "
              "session folder?)", file=sys.stderr)
        return 2
    print(report)
    return 0


def run_why(args) -> int:
    """Root-caused incident reports from the watchdog/incident engine
    (session/incidents.py): what fired, the ranked cause hypotheses with
    their correlated evidence (faults, respawns, SLO breaches, slowest
    exemplar spans), where the auto-captured profile/flight-recorder
    artifacts landed, and — when the remediation engine acted — the
    Actions section (cause -> action -> verdict, reverts marked;
    session/remediate.py). Pure file reading over telemetry/incidents/
    and telemetry/actions/ — no jax, no zmq — so it works off-chip and
    against a live run, like ``diag``/``top``/``trace``."""
    from surreal_tpu.session.incidents import incidents_report

    if not os.path.isdir(args.folder):
        print(f"no session folder {args.folder!r}", file=sys.stderr)
        return 2
    report = incidents_report(args.folder, incident=args.incident)
    if report is None:
        print(f"no telemetry under {args.folder!r} (is this a "
              "session folder?)", file=sys.stderr)
        return 2
    print(report)
    return 0


def run_chaos(args) -> int:
    """Randomized chaos campaign (surreal_tpu/chaos/): N seeded
    multi-site fault schedules executed as short REAL training runs,
    every run judged by the invariant oracles, failing schedules shrunk
    to minimal form. Exit 0 only on zero violations; ``--out`` writes
    the campaign's record."""
    import tempfile

    from surreal_tpu.chaos import campaign as chaos_campaign
    from surreal_tpu.chaos import schedule as chaos_schedule

    profiles = [
        p for p, meta in chaos_schedule.PROFILES.items()
        if args.algo in ("all", meta["algo"])
    ]
    if not profiles:
        print(f"no chaos profile for algo {args.algo!r} "
              f"(profiles: {sorted(chaos_schedule.PROFILES)})",
              file=sys.stderr)
        return 2
    base_dir = args.dir or tempfile.mkdtemp(prefix="surreal_chaos_")
    os.makedirs(base_dir, exist_ok=True)
    env = args.env if args.env not in (None, "default") else None
    artifact = chaos_campaign.run_campaign(
        seeds=args.seeds,
        base_dir=base_dir,
        profiles=profiles,
        env=env,
        max_shrink_runs=args.max_shrink_runs,
    )
    if args.out:
        chaos_campaign.write_artifact(args.out, artifact)
        print(f"wrote {args.out}")
    g = artifact["gauges"]
    print(f"chaos campaign: {int(g['chaos/schedules'])} schedules, "
          f"{int(g['chaos/sites_covered'])} sites fired, "
          f"{int(g['chaos/faults_injected'])} faults injected, "
          f"{int(g['chaos/violations'])} violations "
          f"({g['chaos/run_ms'] / 1e3:.1f}s)")
    for fail in artifact["failures"]:
        print(f"  FAIL seed={fail['seed']} profile={fail['profile']}: "
              f"minimal plan {json.dumps(fail['minimal_plan'])} "
              f"(replay: surreal_tpu chaos ... --seeds 1 with this "
              f"(profile, seed))")
    return 1 if artifact["failures"] else 0


def build_parser() -> argparse.ArgumentParser:
    """The CLI's parser (also how chip_smoke.py turns an argv into the
    same config ``main`` would build)."""
    parser = argparse.ArgumentParser(prog="surreal_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="launch a training experiment")
    t.add_argument("algo", choices=ALGOS)
    t.add_argument("env", help="env name with backend prefix, e.g. jax:lift, "
                   "gym:CartPole-v1, dm_control:cheetah-run")
    t.add_argument("--folder", required=True, help="session/experiment directory")
    t.add_argument("--num-envs", type=int, default=64)
    t.add_argument("--total-steps", type=int, default=None)
    t.add_argument("--restore-from", default=None,
                   help="foreign session folder to warm-start from")
    t.add_argument("--workers", type=int, default=None,
                   help="env-worker processes/threads for host envs (>0 "
                        "selects the SEED inference-server topology)")
    t.add_argument("--local-procs", type=int, default=None,
                   help="spawn this many multi-controller ranks locally as "
                        "one process group (one-command multi-host; the "
                        "reference's symphony/subproc role). Rank 0 owns "
                        "this terminal, ranks>0 log to <folder>/rank<i>.log")
    t.add_argument("--set", nargs="*", metavar="KEY=VAL", default=[],
                   help="dotlist overrides, e.g. learner_config.algo.horizon=64")
    t.set_defaults(fn=run_train)

    e = sub.add_parser("eval", help="evaluate a trained session folder, or "
                       "--follow a live session's parameter server")
    e.add_argument("--folder", required=True)
    e.add_argument("--episodes", type=int, default=10)
    e.add_argument("--mode", choices=("deterministic", "stochastic"),
                   default="deterministic")
    e.add_argument("--best", action="store_true",
                   help="use the keep-best checkpoint instead of the latest")
    e.add_argument("--max-steps", type=int, default=None,
                   help="per-episode step cap (default: env time limit on "
                        "device envs, 10000 on host envs)")
    e.add_argument("--seed", type=int, default=0)
    e.add_argument("--follow", action="store_true",
                   help="score the LIVE session's published params instead "
                        "of a checkpoint (needs session_config.publish)")
    e.add_argument("--connect", default=None,
                   help="parameter-server address (default: discover via "
                        "<folder>/param_server.json)")
    e.add_argument("--rounds", type=int, default=1,
                   help="--follow only: eval rounds, re-fetching params "
                        "each round")
    e.add_argument("--wait", type=float, default=60.0,
                   help="--follow only: seconds to wait for the live "
                        "session's server / first publish")
    e.set_defaults(fn=run_eval)

    a = sub.add_parser("actor", help="standalone actor against a live "
                       "training session's parameter server")
    a.add_argument("--folder", required=True,
                   help="the live session's folder (config.json + "
                        "param_server.json discovery)")
    a.add_argument("--connect", default=None,
                   help="parameter-server address (default: discover via "
                        "<folder>/param_server.json)")
    a.add_argument("--episodes", type=int, default=10)
    a.add_argument("--fetch-every", type=int, default=100,
                   help="re-fetch params every K acts (reference agents' "
                        "periodic fetch)")
    a.add_argument("--min-version", type=int, default=1,
                   help="block until the published version reaches this "
                        "before acting (wait out warmup/compiles)")
    a.add_argument("--mode", choices=("training", "deterministic", "stochastic"),
                   default="training")
    a.add_argument("--num-envs", type=int, default=None,
                   help="actor batch width (default: the session's "
                        "env_config.num_envs)")
    a.add_argument("--max-steps", type=int, default=None,
                   help="total act-step cap across the batch (safety stop)")
    a.add_argument("--video-dir", default=None,
                   help="record episodes (host envs) via VideoWrapper")
    a.add_argument("--wait", type=float, default=60.0,
                   help="seconds to wait for the live session's config/"
                        "server/first publish")
    a.add_argument("--seed", type=int, default=0)
    a.set_defaults(fn=run_actor)

    p = sub.add_parser("profile", help="ask a LIVE session for an "
                       "on-demand jax.profiler capture (writes "
                       "<folder>/profile.trigger; the capture lands under "
                       "<folder>/telemetry/profiles/)")
    p.add_argument("folder", help="the live session's folder")
    p.add_argument("--iters", type=int, default=None,
                   help="capture window length in iterations (default: "
                        "the session's session_config.profile.num_iters)")
    p.set_defaults(fn=run_profile)

    d = sub.add_parser("diag", help="offline session diagnosis from the "
                       "telemetry JSONL log: phase times, health summary, "
                       "heartbeats (works off-chip and on live sessions)")
    d.add_argument("folder", help="session folder (holds telemetry/)")
    d.add_argument("--json", action="store_true",
                   help="print the aggregated summary as one JSON object "
                        "instead of the human-readable report")
    d.set_defaults(fn=run_diag)

    tp = sub.add_parser("top", help="live cross-tier ops view from the "
                        "run's merged snapshot (telemetry/"
                        "ops_snapshot.json): tier health, per-tenant "
                        "SLO/error-budget table, hop latencies, MFU")
    tp.add_argument("folder", help="the live session's folder")
    tp.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (scripts/tests)")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (default 2)")
    tp.set_defaults(fn=run_top)

    tr = sub.add_parser("trace", help="causal span-tree timelines for "
                        "the head-sampled exemplars (gateway act -> "
                        "replica forward -> learner dispatch), from the "
                        "telemetry event log; torn hops marked")
    tr.add_argument("folder", help="session folder (holds telemetry/)")
    tr.add_argument("--limit", type=int, default=16,
                    help="newest exemplars to render (default 16)")
    tr.set_defaults(fn=run_trace)

    w = sub.add_parser("why", help="root-caused incident reports from "
                       "the watchdog (what fired, ranked cause "
                       "hypotheses, correlated faults/SLO breaches/"
                       "exemplars, auto-captured artifacts, remediation "
                       "actions with counter-detector verdicts)")
    w.add_argument("folder", help="session folder (holds telemetry/)")
    w.add_argument("--incident", type=int, default=None,
                   help="render one incident in full detail (default: "
                   "all, newest last)")
    w.set_defaults(fn=run_why)

    c = sub.add_parser("chaos", help="randomized chaos campaign: N "
                       "seeded multi-site fault schedules run as short "
                       "real training sessions, judged by the run-wide "
                       "invariant oracles (chaos/invariants.py); "
                       "failing schedules are shrunk to minimal "
                       "reproducers")
    c.add_argument("algo", choices=("impala", "ddpg", "all"),
                   help="which campaign profiles to run (profile algo "
                   "family; 'all' interleaves every profile)")
    c.add_argument("env", nargs="?", default="default",
                   help="env name override for every profile "
                   "(default: each profile's own env)")
    c.add_argument("--seeds", type=int, default=25,
                   help="number of seeded schedules (seed i -> "
                   "profile i %% len(profiles); intensity ramps with "
                   "seed %% 3)")
    c.add_argument("--out", default=None,
                   help="write the campaign artifact JSON here")
    c.add_argument("--dir", default=None,
                   help="scratch dir for the runs' session folders "
                   "(default: a fresh temp dir)")
    c.add_argument("--max-shrink-runs", type=int, default=12,
                   help="re-run budget per failing schedule for the "
                   "greedy shrinker")
    c.set_defaults(fn=run_chaos)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # the --local-procs supervisor re-issues this exact command per rank
    args.raw_argv = list(sys.argv[1:] if argv is None else argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
