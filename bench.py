"""Headline benchmark: env steps/sec/chip for fused on-device PPO on the
BlockLifting-class workload (the graded metric: BASELINE.json defines
"Robosuite env steps/sec/chip" on BlockLifting state-obs PPO; the
``jax:lift`` env is this repo's TPU-native BlockLifting — see
surreal_tpu/envs/jax/lift.py for the robosuite/MJX-availability note).

Workload: PPO with a large vmapped env batch — rollout + GAE + minibatched
SGD all in one compiled program per iteration. The steps counted are real
policy-driven env steps inside the training loop, not a bare env-step
microbenchmark.

Timing: a CHAINED loop (each iteration consumes the previous state)
after a compile warm-up, fenced by ``jax.block_until_ready`` on the last
iteration's outputs. Sanity checks worth repeating after any change here:
time grows linearly in the iteration count, and the implied FLOP/s stays
below the chip's peak. MFU is reported against the published peak of the
device JAX reports (session/costs.py::PEAK_SPECS); a device without one —
the CPU included — is refused before anything is measured, and any
failure exits non-zero. No number measured on the current code is
recorded here: PERF.md holds what has been measured, and by whom.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline is value / 100_000 — the north-star ">=100k env steps/sec/chip"
from BASELINE.json (the reference itself published no numbers; SURVEY.md §6).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

# Headline geometry (also chip_smoke.py's PPO phase and the compile test
# of tests/test_tpu_compile.py). The sweep that chose it predates the
# current code and machine: to re-measure.
NUM_ENVS = 4096
HORIZON = 256
WARMUP_ITERS = 2
MEASURE_ITERS = 10
NORTH_STAR = 100_000.0
# program autotuner arm (surreal_tpu/tune/): --autotune cache|search and
# --tuning-cache DIR select it; the artifact ALWAYS records the active
# decision so a record can't silently mix tuned and untuned arms
AUTOTUNE = "off"
TUNING_CACHE_DIR = None
# precision-policy arm (surreal_tpu/ops/precision.py): --precision
# f32|mixed|bf16|bf16_fp8 selects the policy the measured program runs
# under; the row records it (plus per-iteration FLOPs / bytes accessed
# from the PR-6 cost accountant) so policy arms can never silently mix.
# --sweep-precision measures the listed arms back-to-back into one
# artifact ({"parsed": <headline arm>, "precision": {...}}), and
# --cost-only skips the timed window (cost model only — how the TRUE
# headline geometry gets per-policy bytes rows on hosts too slow to time
# it).
PRECISION = "mixed"


def _iter_costs(jitted, *args) -> dict | None:
    """Per-iteration FLOPs + bytes accessed from the PR-6 cost
    accountant's path (``lower().cost_analysis()`` — host-side trace +
    HLO cost pass, no compile; the same numbers the driver's
    ``program_cost`` telemetry events record); None when the backend
    reports nothing."""
    from surreal_tpu.session.costs import program_costs

    return program_costs(jitted, *args)


def _iter_flops(jitted, *args) -> float | None:
    """FLOPs-only view of :func:`_iter_costs` (perf_report.py's
    attribution harnesses import this)."""
    costs = _iter_costs(jitted, *args)
    return costs["flops"] if costs else None


def _measure(
    precision: str | None = None,
    num_envs: int | None = None,
    horizon: int | None = None,
    iters: int | None = None,
    cost_only: bool = False,
) -> dict:
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    from surreal_tpu.session.costs import published_peak

    device = jax.devices()[0]
    peak_flops, _ = published_peak(str(device.device_kind))  # raises off-chip
    precision = precision or PRECISION
    num_envs = num_envs or NUM_ENVS
    horizon = horizon or HORIZON
    iters = iters or MEASURE_ITERS
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=4,
                        num_minibatches=4, autotune=AUTOTUNE,
                        precision=precision),
        ),
        env_config=Config(name="jax:lift", num_envs=num_envs),
        session_config=Config(
            folder=os.path.join("chiprun_out", "bench_lift"),
            tuning_cache_dir=TUNING_CACHE_DIR,
            metrics=Config(every_n_iters=10_000),  # no host syncs mid-bench
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())

    trainer = Trainer(cfg)
    key = jax.random.key(0)
    key, init_key, env_key = jax.random.split(key, 3)
    state = trainer.learner.init(init_key)
    from surreal_tpu.launch.rollout import init_device_carry

    carry = init_device_carry(trainer.env, env_key, num_envs)

    result = {
        "metric": "env_steps_per_sec_per_chip_ppo_fused_blocklift",
        "unit": "env_steps/s/chip",
        # the device actually measured, as JAX reports it
        "device": str(device.device_kind),
        "platform": str(device.platform),
        # the active autotuner decision (mode, cache hit/miss, applied
        # config): a bench record must never silently mix tuned and
        # untuned arms (surreal_tpu/tune/)
        "tuning": trainer.tune_decision.artifact(),
        # the active precision policy + geometry: policy arms must never
        # silently mix either (ops/precision.py)
        "precision": precision,
        "num_envs": num_envs,
        "horizon": horizon,
    }
    costs = _iter_costs(trainer._train_iter, state, carry, key)
    if costs is not None:
        result["flops_per_iter"] = costs["flops"]
        result["bytes_accessed_per_iter"] = costs["bytes_accessed"]
    if cost_only:
        result["cost_only"] = True
        return result

    # warm-up (compile) -- not measured
    for _ in range(WARMUP_ITERS):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(iters):
        key, it_key = jax.random.split(key)
        state, carry, metrics = trainer._train_iter(state, carry, it_key)
    # the chain makes the last iteration's outputs depend on every
    # earlier one: waiting for them is waiting for the whole window
    jax.block_until_ready((state, carry, metrics))
    dt = time.perf_counter() - t0

    steps = iters * num_envs * horizon
    sps = steps / dt
    result["value"] = round(sps, 1)
    result["vs_baseline"] = round(sps / NORTH_STAR, 3)
    result["iter_ms"] = round(dt / iters * 1e3, 2)
    if costs is not None:
        achieved = costs["flops"] * iters / dt
        result["model_flops_per_s"] = round(achieved, 1)
        result["mfu"] = round(achieved / peak_flops, 6)
    return result


def _sweep_precision(
    num_envs: int | None, horizon: int | None, iters: int | None
) -> dict:
    """The precision-policy campaign (ISSUE 7): time the f32 and bf16
    arms back-to-back at the given geometry, and pull COST-ONLY per-policy
    rows at the true headline geometry (4096x256 — the accountant's
    ``lower().cost_analysis()`` needs no timed window, so the bytes
    comparison stays anchored to the headline workload even on hosts too
    slow to time it). The bf16 arm is the top-level row (what perf_gate's
    cross-round fingerprint sees); the f32 arm and the headline cost rows
    ride under ``precision_sweep`` for the intra-artifact gate."""
    arms = [
        _measure(precision=p, num_envs=num_envs, horizon=horizon, iters=iters)
        for p in ("f32", "mixed", "bf16")
    ]
    headline_costs = [
        _measure(precision=p, cost_only=True)
        for p in ("f32", "mixed", "bf16")
    ]
    headline = dict(arms[-1])  # bf16 is the policy under test
    headline["precision_sweep"] = {
        "arms": arms,
        "headline_costs": headline_costs,
    }
    return headline


def main() -> int:
    """Measure once and print the JSON line; with a campaign flag
    (``--host-path`` … ``--loop-engine``), delegate to that campaign in
    perf_wallclock instead. Nothing is retried and nothing is caught: a
    failure ends the process non-zero with its traceback."""
    from surreal_tpu.utils.compat import enable_compile_cache

    enable_compile_cache()
    if "--host-path" in sys.argv:
        from perf_wallclock import host_path_main

        return host_path_main(sys.argv[1:])
    if "--experience-plane" in sys.argv:
        # sharded experience plane campaign (ISSUE 8): remote shm/tcp/
        # pickle arms vs the in-process replay reference — writes
        # BENCH_experience.json (perf_gate's experience gate consumes it)
        from perf_wallclock import experience_plane_main

        return experience_plane_main(sys.argv[1:])
    if "--act-path" in sys.argv:
        # serving-tier campaign (ISSUE 10): 1 vs N inference replicas +
        # parameter-fanout bytes-per-publish arms — writes BENCH_act.json
        # (perf_gate's act gate consumes it)
        from perf_wallclock import act_path_main

        return act_path_main(sys.argv[1:])
    if "--gateway" in sys.argv:
        # session-gateway campaign (ISSUE 12): attach latency, act RTT
        # through the gateway vs direct-to-fleet, act-cache hit/served
        # split — writes BENCH_gateway.json (perf_gate's gateway gate
        # consumes it)
        from perf_wallclock import gateway_main

        return gateway_main(sys.argv[1:])
    if "--ops-plane" in sys.argv:
        # ops-plane campaign (ISSUE 13): per-cadence tier push +
        # snapshot-build/SLO cost against steady-state iteration time —
        # writes BENCH_ops.json (perf_gate's ops gate consumes it)
        from perf_wallclock import ops_plane_main

        return ops_plane_main(sys.argv[1:])
    if "--trace" in sys.argv:
        # causal tracing + lineage campaign (ISSUE 14): span emit
        # rate/footprint, exact lineage reduction, modeled per-iteration
        # overhead fraction — writes BENCH_trace.json (perf_gate's trace
        # gate consumes it)
        from perf_wallclock import trace_main

        return trace_main(sys.argv[1:])
    if "--watchdog" in sys.argv:
        # watchdog/incident campaign (ISSUE 15): detector sweep +
        # incident-engine observe cost per snapshot, incident-open e2e
        # latency — writes BENCH_watchdog.json (perf_gate's watchdog
        # gate consumes it)
        from perf_wallclock import watchdog_main

        return watchdog_main(sys.argv[1:])
    if "--control" in sys.argv:
        # closed-loop control campaign (ISSUE 16): remediation decision
        # sweep cost, incident -> journaled-action latency, loadgen
        # sustained rate — writes BENCH_control.json (perf_gate's
        # control gate consumes it)
        from perf_wallclock import control_main

        return control_main(sys.argv[1:])
    if "--replay-tiers" in sys.argv:
        # replay-tiers campaign (ISSUE 18): hot-tier sample wait vs the
        # warm shard fan-in, WAL append bytes/step, quantized vs raw
        # cold bytes/transition — writes BENCH_tiers.json (perf_gate's
        # replay-tiers gate consumes it)
        from perf_wallclock import replay_tiers_main

        return replay_tiers_main(sys.argv[1:])
    if "--learner-group" in sys.argv:
        # elastic learner-group campaign (ISSUE 17): M=1 parity vs the
        # single learner, per-M learn arms (in-process fallback + the
        # 8-device-sim all-reduce round) — writes BENCH_lgroup.json +
        # MULTICHIP_r06.json (perf_gate's learner-group gate consumes
        # them)
        from perf_wallclock import learner_group_main

        return learner_group_main(sys.argv[1:])
    if "--chaos" in sys.argv:
        # chaos campaign (ISSUE 20): N seeded short real runs under
        # generated multi-site fault schedules, judged by the invariant
        # oracles, failures shrunk to minimal repros — writes
        # CHAOS_campaign.json (perf_gate's chaos gate consumes it)
        from perf_wallclock import chaos_main

        return chaos_main(sys.argv[1:])
    if "--loop-engine" in sys.argv:
        # loop-engine campaign (ISSUE 19): per-driver iteration time with
        # boundary pipelining off (the legacy inline loop) vs on, plus the
        # off-critical-path fraction the deferral reclaims — writes
        # BENCH_engine.json (perf_gate's engine gate consumes it)
        from perf_wallclock import engine_main

        return engine_main(sys.argv[1:])
    global AUTOTUNE, TUNING_CACHE_DIR, PRECISION
    if "--autotune" in sys.argv:
        AUTOTUNE = sys.argv[sys.argv.index("--autotune") + 1]
    if "--tuning-cache" in sys.argv:
        TUNING_CACHE_DIR = os.path.abspath(
            sys.argv[sys.argv.index("--tuning-cache") + 1]
        )
    if "--precision" in sys.argv:
        PRECISION = sys.argv[sys.argv.index("--precision") + 1]
    arg = lambda name, cast, default: (
        cast(sys.argv[sys.argv.index(name) + 1])
        if name in sys.argv else default
    )
    num_envs = arg("--num-envs", int, None)
    horizon = arg("--horizon", int, None)
    iters = arg("--iters", int, None)
    cost_only = "--cost-only" in sys.argv
    sweep = "--sweep-precision" in sys.argv
    if sweep:
        print(json.dumps(_sweep_precision(num_envs, horizon, iters)))
    else:
        print(json.dumps(_measure(
            num_envs=num_envs, horizon=horizon, iters=iters,
            cost_only=cost_only,
        )))
    return 0


if __name__ == "__main__":
    sys.exit(main())
