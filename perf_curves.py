"""Direct block-vs-row shuffle validation at the REAL headline geometry
(round-5 VERDICT weak #3: the 13x block-shuffle win was backed by a
degenerate single-minibatch equivalence test plus thresholded learning
tests; this runs the actual A/B).

Trains the headline workload (PPO+MLP on ``jax:lift``, 4096 envs x 256
horizon, 4 epochs x 4 minibatches) under ``algo.shuffle='block'`` (the
TPU default) and ``'row'`` (exact reference semantics: per-epoch row
reshuffle) for N_ITERS iterations x 3 seeds each, recording the
episode-return curve. Writes ``block_vs_row.json``; perf_report.py
renders the comparison into PERF.md from that artifact, so the (slow,
chip-bound) measurement survives PERF.md regens.

Usage: python perf_curves.py [--iters 150] [--seeds 3]
"""

from __future__ import annotations

import json
import time


N_ITERS = 150
SAMPLE_EVERY = 5
TAIL_SAMPLES = 5  # final-performance estimate = mean of the last 5 samples


def run_one(mode: str, seed: int, n_iters: int):
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=256, epochs=4,
                        num_minibatches=4, shuffle=mode),
        ),
        env_config=Config(name="jax:lift", num_envs=4096),
        session_config=Config(
            folder=f"/tmp/curves_{mode}_{seed}",
            seed=seed,
            total_env_steps=10**12,
            # cadence = the sampling stride: every_n_iters=1 would sync
            # the host every iteration for samples on_m would discard
            metrics=Config(every_n_iters=SAMPLE_EVERY, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    trainer = Trainer(cfg)
    curve = []
    t0 = time.perf_counter()

    def on_m(it, m):
        r = m.get("episode/return")
        if it % SAMPLE_EVERY == 0 and r is not None and r == r:
            curve.append({"iteration": it, "return": float(r)})
        return it >= n_iters

    trainer.run(on_metrics=on_m)
    out = {
        "mode": mode,
        "seed": seed,
        "wall_s": time.perf_counter() - t0,
        "curve": curve,
    }
    print(json.dumps({k: v for k, v in out.items() if k != "curve"}
                     | {"final_return": curve[-1]["return"] if curve else None},
                     default=float), flush=True)
    return out


def main(argv=None) -> None:
    import sys

    argv = sys.argv[1:] if argv is None else argv
    n_iters = N_ITERS
    n_seeds = 3
    if "--iters" in argv:
        n_iters = int(argv[argv.index("--iters") + 1])
    if "--seeds" in argv:
        n_seeds = int(argv[argv.index("--seeds") + 1])

    runs = []
    # interleave modes so any slow drift of the machine hits both arms equally
    for seed in range(n_seeds):
        for mode in ("block", "row"):
            runs.append(run_one(mode, seed, n_iters))

    def mode_stats(mode):
        import statistics

        # tail MEAN over the last few sampled iterations, not the single
        # final point: episode/return is a per-iteration mean over only
        # the episodes that finished in that iteration, so one-iteration
        # point estimates carry episode noise straight into the verdict
        finals = [
            statistics.fmean(p["return"] for p in r["curve"][-TAIL_SAMPLES:])
            for r in runs
            if r["mode"] == mode and r["curve"]
        ]
        finals.sort()
        return {
            "final_returns": finals,
            "final_median": statistics.median(finals) if finals else None,
        }

    summary = {
        "geometry": "jax:lift 4096x256, 4 epochs x 4 minibatches",
        "n_iters": n_iters,
        "block": mode_stats("block"),
        "row": mode_stats("row"),
    }
    with open("block_vs_row.json", "w") as f:
        json.dump({"summary": summary, "runs": runs}, f, indent=2,
                  default=float)
    print(json.dumps(summary, indent=2, default=float))


if __name__ == "__main__":
    main()
