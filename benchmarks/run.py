"""Run one cell of BENCHMARK.json once and print its result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Without a TPU of a known kind and the cell's chip count the
command exits non-zero and prints no result. ``--rehearse`` walks the same
path at toy sizes on the CPU (virtual devices for a four-chip cell): its
line names the CPU, is never ``correct``, and the exit code is 3.
See benchmarks/README.md.
"""

import time

T0 = time.perf_counter()  # process start, before JAX or the program loads

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REHEARSAL_EXIT = 3


def result_line(run) -> dict:
    """The contract's last line from one run's record."""
    from benchmarks.harness import checks, manifest

    cell = run.cell["name"]
    verdicts = checks.evaluate(run)
    metrics: dict = {}
    if run.trace:
        for entry in manifest.metrics_of("per_layer", cell):
            value = manifest.load_layer_metric(entry["name"]).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        values = {
            "env_steps_per_s": run.window_steps() / run.window_seconds(),
            "setup_s": run.setup_s,
        }
        for entry in manifest.metrics_of("end_to_end", cell):
            metrics[entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"],
            }
    # the peak, and beside it the two measured numbers it is made of
    device = dict(run.device, **{
        k: run.memory[k] for k in (
            "memory_peak_bytes", "allocator_peak_bytes", "program_temp_bytes",
        )
    })
    line = {
        "correct": all(verdicts.values()) and not run.rehearse,
        "attempted": len(run.window),
        "failed": checks.bad_rows(run.window),
        "metrics": metrics,
        "device": device,
        "checks": verdicts,
        "reference": run.reference,
        "learning": {
            "first_return": checks.first_return(run),
            "return_at_mark": checks.return_at_mark(run),
        },
        "cell": cell,
        "seed": run.asked_seed,
        "session_seed": run.seed,
        "window_s": run.window_seconds(),
        "iterations": run.window_iterations(),
        "cadence_s_per_iteration": run.cadence_seconds(),
        "compiles_in_window": run.compiles_in_window(),
        "cache": run.cache,
        "rehearsal": run.rehearse,
        "memory": run.memory,
        "launch_marks_s": run.marks,
    }
    if run.trace:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        line["breakdown"] = {
            "device_ops": run.reduced["device_ops"],
            "idle_gaps": run.reduced["idle_gaps"],
        }
        line["trace"] = {
            k: run.reduced[k] for k in (
                "devices", "busy_s_per_device", "collective_s",
                "collective_exposed_s", "collective_calls", "op_events",
                "host_span_s",
            )
        }
    return line


def print_comparisons(line: dict) -> None:
    """Every number ``correct`` was decided from beside its limit, one to a
    line, as the last lines of standard error: what the driver's record
    keeps of a run that is not correct."""
    for name, entry in line["reference"].get("comparisons", {}).items():
        print(f"compared {name}: {json.dumps(entry, default=float)}",
              file=sys.stderr)
    for name, ok in line["checks"].items():
        print(f"check {name}: {'ok' if ok else 'FALSE'}", file=sys.stderr)
    print(f"correct: {line['correct']} (failed rows {line['failed']} of "
          f"{line['attempted']})", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import manifest

    cell = manifest.load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        seconds = float(manifest.load_manifest()["run_seconds"])
    if args.rehearse:
        # before JAX loads: the CPU, with as many devices as the cell has chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={cell['chips']}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    from benchmarks.harness import runner

    try:
        run = runner.execute(
            args.workload, args.seed, seconds, bool(args.trace),
            args.rehearse, T0,
        )
    except runner.NoAccelerator as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    line = result_line(run)
    print_comparisons(line)
    print(json.dumps(line, default=float), flush=True)
    return REHEARSAL_EXIT if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
