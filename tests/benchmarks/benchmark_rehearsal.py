"""What a cell's rehearsal file runs. Every cell walks the whole measured
path at toy sizes on the CPU, in a child process (the command is a process
of its own; the four-chip cell needs four virtual devices where this suite
forces eight), and prints the contract's last line.

One test file a cell, ``test_benchmark_rehearse_<cell>.py``, which names its
cell and takes everything else from here:

    from benchmark_rehearsal import *  # noqa: F401,F403

    CELL = "<cell>"

so the driver's ``--dist loadfile`` spreads the cells over its workers, each
file keeps a compile cache of its own module, and a PR that adds a cell adds
one such file and edits none (``test_benchmark_command.py`` holds the files
to the manifest's workloads, by name).
"""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

__all__ = [
    "cell", "cache_dir", "rehearsed",
    "test_cell_rehearses", "test_traced_line_has_only_what_the_sessions_gave",
    "test_reference_check_follows_the_window",
    "test_session_seed_is_listed_or_the_seed_itself",
]

M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
HERE = os.path.dirname(os.path.abspath(__file__))
FILE_OF_CELL = re.compile(r"^test_benchmark_rehearse_(.+)\.py$")


@pytest.fixture(scope="module")
def cell(request):
    """The cell the importing file names, which its own name repeats."""
    return request.module.CELL


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """A compile cache of this module's own: a cell's traced run finds the
    programs its untraced run compiled, which halves the module's time, and
    nothing outlives the module (the suite itself runs with the cache off)."""
    return str(tmp_path_factory.mktemp("rehearse_jax_cache"))


@pytest.fixture(scope="module")
def rehearsed(cell, cache_dir):
    """``trace ->`` the rehearsal's last line, each rehearsed once in the
    module: ``test_cell_rehearses`` fills it and the tests after it read
    what it left (a test run alone rehearses its own trace flag)."""
    done: dict = {}

    def get(trace: int):
        if trace not in done:
            proc = run_cell(cell, trace, "--rehearse", cache=cache_dir)
            assert proc.returncode == 3, proc.stderr[-2000:]
            done[trace] = dict(
                last_line(proc), stderr_tail=proc.stderr.splitlines()[-60:]
            )
        return done[trace]

    return get


def rehearsal_files() -> dict:
    """``cell -> file name`` for every rehearsal file beside this module."""
    matches = (FILE_OF_CELL.match(name) for name in sorted(os.listdir(HERE)))
    return {m.group(1): m.group(0) for m in matches if m}


def worker_core() -> int:
    """The core a child of this process pins itself to: under xdist the
    worker's own (``gw3`` takes the fourth core this process may run on, so
    six files that rehearse at once do not share one), without it the last."""
    cores = sorted(os.sched_getaffinity(0))
    worker = re.fullmatch(r"gw(\d+)", os.environ.get("PYTEST_XDIST_WORKER", ""))
    return cores[int(worker.group(1)) % len(cores)] if worker else cores[-1]


# The child pins itself to one core and then becomes the command: the suite
# runs one worker per core and holds timing-sensitive tests, which a child
# that spread XLA's thread pool over every core would slow.
ON_ONE_CORE = (
    "import os, sys; os.sched_setaffinity(0, {{{core}}}); "
    "os.execv(sys.executable, [sys.executable] + sys.argv[1:])"
)


def run_cell(cell: str, trace: int, *extra: str, cache: str | None = None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache is not None:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
        env["JAX_COMPILATION_CACHE_DIR"] = cache
    return subprocess.run(
        [sys.executable, "-c", ON_ONE_CORE.format(core=worker_core()),
         *M["command"][1:], "--workload", cell, "--seed", "7",
         "--seconds", "1", "--trace", str(trace), *extra],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


def last_line(proc: subprocess.CompletedProcess) -> dict:
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


def declared_and_required(cell: str, trace: int):
    """The manifest's entries, by name, for the metrics this cell's line may
    carry, and the names it must: some readers need the chip (its published
    peak, its allocator) and may find nothing to read in a rehearsal."""
    if not trace:
        declared = {m["name"]: m for m in manifest.metrics_of("end_to_end", cell)}
        return declared, set(declared)
    declared = {m["name"]: m for m in manifest.metrics_of("per_layer", cell)}
    return declared, {
        name for name in declared
        if not getattr(manifest.load_layer_metric(name), "CHIP_ONLY", False)
    }


@pytest.mark.parametrize("trace", (0, 1), ids=("trace0", "trace1"))
def test_cell_rehearses(cell, trace, rehearsed):
    line = rehearsed(trace)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is False
    chips = next(w["chips"] for w in M["workloads"] if w["name"] == cell)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    # every check but the one that needs the chip holds at toy sizes too
    failed = [k for k, ok in line["checks"].items() if not ok]
    assert failed == ["device_is_tpu_in_peak_table"], line["checks"]
    assert line["compiles_in_window"] == 0
    declared, required = declared_and_required(cell, trace)
    assert required <= set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        assert isinstance(got["value"], float) and got["value"] == got["value"]
    if trace:
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            assert len(line["breakdown"][key]) <= 10
    else:
        assert line["metrics"]["setup_s"]["value"] >= line["launch_marks_s"]["first_stamp"]


def test_traced_line_has_only_what_the_sessions_gave(cell, rehearsed):
    """A traced run reads its per-layer metrics from the measured session
    and the phase session and times nothing as a program of its own: the
    line has no ``standalone_s``, and its metrics are the cell's declared
    per-layer ones (all that need no chip, none from outside the list)."""
    line = rehearsed(1)
    assert "standalone_s" not in line
    declared, required = declared_and_required(cell, 1)
    assert required <= set(line["metrics"]) <= set(declared)


def test_reference_check_follows_the_window(cell, rehearsed):
    """The reference check is no part of ``setup_s`` and runs beside nothing
    of the measured session: the window closes, the session is freed, then
    the check runs; and the run's last lines on standard error are every
    number compared beside its limit, every check by name, and the verdict."""
    line = rehearsed(0)
    marks = line["launch_marks_s"]
    setup = line["metrics"]["setup_s"]["value"]
    assert marks["warm"] <= setup
    assert setup + line["window_s"] <= marks["session_freed"]
    assert marks["session_freed"] <= marks["reference_checked"]
    assert "bytes_in_use_after_session" in line["memory"]
    assert line["reference"]["ok"] is True
    tail = line["stderr_tail"]
    assert tail[-1].startswith("correct: False")
    said = "\n".join(tail)
    for name in line["reference"]["comparisons"]:
        assert f"compared {name}: " in said
    for name in line["checks"]:
        assert f"check {name}: " in said


def test_session_seed_is_listed_or_the_seed_itself(cell):
    """A cell that lists ``session_seeds`` (and says why) launches from one
    of them whatever ``--seed`` is, the same one for the same seed; every
    other cell launches from ``--seed`` itself."""
    from benchmarks.harness import runner

    loaded = manifest.load_cell(cell)
    listed = loaded.get("session_seeds")
    for seed in (0, 7, 31337, 2**31 - 1, 2**31, 2**31 + 123456):
        got = runner.session_seed(loaded, seed)
        assert got == runner.session_seed(runner.sized(loaded, True), seed)
        if listed is None:
            assert got == seed
        else:
            assert got in listed and 0 <= got < 2**31 + 2**20
    if listed is not None:
        assert loaded["session_seeds_why"]
        assert len(set(listed)) == len(listed) >= 12
        picked = {runner.session_seed(loaded, s) for s in range(len(listed))}
        assert picked == set(listed)
