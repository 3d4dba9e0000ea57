"""The measured run of one cell.

The cell's config is built the way the CLI builds it
(``main/launch.py``: parser -> ``build_config`` -> ``_apply_backend`` ->
``_require_platform`` -> ``select_trainer``) and trained through the
driver's own ``run(on_metrics=...)``: the loop engine, the session hooks,
the cadence sync and the telemetry a user pays for are all inside the
number. ``on_metrics`` fires after the cadence's one device->host sync, so
every call is a fenced point on the host clock:

    process start --launch--> first stamp --warm-up, until the ring is
    full--> window start ... --seconds--> last stamp.

Once the window has closed the peaks are read, the trainer's buffers are
freed, and only then the configuration's reference check runs: it is no part
of ``setup_s`` and nothing of it is on the device beside the measured session.

Nothing here calls into a trainer's private step.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import time
from dataclasses import dataclass, field

from benchmarks.harness import manifest

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 1.0  # a traced run captures whole cadence windows for this long

# one traffic parameter -> the dotlist key it sets (``num_envs`` goes
# through the CLI's own --num-envs). A cell's ``overrides`` list carries
# whatever has no name here.
TRAFFIC_KEYS = {
    "horizon": "learner_config.algo.horizon",
    "epochs": "learner_config.algo.epochs",
    "num_minibatches": "learner_config.algo.num_minibatches",
    "updates_per_iter": "learner_config.algo.updates_per_iter",
    "batch_size": "learner_config.replay.batch_size",
    "replay_kind": "learner_config.replay.kind",
    "replay_capacity": "learner_config.replay.capacity",
    "priority_alpha": "learner_config.replay.priority_alpha",
    "priority_beta0": "learner_config.replay.priority_beta0",
    "mesh_dp": "session_config.topology.mesh.dp",
}

# metrics read at every cadence, nothing else written or evaluated: the
# side-bands (checkpoint every 500, eval every 100) belong to a cell of
# their own (PERF.md, open questions)
QUIET = [
    "session_config.metrics.tensorboard=false",
    "session_config.metrics.console=false",
    "session_config.checkpoint.every_n_iters=0",
    "session_config.eval.every_n_iters=0",
]


class NoAccelerator(Exception):
    """JAX found no TPU of a known kind, or not the chips the cell asks."""


@dataclass
class Stamp:
    t: float
    iteration: int
    env_steps: int
    row: dict


@dataclass
class Run:
    """Everything one run recorded; what a per-layer metric reader sees."""

    cell: dict
    config: dict
    seed: int                      # the session's seed (session_seed)
    seconds: float
    trace: bool
    rehearse: bool
    t0: float                      # process start (top of run.py)
    cost: dict = field(default_factory=dict)       # the reference's iteration_cost
    peaks: dict = field(default_factory=dict)      # this device's peaks
    device: dict = field(default_factory=dict)
    stamps: list = field(default_factory=list)     # every cadence stamp
    window: list = field(default_factory=list)     # stamps inside the window
    launch_s: float = math.nan
    setup_s: float = math.nan
    window_t0: float = math.nan
    window_steps0: int = 0
    window_iter0: int = 0
    compiles: list = field(default_factory=list)   # (t, seconds) per backend compile
    cache: dict = field(default_factory=dict)      # persistent-cache hits/misses
    events: dict = field(default_factory=dict)     # the run's telemetry, by type
    reference: dict = field(default_factory=dict)  # the reference check's record
    reduced: dict | None = None                    # trace_reduce.reduce_file
    live_bytes: int = 0            # allocator bytes in use at the window start
    program_temp_bytes: int = 0    # largest temporaries of a program of the launch
    memory: dict = field(default_factory=dict)     # the peak and its two parts
    marks: dict = field(default_factory=dict)      # seconds since t0 along the launch
    folder: str = ""
    asked_seed: int = 0            # --seed as given

    @property
    def traffic(self) -> dict:
        return self.cell["traffic"]

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - self.t0

    # -- numbers several readers share ---------------------------------------
    def window_seconds(self) -> float:
        return self.window[-1].t - self.window_t0

    def window_steps(self) -> int:
        return self.window[-1].env_steps - self.window_steps0

    def window_iterations(self) -> int:
        return self.window[-1].iteration - self.window_iter0

    def cadence_seconds(self) -> list[float]:
        """Length of each cadence window inside the measured window, per
        iteration of it."""
        ts = [(self.window_t0, self.window_iter0)] + [
            (s.t, s.iteration) for s in self.window
        ]
        return [
            (b[0] - a[0]) / (b[1] - a[1]) for a, b in zip(ts, ts[1:])
        ]

    def iteration_seconds(self) -> float:
        """Median seconds per iteration over the window's cadence windows
        (the profiler's start and stop stretch one or two, not the median)."""
        per_iter = sorted(self.cadence_seconds())
        return per_iter[len(per_iter) // 2]

    def engine_p50(self, key: str) -> float | None:
        """Median of ``step_ms`` or ``stage_ms`` in the run's last
        ``engine`` telemetry event."""
        events = self.events.get("engine", [])
        percentiles = events[-1].get(key) if events else None
        return float(percentiles["p50"]) if percentiles else None

    def compiles_in_window(self) -> int:
        end = self.window[-1].t
        return sum(1 for t, _ in self.compiles if self.window_t0 < t <= end)


def sized(cell: dict, rehearse: bool) -> dict:
    """The cell as it runs: in rehearsal its toy traffic, extra overrides
    and learning mark replace the real ones."""
    if not rehearse:
        return cell
    toy = cell["rehearse"]
    return dict(
        cell,
        traffic=dict(cell["traffic"], **toy["traffic"]),
        overrides=list(cell["overrides"]) + list(toy["overrides"]),
        learning=toy["learning"],
    )


def session_seed(cell: dict, seed: int) -> int:
    """The seed the session, the reference check and the phase session take
    for ``--seed``. A cell whose program refuses some initialisations lists
    the ``session_seeds`` it is known to launch from (its workload file says
    why), and ``--seed`` picks among them; every other cell takes ``--seed``
    itself."""
    listed = cell.get("session_seeds")
    return int(listed[int(seed) % len(listed)]) if listed else int(seed)


def train_argv(config: dict, cell: dict, folder: str, seed: int,
               backend_cpu: bool) -> list[str]:
    """The ``surreal_tpu train`` command line this cell stands for."""
    traffic = cell["traffic"]
    sets = list(config["overrides"])
    sets += [
        f"{TRAFFIC_KEYS[k]}={json.dumps(v)}"
        for k, v in traffic.items() if k in TRAFFIC_KEYS
    ]
    sets += list(cell["overrides"])
    sets += QUIET + [f"session_config.seed={int(seed)}"]
    if backend_cpu:
        sets.append("session_config.backend=cpu")
    return [
        "train", config["algo"], config["env"], "--folder", folder,
        "--num-envs", str(int(traffic["num_envs"])),
        # the callback ends the run; the budget only has to outlast it
        # (and keeps prioritized replay's beta anneal at beta0)
        "--total-steps", str(10**15),
        "--set", *sets,
    ]


def read_events(folder: str) -> dict:
    """The run's own telemetry log, grouped by event type."""
    from surreal_tpu.session.telemetry import EVENTS_FILE, TELEMETRY_DIR

    out: dict[str, list] = {}
    path = os.path.join(folder, TELEMETRY_DIR, EVENTS_FILE)
    if not os.path.isfile(path):
        return out
    with open(path) as fh:
        for raw in fh:
            rec = json.loads(raw)
            out.setdefault(rec.get("type", "?"), []).append(rec)
    return out


def resolve_device(run: Run) -> None:
    """Hold what JAX resolved to the cell: a TPU whose kind has published
    peaks, and the cell's chip count. A rehearsal takes the CPU, by name."""
    import jax

    devices = jax.devices()
    run.device = {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
    }
    if run.device["count"] != run.cell["chips"]:
        raise NoAccelerator(
            f"cell {run.cell['name']} needs {run.cell['chips']} chip(s), "
            f"JAX found {run.device}"
        )
    if run.rehearse:
        if run.device["platform"] != "cpu":
            raise NoAccelerator("--rehearse is for the CPU; found a chip")
        return
    peaks = manifest.load_peaks()
    if run.device["platform"] != "tpu" or run.device["kind"] not in peaks:
        raise NoAccelerator(
            f"no TPU with published peaks: JAX found {run.device}; the "
            f"table has {sorted(peaks)}"
        )
    run.peaks = peaks[run.device["kind"]]


def bytes_in_use(key: str) -> list[int]:
    """One allocator statistic per local device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    return [
        int((d.memory_stats() or {}).get(key, 0)) for d in jax.local_devices()
    ]


def program_temp_bytes() -> int:
    """The largest temporary allocation among the executables the process
    has loaded, per device, as the runtime itself records it for each
    (``get_compiled_memory_stats``): nothing of the program's telemetry."""
    import jax.extend

    return max(
        (int(ex.get_compiled_memory_stats().temp_size_in_bytes)
         for ex in jax.extend.backend.get_backend().live_executables()),
        default=0,
    )


def memory_parts(run: Run) -> dict:
    """Two measured numbers, kept apart, and the line's
    ``memory_peak_bytes`` made of them. The TPU allocator's peak counts
    the buffers the process holds (state, env carry, replay ring) and not
    the scratch a running program takes (PERF.md section 6: 0.06 GB under
    a fused PPO iteration whose program has 5.9 GB of temporaries), so the
    peak on the fullest chip is the larger of the allocator's own and the
    live buffers at the window's fenced start plus the training program's
    temporaries."""
    allocator = max(bytes_in_use("peak_bytes_in_use"))
    return {
        "allocator_peak_bytes": allocator,
        "program_temp_bytes": run.program_temp_bytes,
        "live_bytes_at_window_start": run.live_bytes,
        "bytes_limit": max(bytes_in_use("bytes_limit")),
        "memory_peak_bytes": max(
            allocator, run.live_bytes + run.program_temp_bytes
        ),
    }


def ring_full(row: dict) -> bool:
    """Whether the replay ring a deployment holds is the ring measured: a
    row without ``replay/fill`` has no ring to fill."""
    return row.get("replay/fill", 1.0) >= 1.0


class Window:
    """The ``on_metrics`` callback: stamps every fenced point and walks
    launch -> warm-up -> window; returns truthy to end the run."""

    def __init__(self, run: Run, trace_dir: str | None):
        self.run = run
        self.trace_dir = trace_dir
        self.phase = "launch"
        self.tracing = False
        self.trace_span = None  # (t_start, t_stop) on the host clock

    def __call__(self, iteration: int, row: dict) -> bool:
        run = self.run
        now = time.perf_counter()
        stamp = Stamp(now, int(iteration), int(row["time/env_steps"]), dict(row))
        run.stamps.append(stamp)
        if self.phase == "launch":
            run.launch_s = run.marks["first_stamp"] = now - run.t0
            # every program of the launch is loaded and none of the
            # harness's own (the reference's, the phase session's) is yet:
            # those run once the window has closed
            run.program_temp_bytes = program_temp_bytes()
            self.phase = "warmup"
            return False
        if self.phase == "warmup":
            # one cadence window after the launch at least; more until the
            # ring is full
            if not ring_full(row):
                return False
            run.mark("warm")
            self.phase = "window"
            # the device is idle here (the cadence sync has returned and
            # nothing is dispatched until this returns): a fenced start
            run.live_bytes = max(bytes_in_use("bytes_in_use"))
            run.window_t0 = time.perf_counter()
            run.window_steps0, run.window_iter0 = stamp.env_steps, stamp.iteration
            run.setup_s = run.window_t0 - run.t0
            return False
        run.window.append(stamp)
        if self.trace_dir is not None:
            self._trace_step()
        if self.tracing:
            return False
        return now - run.window_t0 >= run.seconds

    def _trace_step(self) -> None:
        """Trace whole cadence windows, from the first stamp of the window
        on, until ``TRACE_SECONDS`` have passed; start and stop at fenced
        points."""
        import jax

        if not self.tracing and self.trace_span is None:
            # no Python tracer: it slows the host loop it would observe
            # and fills the trace with frames no reduction here reads
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.tracing = True
            self.trace_span = (time.perf_counter(), None)
        elif self.tracing:
            t_stop = time.perf_counter()
            if t_stop - self.trace_span[0] >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                self.tracing = False
                self.trace_span = (self.trace_span[0], t_stop)


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            rehearse: bool, t0: float) -> Run:
    """Run one cell once and return its record."""
    cell = sized(manifest.load_cell(cell_name), rehearse)
    config = manifest.load_config(cell["config"])
    asked_seed, seed = int(seed), session_seed(cell, seed)
    run = Run(cell=cell, config=config, seed=seed, seconds=seconds,
              trace=trace, rehearse=rehearse, t0=t0, asked_seed=asked_seed)
    reference = manifest.load_reference(config["reference"])
    run.cost = reference.iteration_cost(config, cell["traffic"])

    run.mark("harness_loaded")
    import jax

    run.mark("jax_imported")
    from surreal_tpu.main import launch
    from surreal_tpu.utils.compat import compile_cache_counts

    run.mark("program_imported")

    run.folder = os.path.join(
        manifest.ROOT, "chiprun_out", "benchmarks", cell_name
        + ("_rehearse" if rehearse else "") + ("_trace" if trace else ""),
    )
    shutil.rmtree(run.folder, ignore_errors=True)  # a fresh session: no resume
    argv = train_argv(config, cell, run.folder, seed, rehearse)
    cli = launch.build_parser().parse_args(argv)
    cfg = launch.build_config(cli)
    launch._apply_backend(cfg.session_config.backend)
    launch._require_platform(cfg.session_config.backend)
    resolve_device(run)
    run.mark("device_resolved")

    def on_compile(event, duration, **_kw):
        if event == COMPILE_EVENT:
            run.compiles.append((time.perf_counter(), float(duration)))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    cache_before = compile_cache_counts()
    try:
        trainer = launch.select_trainer(cfg)
        run.mark("trainer_built")

        trace_dir = os.path.join(run.folder, "trace") if trace else None
        window = Window(run, trace_dir)
        trainer.run(on_metrics=window)
        if window.tracing:  # the run ended under the trace: a bug, not a result
            jax.profiler.stop_trace()
            raise RuntimeError("run ended while the profiler was tracing")
        run.events = read_events(run.folder)
        run.memory = memory_parts(run)
        if trace:
            from benchmarks.harness import trace_reduce

            run.reduced = trace_reduce.reduce_dir(trace_dir, host_stand_in=rehearse)
            run.reduced["host_span_s"] = window.trace_span[1] - window.trace_span[0]
            shutil.rmtree(trace_dir)  # tens of MB a run; the numbers are kept
        # the window has closed and the peaks are read: the session's buffers
        # go before the reference check builds its own learner, and before
        # the phase session (harness/phase_session.py) trains a second
        # session when a reader first asks
        del trainer, window
        gc.collect()
        run.mark("session_freed")
        run.memory["bytes_in_use_after_session"] = max(bytes_in_use("bytes_in_use"))
        run.reference = reference.check(cfg, run)
        run.mark("reference_checked")
        run.cache = {
            k: compile_cache_counts()[k] - cache_before[k]
            for k in ("hits", "misses")
        }
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    return run

