"""A launch records its own spans (session/telemetry.py's launch record):
process start to the end of the first ``metrics-sync`` as ten spans of the
program, the compiler's seconds inside each, ONE ``launch`` event a
session. No case has a wall-clock bound: a span's seconds are the
machine's; which spans there are, in what order, that they tile and what
they add up to are the program's.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from surreal_tpu.session import telemetry
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.telemetry import LAUNCH_SPANS, LaunchRecord, Tracer

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

_QUIET = [
    "session_config.metrics.every_n_iters=1",
    "session_config.metrics.tensorboard=false",
    "session_config.metrics.console=false",
    "session_config.checkpoint.every_n_iters=0",
    "session_config.eval.every_n_iters=0",
]
# algo -> (env, num_envs, total steps, --set): toy fused sessions of three
# iterations, the on-policy driver's and the off-policy driver's
_TOY = {
    "ppo": ("jax:cartpole", 16, 8 * 16 * 3, [
        "learner_config.algo.horizon=8", "learner_config.algo.epochs=1",
        "learner_config.algo.num_minibatches=1",
    ]),
    "ddpg": ("jax:pendulum", 8, 8 * 8 * 3, [
        "learner_config.algo.horizon=8",
        "learner_config.replay.capacity=256",
        "learner_config.replay.start_sample_size=16",
        "learner_config.replay.batch_size=8",
    ]),
}


def _argv(algo, folder, extra=()):
    env, num_envs, total, sets = _TOY[algo]
    return [
        "train", algo, env, "--folder", str(folder),
        "--num-envs", str(num_envs), "--total-steps", str(total),
        "--set", *sets, *_QUIET, *extra,
    ]


def _launch_like_the_cli(algo, folder, extra=(), on_metrics=None):
    """The calls ``run_train`` makes, one by one (as the benchmark's
    harness makes them): each span lives in the function it times."""
    from surreal_tpu.main import launch

    cfg = launch.build_config(
        launch.build_parser().parse_args(_argv(algo, folder, extra))
    )
    launch._apply_backend(cfg.session_config.backend)
    launch._require_platform(cfg.session_config.backend)
    trainer = launch.select_trainer(cfg)
    trainer.run(on_metrics=on_metrics)
    return cfg


def _toy_ppo(folder, **session):
    session.setdefault(
        "metrics", Config(every_n_iters=1, tensorboard=False, console=False)
    )
    return Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=8, epochs=1, num_minibatches=1)
        ),
        env_config=Config(name="jax:cartpole", num_envs=16),
        session_config=Config(
            folder=str(folder), total_env_steps=8 * 16 * 3,
            checkpoint=Config(every_n_iters=0), eval=Config(every_n_iters=0),
            **session,
        ),
    ).extend(base_config())


def _events(folder, type_=None):
    path = os.path.join(str(folder), "telemetry", "events.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as fh:
        events = [json.loads(line) for line in fh]
    return [e for e in events if type_ is None or e["type"] == type_]


def _names_in_order(event):
    names = []
    for span in event["spans"]:
        if span["name"] not in names:
            names.append(span["name"])
    return names


def _assert_tiles(event):
    """Every span ends after it starts, the top-level ones do not overlap,
    and what they leave of ``total_s`` is ``unattributed_s``."""
    top = [s for s in event["spans"] if s["parent"] == "launch"]
    assert [s["start_s"] for s in top] == sorted(s["start_s"] for s in top)
    for span in event["spans"]:
        assert 0.0 <= span["start_s"] <= span["end_s"] <= event["total_s"], span
    for before, after in zip(top, top[1:]):
        assert before["end_s"] <= after["start_s"], (before, after)
    covered = sum(s["end_s"] - s["start_s"] for s in top)
    assert event["unattributed_s"] >= 0.0
    assert event["total_s"] - covered == pytest.approx(
        event["unattributed_s"], abs=1e-6
    )


def _counter(event, key):
    return sum(s.get(key, 0) for s in event["spans"]) + event.get(
        "outside", {}
    ).get(key, 0)


@pytest.fixture
def no_open_launch():
    """Whatever an earlier test of this process left open goes (into a
    disabled tracer: nothing is written), the process's own record with
    it, so the test's first span opens a session's record."""
    telemetry.launch_record().close(Tracer(None))
    yield
    telemetry.launch_record().close(Tracer(None))


@pytest.fixture
def first_launch_of_the_process(monkeypatch, no_open_launch):
    """As if no launch span had been opened in this process yet: the next
    one opens the process's own record, from the OS's start of it."""
    monkeypatch.setattr(telemetry, "_LAUNCH", None)


# -- the event ----------------------------------------------------------------

@pytest.mark.parametrize("algo", sorted(_TOY))
def test_a_launch_leaves_one_event_with_the_ten_spans(
    algo, tmp_path, first_launch_of_the_process, compile_cache_on
):
    from surreal_tpu.utils.compat import compile_cache_counts

    before = compile_cache_counts()
    at_first_stamp = []

    def on_metrics(iteration, row):
        if not at_first_stamp:  # the launch closed in this boundary
            at_first_stamp.append(compile_cache_counts())

    folder = tmp_path / algo
    _launch_like_the_cli(algo, folder, on_metrics=on_metrics)
    (event,) = _events(folder, "launch")
    assert event["origin"] == "os" and event["closed"] is True
    assert _names_in_order(event) == list(LAUNCH_SPANS)
    assert {s["parent"] for s in event["spans"]} == {"launch"}
    _assert_tiles(event)
    # the interpreter's start is inside the first span, not lost
    assert event["spans"][0]["start_s"] == 0.0
    assert event["t0_unix"] + event["total_s"] == pytest.approx(
        event["t"], abs=0.5
    )
    # the compiler's seconds and the cache's counts, in the spans that
    # paid them: what the session compiled, it compiled inside its launch
    looked_up = _counter(event, "cache_hits") + _counter(event, "cache_misses")
    assert looked_up >= 1
    assert looked_up == sum(
        at_first_stamp[0][k] - before[k] for k in ("hits", "misses")
    )
    by_name = {s["name"]: s for s in event["spans"]}
    assert by_name["launch.cost_record"]["lower_s"] > 0.0
    assert by_name["launch.cost_record"]["trace_s"] > 0.0
    assert _counter(event, "compile_s") >= _counter(event, "cache_read_s") >= 0.0
    for span in event["spans"]:
        inside = sum(span.get(k, 0.0) for k in ("trace_s", "lower_s", "compile_s"))
        assert inside <= (span["end_s"] - span["start_s"]) + 0.05, span


def test_a_second_session_of_the_process_writes_its_own(tmp_path, no_open_launch):
    from surreal_tpu.launch.trainer import Trainer

    Trainer(_toy_ppo(tmp_path / "first")).run()
    Trainer(_toy_ppo(tmp_path / "second")).run()
    (first,) = _events(tmp_path / "first", "launch")
    (second,) = _events(tmp_path / "second", "launch")
    for event in (first, second):
        # no config was built and no trainer selected through main/launch.py:
        # a Trainer built by hand has the spans of run() alone
        assert event["origin"] == "session" and event["closed"] is True
        assert _names_in_order(event) == list(LAUNCH_SPANS[4:])
        assert event["spans"][0]["start_s"] == pytest.approx(0.0, abs=1e-3)
        _assert_tiles(event)
    assert second["t0_unix"] >= first["t0_unix"] + first["total_s"] - 0.5


def test_a_run_shorter_than_a_cadence_closes_its_launch_at_close(
    tmp_path, no_open_launch
):
    from surreal_tpu.launch.trainer import Trainer

    cfg = _toy_ppo(
        tmp_path,
        metrics=Config(every_n_iters=10, tensorboard=False, console=False),
    )
    Trainer(cfg).run()
    (event,) = _events(tmp_path, "launch")
    assert event["closed"] is False
    assert _names_in_order(event)[-1] == "launch.first_cadence"
    _assert_tiles(event)
    assert "no metrics-sync was reached" in telemetry.diag_report(str(tmp_path))


def test_a_disabled_tracer_writes_none_and_the_spans_still_annotate(
    tmp_path, no_open_launch, monkeypatch
):
    from surreal_tpu.launch.trainer import Trainer

    annotated = []
    real = telemetry.trace_annotation

    def recording(name):
        annotated.append(name)
        return real(name)

    monkeypatch.setattr(telemetry, "trace_annotation", recording)
    Trainer(_toy_ppo(tmp_path, telemetry=Config(enabled=False))).run()
    assert _events(tmp_path) == []
    for name in LAUNCH_SPANS[4:-1]:  # first_cadence ends on another call
        assert name in annotated, (name, annotated)
    # ... and the record closed all the same: the next session's is its own
    assert telemetry._LAUNCH.closed


def test_diag_prints_the_launch_section(tmp_path, first_launch_of_the_process):
    _launch_like_the_cli("ppo", tmp_path)
    report = telemetry.diag_report(str(tmp_path))
    section = report.split("Launch — ", 1)[1].split("\n\n", 1)[0]
    assert "from process start to the first metrics-sync's end" in section
    rows = [line.split()[0] for line in section.splitlines()[2:]]
    assert rows == list(LAUNCH_SPANS) + ["unattributed", "compile"]
    assert "trace / lower / compile / cache read" in section
    summary = telemetry.diag_summary(str(tmp_path))["launch"]
    assert summary["origin"] == "os" and len(summary["spans"]) >= 10


def test_the_steady_loop_writes_no_compile_cache_event(
    tmp_path, no_open_launch, compile_cache_on
):
    from surreal_tpu.launch.trainer import Trainer

    cfg = _toy_ppo(tmp_path)
    cfg.session_config.total_env_steps = 8 * 16 * 8
    Trainer(cfg).run()
    events = _events(tmp_path)
    rows = [e for e in events if e["type"] == "metrics"]
    written = [e for e in events if e["type"] == "compile_cache"]
    assert len(rows) == 8
    # one at the first cadence, and after it only where a count moved
    assert 1 <= len(written) < len(rows)
    for before, after in zip(written, written[1:]):
        assert (before["hits"], before["misses"]) != (after["hits"], after["misses"])
    # the last one is still the session's total (what diag reads)
    from surreal_tpu.utils.compat import compile_cache_counts

    assert {k: written[-1][k] for k in ("hits", "misses")} == compile_cache_counts()
    assert written[-1]["misses"] >= 1
    assert len(_events(tmp_path, "launch")) == 1  # none rewritten at a cadence


# -- the record ---------------------------------------------------------------

class _Sink:
    enabled = True

    def __init__(self):
        self.events = []

    def event(self, type_, **fields):
        self.events.append(dict(fields, type=type_))


def test_record_nests_adds_up_and_closes_what_is_open():
    rec = LaunchRecord("session", t0=telemetry.time.perf_counter())
    with rec.span("launch.build"):
        rec.add("cache_hits", 1)
        with rec.span("inner"):
            rec.add("cache_hits", 2)
            # a function traced inside another's trace, the inner one first
            rec.add_interval("trace_s", 10.0, 11.0)
            rec.add_interval("trace_s", 11.5, 12.0)
            rec.add_interval("trace_s", 9.0, 13.0)
            rec.add_interval("trace_s", 13.0, 13.5)
    rec.add("cache_misses", 1)  # between spans
    rec.begin("launch.first_cadence")
    sink = _Sink()
    rec.close(sink)
    rec.close(sink)  # once
    rec.add("cache_hits", 5)  # nothing after the close
    (event,) = sink.events
    build, inner, cadence = event["spans"]
    assert (build["parent"], inner["parent"], cadence["parent"]) == (
        "launch", "launch.build", "launch"
    )
    assert build["cache_hits"] == 1 and inner["cache_hits"] == 2
    assert inner["trace_s"] == pytest.approx(4.5)
    assert event["outside"] == {"cache_misses": 1}
    assert cadence["end_s"] == event["total_s"]
    # the nested span is inside its parent and adds nothing to the cover
    assert build["start_s"] <= inner["start_s"] <= inner["end_s"] <= build["end_s"]
    _assert_tiles(event)


@pytest.mark.parametrize("os_gives", ["a_start", "none", "a_later_start"])
def test_the_process_record_says_where_its_origin_is(
    os_gives, first_launch_of_the_process, monkeypatch
):
    import surreal_tpu

    since_import = telemetry.time.perf_counter() - surreal_tpu.IMPORTED_AT
    age = {
        "a_start": since_import + 1.5, "none": None,
        "a_later_start": since_import - 0.01,  # not this process's
    }[os_gives]
    monkeypatch.setattr(telemetry, "_process_age_s", lambda: age)
    telemetry.launch_imported()
    telemetry.launch_imported()  # the entry point's first call only
    with telemetry.launch_span("launch.backend"):
        pass
    sink = _Sink()
    telemetry.launch_close(sink)
    (event,) = sink.events
    if os_gives == "a_start":
        assert event["origin"] == "os"
        assert _names_in_order(event) == list(LAUNCH_SPANS[:3])
        assert event["spans"][0]["end_s"] == pytest.approx(1.5, abs=0.05)
    else:
        assert event["origin"] == "import"
        assert _names_in_order(event) == list(LAUNCH_SPANS[1:3])
        assert event["spans"][0]["start_s"] == 0.0
    _assert_tiles(event)
    # the process's record is its first: from here on a session's own
    with telemetry.launch_span("launch.build"):
        assert telemetry.launch_record().origin == "session"


def test_the_os_start_of_this_process_is_before_its_imports():
    import surreal_tpu

    age = telemetry._process_age_s()
    if age is None:
        pytest.skip("no /proc/self/stat here")
    assert age >= telemetry.time.perf_counter() - surreal_tpu.IMPORTED_AT >= 0.0


def test_the_event_is_registered_and_described():
    assert "launch" in telemetry.EVENT_REGISTRY
    assert '{"type": "launch"' in telemetry.__doc__
    for name in LAUNCH_SPANS:
        assert name.startswith("launch.")


def test_run_train_records_a_launch_in_a_process_of_its_own(tmp_path):
    """``python -m surreal_tpu train``: the CLI's own calls, a fresh
    process, nothing of this suite's in its first span."""
    proc = subprocess.run(
        [sys.executable, "-m", "surreal_tpu", *_argv("ppo", tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=str(_REPO_ROOT),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    (event,) = _events(tmp_path, "launch")
    assert event["origin"] == "os" and event["closed"] is True
    assert _names_in_order(event) == list(LAUNCH_SPANS)
    _assert_tiles(event)
    assert "Launch — " in telemetry.diag_report(str(tmp_path))
