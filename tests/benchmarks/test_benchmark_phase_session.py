"""The readers of the phase session (harness/phase_session.py): one session
per run shared by all of them, ``None`` and no exception when it fails or
the program has no digest, and the digest's numbers under their names."""

import types

import pytest

from benchmarks.harness import manifest, phase_session

M = manifest.load_manifest()
SESSION_READERS = sorted(
    m["name"] for m in M["per_layer"]
    if m["name"].startswith("phase_") or m["name"] == "idle_unexplained_pct"
)

DIGEST = {
    "devices": 1, "steps": 3, "window_s": 2.4, "busy_s": 2.0, "idle_s": 0.4,
    "phases": {
        "collect": {"ms_per_iter": 12.5, "share_of_busy": 0.01875},
        "prepare": {"ms_per_iter": 100.0, "share_of_busy": 0.15},
        "shuffle": {"ms_per_iter": 200.0, "share_of_busy": 0.3},
        "sgd": {"ms_per_iter": 300.0, "share_of_busy": 0.45},
        "update": {"ms_per_iter": 9.0, "share_of_busy": 0.0135},
        "replay_insert": {"ms_per_iter": 330.0, "share_of_busy": 0.495},
        "replay_sample": {"ms_per_iter": 310.0, "share_of_busy": 0.465},
        "replay_priority": {"ms_per_iter": 1.5, "share_of_busy": 0.00225},
        "unattributed": {"ms_per_iter": 20.0, "share_of_busy": 0.03},
    },
    "idle_by_span": {"engine.boundary": 0.3, "none": 0.1},
}


def a_run(**attrs):
    return types.SimpleNamespace(**attrs)


def test_session_readers_are_the_manifests():
    assert len(SESSION_READERS) == 10
    for name in SESSION_READERS:
        assert manifest.load_layer_metric(name).CHIP_ONLY is True


@pytest.mark.parametrize("name", SESSION_READERS)
def test_reader_returns_none_and_does_not_raise_when_the_session_fails(
    name, monkeypatch, capsys
):
    def broken(run):
        raise RuntimeError("no chip left")

    monkeypatch.setattr(phase_session, "_session", broken)
    run = a_run()
    assert manifest.load_layer_metric(name).read(run) is None
    assert "session failed: RuntimeError: no chip left" in capsys.readouterr().err
    # the failure is kept too: the next reader starts no second session
    monkeypatch.setattr(phase_session, "_session", lambda run: 1 / 0)
    assert manifest.load_layer_metric(name).read(run) is None


def test_one_session_serves_every_reader(monkeypatch):
    calls = []

    def once(run):
        calls.append(run)
        return {"digest": DIGEST}

    monkeypatch.setattr(phase_session, "_session", once)
    run = a_run()
    got = {
        name: manifest.load_layer_metric(name).read(run)
        for name in SESSION_READERS
    }
    assert len(calls) == 1
    assert got == {
        "phase_collect_ms": 12.5,
        "phase_prepare_ms": 100.0,
        "phase_shuffle_ms": 200.0,
        "phase_sgd_ms": 300.0,
        "phase_update_ms": 9.0,
        "phase_replay_insert_ms": 330.0,
        "phase_replay_sample_ms": 310.0,
        "phase_replay_priority_ms": 1.5,
        "phase_unattributed_pct": pytest.approx(3.0),
        "idle_unexplained_pct": pytest.approx(25.0),
    }


def test_a_digest_without_a_device_plane_reads_as_nothing(monkeypatch):
    """What a rehearsal's capture gives, and what the parent commit's
    program gives (no digest at all)."""
    for record in ({"digest": {"devices": 0, "steps": 3}}, None):
        monkeypatch.setattr(phase_session, "_session", lambda run: record)
        run = a_run()
        for name in SESSION_READERS:
            assert manifest.load_layer_metric(name).read(run) is None


def test_a_device_that_never_idled_has_nothing_unexplained(monkeypatch):
    digest = dict(DIGEST, idle_s=0.0, idle_by_span={})
    monkeypatch.setattr(phase_session, "_session", lambda run: {"digest": digest})
    assert manifest.load_layer_metric("idle_unexplained_pct").read(a_run()) == 0.0


def test_a_program_without_a_digest_starts_no_session(monkeypatch, capsys):
    from surreal_tpu.session import profile

    monkeypatch.delattr(profile, "digest_capture")
    assert phase_session.record(a_run(folder="unused")) is None
    assert "no digest to read" in capsys.readouterr().err


def phases_event(step, total_s=None, count=10):
    phases = {"train_iter": {"count": count, "total_s": 0.02}}
    if total_s is not None:
        phases["cadence"] = {"count": count, "total_s": total_s}
    return {"type": "phases", "step": step, "phases": phases}


def test_fenced_iter_ms_is_the_low_median_cadence_inside_the_window():
    read = manifest.load_layer_metric("fenced_iter_ms").read
    run = a_run(
        window=[a_run(env_steps=200), a_run(env_steps=500)],
        events={"phases": [
            phases_event(100, 50.0),    # before the window
            phases_event(200, 29.0),    # began before the reference check
            phases_event(300, 8.0),
            phases_event(400, 8.2),
            phases_event(500, 28.0),    # the profiler's stop
            phases_event(600, 70.0),    # after the window
            phases_event(-1, 1.0),      # the flush at close
        ]},
    )
    assert read(run) == pytest.approx(820.0)
    # two cadences left, one stretched: the low median takes the other
    run.window = [a_run(env_steps=300), a_run(env_steps=500)]
    assert read(run) == pytest.approx(820.0)
    # one stamp: no cadence lies wholly inside
    run.window = [a_run(env_steps=500)]
    assert read(run) is None
    run.window = [a_run(env_steps=200), a_run(env_steps=500)]
    # the parent commit's program has no cadence phase: nothing to read
    run.events = {"phases": [phases_event(200), phases_event(300)]}
    assert read(run) is None
    assert read(a_run(window=[])) is None
