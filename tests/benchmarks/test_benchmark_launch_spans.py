"""The eleven ``launch_*`` readers (benchmarks/LAUNCH.md) against a canned
``launch`` event: each reads the spans or counters its docstring names, as
a float, and ``None`` from a run whose program wrote no such event (every
program before PR 41, the parent's side of a traced pair)."""

import types

import pytest

from benchmarks.harness import manifest

M = manifest.load_manifest()


def span(name, start, end, parent="launch", **counters):
    return dict(name=name, parent=parent, start_s=start, end_s=end, **counters)


# a launch of 20 s: two calls into the backend and three into the session,
# as the drivers make them; the gaps between spans add up to 0.5 s
EVENT = {
    "type": "launch", "origin": "os", "t0_unix": 1.79e9, "total_s": 20.0,
    "unattributed_s": 0.5, "closed": True,
    "spans": [
        span("launch.process", 0.0, 2.75),
        span("launch.import", 2.75, 3.25),
        span("launch.backend", 3.25, 3.5),
        span("launch.backend", 3.5, 9.5),
        span("launch.build", 9.75, 11.25, trace_s=0.125),
        span("launch.state_init", 11.25, 13.25, trace_s=0.25, lower_s=0.5,
             compile_s=0.75, cache_read_s=0.625, cache_hits=40),
        span("launch.session", 13.25, 13.375),
        span("launch.session", 13.375, 13.5),
        span("launch.session", 13.5, 13.625),
        span("launch.carry_init", 13.625, 14.125, lower_s=0.125,
             compile_s=0.25, cache_read_s=0.125, cache_hits=9),
        span("launch.cost_record", 14.25, 16.25, trace_s=1.0, lower_s=0.5,
             compile_s=0.375, cache_read_s=0.25, cache_hits=1),
        span("launch.first_dispatch", 16.25, 17.75, trace_s=0.0625,
             compile_s=1.25, cache_misses=1),
        span("launch.first_cadence", 17.875, 20.0),
    ],
    "outside": {"trace_s": 0.5, "cache_hits": 2},
}
EXPECTED = {
    "launch_import_s": 3.25,
    "launch_backend_s": 6.25,
    "launch_build_s": 1.5,
    "launch_state_init_s": 2.5,
    "launch_session_s": 0.375,
    "launch_cost_record_s": 2.0,
    "launch_first_dispatch_s": 1.5,
    "launch_first_cadence_s": 2.125,
    "launch_lower_s": 2.5625,  # the spans', not what fired between them
    "launch_cache_read_s": 1.0,
    "launch_unattributed_pct": 2.5,
}
SPAN_READERS = [n for n in EXPECTED if n.endswith("_s")][:8]


def run_with(*launch_events):
    """What a reader sees of a run: its telemetry by type. A later
    session of the process (the phase session's, the reference check's) has
    a folder and an event of its own and is not here."""
    events = {"phases": [{"type": "phases", "phases": {}}]}
    if launch_events:
        events["launch"] = list(launch_events)
    return types.SimpleNamespace(events=events)


def test_the_manifest_declares_exactly_these_readers():
    declared = {
        m["name"]: m for m in M["per_layer"] if m["name"] in EXPECTED
    }
    assert sorted(declared) == sorted(EXPECTED)
    for name, entry in declared.items():
        assert entry["layer"] == "session and launch"
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert "workloads" not in entry  # every cell reports it
        assert entry["unit"] == ("%" if name.endswith("_pct") else "s")
        assert not name.startswith("phase_")
        assert not getattr(manifest.load_layer_metric(name), "CHIP_ONLY", False)
    counters = {"launch_lower_s", "launch_cache_read_s"}
    assert {n for n, e in declared.items()
            if e["source"] == "program_counter"} == counters
    assert {e["source"] for n, e in declared.items()
            if n not in counters} == {"program_span"}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_spans_of_the_measured_sessions_event(name):
    later = dict(EVENT, origin="session", total_s=1.0, unattributed_s=1.0,
                 spans=[])
    value = manifest.load_layer_metric(name).read(run_with(EVENT, later))
    assert isinstance(value, float)
    assert value == pytest.approx(EXPECTED[name], abs=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_none_without_a_launch_event(name):
    assert manifest.load_layer_metric(name).read(run_with()) is None


def test_the_span_readers_and_the_rest_tile_the_launch():
    read = {
        n: manifest.load_layer_metric(n).read(run_with(EVENT)) for n in EXPECTED
    }
    assert sum(read[n] for n in SPAN_READERS) + EVENT["unattributed_s"] == (
        pytest.approx(EVENT["total_s"])
    )


def test_a_launch_that_never_closed_a_span_reads_zero_not_none():
    """A driver without one of the spans (the SEED and host-loop drivers
    have no ``launch.carry_init``) reports 0 s of it and the time as
    unattributed."""
    bare = dict(EVENT, spans=[span("launch.session", 0.0, 1.0)],
                total_s=2.0, unattributed_s=1.0)
    run = run_with(bare)
    assert manifest.load_layer_metric("launch_build_s").read(run) == 0.0
    assert manifest.load_layer_metric("launch_session_s").read(run) == 1.0
    assert manifest.load_layer_metric("launch_unattributed_pct").read(run) == 50.0
