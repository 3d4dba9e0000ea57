"""The readers of the digest's finer tables (harness/digest_tables.py): a
phase's sub-scopes, a model part's phases, a Pallas kernel's calls summed and
the count of op events, each under its name; ``None`` and no exception from a
digest that lacks the table (the parent commit's), from a rehearsal's and
from a session that failed."""

import os
import types

import pytest

from benchmarks.harness import manifest, phase_session

M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
ONE_CHIP_SMALL = [
    "ppo_lift_long", "ppo_lift_dp4", "ddpg_lift_per20m", "impala_pong_1k32",
]
ROUTED = [
    "ppo_lift_joyai_128x128", "ppo_lift_laguna_16x1024",
    "ppo_lift_kimilinear_16x1024",
]
# reader -> (its cells, its layer, what it reads of DIGEST below)
READERS = {
    "collect_act_ms": (ONE_CHIP_SMALL, "fused drivers", 30.0),
    "collect_env_ms": (ONE_CHIP_SMALL, "fused drivers", 25.0),
    "prepare_gae_ms": (["ppo_lift_long", "ppo_lift_dp4"], "learners and ops", 4.8),
    "replay_sample_mass_ms": (["ddpg_lift_per20m"], "replay", 1.4),
    "replay_sample_search_ms": (["ddpg_lift_per20m"], "replay", 2.5),
    "replay_sample_gather_ms": (["ddpg_lift_per20m"], "replay", 5.0),
    "moe_experts_acting_ms": (ROUTED, "learners and ops", 360.0),
    "moe_experts_learn_ms": (ROUTED, "learners and ops", 20.0 + 190.0 + 0.5),
    "kernel_held_experts_live_ms": (ROUTED[1:], "learners and ops", 285.0),
    "kernel_decayed_gram_ms": (ROUTED[2:], "learners and ops", 200.0 + 85.0),
    "kernel_selective_scan_fwd_ms": (
        ["ppo_lift_phi4flash_16x1024"], "learners and ops", 30.0,
    ),
    "kernel_selective_scan_bwd_ms": (
        ["ppo_lift_phi4flash_16x1024"], "learners and ops", 60.0,
    ),
    "device_ops_per_iter": (CELLS, "device", 13000.0 + 400.0 + 12.0),
    "short_ops_ms": (CELLS, "device", 3.5 + 0.25 + 0.0),
}
ENTRIES = {m["name"]: m for m in M["per_layer"] if m["name"] in READERS}


def _phase(ms, ops, short, short_ms):
    return {
        "ms_per_iter": ms, "share_of_busy": 0.1, "top_ops": [],
        "ops_per_iter": ops,
        "short_ops": {"per_iter": short, "ms_per_iter": short_ms},
    }


def _kernel(ms, part):
    return {"ms_per_iter": ms, "calls_per_iter": 4.0, "sites": 4, "part": part,
            "by_phase": {"collect": ms}}


DIGEST = {
    "devices": 1, "steps": 3, "window_s": 2.4, "busy_s": 2.0, "idle_s": 0.4,
    "phases": {
        "collect": _phase(62.0, 13000.0, 8000.0, 3.5),
        "prepare": _phase(20.0, 400.0, 100.0, 0.25),
        "unattributed": _phase(0.5, 12.0, 0.0, 0.0),
    },
    "subphases": {
        "collect": {"act": 30.0, "env": 25.0, "rest": 7.0},
        "prepare": {"gae": 4.8, "rest": 15.2},
        "replay_sample": {"mass": 1.4, "search": 2.5, "gather": 5.0, "rest": 0.4},
    },
    "parts_by_phase": {
        "moe_experts": {
            "collect": 360.0, "prepare": 20.0, "sgd": 190.0, "unattributed": 0.5,
        },
        "attn": {"sgd": 11.0},
    },
    "kernels": {
        "held_experts_live": _kernel(285.0, "moe_experts"),
        "decayed_gram": _kernel(200.0, "kda_scan"),
        "decayed_gram_bwd": _kernel(85.0, "kda_scan"),
        "selective_scan_fwd": _kernel(30.0, "ssm_scan"),
        "selective_scan_bwd": _kernel(60.0, "ssm_scan"),
    },
}
# what the parent commit's program reduces a capture to: no finer table
PARENT_DIGEST = {
    "devices": 1, "steps": 3, "window_s": 2.4, "busy_s": 2.0, "idle_s": 0.4,
    "phases": {
        "collect": {"ms_per_iter": 62.0, "share_of_busy": 0.1, "top_ops": []},
        "unattributed": {"ms_per_iter": 0.5, "share_of_busy": 0.0, "top_ops": []},
    },
    "parts": {"moe_experts": {"ms_per_iter": 570.5, "share_of_busy": 0.5}},
    "idle_by_span": {"none": 0.4},
}


def a_run(**attrs):
    return types.SimpleNamespace(**attrs)


def test_the_manifest_lists_the_fourteen_readers():
    assert sorted(ENTRIES) == sorted(READERS) and len(READERS) == 14


@pytest.mark.parametrize("name", sorted(READERS))
def test_entry_has_its_file_its_cells_and_no_phase_prefix(name):
    cells, layer, _ = READERS[name]
    entry = ENTRIES[name]
    assert entry["workloads"] == cells and set(cells) <= set(CELLS)
    assert entry["layer"] == layer and entry["source"] == "program_span"
    assert entry["moves"] == "env_steps_per_s" and entry["better"] == "lower"
    assert entry["unit"] == ("ops" if name == "device_ops_per_iter" else "ms")
    assert not name.startswith("phase_")
    assert os.path.isfile(
        os.path.join(manifest.BENCH_DIR, "layer_metrics", f"{name}.py")
    )
    assert manifest.load_layer_metric(name).CHIP_ONLY is True


def test_the_new_entries_are_the_last_of_the_list():
    names = [m["name"] for m in M["per_layer"]]
    assert set(names[-14:]) == set(READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_value_from_a_recorded_digest(name, monkeypatch):
    monkeypatch.setattr(phase_session, "_session", lambda run: {"digest": DIGEST})
    assert manifest.load_layer_metric(name).read(a_run()) == pytest.approx(
        READERS[name][2], rel=1e-12
    )


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("record", [
    pytest.param({"digest": PARENT_DIGEST}, id="the-parents-digest"),
    pytest.param({"digest": {"devices": 0, "steps": 3}}, id="no-device-plane"),
    pytest.param({"digest": dict(DIGEST, subphases=None, kernels=[],
                                 parts_by_phase=3, phases="x")},
                 id="tables-of-another-shape"),
    pytest.param({"digest_error": "ValueError: cut short"}, id="no-digest"),
    pytest.param(None, id="no-record"),
])
def test_reader_reads_nothing_where_the_table_is_not(name, record, monkeypatch):
    monkeypatch.setattr(phase_session, "_session", lambda run: record)
    assert manifest.load_layer_metric(name).read(a_run()) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_does_not_raise_when_the_session_fails(name, monkeypatch, capsys):
    def broken(run):
        raise RuntimeError("no chip left")

    monkeypatch.setattr(phase_session, "_session", broken)
    assert manifest.load_layer_metric(name).read(a_run()) is None
    assert "session failed: RuntimeError" in capsys.readouterr().err


def test_the_tables_readers_share_the_phase_readers_session(monkeypatch):
    calls = []

    def once(run):
        calls.append(run)
        return {"digest": DIGEST}

    monkeypatch.setattr(phase_session, "_session", once)
    run = a_run()
    for name in ("phase_collect_ms", *sorted(READERS)):
        manifest.load_layer_metric(name).read(run)
    assert len(calls) == 1


def test_a_kernel_reader_wants_each_of_its_kernels(monkeypatch):
    """``decayed_gram`` without ``decayed_gram_bwd`` is no reading of the
    pair: half a sum would read as a gain."""
    kernels = {"decayed_gram": DIGEST["kernels"]["decayed_gram"]}
    monkeypatch.setattr(
        phase_session, "_session",
        lambda run: {"digest": dict(DIGEST, kernels=kernels)},
    )
    assert manifest.load_layer_metric("kernel_decayed_gram_ms").read(a_run()) is None


def test_acting_and_learn_sum_to_the_part(monkeypatch):
    monkeypatch.setattr(phase_session, "_session", lambda run: {"digest": DIGEST})
    run = a_run()
    both = sum(
        manifest.load_layer_metric(n).read(run)
        for n in ("moe_experts_acting_ms", "moe_experts_learn_ms")
    )
    assert both == pytest.approx(
        sum(DIGEST["parts_by_phase"]["moe_experts"].values()), rel=1e-12
    )


def test_the_programs_own_digest_feeds_the_readers(monkeypatch):
    """A hand-built capture through ``reduce_digest`` itself, so that the
    program's keys and the readers' cannot drift apart."""
    from surreal_tpu.session.profile import reduce_digest

    U = "unattributed"
    ops = [
        (0, 9000, "while.1", "collect", U, U, None),
        (100, 4000, "fusion.2 f32[8]", "collect", U, "collect/act", None),
        (4100, 4500, "fusion.3", "collect", U, "collect/env", None),
        (5000, 8000, "held_experts_live.3 f32[2]", "collect", "moe_experts",
         "collect/act", "held_experts_live"),
        (9000, 12000, "fusion.9", "sgd", "moe_experts", U, None),
    ]
    digest = reduce_digest({"/device:TPU:0": ops}, [], steps=1)
    monkeypatch.setattr(phase_session, "_session", lambda run: {"digest": digest})
    run = a_run()
    got = {n: manifest.load_layer_metric(n).read(run) for n in READERS}
    assert got["collect_act_ms"] == pytest.approx(6900e-6)
    assert got["collect_env_ms"] == pytest.approx(400e-6)
    assert got["kernel_held_experts_live_ms"] == pytest.approx(3000e-6)
    assert got["moe_experts_acting_ms"] == pytest.approx(3000e-6)
    assert got["moe_experts_learn_ms"] == pytest.approx(3000e-6)
    assert got["device_ops_per_iter"] == 5.0
    assert got["short_ops_ms"] == pytest.approx(400e-6)
    assert got["prepare_gae_ms"] is None and got["kernel_decayed_gram_ms"] is None
