"""The readers of ``ppo_lift_qwen3next_16x1024``: their entries in
``BENCHMARK.json`` **as a block wherever it lies** (its names contiguous and
in order: a later PR appends after it, and a pin of the list's tail would turn
red for a PR that did nothing wrong, as the two pins before this one did:
``ROADMAP.md`` S6 (aa)), each entry's cell, layer and unit, what each reads of
a recorded digest and a window's last row, and that each reads nothing, and
does not raise, from a program that lacks the table or the counter.
``per_layer`` may hold 128 entries and held 126 before this PR: of the
fourteen readers its issue lists, these two fit (the part this PR adds, and
the counter its seeds' rates follow). A third, the part's share of its
roofline, was brought and withdrawn: the cost it read held the acting
states' HBM traffic, which the chip does not make (``iteration_cost``; the
last test here)."""

import types

import pytest

from benchmarks.harness import manifest, phase_session

M = manifest.load_manifest()
CELL = "ppo_lift_qwen3next_16x1024"
# in the manifest's order
READERS = ["qwen3next_gdn_scan_part_ms", "qwen3next_acting_live_share"]
UNITS = {
    "qwen3next_gdn_scan_part_ms": ("ms", "lower", "program_span"),
    "qwen3next_acting_live_share": ("ratio", "lower", "program_counter"),
}

DIGEST = {
    "devices": 1, "steps": 3,
    "phases": {
        "collect": {"ms_per_iter": 1100.0}, "prepare": {"ms_per_iter": 250.0},
        "sgd": {"ms_per_iter": 1900.0},
    },
    "parts": {
        "gdn_scan": {"ms_per_iter": 900.0}, "gdn_proj": {"ms_per_iter": 500.0},
        "attn": {"ms_per_iter": 300.0},
    },
}
ROW = {"moe/acting_live_share": 0.2726, "moe/held_share": 0.0646}
READS = {
    "qwen3next_gdn_scan_part_ms": 900.0,
    "qwen3next_acting_live_share": 0.2726,
}


def a_run(row=ROW):
    return types.SimpleNamespace(
        window=[types.SimpleNamespace(row=row)] if row is not None else [],
        peaks={}, cost={}, config={},
    )


def test_the_entries_are_a_block_in_order_wherever_it_lies():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + len(READERS)] == READERS
    assert len(set(names)) == len(names) <= 128
    # the cell reports the two tables of counts every cell reports
    for shared in ("device_ops_per_iter", "short_ops_ms"):
        entry = next(m for m in M["per_layer"] if m["name"] == shared)
        assert CELL in entry["workloads"]
    # and no other reader with a list of cells names it
    listed = [m["name"] for m in M["per_layer"] if CELL in m.get("workloads", ())]
    assert listed == ["device_ops_per_iter", "short_ops_ms", *READERS]


@pytest.mark.parametrize("name", READERS)
def test_entry_lists_the_cell_alone_and_moves_the_rate(name):
    entry = next(m for m in M["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "env_steps_per_s"
    assert entry["layer"] == "learners and ops"
    assert (entry["unit"], entry["better"], entry["source"]) == UNITS[name]


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_its_value(name, monkeypatch):
    monkeypatch.setattr(phase_session, "_session", lambda run: {"digest": DIGEST})
    got = manifest.load_layer_metric(name).read(a_run())
    assert got == pytest.approx(READS[name], rel=1e-12)


def test_the_cells_own_cost_keeps_the_acting_states_out_of_hbm():
    """Why no share of a roofline is read of part ``gdn_scan`` here. The
    first count held the three float32 matrix states read and written
    through HBM by each of the 1024 acting steps: 206 GB, 252 ms at the
    peak, against 229.1 ms the part takes in ``collect`` on the chip (my
    chip runs, PR 63): over 100. The chip keeps the 100.7 MB in VMEM
    through the acting loop, so that traffic is not required, and what is
    (the rule's inputs and outputs, a chunk's starting states, the conv
    tails) is 34 ms of HBM an iteration against 870.7: the part is bound
    by what it does on the chip, not by memory's roof."""
    config = manifest.load_config("ppo_lift_qwen3next")
    ref = manifest.load_reference(config["reference"])
    cost = ref.iteration_cost(config, manifest.load_cell(CELL)["traffic"])
    on_chip = cost["scan_state_on_chip_bytes"]
    assert on_chip == 1024 * 2 * 3 * 16 * (4 * 32 * 128 * 128)
    assert 3 * 16 * 4 * 32 * 128 * 128 < ref.ON_CHIP_BYTES
    # what the part took in collect (PERF.md section 5) is less than HBM
    # would need for the states alone: they cannot have crossed it
    assert 1e3 * on_chip / 819e9 > 229.1
    floor_ms = 1e3 * max(
        cost["scan_flops"] / 197e12, cost["scan_bytes"] / 819e9
    )
    assert floor_ms == pytest.approx(33.56, rel=1e-3)
    assert on_chip not in (cost["scan_bytes"], cost["collect_bytes"])
    assert cost["collect_bytes"] / 819e9 < 1.1281 * 0.6


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("record", [
    pytest.param({"digest": {"devices": 0, "steps": 3}}, id="no-device-plane"),
    pytest.param({"digest": {"devices": 1, "steps": 3, "parts": {
        "kda_scan": {"ms_per_iter": 1221.1}}}}, id="another-familys-parts"),
    pytest.param({"digest_error": "ValueError: the capture is cut"}, id="no-digest"),
    pytest.param(None, id="no-record"),
])
def test_reader_reads_nothing_from_a_program_without_the_table(
    name, record, monkeypatch
):
    monkeypatch.setattr(phase_session, "_session", lambda run: record)
    assert manifest.load_layer_metric(name).read(a_run(row={})) is None
    assert manifest.load_layer_metric(name).read(a_run(row=None)) is None
