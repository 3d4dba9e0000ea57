"""BENCHMARK.json against the benchmark's contract and against the files
under ``benchmarks/``: every name has its file and every file its name, so
a later PR can add a cell, a configuration or a per-layer metric as new
files plus manifest entries and edit nothing that exists."""

import json
import os
import re

import pytest

from benchmarks.harness import manifest, runner

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per_tok")

M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
CONFIGS = [c["name"] for c in M["configs"]]
LAYER = [m["name"] for m in M["per_layer"]]
E2E = [m["name"] for m in M["end_to_end"]]


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(M) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    raw = open(os.path.join(manifest.ROOT, "BENCHMARK.json"), "rb").read()
    assert len(raw) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(line_ok(w) for w in M["command"])
    assert 1 <= len(M["paths"]) <= 16 and all(PATH.match(p) for p in M["paths"])
    assert 1 <= len(M["configs"]) <= 24 and 2 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    # the budget of a full check with all 24 cells a benchmark may have
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_command_stays_inside_paths():
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(manifest.ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"]), word


def test_names_are_unique_and_well_formed():
    for group in (CELLS, CONFIGS, LAYER + E2E):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group), group
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(NAME.match(w["traffic"]) for w in M["workloads"])


@pytest.mark.parametrize("entry", M["configs"], ids=CONFIGS)
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line_ok(entry["source"]) and line_ok(entry["why"])
    assert PATH.match(entry["file"])
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank")), key
        assert not any(w in key for w in WIDTH_WORDS), f"{key} names a width"
    cfg = manifest.load_config(entry["name"])
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    # a key changed from the source is in the file as it is run, and the
    # file says what the source had
    for key in entry["reduced"]:
        assert key in cfg and any(key in note for note in cfg["assumed"]), key
    for key in ("algo", "env", "reference", "stands_for", "widths",
                "overrides", "assumed"):
        assert key in cfg, key
    ref = manifest.load_reference(cfg["reference"])
    assert callable(ref.check)
    assert entry["name"] in {w["config"] for w in M["workloads"]}


def test_config_files_are_one_to_one():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    on_disk = {
        f"benchmarks/configs/{f}"
        for f in os.listdir(os.path.join(manifest.BENCH_DIR, "configs"))
    }
    assert on_disk == set(files)


@pytest.mark.parametrize("entry", M["workloads"], ids=CELLS)
def test_cell_entry_and_file(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and line_ok(entry["why"])
    assert entry["config"] in CONFIGS
    cell = manifest.load_cell(entry["name"])  # checks config and chips agree
    assert entry["name"] == f"{entry['config']}_{entry['traffic']}"
    config = manifest.load_config(cell["config"])
    for key in ("traffic", "overrides", "learning", "rehearse", "why"):
        assert key in cell, key
    # every traffic parameter is one the generator knows, and the counts
    # of required work can be made from it, real and toy
    known = set(runner.TRAFFIC_KEYS) | {"num_envs"}
    assert set(cell["traffic"]) <= known, set(cell["traffic"]) - known
    reference = manifest.load_reference(config["reference"])
    for rehearse in (False, True):
        traffic = runner.sized(cell, rehearse)["traffic"]
        cost = reference.iteration_cost(config, traffic)
        assert cost["flops"] > 0 and cost["bytes"] > 0 and cost["samples"] > 0
    if entry["chips"] == 4:
        assert cell["traffic"]["mesh_dp"] == 4


def test_cell_files_are_one_to_one():
    on_disk = {
        f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "workloads"))
    }
    assert on_disk == set(CELLS)


def test_four_chip_cells_are_rationed():
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("entry", M["end_to_end"], ids=E2E)
def test_end_to_end_entry(entry):
    assert set(entry) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.1
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)


def test_setup_s_is_there_for_every_cell():
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for cell in CELLS:
        names = [m["name"] for m in manifest.metrics_of("end_to_end", cell)]
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("entry", M["per_layer"], ids=LAYER)
def test_per_layer_entry_and_reader(entry):
    assert set(entry) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads",
    }
    assert "bound" not in entry
    assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES and line_ok(entry["layer"])
    cells = entry.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    # moves an end-to-end metric that each of its cells reports
    for cell in cells:
        reported = [m["name"] for m in manifest.metrics_of("end_to_end", cell)]
        assert entry["moves"] in reported, (entry["name"], cell)
    # the reader is the name and how to read it; what the manifest says of
    # the metric is said there alone
    reader = manifest.load_layer_metric(entry["name"])
    assert callable(reader.read)
    public = {k for k in vars(reader) if k.isupper()}
    assert public <= {"NAME", "CHIP_ONLY"}, public


def test_layer_metric_files_are_one_to_one():
    assert manifest.layer_metric_files() == sorted(LAYER)


def test_every_cell_reports_a_per_layer_metric():
    for cell in CELLS:
        assert manifest.metrics_of("per_layer", cell)


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in M["per_layer"]}
    assert len({l.lower().strip() for l in layers}) == len(layers)


def test_test_files_here_have_unique_base_names():
    here = os.path.dirname(os.path.abspath(__file__))
    tests_dir = os.path.dirname(here)
    mine = {f for f in os.listdir(here) if f.endswith(".py")}
    others = {
        f for root, _, files in os.walk(tests_dir) if root != here
        for f in files if f.endswith(".py")
    }
    assert not mine & others
    assert all(f.startswith("test_benchmark_") for f in mine)


def test_peak_table_names_its_source():
    path = os.path.join(manifest.BENCH_DIR, "harness", "peaks.json")
    table = json.load(open(path))
    assert "documentation" in table["_source"]
    for kind, peaks in manifest.load_peaks().items():
        assert peaks["bf16_flops_per_s"] > 0 and peaks["hbm_bytes_per_s"] > 0


def test_no_private_step_is_called():
    """Every run goes through ``select_trainer(...).run``; nothing under
    benchmarks/ names the trainers' private step."""
    for root, _, files in os.walk(manifest.BENCH_DIR):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(root, f)).read()
                assert "_train_iter" not in text, os.path.join(root, f)
