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
# a width's key, matched whole (a name before it is fine: ``moe_``,
# ``mamba_``): a hidden, intermediate, latent, state or projection size,
# a head size, an expansion factor, the number of experts per token, and
# whatever ends in ``_dim`` or ``_rank``. ``num_hidden_layers`` holds the
# word "hidden" and is a depth.
WIDTH_KEY = re.compile(
    r"(?:.*_)?(?:"
    r"(?:hidden|intermediate|latent|state|projection|head)_size"
    r"|d_(?:model|inner|state|ssm|head|conv)|head_?dim"
    r"|expand|expansion_(?:factor|rate)"
    r"|experts_per_tok(?:en)?|top_?k"
    r")|.*_(?:dim|rank)"
)

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
        assert not WIDTH_KEY.fullmatch(key), f"{key} names a width"
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


@pytest.mark.parametrize("key,is_width", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("kv_lora_rank", True),
    ("ssm_state_size", True), ("mamba_d_state", True),
    ("mamba_expand", True), ("num_experts_per_tok", True),
    # depths, counts held here and scale: what a cut may name
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("vocab_size", False), ("num_nextn_predict_layers", False),
    ("replay_capacity", False),
])
def test_a_reduced_key_is_refused_when_it_is_a_widths_key(key, is_width):
    assert bool(WIDTH_KEY.fullmatch(key)) is is_width


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


@pytest.mark.parametrize("metric", E2E)
def test_every_end_to_end_metric_has_a_layer_under_it_in_every_cell(metric):
    """In every cell that reports it, some per-layer metric of a layer of
    the program (not the device's own readings) says it moves it: a change
    to that cell's end-to-end number can be looked for in a layer."""
    entry = next(m for m in M["end_to_end"] if m["name"] == metric)
    for cell in entry.get("workloads", CELLS):
        movers = [
            m["name"] for m in manifest.metrics_of("per_layer", cell)
            if m["moves"] == metric and m["layer"] != "device"
        ]
        assert movers, (metric, cell)


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
    # benchmark_rehearsal.py is no test file: what every cell's
    # test_benchmark_rehearse_<cell>.py imports
    assert all(
        f.startswith("test_benchmark_") or f == "benchmark_rehearsal.py"
        for f in mine
    )


def test_peak_table_names_its_source():
    path = os.path.join(manifest.BENCH_DIR, "harness", "peaks.json")
    table = json.load(open(path))
    assert "documentation" in table["_source"]
    for kind, peaks in manifest.load_peaks().items():
        assert peaks["bf16_flops_per_s"] > 0 and peaks["hbm_bytes_per_s"] > 0


def sources(*folders):
    """``(path, text)`` of every ``.py`` under ``benchmarks/<folder>``, or
    under ``benchmarks/`` itself with no folder named."""
    for folder in folders or ("",):
        for root, _, files in os.walk(os.path.join(manifest.BENCH_DIR, folder)):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(root, f)
                    yield path, open(path).read()


def test_no_private_step_is_called():
    """Every run goes through ``select_trainer(...).run``; nothing under
    benchmarks/ names the trainers' private step."""
    for path, text in sources():
        assert "_train_iter" not in text, path


def test_no_learner_is_built_beside_the_sessions():
    """A traced run holds a configuration's training state once, in the
    sessions it trains through ``select_trainer(cfg).run``: no file of the
    harness or of a reader builds a learner, calls ``learn`` or
    ``device_rollout``, or initialises a state of its own (a second state
    beside the first capped a trained configuration near 500M parameters:
    PERF.md section 6, PR 38). The references' checks, on a few envs, are
    not the harness."""
    for path, text in sources("harness", "layer_metrics"):
        for word in ("build_learner", ".learn(", "device_rollout",
                     "learner.init", "standalone"):
            assert word not in text, (path, word)
