"""Find a cell, its configuration and the per-layer metric readers by name.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them lives in a file of its own under ``benchmarks/``:
``workloads/<cell>.json``, ``configs/<config>.json``,
``layer_metrics/<metric>.py``. Nothing here lists them, so a later PR adds
files and manifest entries and edits no file that exists.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """A name without its file, a file without its name, or a bad field."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


@functools.lru_cache(maxsize=None)
def load_manifest() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_peaks() -> dict:
    table = _read_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    return {k: v for k, v in table.items() if not k.startswith("_")}


def load_cell(name: str) -> dict:
    """The manifest's entry for ``name`` merged over its workload file."""
    entries = [w for w in load_manifest()["workloads"] if w["name"] == name]
    if not entries:
        raise ManifestError(f"BENCHMARK.json has no workload {name!r}")
    cell = _read_json(os.path.join(BENCH_DIR, "workloads", f"{name}.json"))
    for key in ("config", "chips"):
        if cell[key] != entries[0][key]:
            raise ManifestError(
                f"{name}: {key} is {cell[key]!r} in its file and "
                f"{entries[0][key]!r} in BENCHMARK.json"
            )
    return dict(cell, name=name)


def load_config(name: str) -> dict:
    entries = [c for c in load_manifest()["configs"] if c["name"] == name]
    if not entries:
        raise ManifestError(f"BENCHMARK.json has no config {name!r}")
    return dict(_read_json(os.path.join(ROOT, entries[0]["file"])), name=name)


def _import_file(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_metric_files() -> list[str]:
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    return sorted(
        f[:-3] for f in os.listdir(folder)
        if f.endswith(".py") and not f.startswith("_")
    )


def load_layer_metric(name: str) -> ModuleType:
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"missing benchmarks/layer_metrics/{name}.py")
    module = _import_file(path, f"benchmarks_layer_metric_{name}")
    if module.NAME != name:
        raise ManifestError(f"{name}.py declares NAME={module.NAME!r}")
    return module


def load_reference(name: str) -> ModuleType:
    path = os.path.join(BENCH_DIR, "reference", f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"missing benchmarks/reference/{name}.py")
    return _import_file(path, f"benchmarks_reference_{name}")


def metrics_of(kind: str, cell: str) -> list[dict]:
    """The manifest's ``end_to_end`` or ``per_layer`` entries that ``cell``
    reports: those without a ``workloads`` list, or with it in the list."""
    return [
        m for m in load_manifest()[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]
