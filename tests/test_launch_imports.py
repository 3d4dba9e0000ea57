"""A launch imports what it runs: ``orbax.checkpoint`` (and through it
``google.cloud.logging`` and ``tensorstore``) is loaded by the first
:class:`~surreal_tpu.session.checkpoint.CheckpointManager`, not by the
import of ``surreal_tpu.session`` — 44-48 s of a launch on the chip host
(PERF.md §6, PR 40), which every process that reads a config would pay.

Each case runs in a subprocess: this process's ``sys.modules`` holds
whatever the tests before it imported. No case has a wall-clock bound: an
import's seconds are the machine's, what is loaded is the program's.
"""

import os
import pathlib
import subprocess
import sys

import pytest

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

_HEAVY = ("orbax", "google.cloud.logging", "tensorstore")

_IMPORT_PROBE = """
import importlib, sys
importlib.import_module({module!r})
loaded = [m for m in {heavy!r} if m in sys.modules]
assert not loaded, f"importing {module} loaded {{loaded}}"
print("PROBE_OK")
"""

_MANAGER_PROBE = """
import sys, tempfile
import numpy as np
from surreal_tpu.session.checkpoint import CheckpointManager

assert "orbax" not in sys.modules, "the class's module imported orbax"
events = []
sink = lambda type_, **fields: events.append((type_, fields))
tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3), "n": np.array([7], np.int32)}
with tempfile.TemporaryDirectory() as folder:
    cm = CheckpointManager(folder, on_event=sink)
    assert "orbax.checkpoint" in sys.modules
    cm.save(3, tree, env_steps=30)
    state, meta = cm.restore({"w": np.zeros((2, 3), np.float32), "n": np.zeros(1, np.int32)})
    second = CheckpointManager(folder, on_event=sink)
    cm.close()
    second.close()
assert meta == {"iteration": 3, "env_steps": 30}, meta
assert np.array_equal(np.asarray(state["w"]), tree["w"]) and int(state["n"][0]) == 7
imports = [f["phases"]["checkpoint-import"] for t, f in events if t == "phases"]
assert len(imports) == 2 and all(p["count"] == 1 for p in imports), events
# the first manager paid the import, the second found it loaded
assert imports[0]["total_s"] > 0.0 and imports[1]["total_s"] == 0.0, imports
print("PROBE_OK")
"""

_CASES = {
    module: _IMPORT_PROBE.format(module=module, heavy=_HEAVY)
    for module in (
        "surreal_tpu.session",
        "surreal_tpu.session.config",
        "surreal_tpu.learners",
        "surreal_tpu.main.launch",
        "surreal_tpu.launch.hooks",
        "surreal_tpu.distributed.env_worker",
    )
}
_CASES["first_checkpoint_manager"] = _MANAGER_PROBE


@pytest.mark.parametrize("case", sorted(_CASES))
def test_orbax_is_loaded_by_the_first_manager_and_by_no_import(case):
    proc = subprocess.run(
        [sys.executable, "-c", _CASES[case]],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(_REPO_ROOT),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"probe failed:\n{proc.stdout}\n{proc.stderr}"
    assert "PROBE_OK" in proc.stdout
