"""What the command does when it cannot measure, and the rule that keeps the
rehearsals a file a cell: without ``--rehearse`` and without a TPU the
command exits non-zero and prints no result; a cell it does not know is an
error; and every workload of ``BENCHMARK.json`` has its
``test_benchmark_rehearse_<cell>.py`` (``benchmark_rehearsal.py`` says what
such a file holds)."""

import os

import benchmark_rehearsal as rehearsal

CELLS = rehearsal.CELLS


def test_no_tpu_means_no_result():
    proc = rehearsal.run_cell(CELLS[0], 0)
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_unknown_cell_is_an_error():
    proc = rehearsal.run_cell("no_such_cell", 0, "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_every_cell_has_a_rehearsal_file_and_no_file_is_without_its_cell():
    """A PR that adds a cell adds its ``test_benchmark_rehearse_<cell>.py``
    here (two lines: the import and ``CELL``) and edits no file that is."""
    files = rehearsal.rehearsal_files()
    missing = sorted(set(CELLS) - set(files))
    assert not missing, (
        f"add tests/benchmarks/test_benchmark_rehearse_<cell>.py for {missing}: "
        'from benchmark_rehearsal import *  # noqa: F401,F403 ; CELL = "<cell>"'
    )
    assert not sorted(set(files) - set(CELLS)), "rehearsal files of no workload"


def test_a_child_takes_its_workers_own_core(monkeypatch):
    cores = sorted(os.sched_getaffinity(0))
    monkeypatch.delenv("PYTEST_XDIST_WORKER", raising=False)
    assert rehearsal.worker_core() == cores[-1]
    for n in range(len(cores) + 2):
        monkeypatch.setenv("PYTEST_XDIST_WORKER", f"gw{n}")
        assert rehearsal.worker_core() == cores[n % len(cores)]
