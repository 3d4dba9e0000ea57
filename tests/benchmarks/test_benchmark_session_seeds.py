"""A cell whose check has been found failing on some seeds lists the
``session_seeds`` whose whole run was read ``correct`` on the chip, and
``session_seed_readings.json`` beside this file holds each reading: the run's
``correct`` and ``failed``, its rate, whether it was traced, every compared
row's error beside its limit; and the same for every seed found failing. A
cell that was surveyed and has no failing seed keeps its readings there and
lists nothing. A list is also held to one rate: where the work of a run follows
its seed (``ppo_lift_laguna_16x1024``'s routers, which nothing balances), the
listed seeds are those that read one rate, so that two sets of runs on two seeds
do not part by more than the check's spread rule admits. The lists are held to
the readings here, on the CPU, with no chip."""

import json
import os
import statistics

import pytest

from benchmarks.harness import manifest

with open(os.path.join(os.path.dirname(__file__), "session_seed_readings.json")) as fh:
    READINGS = {k: v for k, v in json.load(fh).items() if not k.startswith("_")}
CELLS = sorted(READINGS)
LISTED = [c for c in CELLS if manifest.load_cell(c).get("session_seeds")]
TRACED_MIN = 2  # of a list's seeds, read with --trace 1 as well
RATE = "env_steps_per_s"


def untraced_rates(cell: str) -> dict:
    """Each listed seed's untraced readings of the rate."""
    listed = manifest.load_cell(cell)["session_seeds"]
    return {
        seed: [
            r[RATE] for r in READINGS[cell]
            if r["seed"] == seed and not r["traced"] and r[RATE]
        ]
        for seed in listed
    }


def test_a_surveyed_cell_lists_seeds_only_where_one_was_found_failing():
    assert LISTED
    for cell in CELLS:
        failing = [r["seed"] for r in READINGS[cell] if not r["correct"]]
        assert bool(failing) == (cell in LISTED), (cell, failing)


@pytest.mark.parametrize("cell", LISTED)
def test_every_listed_seed_was_read_correct_on_the_chip(cell):
    listed = manifest.load_cell(cell)["session_seeds"]
    by_seed: dict = {}
    for r in READINGS[cell]:
        by_seed.setdefault(r["seed"], []).append(r)
    for seed in listed:
        runs = by_seed.get(seed)
        assert runs, f"{cell}: seed {seed} is listed and has no reading"
        for r in runs:
            assert r["correct"] is True and r["failed"] == 0, (cell, seed)
            assert r["rows"], (cell, seed)
    traced = {s for s in listed if any(r["traced"] for r in by_seed[s])}
    assert len(traced) >= TRACED_MIN, (cell, sorted(traced))


@pytest.mark.parametrize("cell", LISTED)
def test_no_seed_found_failing_is_listed(cell):
    cell_file = manifest.load_cell(cell)
    failing = {r["seed"] for r in READINGS[cell] if not r["correct"]}
    assert not failing & set(cell_file["session_seeds"])
    # and the file names each of them where it says why it lists seeds
    for seed in failing:
        assert str(seed) in cell_file["session_seeds_why"], (cell, seed)


@pytest.mark.parametrize("cell", LISTED)
def test_every_listed_seed_has_an_untraced_rate(cell):
    missing = [seed for seed, rates in untraced_rates(cell).items() if not rates]
    assert not missing, (cell, missing)


@pytest.mark.parametrize("cell", LISTED)
def test_a_lists_seeds_read_one_rate(cell):
    """End to end, the listed seeds' untraced rates lie within half of the
    rate's bound: the driver's two sets of runs each take a seed of their own,
    and it refuses a bound its runs spread by more than half of."""
    (bound,) = [
        m["bound"] for m in manifest.metrics_of("end_to_end", cell) if m["name"] == RATE
    ]
    rates = [x for per_seed in untraced_rates(cell).values() for x in per_seed]
    width = (max(rates) - min(rates)) / statistics.median(rates)
    assert width <= bound / 2, (cell, min(rates), max(rates), width, bound)
