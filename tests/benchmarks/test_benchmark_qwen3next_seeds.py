"""``ppo_lift_qwen3next_16x1024``'s seeds as the chip read them (PR 63,
``qwen3next_seed_readings.json`` beside this file: one reading a whole run of
the harness's command; ``session_seed_readings.json`` is not edited): twelve
seeds or more, every compared row of every run inside today's limits, some
runs traced; the rate follows the seed's routers by more than half of the
rate's bound end to end, so the cell launches from a list of seeds of one
rate, found ``benchmarks/README.md``'s way (every listed seed's whole run
read, two of them traced as well); what each control of ``TERMS`` read at the
cell's own size; the two attention forms' readings; and the review round's
(``review_round``): half a minibatch's envs left out of one optimizer step,
the precision below on two more listed seeds, and how far two runs of the
same reference part by precision alone. Held here, on the CPU, with no
chip."""

import json
import os
import statistics

import pytest

from benchmarks.harness import manifest

CELL = "ppo_lift_qwen3next_16x1024"
RATE = "env_steps_per_s"
with open(os.path.join(os.path.dirname(__file__), "qwen3next_seed_readings.json")) as fh:
    FILE = json.load(fh)
READINGS = FILE[CELL]
CONTROLS = FILE["controls"]
REVIEW = FILE["review_round"]
CHANGE_ROWS = {
    "all": "learn/param_change", "leaf_moved": "learn/leaf_moved",
    **{g: f"learn/param_change/{g}" for g in
       ("gdn", "attn", "experts", "shared", "norms", "ends")},
}
ref = manifest.load_reference("ppo_qwen3next_ref")
LISTED = manifest.load_cell(CELL).get("session_seeds", [])
(BOUND,) = [
    m["bound"] for m in manifest.metrics_of("end_to_end", CELL) if m["name"] == RATE
]


def _failed(rows) -> list:
    """The rows of one reading that lie outside today's limits."""
    out = []
    for name, tol in ref.TOL.items():
        row = rows.get(name)
        if row is None:     # a control read on the forwards alone
            continue
        err = row.get("p999_abs_err", row["max_abs_err"])
        # null: a NaN when it was read (the state of ``l2_norm``'s control
        # overflows), which is no pass
        if err is None or err > tol["atol"] + tol["rtol"] * row["scale"]:
            out.append(name)
    if rows["route/agree_share"]["value"] < ref.AGREE_SHARE_MIN:
        out.append("route/agree_share")
    if rows["route/tie_gap"]["value"] > ref.TIE_GAP:
        out.append("route/tie_gap")
    if rows["route/score_agree"]["value"] < ref.SCORE_AGREE_MIN:
        out.append("route/score_agree")
    for name in ("moe/overflow", "act/replay_is_rollout", "act/wrap_is_fresh",
                 "collect/rollout_is_session", "session/repeats",
                 "learn/router_still", "learn/early_stopped"):
        if name in rows and not rows[name]["ok"]:
            out.append(name)
    return out


def test_twelve_seeds_were_read_and_none_fails_under_todays_limits():
    seeds = {r["seed"] for r in READINGS}
    assert len(seeds) >= 12, sorted(seeds)
    for r in READINGS:
        assert set(ref.TOL) <= set(r["rows"]), r["seed"]
        assert _failed(r["rows"]) == [], (r["seed"], _failed(r["rows"]))
        # every other check of the harness held when the run was read; the
        # reference's verdict then was by the limits of the day (the first
        # runs met ``route/agree_share``'s placeholder 0.80 before any seed
        # had been read: ppo_qwen3next_ref.py, AGREE_SHARE_MIN)
        assert set(r["checks_false"]) <= {"reference_agrees"}, r["seed"]
        assert set(r["not_ok"]) <= {"route/agree_share"}, (r["seed"], r["not_ok"])
        assert r["memory_peak_bytes"] >= 4e9
    assert sum(1 for r in READINGS if r["traced"]) >= 2
    # the last runs, read under today's limits, were correct as they stood
    assert sum(1 for r in READINGS if r["correct"]) >= 6


def test_the_rate_follows_the_seed_by_more_than_half_the_bound():
    """Why the cell lists seeds: no seed was found failing, but the untraced
    rates part by more than the driver's two sets of runs may."""
    rates = [r[RATE] for r in READINGS if not r["traced"] and r[RATE]]
    assert len(rates) >= 12
    assert (max(rates) - min(rates)) / statistics.median(rates) > BOUND / 2


def test_every_listed_seed_was_read_whole_and_the_list_reads_one_rate():
    assert len(LISTED) >= 6 and len(set(LISTED)) == len(LISTED)
    by_seed: dict = {}
    for r in READINGS:
        by_seed.setdefault(r["seed"], []).append(r)
    rates = []
    for seed in LISTED:
        runs = by_seed.get(seed)
        assert runs, f"seed {seed} is listed and has no reading"
        untraced = [r[RATE] for r in runs if not r["traced"] and r[RATE]]
        assert untraced, f"seed {seed} has no untraced rate"
        rates += untraced
        for r in runs:
            assert _failed(r["rows"]) == [], seed
            # a listed seed is its own session's seed when it was read
            assert r["session_seed"] == seed
    width = (max(rates) - min(rates)) / statistics.median(rates)
    assert width <= BOUND / 2, (min(rates), max(rates), width)
    traced = {s for s in LISTED if any(r["traced"] for r in by_seed[s])}
    assert len(traced) >= 2, sorted(traced)
    assert "session_seeds_why" in manifest.load_cell(CELL)


def test_the_sound_reference_of_the_controls_seed_passes():
    assert _failed(CONTROLS["sound"]) == []


@pytest.mark.parametrize("control", ref.TERMS)
def test_a_changed_term_read_on_the_chip_fails_under_todays_limits(control):
    """Each control of ``TERMS`` at the cell's own size (16 x 1024, the
    published widths), through ``compare`` like the sound reference: not
    correct, by one of the cell's limits."""
    failed = _failed(CONTROLS[control])
    assert failed, control
    assert [n for n in failed if n.startswith(("act/", "prepare/", "route/"))], failed


def test_the_limits_lie_between_the_sound_readings_and_the_precision_below():
    """A forward row's limit lies above every sound seed's reading, with a
    fifth of room at the least, and under what ``all_bf16`` read on the
    controls' seed, for the rows that control fails by; the learn step's
    ``param_change`` lies between its largest reading and 1."""
    below = CONTROLS["all_bf16"]
    failed = [n for n in _failed(below) if n in ref.TOL]
    assert failed
    for name in failed:
        key = "p999_abs_err" if "p999_abs_err" in below[name] else "max_abs_err"
        sound = max(r["rows"][name][key] for r in READINGS)
        limit = ref.TOL[name]["atol"] + ref.TOL[name]["rtol"] * below[name]["scale"]
        assert 1.2 * sound <= limit < below[name][key], (name, sound, limit)
    # the one forward row the control passes: its limit lies above the
    # control's reading and under every changed term's (ppo_qwen3next_ref.TOL)
    assert set(ref.TOL) & {n for n in below if n.startswith(("act/", "prepare/"))} - set(failed) == {"prepare/targets"}
    agree = [r["rows"]["route/agree_share"]["value"] for r in READINGS]
    assert below["route/agree_share"]["value"] < ref.AGREE_SHARE_MIN < min(agree)
    changes = [r["rows"]["learn/param_change"]["max_abs_err"] for r in READINGS]
    assert max(changes) < ref.TOL["learn/param_change"]["atol"] < 1.0


def _change_rows_failed(read: dict) -> list:
    return [
        row for key, row in CHANGE_ROWS.items()
        if read[key] > ref.TOL[row]["atol"]
    ]


def test_half_a_minibatch_left_out_of_the_first_step_fails_and_of_the_last_passes():
    """The one fault only ``learn/param_change`` can see (the first step's
    rows and the forwards are the check's own programs'), planted at the
    cell's size on a listed seed: a step that takes half its minibatch's
    envs. In the first of the four steps it fails by every limit of the
    change, whole, by group and by leaf. **In the last step it passes them
    all**: it reads 0.156 whole where sound seeds read 0.109-0.191, and no
    limit on this row can part the two (``PERF.md`` section 7 says what
    would)."""
    planted = REVIEW["planted"]
    first = planted["half_left_out_step0"]["program_with_plant_vs_sound"]
    last = planted["half_left_out_step3"]["program_with_plant_vs_sound"]
    assert sorted(_change_rows_failed(first)) == sorted(CHANGE_ROWS.values())
    assert _change_rows_failed(last) == []
    sound = [r["rows"]["learn/param_change"]["max_abs_err"] for r in READINGS]
    assert min(sound) < last["all"] < max(sound) < first["all"]
    # the plant is a fault of the steps and of nothing else
    for read in (first, last):
        assert read["unmoved"] == 0 and read["moved_alone"] == 0
    assert REVIEW["planted_seed"] in LISTED


def test_precision_alone_moves_the_change_by_what_the_program_reads():
    """Why a sound program reads a tenth and more on every seed: the same
    reference in the precision below (same code, minibatches and order)
    parts from itself in float32 by as much as the program does, whole and
    in every group of the layers."""
    witness = REVIEW["precision_witness"]
    alone = witness["all_bf16_vs_sound_reference"]
    program = witness["program_vs_sound"]
    assert alone["all"] == pytest.approx(program["all"], rel=0.1)
    for group in ("gdn", "attn", "experts", "shared", "norms"):
        assert 0.7 * program[group] < alone[group] < 1.5 * program[group], group
    assert _change_rows_failed(program) == []


def test_the_precision_below_fails_on_every_seed_it_was_read_on():
    """``all_bf16``'s forwards on three seeds (the controls' and two listed
    ones): not correct on each, by the eight forward rows the limits were
    set under and by ``route/agree_share``; the smallest of the three
    readings lies above each of those limits."""
    reads = [CONTROLS["all_bf16"]] + [
        r["all_bf16"] for r in REVIEW["all_bf16_forwards"].values()
    ]
    sounds = [CONTROLS["sound"]] + [
        r["sound"] for r in REVIEW["all_bf16_forwards"].values()
    ]
    assert len(reads) == 3
    rows = sorted(
        n for n in ref.TOL
        if n.startswith(("act/", "prepare/")) and n != "prepare/targets"
    )
    assert len(rows) == 8
    for below, sound in zip(reads, sounds):
        failed = _failed(below)
        assert set(rows) | {"route/agree_share"} <= set(failed), failed
        assert not [n for n in _failed(sound) if n.startswith(("act/", "prepare/", "route/"))]
    for name in rows:
        key = "p999_abs_err" if "p999_abs_err" in reads[0][name] else "max_abs_err"
        assert min(r[name][key] for r in reads) > ref.TOL[name]["atol"], name
    assert max(r["route/agree_share"]["value"] for r in reads) < ref.AGREE_SHARE_MIN


def test_the_two_attention_forms_were_read_at_a_head_of_256():
    """``models/gdn_moe.py::ATTENTION_KERNELS``: the Pallas pair's forward is
    the faster, its ``jax.grad`` within a twentieth of the ``lax`` form's,
    and 512 queries a block do not fit the kernels' VMEM."""
    from surreal_tpu.models import gdn_moe

    forms = {(f["block"], f["kernels"]): f for f in FILE["attention_forms"]}
    lax, pallas = forms[(256, False)], forms[(256, True)]
    assert pallas["fwd_ms"] < 0.7 * lax["fwd_ms"]
    assert pallas["grad_ms"] < 1.05 * lax["grad_ms"]
    assert "error" in forms[(512, True)]
    assert gdn_moe.ATTENTION_KERNELS and gdn_moe.QUERY_BLOCK == 256
