"""Chaos-campaign tests (ISSUE 20): the schedule generator's determinism
and co-fire constraints, the invariant oracles over synthetic run
records, shrinker convergence to a known-minimal failing plan, and a
budgeted real mini-campaign (two seeded runs, zero violations) with a
slow-marked soak mode over every profile.

The real chaos e2e coverage strategy: the mini-campaign here runs REAL
trainers under multi-site schedules every tier-1 pass, which is why the
single-purpose chaos e2e tests it subsumes (the lineage-chaos run, the
SEED nan rollback run) moved to the slow tier — one budget line instead
of three overlapping ones.
"""

import copy
import json
import os

import pytest

from surreal_tpu.chaos import campaign as chaos_campaign
from surreal_tpu.chaos import invariants as inv
from surreal_tpu.chaos import schedule as chaos_schedule
from surreal_tpu.chaos.invariants import RunRecord, evaluate
from surreal_tpu.utils import faults


# ---------------------------------------------------------------- schedule

def test_schedule_deterministic_per_seed_and_profile():
    for profile in chaos_schedule.PROFILES:
        for seed in (0, 1, 2, 17):
            a = chaos_schedule.generate_schedule(seed, profile)
            b = chaos_schedule.generate_schedule(seed, profile)
            assert a == b, f"({profile}, {seed}) not deterministic"
            assert a["plan"], "empty schedule"
    # different seeds draw different schedules (the campaign sweeps)
    plans = {
        json.dumps(chaos_schedule.generate_schedule(s, "seed_gateway")["plan"])
        for s in range(8)
    }
    assert len(plans) > 4


def test_schedule_respects_constraints():
    for profile, meta in chaos_schedule.PROFILES.items():
        for seed in range(25):
            sched = chaos_schedule.generate_schedule(seed, profile)
            plan = sched["plan"]
            intensity = sched["intensity"]
            # every spec validates against the registry (site AND kind)
            faults.FaultInjector(plan)
            # sites drawn only from the profile's wired topology
            assert {e["site"] for e in plan} <= set(meta["sites"])
            # kill cap: 1 + (intensity > 0), at most one kill per site
            kills = [e for e in plan
                     if e["kind"] in chaos_schedule.KILL_KINDS]
            assert len(kills) <= 1 + (1 if intensity > 0 else 0)
            assert len({e["site"] for e in kills}) == len(kills)
            # at most one nan_state, only on nan_ok profiles, never
            # together with kill_stage (the exclusive group)
            nans = [e for e in plan if e["kind"] == "nan_state"]
            assert len(nans) <= (1 if meta["nan_ok"] else 0)
            pairs = {(e["site"], e["kind"]) for e in plan}
            for group in chaos_schedule.EXCLUSIVE_GROUPS:
                assert len(pairs & group) <= 1
            # delay budget
            delay_ms = sum(
                e.get("ms", 0.0) * e.get("times", 1) for e in plan
                if e["kind"] in chaos_schedule.DELAY_KINDS
            )
            assert delay_ms <= chaos_schedule.DELAY_BUDGET_MS
            # no run-ending kinds in a campaign schedule
            assert "sigterm" not in {e["kind"] for e in plan}


def test_schedule_campaign_covers_ten_sites():
    """The acceptance floor: 25 seeds over the stock profiles must DRAW
    >= 10 distinct sites (firing is checked by the real campaign; a
    generator that can't even draw the spread would cap coverage)."""
    drawn = set()
    profiles = list(chaos_schedule.PROFILES)
    for seed in range(25):
        sched = chaos_schedule.generate_schedule(
            seed, profiles[seed % len(profiles)]
        )
        drawn.update(e["site"] for e in sched["plan"])
    assert len(drawn) >= 10, sorted(drawn)


# ----------------------------------------------------------------- oracles

def _close_event(**over):
    base = {
        "type": "experience_close", "quiesced": 1.0,
        "sent_rows": 100.0, "ingested_rows": 90.0, "dropped_rows": 6.0,
        "inflight_rows": 4.0, "resends": 0.0, "rehellos": 0.0,
        "dead_links": 0.0, "respawns": 0.0, "num_shards": 2.0,
        "shards_live": 2.0,
    }
    base.update(over)
    return base


def _rec(**over):
    base = dict(folder="/nonexistent", plan=[], metrics={}, events=[],
                counts={}, residue={"threads": [], "shm": [], "fds": []})
    base.update(over)
    return RunRecord(**base)


def test_oracle_exactly_once_conservation():
    ok = _rec(events=[_close_event()])
    assert inv.oracle_exactly_once(ok)["violations"] == []
    # duplication: ingested + dropped > sent
    dup = _rec(events=[_close_event(ingested_rows=99.0)])
    v = inv.oracle_exactly_once(dup)["violations"]
    assert len(v) == 1 and "duplication" in v[0]["what"]
    # silent loss: sent - ingested - dropped > inflight
    loss = _rec(events=[_close_event(inflight_rows=0.0)])
    v = inv.oracle_exactly_once(loss)["violations"]
    assert len(v) == 1 and "silent loss" in v[0]["what"]
    # relaxations say WHY, never silently pass
    rekeyed = _rec(events=[_close_event(rehellos=2.0, ingested_rows=999.0)])
    r = inv.oracle_exactly_once(rekeyed)
    assert r["violations"] == [] and "re-keyed" in r["skipped"]
    wedged = _rec(events=[_close_event(quiesced=0.0, ingested_rows=999.0)])
    assert "quiesced" in inv.oracle_exactly_once(wedged)["skipped"]
    none = _rec()
    assert "no experience plane" in inv.oracle_exactly_once(none)["skipped"]


def test_oracle_counted_never_silent():
    plan = [{"site": "env_worker.step", "kind": "kill_worker",
             "at": 3, "times": 1}]
    silent = _rec(plan=plan, counts={"env_worker.step": 10},
                  metrics={"workers/respawns": 0.0})
    v = inv.oracle_counted_never_silent(silent)["violations"]
    assert len(v) == 1 and v[0]["counter"] == "workers/respawns"
    counted = _rec(plan=plan, counts={"env_worker.step": 10},
                   metrics={"workers/respawns": 1.0})
    assert inv.oracle_counted_never_silent(counted)["violations"] == []
    # an undelivered fault (site never reached its window) demands nothing
    undelivered = _rec(plan=plan, counts={"env_worker.step": 2},
                       metrics={"workers/respawns": 0.0})
    assert inv.oracle_counted_never_silent(undelivered)["violations"] == []


def test_oracle_monotone_versions():
    rows = lambda *vals: [
        {"type": "metrics", "values": {"param/publishes": v}} for v in vals
    ]
    assert inv.oracle_monotone_versions(
        _rec(events=rows(1.0, 2.0, 2.0, 5.0)))["violations"] == []
    v = inv.oracle_monotone_versions(
        _rec(events=rows(3.0, 1.0)))["violations"]
    assert len(v) == 1 and v[0]["counter"] == "param/publishes"
    # replica param version regression (same respawn epoch) is flagged
    tiers = [
        {"type": "serving_tier", "fleet/respawns": 0.0,
         "replicas": {"0": {"state": "alive", "param_version": 4}}},
        {"type": "serving_tier", "fleet/respawns": 0.0,
         "replicas": {"0": {"state": "alive", "param_version": 2}}},
    ]
    v = inv.oracle_monotone_versions(_rec(events=tiers))["violations"]
    assert len(v) == 1 and "regressed" in v[0]["what"]
    # ...but a respawn between snapshots legitimizes the reset
    tiers[1]["fleet/respawns"] = 1.0
    assert inv.oracle_monotone_versions(_rec(events=tiers))["violations"] == []


def test_oracle_residue_and_fault_surfacing():
    leaky = _rec(residue={"threads": ["xp-shard-0"], "shm": [], "fds": []})
    v = inv.oracle_residue(leaky)["violations"]
    assert len(v) == 1 and "thread" in v[0]["what"]
    assert inv.oracle_residue(_rec())["violations"] == []

    plan = [{"site": "trace.emit", "kind": "drop_span", "at": 1, "times": 1}]
    surfaced = _rec(
        plan=plan, counts={"trace.emit": 5},
        events=[{"type": "fault", "site": "trace.emit",
                 "kind": "drop_span"}],
    )
    assert inv.oracle_fault_surfacing(surfaced)["violations"] == []
    vanished = _rec(plan=plan, counts={"trace.emit": 5})
    v = inv.oracle_fault_surfacing(vanished)["violations"]
    assert len(v) == 1 and v[0]["site"] == "trace.emit"


def test_evaluate_flags_crashed_run():
    verdict = evaluate(_rec(error="RuntimeError: boom"), oracles=())
    assert len(verdict["violations"]) == 1
    assert verdict["violations"][0]["oracle"] == "run_completed"


# ---------------------------------------------------------------- shrinker

def _stub_runner_factory(bad_pair):
    """Runner whose record 'fails' (via the broken oracle below) iff the
    plan still contains the poisoned (site, kind) spec — every fault
    reads as delivered so the oracles see the whole plan."""
    calls = []

    def runner(sched, folder):
        calls.append([copy.deepcopy(e) for e in sched["plan"]])
        return _rec(
            plan=[dict(e) for e in sched["plan"]],
            counts={e["site"]: e["at"] + 5 for e in sched["plan"]},
        )

    def broken_oracle(rec):
        bad = [e for e in rec.plan
               if (e["site"], e["kind"]) == bad_pair]
        return {"name": "broken", "skipped": None, "violations": [
            {"oracle": "broken", "what": "synthetic", **e} for e in bad
        ]}

    return runner, broken_oracle, calls


def test_shrinker_converges_to_known_minimal_plan():
    """A deliberately-broken oracle (fails iff the poisoned spec is
    still in the plan) must shrink any containing schedule to EXACTLY
    that one spec, and do it deterministically on replay."""
    bad = ("trace.emit", "drop_span")
    profile = "seed_experience"
    # find a stock schedule containing the poisoned pair — the shrinker
    # must reduce a REAL generator draw, not a hand-made toy
    seed = next(
        s for s in range(100)
        if any((e["site"], e["kind"]) == bad
               for e in chaos_schedule.generate_schedule(s, profile)["plan"])
    )
    sched = chaos_schedule.generate_schedule(seed, profile)
    assert len(sched["plan"]) > 1, "need a multi-spec plan to shrink"

    runner, broken_oracle, _ = _stub_runner_factory(bad)

    def still_fails(plan):
        rec = runner(dict(sched, plan=plan), "/nonexistent")
        return bool(evaluate(rec, (broken_oracle,))["violations"])

    minimal, runs = chaos_campaign.shrink(sched["plan"], still_fails)
    assert len(minimal) == 1
    assert (minimal[0]["site"], minimal[0]["kind"]) == bad
    assert runs <= 32
    # deterministic replay: same schedule, same shrink trajectory
    minimal2, runs2 = chaos_campaign.shrink(sched["plan"], still_fails)
    assert minimal2 == minimal and runs2 == runs


def test_campaign_records_shrunk_failure_with_replay_key(tmp_path):
    """run_campaign over the stub runner + broken oracle: the failing
    schedule lands in failures[] with its 1-minimal plan and (profile,
    seed) replay key, and the campaign events hit the telemetry spine."""
    bad = ("trace.emit", "drop_span")
    profile = "seed_experience"
    seed0 = chaos_schedule.generate_schedule(0, profile)
    runner, broken_oracle, _ = _stub_runner_factory(bad)
    artifact = chaos_campaign.run_campaign(
        seeds=3, base_dir=str(tmp_path), profiles=[profile],
        oracles=(broken_oracle,), runner=runner, log=lambda *_: None,
    )
    assert artifact["gauges"]["chaos/schedules"] == 3.0
    poisoned = [
        s["seed"] for s in artifact["schedules"]
        if any((e["site"], e["kind"]) == bad for e in s["plan"])
    ]
    assert {f["seed"] for f in artifact["failures"]} == set(poisoned)
    for fail in artifact["failures"]:
        assert fail["replay"] == {"profile": profile, "seed": fail["seed"]}
        assert len(fail["minimal_plan"]) == 1
        assert (fail["minimal_plan"][0]["site"],
                fail["minimal_plan"][0]["kind"]) == bad
    # determinism end to end: schedule 0 in the artifact IS the generator
    # draw for (profile, 0)
    assert artifact["schedules"][0]["plan"] == seed0["plan"]
    # the campaign mirrored onto the telemetry spine
    events = chaos_campaign._read_events(str(tmp_path))
    kinds = [e.get("type") for e in events]
    assert "chaos_campaign" in kinds
    assert kinds.count("chaos_violation") == len(artifact["failures"])


# ------------------------------------------------------- real mini-campaign

def _assert_clean(artifact):
    for s in artifact["schedules"]:
        assert s["violations"] == 0, (s["seed"], s["profile"], s["oracles"])
    assert artifact["failures"] == []


def test_mini_campaign_two_real_runs_zero_violations(tmp_path):
    """The tier-1 budget line: two seeded REAL runs (SEED + experience
    plane, host off-policy + spill WAL) under generated multi-site
    schedules, every invariant oracle clean. Deterministic by seed —
    a red run here replays with exactly (profile, seed)."""
    artifact = chaos_campaign.run_campaign(
        seeds=2, base_dir=str(tmp_path),
        profiles=["seed_experience", "ddpg_spill"],
        log=lambda *_: None,
    )
    assert artifact["gauges"]["chaos/schedules"] == 2.0
    assert artifact["gauges"]["chaos/faults_injected"] >= 2
    assert len(artifact["sites_covered"]) >= 2
    _assert_clean(artifact)
    # the artifact round-trips through the committed-file writer
    out = tmp_path / "campaign.json"
    chaos_campaign.write_artifact(str(out), artifact)
    assert json.loads(out.read_text())["kind"] == "chaos_campaign"


@pytest.mark.slow
def test_soak_campaign_all_profiles(tmp_path):
    """Soak mode: six seeds across every stock profile (gateway fleet
    included), zero violations. The committed 25-seed artifact is the
    full-strength version of this run."""
    artifact = chaos_campaign.run_campaign(
        seeds=6, base_dir=str(tmp_path), log=lambda *_: None,
    )
    assert artifact["gauges"]["chaos/schedules"] == 6.0
    assert set(p for s in artifact["schedules"]
               for p in [s["profile"]]) == set(chaos_schedule.PROFILES)
    _assert_clean(artifact)


def test_chaos_cli_wiring():
    """`surreal_tpu chaos` parses and exposes the campaign knobs."""
    from surreal_tpu.main import launch as main_launch

    parser_main = main_launch.main
    # parse-only probe: a bogus algo must be rejected by argparse
    with pytest.raises(SystemExit):
        parser_main(["chaos", "nonesuch", "--seeds", "1"])
