"""One vocabulary of phases (utils/phases.py) in three places: the op_name
metadata of the compiled fused programs, the program's spans and the loop
engine's steps on a profile's host plane, and the digest of a triggered
capture that ``diag`` renders."""

import functools
import glob
import json
import os
import re
import time

import jax
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.profile import hlo_op_phases, write_trigger
from surreal_tpu.session.telemetry import Tracer, diag_report, diag_summary
from surreal_tpu.utils.phases import (
    PHASES, SUBPHASES, UNATTRIBUTED, phase, phase_of, subphase_of,
)

PPO_PHASES = ("collect", "prepare", "shuffle", "sgd", "finalize")
DDPG_PHASES = (
    "collect", "replay_insert", "replay_sample", "replay_priority", "update",
)
IMPALA_PHASES = ("collect", "bootstrap", "vtrace", "learn")
# what one Tracer.span may cost with no profile active, per call, on a
# sandbox core shared with five other test workers (measured alone: 2.3 us,
# of which the annotation is 0.4)
SPAN_BUDGET_US = 25.0


def _config(algo: str, folder: str, iters: int = 12) -> Config:
    if algo in ("ppo", "impala"):
        learner = Config(algo=Config(name=algo, horizon=8))
        steps = 8 * 8 * iters
    else:
        learner = Config(
            algo=Config(name="ddpg", horizon=4, updates_per_iter=2),
            replay=Config(kind="prioritized", capacity=256, batch_size=16,
                          start_sample_size=16),
        )
        steps = 4 * 8 * iters
    return Config(
        learner_config=learner,
        env_config=Config(name="jax:lift", num_envs=8),
        session_config=Config(
            folder=folder, total_env_steps=steps,
            metrics=Config(every_n_iters=2, tensorboard=False, console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())


@functools.lru_cache(maxsize=None)
def _compiled_text(algo: str) -> str:
    """The fused iteration of ``algo`` at toy sizes, compiled: its HLO text
    (once a process: two tests read each)."""
    import jax.numpy as jnp

    key = jax.random.key(0)
    if algo in ("ppo", "impala"):
        from surreal_tpu.launch.trainer import Trainer

        trainer = Trainer(_config(algo, "unused"))
        args = (trainer.learner.init(key), trainer.init_loop_state(key), key)
    else:
        from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

        trainer = OffPolicyTrainer(_config("ddpg", "unused"))
        carry, replay_state = trainer.init_loop_state(key)
        args = (
            trainer.learner.init(key), replay_state, carry, key,
            jnp.float32(0.4), jnp.asarray(False), jnp.asarray(True),
        )
    return trainer._train_iter.lower(*args).compile().as_text()


@pytest.mark.parametrize("name", [
    "warmup", "collect/act/inner", "", "Collect",
    # a sub-scope outside the phase's own (SUBPHASES) is refused as a phase is
    "collect/nonsense", "sgd/act", "shuffle/psum", "collect/",
])
def test_a_name_outside_the_vocabulary_is_refused(name):
    with pytest.raises(ValueError, match="vocabulary"):
        phase(name)


def test_vocabulary_is_small_per_algorithm():
    assert set(PPO_PHASES) | set(DDPG_PHASES) | set(IMPALA_PHASES) == set(PHASES)
    assert max(map(len, (PPO_PHASES, DDPG_PHASES, IMPALA_PHASES))) <= 8
    assert UNATTRIBUTED not in PHASES
    with phase("collect"), phase("collect/act"):
        pass


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_iter)/collect/while/body/closed_call/act/tanh", "collect"),
    ("jit(f)/while/body/sgd/transpose(jvp(sgd))/dot_general", "sgd"),
    ("jit(f)/transpose(jvp(sgd))/mul", "sgd"),
    ("jit(f)/vmap(replay_sample)/gather", "replay_sample"),
    ("jit(f)/transpose(jvp(learn))/conv_general_dilated", "learn"),
    ("jit(f)/jvp(vtrace)/while/body/mul", "vtrace"),
    ("jit(f)/learn/psum/psum", "learn"),
    ("jit(update)/add", UNATTRIBUTED),       # a function's name is no phase
    ("jit(learn)/add", UNATTRIBUTED),
    ("jit(f)/collector/add", UNATTRIBUTED),  # whole segments only
    ("jit(f)/replay_insert/replay_insert/scatter", "replay_insert"),
    ("", UNATTRIBUTED),
    (None, UNATTRIBUTED),
])
def test_phase_of_takes_the_first_vocabulary_segment(op_name, expected):
    assert phase_of(op_name) == expected


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_iter)/collect/while/body/closed_call/act/tanh", "collect/act"),
    ("jit(train_iter)/collect/while/body/env/add", "collect/env"),
    ("jit(train_iter)/collect/while/body/add", "collect/rest"),
    ("jit(f)/prepare/gae/while/body/mul", "prepare/gae"),
    ("jit(f)/sgd/transpose(jvp(sgd))/psum/psum", "sgd/psum"),
    ("jit(f)/vmap(replay_sample)/vmap(search)/while/body/lt", "replay_sample/search"),
    ("jit(f)/replay_sample/mass/reduce_sum", "replay_sample/mass"),
    # a sub of another phase's is none of this one's: the refresh after an
    # update sums its blocks inside replay_priority
    ("jit(f)/replay_priority/mass/reduce_sum", "replay_priority/rest"),
    ("jit(f)/act/collect/add", "collect/rest"),      # after the phase only
    ("jit(f)/collect/actor/add", "collect/rest"),    # whole segments only
    ("jit(f)/collect/act/env/add", "collect/act"),   # the first sub
    ("jit(f)/shuffle/gather", "shuffle/rest"),       # a phase without subs
    ("jit(f)/mass/reduce_sum", UNATTRIBUTED),        # a sub outside every phase
    ("", UNATTRIBUTED),
    (None, UNATTRIBUTED),
])
def test_subphase_of_takes_the_first_sub_after_the_phase(op_name, expected):
    assert subphase_of(op_name) == expected


def test_every_sub_scope_site_is_in_the_vocabulary():
    """The sites under ``surreal_tpu/`` and the table are one list."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sites = set()
    for path in glob.glob(os.path.join(here, "surreal_tpu", "**", "*.py"),
                          recursive=True):
        sites |= set(re.findall(r'phase\("(\w+/\w+)"\)', open(path).read()))
    assert sites == {
        f"{top}/{sub}" for top, subs in SUBPHASES.items() for sub in subs
    }
    assert set(SUBPHASES) <= set(PHASES)


@pytest.mark.parametrize("algo,subs", [
    # (the episode sums run after ``learn``, outside ``collect``'s scope:
    # their site enters ``episodes`` alone and the ops are unattributed)
    ("ppo", {"collect/act", "collect/env", "prepare/gae"}),
    ("ddpg", {"collect/act", "collect/env", "replay_sample/mass",
              "replay_sample/search", "replay_sample/gather"}),
    ("impala", {"collect/act", "collect/env"}),
])
def test_sub_scopes_name_ops_of_the_compiled_fused_program(algo, subs):
    text = _compiled_text(algo)
    phases = hlo_op_phases(text)[1]
    found = hlo_op_phases(text, subphase_of)[1]
    assert subs <= set(found.values()), subs - set(found.values())
    # the gap utils/phases.py's docstring and ROADMAP S6 (z) record: the PR
    # that moves the site inside ``collect`` drops this line and that note
    assert "collect/episodes" not in found.values()
    # an op with a phase has a sub of that phase or its rest, and no other
    assert set(found) == set(phases)
    agree = sum(found[k].split("/")[0] == phases[k] for k in phases)
    assert agree >= 0.99 * len(phases), (agree, len(phases))


@pytest.mark.parametrize(
    "algo,phases",
    [("ppo", PPO_PHASES), ("ddpg", DDPG_PHASES), ("impala", IMPALA_PHASES)],
)
def test_every_phase_names_ops_of_the_compiled_fused_program(algo, phases):
    text = _compiled_text(algo)
    seen = {phase_of(n) for n in re.findall(r'op_name="([^"]*)"', text)}
    assert set(phases) <= seen, set(phases) - seen
    # and nothing of the other algorithm's
    assert seen - {UNATTRIBUTED} <= set(phases), seen
    module, ops = hlo_op_phases(text)
    assert module.startswith("jit_")
    assert set(phases) <= set(ops.values())
    # parts nest inside their phase in the op's path
    if algo == "ppo":
        assert re.search(r'op_name="[^"]*/collect/[^"]*/act/', text)
        assert re.search(r'op_name="[^"]*/prepare/[^"]*gae/', text)
    elif algo == "ddpg":
        assert re.search(r'op_name="[^"]*/replay_sample/mass/', text)
    else:
        # the differentiated function's scopes come back wrapped, and resolve
        names = re.findall(r'op_name="([^"]*)"', text)
        assert any("/jvp(learn)/" in n for n in names)
        backward = [n for n in names if "transpose(jvp(learn))" in n]
        assert backward and {phase_of(n) for n in backward} == {"learn"}
        # V-trace's inputs carry no gradient: forward ops only
        assert any("jvp(vtrace)" in n for n in names)
        assert not any("transpose(jvp(vtrace))" in n for n in names)


def test_span_stays_within_its_budget_with_no_profile_active(tmp_path):
    calls = 5000
    for tracer in (Tracer(str(tmp_path)), Tracer(None, enabled=False)):
        with tracer.span("warm"):
            pass
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(calls):
                with tracer.span("x"):
                    pass
            best = min(best, (time.perf_counter() - t0) / calls)
        assert best * 1e6 < SPAN_BUDGET_US, best
        assert "x" in tracer.span_names  # a disabled tracer annotates too
        tracer.close()


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One toy fused PPO run whose trigger file is there from the start:
    a capture of 3 iterations, reduced on close."""
    from surreal_tpu.launch.trainer import Trainer

    folder = str(tmp_path_factory.mktemp("captured"))
    write_trigger(folder, num_iters=3)
    Trainer(_config("ppo", folder)).run()
    return folder


def test_triggered_capture_leaves_a_digest(captured):
    profiles = diag_summary(captured)["profiles"]
    assert len(profiles) == 1 and "digest_error" not in profiles[0]
    event, digest = profiles[0], profiles[0]["digest"]
    assert event["reason"] == "trigger_file"
    # fenced at both ends: the window holds exactly the iterations between
    assert digest["steps"] == event["end_iter"] - event["start_iter"] == 3
    spans = digest["host_spans"]
    # the pass that stops the capture is still open when it stops
    assert spans["iteration"] >= 2 and spans["engine.step"] == 3
    assert spans["engine.boundary"] >= 2 and spans["train_iter"] == 3
    assert spans["metrics-sync"] >= 1
    assert digest["trace_bytes"] > 0 and digest["digest_s"] > 0
    # the CPU has no device plane: no phase split, and it says so
    assert digest["devices"] == 0 and "phases" not in digest
    # the event survives a round trip through the log as JSON
    assert json.loads(json.dumps(event)) == event
    assert not os.path.exists(os.path.join(captured, "profile.trigger"))


def test_diag_renders_the_digest(captured):
    report = diag_report(captured)
    assert "digest of iters" in report and "3 iteration(s)" in report
    assert "engine.step x3" in report and "metrics-sync" in report
    assert "no device plane" in report
    # the fenced span is in the phase table, beside the dispatch span
    assert re.search(r"^\s+cadence\s+\d+", report, re.M)


def test_diag_renders_a_device_digest(tmp_path):
    """With a device plane (hand-built here) diag prints one line per
    phase and the idle time by span."""
    from surreal_tpu.session.profile import reduce_digest

    ops = [(0, 600, "while", "collect"), (100, 500, "fusion.1 f32[8]", "sgd"),
           (800, 1000, "copy.2", UNATTRIBUTED)]
    digest = reduce_digest(
        {"/device:TPU:0": ops}, [(550, 900, "metrics-sync")], steps=2
    )
    digest.update(host_spans={"iteration": 2}, trace_bytes=10, digest_s=0.1)
    tracer = Tracer(str(tmp_path))
    tracer.event("profile", dir="d", reason="trigger_file", start_iter=4,
                 end_iter=6, digest=digest)
    tracer.close()
    report = diag_report(str(tmp_path))
    assert re.search(r"sgd\s+0\.000\s+50\.0%\s+fusion\.1 f32\[8\]", report)
    assert re.search(r"unattributed\s+0\.000\s+25\.0%", report)
    assert "idle by span: metrics-sync" in report


def test_diag_renders_the_finer_tables(tmp_path):
    """Under a phase its sub-scopes and rest, beside it its count of op
    events and those under 1 us; under a model part its phases where it has
    more than one; and a table of the Pallas kernels."""
    from surreal_tpu.session.profile import reduce_digest

    U = UNATTRIBUTED
    ops = [
        (0, 10_000_000, "while.1", "collect", U, "collect/rest", None),
        (1_000_000, 4_000_000, "fusion.2", "collect", "attn", "collect/act", None),
        (4_000_000, 4_000_400, "fusion.3", "collect", U, "collect/env", None),
        (5_000_000, 9_000_000, "held_experts_live.3", "collect", "moe_experts",
         "collect/act", "held_experts_live"),
        (11_000_000, 15_000_000, "held_experts_live.17", "sgd", "moe_experts",
         "sgd/rest", "held_experts_live"),
        (15_000_000, 16_000_000, "fusion.5", "sgd", "dense_ffn", "sgd/rest", None),
    ]
    digest = reduce_digest({"/device:TPU:0": ops}, [], steps=1)
    digest.pop("ops")  # digest_capture moves the rows to ops.json
    digest.update(host_spans={"iteration": 1}, trace_bytes=10, digest_s=0.1)
    tracer = Tracer(str(tmp_path))
    tracer.event("profile", dir="d", reason="trigger_file", start_iter=4,
                 end_iter=5, digest=digest)
    tracer.close()
    report = diag_report(str(tmp_path))
    assert re.search(r"collect\s+10\.000\s+66\.7%.*; 4\.0 ops, 1\.0 under 1 us "
                     r"own 0\.000 ms", report)
    assert re.search(r"\n\s+act\s+7\.000\n\s+rest\s+3\.000\n\s+env\s+0\.000\n",
                     report)
    # moe_experts runs in two phases and says so; dense_ffn in one and does not
    assert re.search(r"moe_experts\s+8\.000\s+53\.3%[^\n]*\n\s+collect\s+4\.000"
                     r"\n\s+sgd\s+4\.000\n", report)
    assert re.search(r"dense_ffn\s+1\.000\s+6\.7%[^\n]*\n\s+kernel\s", report)
    assert re.search(r"held_experts_live\s+8\.000\s+2\.0\s+2\s+moe_experts: "
                     r"(collect 4\.000, sgd 4\.000|sgd 4\.000, collect 4\.000)",
                     report)


def test_a_digest_says_how_long_its_parse_held_the_process(captured, tmp_path):
    """The capture's parser is one foreign call that keeps the interpreter:
    the digest reports its seconds, and the session excuses the tiers that
    live on the learner thread for them (``launch/hooks.py`` hands the
    profiler ``ops.excuse_pause``; found on the chip, PR 44: 35 s of it read
    as a dead engine tier and opened an incident)."""
    from surreal_tpu.launch.hooks import SessionHooks
    from surreal_tpu.learners import build_learner
    from surreal_tpu.envs import make_env
    from surreal_tpu.session.profile import digest_capture

    (trace_dir,) = glob.glob(os.path.join(captured, "telemetry", "profiles", "*"))
    held = []
    digest = digest_capture(trace_dir, {}, (), on_parsed=held.append)
    assert len(held) == 1 and 0.0 <= held[0] <= digest["digest_s"]
    cfg = _config("ppo", str(tmp_path))
    hooks = SessionHooks(
        cfg, build_learner(cfg.learner_config, make_env(cfg.env_config).specs)
    )
    try:
        assert hooks.profile._on_hold == hooks.ops.excuse_pause
    finally:
        hooks.close()
