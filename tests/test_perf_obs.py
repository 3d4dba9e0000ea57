"""Performance observability (ISSUE 6): cost/MFU accounting, cross-process
trace correlation and on-demand profiling.

The acceptance surface: a fresh headline-workload session's diag reports
per-program FLOPs/bytes, an MFU estimate, and (for the SEED topology) a
stitched cross-process timeline with per-hop latency percentiles; a
trigger-file capture produces a trace artifact under
``<folder>/telemetry/profiles/``. Zero-extra-sync proofs live in
tests/test_telemetry.py next to the existing transfer-guard suite.
"""

import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.session.config import Config
from surreal_tpu.session.costs import (
    CostAccountant,
    GAUGE_REGISTRY,
    PeakSpec,
    program_costs,
    resolve_peak_spec,
)
from surreal_tpu.session.default_configs import base_config
from surreal_tpu.session.telemetry import (
    Tracer,
    diag_report,
    diag_summary,
    latency_percentiles,
)


# -- cost extraction -----------------------------------------------------------

def test_program_costs_on_tiny_jitted_program():
    """XLA's cost model of a known matmul: flops within 2x of the
    analytic 2*M*N*K (the HLO pass counts fused elementwise ops too),
    bytes > the operand sizes, AI consistent with flops/bytes."""
    f = jax.jit(lambda a, b: (a @ b).sum())
    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    c = program_costs(f, a, b)
    assert c is not None
    analytic = 2 * 32 * 64 * 16
    assert analytic / 2 <= c["flops"] <= analytic * 2, c
    assert c["bytes_accessed"] >= (32 * 64 + 64 * 16) * 4
    assert c["arithmetic_intensity"] == pytest.approx(
        c["flops"] / c["bytes_accessed"]
    )


def test_program_costs_none_on_unlowerable():
    class NotJitted:
        def lower(self, *a, **k):
            raise RuntimeError("no cost model here")

    assert program_costs(NotJitted()) is None


def test_resolve_peak_spec_override_and_table():
    # override wins and is marked as such
    cfg = Config(perf=Config(peak_flops=1e12, peak_membw=2e11))
    spec = resolve_peak_spec(cfg)
    assert spec.source == "override"
    assert spec.flops == 1e12 and spec.membw == 2e11
    # no override: the table is keyed by device_kind and holds no CPU,
    # so this image resolves to 'unknown' (no utilization gauge) ...
    spec = resolve_peak_spec(Config(perf=Config()))
    assert spec.source == "unknown" and spec.flops is None
    assert spec.device_kind == jax.devices()[0].device_kind
    # ... and a bench path refuses it outright
    from surreal_tpu.session.costs import PEAK_SPECS, published_peak

    with pytest.raises(RuntimeError, match="no published peak"):
        published_peak(spec.device_kind)
    assert published_peak("TPU v5 lite") == PEAK_SPECS["TPU v5 lite"]


# -- MFU gauge arithmetic ------------------------------------------------------

def test_mfu_gauge_arithmetic_hand_computed(tmp_path):
    """The gauge formula against a hand-computed value: one program with
    known flops/bytes, a phase window with known call count and fenced
    ``cadence`` seconds, and an exact peak override -> mfu and membw_util
    must match exactly. The program's own span times its dispatch only and
    is no part of the denominator."""
    cfg = Config(
        perf=Config(peak_flops=1e9, peak_membw=1e8, memory_analysis=False)
    )
    acct = CostAccountant(cfg)
    f = jax.jit(lambda x: x * 2.0)
    rec = acct.record_program(
        "prog", f, jnp.ones((8,)), phase="train_iter", calls_per_phase=1
    )
    assert rec is not None
    # substitute exact numbers so the expectation is hand-computable
    acct._programs["prog"]["flops"] = 1e6
    acct._programs["prog"]["bytes_accessed"] = 5e5
    window = {
        "train_iter": {"count": 4, "total_s": 0.002, "max_ms": 0.6},
        "cadence": {"count": 4, "total_s": 0.5, "max_ms": 500.0},
    }
    g = acct.gauges(window)
    # 4 calls x 1e6 flops / 0.5 fenced s = 8e6 flops/s; peak 1e9 -> 0.008
    assert g["perf/flops_per_s"] == pytest.approx(8e6)
    assert g["perf/mfu"] == pytest.approx(8e6 / 1e9)
    # 4 x 5e5 bytes / 0.5 s = 4e6 B/s; peak 1e8 -> 0.04
    assert g["perf/membw_util"] == pytest.approx(4e6 / 1e8)
    # calls_per_phase multiplies the numerator (an act program running
    # horizon times inside one rollout phase)
    acct._programs["prog"]["calls_per_phase"] = 3
    g3 = acct.gauges(window)
    assert g3["perf/mfu"] == pytest.approx(3 * g["perf/mfu"])
    # phases the program doesn't own contribute nothing
    cadence = {"cadence": {"count": 1, "total_s": 1.0}}
    assert acct.gauges({"other": {"count": 1, "total_s": 1.0}, **cadence}) == {}
    # a window without a fenced cadence span (the first of a run) has no
    # denominator: no gauge, rather than one over the dispatch time
    assert acct.gauges({"train_iter": window["train_iter"]}) == {}
    assert acct.gauges({}) == {}
    assert acct.gauges(None) == {}


def test_gauges_without_peak_spec_still_report_flops():
    acct = CostAccountant(Config(perf=Config(memory_analysis=False)))
    acct.peak = PeakSpec(None, None, "mystery-chip", "unknown")
    acct._programs["p"] = {
        "name": "p", "phase": "learn", "calls_per_phase": 1,
        "flops": 2e6, "bytes_accessed": 1e6, "arithmetic_intensity": 2.0,
    }
    g = acct.gauges({
        "learn": {"count": 2, "total_s": 0.1},
        "cadence": {"count": 2, "total_s": 1.0},
    })
    assert g["perf/flops_per_s"] == pytest.approx(4e6)
    assert "perf/mfu" not in g and "perf/membw_util" not in g


def test_every_registry_gauge_emittable():
    """Every documented perf/* gauge comes out of one fully-specified
    accountant — the registry documents reality, not aspiration. (The
    registry also documents the replay/* and experience/* families since
    ISSUE 8; those are emitted by the replay layer and the experience
    plane respectively — tests/test_experience.py asserts the emitted
    experience gauges against the registry.)"""
    acct = CostAccountant(
        Config(perf=Config(peak_flops=1e9, peak_membw=1e9,
                           memory_analysis=False))
    )
    acct.peak = PeakSpec(1e9, 1e9, "test", "override")
    acct._programs["p"] = {
        "name": "p", "phase": "x", "calls_per_phase": 1,
        "flops": 1e6, "bytes_accessed": 1e6, "arithmetic_intensity": 1.0,
    }
    g = acct.gauges({
        "x": {"count": 1, "total_s": 1.0},
        "cadence": {"count": 1, "total_s": 1.0},
    })
    assert set(g) == {k for k in GAUGE_REGISTRY if k.startswith("perf/")}


# -- trace-id propagation ------------------------------------------------------

def test_tracer_stamps_trace_and_seq(tmp_path):
    tracer = Tracer(str(tmp_path), name="train")
    tracer.event("custom", x=1)
    tracer.event("custom", x=2)
    tracer.close()
    evs = [
        json.loads(l)
        for l in open(os.path.join(str(tmp_path), "telemetry", "events.jsonl"))
        if l.strip()
    ]
    assert len({e["trace"] for e in evs}) == 1
    assert evs[0]["trace"] == tracer.trace_id
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def _const_act_fn(n_actions=2):
    def act_fn(obs):
        b = obs.shape[0]
        return (
            np.zeros(b, np.int64),
            {
                "logp": np.full(b, -np.log(n_actions), np.float32),
                "logits": np.zeros((b, n_actions), np.float32),
            },
        )

    return act_fn


def test_trace_id_propagates_through_spawned_env_worker():
    """A SPAWNED (process-mode) worker inherits the run trace id via
    kwargs and the server records it at the hello/priming message — the
    cross-process half of trace correlation, through a real OS process."""
    import multiprocessing as mp

    from surreal_tpu.distributed.env_worker import run_env_worker
    from surreal_tpu.distributed.inference_server import InferenceServer
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    trace_id = "issue6traceid123"
    server = InferenceServer(act_fn=_const_act_fn(), unroll_length=4)
    env_cfg = Config(name="gym:CartPole-v1", num_envs=2).extend(BASE_ENV_CONFIG)
    ctx = mp.get_context("spawn")
    w = ctx.Process(
        target=run_env_worker,
        args=(env_cfg.to_dict(), server.address, 0),
        kwargs={"max_steps": 40, "trace_id": trace_id},
        daemon=True,
    )
    try:
        w.start()
        deadline = time.monotonic() + 60
        traces = {}
        while time.monotonic() < deadline:
            traces = server.worker_traces()
            if trace_id in traces.values():
                break
            time.sleep(0.2)
        assert trace_id in traces.values(), traces
        # the hop samples carry real transit latencies from the frames'
        # send stamps (the frame-in-flight hop of the stitched timeline)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not server.hop_stats():
            time.sleep(0.2)
        hops = server.hop_stats()
        assert "worker_to_server_ms" in hops, hops
        assert hops["worker_to_server_ms"]["n"] >= 1
        assert hops["worker_to_server_ms"]["p50"] >= 0.0
    finally:
        w.terminate()
        w.join(timeout=10)
        server.close()


def test_trace_id_propagates_through_thread_worker_pickle():
    """Thread-mode pickle workers have no hello handshake: the trace id
    rides the priming message instead."""
    from surreal_tpu.distributed.env_worker import run_env_worker
    from surreal_tpu.distributed.inference_server import InferenceServer
    from surreal_tpu.session.default_configs import BASE_ENV_CONFIG

    trace_id = "threadtrace456"
    server = InferenceServer(
        act_fn=_const_act_fn(), unroll_length=4, transport="pickle"
    )
    env_cfg = Config(name="gym:CartPole-v1", num_envs=2).extend(BASE_ENV_CONFIG)
    stop = threading.Event()
    w = threading.Thread(
        target=run_env_worker,
        args=(env_cfg, server.address, 0),
        kwargs={
            "stop_event": stop, "max_steps": 40, "transport": "pickle",
            "trace_id": trace_id,
        },
        daemon=True,
    )
    try:
        w.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if trace_id in server.worker_traces().values():
                break
            time.sleep(0.1)
        assert trace_id in server.worker_traces().values()
    finally:
        stop.set()
        w.join(timeout=10)
        server.close()


def test_param_fetch_events_carry_client_span():
    """ParameterClient fetch requests carry a span id; a server built
    with an on_event sink mirrors each fetch as a 'param_fetch' event —
    the param-service hop of the cross-process timeline."""
    from surreal_tpu.distributed.param_service import (
        ParameterClient,
        ParameterPublisher,
        ParameterServer,
    )

    events = []
    pub = ParameterPublisher()
    srv = ParameterServer(
        pub.address, on_event=lambda t, **kw: events.append((t, kw))
    )
    client = None
    try:
        template = {"w": np.zeros(3, np.float32)}
        pub.publish({"w": np.ones(3, np.float32)})
        client = ParameterClient(srv.address, template)
        deadline = time.monotonic() + 10
        fetched = None
        while fetched is None and time.monotonic() < deadline:
            fetched = client.fetch(timeout_ms=1000)
        assert fetched is not None
        # second fetch with no new publish -> 'unchanged', still span-tagged
        assert client.fetch(timeout_ms=1000) is None
        deadline = time.monotonic() + 5
        while len(events) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        kinds = [t for t, _ in events]
        assert kinds.count("param_fetch") >= 2
        spans = [kw["span"] for t, kw in events if t == "param_fetch"]
        assert spans == sorted(spans) and spans[0] >= 1
        unchanged = [kw["unchanged"] for t, kw in events if t == "param_fetch"]
        assert unchanged[0] is False and unchanged[-1] is True
    finally:
        if client is not None:
            client.close()
        srv.close()
        pub.close()


def test_latency_percentiles():
    assert latency_percentiles([]) is None
    p = latency_percentiles(range(1, 101))
    assert p["p50"] == pytest.approx(51, abs=1)
    assert p["p99"] == pytest.approx(99, abs=1)
    assert p["n"] == 100


# -- diag Performance section --------------------------------------------------

def _train_tiny(folder, extra_session=None, total_iters=6):
    from surreal_tpu.launch.trainer import Trainer

    horizon, num_envs = 8, 8
    session = Config(
        folder=str(folder),
        total_env_steps=horizon * num_envs * total_iters,
        metrics=Config(every_n_iters=2, tensorboard=False, console=False),
        checkpoint=Config(every_n_iters=0),
        eval=Config(every_n_iters=0),
    )
    if extra_session:
        session = Config(extra_session).extend(session)
    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=1,
                        num_minibatches=1)
        ),
        env_config=Config(name="jax:cartpole", num_envs=num_envs),
        session_config=session,
    ).extend(base_config())
    return Trainer(cfg).run()


def test_diag_renders_performance_section_and_trigger_capture(tmp_path):
    """Acceptance surface, one fresh device-workload session: diag
    reports per-program FLOPs/bytes and an MFU estimate (gauges in the
    metrics stream, program_cost event in the log), AND a pre-armed
    trigger-file capture produced a trace artifact under
    telemetry/profiles/ that diag lists. One shared training run — the
    compile is the expensive part of this test."""
    from surreal_tpu.session.profile import write_trigger

    folder = tmp_path / "exp"
    os.makedirs(folder)
    write_trigger(str(folder), num_iters=2)
    # the CPU has no published peak: the test supplies one
    state, metrics = _train_tiny(
        folder, total_iters=8,
        extra_session={"perf": Config(peak_flops=1e11, peak_membw=5e10)},
    )
    assert "perf/mfu" in metrics and "perf/flops_per_s" in metrics
    assert 0.0 < metrics["perf/mfu"] < 1.0
    s = diag_summary(str(folder))
    assert "train_iter" in s["programs"]
    assert s["programs"]["train_iter"]["flops"] > 0
    assert s["programs"]["train_iter"]["bytes_accessed"] > 0
    assert s["perf"]["perf/mfu"] == pytest.approx(metrics["perf/mfu"])
    assert s["trace_id"]
    report = diag_report(str(folder))
    for needle in ("Performance", "train_iter", "mfu", "GFLOPs/call",
                   "MB/call"):
        assert needle in report, report
    # trigger-file capture: artifact on disk, trigger consumed, event
    # recorded, diag lists it
    caps = glob.glob(str(folder / "telemetry" / "profiles" / "*"))
    assert caps, "no capture directory created"
    files = [
        os.path.join(dp, f)
        for dp, _dn, fn in os.walk(caps[0]) for f in fn
    ]
    assert files, "capture directory is empty (no trace artifact)"
    assert not os.path.exists(folder / "profile.trigger"), (
        "trigger file not consumed"
    )
    assert s["profiles"] and s["profiles"][0]["reason"] == "trigger_file"
    assert s["profiles"][0]["dir"] == caps[0]
    assert "profiler captures" in report and "trigger_file" in report


def test_mfu_uses_peak_override(tmp_path):
    """The config override IS the MFU denominator: flops/s varies run to
    run (wall clock), but the ratio of mfu to flops/s is exactly the
    configured peak — deterministic, so one run proves the override
    reached the denominator (the gauge-arithmetic unit above covers the
    formula itself)."""
    _, m = _train_tiny(
        tmp_path / "lo", {"perf": Config(peak_flops=1e10, peak_membw=1e10)}
    )
    assert m["perf/mfu"] > 0
    assert m["perf/mfu"] / m["perf/flops_per_s"] == pytest.approx(1e-10)
    assert m["perf/membw_util"] > 0


def test_profile_cli_writes_trigger(tmp_path, capsys):
    from surreal_tpu.main.launch import main

    rc = main(["profile", str(tmp_path), "--iters", "3"])
    assert rc == 0
    path = os.path.join(str(tmp_path), "profile.trigger")
    assert os.path.exists(path)
    with open(path) as f:
        assert json.load(f) == {"num_iters": 3}
    rc = main(["profile", str(tmp_path / "nope")])
    assert rc == 2


def test_slow_iteration_auto_trigger(tmp_path, monkeypatch):
    """A single pathologically slow iteration fires the auto capture
    (bounded by max_auto_captures). Driven on a fake monotonic clock —
    real sleeps made this flaky on a busy box, where a scheduler hiccup
    during the EWMA seed ticks could fire (and exhaust) the one-capture
    budget early."""
    from surreal_tpu.session import profile as profile_mod
    from surreal_tpu.session.profile import ProfileManager

    clock = [0.0]
    monkeypatch.setattr(profile_mod.time, "monotonic", lambda: clock[0])

    class Log:
        def info(self, *a):
            pass

        warning = info

    class Sink:
        def __init__(self):
            self.events = []

        def event(self, type_, **kw):
            self.events.append((type_, kw))

    cfg = Config(
        profile=Config(slow_iter_factor=3.0, num_iters=1, max_auto_captures=1,
                       trigger_file=False),
    )
    sink = Sink()
    pm = ProfileManager(cfg, str(tmp_path), sink, Log())
    # seed the EWMA past the warmup with uniform 10 ms ticks...
    for i in range(1, 14):
        clock[0] += 0.01
        pm.tick(i)
    # ...then one 250 ms iteration (25x the EWMA, factor is 3)
    clock[0] += 0.25
    pm.tick(14)
    assert pm._pending is not None or pm._active is not None
    clock[0] += 0.01
    pm.tick(15)   # start (if pending)
    clock[0] += 0.01
    pm.tick(16)   # run past stop_at
    clock[0] += 0.01
    pm.tick(17)
    pm.close()
    profile_events = [kw for t, kw in sink.events if t == "profile"]
    assert profile_events, sink.events
    assert "slow_iter" in profile_events[-1]["reason"]
    # budget exhausted: another slow tick must not re-arm
    clock[0] += 0.5
    pm.tick(18)
    assert pm._pending is None


def test_seed_session_diag_stitches_cross_process_timeline(tmp_path):
    """Acceptance: a fresh SEED-topology session's diag reports the
    stitched cross-process timeline — per-hop latency percentiles for
    worker->server transit, serve batch, chunk queue dwell, and learn
    dispatch — plus the per-program costs, through the real CLI."""
    from surreal_tpu.launch.seed_trainer import SEEDTrainer
    from surreal_tpu.main.launch import main

    folder = tmp_path / "seed"
    cfg = Config(
        learner_config=Config(algo=Config(name="impala", horizon=4)),
        env_config=Config(name="gym:CartPole-v1", num_envs=4),
        session_config=Config(
            folder=str(folder),
            total_env_steps=4 * 4 * 8,
            topology=Config(num_env_workers=2),
            metrics=Config(every_n_iters=1, tensorboard=False,
                           console=False),
            checkpoint=Config(every_n_iters=0),
            eval=Config(every_n_iters=0),
        ),
    ).extend(base_config())
    SEEDTrainer(cfg).run()
    s = diag_summary(str(folder))
    assert s["hops"] is not None
    for hop in ("worker_to_server_ms", "serve_batch_ms",
                "chunk_queue_dwell_ms", "learn_dispatch_ms"):
        assert hop in s["hops"], s["hops"]
        assert s["hops"][hop]["n"] >= 1
        assert (
            s["hops"][hop]["p50"] <= s["hops"][hop]["p90"]
            <= s["hops"][hop]["p99"]
        )
    assert {"act", "learn"} <= set(s["programs"])
    report = diag_report(str(folder))
    for needle in ("per-hop latency", "worker_to_server_ms",
                   "chunk_queue_dwell_ms", "p99"):
        assert needle in report, report
    assert main(["diag", str(folder)]) == 0


@pytest.mark.parametrize("refreshed", [None, 0.0, 31.5])
def test_diag_prints_the_replay_gauges_of_the_last_row(tmp_path, refreshed):
    """The replay/* gauges of the newest metrics row get a line of the
    Performance section; a prioritized fused loop's row also says how many
    block sums an update refreshed (0 = the loop is not carrying them)."""
    tel = tmp_path / "telemetry"
    os.makedirs(tel)
    values = {"replay/fill": 0.25, "replay/sample_age_frac": 0.5,
              "replay/max_priority": 3.0, "loss/critic": 0.1}
    if refreshed is not None:
        values["replay/mass_blocks_refreshed"] = refreshed
    with open(tel / "events.jsonl", "w") as f:
        for step, fill in ((10, 0.125), (20, 0.25)):
            f.write(json.dumps({
                "type": "metrics", "t": time.time(), "step": step,
                "values": dict(values, **{"replay/fill": fill}),
            }) + "\n")
    assert diag_summary(str(tmp_path))["perf"]["replay/fill"] == 0.25
    line = [
        l for l in diag_report(str(tmp_path)).splitlines()
        if "replay (last metrics row)" in l
    ]
    assert len(line) == 1 and "fill 25.0%" in line[0]
    assert "max priority 3" in line[0] and "sample age 0.500" in line[0]
    assert ("block sums refreshed" in line[0]) == (refreshed is not None)
    if refreshed is not None:
        assert f"refreshed an update {refreshed:.1f}" in line[0]
    assert "gauges (last metrics row)" not in diag_report(str(tmp_path))


# -- heartbeat staleness -------------------------------------------------------

def test_diag_flags_stale_heartbeats_dead(tmp_path):
    """A rank whose newest beat is older than 3x its cadence renders as
    DEAD; a fresh rank stays alive. (ISSUE 6 satellite.)"""
    tel = tmp_path / "telemetry"
    os.makedirs(tel)
    now = time.time()
    with open(tel / "heartbeat_rank0.jsonl", "w") as f:
        f.write(json.dumps({
            "type": "heartbeat", "t": now, "rank": 0, "iteration": 5,
            "env_steps": 100, "every_s": 10.0,
        }) + "\n")
    with open(tel / "heartbeat_rank1.jsonl", "w") as f:
        f.write(json.dumps({
            "type": "heartbeat", "t": now - 120.0, "rank": 1, "iteration": 2,
            "env_steps": 40, "every_s": 10.0,
        }) + "\n")
    s = diag_summary(str(tmp_path))
    assert s["heartbeats"][0]["dead"] is False
    assert s["heartbeats"][1]["dead"] is True
    assert s["heartbeats"][1]["age_s"] > 100
    report = diag_report(str(tmp_path))
    assert "DEAD" in report and "alive" in report
    assert "rank(s) 1" in report


def test_heartbeat_cadence_inferred_for_old_logs(tmp_path):
    """Logs written before the every_s field existed: cadence is inferred
    from the observed beat deltas."""
    tel = tmp_path / "telemetry"
    os.makedirs(tel)
    now = time.time()
    with open(tel / "heartbeat_rank0.jsonl", "w") as f:
        for i in range(5):
            f.write(json.dumps({
                "type": "heartbeat", "t": now - 500 + i * 5.0, "rank": 0,
                "iteration": i, "env_steps": i * 10,
            }) + "\n")
    s = diag_summary(str(tmp_path))
    hb = s["heartbeats"][0]
    assert hb["cadence_s"] == pytest.approx(5.0, abs=0.1)
    assert hb["dead"] is True  # last beat ~480 s ago >> 3x5s


# -- torn-tail JSONL tolerance -------------------------------------------------

def test_iter_jsonl_tolerates_truncated_tail(tmp_path):
    """A crash-truncated trailing line — including one cut INSIDE a
    multi-byte UTF-8 sequence — must not raise; the valid prefix lines
    still parse. (Chaos-harness kills from PR 5 can truncate the event
    log mid-record.)"""
    from surreal_tpu.session.telemetry import _iter_jsonl

    path = tmp_path / "events.jsonl"
    good = [{"type": "metrics", "step": i} for i in range(3)]
    with open(path, "wb") as f:
        for rec in good:
            f.write(json.dumps(rec).encode() + b"\n")
        # torn tail: record cut mid-way through a 3-byte UTF-8 char
        f.write(b'{"type": "span", "name": "caf\xe2\x82')  # truncated EUR sign
    out = list(_iter_jsonl(str(path)))
    assert out == good
    # and a torn plain-ASCII tail
    with open(path, "ab") as f:
        f.write(b"\n")
        f.write(b'{"type": "span", "na')
    assert list(_iter_jsonl(str(path))) == good
    # diag_summary over a truncated log keeps working
    tel = tmp_path / "sess" / "telemetry"
    os.makedirs(tel)
    with open(tel / "events.jsonl", "wb") as f:
        f.write(json.dumps({"type": "metrics", "step": 1,
                            "values": {"loss/pg": 0.5}}).encode() + b"\n")
        f.write(b'{"type": "metrics", "step": 2, "values": {"loss/pg\xe2')
    s = diag_summary(str(tmp_path / "sess"))
    assert s is not None and s["health"]["loss/pg"]["last"] == 0.5
