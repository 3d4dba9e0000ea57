"""Import hygiene: importing surreal_tpu must never initialize a JAX backend.

An early regression: a ``jnp.sqrt(2.0)`` default-argument expression in
``models/encoders.py`` ran at import time and initialised the default
backend before ``__graft_entry__.dryrun_multichip`` could select the
simulated CPU devices. On a chip host it is worse: whoever imports the
package takes the chip. The contract this test enforces: every module in the package is
importable with ZERO backend side effects (no device queries, no jnp
computations at module scope or in default-arg expressions).

The check runs in a subprocess so this test file's own jax state (conftest
selects CPU and touches devices) can't mask or pollute the result, and so
it sees the same interpreter-boot conditions the driver's dryrun does.
"""

import pathlib
import subprocess
import sys

import surreal_tpu

_PKG_ROOT = pathlib.Path(surreal_tpu.__file__).parent
_REPO_ROOT = _PKG_ROOT.parent

_PROBE = r"""
import importlib
import pathlib
import pkgutil
import sys

import surreal_tpu

mods = ["surreal_tpu"]
pkg_path = pathlib.Path(surreal_tpu.__file__).parent
for info in pkgutil.walk_packages([str(pkg_path)], prefix="surreal_tpu."):
    if info.name.endswith("__main__"):
        continue  # runs the CLI unconditionally, by design of `python -m`
    mods.append(info.name)

for name in sorted(mods):
    importlib.import_module(name)

# jax._src.xla_bridge._backends is the cache of initialized backend clients;
# it stays empty until the first real device/array operation (verified on
# jax 0.9.0). Private API, so fail loudly if it moves rather than silently
# passing.
from jax._src import xla_bridge

assert hasattr(xla_bridge, "_backends"), "jax moved xla_bridge._backends; update this probe"
assert xla_bridge._backends == {}, (
    f"importing surreal_tpu initialized JAX backend(s) {list(xla_bridge._backends)}: "
    "some module does device work at import time (module-level jnp call or "
    "default-arg expression)"
)
print("IMPORT_HYGIENE_OK", len(mods))
"""


def test_package_import_initializes_no_backend():
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(_REPO_ROOT),
    )
    assert proc.returncode == 0, f"probe failed:\n{proc.stdout}\n{proc.stderr}"
    assert "IMPORT_HYGIENE_OK" in proc.stdout
    # sanity: the walk actually visited the package, not just the top module
    n_modules = int(proc.stdout.split("IMPORT_HYGIENE_OK")[1].split()[0])
    assert n_modules > 30, f"walk found only {n_modules} modules"


def test_package_imports_no_module_from_the_repo_root():
    """The package stands alone: no module under ``surreal_tpu/`` imports
    a top-level module that is a file (or a package) beside it at the
    repository's root — scripts and records there are not part of what is
    installed, and a lower layer that reaches for one (a function-level
    ``from <root script> import ...`` under a ``try``) silently changes
    behaviour with the directory it is started from. The AST walk sees
    imports at every depth of nesting."""
    import ast

    beside = {p.stem for p in _REPO_ROOT.glob("*.py")} | {
        p.parent.name for p in _REPO_ROOT.glob("*/__init__.py")
    }
    beside.discard(_PKG_ROOT.name)
    assert "chip_smoke" in beside, "the walk no longer sees the root scripts"
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            else:
                continue
            for name in names:
                if name.split(".", 1)[0] in beside:
                    bad.append(
                        f"{path.relative_to(_REPO_ROOT)}:{node.lineno}: "
                        f"imports {name}"
                    )
    assert not bad, (
        "modules of the package importing from the repository's root:\n"
        + "\n".join(bad)
    )


_JITTED_STEP_SOURCES = (
    # packages whose modules contain (or are traced into) jitted step code
    "learners", "ops", "replay", "models", "parallel", "envs/jax",
    # single files on the jitted path
    "launch/rollout.py",
)
_FENCE_BANNED = ("time.time(", "time.perf_counter(", "block_until_ready(")


def test_no_host_clocks_or_fences_in_jitted_step_modules():
    """Fence-discipline lint: a host clock inside a module traced into the
    jitted step runs ONCE at compile and lies forever, and a
    ``jax.block_until_ready`` there serializes the async pipeline.
    Wall-clock measurement — the clock and the fence that ends the timed
    region — belongs to session/telemetry.py and launch/hooks.py, at phase
    boundaries only. The substring scan includes call parens so
    prose mentions in docstrings stay legal; the code itself must not
    call these."""
    bad = []
    for entry in _JITTED_STEP_SOURCES:
        root = _PKG_ROOT / entry
        files = [root] if root.suffix == ".py" else sorted(root.rglob("*.py"))
        for path in files:
            src = path.read_text()
            for banned in _FENCE_BANNED:
                if banned in src:
                    bad.append(f"{path.relative_to(_REPO_ROOT)}: {banned}")
    assert not bad, (
        "host clock / fence calls inside jitted-step modules "
        "(move timing to session/telemetry.py, at a phase boundary):\n"
        + "\n".join(bad)
    )


_DONATION_SCOPED_SOURCES = (
    # learner/trainer step modules: every jax.jit here is on (or adjacent
    # to) a training hot loop where the loop-carried state should be
    # donated — and where accidental donation of an aliased state (the
    # SEED act closure, the overlap collector's acting reference) is a
    # use-after-free. Either way the decision must be explicit.
    "learners", "parallel/dp.py", "parallel/learner_group.py",
    "launch/trainer.py", "launch/offpolicy_trainer.py",
    "launch/seed_trainer.py", "launch/multihost_trainer.py",
    # the hot replay tier (ISSUE 18): its insert donates the
    # capacity-sized ring while its sample must NOT donate — exactly the
    # class of decision this lint forces to be written down
    "replay/tiers.py",
)


def _call_spans(src: str, callee: str):
    """(line_number, call_text) for every ``<callee>(`` call, text
    spanning to the balanced closing paren (strings/comments not parsed —
    good enough for a lint over our own style)."""
    spans = []
    needle = callee + "("
    start = 0
    while True:
        i = src.find(needle, start)
        if i < 0:
            return spans
        depth = 0
        for j in range(i + len(callee), len(src)):
            if src[j] == "(":
                depth += 1
            elif src[j] == ")":
                depth -= 1
                if depth == 0:
                    break
        spans.append((src.count("\n", 0, i) + 1, src[i : j + 1]))
        start = j + 1


def _jit_call_spans(src: str):
    return _call_spans(src, "jax.jit")


def test_jitted_steps_declare_donation():
    """Donation-discipline lint (the dispatch-pipeline PR's invariant): a
    new ``jax.jit`` in a learner/trainer step module without an explicit
    ``donate_argnums`` either misses the HBM win (an undonated train state
    is double-buffered every iteration) or — worse — gets donation bolted
    on later without auditing the aliases. Every call must state its
    decision: donate the loop-carried args, or ``donate_argnums=()`` with
    a comment naming the alias that forbids it."""
    bad = []
    for entry in _DONATION_SCOPED_SOURCES:
        root = _PKG_ROOT / entry
        files = [root] if root.suffix == ".py" else sorted(root.rglob("*.py"))
        for path in files:
            for line, call in _jit_call_spans(path.read_text()):
                if "donate_argnums" not in call:
                    bad.append(f"{path.relative_to(_REPO_ROOT)}:{line}")
    assert not bad, (
        "jax.jit calls in learner/trainer step modules without an explicit "
        "donate_argnums (donate the loop-carried state, or declare "
        "donate_argnums=() and comment why the buffers stay aliased):\n"
        + "\n".join(bad)
    )


_UNROLL_SCOPED_SOURCES = (
    # hot-loop scan modules: every ``lax.scan`` here runs inside (or is
    # traced into) a training hot loop — rollout scans, the SGD/update
    # loops, the GAE/V-trace recurrences
    "learners",
    "launch/rollout.py", "launch/trainer.py", "launch/offpolicy_trainer.py",
    "ops/returns.py", "ops/vtrace.py",
)


def test_hot_scans_declare_unroll():
    """Unroll-discipline lint (mirror of the donation lint above): a
    ``lax.scan`` on a training hot path without a declared ``unroll``
    takes jax's default and no key reaches it, which the next reader
    cannot see. Every call must state its decision — thread the key
    (``algo.rollout_unroll`` / ``sgd_unroll`` / ``update_unroll`` /
    ``gae_unroll``), or pin ``unroll=1`` with the reason the scan stays
    default. A key's default is 1, but for ``rollout_unroll``: its default
    0 hands the decision to ``launch/rollout.py::rollout_unroll``, which
    reads the scan's input (4 for a memoryless policy over vector
    observations, 1 otherwise) and whose docstring holds the readings."""
    bad = []
    for entry in _UNROLL_SCOPED_SOURCES:
        root = _PKG_ROOT / entry
        files = [root] if root.suffix == ".py" else sorted(root.rglob("*.py"))
        for path in files:
            for line, call in _call_spans(path.read_text(), "lax.scan"):
                if "unroll" not in call:
                    bad.append(f"{path.relative_to(_REPO_ROOT)}:{line}")
    assert not bad, (
        "lax.scan calls in hot-loop modules without an explicit unroll "
        "decision (thread an algo.*_unroll key, choose from the scan's "
        "input as launch/rollout.py::rollout_unroll does, or state "
        "unroll=1 and why):\n" + "\n".join(bad)
    )


_PRECISION_MARKERS = ("# precision:", "ops.precision import", "ops import precision")


def test_jitted_steps_declare_precision():
    """Precision-discipline lint (ISSUE 7 satellite, mirror of the
    donation/unroll lints): every learner/trainer step module that builds
    a ``jax.jit`` hot program must STATE its precision decision — import
    the policy layer (``surreal_tpu.ops.precision``) because it threads
    the policy, or carry a ``# precision:`` comment naming why the module
    is policy-transparent (dp wrappers, drivers whose dtypes live inside
    ``learner.learn``). A silent module is how a new driver ships f32
    staging under a bf16 policy without anyone noticing."""
    bad = []
    for entry in _DONATION_SCOPED_SOURCES:
        root = _PKG_ROOT / entry
        files = [root] if root.suffix == ".py" else sorted(root.rglob("*.py"))
        for path in files:
            src = path.read_text()
            if "jax.jit(" not in src:
                continue
            if not any(m in src for m in _PRECISION_MARKERS):
                bad.append(str(path.relative_to(_REPO_ROOT)))
    assert not bad, (
        "learner/trainer step modules with jitted hot programs but no "
        "stated precision decision (import surreal_tpu.ops.precision or "
        "add a '# precision:' comment naming why the module is "
        "policy-transparent):\n" + "\n".join(bad)
    )
    # the learners themselves must thread the policy, not just mention it
    for mod in ("learners/ppo.py", "learners/ddpg.py", "learners/impala.py"):
        src = (_PKG_ROOT / mod).read_text()
        assert "ops.precision import" in src or "ops import precision" in src, (
            f"{mod} no longer imports the precision layer; the policy must "
            "thread through every learner (ops/precision.py)"
        )


_KERNEL_ROUTERS = (
    # every module that decides whether a Pallas kernel runs, and how
    "ops", "replay", "learners/ppo.py", "learners/impala.py",
)


def test_pallas_kernels_take_one_interpret_decision_and_no_fallback():
    """Pallas-kernel lint: interpret mode exists so that the CPU suite
    can validate the kernels, never to hide a chip that refused one.

    - every ``pl.pallas_call`` in the op library passes its entry
      point's own ``interpret`` parameter through (no kernel hard-codes
      a mode);
    - every caller outside ``ops/`` gets that value from the one
      decision, ``ops.pallas_interpret()`` (compiled on the TPU,
      interpreted elsewhere) — no second spelling of the backend test;
    - no module that routes to a kernel holds a ``try``: nothing can turn
      a refused Mosaic compile into the XLA path."""
    import re

    bad = []
    has_kernels = False
    for path in sorted((_PKG_ROOT / "ops").rglob("*.py")):
        for line, call in _call_spans(path.read_text(), "pl.pallas_call"):
            has_kernels = True
            if "interpret=interpret" not in call:
                bad.append(
                    f"{path.relative_to(_REPO_ROOT)}:{line}: pallas_call "
                    "does not pass interpret=interpret"
                )
    assert has_kernels, "no pallas_call found under ops/ — update this lint"
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        rel = path.relative_to(_PKG_ROOT)
        src = path.read_text()
        if rel.parts[0] != "ops":
            for m in re.finditer(r"interpret\s*=\s*([\w.]+(?:\(\))?)", src):
                if m.group(1) != "pallas_interpret()":
                    line = src.count("\n", 0, m.start()) + 1
                    bad.append(
                        f"{path.relative_to(_REPO_ROOT)}:{line}: interpret= "
                        "not taken from ops.pallas_interpret()"
                    )
        if 'default_backend() != "tpu"' in src.replace("'", '"') and str(
            rel
        ) != "ops/__init__.py":
            bad.append(
                f"{path.relative_to(_REPO_ROOT)}: spells the backend test "
                "itself instead of calling ops.pallas_interpret()"
            )
    for entry in _KERNEL_ROUTERS:
        root = _PKG_ROOT / entry
        files = [root] if root.suffix == ".py" else sorted(root.rglob("*.py"))
        for path in files:
            if re.search(r"(?m)^\s*try\s*:", path.read_text()):
                bad.append(
                    f"{path.relative_to(_REPO_ROOT)}: holds a try block "
                    "(a refused kernel must fail, not fall back)"
                )
    assert not bad, "\n".join(bad)


_DATA_PLANE_STEADY_STATE = (
    # the steady-state serve/step loop modules: one pickle of an ndarray
    # payload per env step is exactly the cost the zero-copy transport
    # removed, and the easiest regression to reintroduce
    "distributed/env_worker.py",
    "distributed/inference_server.py",
    "launch/seed_trainer.py",
    # the experience plane's steady-state modules (ISSUE 8): every
    # encode/decode routes through experience/wire.py — the negotiated
    # fallback codec is the ONLY place the plane may unpickle
    "experience/shard.py",
    "experience/sender.py",
    "experience/sampler.py",
    "experience/link.py",
    # the serving tier + parameter fanout (ISSUE 10): frames are raw
    # struct/zlib codecs, never pickled pytrees (module_dict's msgpack
    # is the fetch fallback's wire format, not pickle)
    "distributed/fleet.py",
    "distributed/param_fanout.py",
    "experience/plane.py",
    "launch/offpolicy_trainer.py",
    # the session gateway (ISSUE 12): the tenant protocol's negotiated
    # pickle fallback lives in gateway/protocol.py (the codec); the
    # server loop, admission book, and session table never unpickle
    "gateway/server.py",
    "gateway/admission.py",
    "gateway/table.py",
    # the tenant load generator (ISSUE 16): client-side traffic over the
    # real GatewaySession codec — its adversarial profile sends raw
    # hostile bytes, never a pickle of its own
    "gateway/loadgen.py",
    # the replay tiers (ISSUE 18): the spill WAL is struct-framed
    # JSON-header + raw column bytes (wire.py codec discipline), and the
    # hot tier never leaves the device — neither may pickle
    "experience/spill.py",
    "replay/tiers.py",
)


def test_data_plane_pickles_only_in_fallback_codec():
    """Data-plane serialization lint (the shm-transport PR's invariant,
    extended over the experience plane): ``pickle.dumps``/``pickle.loads``
    of ndarray payloads may appear only in the fallback transport modules
    and control-frame codecs (``distributed/shm_transport.py``,
    ``experience/wire.py``, ``gateway/protocol.py``) — never in the
    steady-state serve/step loops, which must route every encode/decode
    through the codec so the transport decision stays in one place."""
    banned = ("pickle.dumps(", "pickle.loads(", "import pickle")
    bad = []
    for rel in _DATA_PLANE_STEADY_STATE:
        src = (_PKG_ROOT / rel).read_text()
        for b in banned:
            if b in src:
                bad.append(f"{rel}: {b}")
    assert not bad, (
        "ndarray pickling belongs to the fallback codecs "
        "(distributed/shm_transport.py, experience/wire.py, "
        "gateway/protocol.py), not the steady-state data-plane loops:\n"
        + "\n".join(bad)
    )
    for codec_rel in (
        "distributed/shm_transport.py",
        "experience/wire.py",
        "gateway/protocol.py",
    ):
        codec = (_PKG_ROOT / codec_rel).read_text()
        assert "pickle.dumps(" in codec and "pickle.loads(" in codec, (
            f"the fallback codec moved out of {codec_rel}; update this lint"
        )


_SUPERVISED_PACKAGES = ("distributed", "launch", "gateway")


def test_no_swallowed_exceptions_in_supervised_code():
    """Robustness lint (ISSUE 5 satellite): a blanket ``except Exception:
    pass`` in the distributed/launch layers silently eats exactly the
    failures the recovery layer exists to handle — a worker thread that
    swallows its crash looks alive to the supervisor and is never
    respawned. Supervised code must re-raise, degrade explicitly through
    a NARROW exception list with the reason commented, or record a
    telemetry event. (Narrow excepts like ``except OSError: pass`` on
    best-effort cleanup paths stay legal — this bans only the blanket
    form.)"""
    import re

    swallow = re.compile(
        r"except\s+(?:BaseException|Exception)(?:\s+as\s+\w+)?\s*:"
        r"\s*(?:#[^\n]*)?\n\s+pass\b"
    )
    bad = []
    for pkg in _SUPERVISED_PACKAGES:
        for path in sorted((_PKG_ROOT / pkg).rglob("*.py")):
            src = path.read_text()
            for m in swallow.finditer(src):
                line = src.count("\n", 0, m.start()) + 1
                bad.append(f"{path.relative_to(_REPO_ROOT)}:{line}")
    assert not bad, (
        "blanket except-and-pass in supervised distributed/launch code "
        "(re-raise, narrow the exception list with a comment, or record "
        "a telemetry event):\n" + "\n".join(bad)
    )


def test_perf_gauges_appear_in_registry():
    """Gauge-registry lint (ISSUE 6 satellite, extended by ISSUE 8 over
    the replay/experience families, ISSUE 10 over the serving-tier
    fleet/param families, ISSUE 12 over the gateway family, ISSUE 13
    over the ops/slo families, ISSUE 14 over the lineage/trace
    families, and ISSUE 16 over the remediation/loadgen families): every
    ``perf/*``, ``replay/*``, ``experience/*``, ``fleet/*``,
    ``param/*``, ``gateway/*``, ``ops/*``, ``slo/*``, ``lineage/*``,
    ``trace/*``, ``remediation/*``, or ``loadgen/*`` gauge name emitted
    anywhere in the package must appear in the documented registry
    (``session/costs.py::GAUGE_REGISTRY``) — an undocumented gauge is
    invisible to diag readers and to the README's knob table. The scan
    covers string literals, so a gauge built by concatenation would dodge
    it; our style writes metric names as whole literals (the
    donation/unroll lints rely on the same convention)."""
    import re

    from surreal_tpu.session.costs import GAUGE_REGISTRY

    lit = re.compile(
        r"[\"']((?:perf|replay|experience|fleet|param|gateway|ops|slo"
        r"|lineage|trace|remediation|loadgen|lgroup|tier|engine|chaos)"
        r"/[a-z0-9_]+)[\"']"
    )
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        if path.name == "costs.py":
            continue  # the registry itself defines the names
        src = path.read_text()
        for m in lit.finditer(src):
            if m.group(1) not in GAUGE_REGISTRY:
                line = src.count("\n", 0, m.start()) + 1
                bad.append(
                    f"{path.relative_to(_REPO_ROOT)}:{line}: {m.group(1)}"
                )
    assert not bad, (
        "perf/replay/experience/fleet/param/gateway/ops/slo/lineage/trace/"
        "remediation/loadgen/lgroup/tier/engine/chaos gauges emitted "
        "but not documented in session/costs.py::GAUGE_REGISTRY:\n"
        + "\n".join(bad)
    )
    # and the registry names must parse as gauge literals themselves
    for name in GAUGE_REGISTRY:
        assert name.startswith(
            ("perf/", "replay/", "experience/", "fleet/", "param/",
             "gateway/", "ops/", "slo/", "lineage/", "trace/",
             "remediation/", "loadgen/", "lgroup/", "tier/", "engine/",
             "chaos/")
        ), name


def test_gauge_registry_entries_declare_units():
    """Gauge-unit lint (ISSUE 15 satellite): every GAUGE_REGISTRY record
    must be a ``{unit, desc}`` dict with a unit from the documented set
    (``session/costs.py::GAUGE_UNITS``) and a nonempty description. The
    watchdog's threshold arithmetic keys off the unit (counters grow
    monotonically, latencies break out, ratios saturate) and
    ``surreal_tpu why`` renders firing values with it — a unitless gauge
    would make both guess."""
    from surreal_tpu.session.costs import GAUGE_REGISTRY, GAUGE_UNITS

    assert GAUGE_UNITS, "GAUGE_UNITS emptied; update this lint"
    bad = []
    for name, rec in GAUGE_REGISTRY.items():
        if not isinstance(rec, dict):
            bad.append(f"{name}: not a {{unit, desc}} record ({type(rec).__name__})")
            continue
        if rec.get("unit") not in GAUGE_UNITS:
            bad.append(f"{name}: unit {rec.get('unit')!r} not in GAUGE_UNITS")
        if not (isinstance(rec.get("desc"), str) and rec["desc"].strip()):
            bad.append(f"{name}: empty description")
    assert not bad, (
        "GAUGE_REGISTRY entries without a declared unit (wrap the entry "
        "as _g('<unit>', '<desc>') with a unit from GAUGE_UNITS):\n"
        + "\n".join(bad)
    )


def test_telemetry_events_appear_in_registry():
    """Event-registry lint (ISSUE 13 satellite, the gauge-lint pattern
    applied to the telemetry spine): every event kind emitted anywhere in
    the package — ``tracer.event("<kind>", ...)`` and the hook-relayed
    ``on_event("<kind>", ...)`` spellings — must appear in the documented
    registry (``session/telemetry.py::EVENT_REGISTRY``). An undocumented
    event kind is invisible to diag readers and silently skews event-log
    consumers that filter by kind. Whole-literal calls only, per the
    repo's metric-name convention."""
    import re

    from surreal_tpu.session.telemetry import EVENT_REGISTRY

    emit = re.compile(
        r"(?:\.event|on_event|_on_event|emit_event)\(\s*\n?\s*"
        r"[\"']([a-z_]+)[\"']"
    )
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        src = path.read_text()
        for m in emit.finditer(src):
            if m.group(1) not in EVENT_REGISTRY:
                line = src.count("\n", 0, m.start()) + 1
                bad.append(
                    f"{path.relative_to(_REPO_ROOT)}:{line}: {m.group(1)}"
                )
    assert not bad, (
        "telemetry event kinds emitted but not documented in "
        "session/telemetry.py::EVENT_REGISTRY:\n" + "\n".join(bad)
    )
    # registry hygiene: lowercase_underscore kinds with descriptions
    for kind, desc in EVENT_REGISTRY.items():
        assert re.fullmatch(r"[a-z_]+", kind), kind
        assert isinstance(desc, str) and desc, kind


def test_gateway_reuses_shared_supervision_utilities():
    """Supervisor-reuse lint (ISSUE 12 satellite): the gateway must NOT
    hand-copy a fourth respawn supervisor — backoff arithmetic lives in
    ``utils/respawn.py::RespawnSchedule`` (the fleet's, the worker
    plane's, and the experience plane's shared schedule) and port
    allocation in ``utils/net.py::alloc_address``. The scan bans the
    exponential-backoff idiom (``2 **`` / ``2.0 **``) anywhere under
    ``gateway/`` and asserts the server imports both shared utilities."""
    bad = []
    for path in sorted((_PKG_ROOT / "gateway").rglob("*.py")):
        src = path.read_text()
        for needle in ("2 **", "2.0 **", "2**", "2.0**"):
            if needle in src:
                bad.append(f"{path.relative_to(_REPO_ROOT)}: {needle!r}")
    assert not bad, (
        "inline exponential-backoff arithmetic in gateway/ (use "
        "utils/respawn.py::RespawnSchedule — one backoff policy, "
        "one implementation):\n" + "\n".join(bad)
    )
    server_src = (_PKG_ROOT / "gateway" / "server.py").read_text()
    assert "RespawnSchedule" in server_src, (
        "gateway/server.py no longer uses utils/respawn.py::RespawnSchedule"
    )
    assert "alloc_address" in server_src, (
        "gateway/server.py no longer uses utils/net.py::alloc_address"
    )


def test_training_loop_skeleton_lives_in_engine_only():
    """Loop-engine lint (ISSUE 19 tentpole): the hand-threaded training
    loop skeleton — ``while env_steps < total`` / ``while ls.env_steps``
    and friends — may exist ONLY in ``engine/core.py``. Every driver
    (trainer.py, offpolicy_trainer.py, seed_trainer.py, the multihost
    subclasses) declares stages and hands the engine a step closure; a
    new driver hand-rolling its own iteration loop silently forks the
    boundary contract (publish/checkpoint/recover/observe ordering,
    interrupt latch, chaos firing) this PR unified. Warmup/eval/bench
    helper loops that do not advance ``env_steps`` stay legal — the scan
    keys on the env-step budget condition, the loop head only the
    skeleton may own."""
    import re

    loop_head = re.compile(r"while\s+[\w.\[\]\"']*env_steps\b")
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        rel = path.relative_to(_PKG_ROOT)
        if str(rel) == "engine/core.py":
            continue
        src = path.read_text()
        for m in loop_head.finditer(src):
            line = src.count("\n", 0, m.start()) + 1
            bad.append(f"{path.relative_to(_REPO_ROOT)}:{line}")
    assert not bad, (
        "hand-threaded training loop heads outside engine/core.py (port "
        "the driver to surreal_tpu.engine.LoopEngine — declare stages, "
        "hand it a step closure):\n" + "\n".join(bad)
    )
    # and the engine actually owns one — the lint dies loudly if the
    # skeleton moves rather than silently scanning nothing
    assert loop_head.search((_PKG_ROOT / "engine" / "core.py").read_text()), (
        "engine/core.py no longer contains the loop skeleton; update this lint"
    )


def test_stage_specs_declare_donation():
    """Stage-donation lint (ISSUE 19 satellite, the jit-donation lint
    lifted to the stage layer): every ``StageSpec(...)`` construction in
    the package must spell ``donate=`` explicitly. The engine's
    donation-safe handoff (snapshot the param tree before a deferred
    boundary reads storage a donating dispatch will reuse) keys off this
    bit — a stage that omits it either misses the snapshot (use-after-
    free under pipelining) or pays a copy it didn't need. The dataclass
    has no default on purpose; this lint keeps call sites honest even
    for positional spellings."""
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        src = path.read_text()
        for line, call in _call_spans(src, "StageSpec"):
            if "donate=" not in call:
                bad.append(f"{path.relative_to(_REPO_ROOT)}:{line}")
    assert not bad, (
        "StageSpec constructions without an explicit donate= decision "
        "(state whether the stage's jitted program donates its "
        "loop-carried inputs):\n" + "\n".join(bad)
    )


def test_fault_sites_covered_and_registered():
    """Fault-site coverage lint (ISSUE 20 satellite, the gauge-lint
    pattern applied to the chaos surface): the injectable-fault registry
    and the code/tests stay honest in BOTH directions —

    - every ``faults.fire("<site>")`` literal in the package names a
      registered site (a typo'd site is a fault hook that can never
      fire, invisible until a campaign claims coverage it doesn't have);
    - every registered site is exercised somewhere under tests/ (a
      site literal in a fault plan or chaos profile) — a site nobody
      injects is dead robustness code;
    - every site in the chaos generator's SITE_META uses kinds from the
      site's declared vocabulary, and every campaign profile draws only
      SITE_META sites (the validation FaultInjector now enforces kinds
      at run time; this keeps the generator's metadata from drifting
      ahead of the registry).
    """
    import re

    from surreal_tpu.chaos import schedule as chaos_schedule
    from surreal_tpu.utils.faults import SITE_KINDS, SITES

    fire_lit = re.compile(r"faults\.fire\(\s*\n?\s*[\"']([a-z_.]+)[\"']")
    bad = []
    for path in sorted(_PKG_ROOT.rglob("*.py")):
        src = path.read_text()
        for m in fire_lit.finditer(src):
            if m.group(1) not in SITES:
                line = src.count("\n", 0, m.start()) + 1
                bad.append(
                    f"{path.relative_to(_REPO_ROOT)}:{line}: {m.group(1)}"
                )
    assert not bad, (
        "faults.fire() call sites naming unregistered fault sites "
        "(register in utils/faults.py::SITE_KINDS or fix the typo):\n"
        + "\n".join(bad)
    )
    test_src = "".join(
        p.read_text() for p in sorted((_REPO_ROOT / "tests").glob("*.py"))
    )
    uncovered = [
        site for site in sorted(SITES)
        if f'"{site}"' not in test_src and f"'{site}'" not in test_src
    ]
    assert not uncovered, (
        "registered fault sites never exercised by any test fault plan "
        "or chaos profile:\n" + "\n".join(uncovered)
    )
    # generator metadata vs the registry
    for site, meta in chaos_schedule.SITE_META.items():
        assert site in SITES, f"SITE_META names unregistered site {site}"
        for kind in meta["kinds"]:
            assert kind in SITE_KINDS[site], (
                f"SITE_META draws kind {kind!r} outside {site}'s "
                "declared vocabulary"
            )
    for name, prof in chaos_schedule.PROFILES.items():
        for site in prof["sites"]:
            assert site in chaos_schedule.SITE_META, (
                f"chaos profile {name} draws site {site} with no "
                "SITE_META entry"
            )


def test_graft_entry_import_initializes_no_backend():
    """__graft_entry__ itself must also be import-clean: the driver imports
    it before calling dryrun_multichip, which is where platform selection
    happens."""
    probe = (
        "import __graft_entry__\n"
        "from jax._src import xla_bridge\n"
        "assert xla_bridge._backends == {}, list(xla_bridge._backends)\n"
        "print('GRAFT_IMPORT_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", probe],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(_REPO_ROOT),
    )
    assert proc.returncode == 0, f"probe failed:\n{proc.stdout}\n{proc.stderr}"
    assert "GRAFT_IMPORT_OK" in proc.stdout
