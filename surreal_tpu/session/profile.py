"""On-demand profiling: ``jax.profiler`` windows started and stopped at
iteration boundaries, and each capture reduced to a digest by the program
itself, so that an operator gets an answer and not only a directory.

Three triggers, one manager (owned by SessionHooks, ticked once per
``end_iteration``), one path:

- **trigger file** — ``surreal_tpu profile <folder>`` writes
  ``<folder>/profile.trigger``; the running session polls for it (stat
  throttled to once per second — the hot loop pays nothing) and captures
  a ``session.profile.num_iters`` window starting at the next iteration
  boundary, then removes the file. The file's JSON body may override
  ``num_iters``.
- **request()** — the incident engine's programmatic spelling of the same.
- **slow-iteration auto-trigger** — when ``session.profile.
  slow_iter_factor`` is set, an iteration whose host wall time exceeds
  factor x the iteration-time EWMA starts a capture automatically (at
  most ``max_auto_captures`` per run). Detection is pure host clock
  deltas between boundary ticks: no device syncs, transfer-guard safe.

A capture starts and stops on an idle device (the tick's ``fence``): the
loop dispatches asynchronously and runs up to a cadence ahead of the
device, so without the two fences a window of n host iterations would
hold whatever the device happened to be doing. With them it holds exactly
the n iterations between the ticks. The Python tracer is off (it slows
the loop it would observe) and host annotations are kept.

Every capture directory is ``<folder>/telemetry/profiles/<tag>/`` and is
announced as a ``profile`` telemetry event carrying the **digest**
(``diag`` renders it), reduced OFF the loop's thread with
``jax.profiler.ProfileData``:

- ``devices``, ``steps`` (iterations the device ran in the window),
  ``window_s`` (first op start to last op end), ``busy_s`` (union of op
  intervals), ``idle_s``, all of one device (the first by name);
- ``phases``: per phase of ``utils/phases.py`` and for ``unattributed``,
  the device time its ops OWN, per iteration: at every instant the
  innermost running op owns the time, so a ``while`` keeps only what its
  body leaves (the rule of a self time) and the phases sum to ``busy_s``
  exactly. An op's phase is the first vocabulary name in the ``op_name``
  path of its HLO instruction; this runtime's op events carry the
  instruction text without metadata, so the path comes from the compiled
  program's HLO text, which the cost accountant keeps the source of
  (``instruction name -> op_name``, looked up under the module the op ran
  in). A fusion takes the ``op_name`` XLA gave the fusion instruction,
  which is its root's. Each phase lists its three largest ops, its
  ``ops_per_iter`` (the device op events whose phase it is) and its
  ``short_ops``: how many of them ran under :data:`SHORT_OP_NS` and the
  time those own;
- ``parts``: the same device time split a second way, by the model part
  of ``utils/phases.py`` (``PARTS``) each op's path names, read and owned
  by the same rules; ``unattributed`` holds what no part names (all of a
  program whose model scopes none), so parts too sum to ``busy_s``;
- ``subphases``: for each phase that has one in the capture, its time by
  sub-scope (``utils/phases.py`` ``SUBPHASES``: ``collect/act``) and
  ``rest`` (its ops outside each of them), which sum to the phase;
- ``parts_by_phase``: the joint of the two splits, ``{part: {phase: ms}}``:
  a row sums to the part, a column (``unattributed`` with it) to the phase;
- ``kernels``: every Pallas kernel (an HLO ``custom-call`` whose target is
  ``tpu_custom_call``, read from the compiled program's text) under the
  ``name=`` its ``pl.pallas_call`` was given, which is its instruction's
  name less the ``.<n>`` of a call site: time and calls per iteration over
  all ``sites``, its part, and its time by phase. XLA:TPU's own kernel for
  ``jax.lax.ragged_dot`` has that target too and is listed as
  ``ragged-dot-none`` / ``ragged-dot-metadata``;
- ``idle_by_span``: every device idle gap charged to the innermost
  program span that covers it on the loop's thread (``metrics-sync``,
  ``engine.boundary``, ``engine.step``, ``iteration``, ...), or to
  ``none``. A span is the program's if the tracer has seen its name or
  the loop engine annotates it.

Every op of the first device, not only a label's largest three, is written
once beside the capture as ``<capture dir>/ops.json``: name and shape, phase,
sub, part, kernel, calls and owned ms per iteration (they sum to ``busy_s``).

A digest that fails writes ``digest_error`` and never stops training.
The arithmetic takes plain tuples so that a test can hand-build a trace.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import threading
import time

from surreal_tpu.session.telemetry import PROFILES_DIR, TELEMETRY_DIR
from surreal_tpu.utils.phases import (
    PARTS, PHASES, REST, SUBPHASES, UNATTRIBUTED, part_of, phase_of,
)

TRIGGER_FILE = "profile.trigger"
# the loop engine's own annotations (engine/core.py); a tracer's span
# names join them
ENGINE_SPANS = ("iteration", "engine.step", "engine.boundary")
DIGEST_WAIT_S = 120.0  # close() waits this long for a digest in flight
TOP_OPS = 3
SHORT_OP_NS = 1000  # a device op event shorter than this is a short op
OPS_FILE = "ops.json"  # the whole op table, beside the capture
OPS_COLUMNS = (
    "op", "phase", "sub", "part", "kernel", "calls_per_iter", "ms_per_iter",
)

# EWMA shape for the slow-iteration detector: first _WARM_TICKS ticks only
# seed the average (compiles + cache warmup dominate there), later ticks
# blend at _ALPHA. A capture in progress suspends detection.
_WARM_TICKS = 10
_ALPHA = 0.1


def write_trigger(folder: str, num_iters: int | None = None) -> str:
    """Drop the trigger file a live session polls for (the CLI side of
    ``surreal_tpu profile <folder>``). Atomic tmp+rename: the session
    may race the write."""
    path = os.path.join(folder, TRIGGER_FILE)
    body = {} if num_iters is None else {"num_iters": int(num_iters)}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, path)
    return path


# -- the arithmetic, on plain tuples ------------------------------------------


def owned_pieces(events):
    """Yield ``(index, start, end)`` pieces such that every instant some
    event covers belongs to exactly one piece: that of the innermost event
    running then (the one started last). ``events`` are ``(start, end,
    ...)`` tuples; a parent keeps what its children leave, and of two that
    overlap in part the later one owns the overlap."""
    order = sorted(
        range(len(events)), key=lambda i: (events[i][0], -events[i][1])
    )
    stack: list[int] = []
    t = 0

    def advance(to):
        nonlocal t
        while stack:
            top = stack[-1]
            end = events[top][1]
            if end <= t:
                stack.pop()
            elif end <= to:
                yield top, t, end
                t = end
                stack.pop()
            else:
                if to > t:
                    yield top, t, to
                t = to
                return
        t = to

    for i in order:
        yield from advance(events[i][0])
        stack.append(i)
    yield from advance(float("inf"))


def idle_gaps(events) -> list[tuple[int, int]]:
    """The gaps between the merged ``(start, end, ...)`` intervals."""
    out, end = [], None
    for s, e, *_ in sorted(events):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def charge_gaps(gaps, spans) -> dict[str, int]:
    """ns of ``gaps`` per name of the innermost ``(start, end, name)``
    span covering each instant, the rest under ``none``."""
    pieces = [(a, b, spans[i][2]) for i, a, b in owned_pieces(spans)]
    starts = [a for a, _, _ in pieces]
    out: dict[str, int] = {}
    for ga, gb in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, ga) - 1, 0)
        while i < len(pieces) and pieces[i][0] < gb:
            a, b, name = pieces[i]
            ns = min(gb, b) - max(ga, a)
            if ns > 0:
                out[name] = out.get(name, 0) + ns
                covered += ns
            i += 1
        if gb - ga > covered:
            out["none"] = out.get("none", 0) + (gb - ga) - covered
    return out


# the maps a digest labels its ops from (``CostAccountant.labels``), in the
# order of an op event's fields after its name, each with what an op reads
# that its map does not hold
LABELS = {
    "phases": UNATTRIBUTED, "parts": UNATTRIBUTED, "subphases": UNATTRIBUTED,
    "kernels": None,
}
# what an op event's tuple may leave out after its phase: part, sub, kernel
_NO_LABELS = tuple(LABELS.values())[1:]


def op_table(events) -> dict:
    """``{(name, phase, part, sub, kernel): [owned ns, events, short
    events, their owned ns]}`` of one device's op events ``(start, end,
    name, phase[, part[, sub[, kernel]]])``, in the order in which the ops
    first own time: every table of the digest is a sum over these rows. A
    key's sub is the last segment of the event's ``phase/sub``; one that
    names another phase than the op's own (the maps are read one label at
    a time) or none is ``rest``, so a phase's subs and rest sum to it."""
    own = [0] * len(events)
    first: list[int] = []
    for i, a, b in owned_pieces(events):
        if not own[i]:
            first.append(i)
        own[i] += b - a
    first += [i for i, t in enumerate(own) if not t]  # wholly covered
    table: dict[tuple, list[int]] = {}
    rows: dict[tuple, list[int]] = {}  # an event's own labels -> its row
    for i in first:
        ev = events[i]
        row = rows.get(ev[2:])
        if row is None:
            name, phase, part, sub, kernel = (
                tuple(ev[2:]) + _NO_LABELS[len(ev) - 4:]
            )
            top, _, sub = sub.partition("/")
            if top != phase or not sub:
                sub = REST
            row = rows[ev[2:]] = table.setdefault(
                (name, phase, part, sub, kernel), [0, 0, 0, 0]
            )
        row[0] += own[i]
        row[1] += 1
        if ev[1] - ev[0] < SHORT_OP_NS:
            row[2] += 1
            row[3] += own[i]
    return table


def _sum_by(table: dict, *columns: int) -> dict:
    """Owned ns of ``table`` by the key's ``columns``, first seen first;
    a row that owns nothing (an op its children cover) is in no sum."""
    out: dict = {}
    for key, row in table.items():
        if row[0]:
            k = tuple(key[c] for c in columns)
            out[k] = out.get(k, 0) + row[0]
    return out


def _split(table: dict, column: int, vocabulary, busy: int,
           per_iter_ms: float) -> dict:
    """``{label: {ms_per_iter, share_of_busy, top_ops}}`` of the op table
    under the labels of key ``column`` (1 the phase, 2 the part), in the
    vocabulary's order with ``unattributed`` last and always present."""
    by_label = {UNATTRIBUTED: 0}
    for (label,), t in _sum_by(table, column).items():
        by_label[label] = t
    by_op = _sum_by(table, column, 0)
    out = {}
    for label in (*vocabulary, UNATTRIBUTED):
        if label not in by_label:
            continue
        ops = sorted(
            ((n, t) for (p, n), t in by_op.items() if p == label),
            key=lambda kv: -kv[1],
        )[:TOP_OPS]
        out[label] = {
            "ms_per_iter": by_label[label] * per_iter_ms,
            "share_of_busy": by_label[label] / busy if busy else 0.0,
            "top_ops": [[n, t * per_iter_ms] for n, t in ops],
        }
    return out


def _subphases(table: dict, per_iter_ms: float) -> dict:
    """``{phase: {sub | "rest": ms_per_iter}}`` for the phases with a sub
    in the table, subs in the vocabulary's order."""
    by_sub = _sum_by(table, 1, 3)
    out = {}
    for phase in sorted({p for p, sub in by_sub if sub != REST},
                        key=PHASES.index):
        out[phase] = {
            sub: by_sub[phase, sub] * per_iter_ms
            for sub in SUBPHASES[phase] if (phase, sub) in by_sub
        }
        out[phase][REST] = by_sub.get((phase, REST), 0) * per_iter_ms
    return out


def _kernels(table: dict, steps: int, per_iter_ms: float) -> dict:
    """``{kernel: {ms_per_iter, calls_per_iter, sites, part, by_phase}}``
    of the rows that are a Pallas call, every call site summed; ``part``
    is the model part that most of its time is in."""
    found: dict[str, dict] = {}
    for (name, phase, part, _, kernel), row in table.items():
        if kernel is None:
            continue
        k = found.setdefault(
            kernel, {"ns": 0, "calls": 0, "sites": set(), "part": {}, "phase": {}}
        )
        k["ns"] += row[0]
        k["calls"] += row[1]
        k["sites"].add(name)
        k["part"][part] = k["part"].get(part, 0) + row[0]
        k["phase"][phase] = k["phase"].get(phase, 0) + row[0]
    return {
        kernel: {
            "ms_per_iter": k["ns"] * per_iter_ms,
            "calls_per_iter": k["calls"] / steps,
            "sites": len(k["sites"]),
            "part": max(k["part"], key=k["part"].get),
            "by_phase": {p: t * per_iter_ms for p, t in k["phase"].items()},
        }
        for kernel, k in found.items()
    }


def reduce_digest(device_ops: dict, host_spans, steps: int) -> dict:
    """The digest's numbers from ``{device: [(start_ns, end_ns, name,
    phase[, part[, sub[, kernel]]]), ...]}``, the loop thread's program
    spans ``[(start_ns, end_ns, name), ...]`` and the iterations the window
    holds. Every table is of the first device by name; seconds are floats,
    nothing is rounded. ``ops`` is :data:`OPS_COLUMNS` of every op of that
    device, largest first: :func:`digest_capture` moves it to ``ops.json``."""
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"devices": len(device_ops), "steps": int(steps)}
    if not device_ops:
        return out
    ns = 1e-9
    steps = max(int(steps), 1)
    per_iter_ms = 1e-6 / steps
    events = device_ops[sorted(device_ops)[0]]
    table = op_table(events)
    busy = sum(row[0] for row in table.values())
    window = max(ev[1] for ev in events) - min(ev[0] for ev in events)
    phases = _split(table, 1, PHASES, busy, per_iter_ms)
    for phase, entry in phases.items():
        rows = [row for key, row in table.items() if key[1] == phase]
        entry["ops_per_iter"] = sum(row[1] for row in rows) / steps
        entry["short_ops"] = {
            "per_iter": sum(row[2] for row in rows) / steps,
            "ms_per_iter": sum(row[3] for row in rows) * per_iter_ms,
        }
    parts_by_phase: dict = {}
    for (part, phase), t in _sum_by(table, 2, 1).items():
        parts_by_phase.setdefault(part, {})[phase] = t * per_iter_ms
    out.update(
        window_s=window * ns,
        busy_s=busy * ns,
        idle_s=(window - busy) * ns,
        busy_s_per_device=[
            (
                max(ev[1] for ev in evs) - min(ev[0] for ev in evs)
                - sum(b - a for a, b in idle_gaps(evs))
            ) * ns
            for _, evs in sorted(device_ops.items())
        ],
        phases=phases,
        parts=_split(table, 2, PARTS, busy, per_iter_ms),
        subphases=_subphases(table, per_iter_ms),
        parts_by_phase=parts_by_phase,
        kernels=_kernels(table, steps, per_iter_ms),
        idle_by_span={
            k: v * ns
            for k, v in charge_gaps(idle_gaps(events), list(host_spans)).items()
        },
        ops=[
            [name, phase, sub, part, kernel, row[1] / steps, row[0] * per_iter_ms]
            for (name, phase, part, sub, kernel), row in sorted(
                table.items(), key=lambda kv: -kv[1][0]
            )
        ],
    )
    return out


# -- from the compiled program and the capture to those tuples ----------------

_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_OP = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_CALLEES = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_OPCODE = re.compile(r"[\]})]\s([a-z][\w\-]*)\(")
_HLO_RELAYOUT = ("copy", "copy-start", "copy-done")
_HLO_KERNEL = 'custom_call_target="tpu_custom_call"'
_HLO_SITE = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def hlo_op_phases(hlo_text: str, *labels_of) -> tuple:
    """``(module name, {instruction name: phase})`` of a compiled
    program's HLO text, for the instructions that have a phase. With
    ``labels_of`` (``part_of``: a model part; ``subphase_of``: a sub-scope
    of their phase), one map for each after the module's name, all from one
    reading of the text; the rules are one:

    1. its own: the first vocabulary name in its ``op_name`` metadata;
    2. a fusion without one takes its fused computation's: the root's,
       else the most frequent among the instructions fused;
    3. an instruction without one inside a computation that an
       instruction with a phase calls (the body of a ``while`` XLA made of
       a gather) takes the caller's, from the innermost caller outwards;
    4. an instruction XLA made itself, without a ``jit(...)/`` path (a
       relayout ``copy``, a ``convert``, the tree a ``cumsum`` expands to),
       takes its first user's, since such an op exists to feed that user,
       else its first operand's; so does a ``copy`` whatever its path
       (layout assignment stamps one with the enclosing call's). Any
       other instruction whose own path lies outside every phase stays
       outside.
    """
    head = _HLO_MODULE.match(hlo_text)
    computations: dict[str, list] = {}   # name -> [(instr, is_root)]
    order: list[tuple[str, list[str], str | None]] = []  # instr, refs, calls
    home: dict[str, str] = {}            # instr -> its computation
    caller: dict[str, str] = {}          # computation -> an instr calling it
    placed: set[str] = set()             # instrs with a path of their own
    paths: dict[str, str] = {}           # instr -> its op_name
    current = here = None
    for line in hlo_text.splitlines():
        if line.startswith("}"):
            current = None
            continue
        comp = _HLO_COMPUTATION.match(line)
        if comp and " = " not in line.split("(", 1)[0]:
            here = comp.group(1)
            current = computations.setdefault(here, [])
            continue
        m = _HLO_OP.match(line)
        if not m or current is None:
            continue
        root, name, rest = m.groups()
        current.append((name, bool(root)))
        home[name] = here
        for one, several in _HLO_CALLEES.findall(rest):
            for callee in (one, *_HLO_REF.findall(several)):
                if callee:
                    caller.setdefault(callee, name)
        named = _HLO_OP_NAME.search(rest)
        opcode = _HLO_OPCODE.search(rest)
        if (
            named and named.group(1).startswith("jit(")
            and not (opcode and opcode.group(1) in _HLO_RELAYOUT)
        ):
            placed.add(name)
        if named:
            paths[name] = named.group(1)
        calls = _HLO_CALLS.search(rest)
        order.append((
            name, _HLO_REF.findall(rest.split(", metadata=", 1)[0]),
            calls.group(1) if calls else None,
        ))
    users: dict[str, list[str]] = {}
    for name, refs, _ in order:
        for ref in refs:
            users.setdefault(ref, []).append(name)
    return (head.group(1) if head else ""), *(
        _hlo_labels(
            label_of, paths, computations, order, home, caller, placed, users
        )
        for label_of in labels_of or (phase_of,)
    )


def _hlo_labels(label_of, paths, computations, order, home, caller, placed,
                users) -> dict[str, str]:
    """One label's map over a text :func:`hlo_op_phases` has read."""
    of_path: dict[str, str] = {}  # many instructions share a path
    phases: dict[str, str] = {}
    for name, path in paths.items():
        label = of_path.get(path)
        if label is None:
            label = of_path[path] = label_of(path)
        if label != UNATTRIBUTED:
            phases[name] = label
    for name, _, calls in order:
        body = computations.get(calls) if name not in phases else None
        if body:
            inner = [phases[n] for n, _ in body if n in phases]
            of_root = [phases[n] for n, is_root in body if is_root and n in phases]
            if inner:
                phases[name] = (
                    of_root[0] if of_root else max(set(inner), key=inner.count)
                )
    own = dict(phases)
    for name, _, _ in order:
        at = name
        while name not in phases and home.get(at) in caller:
            at = caller[home[at]]
            if at in own:
                phases[name] = own[at]
    for name, _, _ in reversed(order):       # users come later in the text
        if name not in phases and name not in placed:
            for user in users.get(name, ()):
                if user in phases:
                    phases[name] = phases[user]
                    break
    for name, refs, _ in order:              # operands come earlier
        if name not in phases and name not in placed:
            for ref in refs:
                if ref in phases:
                    phases[name] = phases[ref]
                    break
    return phases


def hlo_kernels(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction name: kernel})`` of the Pallas calls
    in a compiled program's HLO text: every ``custom-call`` whose target is
    ``tpu_custom_call``, under its instruction's name less the ``.<n>`` XLA
    gives a call site, which is the ``name=`` of its ``pl.pallas_call``
    (a call without one is ``custom-call``). XLA:TPU lowers
    ``jax.lax.ragged_dot`` to the same target under its own names
    (``ragged-dot-none``, ``ragged-dot-metadata``): they are listed too."""
    head = _HLO_MODULE.match(hlo_text)
    kernels = {}
    for line in hlo_text.splitlines():
        if _HLO_KERNEL in line and " custom-call(" in line:
            m = _HLO_OP.match(line)
            if m:
                kernels[m.group(2)] = _HLO_SITE.sub("", m.group(2))
    return (head.group(1) if head else ""), kernels


def _instruction(event_name: str) -> tuple[str, str]:
    """``(instruction name, name and largest result shape)`` of a device
    op event, which this runtime names by its whole HLO instruction
    (``%fusion.5 = bf16[64,17]{...} fusion(...)``)."""
    if " = " not in event_name:
        return event_name, event_name
    op, rest = event_name.split(" = ", 1)
    op = op.lstrip("%")
    rest = _LAYOUT.sub("", rest)
    result = (
        rest[: rest.index(")") + 1] if rest.startswith("(")
        else rest.split(" ", 1)[0]
    )

    def elements(shape: str) -> int:
        n = 1
        for d in shape[shape.index("[") + 1:-1].split(","):
            n *= int(d) if d else 1
        return n

    largest = max(_SHAPE.findall(result), key=elements, default="")
    return op, f"{op} {largest}".strip()


def read_capture(path: str, labels: dict, span_names, on_parsed=None) -> tuple:
    """``(device_ops, loop_spans, host_span_counts)`` of one
    ``.xplane.pb``: per device plane the ``XLA Ops`` line as ``(start_ns,
    end_ns, name, phase, part, sub, kernel)``, each op's labels looked up in
    ``labels`` (``{one of LABELS: {HLO module: {instruction: label}}}``)
    under the module (``XLA Modules`` line) it ran in; the program's spans on the loop's
    thread (the host line with the most ``engine.step``); and how often
    each program span appears on any host line. ``on_parsed(seconds)`` is
    told how long the file took to parse: the parser is one foreign call
    that keeps the interpreter to itself, so every other thread of the
    process stood still that long (35 s for three iterations of a thousand
    five-layer acting steps)."""
    from jax.profiler import ProfileData

    span_names = set(span_names) | set(ENGINE_SPANS)
    maps = [(labels.get(k, {}), absent) for k, absent in LABELS.items()]
    device_ops: dict[str, list] = {}
    lines: list[list] = []
    t0 = time.monotonic()
    planes = ProfileData.from_file(path).planes
    if on_parsed is not None:
        on_parsed(time.monotonic() - t0)
    for plane in planes:
        if plane.name.startswith("/device:"):
            by_name = {line.name: line for line in plane.lines}
            if "XLA Ops" not in by_name:
                continue
            modules = sorted(
                (int(ev.start_ns), ev.name.split("(", 1)[0])
                for ev in (
                    by_name["XLA Modules"].events
                    if "XLA Modules" in by_name else ()
                )
            )
            starts = [s for s, _ in modules]
            # a program's loop runs an instruction a thousand times: its
            # name is taken apart and looked up once a module
            seen: dict[tuple[str, str], tuple] = {}
            ops = []
            for ev in by_name["XLA Ops"].events:
                s = int(ev.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][1] if i >= 0 else ""
                fields = seen.get((module, ev.name))
                if fields is None:
                    instr, shown = _instruction(ev.name)
                    fields = seen[module, ev.name] = (
                        shown,
                        *(m.get(module, {}).get(instr, absent) for m, absent in maps),
                    )
                ops.append((s, s + int(ev.duration_ns), *fields))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
                    for ev in line.events if ev.name in span_names
                ]
                if spans:
                    lines.append(spans)
    counts: dict[str, int] = {}
    for spans in lines:
        for _, _, name in spans:
            counts[name] = counts.get(name, 0) + 1
    loop = max(
        lines, default=[],
        key=lambda spans: (
            sum(1 for _, _, n in spans if n == "engine.step"), len(spans)
        ),
    )
    return device_ops, loop, counts


def digest_capture(trace_dir: str, labels: dict, span_names,
                   steps: int | None = None, on_parsed=None) -> dict:
    """Reduce the one ``.xplane.pb`` a capture left under ``trace_dir``,
    and write every op of its first device to ``trace_dir``'s
    :data:`OPS_FILE`. ``steps`` is the number of iterations the fenced
    window holds; a capture cut short has none, and the ``iteration`` steps
    seen on the host stand in."""
    t0 = time.perf_counter()
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} .xplane.pb files under {trace_dir}, expected 1"
        )
    device_ops, loop_spans, counts = read_capture(
        found[0], labels, span_names, on_parsed
    )
    if steps is None:
        steps = counts.get("iteration", 0)
    out = reduce_digest(device_ops, loop_spans, steps)
    out["host_spans"] = counts
    out["trace_bytes"] = sum(
        os.path.getsize(os.path.join(root, f))
        for root, _, files in os.walk(trace_dir) for f in files
    )
    if "ops" in out:  # the event keeps three names a label, the file all
        with open(os.path.join(trace_dir, OPS_FILE), "w") as f:
            json.dump({
                "device": sorted(k for k, v in device_ops.items() if v)[0],
                "steps": out["steps"], "columns": OPS_COLUMNS,
                "ops": out.pop("ops"),
            }, f)
    out["digest_s"] = time.perf_counter() - t0
    return out


class ProfileManager:
    """Iteration-boundary profiler control. ``tick(iteration)`` is cheap
    in the steady state: one monotonic read, one EWMA update, and (at
    most once per second) one ``os.path.exists``."""

    def __init__(self, session_cfg, folder: str, tracer, log, labels=None,
                 on_hold=None):
        self._folder = folder
        self._tracer = tracer
        self._log = log
        # zero-arg source of the digest's label maps, {one of LABELS: {HLO
        # module: {instruction: label}}} (CostAccountant.labels); called
        # once a digest, off the loop's thread
        self._labels = labels or dict
        # told the seconds for which a digest's parse kept every thread of
        # the process still (read_capture), from the digest's thread
        self._on_hold = on_hold
        prof = session_cfg.get("profile", None)
        self._trigger_enabled = (
            bool(prof.get("trigger_file", True)) if prof is not None else True
        )
        self._num_iters = int(prof.get("num_iters", 5)) if prof is not None else 5
        factor = prof.get("slow_iter_factor", None) if prof is not None else None
        self._slow_factor = float(factor) if factor else None
        self._max_auto = (
            int(prof.get("max_auto_captures", 2)) if prof is not None else 2
        )
        self._auto_fired = 0
        self._trigger_path = os.path.join(folder, TRIGGER_FILE)
        self._last_stat = 0.0
        self._pending: tuple[str, int] | None = None  # (reason, num_iters)
        self._active: dict | None = None
        self._digest: threading.Thread | None = None
        # newest completed capture directory — the incident engine links
        # the capture it auto-requested into the incident record from here
        self.last_capture_dir: str | None = None
        # when the newest capture's digest ended (host clock; None before
        # any): last_capture_t
        self._settled_t: float | None = None
        self._last_tick: float | None = None
        self._last_iter = 0  # newest iteration ticked (close() reports it)
        self._ewma_s: float | None = None
        self._ticks = 0

    # -- capture lifecycle ---------------------------------------------------
    def _start(self, iteration: int, reason: str, num_iters: int, fence) -> None:
        tag = f"iter{iteration:08d}"
        trace_dir = os.path.join(
            self._folder, TELEMETRY_DIR, PROFILES_DIR, tag
        )
        try:
            os.makedirs(trace_dir, exist_ok=True)
            import jax

            if fence is not None:
                fence()
            # no Python tracer: it slows the host loop it would observe
            # and fills the trace with frames no reduction reads; the
            # host's annotations stay
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except Exception as e:
            # profiling must never kill training (missing profiler deps,
            # unwritable folder); record the failure instead
            self._log.warning("profiler start failed (%s): %s", reason, e)
            self._tracer.event(
                "profile", dir=trace_dir, reason=reason, error=str(e)
            )
            return
        self._active = {
            "dir": trace_dir,
            "reason": reason,
            "start_iter": int(iteration),
            "stop_at": int(iteration) + max(1, num_iters),
        }
        self._log.info(
            "profiler capture started (%s) -> %s", reason, trace_dir
        )

    def _stop(self, iteration: int, fence=None) -> None:
        act = self._active
        self._active = None
        fenced = False
        try:
            import jax

            if fence is not None:
                fence()
                fenced = True
            jax.profiler.stop_trace()
        except Exception as e:
            self._log.warning("profiler stop failed: %s", e)
        if act is None:
            return
        self.last_capture_dir = act["dir"]
        self._settled_t = time.time()
        self._log.info("profiler capture saved -> %s", act["dir"])
        fields = dict(
            dir=act["dir"], reason=act["reason"],
            start_iter=act["start_iter"], end_iter=int(iteration),
        )
        # between two fences the device ran exactly these iterations
        steps = int(iteration) - act["start_iter"] if fenced else None
        self._join_digest()
        self._digest = threading.Thread(
            target=self._reduce, args=(fields, steps),
            name="profile-digest", daemon=True,
        )
        self._digest.start()

    def _reduce(self, fields: dict, steps: int | None) -> None:
        """The capture's digest into its ``profile`` event (the digest
        thread's body; a failure is recorded, never raised)."""
        try:
            fields["digest"] = digest_capture(
                fields["dir"], self._labels(),
                getattr(self._tracer, "span_names", ()), steps,
                on_parsed=self._on_hold,
            )
        except Exception as e:
            self._log.warning("profile digest failed: %s", e)
            fields["digest_error"] = f"{type(e).__name__}: {e}"
        self._settled_t = time.time()
        self._tracer.event("profile", **fields)

    @property
    def last_capture_t(self) -> float | None:
        """Host clock at which a capture last disturbed the loop: now while
        one is open or its digest is being reduced (the parse alone keeps
        the interpreter for 35 s after a capture of three thousand-step
        iterations), else when the newest one's digest ended; None before
        any. An incident that opens in that wake does not capture again
        (``IncidentEngine``'s cooldown counts from here)."""
        digesting = self._digest is not None and self._digest.is_alive()
        if self._active is not None or digesting:
            return time.time()
        return self._settled_t

    def _join_digest(self) -> None:
        if self._digest is not None:
            self._digest.join(DIGEST_WAIT_S)
            if self._digest.is_alive():
                self._log.warning(
                    "profile digest still running after %.0fs: left behind",
                    DIGEST_WAIT_S,
                )
            self._digest = None

    def request(self, reason: str, num_iters: int | None = None) -> bool:
        """Queue a capture window starting at the next boundary tick —
        the incident engine's auto-capture path (programmatic spelling of
        the trigger file). Refused (False) while a capture is active or
        already queued, so one incident cannot stack windows."""
        if self._active is not None or self._pending is not None:
            return False
        self._pending = (str(reason), max(1, int(num_iters or self._num_iters)))
        return True

    # -- per-iteration tick --------------------------------------------------
    def tick(self, iteration: int, fence=None) -> None:
        """One boundary tick. ``fence`` blocks until the device has run
        everything dispatched so far; it is called only where a capture
        starts or stops."""
        now = time.monotonic()
        self._last_iter = int(iteration)
        # slow-iteration detector: host wall time between boundary ticks
        if self._last_tick is not None:
            dt = now - self._last_tick
            self._ticks += 1
            if self._ewma_s is None:
                self._ewma_s = dt
            elif self._ticks <= _WARM_TICKS:
                self._ewma_s += (dt - self._ewma_s) / self._ticks
            else:
                if (
                    self._slow_factor is not None
                    and self._active is None
                    and self._pending is None
                    and self._auto_fired < self._max_auto
                    and dt > self._slow_factor * self._ewma_s
                ):
                    self._auto_fired += 1
                    self._log.warning(
                        "slow iteration %d: %.3fs vs %.3fs EWMA (>%.1fx) — "
                        "auto-capturing a profile window",
                        iteration, dt, self._ewma_s, self._slow_factor,
                    )
                    self._pending = (
                        f"slow_iter({dt:.3f}s/{self._ewma_s:.3f}s)",
                        self._num_iters,
                    )
                self._ewma_s += _ALPHA * (dt - self._ewma_s)
        self._last_tick = now

        if self._active is not None:
            if iteration >= self._active["stop_at"]:
                self._stop(iteration, fence)
                # the fences are not an iteration's time
                self._last_tick = time.monotonic()
            return

        if self._pending is not None:
            reason, n = self._pending
            self._pending = None
            self._start(iteration, reason, n, fence)
            self._last_tick = time.monotonic()
            return

        # trigger file, stat-throttled to once per second
        if self._trigger_enabled and now - self._last_stat >= 1.0:
            self._last_stat = now
            if os.path.exists(self._trigger_path):
                n = self._num_iters
                try:
                    with open(self._trigger_path) as f:
                        body = json.load(f)
                    n = int(body.get("num_iters", n))
                except (OSError, json.JSONDecodeError, ValueError, TypeError):
                    pass
                try:
                    os.unlink(self._trigger_path)
                except OSError:
                    pass
                self._start(iteration, "trigger_file", n, fence)
                self._last_tick = time.monotonic()

    def close(self) -> None:
        # a capture cut short by run end must report the iteration it
        # actually reached, not the stop_at it never got to; then wait,
        # bounded, for the digest in flight
        if self._active is not None:
            self._stop(self._last_iter)
        self._join_digest()
