"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. Per device plane
(``/device:TPU:<n>``) the line of XLA ops gives one interval per executed
op, under XLA's own names. From those:

- ``busy_s``: the union of the op intervals, averaged over device planes;
  ``idle share = 1 - busy_s / window_s`` with ``window_s`` the traced span
  (first op start to last op end over all devices);
- ``device_ops``: ops ranked by summed SELF time. Control-flow ops
  (``while``, ``conditional``, ``call``) enclose the ops of their bodies
  on the same line, so a parent's time is its interval minus what its
  children cover; without that a ``while`` would own the whole iteration;
- ``collective_s``: time of the collective ops (all-reduce, all-gather,
  reduce-scatter, all-to-all, collective-permute) on one device, and
  ``collective_exposed_s``: the part of it no other op overlaps;
- ``idle_gaps``: the longest gaps between ops on one device, named by the
  ops on either side (no host annotation exists yet to name a cause:
  PERF.md, open questions).

The arithmetic works on plain ``(start_ns, end_ns, name)`` tuples so that
it can be checked on a hand-built trace.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re

OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
TOP = 10


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events) -> list[tuple[str, int]]:
    """``(name, self_ns)`` per event: its duration minus the part its
    direct children cover, never under 0. An event is a child of the
    innermost earlier event that encloses it whole; two that overlap in
    part (an asynchronous collective and the compute beside it) are
    siblings."""
    out = []
    stack: list[list] = []  # [end, name, duration, covered-by-children]

    def pop():
        _, name, dur, covered = stack.pop()
        out.append((name, max(dur - covered, 0)))

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and (stack[-1][0] <= s or e > stack[-1][0]):
            pop()
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0])
    while stack:
        pop()
    return out


def leaves(events):
    """Events that enclose no other event (the ops that occupy the chip)."""
    ordered = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out = []
    for i, (s, e, name) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or not (nxt[0] < e and nxt[1] <= e):
            out.append((s, e, name))
    return out


def merged(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def enclosed(disjoint, s: int, e: int) -> bool:
    """Whether ``[s, e]`` lies inside one of the sorted, disjoint intervals."""
    i = bisect.bisect_right(disjoint, (s, float("inf"))) - 1
    return i >= 0 and disjoint[i][0] <= s and e <= disjoint[i][1]


def exposed_ns(collectives, others) -> int:
    """Length of ``collectives`` intervals that no ``others`` interval overlaps."""
    others = merged(others)
    starts = [s for s, _ in others]
    covered = 0
    for cs, ce in merged(collectives):
        i = max(bisect.bisect_right(starts, cs) - 1, 0)
        while i < len(others) and others[i][0] < ce:
            covered += max(0, min(ce, others[i][1]) - max(cs, others[i][0]))
            i += 1
    return union_ns(collectives) - covered


def gaps(events, top: int = TOP) -> list[tuple[str, int]]:
    """The longest idle gaps on one device, named by their neighbours."""
    ordered = sorted(events)
    out, end, last = [], None, None
    for s, e, name in ordered:
        if end is not None and s > end:
            out.append((f"after {last} before {name}", s - end))
        if end is None or e > end:
            end, last = e, name
    return sorted(out, key=lambda g: -g[1])[:top]


def rank(pairs, top: int = TOP) -> list[tuple[str, int]]:
    total: dict[str, int] = {}
    for name, ns in pairs:
        total[name] = total.get(name, 0) + ns
    return sorted(total.items(), key=lambda kv: -kv[1])[:top]


def reduce_lines(device_lines: dict[str, list]) -> dict:
    """``{plane name: [(start_ns, end_ns, op name), ...]}`` -> the numbers
    above. Seconds are floats; nothing is rounded."""
    if not device_lines or not any(device_lines.values()):
        raise ValueError("trace holds no device op: nothing ran on the device")
    start = min(s for evs in device_lines.values() for s, _, _ in evs)
    end = max(e for evs in device_lines.values() for _, e, _ in evs)
    busy = [
        union_ns((s, e) for s, e, _ in evs) for evs in device_lines.values()
    ]
    # one device stands for all in the per-op numbers: the first by name
    # among those with the most collective ops (all alike on a real mesh)
    first = device_lines[max(
        sorted(device_lines),
        key=lambda name: sum(
            1 for _, _, n in device_lines[name] if COLLECTIVE.match(n)
        ),
    )]
    coll = merged((s, e) for s, e, n in first if COLLECTIVE.match(n))
    # what can hide a collective: ops that occupy the chip (leaves) and are
    # no part of a collective themselves
    rest = [
        (s, e) for s, e, n in leaves(first)
        if not COLLECTIVE.match(n) and not enclosed(coll, s, e)
    ]
    ns = 1e-9
    return {
        "devices": len(device_lines),
        "window_s": (end - start) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_per_device": [b * ns for b in busy],
        "device_ops": [[n, t * ns] for n, t in rank(self_times(first))],
        "collective_s": union_ns(coll) * ns,
        "collective_exposed_s": exposed_ns(coll, rest) * ns,
        "collective_calls": sum(1 for _, _, n in first if COLLECTIVE.match(n)),
        "idle_gaps": [[n, t * ns] for n, t in gaps(first)],
        "op_events": len(first),
    }


LAYOUT = re.compile(r"\{[^{}]*\}")
SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(name: str) -> str:
    """The TPU names an op event by its whole HLO instruction
    (``%fusion.5 = bf16[64,17]{...} fusion(...)``): keep the op's own name
    and the largest shape of its result, which is what tells two fusions
    apart for a reader."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    rest = LAYOUT.sub("", rest)
    result = rest[: rest.index(")") + 1] if rest.startswith("(") else rest.split(" ", 1)[0]
    shapes = SHAPE.findall(result)

    def elements(shape: str) -> int:
        dims = shape[shape.index("[") + 1 : -1]
        return math.prod(int(d) for d in dims.split(",")) if dims else 1

    largest = max(shapes, key=elements, default="")
    return (op.lstrip("%") + " " + largest).strip()


def _events(line) -> list:
    return [
        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), short_name(ev.name))
        for ev in line.events
    ]


def device_lines_of(profile, host_stand_in: bool = False) -> dict[str, list]:
    """The XLA-op line of every device plane of a ``ProfileData``.

    ``host_stand_in`` is for a rehearsal on the CPU, which has no device
    plane: the XLA runtime's host threads stand in for devices so that the
    same arithmetic runs; what comes out is not a device number."""
    out = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name] = _events(line)
        elif host_stand_in and plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    out[line.name] = [
                        ev for ev in _events(line)
                        if not ev[2].startswith(("Thread", "Thunk", "end: "))
                    ]
    return {name: evs for name, evs in out.items() if evs}


def reduce_file(path: str, host_stand_in: bool = False) -> dict:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    return reduce_lines(device_lines_of(profile, host_stand_in))


def reduce_dir(trace_dir: str, host_stand_in: bool = False) -> dict:
    """Reduce the one ``.xplane.pb`` a ``jax.profiler`` trace left under
    ``trace_dir``."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} .xplane.pb files under {trace_dir}, expected 1"
        )
    return dict(reduce_file(found[0], host_stand_in), file=found[0])
