"""The reduction from a profiler trace to numbers, on a trace built by hand
(known answers) and on one small trace recorded on the chip."""

import os

import pytest

from benchmarks.harness import manifest, trace_reduce as tr

US = 1000  # ns


def xspace(planes: dict) -> str:
    """A text-format XSpace: ``{plane: {line: [(start_us, dur_us, name)]}}``."""
    out = []
    for p_id, (plane, lines) in enumerate(planes.items(), start=1):
        names = sorted({n for evs in lines.values() for _, _, n in evs})
        ids = {n: i for i, n in enumerate(names, start=1)}
        body = [f'id: {p_id} name: "{plane}"']
        for l_id, (line, evs) in enumerate(lines.items(), start=1):
            events = " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * US * 1000} "
                f"duration_ps: {d * US * 1000} }}"
                for s, d, n in evs
            )
            body.append(
                f'lines {{ id: {l_id} name: "{line}" timestamp_ns: 0 {events} }}'
            )
        for n, i in ids.items():
            body.append(
                f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            )
        out.append("planes { " + " ".join(body) + " }")
    return "\n".join(out)


# device 0: a while op encloses three body ops, one an all-reduce of which
# 10 us are covered by nothing else... every leaf is serial on a TPU line,
# so overlap is built with a second, longer fusion; then a 20 us gap and a
# final op. device 1: two overlapping ops. A host plane that must be ignored.
HAND = {
    "/device:TPU:0": {
        "XLA Ops": [
            (0, 100, "while.1"),
            (0, 30, "fusion.1"),
            (30, 20, "all-reduce.1"),
            (40, 25, "fusion.7"),       # overlaps the all-reduce's last 10 us
            (70, 30, "fusion.2"),
            (120, 30, "fusion.1"),
        ],
        "Steps": [(0, 150, "step 0")],
    },
    "/device:TPU:1": {
        "XLA Ops": [(10, 30, "fusion.1"), (20, 40, "fusion.2")],
    },
    "/host:CPU": {"python": [(0, 500, "main")]},
}


def hand_profile():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(xspace(HAND))


def test_hand_built_trace_reduces_exactly():
    lines = tr.device_lines_of(hand_profile())
    assert sorted(lines) == ["/device:TPU:0", "/device:TPU:1"]
    r = tr.reduce_lines(lines)
    us = 1e-6
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(150 * us)
    # device 0: [0,100] + [120,150] = 130; device 1: [10,60] = 50
    assert r["busy_s_per_device"] == pytest.approx([130 * us, 50 * us])
    assert r["busy_s"] == pytest.approx(90 * us)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.4)
    # the all-reduce runs [30,50]; fusion.7 covers [40,50]
    assert r["collective_calls"] == 1
    assert r["collective_s"] == pytest.approx(20 * us)
    assert r["collective_exposed_s"] == pytest.approx(10 * us)
    # self times: fusion.1 30+30, fusion.2 30, fusion.7 25, all-reduce 20;
    # the while keeps what no child covers: 100 - (30+20+25+30) = -5 -> the
    # overlap of two children is theirs, so the parent's share is its rest
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(60 * us)
    assert ops["fusion.2"] == pytest.approx(30 * us)
    assert ops["fusion.7"] == pytest.approx(25 * us)
    assert ops["all-reduce.1"] == pytest.approx(20 * us)
    assert [n for n, _ in r["device_ops"]][:2] == ["fusion.1", "fusion.2"]
    assert ops["while.1"] <= 5 * us
    # one gap on device 0: [100, 120], after the while, before fusion.1
    assert r["idle_gaps"][0][1] == pytest.approx(20 * us)
    assert "while.1" in r["idle_gaps"][0][0] and "fusion.1" in r["idle_gaps"][0][0]
    assert len(r["idle_gaps"]) == 1


def test_host_planes_are_not_devices_unless_rehearsing():
    host_only = {"/host:CPU": {"tf_XLAEigen/1": [(0, 10, "dot.1"), (20, 10, "end: dot.1")]}}
    from jax.profiler import ProfileData

    profile = ProfileData.from_text_proto(xspace(host_only))
    with pytest.raises(ValueError, match="no device op"):
        tr.reduce_lines(tr.device_lines_of(profile))
    stand_in = tr.device_lines_of(profile, host_stand_in=True)
    assert [n for _, _, n in stand_in["tf_XLAEigen/1"]] == ["dot.1"]


@pytest.mark.parametrize("hlo,short", [
    ("%fusion.539 = (bf16[64]{0:T(256)(128)(2,1)S(1)}, bf16[4194304,64]{0,1:T(8,128)(2,1)}) "
     "fusion(bf16[4194304,64]{0,1} %fusion.531), kind=kOutput", "fusion.539 bf16[4194304,64]"),
    ("%copy.85 = bf16[256,65536,17]{1,0,2:T(8,128)(2,1)} copy(bf16[256,65536,17]{1,2,0} %gte.1)",
     "copy.85 bf16[256,65536,17]"),
    ("%all-reduce.3 = f32[]{:T(128)} all-reduce(f32[] %x), replica_groups={}", "all-reduce.3 f32[]"),
    ("dot_general.5", "dot_general.5"),
])
def test_short_name_keeps_the_op_and_its_largest_result(hlo, short):
    assert tr.short_name(hlo) == short
    assert bool(tr.COLLECTIVE.match(short)) == short.startswith("all-reduce")


@pytest.mark.parametrize("intervals,total", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 20)], 20),
    ([(0, 10), (10, 20)], 20), ([(0, 10), (30, 40), (5, 8)], 20),
])
def test_union(intervals, total):
    assert tr.union_ns(intervals) == total


def test_exposed_with_several_collectives():
    coll = [(0, 10), (20, 30), (50, 60)]
    rest = [(5, 25), (55, 70)]
    # exposed: [0,5] + [25,30] + [50,55]
    assert tr.exposed_ns(coll, rest) == 15


RECORDED = os.path.join(manifest.BENCH_DIR, "testdata", "ddpg_lift_uniform.xplane.pb")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces():
    """A trace the chip wrote (TPU v5 lite, one cadence window of the
    ``ddpg_lift_uniform`` cell at reduced size): the reduction finds the
    device plane, a busy share between 0 and 1, and ops under XLA's names."""
    r = tr.reduce_file(RECORDED)
    assert r["devices"] == 1 and r["op_events"] > 100
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == tr.TOP
    assert all(isinstance(n, str) and t > 0 for n, t in r["device_ops"])
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * 1.0001
    assert r["collective_calls"] == 0
