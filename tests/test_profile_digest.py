"""The profile digest's arithmetic (session/profile.py) on traces built by
hand: device time owned per phase, idle gaps charged to host spans, and the
op -> phase map read from a compiled program's HLO text."""

import pytest

from surreal_tpu.session.profile import (
    charge_gaps,
    hlo_op_phases,
    idle_gaps,
    owned_pieces,
    reduce_digest,
)

# one device, ns: a `while` [0, 1000] that encloses two body ops, a gap,
# then an op outside every phase
OPS = [
    (0, 1000, "while.1", "collect"),
    (100, 400, "fusion.2 f32[8,4]", "collect"),
    (500, 900, "fusion.3 bf16[8]", "sgd"),
    (1200, 1500, "copy.4", "unattributed"),
]


def owned(events):
    out = [0] * len(events)
    for i, a, b in owned_pieces(events):
        out[i] += b - a
    return out


def test_a_while_keeps_only_what_its_body_leaves():
    assert owned(OPS) == [300, 300, 400, 300]
    digest = reduce_digest({"/device:TPU:0": OPS}, [], steps=1)
    assert digest["busy_s"] == pytest.approx(1300e-9)
    assert digest["window_s"] == pytest.approx(1500e-9)
    assert digest["idle_s"] == pytest.approx(200e-9)
    ms = {k: v["ms_per_iter"] for k, v in digest["phases"].items()}
    assert ms == pytest.approx(
        {"collect": 600e-6, "sgd": 400e-6, "unattributed": 300e-6}
    )
    assert [n for n, _ in digest["phases"]["collect"]["top_ops"]] == [
        "while.1", "fusion.2 f32[8,4]"
    ]


@pytest.mark.parametrize("steps", [1, 2, 5])
def test_phases_sum_to_busy_exactly(steps):
    digest = reduce_digest({"/device:TPU:0": OPS}, [], steps=steps)
    per_iter = sum(p["ms_per_iter"] for p in digest["phases"].values())
    assert per_iter * steps == pytest.approx(digest["busy_s"] * 1e3, rel=1e-12)
    assert sum(p["share_of_busy"] for p in digest["phases"].values()) == (
        pytest.approx(1.0)
    )


def test_two_ops_that_overlap_in_part_share_without_double_counting():
    events = [(0, 100, "a", "collect"), (60, 160, "b", "sgd")]
    assert owned(events) == [60, 100]  # the later one owns the overlap
    assert sum(owned(events)) == 160   # the union, once


def test_a_gap_is_charged_to_the_innermost_covering_span():
    spans = [
        (0, 2000, "iteration"),
        (950, 1400, "engine.boundary"),
        (1050, 1150, "metrics-sync"),
    ]
    gaps = idle_gaps(OPS)
    assert gaps == [(1000, 1200)]
    assert charge_gaps(gaps, spans) == {
        "engine.boundary": 100, "metrics-sync": 100,
    }
    digest = reduce_digest({"/device:TPU:0": OPS}, spans, steps=1)
    assert digest["idle_by_span"] == pytest.approx(
        {"engine.boundary": 100e-9, "metrics-sync": 100e-9}
    )
    assert sum(digest["idle_by_span"].values()) == pytest.approx(
        digest["idle_s"]
    )


def test_a_gap_under_no_span_is_charged_to_none():
    assert charge_gaps([(1000, 1200)], []) == {"none": 200}
    # a span that covers half of it leaves the other half unexplained
    assert charge_gaps([(1000, 1200)], [(900, 1100, "engine.step")]) == {
        "engine.step": 100, "none": 100,
    }


def test_two_devices_are_counted_and_the_first_is_split():
    second = [(0, 700, "fusion.9", "sgd")]
    digest = reduce_digest(
        {"/device:TPU:1": second, "/device:TPU:0": OPS}, [], steps=1
    )
    assert digest["devices"] == 2
    assert digest["busy_s_per_device"] == pytest.approx([1300e-9, 700e-9])
    assert set(digest["phases"]) == {"collect", "sgd", "unattributed"}
    assert digest["busy_s"] == pytest.approx(1300e-9)


def test_no_device_plane_leaves_counts_only():
    assert reduce_digest({}, [(0, 10, "iteration")], steps=3) == {
        "devices": 0, "steps": 3,
    }


HLO = """HloModule jit_train_iter, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(train_iter)/sgd/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(train_iter)/sgd/transpose(jvp(sgd))/add"}
}

%body.2 (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%s), index=1
  %dynamic-update-slice.7 = f32[8]{0} dynamic-update-slice(%gte.1, %gte.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %dynamic-update-slice.7)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %tanh.3 = f32[8]{0} tanh(%a), metadata={op_name="jit(train_iter)/collect/while/body/act/tanh" source_file="x.py"}
  %copy.5 = f32[8]{0} copy(%tanh.3)
  %fusion.4 = f32[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.1
  %while.6 = (s32[], f32[8]{0}) while(%fusion.4), condition=%cond.2, body=%body.2, metadata={op_name="jit(train_iter)/shuffle/gather"}
  %copy.10 = f32[8]{0:T(8,128)(2,1)} copy(%a), metadata={op_name="jit(train_iter)/shard_map"}
  %log.11 = f32[8]{0} log(%copy.10), metadata={op_name="jit(train_iter)/prepare/log"}
  %exp.8 = f32[8]{0} exponential(%a), metadata={op_name="jit(train_iter)/jit(_threefry_split)/exp"}
  ROOT %copy.9 = f32[8]{0} copy(%exp.8)
}
"""


def test_op_phases_from_hlo_text():
    module, ops = hlo_op_phases(HLO)
    assert module == "jit_train_iter"
    assert ops["tanh.3"] == "collect"           # its own op_name
    assert ops["fusion.4"] == "sgd"             # its fused root's
    assert ops["dynamic-update-slice.7"] == "shuffle"  # its caller's
    assert ops["copy.5"] == "sgd"               # made for its user
    assert ops["copy.10"] == "prepare"          # a copy, whatever its path
    assert "exp.8" not in ops and "copy.9" not in ops  # outside every phase
