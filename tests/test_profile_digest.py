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


# -- the finer tables: sub-scopes, a part's phases, kernels, counts -----------

U = "unattributed"
# ns. A rollout `while` [0, 10000] whose body runs the actor (attention, then
# a routed layer through kernel k at call site k.3) and the env; then two
# learn ops in `sgd`, the routed one through the same kernel at site k.17;
# an op of 0.4 us and one of exactly 1.0 us; a relayout outside every phase.
JOINT = [
    (0, 10000, "while.1", "collect", U, "collect/rest", None),
    (100, 4000, "fusion.2 f32[8,4]", "collect", "attn", "collect/act", None),
    (4100, 4500, "fusion.3 f32[8]", "collect", U, "collect/env", None),
    (5000, 9000, "k.3 f32[2]", "collect", "moe_experts", "collect/act", "k"),
    (11000, 15000, "k.17 f32[2]", "sgd", "moe_experts", "sgd/rest", "k"),
    (15000, 16000, "fusion.5 bf16[8]", "sgd", "attn", "sgd/rest", None),
    (16500, 16800, "copy.6", U, U, U, None),
]


@pytest.fixture(params=[1, 3], ids=["one-step", "three-steps"])
def joint(request):
    digest = reduce_digest({"/device:TPU:0": JOINT}, [], steps=request.param)
    return digest, request.param


def total(values):
    return pytest.approx(sum(values), rel=1e-12)


def test_a_parts_row_sums_to_the_part(joint):
    digest, _ = joint
    assert set(digest["parts_by_phase"]) == set(digest["parts"])
    for part, row in digest["parts_by_phase"].items():
        assert digest["parts"][part]["ms_per_iter"] == total(row.values())


def test_a_phases_column_sums_to_the_phase(joint):
    digest, _ = joint
    for phase, entry in digest["phases"].items():
        assert entry["ms_per_iter"] == total(
            row.get(phase, 0.0) for row in digest["parts_by_phase"].values()
        )


def test_subs_and_rest_sum_to_the_phase(joint):
    digest, steps = joint
    assert list(digest["subphases"]) == ["collect"]   # sgd has no sub here
    subs = digest["subphases"]["collect"]
    assert list(subs) == ["act", "env", "rest"]
    assert digest["phases"]["collect"]["ms_per_iter"] == total(subs.values())
    assert subs["act"] * steps == pytest.approx(7900e-6)
    assert subs["env"] * steps == pytest.approx(400e-6)
    assert subs["rest"] * steps == pytest.approx(1700e-6)  # what the while keeps


def test_a_sub_of_another_phase_is_the_phases_rest():
    """The maps are read one label at a time: where an op's sub names a
    phase that is not the op's own, it is none of this phase's."""
    events = [
        (0, 500, "fusion.1", "collect", U, "collect/act", None),
        (500, 800, "copy.2", "collect", U, "prepare/gae", None),
    ]
    digest = reduce_digest({"/device:TPU:0": events}, [], steps=1)
    assert digest["subphases"] == {
        "collect": {"act": pytest.approx(500e-6), "rest": pytest.approx(300e-6)}
    }


def test_a_kernels_call_sites_sum_under_its_name(joint):
    digest, steps = joint
    assert list(digest["kernels"]) == ["k"]
    k = digest["kernels"]["k"]
    assert k["sites"] == 2 and k["part"] == "moe_experts"
    assert k["calls_per_iter"] * steps == pytest.approx(2.0)
    assert k["ms_per_iter"] * steps == pytest.approx(8000e-6)
    assert {p: ms * steps for p, ms in k["by_phase"].items()} == pytest.approx(
        {"collect": 4000e-6, "sgd": 4000e-6}
    )
    assert k["ms_per_iter"] <= digest["parts"]["moe_experts"]["ms_per_iter"]


def test_ops_are_counted_and_an_op_under_a_microsecond_is_short(joint):
    digest, steps = joint
    counts = {p: e["ops_per_iter"] * steps for p, e in digest["phases"].items()}
    assert counts == pytest.approx({"collect": 4.0, "sgd": 2.0, U: 1.0})
    short = {p: e["short_ops"] for p, e in digest["phases"].items()}
    # fusion.3 ran 0.4 us; fusion.5 ran 1.0 us, which is not under 1 us
    assert short["collect"]["per_iter"] * steps == pytest.approx(1.0)
    assert short["collect"]["ms_per_iter"] * steps == pytest.approx(400e-6)
    assert short["sgd"] == {"per_iter": 0.0, "ms_per_iter": 0.0}
    assert short[U]["per_iter"] * steps == pytest.approx(1.0)   # copy.6: 0.3 us


def test_an_old_tuple_reduces_as_before():
    """Four and five fields (no part; no sub or kernel): the marginals are
    what they were and the finer tables are empty or all ``rest``-less."""
    five = [ev + (U,) for ev in OPS]
    for events in (OPS, five):
        digest = reduce_digest({"/device:TPU:0": events}, [], steps=1)
        ms = {k: v["ms_per_iter"] for k, v in digest["phases"].items()}
        assert ms == pytest.approx(
            {"collect": 600e-6, "sgd": 400e-6, "unattributed": 300e-6}
        )
        assert digest["subphases"] == {} and digest["kernels"] == {}
        assert digest["parts_by_phase"] == {U: {
            "collect": pytest.approx(600e-6), "sgd": pytest.approx(400e-6),
            U: pytest.approx(300e-6),
        }}


def test_the_op_table_holds_every_op_and_sums_to_busy(joint):
    from surreal_tpu.session.profile import OPS_COLUMNS

    digest, steps = joint
    rows = digest["ops"]
    assert len(rows) == len(JOINT)
    table = [dict(zip(OPS_COLUMNS, row)) for row in rows]
    assert sum(r["ms_per_iter"] for r in table) * steps == pytest.approx(
        digest["busy_s"] * 1e3, rel=1e-12
    )
    assert [r["ms_per_iter"] for r in table] == sorted(
        (r["ms_per_iter"] for r in table), reverse=True
    )
    site = next(r for r in table if r["op"] == "k.17 f32[2]")
    assert (site["phase"], site["sub"], site["part"], site["kernel"]) == (
        "sgd", "rest", "moe_experts", "k"
    )
    assert site["calls_per_iter"] * steps == pytest.approx(1.0)


def test_an_op_its_children_cover_is_counted_and_owns_nothing():
    events = [(0, 1000, "while.1", "collect"), (0, 1000, "fusion.2", "sgd")]
    digest = reduce_digest({"/device:TPU:0": events}, [], steps=1)
    assert set(digest["phases"]) == {"sgd", U}   # as before: collect owns nothing
    assert digest["phases"]["sgd"]["ops_per_iter"] == 1.0


def test_subphases_from_hlo_text():
    from surreal_tpu.utils.phases import subphase_of

    module, subs = hlo_op_phases(HLO, subphase_of)
    phases = hlo_op_phases(HLO)[1]
    assert module == "jit_train_iter"
    assert subs["tanh.3"] == "collect/act"      # the one op inside a sub-scope
    assert subs["fusion.4"] == "sgd/rest"       # its fused root's
    assert subs["copy.5"] == "sgd/rest"         # made for its user, not of act
    assert subs["log.11"] == "prepare/rest"
    # every op with a phase has a sub or the rest of that same phase
    assert {k: v.split("/")[0] for k, v in subs.items()} == phases


def test_one_reading_of_a_text_gives_every_label_asked_for():
    from surreal_tpu.utils.phases import part_of, phase_of, subphase_of

    module, phases, parts, subs = hlo_op_phases(HLO, phase_of, part_of, subphase_of)
    assert (module, phases) == hlo_op_phases(HLO)
    assert parts == hlo_op_phases(HLO, part_of)[1]
    assert subs == hlo_op_phases(HLO, subphase_of)[1]


KERNEL_HLO = """HloModule jit_learn, is_scheduled=true

%body.1 (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%s), index=1
  %held_experts_live.36 = f32[8]{0} custom-call(%gte.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8]{0}}, metadata={op_name="jit(learn)/collect/while/body/act/moe_experts/held_experts_live"}, backend_config={"custom_call_config": {"body": "abc"}}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %held_experts_live.36)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %decayed_gram_bwd.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(learn)/sgd/transpose(jvp(kda_scan))/decayed_gram_bwd"}
  %held_experts_live = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call"
  %custom-call.7 = f32[8]{0} custom-call(%a), custom_call_target="Sharding"
  %cholesky.2 = f32[8]{0} custom-call(%a), custom_call_target="lapack_spotrf"
  ROOT %fusion.9 = f32[8]{0} fusion(%decayed_gram_bwd.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(learn)/tpu_custom_call"}
}
"""


def test_kernels_from_hlo_text():
    """A ``custom-call`` to ``tpu_custom_call`` is a Pallas kernel under its
    instruction's name less the call site's number; a custom call to any
    other target, and an op that merely names the target, is none."""
    from surreal_tpu.session.profile import hlo_kernels

    module, kernels = hlo_kernels(KERNEL_HLO)
    assert module == "jit_learn"
    assert kernels == {
        "held_experts_live.36": "held_experts_live",
        "decayed_gram_bwd.4": "decayed_gram_bwd",
        "held_experts_live": "held_experts_live",
    }


def test_the_digest_writes_every_op_beside_the_capture(tmp_path, monkeypatch):
    """``digest_capture`` leaves ``ops.json`` in the capture's directory:
    all the rows, which sum to ``busy_s`` per iteration and are in the file
    alone, not in the event; ``trace_bytes`` is still the capture's alone."""
    import json

    from surreal_tpu.session import profile

    pb = tmp_path / "plugins" / "profile" / "run"
    pb.mkdir(parents=True)
    (pb / "host.xplane.pb").write_bytes(b"x" * 100)
    seen = {}

    def read(path, labels, span_names, on_parsed):
        seen.update(labels)
        on_parsed(0.25)
        return {"/device:TPU:0": JOINT, "/device:TPU:1": JOINT[:2]}, [], {"iteration": 3}

    monkeypatch.setattr(profile, "read_capture", read)
    held = []
    labels = {"subphases": {"m": {}}, "kernels": {"m": {"k.3": "k"}}}
    digest = profile.digest_capture(
        str(tmp_path), labels, (), steps=3, on_parsed=held.append
    )
    assert seen == labels and held == [0.25]
    assert digest["trace_bytes"] == 100 and "ops" not in digest
    table = json.loads((tmp_path / profile.OPS_FILE).read_text())
    assert table["device"] == "/device:TPU:0" and table["steps"] == 3
    assert table["columns"] == list(profile.OPS_COLUMNS)
    assert len(table["ops"]) == len(JOINT)
    ms = table["columns"].index("ms_per_iter")
    assert sum(row[ms] for row in table["ops"]) * 3 == pytest.approx(
        digest["busy_s"] * 1e3, rel=1e-12
    )


def test_a_capture_without_a_device_plane_writes_no_op_table(tmp_path, monkeypatch):
    from surreal_tpu.session import profile

    pb = tmp_path / "plugins" / "profile" / "run"
    pb.mkdir(parents=True)
    (pb / "host.xplane.pb").write_bytes(b"x")
    monkeypatch.setattr(
        profile, "read_capture", lambda *a: ({}, [], {"iteration": 2})
    )
    digest = profile.digest_capture(str(tmp_path), {}, ())
    assert digest["steps"] == 2 and "phases" not in digest
    assert not (tmp_path / profile.OPS_FILE).exists()


def test_a_kernel_under_two_parts_names_the_larger():
    events = [
        (0, 1000, "k.1", "sgd", "attn", "sgd/rest", "k"),
        (1000, 4000, "k.2", "sgd", "moe_experts", "sgd/rest", "k"),
    ]
    digest = reduce_digest({"/device:TPU:0": events}, [], steps=1)
    assert digest["kernels"]["k"]["part"] == "moe_experts"


def test_the_accountant_reads_a_programs_text_once_for_its_four_maps():
    """``CostAccountant``'s maps by phase, part, sub-scope and kernel come
    from one reading of each registered program's text, on first demand;
    a newly registered program makes them stale."""
    from surreal_tpu.session.costs import CostAccountant

    reads = []

    def text():
        reads.append(1)
        return KERNEL_HLO

    costs = CostAccountant(None)
    costs._hlo["learn"] = text
    assert reads == []                       # nothing before a digest asks
    from surreal_tpu.session.profile import LABELS

    labels = costs.labels()
    assert list(labels) == list(LABELS)      # an op event's fields, in order
    assert labels["kernels"] == {"jit_learn": hlo_kernels_of(KERNEL_HLO)}
    assert labels["phases"]["jit_learn"]["held_experts_live.36"] == "collect"
    assert labels["parts"]["jit_learn"]["held_experts_live.36"] == "moe_experts"
    assert labels["subphases"]["jit_learn"]["held_experts_live.36"] == "collect/act"
    assert labels["subphases"]["jit_learn"]["decayed_gram_bwd.4"] == "sgd/rest"
    assert labels["parts"]["jit_learn"]["decayed_gram_bwd.4"] == "kda_scan"
    assert costs.labels() is labels and len(reads) == 1
    costs._labels = None                     # what record_program does
    costs.labels()
    assert len(reads) == 2


def hlo_kernels_of(text):
    from surreal_tpu.session.profile import hlo_kernels

    return hlo_kernels(text)[1]
