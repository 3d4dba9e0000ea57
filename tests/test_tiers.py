"""Replay tiers (ISSUE 18): the device-resident hot tier's bit-equality
contract, the cold codec's documented error bounds, the spill WAL's
chaos discipline (torn segments, ENOSPC), the tiers-off bit-identity
guarantee, and replay-from-log determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.experience import wire
from surreal_tpu.experience.spill import (
    ColdCodec,
    SpillLog,
    build_writer,
    q8_error_bound,
)
from surreal_tpu.replay.tiers import HotTier
from surreal_tpu.replay.uniform import UniformReplay
from surreal_tpu.session.config import Config
from surreal_tpu.utils import faults


def _example():
    return {
        "obs": jnp.zeros((3,), jnp.float32),
        "action": jnp.zeros((1,), jnp.float32),
        "reward": jnp.zeros((), jnp.float32),
        "discount": jnp.zeros((), jnp.float32),
    }


def _batches(n_batches, rows, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        out.append({
            "obs": rng.normal(size=(rows, 3)).astype(np.float32),
            "action": rng.normal(size=(rows, 1)).astype(np.float32),
            "reward": (rng.normal(size=(rows,)) * 5).astype(np.float32),
            "discount": np.full((rows,), 0.99, np.float32),
        })
    return out


# -- hot tier ----------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hot_tier_bit_equal_to_uniform_replay(impl):
    """The tier's bit-equality anchor: same capacity, same insert
    stream, same keys => a hot-tier sample is BIT-EQUAL to the
    in-process UniformReplay draw (both gather impls)."""
    cap, bs = 64, 8
    replay = UniformReplay(Config(
        capacity=cap, batch_size=bs, start_sample_size=bs,
        gather_impl=impl,
    ))
    state = replay.init(_example())
    hot = HotTier(capacity=cap, batch_size=bs, gather_impl=impl,
                  example=_example())
    for batch in _batches(12, 16):  # 192 rows: wraps the 64-ring twice
        state = replay.insert(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        hot.append({k: jnp.asarray(v) for k, v in batch.items()})
    assert hot.size == cap and hot.ready()
    for draw in range(4):
        key = jax.random.fold_in(jax.random.key(7), draw)
        _, want, _ = replay.sample(state, key)
        got = hot.sample(key)
        for k in want:
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


def test_hot_tier_not_ready_until_min_fill():
    hot = HotTier(capacity=32, batch_size=8, gather_impl="xla",
                  example=_example())
    assert not hot.ready()
    hot.append({k: jnp.asarray(v)
                for k, v in _batches(1, 4)[0].items()})
    assert not hot.ready()  # 4 < batch_size
    hot.append({k: jnp.asarray(v)
                for k, v in _batches(1, 4, seed=1)[0].items()})
    assert hot.ready()
    g = hot.gauges()
    assert g["tier/hot_size"] == 8.0 and g["tier/hot_fill"] == 0.25


def test_hot_tier_refuses_undersized_capacity():
    with pytest.raises(ValueError, match="hot_capacity"):
        HotTier(capacity=4, batch_size=8)


# -- cold codec --------------------------------------------------------------

def test_cold_codec_error_within_documented_bound():
    """Quantized cold reads: every Q8 field reconstructs within
    q8_error_bound of its per-segment [lo, hi]; f16 fields within f16
    roundoff; non-f32 fields exact. And the quantized row is >= 25%
    smaller than the raw f32 row (the tier's acceptance bound)."""
    rng = np.random.default_rng(3)
    rows = {
        "obs": rng.normal(size=(64, 3)).astype(np.float32),
        "reward": (rng.normal(size=(64,)) * 50).astype(np.float32),
        "discount": np.full((64,), 0.99, np.float32),
        "done": rng.integers(0, 2, size=(64,)).astype(bool),
    }
    flat = wire.flatten_fields(rows)
    spec = wire.PlaneSpec.from_example({k: v[0] for k, v in flat.items()})
    codec = ColdCodec(spec, quant=True)
    body, qparams = codec.encode(flat, 64)
    back = codec.decode(body, 64, qparams)
    assert set(qparams) == {"reward", "discount"}
    for name, (lo, hi) in qparams.items():
        err = np.abs(back[name].astype(np.float64)
                     - flat[name].astype(np.float64)).max()
        assert err <= q8_error_bound(lo, hi), (name, err)
    # f16 tier: relative roundoff, not Q8 range error
    err = np.abs(back["obs"] - flat["obs"]).max()
    assert err <= 2.0 ** -10 * np.abs(flat["obs"]).max() + 1e-6
    assert np.array_equal(back["done"], flat["done"])
    raw = sum(dtype.itemsize * int(np.prod(shape, dtype=np.int64))
              for _name, shape, dtype in spec.fields)
    assert codec.cold_row_nbytes <= 0.75 * raw  # >= 25% smaller


def test_cold_codec_quant_off_is_lossless():
    rng = np.random.default_rng(4)
    rows = {"reward": (rng.normal(size=(16,)) * 9).astype(np.float32)}
    spec = wire.PlaneSpec.from_example({"reward": rows["reward"][0]})
    codec = ColdCodec(spec, quant=False)
    body, qparams = codec.encode(rows, 16)
    assert qparams == {}
    back = codec.decode(body, 16, qparams)
    assert np.array_equal(back["reward"], rows["reward"])


# -- spill WAL + chaos -------------------------------------------------------

def _spill_spec():
    return wire.PlaneSpec.from_example(
        wire.flatten_fields({
            "obs": np.zeros((3,), np.float32),
            "reward": np.zeros((), np.float32),
        })
    )


def _spill_rows(seed, n=8):
    rng = np.random.default_rng(seed)
    return {
        "obs": rng.normal(size=(n, 3)).astype(np.float32),
        "reward": rng.normal(size=(n,)).astype(np.float32),
    }


def test_spill_roundtrip_merge_order(tmp_path):
    """Two shard logs merge into one deterministic (seq, shard) stream;
    bytes and counters reconcile."""
    spec = _spill_spec()
    cfg = {"enabled": True, "dir": str(tmp_path)}
    writers = [build_writer(cfg, spec, s) for s in range(2)]
    for seq in range(3):
        for s, w in enumerate(writers):
            w.append(_spill_rows(10 * seq + s), 8)
    for w in writers:
        w.close()
    log = SpillLog(str(tmp_path))
    order = [(h["seq"], h["shard"]) for h, _rows, _n in log.segments()]
    assert order == [(0, 0), (0, 1), (1, 0), (1, 1), (2, 0), (2, 1)]
    assert log.torn_segments == 0


def test_spill_torn_segment_is_skipped_and_counted(tmp_path):
    """experience.spill chaos, kind=truncate_segment: a crash mid-append
    leaves a torn frame; the reader skips it by magic-resync, counts it
    in torn_segments, and every OTHER segment decodes intact."""
    faults.configure([
        {"site": "experience.spill", "kind": "truncate_segment", "at": 1},
    ])
    try:
        spec = _spill_spec()
        w = build_writer({"enabled": True, "dir": str(tmp_path)}, spec, 0)
        for seq in range(4):
            w.append(_spill_rows(seq), 8)
        w.close()
        # segment 1 was torn: counted on the writer as a written seq but
        # not a durable segment
        assert w.stats()["spill_segments"] == 3
        log = SpillLog(str(tmp_path))
        got = [(h["seq"], rows) for h, rows, _n in log.segments()]
        assert [seq for seq, _ in got] == [0, 2, 3]
        assert log.torn_segments >= 1  # resync may count a tear twice
        for seq, rows in got:
            want = _spill_rows(seq)
            np.testing.assert_allclose(
                rows["obs"], want["obs"], atol=2.0 ** -9
            )
    finally:
        faults.configure(None)


def test_spill_enospc_degrades_counted(tmp_path):
    """experience.spill chaos, kind=enospc: the append fails, the
    writer counts the error and keeps going — durability degrades,
    ingest never crashes."""
    faults.configure([
        {"site": "experience.spill", "kind": "enospc", "at": 0, "times": 2},
    ])
    try:
        spec = _spill_spec()
        w = build_writer({"enabled": True, "dir": str(tmp_path)}, spec, 0)
        for seq in range(4):
            w.append(_spill_rows(seq), 8)
        w.close()
        st = w.stats()
        assert st["spill_errors"] == 2
        assert st["spill_failed"] == 0  # streak below the latch
        assert st["spill_segments"] == 2
        log = SpillLog(str(tmp_path))
        assert sum(1 for _ in log.segments()) == 2
        assert log.torn_segments == 0
    finally:
        faults.configure(None)


def test_spill_delayed_fsync_never_loses_data(tmp_path):
    faults.configure([
        {"site": "experience.spill", "kind": "delay_fsync", "at": 0,
         "ms": 5},
    ])
    try:
        spec = _spill_spec()
        w = build_writer(
            {"enabled": True, "dir": str(tmp_path), "fsync": True}, spec, 0
        )
        w.append(_spill_rows(0), 8)
        w.close()
        assert sum(1 for _ in SpillLog(str(tmp_path)).segments()) == 1
    finally:
        faults.configure(None)


# -- end-to-end: tiers over the remote plane ---------------------------------

def _tiered_cfg(folder, tiers):
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_experience import _remote_train_cfg

    cfg = _remote_train_cfg(folder, overlap=False, iters=3)
    if tiers is not None:
        cfg.learner_config.replay.tiers = tiers
    return cfg


def test_tiers_off_bit_identical(tmp_path):
    """The tiers-off contract: a config with the tiers block PRESENT but
    disabled trains bit-identically to one without the block at all —
    the hierarchy is zero-cost and zero-effect until switched on."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    finals = []
    for run, tiers in enumerate([
        None,
        Config(hot=Config(enabled=False), spill=Config(enabled=False)),
    ]):
        trainer = OffPolicyTrainer(
            _tiered_cfg(tmp_path / f"run{run}", tiers)
        )
        _state, metrics = trainer.run()
        finals.append(metrics)
    for k in ("loss/critic", "loss/actor", "health/grad_norm",
              "experience/rows"):
        assert finals[0][k] == finals[1][k], k
    assert "tier/hot_hits" not in finals[0]
    assert "tier/hot_hits" not in finals[1]


def test_tiered_training_and_replay_from_log(tmp_path):
    """Tiers on, end to end: hot tier serves updates on-device (hits
    counted), the spill WAL lands under the session folder, cold
    bytes/row beat raw f32 by >= 25%, and two replay-from-log passes
    over the WAL reproduce bit-identical parameters."""
    from surreal_tpu.launch.offpolicy_trainer import OffPolicyTrainer

    cfg = _tiered_cfg(tmp_path / "run", Config(
        hot=Config(enabled=True, capacity=256),
        spill=Config(enabled=True),
    ))
    trainer = OffPolicyTrainer(cfg)
    _state, metrics = trainer.run()
    assert metrics["tier/hot_hits"] > 0
    assert metrics["tier/spill_segments"] > 0
    raw_row = sum(
        np.dtype(np.float32).itemsize * int(np.prod(v.shape))
        for v in jax.device_get(trainer._replay_example()).values()
    )
    assert metrics["tier/cold_bytes_per_row"] <= 0.75 * raw_row
    spill_dir = os.path.join(str(tmp_path / "run"), "spill")
    assert sorted(os.listdir(spill_dir)) == ["shard0.log", "shard1.log"]
    outs = [trainer.replay_from_log(spill_dir) for _ in range(2)]
    assert outs[0]["params_digest"] == outs[1]["params_digest"]
    assert outs[0]["updates"] == outs[1]["updates"] > 0
    assert outs[0]["rows"] == metrics["experience/rows"]
    assert outs[0]["torn_segments"] == 0
