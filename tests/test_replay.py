"""Replay layer tests: insert/sample/evict/priorities (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.replay import build_replay
from surreal_tpu.session.config import Config
from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG


def replay_cfg(kind, **over):
    return Config(dict(kind=kind, **over)).extend(BASE_LEARNER_CONFIG.replay)


def trans(n, base=0):
    return {
        "obs": jnp.arange(base, base + n, dtype=jnp.float32)[:, None] * jnp.ones(3),
        "action": jnp.full((n, 2), 0.5, jnp.float32),
        "reward": jnp.arange(base, base + n, dtype=jnp.float32),
    }


def test_uniform_insert_sample_evict():
    replay = build_replay(replay_cfg("uniform", capacity=8, batch_size=4, start_sample_size=4))
    state = replay.init(jax.tree.map(lambda x: x[0], trans(1)))
    assert not bool(replay.can_sample(state))
    state = jax.jit(replay.insert)(state, trans(4))
    assert bool(replay.can_sample(state))
    assert int(state.size) == 4
    # wraparound eviction: 8 more overwrite everything
    state = jax.jit(replay.insert)(state, trans(8, base=100))
    assert int(state.size) == 8
    _, batch, info = jax.jit(replay.sample)(state, jax.random.key(0))
    assert batch["obs"].shape == (4, 3)
    # every sampled reward must come from the second insert (>=100)
    assert float(batch["reward"].min()) >= 100.0


def test_uniform_sample_respects_fill():
    replay = build_replay(replay_cfg("uniform", capacity=100, batch_size=32, start_sample_size=1))
    state = replay.init(jax.tree.map(lambda x: x[0], trans(1)))
    state = replay.insert(state, trans(3))  # only 3 valid entries
    _, batch, info = replay.sample(state, jax.random.key(1))
    assert int(info["idx"].max()) < 3  # never samples empty slots


def test_uniform_sample_many_matches_sequential_draws():
    """Record-equivalence contract of the batched fast path: set k of
    ``sample_many(state, keys)`` must equal ``sample(state, keys[k])``
    BIT-FOR-BIT (same randint shape/bounds per key, same storage gather) —
    the off-policy update loop's one-gather path then trains on the
    identical record as 64 sequential draws."""
    replay = build_replay(
        replay_cfg("uniform", capacity=64, batch_size=8, start_sample_size=1)
    )
    state = replay.init(jax.tree.map(lambda x: x[0], trans(1)))
    state = replay.insert(state, trans(40))
    keys = jax.random.split(jax.random.key(7), 5)
    _, batches, idx = jax.jit(replay.sample_many)(state, keys)
    assert idx.shape == (5, 8)
    for k in range(5):
        _, batch_k, info_k = replay.sample(state, keys[k])
        np.testing.assert_array_equal(np.asarray(idx[k]), np.asarray(info_k["idx"]))
        for name in batch_k:
            np.testing.assert_array_equal(
                np.asarray(batches[name][k]), np.asarray(batch_k[name])
            )


def test_fifo_dequeue_order_and_overwrite():
    replay = build_replay(replay_cfg("fifo", slots=2))
    traj = lambda v: {"obs": jnp.full((4, 2, 3), v, jnp.float32)}  # [T,B,...]
    state = replay.init(traj(0.0))
    state = jax.jit(replay.insert)(state, traj(1.0))
    state = jax.jit(replay.insert)(state, traj(2.0))
    assert int(state.size) == 2
    # overflow overwrites oldest
    state = jax.jit(replay.insert)(state, traj(3.0))
    state, out = replay.sample(state)
    assert float(out["obs"][0, 0, 0]) == 2.0  # 1.0 was evicted
    state, out = replay.sample(state)
    assert float(out["obs"][0, 0, 0]) == 3.0
    assert not bool(replay.can_sample(state))


def test_prioritized_sampling_prefers_high_priority():
    replay = build_replay(
        replay_cfg("prioritized", capacity=64, batch_size=256, start_sample_size=1)
    )
    state = replay.init(jax.tree.map(lambda x: x[0], trans(1)))
    state = replay.insert(state, trans(64))
    # give slot 7 overwhelming priority
    td = jnp.ones(64) * 1e-3
    td = td.at[7].set(1e3)
    state = jax.jit(replay.update_priorities)(state, jnp.arange(64), td)
    _, batch, info = jax.jit(replay.sample)(state, jax.random.key(0))
    frac = float((info["idx"] == 7).mean())
    assert frac > 0.9, f"high-priority slot sampled only {frac:.2%}"
    # IS weights: rare (low-priority) samples get the max weight 1.0
    assert float(info["is_weights"].max()) <= 1.0 + 1e-6
    w7 = info["is_weights"][info["idx"] == 7]
    assert float(w7.max()) < 1.0  # over-sampled slot downweighted


def test_prioritized_fresh_inserts_get_max_priority():
    replay = build_replay(
        replay_cfg("prioritized", capacity=8, batch_size=4, start_sample_size=1)
    )
    state = replay.init(jax.tree.map(lambda x: x[0], trans(1)))
    state = replay.insert(state, trans(4))
    state = replay.update_priorities(state, jnp.arange(4), jnp.full(4, 50.0))
    assert float(state.max_priority) >= 50.0
    state = replay.insert(state, trans(2, base=10))
    # new slots 4,5 must carry max priority
    np.testing.assert_allclose(np.asarray(state.priorities[4:6]), float(state.max_priority))


def _ddpg_ref():
    """The benchmark's float64 reference of the draw (benchmarks/reference/
    ddpg_ref.py), with the tolerances the chip run is held to."""
    from benchmarks.harness import manifest

    return manifest.load_reference("ddpg_ref")


def _abs_normal(capacity, filled=None, seed=0):
    rng = np.random.default_rng(seed)
    p = np.abs(rng.standard_normal(capacity)).astype(np.float32) + 1e-6
    p[capacity if filled is None else filled:] = 0.0
    return p


def _case(priorities, alpha=0.6, uniforms=None):
    """A ring's priorities (its size is the number of non-empty slots), the
    exponent, and uniforms to draw with in place of the key's."""
    return dict(priorities=priorities, alpha=alpha, uniforms=uniforms)


def _one_hot(capacity, slot, value=3.0):
    p = np.zeros(capacity, np.float32)
    p[slot] = value
    return p


def _zero_runs(capacity):
    """Runs of empty slots that start, end and lie across block edges."""
    p = _abs_normal(capacity, seed=5)
    for lo, hi in ((0, 3), (100, 300), (383, 385), (512, 640), (1000, 1024)):
        p[lo:hi] = 0.0
    return p


def _wide_range(capacity):
    rng = np.random.default_rng(6)
    return (10.0 ** rng.uniform(-6.0, 3.0, capacity)).astype(np.float32)


def _crafted(priorities, uniforms):
    """alpha = 1 and powers of two, so that float32 and the float64
    reference hold the same sums and a draw can be put ON an edge."""
    return _case(
        np.asarray(priorities, np.float32), alpha=1.0,
        uniforms=np.broadcast_to(np.float32(uniforms), (256,)),
    )


TOP = np.float32(1.0 - 2.0**-24)  # the largest uniform: u_255 is the total
DRAW_CASES = {
    "capacity_8": _case(_abs_normal(8)),
    "capacity_64": _case(_abs_normal(64)),
    "capacity_1000": _case(_abs_normal(1000)),            # padded last block
    "capacity_5120": _case(_abs_normal(5120)),            # the rehearsal's
    "capacity_50000": _case(_abs_normal(50000)),
    "capacity_65536_filled_40000": _case(_abs_normal(65536, 40000)),  # the check's ring
    "one_nonzero_slot": _case(_one_hot(1000, 517)),
    "mass_in_last_slot_of_a_block": _case(_one_hot(1024, 383)),
    "mass_in_last_slot_of_the_ring": _case(_one_hot(1000, 999)),
    "zero_runs_across_block_edges": _case(_zero_runs(1024)),
    "priorities_1e-6_to_1e3": _case(_wide_range(5120)),
    # block 0 sums to 2^23, block 1 holds 0.7 in its first slot: the block
    # cdf ends at fl(2^23 + 0.7) = 2^23 + 1, so the top draw's residual is
    # 1 > 0.7 and, unclamped, falls behind the block's last slot with mass
    "residual_rounds_past_the_block_sum": _crafted(
        [2.0**16] * 128 + [0.7] + [0.0] * 127, [0.5] * 255 + [TOP]
    ),
    # u_k = k * 2^10 = cdf[k - 1]: left search gives slot k - 1, u_0 slot 0
    "draws_on_slot_edges": _crafted([2.0**10] * 256, 0.0),
    # u_k = k * 2^10 = block_cdf[k - 1]: block k - 1, its last slot
    "draws_on_block_edges": _crafted([8.0] * 32768, 0.0),
}


@pytest.mark.parametrize("shape,axis", [((163840,), 0), ((7813,), 0), ((256, 128), 1)])
def test_mass_cdf_is_monotone_and_flat_over_zeros(shape, axis):
    """What the draw's left searches lean on, whatever order the backend's
    scan adds in (the CPU's plain ``cumsum`` has neither property at the
    first two shapes): the cdf never falls, and does not move over an entry
    without mass, so a search cannot stop on one."""
    from surreal_tpu.replay.prioritized import mass_cdf

    rng = np.random.default_rng(3)
    p = np.abs(rng.standard_normal(shape)).astype(np.float32)
    p[rng.random(shape) < 0.3] = 0.0
    p[..., shape[-1] // 2:shape[-1] // 2 + 40] = 0.0
    p[..., -shape[-1] // 8:] = 0.0       # the empty tail of a filling ring
    cdf = np.asarray(jax.jit(lambda x: mass_cdf(x, axis=axis))(p), np.float64)
    step = np.diff(cdf, axis=axis)
    assert (step >= 0).all()
    assert (step[np.take(p, np.arange(1, shape[axis]), axis=axis) == 0] == 0).all()
    want = np.cumsum(p.astype(np.float64), axis=axis)
    np.testing.assert_allclose(cdf, want, rtol=0, atol=2e-6 * want.max())


def test_double_float_sum_and_product_are_exact():
    """The block level's arithmetic (``replay/prioritized.py``): a sum and
    a product of two float32 come back as the rounded result and exactly
    what the rounding dropped."""
    from surreal_tpu.replay.prioritized import _two_prod, _two_sum

    rng = np.random.default_rng(5)
    a = (rng.standard_normal(4096) * 10.0 ** rng.uniform(-6, 6, 4096)).astype(np.float32)
    b = (rng.standard_normal(4096) * 10.0 ** rng.uniform(-6, 6, 4096)).astype(np.float32)
    a[:3], b[:3] = (0.0, 1.0, 3.0), (0.0, 0.0, 2.0**-24)
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    for op, want in ((_two_sum, a64 + b64), (_two_prod, a64 * b64)):
        hi, lo = (np.asarray(x) for x in jax.jit(op)(a, b))
        assert hi.dtype == lo.dtype == np.float32
        np.testing.assert_array_equal(hi, want.astype(np.float32))
        np.testing.assert_array_equal(hi.astype(np.float64) + lo, want)


@pytest.mark.parametrize("n", [1, 512, 7813, 163840])
def test_double_float_cumsum_matches_float64(n):
    """``_dd_cumsum`` over block sums with empty runs: hi + lo is the
    float64 cumulative sum to 2^-40 of the total, where a float32
    ``cumsum`` stops at 2^-23; hi alone is that sum rounded."""
    from surreal_tpu.replay.prioritized import _dd_cumsum

    rng = np.random.default_rng(7)
    x = (np.abs(rng.standard_normal((n, 128))) ** 0.6).sum(1).astype(np.float32)
    x[n // 3:n // 3 + 100] = 0.0
    x[::7] = 0.0
    want = np.cumsum(x.astype(np.float64))
    hi, lo = (np.asarray(c) for c in jax.jit(_dd_cumsum)(x))
    assert hi.dtype == lo.dtype == np.float32
    total = max(want[-1], 1.0)
    assert np.abs(hi.astype(np.float64) + lo - want).max() <= 2.0**-40 * total
    assert np.abs(hi - want).max() <= 2.0**-24 * total
    zeros = np.zeros(n, np.float32)  # an empty ring
    assert all((np.asarray(c) == 0).all() for c in _dd_cumsum(zeros))


@pytest.mark.parametrize("batch_size", [64, 256])
def test_prioritized_draw_is_the_float64_draw_on_the_checks_ring(batch_size):
    """On the reference check's ring (40 000 rows in 65 536 slots) the total
    mass is about 3e4 and its float32 ulp 1/500 of a slot's mass: with the
    draw's position and the block cdf in float32, 4 of these 2048 and 13 of
    these 8192 draws fell in the slot next to the float64 reference's, and
    when such a draw is the batch's rarest every max-normalised IS weight
    moves with it (`sample/is_weights` of the benchmark's check). With the
    block level in double-float none does."""
    ref = _ddpg_ref()
    replay = build_replay(replay_cfg(
        "prioritized", capacity=65536, batch_size=batch_size, start_sample_size=1,
    ))
    sample = jax.jit(lambda s, k: replay.sample(s, k, beta=0.4)[2]["idx"])
    for seed in range(32):
        priorities = _abs_normal(65536, 40000, seed=seed)
        key = jax.random.key(100 + seed)
        idx = sample(_prioritized_state(replay, priorities), key)
        want, _ = ref.prioritized_draw(
            priorities, jax.random.uniform(key, (batch_size,)), 40000,
            replay.alpha, 0.4,
        )
        np.testing.assert_array_equal(np.asarray(idx), want)


def _prioritized_state(replay, priorities):
    state = replay.init({"x": jnp.zeros((), jnp.float32)})
    size = int(np.count_nonzero(priorities))
    return state._replace(
        ring=state.ring._replace(size=jnp.asarray(size, jnp.int32)),
        priorities=jnp.asarray(priorities),
        max_priority=jnp.asarray(priorities.max()),
    )


def _check_draw(ref, priorities, size, alpha, beta, key, idx, weights):
    """One draw against the float64 cumulative sum, by ddpg_ref's bounds."""
    idx, weights = np.asarray(idx), np.asarray(weights)
    uniforms = jax.random.uniform(key, idx.shape)
    assert (np.asarray(priorities)[idx] > 0).all(), "a slot of zero mass was drawn"
    ref_idx, ref_w = ref.prioritized_draw(priorities, uniforms, size, alpha, beta)
    same = idx == ref_idx
    assert 1.0 - same.mean() <= ref.MAX_INDEX_MISMATCH
    mass_err, _ = ref.draw_mass_error(priorities, alpha, idx, uniforms)
    assert mass_err <= ref.DRAW_MASS_TOL
    np.testing.assert_allclose(
        weights[same], ref_w[same], **ref.TOL["sample/is_weights"]
    )
    # the batch's largest weight is x / x: 1 on the CPU, one ulp under it on
    # the TPU (PERF.md section 6)
    assert 1.0 - 2.0**-23 <= weights.max() <= 1.0


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", DRAW_CASES)
def test_prioritized_draw_matches_float64_reference(case, jit, monkeypatch):
    """The two-level draw (block sums, then one block) lands where the flat
    float64 cumulative sum of the benchmark's reference puts it: never on a
    slot without mass (an empty slot, the padding, or slot 127 of a block
    because the residual rounded past the block's sum), left-searched at
    both levels, at capacities that are and are not multiples of the block."""
    ref = _ddpg_ref()
    case = DRAW_CASES[case]
    priorities = case["priorities"]
    if case["uniforms"] is not None:  # the replay's and the reference's
        monkeypatch.setattr(
            jax.random, "uniform", lambda key, shape: jnp.asarray(case["uniforms"])
        )
    replay = build_replay(replay_cfg(
        "prioritized", capacity=len(priorities), batch_size=256,
        start_sample_size=1, priority_alpha=case["alpha"],
    ))
    state = _prioritized_state(replay, priorities)
    beta = 0.4
    sample = lambda s, k: replay.sample(s, k, beta=beta)[2]
    if jit:
        sample = jax.jit(sample)
    for key in jax.random.split(jax.random.key(11), 3):
        info = sample(state, key)
        assert info["idx"].dtype == jnp.int32
        _check_draw(
            ref, priorities, int(state.ring.size), replay.alpha, beta, key,
            info["idx"], info["is_weights"],
        )


def _mass_in_one_block(capacity=5120):
    """Nearly all of the mass in block 3, and most of that in one slot: a
    stratified draw repeats that slot, and the rest of its draws share the
    block."""
    p = np.full(capacity, 1e-4, np.float32)
    p[3 * 128:4 * 128] = 50.0
    p[3 * 128 + 17] = 1e4
    return p


# the rings the update loop's draw is pinned on: priorities, filled slots
SCAN_RINGS = {
    "partly_filled": lambda: (_abs_normal(5120, 4000, seed=9), 4000),
    "capacity_5000": lambda: (_abs_normal(5000, seed=9), 5000),  # padded last block
    "mass_in_one_block": lambda: (_mass_in_one_block(), 5120),
}


def _scan_of_updates(replay, state, keys, beta, carried):
    """The fused DDPG iteration's loop: ``lax.scan`` under ``jit`` of sample
    -> new priorities for the drawn slots -> the next sample; ``carried``
    with the block sums in the scan's carry, as the trainer keeps them.
    -> (final state, final mass, (priorities seen, idx, weights) per step)"""

    def one_update(c, key):
        state, mass = c
        seen = state.priorities
        state, _, info = replay.sample(state, key, beta=beta, mass=mass)
        td = jnp.abs(jnp.sin(info["idx"].astype(jnp.float32))) * 4.0
        state = replay.update_priorities(state, info["idx"], td)
        if carried:
            mass = replay.refresh_mass(mass, state, info["idx"])
        return (state, mass), (seen, info["idx"], info["is_weights"])

    def run(state, keys):
        mass = replay.block_mass(state) if carried else None
        return jax.lax.scan(one_update, (state, mass), keys)

    (final, mass), out = jax.jit(run)(state, keys)
    return final, mass, out


@pytest.mark.parametrize("how", ["stateless", "carried"])
@pytest.mark.parametrize("ring", SCAN_RINGS)
def test_prioritized_draw_in_scan_with_priority_updates(ring, how):
    """Stateless, every draw is checked against the reference on the
    priorities it saw. Carried, the loop is the stateless one bit for bit:
    indices, weights and final priorities, and the sums it carried out are
    a fresh pass over the final priorities."""
    priorities, filled = SCAN_RINGS[ring]()
    steps, beta = 8, 0.5
    replay = build_replay(replay_cfg(
        "prioritized", capacity=len(priorities), batch_size=256,
        start_sample_size=1,
    ))
    state = _prioritized_state(replay, priorities)
    keys = jax.random.split(jax.random.key(12), steps)
    final, _, (seen, idx, weights) = _scan_of_updates(
        replay, state, keys, beta, carried=False
    )
    assert not np.array_equal(np.asarray(seen[0]), np.asarray(seen[-1]))
    if how == "stateless":
        ref = _ddpg_ref()
        assert (np.asarray(final.priorities)[filled:] == 0).all()
        for k in range(steps):
            _check_draw(
                ref, np.asarray(seen[k]), filled, replay.alpha, beta, keys[k],
                idx[k], weights[k],
            )
        return
    if ring == "mass_in_one_block":
        drawn = np.asarray(idx[0])
        assert len(np.unique(drawn)) < len(drawn)            # a slot repeats
        assert len(np.unique(drawn // 128)) < len(np.unique(drawn))  # a block too
    final_c, mass, (_, idx_c, weights_c) = _scan_of_updates(
        replay, state, keys, beta, carried=True
    )
    np.testing.assert_array_equal(np.asarray(idx_c), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(weights_c), np.asarray(weights))
    np.testing.assert_array_equal(
        np.asarray(final_c.priorities), np.asarray(final.priorities)
    )
    np.testing.assert_array_equal(
        np.asarray(mass), np.asarray(jax.jit(replay.block_mass)(final_c))
    )


@pytest.mark.parametrize("capacity", [64, 5000, 5120, 65536])
def test_refresh_mass_equals_a_fresh_pass(capacity):
    """Sums taken before a scatter and refreshed on its slots (the ring's
    last slot, slot 0 and duplicates among them) are the sums of a fresh
    pass, to the bit, whether the last block is whole or padded."""
    from surreal_tpu.replay.prioritized import BLOCK

    replay = build_replay(replay_cfg(
        "prioritized", capacity=capacity, batch_size=256, start_sample_size=1,
    ))
    state = _prioritized_state(replay, _abs_normal(capacity, seed=3))
    rng = np.random.default_rng(4)
    idx = rng.integers(0, capacity, 256).astype(np.int32)
    idx[:6] = [capacity - 1, 0, capacity - 1, 0, idx[6], idx[7]]
    idx = jnp.asarray(idx)
    mass = jax.jit(replay.block_mass)(state)
    assert mass.shape == (-(-capacity // BLOCK),) and mass.dtype == jnp.float32
    after = replay.update_priorities(
        state, idx, jnp.asarray(rng.uniform(0.0, 9.0, 256), jnp.float32)
    )
    fresh = np.asarray(jax.jit(replay.block_mass)(after))
    assert not np.array_equal(fresh, np.asarray(mass))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(replay.refresh_mass)(mass, after, idx)), fresh
    )
    blocks = len(np.unique(np.asarray(idx) // BLOCK))
    assert float(jax.jit(replay.blocks_touched)(idx)) == blocks
    assert (np.asarray(mass) != fresh).sum() <= blocks


def _pows_by_place(jaxpr, length, elements, inside=False, found=None):
    """``pow`` equations with an operand of ``elements`` entries or more,
    counted (inside, outside) the scans of ``length`` steps."""
    found = [0, 0] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pow" and any(
            v.aval.size >= elements for v in eqn.invars
        ):
            found[0 if inside else 1] += 1
        within = inside or (
            eqn.primitive.name == "scan" and eqn.params["length"] == length
        )
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pows_by_place(sub, length, elements, within, found)
    return found


def test_fused_ddpg_iteration_raises_the_whole_ring_once(tmp_path, monkeypatch):
    """The structure of the fused iteration at ``ddpg_lift_per20m``'s
    rehearsal sizes: nothing in the update loop's body raises ``capacity``
    priorities to alpha, and one op outside it does (the loop's carry takes
    the block sums from there)."""
    from benchmarks.harness import manifest, runner
    from surreal_tpu.main import launch

    one = jax.devices()[:1]  # the suite simulates eight; the cell has one chip
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    cell = runner.sized(manifest.load_cell("ddpg_lift_per20m"), True)
    argv = runner.train_argv(
        manifest.load_config(cell["config"]), cell, str(tmp_path), 0, True
    )
    trainer = launch.select_trainer(
        launch.build_config(launch.build_parser().parse_args(argv))
    )
    updates, capacity = trainer.algo.updates_per_iter, trainer.replay.capacity
    assert trainer.prioritized and capacity == cell["traffic"]["replay_capacity"]
    assert updates != trainer.horizon  # the rollout's scan is no update loop
    key = jax.random.key(0)
    carry, replay_state = trainer.init_loop_state(key)
    jaxpr = jax.make_jaxpr(trainer._device_train_iter)(
        trainer.learner.init(key), replay_state, carry, key,
        jnp.float32(0.4), jnp.asarray(False), jnp.asarray(True),
    )
    assert _pows_by_place(jaxpr.jaxpr, updates, capacity) == [0, 1]


# capacity, rows of each successive insert[, the obs leaf's storage dtype]
INSERT_CASES = {
    "40000_into_65536_from_slot_0": (65536, [40000]),   # the reference check's
    "second_insert_wraps": (65536, [40000, 40000]),
    "cursor_no_multiple_of_n": (1000, [7, 300, 300, 300, 300]),
    "n_equals_capacity": (64, [10, 64]),
    "n_1": (8, [1] * 11),
    "cell_ratio_21_inserts": (5120, [256] * 21),        # n = capacity / 20
    # the edges of the two windows' arithmetic (replay/base.py::ring_write)
    "ends_on_the_last_slot": (64, [24, 40, 5]),         # tail 0, next cursor 0
    "wraps_by_one_row": (64, [50, 15]),
    "wraps_by_all_but_one_row": (64, [63, 20]),
    "more_rows_than_slots_refused": (8, [3, 9]),
    "bfloat16_leaf_fed_float32": (1000, [300, 300, 300, 300], jnp.bfloat16),
}


class NumpyRing:
    """The ring kept on the host, one row at a time: what every insert
    path is compared with."""

    def __init__(self, example, capacity):
        self.capacity = capacity
        self.rows = {
            k: np.zeros((capacity, *np.shape(v)), np.asarray(v).dtype)
            for k, v in example.items()
        }
        self.prio = np.zeros(capacity, np.float32)
        self.cursor = self.size = 0

    def insert(self, rows, max_prio):
        rows = jax.tree.map(np.asarray, rows)
        n = len(jax.tree.leaves(rows)[0])
        for i in range(n):
            slot = (self.cursor + i) % self.capacity
            for k in self.rows:
                self.rows[k][slot] = rows[k][i]   # casts as the ring does
            self.prio[slot] = max_prio
        self.cursor = (self.cursor + n) % self.capacity
        self.size = min(self.size + n, self.capacity)

    def assert_equals(self, ring, priorities=None):
        assert (int(ring.cursor), int(ring.size)) == (self.cursor, self.size)
        for k in self.rows:
            assert ring.storage[k].dtype == self.rows[k].dtype
            np.testing.assert_array_equal(np.asarray(ring.storage[k]), self.rows[k])
        if priorities is not None:
            np.testing.assert_array_equal(np.asarray(priorities), self.prio)


@pytest.mark.parametrize("prioritized", [False, True], ids=["ring", "prioritized"])
@pytest.mark.parametrize("case", INSERT_CASES)
def test_ring_insert_matches_numpy_ring(case, prioritized):
    """``ring_insert`` and ``PrioritizedReplay.insert`` against a ring kept
    in NumPy, row by row: rows land at ``(cursor + i) % capacity`` in
    order, cast to the storage dtype, the oldest are evicted, cursor and
    size follow, and fresh slots take the max priority of the moment. The
    guard of the window arithmetic of ``replay/base.py::ring_write``."""
    from surreal_tpu.replay.base import init_ring, ring_insert

    capacity, inserts, *obs_dtype = INSERT_CASES[case]
    replay = build_replay(replay_cfg(
        "prioritized", capacity=capacity, batch_size=4, start_sample_size=1,
    ))
    example = jax.tree.map(lambda x: x[0], trans(1))
    if obs_dtype:
        example["obs"] = example["obs"].astype(obs_dtype[0])
    state = replay.init(example) if prioritized else init_ring(example, capacity)
    insert = jax.jit(
        replay.insert if prioritized
        else lambda s, rows: ring_insert(s, rows, capacity)
    )
    want, max_prio, base = NumpyRing(example, capacity), np.float32(1.0), 0
    for n in inserts:
        rows = trans(n, base=base)
        if n > capacity:
            with pytest.raises(ValueError, match="do not fit a ring"):
                insert(state, rows)
            break
        state = insert(state, rows)
        want.insert(rows, max_prio)
        base += n
        want.assert_equals(
            state.ring if prioritized else state,
            state.priorities if prioritized else None,
        )
        if prioritized:
            # move the max, so that the next insert's priorities differ
            newest = (want.cursor - 1) % capacity
            state = replay.update_priorities(
                state, jnp.asarray([newest]), jnp.asarray([max_prio + 1.5])
            )
            want.prio[newest] = max_prio = np.float32(state.max_priority)
            assert max_prio > 1.5 and state.priorities[newest] == max_prio


def _td_of(idx, step):
    """A TD error that depends on the slot alone within a step, so that a
    slot drawn twice is written the same priority twice."""
    return 0.25 * ((idx + step) % 5).astype(jnp.float32) + 0.5


@pytest.mark.parametrize("how", ["donated_jit", "scan"])
def test_insert_sample_update_cycle_matches_numpy_ring(how):
    """The fused iteration's order, insert -> sample -> update_priorities,
    as three donating ``jax.jit`` calls per step (the host drivers) and
    as the body of one ``lax.scan`` (the device driver), against the NumPy
    ring after every step; the cursor passes the wrap twice."""
    capacity, n, steps, bs = 200, 72, 7, 16
    replay = build_replay(replay_cfg(
        "prioritized", capacity=capacity, batch_size=bs, start_sample_size=1,
    ))
    example = jax.tree.map(lambda x: x[0], trans(1))
    chunks = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[trans(n, base=n * t) for t in range(steps)]
    )
    keys = jax.random.split(jax.random.key(3), steps)

    def cycle(state, xs):
        rows, key, step = xs
        state = replay.insert(state, rows)
        after_insert = state
        state, batch, info = replay.sample(state, key)
        state = replay.update_priorities(
            state, info["idx"], _td_of(info["idx"], step)
        )
        return state, (after_insert, batch, info["idx"], state)

    xs = (chunks, keys, jnp.arange(steps))
    if how == "scan":
        _, outs = jax.jit(lambda s: jax.lax.scan(cycle, s, xs))(replay.init(example))
        outs = [jax.tree.map(lambda x: x[t], outs) for t in range(steps)]
    else:
        insert = jax.jit(replay.insert, donate_argnums=(0,))
        sample = jax.jit(replay.sample, donate_argnums=(0,))
        update = jax.jit(replay.update_priorities, donate_argnums=(0,))
        state, outs = replay.init(example), []

        def host(tree):  # read before the next call donates it
            return jax.tree.map(np.asarray, tree)

        for t in range(steps):
            state = insert(state, jax.tree.map(lambda x: x[t], chunks))
            after_insert = host(state)
            state, batch, info = sample(state, keys[t])
            idx = info["idx"]
            state = update(state, idx, _td_of(idx, t))
            outs.append((after_insert, batch, idx, host(state)))

    want, max_prio = NumpyRing(example, capacity), np.float32(1.0)
    for t, (after_insert, batch, idx, after_update) in enumerate(outs):
        want.insert(jax.tree.map(lambda x: x[t], chunks), max_prio)
        want.assert_equals(after_insert.ring, after_insert.priorities)
        idx = np.asarray(idx)
        for k in want.rows:  # the drawn rows are the inserted ones
            np.testing.assert_array_equal(np.asarray(batch[k]), want.rows[k][idx])
        prio = np.abs(np.asarray(_td_of(idx, t))) + np.float32(replay.eps)
        want.prio[idx] = prio
        max_prio = max(max_prio, prio.max())
        want.assert_equals(after_update.ring, after_update.priorities)
        assert np.float32(after_update.max_priority) == max_prio
    assert want.cursor == (n * steps) % capacity and want.size == capacity


def test_sharded_replay_per_device_buffers():
    """Each dp shard owns an independent buffer: inserts inside shard_map
    land in per-device storage (the ShardedReplay capability)."""
    from jax.sharding import PartitionSpec as P
    from surreal_tpu.parallel.mesh import make_mesh
    from surreal_tpu.utils.compat import shard_map

    mesh = make_mesh(Config(mesh=Config(dp=8)))
    replay = build_replay(replay_cfg("uniform", capacity=16, batch_size=4, start_sample_size=1))
    example = jax.tree.map(lambda x: x[0], trans(1))
    state = replay.init(example)
    # replicate bookkeeping, then run per-device insert of DIFFERENT data
    data = trans(8 * 2)  # [16, ...] -> 2 per device

    def per_device(state, shard):
        new = replay.insert(state, shard)
        # lift scalars to [1] so per-device values concatenate over dp
        return new._replace(cursor=new.cursor[None], size=new.size[None])

    sharded_insert = jax.jit(
        shard_map(
            per_device,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state), jax.tree.map(lambda _: P("dp"), data)),
            out_specs=jax.tree.map(lambda _: P("dp"), state),
            check_vma=False,
        )
    )
    out = sharded_insert(state, data)
    # storage leading dim now 8*16 (concatenated shards); each shard holds 2
    assert out.storage["obs"].shape == (8 * 16, 3)
    assert out.size.shape == (8,)
    assert int(out.size.sum()) == 16
    # each device's shard holds ITS OWN envs' data (hash-routing-for-free):
    # device d received rows [2d, 2d+1] -> rewards 2d, 2d+1
    stored = np.asarray(out.storage["reward"]).reshape(8, 16)
    for d in range(8):
        assert set(stored[d, :2].tolist()) == {2.0 * d, 2.0 * d + 1}


@pytest.mark.slow
def test_prioritized_sample_cost_at_1e6_capacity():
    """VERDICT r1 weak #8: the stateless sampler reads the whole priority
    vector on every call by design (p^alpha reduced to block sums; see
    replay/prioritized.py) — measure it at config-③ scale (1e6
    transitions, 64 updates/iter) so the trade is quantified, not assumed.
    The bound is a loose one for the CPU simulation and says nothing about
    the chip, where PERF.md section 5 has the measured cost: 64 fused
    sample+update calls must stay under 2 s once compiled."""
    import time

    cap = 1_000_000
    replay = build_replay(
        replay_cfg("prioritized", capacity=cap, batch_size=256, start_sample_size=1)
    )
    example = {
        "obs": jnp.zeros((17,), jnp.float32),
        "action": jnp.zeros((4,), jnp.float32),
        "reward": jnp.zeros((), jnp.float32),
    }
    state = replay.init(example)
    # fill to capacity in big chunks
    chunk = {
        "obs": jnp.ones((10_000, 17), jnp.float32),
        "action": jnp.ones((10_000, 4), jnp.float32),
        "reward": jnp.ones((10_000,), jnp.float32),
    }
    insert = jax.jit(replay.insert)
    for _ in range(cap // 10_000):
        state = insert(state, chunk)
    assert int(state.ring.size) == cap

    def one_update(state, key):
        state, batch, info = replay.sample(state, key, beta=0.5)
        new_prio = jnp.abs(batch["reward"]) + 0.1
        state = replay.update_priorities(state, info["idx"], new_prio)
        return state, info["is_weights"].mean()

    def sixty_four(state, key):
        return jax.lax.scan(one_update, state, jax.random.split(key, 64))

    run = jax.jit(sixty_four)
    state2, _ = run(state, jax.random.key(0))  # compile
    jax.block_until_ready(state2.priorities)
    t0 = time.perf_counter()
    state3, w = run(state2, jax.random.key(1))
    jax.block_until_ready(state3.priorities)
    dt = time.perf_counter() - t0
    per_call_ms = dt / 64 * 1000
    print(f"\nprioritized@1e6: {per_call_ms:.2f} ms/sample+update (64 calls in {dt:.3f}s)")
    assert np.isfinite(float(w.mean()))
    assert dt < 2.0, f"64 prioritized updates at 1e6 capacity took {dt:.2f}s"
