"""Golden-value tests for the ops layer against slow numpy references
(SURVEY.md §4: the reference had no test suite; this is the designed one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats as sps

from surreal_tpu.ops import distributions as D
from surreal_tpu.ops import returns as R
from surreal_tpu.ops import running_stats as RS
from surreal_tpu.ops.vtrace import vtrace


# ---------- numpy reference implementations ----------

def np_gae(rewards, discounts, values, lam):
    T = len(rewards)
    adv = np.zeros_like(rewards)
    last = np.zeros_like(rewards[0])
    for t in reversed(range(T)):
        delta = rewards[t] + discounts[t] * values[t + 1] - values[t]
        last = delta + discounts[t] * lam * last
        adv[t] = last
    return adv


def np_nstep(rewards, discounts, boot_vals, n):
    T = len(rewards)
    out = np.zeros_like(rewards)
    for t in range(T):
        g = np.zeros_like(rewards[0])
        disc = np.ones_like(discounts[0])
        for k in range(n):
            if t + k < T:
                g = g + disc * rewards[t + k]
                disc = disc * discounts[t + k]
            else:
                disc = disc * 0
        idx = min(t + n - 1, T - 1)
        out[t] = g + disc * boot_vals[idx]
    return out


def np_vtrace(blogp, tlogp, rewards, discounts, values, rho_bar, c_bar):
    T = len(rewards)
    rhos = np.exp(tlogp - blogp)
    crho = np.minimum(rho_bar, rhos)
    cs = np.minimum(c_bar, rhos)
    vs = np.zeros_like(rewards)
    acc = np.zeros_like(rewards[0])
    for t in reversed(range(T)):
        delta = crho[t] * (rewards[t] + discounts[t] * values[t + 1] - values[t])
        acc = delta + discounts[t] * cs[t] * acc
        vs[t] = acc + values[t]
    vs_next = np.concatenate([vs[1:], values[-1:]], axis=0)
    pg_adv = np.minimum(rho_bar, rhos) * (rewards + discounts * vs_next - values[:-1])
    return vs, pg_adv


def random_trajectory(rng, T=40, B=5):
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    done = rng.uniform(size=(T, B)) < 0.1
    discounts = (0.99 * (1.0 - done)).astype(np.float32)
    values = rng.normal(size=(T + 1, B)).astype(np.float32)
    return rewards, discounts, values


# ---------- GAE ----------

def test_gae_matches_numpy():
    rng = np.random.default_rng(0)
    rewards, discounts, values = random_trajectory(rng)
    adv, targets = R.gae_advantages(
        jnp.asarray(rewards), jnp.asarray(discounts), jnp.asarray(values), 0.95
    )
    expected = np_gae(rewards, discounts, values, 0.95)
    np.testing.assert_allclose(adv, expected, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(targets, expected + values[:-1], rtol=1e-5, atol=1e-5)


def test_gae_assoc_matches_scan():
    rng = np.random.default_rng(1)
    rewards, discounts, values = random_trajectory(rng, T=128)
    a1, t1 = R.gae_advantages(
        jnp.asarray(rewards), jnp.asarray(discounts), jnp.asarray(values), 0.9
    )
    a2, t2 = R.gae_advantages_assoc(
        jnp.asarray(rewards), jnp.asarray(discounts), jnp.asarray(values), 0.9
    )
    np.testing.assert_allclose(a1, a2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(t1, t2, rtol=1e-4, atol=1e-4)


def test_gae_respects_episode_boundary():
    # two episodes in one trajectory: advantage must not leak across done
    T = 6
    rewards = jnp.ones((T, 1))
    discounts = jnp.asarray([0.9, 0.9, 0.0, 0.9, 0.9, 0.9])[:, None]
    values = jnp.zeros((T + 1, 1))
    adv, _ = R.gae_advantages(rewards, discounts, values, 1.0)
    # with V=0 and lam=1, A_t = sum of discounted future rewards within episode
    assert float(adv[2, 0]) == pytest.approx(1.0)  # terminal step sees only its reward
    assert float(adv[0, 0]) == pytest.approx(1 + 0.9 + 0.81)


# ---------- n-step ----------

@pytest.mark.parametrize("n", [1, 3, 5])
def test_nstep_matches_numpy(n):
    rng = np.random.default_rng(2)
    rewards, discounts, values = random_trajectory(rng, T=20, B=3)
    boot = values[1:]
    got = R.n_step_returns(
        jnp.asarray(rewards), jnp.asarray(discounts), jnp.asarray(boot), n
    )
    expected = np_nstep(rewards, discounts, boot, n)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


# ---------- V-trace ----------

def test_vtrace_matches_numpy():
    rng = np.random.default_rng(3)
    rewards, discounts, values = random_trajectory(rng, T=30, B=4)
    blogp = rng.normal(size=(30, 4)).astype(np.float32) * 0.5
    tlogp = blogp + rng.normal(size=(30, 4)).astype(np.float32) * 0.2
    out = vtrace(
        jnp.asarray(blogp), jnp.asarray(tlogp), jnp.asarray(rewards),
        jnp.asarray(discounts), jnp.asarray(values),
    )
    evs, epg = np_vtrace(blogp, tlogp, rewards, discounts, values, 1.0, 1.0)
    np.testing.assert_allclose(out.vs, evs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out.pg_advantages, epg, rtol=1e-4, atol=1e-4)


def test_vtrace_on_policy_reduces_to_gae_lam1():
    # with behaviour == target and no clipping active, vs == GAE(lam=1) targets
    rng = np.random.default_rng(4)
    rewards, discounts, values = random_trajectory(rng, T=25, B=2)
    logp = rng.normal(size=(25, 2)).astype(np.float32)
    out = vtrace(
        jnp.asarray(logp), jnp.asarray(logp), jnp.asarray(rewards),
        jnp.asarray(discounts), jnp.asarray(values),
    )
    adv, targets = R.gae_advantages(
        jnp.asarray(rewards), jnp.asarray(discounts), jnp.asarray(values), 1.0
    )
    np.testing.assert_allclose(out.vs, targets, rtol=1e-4, atol=1e-4)


# ---------- distributions ----------

def test_diag_gauss_logp_vs_scipy():
    rng = np.random.default_rng(5)
    mean = rng.normal(size=(7, 3)).astype(np.float32)
    log_std = (rng.normal(size=(7, 3)) * 0.3).astype(np.float32)
    x = rng.normal(size=(7, 3)).astype(np.float32)
    got = D.diag_gauss_logp(jnp.asarray(mean), jnp.asarray(log_std), jnp.asarray(x))
    expected = sps.norm.logpdf(x, mean, np.exp(log_std)).sum(-1)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)


def test_diag_gauss_entropy_vs_scipy():
    log_std = np.asarray([[0.1, -0.3, 0.7]], np.float32)
    got = D.diag_gauss_entropy(jnp.asarray(log_std))
    expected = sps.norm.entropy(0.0, np.exp(log_std)).sum(-1)
    np.testing.assert_allclose(got, expected, rtol=1e-5)


def test_diag_gauss_kl_zero_self():
    mean = jnp.asarray([[0.3, -1.2]])
    ls = jnp.asarray([[0.2, 0.1]])
    np.testing.assert_allclose(D.diag_gauss_kl(mean, ls, mean, ls), 0.0, atol=1e-6)


def test_diag_gauss_kl_known_value():
    # KL(N(0,1) || N(1,1)) = 0.5
    z = jnp.zeros((1, 1))
    np.testing.assert_allclose(
        D.diag_gauss_kl(z, z, jnp.ones((1, 1)), z), 0.5, rtol=1e-6
    )


def test_diag_gauss_sample_moments():
    key = jax.random.PRNGKey(0)
    mean = jnp.asarray([1.0, -2.0])
    log_std = jnp.asarray([0.0, 0.5])
    samples = jax.vmap(lambda k: D.diag_gauss_sample(k, mean, log_std))(
        jax.random.split(key, 20000)
    )
    np.testing.assert_allclose(samples.mean(0), mean, atol=0.05)
    np.testing.assert_allclose(samples.std(0), np.exp(log_std), atol=0.05)


def test_categorical_logp_entropy():
    logits = jnp.asarray([[1.0, 2.0, 0.5], [0.0, 0.0, 0.0]])
    actions = jnp.asarray([1, 2])
    got = D.categorical_logp(logits, actions)
    probs = jax.nn.softmax(logits)
    np.testing.assert_allclose(got[0], np.log(probs[0, 1]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        D.categorical_entropy(logits)[1], np.log(3.0), rtol=1e-4
    )
    np.testing.assert_allclose(D.categorical_kl(logits, logits), 0.0, atol=1e-6)


# ---------- running stats (ZFilter) ----------

def test_running_stats_matches_numpy():
    rng = np.random.default_rng(6)
    stats = RS.init_stats((4,))
    chunks = [rng.normal(loc=3.0, scale=2.0, size=(50, 4)).astype(np.float32) for _ in range(5)]
    for c in chunks:
        stats = RS.update_stats(stats, jnp.asarray(c))
    allx = np.concatenate(chunks)
    np.testing.assert_allclose(stats.mean, allx.mean(0), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(RS.variance(stats), allx.var(0), rtol=1e-2, atol=1e-2)


def test_running_stats_merge():
    rng = np.random.default_rng(7)
    a_data = rng.normal(size=(100, 3)).astype(np.float32)
    b_data = rng.normal(loc=2.0, size=(60, 3)).astype(np.float32)
    sa = RS.update_stats(RS.init_stats((3,)), jnp.asarray(a_data))
    sb = RS.update_stats(RS.init_stats((3,)), jnp.asarray(b_data))
    merged = RS.merge_stats(sa, sb)
    allx = np.concatenate([a_data, b_data])
    np.testing.assert_allclose(merged.mean, allx.mean(0), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(RS.variance(merged), allx.var(0), rtol=1e-2, atol=1e-2)


def test_running_stats_count_exact_past_float32_mantissa():
    """Regression (VERDICT r1 weak #7): a float32 count freezes at 2^24
    single-sample folds (~100 s at the 100k steps/s north star); the int32
    count keeps incrementing exactly and saturates instead of wrapping."""
    big = RS.RunningStats(
        count=jnp.asarray(20_000_000, jnp.int32),  # > 2^24
        mean=jnp.zeros((2,)),
        m2=jnp.full((2,), 20_000_000.0),
    )
    s = big
    for _ in range(3):
        s = RS.update_stats(s, jnp.zeros((2,)))  # single-sample fold
    assert int(s.count) == 20_000_003
    # saturation: no int32 wraparound near the cap
    near_cap = big._replace(count=jnp.asarray(1_999_999_999, jnp.int32))
    s2 = RS.update_stats(near_cap, jnp.zeros((64, 2)))
    assert int(s2.count) == 2_000_000_000
    s3 = RS.update_stats(s2, jnp.zeros((64, 2)))
    assert int(s3.count) == 2_000_000_000
    assert np.isfinite(np.asarray(RS.variance(s3))).all()


def test_running_stats_variance_stays_converged_past_saturation():
    """Once the count saturates, folding stationary data must NOT inflate
    the variance (the cap rescales m2 with count — EMA semantics — rather
    than letting m2 grow against a frozen divisor)."""
    cap = 2_000_000_000
    # converged stats: mean 0, variance exactly 1, at the cap
    s = RS.RunningStats(
        count=jnp.asarray(cap, jnp.int32),
        mean=jnp.zeros((1,)),
        m2=jnp.full((1,), float(cap)),
    )
    # +/-1 batch: mean 0, variance 1 — folding it must keep variance ~1.
    # 20 folds of 4e6 samples add 8e7 to m2 under the frozen-divisor bug
    # (variance would read ~1.04, outside the 1.005 bound) while staying
    # cheap enough for the quick suite
    batch = jnp.tile(jnp.asarray([[1.0], [-1.0]]), (2_000_000, 1))
    for _ in range(20):
        s = RS.update_stats(s, batch)
    var = float(RS.variance(s)[0])
    assert int(s.count) == cap
    assert 0.995 <= var <= 1.005, f"variance drifted to {var} past saturation"
    # merge path: same invariant
    m = RS.merge_stats(s, s)
    assert int(m.count) == cap
    assert 0.99 <= float(RS.variance(m)[0]) <= 1.01


def test_normalize_clips():
    stats = RS.update_stats(
        RS.init_stats((2,)), jnp.asarray(np.random.default_rng(8).normal(size=(1000, 2)), jnp.float32)
    )
    out = RS.normalize(stats, jnp.asarray([[100.0, -100.0]]), clip=5.0)
    assert float(out[0, 0]) == pytest.approx(5.0)
    assert float(out[0, 1]) == pytest.approx(-5.0)


def test_running_stats_3d_batch():
    # time-major [T, B, obs] batches must fold in across both leading axes
    rng = np.random.default_rng(9)
    data = rng.normal(size=(10, 8, 3)).astype(np.float32)
    stats = RS.update_stats(RS.init_stats((3,)), jnp.asarray(data))
    np.testing.assert_allclose(stats.mean, data.reshape(-1, 3).mean(0), rtol=1e-3, atol=1e-3)
    assert float(stats.count) == pytest.approx(80, rel=1e-3)


def test_vtrace_assoc_matches_scan():
    """The associative-scan V-trace must match the reverse-scan reference
    on trajectories with episode boundaries (discounts=0 rows)."""
    from surreal_tpu.ops.vtrace import vtrace, vtrace_assoc

    rng = np.random.default_rng(11)
    T, B = 64, 4
    blogp = jnp.asarray(rng.normal(scale=0.3, size=(T, B)), jnp.float32)
    tlogp = blogp + jnp.asarray(rng.normal(scale=0.2, size=(T, B)), jnp.float32)
    rewards = jnp.asarray(rng.normal(size=(T, B)), jnp.float32)
    done = jnp.asarray(rng.random((T, B)) < 0.05)
    discounts = 0.99 * (1.0 - done.astype(jnp.float32))
    values = jnp.asarray(rng.normal(size=(T + 1, B)), jnp.float32)
    a = vtrace(blogp, tlogp, rewards, discounts, values)
    b = vtrace_assoc(blogp, tlogp, rewards, discounts, values)
    np.testing.assert_allclose(np.asarray(b.vs), np.asarray(a.vs), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(b.pg_advantages), np.asarray(a.pg_advantages), rtol=2e-4, atol=2e-4
    )


# -- the two recurrences the learners run, each against a plain loop ----------

def _ends(case: str):
    """``(T, B, done, terminated, dtype)`` of one case: where episodes end in
    a ``[T, B]`` rollout and how (a termination ends the episode AND the
    bootstrap; a truncation ends the episode only)."""
    T, B = (1, 5) if case == "one-step" else (8, 5)
    done, terminated = np.zeros((T, B), bool), np.zeros((T, B), bool)
    if case == "terminations":
        for t, b in ((2, 0), (5, 0), (3, 3)):
            done[t, b] = terminated[t, b] = True
    elif case == "truncations":
        for t, b in ((2, 1), (6, 4)):
            done[t, b] = True
    elif case in ("both-in-one-column", "bfloat16-in"):
        done[2, 1] = True                        # truncated
        done[5, 1] = terminated[5, 1] = True     # terminated
        done[4, 3] = True
    elif case == "end-on-last-step":
        done[T - 1, 0] = terminated[T - 1, 0] = True
        done[T - 1, 2] = True
    elif case == "end-on-first-step":
        done[0, 0] = terminated[0, 0] = True
        done[0, 2] = True
    elif case == "one-step":
        done[0, 1] = terminated[0, 1] = True
        done[0, 3] = True
    elif case == "every-step-ends":              # nothing accumulates
        done[:] = True
        terminated[:, ::2] = True
    else:
        assert case == "no-end", case
    dtype = jnp.bfloat16 if case == "bfloat16-in" else jnp.float32
    return T, B, done, terminated, dtype


RECURRENCE_CASES = [
    "no-end", "terminations", "truncations", "both-in-one-column",
    "end-on-last-step", "end-on-first-step", "one-step", "bfloat16-in",
    "every-step-ends",
]


@pytest.mark.parametrize("case", RECURRENCE_CASES)
def test_gae_scan_matches_a_numpy_loop(case):
    """``PPOLearner._gae`` is the one GAE the learner runs: the bootstrap
    discount is cut by ``terminated``, the accumulation by ``done``; any
    input dtype in, float32 out."""
    from surreal_tpu.envs.base import ArraySpec, EnvSpecs
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config

    T, B, done, terminated, dtype = _ends(case)
    learner = build_learner(
        Config(algo=Config(name="ppo", gamma=0.99, lam=0.95)),
        EnvSpecs(
            obs=ArraySpec(shape=(3,), dtype=np.dtype(np.float32)),
            action=ArraySpec(shape=(2,), dtype=np.dtype(np.float32)),
        ),
    )
    rng = np.random.default_rng(12)
    reward, values, v_next = (
        jnp.asarray(rng.normal(size=(T, B)), dtype) for _ in range(3)
    )
    adv, targets = learner._gae(
        {"reward": reward, "done": jnp.asarray(done),
         "terminated": jnp.asarray(terminated)},
        values, v_next,
    )
    assert adv.dtype == targets.dtype == jnp.float32
    assert adv.shape == targets.shape == (T, B)

    r, v, vn = (np.asarray(x, np.float64) for x in (reward, values, v_next))
    want = np.zeros((T, B))
    for b in range(B):
        acc = 0.0
        for t in reversed(range(T)):
            delta = r[t, b] + 0.99 * (not terminated[t, b]) * vn[t, b] - v[t, b]
            acc = delta + 0.99 * 0.95 * (not done[t, b]) * acc
            want[t, b] = acc
    np.testing.assert_allclose(np.asarray(adv), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(targets), want + v, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", RECURRENCE_CASES)
def test_vtrace_nextobs_matches_a_numpy_loop(case):
    """``vtrace_nextobs`` is the one V-trace the learner runs: clipped
    importance weights, the bootstrap cut by ``terminated``, the correction
    cut by ``done``, and at a boundary the policy-gradient target falls back
    to the successor's value; bfloat16 rewards and values in, float32 out."""
    from surreal_tpu.ops.vtrace import vtrace_nextobs

    T, B, done, terminated, dtype = _ends(case)
    rng = np.random.default_rng(13)
    blogp = jnp.asarray(rng.normal(scale=0.3, size=(T, B)), jnp.float32)
    tlogp = blogp + jnp.asarray(rng.normal(scale=0.4, size=(T, B)), jnp.float32)
    reward, values, v_next = (
        jnp.asarray(rng.normal(size=(T, B)), dtype) for _ in range(3)
    )
    gamma, clip_rho, clip_c, clip_pg = 0.99, 1.0, 0.9, 1.1
    out = vtrace_nextobs(
        behaviour_logp=blogp, target_logp=tlogp, rewards=reward, values=values,
        values_next=v_next, done=jnp.asarray(done),
        terminated=jnp.asarray(terminated), gamma=gamma, clip_rho=clip_rho,
        clip_c=clip_c, clip_pg_rho=clip_pg,
    )
    assert out.vs.dtype == out.pg_advantages.dtype == jnp.float32
    assert out.vs.shape == out.pg_advantages.shape == (T, B)

    r, v, vn = (np.asarray(x, np.float64) for x in (reward, values, v_next))
    rho = np.exp(np.asarray(tlogp, np.float64) - np.asarray(blogp, np.float64))
    vs, pg = np.zeros((T, B)), np.zeros((T, B))
    for b in range(B):
        acc = 0.0
        for t in reversed(range(T)):
            boot = gamma * (not terminated[t, b])
            delta = min(clip_rho, rho[t, b]) * (r[t, b] + boot * vn[t, b] - v[t, b])
            acc = delta + gamma * (not done[t, b]) * min(clip_c, rho[t, b]) * acc
            vs[t, b] = acc + v[t, b]
            successor = (
                vn[t, b] if done[t, b] or t == T - 1 else vs[t + 1, b]
            )
            pg[t, b] = min(clip_pg, rho[t, b]) * (
                r[t, b] + boot * successor - v[t, b]
            )
    np.testing.assert_allclose(np.asarray(out.vs), vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out.pg_advantages), pg, rtol=1e-5, atol=1e-5
    )


def test_ring_attention_matches_full_attention():
    """Ring attention over a 4-way sp axis must match single-device full
    attention — non-causal and causal, and no [T,T] global materialization
    (each device only ever sees one K/V block at a time)."""
    from jax.sharding import Mesh
    from surreal_tpu.ops.ring_attention import full_attention, ring_self_attention

    rng = np.random.default_rng(21)
    B, T, H, D = 2, 32, 4, 16  # T shards 8 per device over sp=4
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    for causal in (False, True):
        ref = full_attention(q, k, v, causal=causal)
        out = ring_self_attention(mesh, q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"causal={causal}",
        )


def test_ring_attention_bf16_compute_f32_stats():
    """bf16 inputs run the matmuls in bf16 (MXU path) but the online
    softmax statistics stay f32: output must match the f32 reference to
    bf16 tolerance, not diverge from accumulated-in-bf16 drift."""
    from jax.sharding import Mesh
    from surreal_tpu.ops.ring_attention import full_attention, ring_self_attention

    rng = np.random.default_rng(22)
    B, T, H, D = 1, 64, 2, 8
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("sp",))
    out = ring_self_attention(
        mesh, q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), causal=True,
    )
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.06, atol=0.06
    )
