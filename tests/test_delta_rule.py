"""ops/delta_rule.py: the chunked gated delta rule against the recurrence a
position at a time (values and, through the ``custom_vjp``, gradients), a
length that is no multiple of the chunk, steps against a scan, and decays at
both ends of (0, 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.ops import delta_rule as delta_rule_module
from surreal_tpu.ops.delta_rule import CHUNK, SUB, delta_rule, delta_step


def _inputs(seed, B, T, H, K, V, g_scale=1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    state = 0.5 * jax.random.normal(ks[5], (B, H, K, V))
    return q, k, v, g, beta, state


def recurrence(q, k, v, g, beta, state):
    """The rule as its equation reads, a position at a time, written apart
    from ``delta_step``: ``S = (I - b k k^T) Diag(a) S + b k v^T``."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        kk = jnp.einsum("bhk,bhj->bhkj", k_t, k_t, precision="highest")
        S = S - b_t[..., None, None] * jnp.einsum(
            "bhkj,bhjv->bhkv", kk, S, precision="highest"
        ) + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision="highest")

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("T, chunk", [
    (2 * CHUNK + 32, CHUNK), (37, 32), (5, CHUNK),
])
def test_chunked_rule_is_the_recurrence(T, chunk, monkeypatch):
    monkeypatch.setattr(delta_rule_module, "CHUNK", chunk)
    x = _inputs(0, 2, T, 3, 8, 6)
    o, S = jax.jit(lambda *a: delta_rule(*a))(*x)
    o_ref, S_ref = jax.jit(recurrence)(*x)
    assert o.shape == (2, T, 3, 6) and S.shape == (2, 3, 8, 6)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)


def test_zero_start_is_the_default():
    q, k, v, g, beta, state = _inputs(1, 1, 40, 2, 8, 8)
    a = delta_rule(q, k, v, g, beta)
    b = delta_rule(q, k, v, g, beta, jnp.zeros_like(state))
    np.testing.assert_array_equal(a[0], b[0])


@pytest.mark.parametrize("T, chunk", [(80, 32), (21, 32)])
def test_gradients_are_autodiffs_of_the_recurrence(T, chunk, monkeypatch):
    """Every input's gradient through the custom backward (chunks recomputed
    from their saved starts) against autodiff of the plain recurrence, with a
    cotangent on the outputs and on the final state."""
    monkeypatch.setattr(delta_rule_module, "CHUNK", chunk)
    x = _inputs(2, 2, T, 2, 8, 8)
    w_o = jax.random.normal(jax.random.key(7), (2, T, 2, 8))
    w_s = jax.random.normal(jax.random.key(8), (2, 2, 8, 8))

    def loss(fn):
        def f(*a):
            o, S = fn(*a)
            return (o * w_o).sum() + (S * w_s).sum()
        return jax.jit(jax.grad(f, argnums=tuple(range(6))))

    got = loss(lambda *a: delta_rule(*a))(*x)
    want = loss(recurrence)(*x)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-4 * scale, err_msg=name
        )


def test_steps_equal_the_rule():
    """``k`` calls of ``delta_step`` against a carried state are the rule
    over ``k`` positions, from a state that is not zero."""
    q, k, v, g, beta, state = _inputs(3, 2, 24, 2, 8, 8)
    o, S = delta_rule(q, k, v, g, beta, state)
    carried, outs = state, []
    step = jax.jit(delta_step)
    for t in range(24):
        o_t, carried = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], carried)
        outs.append(o_t)
    np.testing.assert_allclose(jnp.stack(outs, 1), o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(carried, S, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("log_decay", [-1e-7, -30.0, -200.0])
def test_decays_at_both_ends_stay_finite(log_decay):
    """A decay next to 1 and one next to 0 (``exp(-200)`` is 0 in float32,
    and 64 steps of ``exp(-30)`` overflow any quotient of cumulative decays):
    values and gradients stay finite and are the recurrence's."""
    q, k, v, g, beta, state = _inputs(4, 1, CHUNK + SUB, 2, 8, 8)
    g = jnp.full_like(g, log_decay)
    # one channel apart from the rest, so blocks see both kinds at once
    g = g.at[..., 0].set(-0.05)
    x = (q, k, v, g, beta, state)
    o, S = jax.jit(delta_rule)(*x)
    o_ref, S_ref = jax.jit(recurrence)(*x)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)
    if log_decay == -30.0:
        grads = jax.jit(jax.grad(
            lambda *a: delta_rule(*a)[0].sum(), argnums=(0, 1, 2, 3, 4, 5)
        ))(*x)
        assert all(bool(jnp.isfinite(d).all()) for d in grads)


def test_bfloat16_values_round_the_products_only():
    """With ``v`` in bfloat16 the products take bfloat16 operands and the
    state stays float32: close to the float32 rule at bfloat16's precision,
    and the final state is float32."""
    q, k, v, g, beta, state = _inputs(5, 1, 2 * CHUNK, 2, 16, 16)
    o, S = delta_rule(q, k, v.astype(jnp.bfloat16), g, beta, state)
    o_ref, S_ref = recurrence(q, k, v, g, beta, state)
    assert o.dtype == jnp.float32 and S.dtype == jnp.float32
    assert float(jnp.abs(o - o_ref).max()) < 3e-2 * float(jnp.abs(o_ref).max())
    assert float(jnp.abs(S - S_ref).max()) < 3e-2 * float(jnp.abs(S_ref).max())


def test_chunk_must_hold_whole_blocks(monkeypatch):
    monkeypatch.setattr(delta_rule_module, "CHUNK", 24)
    q, k, v, g, beta, _ = _inputs(6, 1, 8, 1, 4, 4)
    with pytest.raises(ValueError, match="multiple"):
        delta_rule(q, k, v, g, beta)
