"""The chunked selective scan (``ops/selective_scan.py``) against a plain
loop over positions: outputs, final state and every gradient, at lengths
that are and are not multiples of the chunk, with and without an incoming
state; ``k`` acting steps equal a scan over ``k`` positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.ops.selective_scan import CHUNK, selective_scan, selective_step

B, C, N = 2, 6, 4
NAMES = ("u", "delta", "A", "B", "C", "D", "state")


def inputs(T: int, with_state: bool, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(T), 7)
    return (
        jax.random.normal(k[0], (B, T, C)).astype(dtype),
        jax.nn.softplus(jax.random.normal(k[1], (B, T, C))),
        -jnp.exp(jax.random.normal(k[2], (N, C))),
        jax.random.normal(k[3], (B, T, N)).astype(dtype),
        jax.random.normal(k[4], (B, T, N)).astype(dtype),
        jax.random.normal(k[5], (C,)),
        jax.random.normal(k[6], (B, N, C)) if with_state
        else jnp.zeros((B, N, C)),
    )


def loop(u, delta, A, Bm, Cm, D, state):
    """One position after another, every state kept by autodiff."""
    ys = []
    for t in range(u.shape[1]):
        y, state = selective_step(
            u[:, t], delta[:, t], A, Bm[:, t], Cm[:, t], D, state
        )
        ys.append(y)
    return jnp.stack(ys, 1), state


def by_hand(u, delta, A, Bm, Cm, D, state):
    """The recurrence written out in numpy, float64: what ``selective_step``
    itself is held to."""
    u, delta, A, Bm, Cm, D, s = (
        np.asarray(x, np.float64) for x in (u, delta, A, Bm, Cm, D, state)
    )
    ys = []
    for t in range(u.shape[1]):
        decay = np.exp(delta[:, t, None, :] * A[None])
        s = decay * s + (delta[:, t] * u[:, t])[:, None, :] * Bm[:, t, :, None]
        ys.append((s * Cm[:, t, :, None]).sum(1) + D * u[:, t])
    return np.stack(ys, 1), s


@pytest.mark.parametrize("with_state", [False, True], ids=["zero", "state"])
@pytest.mark.parametrize("T,chunk", [
    (8, 4), (11, 4), (1, 4), (3, 8), (13, 1), (33, CHUNK), (64, CHUNK),
])
def test_chunked_scan_equals_the_loop(T, chunk, with_state):
    args = inputs(T, with_state)
    y, final = selective_scan(*args[:6], args[6] if with_state else None, chunk)
    want_y, want_final = by_hand(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(final, want_final, rtol=1e-5, atol=1e-5)
    assert y.dtype == jnp.float32 and final.dtype == jnp.float32


@pytest.mark.parametrize("with_state", [False, True], ids=["zero", "state"])
@pytest.mark.parametrize("T,chunk", [(8, 4), (11, 4), (5, 8), (9, 1)])
def test_every_gradient_equals_the_loops(T, chunk, with_state):
    """The backward of its own (a chunk's states recomputed from the saved
    start) against autodiff through the plain loop, for every input, the
    final state's cotangent included."""
    args = inputs(T, with_state)
    weigh = lambda y, s: (y ** 2).sum() + (s ** 3).sum()
    got = jax.grad(
        lambda *a: weigh(*selective_scan(*a, chunk=chunk)), argnums=range(7)
    )(*args)
    want = jax.grad(lambda *a: weigh(*loop(*a)), argnums=range(7))(*args)
    for name, g, w in zip(NAMES, got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-6 * max(scale, 1.0), err_msg=name
        )
        assert scale > 0, name


def test_bfloat16_inputs_are_raised_a_step_at_a_time():
    """bfloat16 ``u``, ``B`` and ``C`` give what their float32 values give:
    nothing inside the scan rounds to their dtype."""
    args = inputs(11, True, jnp.bfloat16)
    raised = tuple(a.astype(jnp.float32) for a in args)
    y, final = selective_scan(*args, chunk=4)
    want_y, want_final = selective_scan(*raised, chunk=4)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-6)
    np.testing.assert_allclose(final, want_final, rtol=0, atol=2e-6)
    grads = jax.grad(lambda *a: selective_scan(*a, chunk=4)[0].sum())(*args)
    assert grads.dtype == jnp.bfloat16


@pytest.mark.parametrize("k", [1, 5])
def test_k_acting_steps_equal_a_scan_of_k(k):
    u, delta, A, Bm, Cm, D, state = inputs(k, True)
    y, final = selective_scan(u, delta, A, Bm, Cm, D, state, chunk=4)
    s = state
    for t in range(k):
        y_t, s = jax.jit(selective_step)(
            u[:, t], delta[:, t], A, Bm[:, t], Cm[:, t], D, s
        )
        np.testing.assert_allclose(y_t, y[:, t], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s, final, rtol=1e-6, atol=1e-6)


def test_the_backward_keeps_chunk_starts_and_not_every_state():
    """What the forward saves for the backward: the inputs and ``T /
    chunk`` states, nothing ``[T, B, N, C]``."""
    T, chunk = 32, 4
    args = inputs(T, False)
    _, vjp = jax.vjp(lambda *a: selective_scan(*a, chunk=chunk), *args)
    kept = [x.shape for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
    assert (T // chunk, B, N, C) in kept
    assert not [s for s in kept if len(s) >= 4 and T in s and N in s], kept
