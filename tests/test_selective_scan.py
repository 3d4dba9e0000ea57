"""The chunked selective scan (``ops/selective_scan.py``) against a plain
loop over positions: outputs, final state and every gradient, at lengths
that are and are not multiples of the chunk, with and without an incoming
state; ``k`` acting steps equal a scan over ``k`` positions. Both forms of
it: the ``lax`` loops at widths only they take, and the Pallas kernels
(interpreted) at widths that ask for them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.ops import selective_scan as scan_module
from surreal_tpu.ops.selective_scan import CHUNK, selective_scan, selective_step

B = 2
NAMES = ("u", "delta", "A", "B", "C", "D", "state")
# the kernels' chunk in these tests, and the channels they carry at a time:
# two lane blocks of the 256 channels
KERNEL_CHUNK, LANE_BLOCK = 16, 128


def _interpreted(kernels, kernel, lax_form, L, *args):
    """``_where_lowered`` with the kernel interpreted where a TPU would run
    it."""
    if not kernels:
        return lax_form(*args, L)
    return kernel(*args, L=L, interpret=True)


@pytest.fixture
def form(request, monkeypatch):
    """``(C, N)`` of a form of the scan: ``"lax"`` at widths the kernels do
    not take, ``"kernels"`` at widths they do, with every walk interpreted."""
    if request.param == "lax":
        return 6, 4
    monkeypatch.setattr(scan_module, "KERNEL_CHUNK", KERNEL_CHUNK)
    monkeypatch.setattr(scan_module, "LANE_BLOCK", LANE_BLOCK)
    monkeypatch.setattr(scan_module, "_where_lowered", _interpreted)
    return 256, 16


BOTH = pytest.mark.parametrize("form", ["lax", "kernels"], indirect=True)


def inputs(T: int, with_state: bool, dtype=jnp.float32, widths=(6, 4)):
    C, N = widths
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
@pytest.mark.parametrize("form,T,chunk", [
    ("lax", 8, 4), ("lax", 11, 4), ("lax", 1, 4), ("lax", 3, 8),
    ("lax", 13, 1), ("lax", 33, CHUNK), ("lax", 64, CHUNK),
    # the kernels choose their own chunk: two whole ones, two and a half,
    # less than one
    ("kernels", 32, CHUNK), ("kernels", 40, CHUNK), ("kernels", 5, CHUNK),
], indirect=["form"])
def test_chunked_scan_equals_the_loop(form, T, chunk, with_state):
    args = inputs(T, with_state, widths=form)
    y, final = selective_scan(*args[:6], args[6] if with_state else None, chunk)
    want_y, want_final = by_hand(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(final, want_final, rtol=1e-5, atol=1e-5)
    assert y.dtype == jnp.float32 and final.dtype == jnp.float32


@pytest.mark.parametrize("with_state", [False, True], ids=["zero", "state"])
@pytest.mark.parametrize("form,T,chunk", [
    ("lax", 8, 4), ("lax", 11, 4), ("lax", 5, 8), ("lax", 9, 1),
    ("kernels", 32, CHUNK), ("kernels", 21, CHUNK),
], indirect=["form"])
def test_every_gradient_equals_the_loops(form, T, chunk, with_state):
    """The backward of its own (a chunk's states recomputed from the saved
    start) against autodiff through the plain loop, for every input, the
    final state's cotangent included."""
    args = inputs(T, with_state, widths=form)
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


@BOTH
def test_bfloat16_inputs_are_raised_a_step_at_a_time(form):
    """bfloat16 ``u``, ``B`` and ``C`` give what their float32 values give:
    nothing inside the scan rounds to their dtype."""
    args = inputs(11, True, jnp.bfloat16, widths=form)
    raised = tuple(a.astype(jnp.float32) for a in args)
    y, final = selective_scan(*args, chunk=4)
    want_y, want_final = selective_scan(*raised, chunk=4)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-6)
    np.testing.assert_allclose(final, want_final, rtol=0, atol=2e-6)
    grads = jax.grad(lambda *a: selective_scan(*a, chunk=4)[0].sum())(*args)
    assert grads.dtype == jnp.bfloat16


@BOTH
@pytest.mark.parametrize("k", [1, 5])
def test_k_acting_steps_equal_a_scan_of_k(k, form):
    u, delta, A, Bm, Cm, D, state = inputs(k, True, widths=form)
    y, final = selective_scan(u, delta, A, Bm, Cm, D, state, chunk=4)
    # the kernels sum over n in another order than a step does
    tol = 1e-6 if form == (6, 4) else 5e-6
    s = state
    for t in range(k):
        y_t, s = jax.jit(selective_step)(
            u[:, t], delta[:, t], A, Bm[:, t], Cm[:, t], D, s
        )
        np.testing.assert_allclose(y_t, y[:, t], rtol=tol, atol=tol)
    np.testing.assert_allclose(s, final, rtol=tol, atol=tol)


@BOTH
def test_the_backward_keeps_chunk_starts_and_not_every_state(form):
    """What the forward saves for the backward: the inputs and ``T /
    chunk`` states, nothing ``[T, B, N, C]``."""
    (C, N), T = form, 32
    chunk = 4 if C % 128 else KERNEL_CHUNK
    args = inputs(T, False, widths=form)
    _, vjp = jax.vjp(lambda *a: selective_scan(*a, chunk=chunk), *args)
    kept = [x.shape for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
    assert (T // chunk, B, N, C) in kept
    assert not [s for s in kept if len(s) >= 4 and T in s and N in s], kept


def _walk_inputs(T, with_state, dtype):
    u, delta, A, Bm, Cm, D, state = inputs(T, with_state, dtype, (256, 16))
    k = jax.random.split(jax.random.key(T + 1), 2)
    return (u, delta, A, Bm, Cm, D, state), (
        jax.random.normal(k[0], u.shape), jax.random.normal(k[1], state.shape)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("with_state", [False, True], ids=["zero", "state"])
def test_the_kernels_are_the_lax_form(with_state, dtype, monkeypatch):
    """Both walks of the kernels (interpreted) against the ``lax`` form's at
    the same chunks: ``y``, the final state, the kept starts and all seven
    cotangents, to float32 rounding (the sums over ``n``, the channels and the
    positions run in another order)."""
    monkeypatch.setattr(scan_module, "LANE_BLOCK", LANE_BLOCK)
    L = KERNEL_CHUNK
    args, cts = _walk_inputs(3 * L, with_state, dtype)
    y, final, starts = scan_module._walk(*args, L=L, interpret=True)
    want = scan_module._walk_lax(*args, L)
    for name, a, b in zip(("y", "final", "starts"), (y, final, starts), want):
        assert a.dtype == jnp.float32 and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    got = scan_module._walk_back(*args[:6], starts, *cts, L=L, interpret=True)
    # each cotangent comes in its input's dtype, rounded once at the end
    raised = tuple(a.astype(jnp.float32) for a in args[:6])
    want = scan_module._walk_back_lax(*raised, want[2], *cts, L)
    for name, x, a, b in zip(NAMES, args, got, want):
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert a.dtype == x.dtype and a.shape == b.shape, name
        # (a bfloat16 one may round a near tie the other way: one ulp)
        tol = 3e-6 if a.dtype == jnp.float32 else 2.0 ** -7
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=0, atol=tol * scale, err_msg=name
        )


@pytest.mark.parametrize("widths,asks", [((6, 4), False), ((256, 16), True)])
def test_the_shapes_choose_the_form(widths, asks):
    """Widths that fill whole tiles ask for the kernels where the program is
    lowered for a TPU; on the CPU the ``lax`` form runs there too
    (tests/test_tpu_compile.py sees the kernels chosen), and
    ``scan_in_vmem`` reads 0."""
    args = inputs(40, True, jnp.bfloat16, widths=widths)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: selective_scan(*a)[0].sum()
    ))(*args))
    assert ("pallas_call" in text) == asks
    assert float(jax.jit(scan_module.scan_in_vmem)(args[0], args[2])) == 0.0
    if asks:
        y, final = jax.jit(selective_scan)(*args)
        want_y, want_final = by_hand(*args)
        np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(final, want_final, rtol=1e-5, atol=1e-5)
