"""ops/delta_rule.py: the chunked gated delta rule against the recurrence a
position at a time (values and, through the ``custom_vjp``, gradients), a
length that is no multiple of the chunk, steps against a scan, and decays at
both ends of (0, 1); the Gram kernels and the two walks' kernels
(interpreted) against the ``lax`` forms they stand in for on the chip, and the
whole rule through them (the ``form`` fixture) against the recurrence."""

import functools

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


def _interpreted(kernel, lax_form, xs, *args):
    """``_where_lowered`` with the kernel interpreted where a TPU would run
    it."""
    if not delta_rule_module._kernel_takes(*xs[0].shape[3:], xs[2].shape[4]):
        return lax_form(xs, *args)
    return kernel(xs, *args, interpret=True)


@pytest.fixture
def form(request, monkeypatch):
    """``(K, V)`` of a form of the rule: ``"lax"`` at heads only the scans
    take, ``"kernels"`` at heads that ask for the walks' kernels, with every
    walk (and the Gram kernels inside it) interpreted."""
    if request.param == "lax":
        return 8, 6
    monkeypatch.setattr(delta_rule_module, "_where_lowered", _interpreted)
    return 128, 128


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


@pytest.mark.parametrize("form, T, chunk", [
    ("lax", 2 * CHUNK + 32, CHUNK), ("lax", 37, 32), ("lax", 5, CHUNK),
    # two whole chunks and a padded third through the kernels
    ("kernels", 2 * CHUNK + 32, CHUNK),
], indirect=["form"])
def test_chunked_rule_is_the_recurrence(form, T, chunk, monkeypatch):
    monkeypatch.setattr(delta_rule_module, "CHUNK", chunk)
    K, V = form
    x = _inputs(0, 2, T, 3, K, V)
    o, S = jax.jit(lambda *a: delta_rule(*a))(*x)
    o_ref, S_ref = jax.jit(recurrence)(*x)
    assert o.shape == (2, T, 3, V) and S.shape == (2, 3, K, V)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)


def test_zero_start_is_the_default():
    q, k, v, g, beta, state = _inputs(1, 1, 40, 2, 8, 8)
    a = delta_rule(q, k, v, g, beta)
    b = delta_rule(q, k, v, g, beta, jnp.zeros_like(state))
    np.testing.assert_array_equal(a[0], b[0])


@pytest.mark.parametrize("form, T, chunk", [
    ("lax", 80, 32), ("lax", 21, 32), ("kernels", CHUNK + 16, CHUNK),
], indirect=["form"])
def test_gradients_are_autodiffs_of_the_recurrence(form, T, chunk, monkeypatch):
    """Every input's gradient through the custom backward (chunks recomputed
    from their saved starts) against autodiff of the plain recurrence, with a
    cotangent on the outputs and on the final state."""
    monkeypatch.setattr(delta_rule_module, "CHUNK", chunk)
    K, V = form
    x = _inputs(2, 2, T, 2, K, V)
    w_o = jax.random.normal(jax.random.key(7), (2, T, 2, V))
    w_s = jax.random.normal(jax.random.key(8), (2, 2, K, V))

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


# -- the decayed Gram matrices: the kernels against the ``lax`` form -----------

def _gram_inputs(log_decay, K=128, chunks=2, heads=3):
    """``q, k, G [chunks, heads, CHUNK, K]`` (the chunks as batch rows) and a
    cotangent for the Gram; ``log_decay`` a step where it is not ``None``."""
    q, k, _, g, _, _ = _inputs(11, chunks, CHUNK, heads, K, K)
    if log_decay is not None:
        g = jnp.full_like(g, log_decay)
    q, k, g = (jnp.moveaxis(x, 1, 2) for x in (q, k, g))
    d = jax.random.normal(jax.random.key(12), q.shape[:-1] + (2 * CHUNK,))
    return q, k, jnp.cumsum(g, axis=2), d


@functools.cache
def _gram_forms(cd):
    """``(kernels, lax form)``, each ``q, k, G, d -> (gram, (dq, dk, dG))``,
    jitted once for every case."""
    def kernels(q, k, G, d):
        return (
            delta_rule_module._gram_pallas(q, k, G, cd, interpret=True),
            delta_rule_module._gram_pallas_bwd(q, k, G, d, cd, interpret=True),
        )

    def lax_form(q, k, G, d):
        gram, vjp = jax.vjp(
            functools.partial(delta_rule_module._gram_lax, cd=cd), q, k, G
        )
        return gram, vjp(d)

    return jax.jit(kernels), jax.jit(lax_form)


@pytest.mark.parametrize("log_decay", [None, -100.0, 0.0])
def test_gram_kernels_are_the_lax_form(log_decay):
    """Both matrices and ``dq, dk, dG`` of the kernels (interpreted) against
    the ``lax`` form and its ``jax.vjp`` at a shape the kernel accepts:
    decays as a layer gives them, ``e^-100`` a step (every decay between two
    positions underflows to an exact 0) and none (ties in every clamp)."""
    x = _gram_inputs(log_decay)
    kernels, lax_form = _gram_forms(jnp.float32)
    (gram, grads), (gram_ref, grads_ref) = kernels(*x), lax_form(*x)
    assert gram.shape == x[0].shape[:-1] + (2 * CHUNK,)
    assert all(bool(jnp.isfinite(a).all()) for a in (gram, *grads))
    np.testing.assert_allclose(gram, gram_ref, rtol=1e-5, atol=1e-6)
    for name, a, b in zip(("dq", "dk", "dG"), grads, grads_ref):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5 * max(float(jnp.abs(b).max()), 0.1),
            err_msg=name,   # dG cancels to 0 where every decay underflows
        )
    if log_decay == -100.0:
        # what is left is each position on itself: the two diagonals
        both = jnp.concatenate([jnp.eye(CHUNK, dtype=bool)] * 2, axis=1)
        assert not bool(jnp.where(both, 0.0, gram).any())


def test_gram_kernel_rounds_the_products_between_blocks_only():
    """With ``v`` in bfloat16 the products between blocks take bfloat16
    operands in the kernel as in the ``lax`` form (the same roundings, so the
    matrices agree to float32's precision), and a row's own block stays
    float32."""
    x = _gram_inputs(None, heads=1, chunks=1)
    kernels, lax_form = _gram_forms(jnp.bfloat16)
    (gram, grads), (gram_ref, grads_ref) = kernels(*x), lax_form(*x)
    np.testing.assert_allclose(gram, gram_ref, rtol=1e-5, atol=1e-6)
    exact = _gram_forms(jnp.float32)[1](*x)[0]
    own = jnp.kron(jnp.eye(CHUNK // SUB), jnp.ones((SUB, SUB))) > 0
    own = jnp.concatenate([own, own], axis=1)
    np.testing.assert_allclose(
        jnp.where(own, gram, 0.0), jnp.where(own, exact, 0.0),
        rtol=1e-5, atol=1e-6,
    )
    # the lax form's autodiff rounds each operand's cotangent to bfloat16
    for a, b in zip(grads, grads_ref):
        assert float(jnp.abs(a - b).max()) < 1e-2 * float(jnp.abs(b).max())


# -- the walks: the kernels against the ``lax`` scans ---------------------------

def _walk_inputs(log_decay, with_state, dtype=jnp.float32, K=128, chunks=2, heads=3):
    """``xs`` chunk-major ``[chunks, 1, heads, CHUNK, .]``, a starting state
    and cotangents for ``o`` and the final state."""
    q, k, v, g, beta, state = _inputs(21, 1, chunks * CHUNK, heads, K, K)
    if log_decay is not None:
        g = jnp.full_like(g, log_decay)
    xs = tuple(
        jnp.moveaxis(x.reshape(1, chunks, CHUNK, *x.shape[2:]), (1, 3), (0, 2))
        for x in (q, k, v.astype(dtype), g, beta)
    )
    do = jax.random.normal(jax.random.key(22), xs[2].shape)
    dfinal = jax.random.normal(jax.random.key(23), state.shape)
    return xs, state if with_state else jnp.zeros_like(state), do, dfinal


@functools.cache
def _walk_forms():
    """``(kernels, lax form)``, each ``xs, state, do, dfinal -> ((o, final,
    starts), ((dq, dk, dv, dg, dbeta), dstate))``, jitted once for every
    case."""
    def both(walk, walk_back, **kw):
        def run(xs, state, do, dfinal):
            o, final, starts = walk(xs, state, **kw)
            return (o, final, starts), walk_back(xs, starts, do, dfinal, **kw)
        return jax.jit(run)

    m = delta_rule_module
    return (
        both(m._walk, m._walk_back, interpret=True),
        both(m._walk_lax, m._walk_back_lax),
    )


WALK_NAMES = ("o", "final", "starts", "dq", "dk", "dv", "dg", "dbeta", "dstate")


@pytest.mark.parametrize("with_state", [True, False])
@pytest.mark.parametrize("log_decay", [None, -100.0, 0.0])
def test_walk_kernels_are_the_lax_walks(log_decay, with_state):
    """``delta_chunk_fwd`` and ``delta_chunk_bwd`` (interpreted) against the
    scans of ``_chunk`` and of its ``jax.vjp`` at ``K = V = 128``, two chunks
    of three heads: ``o``, the final state, the kept starts and all six
    gradients, from a state and from none, at a layer's decays, at ``e^-100``
    a step (the state never outlives a position) and at none."""
    x = _walk_inputs(log_decay, with_state)
    kernels, lax_form = _walk_forms()
    got, want = jax.tree.leaves(kernels(*x)), jax.tree.leaves(lax_form(*x))
    for name, a, b in zip(WALK_NAMES, got, want, strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-5 * max(float(jnp.abs(b).max()), 0.1),
            err_msg=name,
        )


def test_walk_kernels_round_the_products_operands_only():
    """With ``v`` in bfloat16 the walks' products take bfloat16 operands in
    the kernels as in the scans and the states stay float32: the forward
    agrees to float32's precision (the same roundings), ``dv`` comes back in
    bfloat16, and the gradients agree at bfloat16's (the scans' autodiff
    rounds each operand's cotangent, the kernels add them in float32)."""
    x = _walk_inputs(None, True, jnp.bfloat16, heads=1)
    kernels, lax_form = _walk_forms()
    got, want = jax.tree.leaves(kernels(*x)), jax.tree.leaves(lax_form(*x))
    for name, a, b in zip(WALK_NAMES, got, want, strict=True):
        assert a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        tol = 1e-5 if name in ("o", "final", "starts") else 1e-2
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max()), name
    assert got[1].dtype == jnp.float32 and got[5].dtype == jnp.bfloat16


@pytest.mark.parametrize("log_decay", [None, 0.0])
def test_the_kernels_solve_is_triangular_solve(log_decay):
    """The inverse the kernels apply (``(I - N)(I + N^2) .. (I + N^32)`` in
    float32 products) against ``triangular_solve`` in float32 on a chunk's
    own system, a right-hand side and a transposed one, at a layer's decays
    and at none (the largest entries a system of unit keys can hold)."""
    q, k, G, _ = _gram_inputs(log_decay, chunks=1, heads=1)
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.key(31), (CHUNK, 1)))
    k_on_k = delta_rule_module._gram_lax(q, k, G, jnp.float32)[0, 0, :, CHUNK:]
    lower = beta * jnp.tril(k_on_k, -1)
    rhs = jax.random.normal(jax.random.key(32), (CHUNK, 128))
    inverse = jax.jit(delta_rule_module._unit_lower_inverse)(lower)
    for transpose in (False, True):
        want = jax.lax.linalg.triangular_solve(
            jnp.eye(CHUNK) + lower, rhs, left_side=True, lower=True,
            unit_diagonal=True, transpose_a=transpose,
        )
        got = jnp.matmul(
            inverse.T if transpose else inverse, rhs, precision="highest"
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K, asks", [(8, False), (128, True)])
def test_the_shapes_choose_the_form(K, asks):
    """Heads of 8 channels never ask for the kernel; heads of 128 ask where
    the program is lowered for a TPU, and on the CPU the ``lax`` form is what
    runs there too (tests/test_tpu_compile.py sees the kernel chosen)."""
    q, k, v, g, beta, state = _inputs(13, 1, CHUNK, 2, K, K)
    text = str(jax.make_jaxpr(delta_rule)(q, k, v, g, beta, state))
    assert ("pallas_call" in text) == asks
    assert float(jax.jit(delta_rule_module.gram_in_vmem)(q)) == 0.0
    assert float(jax.jit(delta_rule_module.walk_in_vmem)(q, v)) == 0.0
    if not asks:    # every other test of this file runs heads of 8 or 16
        return
    o, S = jax.jit(delta_rule)(q, k, v, g, beta, state)
    o_ref, S_ref = jax.jit(recurrence)(q, k, v, g, beta, state)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, rtol=2e-5, atol=2e-5)


# -- one decay a head ----------------------------------------------------------------

@pytest.mark.parametrize("form, T", [
    ("lax", CHUNK + 21), ("kernels", CHUNK + 16),
], indirect=["form"])
def test_a_heads_decay_is_the_same_decay_a_channel(form, T):
    """``g [B, T, H]`` (one decay a head: the Gated DeltaNet rule) against
    the same decay spread over the head's channels by the caller: outputs,
    final state and every gradient, in the ``lax`` form and through the
    kernels, and both against the recurrence with a scalar decay."""
    K, V = form
    q, k, v, g, beta, state = _inputs(5, 2, T, 2, K, V)
    g_head = g[..., 0]
    g_wide = jnp.broadcast_to(g_head[..., None], g.shape)
    w_o = jax.random.normal(jax.random.key(7), (2, T, 2, V))
    w_s = jax.random.normal(jax.random.key(8), (2, 2, K, V))

    def run(fn, g):
        def f(q, k, v, g, beta, state):
            o, S = fn(q, k, v, g, beta, state)
            return (o * w_o).sum() + (S * w_s).sum(), (o, S)
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))(
            q, k, v, g, beta, state
        )

    (_, (o, S)), grads = run(delta_rule, g_head)
    (_, (o_w, S_w)), grads_w = run(delta_rule, g_wide)
    np.testing.assert_array_equal(o, o_w)
    np.testing.assert_array_equal(S, S_w)
    assert grads[3].shape == g_head.shape
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), grads, grads_w):
        if name == "g":
            b = b.sum(-1)       # the spread's transpose
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name
        )
    (_, (o_r, S_r)), grads_r = run(recurrence, g_wide)
    np.testing.assert_allclose(o, o_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        grads[3], grads_r[3].sum(-1), rtol=1e-4,
        atol=1e-4 * float(jnp.abs(grads_r[3].sum(-1)).max()),
    )


def test_steps_with_a_heads_decay_equal_the_rule():
    """``k`` calls of ``delta_step`` with ``g_t [B, H]`` against a carried
    state are the rule over ``k`` positions with ``g [B, T, H]``, and the
    steps with the same decay a channel, to the bit."""
    q, k, v, g, beta, state = _inputs(6, 2, 19, 3, 8, 6)
    g_head = g[..., 0]
    o_rule, S_rule = delta_rule(q, k, v, g_head, beta, state)
    S = S_wide = state
    outs = []
    for t in range(q.shape[1]):
        o_t, S = delta_step(q[:, t], k[:, t], v[:, t], g_head[:, t], beta[:, t], S)
        o_w, S_wide = delta_step(
            q[:, t], k[:, t], v[:, t],
            jnp.broadcast_to(g_head[:, t, :, None], q[:, t].shape), beta[:, t],
            S_wide,
        )
        np.testing.assert_array_equal(o_t, o_w)
        outs.append(o_t)
    np.testing.assert_array_equal(S, S_wide)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_rule, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S_rule, rtol=2e-5, atol=2e-5)
