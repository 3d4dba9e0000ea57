"""The gated delta rule with a decay a channel, in chunks, with its own
backward.

The recurrence of a Kimi Delta Attention layer (Kimi Linear, arXiv:2510.26692,
section 3; the delta rule of Schlag et al. 2021, arXiv:2102.11174, gated a
channel), per batch row and head, the state ``S [K, V]`` a matrix:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                    alpha_t = exp(g_t) in (0, 1]^K

so a step first decays every row of the state by its own channel's
``alpha``, then erases what the decayed state holds along the key it is
about to write (``beta`` of it) and writes the new value there. The state is
float32 throughout.

**In chunks.** With ``u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)``,
the value a step really writes, ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``.
Inside a chunk of ``L`` positions that starts from ``S_0``, with ``G_t`` the
sum of ``g_1 .. g_t`` (so ``exp(G_t - G_i)`` is the decay from after step
``i`` through step ``t``):

    (I + Diag(beta) tril(A, -1)) U = Diag(beta) (V - (K * exp(G)) S_0)
        A[t, i] = sum_c k_t[c] exp(G_t[c] - G_i[c]) k_i[c]
    O   = (Q * exp(G)) S_0 + tril(B) U,   B[t, i] = the same with q_t
    S_L = Diag(exp(G_L)) S_0 + (K * exp(G_L - G))^T U

a unit lower-triangular system solved in float32 and five products. The
chunks run in turn, carrying the state (:data:`CHUNK` positions each: the
lineage's 64).

**No quotient of decays is formed.** ``exp(G_t - G_i)`` factored as
``exp(G_t) / exp(G_i)`` overflows float32 once a channel has decayed by
``e^-88`` inside a chunk. Instead a chunk is cut into blocks of :data:`SUB`
positions. For ``i`` in an earlier block than ``t``, with ``R`` the sum of
``g`` up to the start of ``t``'s block, ``exp(G_t - G_i) = exp(G_t - R)
exp(R - G_i)``: both exponents are sums of ``g`` over positions between the
two, so both are <= 0, and the products run on the matrix unit with the
factors folded into their operands. For ``i`` in ``t``'s own block the
exponent ``G_t - G_i`` is formed pair by pair (``SUB x SUB x K`` a block, on
the vector unit). Every ``exp`` here takes an argument <= 0; a decay near 0
underflows to an exact 0, which is what it is.

**Why a backward of its own.** Differentiated as a plain scan the rule keeps
every state: ``[T, B, H, K, V]`` float32 is 17 GB at 8 x 1024 positions of
32 heads of 128 x 128. The forward keeps the state each chunk started from
(``T / CHUNK`` of them: 268 MB there) and the backward walks the chunks last
to first, recomputes one chunk from its saved start (``jax.vjp`` of the
chunk's own forward) and hands the state's cotangent on to the chunk before,
as ``ops/selective_scan.py`` does.

A length that is no multiple of the chunk is padded at its end with ``g = 0,
beta = 0``: the decay is then 1 and nothing is erased or written, so the
state passes through the padding untouched and the padded outputs are cut off.

:func:`delta_step` is one position of the same recurrence, all on the vector
unit in float32: what an acting step runs against the state it carries, so
``k`` steps equal the rule over ``k`` positions.

The products take their operands in ``v``'s dtype (bfloat16 under the mixed
policy, where a chunk's starting state is rounded once as an operand and
never as a carry) and accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions a chunk: the forward saves T / CHUNK states, the triangular
# system is CHUNK x CHUNK
CHUNK = 64
# positions a block of a chunk: decays between positions of one block are
# formed pair by pair (module docstring)
SUB = 16


def delta_step(q_t, k_t, v_t, g_t, beta_t, state):
    """One position: ``q_t, k_t, g_t [B, H, K]``, ``v_t [B, H, V]``,
    ``beta_t [B, H]``, ``state [B, H, K, V]`` float32 -> ``(o_t [B, H, V]
    float32, new state)``."""
    f32 = jnp.float32
    q_t, k_t, v_t = q_t.astype(f32), k_t.astype(f32), v_t.astype(f32)
    state = jnp.exp(g_t.astype(f32))[..., None] * state
    held = (k_t[..., None] * state).sum(-2)
    u = beta_t.astype(f32)[..., None] * (v_t - held)
    state = state + k_t[..., None] * u[..., None, :]
    return (q_t[..., None] * state).sum(-2), state


def _chunk(state, xs):
    """One chunk: ``xs = (q, k [B, H, L, K], v [B, H, L, V], g [B, H, L, K],
    beta [B, H, L])`` from ``state [B, H, K, V]`` -> ``(state after, o [B,
    H, L, V])``."""
    q, k, v, g, beta = xs
    f32, cd = jnp.float32, v.dtype
    B, H, L, K = q.shape
    C = min(SUB, L)
    nb = L // C
    q, k, g, beta = (x.astype(f32) for x in (q, k, g, beta))
    dot = lambda spec, a, b: jnp.einsum(   # noqa: E731
        spec, a.astype(cd), b.astype(cd), preferred_element_type=f32
    )

    G = jnp.cumsum(g, axis=2)                                   # <= 0
    Gb = G.reshape(B, H, nb, C, K)
    # R[n]: the log-decay up to the start of block n
    R = jnp.concatenate(
        [jnp.zeros((B, H, 1, K), f32), Gb[:, :, :-1, -1]], axis=2
    )
    rows = jnp.stack([q, k]).reshape(2, B, H, nb, C, K)
    # blocks before a row's own: the row decays from its block's start, the
    # column up to that start
    into = jnp.exp(Gb - R[:, :, :, None])
    upto = jnp.exp(jnp.minimum(R[:, :, :, None] - G[:, :, None], 0.0))
    off = dot(
        "xbhnck,bhnlk->xbhncl", rows * into, k[:, :, None] * upto
    )
    earlier = jnp.arange(L) < (jnp.arange(nb) * C)[:, None, None]
    off = jnp.where(earlier, off, 0.0).reshape(2, B, H, L, L)
    # a row's own block: each pair's decay by itself
    lower = jnp.tril(jnp.ones((C, C), bool))
    pair = jnp.exp(jnp.where(
        lower[..., None], Gb[:, :, :, :, None] - Gb[:, :, :, None, :], 0.0
    ))
    own = (
        rows[..., :, None, :] * pair * k.reshape(B, H, nb, 1, C, K)
    ).sum(-1)
    own = jnp.where(lower, own, 0.0)
    own = jnp.einsum(
        "xbhncd,nm->xbhncmd", own, jnp.eye(nb, dtype=f32)
    ).reshape(2, B, H, L, L)
    q_on_k, k_on_k = off + own
    eye = jnp.eye(L, dtype=f32)

    since = jnp.exp(G)                      # decay since the chunk's start
    rhs = beta[..., None] * (
        v.astype(f32) - dot("bhlk,bhkv->bhlv", k * since, state)
    )
    system = eye + beta[..., None] * k_on_k * (1.0 - eye)
    u = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True
    )
    o = dot("bhlk,bhkv->bhlv", q * since, state) + dot(
        "bhls,bhsv->bhlv", q_on_k, u
    )
    to_end = jnp.exp(G[:, :, -1:] - G)      # each position's decay to the end
    state = since[:, :, -1, :, None] * state + dot(
        "bhlk,bhlv->bhkv", k * to_end, u
    )
    return state, o


@jax.custom_vjp
def _chunked(xs, state):
    """``xs`` chunk-major ``[N, B, H, L, .]`` -> ``(o [N, B, H, L, V], final
    state)``."""
    return _chunked_fwd(xs, state)[0]


def _chunked_fwd(xs, state):
    def outer(s, xs_n):
        s_next, o_n = _chunk(s, xs_n)
        return s_next, (o_n, s)

    final, (o, starts) = jax.lax.scan(outer, state, xs)
    return (o, final), (xs, starts)


def _chunked_bwd(res, cts):
    xs, starts = res
    do, dfinal = cts

    def outer(ds, inp):
        xs_n, s_n, do_n = inp
        _, vjp = jax.vjp(_chunk, s_n, xs_n)
        gs, gxs = vjp((ds, do_n))
        return gs, gxs

    dstate, dxs = jax.lax.scan(outer, dfinal, (xs, starts, do), reverse=True)
    return dxs, dstate


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def delta_rule(q, k, v, g, beta, state=None):
    """``q, k, g [B, T, H, K]`` (``g`` the log-decay, <= 0), ``v [B, T, H,
    V]``, ``beta [B, T, H]``, ``state [B, H, K, V]`` float32 (zeros when
    ``None``) -> ``(o [B, T, H, V] float32, final state [B, H, K, V]
    float32)``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    if CHUNK % SUB:
        raise ValueError(f"CHUNK={CHUNK} must be a multiple of {SUB}")
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)
    L = min(CHUNK, -(-T // SUB) * SUB)
    pad = (-T) % L

    def chunked(x):
        # [B, T, H, ...] -> [N, B, H, L, ...], zeros after the end
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, (T + pad) // L, L, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    o, final = _chunked(
        tuple(chunked(x) for x in (q, k, v, g, beta)),
        state.astype(jnp.float32),
    )
    # [N, B, H, L, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(B, T + pad, H, V)
    return o[:, :T], final
