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
lineage's 64). The two Gram matrices ``A`` and ``B`` need ``q``, ``k`` and
``G`` alone, no state: :func:`decayed_gram` forms them, side by side as one
``[L, 2 L]`` array a head, and a chunk step takes them from it.

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

**Where the Gram matrices are formed.** The pairwise decays of a chunk step
are ``[B, H, L / SUB, SUB, SUB, K]`` float32 (134 MB at 8 x 32 heads of 128
channels), thirty times what goes in and comes out. Where the program is
lowered for a TPU, a chunk is whole (``L == CHUNK``) and a head fills the
lanes (``K % 128 == 0``), two Pallas kernels form them in VMEM, a head's
``[L, K]`` tiles a program: :func:`_gram_kernel` writes the two matrices,
:func:`_gram_bwd_kernel` forms the decays again from ``q, k, G`` and the
matrices' cotangent and writes ``dq, dk, dG``, and a ``jax.custom_vjp``
carries the pair, so nothing of the decays' size is an input, an output or a
residual. The mathematics is the paragraph above, place for place. Everywhere
else (the CPU, a length under one chunk, heads of 8 channels)
:func:`_gram_lax` runs and autodiff differentiates it: the one ``lax`` form
the kernels are tested against. The choice is made when the
program is lowered (``jax.lax.platform_dependent``), from the device and the
shapes and from nothing else, so a compile for a described TPU in a CPU
process gets the kernels; :func:`gram_in_vmem` says which form a call takes.

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

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a chunk: the forward saves T / CHUNK states, the triangular
# system is CHUNK x CHUNK
CHUNK = 64
# positions a block of a chunk: decays between positions of one block are
# formed pair by pair (module docstring)
SUB = 16


def _chunk_len(T: int) -> int:
    """Positions a chunk of a length of ``T``: whole blocks, :data:`CHUNK`
    at most."""
    return min(CHUNK, -(-T // SUB) * SUB)


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


def _gram_lax(q, k, G, cd):
    """A chunk's decayed Gram matrices in plain ``lax``: ``q, k, G [B, H, L,
    K]`` float32 -> ``[B, H, L, 2 L]``, ``q_on_k`` in the first ``L`` columns
    and ``k_on_k`` in the last. What :func:`_gram_kernel` is tested against,
    and what runs where the kernel does not."""
    f32 = jnp.float32
    B, H, L, K = q.shape
    C = min(SUB, L)
    nb = L // C
    dot = lambda spec, a, b: jnp.einsum(   # noqa: E731
        spec, a.astype(cd), b.astype(cd), preferred_element_type=f32
    )
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
    return jnp.concatenate(tuple(off + own), axis=-1)


def _earlier_blocks(k, G, at, cd):
    """What a block of rows starting at ``at > 0`` needs of the blocks before
    it, from a head's ``k, G [L, K]``: ``(into [SUB, K]``, its rows' decay
    from the block's start; ``since [L, K]``, each column's log-decay up to
    that start, <= 0 for the earlier ones; ``cols [2 L, K]`` in ``cd``, the
    earlier columns decayed up to the start and zeros after, twice over so
    that one product fills all ``2 L`` lanes of the output)``."""
    R = G[at - 1:at]
    since = R - G
    row = jax.lax.broadcasted_iota(jnp.int32, G.shape, 0)
    cols = jnp.where(
        row < at, k * jnp.exp(jnp.minimum(since, 0.0)), 0.0
    ).astype(cd)
    return jnp.exp(G[at:at + SUB] - R), since, jnp.concatenate([cols, cols])


def _gram_kernel(q_ref, k_ref, G_ref, out_ref, *, cd):
    """One head's chunk: ``q, k, G [L, K]`` -> ``[L, 2 L]``, block of rows by
    block of rows, :func:`_gram_lax` place for place."""
    f32 = jnp.float32
    L, K = q_ref.shape
    k, G = k_ref[...], G_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, 2 * L), 1)
    below = jax.lax.broadcasted_iota(jnp.int32, (SUB, K), 0)
    for n in range(L // SUB):
        at = n * SUB
        here = slice(at, at + SUB)
        q_n, k_n, G_n = q_ref[here, :], k[here], G[here]
        out = jnp.zeros((SUB, 2 * L), f32)
        if n:
            into, _, cols = _earlier_blocks(k, G, at, cd)
            both = jax.lax.dot_general(
                jnp.concatenate([q_n * into, k_n * into]).astype(cd), cols,
                (((1,), (1,)), ((), ())), preferred_element_type=f32,
            )
            out = jnp.where(lane < L, both[:SUB], both[SUB:])
        for i in range(SUB):
            pair = jnp.where(
                below >= i, jnp.exp(jnp.minimum(G_n - G_n[i:i + 1], 0.0)), 0.0
            ) * k_n[i:i + 1]
            out = jnp.where(
                lane == at + i, (q_n * pair).sum(-1, keepdims=True), out
            )
            out = jnp.where(
                lane == L + at + i, (k_n * pair).sum(-1, keepdims=True), out
            )
        out_ref[here, :] = out


def _gram_bwd_kernel(q_ref, k_ref, G_ref, d_ref, dq_ref, dk_ref, dG_ref, *, cd):
    """One head's chunk: ``q, k, G [L, K]`` and the Gram's cotangent ``[L, 2
    L]`` -> ``dq, dk, dG [L, K]``, the decays formed again as the forward
    forms them."""
    f32 = jnp.float32
    L, K = q_ref.shape
    k, G = k_ref[...], G_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, K), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, 2 * L), 1)
    below = jax.lax.broadcasted_iota(jnp.int32, (SUB, K), 0)
    # what reaches k and G through a column, summed over the blocks of rows
    dk_col = jnp.zeros((L, K), f32)
    dG_col = jnp.zeros((L, K), f32)
    for n in range(L // SUB):
        at = n * SUB
        here = slice(at, at + SUB)
        q_n, k_n, G_n, d_n = q_ref[here, :], k[here], G[here], d_ref[here, :]
        dq_n = jnp.zeros((SUB, K), f32)
        dk_n = jnp.zeros((SUB, K), f32)
        if n:
            into, since, cols = _earlier_blocks(k, G, at, cd)
            d_q = jnp.where(lane < at, d_n, 0.0).astype(cd)
            d_k = jnp.where((lane >= L) & (lane < L + at), d_n, 0.0).astype(cd)
            dot = lambda a, b, dims: jax.lax.dot_general(   # noqa: E731
                a, b, (dims, ((), ())), preferred_element_type=f32
            )
            dq_n = dot(d_q, cols, ((1,), (0,))) * into
            dk_n = dot(d_k, cols, ((1,), (0,))) * into
            dcols = (
                dot(d_q, (q_n * into).astype(cd), ((0,), (0,)))[:L]
                + dot(d_k, (k_n * into).astype(cd), ((0,), (0,)))[L:]
            ) * jnp.exp(jnp.minimum(since, 0.0))
            dk_col = dk_col + dcols
            # jnp.minimum's own split of a tie
            through = jnp.where(since < 0, 1.0, jnp.where(since == 0, 0.5, 0.0))
            moved = through * k * dcols
            start = (
                moved.sum(0, keepdims=True)
                - (q_n * dq_n + k_n * dk_n).sum(0, keepdims=True)
            )
            dG_col = dG_col - moved + jnp.where(row == at - 1, start, 0.0)
        own_col = jnp.zeros((SUB, K), f32)
        for i in range(SUB):
            decay = jnp.where(
                below >= i, jnp.exp(jnp.minimum(G_n - G_n[i:i + 1], 0.0)), 0.0
            )
            d_qi = d_n[:, at + i:at + i + 1]
            d_ki = d_n[:, L + at + i:L + at + i + 1]
            pair = decay * k_n[i:i + 1]
            dq_n = dq_n + d_qi * pair
            dk_n = dk_n + d_ki * pair
            reach = ((d_qi * q_n + d_ki * k_n) * decay).sum(0, keepdims=True)
            own_col = jnp.where(below == i, reach, own_col)
        dq_ref[here, :] = dq_n
        dk_ref[here, :] = dk_n + own_col
        dG_ref[here, :] = q_n * dq_n + k_n * dk_n - k_n * own_col
    dk_ref[...] += dk_col
    dG_ref[...] += dG_col


def _gram_call(kernel, name, cd, interpret, ins, outs):
    """``kernel`` over (batch row, head) of ``[B, H, L, .]`` arrays, a head's
    ``[L, .]`` tile a program; ``name`` is what a device trace calls it."""
    tile = lambda x: pl.BlockSpec(   # noqa: E731
        (None, None) + x.shape[2:], lambda b, h: (b, h, 0, 0)
    )
    return pl.pallas_call(
        functools.partial(kernel, cd=cd),
        grid=ins[0].shape[:2],
        in_specs=[tile(x) for x in ins],
        out_specs=jax.tree.map(tile, outs),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name=name,
    )(*ins)


# jitted so that a program traces and lowers each kernel once a shape, not
# once a call site (a fifth of a second each, twenty sites in a fused PPO
# iteration of four such layers)
@functools.partial(jax.jit, static_argnames=("cd", "interpret"))
def _gram_pallas(q, k, G, cd, interpret=False):
    out = jax.ShapeDtypeStruct(q.shape[:3] + (2 * q.shape[2],), jnp.float32)
    return _gram_call(_gram_kernel, "decayed_gram", cd, interpret, (q, k, G), out)


@functools.partial(jax.jit, static_argnames=("cd", "interpret"))
def _gram_pallas_bwd(q, k, G, d, cd, interpret=False):
    out = jax.ShapeDtypeStruct(q.shape, jnp.float32)
    return _gram_call(
        _gram_bwd_kernel, "decayed_gram_bwd", cd, interpret, (q, k, G, d),
        (out,) * 3,
    )


def _kernel_takes(L: int, K: int) -> bool:
    """Whether the kernels take chunks of ``L`` positions of ``K`` channels:
    whole chunks (their four blocks of rows are unrolled) of heads that fill
    the lanes."""
    return L == CHUNK and K % 128 == 0


def gram_in_vmem(q):
    """1.0 where :func:`delta_rule` of ``q [B, T, H, K]`` forms its chunks'
    Gram matrices in the kernel, 0.0 where in the ``lax`` form: a float32
    scalar, settled when the program is lowered for its device."""
    if not _kernel_takes(_chunk_len(q.shape[1]), q.shape[3]):
        return jnp.float32(0.0)
    return jax.lax.platform_dependent(
        tpu=lambda: jnp.float32(1.0), default=lambda: jnp.float32(0.0)
    )


def decayed_gram(q, k, G, cd):
    """``q, k, G [B, H, L, K]`` float32, ``G`` the chunk's running sum of the
    log-decays -> ``[B, H, L, 2 L]`` float32: in the first ``L`` columns
    ``q_on_k[t, i] = sum_c q_t[c] exp(G_t[c] - G_i[c]) k_i[c]`` for ``i <= t``
    and 0 above, in the last ``L`` the same with ``k_t`` for ``q_t``. The
    products between blocks take their operands in ``cd``. The kernels where
    they take the shape and the program is lowered for a TPU, else
    :func:`_gram_lax` (module docstring)."""
    if not _kernel_takes(*q.shape[2:]):
        return _gram_lax(q, k, G, cd)
    return _gram_where_lowered(q, k, G, cd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gram_where_lowered(q, k, G, cd):
    return jax.lax.platform_dependent(
        q, k, G, tpu=functools.partial(_gram_pallas, cd=cd),
        default=functools.partial(_gram_lax, cd=cd),
    )


def _gram_where_lowered_fwd(q, k, G, cd):
    return _gram_where_lowered(q, k, G, cd), (q, k, G)


def _gram_where_lowered_bwd(cd, res, d):
    return jax.lax.platform_dependent(
        *res, d, tpu=functools.partial(_gram_pallas_bwd, cd=cd),
        default=lambda q, k, G, d: jax.vjp(
            functools.partial(_gram_lax, cd=cd), q, k, G
        )[1](d),
    )


_gram_where_lowered.defvjp(_gram_where_lowered_fwd, _gram_where_lowered_bwd)


def _chunk(state, xs):
    """One chunk: ``xs = (q, k [B, H, L, K], v [B, H, L, V], g [B, H, L, K],
    beta [B, H, L])`` from ``state [B, H, K, V]`` -> ``(state after, o [B,
    H, L, V])``."""
    q, k, v, g, beta = xs
    f32, cd = jnp.float32, v.dtype
    L = q.shape[2]
    q, k, g, beta = (x.astype(f32) for x in (q, k, g, beta))
    dot = lambda spec, a, b: jnp.einsum(   # noqa: E731
        spec, a.astype(cd), b.astype(cd), preferred_element_type=f32
    )
    G = jnp.cumsum(g, axis=2)                                   # <= 0
    gram = decayed_gram(q, k, G, cd)
    q_on_k, k_on_k = gram[..., :L], gram[..., L:]
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
    L = _chunk_len(T)
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
