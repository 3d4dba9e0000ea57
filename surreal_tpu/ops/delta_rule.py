"""The gated delta rule with a decay a channel or a head, in chunks, with its
own backward, the matrix state in VMEM where a TPU runs it.

The recurrence of a Kimi Delta Attention layer (Kimi Linear, arXiv:2510.26692,
section 3; the delta rule of Schlag et al. 2021, arXiv:2102.11174, gated a
channel), per batch row and head, the state ``S [K, V]`` a matrix:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                    alpha_t = exp(g_t) in (0, 1]^K

so a step first decays every row of the state by its own channel's
``alpha``, then erases what the decayed state holds along the key it is
about to write (``beta`` of it) and writes the new value there. The state is
float32 throughout.

**The rank of ``g`` says which decay.** ``g`` of ``q``'s rank (``[B, T, H,
K]``; ``[B, H, K]`` a step) is a decay a channel, ``models/kda_moe.py``'s;
``g`` one rank lower (``[B, T, H]``; ``[B, H]`` a step) is one decay a head,
``Diag(alpha_t)`` a multiple of the identity: the Gated DeltaNet rule
(Yang et al. 2024, arXiv:2412.06464), ``models/gdn_moe.py``'s. No key or
flag: :func:`delta_rule` spreads a head's decay over the head's channels and
runs the forms below as they are, which is exact (every channel's running
sum is then the head's); :func:`delta_step` scales the whole state. A
head-wide decay does not need the pairwise ``[SUB, SUB, K]`` decays it pays
for so (a chunk's Gram pair is then ``(Q K^T) * exp(G_t - G_i)`` under the
triangle, an ``[L, L]`` table): that lean form is not written (ROADMAP S16).

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
to first, forms one chunk again from its saved start and hands the state's
cotangent on to the chunk before, as ``ops/selective_scan.py`` does.

**Where the walks run.** As ``lax`` scans (:func:`_walk_lax`, a scan of
:func:`_chunk`; :func:`_walk_back_lax`, a scan of its ``jax.vjp``) a chunk
step passes the state, the solve and the five products' operands through
HBM: 25 ops and 0.40 GB forward, 40 ops and 0.85 GB back at ``[8, 32, 64,
128]`` where 0.05 GB go in and come out. Where the program is lowered for a
TPU, a chunk is whole and the heads fill the lanes (``K`` and ``V`` multiples
of 128), two Pallas kernels walk instead, a grid of (batch row, block of
:data:`HEADS` heads, chunk) with a row's chunks in turn:
``delta_chunk_fwd`` (:func:`_fwd_kernel`) keeps the heads' states ``[K, V]``
float32 in VMEM scratch from a row's first chunk to its last and writes
``o``, the state each chunk started from and the final state;
``delta_chunk_bwd`` (:func:`_bwd_kernel`) takes the chunks last to first
through its index maps with the states' cotangents in scratch, forms a
chunk's ``u`` again from its saved start and writes ``dq, dk, dv, dG, dbeta``,
the Gram pair's cotangent and, after the first chunk, ``dstate``. Both are
:func:`_chunk`'s lines after ``decayed_gram``, place for place, and its
precisions: the products' operands in ``v``'s dtype, the state rounded once
as an operand and never as a carry, the triangular system in float32
(:func:`_unit_lower_inverse`: row substitution inside blocks of :data:`SUB`,
float32 products between blocks; with ``M`` the system and ``U = M^-1 R`` the
backward is ``dR = M^-T dU``, ``dM = -tril(dR U^T, -1)``). The Gram kernels
stay what they are: :func:`_walk` calls ``decayed_gram`` once over all of a
walk's chunks ahead of it, :func:`_walk_back` forms the pairs again (134 MB a
layer, a temporary: the residuals stay ``(xs, starts)``) and calls
``decayed_gram_bwd`` once on the reverse walk's cotangent. No kernel asks for
a VMEM limit of its own (``ops/selective_scan.py`` has the account of what
that does to the program around it). One ``jax.custom_vjp`` carries the rule;
each of its two walks is ``jax.lax.platform_dependent`` beside the ``lax``
form of the same signature, which is what the CPU, a length under one chunk
and heads of 8 channels run and what ``tests/test_delta_rule.py`` holds the
kernels to; :func:`walk_in_vmem` says which form a call takes.

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
    """One position: ``q_t, k_t [B, H, K]``, ``g_t [B, H, K]`` (a decay a
    channel) or ``[B, H]`` (one a head), ``v_t [B, H, V]``, ``beta_t [B,
    H]``, ``state [B, H, K, V]`` float32 -> ``(o_t [B, H, V] float32, new
    state)``."""
    f32 = jnp.float32
    q_t, k_t, v_t = q_t.astype(f32), k_t.astype(f32), v_t.astype(f32)
    if g_t.ndim == q_t.ndim - 1:
        g_t = g_t[..., None]
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


def _kernel_takes(L: int, K: int, V: int | None = None) -> bool:
    """Whether the kernels take chunks of ``L`` positions of ``K`` channels
    (and, the walks, ``V`` values): whole chunks (their four blocks of rows
    are unrolled) of heads that fill the lanes."""
    return L == CHUNK and K % 128 == 0 and (V is None or V % 128 == 0)


def _lowered_for_tpu():
    return jax.lax.platform_dependent(
        tpu=lambda: jnp.float32(1.0), default=lambda: jnp.float32(0.0)
    )


def gram_in_vmem(q):
    """1.0 where :func:`delta_rule` of ``q [B, T, H, K]`` forms its chunks'
    Gram matrices in the kernel, 0.0 where in the ``lax`` form: a float32
    scalar, settled when the program is lowered for its device."""
    if not _kernel_takes(_chunk_len(q.shape[1]), q.shape[3]):
        return jnp.float32(0.0)
    return _lowered_for_tpu()


def walk_in_vmem(q, v):
    """1.0 where :func:`delta_rule` of ``q [B, T, H, K]``, ``v [B, T, H, V]``
    walks its chunks in the kernels, the matrix state in VMEM, 0.0 where in
    the ``lax`` scans: a float32 scalar, settled as :func:`gram_in_vmem`'s."""
    if not _kernel_takes(_chunk_len(q.shape[1]), q.shape[3], v.shape[3]):
        return jnp.float32(0.0)
    return _lowered_for_tpu()


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


# -- a segment's chunks in turn: the ``lax`` form ------------------------------

def _walk_lax(xs, state):
    """The forward walk as a ``lax`` scan of :func:`_chunk`: ``xs``
    chunk-major ``[N, B, H, L, .]`` from ``state [B, H, K, V]`` -> ``(o [N, B,
    H, L, V], final state, the state each chunk started from [N, B, H, K,
    V])``. What :func:`_walk` is tested against, and what runs where the
    kernels do not."""
    def outer(s, xs_n):
        s_next, o_n = _chunk(s, xs_n)
        return s_next, (o_n, s)

    final, (o, starts) = jax.lax.scan(outer, state, xs)
    return o, final, starts


def _walk_back_lax(xs, starts, do, dfinal):
    """The reverse walk as a ``lax`` scan, :func:`_walk_back`'s signature: a
    chunk again from its saved start by ``jax.vjp`` of the chunk's own
    forward -> ``(dxs, dstate)``."""
    def outer(ds, inp):
        xs_n, s_n, do_n = inp
        _, vjp = jax.vjp(_chunk, s_n, xs_n)
        return vjp((ds, do_n))

    dstate, dxs = jax.lax.scan(outer, dfinal, (xs, starts, do), reverse=True)
    return dxs, dstate


# -- the same walks, the state in VMEM -----------------------------------------

# heads a program of a walk: a chunk step is a chain of small products and
# of row substitutions, and the chains of a program's heads are independent,
# so the scheduler overlaps them (on the v5e at [8, 1024, 32, 128] a forward
# walk is 8.0 ms and a reverse one 15.2 at four heads, 7.7 and 14.8 at eight;
# the reverse walk's blocks and scratch of eight are 10 MB of the default 16
# MiB)
HEADS = 8


def _mm(a, b, dims, cd):
    """A product of the matrix unit, operands in ``cd``, float32 out."""
    return jax.lax.dot_general(
        a.astype(cd), b.astype(cd), (dims, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _mm32(a, b, dims):
    """A float32 product in float32's arithmetic (the solve's)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


# contractions of two matrices: a b, a b^T, a^T b
_AB, _ABT, _ATB = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _eye(n):
    return (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    ).astype(jnp.float32)


def _strictly_lower(n):
    return (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        > jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )


def _unit_lower_inverse(lower):
    """``(I + lower)^-1`` of a strictly lower-triangular ``[L, L]`` float32
    matrix, ``L`` a power of two of blocks of :data:`SUB` rows, in float32
    throughout. The diagonal blocks by row substitution on the vector unit,
    all of them at once (a row is final once the rows above it have been
    taken off it: ``SUB - 1`` dependent steps); then the blocks under the
    diagonal by ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``
    over pairs of blocks of twice the size each time, two float32 products
    of ``L / 2`` rows a doubling (ten products of ``L`` rows, the inverse as
    ``(I - N)(I + N^2) .. (I + N^32)``, were 5.0 ms of a walk's 11.1 on the
    v5e where this is 2.5). Applied to a right-hand side, and transposed to
    a cotangent, it is the solve."""
    L = lower.shape[0]
    index = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (L, L), axis)  # noqa: E731
    ours = jnp.concatenate([
        lower[at:at + SUB, at:at + SUB] for at in range(0, L, SUB)
    ])                                                          # [L, SUB]
    inverse = _eye(L)
    for s in range(SUB - 1):
        final = jnp.concatenate([
            jnp.broadcast_to(inverse[at + s:at + s + 1], (SUB, L))
            for at in range(0, L, SUB)
        ])
        inverse = inverse - ours[:, s:s + 1] * final
    size = SUB
    while size < L:
        pairs = range(0, L, 2 * size)
        # a pair's second block gains -B^-1 C A^-1 under the first
        second = jnp.concatenate(
            [inverse[at + size:at + 2 * size] for at in pairs]
        )
        under = jnp.where(
            index(1) // size == index(0) // size - 1, lower, 0.0
        )
        gain = _mm32(_mm32(second, under, _AB), inverse, _AB)
        inverse = jnp.concatenate([
            piece for i, at in enumerate(pairs) for piece in (
                inverse[at:at + size],
                inverse[at + size:at + 2 * size] - gain[i * size:(i + 1) * size],
            )
        ])
        size *= 2
    return inverse


def _column(row):
    """``[1, n]`` -> ``[n, 1]`` through the diagonal (a transpose of one
    row is not worth the transpose unit's set-up)."""
    return (_eye(row.shape[1]) * row).sum(1, keepdims=True)


def _row(column):
    """``[n, 1]`` -> ``[1, n]``."""
    return (_eye(column.shape[0]) * column).sum(0, keepdims=True)


def _chunk_again(q, k, G, v, beta, gram, state, cd):
    """A head's chunk from the state it starts from, :func:`_chunk`'s lines
    after ``decayed_gram`` on ``[L, .]`` tiles: what the forward writes and
    the reverse walk forms again. ``beta [L, 1]``; ``rows [2 L, K]`` are ``q``
    and ``k`` decayed since the chunk's start, the one operand of the two
    products with the state."""
    f32 = jnp.float32
    L = q.shape[0]
    since = jnp.exp(G)                      # decay since the chunk's start
    to_end = jnp.exp(G[L - 1:L] - G)        # each position's decay to the end
    rows = jnp.concatenate([q * since, k * since])
    both = _mm(rows, state, _AB, cd)
    from_state, held = both[:L], v.astype(f32) - both[L:]
    k_on_k = jnp.where(_strictly_lower(L), gram[:, L:], 0.0)
    inverse = _unit_lower_inverse(beta * k_on_k)
    u = _mm32(inverse, beta * held, _AB)
    return since, to_end, rows, from_state, held, k_on_k, inverse, u


def _fwd_kernel(
    q_ref, k_ref, G_ref, v_ref, beta_ref, gram_ref, s0_ref,
    o_ref, starts_ref, final_ref, state, *, cd,
):
    """A block of heads' chunk: ``q, k, G [L, K]``, ``v [L, V]``, ``beta [1,
    L]`` and the Gram pair ``[L, 2 L]`` a head -> ``o [L, V]`` and the state
    the chunk started from; the states ``[K, V]`` stay in ``state`` from a
    row's first chunk to its last."""
    n = pl.program_id(2)
    L = q_ref.shape[1]

    @pl.when(n == 0)
    def _():
        state[...] = s0_ref[...]

    starts_ref[...] = state[...]
    for h in range(q_ref.shape[0]):
        k, gram, start = k_ref[h], gram_ref[h], state[h]
        since, to_end, _, from_state, _, _, _, u = _chunk_again(
            q_ref[h], k, G_ref[h], v_ref[h], _column(beta_ref[h]), gram,
            start, cd,
        )
        o_ref[h] = from_state + _mm(gram[:, :L], u, _AB, cd)
        state[h] = _column(since[L - 1:L]) * start + _mm(
            k * to_end, u, _ATB, cd
        )

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(
    q_ref, k_ref, G_ref, v_ref, beta_ref, gram_ref, starts_ref, do_ref,
    dfinal_ref, dq_ref, dk_ref, dG_ref, dv_ref, dbeta_ref, dgram_ref,
    dstate_ref, ds, *, cd,
):
    """A block of heads' chunk, the chunks coming last to first: a chunk's
    ``u`` again from its saved start, then the five products' transposes and
    the solve's (``d rhs = M^-T du``, ``dM = -tril(d rhs u^T, -1)``); the
    states' cotangents ``[K, V]`` stay in ``ds`` from a row's last chunk to
    its first."""
    f32 = jnp.float32
    n = pl.program_id(2)
    L = q_ref.shape[1]
    last_row = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape[1:], 0) == L - 1

    @pl.when(n == 0)
    def _():
        ds[...] = dfinal_ref[...]

    for h in range(q_ref.shape[0]):
        q, k, gram, start = q_ref[h], k_ref[h], gram_ref[h], starts_ref[h]
        beta = _column(beta_ref[h])
        since, to_end, rows, _, held, k_on_k, inverse, u = _chunk_again(
            q, k, G_ref[h], v_ref[h], beta, gram, start, cd
        )
        d_next, do = ds[h], do_ref[h]
        # the state's update and the output, back to u and their operands
        d_k_to_end = _mm(u, d_next, _ABT, cd)
        du = _mm(k * to_end, d_next, _AB, cd) + _mm(gram[:, :L], do, _ATB, cd)
        d_q_on_k = _mm(do, u, _ABT, cd)
        # the solve, transposed
        d_rhs = _mm32(inverse, du, _ATB)
        d_system = jnp.where(_strictly_lower(L), -_mm32(d_rhs, u, _ABT), 0.0)
        d_held = beta * d_rhs
        # the two products with the chunk's start, back
        d_out = jnp.concatenate([do, -d_held])
        d_rows = _mm(d_out, start, _ABT, cd)
        d_start = _mm(rows, d_out, _ATB, cd)
        moved = d_rows * rows               # what reaches G through `since`
        left = d_k_to_end * k * to_end      # and through `to_end`
        at_end = left.sum(0, keepdims=True) + since[L - 1:L] * _row(
            (start * d_next).sum(1, keepdims=True)
        )
        dq_ref[h] = d_rows[:L] * since
        dk_ref[h] = d_rows[L:] * since + d_k_to_end * to_end
        dG_ref[h] = moved[:L] + moved[L:] - left + jnp.where(
            last_row, at_end, 0.0
        )
        dv_ref[h] = d_held.astype(dv_ref.dtype)
        dbeta_ref[h] = _row(
            (d_system * k_on_k).sum(1, keepdims=True)
            + (d_rhs * held).sum(1, keepdims=True)
        )
        dgram_ref[h] = jnp.concatenate([d_q_on_k, beta * d_system], axis=1)
        ds[h] = _column(since[L - 1:L]) * d_next + d_start

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        dstate_ref[...] = ds[...]


def _walk_call(kernel, name, cd, interpret, back, ins, rows, outs, row_outs):
    """``kernel`` over the grid (batch row, block of heads, chunk) of
    chunk-major ``[N, B, H, L, .]`` arrays ``ins`` and ``outs`` and of ``[B,
    H, K, V]`` arrays ``rows`` and ``row_outs`` (a row's states and their
    cotangents; one such scratch besides), a row's chunks in turn, last to
    first where ``back``; ``name`` is what a device trace calls it."""
    N, B, H = ins[0].shape[:3]
    heads = next(h for h in (HEADS, 4, 2, 1) if H % h == 0)
    at = (lambda n: N - 1 - n) if back else (lambda n: n)
    chunk = lambda x: pl.BlockSpec(   # noqa: E731
        (None, None, heads) + x.shape[3:], lambda b, h, n: (at(n), b, h, 0, 0)
    )
    row = lambda x: pl.BlockSpec(   # noqa: E731
        (None, heads) + x.shape[2:], lambda b, h, n: (b, h, 0, 0)
    )
    return pl.pallas_call(
        functools.partial(kernel, cd=cd),
        grid=(B, H // heads, N),
        in_specs=[chunk(x) for x in ins] + [row(x) for x in rows],
        out_specs=tuple(chunk(x) for x in outs) + tuple(row(x) for x in row_outs),
        out_shape=tuple(outs) + tuple(row_outs),
        scratch_shapes=[pltpu.VMEM((heads,) + rows[0].shape[2:], jnp.float32)],
        # no vmem_limit_bytes: one on any Pallas call re-tiles the fusions of
        # the whole program it sits in (ops/selective_scan.py has the account)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
    )(*ins, *rows)


def _running_sum(x, reverse=False):
    """``x [..., L, K]`` float32 -> its running sum over ``L`` (from the end
    where ``reverse``) as a float32 product with a triangle of ones: XLA's
    ``cumsum`` is a window reduction there, 2.0 ms over a walk's ``[16, 8, 32,
    64, 128]`` on the v5e where the array passes through HBM in 0.33."""
    L = x.shape[-2]
    ones = jnp.tril(jnp.ones((L, L), jnp.float32))
    return jnp.einsum(
        "st,...tk->...sk", ones.T if reverse else ones, x,
        precision=jax.lax.Precision.HIGHEST,
    )


def _gram_inputs(xs):
    """``xs`` -> ``(q, k, G, beta)`` float32, ``G`` the chunks' running sums
    of ``g``, ``beta [N, B, H, 1, L]`` a row a head."""
    q, k, g, beta = (xs[i].astype(jnp.float32) for i in (0, 1, 3, 4))
    return q, k, _running_sum(g), beta[:, :, :, None]


def _all_chunks(gram, *args, **static):
    """A Gram kernel over every chunk of a walk at once: the chunks folded
    into its grid's batch rows."""
    fold = lambda x: x.reshape((-1,) + x.shape[2:])   # noqa: E731
    out = gram(*(fold(x) for x in args), **static)
    return jax.tree.map(lambda x: x.reshape(args[0].shape[:2] + x.shape[1:]), out)


# jitted as the Gram kernels' entry points are, and for the same reason
@functools.partial(jax.jit, static_argnames=("interpret",))
def _walk(xs, state, interpret=False):
    """The forward walk in VMEM, :func:`_walk_lax`'s signature: the Gram
    pairs of all the chunks in one call of ``decayed_gram``, then
    ``delta_chunk_fwd``."""
    f32, cd = jnp.float32, xs[2].dtype
    q, k, G, beta = _gram_inputs(xs)
    gram = _all_chunks(_gram_pallas, q, k, G, cd=cd, interpret=interpret)
    o = jax.ShapeDtypeStruct(xs[2].shape, f32)
    starts = jax.ShapeDtypeStruct(q.shape[:1] + state.shape, f32)
    final = jax.ShapeDtypeStruct(state.shape, f32)
    o, starts, final = _walk_call(
        _fwd_kernel, "delta_chunk_fwd", cd, interpret, False,
        (q, k, G, xs[2], beta, gram), (state,), (o, starts), (final,),
    )
    return o, final, starts


@functools.partial(jax.jit, static_argnames=("interpret",))
def _walk_back(xs, starts, do, dfinal, interpret=False):
    """The reverse walk in VMEM, :func:`_walk_back_lax`'s signature: the Gram
    pairs again (a temporary: nothing of their size is a residual),
    ``delta_chunk_bwd``, then ``decayed_gram_bwd`` once on the pairs'
    cotangent."""
    f32, cd = jnp.float32, xs[2].dtype
    q, k, G, beta = _gram_inputs(xs)
    gram = _all_chunks(_gram_pallas, q, k, G, cd=cd, interpret=interpret)
    like = lambda x, dtype=f32: jax.ShapeDtypeStruct(x.shape, dtype)   # noqa: E731
    dq, dk, dG, dv, dbeta, dgram, dstate = _walk_call(
        _bwd_kernel, "delta_chunk_bwd", cd, interpret, True,
        (q, k, G, xs[2], beta, gram, starts, do), (dfinal,),
        (like(q), like(k), like(G), like(xs[2], cd), like(beta), like(gram)),
        (like(dfinal),),
    )
    through = _all_chunks(
        _gram_pallas_bwd, q, k, G, dgram, cd=cd, interpret=interpret
    )
    dq, dk, dG = (a + b for a, b in zip((dq, dk, dG), through))
    # G is g's running sum, so g gathers what reaches G from its position on
    dg = _running_sum(dG, reverse=True)
    dxs = (dq, dk, dv, dg, dbeta[:, :, :, 0])
    return tuple(d.astype(x.dtype) for d, x in zip(dxs, xs)), dstate


# -- one rule, two forms -------------------------------------------------------

def _where_lowered(kernel, lax_form, xs, *args):
    """``kernel`` where the shapes ask for it and the program is lowered for
    a TPU, else ``lax_form``."""
    if not _kernel_takes(*xs[0].shape[3:], xs[2].shape[4]):
        return lax_form(xs, *args)
    return jax.lax.platform_dependent(
        xs, *args, tpu=kernel, default=lax_form
    )


@jax.custom_vjp
def _chunked(xs, state):
    """``xs`` chunk-major ``[N, B, H, L, .]`` -> ``(o [N, B, H, L, V], final
    state)``."""
    return _chunked_fwd(xs, state)[0]


def _chunked_fwd(xs, state):
    o, final, starts = _where_lowered(_walk, _walk_lax, xs, state)
    return (o, final), (xs, starts)


def _chunked_bwd(res, cts):
    return _where_lowered(_walk_back, _walk_back_lax, *res, *cts)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def delta_rule(q, k, v, g, beta, state=None):
    """``q, k [B, T, H, K]``, ``g`` the log-decay, <= 0, ``[B, T, H, K]`` a
    channel or ``[B, T, H]`` a head (module docstring), ``v [B, T, H, V]``,
    ``beta [B, T, H]``, ``state [B, H, K, V]`` float32 (zeros when ``None``)
    -> ``(o [B, T, H, V] float32, final state [B, H, K, V] float32)``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    if g.ndim == q.ndim - 1:
        g = jnp.broadcast_to(g[..., None], q.shape)
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
