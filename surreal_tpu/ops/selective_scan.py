"""A selective (input-dependent) state-space scan with its own backward.

The recurrence of a Mamba-1 layer (Gu & Dao 2023, arXiv:2312.00752,
algorithm 2), per batch row, channel ``c`` and state index ``n``:

    s_t[n, c] = exp(delta_t[c] A[n, c]) s_{t-1}[n, c] + delta_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n s_t[n, c] C_t[n] + D[c] u_t[c]

``A`` is negative, ``delta`` positive, so every decay lies in (0, 1). The
state is held ``[B, N, C]``: the channels on the lanes, the ``N`` state
indices on the sublanes, float32 throughout (``exp``, the state, the
accumulation over ``n``); the inputs may be bfloat16 and are raised a step
at a time.

**Why a backward of its own.** Differentiated as it stands, a scan over
``T`` positions keeps every state: ``[T, B, N, C]`` float32 is 2.7 GB a
tensor at 8 x 1024 positions of 5120 channels, and autodiff keeps two. So
the scan runs in chunks of :data:`CHUNK` positions: sequential over the
chunks, carrying the state, and inside a chunk a plain ``lax.scan``. The
forward keeps the state each chunk started from (``T / CHUNK`` of them:
84 MB there) and nothing else of the states. The backward walks the chunks
last to first, recomputes one chunk's states from its saved start
(``jax.vjp`` of the chunk's own forward: ``CHUNK`` states live at a time)
and hands the state's cotangent on to the chunk before.

A length that is no multiple of the chunk is padded at its end with
``delta = 0``: the decay is then 1 and the input 0, so the state passes
through the padding untouched and the padded outputs are cut off.

:func:`selective_step` is one position of the same recurrence: what an
acting step runs against the state it carries, so ``k`` steps equal a scan
over ``k`` positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions a chunk: the backward holds this many states ([CHUNK, B, N, C]
# float32, twice) and the forward saves T / CHUNK of them
CHUNK = 32
# positions a trip of the inner loop
_UNROLL = 4


def selective_step(u_t, delta_t, A, b_t, c_t, D, state):
    """One position: ``u_t, delta_t [B, C]``, ``A [N, C]``, ``b_t, c_t
    [B, N]``, ``D [C]``, ``state [B, N, C]`` float32 -> ``(y_t [B, C]
    float32, new state)``."""
    f32 = jnp.float32
    u_t, delta_t = u_t.astype(f32), delta_t.astype(f32)
    decay = jnp.exp(delta_t[:, None, :] * A[None].astype(f32))
    drive = (delta_t * u_t)[:, None, :] * b_t.astype(f32)[:, :, None]
    state = decay * state + drive
    y = (state * c_t.astype(f32)[:, :, None]).sum(1) + D.astype(f32) * u_t
    return y, state


def _chunk(A, D, state, xs):
    """A chunk's positions in turn: ``xs`` time-major ``[L, B, .]`` ->
    ``(state after, y [L, B, C])``."""
    def step(s, x):
        u_t, delta_t, b_t, c_t = x
        y, s = selective_step(u_t, delta_t, A, b_t, c_t, D, s)
        return s, y

    return jax.lax.scan(step, state, xs, unroll=min(_UNROLL, xs[0].shape[0]))


@jax.custom_vjp
def _chunked(xs, A, D, state):
    """``xs`` chunked and time-major ``[K, L, B, .]`` -> ``(y [K, L, B,
    C], final state)``."""
    return _chunked_fwd(xs, A, D, state)[0]


def _chunked_fwd(xs, A, D, state):
    def outer(s, xs_k):
        s_next, y_k = _chunk(A, D, s, xs_k)
        return s_next, (y_k, s)

    final, (y, starts) = jax.lax.scan(outer, state, xs)
    return (y, final), (xs, A, D, starts)


def _chunked_bwd(res, cts):
    xs, A, D, starts = res
    dy, dfinal = cts

    def outer(carry, inp):
        ds, dA, dD = carry
        xs_k, s_k, dy_k = inp
        _, vjp = jax.vjp(_chunk, A, D, s_k, xs_k)
        gA, gD, gs, gxs = vjp((ds, dy_k))
        return (gs, dA + gA, dD + gD), gxs

    (dstate, dA, dD), dxs = jax.lax.scan(
        outer, (dfinal, jnp.zeros_like(A), jnp.zeros_like(D)),
        (xs, starts, dy), reverse=True,
    )
    return dxs, dA, dD, dstate


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def selective_scan(u, delta, A, Bm, Cm, D, state=None, chunk: int = CHUNK):
    """``u, delta [B, T, C]``, ``A [N, C]``, ``Bm, Cm [B, T, N]``, ``D
    [C]``, ``state [B, N, C]`` float32 (zeros when ``None``) -> ``(y [B, T,
    C] float32, final state [B, N, C] float32)``."""
    B, T, C = u.shape
    N = A.shape[0]
    if state is None:
        state = jnp.zeros((B, N, C), jnp.float32)
    L = min(int(chunk), T)
    pad = (-T) % L

    def chunked(x):
        # [B, T, .] -> [K, L, B, .], zeros after the end
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.swapaxes(0, 1).reshape((T + pad) // L, L, B, x.shape[-1])

    y, final = _chunked(
        tuple(chunked(x) for x in (u, delta, Bm, Cm)),
        A.astype(jnp.float32), D.astype(jnp.float32),
        state.astype(jnp.float32),
    )
    return y.reshape(T + pad, B, C)[:T].swapaxes(0, 1), final
