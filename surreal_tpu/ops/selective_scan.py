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
the scan runs in chunks: sequential over the chunks, carrying the state. The
forward keeps the state each chunk started from (``T / chunk`` of them) and
nothing else of the states. The backward walks the chunks last to first,
recomputes one chunk's states from its saved start and hands the state's
cotangent on to the chunk before. One ``jax.custom_vjp`` (:func:`_scan`)
carries the pair, and each of its two walks has two forms.

**The ``lax`` form** (:func:`_walk_lax`, :func:`_walk_back_lax`): a
``lax.scan`` over the chunks and inside a chunk a plain ``lax.scan`` over its
positions, in the backward under ``jax.vjp`` (a chunk's states live at a
time). The carried state goes through HBM every trip: at ``[8, 16, 5120]``
a walk of 1024 positions is bound by its trips (alone on a v5e 4.6 ms
forward and 38 ms back, the chunks' forward again included).

**The kernels** (:func:`_walk`, :func:`_walk_back`): Pallas, a grid of
(batch row, chunk) with the chunks in turn. What they keep where:

- in **VMEM**, from a row's first chunk to its last: the state ``[N, C]``
  (the reverse walk: its cotangent). A chunk's ``u, delta [L, C]`` and ``B, C
  [N, L]`` come in, ``y [L, C]`` goes out, and the walk takes the channels
  :data:`LANE_BLOCK` at a time with that block's ``[N, LANE_BLOCK]`` state in
  registers across the chunk's positions, a position the arithmetic of
  :func:`selective_step`. The reverse walk first forms the block's states
  and decays of the chunk again (``[L + 1, N, LANE_BLOCK]`` scratch), then
  takes the positions last to first.
- in **HBM**: the state each chunk started from (``[T / L, B, N, C]``: 84 MB
  at ``L`` = :data:`KERNEL_CHUNK` there), written by the forward walk and read
  by the reverse one, and the final state.
- ``dA``, ``dD`` add up in VMEM over a row's chunks (the rows summed
  outside); ``dB_t[n]``, ``dC_t[n]``, sums over the channels, add up lane by
  lane over the blocks and are summed along the lanes once a chunk, on the
  matrix unit.

No ``[T, B, N, C]`` tensor exists in either form. At ``[8, 1024, 5120]``, ``N``
= 16, alone on a v5e a forward walk takes 2.6 ms and a reverse walk 5.4 ms:
30 and 62 cycles a position and lane block, some 80 and 210 vector
operations on 8 registers of state, so the vector unit binds (the bytes in
and out are 0.3 and 0.7 ms at the HBM peak). Chunks of 64 or 128 and lane
blocks of 256 or 1024 read the same to 5%.

**The kernels ask for no VMEM limit of their own**, so a chunk's blocks and
scratch have to fit the compiler's default (16 MiB; the reverse walk takes
13 at :data:`KERNEL_CHUNK`). A ``vmem_limit_bytes`` on one Pallas call gives
every fusion of the program it sits in a scoped-memory configuration, and
XLA then tiles other products of that program differently: a fused PPO
iteration's acting loop no longer repeated the rollout of a program of its
own bit for bit (PERF.md section 6, PR 58).

**How a call chooses.** From the device and the shapes and from nothing
else: where the channels fill the lanes (``C % 128 == 0``) and the state
indices the sublanes (``N % 8 == 0``), each walk is
``jax.lax.platform_dependent``: the kernel where the program is lowered for a
TPU (a compile for a described TPU in a CPU process included), the ``lax``
form on any other device, in chunks of the kernels' length there too.
Any other shape runs the ``lax`` form in chunks of :data:`CHUNK`.
:func:`scan_in_vmem` says which form a call takes.

A length that is no multiple of the chunk is padded at its end with
``delta = 0``: the decay is then 1 and the input 0, so the state passes
through the padding untouched and the padded outputs are cut off.

:func:`selective_step` is one position of the same recurrence: what an
acting step runs against the state it carries, so ``k`` steps equal a scan
over ``k`` positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a chunk of the ``lax`` form: its backward holds this many states
# ([CHUNK, B, N, C] float32, twice) and the forward saves T / CHUNK of them
CHUNK = 32
# positions a trip of the ``lax`` form's inner loop
_UNROLL = 4
# positions a chunk of the kernels, one grid step: the reverse walk holds a
# lane block's states and decays of a chunk in VMEM ([KERNEL_CHUNK + 1, N,
# LANE_BLOCK] float32, twice: 2 MB at N = 16) beside two buffers of every
# block, 13 MB in all at 5120 channels, inside the compiler's default limit
# (module docstring: the kernels must not ask for their own)
KERNEL_CHUNK = 32
# channels a walk carries in registers at a time ([N, LANE_BLOCK] float32: 8
# of the 64 vector registers at N = 16)
LANE_BLOCK = 512
# positions unrolled in a trip of a kernel's loop: the sublanes of a float32
# tile, so a trip assembles and stores whole tiles of its outputs
_ROWS = 8
_LANES = 128


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


# -- the ``lax`` form ----------------------------------------------------------

def _chunk(A, D, state, xs):
    """A chunk's positions in turn: ``xs`` time-major ``[L, B, .]`` ->
    ``(state after, y [L, B, C])``."""
    def step(s, x):
        u_t, delta_t, b_t, c_t = x
        y, s = selective_step(u_t, delta_t, A, b_t, c_t, D, s)
        return s, y

    return jax.lax.scan(step, state, xs, unroll=min(_UNROLL, xs[0].shape[0]))


def _time_major(x, L):
    """``[B, T, .]`` -> chunked and time-major ``[K, L, B, .]``."""
    B, T = x.shape[:2]
    return x.swapaxes(0, 1).reshape(T // L, L, B, x.shape[-1])


def _batch_major(x):
    """``[K, L, B, .]`` -> ``[B, T, .]``."""
    K, L, B = x.shape[:3]
    return x.reshape(K * L, B, x.shape[-1]).swapaxes(0, 1)


def _walk_lax(u, delta, A, Bm, Cm, D, state, L):
    """The forward walk as ``lax`` loops, :func:`_walk`'s signature."""
    def outer(s, xs_k):
        s_next, y_k = _chunk(A, D, s, xs_k)
        return s_next, (y_k, s)

    xs = tuple(_time_major(x, L) for x in (u, delta, Bm, Cm))
    final, (y, starts) = jax.lax.scan(outer, state, xs)
    return _batch_major(y), final, starts


def _walk_back_lax(u, delta, A, Bm, Cm, D, starts, dy, dfinal, L):
    """The reverse walk as ``lax`` loops, :func:`_walk_back`'s signature: a
    chunk's states again by ``jax.vjp`` of the chunk's own forward."""
    def outer(carry, inp):
        ds, dA, dD = carry
        xs_k, s_k, dy_k = inp
        _, vjp = jax.vjp(_chunk, A, D, s_k, xs_k)
        gA, gD, gs, gxs = vjp((ds, dy_k))
        return (gs, dA + gA, dD + gD), gxs

    xs = tuple(_time_major(x, L) for x in (u, delta, Bm, Cm))
    (dstate, dA, dD), dxs = jax.lax.scan(
        outer, (dfinal, jnp.zeros_like(A), jnp.zeros_like(D)),
        (xs, starts, _time_major(dy, L)), reverse=True,
    )
    du, ddelta, dB, dC = (_batch_major(x) for x in dxs)
    return du, ddelta, dA, dB, dC, dD, dstate


# -- the kernels ---------------------------------------------------------------

def _lane_block(C: int) -> int:
    """Channels of ``C`` a walk carries in registers at a time."""
    return next(w for w in (LANE_BLOCK, 256, _LANES) if C % w == 0)


def _spread(cols_ref, out_ref):
    """``cols [N, L]``, a position a column -> ``out [L * N, 128]``: each
    position's column along all the lanes, once a chunk for every lane block
    of it."""
    N, L = cols_ref.shape
    for t in range(L):
        out_ref[t * N:(t + 1) * N, :] = jnp.broadcast_to(
            cols_ref[:, t:t + 1], (N, _LANES)
        )


def _rows(t, N):
    """Position ``t``'s ``N`` rows of a ``[L * N, 128]`` scratch."""
    return pl.ds(pl.multiple_of(t * N, N), N)


def _column(spread_ref, t, N, W):
    """Position ``t``'s column out of :func:`_spread`'s scratch, side by side
    up to ``[N, W]``."""
    return jnp.concatenate([spread_ref[_rows(t, N), :]] * (W // _LANES), axis=1)


def _fold(x):
    """``[N, W]`` -> ``[N, 128]``: the lane tiles summed."""
    out = x[:, :_LANES]
    for i in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, i:i + _LANES]
    return out


def _over_n(x):
    """``[N, W]`` -> ``[8, W]``, every row the sum over ``n``: the sublane
    tiles added, then three rotations inside a tile."""
    q = x[:_ROWS]
    for i in range(_ROWS, x.shape[0], _ROWS):
        q = q + x[i:i + _ROWS]
    for shift in (4, 2, 1):
        q = q + pltpu.roll(q, shift, 0)
    return q


def _stage(u_ref, dl_ref, lanes, dls, xs, us):
    """A lane block of the chunk's ``delta`` and ``u`` raised to float32, and
    their product, in scratch: a position is then a one-row load."""
    f32 = jnp.float32
    dl, u = dl_ref[:, lanes].astype(f32), u_ref[:, lanes].astype(f32)
    dls[...] = dl
    us[...] = u
    xs[...] = dl * u


def _fwd_kernel(
    u_ref, dl_ref, b_ref, c_ref, A_ref, D_ref, s0_ref,
    y_ref, starts_ref, final_ref, state, bb, cb, dls, xs, us, *, W,
):
    """One batch row's chunk: ``u, delta [L, C]``, ``b, c [N, L]`` -> ``y
    [L, C]`` and the state the chunk started from; the state ``[N, C]``
    stays in ``state`` from the row's first chunk to its last."""
    f32 = jnp.float32
    L, C = u_ref.shape
    N = A_ref.shape[0]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        state[...] = s0_ref[...]

    starts_ref[...] = state[...]
    _spread(b_ref, bb)
    _spread(c_ref, cb)
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, W), 0)

    def lane_block(j, _):
        lanes = pl.ds(pl.multiple_of(j * W, W), W)
        A, D = A_ref[:, lanes], D_ref[:, lanes]
        _stage(u_ref, dl_ref, lanes, dls, xs, us)

        def trip(g, s):
            at = pl.multiple_of(g * _ROWS, _ROWS)
            y = jnp.zeros((_ROWS, W), f32)
            for i in range(_ROWS):
                t = at + i
                decay = jnp.exp(dls[pl.ds(t, 1), :] * A)
                s = decay * s + xs[pl.ds(t, 1), :] * _column(bb, t, N, W)
                y = jnp.where(row == i, _over_n(s * _column(cb, t, N, W)), y)
            here = pl.ds(at, _ROWS)
            y_ref[here, lanes] = y + D * us[here, :]
            return s

        state[:, lanes] = jax.lax.fori_loop(
            0, L // _ROWS, trip, state[:, lanes]
        )
        return 0

    jax.lax.fori_loop(0, C // W, lane_block, 0)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(
    u_ref, dl_ref, b_ref, c_ref, A_ref, D_ref, starts_ref, dy_ref, dfinal_ref,
    du_ref, ddl_ref, dbc_ref, dA_ref, dD_ref, ds0_ref,
    ds, bb, cb, dls, xs, us, states, decays, db, dc, *, W,
):
    """One batch row's chunk, the chunks coming last to first: a lane block's
    states and decays of the chunk again from its saved start (``states [L +
    1, N, W]``, ``decays [L, N, W]``), then its positions last to first; the
    state's cotangent ``[N, C]`` stays in ``ds`` from the row's last chunk to
    its first. ``dA`` and ``dD`` add up over the row's chunks in their output
    blocks; ``dB``, ``dC`` over the lane blocks, lane by lane, in ``db``,
    ``dc`` ``[L * N, 128]``."""
    f32 = jnp.float32
    L, C = u_ref.shape
    N = A_ref.shape[0]
    k = pl.program_id(1)
    trips = L // _ROWS

    @pl.when(k == 0)
    def _():
        ds[...] = dfinal_ref[...]
        dA_ref[...] = jnp.zeros_like(dA_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    _spread(b_ref, bb)
    _spread(c_ref, cb)
    db[...] = jnp.zeros_like(db)
    dc[...] = jnp.zeros_like(dc)
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, W), 0)

    def lane_block(j, _):
        lanes = pl.ds(pl.multiple_of(j * W, W), W)
        A, D = A_ref[:, lanes], D_ref[:, lanes]
        _stage(u_ref, dl_ref, lanes, dls, xs, us)

        def again(g, s):
            at = pl.multiple_of(g * _ROWS, _ROWS)
            for i in range(_ROWS):
                t = at + i
                states[t] = s
                decay = jnp.exp(dls[pl.ds(t, 1), :] * A)
                decays[t] = decay
                s = decay * s + xs[pl.ds(t, 1), :] * _column(bb, t, N, W)
            return s

        states[L] = jax.lax.fori_loop(0, trips, again, starts_ref[:, lanes])

        def trip(r, carry):
            g, dA = carry
            at = pl.multiple_of((trips - 1 - r) * _ROWS, _ROWS)
            into_x = jnp.zeros((_ROWS, W), f32)
            into_dl = jnp.zeros((_ROWS, W), f32)
            for i in reversed(range(_ROWS)):
                t = at + i
                dy_t = dy_ref[pl.ds(t, 1), lanes]
                g = g + dy_t * _column(cb, t, N, W)
                dc[_rows(t, N), :] += _fold(states[t + 1] * dy_t)
                db[_rows(t, N), :] += _fold(g * xs[pl.ds(t, 1), :])
                decay = decays[t]
                through = g * states[t] * decay     # what reaches delta A
                dA = dA + through * dls[pl.ds(t, 1), :]
                into_x = jnp.where(
                    row == i, _over_n(g * _column(bb, t, N, W)), into_x
                )
                into_dl = jnp.where(row == i, _over_n(through * A), into_dl)
                g = g * decay
            here = pl.ds(at, _ROWS)
            du_ref[here, lanes] = into_x * dls[here, :] + D * dy_ref[here, lanes]
            ddl_ref[here, lanes] = into_dl + into_x * us[here, :]
            return g, dA

        g, dA = jax.lax.fori_loop(
            0, trips, trip, (ds[:, lanes], jnp.zeros((N, W), f32))
        )
        ds[:, lanes] = g
        dA_ref[:, lanes] += dA
        dD_ref[:, lanes] += (dy_ref[:, lanes] * us[...]).sum(0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, C // W, lane_block, 0)
    # along the lanes on the matrix unit, which idles: a row of sums, the
    # positions and their n side by side
    ones = jnp.ones((_ROWS, _LANES), f32)
    for i, part in enumerate((db, dc)):
        dbc_ref[i] = jax.lax.dot_general(
            ones, part[...], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32,
        )

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = ds[...]


def _specs(K, L, N, C, back: bool):
    """Block specs over the grid ``(batch row, chunk)``; ``back`` takes the
    chunks last to first."""
    at = (lambda k: K - 1 - k) if back else (lambda k: k)
    return dict(
        seq=pl.BlockSpec((None, L, C), lambda b, k: (b, at(k), 0)),
        cols=pl.BlockSpec((None, None, N, L), lambda b, k: (b, at(k), 0, 0)),
        A=pl.BlockSpec((N, C), lambda b, k: (0, 0)),
        D=pl.BlockSpec((1, C), lambda b, k: (0, 0)),
        row=pl.BlockSpec((None, N, C), lambda b, k: (b, 0, 0)),
        row_D=pl.BlockSpec((None, 1, C), lambda b, k: (b, 0, 0)),
        starts=pl.BlockSpec((None, None, N, C), lambda b, k: (at(k), b, 0, 0)),
        sums=pl.BlockSpec(
            (None, None, 2, _ROWS, L * N), lambda b, k: (b, at(k), 0, 0, 0)
        ),
    )


def _columns(x, L):
    """``[B, T, N]`` -> float32 ``[B, K, N, L]``: a chunk's positions on the
    lanes, so that a position's column broadcasts along them."""
    B, T, N = x.shape
    return x.astype(jnp.float32).reshape(B, T // L, L, N).swapaxes(2, 3)


# the rows apart, a row's chunks in turn; no vmem_limit_bytes (module docstring)
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# jitted so that a program traces and lowers each kernel once a shape, not
# once a call site (ops/delta_rule.py::_gram_pallas has the measurement)
@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def _walk(u, delta, A, Bm, Cm, D, state, L, interpret=False):
    """The forward walk in VMEM: ``u, delta [B, T, C]``, ``Bm, Cm [B, T,
    N]``, ``T`` whole chunks of ``L``, ``A, D, state`` float32 -> ``(y [B, T,
    C] float32, final state, the state each chunk started from [T / L, B, N,
    C])``."""
    f32 = jnp.float32
    B, T, C = u.shape
    N, K, W = A.shape[0], T // L, _lane_block(C)
    s = _specs(K, L, N, C, back=False)
    y, starts, final = pl.pallas_call(
        functools.partial(_fwd_kernel, W=W),
        grid=(B, K),
        in_specs=[
            s["seq"], s["seq"], s["cols"], s["cols"], s["A"], s["D"], s["row"]
        ],
        out_specs=(s["seq"], s["starts"], s["row"]),
        out_shape=(
            jax.ShapeDtypeStruct((B, T, C), f32),
            jax.ShapeDtypeStruct((K, B, N, C), f32),
            jax.ShapeDtypeStruct((B, N, C), f32),
        ),
        scratch_shapes=[
            pltpu.VMEM((N, C), f32),
            pltpu.VMEM((L * N, _LANES), f32), pltpu.VMEM((L * N, _LANES), f32),
            pltpu.VMEM((L, W), f32), pltpu.VMEM((L, W), f32),
            pltpu.VMEM((L, W), f32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="selective_scan_fwd",
    )(u, delta, _columns(Bm, L), _columns(Cm, L), A, D[None], state)
    return y, final, starts


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def _walk_back(u, delta, A, Bm, Cm, D, starts, dy, dfinal, L, interpret=False):
    """The reverse walk in VMEM: :func:`_walk`'s inputs, the starts it kept
    and the cotangents ``dy [B, T, C]``, ``dfinal [B, N, C]`` float32 ->
    ``(du, ddelta [B, T, C], dA [N, C], dB, dC [B, T, N], dD [C], dstate [B,
    N, C])``, each in its input's dtype."""
    f32 = jnp.float32
    B, T, C = u.shape
    N, K, W = A.shape[0], T // L, _lane_block(C)
    s = _specs(K, L, N, C, back=True)
    du, ddelta, sums, dA, dD, dstate = pl.pallas_call(
        functools.partial(_bwd_kernel, W=W),
        grid=(B, K),
        in_specs=[
            s["seq"], s["seq"], s["cols"], s["cols"], s["A"], s["D"],
            s["starts"], s["seq"], s["row"],
        ],
        out_specs=(
            s["seq"], s["seq"], s["sums"], s["row"], s["row_D"], s["row"]
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, T, C), f32),
            jax.ShapeDtypeStruct((B, T, C), f32),
            jax.ShapeDtypeStruct((B, K, 2, _ROWS, L * N), f32),
            jax.ShapeDtypeStruct((B, N, C), f32),
            jax.ShapeDtypeStruct((B, 1, C), f32),
            jax.ShapeDtypeStruct((B, N, C), f32),
        ),
        scratch_shapes=[
            pltpu.VMEM((N, C), f32),
            pltpu.VMEM((L * N, _LANES), f32), pltpu.VMEM((L * N, _LANES), f32),
            pltpu.VMEM((L, W), f32), pltpu.VMEM((L, W), f32),
            pltpu.VMEM((L, W), f32),
            pltpu.VMEM((L + 1, N, W), f32), pltpu.VMEM((L, N, W), f32),
            pltpu.VMEM((L * N, _LANES), f32), pltpu.VMEM((L * N, _LANES), f32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="selective_scan_bwd",
    )(u, delta, _columns(Bm, L), _columns(Cm, L), A, D[None], starts, dy, dfinal)
    # a chunk's sums lie (position, n) along a row: [B, T, N] as they are
    dB, dC = (sums[:, :, i, 0].reshape(B, T, N) for i in range(2))
    return (
        du.astype(u.dtype), ddelta.astype(delta.dtype), dA.sum(0),
        dB.astype(Bm.dtype), dC.astype(Cm.dtype), dD.sum((0, 1)), dstate,
    )


# -- one scan, two forms -------------------------------------------------------

def _kernels_take(N: int, C: int) -> bool:
    """Whether the kernels take ``N`` state indices of ``C`` channels: whole
    sublane tiles of whole lane tiles."""
    return N % _ROWS == 0 and C % _LANES == 0


def _kernel_chunk(T: int) -> int:
    """Positions a chunk of the kernels at a length of ``T``: whole bfloat16
    tiles, :data:`KERNEL_CHUNK` at most."""
    return min(KERNEL_CHUNK, -(-T // 16) * 16)


def scan_in_vmem(u, A):
    """1.0 where :func:`selective_scan` of ``u [B, T, C]``, ``A [N, C]`` walks
    in the kernels, 0.0 where in the ``lax`` form: a float32 scalar, settled
    when the program is lowered for its device."""
    if not _kernels_take(A.shape[0], u.shape[-1]):
        return jnp.float32(0.0)
    return jax.lax.platform_dependent(
        tpu=lambda: jnp.float32(1.0), default=lambda: jnp.float32(0.0)
    )


def _where_lowered(kernels: bool, kernel, lax_form, L, *args):
    if not kernels:
        return lax_form(*args, L)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, L=L),
        default=functools.partial(lax_form, L=L),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(u, delta, A, Bm, Cm, D, state, L, kernels):
    """``u, delta [B, T, C]``, ``Bm, Cm [B, T, N]``, ``T`` whole chunks of
    ``L``, ``A, D, state`` float32 -> ``(y [B, T, C] float32, final
    state)``; ``kernels``: whether the shapes ask for them."""
    return _scan_fwd(u, delta, A, Bm, Cm, D, state, L, kernels)[0]


def _scan_fwd(u, delta, A, Bm, Cm, D, state, L, kernels):
    y, final, starts = _where_lowered(
        kernels, _walk, _walk_lax, L, u, delta, A, Bm, Cm, D, state
    )
    return (y, final), (u, delta, A, Bm, Cm, D, starts)


def _scan_bwd(L, kernels, res, cts):
    return _where_lowered(kernels, _walk_back, _walk_back_lax, L, *res, *cts)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, Bm, Cm, D, state=None, chunk: int = CHUNK):
    """``u, delta [B, T, C]``, ``A [N, C]``, ``Bm, Cm [B, T, N]``, ``D
    [C]``, ``state [B, N, C]`` float32 (zeros when ``None``) -> ``(y [B, T,
    C] float32, final state [B, N, C] float32)``. ``chunk`` is the ``lax``
    form's where the shapes do not ask for the kernels (module docstring)."""
    B, T, C = u.shape
    N = A.shape[0]
    if state is None:
        state = jnp.zeros((B, N, C), jnp.float32)
    kernels = _kernels_take(N, C)
    L = _kernel_chunk(T) if kernels else min(int(chunk), T)
    # zeros after the end
    u, delta, Bm, Cm = (
        jnp.pad(x, ((0, 0), (0, (-T) % L), (0, 0))) for x in (u, delta, Bm, Cm)
    )
    y, final = _scan(
        u, delta, A.astype(jnp.float32), Bm, Cm, D.astype(jnp.float32),
        state.astype(jnp.float32), L, kernels,
    )
    return y[:, :T], final
