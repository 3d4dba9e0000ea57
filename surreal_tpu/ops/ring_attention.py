"""Ring attention over a sequence-parallel mesh axis.

The reference has NO attention anywhere (SURVEY.md §2.4/§5.7 — its
"sequence" machinery is trajectory windowing), so there is nothing to
port; this module exists because long-context scaling is first-class in
the TPU rebuild's design: if a sequence model ever joins the policy stack
(trajectory transformers, attention critics over long horizons), the
sequence axis must be able to shard past one device's HBM. Ring attention
is the canonical recipe: each device holds one block of the sequence,
K/V blocks rotate around the ring via ``lax.ppermute`` (ICI
neighbor-to-neighbor traffic, no all-gather), and softmax is computed
ONLINE (flash-style running max/denominator) so the full [T, T] score
matrix never materializes on any device.

Layout: [B, T, H, D] (batch, time, heads, head dim). Inside
``shard_map``, T is the LOCAL block; global positions for causal masking
derive from ``lax.axis_index``. Compute runs in the input dtype (bf16 on
TPU hits the MXU); the online-softmax statistics are always f32 — running
max/denominator accumulate across the whole ring and drift in bf16.

Pallas note (SURVEY.md §2.3 kernel policy): within one ring block this is
plain XLA einsum; the cross-device ring is mesh communication, not kernel
work. The single-device learn passes are another matter:

**``blocked_attention``: a block of queries, two forms.** A trajectory
policy's learn pass attends a block of queries at a time over the keys the
block can see. One ``jax.custom_vjp`` (:func:`_attend`) carries a block, and
its forward and its backward each have two forms.

- **The ``lax`` form** (:func:`_fwd_lax`, :func:`_bwd_lax`): two XLA products
  around a float32 softmax. The ``[block, keys]`` float32 scores of every
  head are written to HBM, read for the softmax, written again in the
  inputs' dtype and read for the second product, and the backward does it
  all again: at ``[2, 4096]`` positions of 32 heads of 128 over 4 key-value
  heads, alone on a v5e, a layer's forward takes 20 ms and its gradient 27,
  bound by that traffic (537 MB of scores a block).
- **The kernels** (:func:`_fwd_kernels`, :func:`_bwd_kernels`: Pallas,
  ``blocked_attention_fwd`` and ``blocked_attention_bwd`` in a trace). The
  ``H / G`` query heads of a key-value head are the rows of one query tile, so
  a K and a V tile are read once a group. The forward, grid ``(batch row,
  group, query tile, key tile)``, walks a query tile's keys with the running
  max, the running denominator and the output accumulator in VMEM (online
  softmax) and writes the output and one float32 log-sum-exp a query and
  head. The backward, grid ``(batch row, group, key tile, query tile)``,
  forms each score tile again from ``q``, ``k`` and the log-sum-exp, adds up
  a key tile's ``dk`` and ``dv`` in VMEM over its query tiles and keeps the
  whole block's ``dq`` in VMEM across the key tiles. No score, probability
  or cotangent of one reaches HBM: 4.3 and 10.0 ms there. The causal and the
  window limit come from the positions (program ids and ``iota``); a key
  tile wholly outside a query tile's reach is not visited (``pl.when``, and
  its block index is held so that nothing is fetched); a keep-mask enters
  as one byte a pair, read once a group. The residuals are ``q``, ``k``,
  ``v``, the output and the log-sum-exp, so a block needs no
  ``jax.checkpoint`` of its own.

The arithmetic is one: float32 scores scaled by ``1 / sqrt(D)``, ``_NEG_BIG``
for a masked pair, float32 softmax statistics, both products on operands of
the inputs' dtype with float32 accumulation. The kernels add a tile at a
time under a running max, and round a probability to the inputs' dtype
before it is divided by the denominator, not after.

**The kernels ask for no VMEM limit of their own**, so their tiles fit the
compiler's default 16 MiB (:data:`_FWD_TILE`, :data:`_BWD_TILE`): a
``vmem_limit_bytes`` on one Pallas call re-tiles other products of the
program it sits in (``ops/selective_scan.py``; PERF.md section 6, PR 58).

**How a call chooses.** From the device and the shapes and from nothing
else: where the head fills the lanes (``D % 128 == 0``) and the inputs are
bfloat16, each pass of a block is ``jax.lax.platform_dependent``: the kernel
where the program is lowered for a TPU (a compile for a described TPU in a
CPU process included), the ``lax`` form on any other device. Any other
shape or dtype is traced as it always was, the ``lax`` form under
``jax.checkpoint`` with no ``custom_vjp``. :func:`scores_in_vmem` says which
form a call takes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30  # mask value: -inf would propagate NaN through exp(m - m)


def _block_attend(q, k, v, mask, m_prev, l_prev, acc_prev, scale):
    """One flash-attention block update with f32 running statistics.

    q [B,Tq,H,D], k/v [B,Tk,H,D], mask [Tq,Tk] bool (True = attend).
    Carries: m [B,H,Tq] running max, l [B,H,Tq] running denominator,
    acc [B,Tq,H,D] unnormalized output accumulator.
    """
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    # rescale previous accumulators to the new max
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[..., None])  # [B,H,Tq,Tk] f32
    l_new = l_prev * correction + p.sum(axis=-1)
    # flash practice: the p@v contraction runs in the COMPUTE dtype (bf16
    # operands hit the MXU) while accumulation stays f32
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    acc_new = acc_prev * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def full_attention(q, k, v, causal: bool = False):
    """Reference single-device attention (softmax in f32), [B,T,H,D] ->
    [B,T,H,D]. The golden model ring_attention must match."""
    B, T, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# -- blocked attention: one block of queries, two forms ---------------------------

# (queries, keys) a tile of the forward and of the backward kernel at 8 heads a
# group, the heads stacked as the rows of one tile: a [1024, keys] float32
# score tile is 4 MB and 2 MB, and a pass holds a handful of such inside the
# compiler's default VMEM limit (module docstring: the kernels must not ask
# for their own; 256 queries, or twice the keys in the backward, overrun it).
# One layer's forward and gradient at keye's shapes, alone on a v5e: 4.3 and
# 10.0 ms; at (128, 512) and (128, 256) 6.1 and 12.2 (PERF.md section 6, PR 62)
_FWD_TILE = (128, 1024)
_BWD_TILE = (128, 512)
_LANES = 128


class _Block(NamedTuple):
    """Where a block of queries and its keys lie in the segment: the first
    query's position, the first key's, and the window (None: all before)."""
    lo: int
    first: int
    window: int | None


def _visible(blk: _Block, qpos, kpos):
    mask = kpos <= qpos
    if blk.window is not None:
        mask &= kpos > qpos - blk.window
    return mask


def _scores_lax(q_blk, k_blk, mask, scale):
    """Masked float32 scores ``[B, G, R, q, k]`` of ``q_blk [B, q, G, R, D]``
    on ``k_blk [B, k, G, D]``; ``mask`` broadcasts to them."""
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", q_blk, k_blk, preferred_element_type=jnp.float32
    ) * scale
    return jnp.where(mask, scores, _NEG_BIG)


def _weigh_lax(scores, v_blk, dtype):
    """The softmax of ``scores`` over ``v_blk [B, k, G, D]`` -> ``[B, q, G, R,
    D]``."""
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bgrqk,bkgd->bqgrd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def _block_mask(blk: _Block, nq: int, nk: int, kept):
    """The block's mask ``[nq, nk]``, or with ``kept [B, nq, nk]`` their
    ``[B, 1, 1, nq, nk]``, under every group and head of it."""
    qpos = jnp.arange(blk.lo, blk.lo + nq)[:, None]
    kpos = jnp.arange(blk.first, blk.first + nk)[None, :]
    mask = _visible(blk, qpos, kpos)
    return mask if kept is None else (mask & kept)[:, None, None]


def _scale(D: int):
    return 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))


def _fwd_lax(q, k, v, kept=None, *, blk):
    """The block as two XLA products around a float32 softmax: ``(out [B, q,
    G, R, D], log-sum-exp [B, G, R, q])``."""
    mask = _block_mask(blk, q.shape[1], k.shape[1], kept)
    scores = _scores_lax(q, k, mask, _scale(q.shape[-1]))
    return _weigh_lax(scores, v, q.dtype), jax.nn.logsumexp(scores, axis=-1)


def _bwd_lax(q, k, v, out, lse, dout, kept=None, *, blk):
    """The block again, and its transpose: what ``jax.checkpoint`` gives."""
    del out, lse
    _, pull = jax.vjp(lambda *qkv: _fwd_lax(*qkv, kept, blk=blk)[0], q, k, v)
    return pull(dout)


def _tiles(R: int, nq: int, nk: int, tile: tuple) -> tuple:
    """``(queries, keys)`` a tile for a block of ``nq`` queries of ``R`` heads
    a group over ``nk`` keys: ``tile`` at most, whole bfloat16 sublane tiles
    and whole lanes, no more than the block needs, and no more pairs than
    ``tile`` has at 8 heads a group."""
    tq = min(tile[0], -(-nq // 16) * 16)
    tk = min(tile[1], -(-nk // _LANES) * _LANES)
    while R * tq * tk > 8 * tile[0] * tile[1] and tq % 32 == 0:
        tq //= 2
    return tq, tk


def _reach(blk: _Block, nq: int, tq: int, tk: int, i, j):
    """Whether query tile ``i`` sees any key of key tile ``j``."""
    q_first = blk.lo + i * tq
    q_last = jnp.minimum(q_first + tq, blk.lo + nq) - 1
    k_first = blk.first + j * tk
    seen = k_first <= q_last
    if blk.window is not None:
        seen &= k_first + tk - 1 > q_first - blk.window
    return seen


def _key_tiles(blk: _Block, nq: int, tq: int, tk: int, i):
    """The first and the last key tile query tile ``i`` reaches."""
    q_first = blk.lo + i * tq
    q_last = jnp.minimum(q_first + tq, blk.lo + nq) - 1
    last = (q_last - blk.first) // tk
    if blk.window is None:
        return 0, last
    return jnp.maximum(q_first - blk.window + 1 - blk.first, 0) // tk, last


def _query_tiles(blk: _Block, nq: int, tq: int, tk: int, j):
    """The first and the last query tile that reaches key tile ``j``."""
    n = -(-nq // tq)
    k_first = blk.first + j * tk
    first = jnp.clip((k_first - blk.lo) // tq, 0, n - 1)
    if blk.window is None:
        return first, n - 1
    return first, jnp.clip((k_first + tk + blk.window - 2 - blk.lo) // tq, 0, n - 1)


def _tile_scores(q, k, kept, blk, i, j, shape):
    """A tile's masked float32 scores ``[R, tq, tk]``: ``q [R x tq, D]`` on
    ``k [tk, D]``, positions from the tile's place, ``kept [tq, tk]`` int8 or
    None."""
    R, tq, tk = shape
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ).reshape(R, tq, tk) * scale
    qpos = blk.lo + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = blk.first + j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = _visible(blk, qpos, kpos)
    if kept is not None:
        mask &= kept.astype(jnp.int32) != 0
    return jnp.where(mask[None], s, _NEG_BIG)


def _fwd_kernel(*refs, blk, nq, masked):
    """Grid ``(batch row, group, query tile, key tile)``, a query tile's keys
    in turn: the running max, denominator and output stay in VMEM."""
    q_ref, k_ref, v_ref = refs[:3]
    kept_ref = refs[3] if masked else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = refs[3 + masked:]
    R, tq, D = q_ref.shape
    tk = k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_BIG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(_reach(blk, nq, tq, tk, i, j))
    def _():
        v = v_ref[...]
        s = _tile_scores(
            q_ref[...].reshape(R * tq, D), k_ref[...],
            None if kept_ref is None else kept_ref[...], blk, i, j, (R, tq, tk),
        )
        m_prev = m_sc[...]
        m = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        fade = jnp.exp(m_prev - m)
        l_sc[...] = l_sc[...] * fade + p.sum(axis=-1, keepdims=True)
        m_sc[...] = m
        acc_sc[...] = acc_sc[...] * fade + jnp.dot(
            p.reshape(R * tq, tk).astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ).reshape(R, tq, D)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l_sc[...])


def _bwd_kernel(*refs, blk, nq, masked):
    """Grid ``(batch row, group, key tile, query tile)``, a key tile's queries
    in turn: its ``dk``, ``dv`` add up in VMEM over them, and the whole
    block's ``dq`` stays in VMEM from the first key tile to the last."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref = refs[:6]
    kept_ref = refs[6] if masked else None
    dq_ref, dk_ref, dv_ref, dk_sc, dv_sc = refs[6 + masked:]
    R, tq, D = q_ref.shape
    tk = k_ref.shape[0]
    j, i = pl.program_id(2), pl.program_id(3)
    rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
    tn = (((0,), (0,)), ((), ()))

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        dq_ref[:, rows, :] = jnp.zeros((R, tq, D), jnp.float32)

    @pl.when(_reach(blk, nq, tq, tk, i, j))
    def _():
        q, do = q_ref[...].reshape(R * tq, D), do_ref[...].reshape(R * tq, D)
        k, v = k_ref[...], v_ref[...]
        s = _tile_scores(
            q, k, None if kept_ref is None else kept_ref[...], blk, i, j,
            (R, tq, tk),
        )
        p = jnp.exp(s - lse_ref[...])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ).reshape(R, tq, tk)
        ds = (p * (dp - dl_ref[...])).reshape(R * tq, tk).astype(q.dtype)
        p = p.reshape(R * tq, tk).astype(q.dtype)
        dv_sc[...] += jax.lax.dot_general(
            p, do, tn, preferred_element_type=jnp.float32
        )
        dk_sc[...] += jax.lax.dot_general(
            ds, q, tn, preferred_element_type=jnp.float32
        )
        dq_ref[:, rows, :] += jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        ).reshape(R, tq, D)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = (dk_sc[...] * (1.0 / math.sqrt(D))).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _rows(x, tq: int):
    """``[B, nq, G, R, D]`` -> ``[B, G, R, Tq, D]``, whole tiles of ``tq``
    queries: a group's heads one after another, zeros after the end."""
    pad = ((0, 0),) * 3 + ((0, (-x.shape[1]) % tq), (0, 0))
    return jnp.pad(x.transpose(0, 2, 3, 1, 4), pad)


def _cols(x, tk: int):
    """``[B, nk, G, D]`` -> ``[B, G, Tk, D]``, whole tiles of ``tk`` keys."""
    pad = ((0, 0), (0, 0), (0, (-x.shape[1]) % tk), (0, 0))
    return jnp.pad(x.transpose(0, 2, 1, 3), pad)


def _pairs(kept, tq: int, tk: int) -> tuple:
    """A keep-mask ``bool [B, nq, nk]`` as the kernels' operand, one byte a
    pair over whole tiles (nothing kept after the end); none for ``None``."""
    if kept is None:
        return ()
    pad = ((0, 0), (0, (-kept.shape[1]) % tq), (0, (-kept.shape[2]) % tk))
    return (jnp.pad(kept.astype(jnp.int8), pad),)


# the rows, groups and query tiles apart, a query tile's keys in turn (the
# backward: a key tile's queries, and dq across the key tiles); no
# vmem_limit_bytes (module docstring)
_FWD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
)
_BWD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")
)


# jitted so that a program traces and lowers a block's kernel once a shape,
# not once a layer (ops/delta_rule.py::_gram_pallas has the measurement)
@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _fwd_kernels(q, k, v, kept=None, *, blk, interpret=False):
    """The block's forward in VMEM: :func:`_fwd_lax`'s arguments and
    results."""
    B, nq, G, R, D = q.shape
    tq, tk = _tiles(R, nq, k.shape[1], _FWD_TILE)
    qt, kt, vt, kept = _rows(q, tq), _cols(k, tk), _cols(v, tk), _pairs(kept, tq, tk)
    Tq, Tk = qt.shape[3], kt.shape[2]

    def keys(i, j):
        return jnp.clip(j, *_key_tiles(blk, nq, tq, tk, i))

    rows = pl.BlockSpec((None, None, R, tq, D), lambda b, g, i, j: (b, g, 0, i, 0))
    stat = pl.BlockSpec((None, None, R, tq, 1), lambda b, g, i, j: (b, g, 0, i, 0))
    cols = pl.BlockSpec((None, None, tk, D), lambda b, g, i, j: (b, g, keys(i, j), 0))
    pairs = pl.BlockSpec((None, tq, tk), lambda b, g, i, j: (b, i, keys(i, j)))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, blk=blk, nq=nq, masked=bool(kept)),
        grid=(B, G, Tq // tq, Tk // tk),
        in_specs=[rows, cols, cols] + [pairs] * len(kept),
        out_specs=(rows, stat),
        out_shape=(
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((B, G, R, Tq, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((R, tq, 1), jnp.float32), pltpu.VMEM((R, tq, 1), jnp.float32),
            pltpu.VMEM((R, tq, D), jnp.float32),
        ],
        compiler_params=_FWD_PARAMS,
        interpret=interpret,
        name="blocked_attention_fwd",
    )(qt, kt, vt, *kept)
    return out[:, :, :, :nq].transpose(0, 3, 1, 2, 4), lse[:, :, :, :nq, 0]


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _bwd_kernels(q, k, v, out, lse, dout, kept=None, *, blk, interpret=False):
    """The block's backward in VMEM: :func:`_bwd_lax`'s arguments and
    results. Each score tile is formed again from ``q``, ``k`` and the
    log-sum-exp; no score or probability goes to HBM."""
    B, nq, G, R, D = q.shape
    nk = k.shape[1]
    tq, tk = _tiles(R, nq, nk, _BWD_TILE)
    qt, kt, vt, kept = _rows(q, tq), _cols(k, tk), _cols(v, tk), _pairs(kept, tq, tk)
    Tq, Tk = qt.shape[3], kt.shape[2]
    # a query's sum of its probabilities' cotangents: out . dout
    dl = (out.astype(jnp.float32) * dout.astype(jnp.float32)).sum(-1)
    stats = [
        jnp.pad(x, ((0, 0),) * 3 + ((0, Tq - nq),))[..., None]
        for x in (lse, dl.transpose(0, 2, 3, 1))
    ]

    def queries(j, i):
        return jnp.clip(i, *_query_tiles(blk, nq, tq, tk, j))

    rows = pl.BlockSpec(
        (None, None, R, tq, D), lambda b, g, j, i: (b, g, 0, queries(j, i), 0)
    )
    stat = pl.BlockSpec(
        (None, None, R, tq, 1), lambda b, g, j, i: (b, g, 0, queries(j, i), 0)
    )
    cols = pl.BlockSpec((None, None, tk, D), lambda b, g, j, i: (b, g, j, 0))
    pairs = pl.BlockSpec((None, tq, tk), lambda b, g, j, i: (b, queries(j, i), j))
    whole = pl.BlockSpec((None, None, R, Tq, D), lambda b, g, j, i: (b, g, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, blk=blk, nq=nq, masked=bool(kept)),
        grid=(B, G, Tk // tk, Tq // tq),
        in_specs=[rows, cols, cols, rows, stat, stat] + [pairs] * len(kept),
        out_specs=(whole, cols, cols),
        out_shape=(
            jax.ShapeDtypeStruct(qt.shape, jnp.float32),
            jax.ShapeDtypeStruct(kt.shape, k.dtype),
            jax.ShapeDtypeStruct(vt.shape, v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((tk, D), jnp.float32), pltpu.VMEM((tk, D), jnp.float32)
        ],
        compiler_params=_BWD_PARAMS,
        interpret=interpret,
        name="blocked_attention_bwd",
    )(qt, kt, vt, _rows(dout, tq), *stats, *kept)
    dq = (dq[:, :, :, :nq] * (1.0 / math.sqrt(D))).astype(q.dtype)
    return (
        dq.transpose(0, 3, 1, 2, 4),
        dk[:, :, :nk].transpose(0, 2, 1, 3), dv[:, :, :nk].transpose(0, 2, 1, 3),
    )


def _kernels_take(q) -> bool:
    """Whether the kernels take ``q [..., D]``: a head that fills the lanes,
    in the dtype the matrix unit takes."""
    return q.shape[-1] % _LANES == 0 and q.dtype == jnp.bfloat16


def scores_in_vmem(q, kernels: bool = True):
    """1.0 where :func:`blocked_attention` of ``q [B, T, H, D]`` keeps its
    scores in VMEM (the kernels), 0.0 where in the ``lax`` form: a float32
    scalar, settled when the program is lowered for its device."""
    if not (kernels and _kernels_take(q)):
        return jnp.float32(0.0)
    return jax.lax.platform_dependent(
        tpu=lambda: jnp.float32(1.0), default=lambda: jnp.float32(0.0)
    )


def _where_lowered(kernel, lax_form, blk, *args):
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, blk=blk),
        default=functools.partial(lax_form, blk=blk),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attend(q_blk, k_blk, v_blk, kept, blk):
    """One block of queries on the keys it can see, in the form its device
    takes: ``q_blk [B, q, G, R, D]`` over ``k_blk, v_blk [B, k, G, D]``,
    ``kept`` None or ``bool [B, q, k]`` -> ``[B, q, G, R, D]``."""
    return _attend_fwd(q_blk, k_blk, v_blk, kept, blk)[0]


def _attend_fwd(q, k, v, kept, blk):
    mask = () if kept is None else (kept,)
    out, lse = _where_lowered(_fwd_kernels, _fwd_lax, blk, q, k, v, *mask)
    return out, (q, k, v, out, lse, mask)


def _attend_bwd(blk, res, dout):
    *res, mask = res
    dq, dk, dv = _where_lowered(_bwd_kernels, _bwd_lax, blk, *res, dout, *mask)
    return dq, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def blocked_attention(q, k, v, window: int | None = None, block: int = 256,
                      keep=None, kernels: bool = True):
    """Causal grouped-query attention a block of queries at a time, with
    an optional sliding window: ``q [B, T, H, D]`` over ``k, v [B, T, G,
    D]`` (``H`` a multiple of ``G``; query head ``h`` reads key-value head
    ``h // (H / G)``) -> ``([B, T, H, D], keys a query saw on average)``.
    A query at ``t`` sees keys ``max(0, t - window + 1) .. t``; with
    ``keep``, of those only the keys a selection kept: ``keep(lo, hi,
    first)`` gives queries ``[lo, hi)`` their mask over keys ``[first, hi)``
    as ``bool [B, hi - lo, hi - first]``, the same for every head, or None
    where it drops nothing (``ops/sparse_select.py`` makes such masks). The
    mask is a constant of the backward pass.

    Queries ``[i, i + block)`` meet only the keys they can see, a static
    slice, so a window costs its width and not the segment's. Scores and
    softmax in float32; both products take their operands in the inputs'
    dtype and accumulate in float32. Any ``T``: the last block is short.

    A block takes one of two forms (module docstring). Where the program is
    lowered for a TPU, the head fills the lanes and the inputs are bfloat16,
    the Pallas pair: no score reaches HBM, and the block keeps ``q``, ``k``,
    ``v``, its output and a float32 log-sum-exp a query and head for its
    backward. Everywhere else two XLA products around a float32 softmax over
    ``[block, keys]`` a head, the largest thing alive (``full_attention``
    holds ``[T, T]`` a head: 1.3 GB at 8 x 40 heads x 1024 positions),
    recomputed in the backward (``jax.checkpoint``), so nothing of the
    scores is kept. ``kernels=False`` keeps that form whatever the device."""
    B, T, H, D = q.shape
    G = k.shape[2]
    vmem = kernels and _kernels_take(q)
    q = q.reshape(B, T, G, H // G, D)
    scale = _scale(D)

    @jax.checkpoint
    def attend(q_blk, k_blk, v_blk, mask):
        return _weigh_lax(_scores_lax(q_blk, k_blk, mask, scale), v_blk, q_blk.dtype)

    outs, seen = [], 0.0
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        first = 0 if window is None else max(0, lo - window + 1)
        blk = _Block(lo, first, window)
        kept = None if keep is None else keep(lo, hi, first)
        mask = _block_mask(blk, hi - lo, hi - first, kept)
        blocks = q[:, lo:hi], k[:, first:hi], v[:, first:hi]
        outs.append(
            _attend(*blocks, kept, blk) if vmem else attend(*blocks, mask)
        )
        seen = seen + (mask.sum() if kept is None else mask.sum() / B)
    out = jnp.concatenate(outs, axis=1).reshape(B, T, H, D)
    return out, seen.astype(jnp.float32) / T


def decode_attention(q_t, k_cache, v_cache, pos):
    """Single-position causal attention against a K/V cache — the O(T)
    incremental acting step for trajectory policies, matching
    ``full_attention``'s numerics exactly (f32 scores/softmax, 1/sqrt(D)
    scale, value contraction in f32).

    q_t [B, H, D] (the query at position ``pos``); k_cache/v_cache
    [B, T, H, D] with positions > ``pos`` ignored via the mask (their
    contents may be stale/zero). Returns [B, H, D] in q_t's dtype.
    """
    T = k_cache.shape[1]
    D = q_t.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    scores = (
        jnp.einsum("bhd,bkhd->bhk", q_t, k_cache).astype(jnp.float32) * scale
    )
    mask = jnp.arange(T) <= pos
    scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v_cache.astype(jnp.float32))
    return out.astype(q_t.dtype)


def ring_attention(
    q, k, v, axis_name: str, causal: bool = False, remat: bool = True
):
    """Blockwise ring attention; call INSIDE ``shard_map`` with the time
    axis sharded over ``axis_name``.

    Args: q, k, v [B, T_local, H, D] — this device's sequence block.
    Returns [B, T_local, H, D], the exact attention output for this block
    over the FULL (global) sequence.

    K/V rotate one neighbor per step (``ppermute``); after
    ``axis_size`` steps every device has attended to every block. Causal
    masking uses global block offsets, so cross-block masks are all-or-
    nothing except the diagonal block's triangle.

    ``remat`` (default on) wraps each block update in ``jax.checkpoint``:
    the backward pass recomputes the [Tq, Tk] probability blocks instead
    of saving n of them, eliminating the quadratic
    O(T_local * T_global) residual — the flash-attention memory story
    (FLOPs traded for HBM). The linear O(T_global * H * D) term (each
    block's K/V/stat inputs) is still saved by the scan; size HBM for
    that, not for zero.
    """
    B, T, H, D = q.shape
    from surreal_tpu.utils.compat import axis_size

    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    m0 = jnp.full((B, H, T), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    acc0 = jnp.zeros((B, T, H, D), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: shift blocks right
    tri = jnp.tril(jnp.ones((T, T), bool))

    def attend(i, k_blk, v_blk, m, l, acc):
        # after i rotations this device holds the block originally at
        # ring position (my - i) mod n
        src = (my - i) % n
        if causal:
            # cross-block causality is all-or-nothing (src block strictly
            # earlier -> fully visible, strictly later -> fully masked);
            # only the diagonal block needs the triangle
            mask = jnp.where(src == my, tri, jnp.broadcast_to(src < my, (T, T)))
        else:
            mask = jnp.ones((T, T), bool)
        # prevent_cse=False: the CSE-guard barriers are unnecessary (and
        # cost) when differentiating under lax.scan, per jax's own docs
        block = (
            jax.checkpoint(_block_attend, prevent_cse=False)
            if remat
            else _block_attend
        )
        return block(q, k_blk, v_blk, mask, m, l, acc, scale)

    def body(i, carry):
        k_blk, v_blk, m, l, acc = carry
        m, l, acc = attend(i, k_blk, v_blk, m, l, acc)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    # n-1 attend+rotate rounds, then the last block attends WITHOUT a
    # final rotation — the n-th ppermute's result would be discarded, a
    # wasted neighbor exchange of both K and V on the hot path
    k_blk, v_blk, m, l, acc = jax.lax.fori_loop(
        0, n - 1, body, (k, v, m0, l0, acc0)
    )
    m, l, acc = attend(n - 1, k_blk, v_blk, m, l, acc)
    # rows that attended to nothing (can't happen causally: the diagonal
    # block always contributes) would divide by zero; guard anyway
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=32)
def _ring_jit(mesh, axis: str, causal: bool, remat: bool, batch_axis):
    """One compiled ring program per (mesh, axis, causal, remat,
    batch_axis) — rebuilding the shard_map/jit per call would miss the
    jit cache and recompile every eager invocation (Mesh is hashable, so
    it keys the cache directly)."""
    from jax.sharding import PartitionSpec as P

    from surreal_tpu.utils.compat import shard_map

    spec = P(batch_axis, axis)
    attend = shard_map(
        functools.partial(
            ring_attention, axis_name=axis, causal=causal, remat=remat
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,  # house style (parallel/dp.py): the loop carry
        # mixes axis-varying (q-derived) and freshly-created accumulators
    )
    return jax.jit(attend)


def ring_self_attention(
    mesh, q, k, v, causal: bool = False, axis: str = "sp",
    remat: bool = True, batch_axis: str | None = None,
):
    """Host-side convenience: run :func:`ring_attention` under
    ``shard_map`` with the time axis of [B, T, H, D] inputs sharded over
    ``mesh[axis]``. ``batch_axis`` additionally shards B over that mesh
    axis (the dp x sp composed-mesh case) — attention rows are
    independent in B, so the ring body is unchanged: collectives ride
    only the sp axis, and each (dp, sp) tile works its local batch
    block. With batch_axis=None batch/heads replicate (shard them
    outside if needed)."""
    return _ring_jit(mesh, axis, causal, remat, batch_axis)(q, k, v)
