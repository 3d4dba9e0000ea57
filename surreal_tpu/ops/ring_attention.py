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

Pallas note (SURVEY.md §2.3 kernel policy): within one block this is
plain XLA einsum — fused well already; the cross-device ring is mesh
communication, not kernel work. A Pallas flash kernel would slot in at
``_block_attend`` if per-block HBM traffic ever dominates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def blocked_attention(q, k, v, window: int | None = None, block: int = 256):
    """Causal grouped-query attention a block of queries at a time, with
    an optional sliding window: ``q [B, T, H, D]`` over ``k, v [B, T, G,
    D]`` (``H`` a multiple of ``G``; query head ``h`` reads key-value head
    ``h // (H / G)``) -> ``([B, T, H, D], keys a query saw on average)``.
    A query at ``t`` sees keys ``max(0, t - window + 1) .. t``.

    Queries ``[i, i + block)`` meet only the keys they can see, a static
    slice, so a window costs its width and not the segment's, and the
    ``[block, keys]`` float32 scores of one block are the largest thing
    alive (``full_attention`` holds ``[T, T]`` a head: 1.3 GB at 8 x 40
    heads x 1024 positions). Each block is recomputed in the backward
    (``jax.checkpoint``), so nothing of the scores is kept. Scores and
    softmax in float32; both products take their operands in the inputs'
    dtype and accumulate in float32. Any ``T``: the last block is short."""
    B, T, H, D = q.shape
    G = k.shape[2]
    q = q.reshape(B, T, G, H // G, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    @jax.checkpoint
    def attend(q_blk, k_blk, v_blk, mask):
        scores = jnp.einsum(
            "bqgrd,bkgd->bgrqk", q_blk, k_blk,
            preferred_element_type=jnp.float32,
        ) * scale
        p = jax.nn.softmax(jnp.where(mask, scores, _NEG_BIG), axis=-1)
        return jnp.einsum(
            "bgrqk,bkgd->bqgrd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        ).astype(q_blk.dtype)

    outs, seen = [], 0.0
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        first = 0 if window is None else max(0, lo - window + 1)
        qpos = jnp.arange(lo, hi)[:, None]
        kpos = jnp.arange(first, hi)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        outs.append(attend(q[:, lo:hi], k[:, first:hi], v[:, first:hi], mask))
        seen = seen + mask.sum()
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
