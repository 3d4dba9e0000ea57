"""``ops/ring_attention.py::blocked_attention``'s Pallas kernels, interpreted on
the CPU, against its ``lax`` form: the output and the three gradients, over
the masks, head groupings and lengths the learn passes bring; and which form a
call takes where."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surreal_tpu.ops import ring_attention
from surreal_tpu.ops.ring_attention import blocked_attention, scores_in_vmem

D = 128
# float32: tests/test_ssm_hybrid.py::test_blocked_attention_is_the_masked_
# softmax_whatever_the_block's; bfloat16: that file's ``mixed``
TOLERANCE = {
    jnp.float32: dict(rtol=1e-4, atol=1e-5),
    jnp.bfloat16: dict(rtol=2e-2, atol=2e-2),
}
SMALL_TILES = ((16, 128), (16, 128))


def _interpreted(kernel, lax_form, blk, *args):
    """``_where_lowered`` with the kernel interpreted where a TPU would run
    it."""
    return kernel(*args, blk=blk, interpret=True)


def _keep(kind: str, B: int, block: int):
    """``keep(lo, hi, first)`` of a case: ``None`` for the first blocks, then
    a random half of the pairs and the diagonal; ``one_key``: a row of the
    masked blocks keeps its own position alone."""
    if kind is None:
        return None

    def keep(lo, hi, first):
        if lo < block:
            return None
        own = jnp.arange(lo, hi)[:, None] == jnp.arange(first, hi)[None, :]
        kept = jax.random.bernoulli(
            jax.random.key(lo), 0.5, (B, hi - lo, hi - first)
        ) | own
        if kind == "one_key":
            kept = kept.at[B - 1, 3].set(own[3])
        return kept

    return keep


def _inputs(B, T, G, R, dtype):
    keys = jax.random.split(jax.random.key(T + R), 4)
    q, w = (jax.random.normal(k, (B, T, G * R, D)) for k in (keys[0], keys[3]))
    k, v = (jax.random.normal(k, (B, T, G, D)) for k in keys[1:3])
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


CASES = {
    # B, T, G, R, window, block, keep, dtype, tiles
    "causal": (1, 128, 1, 8, None, 64, None, jnp.float32, None),
    "causal-bf16-two-rows": (2, 128, 1, 8, None, 64, None, jnp.bfloat16, None),
    "window-512-past-it": (1, 600, 1, 2, 512, 256, None, jnp.float32, None),
    "window-over-the-length": (1, 128, 1, 8, 512, 64, None, jnp.float32, None),
    "keep-mask-on-some-blocks": (2, 128, 1, 8, None, 64, "some", jnp.float32, None),
    "keep-mask-bf16": (2, 128, 1, 8, None, 64, "some", jnp.bfloat16, None),
    "a-row-keeps-one-key": (2, 128, 1, 8, None, 64, "one_key", jnp.float32, None),
    "nine-heads-a-group-window": (1, 128, 1, 9, 100, 64, None, jnp.float32, None),
    "six-heads-a-group-bf16": (1, 128, 2, 6, None, 64, None, jnp.bfloat16, None),
    "a-short-last-block": (1, 136, 1, 2, None, 64, None, jnp.float32, None),
    "short-last-block-mask-bf16": (2, 72, 1, 8, None, 64, "some", jnp.bfloat16, None),
    "many-tiles-causal": (1, 300, 1, 2, None, 150, None, jnp.float32, SMALL_TILES),
    "many-tiles-window": (1, 300, 1, 2, 100, 150, None, jnp.float32, SMALL_TILES),
    "many-tiles-window-mask": (
        2, 300, 1, 2, 140, 150, "some", jnp.float32, SMALL_TILES,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_lax_form(case, monkeypatch):
    """The block's forward and backward kernels, interpreted, give the
    ``lax`` form's output, ``seen`` and gradients with respect to ``q``,
    ``k`` and ``v``; float32 inputs go through the kernels too here (the
    program sends them bfloat16 alone), so that the logic is held to
    float32's tolerance."""
    B, T, G, R, window, block, keep, dtype, tiles = CASES[case]
    q, k, v, w = _inputs(B, T, G, R, dtype)
    keep = _keep(keep, B, block)

    def both(kernels):
        def loss(q, k, v):
            out, seen = blocked_attention(
                q, k, v, window=window, block=block, keep=keep, kernels=kernels
            )
            return (out.astype(jnp.float32) * w).sum(), (out, seen)

        (_, aux), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return (*aux, *grads)

    want = both(False)
    monkeypatch.setattr(ring_attention, "_kernels_take", lambda q: True)
    monkeypatch.setattr(ring_attention, "_where_lowered", _interpreted)
    if tiles:
        monkeypatch.setattr(ring_attention, "_FWD_TILE", tiles[0])
        monkeypatch.setattr(ring_attention, "_BWD_TILE", tiles[1])
    got = both(True)
    for name, a, b in zip(("out", "seen", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), err_msg=name,
            **TOLERANCE[dtype],
        )
    assert float(jnp.abs(want[2].astype(jnp.float32)).max()) > 1e-2


def test_a_key_tile_out_of_reach_is_not_visited():
    """The reach of a query tile, the limits its key tiles are held to and
    the query tiles a key tile is held to agree, tile by tile, with the
    positions' own mask: causal, and a window that leaves early tiles out."""
    tq, tk, nq = 16, 128, 256
    Block = ring_attention._Block
    for blk in (Block(256, 0, None), Block(512, 413, 100)):
        nk = blk.lo + nq - blk.first
        qpos = jnp.arange(blk.lo, blk.lo + nq)[:, None]
        kpos = blk.first + jnp.arange(-(-nk // tk) * tk)[None, :]
        mask = np.asarray(ring_attention._visible(blk, qpos, kpos))
        any_pair = mask.reshape(nq // tq, tq, -1, tk).any((1, 3))
        # the causal limit leaves late key tiles out, the window early ones
        assert not any_pair[0, -1] and (blk.window is None or not any_pair[-1, 0])
        for i in range(nq // tq):
            lo, hi = (int(x) for x in ring_attention._key_tiles(blk, nq, tq, tk, i))
            for j in range(any_pair.shape[1]):
                reach = bool(ring_attention._reach(blk, nq, tq, tk, i, j))
                assert reach == any_pair[i, j] == (lo <= j <= hi), (blk, i, j)
                first, last = (
                    int(x) for x in ring_attention._query_tiles(blk, nq, tq, tk, j)
                )
                assert not reach or first <= i <= last, (blk, i, j)


def _has_custom_vjp(q, k, v, **kw):
    jaxpr = jax.make_jaxpr(lambda *a: blocked_attention(*a, **kw))(q, k, v)
    return "custom_vjp" in str(jaxpr)


@pytest.mark.parametrize("why,head,dtype,kw", [
    ("a-64-wide-head", 64, jnp.bfloat16, {}),
    ("float32-inputs", 128, jnp.float32, {}),
    ("a-call-site-that-says-so", 128, jnp.bfloat16, {"kernels": False}),
])
def test_the_lax_form_is_traced_as_it_was_where_the_kernels_do_not_take(
    why, head, dtype, kw
):
    """Where the head does not fill the lanes, the inputs are not bfloat16 or
    the call says ``kernels=False``, the block is the plain ``lax`` form under
    ``jax.checkpoint``: no ``custom_vjp`` in the jaxpr, and the counter
    reads 0."""
    q = jnp.ones((1, 24, 4, head), dtype)
    k = v = jnp.ones((1, 24, 2, head), dtype)
    assert not _has_custom_vjp(q, k, v, block=8, **kw)
    assert float(jax.jit(lambda: scores_in_vmem(q, **kw))()) == 0.0


def test_off_a_tpu_a_call_the_kernels_would_take_runs_the_lax_form():
    """bfloat16 at a 128-wide head on the CPU: the block is the
    ``custom_vjp`` whose passes choose when lowered, the CPU's program holds
    no Mosaic call, its numbers are the ``lax`` form's and the counter reads
    0."""
    q, k, v, w = _inputs(1, 32, 1, 2, jnp.bfloat16)
    assert _has_custom_vjp(q, k, v, block=16)

    def loss(kernels):
        return lambda q, k, v: (
            blocked_attention(q, k, v, block=16, kernels=kernels)[0] * w
        ).sum()

    grad = jax.jit(jax.grad(loss(True), (0, 1, 2)))
    assert "tpu_custom_call" not in grad.lower(q, k, v).as_text()
    for a, b in zip(grad(q, k, v), jax.grad(loss(False), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), **TOLERANCE[jnp.bfloat16]
        )
    assert float(jax.jit(lambda: scores_in_vmem(q))()) == 0.0
