"""``ppo_lift_qwen3next_16x1024``'s fused iteration compiled for the
described v5e: a case of ``tests/test_tpu_compile.py`` (its fixtures and its
``_fused_step``), in a file of its own because the suite hands a worker one
file at a time (``--dist loadfile``) and that file is already the longest a
worker takes (``tests/test_tpu_compile_keye.py`` says the same); and under a
name that sorts early, because the suite's last files are those two and a
third long compile at its tail is what the run's time limit meets first."""

import re

import jax
from test_tpu_compile import (  # noqa: F401  (fixtures)
    _computations, _fused_step, _live_calls, _no_persistent_cache, chip, sds,
)


def test_qwen3next_iteration_fits_the_chip_and_says_which_forms_it_took(sds):
    """The fused iteration of ``ppo_lift_qwen3next_16x1024`` (16 envs x 1024,
    2 x 2 minibatches of 8192 tokens, the family's published widths, one
    period of four layers, 32 held experts a layer: 548M parameters, 8.8 GB
    of state) compiles for the v5e inside its 16.9 GB: each layer is
    recomputed in the backward, the delta rule keeps chunk starts and not
    every state. Which forms it took: the rule's two walks and its Gram
    pairs in their kernels (a head's decay is spread over the head's
    channels, so ``ppo_lift_kimilinear_16x1024``'s kernels run at its
    shape); the full layer's attention at a head of 256 in
    ``blocked_attention``'s Pallas pair (``models/gdn_moe.py::
    ATTENTION_KERNELS``); an acting step's routed layers in the live
    experts' kernel. The acting scan carries three float32 matrix states
    with one conv tail each ``[16, 3, 8192]`` and the full layer's keys and
    values a position a row ``[16, 1024, 1, 512]``."""
    from surreal_tpu.models import gdn_moe
    from surreal_tpu.session.config import Config

    cell = Config(
        algo=Config(
            epochs=2, num_minibatches=2, precision="mixed", clip_ratio=0.2,
        ),
        model=Config(encoder=Config(
            kind="trajectory", block="gdn_moe", num_heads=16, num_layers=4,
        )),
        optimizer=Config(lr=1e-5),
    )
    step, args = _fused_step(sds, envs=16, learner=cell, horizon=1024)
    # the layers' 547 873 856, the projection in, the last norm and the heads
    assert sum(x.size for x in jax.tree.leaves(args[0].params)) == (
        547_873_856 + 17 * 2048 + 2048 + 2048 * 5 + 5 + 4
    )
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    print("held bytes", held)
    assert 4.0e9 < held < 14.6e9, held
    text = compiled.as_text()
    # no pass keeps a state a position, nor a whole map of scores
    assert not re.search(r"f32\[(1024|1025|1088),\d+,32,128,128\]", text)
    assert not re.search(r"\[\d+,102[45],102[45]\]", text)
    # the forms: the rule's kernels, the attention's, the live experts'
    for kernel in ("decayed_gram", "delta_chunk_fwd", "delta_chunk_bwd"):
        assert kernel in text, kernel
    assert ("blocked_attention_fwd" in text) == gdn_moe.ATTENTION_KERNELS
    # the acting loop's carry: the matrix states, the tails, the cache rows
    loops = [line.split(" while(")[0] for line in text.splitlines()
             if " while(" in line and "f32[16,32,128,128]" in line]
    assert any(
        "bf16[16,3,8192]" in c and "bf16[16,1024,1,512]" in c for c in loops
    )
    assert "agged" in text
    acting = [c for c in _computations(text) if "held_experts_live" in c]
    assert len(acting) == 1
    assert len(_live_calls(acting[0])) == 4
