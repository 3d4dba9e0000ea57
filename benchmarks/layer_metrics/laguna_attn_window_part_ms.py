"""Device milliseconds per iteration owned by the ops of model part
``attn_window``, whatever phase runs them: the three sliding layers'
attention (72 query heads over 8 key-value heads of 128, every dimension
turned at theta 10 000, keys t-511 .. t, a gate a head): projections,
rotation, scores, softmax, output projection, in the learn passes and
against the 512-slot rings. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names); a
program without the part reads nothing."""

from benchmarks.harness import parts

NAME = "laguna_attn_window_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "attn_window")
