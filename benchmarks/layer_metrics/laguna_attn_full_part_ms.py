"""Device milliseconds per iteration owned by the ops of model part
``attn_full``, whatever phase runs them: the two full layers' attention (48
query heads, YaRN frequencies on the first half of the head, keys 0 .. t, a
gate a head): in the learn passes and against the whole-segment caches. From
the ``parts`` split of the phase session's digest (harness/parts.py;
``surreal_tpu/utils/phases.py`` has the names); a program without the part
reads nothing."""

from benchmarks.harness import parts

NAME = "laguna_attn_full_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "attn_full")
