"""Device milliseconds per iteration owned by the ops of model part
``gdn_scan``, whatever phase runs them: the three Gated DeltaNet layers' conv
over the concatenated q | k | v, SiLU, L2 norms, the head's decay
(``softplus``, ``exp``), ``beta``, the chunked delta rule of
``ops/delta_rule.py`` with a head-wide decay (its Gram matrices, triangular
systems and products; a step of it on the matrix state in acting), the
output norm and its SiLU gate. From the ``parts`` split of the phase session's
digest (harness/parts.py; ``surreal_tpu/utils/phases.py`` has the names); a
program without the part reads nothing. As ``kimi_kda_scan_part_ms`` reads
part ``kda_scan`` for ``ppo_lift_kimilinear_16x1024``: the two cells run one
file's kernels at one shape."""

from benchmarks.harness import parts

NAME = "qwen3next_gdn_scan_part_ms"
CHIP_ONLY = True  # the CPU's capture has no device plane


def read(run):
    return parts.part_ms(run, "gdn_scan")
