"""One vocabulary of phases for the ops of the fused programs.

A phase is a name a performance change claims against: the profile digest
(``session/profile.py``) sums device time per phase, ``diag`` renders it
and the benchmark's ``phase_*_ms`` metrics read it. The names reach the
device's ops through ``jax.named_scope`` — metadata in each op's
``op_name`` path, nothing at run time — so a site is always inside jitted
code and no host clock or fence enters it.

    collect          rollout scan: act, env step, episode bookkeeping
    prepare          PPO: obs filter, value forward, GAE, advantage norm
    shuffle          PPO: per-epoch permutation; the minibatch gather, if one is built
    sgd              PPO: loss, grad, optimizer apply (dp psums: sgd/psum)
    finalize         PPO: beta adaptation, new state, metrics
    replay_insert    ring insert (+ fresh priorities)
    replay_sample    index draw (mass, search) and row gather
    replay_priority  priority scatter after an update
    update           off-policy learner update (DDPG ``learn``)
    bootstrap        IMPALA: value forward over ``next_obs`` (no gradient)
    vtrace           IMPALA: importance ratios, clips, reverse recurrence,
                     pg advantages
    learn            IMPALA: forward over ``obs``, the three losses, grad,
                     optimizer apply, new state, metrics (dp pmean:
                     learn/psum)

    algorithm   its phases
    PPO         collect prepare shuffle sgd finalize
    DDPG        collect replay_insert replay_sample replay_priority update
    IMPALA      collect bootstrap vtrace learn

At most eight per algorithm, so a reader can hold a split in one line.
An op outside every scope is ``unattributed``.

Phases say *when* in the iteration an op runs. A second, short
vocabulary of **parts** says *which part of the model* it belongs to, for
a trunk large enough that this is the question (``models/latent_moe.py``,
``models/ssm_hybrid.py``, ``models/swa_moe.py``, ``models/kda_moe.py``).
A part's scope sits inside whatever phase runs the model, so an op has
one phase and at most one part, and the digest sums each on its own:

    attn         attention: projections (latent attention's two low-rank
                 paths and rotary part; a hybrid trunk's window, full and
                 cross layers), scores, softmax, output projection, in
                 the learn pass and against the acting cache
    moe_route    router scores, biased top-k, weights, the sort by expert
    moe_experts  row gather, the held experts' grouped products, the
                 weighted combine, and the shared expert
    dense_ffn    a dense layer's SwiGLU
    optimizer    PPO: clip, Adam, apply, the router-bias rule
    ssm_scan     a state-space layer's conv, ``softplus``, the selective
                 scan (or an acting step of it), the skip and the gate
    ssm_proj     a state-space layer's four products: in, x, dt, out
    gmu          a gated memory unit: both products and the gate
    attn_window  ``models/swa_moe.py``'s sliding layers (72 query heads at
                 the published widths): projections, the rotation, scores
                 over the window, softmax, the gate a head, the output
                 projection, in the learn pass and against the ring
    attn_full    the same of its full layers (48 heads, YaRN frequencies
                 on half the head), against the whole segment or cache
    kda_scan     a Kimi Delta Attention layer's (``models/kda_moe.py``)
                 convs, SiLU, the L2 norms, the decay's ``softplus`` and
                 ``exp``, the chunked delta rule (or an acting step of it),
                 the output norm and gate
    kda_proj     its products: q, k, v, the decay's and the gate's low-rank
                 pairs, ``beta``, the output projection
"""

from __future__ import annotations

PHASES = (
    "collect", "prepare", "shuffle", "sgd", "finalize",
    "replay_insert", "replay_sample", "replay_priority", "update",
    "bootstrap", "vtrace", "learn",
)
PARTS = (
    "attn", "moe_route", "moe_experts", "dense_ffn", "optimizer",
    "ssm_scan", "ssm_proj", "gmu", "attn_window", "attn_full",
    "kda_scan", "kda_proj",
)
UNATTRIBUTED = "unattributed"
_VOCABULARY = frozenset(PHASES)
_PARTS = frozenset(PARTS)
# transforms wrap a scope's name in the op_name path: jvp(sgd),
# transpose(jvp(sgd)), vmap(collect). ``jit(...)`` names a function, never
# a phase.
_WRAPPERS = ("transpose(", "jvp(", "vmap(", "remat(", "checkpoint(")


def phase(name: str):
    """The ``jax.named_scope`` of phase ``name``: ``"collect"`` for a
    top-level phase, ``"collect/act"`` for a part of one. A part enters
    only its last segment (the site sits inside its phase's scope, where
    the path already reads ``collect/.../act``). A name whose top level is
    outside :data:`PHASES` is refused: one vocabulary, kept here."""
    top, _, sub = name.partition("/")
    if top not in _VOCABULARY or "/" in sub:
        raise ValueError(
            f"phase {name!r} is not in the vocabulary {PHASES} "
            "(surreal_tpu/utils/phases.py): 'top' or 'top/part'"
        )
    import jax  # at trace time only: the digest and the CLI read names

    return jax.named_scope(sub or top)


def part(name: str):
    """The ``jax.named_scope`` of model part ``name``; a name outside
    :data:`PARTS` is refused."""
    if name not in _PARTS:
        raise ValueError(
            f"part {name!r} is not in the vocabulary {PARTS} "
            "(surreal_tpu/utils/phases.py)"
        )
    import jax

    return jax.named_scope(name)


def _first_segment_in(op_name: str | None, vocabulary: frozenset) -> str:
    for segment in (op_name or "").split("/"):
        while segment.startswith(_WRAPPERS) and segment.endswith(")"):
            segment = segment[segment.index("(") + 1:-1]
        if segment in vocabulary:
            return segment
    return UNATTRIBUTED


def phase_of(op_name: str | None) -> str:
    """The phase an op belongs to: the first vocabulary name among the
    segments of its ``op_name`` path (``jit(train_iter)/collect/while/
    body/act/tanh`` -> ``collect``), :data:`UNATTRIBUTED` without one."""
    return _first_segment_in(op_name, _VOCABULARY)


def part_of(op_name: str | None) -> str:
    """The model part an op belongs to, by the same rule over
    :data:`PARTS` (``jit(train_iter)/sgd/transpose(jvp(attn))/dot`` ->
    ``attn``)."""
    return _first_segment_in(op_name, _PARTS)
