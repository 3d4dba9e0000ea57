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

A phase may name **sub-scopes** (:data:`SUBPHASES`), entered inside it as
``phase("collect/act")``; an op of the phase outside every one of them is
the phase's ``rest``, so a phase's subs and ``rest`` sum to the phase:

    collect        act (the policy's forward and sampling), env (the env
                   step), episodes (return and length bookkeeping),
                   obs_stats (the running observation filter's update)
    prepare        gae (the reverse advantage recurrence)
    sgd, learn     psum (the gradients' cross-device sum)
    replay_sample  mass (p^alpha summed a block where no caller carries
                   the sums, and the blocks' cdf), search (the index draw),
                   gather (the rows read)

A sub's site has to sit inside its phase's scope: one that enters ``act``
where the path holds no ``collect`` names no op of ``collect``. Two sites
do not today, so no capture shows them and their ops read ``unattributed``
(a missing row is not zero time; ROADMAP S6 (z) may move them, once for
every cell, since a traced line that moves compiles every program anew):

    collect/episodes     both of its sites (``launch/trainer.py``,
                         ``launch/offpolicy_trainer.py``: after the learn
                         step, outside ``collect``'s scope)
    replay_sample/mass   ``replay/prioritized.py::block_mass``'s, called
                         before the update scan; the site inside ``sample``
                         (the blocks' cdf of each learn step) is in its
                         phase and is what ``mass`` reads

Phases say *when* in the iteration an op runs. A second, short
vocabulary of **parts** says *which part of the model* it belongs to, for
a trunk large enough that this is the question (``models/latent_moe.py``,
``models/ssm_hybrid.py``, ``models/swa_moe.py``, ``models/kda_moe.py``,
``models/dsa_moe.py``, ``models/gdn_moe.py``).
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
    attn_index   an indexer that selects an attention layer's keys
                 (``models/dsa_moe.py``, ``ops/sparse_select.py``): its three
                 products, the index key's LayerNorm and rotary turn, the
                 scores, the k-th-value search and the keep-mask, in the
                 learn pass and against the acting step's index cache; the
                 attention over the kept keys is ``attn``
    gdn_scan     a Gated DeltaNet layer's (``models/gdn_moe.py``) conv over
                 the concatenated q | k | v, SiLU, L2 norms, the head's
                 decay, ``beta``, the delta rule over a segment
                 (``ops/delta_rule.py``) or one acting step of it, the
                 output norm and its SiLU gate
    gdn_proj     its products: the one to q, k, v and the gate, the one to
                 ``beta`` and the decay, the output projection
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
    "kda_scan", "kda_proj", "attn_index", "gdn_scan", "gdn_proj",
)
# the sub-scopes a phase may have: phase("collect/act")
SUBPHASES = {
    "collect": ("act", "env", "episodes", "obs_stats"),
    "prepare": ("gae",),
    "sgd": ("psum",),
    "learn": ("psum",),
    "replay_sample": ("mass", "search", "gather"),
}
UNATTRIBUTED = "unattributed"
REST = "rest"  # of a phase: its ops outside every sub-scope of it
_VOCABULARY = frozenset(PHASES)
_SUBS = {top: frozenset(subs) for top, subs in SUBPHASES.items()}
_PARTS = frozenset(PARTS)
# transforms wrap a scope's name in the op_name path: jvp(sgd),
# transpose(jvp(sgd)), vmap(collect). ``jit(...)`` names a function, never
# a phase.
_WRAPPERS = ("transpose(", "jvp(", "vmap(", "remat(", "checkpoint(")


def phase(name: str):
    """The ``jax.named_scope`` of phase ``name``: ``"collect"`` for a
    top-level phase, ``"collect/act"`` for a sub-scope of one. A sub
    enters only its last segment (the site sits inside its phase's scope,
    where the path already reads ``collect/.../act``). A name whose top
    level is outside :data:`PHASES`, or whose sub is outside the phase's
    :data:`SUBPHASES`, is refused: one vocabulary, kept here."""
    top, slash, sub = name.partition("/")
    if top not in _VOCABULARY or (slash and sub not in _SUBS.get(top, ())):
        raise ValueError(
            f"phase {name!r} is not in the vocabulary {PHASES} with the "
            f"sub-scopes {SUBPHASES} (surreal_tpu/utils/phases.py): "
            "'top' or 'top/sub'"
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


def _segments(op_name: str | None):
    """The segments of an ``op_name`` path, transforms peeled."""
    for segment in (op_name or "").split("/"):
        while segment.startswith(_WRAPPERS) and segment.endswith(")"):
            segment = segment[segment.index("(") + 1:-1]
        yield segment


def _first_segment_in(op_name: str | None, vocabulary: frozenset) -> str:
    for segment in _segments(op_name):
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


def subphase_of(op_name: str | None) -> str:
    """``"top/sub"`` of an op inside a phase: the first name of the
    phase's :data:`SUBPHASES` among the segments after the phase's own
    (``jit(train_iter)/collect/while/body/act/tanh`` -> ``collect/act``),
    ``"top/rest"`` for an op of the phase outside each of its subs, and
    :data:`UNATTRIBUTED` for an op outside every phase. The rest is a label
    too, so that an op XLA made itself (``session/profile.py::
    hlo_op_phases``, rule 4) takes a neighbour's as it takes its phase."""
    top = subs = None
    for segment in _segments(op_name):
        if top is None:
            if segment in _VOCABULARY:
                top, subs = segment, _SUBS.get(segment, ())
        elif segment in subs:
            return f"{top}/{segment}"
    return UNATTRIBUTED if top is None else f"{top}/{REST}"
