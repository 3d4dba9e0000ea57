"""Base config trees (parity: reference ``surreal/session/default_configs.py``
plus ``surreal/main/ppo_configs.py`` / ``ddpg_configs.py``, SURVEY.md §5.6).

Three trees: learner / env / session. Algorithm-specific defaults live next
to each learner (``surreal_tpu.learners.ppo.PPO_LEARNER_CONFIG`` etc.) and
are ``extend()``-ed onto these bases.

New relative to the reference: ``session.topology`` selects the device mesh
(the reference instead wired ZMQ ports between processes), and
``session.backend`` selects tpu/cpu.
"""

from __future__ import annotations

from surreal_tpu.session.config import REQUIRED, Config

BASE_LEARNER_CONFIG = Config(
    algo=Config(
        name=REQUIRED,  # 'ppo' | 'ddpg' | 'impala'
        gamma=0.99,
        n_step=1,
        use_obs_filter=True,  # ZFilter running obs normalization
        # SEED topology only: drop trajectory chunks whose oldest transition
        # was acted more than this many updates ago (None = train on all;
        # V-trace absorbs bounded staleness, PPO-over-SEED should bound it)
        max_staleness=None,
        # scan-unroll knobs (every hot lax.scan states its decision
        # explicitly — enforced by the test_import_hygiene unroll lint):
        # device rollout scan over the horizon. 0 = the collector chooses
        # (launch/rollout.py::rollout_unroll: 4 for a memoryless policy
        # over vector observations, 1 for a trajectory or pixel policy;
        # DDPG's own collector in launch/offpolicy_trainer.py takes 1);
        # a number >= 1 is the user's and wins
        rollout_unroll=0,
        gae_unroll=1,      # time recurrences: PPO's GAE scan, IMPALA's
                           # V-trace scan, ops/returns estimators
        # precision policy (ops/precision.py) — ONE knob governing model
        # compute dtype, trajectory/SGD/replay staging dtype, and dynamic
        # loss scaling, threaded through every learner and trainer:
        #   'f32'      compute f32, staging f32 (numerics baseline)
        #   'mixed'    compute bf16, staging f32 (the pre-ISSUE-7 default
        #              — kept default so existing configs/checkpoints
        #              reproduce exactly; no loss-scale state in the
        #              optimizer pytree)
        #   'bf16'     compute bf16 AND staging bf16 (obs-class arrays
        #              move half the bytes) + dynamic loss scaling
        #   'bf16_fp8' 'bf16' plus the experimental fp8 matmul path in
        #              Dense layers
        precision="mixed",
    ),
    model=Config(
        actor_hidden=(64, 64),
        critic_hidden=(64, 64),
        activation="tanh",
        encoder=Config(
            # policy/critic trunk family: 'auto' = CNN stem when
            # model.cnn.enabled else MLP (the reference's two shapes);
            # 'trajectory' = causal trajectory transformer
            # (models/attention.py) whose attention rides ring attention
            # over an `sp` mesh axis when one is bound — the long-context
            # seam as a config knob (on-policy learners: ppo AND impala,
            # device envs; ddpg fails fast rather than silently ignore it)
            kind="auto",
            # the trajectory trunk's block family, and which keys each
            # reads (a key of the other family that is set is an error,
            # learners/seq_policy.py::family_config):
            # 'preln' (default; models/attention.py): pre-LayerNorm,
            #   learned positions, q/k/v/o of num_heads x head_dim, GELU
            #   MLP of 4 x features; a preset for CPU tests and small
            #   policies. Reads features, head_dim, max_len.
            # 'mla_moe' (models/latent_moe.py): RMSNorm, latent attention
            #   with a rotary part and a latent acting cache, SwiGLU,
            #   sigmoid-routed experts of which this chip holds a share.
            #   Reads the keys below that default to None (None = the
            #   published JoyAI-LLM-Flash value, FAMILY_DEFAULTS there).
            # 'ssm_hybrid' (models/ssm_hybrid.py): LayerNorm, state-space
            #   layers with a constant-size acting state, window attention
            #   with a ring that forgets, one full layer whose keys and
            #   values cross-attention layers read, gated memory units;
            #   Phi-4-mini-flash-reasoning's widths where a key is None.
            # 'swa_moe' (models/swa_moe.py): RMSNorm, grouped-query
            #   attention that is full in every fourth layer (num_heads
            #   query heads, YaRN rotary on half the head) and sliding in
            #   the others (window_heads, plain rotary), a sigmoid gate a
            #   head, a leading dense SwiGLU layer, then softmax-routed
            #   experts of which this chip holds a share beside a shared
            #   one; full caches and rings of rotated keys to act from.
            #   Laguna-S-2.1's widths where a key is None.
            # 'kda_moe' (models/kda_moe.py): RMSNorm, Kimi Delta Attention
            #   (a gated delta rule with a decay a channel over a matrix
            #   state a head, a short conv before it) three layers to one
            #   of latent attention without rotary, a leading dense SwiGLU
            #   layer, then sigmoid-routed experts of which this chip holds
            #   a share beside a shared one; constant-size states and conv
            #   tails beside a latent cache to act from.
            #   Kimi-Linear-48B-A3B-Instruct's widths where a key is None.
            # 'dsa_moe' (models/dsa_moe.py): RMSNorm, grouped-query
            #   attention with a norm a head on q and k and plain rotary
            #   over the whole head, whose keys a learned indexer selects
            #   (index_n_heads heads of index_head_dim over one shared key
            #   head score every causal key; a query keeps its index_topk
            #   best: ops/sparse_select.py), every layer routed by a
            #   softmax with no shared expert; rotated keys, values and the
            #   indexer's own key rows to act from. Reads hidden_size,
            #   num_kv_heads, attn_head_dim, moe_intermediate_size,
            #   n_routed_experts, num_experts_per_tok, rms_norm_eps,
            #   first_held, num_held and its own three keys below.
            #   Keye-VL-2.0-30B-A3B's widths where a key is None.
            # 'gdn_moe' (models/gdn_moe.py): zero-centred RMSNorm (1 + w),
            #   Gated DeltaNet layers (the delta rule with one decay a head
            #   over a matrix state a value head, linear_num_key_heads key
            #   heads under linear_num_value_heads value heads, one conv
            #   over q | k | v, an output norm gated by SiLU) three to one
            #   gated attention layer (a norm a head on q and k, rotary on
            #   partial_rotary_factor of the head, a sigmoid gate a channel
            #   before the output product), every layer routed by a softmax
            #   beside a shared expert with a sigmoid gate a token; matrix
            #   states and conv tails beside rotated keys and values to act
            #   from. Reads hidden_size, num_kv_heads, attn_head_dim,
            #   rope_theta, short_conv_kernel_size, moe_intermediate_size,
            #   shared_intermediate_size, n_routed_experts,
            #   num_experts_per_tok, rms_norm_eps, first_held, num_held and
            #   its own four keys below. Qwen3-Next-80B-A3B-Instruct's widths
            #   where a key is None.
            # All read kind, block, num_heads, act_impl, and num_layers
            # ('ssm_hybrid': pairs_before, pairs_after instead).
            block="preln",
            features=64,
            num_layers=2,
            num_heads=4,
            head_dim=16,
            # trajectory acting: 'kv' (incremental decode against the
            # model's own cache, models/attention.py::acting_cache — O(T)
            # per step) | 'padded' (re-run the full padded segment each
            # step — O(T^2), the simple reference form)
            act_impl="kv",
            # pos_embed capacity; the sequence learn pass uses horizon+1
            # positions, validated at learner build (seq_policy.py)
            max_len=4096,
            # -- 'mla_moe' (the keys 'swa_moe' reads too: hidden_size,
            # intermediate_size, moe_intermediate_size, n_routed_experts,
            # num_experts_per_tok, routed_scaling_factor,
            # first_k_dense_replace, rms_norm_eps, first_held, num_held) --
            hidden_size=None,
            q_lora_rank=None,
            kv_lora_rank=None,
            qk_nope_head_dim=None,
            qk_rope_head_dim=None,
            v_head_dim=None,
            intermediate_size=None,        # dense layers' SwiGLU width
            moe_intermediate_size=None,    # an expert's width
            n_routed_experts=None,         # the router's width
            num_experts_per_tok=None,
            n_shared_experts=None,
            routed_scaling_factor=None,
            first_k_dense_replace=None,    # leading dense layers
            rope_theta=None,
            rms_norm_eps=None,
            # the experts this chip holds of the routed ones
            first_held=None,
            num_held=None,
            bias_update_speed=None,        # router selection bias, a step
            # -- 'ssm_hybrid' only (it reads hidden_size and
            # intermediate_size above too; None = the published
            # Phi-4-mini-flash-reasoning value, FAMILY_DEFAULTS in
            # models/ssm_hybrid.py) ---------------------------------------
            num_kv_heads=None,
            sliding_window=None,
            ssm_state_size=None,
            ssm_dt_rank=None,              # None = ceil(hidden_size / 16)
            # depth: [ssm, window] x pairs_before, ssm + full,
            # [gmu, cross] x pairs_after
            pairs_before=None,
            pairs_after=None,
            # -- 'swa_moe' only (it reads num_kv_heads and sliding_window
            # above too; None = the published Laguna-S-2.1 value,
            # FAMILY_DEFAULTS in models/swa_moe.py) -------------------------
            window_heads=None,             # a sliding layer's query heads
            attn_head_dim=None,            # a head's size (not hidden / heads)
            shared_intermediate_size=None, # the shared expert's width
            # -- 'kda_moe' only (it reads 'mla_moe''s keys above too, but
            # q_lora_rank and rope_theta: its latent layers have neither;
            # None = the published Kimi-Linear-48B-A3B-Instruct value,
            # FAMILY_DEFAULTS in models/kda_moe.py) --------------------------
            kda_head_dim=None,             # a delta-rule head's keys and values
            short_conv_kernel_size=None,   # taps of the conv before the rule
            # -- 'dsa_moe' only (None = the published Keye-VL-2.0-30B-A3B
            # sa_config value, FAMILY_DEFAULTS in models/dsa_moe.py) ---------
            index_n_heads=None,            # the indexer's query heads
            index_head_dim=None,           # their size, and the one key head's
            index_topk=None,               # keys a query keeps
            # -- 'gdn_moe' only (None = the published
            # Qwen3-Next-80B-A3B-Instruct value, FAMILY_DEFAULTS in
            # models/gdn_moe.py) ---------------------------------------------
            linear_num_key_heads=None,     # a delta-rule layer's key heads
            linear_num_value_heads=None,   # its value heads, a multiple of them
            linear_head_dim=None,          # a key's and a value's size alike
            partial_rotary_factor=None,    # the share of a head that turns
        ),
        cnn=Config(
            enabled=False,          # pixel observations -> Nature-CNN stem
            channels=(32, 64, 64),
            kernels=(8, 4, 3),
            strides=(4, 2, 1),
            dense=512,
        ),
        # 'auto' resolves BOTH dtypes from algo.precision (the unified
        # policy knob above — ops/precision.py); an explicit dtype string
        # here overrides the policy for this model alone (the pre-ISSUE-7
        # spelling, kept honored for old configs)
        dtype="auto",           # parameter dtype ('auto' -> float32)
        compute_dtype="auto",   # activations dtype ('auto' -> per policy)
    ),
    optimizer=Config(
        name="adam",
        lr=3e-4,
        max_grad_norm=0.5,
        lr_schedule="constant",  # 'constant' | 'linear'
        # dynamic loss scaling (ops/precision.py::dynamic_loss_scaling):
        # 'auto' enables it exactly when the precision policy stages in
        # bf16 ('bf16'/'bf16_fp8'); True/False force it. All factors are
        # powers of two, so scaling is exact on healthy steps; an
        # overflow skips the step (Adam moments untouched) and backs the
        # scale off. NOTE: enabling adds a LossScaleState leaf to the
        # optimizer pytree — checkpoints do not restore across a
        # loss-scaling flip (the run-metadata guard makes that a clear
        # error, session/checkpoint.py).
        loss_scaling=Config(
            enabled="auto",
            init=2.0**15,
            growth_interval=2000,
            growth_factor=2.0,
            backoff_factor=0.5,
            min=1.0,
            max=2.0**24,
        ),
    ),
    replay=Config(
        # 'fifo' | 'uniform' | 'prioritized' (algo defaults override), or
        # 'remote' — the sharded experience plane (surreal_tpu/experience/):
        # replay lives in ReplayShardServer processes fed by an
        # ExperienceSender and drained by a prefetched ShardedSampler, so
        # actor fleets on other hosts can feed one learner group. Host
        # off-policy path only; shard geometry/transport under
        # session.topology.experience_plane.
        kind="fifo",
        # remote only: the shard servers' sampling discipline
        remote_kind="uniform",   # 'uniform' | 'prioritized'
        capacity=100_000,
        start_sample_size=1_000,
        batch_size=256,
        # prioritized-replay knobs (ignored by other kinds)
        priority_alpha=0.6,
        priority_beta0=0.4,
        priority_eps=1e-6,
    ),
)

BASE_ENV_CONFIG = Config(
    name=REQUIRED,        # 'jax:cartpole', 'gym:CartPole-v1', 'dm_control:cheetah-run', ...
    num_envs=1,           # batched envs (vmap width on device, workers on host)
    action_repeat=1,
    frame_stack=1,
    grayscale=False,
    image_size=None,      # (H, W) resize for pixel obs
    pixel_obs=False,
    flatten_obs=True,     # adapters always flatten dict obs to one vector;
                          # kept for config parity (FilterWrapper/concat role)
    time_limit=None,      # None -> backend default
    video=Config(enabled=False, dir=None, every_n_episodes=50),
    seed=0,
)

BASE_SESSION_CONFIG = Config(
    folder=REQUIRED,  # experiment directory (checkpoints, metrics, logs)
    backend="tpu",    # 'tpu' | 'cpu' (cpu = host-simulated devices for tests)
    topology=Config(
        # mesh axes for the SPMD program; product must divide device count.
        # dp = data parallel (gradient psum), tp = tensor parallel seam.
        mesh=Config(dp=-1, tp=1),  # -1 -> use all remaining devices
        # host-side env worker processes (0 = in-process); each worker
        # steps its own env_config.num_envs-wide batch, so total host envs
        # = num_env_workers * num_envs
        num_env_workers=0,
        # 'thread' (fine for gym classic-control) | 'process' (OS workers,
        # spawn ctx — MuJoCo-heavy stepping holds the GIL, so real
        # deployments fork like the reference's actor pool did)
        worker_mode="thread",
        # SEED host data plane (distributed/shm_transport.py):
        # - transport: 'auto' negotiates per-worker zero-copy shared-memory
        #   slabs for process workers against the local server (pickle for
        #   thread mode and remote workers); 'shm' forces the slab grant;
        #   'pickle' keeps the original serialized wire everywhere.
        # - pipeline_workers: each worker splits its env slice into two
        #   sub-slices and steps one while the other's actions are in
        #   flight (double-buffered acting, Stooke & Abbeel 1803.02811) —
        #   hides the server round trip; needs an even num_envs (auto-
        #   disabled otherwise, and under a dp mesh whose width the
        #   sub-slice would not divide).
        # - worker_silence_s: per-step server-liveness budget in the
        #   worker (the first replies legitimately wait out XLA
        #   compiles).
        transport="auto",
        pipeline_workers=True,
        worker_silence_s=120.0,
        # SEED worker supervision: a dead worker respawns immediately the
        # first time, then exponentially backed off (base * 2^k, capped) —
        # a worker that dies AT STARTUP must not respawn-loop hot. The
        # streak resets once a respawn survives its probation window; the
        # current backoff is exported as the workers/respawn_backoff_s
        # gauge.
        respawn_backoff_s=0.5,
        respawn_backoff_cap_s=30.0,
        # inference server: sanitize nonfinite observation payloads
        # (np.nan_to_num + a server/sanitized_requests gauge) instead of
        # letting one corrupt slab slot poison the micro-batch, the acting
        # policy, and every trajectory in flight
        sanitize_obs=True,
        # sharded experience plane (surreal_tpu/experience/): the
        # cross-host replay tier behind replay.kind='remote' (off-policy
        # host path) and, with enabled=true, the SEED trainer's chunk
        # relay (trajectory chunks route server -> shard -> learner over
        # the negotiated wire — the cross-host seam for actor fleets on
        # other machines). Transport negotiates per peer: shm slabs
        # same-host, the length-framed tcp codec cross-host, pickle as
        # the fallback.
        experience_plane=Config(
            enabled=False,           # SEED chunk-relay arm only; the
                                     # off-policy plane keys off replay.kind
            num_shards=2,
            shard_mode="thread",     # 'thread' | 'process' (spawn ctx;
                                     # shards pin themselves to CPU — a
                                     # replay shard must never grab a chip)
            transport="auto",        # 'auto' | 'shm' | 'tcp' | 'pickle'
            insert_slots=4,          # sender backpressure window (shm:
                                     # slab slots; tcp/pickle: unacked
                                     # frames)
            watermark_timeout_s=5.0, # shard-side bound on sample deferral
                                     # (a respawned-empty shard must not
                                     # deadlock the learner)
            ack_timeout_s=5.0,       # sender per-attempt ack budget
            sample_timeout_s=10.0,   # sampler per-attempt reply budget
            fifo_depth=64,           # SEED arm: chunks held per shard
            # shard respawn schedule (the SEED worker supervisor's rule:
            # immediate first respawn, then base * 2^k capped)
            respawn_backoff_s=0.5,
            respawn_backoff_cap_s=30.0,
        ),
        # autoscaling act-serving tier (distributed/fleet.py): replicas>1
        # (or autoscale=true) replaces the single InferenceServer with an
        # InferenceFleet — N replicas behind session-affinity routing
        # (workers rendezvous-hash to a replica at spawn and stay there,
        # so trajectory streams and shm slabs keep one owner), each with
        # its OWN coalescing budget (min_batch = its affinity share of
        # the worker fleet; auto_tune tracks per-replica liveness).
        # Lifecycle is the SEED respawn schedule: a dead replica respawns
        # in place (fixed address) under base * 2^k backoff while its
        # workers re-hello to survivors. Autoscaling adds/drains replicas
        # off the serve-latency EWMA (the PR-1 gauge), cooldown-bounded,
        # within [min_replicas, max_replicas].
        inference_fleet=Config(
            replicas=1,               # 1 = the original single server
            min_replicas=1,
            max_replicas=4,
            autoscale=False,
            scale_up_serve_ms=40.0,   # fleet-mean serve EWMA above: add
            scale_down_serve_ms=5.0,  # ...below: drain one replica
            scale_cooldown_s=30.0,    # min seconds between decisions
            respawn_backoff_s=0.5,
            respawn_backoff_cap_s=30.0,
            # bounded {version -> act closure} history kept for the
            # gateway's version-pinned serves (oldest evicted; an
            # evicted pin surfaces as a counted gateway catch_up)
            act_history=8,
        ),
        # production session gateway (surreal_tpu/gateway/): the
        # tenant-facing session tier in front of the inference fleet —
        # external sessions attach (id + lease), act over the gateway
        # wire protocol (tcp struct frames; pickle as the negotiated
        # per-session fallback), and detach. The gateway OWNS the
        # session->replica mapping (rendezvous-hashed like workers), so
        # routing survives client churn and replica death (sessions
        # rebind to survivors from the session table — counted
        # migrations, invisible to tenants). Admission is per-tenant:
        # token-bucket act rates, max-session quotas, bounded
        # backpressure queues (oldest evicted WITH an error reply), and
        # lease expiry reaping idle sessions. Version pinning serves a
        # tenant from a held param version while others ride the fanout
        # head; the act cache short-circuits duplicate observations at
        # the same version (hit/miss counted).
        gateway=Config(
            enabled=False,
            bind=None,            # fixed service address (None = allocate
                                  # a loopback port at start)
            max_sessions=256,     # global cap (0 = unbounded)
            lease_s=30.0,         # idle lease; any session frame renews
            act_cache=256,        # LRU act-result entries (0 = off)
            pin_versions=True,    # honor per-session version pins
            # per-tenant quotas; the 'default' entry covers tenants not
            # named here. rate=0 disables the token bucket.
            tenant_quotas=Config(
                default=Config(
                    max_sessions=64,   # sessions per tenant (0 = unbounded)
                    rate=200.0,        # acts/s refill
                    burst=400.0,       # bucket depth
                    queue_depth=64,    # backpressure queue bound
                ),
            ),
            # gateway serve-thread supervision (the shared respawn
            # schedule — utils/respawn.py)
            respawn_backoff_s=0.5,
            respawn_backoff_cap_s=30.0,
        ),
        # host-env (gym/dm_control) loops: collect iteration k+1 on a
        # worker thread while the device learns on k (the reference's
        # learner never waited on actors — its prefetch thread kept
        # batches queued, SURVEY.md §3.4). Costs one update of policy
        # staleness, which PPO ratios / V-trace absorb; false restores
        # strict rollout->learn alternation.
        overlap_rollouts=True,
        multihost=Config(          # multi-controller scaling (parallel/multihost.py)
            coordinator=None,      # "host:port" of process 0 ($JAX_COORDINATOR_ADDRESS)
            num_processes=None,    # total hosts/processes ($JAX_NUM_PROCESSES); None/1 = single
            process_id=None,       # this process's rank ($JAX_PROCESS_ID)
        ),
    ),
    total_env_steps=1_000_000,
    checkpoint=Config(
        every_n_iters=500,
        keep_last=3,
        keep_best=True,
        restore_from=None,   # foreign session folder to warm-start from
        auto_resume=True,    # resume from own folder's latest checkpoint
        # off-policy only: also checkpoint the replay buffer so a resume
        # skips the warmup refill (the reference did NOT checkpoint replay,
        # SURVEY.md §5.4 — this is a beyond-parity opt-in; storage cost is
        # the buffer itself)
        include_replay=False,
    ),
    # fault-tolerant training (session/interrupt.py, launch/recovery.py):
    recovery=Config(
        # SIGTERM/SIGINT sentinel: latch the signal, stop at the next
        # iteration boundary, write an emergency checkpoint — a TPU
        # preemption costs at most one iteration instead of one
        # checkpoint interval. Polled, never raced against orbax saves.
        interrupt=True,
        # divergence guard on the in-graph health/* signals, checked at
        # the metrics cadence: 'rollback' restores the newest FINITE
        # checkpoint (+ replay extra/ when snapshotted), re-seeds the
        # offending batch, and applies bounded LR backoff; 'warn' only
        # logs/emits (and still refuses to checkpoint poisoned state);
        # 'off' disables detection. Multi-host drivers force 'warn'
        # (rollback is a collective restore — relaunch with auto_resume
        # instead).
        on_divergence="rollback",
        max_rollbacks=3,          # then TrainingDiverged — bounded, loud
        lr_backoff=0.5,           # lr scale = lr_backoff ** rollback_count
        min_lr_scale=0.05,        # ...floored here (bounded backoff)
        grad_norm_limit=None,     # optional extra trip wire (None = NaN only)
        # this many consecutive HEALTHY metrics windows clear the rollback
        # streak: the budget targets a state that RE-diverges, not isolated
        # transients spread over a production-length run (same reset rule
        # as the SEED respawn backoff)
        heal_after_windows=20,
    ),
    # deterministic chaos harness (utils/faults.py): a list of fault specs
    # ({"site": ..., "kind": ..., "at": K, "times": N, ...}) injected at
    # fixed call counts of named data-plane/trainer sites — worker kills,
    # dropped/delayed frames, slab corruption, forced NaN state, SIGTERM
    # mid-iteration. None = chaos off (and the registry is reset at every
    # run start, so it can never leak between runs). CLI: --set
    # 'session_config.faults.plan=[{"site":"trainer.iteration",...}]'.
    faults=Config(plan=None),
    metrics=Config(
        every_n_iters=10,
        tensorboard=True,
        console=True,
    ),
    telemetry=Config(
        # telemetry spine (session/telemetry.py): span tracing into an
        # append-only JSONL event log under <folder>/telemetry/, mirrored
        # as time/* scalars through the MetricsWriter. Spans accumulate
        # in-memory and are written as ONE 'phases' event per metrics
        # cadence, so log volume scales with metrics.every_n_iters, not
        # iteration rate; the in-graph health/* diagnostics
        # (learners/base.py::training_health) ride the metrics dict and
        # sync at the same cadence — the hot loop gains zero extra
        # device->host syncs (tests/test_telemetry.py proves it).
        # Read a session offline with `python -m surreal_tpu diag <folder>`.
        enabled=True,
        # multi-host runs: each rank appends liveness events to its own
        # telemetry/heartbeat_rank<k>.jsonl at this cadence (seconds);
        # ranks whose host cannot write the folder disable silently
        heartbeat_every_s=10.0,
        # size-based rotation for events.jsonl: past this size the log is
        # renamed to events.jsonl.1 (one rotated segment kept; an older
        # .1 is overwritten) and a fresh file starts — diag and the
        # _iter_jsonl readers stitch .1 + current in order. None = never
        # rotate (the pre-PR-13 behavior).
        max_log_mb=256,
    ),
    # live ops plane (ISSUE 13, session/opsplane.py): every tier pushes
    # its gauge/hop row to a run-scoped aggregator; at the metrics cadence
    # the learner merges them into telemetry/ops_snapshot.json (the file
    # `surreal_tpu top <folder>` renders) and feeds the flight recorder —
    # a bounded ring of the last `ring` snapshots + fault/recovery events,
    # dumped to telemetry/flightrec/<trigger>/ when the recovery guard
    # trips, a chaos fault fires, or an SLO error budget exhausts (at most
    # one dump per trigger per min_dump_interval_s).
    ops=Config(
        enabled=True,
        ring=64,
        min_dump_interval_s=5.0,
    ),
    # per-tenant SLOs (session/slo.py), evaluated per metrics window
    # against the gateway's per-tenant stats + the merged hop percentiles.
    # Objectives default to None = not declared (no noise in normal runs);
    # set a target to arm one. `budget` is the tolerated breach fraction
    # over a rolling `budget_windows` evaluation windows — exhausting it
    # emits a counted slo_breach with exhausted=True and freezes a flight
    # recorder dump under flightrec/slo/.
    slo=Config(
        enabled=True,
        budget_windows=20,
        budget=0.2,
        act_rtt_p99_ms=None,      # gateway act round-trip p99 (ms)
        attach_p99_ms=None,       # session attach/hello latency p99 (ms)
        throttle_rate=None,       # throttled / (throttled + served) per window
        staleness_updates=None,   # published version - oldest replica version
    ),
    # watchdog & incident engine (ISSUE 15, session/watchdog.py +
    # session/incidents.py): detector sweeps over the merged ops snapshot
    # at the metrics cadence — EWMA/MAD breakouts on the headline
    # latencies/throughputs, queue/backpressure saturation, monotonic
    # growth of every dropped/bad_frames counter and tier liveness from
    # the ops plane's DEAD rendering. Firings open root-caused incidents
    # (one open at a time) persisted under telemetry/incidents/ and
    # rendered by `surreal_tpu why <folder>`. Pure host arithmetic over
    # the snapshot dict — no device->host syncs (transfer-guard tested).
    watchdog=Config(
        enabled=True,
        warmup=8,            # sweeps before breakout detectors arm
        window=32,           # rolling median/MAD window (sweeps)
        mad_k=6.0,           # breakout: |x - median| > mad_k * MAD floor
        min_rel=0.25,        # ... AND relative deviation above this
        sustain=2,           # consecutive outlier sweeps before firing
        queue_depth_max=512.0,   # saturation threshold for queue gauges
        respawn_burst=2,     # respawn deltas per window that count as a burst
        growth_windows=2,    # consecutive growing windows for drop counters
        staleness_growth_windows=4,  # ... for lineage/staleness_p99
        staleness_floor=64.0,  # versions; the startup ramp toward
        # steady-state pipeline depth stays below this and never fires
        # incident engine knobs (session/incidents.py)
        close_windows=5,         # clean sweeps before incident_close
        evidence_window_s=120.0,  # fault/recovery correlation horizon
        update_every=5,          # firing windows between incident_update
        max_captures=4,          # auto profile+flightrec captures per run
        capture_cooldown_s=60.0,
    ),
    # closed-loop remediation (ISSUE 16, session/remediate.py): once per
    # metrics cadence — after the watchdog sweep and the incident
    # observe — the engine maps the open incident's top-ranked cause
    # tier to ONE bounded action on an existing actuator (fleet
    # scale_up, per-tenant throttle via AdmissionController.set_quota,
    # RespawnSchedule-backed targeted restart, learner-group scale_up).
    # Every action is a counted `remediation` event + an atomic
    # telemetry/actions/action-<n>.json record + evidence on its
    # incident; a counter-detector watches the triggering objective for
    # verify_windows post-action sweeps and reverts what regressed
    # further. Suppressions (budget/cooldown) are loud, never silent.
    remediate=Config(
        enabled=True,
        max_actions=8,        # global per-run action budget
        cooldown_s=30.0,      # per-action-kind cooldown
        verify_windows=4,     # post-action sweeps before a verdict
        regress_margin=0.1,   # "regressed further" relative margin
        throttle_factor=0.5,  # tenant quota multiplier per throttle
        min_rate=1.0,         # throttled tenants never drop below this
        shed_rate=50.0,       # rate applied when the old quota was
                              # unlimited (rate=0 has nothing to scale)
    ),
    eval=Config(
        every_n_iters=100,
        episodes=5,
        mode="deterministic",  # 'deterministic' | 'stochastic'
        max_steps=None,        # per-episode step cap (None -> env time limit
                               # on device, 10k on host)
    ),
    # cost/MFU accounting (session/costs.py): per-program FLOPs / bytes
    # from XLA's cost model, recorded once per hot program at driver
    # startup, plus live perf/mfu + perf/membw_util gauges at the metrics
    # cadence (pure host arithmetic over already-recorded phase times —
    # zero extra device->host syncs, transfer-guard tested).
    perf=Config(
        enabled=True,
        # peak-spec override: peak FLOP/s and memory bytes/s used as the
        # MFU / bandwidth-utilization denominators. None resolves from
        # the device_kind table in session/costs.py (published TPU
        # peaks); a device that is not there — the CPU included — gets
        # no utilization gauge unless both are set here.
        peak_flops=None,
        peak_membw=None,
        # memory_analysis() of the compiled program (argument/output/temp
        # bytes): 'auto' takes it only when the persistent compile cache
        # is active; True/False force it
        memory_analysis="auto",
    ),
    # on-demand profiling (session/profile.py): jax.profiler windows
    # captured at iteration boundaries into <folder>/telemetry/profiles/,
    # each logged as a 'profile' telemetry event (rendered by diag).
    profile=Config(
        # watch <folder>/profile.trigger (written by `surreal_tpu
        # profile <folder>`, checked at most once per second): when it
        # appears, capture a num_iters window starting at the next
        # iteration boundary, then remove the file
        trigger_file=True,
        num_iters=5,
        # auto-trigger: an iteration slower than slow_iter_factor x the
        # iteration-time EWMA starts a capture (None = off). Detection is
        # host wall-clock between iteration boundaries — no device syncs.
        slow_iter_factor=None,
        max_auto_captures=2,  # bound auto captures per run
    ),
    publish=Config(
        # live parameter publishing (reference: the learner published every
        # publish_interval and agents/evals attached to the running session,
        # SURVEY.md §3.4/§2.1 PS row). When enabled the session starts a
        # ParameterPublisher + ParameterServer and publishes the agent's
        # acting view every N iterations; the server address lands in
        # <folder>/param_server.json so `surreal_tpu actor` / `eval
        # --follow` processes can discover it.
        enabled=False,
        every_n_iters=1,
        bind="tcp://127.0.0.1:*",  # REP endpoint(s) served to actor/eval
                                   # clients; set a real interface for
                                   # cross-machine actors
        # parameter FANOUT (distributed/param_fanout.py): versioned
        # weight frames over pub/sub — publish bytes scale with one
        # encode + N subscribes instead of N full-pytree fetch pickles.
        # wire='bf16' casts floating leaves to bfloat16 on the wire (f32
        # reconstruct, ops/precision.py's bf16 dtype); delta=true encodes
        # zlib'd deltas against the subscribers' acked version (a stale
        # ack re-keys with a full frame; a subscriber that missed a frame
        # falls back to ParameterClient.fetch — counted, never silent).
        fanout=Config(
            enabled=False,
            wire="f32",      # 'f32' | 'bf16'
            delta=True,
            ack_ttl_s=60.0,  # acks older than this don't pin full frames
        ),
    ),
    seed=0,
)


def base_config() -> Config:
    """The three-tree default bundle.

    ``learner_config`` is deliberately EMPTY here: the learner tree layers
    as user-overrides -> per-algorithm defaults -> BASE_LEARNER_CONFIG
    inside ``learners.build_learner``. Materializing BASE defaults into the
    user tree at bundle time would turn them into explicit "user" values
    that silently stomp per-algorithm defaults (e.g. IMPALA's lr)."""
    return Config(
        learner_config=Config(),
        env_config=BASE_ENV_CONFIG,
        session_config=BASE_SESSION_CONFIG,
    )
