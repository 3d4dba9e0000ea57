"""Compile the main path's kernels and the fused PPO iteration for a
TPU v5e that is described, not attached (the chip's compiler is installed
with libtpu and needs no chip). Interpret mode cannot show what Mosaic
refuses — a block not aligned to the tiling, too much VMEM — and these
compiles do, at the real shapes of ``chip_smoke.py``'s phases, at no chip
time. Nothing runs: a compile that passes says nothing about results.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

T, B = 256, 4096            # the fused PPO geometry (chip_smoke.py)
VT, VB = 32, 1024           # impala_pong_1k32's rollout geometry
BATCH = 256                 # DDPG replay batch
# leaves of the DDPG jax:lift replay example (OffPolicyTrainer._replay_example)
LIFT_LEAVES = {"obs": (17,), "action": (4,), "reward": ()}


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def sds(chip):
    """A shape on the described chip: what a compile takes for an array."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one, so the next run would warn."""
    from jax.experimental.compilation_cache.compilation_cache import reset_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    reset_cache()


def _returns(sds):
    from surreal_tpu.ops.pallas_returns import discounted_returns_pallas

    x = sds((T, B), jnp.float32)
    return discounted_returns_pallas, (x, x, sds((B,), jnp.float32))


def _gather(capacity, leaf):
    def build(sds):
        from surreal_tpu.ops.pallas_replay import gather_rows_pallas

        return gather_rows_pallas, (
            sds((capacity, *LIFT_LEAVES[leaf]), jnp.float32),
            sds((BATCH,), jnp.int32),
        )

    return build


def _scatter(capacity):
    def build(sds):
        from surreal_tpu.ops.pallas_replay import scatter_rows_pallas

        return scatter_rows_pallas, (
            sds((capacity,), jnp.float32),
            sds((BATCH,), jnp.int32),
            sds((BATCH,), jnp.float32),
        )

    return build


def _fused_step(sds, envs=B, learner=None, horizon=T, algo="ppo", env="jax:lift"):
    """Trainer's fused rollout+learn step, PPO at the headline geometry
    unless told otherwise. The
    test jits the step itself: ``Trainer._train_iter`` is built over this
    process's (CPU) devices."""
    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    cfg = Config(
        learner_config=Config(algo=Config(name=algo, horizon=horizon)).extend(
            learner or {}
        ),
        env_config=Config(name=env, num_envs=envs),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    trainer = Trainer(cfg)

    def like(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(trainer.learner.init, key)
    carry = jax.eval_shape(
        lambda k: init_device_carry(trainer.env, k, envs), key
    )
    step = jax.jit(trainer._device_train_iter, donate_argnums=(0, 1))
    return step, (like(state), like(carry), like(key))


CASES = [
    pytest.param(_returns, True, id="returns-256x4096"),
    *[
        pytest.param(_gather(cap, leaf), True, id=f"gather-{leaf}-{cap}")
        for cap in (200_000, 1_000_000)
        for leaf in LIFT_LEAVES
    ],
    *[
        pytest.param(_scatter(cap), True, id=f"scatter-{cap}")
        for cap in (200_000, 1_000_000)
    ],
    pytest.param(_fused_step, False, id="fused-ppo-4096x256"),
]


@pytest.mark.parametrize("build,is_kernel", CASES)
def test_compiles_for_v5e(sds, build, is_kernel):
    fn, args = build(sds)
    lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args)
    if is_kernel:
        assert "tpu_custom_call" in lowered.as_text(), (
            "lowered without a Mosaic kernel (interpret mode leaked in?)"
        )
    compiled = lowered.compile()  # raises what the chip's compiler raises
    mem = compiled.memory_analysis()
    hbm = 16 * 2**30
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < hbm


def test_ring_insert_compiles_to_window_writes(chip):
    """``PrioritizedReplay.insert`` at the shapes of ``ddpg_lift_per20m``
    (1 048 576 rows into 20 971 520 slots, the five leaves of
    ``OffPolicyTrainer._replay_example`` and the priorities, state donated):
    windows written in place. A row scatter there takes 105 ns a row and
    leaf on the chip (PERF_LEDGER.jsonl, PR 28: 362 of the cell's 395 ms),
    and a copy of one obs leaf is 2 GB of temporaries."""
    from surreal_tpu.replay import build_replay
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import BASE_LEARNER_CONFIG

    capacity, n = 20 * 2**20, 2**20
    leaves = dict(LIFT_LEAVES, next_obs=(17,), discount=())
    replay = build_replay(
        Config(kind="prioritized", capacity=capacity).extend(BASE_LEARNER_CONFIG.replay)
    )

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
        )

    state = jax.eval_shape(
        replay.init, {k: jnp.zeros(shape) for k, shape in leaves.items()}
    )
    rows = {
        k: jax.ShapeDtypeStruct((n, *shape), jnp.float32)
        for k, shape in leaves.items()
    }
    compiled = (
        jax.jit(replay.insert, donate_argnums=(0,))
        .lower(on_chip(state), on_chip(rows))
        .compile()
    )
    assert " scatter(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    # every output (the six ring arrays, three scalars) reuses its input
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2**20
    assert mem.temp_size_in_bytes < 0.6e9


def test_fused_step_65536x256_reads_blocks_in_place(sds):
    """The fused PPO iteration at the geometry and overrides of
    ``ppo_lift_long`` (65 536 envs x 256, 64-64 tanh, ``mixed``): a
    minibatch's 64 blocks are each one time step of the rollout's own
    ``[T, B, ...]``, read by a slice over the major axis. Gathered, XLA
    relays the obs leaf so that the blocks lie on sublanes and fills six
    column pieces a sublane row a trip: 336 of the cell's 804 ms
    (PERF_LEDGER.jsonl, PR 30: ``phase_shuffle_ms``, five
    ``dynamic-update-slice bf16[64,11008,17]`` and ``copy
    bf16[256,65536,17]``). The phases are the profile digest's own
    reading of the program's text."""
    import re

    from surreal_tpu.session.config import Config
    from surreal_tpu.session.profile import hlo_op_phases

    cell = Config(
        algo=Config(clip_ratio=0.2, precision="mixed"),
        model=Config(
            actor_hidden=[64, 64], critic_hidden=[64, 64], activation="tanh"
        ),
        optimizer=Config(lr=3e-4),
    )
    step, args = _fused_step(sds, envs=65536, learner=cell)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    _, phases = hlo_op_phases(text)
    written = [
        name for name, phase in phases.items()
        if phase == "shuffle" and name.startswith("dynamic-update-slice")
    ]
    assert not written, f"the minibatch is built again: {written}"
    assert "mini-gather" not in text
    layouts = set(re.findall(r"bf16\[256,65536,17\]\{[^}]*\}", text))
    assert len(layouts) == 1, f"the obs leaf is relaid: {layouts}"
    assert compiled.memory_analysis().temp_size_in_bytes < 6.1e9


def test_fused_impala_1024x32_convolves_the_frames_where_they_lie(sds):
    """The fused IMPALA iteration at the geometry and overrides of
    ``impala_pong_1k32`` (1024 envs x 32, ``jax:pong84``, the Nature CNN,
    ``mixed``). The rollout's scan stacks the frames as
    ``u8[32,1024,84,84,4]{1,4,3,2,0}``: envs on the lanes, ``H, W, C``
    between them and the steps. ``learn``'s one pass convolves that buffer
    as it lies (``models/encoders.py::_FramesConv``): the cast and the
    ``/ 255`` are inside conv1's read of it. Flattened to a batch of
    32 768, XLA first wrote the frames out in bfloat16 and transposed all
    1.85 GB (PERF_LEDGER.jsonl, PR 44: ``multiply_bitcast_fusion`` and
    ``copy.98 bf16[32,84,84,1,4,1024]``, 10.0 of the cell's 68.3 ms). The
    names below are the ones the ledger's ``breakdown`` prints: this
    compile is the chip's program (``hbm_program_temp_gb`` to the byte)."""
    import math
    import re

    from surreal_tpu.session.config import Config

    cell = Config(
        algo=Config(
            gamma=0.99, entropy_coeff=0.01, value_coeff=0.5, clip_rho=1.0,
            clip_c=1.0, precision="mixed",
        ),
        model=Config(cnn=Config(enabled=True)),
    )
    step, args = _fused_step(
        sds, envs=VB, learner=cell, horizon=VT, algo="impala", env="jax:pong84"
    )
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    frames = VT * VB * 84 * 84 * 4
    whole = []
    for line in entry:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) == frames:
            whole.append(line.strip()[:160])
    assert not whole, f"the frames are written out in bfloat16: {whole}"
    conv1 = [
        line for line in entry
        if f" = bf16[{VT},{VB},20,20,32]" in line
        and "NatureCNN_0/Conv_0/conv_general_dilated" in line
    ]
    assert len(conv1) == 1, conv1
    operands = re.search(r" fusion\(([^)]*)\)", conv1[0]).group(1).split(", ")
    loop_out = re.compile(
        rf"\s*(%[\w.\-]+) = u8\[{VT},{VB},84,84,4\]\S* get-tuple-element\(%while"
    )
    rollout = {m.group(1) for m in map(loop_out.match, entry) if m}
    assert rollout & set(operands), (
        f"conv1 does not read the rollout's buffer {rollout}: {conv1[0][:300]}"
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 3.45e9


def _joyai_learner(num_layers: int, horizon: int = 128):
    """The 'mla_moe' trajectory policy at JoyAI-LLM-Flash's published
    widths (the family's defaults), cut in depth alone."""
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=horizon, epochs=2, num_minibatches=2),
            model=Config(encoder=Config(
                kind="trajectory", block="mla_moe", num_layers=num_layers,
                num_heads=32,
            )),
        ),
        env_config=Config(name="jax:lift", num_envs=128),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    env = make_env(cfg.env_config)
    return build_learner(cfg.learner_config, env.specs), env


def test_acting_scan_carries_the_latent_rows_and_nothing_per_head(chip):
    """``ppo_lift_joyai_128x128``'s acting carry, read from the compiled
    rollout (two layers deep, the published widths): the scan's loop
    carries ``bf16[128,128,576]`` a layer, and no array with the 32 heads
    beside the 128 cached positions (expanded keys would be
    ``[128,128,32,192]``, values ``[128,128,32,128]``)."""
    import re

    from surreal_tpu.launch.rollout import device_rollout, init_device_carry

    learner, env = _joyai_learner(num_layers=2)
    key = jax.eval_shape(lambda: jax.random.key(0))
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
    )
    state = jax.eval_shape(learner.init, key)
    carry = jax.eval_shape(lambda k: init_device_carry(env, k, 128), key)
    text = (
        jax.jit(lambda s, c, k: device_rollout(env, learner, s, c, k, 128))
        .lower(on_chip(state), on_chip(carry), on_chip(key)).compile().as_text()
    )
    loops = [
        line for line in text.splitlines()
        if re.search(r" while\(", line) and "bf16[128,128,576]" in line
    ]
    assert loops, "no loop carries the latent cache"
    for line in loops:
        carried = line.split(" while(")[0]
        assert carried.count("bf16[128,128,576]") >= 2      # one a layer
        assert not re.search(r"\[128,128,32,\d+\]", carried), carried[:400]


_COMPILED = {}  # what two tests of this file both read is compiled once


def _once(key, build):
    if key not in _COMPILED:
        _COMPILED[key] = build()
    return _COMPILED[key]


def _routed_layer_grad(chip):
    """One routed layer at the published widths over a minibatch's 8192
    tokens, forward and backward, compiled for the described chip."""
    return _once("routed_layer_grad", lambda: _compile_routed_layer_grad(chip))


def _compile_routed_layer_grad(chip):
    from surreal_tpu.models import latent_moe

    cfg = latent_moe.resolve(dict(num_layers=5, num_heads=32))
    layer = latent_moe.RoutedExperts(cfg, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16, sharding=chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros((8, 2048), jnp.bfloat16))
    )["params"]
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), params
    )

    def loss(p, x):
        return layer.apply({"params": p}, x)[0].astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss)).lower(params, x).compile()


def test_routed_layer_compiles_to_the_ragged_kernel(chip):
    """One routed layer at the published widths over a minibatch's 8192
    tokens, forward and backward: XLA:TPU takes ``jax.lax.ragged_dot`` as
    a kernel of its own (a ``ragged-dot`` custom call: three forward, six
    backward), not as sixteen masked dense products."""
    compiled = _routed_layer_grad(chip)
    text = compiled.as_text()
    assert text.count("custom-call") >= 9 and "agged" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def _computations(text: str) -> list:
    """A compiled module's text, a computation an entry."""
    import re

    return re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> )", text)


def _live_calls(computation: str) -> list:
    """The operands' names of each call of the live experts' kernel."""
    import re

    return [
        [name.strip() for name in re.sub(r"/\*.*?\*/", "", line).split(", ")]
        for line in re.findall(
            r"= \S+ custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\""
            r"[^\n]*held_experts_live", computation
        )
    ]


def _routed_acting_text(chip, family: str, tokens: int) -> str:
    """A routed layer's acting step of ``family`` at the published widths
    inside an eight-step ``lax.scan``, compiled for the described chip."""
    return _once(
        ("routed_acting", family, tokens),
        lambda: _compile_routed_acting(chip, family, tokens),
    )


def _compile_routed_acting(chip, family: str, tokens: int) -> str:
    from surreal_tpu.models import kda_moe, latent_moe

    resolve = {"kda_moe": kda_moe.resolve, "mla_moe": latent_moe.resolve}[family]
    cfg = resolve(dict(num_layers=5, num_heads=32))
    D = int(cfg["hidden_size"])
    layer = latent_moe.RoutedExperts(cfg, jnp.bfloat16)
    on_chip = lambda tree: jax.tree.map(       # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
    )
    x = jax.ShapeDtypeStruct((tokens, D), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros((tokens, D), jnp.bfloat16))
    )["params"]

    def acting(p, x):
        def step(x, _):
            y, read = layer.apply({"params": p}, x)
            return x + y, read
        return jax.lax.scan(step, x, None, length=8)

    return jax.jit(acting).lower(on_chip(params), on_chip(x)).compile().as_text()


@pytest.mark.parametrize("family,tokens,kernel", [
    pytest.param("kda_moe", 16, True, id="kimilinear-16-tokens-live-experts"),
    pytest.param("mla_moe", 128, False, id="joyai-128-tokens-every-expert"),
])
def test_an_acting_steps_routed_layer_reads_the_live_experts(
    chip, family, tokens, kernel
):
    """A routed layer's acting step inside a ``lax.scan`` at the published
    widths. ``ppo_lift_kimilinear_16x1024``'s (``[16, 2304]``, 8 held of 256,
    top-8: 0.39 of the held experts expected live) calls the live experts'
    kernel, Mosaic takes it, and its weights are bfloat16 arrays that enter
    the loop's body as its own operands: cast once outside and not a step
    (float32 parameters cast inside would be three times the bytes).
    ``ppo_lift_joyai_128x128``'s (``[128, 2048]``, 16 held, top-8: 0.98 live,
    nothing to skip) keeps XLA's dense form and has no custom call."""
    import re

    text = _routed_acting_text(chip, family, tokens)
    if not kernel:
        assert "tpu_custom_call" not in text
        return
    body = [c for c in _computations(text) if "held_experts_live" in c]
    assert len(body) == 1 and " while(" in text
    (operands,) = _live_calls(body[0])
    assert len(operands) == 7
    for name in operands[-3:]:      # gate, up, down
        defined = re.search(rf"{re.escape(name)} = (\S+) (\S+)\(", body[0])
        assert defined and defined.group(1).startswith("bf16[8,")
        assert defined.group(2) == "get-tuple-element", defined.group(0)


def _minor_axes(text, shape):
    """The minor-most logical axis of every bfloat16 array of ``shape`` (a
    pattern) in a compiled program's text."""
    return {int(a) for a in re.findall(rf"bf16\[{shape}\]\{{(\d)", text)}


def _cache_writes(text, shape):
    """The ``dynamic-update-slice`` ops whose result is such an array."""
    return re.findall(
        rf"= bf16\[{shape}\]\{{[^}}]*\}} dynamic-update-slice\(", text
    )


def test_phi4flash_iteration_fits_the_chip_with_each_layer_recomputed(sds):
    """The fused iteration of ``ppo_lift_phi4flash_16x1024`` (16 envs x
    1024, 2 x 2 minibatches of 8192 tokens, the family's published widths,
    one layer of each kind: 633M parameters, 10.1 GB of state) compiles
    for the v5e inside its 16.9 GB: each layer is recomputed in the
    backward (models/ssm_hybrid.py chooses that from the shapes), the scan
    keeps chunk starts and not every state (ops/selective_scan.py:
    ``[1024, 8, 16, 5120]`` float32 would be 2.7 GB a tensor) and walks its
    segments in the Pallas kernels, and the acting scan carries three kinds
    of state side by side."""
    import re

    from surreal_tpu.launch.rollout import init_device_carry
    from surreal_tpu.launch.trainer import Trainer
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    envs, horizon = 16, 1024
    cfg = Config(
        learner_config=Config(
            algo=Config(
                name="ppo", horizon=horizon, epochs=2, num_minibatches=2,
                precision="mixed", clip_ratio=0.2,
            ),
            model=Config(encoder=Config(
                kind="trajectory", block="ssm_hybrid", num_heads=40,
                pairs_before=1, pairs_after=1,
            )),
            optimizer=Config(lr=3e-4),
        ),
        env_config=Config(name="jax:lift", num_envs=envs),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    trainer = Trainer(cfg)
    like = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(trainer.learner.init, key)
    carry = jax.eval_shape(lambda k: init_device_carry(trainer.env, k, envs), key)
    compiled = (
        jax.jit(trainer._device_train_iter, donate_argnums=(0, 1))
        .lower(like(state), like(carry), like(key)).compile()
    )
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert held < 15.0e9, held          # 13.70 GB when this was written
    text = compiled.as_text()
    assert not re.search(r"f32\[(1024|1025|1056|1088),\d+,16,5120\]", text)
    # the learn passes walk in the scan's kernels (two state-space layers:
    # prepare's forward, an SGD pass's forward, its recomputed one and its
    # reverse walk), and no loop of theirs carries a state through HBM
    walks = re.findall(
        r"%(selective_scan_(?:fwd|bwd))[.\d]* = [^\n]*tpu_custom_call", text
    )
    assert sorted(walks) == (
        ["selective_scan_bwd"] * 2 + ["selective_scan_fwd"] * 6
    )
    assert not [line for line in text.splitlines() if " while(" in line
                and "f32[8,16,5120]" in line.split(" while(")[0]]
    # the acting loop's carry: the state, the ring, the shared cache, two
    # heads of 64 a row (models/ssm_hybrid.py::heads_per_row)
    loops = [line.split(" while(")[0] for line in text.splitlines()
             if " while(" in line and "f32[16,16,5120]" in line]
    assert any(
        "bf16[16,512,10,128]" in c and "bf16[16,1024,10,128]" in c
        and "bf16[16,3,5120]" in c for c in loops
    )
    # and a step's four cache writes are one row each: no cache anywhere in
    # the program has its slots on the lanes
    assert _minor_axes(text, r"16,(?:512|1024),10,128") == {3}
    assert len(_cache_writes(text, r"16,(?:512|1024),10,128")) == 4


@pytest.mark.parametrize("G,hd,H", [(20, 64, 40), (8, 128, 48)])
def test_an_acting_cache_keeps_its_slots_off_the_lanes(sds, G, hd, H):
    """A 1024-trip scan of ``ssm_hybrid.attention_step`` alone, its cache
    donated, at phi4flash's heads (20 of 64) and at laguna's (8 of 128):
    the compiler lays every ``[16, 1024, ., .]`` cache out with the slot
    axis (logical axis 1) off the minor position, the two writes' results
    included, so a one-slot write is one row and not a lane of every tile
    (with a head of 64 a row it chose ``{1,3,2,0}``: 72-76 us a write in
    ``ppo_lift_phi4flash_16x1024`` until PR 53)."""
    from surreal_tpu.models import ssm_hybrid

    envs, slots, dt = 16, 1024, jnp.bfloat16
    cfg = ssm_hybrid.resolve(
        dict(num_heads=H, num_kv_heads=G, hidden_size=H * hd)
    )
    like = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    cache = like(jax.eval_shape(
        lambda: ssm_hybrid.acting_cache(cfg, envs, slots, dt)["shared"]
    ))
    params = {
        name: sds(shape, jnp.float32)
        for name, shape, _ in ssm_hybrid.mixer_spec("full", cfg)
    }

    def decode(params, cache, hs):
        def step(carry, h):
            cache, pos = carry
            out, cache = ssm_hybrid.attention_step(
                params, h, cache, pos, dt, ring=False
            )
            return (cache, pos + 1), out

        (cache, _), out = jax.lax.scan(step, (cache, jnp.int32(0)), hs)
        return cache, out

    text = (
        jax.jit(decode, donate_argnums=1)
        .lower(params, cache, sds((slots, envs, H * hd), dt))
        .compile().as_text()
    )
    shape = rf"{envs},{slots},\d+,\d+"
    assert 1 not in _minor_axes(text, shape), _minor_axes(text, shape)
    assert len(_cache_writes(text, shape)) == 2


def test_laguna_iteration_fits_the_chip_with_each_layer_recomputed(sds):
    """The fused iteration of ``ppo_lift_laguna_16x1024`` (16 envs x 1024,
    2 x 4 minibatches of 4096 tokens, the family's published widths, layer 0
    and the period after it: 734M parameters, 11.7 GB of state) compiles for
    the v5e inside its 16.9 GB: each layer is recomputed in the backward
    (models/swa_moe.py chooses that from the shapes), attention keeps no
    scores, and the acting scan carries two full caches beside three rings."""
    from surreal_tpu.session.config import Config

    cell = Config(
        algo=Config(
            epochs=2, num_minibatches=4, precision="mixed", clip_ratio=0.2,
        ),
        model=Config(encoder=Config(
            kind="trajectory", block="swa_moe", num_heads=48, num_layers=5,
        )),
        optimizer=Config(lr=3e-4),
    )
    step, args = _fused_step(sds, envs=16, learner=cell, horizon=1024)
    assert sum(x.size for x in jax.tree.leaves(args[0].params)) == 734_014_473
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert held < 15.6e9, held          # 15.22 GB when this was written
    text = compiled.as_text()
    # the acting loop's carry: both kinds of cache, in the compute dtype
    loops = [line.split(" while(")[0] for line in text.splitlines()
             if " while(" in line and "bf16[16,512,8,128]" in line]
    assert any("bf16[16,1024,8,128]" in c for c in loops)
    # the routed layers run the ragged kernel over the bound's rows
    assert "agged" in text


def test_laguna_check_asks_the_program_for_its_experts_inside_highest(chip):
    """``ppo_laguna_ref.program_choice`` runs the program's own learn-side
    apply from inside the reference's ``default_matmul_precision("highest")``.
    Mosaic refuses the ragged product's bfloat16 operands at that precision
    ("Bad lhs type": found on the chip, PR 44; the CPU has no such kernel),
    so the function sets the program's own precision back. Three layers at
    the published widths (the third's router reads what the second's experts
    gave, so their product stays), a minibatch's 4 x 1024 tokens."""
    from benchmarks.harness import manifest
    from surreal_tpu.envs import make_env
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config
    from surreal_tpu.session.default_configs import base_config

    cfg = Config(
        learner_config=Config(
            algo=Config(name="ppo", horizon=1024, epochs=2, num_minibatches=4),
            model=Config(encoder=Config(
                kind="trajectory", block="swa_moe", num_heads=48, num_layers=3,
            )),
        ),
        env_config=Config(name="jax:lift", num_envs=16),
        session_config=Config(folder="unused"),
    ).extend(base_config())
    learner = build_learner(cfg.learner_config, make_env(cfg.env_config).specs)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
    )
    params = on_chip(jax.eval_shape(learner.init, jax.random.key(0)).params)
    obs = jax.ShapeDtypeStruct((4, 1024, 17), jnp.float32, sharding=chip)
    choose = manifest.load_reference("ppo_laguna_ref").program_choice(
        {"learner": learner}
    )
    with jax.default_matmul_precision("highest"):
        choose.lower(params, obs).compile()


def test_kimilinear_iteration_fits_the_chip_with_each_layer_recomputed(sds):
    """The fused iteration of ``ppo_lift_kimilinear_16x1024`` (16 envs x 1024,
    2 x 2 minibatches of 8192 tokens, the family's published widths, layer 1
    and the period after it: 508M parameters, 8.1 GB of state) compiles for
    the v5e inside its 16.9 GB: each layer is recomputed in the backward
    (models/attention.py::recomputed, from the shapes), the delta rule keeps
    chunk starts and not every state (ops/delta_rule.py: ``[1024, 8, 32, 128,
    128]`` float32 would be 17 GB), and the acting scan carries four matrix
    states and their conv tails beside one latent cache. An acting step's
    routed layers call the live experts' kernel (ops/moe.py) on bfloat16
    weights cast once outside the loop, and the kernel's VMEM costs the
    carried states none of theirs: of the step's four state updates
    (``multiply_reduce_fusion (f32[16,32,128], f32[16,32,128,128])``) the
    compiler writes three to memory space ``S(1)``, VMEM, where the parent
    of PR 48, whose dense form streamed all eight experts through XLA's own
    fusions, wrote two (my compiles here, PR 48)."""
    import re

    from surreal_tpu.session.config import Config

    cell = Config(
        algo=Config(
            epochs=2, num_minibatches=2, precision="mixed", clip_ratio=0.2,
        ),
        model=Config(encoder=Config(
            kind="trajectory", block="kda_moe", num_heads=32, num_layers=5,
        )),
        optimizer=Config(lr=3e-4),
    )
    step, args = _fused_step(sds, envs=16, learner=cell, horizon=1024)
    # the layers' 508 060 288, the projection in, the last norm and the heads
    assert sum(x.size for x in jax.tree.leaves(args[0].params)) == (
        508_060_288 + 17 * 2304 + 2304 + 2304 * 5 + 5 + 4
    )
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    held = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert held < 13.0e9, held          # 12.35 GB when this was written
    text = compiled.as_text()
    # no pass keeps a state a position
    assert not re.search(r"f32\[(1024|1025|1088),\d+,32,128,128\]", text)
    # the acting loop's carry: the matrix states, the tails, the latent rows
    loops = [line.split(" while(")[0] for line in text.splitlines()
             if " while(" in line and "f32[16,32,128,128]" in line]
    assert any(
        "bf16[16,3,32,128]" in c and "bf16[16,1024,576]" in c for c in loops
    )
    assert "agged" in text
    # the acting step: four calls of the live experts' kernel, their weights
    # the loop's own bfloat16 operands, and the states VMEM keeps
    acting = [c for c in _computations(text) if "held_experts_live" in c]
    assert len(acting) == 1
    calls = _live_calls(acting[0])
    assert len(calls) == 4
    for operands in calls:
        assert all(
            re.search(rf"{re.escape(w)} = bf16\[8,\d+,\d+\]\S* get-tuple-element\(", acting[0])
            for w in operands[-3:]
        ), operands
    resident = [
        line for line in acting[0].splitlines()
        if " fusion(" in line
        and re.search(r"= \([^=]*f32\[16,32,128,128\]\{[^}]*S\(1\)\}", line)
    ]
    assert len(resident) >= 2, len(resident)    # the parent's 2; 3 with the kernel


def _selective_scan_grad_text(sds) -> str:
    """``jax.grad`` of the selective scan at a learn pass's shapes,
    compiled for the described chip."""
    return _once("selective_scan_grad", lambda: _compile_selective_scan_grad(sds))


def _compile_selective_scan_grad(sds) -> str:
    from surreal_tpu.ops.selective_scan import selective_scan

    def loss(u, delta, A, Bm, Cm, D, state):
        y, final = selective_scan(u, delta, A, Bm, Cm, D, state)
        return (y * y).sum() + (final * final).sum()

    B, T, C, N = 8, 1024, 5120, 16
    bf16, f32 = jnp.bfloat16, jnp.float32
    return jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        sds((B, T, C), bf16), sds((B, T, C), f32), sds((N, C), f32),
        sds((B, T, N), bf16), sds((B, T, N), bf16), sds((C,), f32),
        sds((B, N, C), f32),
    ).compile().as_text()


def test_selective_scan_gradient_walks_in_vmem(sds):
    """``jax.grad`` of the selective scan at a learn pass's shapes (8 rows of
    1024 positions, 5120 channels, 16 state indices, ``u, B, C`` in
    bfloat16), compiled for the v5e in this CPU process: the lowering takes
    the two walks' kernels (ops/selective_scan.py chooses from the device it
    lowers for and the shapes), Mosaic accepts both, no loop carries the
    ``[8, 16, 5120]`` state through HBM and no array holds every state."""
    import re

    text = _selective_scan_grad_text(sds)
    calls = re.findall(
        r"%(selective_scan_(?:fwd|bwd))[.\d]* = [^\n]*tpu_custom_call", text
    )
    assert sorted(calls) == ["selective_scan_bwd", "selective_scan_fwd"]
    assert not [line for line in text.splitlines() if " while(" in line
                and "f32[8,16,5120]" in line.split(" while(")[0]]
    assert not re.search(r"f32\[(1024|1025|1056|1088),8,16,5120\]", text)


def _delta_rule_grad_text(sds) -> str:
    """``jax.grad`` of the delta rule at a learn pass's shapes, compiled
    for the described chip."""
    return _once("delta_rule_grad", lambda: _compile_delta_rule_grad(sds))


def _compile_delta_rule_grad(sds) -> str:
    from surreal_tpu.ops.delta_rule import delta_rule

    def loss(q, k, v, g, beta):
        o, state = delta_rule(q, k, v, g, beta)
        return (o * o).sum() + (state * state).sum()

    wide = (8, 1024, 32, 128)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds(wide, jnp.float32), sds(wide, jnp.float32), sds(wide, jnp.bfloat16),
        sds(wide, jnp.float32), sds(wide[:3], jnp.float32),
    ).compile().as_text()


def test_delta_rule_gradient_forms_the_gram_matrices_in_vmem(sds):
    """``jax.grad`` of the delta rule at a learn pass's shapes (8 rows of
    1024 positions, 32 heads of 128 channels, ``v`` in bfloat16), compiled
    for the v5e in this CPU process: the lowering takes the two Gram kernels
    and the two walks' (ops/delta_rule.py chooses from the device it lowers
    for and the shapes), Mosaic accepts all four, no array of a chunk step's
    pairwise decays is left in the program, no loop carries the ``[8, 32,
    128, 128]`` states through HBM and no triangular solve is left outside
    the kernels."""
    import re

    text = _delta_rule_grad_text(sds)
    calls = re.findall(
        r"%(decayed_gram(?:_bwd)?|delta_chunk_(?:fwd|bwd))[.\d]* = "
        r"[^\n]*tpu_custom_call", text,
    )
    # the Gram pairs of all the chunks ahead of each walk, the two walks, and
    # the pairs' cotangent after the reverse one
    assert sorted(calls) == [
        "decayed_gram", "decayed_gram", "decayed_gram_bwd",
        "delta_chunk_bwd", "delta_chunk_fwd",
    ]
    assert not re.search(r"f32\[8,32,4,16,16,128\]", text)
    assert not [line for line in text.splitlines() if " while(" in line
                and "f32[8,32,128,128]" in line.split(" while(")[0]]
    assert "triangular-solve" not in text


# blocked_attention's learn shapes: B, T, H, G, window, block, a keep-mask on
# the blocks past the first half
ATTENTION = {
    "keye": (2, 4096, 32, 4, None, 512, True),
    "laguna-window": (4, 1024, 72, 8, 512, 256, False),
}


def _blocked_attention_grad_text(sds, which: str) -> str:
    """``jax.grad`` of ``blocked_attention`` at a learn pass's shapes,
    compiled for the described chip."""
    return _once(
        f"blocked_attention_grad_{which}",
        lambda: _compile_blocked_attention_grad(sds, *ATTENTION[which]),
    )


def _compile_blocked_attention_grad(sds, B, T, H, G, window, block, masked) -> str:
    from surreal_tpu.ops.ring_attention import blocked_attention

    def loss(q, k, v, *kept):
        keep = None
        if masked:
            def keep(lo, hi, first):
                return kept[0][:, lo:hi, first:hi] if hi > T // 2 else None
        out, _ = blocked_attention(q, k, v, window=window, block=block, keep=keep)
        return (out.astype(jnp.float32) ** 2).sum()

    bf16 = jnp.bfloat16
    args = [sds((B, T, H, 128), bf16)] + [sds((B, T, G, 128), bf16)] * 2
    if masked:
        args.append(sds((B, T, T), jnp.bool_))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()


@pytest.mark.parametrize("which,scores", [
    ("keye", "f32[2,4,8,512,"), ("laguna-window", "f32[4,8,9,256,"),
])
def test_blocked_attention_gradient_keeps_its_scores_in_vmem(sds, which, scores):
    """``jax.grad`` of ``blocked_attention`` at ``ppo_lift_keye_4x4096``'s
    learn shapes (2 rows of 4096 positions, 32 heads of 128 over 4 key-value
    heads, blocks of 512, a keep-mask on half of them) and at
    ``ppo_lift_laguna_16x1024``'s window layer's (4 x 1024, 72 over 8, a
    window of 512, blocks of 256), bfloat16, compiled for the v5e in this CPU
    process: the lowering takes the two kernels a block
    (ops/ring_attention.py chooses from the device it lowers for, the head's
    width and the dtype), Mosaic accepts both with no ``vmem_limit_bytes``,
    and no float32 buffer of ``block x keys`` a head is left in the
    program."""
    import inspect

    from surreal_tpu.ops import ring_attention

    text = _blocked_attention_grad_text(sds, which)
    calls = re.findall(
        r"%(blocked_attention_(?:fwd|bwd))[.\d]* = [^\n]*tpu_custom_call", text
    )
    blocks = -(-ATTENTION[which][1] // ATTENTION[which][5])
    assert sorted(calls) == (
        ["blocked_attention_bwd"] * blocks + ["blocked_attention_fwd"] * blocks
    )
    # a block's dq [.., block, 128] and log-sum-exp [.., block, 1] are there
    assert not re.search(re.escape(scores) + r"(?!128\]|1\])", text)
    assert "vmem_limit_bytes" not in text
    assert "vmem_limit_bytes=" not in inspect.getsource(ring_attention)


@pytest.mark.parametrize("build,kernels", [
    pytest.param(
        lambda chip, sds: _selective_scan_grad_text(sds),
        {"selective_scan_fwd": 1, "selective_scan_bwd": 1},
        id="selective-scan-gradient",
    ),
    pytest.param(
        lambda chip, sds: _delta_rule_grad_text(sds),
        {"decayed_gram": 2, "decayed_gram_bwd": 1, "delta_chunk_fwd": 1,
         "delta_chunk_bwd": 1}, id="delta-rule-gradient",
    ),
    pytest.param(
        lambda chip, sds: _blocked_attention_grad_text(sds, "keye"),
        {"blocked_attention_fwd": 8, "blocked_attention_bwd": 8},
        id="blocked-attention-gradient",
    ),
    pytest.param(
        lambda chip, sds: _routed_acting_text(chip, "kda_moe", 16),
        {"held_experts_live": 1}, id="kimilinear-acting-step",
    ),
    pytest.param(
        lambda chip, sds: _routed_acting_text(chip, "mla_moe", 128),
        {}, id="joyai-acting-step-no-kernel",
    ),
    # XLA:TPU's own ragged product is a Mosaic call too, under XLA's name
    pytest.param(
        lambda chip, sds: _routed_layer_grad(chip).as_text(),
        {"ragged-dot-none": 6, "ragged-dot-metadata": 2},
        id="routed-layer-gradient-xlas-ragged-dot",
    ),
])
def test_the_digests_kernel_map_names_each_pallas_call(chip, sds, build, kernels):
    """``session/profile.py::hlo_kernels`` on the chip's own compiled text:
    every Pallas call under the ``name=`` its ``pl.pallas_call`` was given
    (what the digest's ``kernels`` table and the benchmark's ``kernel_*_ms``
    readers key on), as many instructions as call sites, and no other
    instruction of the program. The one ``tpu_custom_call`` that is not
    ours, XLA's lowering of ``jax.lax.ragged_dot``, is listed under XLA's
    own names."""
    from surreal_tpu.session.profile import hlo_kernels

    text = build(chip, sds)
    module, found = hlo_kernels(text)
    assert module.startswith("jit_")
    sites = {k: sum(1 for v in found.values() if v == k) for k in set(found.values())}
    assert sites == kernels
    for instruction in found:
        assert f"%{instruction} = " in text
    assert text.count('custom_call_target="tpu_custom_call"') == len(found)


@pytest.mark.parametrize("carry_is", [
    "an_argument",
    pytest.param("a_constant", marks=pytest.mark.xfail(
        reason="XLA:TPU (libtpu 0.0.34) takes the latent cache for a scan's "
        "output when it starts as zeros inside the jit and `pos` is the "
        "scan's own counter, and allocates it without writing the zeros; "
        "rows past `pos` then hold whatever the memory held, and a masked "
        "row's zero weight times a NaN is a NaN (seen on the chip at 1024 "
        "positions: PERF.md section 7, PR 46)",
        strict=False,
    )),
])
def test_a_decode_scan_reads_a_latent_cache_that_was_zeroed(chip, carry_is):
    """A scan of the bare decode path (``model.apply(cache=, pos=)``) over a
    segment, ``kda_moe`` at toy widths, compiled for the v5e: the latent
    cache the loop reads whole at every step has to hold the zeros
    ``act_init`` gave it. With the carry handed in (``act_step``'s rollout,
    the reference's replay) it does. With ``act_init`` called inside the jit
    and no reset between the steps the compiler drops the zeros for an
    ``AllocateBuffer``: the one decode path of the product never does that,
    and a second caller must not either."""
    import re

    import numpy as np

    from surreal_tpu.envs.base import ArraySpec, EnvSpecs
    from surreal_tpu.learners import build_learner
    from surreal_tpu.session.config import Config

    envs, T = 4, 24
    specs = EnvSpecs(
        obs=ArraySpec(shape=(17,), dtype=np.dtype(np.float32)),
        action=ArraySpec(shape=(4,), dtype=np.dtype(np.float32)),
    )
    learner = build_learner(Config(
        algo=Config(name="ppo", horizon=T, epochs=2, num_minibatches=2,
                    precision="mixed"),
        model=Config(encoder=Config(
            kind="trajectory", block="kda_moe", num_layers=5, num_heads=2,
            hidden_size=32, kda_head_dim=8, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=64, moe_intermediate_size=16,
            n_routed_experts=8, num_experts_per_tok=2, num_held=2,
        )),
    ), specs)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
    )
    state = on_chip(jax.eval_shape(learner.init, jax.random.key(0)))
    obs = jax.ShapeDtypeStruct((T, envs, 17), jnp.float32, sharding=chip)

    def step(state, carry, o):
        out, cache = learner.model.apply(
            state.params, learner._norm_obs(state.obs_stats, o),
            cache=carry["cache"], pos=carry["pos"],
        )
        return {"cache": cache, "pos": carry["pos"] + 1}, out.mean

    if carry_is == "an_argument":
        scan = jax.jit(lambda s, c, o: jax.lax.scan(
            lambda c, x: step(s, c, x), c, o
        ))
        carry = on_chip(jax.eval_shape(lambda: learner.act_init(envs)))
        text = scan.lower(state, carry, obs).compile().as_text()
    else:
        scan = jax.jit(lambda s, o: jax.lax.scan(
            lambda c, x: step(s, c, x), learner.act_init(envs), o
        ))
        text = scan.lower(state, obs).compile().as_text()
    rows = re.escape(f"bf16[{envs},{T},20]")        # kv_lora 16 + rope 4
    assert re.search(rows, text), "no latent cache in the program"
    assert not [
        line for line in text.splitlines()
        if re.search(rf"= {rows}\S* custom-call\(\)", line)
        and "AllocateBuffer" in line
    ]
