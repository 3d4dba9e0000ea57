"""``make_env`` factory (parity: reference ``surreal/env/__init__.py``
dispatch on name prefix — ``gym:*``, ``dm_control:*``, ``robosuite:*``;
SURVEY.md §2.1). New prefix ``jax:*`` selects pure on-device envs.

Host path returns a wrapped :class:`HostEnv`; ``jax:`` path returns an
:class:`AutoReset`-wrapped functional env — callers branch on
:func:`is_jax_env` (the trainer runs different collection loops for the
two families).
"""

from __future__ import annotations

from typing import Union

from surreal_tpu.envs.base import HostEnv
from surreal_tpu.envs.jax.base import AutoReset, JaxEnv
from surreal_tpu.envs.wrappers import (
    ActionRepeatWrapper,
    EpisodeStatsWrapper,
    FrameStackWrapper,
    GrayscaleWrapper,
    PixelObsWrapper,
)

AnyEnv = Union[HostEnv, AutoReset]

_JAX_ENVS = {}
_BUILTINS_LOADED = False


def register_jax_env(name: str, cls) -> None:
    _JAX_ENVS[name] = cls


def _builtin_jax_envs():
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    from surreal_tpu.envs.jax.cartpole import CartPole
    from surreal_tpu.envs.jax.pendulum import Pendulum

    # all first-party pure-JAX modules (jax/numpy only — no optional
    # deps): import unconditionally so a broken module surfaces instead
    # of silently unregistering its envs
    from surreal_tpu.envs.jax.lift import BlockLift
    from surreal_tpu.envs.jax.nut_assembly import NutAssembly
    from surreal_tpu.envs.jax.pixels import BlockLiftPixels, NutAssemblyPixels
    from surreal_tpu.envs.jax.pong import Pong, Pong84, PongSmall

    _JAX_ENVS.setdefault("cartpole", CartPole)
    _JAX_ENVS.setdefault("pendulum", Pendulum)
    _JAX_ENVS.setdefault("lift", BlockLift)
    _JAX_ENVS.setdefault("pong", Pong)
    _JAX_ENVS.setdefault("pong16", PongSmall)
    _JAX_ENVS.setdefault("pong84", Pong84)
    _JAX_ENVS.setdefault("nut", NutAssembly)
    _JAX_ENVS.setdefault("lift_pixels", BlockLiftPixels)
    _JAX_ENVS.setdefault("nut_pixels", NutAssemblyPixels)


def is_jax_env(env: AnyEnv) -> bool:
    return isinstance(env, (JaxEnv, AutoReset))


def make_env(env_config) -> AnyEnv:
    """Build the configured environment from an ``env_config`` tree."""
    name = env_config.name
    if ":" not in name:
        raise ValueError(
            f"env name {name!r} needs a backend prefix (jax:, gym:, dm_control:, robosuite:)"
        )
    backend, _, env_id = name.partition(":")

    if backend == "jax":
        _builtin_jax_envs()
        if env_id not in _JAX_ENVS:
            raise ValueError(f"unknown jax env {env_id!r}; have {sorted(_JAX_ENVS)}")
        env = _JAX_ENVS[env_id]()
        return AutoReset(env, time_limit=env_config.time_limit)

    if backend == "gym":
        from surreal_tpu.envs.gym_adapter import GymAdapter

        kwargs = {}
        if env_config.pixel_obs or env_config.video.enabled:
            # both pixel obs and video recording need rendered frames
            kwargs["render_mode"] = "rgb_array"
        env: HostEnv = GymAdapter(
            env_id, num_envs=env_config.num_envs, seed=env_config.seed, **kwargs
        )
    elif backend == "dm_control":
        from surreal_tpu.envs.dm_control_adapter import DmControlAdapter

        domain, _, task = env_id.partition("-")
        env = DmControlAdapter(
            domain, task, num_envs=env_config.num_envs, seed=env_config.seed
        )
    elif backend == "robosuite":
        try:
            import robosuite  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "robosuite is not installed in this image (SURVEY.md §7); "
                "use the on-device BlockLifting-class env 'jax:lift' for "
                "Robosuite-class workloads"
            ) from e
        from surreal_tpu.envs.robosuite_adapter import RobosuiteAdapter

        env = RobosuiteAdapter(
            env_id,
            num_envs=env_config.num_envs,
            seed=env_config.seed,
            renderable=bool(env_config.pixel_obs or env_config.video.enabled),
        )
    else:
        raise ValueError(f"unknown env backend {backend!r}")

    if env_config.pixel_obs:
        env = PixelObsWrapper(env, image_size=tuple(env_config.image_size or (84, 84)))
    if env_config.grayscale:
        env = GrayscaleWrapper(env)
    if env_config.frame_stack > 1:
        env = FrameStackWrapper(env, env_config.frame_stack)
    if env_config.action_repeat > 1:
        env = ActionRepeatWrapper(env, env_config.action_repeat)
    env = EpisodeStatsWrapper(env)
    if env_config.video.enabled and env_config.video.dir:
        from surreal_tpu.envs.video import VideoWrapper

        env = VideoWrapper(env, env_config.video.dir, env_config.video.every_n_episodes)
    return env
