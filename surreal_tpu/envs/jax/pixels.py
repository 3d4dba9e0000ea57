"""Device-rendered pixel variants of the manipulation envs (BASELINE
config ④: "PPO Robosuite NutAssembly pixels (CNN, frame-stack)").

The reference rendered robosuite camera frames on the host (MuJoCo
offscreen GL) and shipped them through frame-stack wrappers (SURVEY.md
§2.1 obs-wrappers row). The TPU-native answer renders ON DEVICE, like
``jax:pong``: the scene is rasterized from env state with elementwise
masks — jit/vmap/scan-able, so 1000+ pixel envs step and render in HBM
next to the CNN policy with zero host traffic.

Camera model: two orthographic views, each ``RES x RES``:
- channel 0: SIDE view (x right, z up) — the lifting/threading axis;
- channel 1: TOP view (x right, y down) — the tabletop reach plane.
Objects draw at distinct intensities (fingers 255, object 170, peg 110,
table line 60) so a grayscale channel still separates them. The previous
two-view frame is carried in env state and concatenated (pong-style
motion channels), giving obs ``[RES, RES, 4] uint8`` — the frame-stack
role, rendered in-env so no host wrapper is needed on the device path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from surreal_tpu.envs.base import ArraySpec, EnvSpecs
from surreal_tpu.envs.jax.base import JaxEnv
from surreal_tpu.envs.jax.lift import (
    _BLOCK_HALF,
    _PAD_HALF_H,
    _WS_XY,
    _WS_Z_MAX,
    BlockLift,
)
from surreal_tpu.envs.jax.nut_assembly import (
    PEG_HEIGHT,
    PEG_XY,
    NutAssembly,
)

RES = 64
_FINGER_HALF_X = 0.006   # finger pad half-thickness along the travel axis
_FINGER_HALF_Y = 0.010
_PEG_HALF_R = 0.012

# world extents mapped onto the image square
_X_LO, _X_HI = -_WS_XY - 0.02, _WS_XY + 0.02
_Y_LO, _Y_HI = -_WS_XY - 0.02, _WS_XY + 0.02
_Z_LO, _Z_HI = -0.02, _WS_Z_MAX + 0.02


def _axis(lo: float, hi: float) -> jax.Array:
    """Pixel-center world coordinates along one image axis."""
    return lo + (jnp.arange(RES, dtype=jnp.float32) + 0.5) * ((hi - lo) / RES)


def _boxes_view(u, v, boxes) -> jax.Array:
    """Rasterize axis-aligned boxes onto a [RES, RES] uint8 view.

    ``u``/``v``: world coordinates of pixel columns/rows. ``boxes``:
    sequence of (cu, cv, hu, hv, intensity) — center/half-extent along
    each image axis. Overlaps resolve by max intensity.
    """
    img = jnp.zeros((RES, RES), jnp.uint8)
    for cu, cv, hu, hv, val in boxes:
        mask = (jnp.abs(u[None, :] - cu) <= hu) & (jnp.abs(v[:, None] - cv) <= hv)
        img = jnp.maximum(img, jnp.where(mask, jnp.uint8(val), jnp.uint8(0)))
    return img


def _render_hand_scene(hand, extra_side=(), extra_top=()) -> jax.Array:
    """[RES, RES, 2] uint8: side + top orthographic views of the gripper
    and its object, plus per-view extra boxes (e.g. the peg)."""
    xs = _axis(_X_LO, _X_HI)
    ys = _axis(_Y_LO, _Y_HI)
    zs = _axis(_Z_HI, _Z_LO)  # rows top-down: high z at row 0
    gx, gy, gz = hand.grip_pos[0], hand.grip_pos[1], hand.grip_pos[2]
    half_w = hand.grip_width / 2.0
    bx, by, bz = hand.block_pos[0], hand.block_pos[1], hand.block_pos[2]

    side = _boxes_view(
        xs,
        zs,
        [
            # two finger pads straddling the travel axis
            (gx - half_w, gz, _FINGER_HALF_X, _PAD_HALF_H, 255),
            (gx + half_w, gz, _FINGER_HALF_X, _PAD_HALF_H, 255),
            # palm bar joining the fingers
            (gx, gz + _PAD_HALF_H, half_w, _FINGER_HALF_X, 255),
            (bx, bz, _BLOCK_HALF, _BLOCK_HALF, 170),
            # table surface line at z = 0
            (0.0, 0.0, _X_HI, 0.004, 60),
            *extra_side,
        ],
    )
    top = _boxes_view(
        xs,
        ys,
        [
            (gx - half_w, gy, _FINGER_HALF_X, _FINGER_HALF_Y, 255),
            (gx + half_w, gy, _FINGER_HALF_X, _FINGER_HALF_Y, 255),
            (bx, by, _BLOCK_HALF, _BLOCK_HALF, 170),
            *extra_top,
        ],
    )
    return jnp.stack([side, top], axis=-1)


def render_lift(state) -> jax.Array:
    return _render_hand_scene(state)


def render_nut(state) -> jax.Array:
    return _render_hand_scene(
        state.hand,
        extra_side=[(PEG_XY[0], PEG_HEIGHT / 2.0, _PEG_HALF_R, PEG_HEIGHT / 2.0, 110)],
        extra_top=[(PEG_XY[0], PEG_XY[1], _PEG_HALF_R, _PEG_HALF_R, 110)],
    )


class _PixelState(NamedTuple):
    inner: object
    prev: jax.Array  # [RES, RES, 2] previous two-view frame


class _DevicePixels(JaxEnv):
    """Pixel wrapper over a state-obs device env: same dynamics/reward,
    observations become current+previous two-view frames."""

    inner: JaxEnv       # set by subclasses (stateless pure-fn env)
    render = None       # staticmethod(state) -> [RES, RES, 2] uint8

    def reset(self, key: jax.Array):
        s, _ = self.inner.reset(key)
        frame = type(self).render(s)
        return _PixelState(s, frame), jnp.concatenate([frame, frame], axis=-1)

    def step(self, state: _PixelState, action: jax.Array):
        s, _, reward, done, info = self.inner.step(state.inner, action)
        frame = type(self).render(s)
        obs = jnp.concatenate([frame, state.prev], axis=-1)
        return _PixelState(s, frame), obs, reward, done, info


_PIXEL_SPECS = lambda inner: EnvSpecs(  # noqa: E731
    obs=ArraySpec(shape=(RES, RES, 4), dtype=np.dtype(np.uint8), name="pixels"),
    action=inner.specs.action,
)


class BlockLiftPixels(_DevicePixels):
    """Factory name ``jax:lift_pixels``."""

    inner = BlockLift()
    render = staticmethod(render_lift)
    max_episode_steps = BlockLift.max_episode_steps
    specs = _PIXEL_SPECS(BlockLift)


class NutAssemblyPixels(_DevicePixels):
    """Factory name ``jax:nut_pixels`` — BASELINE config ④'s shape."""

    inner = NutAssembly()
    render = staticmethod(render_nut)
    max_episode_steps = NutAssembly.max_episode_steps
    specs = _PIXEL_SPECS(NutAssembly)


# -- eval-video frame rendering ---------------------------------------------

def _views_to_rgb(views, upscale: int = 3):
    """[R, R, 2] two-view uint8 -> side-by-side RGB [R*u, 2*R*u + u, 3]
    (host numpy; per-frame eval-video work, not a device op)."""
    import numpy as np

    v = np.asarray(views)
    sep = np.full((v.shape[0], 1), 40, np.uint8)  # thin divider column
    panel = np.concatenate([v[..., 0], sep, v[..., 1]], axis=1)
    panel = panel.repeat(upscale, axis=0).repeat(upscale, axis=1)
    return np.stack([panel] * 3, axis=-1)


def frame_renderer(env):
    """Optional eval-video renderer for a device env: returns
    ``state -> [H, W, 3] uint8`` or None when the env has no visual form
    (the reference recorded eval videos via VideoWrapper; device envs
    render from state instead of a GL context)."""
    from surreal_tpu.envs.jax.pong import Pong

    if isinstance(env, _DevicePixels):
        render = jax.jit(type(env).render)  # one dispatch per frame, not per op
        return lambda s: _views_to_rgb(render(s.inner))
    if isinstance(env, BlockLift):
        render = jax.jit(render_lift)
        return lambda s: _views_to_rgb(render(s))
    if isinstance(env, NutAssembly):
        render = jax.jit(render_nut)
        return lambda s: _views_to_rgb(render(s))
    if isinstance(env, Pong):
        import numpy as np

        def pong_frame(s):
            f = np.asarray(s.history[..., 0]).repeat(4, axis=0).repeat(4, axis=1)
            return np.stack([f] * 3, axis=-1)

        return pong_frame
    return None
