"""On-device pixel Pong (BASELINE config ⑤'s workload class: "IMPALA/V-trace
256-env Atari Pong"). The ALE and its ROMs are not in this image (SURVEY.md
§7 flagged this), so — consistent with the BlockLifting answer in
``lift.py`` — the TPU-native substitute is the game itself re-implemented
as a pure-JAX functional env: paddle-vs-paddle Pong with PIXEL
observations rendered on device, jit/vmap/scan-able, so 256+ envs step in
HBM next to the CNN policy.

Game (Atari-Pong-shaped):
- Court is the unit square; the agent's paddle is the LEFT edge, a
  tracking opponent (capped speed, slightly slower than the ball) is the
  RIGHT edge. Actions: Discrete(3) = stay / up / down.
- Ball bounces off top/bottom walls and paddles; paddle hits deflect the
  ball with a vertical angle proportional to the hit offset (classic Pong
  control surface), and speed up slightly toward a cap.
- A miss scores the point: reward +1 when the opponent misses, -1 when
  the agent misses; the ball re-serves toward the scored-against side.
  Like Atari Pong the episode runs many points; it ends by time limit
  (AutoReset truncation) or when either side reaches 21
  (``info['score']`` tracks agent minus opponent).

Observation: [res, res, stack] uint8 pixels — channel k is the frame k
steps back (paddles + ball as bright blocks), giving the CNN the motion
information Atari setups get from frame-stacking (rendered in-env, so no
host wrapper is needed on the device path). ``jax:pong84`` is the
published shape, [84, 84, 4] (Mnih et al. 2015: the input the Nature CNN
was sized for, 3136 -> 512 at its dense layer); ``jax:pong`` is
[42, 42, 2] and ``jax:pong16`` [16, 16, 2], the same game rendered
smaller for runs and tests where the CNN's cost is in the way.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from surreal_tpu.envs.base import ArraySpec, DiscreteSpec, EnvSpecs
from surreal_tpu.envs.jax.base import JaxEnv

_RES = 42                 # render resolution (square)
_PADDLE_HALF = 0.08       # paddle half-height (court units)
_PADDLE_SPEED = 0.04      # agent paddle speed per step
_OPP_SPEED = 0.03         # opponent tracking speed (beatable: < ball |vy| cap)
_BALL_SPEED0 = 0.03       # serve speed
_BALL_SPEED_MAX = 0.06
_SPEEDUP = 1.05           # per paddle hit
_AGENT_X = 0.04           # paddle plane x positions
_OPP_X = 0.96
_DEFLECT = 0.04           # max |vy| added by hit offset
_WIN_SCORE = 21


class PongState(NamedTuple):
    ball: jax.Array        # [2] position
    vel: jax.Array         # [2] velocity
    agent_y: jax.Array     # [] agent paddle center
    opp_y: jax.Array       # [] opponent paddle center
    agent_score: jax.Array # [] int32 points won by the agent
    opp_score: jax.Array   # [] int32 points won by the opponent
    history: jax.Array     # [res, res, stack - 1] uint8, newest first
    key: jax.Array         # serve randomness


def _serve(key: jax.Array, toward_agent: jax.Array):
    """Ball from center toward the scored-against side, random angle."""
    vy = jax.random.uniform(key, (), jnp.float32, -0.02, 0.02)
    vx = jnp.where(toward_agent, -_BALL_SPEED0, _BALL_SPEED0)
    return jnp.asarray([0.5, 0.5], jnp.float32), jnp.stack([vx, vy])


def _render(ball, agent_y, opp_y, res: int = _RES) -> jax.Array:
    """[res, res] uint8 frame: rows = y (top=0), cols = x. The court is
    normalized, so resolution is render-only — the 16x16 variant plays the
    identical game."""
    grid = (jnp.arange(res, dtype=jnp.float32) + 0.5) / res
    ys = grid[:, None]  # [R, 1]
    xs = grid[None, :]  # [1, R]
    cell = 1.0 / res
    ball_px = (jnp.abs(ys - ball[1]) <= cell) & (jnp.abs(xs - ball[0]) <= cell)
    agent_px = (jnp.abs(ys - agent_y) <= _PADDLE_HALF) & (
        jnp.abs(xs - _AGENT_X) <= cell
    )
    opp_px = (jnp.abs(ys - opp_y) <= _PADDLE_HALF) & (jnp.abs(xs - _OPP_X) <= cell)
    return jnp.where(ball_px | agent_px | opp_px, 255, 0).astype(jnp.uint8)


def _specs(res: int, stack: int) -> EnvSpecs:
    return EnvSpecs(
        obs=ArraySpec(shape=(res, res, stack), dtype=np.dtype(np.uint8), name="pixels"),
        action=DiscreteSpec(shape=(), dtype=np.dtype(np.int32), name="action", n=3),
    )


class Pong(JaxEnv):
    max_episode_steps = 2048
    res = _RES  # render resolution; physics is resolution-independent
    stack = 2   # frames in the observation; the state keeps stack - 1

    specs = _specs(res, stack)

    def reset(self, key: jax.Array):
        key, serve_key, side_key = jax.random.split(key, 3)
        ball, vel = _serve(serve_key, jax.random.bernoulli(side_key))
        frame = _render(ball, 0.5, 0.5, self.res)
        state = PongState(
            ball=ball,
            vel=vel,
            agent_y=jnp.asarray(0.5, jnp.float32),
            opp_y=jnp.asarray(0.5, jnp.float32),
            agent_score=jnp.zeros((), jnp.int32),
            opp_score=jnp.zeros((), jnp.int32),
            # before the first step every frame back is the first one
            history=jnp.repeat(frame[..., None], self.stack - 1, axis=-1),
            key=key,
        )
        return state, jnp.repeat(frame[..., None], self.stack, axis=-1)

    def step(self, state: PongState, action: jax.Array):
        # paddles
        move = jnp.asarray([0.0, -_PADDLE_SPEED, _PADDLE_SPEED], jnp.float32)[action]
        agent_y = jnp.clip(state.agent_y + move, _PADDLE_HALF, 1.0 - _PADDLE_HALF)
        opp_y = jnp.clip(
            state.opp_y
            + jnp.clip(state.ball[1] - state.opp_y, -_OPP_SPEED, _OPP_SPEED),
            _PADDLE_HALF,
            1.0 - _PADDLE_HALF,
        )

        # ball flight + wall bounce
        ball = state.ball + state.vel
        vy = jnp.where((ball[1] < 0.0) | (ball[1] > 1.0), -state.vel[1], state.vel[1])
        ball = ball.at[1].set(jnp.clip(ball[1], 0.0, 1.0))
        vel = state.vel.at[1].set(vy)

        def paddle_bounce(ball, vel, paddle_y, plane_x, left: bool):
            # `left` is a STATIC side selector (which paddle); the traced
            # part is whether the ball is moving toward that side
            toward = (vel[0] < 0) if left else (vel[0] > 0)
            plane = (ball[0] <= plane_x) if left else (ball[0] >= plane_x)
            crossed = plane & toward
            hit = crossed & (jnp.abs(ball[1] - paddle_y) <= _PADDLE_HALF)
            offset = (ball[1] - paddle_y) / _PADDLE_HALF  # [-1, 1]
            speed = jnp.minimum(jnp.abs(vel[0]) * _SPEEDUP, _BALL_SPEED_MAX)
            new_vx = speed if left else -speed
            # vy capped like vx: without the clamp, deflections random-walk
            # |vy| up within a rally, and the opponent's beatability rests
            # on its tracking speed staying below this cap
            new_vy = jnp.clip(
                vel[1] + offset * _DEFLECT, -_BALL_SPEED_MAX, _BALL_SPEED_MAX
            )
            new_vel = jnp.stack([new_vx, new_vy])
            vel = jnp.where(hit, new_vel, vel)
            ball = jnp.where(hit, ball.at[0].set(plane_x), ball)
            return ball, vel, hit, crossed

        ball, vel, hit_a, crossed_a = paddle_bounce(ball, vel, agent_y, _AGENT_X, True)
        ball, vel, hit_o, crossed_o = paddle_bounce(ball, vel, opp_y, _OPP_X, False)
        agent_missed = crossed_a & ~hit_a
        opp_missed = crossed_o & ~hit_o
        reward = jnp.where(
            opp_missed, 1.0, jnp.where(agent_missed, -1.0, 0.0)
        ).astype(jnp.float32)
        agent_score = state.agent_score + opp_missed.astype(jnp.int32)
        opp_score = state.opp_score + agent_missed.astype(jnp.int32)

        # re-serve after a point, toward whoever was scored against
        key, serve_key = jax.random.split(state.key)
        serve_ball, serve_vel = _serve(serve_key, agent_missed)
        point = agent_missed | opp_missed
        ball = jnp.where(point, serve_ball, ball)
        vel = jnp.where(point, serve_vel, vel)

        frame = _render(ball, agent_y, opp_y, self.res)
        obs = jnp.concatenate([frame[..., None], state.history], axis=-1)
        new_state = PongState(
            ball=ball,
            vel=vel,
            agent_y=agent_y,
            opp_y=opp_y,
            agent_score=agent_score,
            opp_score=opp_score,
            history=obs[..., :-1],
            key=key,
        )
        # like Atari Pong: game over when EITHER side reaches 21 points
        done = (agent_score >= _WIN_SCORE) | (opp_score >= _WIN_SCORE)
        info = {"score": agent_score - opp_score, "point": point}
        return new_state, obs, reward, done, info


class PongSmall(Pong):
    """16x16 Pong (``jax:pong16``): the same court, physics, and opponent —
    resolution is render-only — at a size whose CNN forward is cheap enough
    for the CPU-sim suite to LEARN on (the in-suite pixel-learning guard,
    round-3 VERDICT missing #5; the real-chip result is ``jax:pong84``'s,
    the published shape)."""

    res = 16
    specs = _specs(res, Pong.stack)


class Pong84(Pong):
    """84x84x4 Pong (``jax:pong84``): the same court, physics and opponent
    at the input the Nature CNN was published for (Mnih et al. 2015: 84 x
    84 pixels, the last four frames), so the CNN's defaults give the
    paper's 3136 -> 512 dense layer. What the benchmark's IMPALA cell runs."""

    res = 84
    stack = 4
    specs = _specs(res, stack)
