"""Plain reference of the ``ant_lite`` configuration: one agent step of
the repo's ant-lite locomotion task (its stand-in for MuJoCo Ant-v3),
from the served observation.

The served observation holds the whole physical state the dynamics
read: torso height, orientation, joint angles, torso and angular
velocities, joint velocities (the torso's x and y enter nothing).  So
one step can be checked by itself: rebuild the state from the
observation a lane was served, apply the action it was sent, run the
step's substeps, and compare the next observation and reward the pool
served for that lane.

Dynamics (semi-implicit Euler, ``DT`` = 0.01, per substep): joint torque
``18 a`` against a spring ``4 q`` and damping ``1.2 qd``; each foot whose
height is under 0.05 is in contact, pushes the torso forward by its hip
velocity and up by its depth; gravity, viscous damping 0.995; contact
asymmetry tilts the torso.  Reward per substep: forward velocity x 0.2,
minus 0.005 |a|^2, plus 0.01 alive.  A step runs 5 substeps plus one
per foot in contact, times the episode's solver multiplier (1, or
``heavy_iters`` for a heavy scene).  The episode terminates when the
torso leaves (0.2, 1.0) in height or tilts by 1 rad or more.

``dtype`` is float32 for the reference; bfloat16 gives the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DT = 0.01
OBS_DIM = 29
N_JOINTS = 8
BASE_COST = 5
RESET_Z = 0.55


def unpack(obs):
    """Observation -> (z, rot, q, vel, ang, qd)."""
    return (obs[..., 0:1], obs[..., 1:4], obs[..., 4:12], obs[..., 12:15],
            obs[..., 15:18], obs[..., 18:26])


def foot_height(z, q):
    hip, knee = q[..., 0::2], q[..., 1::2]
    drop = 0.2 * jnp.cos(hip) + 0.2 * jnp.cos(hip + knee)
    return z - drop


def _substep(carry, a, dt):
    z, vel, rot, ang, q, qd, rew, _ = carry
    t = lambda v: jnp.asarray(v, z.dtype)  # noqa: E731
    qdd = t(18.0) * a - t(4.0) * q - t(1.2) * qd
    qd2 = qd + dt * qdd
    q2 = jnp.clip(q + dt * qd2, t(-1.2), t(1.2))
    fh = foot_height(z, q)
    contact = (fh < t(0.05)).astype(z.dtype)
    thrust = jnp.sum(contact * (-qd[..., 0::2]), -1, keepdims=True) * t(0.08)
    normal = jnp.sum(contact * jnp.maximum(t(0.05) - fh, t(0.0)), -1,
                     keepdims=True) * t(120.0)
    zero = jnp.zeros_like(thrust)
    acc = jnp.concatenate([thrust, zero, t(-9.81) + normal], -1)
    vel2 = (vel + dt * acc) * t(0.995)
    z2 = jnp.maximum(z + dt * vel2[..., 2:3], t(0.1))
    asym = (contact[..., 0:1] + contact[..., 1:2] - contact[..., 2:3]
            - contact[..., 3:4])
    dang = jnp.concatenate([t(0.4) * asym, t(0.2) * asym, zero], -1)
    ang2 = (ang + dt * dang) * t(0.98)
    rot2 = rot + dt * ang2
    fwd = vel2[..., 0:1]
    ctrl = t(0.5) * jnp.sum(a * a, -1, keepdims=True) * dt
    rew2 = ((rew + fwd * dt * t(20.0)) - ctrl) + dt
    near = jnp.min(jnp.abs(fh - t(0.05)), -1, keepdims=True)
    return z2, vel2, rot2, ang2, q2, qd2, rew2, near


def step(obs, action, cost, max_cost: int, dtype=jnp.float32):
    """Run ``cost[i]`` substeps from each observation ``obs[i]`` under
    ``action[i]``.  Returns ``(next_obs, reward, terminated, margin,
    contact_margin)``, float32: ``margin`` is the distance of the
    terminal test's quantities from their thresholds, ``contact_margin``
    the least distance of a foot's height from the contact threshold
    over the step (a foot within rounding of it may be in contact on one
    side and not on the other, which changes the step)."""
    obs = jnp.asarray(obs).astype(dtype)
    a = jnp.clip(jnp.asarray(action).astype(dtype), -1.0, 1.0)
    cost = jnp.asarray(cost, jnp.int32)[:, None]
    z, rot, q, vel, ang, qd = unpack(obs)
    dt = jnp.asarray(DT, dtype)
    big = jnp.full_like(z, 1.0)
    carry = (z, vel, rot, ang, q, qd, jnp.zeros_like(z), big)

    def body(i, c):
        new = _substep(c, a, dt)
        new = new[:-1] + (jnp.minimum(c[-1], new[-1]),)
        return tuple(jnp.where(i < cost, n, o) for n, o in zip(new, c))

    z, vel, rot, ang, q, qd, rew, near = jax.lax.fori_loop(
        0, max_cost, body, carry)
    fh = foot_height(z, q)
    near = jnp.minimum(near, jnp.min(jnp.abs(fh - 0.05), -1, keepdims=True))
    nxt = jnp.concatenate([
        z, rot, q, vel, ang, qd,
        jnp.sum(fh < 0.05, -1, keepdims=True).astype(dtype),
        jnp.min(fh, -1, keepdims=True), jnp.max(fh, -1, keepdims=True),
    ], -1).astype(jnp.float32)
    z32 = z[..., 0].astype(jnp.float32)
    tilt = jnp.max(jnp.abs(rot.astype(jnp.float32)), -1)
    healthy = (z32 > 0.2) & (z32 < 1.0) & (tilt < 1.0)
    margin = jnp.minimum(jnp.minimum(jnp.abs(z32 - 0.2), jnp.abs(z32 - 1.0)),
                         jnp.abs(tilt - 1.0))
    return (nxt, rew[..., 0].astype(jnp.float32), ~healthy, margin,
            near[..., 0].astype(jnp.float32))


def is_reset_obs(obs: np.ndarray) -> np.ndarray:
    """True where an observation is a fresh episode's: torso at rest at
    its start height, joints within 0.1 rad."""
    obs = np.asarray(obs)
    z, rot, q, vel, ang, qd = unpack(obs)
    return ((z[..., 0] == np.float32(RESET_Z))
            & np.all(rot == 0, -1) & np.all(vel == 0, -1)
            & np.all(ang == 0, -1) & np.all(np.abs(q) <= 0.1, -1))
