"""Pure-jnp oracle for the batched env physics substep kernel.

This is exactly MujocoLike.substep vmapped over a flat state layout —
the oracle the kernel must match bit-for-bit in f32.  The op *order*
matters: the contact model (foot height, contact set, thrust/normal
forces) reads the PRE-update joint state, exactly as
``MujocoLike.substep`` does, so the batched-native engine path is
bitwise-identical to the per-lane ``vmap(env.step)`` path
(tests/test_conformance.py::test_batched_native_matches_vmap_lifted).

``env_multi_substep_reference`` is the CPU fallback for the fused
multi-substep hot loop: one ``lax.while_loop`` over the whole (N, 28)
state block with per-lane cost masking — the same select semantics JAX
gives a vmapped per-lane ``while_loop``, so results are bitwise equal,
but without materializing per-lane loop carries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

N_JOINTS = 8
DT = 0.01
STATE_DIM = 28  # pos(3) + vel(3) + rot(3) + ang(3) + q(8) + qd(8)


def pack_state(pos, vel, rot, ang, q, qd) -> jnp.ndarray:
    """(..., 3+3+3+3+8+8=28) flat state."""
    return jnp.concatenate([pos, vel, rot, ang, q, qd], axis=-1)


def unpack_state(s):
    return s[..., 0:3], s[..., 3:6], s[..., 6:9], s[..., 9:12], s[..., 12:20], s[..., 20:28]


def _legs(x, joint: int):
    """``x[..., joint::2]`` (one joint of each of the 4 legs) built from
    unit lane slices: Mosaic has no lowering for a strided lane slice
    (it becomes a gather), and the values are the same either way."""
    return jnp.concatenate(
        [x[..., 2 * leg + joint:2 * leg + joint + 1] for leg in range(4)],
        axis=-1,
    )


def _substep_core(pos, vel, rot, ang, q, qd, a):
    """One physics substep on unpacked (..., k) components.

    THE single definition of the batched physics body: the jnp
    reference, the fused multi-substep, and the Pallas kernel
    (kernel.py) all call this, so kernel-vs-oracle bitwise identity
    cannot drift through parallel edits.  Everything here must stay
    Mosaic-lowerable (elementwise / concatenate / keepdims minor-axis
    reduce; no scatter, no strided slice, no rank-1 intermediate) and
    shape-polymorphic over (..., k).

    Mirrors MujocoLike.substep op-for-op (contact model reads the old
    state; reward term association matches ``reward_acc + fwd - ctrl +
    alive``).  Returns the new components plus this substep's reward
    contribution terms (fwd, ctrl as (..., 1); alive a scalar) so callers
    can accumulate with the exact association the env class uses.
    """
    # contact model: PRE-update joint state (MujocoLike.substep order)
    hip, knee = _legs(q, 0), _legs(q, 1)
    foot_h = pos[..., 2:3] - (0.2 * jnp.cos(hip) + 0.2 * jnp.cos(hip + knee))
    contact = (foot_h < 0.05).astype(jnp.float32)
    hip_vel = _legs(qd, 0)
    thrust = jnp.sum(contact * (-hip_vel), axis=-1, keepdims=True) * 0.08
    normal = jnp.sum(contact * jnp.maximum(0.05 - foot_h, 0.0), axis=-1,
                     keepdims=True) * 120.0

    # joint dynamics: torque − spring − damping
    qdd = 18.0 * a - 4.0 * q - 1.2 * qd
    qd = qd + DT * qdd
    q = jnp.clip(q + DT * qd, -1.2, 1.2)

    acc = jnp.concatenate(
        [thrust, jnp.zeros_like(thrust), -9.81 + normal], axis=-1
    )
    vel = (vel + DT * acc) * 0.995
    pos = pos + DT * vel
    pos = jnp.concatenate(
        [pos[..., :2], jnp.maximum(pos[..., 2:3], 0.1)], axis=-1
    )

    asym = (contact[..., 0:1] + contact[..., 1:2] - contact[..., 2:3]
            - contact[..., 3:4])
    ang = (ang + DT * jnp.concatenate(
        [0.4 * asym, 0.2 * asym, jnp.zeros_like(asym)], axis=-1
    )) * 0.98
    rot = rot + DT * ang

    fwd = vel[..., 0:1] * DT * 20
    ctrl = 0.5 * jnp.sum(a**2, axis=-1, keepdims=True) * DT
    alive = 1.0 * DT
    return pos, vel, rot, ang, q, qd, fwd, ctrl, alive


def env_substep_reference(state: jnp.ndarray, action: jnp.ndarray
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """state: (N, 28), action: (N, 8) -> (new_state, reward (N,))."""
    pos, vel, rot, ang, q, qd = unpack_state(state.astype(jnp.float32))
    a = jnp.clip(action.astype(jnp.float32), -1.0, 1.0)
    pos, vel, rot, ang, q, qd, fwd, ctrl, alive = _substep_core(
        pos, vel, rot, ang, q, qd, a
    )
    reward = fwd - ctrl + alive
    return pack_state(pos, vel, rot, ang, q, qd), reward[..., 0]


def env_multi_substep_reference(
    state: jnp.ndarray,     # (N, 28)
    action: jnp.ndarray,    # (N, 8)
    cost: jnp.ndarray,      # (N,) int32: substeps to run per lane
    reward0: jnp.ndarray | None = None,   # (N,) f32 accumulator seed
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused multi-substep with per-lane cost masking (CPU hot path).

    Lane ``n`` advances exactly ``cost[n]`` substeps; the reward
    accumulator is seeded with ``reward0`` (the env's ``reward_acc``)
    and updated with the env class's association ``((acc + fwd) - ctrl)
    + alive``, so the result is bitwise-identical to per-lane iterated
    ``MujocoLike.substep``.
    """
    state = state.astype(jnp.float32)
    a = jnp.clip(action.astype(jnp.float32), -1.0, 1.0)
    cost = cost.astype(jnp.int32)
    if reward0 is None:
        reward0 = jnp.zeros(state.shape[:-1], jnp.float32)
    trip = jnp.max(cost)

    def cond(carry):
        return carry[0] < trip

    def body(carry):
        i, s, r = carry
        pos, vel, rot, ang, q, qd = unpack_state(s)
        pos, vel, rot, ang, q, qd, fwd, ctrl, alive = _substep_core(
            pos, vel, rot, ang, q, qd, a
        )
        new_s = pack_state(pos, vel, rot, ang, q, qd)
        new_r = ((r + fwd) - ctrl) + alive
        m = (i < cost)[:, None]
        s = jnp.where(m, new_s, s)
        r = jnp.where(m, new_r, r)
        return i + 1, s, r

    _, state, reward = lax.while_loop(
        cond, body,
        (jnp.int32(0), state, reward0.astype(jnp.float32)[:, None]),
    )
    return state, reward[:, 0]
