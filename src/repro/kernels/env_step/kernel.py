"""Batched environment physics substep as a Pallas TPU kernel.

THE paper's hot loop, TPU-adapted: EnvPool's C++ worker threads each step
one env; here a (block_n, 28) tile of env states is resident in VMEM and
the whole substep — joint dynamics, contact model, integration, reward —
runs as 8-lane-wide VPU arithmetic, ``num_envs/block_n`` grid steps.  The
multi-substep loop (``n_sub``) runs inside the kernel so intermediate
states never touch HBM: per agent-step traffic is exactly one state tile
read + one write (the paper's zero-copy StateBufferQueue property, now at
the register level).

Per-lane cost masking (``cost``): MuJoCo's solver cost is data-dependent
(contacts add iterations), so a batch of envs needs lane ``n`` to run
exactly ``cost[n]`` substeps.  The kernel loops ``n_sub = max_cost``
iterations and freezes finished lanes with selects — the same semantics
JAX gives a vmapped per-lane ``while_loop``, so results are
bitwise-identical to the per-lane engine path, but with one fused kernel
launch per agent step instead of a lane-strided loop.

Layout note: state is SoA (N, 28) with the 28 physics scalars in the minor
(lane) dim; per-lane scalars (cost, reward) are (N, 1) columns.
The physics op order matches ``MujocoLike.substep`` exactly (the contact
model reads the PRE-update joint state) — see ref.py for the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.env_step.ref import _substep_core


def _env_kernel(state_ref, action_ref, out_ref, reward_ref, *, n_sub: int):
    """Uniform-cost variant: every lane runs all ``n_sub`` substeps."""
    s = state_ref[...].astype(jnp.float32)        # (block_n, 28)
    a = jnp.clip(action_ref[...].astype(jnp.float32), -1.0, 1.0)

    pos = s[:, 0:3]
    vel = s[:, 3:6]
    rot = s[:, 6:9]
    ang = s[:, 9:12]
    q = s[:, 12:20]
    qd = s[:, 20:28]
    reward = jnp.zeros((s.shape[0], 1), jnp.float32)

    def body(_, carry):
        pos, vel, rot, ang, q, qd, reward = carry
        pos, vel, rot, ang, q, qd, fwd, ctrl, alive = _substep_core(
            pos, vel, rot, ang, q, qd, a
        )
        return pos, vel, rot, ang, q, qd, ((reward + fwd) - ctrl) + alive

    # a rolled loop: unrolling n_sub substeps multiplies compile time
    pos, vel, rot, ang, q, qd, reward = lax.fori_loop(
        0, n_sub, body, (pos, vel, rot, ang, q, qd, reward)
    )

    out_ref[...] = jnp.concatenate([pos, vel, rot, ang, q, qd], axis=-1).astype(
        out_ref.dtype
    )
    reward_ref[...] = reward.astype(reward_ref.dtype)


def _env_kernel_masked(state_ref, action_ref, cost_ref, reward_in_ref,
                       out_ref, reward_ref, *, n_sub: int):
    """Per-lane cost variant: lane ``n`` advances ``cost[n] <= n_sub``
    substeps; finished lanes are frozen by selects (vmapped-while
    semantics, bitwise).  The reward accumulator is seeded from
    ``reward_in_ref`` (the env's ``reward_acc``) so the in-kernel
    accumulation ``((acc + fwd) - ctrl) + alive`` matches the env
    class's float association exactly."""
    s = state_ref[...].astype(jnp.float32)        # (block_n, 28)
    a = jnp.clip(action_ref[...].astype(jnp.float32), -1.0, 1.0)
    cost = cost_ref[...].astype(jnp.int32)        # (block_n, 1)

    pos = s[:, 0:3]
    vel = s[:, 3:6]
    rot = s[:, 6:9]
    ang = s[:, 9:12]
    q = s[:, 12:20]
    qd = s[:, 20:28]
    reward = reward_in_ref[...].astype(jnp.float32)   # (block_n, 1)

    def body(i, carry):
        pos, vel, rot, ang, q, qd, reward = carry
        *new, fwd, ctrl, alive = _substep_core(pos, vel, rot, ang, q, qd, a)
        new.append(((reward + fwd) - ctrl) + alive)
        m = i < cost                              # (block_n, 1) lane mask
        return tuple(jnp.where(m, n, o) for n, o in zip(new, carry))

    # n_sub = spec.max_cost; a rolled loop keeps compile time flat in it
    pos, vel, rot, ang, q, qd, reward = lax.fori_loop(
        0, n_sub, body, (pos, vel, rot, ang, q, qd, reward)
    )

    out_ref[...] = jnp.concatenate([pos, vel, rot, ang, q, qd], axis=-1).astype(
        out_ref.dtype
    )
    reward_ref[...] = reward.astype(reward_ref.dtype)


def env_substep_batch(
    state: jnp.ndarray,    # (N, 28)
    action: jnp.ndarray,   # (N, 8)
    cost: jnp.ndarray | None = None,   # (N,) int32 per-lane substep count
    reward0: jnp.ndarray | None = None,  # (N,) f32 accumulator seed
    *,
    n_sub: int = 1,
    block_n: int = 256,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused batched substeps.  With ``cost=None`` every lane runs
    ``n_sub`` substeps; with a ``cost`` vector, lane ``n`` runs
    ``cost[n]`` (callers pass ``n_sub = spec.max_cost``) and the reward
    output continues accumulating from ``reward0`` (default zeros).

    A batch of at most ``block_n`` lanes is one block; a larger one that
    ``block_n`` does not divide is padded up to a multiple of it (padded
    lanes are computed and dropped), so any served block size runs."""
    N = state.shape[0]
    block_n = min(block_n, N)
    n_pad = -N % block_n

    def rows(width):
        return pl.BlockSpec((block_n, width), lambda i: (i, 0))

    args, in_specs = [state, action], [rows(28), rows(8)]
    if cost is None:
        kernel = functools.partial(_env_kernel, n_sub=n_sub)
    else:
        if reward0 is None:
            reward0 = jnp.zeros((N,), jnp.float32)
        kernel = functools.partial(_env_kernel_masked, n_sub=n_sub)
        # per-lane scalars travel as (N, 1) columns: a rank-1 (block_n,)
        # block must match the array's 128- or 1024-wide XLA tiling
        args += [cost.astype(jnp.int32)[:, None],
                 reward0.astype(jnp.float32)[:, None]]
        in_specs += [rows(1), rows(1)]
    Np = N + n_pad
    out, reward = pl.pallas_call(
        kernel,
        grid=(Np // block_n,),
        in_specs=in_specs,
        out_specs=[rows(28), rows(1)],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 28), state.dtype),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*[jnp.pad(x, ((0, n_pad), (0, 0))) for x in args])
    return out[:N], reward[:N, 0]
