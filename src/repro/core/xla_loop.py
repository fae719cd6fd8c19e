"""Rollout collection over the ``EnvPool`` protocol (paper Appendix E).

The paper exposes ``handle, recv, send, step = env.xla()`` so the whole
collect loop lowers into XLA and runs free of the Python GIL.  For the
mesh engine (``core/engine.py``) the pool already lives on-device, so
the actor loop is a single donated-buffer ``lax.scan`` — the logical
conclusion of Appendix E: *zero* host round-trips, the ``PoolState``
stays sharded across the mesh for the whole rollout, and donation lets
XLA reuse the SoA env buffers in place.

``build_collect_fn`` is engine-agnostic: functional engines get the
jitted ``lax.scan`` body; host engines (thread / forloop / subprocess)
get a numpy driver with the SAME signature and the same stacked
``(num_steps, batch, ...)`` trajectory layout, so benchmarks and
training code run unchanged across all five engines.

``build_pipelined_collect_fn`` is the double-buffer sibling: the same
donated scan, but returning a flat *rollout dict* (obs / actions /
behavior logp / rewards / dones / episode returns / bootstrap obs) —
the hand-off layout ``rl/ppo.py::train_pipelined`` dispatches
concurrently with the learner's update program, and the device twin of
the ``StateBufferQueue`` block layout the host pipeline streams.  Its
``policy_fn`` must return ``(actions, logp)``: the behavior log-prob is
recorded at collect time so the one-step-stale rollout can be V-trace
corrected (``rl/vtrace.py``) by the learner.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.engine import PoolState
from repro.core.protocol import EnvPool, is_functional, to_timestep
from repro.core.specs import TimeStep
from repro.obs.trace import COLLECT, POLICY
from repro.utils.pytree import tree_stack

# any object satisfying core.protocol.EnvPool (kept loose for typing)
DevicePool = Any


def collect_init(pool: EnvPool, key: jax.Array):
    """Engine-agnostic reset: ``(carry, first TimeStep)``.  ``carry`` is
    the PoolState for functional engines, None for host engines."""
    if is_functional(pool):
        return pool.reset(key)
    if hasattr(pool, "async_reset") and pool.batch_size < pool.num_envs:
        pool.async_reset()
        return None, to_timestep(pool.recv())
    return None, to_timestep(pool.reset())


def build_collect_fn(
    pool: EnvPool,
    policy_fn: Callable[[Any, Any, jax.Array], Any],
    num_steps: int,
    donate: bool = True,
):
    """Returns ``collect(ps, policy_params, last_ts, key) ->
    (ps, last_ts, trajectory, actions)`` where trajectory stacks
    ``num_steps`` TimeStep batches of size ``batch_size``.

    Functional engines: one jitted ``lax.scan`` (``ps`` is the
    PoolState).  Host engines: a numpy loop with the same signature
    (``ps`` is ignored and returned as None).

    ``policy_fn(params, obs, key) -> actions`` must be jit-traceable
    for the functional path.
    """
    if is_functional(pool):
        def one_step(carry, key):
            ps, ts, params = carry
            with jax.named_scope(POLICY):
                actions = policy_fn(params, ts.obs, key)
            ps, new_ts = pool.step(ps, actions, ts.env_id)
            return (ps, new_ts, params), (ts, actions)

        @jax.named_scope(COLLECT)
        def collect(ps: PoolState, params: Any, last_ts: TimeStep,
                    key: jax.Array):
            keys = jax.random.split(key, num_steps)
            (ps, last_ts, _), (traj, acts) = lax.scan(
                one_step, (ps, last_ts, params), keys
            )
            return ps, last_ts, traj, acts

        kwargs = {"donate_argnums": (0,)} if donate else {}
        return jax.jit(collect, **kwargs)

    def collect_host(ps: Any, params: Any, last_ts: TimeStep, key: jax.Array):
        ts = to_timestep(last_ts)
        steps, acts = [], []
        for k in jax.random.split(key, num_steps):
            actions = policy_fn(params, jnp.asarray(ts.obs), k)
            steps.append(ts)
            acts.append(jnp.asarray(actions))
            out = pool.step(np.asarray(actions), np.asarray(ts.env_id))
            ts = to_timestep(out)
        traj = tree_stack([
            jax.tree.map(jnp.asarray, s) for s in steps
        ])
        return None, ts, traj, jnp.stack(acts)

    return collect_host


def build_pipelined_collect_fn(
    pool: EnvPool,
    policy_fn: Callable[[Any, Any, jax.Array], tuple[Any, Any]],
    num_steps: int,
    donate: bool = True,
):
    """Returns ``collect(ps, params, last_ts, key) -> (ps, last_ts,
    rollout)`` — the collect half of the pipelined driver.

    ``rollout`` is a flat dict of stacked ``(num_steps, batch, ...)``
    leaves: ``obs``, ``actions``, ``logp`` (the BEHAVIOR policy's
    log-prob, recorded at collect time), ``rewards``, ``dones``,
    ``ep_ret``, plus ``last_obs`` ``(batch, ...)`` for the learner's
    bootstrap value.  ``policy_fn(params, obs, key) -> (actions, logp)``
    must be jit-traceable.

    ``ps`` and ``last_ts`` are donated by default: the driver dispatches
    one ``collect`` per iteration and carries both forward, so XLA
    reuses the SoA env buffers in place exactly like the fused path —
    the rollout itself is a FRESH buffer each call, which is what lets
    two of them be in flight at once (double buffering)."""
    if not is_functional(pool):
        raise ValueError("build_pipelined_collect_fn needs a functional "
                         "(device-family) engine")

    def one_step(carry, key):
        ps, ts, params = carry
        actions, logp = policy_fn(params, ts.obs, key)
        ps, new_ts = pool.step(ps, actions, ts.env_id)
        data = {
            "obs": ts.obs, "actions": actions, "logp": logp,
            "rewards": new_ts.reward, "dones": new_ts.done,
            "ep_ret": new_ts.episode_return,
        }
        return (ps, new_ts, params), data

    def collect(ps: PoolState, params: Any, last_ts: TimeStep,
                key: jax.Array):
        keys = jax.random.split(key, num_steps)
        (ps, last_ts, _), rollout = lax.scan(
            one_step, (ps, last_ts, params), keys
        )
        rollout["last_obs"] = last_ts.obs
        return ps, last_ts, rollout

    kwargs = {"donate_argnums": (0, 2)} if donate else {}
    return jax.jit(collect, **kwargs)


def build_random_collect_fn(pool: DevicePool, num_steps: int):
    """Random-action collect loop — the paper's pure-simulation benchmark
    (§4.1: "randomly sampled actions as inputs")."""

    spec = pool.spec

    def policy(params, obs, key):
        del params, obs
        return spec.act_spec.sample_jax(key, (pool.batch_size,))

    return build_collect_fn(pool, policy, num_steps)


def frames_per_batch(pool: DevicePool) -> int:
    """Frames produced by one recv: batch_size steps × frameskip
    (paper counts Atari FPS with frameskip 4, MuJoCo with 5 substeps)."""
    return pool.batch_size * pool.spec.min_cost
