"""Pure-JAX environment interface.

Every environment is a pure-function state machine so it can be ``vmap``-ed
into the SIMD lanes that replace EnvPool's worker threads (DESIGN.md §2.1).

The cost model is first-class: ``step_cost(state, action)`` returns the
data-dependent number of work units (substeps) the next step will consume.
EnvPool's asynchronous scheduler exploits exactly this variability — on the
CPU original, slow steps make threads finish late; here they make lanes
run more ``substep`` iterations.  The engines use ``step_cost`` for
shortest-job-first top-M selection (paper §3.3's long-tail avoidance).
"""

from __future__ import annotations

from typing import Any, TypeVar

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.specs import EnvSpec, TimeStep

S = TypeVar("S")


class Environment:
    """Base class. Subclasses implement the five primitive methods."""

    spec: EnvSpec

    # ------------------------------------------------------------------ #
    # primitives to implement
    # ------------------------------------------------------------------ #
    def init_state(self, key: jax.Array) -> Any:
        """Fresh episode state. Must contain fields t, rng, ep_return, reward_acc."""
        raise NotImplementedError

    def substep(self, state: Any, action: Any) -> Any:
        """Advance one work unit; accumulate reward into state.reward_acc."""
        raise NotImplementedError

    def step_cost(self, state: Any, action: Any) -> jnp.ndarray:
        """Predicted work units of the next step (int32 scalar)."""
        return jnp.int32(self.spec.min_cost)

    def terminal(self, state: Any) -> jnp.ndarray:
        """True if the episode terminated (not truncation)."""
        raise NotImplementedError

    def observe(self, state: Any) -> Any:
        raise NotImplementedError

    def pre_step(self, state: Any) -> Any:
        """Hook run after ``step_cost`` is read but before substeps.

        Default clears the per-step reward accumulator; envs may also
        clear cost-model latches here (see AtariLike.just_scored).
        """
        return state.replace(reward_acc=jnp.zeros_like(state.reward_acc))

    # ------------------------------------------------------------------ #
    # derived API (shared by all engines)
    # ------------------------------------------------------------------ #
    def init(self, key: jax.Array) -> tuple[Any, Any]:
        """Reset: returns (state, obs)."""
        state = self.init_state(key)
        return state, self.observe(state)

    def finalize_step(self, state: Any, cost: jnp.ndarray) -> tuple[Any, TimeStep]:
        """Tail of a step after all substeps ran: episode bookkeeping,
        termination, auto-reset.  Shared by the full ``step`` and the
        batched envs' fused multi-substep (``envs/batch.py``)."""
        spec = self.spec
        state = state.replace(t=state.t + 1)
        reward = state.reward_acc
        terminated = self.terminal(state)
        truncated = jnp.logical_and(state.t >= spec.max_episode_steps, ~terminated)
        done = jnp.logical_or(terminated, truncated)

        ep_return = state.ep_return + reward
        ep_length = state.t

        # auto-reset (EnvPool semantics: on done, the returned obs is the
        # first obs of the next episode; reward/done describe the episode
        # that just finished).
        rng, reset_key = jax.random.split(state.rng)
        state = state.replace(rng=rng, ep_return=ep_return)
        fresh = self.init_state(reset_key)
        state = jax.tree.map(
            lambda f, s: jnp.where(
                done.reshape(done.shape + (1,) * (f.ndim - done.ndim)), f, s
            ),
            fresh,
            state,
        )

        ts = TimeStep(
            obs=self.observe(state),
            reward=reward.astype(jnp.float32),
            done=done,
            terminated=terminated,
            truncated=truncated,
            env_id=jnp.int32(0),  # filled by the pool
            episode_return=jnp.where(done, ep_return, 0.0).astype(jnp.float32),
            episode_length=jnp.where(done, ep_length, 0).astype(jnp.int32),
            step_cost=cost,
        )
        return state, ts

    def step(self, state: Any, action: Any, do: jnp.ndarray | bool = True
             ) -> tuple[Any, TimeStep]:
        """One full environment step: run ``step_cost`` substeps, compute
        reward/termination, auto-reset.  Under ``vmap`` the while-loop pads
        to the per-batch max cost — this *is* the synchronous-mode penalty
        of paper Fig. 2(a), now measurable in FLOPs.

        ``do=False`` freezes the env (zero substeps, state unchanged): the
        async engine uses it for lanes in the top-M block that already hold
        a ready result.
        """
        spec = self.spec
        do = jnp.asarray(do, jnp.bool_)
        orig = state
        cost = jnp.clip(
            self.step_cost(state, action), spec.min_cost, spec.max_cost
        ).astype(jnp.int32)
        cost = jnp.where(do, cost, 0)
        state = self.pre_step(state)

        def body(carry):
            i, s = carry
            return i + 1, self.substep(s, action)

        _, state = lax.while_loop(lambda c: c[0] < cost, body, (jnp.int32(0), state))

        state, ts = self.finalize_step(state, cost)
        state = jax.tree.map(
            lambda n, o: jnp.where(
                do.reshape(do.shape + (1,) * (n.ndim - do.ndim)), n, o
            ),
            state,
            orig,
        )
        return state, ts

    # ------------------------------------------------------------------ #
    # batched-native view (envs/batch.py)
    # ------------------------------------------------------------------ #
    def as_batch(self):
        """Batched-native view of this env (``BatchEnvironment``).

        Default: the generic vmap-lifting adapter.  Envs with a
        natively batched SoA implementation (e.g. ``MujocoLike`` via the
        Pallas ``env_step`` kernel) override this; engines call it once
        at construction and drive only batched primitives on the hot
        path.
        """
        from repro.envs.batch import VmapBatchEnv

        return VmapBatchEnv(self)

    # vmapped helpers (built lazily, cached)
    def v_init(self, keys: jax.Array):
        return jax.vmap(self.init)(keys)

    def v_step(self, states: Any, actions: Any, do: Any = None):
        if do is None:
            return jax.vmap(self.step)(states, actions)
        return jax.vmap(self.step)(states, actions, do)

    def v_substep(self, states: Any, actions: Any):
        return jax.vmap(self.substep)(states, actions)

    def v_finalize(self, states: Any, costs: Any):
        return jax.vmap(self.finalize_step)(states, costs)

    def v_step_cost(self, states: Any, actions: Any):
        return jax.vmap(self.step_cost)(states, actions)

    def sample_actions(self, key: jax.Array, batch: int):
        return self.spec.act_spec.sample_jax(key, (batch,))
