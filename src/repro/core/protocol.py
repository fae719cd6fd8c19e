"""The ``EnvPool`` protocol — ONE spec-driven front-end for all engines.

Every engine (`device`, `device-sharded`, `thread`, `forloop`,
`subprocess`) satisfies the same structural contract: specs
(``spec``/``num_envs``/``batch_size``) plus the paper's §3.1 API
(``send``/``recv``/``step``/sync ``reset``).  Drivers — the dm_env
facade, the XLA collect loop, PPO — program against this protocol, so
the engine is an execution detail, not an API fork.

Two calling conventions exist underneath:

* **functional** engines (the mesh engine, ``core/engine.py``): pure
  functions over an explicit ``PoolState`` — ``send(ps, actions, ids)
  -> ps``, ``recv(ps) -> (ps, TimeStep)``, ``reset(key) -> (ps,
  TimeStep)`` — jittable, scannable, donate-able (paper Appendix E).
  There is ONE functional engine class (``MeshEnvPool``): its bodies
  are per-shard pure functions wrapped in ``shard_map`` over a 1-D
  device mesh, and ``device`` / ``device-sharded`` differ only in the
  mesh (``device`` is the degenerate 1-shard mesh).  ``PoolState``
  stays sharded over the mesh for the life of the pool — drivers scan
  over it without ever pulling it to the host, and
  ``state_shardings``/``device_put`` expose the layout
  (``distributed/sharding.py`` rules) for long-lived carries.
* **host** engines (thread / forloop / subprocess): stateful objects —
  ``send(actions, ids)``, ``recv() -> dict``, ``reset() -> dict``.

``bind(pool)`` erases the difference: it returns a uniform stateful
handle (``reset()/step()/send()/recv()`` all yielding ``TimeStep``
batches) that every driver can loop over, while ``is_functional``
lets jit-native drivers keep the pure path when it exists.

Async engines additionally share the scheduling-policy axis
(``core/scheduler.py``, selected by ``make(..., schedule=...)``): which
M lanes each ``recv`` serves is a pluggable policy — ``"fifo"``
(default, the classic engine behavior), ``"sjf"``, or
``"hierarchical"`` (sharded; its fairness deadline is
``make(..., sched_patience=...)``) — consumed by the functional engines
as pure ``SchedState`` primitives and by the host thread engine through
the numpy mirror.  The policy never changes per-env trajectories (those
depend only on init keys and routed actions), only the serving order.

Every engine also carries the in-engine transform hook
(``core/transforms.py``, selected by ``make(..., transforms=[...])``):
an ordered pipeline of pure per-block preprocessing stages (frame
stacking, reward clipping, casting, normalization, episodic-life)
applied to each served result exactly once, inside the jitted recv for
the device family and as a numpy mirror for the host engines —
bitwise-identical for the deterministic transforms (stack / clip /
cast); ``NormalizeObs`` agrees only to f32 reduction-order tolerance.
Spec-transformation rule: ``pool.spec`` is the RAW env
spec passed through every transform's ``transform_spec`` in list order,
so ``obs_spec`` shape/dtype/bounds (and the reward range after
clipping) are always truthful for the stream the driver actually
receives; ``act_spec`` is never transformed.  Transforms change only
the served view of a trajectory — never the underlying env dynamics,
scheduling, auto-reset points, or ``episode_return`` bookkeeping —
so engine conformance (identical streams across engines for identical
seeds/actions) holds for transformed streams exactly as for raw ones.
The image transforms (``Grayscale`` / ``Resize(h, w)`` / ``Crop``,
backed by the ``kernels/image`` Pallas family) follow the same rules
with image-specific spec transformations: ``Grayscale`` requires a
trailing channel axis — uint8 ``(..., H, W, 3)`` — and drops it;
``Resize`` requires uint8 rank >= 2 and replaces the trailing two axes
with ``(h, w)``; ``Crop`` validates its window against the trailing
``(H, W)`` at pipeline-construction time (out-of-bounds windows raise
``ValueError`` before any tracing).  All three are stateless and
integer-fixed-point, so the device kernels and the host numpy mirrors
are bitwise-identical — image streams keep full engine conformance,
and the served dtype stays uint8 end to end.
Stateful transform pipelines (e.g. ``NormalizeObs`` running moments)
are checkpointable on the functional engines:
``save_transform_state``/``restore_transform_state`` round-trip
``PoolState.tf_state`` through ``checkpoint/store.py`` mesh-elastically
(global statistics are stored once and re-broadcast to the restoring
pool's shard count), so preprocessing statistics survive training
restarts.

Cache-as-lane-state contract (the LLM-policy decode path,
``rl/policy_lm.py``): policy-side per-lane state — the KV cache rows,
cache lengths, and token histories of ``LMLaneState`` — follows the
same carriage rules as ``PoolState.tf_state``.  Every leaf is
lane-major SoA with leading dim ``num_envs``; the block a ``recv``
serves is lifted with ``tree_gather(lanes, ts.env_id)``, updated by a
fixed-shape block program, and written back with ``tree_scatter`` —
never resized, so top-M selection doubles as continuous batching: a
lane whose episode ended (``ts.done``) simply restarts its cache at
position 0 when next served, and fresh lanes join the decode block
without recompiling.  Like transform state, lane state never alters
env dynamics, scheduling, or auto-reset points; it is policy-private
carry that happens to be addressed by the same ``env_id`` routing the
paper's §3.1 API already mandates.

Telemetry-as-PoolState contract (``obs/telemetry.py``): every engine
exposes ``stats()`` — a host snapshot of the engine's own counters
(recvs, per-lane serves, queue-wait ticks and their fixed-edge
histogram, served/stepped totals and their occupancy ratio, substep
cost sums, scheduler overdue-band admissions).  On the functional
engines the counters are a ``Telemetry`` pytree riding on ``PoolState``
(the ``tf_state`` carriage pattern: per-lane ``(N,)`` leaves partition
with the env states, per-shard partial sums carry the ``(D,)`` dim),
updated INSIDE the jitted recv body as fixed-size integer ops
and crossing to the host only at the explicit ``stats(ps)`` call —
never on the hot path, never via collectives (integer partial sums
are summed on the host, so snapshots are bitwise mesh-size-invariant
at every D).  Host engines mirror the same counters in numpy
(``HostTelemetry``) with identical semantics, so ``stats()`` is
engine-conformant: the same scripted rollout yields the same counter
values on every engine (tests/test_obs.py).  Like transform and lane
state, telemetry never feeds back into env math, scheduling, or RNG —
served streams (and goldens) are bitwise-unchanged with it on.

Multi-host contract (``launch/mesh.py`` + ``distributed/sharding.py``):
after ``initialize_multihost(coordinator, num_processes, process_id)``
the mesh engine's 1-D device mesh may SPAN processes —
``make_env_mesh`` builds it over the global ``jax.devices()`` and the
engine bodies are unchanged (the same ``shard_map`` programs, now
compiled SPMD across hosts).  What lives where:

* **env state** — every ``PoolState`` leaf stays sharded over the
  global mesh (each process holds only its shards' rows); it never
  crosses hosts on the hot path.
* **hot-path collectives** — exactly two fixed-size families are
  permitted in a compiled step/recv, independent of env count and
  observation size: the scheduler's ``(D, C)`` per-shard cost/priority
  ``all_gather`` and the ``NormalizeObs`` moment ``psum``.  Nothing
  env-data-sized ever moves between hosts (audited from compiled HLO
  in tests/test_multihost.py).
* **host reads** — ``stats(ps)`` and any host materialization of
  sharded leaves go through ``replicate()`` (a jitted all-gather to a
  fully-replicated layout) so every process can ``np.asarray`` the
  result; these are explicit, off-hot-path calls, and the integer
  partial-sum telemetry keeps snapshots bitwise process-count-
  invariant (the same rollout on a 1-process mesh=D and a multi-
  process mesh=D yields identical streams AND identical ``stats()``).
* **disaggregation** (``rl/ppo.py::train_disaggregated``) — env shards
  live on the env processes' mesh, the learner update runs on its own
  process with per-role local jits; rollouts and refreshed params are
  handed off by host-level broadcast each iteration (small, fixed
  payloads), params re-enter the env mesh via
  ``distributed/sharding.py::policy_shardings`` placement, and the
  one-iteration staleness is the same policy lag ``train_pipelined``'s
  V-trace correction already makes principled.
* **checkpoint/elastic restore** — unaffected: transform state is
  stored as global statistics and re-broadcast to the restoring pool's
  shard count, whatever its process topology.
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import numpy as np

from repro.core.specs import EnvSpec, TimeStep


@runtime_checkable
class EnvPool(Protocol):
    """Structural contract every engine satisfies (paper §3.1/§3.4)."""

    spec: EnvSpec
    num_envs: int
    batch_size: int

    def send(self, *args: Any, **kwargs: Any) -> Any: ...

    def recv(self, *args: Any, **kwargs: Any) -> Any: ...

    def step(self, *args: Any, **kwargs: Any) -> Any: ...

    def reset(self, *args: Any, **kwargs: Any) -> Any: ...

    def stats(self, *args: Any, **kwargs: Any) -> Any: ...


@runtime_checkable
class FunctionalEnvPool(EnvPool, Protocol):
    """Pure-state engines: additionally expose ``init`` (key ->
    PoolState) and the jitted ``xla()`` handle API (paper Appendix E)."""

    def init(self, key: Any) -> Any: ...

    def xla(self, *args: Any, **kwargs: Any) -> Any: ...


def is_functional(pool: Any) -> bool:
    """True for the device-family engines (pure state, jittable)."""
    return isinstance(pool, FunctionalEnvPool)


def to_timestep(out: "dict[str, np.ndarray] | TimeStep") -> TimeStep:
    """Normalize a host-engine recv dict to the TimeStep container."""
    if isinstance(out, TimeStep):
        return out
    return TimeStep(
        obs=out["obs"],
        reward=out["reward"],
        done=out["done"],
        terminated=out["terminated"],
        truncated=out["truncated"],
        env_id=out["env_id"],
        episode_return=out["episode_return"],
        episode_length=out["episode_length"],
        step_cost=out["step_cost"],
    )


class BoundEnvPool:
    """Uniform stateful handle over any ``EnvPool`` engine.

    Owns the rollout state (the ``PoolState`` for functional engines,
    nothing for host engines) so drivers see one interface:

        h = bind(pool, key)
        ts = h.reset()
        ts = h.step(actions, ts.env_id)   # or h.send(...) / h.recv()

    Functional engines get jitted send/recv/step; host engines pass
    numpy through unchanged.  ``ts`` is always a ``TimeStep``.
    """

    def __init__(self, pool: EnvPool, key: Any = None, seed: int = 0):
        import jax

        self.pool = pool
        self.spec = pool.spec
        self.num_envs = pool.num_envs
        self.batch_size = pool.batch_size
        self.functional = is_functional(pool)
        self._ps = None
        if self.functional:
            self._key = key if key is not None else jax.random.PRNGKey(seed)
            self._jit_step = jax.jit(pool.step)
            self._jit_send = jax.jit(pool.send)
            self._jit_recv = jax.jit(pool.recv)

    # ------------------------------------------------------------------ #
    @property
    def state(self):
        """The functional engine's PoolState (None for host engines)."""
        return self._ps

    def reset(self) -> TimeStep:
        if self.functional:
            self._ps, ts = self.pool.reset(self._key)
            return ts
        pool = self.pool
        if hasattr(pool, "async_reset") and pool.batch_size < pool.num_envs:
            pool.async_reset()
            return to_timestep(pool.recv())
        return to_timestep(pool.reset())

    def send(self, actions: Any, env_ids: Any) -> None:
        if self.functional:
            self._ps = self._jit_send(self._ps, actions, env_ids)
        else:
            self.pool.send(np.asarray(actions), np.asarray(env_ids))

    def recv(self) -> TimeStep:
        if self.functional:
            self._ps, ts = self._jit_recv(self._ps)
            return ts
        return to_timestep(self.pool.recv())

    def step(self, actions: Any, env_ids: Any) -> TimeStep:
        if self.functional:
            self._ps, ts = self._jit_step(self._ps, actions, env_ids)
            return ts
        return to_timestep(self.pool.step(np.asarray(actions), np.asarray(env_ids)))

    def stats(self) -> dict:
        """Engine telemetry snapshot (the ``stats()`` contract): the
        functional engines read their in-graph counters off the owned
        ``PoolState``; host engines return their numpy mirror."""
        if self.functional:
            return self.pool.stats(self._ps)
        return self.pool.stats()

    def close(self) -> None:
        if hasattr(self.pool, "close"):
            self.pool.close()


def bind(pool: EnvPool, key: Any = None, seed: int = 0) -> BoundEnvPool:
    """Uniform stateful view of any engine (see ``BoundEnvPool``)."""
    return BoundEnvPool(pool, key=key, seed=seed)


__all__ = [
    "BoundEnvPool",
    "EnvPool",
    "FunctionalEnvPool",
    "bind",
    "is_functional",
    "to_timestep",
]
