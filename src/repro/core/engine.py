"""The mesh-native engine core — ONE device engine for every mesh size.

EnvPool's thesis is that environment execution is a *system* component;
its fastest configurations (CuLE's on-device argument, Sample Factory's
no-idle-hardware design) keep data next to the accelerator.  This module
is that component for the JAX engines, written exactly once:

  * every piece of engine logic — scheduler aliasing
    (``core/scheduler.py``), transform application
    (``core/transforms.py``), ``_serve`` / ``_recv_topm``,
    init-from-keys — is a **per-shard pure function** over a local
    ``PoolState`` block;
  * the public ``init``/``send``/``recv`` wrap those bodies in ONE
    ``shard_map`` over a 1-D device mesh.  ``engine="device"`` is simply
    the degenerate ``num_shards=1`` mesh; ``engine="device-sharded"``
    is the same class over more devices.  There is no inner/outer class
    split and no per-method re-wrapping layer.

Layout: ``PoolState`` leaves keep their *logical* shapes — per-lane
leaves are ``(N, ...)`` (partitioned over the mesh axis on dim 0, so
each device materializes its ``N/D`` rows), per-shard scalars (``tick``,
``rng``, global transform state such as ``NormalizeObs`` moments) carry
a leading ``(D, ...)`` shard dim.  The engine's own lane rows (env
state, action table, stored results: ``LaneRows``) live in one
``(N, W) uint32`` slab (``core/lanes.py``), so a recv moves a block of
lanes with one gather and one scatter; ``MeshEnvPool.lane_view`` reads
them back as a pytree.  Batches cross the API boundary flat
(``(M, ...)``, shard-major order); ``send`` requires batches to stay in
the recv grouping (the standard ``send(actions, ts.env_id)`` loop
preserves it — EnvPool's route-by-env_id contract).

Determinism: per-env init keys derive from the *global* pool key
(``derive_env_keys``), so every env's *trajectory* (its per-env
reward/done/obs stream) is independent of the mesh size at every D.
Block *emission order*: async blocks are emitted in per-shard
selection order (at D=1 exactly the classic single-device engine).
Sync blocks are emitted in env-id order at every D, as gym's and
EnvPool's sync ``step(actions)`` contract requires: row ``i`` of every
block is lane ``i``, whatever the per-shard top-k cost ordering, so a
trainer that stacks sync blocks over time (GAE, ``rl/ppo.py``) reads
each lane's own stream, and sync streams are bitwise-identical at all
mesh sizes (tests/test_sharded_pool.py, tests/_sharded_check.py).

Two execution modes, one recv path (``_recv_topm``):
  * ``sync``  — step all N each recv (gym.vector semantics, M = N).
  * ``async`` — top-M selection under the pool's ``schedule=`` policy.

All public methods are pure functions over ``PoolState`` → the whole
pool is jittable, scannable and donate-able inside ``lax.scan`` (paper
Appendix E's ``env.xla()``), and the state never has to leave the mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.lanes import LaneLayout
from repro.core.scheduler import (
    HAS_ACTION,
    READY,
    SchedState,
    Scheduler,
    get_scheduler,
)
from repro.core.specs import TimeStep
from repro.core.transforms import TransformPipeline
from repro.envs.base import Environment
from repro.envs.batch import as_batch_env
from repro.obs.telemetry import (
    init_telemetry,
    record_serve,
    snapshot_device,
    telemetry_local,
    telemetry_shard,
)
from repro.obs.trace import (
    ENV_STEP,
    GATHER,
    SEND,
    TELEMETRY,
    TRANSFORM,
    WRITEBACK,
)
from repro.utils.pytree import pytree_dataclass

ENV_AXIS = "env"


def _traced(*trees: Any) -> bool:
    """True when any leaf is a tracer — i.e. the caller already runs
    under jit/scan/vmap, so the raw ``shard_map`` body must be inlined
    into *their* program.  Concrete (eager) calls instead dispatch
    through the pool's cached jitted entry points: eager ``shard_map``
    evaluates op-by-op across the mesh, which is pathologically slow on
    CPU-simulated meshes and wasteful everywhere."""
    return any(
        isinstance(leaf, jax.core.Tracer)
        for tree in trees
        for leaf in jax.tree.leaves(tree)
    )


def make_env_mesh(num_shards: int | None = None, axis_name: str = ENV_AXIS
                  ) -> Mesh:
    """1-D mesh over the first ``num_shards`` devices (default: all).

    ``jax.devices()`` is the GLOBAL device list, so after
    ``launch.mesh.initialize_multihost()`` the returned mesh spans
    processes and a ``MeshEnvPool`` built on it runs the same per-shard
    bodies across hosts (multi-host contract: ``core/protocol.py``)."""
    devices = jax.devices()
    d = num_shards if num_shards is not None else len(devices)
    if d < 1 or d > len(devices):
        raise ValueError(
            f"num_shards={d} not in [1, {len(devices)}] available devices"
        )
    return Mesh(np.array(devices[:d]), (axis_name,))


def derive_env_keys(key: jax.Array, num_envs: int) -> tuple[jax.Array, jax.Array]:
    """``(env_keys, pool_rng)`` from one seed key — THE formula every
    engine shares, so identical seeds give identical per-env init states
    across device, sharded, and host engines (engine-conformance
    contract, tests/test_conformance.py)."""
    rng, sub = jax.random.split(key)
    return jax.random.split(sub, num_envs), rng


@pytree_dataclass
class LaneRows:
    """The engine's per-lane rows, each leaf with a leading lane dim.

    On ``PoolState`` they are packed into one slab (``core/lanes.py``);
    the engine unpacks the rows of the lanes it serves, and
    ``MeshEnvPool.lane_view`` unpacks all of them for cold readers.
    """

    env_states: Any            # the env's state pytree
    actions: jnp.ndarray       # (*act_shape) action table
    # stored results for READY envs (obs always re-derived from env state)
    r_reward: jnp.ndarray
    r_done: jnp.ndarray
    r_term: jnp.ndarray
    r_trunc: jnp.ndarray
    r_ep_return: jnp.ndarray
    r_ep_length: jnp.ndarray
    r_cost: jnp.ndarray


@pytree_dataclass
class PoolState:
    """The pool's full execution state, one pytree.

    Per-lane leaves carry a leading ``N`` dim (partitioned over the mesh
    axis); ``tick``/``rng`` and global transform-state leaves carry a
    leading ``(D,)`` per-shard dim.  The ``LaneRows`` are packed into
    ``lanes`` by the pool's ``LaneLayout``.
    """

    lanes: jnp.ndarray         # (N, W) uint32 packed LaneRows
    # the scheduler's lane signals, read densely over all N
    phase: jnp.ndarray         # (N,) int32
    cost: jnp.ndarray          # (N,) int32 predicted cost of pending step
    send_tick: jnp.ndarray     # (N,) int32 tick when action was enqueued
    tick: jnp.ndarray          # (D,) int32 per-shard recv counter
    rng: jax.Array             # (D, ...) per-shard rng keys
    # transform-pipeline state (core/transforms.py): one entry per
    # transform; per-lane leaves carry the leading N dim, global leaves
    # (e.g. NormalizeObs moments) carry the (D,) shard dim — each
    # shard's replicated copy, kept identical by collective merges.
    # Empty tuple when the pool has no transforms — zero pytree leaves,
    # so the classic engine behavior (and its goldens) is
    # bitwise-unchanged.
    tf_state: Any = ()
    # in-graph engine counters (obs/telemetry.py): a ``Telemetry``
    # pytree updated inside the jitted recv body — per-lane leaves
    # carry the N dim, per-shard partial sums the (D,) dim — and read
    # on the host only by an explicit ``pool.stats()`` snapshot.
    # Counters never feed back into env math, scheduling, or RNG, so
    # served streams stay bitwise-unchanged.
    telemetry: Any = ()


class MeshEnvPool:
    """EnvPool with ``num_envs`` N and ``batch_size`` M over a 1-D device
    mesh of D shards (paper §3.2 + §4.1 scale-out in one class).

    ``batch_size == num_envs`` is synchronous mode; smaller is async.
    ``mesh=None`` is the degenerate single-device mesh (the classic
    ``engine="device"``); an int or a ``Mesh`` scales the same engine
    out.  N and M are *global*; each shard owns N/D envs and serves
    M/D results per recv with its own top-(M/D) selection — no gathers
    of env data on the hot path.
    """

    def __init__(
        self,
        env: Environment,
        num_envs: int,
        batch_size: int | None = None,
        mode: str | None = None,
        mesh: Mesh | int | None = None,
        axis_name: str = ENV_AXIS,
        aging: float = 1.0,
        batched: bool | None = None,
        schedule: str | Scheduler = "fifo",
        sched_patience: float = 1.0,
        transforms: Any = (),
    ):
        if batch_size is None:
            batch_size = num_envs
        if mode is None:
            mode = "sync" if batch_size == num_envs else "async"
        if batch_size > num_envs:
            raise ValueError("batch_size cannot exceed num_envs (paper §3.2)")
        if mode not in ("sync", "async"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "sync" and batch_size != num_envs:
            raise ValueError("sync mode requires batch_size == num_envs")
        if isinstance(mesh, int):
            mesh = make_env_mesh(mesh, axis_name)
        elif mesh is None:
            mesh = make_env_mesh(1, axis_name)
        if axis_name not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.shape}")
        d = int(mesh.shape[axis_name])
        if num_envs % d:
            raise ValueError(f"num_envs={num_envs} % num_shards={d}")
        if batch_size % d:
            raise ValueError(f"batch_size={batch_size} % num_shards={d}")
        self.env = env
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_shards = d
        self.num_envs = int(num_envs)
        self.batch_size = int(batch_size)
        self.mode = mode
        self._n_local = self.num_envs // d
        self._m_local = self.batch_size // d
        # selection policy (core/scheduler.py): which M/D lanes each
        # shard serves per recv.  The mesh context is always available
        # (this IS the mesh engine), so ``hierarchical`` resolves here;
        # fifo/sjf stay communication-free per-shard policies.  An
        # explicit Scheduler instance wins over all knobs.
        self.scheduler = get_scheduler(
            schedule, aging=aging, axis_name=axis_name, num_shards=d,
            patience=sched_patience,
        )
        # in-engine transform pipeline (core/transforms.py): applied to
        # every served block INSIDE the per-shard recv body, so
        # preprocessing lowers into the same XLA program as the fused
        # multi-substep; per-lane transform state shards with the env
        # states and NormalizeObs merges its moment sums with one
        # fixed-size psum over the mesh axis (statistics only — never
        # env data), keeping the replicated moments identical per shard.
        # The degenerate mesh skips the collective: a 1-shard psum is a
        # value no-op but changes XLA fusion/rounding, and the classic
        # single-device stream is pinned bitwise.
        self.pipeline = TransformPipeline(
            transforms, env.spec, axis_name=axis_name if d > 1 else None
        )
        self.raw_spec = env.spec
        # THE hot-path engine: a batched-native view of the env.  The
        # recv body drives batched primitives (one fused multi-substep
        # call per shard per recv) — never per-lane ``env.step`` under
        # vmap.  ``batched=False`` forces the generic vmap-lifting
        # adapter (the conformance reference).
        self.benv = as_batch_env(env, native=batched)
        # drivers see the TRANSFORMED spec; act_spec is never changed
        self.spec = self.pipeline.out_spec
        # the static column layout of the lane rows (core/lanes.py), from
        # the shapes and dtypes of a fresh block's rows; env states hold
        # raw PRNG keys (``init_from_keys`` unwraps typed ones)
        self.lanes = LaneLayout(jax.eval_shape(lambda: self._fresh_rows(
            derive_env_keys(jax.random.PRNGKey(0), 1)[0])))

    # ------------------------------------------------------------------ #
    # per-shard <-> stacked layout plumbing (the ONLY conversion code)
    # ------------------------------------------------------------------ #
    def _tf_local(self, tf_state: Any) -> Any:
        """Strip the (1,) shard dim from global transform-state entries
        (per-lane entries already arrive as local (N/D, ...) blocks)."""
        return tuple(
            s if t.per_lane else jax.tree.map(lambda x: x[0], s)
            for t, s in zip(self.pipeline.transforms, tf_state)
        )

    def _tf_shard(self, tf_state: Any) -> Any:
        """Inverse: re-add the per-shard leading dim to global entries."""
        return tuple(
            s if t.per_lane else jax.tree.map(lambda x: x[None], s)
            for t, s in zip(self.pipeline.transforms, tf_state)
        )

    def _local_view(self, ps: PoolState) -> PoolState:
        """Classic single-device layout of one shard's block (inside
        shard_map): scalar leaves lose their (1,) shard dim."""
        return ps.replace(
            tick=ps.tick[0], rng=ps.rng[0],
            tf_state=self._tf_local(ps.tf_state),
            telemetry=telemetry_local(ps.telemetry),
        )

    def _shard_view(self, ps: PoolState) -> PoolState:
        """Inverse of ``_local_view`` (leaving shard_map)."""
        return ps.replace(
            tick=ps.tick[None], rng=ps.rng[None],
            tf_state=self._tf_shard(ps.tf_state),
            telemetry=telemetry_shard(ps.telemetry),
        )

    def _smap(self, f, n_in: int, n_out: int = 1):
        spec = P(self.axis_name)
        return shard_map(
            f, mesh=self.mesh, in_specs=(spec,) * n_in,
            out_specs=spec if n_out == 1 else (spec,) * n_out,
            check_rep=False,
        )

    # ------------------------------------------------------------------ #
    # construction / reset
    # ------------------------------------------------------------------ #
    def _fresh_rows(self, env_keys: jax.Array) -> LaneRows:
        n = env_keys.shape[0]
        act = self.spec.act_spec
        return LaneRows(
            env_states=self.benv.v_init_state(env_keys),
            actions=jnp.zeros((n,) + act.shape, act.dtype),
            r_reward=jnp.zeros((n,), jnp.float32),
            r_done=jnp.zeros((n,), jnp.bool_),
            r_term=jnp.zeros((n,), jnp.bool_),
            r_trunc=jnp.zeros((n,), jnp.bool_),
            r_ep_return=jnp.zeros((n,), jnp.float32),
            r_ep_length=jnp.zeros((n,), jnp.int32),
            r_cost=jnp.zeros((n,), jnp.int32),
        )

    def _local_init(self, env_keys: jax.Array, rng: jax.Array) -> PoolState:
        """Fresh per-shard block: every env resets; all results READY
        (async_reset semantics, paper A.3)."""
        n = env_keys.shape[0]
        return PoolState(
            lanes=self.lanes.pack(self._fresh_rows(env_keys)),
            phase=jnp.full((n,), READY, jnp.int32),
            cost=jnp.zeros((n,), jnp.int32),
            send_tick=jnp.zeros((n,), jnp.int32),
            tick=jnp.int32(0),
            rng=rng,
            tf_state=self.pipeline.init(n),
            telemetry=init_telemetry(n),
        )

    def _init_from_keys_impl(self, env_keys: jax.Array, rng: jax.Array
                             ) -> PoolState:
        if jnp.issubdtype(env_keys.dtype, jax.dtypes.prng_key):
            # the lane layout holds raw keys; the same key data draws
            # the same numbers
            env_keys = jax.random.key_data(env_keys)
        shard_rngs = jax.random.split(rng, self.num_shards)

        def init_shard(keys, rngs):
            return self._shard_view(self._local_init(keys, rngs[0]))

        return self._smap(init_shard, 2)(env_keys, shard_rngs)

    def init_from_keys(self, env_keys: jax.Array, rng: jax.Array) -> PoolState:
        """Init from externally-derived per-env keys (the shared engine
        formula): the per-env key assignment — and hence every env's
        trajectory — is independent of the mesh size."""
        if _traced(env_keys, rng):
            return self._init_from_keys_impl(env_keys, rng)
        return self._jit_init(env_keys, rng)

    def init(self, key: jax.Array) -> PoolState:
        """async_reset (paper A.3): every env resets; all N results READY."""
        env_keys, rng = derive_env_keys(key, self.num_envs)
        return self.init_from_keys(env_keys, rng)

    # ------------------------------------------------------------------ #
    # send — ActionBufferQueue enqueue (per-shard scatter)
    # ------------------------------------------------------------------ #
    def _sched_view(self, ps: PoolState) -> SchedState:
        """The scheduler's lane signals, aliased onto PoolState fields."""
        return SchedState(
            phase=ps.phase, cost=ps.cost, send_tick=ps.send_tick, tick=ps.tick
        )

    @jax.named_scope(SEND)
    def _local_send(self, ps: PoolState, actions: jnp.ndarray,
                    local_ids: jnp.ndarray) -> PoolState:
        sel = self.lanes.gather(ps.lanes, local_ids)
        costs = self.benv.v_step_cost(sel.env_states, actions)
        costs = jnp.clip(costs, self.spec.min_cost, self.spec.max_cost)
        ss = self.scheduler.enqueue(self._sched_view(ps), local_ids, costs)
        # the gathered rows with the new action: one row scatter
        lanes = self.lanes.scatter(ps.lanes, local_ids,
                                   sel.replace(actions=actions))
        return ps.replace(
            lanes=lanes,
            phase=ss.phase,
            cost=ss.cost,
            send_tick=ss.send_tick,
        )

    def _send_impl(self, ps: PoolState, actions: jnp.ndarray,
                   env_ids: jnp.ndarray) -> PoolState:
        env_ids = env_ids.astype(jnp.int32)
        n_local = self._n_local

        def send_shard(ps_s, a, ids):
            local = self._local_view(ps_s)
            # global id -> shard-local row (shards own contiguous ranges)
            return self._shard_view(self._local_send(local, a, ids % n_local))

        return self._smap(send_shard, 3)(ps, actions, env_ids)

    def send(self, ps: PoolState, actions: jnp.ndarray, env_ids: jnp.ndarray
             ) -> PoolState:
        """Store actions for ``env_ids``; returns immediately (paper §3.1).
        Batches must stay in the recv grouping (shard-major)."""
        if _traced(ps, actions, env_ids):
            return self._send_impl(ps, actions, env_ids)
        with jax.profiler.TraceAnnotation("pool.send"):
            return self._jit_send(ps, actions, env_ids)

    # ------------------------------------------------------------------ #
    # recv — StateBufferQueue block of M results (per-shard top-M/D)
    # ------------------------------------------------------------------ #
    def _serve(self, ps: PoolState, idx: jnp.ndarray, out: TimeStep
               ) -> tuple[PoolState, TimeStep]:
        """Run the transform pipeline over one served (raw) block —
        inside the per-shard recv body, so the preprocessing fuses into
        the same XLA program as the recv itself.  Applied exactly once
        per served result; per-lane transform state rows are gathered
        for the block and scattered back onto ``PoolState``."""
        if not self.pipeline:
            return ps, out
        with jax.named_scope(TRANSFORM):
            blk = self.pipeline.gather(ps.tf_state, idx)
            blk, out = self.pipeline.apply(blk, out)
            tf_state = self.pipeline.scatter(ps.tf_state, idx, blk)
        return ps.replace(tf_state=tf_state), out

    def _recv_topm(self, ps: PoolState) -> tuple[PoolState, TimeStep]:
        full_block = self._m_local == ps.phase.shape[0]
        idx, overdue = self.scheduler.select_info(
            self._sched_view(ps), self._m_local
        )
        # queue-wait (recv ticks since the action was enqueued), read
        # BEFORE ``complete`` advances the tick.  A full-size block
        # serves every lane, so wait stays in lane order and
        # record_serve takes its scatter-free fast path.
        with jax.named_scope(TELEMETRY):
            wait = (ps.tick - ps.send_tick if full_block
                    else ps.tick - ps.send_tick[idx])

        with jax.named_scope(GATHER):
            sel = self.lanes.gather(ps.lanes, idx)
            need_step = ps.phase[idx] == HAS_ACTION

        with jax.named_scope(ENV_STEP):
            # batched-native step: ONE fused multi-substep call for the
            # whole block (per-lane data-dependent cost handled inside)
            new_states, ts = self.benv.v_step(sel.env_states, sel.actions,
                                              need_step)
            # ONE observe pass over the post-step states serves every
            # lane: for stepped lanes ``new_states`` is the finalized
            # state (its observe is bitwise ``ts.obs``); for ``do=False``
            # lanes ``v_step`` restored the original state, so this
            # re-derives the CURRENT obs — the phantom-obs fix.  Not
            # reading ``ts.obs`` lets XLA dead-code-eliminate the
            # finalize observe (one frame render per recv for
            # render-on-observe envs like AtariLike).
            obs = self.benv.v_observe(new_states)

        with jax.named_scope(GATHER):
            out = TimeStep(
                obs=obs,
                reward=jnp.where(need_step, ts.reward, sel.r_reward),
                done=jnp.where(need_step, ts.done, sel.r_done),
                terminated=jnp.where(need_step, ts.terminated, sel.r_term),
                truncated=jnp.where(need_step, ts.truncated, sel.r_trunc),
                env_id=idx,
                episode_return=jnp.where(
                    need_step, ts.episode_return, sel.r_ep_return
                ),
                episode_length=jnp.where(
                    need_step, ts.episode_length, sel.r_ep_length
                ),
                step_cost=jnp.where(need_step, ts.step_cost, sel.r_cost),
            )
        with jax.named_scope(WRITEBACK):
            lanes = self.lanes.scatter(
                ps.lanes, idx, sel.replace(
                    env_states=new_states,
                    r_reward=out.reward,
                    r_done=out.done,
                    r_term=out.terminated,
                    r_trunc=out.truncated,
                    r_ep_return=out.episode_return,
                    r_ep_length=out.episode_length,
                    r_cost=out.step_cost,
                ))
            ss = self.scheduler.complete(self._sched_view(ps), idx)
            ps = ps.replace(lanes=lanes, phase=ss.phase, tick=ss.tick)
        ps = ps.replace(
            telemetry=record_serve(
                ps.telemetry, idx, wait, need_step,
                out.step_cost, overdue, full_block=full_block,
            )
        )
        # stored r_* results stay RAW; the pipeline runs at serve time
        return self._serve(ps, idx, out)

    def _recv_impl(self, ps: PoolState) -> tuple[PoolState, TimeStep]:
        n_local = self._n_local

        def recv_shard(ps_s):
            local, ts = self._recv_topm(self._local_view(ps_s))
            shard = lax.axis_index(self.axis_name).astype(jnp.int32)
            ts = ts.replace(env_id=ts.env_id + shard * n_local)
            if self.mode == "sync":
                # sync blocks in env-id order (a shard-local
                # permutation, no comms): row i is lane i at every D
                with jax.named_scope(WRITEBACK):
                    order = jnp.argsort(ts.env_id)
                    ts = jax.tree.map(lambda x: x[order], ts)
            return self._shard_view(local), ts

        return self._smap(recv_shard, 1, n_out=2)(ps)

    def recv(self, ps: PoolState) -> tuple[PoolState, TimeStep]:
        if _traced(ps):
            return self._recv_impl(ps)
        with jax.profiler.TraceAnnotation("pool.recv"):
            return self._jit_recv(ps)

    # ------------------------------------------------------------------ #
    # gym-style combined step + reset views
    # ------------------------------------------------------------------ #
    def step(self, ps: PoolState, actions: jnp.ndarray, env_ids: jnp.ndarray
             ) -> tuple[PoolState, TimeStep]:
        """``step = send ∘ recv`` (paper §3.1)."""
        if _traced(ps, actions, env_ids):
            return self._recv_impl(self._send_impl(ps, actions, env_ids))
        with jax.profiler.TraceAnnotation("pool.step"):
            return self._jit_step(ps, actions, env_ids)

    # ------------------------------------------------------------------ #
    # cached jitted entry points for eager callers (see ``_traced``)
    # ------------------------------------------------------------------ #
    @functools.cached_property
    def _jit_init(self):
        return jax.jit(self._init_from_keys_impl)

    @functools.cached_property
    def _jit_send(self):
        return jax.jit(self._send_impl)

    @functools.cached_property
    def _jit_recv(self):
        return jax.jit(self._recv_impl)

    @functools.cached_property
    def _jit_step(self):
        return jax.jit(
            lambda ps, a, ids: self._recv_impl(self._send_impl(ps, a, ids))
        )

    @functools.cached_property
    def _jit_reset(self):
        return jax.jit(lambda key: self._recv_impl(self.init(key)))

    def reset(self, key: jax.Array) -> tuple[PoolState, TimeStep]:
        """Sync-style reset: init + drain the first batch of M results."""
        with jax.profiler.TraceAnnotation("pool.reset"):
            return self._jit_reset(key)

    # ------------------------------------------------------------------ #
    # cold readers of the lane rows
    # ------------------------------------------------------------------ #
    def lane_view(self, ps: PoolState) -> LaneRows:
        """Every lane's rows unpacked from the slab, ``(N, ...)`` leaves:
        env states, the action table and the stored results.  A view for
        readers off the hot path (tests, inspection); the pool keeps no
        second copy."""
        if _traced(ps):
            return self.lanes.unpack(ps.lanes)
        return self._jit_lane_view(ps.lanes)

    @functools.cached_property
    def _jit_lane_view(self):
        return jax.jit(self.lanes.unpack)

    # ------------------------------------------------------------------ #
    # telemetry snapshot (core/protocol.py ``stats()`` contract)
    # ------------------------------------------------------------------ #
    def stats(self, ps: PoolState) -> dict:
        """Host snapshot of the in-graph counters — the ONLY point where
        telemetry crosses to the host.  Per-shard partial sums are summed
        over D (integer adds: bitwise mesh-size-invariant); ``recvs``
        comes from the replicated tick, shard 0's copy."""
        with jax.profiler.TraceAnnotation("pool.stats"):
            tel, tick = ps.telemetry, ps.tick
            if self.is_multiprocess:
                # multi-host: counter leaves live on remote shards, so
                # gather a replicated copy first.  Fixed-size integer
                # leaves on an explicit stats() call only — never the
                # hot path — and the cross-shard sums stay integer adds,
                # so the snapshot remains bitwise identical to the
                # single-process one.
                tel, tick = self.replicate((tel, tick))
            return snapshot_device(tel, tick)

    # ------------------------------------------------------------------ #
    # paper Appendix E: jittable handle API
    # ------------------------------------------------------------------ #
    def xla(self, seed: int = 0, key: jax.Array | None = None):
        """Returns ``(handle, recv, send, step)`` — all jitted pure fns,
        mirroring EnvPool's ``env.xla()`` (paper Appendix E).  The
        handle's init key is ``key`` if given, else ``PRNGKey(seed)``."""
        handle = self.init(jax.random.PRNGKey(seed) if key is None else key)
        return handle, jax.jit(self.recv), jax.jit(self.send), jax.jit(self.step)

    # ------------------------------------------------------------------ #
    # placement helpers
    # ------------------------------------------------------------------ #
    def state_shardings(self, ps: PoolState) -> Any:
        """Per-leaf ``NamedSharding`` pytree pinning every leaf's leading
        dim (N per-lane rows / D per-shard scalars) to the mesh axis —
        resolved through the shared logical-axis machinery
        (``distributed/sharding.py``), so divisibility fallback matches
        the model layouts.  Pass as ``in_shardings`` hints for
        long-lived states (the device-resident PPO loop pins its carried
        ``PoolState`` with these)."""
        from repro.distributed.sharding import RuleSet, pool_state_shardings

        rules = RuleSet({"env_shard": self.axis_name}, name="envpool")
        return pool_state_shardings(self.mesh, ps, rules)

    def device_put(self, ps: PoolState) -> PoolState:
        """Explicitly lay the state out across the mesh."""
        return jax.tree.map(jax.device_put, ps, self.state_shardings(ps))

    # ------------------------------------------------------------------ #
    # multi-host plumbing (contract: core/protocol.py)
    # ------------------------------------------------------------------ #
    @functools.cached_property
    def is_multiprocess(self) -> bool:
        """True when the env mesh spans OS processes (multi-host run)."""
        pid = jax.process_index()
        return any(d.process_index != pid for d in self.mesh.devices.flat)

    @functools.cached_property
    def _jit_replicate(self):
        return jax.jit(lambda t: t,
                       out_shardings=NamedSharding(self.mesh, P()))

    def replicate(self, tree: Any) -> Any:
        """All-gather a mesh-partitioned pytree so every device — and so
        every process — holds a full copy, making ``np.asarray`` legal on
        the result in multi-process runs (host reads of remote shards are
        otherwise non-addressable).  Driver/``stats()`` plumbing only:
        this IS an env-data-sized collective, so it must never appear in
        the engine's send/recv/step programs (the compiled-HLO audit in
        tests/test_multihost.py holds the hot path to that)."""
        return self._jit_replicate(tree)

    def put_batch(self, tree: Any) -> Any:
        """Explicitly place an ``(M, ...)`` shard-major host batch onto
        the mesh, partitioned on dim 0.  Required in multi-process
        drivers — raw host arrays cannot implicitly cross to
        non-addressable devices — and a no-op-cost explicit placement on
        one process (every process passes the same host values)."""
        sh = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def put_replicated(self, tree: Any) -> Any:
        """As :meth:`put_batch` for unpartitioned values (e.g. the init
        key): replicate a host value across the mesh explicitly."""
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    # ------------------------------------------------------------------ #
    # transform-state checkpointing (ROADMAP transforms open item)
    # ------------------------------------------------------------------ #
    def _tf_canonical(self, tf_state: Any) -> Any:
        """Mesh-elastic canonical form of ``PoolState.tf_state``:
        per-lane entries keep their full (N, ...) rows (mesh-size-
        independent by layout), global entries drop the per-shard dim —
        shard copies are identical by the collective-merge invariant, so
        shard 0's copy IS the state."""
        return self._tf_local(tf_state)

    def save_transform_state(self, store, step: int, ps: PoolState,
                             meta: dict | None = None) -> str:
        """Persist the transform-pipeline state (e.g. ``NormalizeObs``
        running moments) through ``checkpoint/store.py`` so the
        preprocessing statistics survive training restarts."""
        return store.save(step, self._tf_canonical(ps.tf_state), meta or {})

    def restore_transform_state(self, store, step: int, ps: PoolState
                                ) -> PoolState:
        """Restore a saved transform state into ``ps`` — elastically:
        global entries are re-broadcast to this pool's shard count, so a
        checkpoint taken at mesh 1 restores onto mesh D (and back)."""
        like = self._tf_canonical(ps.tf_state)
        canon = store.restore(step, like)
        tf = tuple(
            s if t.per_lane
            else jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (self.num_shards,) + x.shape
                ).copy() if hasattr(x, "shape") else x,
                s,
            )
            for t, s in zip(self.pipeline.transforms, canon)
        )
        return ps.replace(tf_state=tf)


def make_pool(
    env: Environment,
    num_envs: int,
    batch_size: int | None = None,
    mode: str | None = None,
    batched: bool | None = None,
    schedule: str | Scheduler = "fifo",
    transforms: Any = (),
) -> MeshEnvPool:
    """EnvPool constructor with the paper's mode convention: sync iff
    batch_size in (None, num_envs) — which is exactly the engine's own
    ``mode=None`` default."""
    return MeshEnvPool(env, num_envs, batch_size, mode=mode, batched=batched,
                       schedule=schedule, transforms=transforms)


__all__ = [
    "ENV_AXIS",
    "LaneRows",
    "MeshEnvPool",
    "PoolState",
    "derive_env_keys",
    "make_env_mesh",
    "make_pool",
]
