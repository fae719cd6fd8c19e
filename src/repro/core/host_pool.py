"""ThreadEnvPool — the paper-faithful host engine (DESIGN.md §2, layer L1).

A fixed pool of worker threads (paper §3.3) consumes (env_id, action) work
items from the ActionBufferQueue, steps the environment, and writes results
into pre-allocated StateBufferQueue blocks.  ``recv`` returns one block of
``batch_size`` results — the first M environments to finish (paper §3.2).

Environments here are *host* envs: objects with ``reset()``/``step(a)``.
The "C++ environment" analogue is ``JittedHostEnv`` — a per-instance
jit-compiled JAX env whose step releases the GIL while XLA executes, just
as EnvPool's C++ envs release it inside pybind11 calls.  Pure-Python
NumPy envs (``envs/host_numpy.py``) play the role of the original Python
envs in the paper's Table 2 comparison.
"""

from __future__ import annotations

import atexit
import functools
import threading
import time
import traceback
import weakref
from typing import Any, Callable

import numpy as np

from repro.core.buffers import ActionBufferQueue, StateBufferQueue
from repro.core.scheduler import SCHEDULES, numpy_priority
from repro.core.specs import EnvSpec
from repro.core.transforms import TransformPipeline
from repro.obs.telemetry import HostTelemetry

_RESET = object()  # sentinel action: reset the env
_STOP = object()   # sentinel work item: worker shutdown


def _close_at_exit(pool_ref: weakref.ref) -> None:
    """atexit hook: close a still-live pool BEFORE interpreter teardown.

    Daemon workers don't keep the process alive, but a worker still
    inside a jitted env step when the runtime starts tearing down
    aborts the whole process (XLA's C++ threads hit std::terminate).
    Joining the workers while Python is still fully alive avoids that;
    ``__del__`` alone can't guarantee it (shutdown-order dependent)."""
    pool = pool_ref()
    if pool is not None:
        try:
            pool.close()
        except Exception:
            pass


class HostEnv:
    """Host environment interface for the thread/process engines."""

    spec: EnvSpec

    def reset(self) -> np.ndarray:
        raise NotImplementedError

    def step(self, action) -> tuple[np.ndarray, float, bool, dict]:
        raise NotImplementedError


class JittedHostEnv(HostEnv):
    """Wraps a pure-JAX Environment as a host env with a compiled step.

    The jitted call releases the GIL during XLA execution — the same
    property that lets EnvPool's C++ envs scale across threads.  Every
    call runs on the host CPU device: a host engine is the CPU baseline,
    and on an accelerator host the chip belongs to the driving process.
    """

    def __init__(self, env, seed: int = 0, init_key=None):
        import jax

        self._env = env
        self.spec = env.spec
        self._cpu = jax.devices("cpu")[0]
        self._jit_step = jax.jit(env.step)
        self._jit_init = jax.jit(env.init_state)
        self._seed = seed
        # explicit init key: lets ``make()`` give host and device engines
        # the SAME per-env reset keys (engine-conformance contract) —
        # after the first reset the env's own rng chain takes over, so
        # auto-resets stay aligned too
        self._init_key = None if init_key is None else np.asarray(init_key)
        self._resets = 0
        self._state = None

    def reset(self) -> np.ndarray:
        import jax

        with jax.default_device(self._cpu):
            return self._reset()

    def _reset(self) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        if self._init_key is not None:
            # first reset uses the key verbatim (conformance with the
            # device engines); later resets fold in a counter so repeated
            # resets still give fresh episodes
            key = jnp.asarray(self._init_key)
            if self._resets:
                key = jax.random.fold_in(key, self._resets)
        else:
            self._seed += 1
            key = jax.random.PRNGKey(self._seed)
        self._resets += 1
        self._state = self._jit_init(key)
        return np.asarray(self._env.observe(self._state))

    def step(self, action):
        import jax

        with jax.default_device(self._cpu):
            self._state, ts = self._jit_step(self._state, action)
        return (
            np.asarray(ts.obs),
            float(ts.reward),
            bool(ts.done),
            {
                "terminated": bool(ts.terminated),
                "truncated": bool(ts.truncated),
                "episode_return": float(ts.episode_return),
                "episode_length": int(ts.episode_length),
                "step_cost": int(ts.step_cost),
            },
        )


class ThreadEnvPool:
    """EnvPool's C++ engine, re-built on Python threads (paper §3.1–3.3)."""

    def __init__(
        self,
        env_fns: list[Callable[[], HostEnv]],
        batch_size: int | None = None,
        num_threads: int | None = None,
        schedule: str = "fifo",
        aging: float = 1.0,
        cost_ema_alpha: float = 1.0,
        transforms: Any = (),
    ):
        self.num_envs = len(env_fns)
        self.batch_size = batch_size or self.num_envs
        if self.batch_size > self.num_envs:
            raise ValueError("batch_size cannot exceed num_envs")
        if schedule not in ("fifo", "sjf"):
            raise ValueError(
                f"thread engine supports schedules ('fifo', 'sjf'); "
                f"{schedule!r} is the cross-shard policy "
                "(use engine='device-sharded')" if schedule in SCHEDULES
                else f"unknown schedule {schedule!r}; known: {SCHEDULES}"
            )
        # paper §3.3: thread count bounded by cores; envs 2-3x threads
        self.num_threads = num_threads or min(self.num_envs, _cpu_count())
        # numpy mirror of core/scheduler.py: ``send`` enqueues work in
        # policy-priority order, so workers pull (and thus finish) the
        # scheduled lanes first and recv's "first M finished" block is
        # policy-shaped.  Cost estimates feed the SJF mirror through an
        # EMA of the observed per-env step_cost: ``cost_ema_alpha=1.0``
        # (default) is the classic last-observed estimator, bitwise-
        # preserved; lower alpha smooths noisy per-step costs so one
        # cheap step doesn't erase a lane's heavy history.  fifo keeps
        # the caller's order — the pre-scheduler behavior, bitwise.
        if not 0.0 < cost_ema_alpha <= 1.0:
            raise ValueError(
                f"cost_ema_alpha must be in (0, 1], got {cost_ema_alpha}"
            )
        self.schedule = schedule
        self.aging = float(aging)
        self.cost_ema_alpha = float(cost_ema_alpha)
        self._est_cost = np.ones(self.num_envs, np.float32)
        self._send_tick = np.zeros(self.num_envs, np.float32)
        self._tick = 0
        # numpy mirror of the device engines' in-graph counters
        # (obs/telemetry.py): the pool tags what it enqueues and counts
        # what it serves, so ``stats()`` is engine-conformant
        self._tele = HostTelemetry(self.num_envs)

        self._envs = [fn() for fn in env_fns]
        # host side of the in-engine pipeline (core/transforms.py): the
        # IDENTICAL transform list the device engines fuse into recv,
        # applied here to each assembled result block (raw results sit
        # in the StateBufferQueue; ``recv`` transforms the taken block).
        self._pipeline = TransformPipeline(transforms, self._envs[0].spec)
        self._tf_state = self._pipeline.np_init(self.num_envs)
        self.raw_spec = self._envs[0].spec
        self.spec = self._pipeline.out_spec

        obs_spec = self.raw_spec.obs_spec
        fields = {
            "obs": (obs_spec.shape, obs_spec.dtype),
            "reward": ((), np.float32),
            "done": ((), np.bool_),
            "terminated": ((), np.bool_),
            "truncated": ((), np.bool_),
            "env_id": ((), np.int32),
            "episode_return": ((), np.float32),
            "episode_length": ((), np.int32),
            "step_cost": ((), np.int32),
        }
        self._actions = ActionBufferQueue(self.num_envs)
        self._states = StateBufferQueue(fields, self.batch_size, self.num_envs)
        self._running = True
        self._close_lock = threading.Lock()
        # first worker exception: (env_id, formatted traceback).  recv
        # re-raises it instead of waiting out the block timeout.
        self._error: tuple[int, str] | None = None
        self._error_lock = threading.Lock()
        self._threads = [
            threading.Thread(target=self._worker, daemon=True, name=f"envpool-{i}")
            for i in range(self.num_threads)
        ]
        # a dropped (never-closed) pool must neither hang nor abort the
        # interpreter at exit — see _close_at_exit.  weakref so the hook
        # doesn't keep the pool alive; partial so unregister in close()
        # removes exactly this pool's hook.
        self._atexit_cb = functools.partial(
            _close_at_exit, weakref.ref(self))
        atexit.register(self._atexit_cb)
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------------ #
    def _worker(self) -> None:
        while True:
            # bounded waits + a _running re-check on every block point:
            # a closed pool must never strand a worker in an unbounded
            # queue wait (the semaphores have no close() to wake them)
            try:
                item = self._actions.get(timeout=0.2)
            except TimeoutError:
                if not self._running:
                    return
                continue
            if item is _STOP:
                return
            env_id, action = item
            env = self._envs[env_id]
            try:
                if action is _RESET:
                    obs = env.reset()
                    rew, done, info = 0.0, False, {}
                else:
                    obs, rew, done, info = env.step(action)
            except Exception:
                # the failed item produces no result slot, so its block
                # can never fill — record the traceback for recv to
                # re-raise (the pool is in a terminal error state) and
                # keep the worker alive for a clean close()
                with self._error_lock:
                    if self._error is None:
                        self._error = (env_id, traceback.format_exc())
                continue
            while True:
                try:
                    blk, slot = self._states.acquire_slot(timeout=0.2)
                    break
                except TimeoutError:
                    # result buffer saturated and nobody is recv()ing —
                    # the classic dropped-pool state.  Exit on close()
                    # instead of wedging forever under backpressure.
                    if not self._running:
                        return
            blk.write(
                slot,
                {
                    "obs": obs,
                    "reward": rew,
                    "done": done,
                    "terminated": info.get("terminated", done),
                    "truncated": info.get("truncated", False),
                    "env_id": env_id,
                    "episode_return": info.get("episode_return", 0.0),
                    "episode_length": info.get("episode_length", 0),
                    "step_cost": info.get("step_cost", 1),
                },
            )

    # ------------------------------------------------------------------ #
    # EnvPool API
    # ------------------------------------------------------------------ #
    def async_reset(self) -> None:
        """Enqueue a reset for every env (paper A.3: call once at start)."""
        # every episode restarts: the transform pipeline restarts with
        # it (matching the device family, where init() rebuilds
        # tf_state) — without this a second reset would serve frame
        # stacks still holding pre-reset frames
        self._tf_state = self._pipeline.np_init(self.num_envs)
        self._tele.on_enqueue(np.arange(self.num_envs), stepped=False)
        self._actions.put_batch([(i, _RESET) for i in range(self.num_envs)])

    def send(self, actions: np.ndarray, env_ids: np.ndarray) -> None:
        self._tele.on_enqueue(np.asarray(env_ids), stepped=True)
        items = [(int(e), a) for e, a in zip(env_ids, actions)]
        if self.schedule != "fifo":
            ids = np.asarray(env_ids, np.int64)
            pri = numpy_priority(
                self.schedule, self._est_cost[ids], self._send_tick[ids],
                self._tick, self.aging,
            )
            items = [items[j] for j in np.argsort(pri, kind="stable")]
            self._send_tick[ids] = self._tick
        self._actions.put_batch(items)

    def _raise_worker_error(self) -> None:
        env_id, tb = self._error  # type: ignore[misc]
        raise RuntimeError(
            f"ThreadEnvPool worker failed on env {env_id} (pool is dead; "
            f"close() it):\n{tb}"
        )

    def recv(self, timeout: float | None = 60.0) -> dict[str, np.ndarray]:
        """One block of ``batch_size`` results.  A worker exception is
        re-raised here (and on every later recv) instead of letting the
        never-filling block run out the full timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._error is not None:
                self._raise_worker_error()
            wait = 0.05
            if deadline is not None:
                wait = min(wait, max(deadline - time.monotonic(), 0.0))
            try:
                out = self._states.take(timeout=wait)
                break
            except TimeoutError:
                if deadline is not None and time.monotonic() >= deadline:
                    # a worker may have failed DURING this final take —
                    # without this re-check the real error would be
                    # masked by a spurious TimeoutError until the next
                    # recv (or forever, for a one-shot caller)
                    if self._error is not None:
                        self._raise_worker_error()
                    raise
        # refresh the per-env cost estimates the sjf mirror orders by:
        # EMA of observed cost (alpha=1.0 -> last-observed, bitwise the
        # classic estimator)
        ids = out["env_id"]
        self._tele.record_block(ids, out["step_cost"])
        observed = np.maximum(out["step_cost"], 1).astype(np.float32)
        a = self.cost_ema_alpha
        self._est_cost[ids] = a * observed + (1.0 - a) * self._est_cost[ids]
        self._tick += 1
        self._tf_state, out = self._pipeline.np_apply(self._tf_state, out)
        return out

    def step(self, actions: np.ndarray, env_ids: np.ndarray
             ) -> dict[str, np.ndarray]:
        self.send(actions, env_ids)
        return self.recv()

    def reset(self) -> dict[str, np.ndarray]:
        """Synchronous reset: every env resets and ONE full batch comes
        back.  Only well-defined when ``batch_size == num_envs`` — with
        a smaller batch the first recv would silently hold just the
        first ``batch_size`` finishers while the rest stay queued, so
        that case raises: async pools must use ``async_reset()`` + the
        send/recv loop (paper A.3)."""
        if self.batch_size < self.num_envs:
            raise RuntimeError(
                f"reset() on an async ThreadEnvPool (batch_size="
                f"{self.batch_size} < num_envs={self.num_envs}) would "
                "return a partial batch; use async_reset() and recv()"
            )
        self.async_reset()
        return self.recv()

    def stats(self) -> dict:
        """Telemetry snapshot (core/protocol.py ``stats()`` contract) —
        same keys and semantics as the device engines'."""
        return self._tele.snapshot()

    def close(self) -> None:
        """Idempotent and safe under concurrent calls (e.g. an explicit
        ``close()`` racing ``__del__`` at interpreter shutdown): exactly
        one caller wins the flag flip under the lock and performs the
        shutdown; everyone else returns immediately."""
        with self._close_lock:
            if not self._running:
                return
            self._running = False
        atexit.unregister(self._atexit_cb)
        # sentinels wake idle workers immediately; workers wedged on
        # result-buffer backpressure exit via their _running poll, so a
        # FULL action ring (close() with num_envs actions still queued)
        # must not turn this into an unbounded block — drop the
        # sentinels on timeout rather than hang the closer
        try:
            self._actions.put_batch([_STOP] * self.num_threads, timeout=1.0)
        except TimeoutError:
            pass
        for t in self._threads:
            t.join(timeout=5.0)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _cpu_count() -> int:
    import os

    return os.cpu_count() or 1
