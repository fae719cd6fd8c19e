"""Baseline executors from the paper's Table 1: For-loop and Subprocess.

* ``ForLoopEnv`` — all envs stepped sequentially in the caller's thread.
* ``SubprocessEnv`` — gym.vector-style: worker processes step their env
  shard and write observations into shared memory; the parent coordinates
  over pipes.  This is the "most popular implementation" the paper
  benchmarks against (Brockman et al. 2016).

Both are synchronous (M = N) and return the same dict layout as
ThreadEnvPool.recv for drop-in benchmarking; both also satisfy the
``core.protocol.EnvPool`` contract (send parks a batch, recv executes
it) so protocol-driven code runs unchanged over them.
"""

from __future__ import annotations

import multiprocessing as mp
import pickle
import threading
import traceback
from multiprocessing import shared_memory
from typing import Callable

import numpy as np

from repro.core.host_pool import HostEnv
from repro.core.transforms import TransformPipeline
from repro.obs.telemetry import HostTelemetry


def _result_dict(n, obs_spec):
    return {
        "obs": np.zeros((n,) + obs_spec.shape, obs_spec.dtype),
        "reward": np.zeros((n,), np.float32),
        "done": np.zeros((n,), np.bool_),
        "terminated": np.zeros((n,), np.bool_),
        "truncated": np.zeros((n,), np.bool_),
        "env_id": np.arange(n, dtype=np.int32),
        "episode_return": np.zeros((n,), np.float32),
        "episode_length": np.zeros((n,), np.int32),
        "step_cost": np.ones((n,), np.int32),
    }


class _SyncSendRecv:
    """send/recv facade for synchronous engines (EnvPool protocol):
    ``send`` parks one full batch of actions, ``recv`` executes it.
    Exactly one send may be outstanding (M == N: there is only one
    block in flight by construction)."""

    _pending: "tuple | None" = None

    def send(self, actions, env_ids=None) -> None:
        if self._pending is not None:
            raise RuntimeError(
                "send() called twice without recv() on a sync engine"
            )
        self._pending = (np.asarray(actions), env_ids)

    def recv(self) -> dict[str, np.ndarray]:
        if self._pending is None:
            raise RuntimeError("recv() without a pending send()/async_reset()")
        pending, self._pending = self._pending, None
        if pending == "reset":
            return self.reset()
        actions, env_ids = pending
        return self.step(actions, env_ids)

    def async_reset(self) -> None:
        """Paper A.3 analogue: park a reset; the next recv returns it."""
        if self._pending is not None:
            raise RuntimeError("async_reset() with a send() outstanding")
        self._pending = "reset"


class ForLoopEnv(_SyncSendRecv):
    """Paper Table 1 row 1: single-thread sequential stepping."""

    def __init__(self, env_fns: list[Callable[[], HostEnv]],
                 transforms=(), obs: bool = True):
        self._envs = [fn() for fn in env_fns]
        self.num_envs = len(self._envs)
        self.batch_size = self.num_envs
        self.obs = bool(obs)
        self._tele = HostTelemetry(self.num_envs) if self.obs else None
        # same transform pipeline as every other engine (numpy mirror),
        # applied to each assembled M == N block
        self._pipeline = TransformPipeline(transforms, self._envs[0].spec)
        self._tf_state = self._pipeline.np_init(self.num_envs)
        self.raw_spec = self._envs[0].spec
        self.spec = self._pipeline.out_spec
        self._pending = None

    def reset(self) -> dict[str, np.ndarray]:
        # pipeline state restarts with the envs (device init() parity)
        self._tf_state = self._pipeline.np_init(self.num_envs)
        out = _result_dict(self.num_envs, self.raw_spec.obs_spec)
        if self._tele is not None:
            self._tele.on_enqueue(out["env_id"], stepped=False)
        for i, e in enumerate(self._envs):
            out["obs"][i] = e.reset()
        if self._tele is not None:
            self._tele.record_block(out["env_id"], out["step_cost"])
        self._tf_state, out = self._pipeline.np_apply(self._tf_state, out)
        return out

    def step(self, actions, env_ids=None) -> dict[str, np.ndarray]:
        out = _result_dict(self.num_envs, self.raw_spec.obs_spec)
        if self._tele is not None:
            self._tele.on_enqueue(out["env_id"], stepped=True)
        for i, e in enumerate(self._envs):
            obs, rew, done, info = e.step(actions[i])
            out["obs"][i] = obs
            out["reward"][i] = rew
            out["done"][i] = done
            out["terminated"][i] = info.get("terminated", done)
            out["truncated"][i] = info.get("truncated", False)
            out["episode_return"][i] = info.get("episode_return", 0.0)
            out["episode_length"][i] = info.get("episode_length", 0)
            out["step_cost"][i] = info.get("step_cost", 1)
        if self._tele is not None:
            self._tele.record_block(out["env_id"], out["step_cost"])
        self._tf_state, out = self._pipeline.np_apply(self._tf_state, out)
        return out

    def stats(self) -> dict:
        """Telemetry snapshot (core/protocol.py ``stats()`` contract)."""
        if self._tele is None:
            raise RuntimeError(
                "telemetry disabled: pool was constructed with obs=False"
            )
        return self._tele.snapshot()

    def close(self) -> None:
        pass


def _subproc_worker(conn, shm_name, shape, dtype_str, lo, hi, factory_bytes):
    """Worker process: owns envs [lo, hi); writes obs into shared memory.

    The worker is a host-CPU process: it restricts JAX to the CPU before
    any backend starts, so it never tries to open the parent's chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    factory = pickle.loads(factory_bytes)
    envs = [factory(i) for i in range(lo, hi)]
    shm = shared_memory.SharedMemory(name=shm_name)
    obs_block = np.ndarray(shape, dtype=np.dtype(dtype_str), buffer=shm.buf)
    try:
        while True:
            cmd, payload = conn.recv()
            if cmd == "close":
                break
            try:
                if cmd == "reset":
                    for i, e in enumerate(envs):
                        obs_block[lo + i] = e.reset()
                    conn.send(("ok", None))
                elif cmd == "step":
                    actions = payload
                    rews, dones = [], []
                    for i, e in enumerate(envs):
                        obs, rew, done, _ = e.step(actions[i])
                        obs_block[lo + i] = obs  # one IPC copy saved vs pipe
                        rews.append(rew)
                        dones.append(done)
                    conn.send(("ok", (rews, dones)))
            except Exception:
                # env raised: ship the traceback instead of dying with
                # the reply unsent (which would hang the parent's recv)
                conn.send(("err", traceback.format_exc()))
    finally:
        shm.close()
        conn.close()


class SubprocessEnv(_SyncSendRecv):
    """Paper Table 1 row 2: multiprocessing with shared-memory obs."""

    def __init__(
        self,
        env_factory: Callable[[int], HostEnv],
        num_envs: int,
        num_workers: int | None = None,
        spec=None,
        transforms=(),
        obs: bool = True,
    ):
        self.num_envs = num_envs
        self.batch_size = num_envs
        self.obs = bool(obs)
        self._tele = HostTelemetry(num_envs) if self.obs else None
        if spec is None:
            probe = env_factory(0)
            spec = probe.spec
            del probe
        # workers step raw envs and write raw obs into shared memory;
        # the parent applies the shared transform pipeline (numpy
        # mirror) to each assembled block, so pipeline state stays
        # centralized and identical to every other engine's
        self._pipeline = TransformPipeline(transforms, spec)
        self._tf_state = self._pipeline.np_init(num_envs)
        self.raw_spec = spec
        self.spec = self._pipeline.out_spec

        ctx = mp.get_context("spawn")  # fork is unsafe with an XLA runtime
        self.num_workers = min(num_workers or num_envs, num_envs)
        obs_spec = spec.obs_spec
        shape = (num_envs,) + obs_spec.shape
        nbytes = int(np.prod(shape)) * np.dtype(obs_spec.dtype).itemsize
        self._shm = shared_memory.SharedMemory(create=True, size=max(nbytes, 1))
        self._obs = np.ndarray(shape, dtype=obs_spec.dtype, buffer=self._shm.buf)

        factory_bytes = pickle.dumps(env_factory)
        bounds = np.linspace(0, num_envs, self.num_workers + 1).astype(int)
        self._conns, self._procs, self._bounds = [], [], []
        for w in range(self.num_workers):
            lo, hi = int(bounds[w]), int(bounds[w + 1])
            if lo == hi:
                continue
            parent, child = ctx.Pipe()
            p = ctx.Process(
                target=_subproc_worker,
                args=(child, self._shm.name, shape, np.dtype(obs_spec.dtype).str,
                      lo, hi, factory_bytes),
                daemon=True,
            )
            p.start()
            child.close()
            self._conns.append(parent)
            self._procs.append(p)
            self._bounds.append((lo, hi))
        self._closed = False
        self._close_lock = threading.Lock()
        self._error: str | None = None
        self._pending = None

    # ------------------------------------------------------------------ #
    # worker error propagation: the first traceback shipped back by a
    # worker puts the pool in a terminal error state, re-raised by every
    # subsequent reset/step/recv (instead of hanging on a dead pipe)
    # ------------------------------------------------------------------ #
    def _raise_worker_error(self) -> None:
        raise RuntimeError(
            "SubprocessEnv worker failed (pool is dead; close() it):\n"
            + (self._error or "")
        )

    def _recv_checked(self, conn):
        tag, payload = conn.recv()
        if tag == "err":
            self._error = payload
            self._raise_worker_error()
        return payload

    def recv(self) -> dict[str, np.ndarray]:
        if self._error is not None:
            self._raise_worker_error()
        return super().recv()

    def reset(self) -> dict[str, np.ndarray]:
        if self._error is not None:
            self._raise_worker_error()
        # pipeline state restarts with the envs (device init() parity)
        self._tf_state = self._pipeline.np_init(self.num_envs)
        for c in self._conns:
            c.send(("reset", None))
        for c in self._conns:
            self._recv_checked(c)
        out = _result_dict(self.num_envs, self.raw_spec.obs_spec)
        out["obs"][:] = self._obs  # batching copy (the paper counts this)
        if self._tele is not None:
            self._tele.on_enqueue(out["env_id"], stepped=False)
            self._tele.record_block(out["env_id"], out["step_cost"])
        self._tf_state, out = self._pipeline.np_apply(self._tf_state, out)
        return out

    def step(self, actions, env_ids=None) -> dict[str, np.ndarray]:
        if self._error is not None:
            self._raise_worker_error()
        for c, (lo, hi) in zip(self._conns, self._bounds):
            c.send(("step", actions[lo:hi]))
        out = _result_dict(self.num_envs, self.raw_spec.obs_spec)
        for c, (lo, hi) in zip(self._conns, self._bounds):
            rews, dones = self._recv_checked(c)
            out["reward"][lo:hi] = rews
            out["done"][lo:hi] = dones
        out["obs"][:] = self._obs
        if self._tele is not None:
            self._tele.on_enqueue(out["env_id"], stepped=True)
            self._tele.record_block(out["env_id"], out["step_cost"])
        self._tf_state, out = self._pipeline.np_apply(self._tf_state, out)
        return out

    def stats(self) -> dict:
        """Telemetry snapshot (core/protocol.py ``stats()`` contract)."""
        if self._tele is None:
            raise RuntimeError(
                "telemetry disabled: pool was constructed with obs=False"
            )
        return self._tele.snapshot()

    def close(self) -> None:
        """Idempotent and safe under concurrent calls (an explicit
        ``close()`` racing ``__del__`` at interpreter shutdown), like
        ``ThreadEnvPool.close()``: exactly one caller wins the flag flip
        under the lock and performs the shutdown."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for c in self._conns:
            try:
                c.send(("close", None))
                c.close()
            except Exception:
                pass
        for p in self._procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
        self._shm.close()
        self._shm.unlink()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
