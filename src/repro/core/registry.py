"""``repro.make`` — the EnvPool ``envpool.make`` analogue (paper §1 API).

    env = make("Pong-v5", num_envs=100)                 # device pool, sync
    env = make("Pong-v5", num_envs=100, batch_size=90)  # device pool, async
    env = make("TokenCopy-v0", num_envs=256,
               engine="device-sharded", num_shards=4)   # multi-device pool
    env = make("TokenSkew-v0", num_envs=256, batch_size=64,
               engine="device-sharded", num_shards=4,
               schedule="hierarchical")                 # + scheduling policy
    env = make("Ant-v3", engine="thread", num_envs=64)  # host thread pool
    env = make("Ant-v3", engine="subprocess", ...)      # gym.vector baseline
    env = make("Pong-v5", num_envs=100,
               transforms=[FrameStack(4), RewardClip()])  # in-engine
                                                          # preprocessing
    env = make("PongStack-v5", num_envs=100)            # preset pipeline

One spec-driven front-end constructs every engine:

  engine            pool class              execution substrate
  ----------------  ----------------------  ---------------------------------
  device (default)  MeshEnvPool             one-device mesh
  device-sharded    MeshEnvPool             shard_map over a device mesh
  thread            ThreadEnvPool           host threads (paper's C++ pool)
  forloop           ForLoopEnv              sequential baseline (Table 1)
  subprocess        SubprocessEnv           gym.vector-style workers

Engine conformance: all engines derive per-env init keys the same way
(``split(split(PRNGKey(seed))[1], num_envs)``), so with deterministic
actions routed by ``env_id`` they emit identical reward/done streams —
asserted in tests/test_conformance.py.  Pure-Python single-env classes
are reachable via ``make_py``.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from repro.core.engine import MeshEnvPool, derive_env_keys, make_env_mesh
from repro.core.transforms import Transform, resolve_transforms
from repro.envs.base import Environment

_REGISTRY: dict[str, Callable[..., Environment]] = {}
_PY_REGISTRY: dict[str, Callable[..., Any]] = {}
# per-task default transform pipeline (core/transforms.py), applied when
# ``make(..., transforms=None)``; an explicit list (incl. []) replaces it
_TRANSFORMS: dict[str, tuple[Transform, ...]] = {}
_DEFAULTS_DONE = False

ENGINES = ("device", "device-sharded", "thread", "forloop", "subprocess")


def register(name: str, factory: Callable[..., Environment],
             transforms: tuple[Transform, ...] = ()) -> None:
    """Register a task; ``transforms`` is its default in-engine pipeline
    (e.g. ``Pong-v5`` ships ``FrameStack(4)`` so the classic stacked
    ALE layout stays the out-of-the-box observation)."""
    _REGISTRY[name] = factory
    _TRANSFORMS[name] = tuple(transforms)


def default_transforms(task_id: str) -> tuple[Transform, ...]:
    """The task's registered default transform pipeline."""
    _ensure_defaults()
    return _TRANSFORMS.get(task_id, ())


def register_py(name: str, factory: Callable[..., Any]) -> None:
    _PY_REGISTRY[name] = factory


def list_envs() -> list[str]:
    _ensure_defaults()
    return sorted(_REGISTRY)


def list_engines() -> tuple[str, ...]:
    return ENGINES


def _jax_env(task_id: str, **kwargs: Any) -> Environment:
    _ensure_defaults()
    if task_id not in _REGISTRY:
        raise KeyError(f"unknown env {task_id!r}; known: {list_envs()}")
    return _REGISTRY[task_id](**kwargs)


def _host_env_keys(seed: int, num_envs: int) -> np.ndarray:
    """Per-env init keys matching ``MeshEnvPool.init(PRNGKey(seed))``."""
    import jax

    keys, _ = derive_env_keys(jax.random.PRNGKey(seed), num_envs)
    return np.asarray(keys)


def make(
    task_id: str,
    num_envs: int,
    batch_size: int | None = None,
    engine: str = "device",
    num_threads: int | None = None,
    num_shards: int | None = None,
    mesh: Any = None,
    seed: int = 0,
    batched: bool | None = None,
    schedule: str = "fifo",
    sched_patience: float = 1.0,
    cost_ema_alpha: float = 1.0,
    transforms: Any = None,
    **env_kwargs: Any,
):
    """Create a vectorized env pool, EnvPool-style.

    Every returned engine satisfies ``core.protocol.EnvPool``.  For the
    device family, ``batched`` selects the batched-env implementation:
    None (default) lets the env pick its native one (e.g. the Pallas
    ``env_step`` kernel for MujocoLike), False forces the generic
    vmap-lifting adapter (the conformance reference).

    ``schedule`` picks the async selection policy (``core/scheduler.py``:
    ``"fifo"`` — the default, preserving the classic engine behavior —
    ``"sjf"``, or ``"hierarchical"`` for ``device-sharded``).  The
    host thread engine consumes the same enum through the numpy mirror;
    the synchronous baselines (forloop/subprocess, M == N by
    construction) have no selection freedom and only accept ``"fifo"``.
    ``sched_patience`` is the hierarchical policy's fairness deadline
    (see ``core/scheduler.py``); ``cost_ema_alpha`` smooths the thread
    engine's observed-cost estimator (1.0 = last-observed, the classic).

    ``transforms`` selects the in-engine preprocessing pipeline
    (``core/transforms.py``) fused into every engine's recv:
    ``None`` (default) uses the task's registered preset (e.g.
    ``Pong-v5`` -> ``[FrameStack(4)]``), an explicit list — like
    ``[FrameStack(4), RewardClip()]`` — replaces it, and ``[]`` gives
    the raw env stream.  ``pool.spec`` always reflects the transformed
    observation layout.

    Every engine counts its own work (``obs/telemetry.py``): the
    in-graph counters on the device family, the numpy mirror on the
    host engines — surfaced by ``pool.stats()``.
    """
    _ensure_defaults()
    tfs = resolve_transforms(transforms, _TRANSFORMS.get(task_id, ()))
    if engine in ("device", "device-sharded"):
        if engine == "device":
            if schedule == "hierarchical":
                # the cross-shard policy only makes sense with a real
                # mesh; the degenerate single-device engine rejects it
                raise ValueError(
                    "schedule='hierarchical' is the cross-shard policy: "
                    "it needs a device mesh (use engine='device-sharded')"
                )
            mesh = None
        elif mesh is None:
            # all devices unless ``num_shards`` names fewer
            mesh = make_env_mesh(num_shards)
        return MeshEnvPool(_jax_env(task_id, **env_kwargs), num_envs,
                           batch_size, mesh=mesh, batched=batched,
                           schedule=schedule, sched_patience=sched_patience,
                           transforms=tfs)

    if engine == "thread":
        from repro.core.host_pool import JittedHostEnv, ThreadEnvPool

        keys = _host_env_keys(seed, num_envs)
        fns = [
            (lambda i=i: JittedHostEnv(
                _jax_env(task_id, **env_kwargs), seed=seed + i,
                init_key=keys[i],
            ))
            for i in range(num_envs)
        ]
        return ThreadEnvPool(fns, batch_size=batch_size,
                             num_threads=num_threads, schedule=schedule,
                             cost_ema_alpha=cost_ema_alpha, transforms=tfs)

    if engine in ("forloop", "subprocess") and schedule != "fifo":
        raise ValueError(
            f"engine {engine!r} is synchronous (M == N): no selection "
            f"freedom, schedule must stay 'fifo' (got {schedule!r})"
        )

    if engine == "forloop":
        from repro.core.baselines import ForLoopEnv
        from repro.core.host_pool import JittedHostEnv

        keys = _host_env_keys(seed, num_envs)
        fns = [
            (lambda i=i: JittedHostEnv(
                _jax_env(task_id, **env_kwargs), seed=seed + i,
                init_key=keys[i],
            ))
            for i in range(num_envs)
        ]
        return ForLoopEnv(fns, transforms=tfs)

    if engine == "subprocess":
        from repro.core.baselines import SubprocessEnv

        env = _jax_env(task_id, **env_kwargs)
        return SubprocessEnv(
            _SpawnFactory(task_id, seed, env_kwargs,
                          _host_env_keys(seed, num_envs)),
            num_envs,
            num_workers=num_threads,
            spec=env.spec,
            transforms=tfs,
        )

    raise ValueError(f"unknown engine {engine!r}; known: {ENGINES}")


def make_py(task_id: str, seed: int = 0, **kwargs: Any):
    """Single pure-Python env (the paper's Table 2 'Python' baseline)."""
    _ensure_defaults()
    if task_id not in _PY_REGISTRY:
        raise KeyError(f"no python env {task_id!r}; known: {sorted(_PY_REGISTRY)}")
    return _PY_REGISTRY[task_id](seed=seed, **kwargs)


class _SpawnFactory:
    """Picklable env factory for spawn-based subprocess workers."""

    def __init__(self, task_id: str, seed: int, env_kwargs: dict[str, Any],
                 init_keys: np.ndarray | None = None):
        self.task_id = task_id
        self.seed = seed
        self.env_kwargs = env_kwargs
        self.init_keys = init_keys

    def __call__(self, i: int):
        from repro.core.host_pool import JittedHostEnv

        key = None if self.init_keys is None else self.init_keys[i]
        return JittedHostEnv(
            _jax_env(self.task_id, **self.env_kwargs), seed=self.seed + i,
            init_key=key,
        )


# --------------------------------------------------------------------- #
# default registrations
# --------------------------------------------------------------------- #
def _ensure_defaults() -> None:
    # lazy: avoids the repro.core <-> repro.envs import cycle
    global _DEFAULTS_DONE
    if _DEFAULTS_DONE:
        return
    _DEFAULTS_DONE = True
    from repro.envs.atari_like import AtariLike
    from repro.envs.classic import CartPole, MountainCar, Pendulum
    from repro.envs.mujoco_like import MujocoLike
    from repro.envs.token_env import TokenEnv
    from repro.envs.host_numpy import (
        PyAtariLike,
        PyCartPole,
        PyMujocoLike,
        PyPendulum,
    )

    from repro.core.transforms import (
        FrameStack,
        Grayscale,
        NormalizeObs,
        Resize,
        RewardClip,
    )

    register("CartPole-v1", CartPole)
    register("MountainCar-v0", MountainCar)
    register("Pendulum-v1", Pendulum)
    # AtariLike emits RAW single frames; the classic stacked 4x84x84
    # layout is the default in-engine pipeline (paper §3.4: the
    # preprocessing runs inside the engine, not in Python wrappers)
    register("Pong-v5", AtariLike, transforms=(FrameStack(4),))
    register("AtariLike-Pong-v5", AtariLike, transforms=(FrameStack(4),))
    register("Ant-v3", MujocoLike)
    register("MujocoLike-Ant-v3", MujocoLike)
    register("TokenCopy-v0", TokenEnv)
    # preset pipelines: the DQN-style Atari stack (stack + clip) and
    # the normalized-observation MuJoCo task
    register("PongStack-v5", AtariLike,
             transforms=(FrameStack(4), RewardClip()))
    # THE classic EnvPool/ALE pipeline, fully in-engine: the env renders
    # the native 210x160 RGB screen (one batched kernels/image render
    # per recv) and the jitted recv fuses grayscale -> 84x84 area-resize
    # -> stack -> clip, so pixels never leave the device raw
    register("PongClassic-v5",
             lambda **kw: AtariLike(**{"obs_mode": "rgb", **kw}),
             transforms=(Grayscale(), Resize(84, 84), FrameStack(4),
                         RewardClip()))
    register("AntNorm-v3", MujocoLike, transforms=(NormalizeObs(),))
    # long-tail-skew workloads (heterogeneous per-episode step cost —
    # the scheduling-policy workload)
    register(
        "TokenSkew-v0",
        lambda **kw: TokenEnv(**{"heavy_frac": 0.25, "heavy_scale": 8, **kw}),
    )
    # ragged GENERATION lengths (75% of episodes end at ep_len/4): the
    # continuous-batching serving mix
    register(
        "TokenRagged-v0",
        lambda **kw: TokenEnv(**{"short_frac": 0.75, "len_scale": 4, **kw}),
    )
    register(
        "AntSkew-v3",
        lambda **kw: MujocoLike(**{"heavy_frac": 0.25, "heavy_iters": 4, **kw}),
    )

    register_py("CartPole-v1", PyCartPole)
    register_py("Pendulum-v1", PyPendulum)
    register_py("Pong-v5", PyAtariLike)
    register_py("Ant-v3", PyMujocoLike)
