# The paper's primary contribution: the EnvPool execution engine,
# re-built TPU-native in JAX (DESIGN.md §2) around two seams:
#
#   * ``core.protocol.EnvPool`` — ONE structural contract (specs +
#     send/recv/step/sync reset) that all five engines satisfy; drivers
#     (``DmEnv``, ``build_collect_fn``, ``rl.ppo.train``) program
#     against it, so the engine is an execution detail.  The device
#     family additionally satisfies ``FunctionalEnvPool`` (pure state,
#     jittable, ``xla()`` handle API); ``bind()`` gives a uniform
#     stateful view when jit-purity is not needed.
#   * ``envs.batch.BatchEnvironment`` — the batched-native env layer:
#     engines drive SoA batched primitives (one fused multi-substep
#     call per recv — the Pallas ``kernels/env_step`` kernel where the
#     env provides it, compiled on TPU with a bit-identical jnp
#     reference fallback on CPU; a bitwise-equivalent vmap-lifting
#     adapter everywhere else).
from repro.core.engine import MeshEnvPool, PoolState, make_env_mesh, make_pool
from repro.core.protocol import (
    BoundEnvPool,
    EnvPool,
    FunctionalEnvPool,
    bind,
    is_functional,
    to_timestep,
)
from repro.core.registry import (
    list_engines,
    list_envs,
    make,
    make_py,
    register,
    register_py,
)
from repro.core.specs import ArraySpec, EnvSpec, TimeStep
from repro.core.transforms import (
    Crop,
    EpisodicLife,
    FrameStack,
    Grayscale,
    NormalizeObs,
    ObsCast,
    Resize,
    RewardClip,
    Transform,
    TransformPipeline,
)
from repro.core.dm_api import DmEnv
from repro.core.xla_loop import build_collect_fn, build_random_collect_fn, collect_init

__all__ = [
    "ArraySpec",
    "BoundEnvPool",
    "Crop",
    "DmEnv",
    "EnvPool",
    "EnvSpec",
    "EpisodicLife",
    "FrameStack",
    "FunctionalEnvPool",
    "Grayscale",
    "MeshEnvPool",
    "NormalizeObs",
    "ObsCast",
    "PoolState",
    "Resize",
    "RewardClip",
    "Transform",
    "TransformPipeline",
    "TimeStep",
    "bind",
    "build_collect_fn",
    "build_random_collect_fn",
    "collect_init",
    "is_functional",
    "list_engines",
    "list_envs",
    "make",
    "make_env_mesh",
    "make_pool",
    "make_py",
    "register",
    "register_py",
    "to_timestep",
]
